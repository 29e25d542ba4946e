"""Pallas TPU packed-prefill kernel: segment-aware causal flash attention
over the packed token stream.

The hand-tiled fast path filling the `impl="pallas"` slot
ops/packed_prefill.py reserved.  The XLA reference there runs one flash
pass PER SEGMENT ROW over the WHOLE packed stream and masks foreign
tokens out — an S-fold attention-FLOP overhead (S = co-scheduled
segment rows), plus a gathered-context round trip through HBM.  This
kernel removes both:

  * **Tile-skip iteration.**  The grid walks the packed stream in
    TOKEN BLOCKS.  For each (token block, segment) pair the wrapper
    precomputes how many context CHUNKS the pair actually needs —
    zero when the segment owns no token in the block (the skip), and
    otherwise only up to the block's own causal frontier
    ``ceil((max position in block)/chunk)`` rather than the full table
    width.  The packed stream is segment-contiguous (engine/prefill.py
    packs each slot's chunk back to back), so almost every token block
    intersects exactly ONE segment: total attention work is ~1x the
    stream's own context instead of S x, and the *causal* half of each
    segment's score rectangle is skipped at chunk granularity too.

  * **In-VMEM context.**  Each chunk's KV blocks are DMA'd from HBM by
    physical block id into double-buffered VMEM chunk buffers (the
    layout conventions of pallas_paged_attention.py: head-major
    TRANSPOSED blocks, [nkv, hd, bs] per-block strided descriptors,
    lane-aligned for block_size multiples of 128) and consumed by an
    online-softmax accumulation — no gathered [S, ctx, hd] tensor ever
    materializes in HBM.

Int8 KV caches (quant/kv.py) are first-class: pass the per-position
fp32 scale planes and the kernel DMAs int8 blocks + their scale rows
into VMEM and fuses the dequantizing multiply into the chunk consume
(operands in the query dtype — bf16 on the serving path — with fp32
softmax/accumulation), so quantization's halved HBM traffic lands
inside the fast path instead of routing around it.

The chunk DMA chain CROSSES tile and segment boundaries (the decode
kernel's never-drain scheme, generalized): the wrapper derives two more
scalar-prefetch planes from `nchunks` — a global slot PHASE (exclusive
tile-major cumulative sum: how many chunks all earlier (tile, segment)
pairs consume) and each pair's successor row (the next active pair in
tile-major order, -1 at the end) — and every pair's last chunk
prefetches its successor's chunk 0 into the opposite double-buffer
slot (pallas_paged_attention.make_chunk_chain, one definition site
with the decode kernel).  Only the launch's globally first fetch is
un-overlapped; no per-(tile, segment) chunk-0 latency is exposed.

Numerics: fp32 online softmax and accumulation, operands in the query
dtype.  One shared running (m, l, acc) per token row accumulates across
segments; masked positions contribute exp=0 explicitly (not just
NEG_INF scores), so a token's accumulator is untouched while foreign
segments stream past — the property that lets all S segment passes
share one carry without the reference's per-pass output select.
Matches packed_prefill_attention's XLA path to bf16 matmul tolerance;
interpret mode keeps the kernel runnable on CPU for tier-1
(tests/test_packed_pallas.py), tests/test_tpu_compile.py compiles it for
a described v5e at serving widths, and chip_smoke.py checks the compiled
kernel against the XLA path on the chip.

Layout: query rows are (token, head-group) pairs flattened to one
sublane axis — every in-kernel tensor is [nkv, R, *] with R = TB * group
— and the per-row segment/position planes are [R, 1] columns.  Mosaic
tiles the last two dims and cannot re-lay a [TB] lane vector out as the
rows of a [TB, g, C] score tile, so nothing in the kernel ever needs
that reshape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import make_chunk_chain, make_chunk_dma

NEG_INF = -1e30


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _packed_kernel(
    # scalar prefetch
    tables_ref,    # [S, n_chunks * bpc] int32 physical block ids
    nchunks_ref,   # [n_tiles, S] int32 context chunks per (tile, segment)
    base_ref,      # [n_tiles, S] int32 global slot phase per pair
    nseg_ref,      # [n_tiles, S] int32 successor segment row (-1 = none)
    # inputs
    seg_ref,       # [R, 1] int32 segment row per query row (-1 = padded)
    pos_ref,       # [R, 1] int32 absolute position per query row
    q_ref,         # [nkv, R, hd] VMEM (this tile's queries, pre-scaled;
                   #   R = TB * g rows, token-major / group-minor)
    k_hbm,         # [nkv, num_blocks, hd, bs] ANY (stays in HBM)
    v_hbm,
    *rest,         # (+ks_hbm, vs_hbm when quantized) o_ref, scratch...
    S: int,
    bpc: int,
    bs: int,
    quantized: bool,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem) = rest
    else:
        (o_ref, k_buf, v_buf, sem) = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    t = pl.program_id(0)
    C = bpc * bs  # context positions per chunk
    q = q_ref[...]            # [nkv, R, hd]
    seg = seg_ref[...]        # [R, 1]
    pos = pos_ref[...]
    nkv, R, hd = q.shape

    # the chunk DMA contract (descriptor shapes, semaphore pairing, int8
    # scale lanes) is shared with the decode kernel; `row` here is the
    # segment index into the per-segment block tables
    start_chunk, wait_chunk = make_chunk_dma(
        tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem, bpc=bpc, bs=bs,
        ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf, vs_buf=vs_buf)
    prime, chain_step = make_chunk_chain(start_chunk, wait_chunk)

    # rows are sublanes and context positions lanes throughout: every
    # in-kernel tensor is [nkv, R, *] and the per-row planes [R, 1], so
    # the mask broadcasts along lanes and no vector is re-laid-out
    carry = (
        jnp.full((nkv, R, 1), NEG_INF, jnp.float32),
        jnp.zeros((nkv, R, 1), jnp.float32),
        jnp.zeros((nkv, R, hd), jnp.float32),
    )
    # static unroll over segment rows (S is small — max_prefill_seqs
    # pow2); the chunk count is 0 for every segment with no token in
    # this tile, so the fori_loop below skips foreign (tile, segment)
    # pairs entirely — the tile-skip that removes the S-fold overhead
    for s in range(S):
        nch = nchunks_ref[t, s]
        base = base_ref[t, s]
        nseg = nseg_ref[t, s]

        # only the launch's globally first active pair primes chunk 0;
        # every other pair's chunk 0 was prefetched by its predecessor's
        # last chunk (cross-tile/segment never-drain chain)
        prime(s, nch, base)

        owned = seg == s  # [R, 1]

        def body(c, carry, s=s, owned=owned, nch=nch, base=base,
                 nseg=nseg):
            m, l, acc = carry
            slot = chain_step(s, c, nch, base, nseg)
            k = k_buf[slot]  # [nkv, hd, C]
            v = v_buf[slot]
            if quantized:
                # fused dequant on the chunk consume: int8 streamed from
                # HBM, multiplied by the per-position fp32 scale row,
                # cast to the query dtype for the MXU (bf16 operands,
                # fp32 accumulation on the serving path)
                k = (k.astype(jnp.float32)
                     * ks_buf[slot][:, None, :]).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * vs_buf[slot][:, None, :]).astype(q.dtype)
            # scores [nkv, R, C]: one batched matmul for the tile
            sc = jax.lax.dot_general(
                q, k, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            span = c * C + jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
            mask = (owned & (span <= pos))[None]  # [1, R, C]
            sc = jnp.where(mask, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # explicit zero outside the mask: a fully-masked row leaves
            # (m, l, acc) untouched, so the shared carry never mixes
            # foreign segments' junk into a real token's accumulation
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            acc = acc * alpha + pv
            return m_new, l, acc

        carry = jax.lax.fori_loop(0, nch, body, carry)
    m, l, acc = carry
    # tokens no segment owns (padded tail) have l == 0 -> output 0,
    # matching the XLA reference's untouched zero-init output rows
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs (prefill_packed/spec_verify); direct calls are bench/test-only
    jax.jit,
    static_argnames=("layer", "chunk_cols", "token_block", "interpret"),
)
def packed_prefill_attention_pallas(
    q: jax.Array,             # [T, nh, hd] packed-stream queries (rope'd)
    k_cache: jax.Array,       # [L, nkv, num_blocks, hd, bs]
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [S, mb] int32 per-segment block tables
    seg_ids: jax.Array,       # [T] int32 segment row per token
    positions: jax.Array,     # [T] int32 absolute position per token
    valid: jax.Array,         # [T] bool (False = padded tail)
    *,
    chunk_cols: int = 8,      # block columns per context chunk
    token_block: int = 0,     # query tokens per tile (0 = auto)
    interpret: bool = False,
    k_scale: jax.Array = None,  # [L, nkv, num_blocks, bs] fp32 (int8)
    v_scale: jax.Array = None,
) -> jax.Array:
    """Drop-in fast path for packed_prefill.packed_prefill_attention
    (impl="pallas"/"pallas_interpret").  Returns [T, nh, hd] in q's
    dtype; tokens outside every segment (the padded tail) return 0."""
    T, nh, hd = q.shape
    kc, vc = k_cache[layer], v_cache[layer]
    nkv, _, _, bs = kc.shape
    group = nh // nkv
    S, mb = block_tables.shape
    quantized = k_scale is not None

    TB = token_block or min(128, _next_pow2(T))
    n_tiles = -(-T // TB)
    Tp = n_tiles * TB

    bpc = max(1, min(mb, chunk_cols))
    n_chunks = -(-mb // bpc)
    pad_cols = n_chunks * bpc - mb
    if pad_cols:
        # padded table entries point at the garbage block (0); the span
        # mask keeps them out of every real token's window
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad_cols)))
    C = bpc * bs

    # padded-tail / invalid tokens get segment -1: they match no
    # segment row, so no mask ever selects them and no chunk count
    # grows on their behalf
    seg_eff = jnp.where(valid, seg_ids, -1).astype(jnp.int32)
    pad_t = Tp - T
    if pad_t:
        seg_eff = jnp.pad(seg_eff, (0, pad_t), constant_values=-1)
        positions = jnp.pad(positions, (0, pad_t))
        q = jnp.pad(q, ((0, pad_t), (0, 0), (0, 0)))

    # per-(tile, segment) causal chunk frontier: 0 chunks when the
    # segment owns no token in the tile (the skip), else enough chunks
    # to cover the tile's farthest owned position — the wrapper-side
    # half of the tile-skip scheme
    seg2d = seg_eff.reshape(n_tiles, TB)
    pos2d = positions.reshape(n_tiles, TB).astype(jnp.int32)
    owned = seg2d[None, :, :] == jnp.arange(S, dtype=jnp.int32)[:, None,
                                                                None]
    maxpos = jnp.max(jnp.where(owned, pos2d[None, :, :], -1), axis=2)
    nch = jnp.where(maxpos >= 0, maxpos // C + 1, 0)
    nchunks = jnp.minimum(nch, n_chunks).astype(jnp.int32).T  # [n_tiles, S]

    # cross-tile/segment DMA chain planes (make_chunk_chain): the global
    # slot PHASE of each (tile, segment) pair — exclusive tile-major
    # cumulative sum of nchunks, so slot(chunk c of pair) = (base+c)%2 —
    # and each pair's successor row: the segment index of the next
    # active pair in tile-major order (suffix-min over flat indices,
    # -1 past the last), whose chunk 0 the pair's last chunk prefetches
    flat = nchunks.reshape(-1)                    # tile-major [n_tiles*S]
    chunk_base = (jnp.cumsum(flat) - flat).astype(jnp.int32) \
        .reshape(n_tiles, S)
    npairs = flat.shape[0]
    fidx = jnp.arange(npairs, dtype=jnp.int32)
    cand = jnp.where(flat > 0, fidx, npairs)      # inactive -> sentinel
    suf = jax.lax.cummin(cand[::-1])[::-1]        # min over cand[i:]
    suf_excl = jnp.concatenate(
        [suf[1:], jnp.full((1,), npairs, jnp.int32)])
    next_seg = jnp.where(suf_excl < npairs, suf_excl % S, -1) \
        .astype(jnp.int32).reshape(n_tiles, S)

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    # query rows are (token, group) pairs, token-major: R = TB * group
    # rows per tile, [nkv, Tp * group, hd] overall
    R = TB * group
    qg = qg.reshape(Tp, nkv, group, hd).transpose(1, 0, 2, 3) \
        .reshape(nkv, Tp * group, hd)

    # per-row segment/position planes ride as [n_tiles, R, 1] columns
    # (rows on sublanes, like the score rows they mask): the block's
    # last two dims are (R, 1) = (multiple of 8 or the whole array, the
    # array's own 1), which is what Mosaic's block-shape rule asks for
    def row_plane(x2d):
        return jnp.repeat(x2d, group, axis=1)[:, :, None]

    inputs = [row_plane(seg2d), row_plane(pos2d), qg, kc, vc]
    in_specs = [
        pl.BlockSpec((None, R, 1), lambda t, *refs: (t, 0, 0)),
        pl.BlockSpec((None, R, 1), lambda t, *refs: (t, 0, 0)),
        pl.BlockSpec((nkv, R, hd), lambda t, *refs: (0, t, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, nkv, hd, C), kc.dtype),
        pltpu.VMEM((2, nkv, hd, C), vc.dtype),
    ]
    if quantized:
        inputs += [k_scale[layer], v_scale[layer]]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((2, nkv, C), jnp.float32),
                    pltpu.VMEM((2, nkv, C), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)))

    # bytes per context position per head: the int8 path streams 1-byte
    # elements plus one fp32 scale per (head, position)
    pos_bytes = hd * jnp.dtype(kc.dtype).itemsize + (4 if quantized else 0)
    out = pl.pallas_call(
        functools.partial(_packed_kernel, S=S, bpc=bpc, bs=bs,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((nkv, R, hd),
                                   lambda t, *refs: (0, t, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((nkv, Tp * group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        # 1x the stream's own context, NOT the reference's S-fold: each
        # tile visits at most its own segment's table (upper bound —
        # the causal frontier skips chunks beyond a tile's last token)
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * Tp * nh * hd * n_chunks * C,
            bytes_accessed=2 * n_tiles * nkv * n_chunks * C * pos_bytes,
            transcendentals=Tp * nh * n_chunks * C,
        ),
        interpret=interpret,
    )(block_tables, nchunks, chunk_base, next_seg, *inputs)
    out = out.reshape(nkv, Tp, group, hd).transpose(1, 0, 2, 3) \
        .reshape(Tp, nh, hd)
    return out[:T].astype(q.dtype)
