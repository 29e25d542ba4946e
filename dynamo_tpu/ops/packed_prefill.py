"""Packed multi-sequence prefill over the paged KV cache.

The padding killer for the prefill phase.  The batched prefill
path pads EVERY co-scheduled row to the largest chunk's bucket, so a
(100, 500, 37, 1800)-token admission wave computes 4x2048 padded tokens
for 2437 real ones — and the B=1 path serializes one jit dispatch per
sequence per bucket on top.  Here multiple prompts (and prompt TAILS
after prefix-cache hits) concatenate into ONE padding-free token stream
with segment ids:

    tokens    [T]      packed stream (chunks back to back, tail padded)
    seg_ids   [T]      which segment row each token belongs to
    positions [T]      each token's ABSOLUTE position in its sequence
    tables    [S, mb]  per-segment block tables (mb sliced+bucketed to
                       the blocks this dispatch actually touches)
    valid     [T]      False for the padded tail (writes -> garbage)

KV writes come first: each block a chunk touches is rewritten as whole
[nkv, hd, bs] planes, in the layout the pool is resident in
(`write_packed_kv`; a chunk of T tokens in S segments touches at most
(T - 2S) // bs + 2S blocks).  Attention then reads everything — cached
prefix AND this chunk — back through the block table, one gather of the
blocks a flash step names (`_gather_blocks`), masked causal-within-segment
by absolute position (token t sees its segment's cache positions
[0, positions[t]]).  Nothing in the program copies, relayouts or slices
the pool (tests/test_tpu_compile.py holds the compiled program to that).
Because the chunk's K/V are in the cache before attention runs, chunk
boundaries need no special casing: later chunks of the same prompt (even
co-packed in one dispatch at consecutive positions) attend to earlier
ones exactly like a prefix-cache hit.

The attention is flash-style, in two forms.  The reference ("xla") is
an online-softmax (running max / sum) lax.scan over block-column chunks
of the gathered context in float32: the score block [T, nh, chunk]
goes out to HBM and back every step, and one pass runs per segment row
over the WHOLE stream and table (foreign tokens and the pairs above the
causal diagonal are computed, then masked).  The kernel ("pallas" /
"pallas_interpret", ops/pallas_packed_prefill.py) runs the same pairs'
attention as one Pallas call a layer: operands in the cache's dtype,
running max, sum and accumulator float32 in VMEM, no score block in
HBM, a (query tile, key tile) pair computed only where a query of the
tile can see a key of the tile (its own segment row, at or before its
position), K and V moved from the pool by physical block id.  "auto"
is decided in one place, `resolve_packed_impl`, from the platform, the
cache and the stream's length.  Both forms accept int8 caches (the
kernel dequantizes in VMEM, the reference on the gather).

Shape/layout conventions match ops/paged_attention.py: cache
[L, nkv, nb, hd, bs] head-major transposed blocks, physical block 0 is
garbage, all shapes static.

Second consumer: speculative decoding's multi-token verification
(spec/, models/*.spec_verify_packed) runs each speculating sequence's
[last_token, d1..dk] row through this exact path — the draft positions'
KV scatters in place and every row scores against its own paged context
causally, which is precisely the k-token verify step.  Rows there are
short (k+1 tokens), so the S-fold attention overhead is negligible
against the weight pass the verify amortizes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..quant.kv import quantize_tokens
from .paged_attention import NEG_INF, _gqa_out, _gqa_scores

# the packed-prefill dispatch's impl vocabulary — the single source of
# truth the engine's --packed-attn-impl validation and CLI choices
# reference (a new impl added here is accepted end-to-end)
PACKED_IMPLS = ("auto", "xla", "pallas", "pallas_interpret")

# the stream from which "auto" is the kernel on a TPU.  One layer alone on
# a v5e (32 heads over 8 KV heads of 128, block 128, tables of 16 to 50
# blocks; PERF.md section 6, PR 34) the kernel is no slower from 128
# tokens, but to 512 tokens the two forms are within 0.11 ms a layer
# (scan 0.09-0.61, kernel 0.08-0.50), and a program that holds the
# kernel costs a warm set-up a third of a second more to trace and
# lower; from 1024 tokens the scan's score block no longer stays on the
# chip (1.25-4.2 ms, then 2.5-8.3 at 2048) and the kernel takes 0.33-0.94,
# then 0.53-1.76.  The table width moves neither crossover.
KERNEL_MIN_TOKENS = 1024


def resolve_packed_impl(impl: str, platform: str, block_size: int,
                        head_dim: int, cache_dtype, tokens: int,
                        group: int) -> str:
    """What `impl` means for this cache, on this platform, for a packed
    stream of `tokens` whose `group` query heads share a KV head: the
    one place "auto" is decided, from what the code can observe
    (paged_attention.resolve_decode_impl's twin; the engine asks it too,
    to count the tokens whose program ran the kernel).  An explicit impl
    is returned as given.

    "auto" is the kernel ("pallas") where it can run as written and
    repays its set-up: a TPU backend, block_size a multiple of 128 (the
    lane dimension of the [hd, bs] planes it moves), a body's query tile
    of whole 128-lane vregs (`pallas_packed_prefill.body_lanes`: any
    group of 128-wide heads; 64-wide heads where a body holds an even
    number of them, which it slices at 64-lane offsets: LFM2's 4 a KV
    head, compiled for a described v5e and run on the chip, PERF.md
    section 6, PR 55), a bf16 or int8 cache, and a stream of
    KERNEL_MIN_TOKENS tokens or more.  Everywhere else (CPU, block_size
    16, fp32 caches, the short buckets, speculative verification's rows
    of k + 1 tokens) it is the float32 scan ("xla").  Under tensor
    parallelism the kernel runs per shard (`_packed_pallas_tp`): the
    rule is the same."""
    if impl != "auto":
        return impl
    from .pallas_packed_prefill import body_lanes

    dt = jnp.dtype(cache_dtype)
    if (platform == "tpu" and block_size % 128 == 0
            and body_lanes(group, head_dim) % 128 == 0
            and dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.int8))
            and tokens >= KERNEL_MIN_TOKENS):
        return "pallas"
    return "xla"


def check_packed_stream(seg_ids: np.ndarray, positions: np.ndarray,
                        valid: np.ndarray, rows: int) -> None:
    """The packed stream's contract, checked on the host where its
    arrays are built (engine/prefill.py, spec/verify.py; numpy over T
    elements): the valid tokens lead the stream, and each segment row's
    tokens are ONE run of it at consecutive positions, the rows in
    rising order.  `plan_packed_write` sizes its plan by this and drops
    what overflows it, where the flat scatter it replaced took tokens
    in any order: a stream outside the contract would lose K/V columns
    on the device without an error, so it is refused here."""
    n = int(np.count_nonzero(valid))
    seg, pos = seg_ids[:n], positions[:n]
    same = seg[1:] == seg[:-1]
    if not (valid[:n].all()
            and (n == 0 or (seg[0] >= 0 and seg[-1] < rows))
            and np.where(same, pos[1:] == pos[:-1] + 1,
                         seg[1:] > seg[:-1]).all()):
        raise ValueError(
            "packed stream outside its contract: every segment row is "
            "one run of the stream at consecutive positions, rows in "
            f"order, padding last ({n} tokens of {len(valid)}, {rows} "
            "rows)")


def plan_packed_write(block_tables: jax.Array, seg_ids: jax.Array,
                      positions: jax.Array, valid: jax.Array,
                      block_size: int) -> Tuple[jax.Array, ...]:
    """Which blocks a packed stream writes and which token fills which
    column of each (the same for every layer of a program).  A segment
    row's tokens are one run of the stream at consecutive positions
    (`check_packed_stream`, which both planners hold their arrays to),
    so a new (segment, block column) pair starts a new plane, a run of
    m tokens starts at most (m - 2) // bs + 2 of them (it may begin and
    end inside a block), and T tokens in S rows at most
    (T - 2S) // bs + 2S: the static size of the plan.  A stream outside
    the contract can start more, and what overflows is dropped.
    Returns (blocks [NP] physical block per plane, src [NP, bs] packed
    index of the token for each column, T where the stream has none,
    and the number of planes in use; the padded tail starts none)."""
    T, S = seg_ids.shape[0], block_tables.shape[0]
    n_planes = max(1, min(T, (T - 2 * S) // block_size + 2 * S))
    col = positions // block_size

    def differs(x):
        return x[1:] != x[:-1]

    starts = valid & jnp.concatenate([
        jnp.ones((1,), bool),
        differs(seg_ids) | differs(col) | differs(valid)])
    plane = jnp.where(valid, jnp.cumsum(starts, dtype=jnp.int32) - 1,
                      n_planes)
    blocks = jnp.zeros((n_planes,), jnp.int32).at[plane].set(
        block_tables[seg_ids, col], mode="drop")
    src = jnp.full((n_planes, block_size), T, jnp.int32).at[
        plane, positions % block_size].set(
            jnp.arange(T, dtype=jnp.int32), mode="drop")
    return blocks, src, jnp.sum(starts, dtype=jnp.int32)


# dynlint: disable=DYN001 op-level jit: reached only inside the engine's watched prefill_packed / spec_verify programs; `layer` is traced, so one trace serves every layer of a program
@jax.jit
def _store_planes(caches, layer, xs, blocks, src, used):
    """The packed stream's sibling of paged_attention._store_columns:
    plane i of the plan is block blocks[i]'s whole [nkv, hd, bs] planes
    (or [nkv, bs] of a scale plane), read, given xs[j][src[i, o]] in
    every column o the stream has a token for, and written back — the
    pools keep the layout they are resident in, {4,3,2,1,0}, where the
    flat column scatter made XLA's TPU compiler hold a {3,1,4,2,0} twin
    of the pool and copy all of it back for every layer's read (34
    copies of 1.34 GB in a 16-layer program, PR 30).  One plane at a
    time and in stream order, so two segments that continue one block
    (two chunks of one prompt in one stream) both land."""
    T = xs[0].shape[0]
    zero = jnp.int32(0)
    has = src < T                       # [NP, bs]
    at_tok = jnp.minimum(src, T - 1)
    # the stream's columns in the planes' own shape: [NP, nkv, (hd,) bs]
    new = [jnp.moveaxis(x.astype(c.dtype)[at_tok], 1, -1)
           for c, x in zip(caches, xs)]

    def body(i, cs):
        out = []
        for c, n in zip(cs, new):
            at = (layer, zero, blocks[i]) + (zero,) * (c.ndim - 3)
            plane = jax.lax.dynamic_slice(
                c, at, (1, c.shape[1], 1) + c.shape[3:])
            out.append(jax.lax.dynamic_update_slice(
                c, jnp.where(has[i], n[i][None, :, None], plane), at))
        return tuple(out)

    return jax.lax.fori_loop(0, used, body, tuple(caches))


@jax.named_scope("dyn.kv_write")
def write_packed_kv(
    k_cache: jax.Array,       # [L, nkv, nblocks, hd, bs]
    v_cache: jax.Array,
    layer: int,
    k: jax.Array,             # [T, nkv, hd] packed-stream keys
    v: jax.Array,
    block_tables: jax.Array,  # [S, mb] int32
    seg_ids: jax.Array,       # [T] int32 segment row per token
    positions: jax.Array,     # [T] int32 absolute position per token
    valid: jax.Array,         # [T] bool (False = padded tail)
    k_scale: jax.Array = None,  # [L, nkv, nblocks, bs] fp32 (int8 cache)
    v_scale: jax.Array = None,
) -> Tuple[jax.Array, ...]:
    """Write a packed chunk's K/V into each token's own sequence blocks:
    every block the chunk touches is rewritten whole, the stream's
    tokens in their columns and what the block held elsewhere (a chunk
    may start or end inside a block; `_store_planes`).  The cells
    written hold what the flat scatter of paged_attention._store_kv
    would have put there; the padded tail writes nothing.  With scales,
    tokens quantize per (token, head) on the way in (quant/kv.py).

    Contract (`check_packed_stream`): each segment row is ONE run of
    the stream at consecutive positions, rows in order, padding last.
    The scatter took tokens in any order; this writer does not."""
    # the same for every layer of a program: XLA merges the copies
    plan = plan_packed_write(block_tables, seg_ids, positions, valid,
                             k_cache.shape[4])
    caches, xs = (k_cache, v_cache), (k, v)
    if k_scale is not None:
        k, ks = quantize_tokens(k)
        v, vs = quantize_tokens(v)
        caches, xs = caches + (k_scale, v_scale), (k, v, ks, vs)
    return _store_planes(caches, jnp.int32(layer), xs, *plan)


def _gather_blocks(cache: jax.Array, layer: int, cols: jax.Array,
                   scale: jax.Array = None) -> jax.Array:
    """[L,nkv,nb,hd,bs] + [C] block ids -> [nkv, C*bs, hd], what
    paged_attention._gather_ctx returns, with layer and blocks indexed
    by ONE gather: `cache[layer][:, cols]` made the TPU compiler
    materialise the layer's whole [nkv, nb, hd, bs] slice of the pool
    in every flash step before gathering C blocks from it (PR 30).
    A fork for one PR: `_gather_ctx` (jnp decode, window_attention)
    was left as it is so that the cells that run it stay controls; it
    takes this form in place next, and this copy goes (ROADMAP S4)."""
    li = jnp.int32(layer)
    g = cache[li, :, cols]              # [C, nkv, hd, bs]
    C, nkv, hd, bs = g.shape
    g = g.transpose(1, 0, 3, 2).reshape(nkv, C * bs, hd)
    if scale is not None:
        s = scale[li, :, cols].swapaxes(0, 1).reshape(nkv, C * bs)
        g = g.astype(jnp.float32) * s[..., None]
    return g


def _segment_flash(q, k_cache, v_cache, layer, table, token_mask,
                   positions, chunk_cols, k_scale=None, v_scale=None,
                   lower=None):
    """One segment row's flash pass: online-softmax scan over chunks of
    `chunk_cols` block columns of the segment's paged context.  Returns
    fp32 attention output [T, nh, hd] for every packed token (foreign
    tokens produce junk the caller masks out)."""
    T, nh, hd = q.shape
    bs = k_cache.shape[4]
    mb = table.shape[0]
    n_chunks = -(-mb // chunk_cols)
    pad = n_chunks * chunk_cols - mb
    if pad:
        table = jnp.pad(table, (0, pad))  # padded columns hit garbage
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def body(carry, jc):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice(table, (jc * chunk_cols,),
                                     (chunk_cols,))
        k_c = _gather_blocks(k_cache, layer, cols, k_scale)  # [nkv, C, hd]
        v_c = _gather_blocks(v_cache, layer, cols, v_scale)
        C = chunk_cols * bs
        s = _gqa_scores(q, k_c) * scale          # [T, nh, C] fp32
        span = jc * C + jnp.arange(C)
        mask = token_mask[:, None, None] \
            & (span[None, None, :] <= positions[:, None, None])
        if lower is not None:
            mask = mask & (span[None, None, :] >= lower[:, None, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + _gqa_out(p, v_c)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((T, nh), NEG_INF, jnp.float32),
        jnp.zeros((T, nh), jnp.float32),
        jnp.zeros((T, nh, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return acc / jnp.maximum(l, 1e-20)[..., None]


def _packed_pallas_tp(q, k_cache, v_cache, layer, block_tables, seg_ids,
                      positions, valid, *, mesh, interpret,
                      k_scale=None, v_scale=None):
    """Packed-prefill kernel under tensor parallelism
    (paged_attention.kernel_tp_call — the shard_map scaffolding shared
    with the decode kernel: local kv-head slices, replicated stream
    metadata, scale planes sharded with the cache)."""
    from jax.sharding import PartitionSpec as P

    from .paged_attention import kernel_tp_call
    from .pallas_packed_prefill import packed_prefill_attention_pallas

    quantized = k_scale is not None

    def local(q, kc, vc, tables, seg, pos, val, *scales):
        ks, vs = scales if quantized else (None, None)
        return packed_prefill_attention_pallas(
            q, kc, vc, layer, tables, seg, pos, val,
            interpret=interpret, k_scale=ks, v_scale=vs,
        )

    return kernel_tp_call(
        mesh, local,
        [q, k_cache, v_cache, block_tables, seg_ids, positions, valid],
        [P(None, "tp", None), P(None, "tp", None, None, None),
         P(None, "tp", None, None, None), P(None, None), P(None),
         P(None), P(None)],
        k_scale=k_scale, v_scale=v_scale,
    )


@jax.named_scope("dyn.attention")
def packed_prefill_attention(
    q: jax.Array,             # [T, nh, hd] packed-stream queries (rope'd)
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [S, mb]
    seg_ids: jax.Array,       # [T]
    positions: jax.Array,     # [T]
    valid: jax.Array,         # [T]
    impl: str = "auto",
    chunk_cols: int = 8,      # block columns per flash step
    k_scale: jax.Array = None,  # int8 cache: dequant scales (quant/kv.py)
    v_scale: jax.Array = None,
    mesh=None,                # required for the Pallas path under tp>1
    lower: jax.Array = None,  # [T] first position of its row a token sees
    upper: jax.Array = None,  # [T] last position of its row a token sees
) -> jax.Array:
    """Causal-within-segment attention for a packed prefill chunk.

    Every token attends to its OWN segment's paged cache over absolute
    positions [0, positions[t]] — cached prefix plus the chunk itself,
    whose K/V write_packed_kv already scattered in (so on an int8 cache
    the chunk's own K/V round-trip the quantizer before attention reads
    them — bit-consistent with how every later chunk will see them).

    impl: "xla" (the float32 scan: one masked flash pass per segment
    row, S-fold attention FLOPs), "pallas" / "pallas_interpret"
    (ops/pallas_packed_prefill.py: one kernel a layer, bf16 operands,
    pairs outside a tile's segment and causal frontier skipped, context
    moved by physical block id), or "auto" (`resolve_packed_impl` on the
    default backend: the kernel on a TPU from KERNEL_MIN_TOKENS tokens,
    the scan under that and elsewhere).  Int8 caches work on
    every impl.  `mesh` is required for the kernel when the cache is
    tensor-parallel (kv_heads over a "tp" axis): it then runs under
    shard_map per shard, like the decode kernel.  `chunk_cols` is the
    scan's step; the kernel's tiles are its own.

    `lower`: a LOWER bound a token: it attends its row's positions
    [lower[t], positions[t]], a band (ops/window_attention.py hands a
    window layer's context over that way).  The kernel skips the key
    tiles wholly under a query tile's bounds; the scan masks them.
    Without it every caller's program is the one it was.  Not carried
    under tp.

    `upper`: the twin, an UPPER bound a token: it attends its row's
    positions [0, upper[t]] where upper[t] >= positions[t], a frontier
    that several queries share (models/sdar.py: the last position of a
    query's diffusion block, so attention inside a block runs both
    ways).  Both forms read `positions` only as each query's frontier
    (rotary is applied before, the write takes positions separately),
    so the bound stands in its place; every key up to it has to be in
    the cache already (the caller's chunks end where a block ends).
    Without it every caller's program is the one it was.  Not carried
    under tp.
    """
    if upper is not None:
        positions = upper
    impl = resolve_packed_impl(impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype, q.shape[0],
                               q.shape[1] // k_cache.shape[1])
    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        # traced, like `_store_planes`' layer: the kernel (a jit of its
        # own) is traced and lowered once a program, not once a layer
        layer = jnp.int32(layer)
        tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        if tp > 1:
            if lower is not None or upper is not None:
                raise NotImplementedError("lower / upper under tp > 1")
            return _packed_pallas_tp(
                q, k_cache, v_cache, layer, block_tables, seg_ids,
                positions, valid, mesh=mesh, interpret=interpret,
                k_scale=k_scale, v_scale=v_scale,
            )
        from .pallas_packed_prefill import packed_prefill_attention_pallas

        return packed_prefill_attention_pallas(
            q, k_cache, v_cache, layer, block_tables, seg_ids,
            positions, valid, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale, lower=lower,
        )
    if impl != "xla":
        raise ValueError(
            f"unknown packed-prefill impl {impl!r}; expected "
            + " | ".join(PACKED_IMPLS)
        )
    S = block_tables.shape[0]
    out = jnp.zeros(q.shape, jnp.float32)
    for s in range(S):  # static unroll: S = co-scheduled segment rows
        seg_mask = (seg_ids == s) & valid
        o_s = _segment_flash(q, k_cache, v_cache, layer, block_tables[s],
                             seg_mask, positions, chunk_cols,
                             k_scale, v_scale, lower)
        out = jnp.where(seg_mask[:, None, None], o_s, out)
    return out.astype(q.dtype)
