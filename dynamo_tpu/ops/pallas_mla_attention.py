"""Pallas TPU kernels over the latent (MLA) cache: the weight-absorbed
DECODE read, the decode step's token write, and (PR 49, at the end of
this module's first half: `mla_prefill_pallas`) the PREFILL read as one
flash pass over the pool's live blocks with K and V materialised a key
tile and head at a time in VMEM.

The decode kernel is the latent-cache sibling of pallas_paged_attention._decode_kernel, built
on the same chunk DMA contract (`make_chunk_dma`, `make_chunk_chain`,
`chunk_chain_planes`: one definition site): the two pools
    c_cache  [L, 1, nb, R,  bs]   latents
    kr_cache [L, 1, nb, dr, bs]   shared rope keys
are the transposed nkv = 1 layout that contract moves, they go in WHOLE
and in their resident layout, the DMA descriptor indexes layer and
block, a lane copies only the blocks that hold live positions and a
lane with kv_len 0 copies nothing.

What differs from the GQA kernel is both matmuls.  A chunk's consume is
    s   = qa[nh, R] @ c[R, S] + qr[nh, dr] @ kr[dr, S]
    acc += softmax-weights[nh, S] @ c[R, S]^T
so the latent chunk is read ONCE from HBM and used as key and as value:
(R + dr) x 2 B a live token and layer, the floor's count.  All heads
share the one latent "head", so the score matmul has M = nh rows where
the GQA kernel has a group's.  Matmul operands are the cache's bf16;
scores, scale, softmax and the accumulator are fp32; the output is the
latent-space context [B, nh, R] in fp32 and the caller up-projects it
by W_UV as the jnp path does.  There is no int8 and no bias branch: the
latent cache is bf16 by design (models/deepseek.py kv_cache_shapes).

The arithmetic follows the jnp body's to about 1e-6 of a value, closer
than the GQA kernel follows its own: the queries go in as they are and
the fp32 scores take the scale (folded into a query it would be rounded
to bf16 again), and the softmax weights meet the latent as a bf16 pair
hi + lo (two MXU passes; one bf16 weight is 2^-9 off).  Why it matters
here: both cells' models pick 6 or 8 of 64..512 experts a token and
layer from random weights, and a change of 0.1 % in one attention output
flips a pick somewhere in 7 layers at about half the positions, after
which the logits differ by 0.1-0.4 of their range (parent and kernel
alike read a median of 0.09-0.11 against the float32 reference at
Moonlight's 8 layers, chip_logits.py, PR 36): every token this kernel
rounds otherwise than the jnp body is a chance to leave its trajectory.

tests/test_mla.py holds the kernels to the jnp forms under the
interpreter on the CPU, tests/test_tpu_compile.py compiles them for a
described v5e inside both families' decode bursts and prefill programs
(and holds the prefill body's executable to 1.5 x the jnp form's),
chip_smoke.py checks the compiled kernels against the jnp forms on the
chip, and benchmarks/bench_mla_decode.py / bench_mla_prefill.py time
them (PERF.md section 6, PR 36 and PR 49).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import (
    NEG_INF,
    chunk_chain_planes,
    make_chunk_chain,
    make_chunk_dma,
)


def _in_hbm(*pools):
    """The pools as operands that XLA must hand over IN HBM.  Left to
    itself the TPU compiler keeps a pool that fits (the rope keys: 67 MB
    at the chat cell's 8 layers x 512 blocks, 94 MB at the wide cell's)
    in VMEM across the decode step and moves it out to HBM and back
    around the custom calls: up to 2 x the pool a layer and step of
    traffic for nothing (compiled for a described v5e, PR 36)."""
    return tuple(pltpu.with_memory_space_constraint(x, pltpu.HBM)
                 for x in pools)


def _mla_decode_kernel(
    # scalar prefetch (pallas_paged_attention._decode_kernel's planes)
    tables_ref,   # [B, n_chunks * bpc] int32 physical block ids
    kv_lens_ref,  # [B] int32 valid positions (incl. current token); 0 =
                  #   a lane with nothing to attend (no chunk, output 0)
    base_ref,     # [B] int32 chunks consumed by all earlier rows
    next_ref,     # [B] int32 next row with a chunk (-1 = none)
    layer_ref,    # [1] int32 the layer of the pools this call reads
    # inputs
    scale_ref,    # [1] fp32 SMEM the softmax scale
    qa_ref,       # [1, nh, R]  VMEM absorbed queries
    qr_ref,       # [1, nh, dr] VMEM rope queries
    c_hbm,        # [L, 1, nb, R, bs]  ANY: the WHOLE pools, in HBM; the
    kr_hbm,       # [L, 1, nb, dr, bs]   DMA descriptor picks layer, block
    o_ref,        # [1, nh, R] fp32 VMEM latent-space context
    c_buf,        # [2, 1, R, S]  VMEM chunk buffers, double-buffered
    kr_buf,       # [2, 1, dr, S]
    sem,          # DMA semaphores [2 slots, 2 (latent / rope key)]
    *,
    bpc: int,
    bs: int,
):
    b = pl.program_id(0)
    S = bpc * bs  # positions per chunk
    kv_len = kv_lens_ref[b]
    n_chunks = pl.cdiv(kv_len, S)
    start_chunk, wait_chunk = make_chunk_dma(
        tables_ref, c_hbm, kr_hbm, c_buf, kr_buf, sem, bpc=bpc, bs=bs,
        layer=layer_ref[0],
        live_blocks=lambda row, c: pl.cdiv(kv_lens_ref[row] - c * S, bs))
    prime, chain_step = make_chunk_chain(start_chunk, wait_chunk)

    # Blocks past a chunk's live ones are never copied, so those lanes
    # of the buffers keep what they held.  The latent is the VALUE too:
    # p is exactly 0 there but 0 * NaN is NaN, so zero it once a launch,
    # before the first DMA; afterwards it only ever holds cache data.
    # (Masked scores never read the rope keys.)
    @pl.when(b == 0)
    def _():
        c_buf[...] = jnp.zeros(c_buf.shape, c_buf.dtype)

    base = base_ref[b]
    prime(b, n_chunks, base)
    next_row = next_ref[b]
    qa = qa_ref[0]   # [nh, R] bf16
    qr = qr_ref[0]   # [nh, dr]
    nh, R = qa.shape
    scale = scale_ref[0]

    def body(c, carry):
        m, l, acc = carry
        slot = chain_step(b, c, n_chunks, base, next_row)
        lat = c_buf[slot, 0]    # [R, S]: key AND value, read once
        s = jax.lax.dot_general(
            qa, lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = (s + jax.lax.dot_general(
            qr, kr_buf[slot, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)) * scale    # [nh, S]
        pos = c * S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        # [nh, S] x [R, S] over the lane axis -> [nh, R], the weights as
        # a pair of the operand dtype (module docstring)
        p_hi = p.astype(lat.dtype)
        p_lo = (p - p_hi.astype(jnp.float32)).astype(lat.dtype)
        pv = sum(jax.lax.dot_general(
            part, lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for part in (p_hi, p_lo))
        return m_new, l, acc * alpha + pv

    m0 = jnp.full((nh, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    a0 = jnp.zeros((nh, R), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    # a row with no chunk has l == 0: its output is 0, not 0/0
    o_ref[0] = acc / jnp.maximum(l, 1e-30)


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit,
    static_argnames=("blocks_per_chunk", "interpret"),
)
def mla_decode_pallas(
    q_abs: jax.Array,         # [B, nh, R]  absorbed queries
    q_rope: jax.Array,        # [B, nh, dr]
    c_cache: jax.Array,       # [L, 1, nb, R, bs]
    kr_cache: jax.Array,      # [L, 1, nb, dr, bs]
    layer,                    # int scalar, traced: one trace and one
                              #   lowering serve every MLA layer of a
                              #   program (paged_attention_decode_pallas)
    block_tables: jax.Array,  # [B, max_blocks] int32
    kv_lens: jax.Array,       # [B] int32, valid positions incl. current;
                              #   0 = idle lane (reads nothing, output 0)
    scale,                    # softmax scale, applied to the fp32 scores
    *,
    blocks_per_chunk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """softmax(q_abs . c + q_rope . k_R) . c over each lane's live
    latent blocks -> the latent-space context [B, nh, R] in fp32 (the
    caller applies W_UV).  HBM traffic is the live context, once."""
    B, nh, R = q_abs.shape
    dr = q_rope.shape[-1]
    bs = c_cache.shape[4]
    max_blocks = block_tables.shape[1]
    # chunks of up to 8 blocks, as the GQA kernel: [R, 1024] bf16 twice
    # over is 2 MB of VMEM at R = 512
    bpc = blocks_per_chunk or max(1, min(max_blocks, -(-1024 // bs)))
    S = bpc * bs
    block_tables, kv_lens, base, next_row = chunk_chain_planes(
        block_tables, kv_lens, bpc, bs)

    if not interpret:
        c_cache, kr_cache = _in_hbm(c_cache, kr_cache)

    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, bpc=bpc, bs=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, nh, R), lambda b, *refs: (b, 0, 0)),
                pl.BlockSpec((1, nh, dr), lambda b, *refs: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, nh, R), lambda b, *refs: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, R, S), c_cache.dtype),
                pltpu.VMEM((2, 1, dr, S), kr_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * nh * (3 * R + dr) * max_blocks * bs,
            bytes_accessed=B * max_blocks * bs * (R + dr)
            * c_cache.dtype.itemsize,
            transcendentals=B * nh * max_blocks * bs,
        ),
        interpret=interpret,
    )(block_tables, kv_lens, base, next_row,
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(scale, jnp.float32).reshape(1),
      q_abs.astype(c_cache.dtype), q_rope.astype(c_cache.dtype),
      c_cache, kr_cache)


# What one (query tile, key tile) pair of the PREFILL kernel is, where the
# caller names none: heads unrolled a loop step, query tokens a tile,
# block columns a key tile.  Chosen by THREE numbers a candidate (PERF.md
# section 6, PR 49; benchmarks/bench_mla_prefill.py --tune): ms a call
# on the chip, seconds to compile, and bytes of executable -- Mosaic's
# code for the body is embedded once a layer in every kernel-bearing
# prefill program and is read, deserialized and loaded at every start
# (PR 48's body, 8 heads a step and a pair with and without the mask,
# came to 27.0 MB and 14 s for 8 layers and cost Moonlight 11 s of
# set-up).  Mosaic unrolls every vector operation over its vregs, so the
# code grows with heads x bodies x tile area: 2 heads is the least that
# keeps a step's rope queries whole 128-lane tiles at dr = 64, and there
# is ONE body, under the mask (on the chip the mask's selects hide under
# the matmuls: 1.026 against 1.015 ms).  8.3 MB and 2.8 s for 8 layers
# (the jnp form: 8.1 MB, 4.7 s), 1.03 / 2.15 ms a 2048-token call at
# Ling's 32 heads and context 0 / 2048, 0.53 at Moonlight's 16.
LATENT_HEADS_A_STEP, LATENT_TOKEN_BLOCK, LATENT_CHUNK_COLS = 2, 512, 4
# What the call may claim of VMEM, as the packed kernel's: XLA takes a
# custom call's limit out of what its own fusions may keep there for
# the WHOLE program: at 96 MB the ten short-convolution fusions of
# Ling's 2048-token prefill program took 0.72 ms each where they take
# 0.14, and the program 72.0 ms where it takes 65.0 at 64 MB (and at 48
# and 32: my chip runs, PR 49).
LATENT_VMEM_LIMIT = 64 * 1024 * 1024


def _mla_prefill_kernel(
    # scalar prefetch
    layer_ref,    # [1] int32 the pools' layer
    tables_ref,   # [S * wp] int32 physical block ids, row-major
    fetch_ref,    # [n_q * n_c] int32 key tile whose blocks a step holds
    runs_ref,     # [n_q * n_c] int32 0 = a pair no query of the tile sees
    # inputs
    pos_ref,      # [TB, 1] int32 absolute position a query (-1 = padding)
    qn_ref,       # [TB, nh * dn] the tile's queries, a head every dn lanes
    qr_ref,       # [TB, nh * dr] their rope parts, a head every dr lanes
    wk_ref,       # [nh, dn, R] W_UK^T, whole
    wv_ref,       # [nh, dv, R] W_UV^T, whole
    *rest,        # cc latent blocks [R, bs], cc rope-key blocks [dr, bs],
                  #   o_ref [TB, nh * dv], m, l [nh, TB, 128], acc [nh, TB, dv]
    cc: int,
    H: int,
    scale: float,
):
    c_refs, kr_refs = rest[:cc], rest[cc:2 * cc]
    o_ref, m_sc, l_sc, acc_sc = rest[2 * cc:]
    i, j = pl.program_id(0), pl.program_id(1)
    n_c = pl.num_programs(1)
    TB = pos_ref.shape[0]
    nh, dn, _ = wk_ref.shape
    dv = wv_ref.shape[1]
    dr, bs = kr_refs[0].shape
    tk = cc * bs

    # loops over the heads, not unrolled: the body's size is what a
    # start pays for
    def lanes(h, width):
        return pl.ds(pl.multiple_of(h * width, width), width)

    @pl.when(j == 0)
    def _():
        def clear(h, _):
            m_sc[h] = jnp.full(m_sc.shape[1:], NEG_INF, jnp.float32)
            l_sc[h] = jnp.zeros(l_sc.shape[1:], jnp.float32)
            acc_sc[h] = jnp.zeros(acc_sc.shape[1:], jnp.float32)
            return 0
        jax.lax.fori_loop(0, nh, clear, 0)

    @pl.when(runs_ref[i * n_c + j] != 0)
    def _():
        # the tile's cc blocks side by side, keys on lanes as they lie
        lat, kr = (refs[0][...] if cc == 1 else jnp.concatenate(
            [r[...] for r in refs], axis=1) for refs in (c_refs, kr_refs))
        # the keys are the query's own row's, from position j * tk: a
        # query keeps those at or before its own (padding keeps none)
        keep = j * tk + jax.lax.broadcasted_iota(
            jnp.int32, (TB, tk), 1) <= pos_ref[...]

        def step(g, _):
            qr = qr_ref[:, lanes(g, H * dr)]       # H heads' rope queries
            for u in range(H):
                h = g * H + u
                # this head's K and V of the key tile, up-projected from
                # the latent here and live for this pair only
                k_h = jnp.dot(wk_ref[h], lat,
                              preferred_element_type=jnp.float32)
                v_h = jnp.dot(wv_ref[h], lat,
                              preferred_element_type=jnp.float32)
                sc = jnp.dot(qn_ref[:, lanes(h, dn)], k_h.astype(lat.dtype),
                             preferred_element_type=jnp.float32)
                sc = (sc + jnp.dot(qr[:, u * dr:(u + 1) * dr], kr,
                                   preferred_element_type=jnp.float32)
                      ) * scale
                sc = jnp.where(keep, sc, NEG_INF)
                m_prev = m_sc[h][:, :1]
                m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a query with nothing kept so far has m_new = NEG_INF
                # and exp(0) = 1 for a pair that is out: zero it
                p = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
                l_sc[h] = alpha * l_sc[h] + p.sum(axis=1, keepdims=True)
                acc_sc[h] = acc_sc[h] * alpha + jax.lax.dot_general(
                    p.astype(lat.dtype), v_h.astype(lat.dtype),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_sc[h] = jnp.broadcast_to(m_new, m_sc.shape[1:])
            return 0

        jax.lax.fori_loop(0, nh // H, step, 0)

    @pl.when(j == n_c - 1)
    def _():
        # padding queries have l == 0 -> 0
        def put(h, _):
            o_ref[:, lanes(h, dv)] = (acc_sc[h] / jnp.maximum(
                l_sc[h][:, :1], 1e-20)).astype(o_ref.dtype)
            return 0
        jax.lax.fori_loop(0, nh, put, 0)


def prefill_tile_plan(ctx_lens, true_lens, tiles_a_row: int, TB: int,
                      n_c: int, tk: int):
    """Which (query tile, key tile) pairs of the rows run.  Query tile t
    of row s holds the row's chunk tokens [t * TB, (t + 1) * TB), at
    positions ctx + token, `true` of the row's tokens real; key tile j
    the row's positions [j * tk, (j + 1) * tk).  -> (runs, fetch), each
    [S * tiles_a_row, n_c] int32: a pair runs up to the tile's farthest
    real query (none where it has no real query), and a skipped step
    names the blocks of the last pair that ran, so nothing moves for
    it."""
    q0 = jnp.arange(tiles_a_row, dtype=jnp.int32)[None, :] * TB
    real = jnp.clip(true_lens[:, None] - q0, 0, TB)          # [S, tiles]
    far = ctx_lens[:, None] + q0 + real - 1
    first = jnp.arange(n_c, dtype=jnp.int32) * tk             # [n_c]
    runs = (real > 0)[..., None] & (first <= far[..., None])
    live = jnp.sum(runs, axis=2, keepdims=True, dtype=jnp.int32)
    fetch = jnp.clip(jnp.arange(n_c, dtype=jnp.int32), 0,
                     jnp.maximum(live - 1, 0))
    return (runs.astype(jnp.int32).reshape(-1, n_c),
            fetch.reshape(-1, n_c))


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs (prefill / prefill_batched); direct calls are bench/test-only
    jax.jit,
    static_argnames=("heads_a_step", "token_block", "chunk_cols",
                     "interpret"),
)
def mla_prefill_pallas(
    q_nope: jax.Array,        # [S, T, nh, dn] the rows' queries (no rope)
    q_rope: jax.Array,        # [S, T, nh, dr] (rope applied)
    c_cache: jax.Array,       # [L, 1, nb, R, bs], the chunk ALREADY in it
    kr_cache: jax.Array,      # [L, 1, nb, dr, bs]
    layer,                    # int scalar, traced: one trace and one
                              #   lowering serve every MLA layer
    block_tables: jax.Array,  # [S, mb] int32
    ctx_lens: jax.Array,      # [S] tokens cached before each row's chunk
    true_lens: jax.Array,     # [S] real tokens of each row (0: no row)
    w_uk: jax.Array,          # [nh, R, dn]
    w_uv: jax.Array,          # [nh, R, dv]
    *,
    heads_a_step: int = 0,    # 0 = the LATENT_* defaults
    token_block: int = 0,
    chunk_cols: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """The MLA layers' PREFILL read as one flash pass over the live
    blocks of the latent pool: query (s, t) attends its row's table at
    positions [0, ctx[s] + t], the chunk's own latents among them (they
    are written first: mla_attention.mla_write_rows).  Returns
    [S, T, nh, dv] in the queries' dtype, 0 for a row's padding.

    The MATERIALISED form: a key tile is the latent [R, tk] and its
    rope keys [dr, tk], moved from the pool where it lies by the
    table's block ids (a block a BlockSpec, as packed prefill's kernel
    moves K and V), and each head's K and V of the tile are up-projected
    in VMEM, live for that (query tile, key tile) pair only: 640 FLOP a
    pair and head where the absorbed form pays 2176.  Scores, running
    max, sum and accumulator never leave VMEM; key tiles above a query
    tile's frontier are skipped, compute and DMA (`prefill_tile_plan`).
    Operands bf16 (the cache's and the weights'), products accumulated
    in float32, the float32 scores scaled, softmax and accumulator
    float32: the jnp form's arithmetic at XLA's default precision on
    the chip.  The heads run `heads_a_step` a step of an in-kernel
    loop: the body's size is what a start pays for (module constants)."""
    S, T, nh, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = w_uv.shape[-1]
    R, bs = c_cache.shape[3:]
    mb = block_tables.shape[1]
    H = min(heads_a_step or LATENT_HEADS_A_STEP, nh)
    if nh % H:
        raise ValueError(f"{nh} heads do not split into steps of {H}")
    TB = min(token_block or LATENT_TOKEN_BLOCK, T)
    tiles = -(-T // TB)
    Tp = tiles * TB
    cc = max(1, min(mb, chunk_cols or LATENT_CHUNK_COLS))
    n_c = -(-mb // cc)
    wp = n_c * cc
    tk = cc * bs
    # padded table entries point at the garbage block (0): no pair that
    # is computed reaches them (a tile runs to its queries' frontier)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, wp - mb))).reshape(-1)
    ctx_lens = ctx_lens.astype(jnp.int32)
    true_lens = true_lens.astype(jnp.int32)
    t = jnp.arange(Tp, dtype=jnp.int32)[None, :]
    pos = jnp.where(t < true_lens[:, None], ctx_lens[:, None] + t, -1)
    runs, fetch = prefill_tile_plan(ctx_lens, true_lens, tiles, TB, n_c, tk)

    dt = c_cache.dtype
    if not interpret:
        # (what XLA's fusions may keep in VMEM, a rope-key pool that
        # fits would be: moved out and back around every call)
        c_cache, kr_cache = _in_hbm(c_cache, kr_cache)

    def stream(q):   # [S, T, nh, d] -> [S * Tp, nh * d]
        q = jnp.pad(q.astype(dt), ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        return q.reshape(S * Tp, -1)

    def block_of(b):
        def index(i, j, layer_ref, tables_ref, fetch_ref, runs_ref):
            return (layer_ref[0], 0,
                    tables_ref[(i // tiles) * wp
                               + fetch_ref[i * n_c + j] * cc + b], 0, 0)
        return index

    row = lambda i, j, *refs: (i, 0)
    whole = lambda i, j, *refs: (0, 0, 0)
    n_q = S * tiles
    pairs = n_q * TB * n_c * tk
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, cc=cc, H=H,
                          scale=1.0 / math.sqrt(dn + dr)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_q, n_c),
            in_specs=[pl.BlockSpec((TB, 1), row),
                      pl.BlockSpec((TB, nh * dn), row),
                      pl.BlockSpec((TB, nh * dr), row),
                      pl.BlockSpec((nh, dn, R), whole),
                      pl.BlockSpec((nh, dv, R), whole)]
            + [pl.BlockSpec((None, None, None, R, bs), block_of(b))
               for b in range(cc)]
            + [pl.BlockSpec((None, None, None, dr, bs), block_of(b))
               for b in range(cc)],
            out_specs=pl.BlockSpec((TB, nh * dv), row),
            scratch_shapes=[pltpu.VMEM((nh, TB, 128), jnp.float32),
                            pltpu.VMEM((nh, TB, 128), jnp.float32),
                            pltpu.VMEM((nh, TB, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S * Tp, nh * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=LATENT_VMEM_LIMIT,
        ),
        # an upper bound: every pair of the rows x their tables (the
        # tiles above a query tile's frontier are not computed)
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * nh * (dn + dr + dv)
            + 2 * n_q * n_c * tk * nh * R * (dn + dv),
            bytes_accessed=n_q * n_c * tk * (R + dr) * dt.itemsize,
            transcendentals=pairs * nh,
        ),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables, fetch.reshape(-1),
      runs.reshape(-1), pos.reshape(-1, 1), stream(q_nope), stream(q_rope),
      jnp.swapaxes(w_uk, 1, 2).astype(dt), jnp.swapaxes(w_uv, 1, 2).astype(dt),
      *[c_cache] * cc, *[kr_cache] * cc)
    return out.reshape(S, Tp, nh, dv)[:, :T]


def _write_token_kernel(
    # scalar prefetch
    blocks_ref,   # [B] int32 the block each lane's token falls in
    offs_ref,     # [B] int32 its column in that block
    valid_ref,    # [B] int32 0 = a lane that writes nothing
    layer_ref,    # [1] int32
    # inputs
    c_t_ref,      # [R, Bp]  VMEM the new latents, lane b in column b
    kr_t_ref,     # [dr, Bp] VMEM the new rope keys
    c_in, kr_in,  # the WHOLE pools (ANY), aliased to the outputs
    c_out, kr_out,
    c_plane,      # [R, bs]  VMEM one block's planes
    kr_plane,     # [dr, bs]
    sem,          # DMA semaphores [2 (latent / rope key)]
):
    del c_in, kr_in    # one buffer with c_out / kr_out
    b = pl.program_id(0)

    @pl.when(valid_ref[b] != 0)
    def _():
        at = (layer_ref[0], 0, blocks_ref[b])
        moves = [(c_out.at[at], c_plane, sem.at[0]),
                 (kr_out.at[at], kr_plane, sem.at[1])]
        reads = [pltpu.make_async_copy(hbm, vmem, s)
                 for hbm, vmem, s in moves]
        for dma in reads:
            dma.start()
        # lane b's column, set down at the token's offset: a one-hot
        # product (exact), then a select against the plane
        bp, bs = c_t_ref.shape[1], c_plane.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (bp, bs), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bp, bs), 1)
        sel = ((lane == b) & (col == offs_ref[b])).astype(c_plane.dtype)
        here = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1) == offs_ref[b]
        for dma, new_t, (_, plane, _) in zip(reads, (c_t_ref, kr_t_ref),
                                             moves):
            put = jax.lax.dot_general(
                new_t[...], sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(plane.dtype)
            dma.wait()
            plane[...] = jnp.where(here, put, plane[...])
        writes = [pltpu.make_async_copy(vmem, hbm, s)
                  for hbm, vmem, s in moves]
        for dma in writes:
            dma.start()
        for dma in writes:
            dma.wait()


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("interpret",))
def mla_write_token_pallas(
    c_cache: jax.Array,       # [L, 1, nb, R, bs]
    kr_cache: jax.Array,      # [L, 1, nb, dr, bs]
    layer,                    # int scalar, traced
    c: jax.Array,             # [B, R]  the step's new latents
    kr: jax.Array,            # [B, dr] and rope keys
    blocks: jax.Array,        # [B] int32 the block each lane writes
    offsets: jax.Array,       # [B] int32 the column in it
    valid: jax.Array,         # [B] bool: lanes that write
    *,
    interpret: bool = False,
):
    """Each valid lane's new column into both pools, in place and in
    their resident layout: the block's [R, bs] and [dr, bs] planes come
    into VMEM, take the column and go back (the read-modify-write of
    paged_attention._store_columns, both tensors at once).  A kernel
    and not that XLA loop because of where XLA then KEEPS a small pool:
    with `_store_columns` in a decode step the TPU compiler holds the
    rope-key pool (67 MB at the chat cell's 8 layers x 512 blocks, 94 MB
    at the wide cell's) in VMEM across the step for the loop's sake and
    moves it out to HBM and back around every latent kernel call, whose
    operands must lie in HBM: 2 x the pool a layer and step (compiled for
    a described v5e, PR 36).  With custom calls as the pools' only users
    inside the step they stay in HBM.  (The pools a program is handed
    have to be DONATED to it, as every caller on a chip does: the
    engine's programs, benchmark/chip_logits*.py.  The copy XLA makes of
    a pool that is not, it wants in VMEM, and its memory-space
    assignment then aborts the compile on the colour these outputs
    carry.)"""
    B, R = c.shape
    bs = c_cache.shape[4]
    bp = -(-B // 128) * 128    # the one-hot product contracts over lanes

    def columns(x, cache):    # [B, w] -> [w, bp], lane b in column b
        return jnp.pad(x.astype(cache.dtype), ((0, bp - B), (0, 0))).T

    whole = lambda x: pl.BlockSpec(x.shape, lambda b, *refs: (0, 0))
    c_t, kr_t = columns(c, c_cache), columns(kr, kr_cache)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _write_token_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[whole(c_t), whole(kr_t), any_, any_],
            out_specs=[any_, any_],
            scratch_shapes=[
                pltpu.VMEM((R, bs), c_cache.dtype),
                pltpu.VMEM((kr.shape[1], bs), kr_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        # in HBM, and with them the operands they alias (`_in_hbm`)
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) if interpret
                   else pltpu.HBM(x.shape, x.dtype)
                   for x in (c_cache, kr_cache)],
        # (operand indices count the scalar-prefetch arguments)
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blocks.astype(jnp.int32), offsets.astype(jnp.int32),
      valid.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      c_t, kr_t, c_cache, kr_cache)

