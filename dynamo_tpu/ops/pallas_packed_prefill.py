"""Pallas TPU packed-prefill kernel: segment-aware causal flash attention
over the packed token stream, one call a layer.

What `packed_prefill.packed_prefill_attention` runs for "pallas" /
"pallas_interpret", and for "auto" where `resolve_packed_impl` says so.
The XLA reference there runs one flash pass PER SEGMENT ROW over the
WHOLE packed stream and table in float32, writes its score block to HBM
every step, and masks foreign tokens and the pairs above the causal
diagonal after computing them.  This kernel computes the same pairs'
attention and none of the rest:

  * **Tile skip.**  The grid is (KV head and run of its query heads,
    query tile, key tile); a key tile is `chunk_cols` block columns of
    ONE segment row's table.  The
    wrapper works out from `seg_ids` / `positions` which pairs run: a
    segment row's key tiles up to the farthest position that one of the
    tile's queries of that row holds — none where the row owns no query
    of the tile (the skip by segment; the packed stream is
    segment-contiguous, so almost every query tile meets one row), none
    above the tile's causal frontier, none past the stream.  The flags
    ride in as scalar prefetch; no [T, S] mask plane exists.  A pair
    under the NEAREST position of a tile that one row owns whole needs
    no mask at all and takes a body without one; only the tiles on the
    diagonal (and at a segment boundary) build the mask, from the two
    [TB, 1] planes and an iota.

  * **Context by block id.**  K and V come straight from the pool, a
    block a BlockSpec, indexed by the table's physical ids from scalar
    prefetch (head-major TRANSPOSED blocks, [hd, bs] planes with the
    keys on lanes: nothing is transposed for the MXU and no gathered
    copy of the context exists in HBM).  Pallas's own pipeline fetches
    the next pair's blocks under the current pair's matmuls; a skipped
    step names the blocks already held, so nothing moves for it.

  * **The group shares the tile.**  A query tile is [TB, Gk * hd]: Gk
    query heads of one KV head side by side, sliced by lanes, so the
    stream's [T, nh, hd] queries and output are used where they lie (no
    head-major copy) and each key tile is read once for them.  Gk is
    the whole group up to `GROUP_WHOLE` heads a KV head and
    `GROUP_HEADS` of them above (`_group_heads`): the stream's heads lie
    KV head major, so a run of Gk heads is the next column block of the
    same stream and the grid's first axis walks (KV head, run of
    heads); a key tile is then fetched once a run.

Numerics: matmul operands in the cache's dtype (the queries', bf16 on
the serving path; an int8 cache's blocks and their per-position fp32
scale rows are moved as they are and dequantized in VMEM), running max,
sum and accumulator float32 in VMEM scratch, one row of them a query
and head; masked pairs contribute exp = 0 explicitly (not just NEG_INF
scores), so a query's sums are untouched while other rows' tiles pass —
which lets all segment rows share one carry.  Matches the XLA path to
bf16 matmul tolerance.  Interpret mode keeps the kernel runnable on CPU
for tier-1 (tests/test_packed_pallas.py), tests/test_tpu_compile.py
compiles it for a described v5e at serving widths and holds the
`prefill_packed` program to one custom call a layer and no score block,
and chip_smoke.py checks the compiled kernel against the XLA path on the
chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


# query tokens and block columns of one (query tile, key tile) pair where
# the caller names none: the pair at which the kernel was the faster at
# 2048 tokens over 16 / 32 / 50 blocks (PERF.md section 6, PR 34)
TOKEN_BLOCK, CHUNK_COLS = 512, 8

# query heads of one KV head that ONE kernel body holds, and the largest
# group a body still takes whole.  The body is unrolled over its heads
# and keeps their running max, sum and accumulator in VMEM ([heads, TB,
# 128] x 2 + [heads, TB, hd] float32: 12 MB at 16 heads); the tiles above
# were chosen at 4 heads a KV head (Mistral).  One layer-call of 2048
# tokens on a v5e, ms at context 0 / 8192 / 22528, ONE body | 4 heads a
# body, bit for bit the same output (PERF.md section 6, PR 54):
#   16 a KV head (128 over 8), inside Command A+'s prefill program under
#   the profiler                read 2.46 / 11.19 / 25.25 | 1.98 / 11.04 / 24.76
#                               band 2.60 /  6.85 /  6.85 | 1.90 /  5.17 /  5.17
#   the op alone (benchmarks/bench_window_reads.py; it reads the 16-head
#   body 1.5-1.9 x slower than the program runs it, cause unknown)
#                               read 4.38 / 21.33 / 38.93 | 3.38 / 12.43 / 26.23
#                               band 7.63 / 10.46 / 10.47 | 4.10 /  7.41 /  7.40
#    8 a KV head (32 over 4), the op alone
#                               read 0.60 /  2.72 /  6.10 | 0.63 /  2.95 /  6.37
#                               band 0.70 /  1.53 /  1.53 | 0.82 /  1.62 /  1.63
# (`lax.map` over four calls of 4, the op alone: 3.60 / 12.59 / 26.25 and
# 4.34 / 7.60 / 7.60 beside two copies of q and the output.)  What the
# 16-head body costs every start: 41.7 MB of code in that program
# against 20.9, 30-38 s against 10-18 to compile.
GROUP_HEADS, GROUP_WHOLE = 4, 8


def _group_heads(G: int) -> int:
    """Query heads a kernel body takes of a KV head's G: read from the
    shape and from nothing else."""
    return GROUP_HEADS if G > GROUP_WHOLE and G % GROUP_HEADS == 0 else G


def body_lanes(G: int, head_dim: int) -> int:
    """Lanes of one body's query tile for G query heads a KV head: its
    heads side by side, a head every head_dim lanes.  The rule that
    decides "auto" asks for whole 128-lane vregs
    (packed_prefill.resolve_packed_impl)."""
    return _group_heads(G) * head_dim


def _packed_kernel(
    # scalar prefetch
    layer_ref,     # [1] int32 the pool's layer
    tables_ref,    # [S * wp] int32 physical block ids, row-major
    fetch_ref,     # [n_q * n_kt] int32 key tile whose blocks a step holds
    flag_ref,      # [n_q * n_kt] int32 0 skip | 1 under the mask | 2 whole
    # inputs
    seg_ref,       # [TB, 1] int32 segment row per query (-1 = padded)
    pos_ref,       # [TB, 1] int32 absolute position per query
    q_ref,         # [TB, G * hd] this tile's queries of G heads of one
                   #   KV head, pre-scaled, a head every hd lanes
    *rest,         # (`banded`: lo_ref [TB, 1] int32, the first position
                   #   a query keeps,) cc K blocks [hd, bs], cc V blocks
                   #   (+ cc + cc scale rows [1, bs] when quantized),
                   #   o_ref, m, l, acc
    G: int,
    cc: int,
    n_c: int,
    quantized: bool,
    banded: bool = False,
):
    if banded:
        lo_ref, rest = rest[0], rest[1:]
    k_refs, v_refs = rest[:cc], rest[cc:2 * cc]
    rest = rest[2 * cc:]
    if quantized:
        ks_refs, vs_refs = rest[:cc], rest[cc:2 * cc]
        rest = rest[2 * cc:]
    o_ref, m_sc, l_sc, acc_sc = rest
    i, j = pl.program_id(1), pl.program_id(2)
    n_kt = pl.num_programs(2)
    TB = q_ref.shape[0]
    hd, bs = k_refs[0].shape
    tk = cc * bs

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def planes(refs, scale_refs):
        """The tile's cc blocks side by side: [hd, tk], keys on lanes
        (blocks lie [hd, bs] in the pool: nothing is transposed)."""
        blocks = [r[...] for r in refs]
        if quantized:
            # int8 moved, dequantized here by each key's own scale;
            # operands in the queries' dtype, float32 sums
            blocks = [(b.astype(jnp.float32) * sr[...]).astype(q_ref.dtype)
                      for b, sr in zip(blocks, scale_refs)]
        return blocks[0] if cc == 1 else jnp.concatenate(blocks, axis=1)

    def pair(masked: bool):
        k = planes(k_refs, ks_refs if quantized else None)
        v = planes(v_refs, vs_refs if quantized else None)
        if masked:
            # the tile's keys are segment row j // n_c's, from position
            # (j % n_c) * tk: a query keeps those of its own row at or
            # before its own position
            span = (j % n_c) * tk + jax.lax.broadcasted_iota(
                jnp.int32, (TB, tk), 1)
            keep = (seg_ref[...] == j // n_c) & (span <= pos_ref[...])
            if banded:
                keep = keep & (span >= lo_ref[...])
        for g in range(G):   # the group's heads share the key tile
            sc = jnp.dot(q_ref[:, g * hd:(g + 1) * hd], k,
                         preferred_element_type=jnp.float32)
            if masked:
                sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_sc[g][:, :1]
            m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            if masked:
                # a query with nothing kept so far has m_new = NEG_INF
                # and exp(0) = 1 for a pair that is out: zero it, so
                # other rows' tiles leave its sums untouched
                p = jnp.where(keep, p, 0.0)
            l_sc[g] = alpha * l_sc[g] + p.sum(axis=1, keepdims=True)
            acc_sc[g] = acc_sc[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[g] = jnp.broadcast_to(m_new, m_sc.shape[1:])

    flag = flag_ref[i * n_kt + j]
    pl.when(flag == 1)(lambda: pair(True))
    # every query of the tile keeps every key of the tile: no mask
    pl.when(flag == 2)(lambda: pair(False))

    @pl.when(j == n_kt - 1)
    def _():
        # queries no segment owns (the padded tail) have l == 0 -> 0,
        # the XLA path's untouched zero rows
        for g in range(G):
            o_ref[:, g * hd:(g + 1) * hd] = (acc_sc[g] / jnp.maximum(
                l_sc[g][:, :1], 1e-20)).astype(o_ref.dtype)


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs (prefill_packed/spec_verify); direct calls are bench/test-only
    jax.jit,
    static_argnames=("chunk_cols", "token_block", "group_heads",
                     "interpret"),
)
def packed_prefill_attention_pallas(
    q: jax.Array,             # [T, nh, hd] packed-stream queries (rope'd)
    k_cache: jax.Array,       # [L, nkv, num_blocks, hd, bs]
    v_cache: jax.Array,
    layer,                    # int or int32 scalar: traced, so ONE trace
                              #   and one kernel serve a program's layers
    block_tables: jax.Array,  # [S, mb] int32 per-segment block tables
    seg_ids: jax.Array,       # [T] int32 segment row per token
    positions: jax.Array,     # [T] int32 absolute position per token
    valid: jax.Array,         # [T] bool (False = padded tail)
    *,
    chunk_cols: int = 0,      # block columns per key tile (0 = CHUNK_COLS)
    token_block: int = 0,     # query tokens per tile (0 = TOKEN_BLOCK)
    group_heads: int = 0,     # query heads of a KV head a kernel body
                              #   (0 = `_group_heads`; tests and benches
                              #   name the whole group for the one-body
                              #   form)
    interpret: bool = False,
    k_scale: jax.Array = None,  # [L, nkv, num_blocks, bs] fp32 (int8)
    v_scale: jax.Array = None,
    lower: jax.Array = None,    # [T] int32: a query keeps its row's
                                #   positions lower <= pos <= its own (a
                                #   band: ops/window_attention.py); key
                                #   tiles wholly under a query tile's
                                #   bounds are skipped like those above
                                #   its frontier.  None: the program is
                                #   the one it was
) -> jax.Array:
    """The packed stream's attention as one kernel a layer
    (packed_prefill.packed_prefill_attention's "pallas" /
    "pallas_interpret", and its "auto" where `resolve_packed_impl` says
    so).  Returns [T, nh, hd] in q's dtype; tokens outside every segment
    (the padded tail) return 0."""
    T, nh, hd = q.shape
    _, nkv, _, _, bs = k_cache.shape
    G = nh // nkv
    Gk = group_heads or _group_heads(G)
    n_g, odd = divmod(G, Gk)  # runs of Gk heads a KV head
    if odd:
        raise ValueError(f"group_heads {Gk} does not divide the {G} query "
                         "heads of a KV head")
    S, mb = block_tables.shape
    quantized = k_scale is not None

    TB = min(token_block or TOKEN_BLOCK, _next_pow2(T))
    n_q = -(-T // TB)
    Tp = n_q * TB
    cc = max(1, min(mb, chunk_cols or CHUNK_COLS))
    n_c = -(-mb // cc)
    wp = n_c * cc
    # padded table entries point at the garbage block (0): no pair that
    # is computed reaches them (a tile runs to its queries' frontier)
    tables = jnp.pad(block_tables, ((0, 0), (0, wp - mb))).reshape(-1)
    tk = cc * bs
    n_kt = S * n_c

    # padded-tail / invalid tokens get segment -1: they match no
    # segment row, so no mask ever selects them and no tile runs on
    # their behalf
    seg_eff = jnp.where(valid, seg_ids, -1).astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    banded = lower is not None
    if Tp > T:
        seg_eff = jnp.pad(seg_eff, (0, Tp - T), constant_values=-1)
        positions = jnp.pad(positions, (0, Tp - T))
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
        if banded:
            lower = jnp.pad(lower, (0, Tp - T))

    # which (query tile, key tile) pairs run: a segment row's key tiles
    # up to the farthest position one of the tile's queries of that row
    # holds (none where it owns no query: the skip by segment), the rest
    # lies above the causal diagonal or past the stream; a pair under
    # the nearest position of a tile that one row owns whole needs no
    # mask.  [n_q, S, n_c] -> flat [n_q * n_kt], key tiles row-major
    seg2d = seg_eff.reshape(n_q, TB)
    pos2d = positions.reshape(n_q, TB)
    owned = seg2d[:, None, :] == jnp.arange(S, dtype=jnp.int32)[None, :,
                                                                 None]
    far = jnp.max(jnp.where(owned, pos2d[:, None, :], -1), axis=2)
    near = jnp.where(jnp.all(owned, axis=2), jnp.min(pos2d, axis=1)[:, None],
                     -1)
    first = jnp.arange(n_c, dtype=jnp.int32) * tk     # a tile's first key
    runs = first[None, None, :] <= far[:, :, None]
    whole = first[None, None, :] + tk - 1 <= near[:, :, None]
    if banded:
        # the band's other edge: no tile that ends under the nearest
        # bound of the row's queries, no mask only from the farthest
        lower = lower.astype(jnp.int32)
        lo2d = lower.reshape(n_q, TB)
        big = jnp.iinfo(jnp.int32).max
        lo_near = jnp.min(jnp.where(owned, lo2d[:, None, :], big), axis=2)
        lo_far = jnp.max(lo2d, axis=1)[:, None]
        runs = runs & (first[None, None, :] + tk - 1 >= lo_near[:, :, None])
        whole = whole & (first[None, None, :] >= lo_far[:, :, None])
    flags = (runs.astype(jnp.int32) + (runs & whole)).reshape(n_q, n_kt)
    # a step that is skipped names the blocks the pipeline already holds
    # (the last pair that ran, or the first that will): nothing is
    # fetched for it
    at = jnp.arange(n_kt, dtype=jnp.int32)[None, :]
    last = jax.lax.cummax(jnp.where(flags > 0, at, -1), axis=1)
    nxt = jnp.min(jnp.where(flags > 0, at, n_kt), axis=1, keepdims=True)
    fetch = jnp.where(last >= 0, last, nxt % n_kt).astype(jnp.int32)

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype) \
        .reshape(Tp, nh * hd)

    # the grid's first axis is (KV head, run of Gk heads), KV head major
    # like the stream's heads: step h reads column block h of q and the
    # pool's head h // n_g
    def block_of(b):
        def index(h, i, j, layer_ref, tables_ref, fetch_ref, flag_ref):
            t = fetch_ref[i * n_kt + j]
            return (layer_ref[0], h if n_g == 1 else h // n_g,
                    tables_ref[(t // n_c) * wp + (t % n_c) * cc + b], 0, 0)
        return index

    def row(h, i, j, *refs):
        return (i, 0)

    def heads(h, i, j, *refs):
        return (i, h)

    # K and V come straight from the pool, a block a descriptor, by the
    # table's physical ids: no gathered copy of the context exists
    plane = [pl.BlockSpec((None, None, None, hd, bs), block_of(b))
             for b in range(cc)]
    bound = [lower[:, None]] if banded else []
    inputs = [seg_eff[:, None], positions[:, None], qs] + bound \
        + [k_cache] * cc + [v_cache] * cc
    in_specs = [pl.BlockSpec((TB, 1), row), pl.BlockSpec((TB, 1), row),
                pl.BlockSpec((TB, Gk * hd), heads)] \
        + [pl.BlockSpec((TB, 1), row)] * len(bound) + plane + plane
    if quantized:
        # scale rows as [.., 1, bs] planes, so a block is a whole tile
        srow = [pl.BlockSpec((None, None, None, 1, bs), block_of(b))
                for b in range(cc)]
        inputs += [k_scale[..., None, :]] * cc + [v_scale[..., None, :]] * cc
        in_specs += srow + srow

    pairs = Tp * n_kt * tk
    out = pl.pallas_call(
        functools.partial(_packed_kernel, G=Gk, cc=cc, n_c=n_c,
                          quantized=quantized, banded=banded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nkv * n_g, n_q, n_kt),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((TB, Gk * hd), heads),
            scratch_shapes=[pltpu.VMEM((Gk, TB, 128), jnp.float32),
                            pltpu.VMEM((Gk, TB, 128), jnp.float32),
                            pltpu.VMEM((Gk, TB, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, nh * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        # an upper bound: every pair of the stream x its rows' tables
        # (the tiles above a query tile's frontier are not computed)
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * nh * hd,
            bytes_accessed=2 * n_q * nkv * n_g * n_kt * tk * hd
            * jnp.dtype(k_cache.dtype).itemsize,
            transcendentals=pairs * nh,
        ),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables, fetch.reshape(-1),
      flags.reshape(-1), *inputs)
    return out[:T].reshape(T, nh, hd)
