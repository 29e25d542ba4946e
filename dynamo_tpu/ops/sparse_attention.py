"""Learned sparse attention over the paged cache: a lightning indexer
scores every earlier token, the `topk` best are kept, attention runs
over those (the DeepSeek-sparse-attention scheme; models/keye.py).

For a query token t and every cached token s <= t of its sequence

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])     (float32)

over `H` index heads; kI is ONE index key a token, kept in a third
table-paged cache member [L, 1, blocks, D, bs] beside K and V (same
block table, same head-major transposed blocks).  S_t = the `topk`
tokens with the largest I[t, s], ties to the lower index, all of them
while t + 1 <= topk; one set a query token and layer, shared by every
attention head.  Attention is the usual GQA softmax over S_t only.

Selection (`topk_mask`) is exact and sort-free: the k-th largest score
of a row is found by bisection on the float's bit pattern (32 passes
that count the keys at or above a candidate), ties at the threshold by
a second bisection on the index; the mask is `score > it`, or `== it`
up to that index.  On a TPU the rows of a tile stay in VMEM for all
their passes (`_search_kth_pallas`); elsewhere the same loop runs over
the array (`_search_kth`).  A sort of [2048 queries, 26k keys] a layer
and chunk is what it replaces.

Decode (`sparse_decode_attention`): K and V of the whole live context
are read as for dense attention and the unchosen tokens are masked out
of the softmax (the Pallas decode kernel with a per-token bias where
`resolve_decode_impl` names it, the jnp gather elsewhere).  In the
pools' resident layout a block is [nkv, hd, bs]: a token is a COLUMN, 2
bytes in each of nkv x hd rows of 256, so gathering the chosen columns
moves far more of HBM than it uses: on a v5e, 8 lanes of 16385 tokens,
one layer, the masked read takes 1.10 ms and a gathered one took 4.68
(my chip runs, PR 33; PERF.md section 6).  A gathered read belongs with
a token-major member that makes the chosen rows contiguous (ROADMAP
R7a); `sparse_read_tokens` counts what is moved: the context.

Prefill (`sparse_prefill_attention`) over a packed stream, as
ops/packed_prefill.py: every query of a chunk has its own key set over
its segment's cache (the chunk's own tokens are written first).  Index
scores for the whole [T, context] rectangle, the exact mask, then one
flash pass over the context under that mask: a Pallas kernel on a TPU
(`_masked_flash_pallas`: no score leaves VMEM), an XLA scan elsewhere
(`_masked_flash`).  (2048 queries at the end of 16384 tokens, one
layer: 11.5 ms; each query gathering its own chosen columns took 1268.)

Not skipped while t + 1 <= topk: a program has one shape a bucket and
does not know its positions; there every valid key is chosen, and the
mask then leaves exactly the dense softmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .packed_prefill import _gather_blocks, _store_planes, plan_packed_write
from .paged_attention import NEG_INF, PALLAS_IMPLS, _store_columns

# keys scored against a chunk's queries at a time: [T, H, C] float32
_INDEX_CHUNK = 1024


def _dot(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum with a float32 result.  On the TPU the operands stay in
    their own dtype (bf16 feeds the MXU) and the sum is float32; the CPU
    runtime has no bf16 x bf16 -> float32 dot, so everywhere else, and
    for float32 operands, they are upcast first."""
    def upcast(a, b):
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))

    def native(a, b):
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)

    if a.dtype == jnp.float32 or b.dtype == jnp.float32:
        return upcast(a, b)
    return jax.lax.platform_dependent(a, b, tpu=native, default=upcast)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [.., nh, hd] x k [nkv, S, hd] -> [.., nh, S] float32 (query
    head i reads KV head i // group)."""
    nh, nkv = q.shape[-2], k.shape[0]
    qg = q.reshape(*q.shape[:-2], nkv, nh // nkv, q.shape[-1])
    return _dot("...kgh,ksh->...kgs", qg, k).reshape(
        *q.shape[:-2], nh, k.shape[1])


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [.., nh, S] float32 x v [nkv, S, hd] -> [.., nh, hd] float32;
    p goes to the MXU in v's dtype."""
    nh, nkv = p.shape[-2], v.shape[0]
    pg = p.reshape(*p.shape[:-2], nkv, nh // nkv, p.shape[-1])
    return _dot("...kgs,ksh->...kgh", pg.astype(v.dtype), v).reshape(
        *p.shape[:-2], nh, v.shape[-1])


# ---------------------------------------------------------------------------
# cache writes: any number of table-paged members at once
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.kv_write")
def write_token_members(caches, layer, xs, block_tables, ctx_lens, valid):
    """One decode token a lane into every table-paged member: xs[j]
    [B, heads_j, width_j] goes to column ctx_lens[b] % bs of block
    block_tables[b, ctx_lens[b] // bs] of caches[j] [L, heads_j, nb,
    width_j, bs], whole planes in the resident layout and only the
    lanes `valid` marks (paged_attention._store_columns)."""
    bs = caches[0].shape[-1]
    B = xs[0].shape[0]
    blocks = block_tables[jnp.arange(B), ctx_lens // bs]
    if valid is None:
        valid = jnp.ones((B,), bool)
    return _store_columns(tuple(caches), jnp.int32(layer), tuple(xs),
                          blocks, ctx_lens % bs, valid)


@jax.named_scope("dyn.kv_write")
def write_packed_members(caches, layer, xs, block_tables, seg_ids,
                         positions, valid):
    """A packed chunk into every table-paged member (packed_prefill.
    write_packed_kv for any number of members): each touched block's
    whole planes are rewritten, in the resident layout."""
    plan = plan_packed_write(block_tables, seg_ids, positions, valid,
                             caches[0].shape[-1])
    return _store_planes(tuple(caches), jnp.int32(layer), tuple(xs), *plan)


# ---------------------------------------------------------------------------
# the indexer's scores and the exact top-k mask
# ---------------------------------------------------------------------------


def _index_pairs(qi, wi, ki):
    """qi [..., H, D], wi [..., H], ki [..., D, C] -> [..., C] float32:
    sum_j w_j relu(qI_j . kI); the dot in the operands' dtype with
    float32 accumulation, everything after it in float32."""
    s = _dot("...hd,...dc->...hc", qi, ki)
    return jnp.einsum("...hc,...h->...c", jax.nn.relu(s),
                      wi.astype(jnp.float32))


_INT_MIN = -(1 << 31)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose SIGNED order is the floats' order; -0.0
    is read as +0.0 (they tie by index, as equal floats do).  No float
    maps to the smallest int32, which marks an entry out of the race."""
    x = jnp.where(x == 0, jnp.float32(0), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _search_kth(key, k: int, index_bits: int):
    """key [r, S] int32 (signed order; `_INT_MIN` = out of the race) ->
    (thr [r, 1], cut [r, 1]): thr the k-th largest key of each row
    (`_INT_MIN` where a row has fewer than k in the race), cut the
    index up to which a key EQUAL to thr is still among the k largest
    when ties go to the lower index.  Both by bisection on bits, one
    bit a pass: thr on the key's bit pattern read as unsigned (xor with
    the sign bit turns one order into the other), cut on the index.
    Plain jnp on values: the body of the Pallas kernel and, under a
    fori_loop over HBM, the XLA form."""
    sign = jnp.int32(_INT_MIN)

    def count(pred):
        return jnp.sum(pred.astype(jnp.int32), axis=-1, keepdims=True)

    def key_bit(i, prefix):
        cand = prefix | (jnp.int32(1) << (31 - i))
        return jnp.where(count(key >= (cand ^ sign)) >= k, cand, prefix)

    zero = jnp.zeros(key.shape[:-1] + (1,), jnp.int32)
    thr = jax.lax.fori_loop(0, 32, key_bit, zero) ^ sign
    room = k - count(key > thr)
    at = key == thr
    idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)

    def index_bit(i, c):
        cand = c | (jnp.int32(1) << (index_bits - 1 - i))
        return jnp.where(count(at & (idx < cand)) < room, cand, c)

    # the largest c with fewer than `room` ties below it IS the index of
    # the last tie that still fits (all ones where every tie fits)
    return thr, jax.lax.fori_loop(0, index_bits, index_bit, zero)


def _search_kth_pallas(key, k: int, index_bits: int, interpret: bool,
                       rows: int = 0):
    """`_search_kth` with each tile of rows resident in VMEM for all of
    its 32 + index_bits passes: the XLA form reads the [R, S] keys from
    HBM once a pass.  `rows`: the rows a grid step (0: 16, or 8 where R
    is no multiple of 16).  A step is a chain of dependent passes, each
    a count across lanes, so over SHORT rows it is latency and not work:
    4096 rows of 782 keys take 1.27 ms at 16 rows a step (my chip run,
    PR 56); the caller that knows its rows short names more."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S = key.shape
    rows = rows or (8 if R % 16 else 16)
    pad = -R % rows
    if pad:
        key = jnp.pad(key, ((0, pad), (0, 0)), constant_values=_INT_MIN)

    def kernel(key_ref, thr_ref, cut_ref):
        thr, cut = _search_kth(key_ref[...], k, index_bits)
        thr_ref[...] = jnp.broadcast_to(thr, thr_ref.shape)
        cut_ref[...] = jnp.broadcast_to(cut, cut_ref.shape)

    out = jax.ShapeDtypeStruct((R + pad, 128), jnp.int32)
    thr, cut = pl.pallas_call(
        kernel, grid=((R + pad) // rows,),
        in_specs=[pl.BlockSpec((rows, S), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))] * 2,
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(key)
    return thr[:R, :1], cut[:R, :1]


def topk_mask(scores: jax.Array, ok: jax.Array, k: int,
              impl: str = "auto", rows: int = 0) -> jax.Array:
    """[R, S] float32 scores, [R, S] bool `ok` (the keys a row may
    choose from) -> [R, S] bool: the k largest of a row's ok entries,
    ties to the lower index; all of them where a row has at most k.
    Exact and sort-free (`_search_kth`); `impl`: "auto" (the Pallas
    kernel on a TPU, the XLA loop elsewhere) | "xla" | "pallas" |
    "pallas_interpret"; `rows`: the kernel's rows a grid step where the
    caller knows better than its own 16 (`_search_kth_pallas`)."""
    R, S = scores.shape
    key = jnp.where(ok, _sortable(scores), jnp.int32(_INT_MIN))
    bits = max(1, (S - 1).bit_length())
    if impl == "auto":
        thr, cut = jax.lax.platform_dependent(
            key,
            tpu=lambda key: _search_kth_pallas(key, k, bits, False, rows),
            default=lambda key: _search_kth(key, k, bits))
    elif impl == "xla":
        thr, cut = _search_kth(key, k, bits)
    else:
        thr, cut = _search_kth_pallas(key, k, bits,
                                      impl == "pallas_interpret", rows)
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    return ((key > thr) | ((key == thr) & (idx <= cut))) & ok


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_index_mask(qi, wi, ik_cache, layer, block_tables, kv_lens,
                      topk: int):
    """qi [B, H, D], wi [B, H] -> [B, W * bs] bool: each lane's chosen
    tokens among its kv_lens[b] live ones (the one just written
    included).  The index keys are read by block table, one gather of
    the table's whole width for every lane (128 B a token: 26 MB a layer
    at 8 lanes x 199 blocks, against 1.1 ms of K and V)."""
    with jax.named_scope("dyn.attn_index"):
        ki = ik_cache[jnp.int32(layer), 0, block_tables]   # [B, W, D, bs]
        B, W, _, bs = ki.shape
        scores = _index_pairs(qi[:, None], wi[:, None], ki).reshape(
            B, W * bs)
    with jax.named_scope("dyn.attn_select"):
        ok = jnp.arange(W * bs)[None, :] < kv_lens[:, None]
        return topk_mask(scores, ok, topk)


def _decode_masked_jnp(q, k_cache, v_cache, layer, block_tables, sel):
    li = jnp.int32(layer)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    def one(qb, table, selb):
        kb = _gather_blocks(k_cache, li, table)            # [nkv, S, hd]
        vb = _gather_blocks(v_cache, li, table)
        s = _gqa_scores(qb, kb) * scale
        p = jax.nn.softmax(jnp.where(selb[None, :], s, NEG_INF), axis=-1)
        # a lane with nothing chosen (idle) averages garbage: unused
        return _gqa_out(p, vb)

    return jax.vmap(one)(q, block_tables, sel)


def sparse_decode_attention(q, qi, wi, k_cache, v_cache, ik_cache, layer,
                            block_tables, kv_lens, topk: int,
                            attn_impl: str = "jnp"):
    """One decode token a lane: q [B, nh, hd], qi [B, H, D], wi [B, H];
    kv_lens [B] live tokens including the one just written (0 = an idle
    lane).  `attn_impl`: the RESOLVED dense decode impl
    (paged_attention.resolve_decode_impl) that K and V are read through
    under the mask.  -> [B, nh, hd]."""
    sel = decode_index_mask(qi, wi, ik_cache, layer, block_tables,
                            kv_lens, topk)
    with jax.named_scope("dyn.attn_sparse"):
        if attn_impl in PALLAS_IMPLS:
            from .pallas_paged_attention import paged_attention_decode_pallas

            out = paged_attention_decode_pallas(
                q, k_cache, v_cache, layer, block_tables, kv_lens,
                interpret=attn_impl == "pallas_interpret",
                bias=jnp.where(sel, 0.0, NEG_INF).astype(jnp.float32))
        else:
            out = _decode_masked_jnp(q, k_cache, v_cache, layer,
                                     block_tables, sel)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# prefill over a packed stream
# ---------------------------------------------------------------------------


def prefill_index_mask(qi, wi, ik_cache, layer, table, token_mask,
                       positions, topk: int):
    """One segment row: qi [T, H, D], wi [T, H] against the segment's
    index keys (table [mb]) -> [T, mb * bs] bool, the chosen keys of
    every stream token that `token_mask` gives to this segment (none
    for the others).  Scores are made `_INDEX_CHUNK` keys at a time:
    [T, H, chunk] float32 is the largest value held."""
    T = qi.shape[0]
    with jax.named_scope("dyn.attn_index"):
        ki = ik_cache[jnp.int32(layer), 0, table]          # [mb, D, bs]
        mb, D, bs = ki.shape
        S = mb * bs
        ki = ki.transpose(1, 0, 2).reshape(D, S)
        step = min(_INDEX_CHUNK, S)
        pad = -S % step
        if pad:
            ki = jnp.pad(ki, ((0, 0), (0, pad)))
        chunks = ki.reshape(D, -1, step).transpose(1, 0, 2)  # [n, D, step]
        scores = jax.lax.map(lambda kc: _index_pairs(qi, wi, kc), chunks)
        scores = scores.transpose(1, 0, 2).reshape(T, -1)[:, :S]
    with jax.named_scope("dyn.attn_select"):
        ok = token_mask[:, None] \
            & (jnp.arange(S)[None, :] <= positions[:, None])
        return topk_mask(scores, ok, topk)


def _masked_flash(q, k_cache, v_cache, layer, table, sel, chunk_cols):
    """packed_prefill._segment_flash under an explicit [T, mb * bs]
    mask: an online-softmax scan over chunks of `chunk_cols` block
    columns; matmul operands in the cache's dtype, float32 sums."""
    T, nh, hd = q.shape
    bs = k_cache.shape[4]
    mb = table.shape[0]
    n_chunks = -(-mb // chunk_cols)
    pad = n_chunks * chunk_cols - mb
    if pad:
        table = jnp.pad(table, (0, pad))      # padded columns: garbage
        sel = jnp.pad(sel, ((0, 0), (0, pad * bs)))
    C = chunk_cols * bs
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def body(carry, xs):
        m, l, acc = carry
        cols, mask = xs                                    # [cc], [T, C]
        k_c = _gather_blocks(k_cache, layer, cols)         # [nkv, C, hd]
        v_c = _gather_blocks(v_cache, layer, cols)
        s = _gqa_scores(q, k_c) * scale                    # [T, nh, C]
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        # a row with nothing chosen yet has m_new = NEG_INF: exp(0) = 1
        # a masked pair; the mask keeps it out of the sums
        p = jnp.where(mask[:, None, :], jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + _gqa_out(p, v_c)
        return (m_new, l_new, acc), None

    init = (jnp.full((T, nh), NEG_INF, jnp.float32),
            jnp.zeros((T, nh), jnp.float32),
            jnp.zeros((T, nh, hd), jnp.float32))
    xs = (table.reshape(n_chunks, chunk_cols),
          sel.reshape(T, n_chunks, C).swapaxes(0, 1))
    (m, l, acc), _ = jax.lax.scan(body, init, xs)
    return acc / jnp.maximum(l, 1e-20)[..., None]


# queries and keys of one tile of the Pallas flash pass under a mask
_FLASH_TQ, _FLASH_TK = 256, 512


def _masked_flash_pallas(q, k_cache, v_cache, layer, table, sel,
                         interpret: bool = False):
    """`_masked_flash` as one Pallas kernel: the segment's context is
    gathered once into [nkv, hd, S] (blocks already lie [hd, bs], so no
    tile is transposed for the MXU), the grid walks (kv head, query
    tile, key tile), the query heads of a KV head's group share each
    key tile and the mask's tile, and no score leaves VMEM (the XLA
    scan writes a [T, heads, 1024] float32 block to HBM and reads it
    back every step)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    nkv, bs = k_cache.shape[1], k_cache.shape[4]
    G, S = nh // nkv, table.shape[0] * bs
    tq = min(_FLASH_TQ, T)
    tk = min(_FLASH_TK, S)
    pad_q, pad_k = -T % tq, -S % tk
    li = jnp.int32(layer)

    def planes(cache):                      # -> [nkv, hd, S (+ pad)]
        g = cache[li, :, table].transpose(1, 2, 0, 3).reshape(nkv, hd, S)
        return jnp.pad(g, ((0, 0), (0, 0), (0, pad_k)))

    qg = jnp.pad(q.reshape(T, nkv, G, hd).transpose(1, 2, 0, 3),
                 ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    mask = jnp.pad(sel, ((0, pad_q), (0, pad_k))).astype(jnp.int8)
    scale = 1.0 / (hd ** 0.5)
    n_k = (S + pad_k) // tk

    def kernel(q_ref, k_ref, v_ref, m_ref, o_ref, m_sc, l_sc, acc_sc):
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
            l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        k, v = k_ref[0], v_ref[0]                          # [hd, tk]
        keep = m_ref[...] != 0                             # [tq, tk]
        for g in range(G):
            sc = jnp.dot(q_ref[0, g], k,
                         preferred_element_type=jnp.float32) * scale
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_sc[g][:, :1]
            m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with nothing kept so far has m_new = NEG_INF and
            # exp(0) = 1 for a pair that is out: the mask zeroes it
            p = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
            l_sc[g] = alpha * l_sc[g] + p.sum(axis=1, keepdims=True)
            acc_sc[g] = acc_sc[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[g] = jnp.broadcast_to(m_new, m_sc.shape[1:])

        @pl.when(j == n_k - 1)
        def _():
            for g in range(G):
                o_ref[0, g] = (acc_sc[g] / jnp.maximum(
                    l_sc[g][:, :1], 1e-20)).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(nkv, (T + pad_q) // tq, n_k),
        in_specs=[
            pl.BlockSpec((1, G, tq, hd), lambda h, i, j: (h, 0, i, 0)),
            pl.BlockSpec((1, hd, tk), lambda h, i, j: (h, 0, j)),
            pl.BlockSpec((1, hd, tk), lambda h, i, j: (h, 0, j)),
            pl.BlockSpec((tq, tk), lambda h, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, G, tq, hd),
                               lambda h, i, j: (h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nkv, G, T + pad_q, hd),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((G, tq, 128), jnp.float32),
                        pltpu.VMEM((G, tq, 128), jnp.float32),
                        pltpu.VMEM((G, tq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(qg, planes(k_cache), planes(v_cache), mask)
    return out[:, :, :T].transpose(2, 0, 1, 3).reshape(T, nh, hd)


def _flash_under_mask(q, k_cache, v_cache, layer, table, sel, chunk_cols,
                      flash: str):
    """Attention under the mask by `flash`: "auto" (the kernel on a
    TPU, the scan elsewhere) | "xla" | "pallas" | "pallas_interpret"."""
    def scan(q, k_cache, v_cache, table, sel):
        return _masked_flash(q, k_cache, v_cache, layer, table, sel,
                             chunk_cols)

    def kernel(q, k_cache, v_cache, table, sel, interpret=False):
        return _masked_flash_pallas(q, k_cache, v_cache, layer, table, sel,
                                    interpret)

    args = (q, k_cache, v_cache, table, sel)
    if flash == "xla":
        return scan(*args)
    if flash == "auto":
        return jax.lax.platform_dependent(*args, tpu=kernel, default=scan)
    return kernel(*args, interpret=flash == "pallas_interpret")


def sparse_prefill_attention(q, qi, wi, k_cache, v_cache, ik_cache, layer,
                             block_tables, seg_ids, positions, valid,
                             topk: int, chunk_cols: int = 8,
                             flash: str = "auto"):
    """Packed-stream prefill attention (packed_prefill.
    packed_prefill_attention's contract): q [T, nh, hd], qi [T, H, D],
    wi [T, H]; token t reads its own segment's cache over positions
    [0, positions[t]], of which its indexer keeps `topk`.  The chunk's
    K, V and index keys are in the cache already.  One pass a segment
    row (static; the rows are few).  `flash`: how the pass under the
    mask runs: "auto" (the Pallas kernel on a TPU, the XLA scan
    elsewhere) | "xla" | "pallas" | "pallas_interpret"."""
    out = jnp.zeros(q.shape, jnp.float32)
    for s in range(block_tables.shape[0]):
        seg_mask = (seg_ids == s) & valid
        table = block_tables[s]
        sel = prefill_index_mask(qi, wi, ik_cache, layer, table, seg_mask,
                                 positions, topk)
        with jax.named_scope("dyn.attn_sparse"):
            o_s = _flash_under_mask(q, k_cache, v_cache, layer, table,
                                    sel, chunk_cols, flash)
        out = jnp.where(seg_mask[:, None, None], o_s, out)
    return out.astype(q.dtype)
