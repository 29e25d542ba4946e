"""Attention that reads the BLOCKS of keys a query chooses from
mean-pooled compressed keys (InfLLM-V2, the sparse layers of MiniCPM4 /
MiniCPM-SALA; models/minicpm_sala.py), over the paged cache.  The choice
has no parameters.

A KV head keeps, beside K and V, one compressed key for every `stride`
tokens: c_i = mean(k[stride i : stride i + kernel]), visible to query t
once its window is whole (stride i + kernel - 1 <= t).  A query at
position t whose context is longer than `dense_len` (t + 1 > dense_len)
scores them, one set a KV GROUP (query head h reads KV head h // G):

    a_h[i] = softmax_i(q_h . c_i / sqrt(hd))   over the visible i, float32
    A[i]   = sum of a_h[i] over the group's G heads
    P[j]   = max of A[i] over the windows that touch block j of `block`
             keys (i in per j - pad .. per j + per - 1, per = block /
             stride, pad = kernel / stride - 1)
    chosen = block 0 .. init_blocks - 1, the window / block blocks that
             end with the query's own, and of the other blocks
             j <= t // block those with the largest P until `topk` are
             chosen in all (ties to the lower index)

and attends s <= t with s // block chosen; at or under `dense_len` it
attends every s <= t.  `topk_mask` of ops/sparse_attention.py makes the
set (a forced block's score is +inf): no second search.

The third cache member `ck` [sparse layers, blocks, block_size / stride,
nkv, hd] is paged by the sequence's block table like K and V: a page
holds the compressed keys whose window ENDS in it, so a page's members
depend on nothing after it, and window i lies at flat slot
i + (kernel - 1) // stride of the sequence's pages laid end to end.  In
those slots block j pools the slots per j .. per j + per + pad - 1.
Every program that writes a token's K writes the compressed key its
arrival completes (`compress_chunk`, `compress_token`); a window may
start in the chunk, the page or the phase before, so the keys it lacks
are read back from K (`_last_keys`).

Decode (`sparse_decode_attention`) fetches the PAGES that hold a chosen
block and nothing else of K and V: at most max(dense_len / block_size,
topk) pages a (lane, KV group), whatever the context.  The chosen pages
of a group, in order, stand in the block table's place: on a TPU the
paged pool's decode kernel (ops/pallas_paged_attention.py) is called
once a KV group over the pool seen as [layers x nkv, 1, ...] (a bitcast:
layer and head are adjacent and major), so a row moves its own group's
planes only; elsewhere the pages are gathered.  A page of `block_size`
tokens holds block_size / block blocks: one half chosen is read whole
and masked (`sala_read_tokens` counts what is moved).

Prefill (`sparse_prefill_attention`) is exact under the block mask: a
row's compressed keys are scored (`prefill_block_choice`: on a TPU a
prompt-sized row in ops/pallas_block_choice.py's kernel, which holds a
query tile's scores in VMEM, stops at the tile's visible frontier and
skips a tile with no query past `dense_len`; elsewhere, and for fewer
rows, `_SCORE_QUERIES` queries at a time through `block_scores`), the
token mask [nkv, T, S] is laid out once, and one flash pass runs under
it: a Pallas kernel on a TPU whose (query tile, key tile) steps are
skipped, compute and DMA, where no query of the tile chose a key of it
(everything past the causal diagonal, and whatever the choices left
out), an XLA scan elsewhere.  The pairs of the tiles that ran are
counted (`sala_pairs_computed`): with choices that differ query by
query a tile is rarely empty, which the count says.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .paged_attention import NEG_INF, PALLAS_IMPLS
from .sparse_attention import _dot, _gqa_out, _gqa_scores, topk_mask

# queries whose compressed-key scores are held at once: [n, nh, slots] f32
_SCORE_QUERIES = 256
# queries and keys of one tile of the Pallas flash pass under the mask
_FLASH_TQ, _FLASH_TK = 256, 512
# rows a grid step of the search over a prompt's (query, group) rows of
# blocks: a block table's rows are short (782 blocks at 50 k tokens)
_SEARCH_ROWS = 128


class BlockSizes(NamedTuple):
    """The published block sizes (MiniCPM4's `sparse_config`)."""
    kernel: int       # keys a compressed key averages
    stride: int       # keys between two windows' starts
    block: int        # keys a block
    init_blocks: int  # forced: the sequence's first blocks
    window: int       # forced: the keys before the query (whole blocks)
    topk: int         # blocks chosen in all, the forced ones among them
    dense_len: int    # contexts up to this attend everything

    def check(self, block_size: int) -> None:
        if (self.kernel % self.stride or self.block % self.stride
                or block_size % self.block or self.window % self.block
                or block_size % self.stride):
            raise ValueError(f"{self} over pages of {block_size}: kernel "
                             "and block are whole strides, a page whole "
                             "blocks, the window whole blocks")
        if self.init_blocks + self.window // self.block > self.topk:
            raise ValueError(f"{self}: the forced blocks alone pass topk")

    @property
    def slot0(self) -> int:
        """The flat slot of window 0."""
        return (self.kernel - 1) // self.stride

    def max_pages(self, block_size: int, table_width: int) -> int:
        """The most pages a (lane, group) reads in a decode step."""
        return min(table_width,
                   max(-(-self.dense_len // block_size), self.topk))


# ---------------------------------------------------------------------------
# compressed keys, written as tokens arrive
# ---------------------------------------------------------------------------


def _last_keys(k_cache, layer, tables, end, n: int):
    """The `n` keys before position `end` of each row, read back from
    the pool: k_cache [L, nkv, nb, hd, bs], tables [R, W], end [R] ->
    [R, n, nkv, hd] (positions under 0: whatever block 0 holds; the
    caller never uses them).  Whole pages are gathered (the last page
    and as many before it as n keys can reach into): a column gather
    would pick 2 bytes out of every row of a plane."""
    bs = k_cache.shape[-1]
    npg = (n + bs - 2) // bs + 1
    last = jnp.maximum(end - 1, 0) // bs                       # [R]
    cols = last[:, None] - (npg - 1) + jnp.arange(npg)[None, :]
    pages = jnp.take_along_axis(tables, jnp.maximum(cols, 0), axis=1)
    g = k_cache[jnp.int32(layer), :, pages]          # [R, npg, nkv, hd, bs]
    R, _, nkv, hd, _ = g.shape
    seq = g.transpose(0, 1, 4, 2, 3).reshape(R, npg * bs, nkv, hd)
    rel = end[:, None] - n + jnp.arange(n)[None, :] \
        - (last[:, None] - (npg - 1)) * bs
    idx = jnp.clip(rel, 0, npg * bs - 1)[:, :, None, None]
    return jnp.take_along_axis(seq, idx, axis=1)


def _put_compressed(ck, layer, c, t, ok, tables, stride: int, bs: int):
    """c [R, M, nkv, hd] the windows that end at positions t [R, M],
    written where `ok`: page tables[r, t // bs], slot (t % bs) //
    stride."""
    nb = ck.shape[1]
    page = jnp.take_along_axis(
        tables, jnp.clip(t // bs, 0, tables.shape[1] - 1), axis=1)
    page = jnp.where(ok, page, nb)                 # outside: dropped
    return ck.at[jnp.int32(layer), page, (t % bs) // stride].set(
        c.astype(ck.dtype), mode="drop")


@jax.named_scope("dyn.attn_compress")
def compress_chunk(ck, k_cache, layer, k, tables, ctx_lens, true_lens,
                   sizes: BlockSizes):
    """Prefill: k [Bp, T, nkv, hd] the chunk's keys at positions
    ctx_lens[b] + arange(T) (the first true_lens[b] real), ALREADY in
    `k_cache`.  Writes every compressed key whose window ends on a real
    token of the chunk; the kernel - 1 keys before the chunk come from
    the pool."""
    K, s = sizes.kernel, sizes.stride
    bs = k_cache.shape[-1]
    Bp, T = k.shape[:2]
    prev = _last_keys(k_cache, layer, tables, ctx_lens, K - 1)
    ext = jnp.concatenate([prev.astype(k.dtype), k], axis=1)
    # ext[e] is position ctx - (K - 1) + e: the window that ends at
    # chunk offset o is ext[o : o + K]; the offsets that end a window
    # are o0 + s m with (ctx + o0) % s == (K - 1) % s
    M = -(-T // s)
    o = ((K - 1 - ctx_lens) % s)[:, None] + s * jnp.arange(M)[None, :]
    idx = jnp.minimum(o[:, :, None] + jnp.arange(K)[None, None, :],
                      T + K - 2)
    win = jax.vmap(lambda e, i: e[i])(ext, idx)      # [Bp, M, K, nkv, hd]
    c = jnp.mean(win.astype(jnp.float32), axis=2)
    t = ctx_lens[:, None] + o
    ok = (o < true_lens[:, None]) & (t >= K - 1)
    return _put_compressed(ck, layer, c, t, ok, tables, s, bs)


@jax.named_scope("dyn.attn_compress")
def compress_token(ck, k_cache, layer, tables, ctx_lens, valid,
                   sizes: BlockSizes):
    """Decode: the token at position ctx_lens[b] is ALREADY in
    `k_cache`; where it ends a window (and the lane is `valid`) the
    window's mean is written."""
    K, s = sizes.kernel, sizes.stride
    t = ctx_lens
    win = _last_keys(k_cache, layer, tables, t + 1, K)   # [B, K, nkv, hd]
    c = jnp.mean(win.astype(jnp.float32), axis=1)
    ok = (t >= K - 1) & ((t - (K - 1)) % s == 0)
    if valid is not None:
        ok = ok & valid
    return _put_compressed(ck, layer, c[:, None], t[:, None], ok[:, None],
                           tables, s, k_cache.shape[-1])


# ---------------------------------------------------------------------------
# the choice
# ---------------------------------------------------------------------------


def block_scores(q, ck_seq, t, sizes: BlockSizes):
    """q [R, nh, hd] at positions t [R]; ck_seq the compressed keys by
    flat slot, [NC, nkv, hd] (one sequence's, for every row) or [R, NC,
    nkv, hd] (a sequence a row) -> P [R, nkv, NB] float32, each KV
    group's score of every block (0 for a block none of whose windows
    is visible)."""
    R, nh, hd = q.shape
    NC, nkv, _ = ck_seq.shape[-3:]
    per, pad = sizes.block // sizes.stride, sizes.kernel // sizes.stride - 1
    NB = NC // per
    s = _dot("rkgh,rckh->rkgc" if ck_seq.ndim == 4 else "rkgh,ckh->rkgc",
             q.reshape(R, nkv, nh // nkv, hd), ck_seq).reshape(R, nh, NC) \
        / jnp.sqrt(jnp.float32(hd))
    f = jnp.arange(NC)[None, :]
    # window f - slot0 is whole at t once it is <= (t - (kernel - 1)) //
    # stride (floor division: under 0 while no window is whole)
    seen = (f >= sizes.slot0) \
        & (f - sizes.slot0 <= (t[:, None] - (sizes.kernel - 1))
           // sizes.stride)
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    e = jnp.where(seen[:, None, :],
                  jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    a = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    A = a.reshape(R, nkv, nh // nkv, NB, per).sum(axis=2)  # [R, nkv, NB, per]
    P = A.max(axis=-1)
    if pad:
        # the first `pad` slots of block j + 1's run touch block j too
        nxt = jnp.pad(A[..., 1:, :pad].max(axis=-1),
                      ((0, 0), (0, 0), (0, 1)))
        P = jnp.maximum(P, nxt)
    return P


def _causal_blocks(t, valid, nkv: int, NB: int, block: int):
    """-> [R, nkv, NB] bool: the blocks a valid row may attend,
    j <= t // block."""
    ok = (jnp.arange(NB)[None, :] <= (t // block)[:, None]) & valid[:, None]
    return jnp.broadcast_to(ok[:, None, :], (t.shape[0], nkv, NB))


def _forced_topk(P, t, valid, sizes: BlockSizes, rows: int = 0):
    """P [R, nkv, NB] -> [R, nkv, NB] bool: `topk` of the blocks a
    valid row may attend, the forced ones and then the best-scored.
    `rows`: `topk_mask`'s."""
    R, nkv, NB = P.shape
    j = jnp.arange(NB)[None, :]
    own = (t // sizes.block)[:, None]
    forced = (j < sizes.init_blocks) \
        | (j > own - sizes.window // sizes.block)
    score = jnp.where(forced[:, None, :], jnp.inf, P)
    okg = _causal_blocks(t, valid, nkv, NB, sizes.block)
    return topk_mask(score.reshape(R * nkv, NB), okg.reshape(R * nkv, NB),
                     sizes.topk, rows=rows).reshape(R, nkv, NB)


def choose_blocks(q, ck_seq, t, valid, sizes: BlockSizes):
    """-> [R, nkv, NB] bool: the blocks each (query, KV group) attends;
    every block j <= t // block at or under `dense_len`; none for a row
    that is not `valid`."""
    with jax.named_scope("dyn.attn_index"):
        P = block_scores(q, ck_seq, t, sizes)
    with jax.named_scope("dyn.attn_select"):
        return jnp.where((t + 1 <= sizes.dense_len)[:, None, None],
                         _causal_blocks(t, valid, *P.shape[1:], sizes.block),
                         _forced_topk(P, t, valid, sizes))


# ---------------------------------------------------------------------------
# decode: the chosen pages in the block table's place
# ---------------------------------------------------------------------------


def chosen_pages(chosen, block_tables, t, bs: int, block: int,
                 max_pages: int):
    """chosen [B, nkv, NB] -> (phys [B, nkv, P] the physical pages that
    hold a chosen block, in order, P = max_pages; count [B, nkv] of
    them; keep [B, nkv, P * bs] bool, the tokens of those pages that are
    attended: in a chosen block and at or before t)."""
    B, nkv, NB = chosen.shape
    W = block_tables.shape[1]
    pm_blocks = chosen.reshape(B, nkv, W, bs // block)
    pm = pm_blocks.any(axis=-1)
    count = jnp.sum(pm, axis=-1, dtype=jnp.int32)
    order = jnp.argsort(~pm, axis=-1, stable=True)[..., :max_pages]
    phys = jnp.take_along_axis(
        jnp.broadcast_to(block_tables[:, None, :], pm.shape), order, axis=-1)
    # the chosen blocks of each listed page (a gather of P entries a row:
    # token by token it was 131072 single elements a layer and step, 1.2
    # ms on a v5e), spread over their tokens
    bits = jnp.take_along_axis(pm_blocks, order[..., None], axis=2)
    keep = jnp.repeat(bits, block, axis=-1).reshape(B, nkv, max_pages * bs)
    pos = (order[..., None] * bs + jnp.arange(bs)).reshape(keep.shape)
    keep = keep & (pos <= t[:, None, None]) \
        & (jnp.repeat(jnp.arange(max_pages), bs)[None, None, :]
           < count[..., None])
    return phys, count, keep


def _decode_pages_jnp(q, k_cache, v_cache, layer, phys, keep):
    B, nkv, Pn = phys.shape
    hd, bs = k_cache.shape[3:]
    heads = jnp.arange(nkv)[None, :, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    def planes(cache):          # -> [B, nkv, P * bs, hd]
        g = cache[jnp.int32(layer), heads, phys]        # [B, nkv, P, hd, bs]
        return g.transpose(0, 1, 2, 4, 3).reshape(B, nkv, Pn * bs, -1)

    qg = q.reshape(B, nkv, -1, hd)
    s = _dot("bkgh,bksh->bkgs", qg, planes(k_cache)) * scale
    p = jax.nn.softmax(jnp.where(keep[:, :, None, :], s, NEG_INF), axis=-1)
    # a lane with nothing kept (idle) averages garbage: unused
    vg = planes(v_cache)
    return _dot("bkgs,bksh->bkgh", p.astype(vg.dtype), vg).reshape(q.shape)


def sparse_decode_attention(q, k_cache, v_cache, ck, layer, block_tables,
                            kv_lens, sizes: BlockSizes,
                            attn_impl: str = "jnp"):
    """One decode token a lane: q [B, nh, hd] at position kv_lens - 1
    (kv_lens 0 = an idle lane), its K, V and compressed key already
    written.  `attn_impl`: the RESOLVED decode impl of the paged pools
    (paged_attention.resolve_decode_impl).  -> (out [B, nh, hd], the
    tokens whose K and V it moved, summed over lanes: a page moved for
    one of nkv groups is 1 / nkv of its tokens)."""
    B, nh, hd = q.shape
    nkv, bs = k_cache.shape[1], k_cache.shape[4]
    t = kv_lens - 1
    ck_seq = ck[jnp.int32(layer), block_tables]     # [B, W, spp, nkv, hd]
    chosen = choose_blocks(q, ck_seq.reshape(B, -1, *ck_seq.shape[3:]), t,
                           kv_lens > 0, sizes)
    with jax.named_scope("dyn.attn_sparse"):
        phys, count, keep = chosen_pages(
            chosen, block_tables, t, bs, sizes.block,
            sizes.max_pages(bs, block_tables.shape[1]))
        if attn_impl in PALLAS_IMPLS:
            from .pallas_paged_attention import paged_attention_decode_pallas

            G = nh // nkv
            L = k_cache.shape[0]
            # layer and KV head are adjacent and major: a bitcast
            by_head = lambda c: c.reshape(L * nkv, 1, *c.shape[2:])
            bias = jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)
            out = jnp.concatenate([
                paged_attention_decode_pallas(
                    q[:, g * G:(g + 1) * G], by_head(k_cache),
                    by_head(v_cache), jnp.int32(layer) * nkv + g,
                    phys[:, g], count[:, g] * bs,
                    interpret=attn_impl == "pallas_interpret",
                    bias=bias[:, g])
                for g in range(nkv)], axis=1)
        else:
            out = _decode_pages_jnp(q, k_cache, v_cache, layer, phys, keep)
    return out.astype(q.dtype), jnp.sum(count) * bs // nkv


# ---------------------------------------------------------------------------
# prefill: one flash pass under the block mask
# ---------------------------------------------------------------------------


def choice_impl(attn_impl: str, rows: int) -> str:
    """What the RESOLVED `attn_impl` (paged_attention.
    resolve_decode_impl) means for the choice of a prefill row of `rows`
    queries: ops/pallas_block_choice.py's kernel where the impl names
    one and the ROW COUNT fills a tile of it (a prompt-sized input), the
    jnp form (`block_scores` a `_SCORE_QUERIES` block under `lax.map`)
    elsewhere: a decode step's 8 rows score [8, 32, 3128] = 3 MB,
    nothing to win.  Asked by the traced program and by the host's
    counts alike."""
    from .pallas_block_choice import TILE_QUERIES

    if attn_impl in PALLAS_IMPLS and rows >= TILE_QUERIES:
        return attn_impl
    return "jnp"


@partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs (prefill / prefill_batched); `layer` is traced, so one trace serves every sparse layer of a program
    jax.jit, static_argnames=("sizes", "interpret"))
def _choice_kernel(q, ck, layer, table, positions, valid, sizes: BlockSizes,
                   interpret: bool):
    """`prefill_block_choice` with the scores made on chip, every query
    of the row in one call.  A chunk with no valid query past
    `dense_len` scores and searches nothing."""
    from .pallas_block_choice import block_scores_pallas

    nkv = ck.shape[3]
    NB = table.shape[0] * ck.shape[2] * sizes.stride // sizes.block
    dense = positions + 1 <= sizes.dense_len
    read = valid & ~dense
    all_of = partial(_causal_blocks, positions, valid, nkv, NB, sizes.block)

    # behind a barrier: XLA lays a gather's operand out for what reads the
    # result, and the kernel's layout of the keys has it relay the WHOLE
    # pool a layer (the jnp form's einsum takes the pages as they lie)
    ck_seq = jax.lax.optimization_barrier(ck[layer, table])
    ck_seq = ck_seq.reshape(-1, *ck.shape[3:])             # [NC, nkv, hd]

    def choose():
        with jax.named_scope("dyn.attn_index"):
            P = block_scores_pallas(q, ck_seq, positions, read, sizes,
                                    interpret=interpret)
        with jax.named_scope("dyn.attn_select"):
            return jnp.where(dense[:, None, None], all_of(),
                             _forced_topk(P, positions, valid, sizes,
                                          _SEARCH_ROWS))

    return jax.lax.cond(jnp.any(read), choose, all_of)


def prefill_block_choice(q, ck, layer, table, positions, valid,
                         sizes: BlockSizes, attn_impl: str = "jnp"):
    """One row: q [T, nh, hd] at `positions` -> [T, nkv, NB] bool.
    `attn_impl` as `sparse_prefill_attention`'s: by `choice_impl` one
    kernel call over every query, or `_SCORE_QUERIES` queries at a time
    through the jnp form."""
    T, nkv = q.shape[0], ck.shape[3]
    reach = table.shape[0] * ck.shape[2] * sizes.stride    # tokens
    if reach <= sizes.dense_len:
        # no position of this table lies past dense_len: nothing to score
        return _causal_blocks(positions, valid, nkv, reach // sizes.block,
                              sizes.block)
    impl = choice_impl(attn_impl, T)
    if impl in PALLAS_IMPLS:
        return _choice_kernel(q, ck, jnp.int32(layer), table, positions,
                              valid, sizes, impl == "pallas_interpret")
    ck_seq = ck[jnp.int32(layer), table]              # [W, spp, nkv, hd]
    ck_seq = ck_seq.reshape(-1, *ck_seq.shape[2:])
    n = min(_SCORE_QUERIES, T)
    if T % n:
        n = T
    parts = lambda x: x.reshape(T // n, n, *x.shape[1:])
    out = jax.lax.map(
        lambda a: choose_blocks(a[0], ck_seq, a[1], a[2], sizes),
        (parts(q), parts(positions), parts(valid)))
    return out.reshape(T, *out.shape[2:])


def _token_mask(chosen, positions, block: int):
    """chosen [T, nkv, NB] -> [nkv, T, NB * block] bool: the keys each
    (query, group) attends."""
    m = jnp.repeat(chosen.swapaxes(0, 1), block, axis=-1)
    return m & (jnp.arange(m.shape[-1])[None, None, :]
                <= positions[None, :, None])


def _planes(cache, layer, table):
    """[L, nkv, nb, hd, bs] + table [W] -> [nkv, hd, W * bs]: blocks
    already lie [hd, bs], so no tile is transposed for the MXU."""
    g = cache[jnp.int32(layer), :, table]              # [W, nkv, hd, bs]
    W, nkv, hd, bs = g.shape
    return g.transpose(1, 2, 0, 3).reshape(nkv, hd, W * bs)


def _block_flash_scan(q, kp, vp, mask, cols: int = 1024):
    """The XLA form: an online-softmax scan over `cols` keys a step,
    every step computed.  q [T, nh, hd], kp, vp [nkv, hd, S], mask
    [nkv, T, S] -> (out [T, nh, hd] float32, pairs computed)."""
    T, nh, hd = q.shape
    nkv, _, S = kp.shape
    G = nh // nkv
    C = min(cols, S)
    pad = -S % C
    if pad:
        kp, vp = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (kp, vp))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    n = (S + pad) // C
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def body(carry, xs):
        m, l, acc = carry
        k_c, v_c, keep = xs               # [nkv, hd, C] x 2, [nkv, T, C]
        s = _gqa_scores(q, k_c.swapaxes(1, 2)) * scale       # [T, nh, C]
        keep = jnp.repeat(keep.swapaxes(0, 1), G, axis=1)    # [T, nh, C]
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        # a row with nothing kept yet has m_new = NEG_INF: exp(0) = 1
        # for a masked pair; the mask keeps it out of the sums
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + _gqa_out(p, v_c.swapaxes(1, 2))
        return (m_new, l * alpha + p.sum(-1), acc), None

    init = (jnp.full((T, nh), NEG_INF, jnp.float32),
            jnp.zeros((T, nh), jnp.float32),
            jnp.zeros((T, nh, hd), jnp.float32))
    chunks = lambda x: jnp.moveaxis(x.reshape(*x.shape[:-1], n, C), -2, 0)
    (_, l, acc), _ = jax.lax.scan(body, init,
                                  (chunks(kp), chunks(vp), chunks(mask)))
    return acc / jnp.maximum(l, 1e-20)[..., None], jnp.int32(T * n * C)


def _block_flash_pallas(q, kp, vp, mask, interpret: bool = False):
    """The same pass as one Pallas kernel: the grid walks (KV head,
    query tile, key tile), the G query heads of a KV head share each key
    tile and the mask's tile, no score leaves VMEM.  A step whose mask
    tile is empty is skipped: its body does not run, and its operands
    map to the tile already resident (the last one that ran), so nothing
    is copied for it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    nkv, _, S = kp.shape
    G = nh // nkv
    tq, tk = min(_FLASH_TQ, T), min(_FLASH_TK, S)
    pad_q, pad_k = -T % tq, -S % tk
    nq, nk = (T + pad_q) // tq, (S + pad_k) // tk
    qg = jnp.pad(q.reshape(T, nkv, G, hd).transpose(1, 2, 0, 3),
                 ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp, vp = (jnp.pad(x, ((0, 0), (0, 0), (0, pad_k))) for x in (kp, vp))
    mask = jnp.pad(mask, ((0, 0), (0, pad_q), (0, pad_k)))
    runs = mask.reshape(nkv, nq, tq, nk, tk).any(axis=(2, 4))
    last = jax.lax.cummax(
        jnp.where(runs, jnp.arange(nk, dtype=jnp.int32), -1), axis=2)
    fetch = jnp.maximum(last, 0).reshape(-1)
    flags = runs.astype(jnp.int32).reshape(-1)
    scale = 1.0 / (hd ** 0.5)

    def at(h, i, j):
        return (h * nq + i) * nk + j

    def kernel(flag_ref, fetch_ref, q_ref, k_ref, v_ref, m_ref, o_ref,
               m_sc, l_sc, acc_sc):
        del fetch_ref
        h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
            l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
            acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        @pl.when(flag_ref[at(h, i, j)] != 0)
        def _():
            k, v = k_ref[0], v_ref[0]                      # [hd, tk]
            keep = m_ref[0] != 0                           # [tq, tk]
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

        @pl.when(j == nk - 1)
        def _():
            for g in range(G):
                o_ref[0, g] = (acc_sc[g] / jnp.maximum(
                    l_sc[g][:, :1], 1e-20)).astype(o_ref.dtype)

    kv_spec = pl.BlockSpec(
        (1, hd, tk), lambda h, i, j, fl, fe: (h, 0, fe[at(h, i, j)]))
    q_spec = pl.BlockSpec((1, G, tq, hd),
                          lambda h, i, j, fl, fe: (h, 0, i, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nkv, nq, nk),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, tq, tk),
                             lambda h, i, j, fl, fe: (h, i,
                                                      fe[at(h, i, j)])),
            ],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((G, tq, 128), jnp.float32),
                            pltpu.VMEM((G, tq, 128), jnp.float32),
                            pltpu.VMEM((G, tq, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nkv, G, T + pad_q, hd),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(flags, fetch, qg, kp, vp, mask.astype(jnp.int8))
    return (out[:, :, :T].transpose(2, 0, 1, 3).reshape(T, nh, hd),
            jnp.sum(flags) * (tq * tk) // nkv)


def sparse_prefill_attention(q, k_cache, v_cache, ck, layer, block_tables,
                             ctx_lens, true_lens, sizes: BlockSizes,
                             attn_impl: str = "jnp"):
    """Padded rows: q [Bp, T, nh, hd] at positions ctx_lens[b] +
    arange(T), the first true_lens[b] real; the chunk's K, V and
    compressed keys are in the cache already.  One pass a row (static;
    the rows are few).  `attn_impl` as `sparse_decode_attention`'s: the
    kernel where it names one, the scan elsewhere.  -> (out [Bp, T, nh,
    hd], the pairs the passes computed, a KV group)."""
    Bp, T = q.shape[:2]
    outs, pairs = [], jnp.zeros((), jnp.int32)
    for b in range(Bp):
        positions = ctx_lens[b] + jnp.arange(T, dtype=jnp.int32)
        chosen = prefill_block_choice(q[b], ck, layer, block_tables[b],
                                      positions, jnp.arange(T) < true_lens[b],
                                      sizes, attn_impl)
        with jax.named_scope("dyn.attn_sparse"):
            mask = _token_mask(chosen, positions, sizes.block)
            kp = _planes(k_cache, layer, block_tables[b])
            vp = _planes(v_cache, layer, block_tables[b])
            if attn_impl in PALLAS_IMPLS:
                o, n = _block_flash_pallas(
                    q[b], kp, vp, mask, attn_impl == "pallas_interpret")
            else:
                o, n = _block_flash_scan(q[b], kp, vp, mask)
        outs.append(o)
        pairs = pairs + n
    return jnp.stack(outs).astype(q.dtype), pairs
