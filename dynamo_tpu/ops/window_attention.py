"""Sliding-window attention over a ring of cache blocks a lane, and the
tiled causal prefill read for layers whose K and V differ in width.

A window layer attends to the last `window` positions (the current one
counted), so its state is bounded: a lane keeps `ring_blocks(window,
block_size)` = ceil(window / block_size) + 1 blocks whatever the
sequence's length.  The ring pool is one more `[L, nkv, blocks, hd, bs]`
array in the layout of ops/paged_attention.py, addressed by LANE (the
scheduler's slot) and POSITION, never by a block table:

    block(lane, pos) = 1 + lane * W + (pos // bs) % W,   offset = pos % bs

so a continuation burst needs no upload, nothing is allocated or freed,
and a preempted sequence's replay simply writes its lane's ring again.
Block 0 is the garbage block, as in the paged pools.  A position's cell
is overwritten W * bs positions later; every read masks by position
(`0 <= pos - kpos < window`), so a stale cell is never attended.

Softmax of a window layer may carry a learned per-head SINK: one scalar
logit a head, in the denominator only (no value): p_ij = exp(s_ij) /
(sum_j' exp(s_ij') + exp(sink_h)).

`window_prefill_attention` / `window_decode_attention` are jnp paths for
a window of about a block (K and V of any widths, a sink).  Decode reads
W blocks a lane (window-bounded, never lanes x table width); prefill
reads the ring's last `window` cells once and then only the chunk
itself, in tiles of `window` queries against 2 x `window` keys, so its
work is T x 2 x window and not T x T.

A window of many blocks (4096 tokens over 33 blocks a lane) goes through
the two kernels the paged pools have, the ring seen AS A BLOCK TABLE of
period W (`ring_table`: column c of lane b is block 1 + b W + c % W):

  * writes: `paged_attention.write_token_kv` and
    `packed_prefill.write_packed_kv` over `ring_table`, in the pool's
    resident layout (same cells as `write_ring_token` /
    `write_ring_prompt`);
  * decode: `ring_decode_table` cuts the table to the W columns from
    the oldest live block and gives `paged_attention_decode` a lower
    bound beside the length (`kv_lo`): live blocks only, the stale cells
    of the oldest one masked;
  * prefill: `window_prefill_flash` lays [the ring's last `window` cells
    || the chunk's own K/V] out as a small pool of its own and hands it
    to `packed_prefill_attention` with a lower bound a query (`lower`):
    flash over the band, key tiles outside it skipped, no score block.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .packed_prefill import packed_prefill_attention, resolve_packed_impl
from .paged_attention import NEG_INF, _gather_ctx, _store_kv


def ring_blocks(window: int, block_size: int) -> int:
    """Blocks a lane's ring holds: the window can straddle one block
    boundary more than it fills."""
    return -(-window // block_size) + 1


def ring_pool_blocks(lanes: int, window: int, block_size: int) -> int:
    """Blocks of a ring pool for `lanes` lanes (block 0 is garbage)."""
    return 1 + lanes * ring_blocks(window, block_size)


def _ring_block(lane, pos, W: int, bs: int):
    return 1 + lane * W + (pos // bs) % W


def _ring_col(lanes, cols, W: int):
    """Block of column `cols` (positions c bs .. c bs + bs - 1; any
    integer, floor semantics) of the rings of `lanes` [B] -> [B, C]."""
    return 1 + lanes.astype(jnp.int32)[:, None] * W + cols % W


def ring_table(lanes, W: int, width: int):
    """The rings of `lanes` [B] as block tables [B, width]: column c is
    the lane's block c % W."""
    return _ring_col(lanes, jnp.arange(width, dtype=jnp.int32)[None, :], W)


def ring_decode_table(positions, valid, window: int, block_size: int):
    """What `paged_attention_decode` reads a window layer's ring by; row
    b is lane b and its token at `positions[b]` is already written.
    -> (table [B, W] from the oldest live block on, kv_lens [B] the
    table-relative end of the live positions (0 on an idle lane), kv_lo
    [B] their table-relative start, inside the first block)."""
    B = positions.shape[0]
    W = ring_blocks(window, block_size)
    lo = jnp.maximum(positions - window + 1, 0)
    first = lo // block_size
    table = _ring_col(jnp.arange(B, dtype=jnp.int32), first[:, None]
                      + jnp.arange(W, dtype=jnp.int32)[None, :], W)
    kv_lens = positions + 1 - first * block_size
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    return table, kv_lens.astype(jnp.int32), \
        (lo - first * block_size).astype(jnp.int32)


def _sink_softmax(s: jax.Array, sink: Optional[jax.Array]) -> jax.Array:
    """Softmax over the last axis of s [..., heads-like, S]; `sink`
    broadcastable to s[..., :1] joins the denominator only."""
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink - m)
    return p / den


# ---------------------------------------------------------------------------
# ring writes
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.kv_write")
def write_ring_prompt(k_cache, v_cache, layer: int, k, v, lanes, ctx_lens,
                      true_lens, window: int) -> Tuple[jax.Array, jax.Array]:
    """A prefill chunk's K/V into the rings: k [Bp, T, nkv, hd], v
    [Bp, T, nkv, hdv], lanes/ctx_lens/true_lens [Bp].  Written is only
    what the next chunk or decode can still read, the chunk's last
    `window` valid positions (fewer than the ring holds, so no two of
    them share a cell); everything else lands in the garbage block."""
    Bp, T = k.shape[:2]
    bs = k_cache.shape[4]
    W = ring_blocks(window, bs)
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    pos = ctx_lens[:, None] + idx
    keep = (idx < true_lens[:, None]) & (idx >= true_lens[:, None] - window)
    blocks = jnp.where(keep, _ring_block(lanes[:, None], pos, W, bs), 0)
    return _store_kv(k_cache, v_cache, layer,
                     k.reshape(Bp * T, *k.shape[2:]),
                     v.reshape(Bp * T, *v.shape[2:]),
                     blocks.reshape(-1), (pos % bs).reshape(-1), None, None)


@jax.named_scope("dyn.kv_write")
def write_ring_token(k_cache, v_cache, layer: int, k, v, positions,
                     window: int, valid=None
                     ) -> Tuple[jax.Array, jax.Array]:
    """One decode token a lane: k [B, nkv, hd], v [B, nkv, hdv]; row b is
    lane b.  Idle lanes (valid False) write to the garbage block: a lane
    that is mid-prefill must keep its ring."""
    B = k.shape[0]
    bs = k_cache.shape[4]
    W = ring_blocks(window, bs)
    blocks = _ring_block(jnp.arange(B, dtype=jnp.int32), positions, W, bs)
    if valid is not None:
        blocks = jnp.where(valid, blocks, 0)
    return _store_kv(k_cache, v_cache, layer, k, v, blocks, positions % bs,
                     None, None)


# ---------------------------------------------------------------------------
# reads
# ---------------------------------------------------------------------------


def gather_ring_tail(cache, layer: int, lane, ctx_len, window: int, W: int):
    """The `window` cells before position `ctx_len` of one lane's ring:
    [window, nkv, hd], oldest first.  Cells of negative positions hold
    whatever the ring held; the caller masks them."""
    bs = cache.shape[4]
    pos = jnp.maximum(ctx_len - window + jnp.arange(window, dtype=jnp.int32),
                      0)
    return cache[layer][:, _ring_block(lane, pos, W, bs), :, pos % bs]


@jax.named_scope("dyn.attn_window")
def window_prefill_attention(q, k, v, k_tail, v_tail, ctx_len, true_len,
                             window: int, sink=None) -> jax.Array:
    """q [T, nh, hd], this chunk's k [T, nkv, hd] / v [T, nkv, hdv],
    the ring's tail before the chunk (gather_ring_tail, [window, ...]);
    query i attends keys j with 0 <= i - j < window.  Tiles of
    tq = min(T, window) queries against the tq + window keys that end
    with the tile.  -> [T, nh, hdv]."""
    T, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    w = window
    tq = min(T, w)
    if T % tq:
        raise ValueError(f"chunk of {T} tokens is not whole tiles of {tq}")
    nt = T // tq

    def keys(tail, cur):
        ext = jnp.concatenate([tail.astype(cur.dtype), cur], axis=0)
        older = ext[:w][None] if nt == 1 else \
            ext[:T].reshape(nt, tq, *cur.shape[1:])
        newer = ext[w:].reshape(nt, tq, *cur.shape[1:])
        return jnp.concatenate([older, newer], axis=1)   # [nt, w+tq, ..]

    kt, vt = keys(k_tail, k), keys(v_tail, v)
    qt = q.reshape(nt, tq, nkv, g, hd)
    s = jnp.einsum("nakgh,nckh->nkgac", qt, kt,
                   preferred_element_type=jnp.float32) \
        / jnp.sqrt(jnp.float32(hd))
    a = jnp.arange(tq)[:, None]                 # query index in its tile
    c = jnp.arange(w + tq)[None, :]             # key index in its window
    kidx = jnp.arange(nt)[:, None, None] * tq + c[None] - w   # in chunk
    ok = ((c - w <= a) & (c > a))[None] & (kidx < true_len) \
        & (ctx_len + kidx >= 0)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = _sink_softmax(s, None if sink is None else
                      sink.astype(jnp.float32).reshape(1, nkv, g, 1, 1))
    o = jnp.einsum("nkgac,nckh->nakgh", p.astype(vt.dtype), vt,
                   preferred_element_type=jnp.float32)
    return o.reshape(T, nh, v.shape[-1]).astype(q.dtype)


@jax.named_scope("dyn.attn_window")
def window_decode_attention(q, k_cache, v_cache, layer: int, positions,
                            valid, window: int, sink=None) -> jax.Array:
    """q [B, nh, hd], row b is lane b and its token at `positions[b]` is
    already in the ring.  Each lane reads its own W blocks, oldest block
    first, and masks by position.  -> [B, nh, hdv]."""
    B, nh, hd = q.shape
    nkv, bs = k_cache.shape[1], k_cache.shape[4]
    W = ring_blocks(window, bs)
    g = nh // nkv
    sinkf = None if sink is None else \
        sink.astype(jnp.float32).reshape(nkv, g, 1)
    age = jnp.arange(W, dtype=jnp.int32) - (W - 1)

    def one(qb, lane, pos, ok):
        bidx = pos // bs + age                        # [W] block numbers
        table = 1 + lane * W + jnp.mod(bidx, W)
        kb = _gather_ctx(k_cache, layer, table)       # [nkv, W*bs, hd]
        vb = _gather_ctx(v_cache, layer, table)
        kpos = (bidx[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        s = jnp.einsum("kgh,ksh->kgs", qb.reshape(nkv, g, hd), kb,
                       preferred_element_type=jnp.float32) \
            / jnp.sqrt(jnp.float32(hd))
        live = (kpos >= 0) & (kpos <= pos) & (pos - kpos < window) & ok
        s = jnp.where(live[None, None, :], s, NEG_INF)
        p = _sink_softmax(s, sinkf)
        o = jnp.einsum("kgs,ksh->kgh", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        return o.reshape(nh, vb.shape[-1])

    if valid is None:
        valid = jnp.ones((B,), bool)
    out = jax.vmap(one)(q, jnp.arange(B, dtype=jnp.int32), positions, valid)
    return out.astype(q.dtype)


@jax.named_scope("dyn.attn_global")
def causal_prefill_attention(q, k_cache, v_cache, layer: int, block_table,
                             ctx_len, true_len, q_tile: int = 256
                             ) -> jax.Array:
    """q [T, nh, hd] at positions ctx_len + i; the chunk's own K/V are
    ALREADY in the pool (written before the read), so context and chunk
    are one gather of the table.  K [.., hd, bs] and V [.., hdv, bs] may
    differ in width.  Queries go in tiles of `q_tile` so the scores are
    [q_tile, nh, table width x bs] at a time.  -> [T, nh, hdv]."""
    T, nh, hd = q.shape
    kc = _gather_ctx(k_cache, layer, block_table)     # [nkv, S, hd]
    vc = _gather_ctx(v_cache, layer, block_table)
    nkv, S = kc.shape[:2]
    g = nh // nkv
    tq = min(T, q_tile)
    if T % tq:
        raise ValueError(f"chunk of {T} tokens is not whole tiles of {tq}")
    kpos = jnp.arange(S)

    def tile(args):
        qt, i0 = args                                 # [tq, nh, hd]
        s = jnp.einsum("akgh,ksh->kgas", qt.reshape(tq, nkv, g, hd), kc,
                       preferred_element_type=jnp.float32) \
            / jnp.sqrt(jnp.float32(hd))
        qpos = ctx_len + i0 + jnp.arange(tq)
        ok = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] < ctx_len + true_len)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, NEG_INF), axis=-1)
        o = jnp.einsum("kgas,ksh->akgh", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return o.reshape(tq, nh, vc.shape[-1]).astype(q.dtype)

    out = jax.lax.map(tile, (q.reshape(T // tq, tq, nh, hd),
                             jnp.arange(T // tq, dtype=jnp.int32) * tq))
    return out.reshape(T, nh, vc.shape[-1])


# ---------------------------------------------------------------------------
# a window of many blocks: the packed flash forms over the band
# ---------------------------------------------------------------------------


def _band_block(window: int, tokens: int) -> int:
    """Block size of the band's own pool: 128 (the kernel's lane tile)
    where window and chunk are whole blocks of it."""
    return math.gcd(window, tokens, 128)


def resolve_window_prefill_impl(impl: str, platform: str, window: int,
                                head_dim: int, dtype, tokens: int,
                                group: int) -> str:
    """The impl of `window_prefill_flash` for a stream of `tokens`:
    `packed_prefill.resolve_packed_impl`'s rule over the band's own pool,
    asked by the traced read and by the host's count alike."""
    return resolve_packed_impl(impl, platform, _band_block(window, tokens),
                               head_dim, dtype, tokens, group)


@jax.named_scope("dyn.attn_window")
def window_prefill_flash(q, k, v, k_ring, v_ring, layer: int, lanes,
                         seg_ids, positions, valid, window: int,
                         impl: str = "auto") -> jax.Array:
    """A packed stream's window read, BEFORE the chunk is written to the
    rings (a chunk may overwrite cells its first queries still see).
    q [T, nh, hd], the stream's own k / v [T, nkv, hd], the ring pools,
    lanes [S] the lane of each segment row; the stream's contract is
    packed_prefill's (a row is one run at consecutive positions).

    Row s's keys are laid out as blocks of a pool of their own: the
    `window` cells before the row's first position (W ring blocks from
    the one that holds position ctx - window, cut at that cell), then the
    stream rolled so that the row's first token follows them.  Cell r of
    the row is position ctx - window + r, so a query at position p reads
    cells [max(p - ctx + 1, window - ctx), p - ctx + window]: a band,
    given to `packed_prefill_attention` as `lower`.  -> [T, nh, hd]."""
    T, nh, hd = q.shape
    S = lanes.shape[0]
    nkv, bs = k_ring.shape[1], k_ring.shape[4]
    W = ring_blocks(window, bs)
    tb = _band_block(window, T)
    nb = (window + T) // tb
    rows = jnp.arange(S, dtype=jnp.int32)
    own = valid[None, :] & (seg_ids[None, :] == rows[:, None])   # [S, T]
    start = jnp.argmax(own, axis=1).astype(jnp.int32)
    ctx = positions[start].astype(jnp.int32)
    first = (ctx - window) // bs                  # floor: may be negative
    cut = ctx - window - first * bs               # in [0, bs)
    ids = _ring_col(lanes, first[:, None]
                    + jnp.arange(W, dtype=jnp.int32)[None, :], W)
    li = jnp.int32(layer)

    def pool(ring, cur):
        g = ring[li, :, ids]                      # [S, W, nkv, hd, bs]
        g = g.transpose(0, 2, 3, 1, 4).reshape(S, nkv, cur.shape[-1], W * bs)
        tail = jax.vmap(lambda x, c: jax.lax.dynamic_slice_in_dim(
            x, c, window, axis=2))(g, cut)
        cur = cur.astype(ring.dtype).transpose(1, 2, 0)          # [nkv, hd, T]
        own_k = jax.vmap(lambda s0: jnp.roll(cur, -s0, axis=2))(start)
        ext = jnp.concatenate([tail, own_k], axis=3)
        ext = ext.reshape(S, nkv, cur.shape[1], nb, tb)
        return ext.transpose(1, 0, 3, 2, 4).reshape(
            1, nkv, S * nb, cur.shape[1], tb)

    tables = rows[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)[None, :]
    rel = positions - ctx[seg_ids] + window
    lower = jnp.maximum(rel - window + 1, window - ctx[seg_ids])
    impl = resolve_window_prefill_impl(impl, jax.default_backend(), window,
                                       hd, k_ring.dtype, T,
                                       q.shape[1] // k.shape[1])
    return packed_prefill_attention(
        q, pool(k_ring, k), pool(v_ring, v), 0, tables, seg_ids, rel, valid,
        impl=impl, lower=lower)
