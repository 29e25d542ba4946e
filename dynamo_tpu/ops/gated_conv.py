"""The gated short convolution (LFM2's `conv` operator): a mixer whose
memory of the past is the convolution's own tail and nothing else.

    [B, C, u] = h W_in                      three thirds, in that order
    g_t = B_t * u_t
    c_t = sum_{j<W} w[j] * g_{t-(W-1)+j}    depthwise, causal, g before
                                            the sequence's start is 0
    y_t = C_t * c_t                         then W_out; NO activation

The state after token t is g's last W - 1 rows (W 3: two rows of the
layer's width a lane, bf16), a lane-addressed STATE member whose life
ops/lane_state.py keeps.  The tap arithmetic is ops/delta_attention.py's
(`conv_taps`, `causal_conv_step`): the one copy in the tree; what is
this operator's own is the gate on both sides, the absence of the SiLU,
and the PACKED form: rows of several sequences end to end in one stream,
where a tap that would reach before its row's first token of this chunk
reads the row's carried tail (zeros where the row starts its sequence)
and never the row in front of it.

`g` is rounded to the tail's dtype BEFORE the taps in every form, so a
prompt cut into chunks anywhere, one chunk, and decode steps all
multiply the same numbers; the sums are float32.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .delta_attention import causal_conv_step, conv_taps


class PackedRows(NamedTuple):
    """Where each row of a packed stream lies (`packed_rows`), the same
    for every layer of a program."""
    first: jax.Array   # [S] stream index of the row's first token
    n: jax.Array       # [S] the row's tokens in this stream
    off: jax.Array     # [T] a token's offset inside its row's run
    valid: jax.Array   # [T]


def packed_rows(seg_ids: jax.Array, valid: jax.Array,
                rows: int) -> PackedRows:
    """The packed stream's contract (ops/packed_prefill.py
    `check_packed_stream`: a segment row is ONE run of the stream) read
    off `seg_ids` [T] and `valid` [T] for `rows` segment rows.  A row of
    no tokens has n 0 (and first 0, which nothing then uses)."""
    own = valid[None, :] & (
        seg_ids[None, :] == jnp.arange(rows, dtype=jnp.int32)[:, None])
    first = jnp.argmax(own, axis=1).astype(jnp.int32)
    off = jnp.arange(seg_ids.shape[0], dtype=jnp.int32) \
        - first[jnp.clip(seg_ids, 0, rows - 1)]
    return PackedRows(first, jnp.sum(own, axis=1, dtype=jnp.int32), off,
                      valid)


@jax.named_scope("dyn.short_conv")
def gated_conv_packed(b: jax.Array, c: jax.Array, u: jax.Array,
                      w: jax.Array, rows: PackedRows, start: jax.Array):
    """A packed stream through the operator.  b, c, u [T, C] float32
    (the projection's thirds), w [W, C], `start` [S, W - 1, C] each
    row's tail BEFORE this chunk (`lane_state.rows_start`: zeros where
    the row begins its sequence) -> (y [T, C] float32, the tail each row
    leaves [S, W - 1, C]: its last W - 1 `g` at its true length, reaching
    back into `start` where the run is shorter than the tail).  What a
    row of no tokens gets is for `rows_target` to drop."""
    T, W = u.shape[0], w.shape[0]
    S = start.shape[0]
    g = (b * u).astype(start.dtype)
    # inside a run: tap j reaches W - 1 - j tokens back and stops at the
    # run's first token; padding reads nothing
    reach = (W - 1) - jnp.arange(W, dtype=jnp.int32)
    keep = rows.valid[None, :] & (rows.off[None, :] >= reach[:, None])
    conv = conv_taps(jnp.concatenate(
        [jnp.zeros((W - 1, g.shape[1]), g.dtype), g]), w, T, keep)
    # across the run's start: its first W - 1 tokens read the carried
    # tail, the same taps over [tail || nothing]
    k = jnp.arange(W - 1, dtype=jnp.int32)
    carried = jax.vmap(conv_taps, in_axes=(0, None, None))(
        jnp.concatenate([start, jnp.zeros_like(start)], axis=1), w, W - 1)
    at = jnp.where(k[None, :] < rows.n[:, None],
                   rows.first[:, None] + k[None, :], T)
    conv = conv.at[at.reshape(-1)].add(
        carried.reshape(S * (W - 1), -1), mode="drop")
    # the tail a row leaves: the last W - 1 of [start || its run]
    src = rows.n[:, None] - (W - 1) + k[None, :]            # [S, W - 1]
    from_run = g[jnp.clip(rows.first[:, None] + src, 0, T - 1)]
    from_start = jnp.take_along_axis(
        start, jnp.clip(src + (W - 1), 0, W - 2)[..., None], axis=1)
    tail = jnp.where((src >= 0)[..., None], from_run, from_start)
    return c * conv, tail


@jax.named_scope("dyn.short_conv")
def gated_conv_step(b: jax.Array, c: jax.Array, u: jax.Array,
                    w: jax.Array, tail: jax.Array):
    """One token a lane: b, c, u [B, C] float32, tail [B, W - 1, C] ->
    (y [B, C] float32, the new tail).  Keeping an idle lane's tail is
    the caller's (`lane_state.lanes_keep`)."""
    conv, tail = causal_conv_step((b * u).astype(tail.dtype), tail, w,
                                  act=None)
    return c * conv, tail
