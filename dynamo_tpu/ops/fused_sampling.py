"""Fused sampling/top-k epilogue: final projection -> token ids without
materializing [B, vocab] logits in HBM.

The decode hot loop's second documented stall (after attention): every
step runs the [B, d] x [d, vocab] final projection, writes [B, vocab]
fp32 logits to HBM, then reads them straight back for an argmax or a
top-CAP window — at Llama-3 vocab (128k) that round trip is ~1 MB per
slot per token of pure HBM traffic on an otherwise bandwidth-bound
phase.  This epilogue streams the projection in vocab TILES and reduces
each tile on the fly into exactly the statistics sampling needs:

  * a running argmax over the RAW logits (strict `>` update, so the
    first maximum wins — byte-identical to `jnp.argmax` over the full
    vector, which is the sampler's greedy and temp<=0 contract);
  * a running top-CAP candidate window over the TEMPERATURE-SCALED
    logits (merge order: running candidates concatenated BEFORE the
    tile's, so `lax.top_k`'s stable lower-index tie-break matches the
    full-vocab call);
  * a running logsumexp of the scaled logits (online max/sum rescale),
    the true-softmax normalizer the top-p nucleus is measured against.

From those three, `fused_sample_tokens` replays engine/sampler.py's
`sample_tokens` EXACTLY — same fold_in(PRNGKey(seed), step) key, same
top-k clamp, same first-candidate-always-kept nucleus mask, same masked
categorical — so greedy output is byte-identical and sampled output is
distribution-identical (the only divergence is the fp32 summation
order inside logsumexp, ~1 ulp on the nucleus boundary).

Implementation choice (the "measured choice" the EngineConfig knob
gates): fused-XLA (a fori_loop of dynamic-sliced tile matmuls inside
the already-jitted decode program) rather than a Pallas kernel — the
projection is a plain MXU matmul XLA already schedules at peak, the
reduction carry is tiny ([B, CAP]), and keeping it in XLA lets the
epilogue fuse into decode/decode_multi without a second kernel launch
or its own VMEM budget.  A Pallas variant only pays once the tile
reductions themselves bound the step; the knob ("off" | "fused") keeps
the jnp reference path as fallback and A/B row.

Callers pass the FINAL-NORM hidden state (models/llama.py decode_hidden)
plus the unembedding matrix (models/llama.py unembed_weight); each tile
computes `(h @ w[:, a:b]).astype(fp32)` — columnwise identical to the
reference `_logits` matmul, which is what the byte-identity contract
rides on (tests/test_fused_sampling.py, tests/test_engine_epilogue.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

#: sampling candidate window — MUST equal engine/sampler.py CAP (the
#: reference this epilogue is byte/distribution-identical to); asserted
#: in tests/test_fused_sampling.py
CAP = 64

#: vocab columns per streamed tile: big enough that the tile matmul is
#: MXU-efficient, small enough that [B, tile] fp32 stays in registers /
#: VMEM-resident fusion instead of round-tripping HBM
DEFAULT_TILE = 2048

#: EngineConfig.sampling_epilogue vocabulary (validated in
#: engine/core.py, advertised by the worker MDC)
EPILOGUE_MODES = ("off", "fused")


def _tile_plan(V: int, tile: int):
    """Clamped tile width and count.  The last tile's start is clamped
    to V - tile (dynamic_slice semantics), so its leading columns
    overlap the previous tile; per-tile `fresh` masks re-hide them."""
    tile = max(1, min(tile, V))
    return tile, -(-V // tile)


def _tile_logits(h, w, i, tile, V):
    """One streamed tile: fp32 logits [B, tile], global column ids
    [tile], and the fresh-mask hiding the clamped last tile's overlap
    with its predecessor."""
    D = h.shape[1]
    start = jnp.minimum(i * tile, V - tile)
    wt = jax.lax.dynamic_slice(w, (0, start), (D, tile))
    lg = (h @ wt).astype(jnp.float32)
    cols = start + jnp.arange(tile, dtype=jnp.int32)
    fresh = cols >= i * tile
    return lg, cols, fresh


@jax.named_scope("dyn.lm_head_sample")
def fused_greedy_tokens(h: jax.Array,   # [B, d] final-norm hidden
                        w: jax.Array,   # [d, vocab] unembedding matrix
                        *, tile: int = DEFAULT_TILE) -> jax.Array:
    """Streaming argmax of the final projection: byte-identical to
    sampler.greedy_tokens(_logits(...)) — strict `>` keeps the first
    maximum, tiles ascend, so ties resolve to the lowest vocab id
    exactly like jnp.argmax.  Returns token ids [B] int32."""
    B = h.shape[0]
    V = w.shape[1]
    tile, n_t = _tile_plan(V, tile)

    def body(i, carry):
        bv, bi = carry
        lg, cols, fresh = _tile_logits(h, w, i, tile, V)
        lg = jnp.where(fresh[None, :], lg, -jnp.inf)
        tv = jnp.max(lg, axis=-1)
        ta = cols[jnp.argmax(lg, axis=-1)]
        upd = tv > bv
        return jnp.where(upd, tv, bv), jnp.where(upd, ta, bi)

    _, bi = jax.lax.fori_loop(
        0, n_t, body,
        (jnp.full((B,), -jnp.inf, jnp.float32),
         jnp.zeros((B,), jnp.int32)))
    return bi


@jax.named_scope("dyn.lm_head_sample")
def fused_sample_tokens(
    h: jax.Array,            # [B, d] final-norm hidden
    w: jax.Array,            # [d, vocab] unembedding matrix
    seeds: jax.Array,        # [B] int32 per-request seed
    steps: jax.Array,        # [B] int32 decode step counter (rng stream)
    temperature: jax.Array,  # [B] fp32; <=0 means greedy
    top_k: jax.Array,        # [B] int32; 0 disables
    top_p: jax.Array,        # [B] fp32; >=1 disables
    *, tile: int = DEFAULT_TILE,
) -> jax.Array:
    """Streaming sample_tokens: one pass over the projection tiles
    accumulates (argmax, top-CAP window, logsumexp), then the sampler's
    masked-window categorical replays verbatim on the window.  Requires
    vocab >= CAP — the same bound lax.top_k imposes on the reference."""
    B = h.shape[0]
    V = w.shape[1]
    tile, n_t = _tile_plan(V, max(tile, CAP))
    denom = jnp.maximum(temperature, 1e-6)  # sampler.py's scaled = lg/..

    def body(i, carry):
        bv, bi, rv, ri, m, s = carry
        lg, cols, fresh = _tile_logits(h, w, i, tile, V)
        # greedy stream over RAW logits (the temp<=0 per-slot fallback)
        lgm = jnp.where(fresh[None, :], lg, -jnp.inf)
        tv = jnp.max(lgm, axis=-1)
        ta = cols[jnp.argmax(lgm, axis=-1)]
        upd = tv > bv
        bv = jnp.where(upd, tv, bv)
        bi = jnp.where(upd, ta, bi)
        # temperature-scaled stream (division, matching the reference's
        # rounding exactly); overlap columns hide at -inf: exp -> 0 in
        # the normalizer, never a candidate
        sc = jnp.where(fresh[None, :], lg / denom[:, None], -jnp.inf)
        # online logsumexp
        mn = jnp.maximum(m, jnp.max(sc, axis=-1))
        s = s * jnp.exp(m - mn) \
            + jnp.sum(jnp.exp(sc - mn[:, None]), axis=-1)
        # top-CAP merge: running window FIRST so lax.top_k's stable
        # tie-break prefers earlier (lower-id) candidates, matching the
        # full-vocab call's ascending-index tie order
        tvk, tik = jax.lax.top_k(sc, CAP)
        cat_v = jnp.concatenate([rv, tvk], axis=-1)
        cat_i = jnp.concatenate([ri, cols[tik]], axis=-1)
        rv, sel = jax.lax.top_k(cat_v, CAP)
        ri = jnp.take_along_axis(cat_i, sel, axis=-1)
        return bv, bi, rv, ri, mn, s

    bv, bi, rv, ri, m, s = jax.lax.fori_loop(
        0, n_t, body,
        (jnp.full((B,), -jnp.inf, jnp.float32),
         jnp.zeros((B,), jnp.int32),
         jnp.full((B, CAP), -jnp.inf, jnp.float32),
         jnp.zeros((B, CAP), jnp.int32),
         jnp.full((B,), -jnp.inf, jnp.float32),
         jnp.zeros((B,), jnp.float32)))
    lse = m + jnp.log(s)

    # engine/sampler.py sample_tokens' window math, verbatim, on the
    # streamed (vals, idx, lse) instead of a full-vocab top_k
    def one(gidx, vals, idx, lse1, seed, step, temp, tk, tp):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        k_eff = jnp.clip(jnp.where(tk > 0, tk, CAP), 1, CAP)
        keep_k = jnp.arange(CAP) < k_eff
        probs = jnp.exp(vals - lse1)
        cum = jnp.cumsum(probs)
        keep_p = jnp.concatenate([jnp.array([True]), cum[:-1] < tp])
        masked = jnp.where(keep_k & keep_p, vals, NEG_INF)
        sampled = idx[jax.random.categorical(key, masked)]
        return jnp.where(temp <= 0.0, gidx, sampled)

    return jax.vmap(one)(bi, rv, ri, lse, seeds, steps, temperature,
                         top_k, top_p)
