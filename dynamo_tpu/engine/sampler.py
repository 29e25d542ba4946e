"""On-device token sampling: greedy / temperature / top-k / top-p.

Runs inside the jitted decode/prefill step so only the sampled token ids
cross back to the host.  All parameters are per-slot arrays so one compiled
program serves heterogeneous batches (mixing greedy and sampled requests).

Candidate-capped design: sampling is restricted to the CAP (64) highest
logits per slot.  A full-vocab sort per token (3 sorts of 128k on Llama-3
vocab) measured ~40% of the whole decode burst on v5e; lax.top_k over a
64-candidate window costs ~nothing and is the standard serving
approximation (requested top_k is clamped to CAP; top-p nucleus mass is
computed against the TRUE full softmax via logsumexp, truncated to the
window, so small-p nuclei are exact and only a pathological p over a
near-uniform distribution feels the cap).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

#: sampling candidate window (max effective top-k)
CAP = 64


@jax.named_scope("dyn.sample")
def sample_tokens(
    logits: jax.Array,        # [B, vocab] fp32
    seeds: jax.Array,         # [B] int32 per-request seed
    steps: jax.Array,         # [B] int32 decode step counter (rng stream)
    temperature: jax.Array,   # [B] fp32; <=0 means greedy
    top_k: jax.Array,         # [B] int32; 0 disables
    top_p: jax.Array,         # [B] fp32; >=1 disables
) -> jax.Array:
    """Returns sampled token ids [B]."""

    def one(lg, seed, step, temp, tk, tp):
        greedy = jnp.argmax(lg)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        idx, masked = _candidate_window(lg, temp, tk, tp)
        sampled = idx[jax.random.categorical(key, masked)]
        return jnp.where(temp <= 0.0, greedy, sampled)

    return jax.vmap(one)(logits, seeds, steps, temperature, top_k, top_p)


def _candidate_window(lg, temp, tk, tp):
    """One slot's candidates: (ids [CAP] of the CAP highest
    temperature-scaled logits, their scaled logits with what top-k and
    top-p leave out at NEG_INF)."""
    scaled = lg / jnp.maximum(temp, 1e-6)
    vals, idx = jax.lax.top_k(scaled, CAP)     # sorted descending
    k_eff = jnp.clip(jnp.where(tk > 0, tk, CAP), 1, CAP)
    keep_k = jnp.arange(CAP) < k_eff
    # nucleus mass against the TRUE distribution (full-vocab logsumexp,
    # no sort); first candidate always kept
    probs = jnp.exp(vals - jax.scipy.special.logsumexp(scaled))
    cum = jnp.cumsum(probs)
    keep_p = jnp.concatenate([jnp.array([True]), cum[:-1] < tp])
    return idx, jnp.where(keep_k & keep_p, vals, NEG_INF)


@jax.named_scope("dyn.sample")
def sample_block_tokens(
    logits: jax.Array,        # [L, B, vocab] fp32: a block's positions a lane
    seeds: jax.Array,         # [L] int32 per-request seed
    steps: jax.Array,         # [L, B] int32 rng stream of each position
    temperature: jax.Array,   # [L] fp32; <=0 means greedy
    top_k: jax.Array,         # [L] int32; 0 disables
    top_p: jax.Array,         # [L] fp32; >=1 disables
):
    """One distribution a (lane, block position), for a family that
    generates by blocks (models/sdar.py): `sample_tokens`' draw at every
    position of a lane's block, and beside each token the probability it
    was drawn with: of the filtered, renormalised window for a sampled
    request, softmax(logits)[argmax] over the unfiltered logits for a
    greedy one.  Returns (tokens [L, B] int32, probabilities [L, B])."""

    def one(lg, seed, step, temp, tk, tp):
        top = jnp.max(lg)
        p_greedy = jnp.exp(top - jax.scipy.special.logsumexp(lg))
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        idx, masked = _candidate_window(lg, temp, tk, tp)
        j = jax.random.categorical(key, masked)
        greedy = temp <= 0.0
        return (jnp.where(greedy, jnp.argmax(lg), idx[j]).astype(jnp.int32),
                jnp.where(greedy, p_greedy, jax.nn.softmax(masked)[j]))

    lane = jax.vmap(one, in_axes=(0, None, 0, None, None, None))
    return jax.vmap(lane)(logits, seeds, steps, temperature, top_k, top_p)


@jax.named_scope("dyn.sample")
def greedy_tokens(logits: jax.Array) -> jax.Array:
    """Argmax-only fast path: the engine dispatches this specialization when
    every slot in the batch is greedy (temperature <= 0), skipping the
    sampling machinery entirely."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# speculative decoding: host-side rejection sampling (spec/)
#
# The verify program (engine/core.py _spec_verify_impl) returns, per packed
# position, the top-CAP candidate ids + temperature-scaled logits and the
# full-vocab logsumexp of the scaled logits.  From those three arrays the
# host reconstructs EXACTLY the masked-window categorical `sample_tokens`
# draws from (same CAP window, same top-k clamp, same true-softmax top-p
# nucleus), so acceptance decisions are made against the real target
# distribution, not an approximation of it.
#
# Proposals are point masses (greedy n-gram / greedy draft model), so the
# Leviathan rejection rule specializes to: accept draft d with probability
# p(d); on rejection, sample from p with d's mass removed, renormalized.
# The emitted marginal is p(d)*1[x=d] + (1-p(d)) * p(x)*1[x!=d]/(1-p(d))
# = p(x) — the target distribution exactly, per position.  Greedy
# (temperature <= 0) degenerates to exact argmax-prefix matching, so the
# speculative stream is token-identical to plain greedy decode.
# ---------------------------------------------------------------------------


def spec_window_weights(vals: np.ndarray, lse: float, top_k: int,
                        top_p: float) -> np.ndarray:
    """Normalized target weights over the CAP candidate window — the same
    masking sample_tokens applies on device.  vals: [CAP] scaled logits
    sorted descending; lse: logsumexp of the full scaled logits."""
    probs = np.exp(vals.astype(np.float64) - float(lse))
    k_eff = int(np.clip(top_k if top_k > 0 else CAP, 1, CAP))
    keep = np.arange(CAP) < k_eff
    cum = np.cumsum(probs)
    keep &= np.concatenate(([True], cum[:-1] < top_p))
    w = np.where(keep, probs, 0.0)
    s = w.sum()
    if s <= 0.0:  # fp underflow corner: the argmax candidate stands alone
        w = np.zeros(CAP)
        w[0] = 1.0
        return w
    return w / s


def spec_accept_tokens(
    ids: np.ndarray,      # [n, CAP] candidate ids per position, sorted
    vals: np.ndarray,     # [n, CAP] scaled logits per position
    lse: np.ndarray,      # [n] full-vocab logsumexp of scaled logits
    drafts: List[int],    # k point-mass proposals (n == k + 1)
    *,
    greedy: bool,
    top_k: int,
    top_p: float,
    rng: np.random.Generator,
) -> Tuple[int, List[int]]:
    """Verify k drafted tokens against the target's per-position window
    distributions.  Returns (accepted_count, emitted_tokens): the
    accepted draft prefix plus exactly ONE more token — the corrected
    sample at the first rejection, or the bonus token from the position
    after the last draft when everything was accepted."""
    emitted: List[int] = []
    for i, d in enumerate(drafts):
        if greedy:
            t = int(ids[i, 0])
            if t == d:
                emitted.append(d)
                continue
            emitted.append(t)
            return i, emitted
        w = spec_window_weights(vals[i], lse[i], top_k, top_p)
        j = np.nonzero(ids[i] == d)[0]
        p_d = float(w[j[0]]) if len(j) else 0.0
        if rng.random() < p_d:
            emitted.append(d)
            continue
        if len(j):
            w[j[0]] = 0.0
        s = w.sum()
        if s <= 0.0:
            # the target was itself a point mass at d and the float
            # comparison still rejected: d IS the sample
            emitted.append(d)
            continue
        emitted.append(int(ids[i, rng.choice(CAP, p=w / s)]))
        return i, emitted
    # every draft accepted: bonus token from the last scored position
    i = len(drafts)
    if greedy:
        emitted.append(int(ids[i, 0]))
    else:
        w = spec_window_weights(vals[i], lse[i], top_k, top_p)
        emitted.append(int(ids[i, rng.choice(CAP, p=w)]))
    return len(drafts), emitted


def apply_penalties(
    logits: jax.Array,          # [B, vocab]
    token_counts: jax.Array,    # [B, vocab] int32: counts in generated output
    frequency_penalty: jax.Array,  # [B]
    presence_penalty: jax.Array,   # [B]
) -> jax.Array:
    lf = logits
    lf = lf - frequency_penalty[:, None] * token_counts.astype(jnp.float32)
    lf = lf - presence_penalty[:, None] * (token_counts > 0).astype(jnp.float32)
    return lf
