"""What every family's programs repeat and that says nothing about a
family: the decode burst's scan, the one-row prefill, a layer's index
inside its kind's cache members.  Imports no family module."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
               num_steps: int, sample_fn=None):
    """`num_steps` fused decode steps in ONE compiled program (lax.scan),
    the body of every family's `decode_multi`.

    The serving hot loop's dominant off-roofline cost on this platform is
    per-dispatch overhead (each jit call round-trips the host); fusing k
    steps amortizes it k-fold — the on-device generate loop every
    production TPU serving stack runs.  Sampled ids chain on device; block
    tables are fixed across the burst, so callers must pre-allocate blocks
    covering positions [ctx, ctx + num_steps).

    `step(kv, tokens, pos, ctx) -> (logits or hidden, kv)` is the
    family's one decode step with everything else bound;
    `sample_fn(step's first result, step_idx) -> tokens [B]`, greedy
    over logits where absent.  Returns (tokens [num_steps, B], updated
    kv_cache)."""
    if sample_fn is None:
        def sample_fn(logits, _):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def body(carry, step_idx):
        tokens, kv, pos, cls = carry
        out, kv = step(kv, tokens, pos, cls)
        nt = sample_fn(out, step_idx).astype(jnp.int32)
        return (nt, kv, pos + 1, cls + 1), nt

    (_, kv_cache, _, _), toks = jax.lax.scan(
        body, (token_ids, kv_cache, positions, ctx_lens),
        jnp.arange(num_steps), length=num_steps,
    )
    return toks, kv_cache


def prefill_one_row(prefill_batched):
    """The family's `prefill` (llama.prefill's contract: one sequence's
    chunk) as a batch of one through its `prefill_batched`.  `lanes`
    (scalar: this sequence's lane) is handed on only where the caller
    gives it, so a family whose cache has no lane-addressed member
    shares this."""
    def prefill(params, cfg, kv_cache, token_ids, positions, block_table,
                ctx_len, true_len, lanes=None):
        row = (token_ids[None], positions[None], block_table[None],
               ctx_len[None], true_len[None])
        lane_kw = {} if lanes is None else {"lanes": lanes[None]}
        logits, kv_cache = prefill_batched(params, cfg, kv_cache, *row,
                                           **lane_kw)
        return logits[0], kv_cache

    return prefill


def pool_index(cfg):
    """layer -> its index inside its kind's cache members, by
    `cfg.layer_kinds`."""
    seen, out = {}, []
    for kind in cfg.layer_kinds:
        out.append(seen.setdefault(kind, 0))
        seen[kind] += 1
    return out
