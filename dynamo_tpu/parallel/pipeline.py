"""Pipeline parallelism: stage-stacked SPMD pipelining over a "pp" axis.

The TPU-native expression of the reference's PP strategy (SURVEY §2.4):
instead of one process per stage exchanging activations over NCCL P2P, ALL
stages run one SPMD program.  Layer parameters (and any per-stage state,
e.g. that stage's KV slice) are stacked on a leading stage axis and sharded
over the "pp" mesh axis, so each device physically holds only its own
stage's weights; activations rotate stage-to-stage with `lax.ppermute`
(neighbor hops on the ICI ring) under `shard_map`.

Schedule: the standard rotating microbatch pipeline (GPipe-style fill +
drain).  With S stages and M microbatches, the loop runs S+M-1 ticks; at
tick t, stage s processes microbatch m = t - s when 0 <= m < M, else it is
a bubble.  Utilization is M/(S+M-1) — callers should feed M >= S
microbatches.  Bubbles still execute the stage computation (SPMD programs
cannot diverge) but their `active` flag is False so stage_fn masks its
state writes and the result is discarded.

This module is the PP primitive; the serving engine composes it by making
one "stage" = its contiguous slice of transformer layers with that slice's
KV as the per-stage state.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


# stage_fn(params_slice, state_slice, x, active) -> (y, new_state_slice)
#   params_slice/state_slice: this stage's slice (leading stage axis
#   removed), x: one microbatch's activations, active: bool scalar — False
#   during pipeline bubbles; stage_fn MUST make state writes a no-op then.
StageFn = Callable


def _pipeline_shard(params, state, xs, *, stage_fn: StageFn, axis: str,
                    n_micro: int):
    """Per-device body.  params/state arrive as this stage's slice with a
    leading axis of size 1; xs [M, ...] is replicated."""
    S = lax.psum(1, axis)
    sidx = lax.axis_index(axis)
    params = jax.tree_util.tree_map(lambda a: a[0], params)
    state = jax.tree_util.tree_map(lambda a: a[0], state)
    M = n_micro

    def tick(t, carry):
        buf, ys, state = carry
        m = t - sidx                      # microbatch at this stage now
        active = (m >= 0) & (m < M)
        m_c = jnp.clip(m, 0, M - 1)
        # stage 0 ingests fresh microbatches; later stages consume the
        # rotated activation from their predecessor
        x_in = jnp.where(sidx == 0, xs[m_c], buf)
        y, state = stage_fn(params, state, x_in, active)
        # the LAST stage's result is final: accumulate into ys (masked)
        is_out = active & (sidx == S - 1)
        ys = ys.at[m_c].set(jnp.where(is_out, y, ys[m_c]))
        # rotate activations one stage forward (ring hop)
        perm = [(j, (j + 1) % S) for j in range(S)]
        buf = lax.ppermute(y, axis, perm)
        return buf, ys, state

    buf0 = lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
    ys0 = lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
    _, ys, state = lax.fori_loop(0, S + M - 1, tick, (buf0, ys0, state))
    # outputs live on the last stage only; sum-reduce replicates them
    ys = lax.psum(ys, axis)
    state = jax.tree_util.tree_map(lambda a: a[None], state)
    return ys, state


def pipeline_apply(
    stage_fn: StageFn,
    params,            # pytree, leaves [S, ...] (stage-stacked)
    state,             # pytree, leaves [S, ...] (per-stage state; may be {})
    xs: jax.Array,     # [M, ...] microbatches
    mesh: Mesh,
    axis: str = "pp",
) -> Tuple[jax.Array, object]:
    """Run every microbatch through all S stages; returns (ys [M, ...],
    updated per-stage state, still stage-stacked/sharded)."""
    S = mesh.shape[axis]
    for path, leaf in jax.tree_util.tree_leaves_with_path(params) + \
            jax.tree_util.tree_leaves_with_path(state):
        if leaf.shape[:1] != (S,):
            # P(axis) would hand each device a multi-stage slice and the
            # body would silently apply only the first — be loud instead
            raise ValueError(
                f"stage-stacked leaf {jax.tree_util.keystr(path)} has "
                f"leading dim {leaf.shape[0] if leaf.ndim else None}, "
                f"expected the pp axis size {S}"
            )
    n_micro = xs.shape[0]
    stage_spec = jax.tree_util.tree_map(lambda _: P(axis), params)
    state_spec = jax.tree_util.tree_map(lambda _: P(axis), state)
    fn = shard_map(
        partial(_pipeline_shard, stage_fn=stage_fn, axis=axis,
                n_micro=n_micro),
        mesh=mesh,
        in_specs=(stage_spec, state_spec, P()),
        out_specs=(P(), state_spec),
    )
    return fn(params, state, xs)
