"""Pallas TPU kernel for the decode step of a cache member that is a
STATE addressed by lane (ops/lane_state.py): one shell, and one body a
recurrence.

The member [layers of the kind, lanes, heads, dk, dv] float32 goes in
WHOLE and is updated in place (`input_output_aliases`); the layer is a
scalar the block's index map reads, so there is no `member[pli]` slice,
no `.at[pli].set` and no `where` over the member.  What the jnp step
moves a layer and step is every lane's state twice in and once out
(`ssd_step` / `kda_step` over `member[pli]`, the idle lanes kept by a
select); what this moves is the BUSY lanes' state once in and once out,
the count of benchmark/lib/ssm_floors.py and recurrent_floors.py.

The shell (`_lane_state_call`) owns everything that is not the
recurrence:

  * the busy lanes, compacted once a burst (`lanes_plan` of
    ops/lane_state.py: busy lanes first, the tail repeating the last
    busy one, and their number), are scalar-prefetched; the grid is
    (lanes, heads // head_block) and step (i, j) works on lane
    live_lanes[i], head block j;
  * a step with i >= n_live maps every operand to the block ALREADY
    resident (the last busy lane's last head block), so nothing is
    copied for it, and its body is skipped: an idle lane's state is
    never read and never written;
  * n_live == 0 (a warm-up burst; a burst whose lanes all finished)
    would leave the one resident output block unwritten and the pipeline
    would still copy it back at the end: the first step then hands the
    input block through, so every lane is bit for bit what it was;
  * head blocks so that the state block in and out, each double-
    buffered, stays inside `_BLOCK_BUDGET` of VMEM.

What a lane's operands look like is the tile's business.  A factor that
differs by ROW of the [dk, dv] tile has to meet it as a column [dk, 1].
An array [.., dk, 1] is not the way to hand one over: the TPU's tiled
layout pads the minor dimension to 128 lanes, so XLA writes 128 x the
column and the kernel's DMA reads it (f32[64,64,64,1] lies as 134 MB;
compiled for a described v5e, PR 41).  The heads go on the minor axis
instead: columns [lanes, head blocks, dk, head_block], transposed by XLA
outside (KB a lane), and head h's column is the static lane slice
[:, h:h+1], which Mosaic broadcasts over the tile.  A factor that
differs by COLUMN of the tile is a row [1, dv] of a [heads, dv] block,
one a head and step, and a factor a head is a float32 scalar in SMEM.
The Mamba-2 read comes out as columns the same way and XLA transposes
it back.

The bodies follow the jnp steps' arithmetic operation for operation
(float32 throughout, the same products in the same order; only the
order inside a reduction over 128 values is Mosaic's), because both
cells' `correct` on random expert weights is a trajectory, not a
tolerance (PERF.md section 7t): tests/test_lane_state_kernel.py holds
them to `ssd_step` / `kda_step` under the interpreter,
tests/test_tpu_compile.py compiles them inside both families' decode
bursts for a described v5e, benchmarks/bench_state_step.py times them
against the jnp steps on the chip.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_attention import F32

# state block in + out, double-buffered: 4 x head_block x dk x dv x 4 B
_BLOCK_BUDGET = 8 << 20


class LanePlan(NamedTuple):
    """A burst's busy lanes (ops/lane_state.lanes_plan); the jnp step
    needs `valid` alone and gets the other two as None."""
    valid: jax.Array        # [lanes] bool
    live_lanes: jax.Array   # [lanes] int32: busy lanes first, then the
                            #   last busy one repeated (0 where none is)
    n_live: jax.Array       # [1] int32


def head_block_for(heads: int, unit: int, dk: int, dv: int) -> int:
    """The most heads a block (a divisor of `heads`, a multiple of
    `unit`: the heads that share a group's operands) whose state tiles,
    in and out and double-buffered, fit `_BLOCK_BUDGET`."""
    fits = [hb for hb in range(unit, heads + 1, unit)
            if heads % hb == 0 and 16 * hb * dk * dv <= _BLOCK_BUDGET]
    return max(fits) if fits else unit


class _Blocked(NamedTuple):
    """A VMEM operand (or the read): the array, its block, and the
    block's index from the step's (lane, head block)."""
    array: jax.Array | jax.ShapeDtypeStruct
    block: Tuple
    index: Callable


def _lane_state_call(
    body: Callable,
    member: jax.Array,           # [L, lanes, H, dk, dv] float32, whole
    layer,                       # int scalar, traced
    plan: LanePlan,
    scalars: Sequence[jax.Array],    # [lanes, H] float32 each -> SMEM
    blocked: Sequence[_Blocked],     # the lane's vector operands
    read: _Blocked,                  # what the body reads of the state
    *,
    head_block: int,
    interpret: bool,
):
    """body(lane, j, scalar refs, operand refs, state in, read out,
    state out) for every (busy lane, head block); -> (read, member)."""
    _, lanes, H, dk, dv = member.shape
    hb, nj = head_block, H // head_block
    if nj * hb != H or member.dtype != F32:
        raise ValueError(f"{member.dtype} member of {H} heads in blocks of "
                         f"{hb}: the kernel takes a float32 member in whole "
                         "head blocks (resolve_state_impl)")
    n_pre, n_sc, n_in = 3, len(scalars), len(blocked)

    def spec(block, index):
        def index_map(i, j, live_ref, n_ref, layer_ref):
            # past the busy lanes: the block already resident
            jb = jnp.where(i < n_ref[0], j, nj - 1)
            return index(live_ref[i], jb, layer_ref[0])
        return pl.BlockSpec(block, index_map)

    state_spec = spec((None, None, hb, dk, dv),
                      lambda lane, jb, layer: (layer, lane, jb, 0, 0))

    def kernel(live_ref, n_ref, layer_ref, *refs):
        del layer_ref
        sc, ins = refs[:n_sc], refs[n_sc:n_sc + n_in]
        s_in, r_out, s_out = refs[n_sc + n_in:]
        i, j = pl.program_id(0), pl.program_id(1)
        n = n_ref[0]

        @pl.when(i < n)
        def _():
            body(live_ref[i], j, sc, ins, s_in, r_out, s_out)

        @pl.when((n == 0) & (i == 0) & (j == 0))
        def _():
            s_out[...] = s_in[...]

    read_out, member = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=(lanes, nj),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * n_sc
            + [spec(b.block, b.index) for b in blocked] + [state_spec],
            out_specs=[spec(read.block, read.index), state_spec],
        ),
        out_shape=[read.array,
                   jax.ShapeDtypeStruct(member.shape, member.dtype)],
        # (operand indices count the scalar-prefetch arguments)
        input_output_aliases={n_pre + n_sc + n_in: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state block in and out, double-buffered, and room for
            # the operands' planes
            vmem_limit_bytes=16 * hb * dk * dv + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=6 * lanes * H * dk * dv,
            bytes_accessed=2 * lanes * H * dk * dv * 4,
            transcendentals=0),
        interpret=interpret,
    )(plan.live_lanes, plan.n_live, jnp.asarray(layer, jnp.int32).reshape(1),
      *[s.astype(F32) for s in scalars], *[b.array for b in blocked],
      member)
    return read_out, member


def _by_head_block(x: jax.Array, nj: int) -> jax.Array:
    """[lanes, H, w] -> [lanes, nj, w, hb]: each head's [w] as a COLUMN
    of its head block's [w, hb] plane (heads on the minor axis)."""
    lanes, H, w = x.shape
    return jnp.swapaxes(x.reshape(lanes, nj, H // nj, w), 2, 3)


# ---------------------------------------------------------------------------
# Mamba-2 (ops/ssm.py ssd_step)
# ---------------------------------------------------------------------------


def _ssd_body(lane, j, scalars, ins, s_in, r_out, s_out, *, per_group: int):
    """new = decay S + feed (x) B; read = S . C, both from the one
    resident tile.  s_in [hb, P, N]; feed [P, hb] columns; B and C
    [2, groups of the block, N] rows; decay a head in SMEM."""
    (decay_ref,), (feed_ref, bc_ref) = scalars, ins
    hb = s_in.shape[0]
    for h in range(hb):
        g = h // per_group
        s = s_in[h]                                        # [P, N]
        feed = feed_ref[:, h:h + 1]                        # [P, 1]
        s_out[h] = decay_ref[lane, j * hb + h] * s \
            + feed * bc_ref[0, g:g + 1, :]
        r_out[:, h:h + 1] = jnp.sum(s * bc_ref[1, g:g + 1, :], axis=-1,
                                    keepdims=True)


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("head_block", "interpret"))
@jax.named_scope("dyn.ssm_scan")
def ssd_lanes_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d_skip: jax.Array, member: jax.Array,
                   layer, plan: LanePlan, *, head_block: int | None = None,
                   interpret: bool = False):
    """`ssd_step` for the busy lanes of `member[layer]`, in place.  x
    [lanes, H, P], dt [lanes, H] (after softplus), a [H], b, c [lanes,
    G, N], d_skip [H], member [L, lanes, H, P, N] float32 -> (y [lanes,
    H, P] float32, 0 for an idle lane; member)."""
    Bn, H, P = x.shape
    G, N = b.shape[1:]
    R = H // G
    hb = head_block or head_block_for(H, R, P, N)
    if hb % R:
        raise ValueError(f"a head block of {hb} is not whole groups of {R}")
    nj = H // hb
    x, b, c = x.astype(F32), b.astype(F32), c.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))                       # [B, H]
    feed = dt[..., None] * x                                  # [B, H, P]
    # a head block's groups: B beside C, [lanes, nj, 2, hb // R, N]
    bc = jnp.stack([b, c], axis=1).reshape(Bn, 2, nj, hb // R, N)
    cols = jax.ShapeDtypeStruct((Bn, nj, P, hb), F32)
    at = lambda lane, jb, layer: (lane, jb, 0, 0)
    r, member = _lane_state_call(
        functools.partial(_ssd_body, per_group=R), member, layer, plan,
        [decay],
        [_Blocked(_by_head_block(feed, nj), (None, None, P, hb), at),
         _Blocked(jnp.swapaxes(bc, 1, 2), (None, None, 2, hb // R, N),
                  lambda lane, jb, layer: (lane, jb, 0, 0, 0))],
        _Blocked(cols, (None, None, P, hb), at),
        head_block=hb, interpret=interpret)
    # S . C [lanes, nj, P, hb] -> [lanes, G, R, P]; then ssd_step's read
    sc = jnp.swapaxes(r, 2, 3).reshape(Bn, G, R, P)
    y = decay.reshape(Bn, G, R, 1) * sc \
        + feed.reshape(Bn, G, R, P) \
        * jnp.sum(b * c, axis=-1)[:, :, None, None]
    y = y.reshape(Bn, H, P) + d_skip.astype(F32)[:, None] * x
    return jnp.where(plan.valid[:, None, None], y, 0.0), member


# ---------------------------------------------------------------------------
# the delta rule (ops/delta_attention.py kda_step)
# ---------------------------------------------------------------------------


def _kda_body(lane, j, scalars, ins, s_in, r_out, s_out, *, scale: float):
    """S' = a S; r_k = S'^T k and r_q = S'^T q from the one resident
    tile; u = beta (v - r_k); new = S' + k (x) u; read = (r_q + (k . q)
    u) scale.  s_in [hb, dk, dv]; a, k and q side by side as columns
    [dk, 3 hb] (one plane: the minor axis is padded to 128 lanes in HBM
    whatever it holds); v [hb, dv] rows; beta and k . q a head in SMEM."""
    (beta_ref, kq_ref), (cols_ref, v_ref) = scalars, ins
    hb = s_in.shape[0]
    col = lambda i, h: cols_ref[:, i * hb + h:i * hb + h + 1]  # [dk, 1]
    for h in range(hb):
        head = j * hb + h
        k = col(1, h)
        sp = col(0, h) * s_in[h]                           # S'
        r_k = jnp.sum(sp * k, axis=0, keepdims=True)       # [1, dv]
        r_q = jnp.sum(sp * col(2, h), axis=0, keepdims=True)
        u = beta_ref[lane, head] * (v_ref[h:h + 1, :] - r_k)
        r_out[h:h + 1, :] = (r_q + kq_ref[lane, head] * u) * scale
        s_out[h] = sp + k * u


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("scale", "head_block", "interpret"))
@jax.named_scope("dyn.attn_delta")
def kda_lanes_step(q: jax.Array, k: jax.Array, v: jax.Array,
                   log_a: jax.Array, beta: jax.Array, member: jax.Array,
                   layer, plan: LanePlan, *, scale: float,
                   head_block: int | None = None, interpret: bool = False):
    """`kda_step` for the busy lanes of `member[layer]`, in place.  q,
    k, log_a [lanes, H, dk], v [lanes, H, dv], beta [lanes, H], member
    [L, lanes, H, dk, dv] float32 -> (o [lanes, H, dv] float32, 0 for an
    idle lane; member)."""
    Bn, H, dk = k.shape
    dv = v.shape[-1]
    hb = head_block or head_block_for(H, 1, dk, dv)
    nj = H // hb
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    cols = jnp.concatenate([_by_head_block(x, nj)
                            for x in (jnp.exp(log_a), k, q)], axis=-1)
    rows = lambda lane, jb, layer: (lane, jb, 0)
    o, member = _lane_state_call(
        functools.partial(_kda_body, scale=scale), member, layer, plan,
        [beta, jnp.sum(k * q, -1)],
        [_Blocked(cols, (None, None, dk, 3 * hb),
                  lambda lane, jb, layer: (lane, jb, 0, 0)),
         _Blocked(v, (None, hb, dv), rows)],
        _Blocked(jax.ShapeDtypeStruct((Bn, H, dv), F32), (None, hb, dv),
                 rows),
        head_block=hb, interpret=interpret)
    return jnp.where(plan.valid[:, None, None], o, 0.0), member
