"""Pallas TPU kernel for the dropless expert dispatch of a decode-sized
program (models/moe.py `moe_dispatch_visited`): one call a layer that
moves an expert's matrices from HBM only if a valid row of this step
picked it.

The dense form's einsum "td,edf->etf" reads every HELD expert's stacks
whatever the step's lanes picked; at a few rows a step the weights' read
is the whole cost and most of it is experts nobody asked for.  Here the
VISITED experts' local ids (ascending, the tail repeating the last one)
and their number are scalar-prefetched; the grid is (visited slot, tile
of the expert's hidden width f) and step (s, j) works on the gate / up
tile [d, tf] and the down tile [tf, d] of expert ids[s]:

  * a slot past the count maps every stack to the block ALREADY resident
    (the last visited expert's last tile), so nothing is copied for it,
    and its body is skipped; with no expert visited (a warm-up burst)
    one tile of expert 0 is fetched and nothing is computed;
  * x [T, d] and the combine weights [held, T, 1] (a column an expert,
    0 for a row that did not pick it or that `valid` masks) lie whole in
    VMEM; the float32 [T, d] accumulator is written out once;
  * a stack the TPU keeps with d on the minor axis (`stacks_lie_flipped`:
    an f that is not whole lanes) is taken transposed, tiles [tf, d].

Every row goes through every visited expert and the combine weight
decides: the dense form's mathematics over fewer experts, a row's
result whatever the other rows hold.  The rounding points are the dense
form's too (products of `dtype` operands accumulated in float32; gate,
up, the hidden and each expert's output rounded to `dtype`; the combine
weight in `dtype`, the experts summed in float32 in ascending order),
because `correct` on random expert weights is a trajectory, not a
tolerance (PERF.md section 7t).

tests/test_moe_visited.py holds it to `moe_dispatch_dense` and the
float32 references under the interpreter, tests/test_tpu_compile.py
compiles it inside six families' decode bursts for a described v5e,
benchmarks/bench_moe_decode.py times it against the dense and the
grouped form on the chip.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_attention import F32
from .lane_state import lanes_plan

# one step's weight tiles (gate, up, down), a buffer; the pipeline holds
# two.  Timed on the chip (PR 44, models/moe.py moe_dispatch_form):
# the wider tile is the faster one (a [d, 128] tile is runs of 4 KB:
# Moonlight 0.231 ms at 128 against 0.158 whole, 6 experts visited)
_TILE_BUDGET = 26 << 20


def stacks_lie_flipped(d: int, f: int) -> bool:
    """Whether the TPU keeps a [held, d, f] stack with d, not f, on the
    minor axis: where f is not whole lanes and d is, the compiler lays
    the parameter out that way round to save the padding (Nemotron's
    f = 1856: `bf16[16,2688,1856]{1,2,0}`; compiled for a described v5e
    and read off the chip, PR 44).  A row-major operand of such a stack
    would be a copy of all of it a call; its transpose is free."""
    return f % 128 != 0 and d % 128 == 0


def _tile_unit(d: int, f: int, itemsize: int) -> int:
    """What a hidden tile's width is whole multiples of: lanes (128), or
    sublane tiles (16 of a 2-byte dtype) where the stacks lie flipped
    and f is a tile's second-minor axis."""
    return 32 // itemsize if stacks_lie_flipped(d, f) else 128


def f_tile(d: int, f: int, itemsize: int, matrices: int) -> int:
    """The width of the hidden tile: the widest divisor of f in whole
    units (`_tile_unit`) whose `matrices` tiles [d, tf] fit
    `_TILE_BUDGET`; the narrowest such divisor where none fits; f whole
    where it has none (a block may be ragged only if it is the whole
    axis)."""
    unit = _tile_unit(d, f, itemsize)
    fits = [tf for tf in range(unit, f + 1, unit) if f % tf == 0]
    if not fits:
        return f
    under = [tf for tf in fits
             if matrices * d * tf * itemsize <= _TILE_BUDGET]
    return max(under) if under else min(fits)


def visited_plan(seen: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """seen [held] bool -> (ids [held] int32: the visited experts
    ascending, then the last one repeated (0 where none is); their
    number [1] int32): the compaction a burst's busy lanes get for the
    state kernel's scalar prefetch (ops/lane_state.py `lanes_plan`)."""
    plan = lanes_plan(seen, "pallas")
    return plan.live_lanes, plan.n_live


_IN = ("moe_w_gate", "moe_w_up")      # [held, d, f] stacks a layer may hold


def _kernel(ids_ref, n_ref, x_ref, w_ref, *refs, names: Tuple[str, ...],
            hidden: Callable, nj: int, flipped: bool):
    ins = dict(zip(names, refs))
    wd_ref, o_ref, acc_ref, part_ref = refs[len(names):]
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < n_ref[0])
    def _():
        x = x_ref[...]
        dtype = x.dtype
        # x [T, d] with a tile [d, tf], or [tf, d] where it lies flipped
        contract = (((1,), (1 if flipped else 0,)), ((), ()))
        h = hidden(ins, lambda w_ref: jax.lax.dot_general(
            x, w_ref[...], contract, preferred_element_type=F32
        ).astype(dtype).astype(F32))                          # [T, tf]
        part = jnp.dot(h.astype(dtype), wd_ref[...],
                       preferred_element_type=F32)            # [T, d]

        def combine(eout):
            acc_ref[...] += eout.astype(dtype).astype(F32) * w_ref[ids_ref[s]]

        if nj == 1:
            combine(part)
        else:
            @pl.when(j == 0)
            def _():
                part_ref[...] = part

            @pl.when((j > 0) & (j < nj - 1))
            def _():
                part_ref[...] += part

            @pl.when(j == nj - 1)
            def _():
                combine(part_ref[...] + part)

    @pl.when((s == pl.num_programs(0) - 1) & (j == nj - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@jax.named_scope("dyn.moe_dispatch")
def moe_visited(layer, hidden: Callable, x: jax.Array, wmat: jax.Array,
                ids: jax.Array, n: jax.Array, *,
                tile: Optional[int] = None,
                interpret: bool = False) -> jax.Array:
    """x [T, d]; wmat [T, held] combine weights in x's dtype; (ids, n) of
    `visited_plan`; the layer's stacks moe_w_gate (absent for a plain
    expert) / moe_w_up [held, d, f] and moe_w_down [held, f, d];
    hidden(refs by name, mm) an expert's hidden activations from its
    tiles (models/moe.py `_expert_hidden`) -> [T, d] in x's dtype."""
    T, d = x.shape
    names = tuple(k for k in _IN if k in layer)
    stacks = [layer[k] for k in names] + [layer["moe_w_down"]]
    held, _, f = layer["moe_w_up"].shape
    itemsize = stacks[0].dtype.itemsize
    tf = tile or f_tile(d, f, itemsize, len(stacks))
    nj = f // tf
    flipped = stacks_lie_flipped(d, f)
    if nj * tf != f or (nj > 1 and tf % _tile_unit(d, f, itemsize)):
        raise ValueError(f"a hidden width of {f} in tiles of {tf}")
    # whole sublane tiles of rows (16 of a 2-byte dtype); the padding
    # rows are zeros with a combine weight of 0
    rows = -T % (32 // x.dtype.itemsize)
    Tp = T + rows
    xp = jnp.pad(x, ((0, rows), (0, 0)))
    # a column [T, 1] an expert (the minor axis pads to 128 lanes: KB)
    wcol = jnp.pad(wmat.astype(F32).T, ((0, 0), (0, rows)))[..., None]

    def at(index):
        def index_map(s, j, ids_ref, n_ref):
            # past the visited experts: the block already resident
            return index(ids_ref[s], jnp.where(s < n_ref[0], j, nj - 1))
        return index_map

    in_tile = pl.BlockSpec((None, d, tf), at(lambda e, j: (e, 0, j)))
    down_tile = pl.BlockSpec((None, tf, d), at(lambda e, j: (e, j, 0)))
    if flipped:
        stacks = [jnp.swapaxes(w, 1, 2) for w in stacks[:-1]] + stacks[-1:]
        in_tile = down_tile
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    tile_bytes = len(stacks) * d * tf * itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, names=names, hidden=hidden, nj=nj,
                          flipped=flipped),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, nj),
            in_specs=[whole, whole] + [in_tile] * len(names) + [down_tile],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((Tp, d), F32),
                            pltpu.VMEM((Tp, d) if nj > 1 else (8, 128),
                                       F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two buffers a tile, and room for x, the columns, the two
            # accumulators and the body's temporaries
            vmem_limit_bytes=2 * tile_bytes + 8 * Tp * d * 4 + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * held * len(stacks) * d * f,
            bytes_accessed=held * len(stacks) * d * f * itemsize,
            transcendentals=Tp * held * f),
        interpret=interpret,
    )(ids, n, xp, wcol, *stacks)
    return out[:T]
