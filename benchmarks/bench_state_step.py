#!/usr/bin/env python3
"""Time the decode step of a lane-addressed STATE member alone on the
chip (one layer-step: ops/lane_state.py `lanes_step`), the jnp step over
every lane of `member[pli]` against ops/pallas_lane_state.py's kernel
over the busy lanes, at the two cells' shapes:

    nemotron  Mamba-2 (`ssd_step`): 64 lanes x 64 heads x 64 x 128 float32
    ling      delta rule (`kda_step`): 64 lanes x 32 heads x 128 x 128

over 10 / 25 / 50 / 75 / 100 % busy lanes (scattered over the slots) and
the kernel's head block.

    python3 benchmarks/bench_state_step.py [--reps 20] [--head-blocks 8,16,0]

Prints one JSON line a row: milliseconds a layer-step = the host's clock
around block_until_ready of ONE program that makes `reps` dependent
steps on a DONATED member of two layers, over `reps` (median of 5 after
2 warm runs: no dispatch in the number), LESS `loop_ms`, what the same
program takes a step with no step in it (the `fori_loop`'s own turn and
the read's add: 0.045 ms on a v5e, as much as the kernel's whole call at
a few busy lanes); the bytes the floor counts (the busy lanes' state
read once and written once) and each step's share of 819 GB/s on them;
and how far the kernel's new state and read lie from the jnp step's on
the chip (one step from the same member, busy lanes; the idle lanes must
be bit for bit).  Head block 0 is the kernel's own choice
(`head_block_for`).  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES, LAYERS, PLI = 64, 2, 1
SHAPES = {"nemotron": ("ssd", 64, 64, 128), "ling": ("kda", 32, 128, 128)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shares", default="0.1,0.25,0.5,0.75,1.0")
    ap.add_argument("--head-blocks", default="8,16,0")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.ops.delta_attention import kda_step, l2norm
    from dynamo_tpu.ops.lane_state import lanes_plan, lanes_step
    from dynamo_tpu.ops.pallas_lane_state import (
        head_block_for,
        kda_lanes_step,
        ssd_lanes_step,
    )
    from dynamo_tpu.ops.ssm import ssd_step
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    hbm = device_peaks(ident["kind"])["hbm_bytes_per_s"]
    f32 = jnp.float32

    def operands(rule, H, dk, dv, key):
        """-> (jnp step, kernel step) over one token a lane's operands,
        drawn as the families make them."""
        ks = jax.random.split(key, 6)
        if rule == "ssd":
            G = 8
            ops = (jax.random.normal(ks[0], (LANES, H, dk), f32),
                   jax.nn.softplus(jax.random.normal(ks[1], (LANES, H))
                                   - 2.0),
                   -jnp.exp(jax.random.normal(ks[2], (H,))),
                   jax.random.normal(ks[3], (LANES, G, dv), f32),
                   jax.random.normal(ks[4], (LANES, G, dv), f32),
                   jnp.ones((H,), f32))
            return partial(ssd_step, *ops), partial(ssd_lanes_step, *ops)
        ops = (l2norm(jax.random.normal(ks[0], (LANES, H, dk), f32)),
               l2norm(jax.random.normal(ks[1], (LANES, H, dk), f32)),
               jax.random.normal(ks[2], (LANES, H, dv), f32),
               -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3],
                                                       (LANES, H, dk))),
               jax.nn.sigmoid(jax.random.normal(ks[4], (LANES, H))))
        scale = dk ** -0.5
        return (partial(kda_step, *ops, scale=scale),
                partial(kda_lanes_step, *ops, scale=scale))

    def stepper(jnp_step, kernel_step, impl, valid):
        plan = lanes_plan(valid, impl)
        return lambda member: lanes_step(member, PLI, plan, jnp_step,
                                         kernel_step, impl)

    def timed(step, member_shape, read_shape):
        """ms a call of step(member) -> (read, member)."""

        @partial(jax.jit, donate_argnums=0)
        def program(member):
            def body(_, carry):
                member, acc = carry
                read, member = step(member)
                return member, acc + read
            member, acc = jax.lax.fori_loop(
                0, args.reps, body, (member, jnp.zeros(read_shape, f32)))
            return member, acc

        member = jnp.zeros(member_shape, f32)
        ts = []
        for i in range(7):
            t0 = time.perf_counter()
            member, acc = program(member)
            jax.block_until_ready((member, acc))
            if i >= 2:
                ts.append((time.perf_counter() - t0) * 1e3 / args.reps)
        del member
        return statistics.median(ts)

    rng = np.random.default_rng(0)
    for name in args.shapes.split(","):
        rule, H, dk, dv = SHAPES[name]
        shapes = ((LAYERS, LANES, H, dk, dv),
                  (LANES, H, dk if rule == "ssd" else dv))
        jnp_step, kernel_step = operands(rule, H, dk, dv,
                                         jax.random.PRNGKey(1))
        unit = 8 if rule == "ssd" else 1
        own = head_block_for(H, unit, dk, dv)
        blocks = [own if hb == 0 else hb
                  for hb in map(int, args.head_blocks.split(","))]
        zero_read = jnp.zeros(shapes[1], f32)
        loop_ms = timed(lambda member: (zero_read, member), *shapes)
        net = lambda step: round(timed(step, *shapes) - loop_ms, 4)
        for share in (float(s) for s in args.shares.split(",")):
            busy = max(1, round(share * LANES))
            mask = np.zeros(LANES, bool)
            mask[rng.permutation(LANES)[:busy]] = True
            valid = jnp.asarray(mask)
            row = {"shape": name, "rule": rule, "lanes": LANES,
                   "busy": busy, "kernel_head_block": own,
                   "loop_ms": round(loop_ms, 4),
                   "jnp_ms": net(stepper(jnp_step, kernel_step, "jnp",
                                         valid))}
            for hb in dict.fromkeys(blocks):
                row[f"kernel_hb{hb}_ms"] = net(
                    stepper(jnp_step, partial(kernel_step, head_block=hb),
                            "pallas", valid))
            # the two on the same dirty member, one step
            dirty = jax.random.normal(jax.random.PRNGKey(2), shapes[0],
                                      f32)
            ra, ma = jax.jit(stepper(jnp_step, kernel_step, "jnp",
                                     valid))(dirty)
            rb, mb = jax.jit(stepper(jnp_step, kernel_step, "pallas",
                                     valid))(dirty)
            row["new_max_err"] = float(jnp.abs(ma - mb)[PLI][mask].max())
            row["read_max_err"] = float(jnp.abs(ra - rb)[mask].max())
            row["read_max"] = float(jnp.abs(ra)[mask].max())
            row["idle_bit_for_bit"] = bool(
                jnp.array_equal(mb[PLI][~mask], dirty[PLI][~mask])
                and jnp.array_equal(mb[0], dirty[0]))
            del dirty, ma, mb
            moved = busy * H * dk * dv * 4 * 2
            row["busy_mb"] = round(moved / 1e6, 2)
            row["kernel_hbm_share"] = round(
                100 * moved / hbm / (row[f"kernel_hb{own}_ms"] / 1e3), 1)
            row["jnp_hbm_share"] = round(
                100 * moved / hbm / (row["jnp_ms"] / 1e3), 1)
            print(json.dumps(row), flush=True)
    print(json.dumps({"device": ident, "reps": args.reps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
