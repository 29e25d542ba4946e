#!/usr/bin/env python3
"""Time one expert layer's dropless dispatch alone on the chip
(models/moe.py `moe_dispatch_dense` / `moe_dispatch_grouped` /
`moe_dispatch_visited`), at each expert cell's widths and decode rows:

    moonlight  16 rows, 64 of 64 experts of 2048 x 1408, top 6
    mimo       32 rows, 16 of 256 of 4096 x 2048, top 8
    keye        8 rows, 16 of 128 of 2048 x 768, top 8
    ling       64 rows, 16 of 512 of 2560 x 768, top 8
    nemotron   64 rows, 16 of 128 of 2688 x 1856, top 6, plain ReLU^2
    command     8 rows, 16 of 128 of 4096 x 4096, top 8

over a visited share of 1 expert, 25 %, 50 % and 100 % of the held ones
(the picks drawn so that exactly that many are visited; where every
routed expert is held a row's k picks visit at least k), and, with
`--rows`, over more rows with picks drawn evenly over the routed
experts (where the rule's bound between the forms lies).

    python3 benchmarks/bench_moe_decode.py [--reps 20] [--tiles 0,128]
        [--shapes moonlight,mimo] [--rows 64,128,256]

Prints one JSON line a row: milliseconds a layer = the host's clock
around block_until_ready of ONE program that makes `reps` dependent
calls, over `reps` (median of 5 after 2 warm runs), LESS `loop_ms`, what
the same program takes a turn with no dispatch in it; the visited
experts' bytes and each form's share of 819 GB/s on them; and how far
the visited form's result lies from the dense form's on the chip.  Tile
0 is the kernel's own choice (`f_tile`).  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: rows, d, f, held, routed, k, gated
SHAPES = {
    "moonlight": (16, 2048, 1408, 64, 64, 6, True),
    "mimo": (32, 4096, 2048, 16, 256, 8, True),
    "keye": (8, 2048, 768, 16, 128, 8, True),
    "ling": (64, 2560, 768, 16, 512, 8, True),
    "nemotron": (64, 2688, 1856, 16, 128, 6, False),
    "command": (8, 4096, 4096, 16, 128, 8, True),
}


@dataclass(frozen=True)
class Cfg:
    """What the dispatch reads of a family's configuration (hashable:
    the visited form is jitted on it)."""
    n_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    dtype: Any
    expert_gated: bool
    expert_act: Callable


def picks_visiting(rng, T, k, held, routed, visited):
    """top_e [T, k]: distinct experts a row, exactly `visited` of the
    held ones (local ids 0..held-1) picked over the rows, the rest of a
    row's picks on experts held elsewhere."""
    import numpy as np

    chosen = rng.permutation(held)[:visited]
    here = min(k, visited)
    top_e = np.empty((T, k), np.int32)
    for t in range(T):
        top_e[t, :here] = chosen[(t * here + np.arange(here)) % visited]
        top_e[t, here:] = held + rng.permutation(routed - held)[:k - here]
    assert len(np.intersect1d(top_e, np.arange(held))) == visited
    return top_e


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shares", default="0,0.25,0.5,1.0")
    ap.add_argument("--tiles", default="0")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--rows", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.models import moe
    from dynamo_tpu.ops import pallas_moe_visited as pmv
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    hbm = device_peaks(ident["kind"])["hbm_bytes_per_s"]
    bf16 = jnp.bfloat16

    def timed(step, layer, x):
        """ms a call of step(layer, x) -> [T, d].  (The stacks are the
        program's ARGUMENTS: closed over they would be 2 GB of constants
        in every executable.)"""

        @jax.jit
        def program(layer, x):
            return jax.lax.fori_loop(
                0, args.reps,
                lambda _, x: (x + step(layer, x) * 1e-3).astype(x.dtype), x)

        ts = []
        for i in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(program(layer, x))
            if i >= 2:
                ts.append((time.perf_counter() - t0) * 1e3 / args.reps)
        return statistics.median(ts)

    rng = np.random.default_rng(0)
    for name in args.shapes.split(","):
        T0, d, f, held, routed, k, gated = SHAPES[name]
        cfg = Cfg(routed, k, (0, held), bf16, gated,
                  jax.nn.silu if gated else moe.relu2)
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        layer = {"moe_w_up": jax.random.normal(ks[1], (held, d, f), bf16)
                 * d ** -0.5,
                 "moe_w_down": jax.random.normal(ks[2], (held, f, d), bf16)
                 * f ** -0.5}
        if gated:
            layer["moe_w_gate"] = jax.random.normal(
                ks[0], (held, d, f), bf16) * d ** -0.5
        print(json.dumps({"shape": name, "stack_layouts": {
            n: str(getattr(w, "format", None))
            for n, w in layer.items()}}), flush=True)
        matrices = len(layer)
        own = pmv.f_tile(d, f, 2, matrices)
        tiles = [t for t in dict.fromkeys(
            own if t == 0 else t for t in map(int, args.tiles.split(",")))
            if f % t == 0]
        cases = [(T0, max(1, round(float(s) * held)))
                 for s in args.shares.split(",") if s]
        cases += [(int(T), None) for T in args.rows.split(",") if T]
        for T, visited in cases:
            x = jax.random.normal(ks[3], (T, d), bf16)
            if visited is None:         # picks drawn evenly over the routed
                top_e = np.stack([rng.permutation(routed)[:k]
                                  for _ in range(T)]).astype(np.int32)
                visited = len(np.intersect1d(top_e, np.arange(held)))
            else:
                if routed == held:
                    visited = max(visited, k)
                top_e = picks_visiting(rng, T, k, held, routed, visited)
            top_w = rng.random((T, k)).astype(np.float32) + 0.1
            top_w = jnp.asarray(top_w / top_w.sum(-1, keepdims=True))
            top_e = jnp.asarray(top_e)
            loop_ms = timed(lambda layer, x: x, layer, x)

            def form(fn, **kw):
                return lambda layer, x: fn(layer, cfg, x, top_w, top_e, **kw)

            def net(step):
                return round(timed(step, layer, x) - loop_ms, 4)

            row = {"shape": name, "rows": T, "held": held,
                   "visited": visited, "kernel_tile": own,
                   "loop_ms": round(loop_ms, 4),
                   "dense_ms": net(form(moe.moe_dispatch_dense)),
                   "grouped_ms": net(form(moe.moe_dispatch_grouped))}
            for tf in tiles:
                row[f"visited_t{tf}_ms"] = net(
                    form(moe.moe_dispatch_visited, tile=tf))
            a = jax.jit(form(moe.moe_dispatch_dense))(layer, x).astype(
                jnp.float32)
            b = jax.jit(form(moe.moe_dispatch_visited))(layer, x).astype(
                jnp.float32)
            row["out_max"] = float(jnp.abs(a).max())
            row["visited_max_err"] = float(jnp.abs(a - b).max())
            moved = visited * matrices * d * f * 2
            row["visited_mb"] = round(moved / 1e6, 2)
            for key in ("dense", "grouped", f"visited_t{own}"):
                row[f"{key}_hbm_share"] = round(
                    100 * moved / hbm / (row[f"{key}_ms"] / 1e3), 1)
            print(json.dumps(row), flush=True)
    print(json.dumps({"device": ident, "reps": args.reps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
