#!/usr/bin/env python3
"""Time ONE PASS of the family that generates by diffusion over blocks
(models/sdar.py) alone on the chip, at the reasoning cell's widths
(SDAR-30B-A3B, 6 layers, all 128 experts, block of 4): the paged decode
kernel reads the cache with a lane's 4 queries as 4 x 8 query heads a
KV head against one kv length; at 32 and 64 lanes x 4 rows, every lane
busy, contexts uniform in [512, 3584) (the cell's prompt + output
range).

    python3 benchmarks/bench_diffusion_pass.py [--lanes 32,64] [--passes 8]

Prints one JSON line a row: milliseconds a pass = the host's clock
around block_until_ready of ONE program of `passes` fused passes (the
engine's own `denoise_multi`, greedy, cache donated) over `passes`
(median of 5 after 2 warm runs: no dispatch in the number); the bytes
the floor counts for a pass (benchmark/lib/diffusion_floors.py
`pass_bytes`: dense weights + every expert + the lanes' live cache
blocks), the pass's share of 819 GB/s on them, and the experts a pass
really visits.  The op-alone number a `perf_opt` on the cell starts
from: 12.04 ms at 32 lanes, 16.14 at 64 (my chip run, PR 51; the packed
stream's read under `upper` in the pass's place took 31.84 / 80.87 as
the float32 scan and 24.45 / 52.71 as the Pallas kernel in the same
run and was not kept).  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BS, TABLE, LAYERS = 128, 29, 6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", default="32,64")
    ap.add_argument("--passes", type=int, default=8)
    ap.add_argument("--layers", type=int, default=LAYERS)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import diffusion_floors
    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.models import sdar
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    peak = device_peaks(ident["kind"])["hbm_bytes_per_s"]
    base = dataclasses.replace(sdar.PRESETS["sdar-30b-a3b"],
                               n_layers=args.layers)
    base = dataclasses.replace(base, attn_impl=resolve_decode_impl(
        "auto", ident["platform"], BS, base.head_dim, base.dtype))
    params = jax.block_until_ready(jax.jit(
        lambda key: sdar.init_params(base, key))(jax.random.PRNGKey(23)))
    consts = diffusion_floors.constants({
        "hidden_size": base.d_model, "num_attention_heads": base.n_heads,
        "num_key_value_heads": base.n_kv_heads, "head_dim": base.head_dim,
        "num_hidden_layers": base.n_layers, "num_experts": base.n_experts,
        "moe_intermediate_size": base.moe_ffn_dim,
        "vocab_size": base.vocab_size,
        "assumed": {"block_length": base.block_length}}, BS)
    B, k = base.block_length, args.passes
    rng = np.random.default_rng(0)
    for lanes in (int(x) for x in args.lanes.split(",")):
        nb = lanes * TABLE + 1
        ctx = rng.integers(512, 3584, lanes) // B * B
        tables = 1 + np.arange(lanes * TABLE, dtype=np.int32).reshape(
            lanes, TABLE)
        state = np.stack([sdar.new_lane_state(base, int(c), ())
                          for c in ctx])
        live = int(np.sum(-(-(ctx + B) // BS))) * base.n_layers
        need = diffusion_floors.pass_bytes(
            1, base.n_layers * base.n_experts, live,
            dense_weight_bytes=consts["dense_weight_bytes"],
            expert_bytes=consts["expert_bytes"],
            block_bytes=consts["block_bytes"])
        kv = tuple(jnp.zeros(s, d) for s, d in zip(
            sdar.kv_cache_shapes(base, nb, BS), sdar.kv_cache_dtypes(base)))
        fn = jax.jit(lambda kv, w, st, tb: sdar.denoise_multi(
            w, base, kv, st, tb, k), donate_argnums=(0,))
        st, tb = jnp.asarray(state), jnp.asarray(tables)
        ts = []
        for rep in range(7):
            t0 = time.perf_counter()
            outs, _, kv = fn(kv, params, st, tb)
            jax.block_until_ready((outs, kv))
            if rep >= 2:
                ts.append((time.perf_counter() - t0) * 1e3 / k)
        ms = statistics.median(ts)
        print(json.dumps({
            "lanes": lanes, "rows": lanes * B, "layers": base.n_layers,
            "ms_per_pass": round(ms, 3), "floor_bytes": int(need),
            "hbm_share_pct": round(100 * need / (ms / 1e3) / peak, 1),
            "visited_experts": int(np.asarray(kv[-1])[2]) // (7 * k),
        }), flush=True)
        del kv
    return 0


if __name__ == "__main__":
    sys.exit(main())
