#!/usr/bin/env python3
"""Time the absorbed MLA decode read alone on the chip (one layer-call of
ops/mla_attention.py `mla_decode_attention` before W_UV), the gathering
jnp body against the Pallas latent kernel, at the two cells' shapes:

    ling       32 heads, 64 lanes x 45-block tables, a 2881-block pool
    moonlight  16 heads, 16 lanes x 20-block tables, a 512-block pool

(R 512, rope key 64, blocks of 128, bf16), over a few live shares (the
part of every lane's table that holds context; the last block is
partly full) and the kernel's chunk of 4 / 8 blocks.

    python3 benchmarks/bench_mla_decode.py [--reps 20]

Prints one JSON line: milliseconds a call = the host's clock around
block_until_ready of ONE program that makes `reps` dependent calls, over
`reps` (median of 5 after 2 warm runs: no dispatch in the number), the
live bytes the floor counts ((R + dr) x 2 B a live token), and the
kernel's share of 819 GB/s on them.  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {"ling": (32, 64, 45, 2881), "moonlight": (16, 16, 20, 512)}
R, DR, BS = 512, 64, 128


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shares", default="0.08,0.25,0.5,0.97")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.ops.mla_attention import _mla_decode_jnp
    from dynamo_tpu.ops.pallas_mla_attention import mla_decode_pallas
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    hbm = device_peaks(ident["kind"])["hbm_bytes_per_s"]
    scale = (128 + DR) ** -0.5

    def timed(read, qa, qr, c, kr):
        """ms a call of read(qa, qr, c, kr) -> [B, nh, R] fp32."""

        @jax.jit
        def program(qa, qr, c, kr):
            def body(_, qa):
                ctx = read(qa, qr, c, kr)
                return (qa.astype(jnp.float32) + 1e-6 * ctx).astype(qa.dtype)
            return jax.lax.fori_loop(0, args.reps, body, qa)

        for _ in range(2):
            jax.block_until_ready(program(qa, qr, c, kr))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(program(qa, qr, c, kr))
            ts.append((time.perf_counter() - t0) * 1e3 / args.reps)
        return round(statistics.median(ts), 4)

    out = {"device": ident, "reps": args.reps, "rows": []}
    for name, (nh, B, mb, nb) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        c = jax.random.normal(ks[0], (2, 1, nb, R, BS), jnp.bfloat16)
        kr = jax.random.normal(ks[1], (2, 1, nb, DR, BS), jnp.bfloat16)
        qa = jax.random.normal(ks[2], (B, nh, R), jnp.bfloat16)
        qr = jax.random.normal(ks[3], (B, nh, DR), jnp.bfloat16)
        rng = np.random.default_rng(0)
        for share in (float(s) for s in args.shares.split(",")):
            used = max(1, round(share * mb))
            tables = np.zeros((B, mb), np.int32)
            for b in range(B):     # scattered over the pool, distinct
                tables[b, :used] = 1 + (b * mb + rng.permutation(mb)[:used]
                                        ) % (nb - 1)
            lens = np.full(B, used * BS - 37, np.int32)
            t, n = jnp.asarray(tables), jnp.asarray(lens)
            row = {"shape": name, "lanes": B, "table": mb,
                   "live_blocks": B * used,
                   "live_share": round(used / mb, 3),
                   "jnp_ms": timed(
                       lambda qa, qr, c, kr: _mla_decode_jnp(
                           qa, qr, c, kr, 1, t, n, scale), qa, qr, c, kr)}
            for bpc in (4, 8):
                row[f"kernel_bpc{bpc}_ms"] = timed(
                    lambda qa, qr, c, kr: mla_decode_pallas(
                        qa, qr, c, kr, 1, t, n, scale,
                        blocks_per_chunk=bpc), qa, qr, c, kr)
            live_bytes = int(lens.sum()) * (R + DR) * 2
            row["live_mb"] = round(live_bytes / 1e6, 2)
            row["kernel_hbm_share"] = round(
                100 * live_bytes / hbm / (row["kernel_bpc8_ms"] / 1e3), 1)
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
