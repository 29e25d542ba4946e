#!/usr/bin/env python3
"""Time the window + global family's four reads alone on the chip, at
`command-a-plus.longctx-closed`'s shapes (128 heads over 8 KV heads of
128, blocks of 128, bf16; 8 lanes, a global pool of 1593 blocks with
tables of 199, rings of 33 blocks a lane; one layer-call each):

    decode   8 lanes at contexts 8k / 16k / 25k
             global   paged_attention_decode over the table: kernel | jnp
             window   the same over the ring's table with `kv_lo`: kernel
                      | jnp | MiMo's gathering `window_decode_attention`
    prefill  a 2048-token chunk at context 0 / 8k / 22k (a 512-token one
             at 22k beside it)
             global   packed_prefill_attention: kernel (| the float32
                      scan at 512 tokens, where `auto` takes it)
             window   window_prefill_flash ([ring's tail || chunk], the
                      band): kernel (| scan at 512)

    python3 benchmarks/bench_window_reads.py [--reps 10]

Prints one JSON line: milliseconds a call = the host's clock around
block_until_ready of ONE program that makes `reps` dependent calls, over
`reps` (median of 5 after 2 warm runs: no dispatch in the number); for
decode the live bytes the floor counts and their share of 819 GB/s; for
prefill the (query, key) pairs the mask keeps, their FLOPs (65,536 a
pair) and the share of 197 TFLOP/s.  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NH, NKV, HD, BS, LANES, NB, MB, WINDOW = 128, 8, 128, 128, 8, 1593, 199, 4096
PAIR_FLOPS = NH * 4 * HD


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.ops.packed_prefill import packed_prefill_attention
    from dynamo_tpu.ops.paged_attention import paged_attention_decode
    from dynamo_tpu.ops.window_attention import (
        ring_blocks,
        ring_decode_table,
        window_decode_attention,
        window_prefill_flash,
    )
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    peaks = device_peaks(ident["kind"])
    W = ring_blocks(WINDOW, BS)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    kg = jax.random.normal(ks[0], (1, NKV, NB, HD, BS), jnp.bfloat16)
    vg = jax.random.normal(ks[1], (1, NKV, NB, HD, BS), jnp.bfloat16)
    kw = jax.random.normal(ks[2], (1, NKV, 1 + LANES * W, HD, BS),
                           jnp.bfloat16)
    vw = jax.random.normal(ks[3], (1, NKV, 1 + LANES * W, HD, BS),
                           jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(LANES * MB, dtype=np.int32)
                         .reshape(LANES, MB))

    def timed(read, q, *rest):
        """ms a call of read(q, *rest) -> q-shaped."""

        @jax.jit
        def program(q, *rest):
            def body(_, q):
                out = read(q, *rest)
                return (q.astype(jnp.float32) + 1e-6 * out.astype(
                    jnp.float32)).astype(q.dtype)
            return jax.lax.fori_loop(0, args.reps, body, q)

        for _ in range(2):
            jax.block_until_ready(program(q, *rest))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(program(q, *rest))
            ts.append((time.perf_counter() - t0) * 1e3 / args.reps)
        return round(statistics.median(ts), 4)

    out = {"device": ident, "reps": args.reps, "decode": [], "prefill": []}
    qd = jax.random.normal(ks[4], (LANES, NH, HD), jnp.bfloat16)
    block_bytes = NKV * 2 * HD * BS * 2
    for ctx in (8192, 16384, 25000):
        pos = jnp.full((LANES,), ctx, jnp.int32)
        row = {"ctx": ctx}
        live_g = LANES * -(-(ctx + 1) // BS)
        for impl in ("pallas", "jnp_bf16"):
            row[f"global.{impl}_ms"] = timed(
                lambda q, impl=impl: paged_attention_decode(
                    q, kg, vg, 0, tables, pos + 1, impl=impl), qd)
        w_table, w_lens, w_lo = ring_decode_table(pos, None, WINDOW, BS)
        live_w = LANES * (ctx // BS - (ctx - WINDOW + 1) // BS + 1)
        for impl in ("pallas", "jnp_bf16"):
            row[f"window.{impl}_ms"] = timed(
                lambda q, impl=impl: paged_attention_decode(
                    q, kw, vw, 0, w_table, w_lens, impl=impl, kv_lo=w_lo),
                qd)
        row["window.mimo_gather_ms"] = timed(
            lambda q: window_decode_attention(q, kw, vw, 0, pos, None,
                                              WINDOW), qd)
        for kind, live in (("global", live_g), ("window", live_w)):
            row[f"{kind}.live_mb"] = round(live * block_bytes / 1e6, 1)
            row[f"{kind}.kernel_hbm_share"] = round(
                100 * live * block_bytes
                / (row[f"{kind}.pallas_ms"] * 1e-3)
                / peaks["hbm_bytes_per_s"], 1)
        out["decode"].append(row)
        print(json.dumps(row), flush=True)

    lanes1 = jnp.asarray([3], jnp.int32)
    for T, ctx in ((2048, 0), (2048, 8192), (2048, 22528), (512, 22528)):
        q = jax.random.normal(ks[5], (T, NH, HD), jnp.bfloat16)
        k = jax.random.normal(ks[4], (T, NKV, HD), jnp.bfloat16)
        seg = jnp.zeros(T, jnp.int32)
        positions = ctx + jnp.arange(T, dtype=jnp.int32)
        valid = jnp.ones(T, bool)
        width = 1
        while width < -(-(ctx + T) // BS):
            width *= 2
        table = tables[3:4, :min(width, MB)]
        seen = np.arange(ctx, ctx + T) + 1
        row = {"tokens": T, "ctx": ctx,
               "global.pairs_m": round(float(seen.sum()) / 1e6, 2),
               "window.pairs_m": round(float(np.minimum(
                   seen, WINDOW).sum()) / 1e6, 2)}
        impls = ("pallas", "xla") if T <= 512 else ("pallas",)
        for impl in impls:
            row[f"global.{impl}_ms"] = timed(
                lambda q, impl=impl: packed_prefill_attention(
                    q, kg, vg, 0, table, seg, positions, valid, impl=impl),
                q)
            row[f"window.{impl}_ms"] = timed(
                lambda q, impl=impl: window_prefill_flash(
                    q, k, k, kw, vw, 0, lanes1, seg, positions, valid,
                    WINDOW, impl=impl), q)
        for kind in ("global", "window"):
            row[f"{kind}.kernel_mxu_share"] = round(
                100 * row[f"{kind}.pairs_m"] * 1e6 * PAIR_FLOPS
                / (row[f"{kind}.pallas_ms"] * 1e-3) / peaks["bf16_flops"],
                1)
        out["prefill"].append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
