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
             at 2048 tokens, 8 or 16 query heads a KV head, each beside
             the forms of the kernel's head grouping (PR 54; the row
             says which the kernel's own rule took, `heads_a_body`):
             `one_body` (ONE kernel body over all the query heads of a
             KV head), `body4` (4 heads a body, the runs of heads on
             the grid) and `map4` (`lax.map` over calls of 4 heads a KV
             head, q and the output transposed around it), with the
             largest difference of their outputs from the kernel's
             (0.0: a head's arithmetic is the same)

    python3 benchmarks/bench_window_reads.py [--reps 10] [--prefill-only]
        [--heads 128 --kv-heads 8]

Prints one JSON line: milliseconds a call = the host's clock around
block_until_ready of ONE program that makes `reps` dependent calls, over
`reps` (median of 5 after 2 warm runs: no dispatch in the number); for
decode the live bytes the floor counts and their share of 819 GB/s; for
prefill the (query, key) pairs the mask keeps, their FLOPs (65,536 a
pair) and the share of 197 TFLOP/s.  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HD, BS, LANES, NB, MB, WINDOW = 128, 128, 8, 1593, 199, 4096


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--prefill-only", action="store_true")
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--kv-heads", type=int, default=8,
                    help="another ratio of query heads a KV head at the "
                    "same pools' sizes (SDAR: --heads 32 --kv-heads 4)")
    args = ap.parse_args()
    NH, NKV = args.heads, args.kv_heads
    PAIR_FLOPS = NH * 4 * HD

    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.ops import pallas_packed_prefill as ppk
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
        """ms a call of read(q, *rest) -> q-shaped.  The pools go in as
        `rest`: closed over, they are constants of the executable (1.6
        GB each program here, minutes a compile)."""

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
    for ctx in () if args.prefill_only else (8192, 16384, 25000):
        pos = jnp.full((LANES,), ctx, jnp.int32)
        row = {"ctx": ctx}
        live_g = LANES * -(-(ctx + 1) // BS)
        for impl in ("pallas", "jnp_bf16"):
            row[f"global.{impl}_ms"] = timed(
                lambda q, kc, vc, impl=impl: paged_attention_decode(
                    q, kc, vc, 0, tables, pos + 1, impl=impl), qd, kg, vg)
        w_table, w_lens, w_lo = ring_decode_table(pos, None, WINDOW, BS)
        live_w = LANES * (ctx // BS - (ctx - WINDOW + 1) // BS + 1)
        for impl in ("pallas", "jnp_bf16"):
            row[f"window.{impl}_ms"] = timed(
                lambda q, kc, vc, impl=impl: paged_attention_decode(
                    q, kc, vc, 0, w_table, w_lens, impl=impl, kv_lo=w_lo),
                qd, kw, vw)
        row["window.mimo_gather_ms"] = timed(
            lambda q, kc, vc: window_decode_attention(
                q, kc, vc, 0, pos, None, WINDOW), qd, kw, vw)
        for kind, live in (("global", live_g), ("window", live_w)):
            row[f"{kind}.live_mb"] = round(live * block_bytes / 1e6, 1)
            row[f"{kind}.kernel_hbm_share"] = round(
                100 * live * block_bytes
                / (row[f"{kind}.pallas_ms"] * 1e-3)
                / peaks["hbm_bytes_per_s"], 1)
        out["decode"].append(row)
        print(json.dumps(row), flush=True)

    G = NH // NKV
    Gk = ppk._group_heads(G)
    kernel = ppk.packed_prefill_attention_pallas

    def body_of(heads):
        """A read whose kernel calls hold `heads` query heads of a KV
        head a body (G: ONE body over the group), by the wrapper's own
        static argument, which no model passes."""
        def form(read):
            def run(q, *rest):
                with mock.patch.object(
                        ppk, "packed_prefill_attention_pallas",
                        lambda *a, **kw: kernel(*a, group_heads=heads,
                                                **kw)):
                    return read(q, *rest)
            return run
        return form

    def map4(read):
        """`read` a call of 4 heads a KV head, the calls one after
        another (models/nemotron_h.py until PR 54): the heads lie KV
        head major, [nkv, n, 4] -> n streams of [nkv, 4]."""
        n = G // 4

        def run(q, *rest):
            T = q.shape[0]
            qs = q.reshape(T, NKV, n, 4, HD).transpose(2, 0, 1, 3, 4)
            out = jax.lax.map(lambda qi: read(qi, *rest),
                              qs.reshape(n, T, NKV * 4, HD))
            return out.reshape(n, T, NKV, 4, HD).transpose(
                1, 2, 0, 3, 4).reshape(T, NH, HD)
        return run

    # the forms beside the rule's own (which a row's `pallas_ms` ran)
    forms = [(name, form) for name, heads, form in (
        ("one_body", G, body_of(G)), ("body4", 4, body_of(4)),
        ("map4", 0, map4)) if heads != Gk and G in (8, 16)]
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
        row = {"tokens": T, "ctx": ctx, "heads_a_kv_head": G,
               "heads_a_body": Gk,
               "global.pairs_m": round(float(seen.sum()) / 1e6, 2),
               "window.pairs_m": round(float(np.minimum(
                   seen, WINDOW).sum()) / 1e6, 2)}
        reads = {
            "global": (lambda q, kc, vc, impl="pallas":
                       packed_prefill_attention(
                           q, kc, vc, 0, table, seg, positions, valid,
                           impl=impl), (kg, vg)),
            "window": (lambda q, kc, vc, impl="pallas":
                       window_prefill_flash(
                           q, k, k, kc, vc, 0, lanes1, seg, positions,
                           valid, WINDOW, impl=impl), (kw, vw)),
        }
        for kind, (read, pools) in reads.items():
            row[f"{kind}.pallas_ms"] = timed(read, q, *pools)
            if T <= 512:
                row[f"{kind}.xla_ms"] = timed(
                    functools.partial(read, impl="xla"), q, *pools)
                continue
            for name, form in forms:
                row[f"{kind}.{name}_ms"] = timed(form(read), q, *pools)
                if ctx == 8192:   # whole and masked tiles, the band cut
                    got, want = (jax.jit(f)(q, *pools).astype(jnp.float32)
                                 for f in (form(read), read))
                    row[f"{kind}.{name}_max_diff"] = float(
                        jnp.abs(got - want).max())
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
