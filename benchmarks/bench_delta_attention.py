#!/usr/bin/env python3
"""Time ops/delta_attention.py alone on the chip, at the published
Ling-3.0-flash shapes (32 heads, a 128 x 128 float32 state a head): one
decode step for 64 lanes with the state updated in place, and a prefill
row of 2048 tokens through the chunked rule, with its parts (the
operands a chunk needs before the state, the unit-triangular inverse)
and the forms beside them (XLA's triangular solve; the token recurrence
as a scan).

    python3 benchmarks/bench_delta_attention.py [--lanes 64] [--tokens 2048]

The chunked rule's KERNEL (ops/pallas_chunk_state.py, PR 45) is timed
beside `kda_chunked` at 512 / 1024 / 2048 tokens, with each one's error
against the token recurrence under slow decay, by
benchmarks/bench_chunk_state.py `--kda`, as ONE program of 8 dependent
calls: a call alone, which is what this script times, has 0.8-1.0 ms of
dispatch and sync in it on the chip's host (`chunked_ms` 4.52 here is
3.54 there).

Prints one JSON line of milliseconds a call (device time by the host's
clock around block_until_ready, median of 10 after 3 warm calls), the
bytes and FLOPs the floors of benchmark/lib/recurrent_floors.py count
for the call, and the share of the roofline each time is.  Fails without
a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=2048)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.lib import recurrent_floors
    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.ops import delta_attention as da
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    peaks = device_peaks(ident["kind"])
    H, dk, C, SUB = 32, 128, 64, 16
    B, T = args.lanes, args.tokens
    scale = dk ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(0), 6)

    def inputs(*lead):
        q = da.l2norm(jax.random.normal(ks[0], (*lead, H, dk)))
        k = da.l2norm(jax.random.normal(ks[1], (*lead, H, dk)))
        v = jax.random.normal(ks[2], (*lead, H, dk))
        log_a = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (*lead, H, dk)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (*lead, H)))
        return q, k, v, log_a, beta

    def timed(fn, *a, donate=()):
        """`donate`: the index of an argument the call updates in place;
        its output takes the argument's place in the next call."""
        fn = jax.jit(fn, donate_argnums=donate)
        a = list(a)

        def call():
            out = jax.block_until_ready(fn(*a))
            if donate:
                a[donate[0]] = out[-1]

        for _ in range(3):
            call()
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            call()
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(statistics.median(ts), 4)

    out = {"device": ident, "lanes": B, "tokens": T}

    # decode: every lane's state read and written once is the floor
    step_in = inputs(B)
    state = jax.random.normal(ks[5], (B, H, dk, dk))
    valid = jnp.ones((B,), bool)
    out["step_ms"] = timed(
        lambda q, k, v, la, b, S: da.kda_step(q, k, v, la, b, S, scale,
                                              valid),
        *step_in, state, donate=(5,))
    step_bytes = B * 2 * H * dk * dk * 4
    out["step_floor_bytes"] = step_bytes
    out["step_hbm_share"] = round(
        100 * step_bytes / (out["step_ms"] * 1e-3) / peaks["hbm_bytes_per_s"],
        2)

    # prefill: one row of T tokens
    row = inputs(T)
    S0 = jax.random.normal(ks[5], (H, dk, dk))
    out["chunked_ms"] = timed(
        lambda *a: da.kda_chunked(*a, scale, chunk=C, sub=SUB), *row, S0)
    flops = T * recurrent_floors.chunk_rule_flops(H, dk, dk, C)
    out["chunked_floor_flops"] = flops
    out["chunked_mxu_share"] = round(
        100 * flops / (out["chunked_ms"] * 1e-3) / peaks["bf16_flops"], 2)

    def chunks(x):
        x = x.reshape(T // C, C, *x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    q, k, v, log_a, beta = map(chunks, row)
    out["chunk_operands_ms"] = timed(
        lambda q, k, la, b: da._chunk_operands(q, k, la, b, SUB),
        q, k, log_a, beta)
    G, A, _ = da._chunk_operands(q, k, log_a, beta, SUB)
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(G)], -1)
    out["chunk_inverse_ms"] = timed(
        lambda A, rhs: jnp.matmul(da._unit_lower_inverse(A, SUB), rhs,
                                  precision=da.HI), A, rhs)
    out["chunk_xla_triangular_solve_ms"] = timed(
        lambda A, rhs: jax.lax.linalg.triangular_solve(
            A + jnp.eye(C), rhs, left_side=True, lower=True,
            unit_diagonal=True), A, rhs)

    def recurrence(q, k, v, log_a, beta, S):
        def token(S, x):
            o, S = da.kda_step(*(a[None] for a in x), S[None], scale)
            return S[0], o[0]
        S, o = jax.lax.scan(token, S, (q, k, v, log_a, beta))
        return o, S

    out["token_scan_ms"] = timed(recurrence, *row, S0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
