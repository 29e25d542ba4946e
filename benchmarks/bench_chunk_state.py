#!/usr/bin/env python3
"""Time the PREFILL half of a lane-addressed STATE member on the chip:
the chunked form of the family's recurrence over one row, the jnp form
(`kda_chunked`; `ssd_chunked`) beside ops/pallas_chunk_state.py's kernel
where it has a body for it (the delta rule), and a whole prefill program
of the family with the rule in it, with the kernel in it and with NO
rule in it (a stand-in that only adds its operands; it UNDERSTATES the
rule's share, because the projections fuse into it: the op's own row
times the layers is the better number).

    python3 benchmarks/bench_chunk_state.py \
        [--ssd 256,512,2048] [--kda 512,1024,2048] \
        [--program ling-3.0-flash-12l-ep32:512,1024,2048 \
         --program nemotron-twotower-30b-a3b-27l-ep8:256,512,2048]

Prints one JSON line a row: milliseconds a call (the host's clock around
block_until_ready of ONE program that makes 8 dependent calls, over 8:
median of 5 after 2 warm runs; a program row: a call, median of 5 after
3), the FLOPs the floors of benchmark/lib count for the row and their
share of the MXU's peak, and how far the row's read and state lie from
the token recurrence's and, a kernel row's, from the jnp form's on the
chip.  Shapes are the
two cells': Mamba-2 64 heads x 64 x 128 in 8 groups, chunk 128; the
delta rule 32 heads x 128 x 128, chunk 64 / sub 16.  Fails without a
TPU.
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

REPS = 8


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ssd", default="256,512,2048")
    ap.add_argument("--kda", default="512,1024,2048")
    ap.add_argument("--program", action="append", default=[],
                    help="<configuration>:<bucket>,<bucket>")
    ap.add_argument("--impls", default="jnp,pallas")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import recurrent_floors, spec, ssm_floors
    from benchmark.lib.model import source_keys
    from benchmark.lib.peaks import device_peaks
    from dynamo_tpu.models import get_family
    from dynamo_tpu.ops import delta_attention as da
    from dynamo_tpu.ops import ssm
    from dynamo_tpu.ops.pallas_chunk_state import kda_chunk_rows
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    peak = device_peaks(ident["kind"])["bf16_flops"]
    impls = args.impls.split(",")
    sizes = lambda s: [int(t) for t in s.split(",") if t]
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    print(json.dumps({"device": ident}), flush=True)

    def timed(form, *a):
        """Milliseconds a call: ONE program of `REPS` dependent calls
        (the state carried from call to call) over `REPS`, so that no
        dispatch is in the number (a call alone reads 0.8-1.0 ms more on
        this host: PERF.md section 6, PR 45).  A barrier ties the
        operands to the carry: without it XLA hoists everything of the
        jnp form that does not depend on the state out of the loop."""
        *ops, s0 = a

        def program(ops, s0):
            def body(_, carry):
                held, s = jax.lax.optimization_barrier((ops, carry[1]))
                return form(*held, s)
            read = jax.eval_shape(form, *ops, s0)[0]
            return jax.lax.fori_loop(
                0, REPS, body, (jnp.zeros(read.shape, read.dtype), s0))

        fn = jax.jit(program)
        for _ in range(2):
            jax.block_until_ready(fn(tuple(ops), s0))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(tuple(ops), s0))
            ts.append((time.perf_counter() - t0) * 1e3 / REPS)
        return round(statistics.median(ts), 4), jax.jit(form)(*a)

    def far(got, want):
        return float(jnp.max(jnp.abs(got - want))
                     / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))

    def rows(name, T, forms, token_scan, operands, flops):
        """One line a form; the kernel's beside the jnp form's result
        and the token recurrence's."""
        want, truth = None, None
        for impl, form in forms.items():
            if impl not in impls:
                continue
            ms, out = timed(form, *operands)
            line = {"op": name, "tokens": T, "impl": impl, "ms": ms,
                    "floor_flops": flops,
                    "mxu_share": round(100 * flops / (ms * 1e-3) / peak, 2)}
            if truth is None:
                truth = jax.jit(token_scan)(*operands)
            line["read_vs_tokens"] = far(out[0], truth[0])
            line["state_vs_tokens"] = far(out[1], truth[1])
            if impl == "jnp":
                want = out
            elif want is not None:
                line["read_vs_jnp"] = far(out[0], want[0])
                line["state_vs_jnp"] = far(out[1], want[1])
            print(json.dumps(line), flush=True)

    # Mamba-2
    H, P, N, G, C = 64, 64, 128, 8, 128
    a = -jnp.exp(jax.random.normal(ks[0], (H,)) * 0.5)
    d_skip = jax.random.normal(ks[1], (H,))
    for T in sizes(args.ssd):
        x = jax.random.normal(ks[2], (T, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[3], (T, H)) - 2.0)
        b = jax.random.normal(ks[4], (T, G, N)) * N ** -0.5
        c = jax.random.normal(ks[5], (T, G, N)) * N ** -0.5
        s0 = jax.random.normal(ks[6], (H, P, N))

        def tokens(x, dt, b, c, s0):
            def one(s, t):
                y, s = ssm.ssd_step(t[0][None], t[1][None], a, t[2][None],
                                    t[3][None], d_skip, s[None])
                return s[0], y[0]
            s, y = jax.lax.scan(one, s0, (x, dt, b, c))
            return y, s

        rows("ssd", T,
             {"jnp": lambda x, dt, b, c, s0: ssm.ssd_chunked(
                 x, dt, a, b, c, d_skip, s0, chunk=C)},
             tokens, (x, dt, b, c, s0),
             T * ssm_floors.scan_flops(H, P, N, G, C))

    # the delta rule
    H, dk, C, SUB = 32, 128, 64, 16
    scale = dk ** -0.5
    for T in sizes(args.kda):
        q = da.l2norm(jax.random.normal(ks[0], (T, H, dk)))
        k = da.l2norm(jax.random.normal(ks[1], (T, H, dk)))
        v = jax.random.normal(ks[2], (T, H, dk))
        # slow decay (chip_logits_ling.py's rule-alone row: TOL_RULE)
        log_a = -0.1 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, dk)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
        s0 = jax.random.normal(ks[5], (H, dk, dk))

        def tokens(q, k, v, log_a, beta, s0):
            def one(s, t):
                o, s = da.kda_step(*(u[None] for u in t), s[None], scale)
                return s[0], o[0]
            s, o = jax.lax.scan(one, s0, (q, k, v, log_a, beta))
            return o, s

        def kernel(q, k, v, log_a, beta, s0):
            o, s = kda_chunk_rows(
                q[None], k[None], v[None], log_a[None], beta[None],
                s0[None], scale=scale, chunk=C, sub=SUB)
            return o[0], s[0]

        rows("kda", T,
             {"jnp": lambda *r: da.kda_chunked(*r, scale, chunk=C, sub=SUB),
              "pallas": kernel},
             tokens, (q, k, v, log_a, beta, s0),
             T * recurrent_floors.chunk_rule_flops(H, dk, dk, C))

    # a whole prefill program: the rule as `impl` has it, and none
    def no_kda(q, k, v, log_a, beta, state, scale, **_):
        o = (q + k + log_a).astype(jnp.float32) * beta[..., None] + v
        return o, state + jnp.sum(o, axis=0)[..., None, :] * 1e-9

    def no_ssd(x, dt, a, b, c, d_skip, state, **_):
        R = x.shape[1] // b.shape[1]
        bc = jnp.repeat(jnp.sum(b + c, -1), R, axis=1)       # [T, H]
        y = x.astype(jnp.float32) * (dt * a + bc + d_skip)[..., None]
        return y, state + jnp.sum(y, axis=0)[..., None] * 1e-9

    bench = spec.load_benchmark()
    for item in args.program:
        name, _, buckets = item.partition(":")
        entry = next(c for c in bench["configs"] if c["name"] == name)
        with open(os.path.join(spec.REPO_ROOT, entry["file"])) as f:
            config = json.load(f)
        cfg = spec.model_class(config).program_config(
            source_keys(config, False), name)
        family = get_family(cfg)
        params = jax.jit(lambda key: family.init_params(cfg, key))(
            jax.random.PRNGKey(int(config["engine"]["weights_seed"])))
        jax.block_until_ready(params)
        bs = int(config["engine"]["block_size"])
        lanes, lane = 4, 2
        module = family
        stand_in = {"kda_chunked": no_kda, "ssd_chunked": no_ssd}
        target = next(n for n in stand_in if hasattr(module, n))
        for T in sizes(buckets):
            width = T // bs + 2
            table = jnp.asarray(1 + np.arange(width, dtype=np.int32))
            toks = jnp.asarray(np.random.default_rng(T).integers(
                3, cfg.vocab_size, T).astype(np.int32))
            line = {"program": name, "bucket": T}
            for form in impls + ["none"]:
                c = dataclasses.replace(
                    cfg, attn_impl="jnp" if form == "none" else form)
                real = getattr(module, target)
                if form == "none":
                    setattr(module, target, stand_in[target])
                try:
                    prefill = jax.jit(
                        lambda kv, w, *r, c=c, **k: family.prefill(
                            w, c, kv, *r, **k), donate_argnums=(0,))
                    kv = tuple(jnp.zeros(s, d) for s, d in zip(
                        family.kv_cache_shapes(c, width + 1, bs,
                                               lanes=lanes),
                        family.kv_cache_dtypes(c)))
                    ts = []
                    for i in range(8):
                        t0 = time.perf_counter()
                        # a carried state: the row starts at position T
                        _, kv = prefill(
                            kv, params, toks,
                            jnp.arange(T, dtype=jnp.int32) + bs,
                            table, jnp.int32(bs), jnp.int32(T),
                            lanes=jnp.int32(lane))
                        jax.block_until_ready(kv)
                        ts.append((time.perf_counter() - t0) * 1e3)
                finally:
                    setattr(module, target, real)
                line[f"{form}_ms"] = round(statistics.median(ts[3:]), 3)
                del kv, prefill
            for form in impls:
                line[f"rule_share_{form}"] = round(
                    100 * (1 - line["none_ms"] / line[f"{form}_ms"]), 2)
            print(json.dumps(line), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
