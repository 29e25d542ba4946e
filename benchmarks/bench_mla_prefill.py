#!/usr/bin/env python3
"""Time the MLA layers' PREFILL read alone on the chip (one layer-call,
one row), the jnp form against the Pallas flash kernel
(ops/pallas_mla_attention.py `mla_prefill_pallas`), at the two cells'
shapes:

    ling       32 heads, a 45-block table, a 2881-block pool; the jnp
               form is models/ling.py's: 512 queries a pass
    moonlight  16 heads, a 20-block table, a 512-block pool

(R 512, nope 128 / rope 64 / v 128, blocks of 128, bf16).

    python3 benchmarks/bench_mla_prefill.py [--buckets 32,..,2048]
        [--contexts 0,2048,4096] [--reps 8]
    python3 benchmarks/bench_mla_prefill.py --tune [h2_q256_k512,...]
    python3 benchmarks/bench_mla_prefill.py --precision

Default: a JSON line a (shape, bucket, context) that fits the table:
ms a call of both forms and the kernel's largest distance from the jnp
form.  ms a call = the host's clock around block_until_ready of ONE
program that makes `reps` dependent calls, over `reps` (median of 5
after 2 warm runs: no dispatch in the number).

--tune: THREE numbers a candidate body `h<heads a loop step>_q<query
tile>_k<key tile>`: ms a call at the buckets that carry the cells'
tokens, and seconds to compile and bytes of serialized executable of an
8-layer Moonlight-shaped program of the reads alone (2048 queries, 8
calls with a traced layer) beside the jnp form's: Mosaic's code for the
body is embedded once a layer in every kernel-bearing prefill program
and paid at every start (PERF.md section 6, PR 49).

--precision: the kernel and the jnp form at XLA's default precision,
each against the jnp form at precision HIGHEST (float32 products), max
and mean distance over the real queries.  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# heads, table blocks, pool blocks, the jnp form's queries a pass
SHAPES = {"ling": (32, 45, 2881, 512), "moonlight": (16, 20, 512, 0)}
R, DN, DR, DV, BS = 512, 128, 64, 128, 128
CANDIDATES = ("h2_q512_k512,h2_q256_k512,h2_q512_k256,h2_q512_k1024,"
              "h4_q512_k512,h8_q512_k256")


def candidate(tag: str) -> dict:
    h, q, k = (int(x[1:]) for x in tag.split("_"))
    return dict(heads_a_step=h, token_block=q, chunk_cols=k // BS)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--buckets", default="32,64,128,256,512,1024,2048")
    ap.add_argument("--contexts", default="0,2048,4096")
    ap.add_argument("--tune", nargs="?", const=CANDIDATES, default=None)
    ap.add_argument("--precision", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.serialize_executable import serialize

    from dynamo_tpu.ops.mla_attention import (
        mla_prefill_attention,
        mla_write_rows,
    )
    from dynamo_tpu.ops.pallas_mla_attention import mla_prefill_pallas
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    bf = jnp.bfloat16

    def jnp_form(q_block):
        """One row [T, ...] -> [T, nh, dv]: the family's jnp read
        (models/ling.py's passes of `q_block` queries, or the op)."""
        from types import SimpleNamespace

        from dynamo_tpu.models.ling import _mla_prefill

        def read(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv):
            if not q_block:
                return mla_prefill_attention(qn, qr, c, kr, cc, krc, 1,
                                             table, ctx, true, w_uk, w_uv)
            return _mla_prefill(
                {"w_uk": w_uk, "w_uv": w_uv},
                SimpleNamespace(mla_q_block=q_block), qn, qr, c, kr, cc,
                krc, 1, table, ctx, true)
        return read

    def kernel_form(**kw):
        def read(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv):
            return mla_prefill_pallas(
                qn[None], qr[None], cc, krc, 1, table[None], ctx[None],
                true[None], w_uk, w_uv, **kw)[0]
        return read

    def timed(read, qn, *rest):
        @jax.jit
        def program(qn, *rest):
            def body(_, qn):
                out = read(qn, *rest)
                return (qn.astype(jnp.float32) + 1e-6 * jnp.mean(
                    out.astype(jnp.float32))).astype(qn.dtype)
            return jax.lax.fori_loop(0, args.reps, body, qn)

        for _ in range(2):
            jax.block_until_ready(program(qn, *rest))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(program(qn, *rest))
            ts.append((time.perf_counter() - t0) * 1e3 / args.reps)
        return round(statistics.median(ts), 4)

    def operands(name, T, ctx_len):
        """A row of T - 5 real tokens at context ctx_len, its chunk
        written into the pool (whole planes), over random data."""
        nh, mb, nb, _ = SHAPES[name]
        ks = jax.random.split(jax.random.PRNGKey(0), 8)
        normal = lambda k, *shape: jax.random.normal(k, shape, bf)
        cc, krc = normal(ks[0], 2, 1, nb, R, BS), normal(ks[1], 2, 1, nb, DR,
                                                         BS)
        w_uk = normal(ks[2], nh, R, DN) * R ** -0.5
        w_uv = normal(ks[3], nh, R, DV) * R ** -0.5
        table = jnp.asarray(
            1 + np.random.default_rng(0).permutation(nb - 1)[:mb], jnp.int32)
        qn, qr = normal(ks[4], T, nh, DN), normal(ks[5], T, nh, DR)
        c, kr = normal(ks[6], T, R), normal(ks[7], T, DR)
        ctx, true = jnp.int32(ctx_len), jnp.int32(max(T - 5, 1))
        cc, krc = jax.jit(mla_write_rows)(
            cc, krc, 1, c[None], kr[None], table[None], ctx[None],
            true[None])
        return qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv

    def fits(name, T, ctx_len):
        return ctx_len + T <= SHAPES[name][1] * BS

    print(json.dumps({"device": ident, "reps": args.reps}), flush=True)
    if args.tune:
        tags = args.tune.split(",")
        # the 8-layer Moonlight-shaped program of the reads alone
        nh, mb, nb, _ = SHAPES["moonlight"]
        L, T = 8, 2048
        S = jax.ShapeDtypeStruct
        shapes = (S((T, nh, DN), bf), S((T, nh, DR), bf), S((T, R), bf),
                  S((T, DR), bf), S((L, 1, nb, R, BS), bf),
                  S((L, 1, nb, DR, BS), bf), S((mb,), jnp.int32),
                  S((), jnp.int32), S((), jnp.int32),
                  S((L, nh, R, DN), bf), S((L, nh, R, DV), bf))

        def built(read):
            def program(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv):
                out = 0.0
                for li in range(L):
                    o = read(qn, qr, c, kr, cc, krc, table, ctx, true,
                             w_uk[li], w_uv[li], layer=jnp.int32(li))
                    out = out + o.astype(jnp.float32)
                    qn = (qn + 1e-3 * o[..., :DN]).astype(bf)
                return out
            jax.clear_caches()
            t0 = time.perf_counter()
            compiled = jax.jit(program).lower(*shapes).compile()
            return {"compile_s": round(time.perf_counter() - t0, 2),
                    "bytes": len(serialize(compiled)[0])}

        def jnp_layer(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv,
                      layer):
            return mla_prefill_attention(qn, qr, c, kr, cc, krc, layer,
                                         table, ctx, true, w_uk, w_uv)

        def kernel_layer(**kw):
            def read(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv,
                     layer):
                return mla_prefill_pallas(
                    qn[None], qr[None], cc, krc, layer, table[None],
                    ctx[None], true[None], w_uk, w_uv, **kw)[0]
            return read

        print(json.dumps({"program": "moonlight 8 layers x 2048 queries",
                          "jnp": built(jnp_layer)}), flush=True)
        for tag in tags:
            row = {"candidate": tag}
            try:
                row.update(built(kernel_layer(**candidate(tag))))
            except Exception as e:
                row["why"] = str(e)[-300:]
            print(json.dumps(row), flush=True)
        for name, T, ctxs in (("ling", 2048, (0, 2048)),
                              ("moonlight", 2048, (0,)),
                              ("moonlight", 512, (0, 2048)),
                              ("ling", 512, (0, 2048))):
            for ctx_len in ctxs:
                ops = operands(name, T, ctx_len)
                row = {"shape": name, "bucket": T, "ctx": ctx_len}
                for tag in tags:
                    jax.clear_caches()
                    try:
                        row[tag] = timed(kernel_form(**candidate(tag)), *ops)
                    except Exception as e:
                        row[tag] = None
                        row[tag + "_why"] = str(e)[-200:]
                print(json.dumps(row), flush=True)
        return 0

    if args.precision:
        for name in SHAPES:
            for T, ctx_len in ((256, 0), (512, 195), (2048, 2048)):
                if not fits(name, T, ctx_len):
                    continue
                ops = operands(name, T, ctx_len)
                true = int(ops[8])
                form = jax.jit(jnp_form(SHAPES[name][3]))
                default = form(*ops).astype(jnp.float32)[:true]
                with jax.default_matmul_precision("highest"):
                    exact = jax.jit(jnp_form(SHAPES[name][3]))(*ops).astype(
                        jnp.float32)[:true]
                got = jax.jit(kernel_form())(*ops).astype(jnp.float32)[:true]
                err = lambda a: (float(jnp.max(jnp.abs(a - exact))),
                                 float(jnp.mean(jnp.abs(a - exact))))
                print(json.dumps({
                    "shape": name, "T": T, "ctx": ctx_len,
                    "kernel_vs_highest(max,mean)": err(got),
                    "jnp_default_vs_highest(max,mean)": err(default)}),
                    flush=True)
        return 0

    for name, (nh, mb, nb, q_block) in SHAPES.items():
        for T in (int(x) for x in args.buckets.split(",")):
            for ctx_len in (int(x) for x in args.contexts.split(",")):
                if not fits(name, T, ctx_len) or (ctx_len and T < 512):
                    continue
                ops = operands(name, T, ctx_len)
                true = int(ops[8])
                a = jax.jit(jnp_form(q_block))(*ops)[:true]
                b = jax.jit(kernel_form())(*ops)[:true]
                print(json.dumps({
                    "shape": name, "bucket": T, "ctx": ctx_len, "table": mb,
                    "jnp_ms": timed(jnp_form(q_block), *ops),
                    "kernel_ms": timed(kernel_form(), *ops),
                    "kernel_err": round(float(jnp.max(jnp.abs(
                        a.astype(jnp.float32) - b.astype(jnp.float32)))), 5),
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
