#!/usr/bin/env python3
"""Time the prefill CHOICE of the block-selecting family on the chip
(ops/block_sparse_attention.py `prefill_block_choice`): the jnp form
(`block_scores` 256 queries at a time under `lax.map`, then `topk_mask`)
beside ops/pallas_block_choice.py's kernel, at the long-document cell's
shapes: 2048 queries, 32 heads over 2 KV groups of 128, a table of 391
pages = 3128 compressed keys = 782 blocks, bf16.

    python3 benchmarks/bench_block_choice.py [--contexts 8192,20480,40960]
        [--tiles 128,256] [--program minicpm-sala-9b-12l]

A context is where the chunk ENDS: 8192 is the last all-dense chunk.
Every time is the PROFILER's device time of the program's module (the
`XLA Modules` line, median of 3 runs after 2 warm ones), not the host's
clock around a call.  One JSON line a (context, form):

  * `choice_jnp` / `choice_kernel`: the whole op;
  * `scores_jnp` / `scores_kernel`: P alone (`block_scores` under the
    same `lax.map` / the kernel with the keys' relayout and P's);
  * `search_rows16` / `search_rows128`: the forced blocks and
    `topk_mask` over a given P at the search kernel's own 16 rows a grid
    step (the jnp form's programs) and at the kernel path's;
  * on the kernel's lines: the (query, group) rows whose chosen set
    differs from the jnp form's and how near a tie the farthest of them
    was, the largest relative difference of P over the rows that are
    read, the key tiles visited of those the table has, and the
    kernel's tile and declared VMEM.

`--program <configuration>`: one 2048-token prefill program of the
benchmark's configuration at each context, with the choice as jnp and
as kernel, under the profiler: device ms a program, the device's own
counters after the same five calls of each form (the flash tiles that
ran: the same sets run the same tiles), the kernel form's logits beside
the jnp form's, and the device ops that took most of it in
`chiprun_out/block_choice/`.  Fails without a TPU.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import shutil
import statistics
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T, NH, NKV, HD, PAGES, BS = 2048, 32, 2, 128, 391, 128
OUT = "chiprun_out/block_choice"


def device_ms(name, fn, *a, runs=3, ops=None):
    """Median device milliseconds of the module `jit_<name>` over
    `runs` calls under the profiler; `ops`, where a Counter, gets
    the mean ms a call of every device op of the session."""
    import jax

    from benchmark.lib.trace_reduce import load_xplane

    for _ in range(2):
        out = fn(*a)
    jax.block_until_ready(out)
    d = os.path.join(".bench_out", "block_choice", name)
    shutil.rmtree(d, ignore_errors=True)
    with jax.profiler.trace(d):
        for _ in range(runs):
            out = fn(*a)
        jax.block_until_ready(out)
    ev = load_xplane(glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0])
    shutil.rmtree(d, ignore_errors=True)
    ms = []
    for dev in ev["devices"].values():
        ms += [dur / 1e6 for n, _, dur in dev["modules"]
               if n.startswith(f"jit_{name}(")]
        if ops is not None:
            for n, _, dur in dev["ops"]:
                ops[n] += dur / 1e6 / runs
    if len(ms) != runs:
        raise RuntimeError(f"{name}: {len(ms)} module events")
    return round(statistics.median(ms), 4), out


def named(name, fn, **jit_kw):
    """`fn` jitted as the module `jit_<name>`."""
    import jax

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", default="8192,20480,40960")
    ap.add_argument("--tiles", default="",
                    help="query tiles of the kernel to time beside its own")
    ap.add_argument("--program", default="",
                    help="a configuration of BENCHMARK.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import block_sparse_attention as bsa
    from dynamo_tpu.ops import pallas_block_choice as pbc
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    print(json.dumps({"device": ident}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    contexts = [int(c) for c in args.contexts.split(",") if c]
    sizes = bsa.BlockSizes(32, 16, 64, 1, 2048, 64, 8192)
    per = sizes.block // sizes.stride

    rng = np.random.default_rng(0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    # normed queries and means of normed keys, as the model's: the
    # softmax is neither flat nor one-hot
    q = jax.random.normal(k1, (T, NH, HD), jnp.float32)
    q = (q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True))).astype(
        jnp.bfloat16)
    ck = (jax.random.normal(k2, (1, PAGES + 9, BS // sizes.stride, NKV, HD),
                            jnp.float32) * 0.7).astype(jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(PAGES + 8)[:PAGES], jnp.int32)
    valid = jnp.arange(T) < T - 5

    def seq(ck):
        s = ck[0, table]
        return s.reshape(-1, *s.shape[2:])

    def scores_jnp(q, ck, pos):
        parts = lambda x: x.reshape(T // 256, 256, *x.shape[1:])
        return jax.lax.map(
            lambda a: bsa.block_scores(a[0], seq(ck), a[1], sizes),
            (parts(q), parts(pos))).reshape(T, NKV, -1)

    def scores_kernel(tq):
        def f(q, ck, pos):
            read = valid & (pos + 1 > sizes.dense_len)
            return pbc.block_scores_pallas(q, seq(ck), pos, read, sizes,
                                           tq=tq)
        return f

    forms = {
        "scores_jnp": scores_jnp,
        "scores_kernel": scores_kernel(0),
        "choice_jnp": lambda q, ck, pos: bsa.prefill_block_choice(
            q, ck, 0, table, pos, valid, sizes, "jnp"),
        "choice_kernel": lambda q, ck, pos: bsa.prefill_block_choice(
            q, ck, 0, table, pos, valid, sizes, "pallas"),
    }
    for tq in (int(t) for t in args.tiles.split(",") if t):
        forms[f"scores_kernel_tq{tq}"] = scores_kernel(tq)
    forms = {n: named(n, f) for n, f in forms.items()}
    # the search at `topk_mask`'s own 16 rows a grid step (the jnp form's
    # and decode's) and at the prefill kernel path's
    searches = {
        name: named(name, partial(
            lambda rows, P, pos: bsa._forced_topk(P, pos, valid, sizes,
                                                  rows), rows))
        for name, rows in (("search_rows16", 0),
                           (f"search_rows{bsa._SEARCH_ROWS}",
                            bsa._SEARCH_ROWS))}
    tile = pbc.choice_tile(T, NH // NKV, HD, per, PAGES * 8 // per, 2)
    vmem = 2 * pbc.vmem_bytes(tile, NH // NKV, HD, per, PAGES * 8 // per, 2)

    for ctx in contexts:
        pos = jnp.asarray(ctx - T + np.arange(T), jnp.int32)
        read = np.asarray(valid & (pos + 1 > sizes.dense_len))
        got = {}
        for name, fn in forms.items():
            ms, out = device_ms(name, fn, q, ck, pos)
            got[name] = np.asarray(out)
            line = {"context": ctx, "form": name, "device_ms": ms,
                    "queries_read": int(read.sum())}
            if name.startswith("scores_kernel"):
                want = got["scores_jnp"][read]
                line["P_max_rel_diff"] = float(np.max(
                    np.abs(got[name][read] - want)
                    / np.maximum(np.abs(want), 1e-30), initial=0.0))
                line["P_max_abs_diff"] = float(np.max(
                    np.abs(got[name][read] - want), initial=0.0))
            if name == "choice_kernel":
                differ = (got[name] != got["choice_jnp"]).any(-1)
                # how near a tie a set that differs was: the jnp form's
                # score of the block one side took and the other left,
                # over the row's own largest free score
                P, gap = got["scores_jnp"], 0.0
                for r, g in zip(*np.nonzero(differ)):
                    a, b = got[name][r, g], got["choice_jnp"][r, g]
                    free = P[r, g][a | b]
                    gap = max(gap, float(
                        np.ptp(P[r, g][a ^ b]) / max(free.max(), 1e-30)))
                line["largest_gap_of_a_set_that_differs"] = gap
                n_tiles = -(-PAGES * 8 // per // 128)
                visit = pbc.frontier(pos, jnp.asarray(read), sizes, tile,
                                     n_tiles)[1]
                line.update(
                    sets_that_differ=int(differ.sum()),
                    sets=int(differ.size),
                    key_tiles_visited=int(visit.sum()),
                    key_tiles=int(visit.size * n_tiles),
                    tile_queries=tile, vmem_limit_bytes=vmem)
            print(json.dumps(line), flush=True)
        for name, fn in searches.items():
            ms, _ = device_ms(name, fn, jnp.asarray(got["scores_jnp"]), pos)
            print(json.dumps({"context": ctx, "form": name,
                              "device_ms": ms}), flush=True)

    if args.program:
        program_rows(args.program, contexts)
    return 0


def program_rows(name, contexts):
    """One 2048-token prefill program of configuration `name` at the
    long-document cell's cache, the choice as jnp and as kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import spec
    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import get_family
    from dynamo_tpu.ops import block_sparse_attention as bsa

    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(spec.REPO_ROOT, entry["file"])) as f:
        config = json.load(f)
    cfg = spec.model_class(config).program_config(
        source_keys(config, False), name)
    cfg = dataclasses.replace(cfg, attn_impl="pallas")
    family = get_family(cfg)
    # the weights are an argument: closed over they are constants
    params = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.PRNGKey(int(config["engine"]["weights_seed"])))
    jax.block_until_ready(params)
    nb, lanes = 1 + 8 * PAGES, 8
    rng = np.random.default_rng(1)
    table = jnp.asarray(1 + rng.permutation(nb - 1)[:PAGES], jnp.int32)
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, T), jnp.int32)
    rule, seen = bsa.choice_impl, {}
    for form in ("jnp", "kernel"):
        # the A/B: the parent's program is the rule answering "jnp"
        bsa.choice_impl = rule if form == "kernel" \
            else (lambda impl, rows: "jnp")
        jax.clear_caches()

        def prefill(kv, w, pos, ctx):
            return family.prefill(w, cfg, kv, toks, pos, table, ctx,
                                  jnp.int32(T - 5), lanes=jnp.int32(2))

        mod = f"prefill_{form}"
        fn = named(mod, prefill, donate_argnums=(0,))
        for ctx in contexts:
            # random K, V and compressed keys: the choices spread
            kv = tuple(
                (jax.random.normal(jax.random.PRNGKey(i), s, jnp.float32)
                 * 0.7).astype(d) if i < 3 else jnp.zeros(s, d)
                for i, (s, d) in enumerate(zip(
                    family.kv_cache_shapes(cfg, nb, BS, lanes=lanes),
                    family.kv_cache_dtypes(cfg))))
            pos = jnp.asarray(ctx - T + np.arange(T), jnp.int32)
            state = {"kv": kv}

            def call(w, pos, c):
                logits, state["kv"] = fn(state["kv"], w, pos, c)
                return logits

            ops = collections.Counter()
            ms, logits = device_ms(mod, call, params, pos,
                                   jnp.int32(ctx - T), ops=ops)
            # five calls each: the device's own counts and the logits of
            # the two forms side by side (the same sets run the same
            # flash tiles)
            seen[form, ctx] = (np.asarray(state["kv"][4]).tolist(),
                               np.asarray(logits, np.float32))
            top = [[n, round(v, 4)] for n, v in ops.most_common(60)]
            with open(os.path.join(OUT, f"{mod}_{ctx}.json"), "w") as f:
                json.dump(top, f, indent=0)
            # an op's name is the head of its HLO text: the jnp form's
            # loops give out pred[8, 256, ...], the kernel form's choice
            # is a conditional
            named_ops = {
                "while": sum(v for n, v in ops.items()
                             if n.startswith("%while") and "pred[" in n),
                "custom_calls": sum(v for n, v in ops.items()
                                    if "custom-call(" in n),
                "conditional": sum(v for n, v in ops.items()
                                   if n.startswith("%cond")),
            }
            line = {"program": name, "form": form, "context": ctx,
                    "device_ms": ms, "device_counters": seen[form, ctx][0],
                    **{k: round(v, 4) for k, v in named_ops.items()}}
            if form == "kernel":
                line["logits_max_abs_diff_from_jnp"] = float(np.abs(
                    seen[form, ctx][1] - seen["jnp", ctx][1]).max())
            print(json.dumps(line), flush=True)
            del state, kv
    bsa.choice_impl = rule


if __name__ == "__main__":
    sys.exit(main())
