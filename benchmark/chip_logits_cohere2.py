#!/usr/bin/env python3
"""Engine-path LOGITS against the float32 reference for the window +
NoPE-global family (models/cohere2.py), at the configuration's own
widths and the long-context cell's engine sizes, on the chip.

    python3 benchmark/chip_logits_cohere2.py [--skip-omissions]

What `lib/correct.py` (264 positions: inside one window, one prefill
program, a ring that never wraps) cannot reach.  Two prompts through the
family's own `prefill_packed` in 2048-token chunks and then 16
teacher-forced decode steps through the cache, both lanes in one decode
program:

    A   6400 tokens on lane 2, which held another sequence before (no
        program clears a lane): chunks of 2048 x 3 and 256 (a bucket of
        512); the band leaves the first chunk behind at 4096, the ring
        (33 blocks, 4224 cells) wraps at 4224, the third and fourth
        chunk read a tail that the wrap wrote
    B   12800 tokens on lane 5 (the last lane where there are fewer): 6 x 2048 and 512; three windows long,
        the global layer reads 100 blocks where a window layer reads 33

Checked: every chunk's last position and the 16 decode positions of both
(11 + 32 positions).  Printed: the median, the quartiles and the worst
of |program - reference| as a share of the position's logit range
(max - min), and how often the argmax agrees.  Then the reference with
each of four published details left out, over B: no window mask, rotary
on the global layer, the shared experts' sum not divided by 4, the norm
without its mean: each must read over the limit.

The three omissions that act inside one part of a block are ALSO judged
where they act, which says which part an end-to-end reading comes from:
one window layer and the global layer ALONE on a random normed input,
the program's own projections, writes and reads (B's chunks through
`window_prefill_flash` / `packed_prefill_attention`, then the decode
steps through `paged_attention_decode` with and without the lower
bound) against the reference's layer, with and without the detail: the
relative error of the layer's output rows.

The shared experts' mean is judged the same way (one layer's experts
alone on 2048 random normed rows).

Exits 1 where the program reads over `TOL_LOGITS` or `TOL_LAYER`, or an
omission under them (each of the four over `TOL_LOGITS` end to end, and
the three that act inside one part of a block over `TOL_LAYER` in that
part alone).  Without a TPU it fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import spec  # noqa: E402

# Limits, each between two readings at published widths, 4 layers and
# `weights_seed` 23 (my chip runs, PR 42; PERF.md section 6 has the table).
#   TOL_LOGITS  the MEDIAN share of the range over the 43 positions: the
#               program read 0.0039 (quartiles 0.0036 / 0.0042; worst
#               0.085, argmax agreeing at 42); left out, over B's 23
#               positions: the norm's mean 0.0199 (0.014 / 0.028), rotary
#               on the global layer 0.0498, the window's bound 0.214, the
#               shared experts' mean 0.344.  (lib/correct.py allows an
#               emitted token 0.04.)  The WORST position is printed and
#               not judged: with random weights one pick that flips
#               against the float32 reference moves one position far
#               (PERF.md section 7t).
#   TOL_LAYER   one part of a block alone, |program - reference| /
#               |reference|: bf16 operands against float32 read 0.0040
#               (window read), 0.0033 (global read), 0.0027 (the
#               experts); without the window's bound 0.72, with rotary on
#               the global layer 1.14, the shared experts' sum not
#               divided 0.75.  These say WHICH part an end-to-end
#               reading over its limit comes from, and they alone told
#               three of the omissions from the program in this PR's
#               first build, whose routed experts' weights were drawn
#               64 times too large and drowned everything else in the
#               stream (PERF.md section 6, PR 42).
TOL_LOGITS = 0.009
TOL_LAYER = 0.05
OMISSIONS = ("window", "nope", "shared_mean", "norm_mean")
A_TOKENS, B_TOKENS, CHUNK, STEPS, BEFORE = 6400, 12800, 2048, 16, 300


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="command-a-plus-05-2026-4l-ep8")
    ap.add_argument("--skip-omissions", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: walks the script only")
    ap.add_argument("--seed", type=int, default=20261001)
    ap.add_argument("--weights-seed", type=int, default=None,
                    help="in place of the configuration's engine.weights_seed")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import cohere2
    from dynamo_tpu.runtime.device import device_identity, require_tpu

    ident = device_identity() if args.rehearse else require_tpu()
    cell = spec.load_cell("command-a-plus.longctx-closed")
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(spec.REPO_ROOT, entry["file"])) as f:
        config = json.load(f)
    klass = spec.model_class(config)
    cfg = klass.program_config(source_keys(config, args.rehearse),
                               args.config)
    sizes = dict(cell["config"]["engine"])
    a_len, b_len, chunk, steps, before = (A_TOKENS, B_TOKENS, CHUNK, STEPS,
                                          BEFORE)
    buckets = list(sizes["prefill_buckets"])
    if args.rehearse:
        sizes.update(config["rehearse"]["engine"])
        a_len, b_len, chunk, steps, before = 100, 200, 32, 6, 20
        buckets = [16, 32]
    bs, lanes = int(sizes["block_size"]), int(sizes["max_num_seqs"])
    table_w = int(sizes["max_blocks_per_seq"])
    lane_a, lane_b = 2, min(5, lanes - 1)
    t0 = time.perf_counter()
    weights_seed = int(sizes["weights_seed"]) if args.weights_seed is None \
        else args.weights_seed
    params = jax.jit(lambda key: cohere2.init_params(cfg, key))(
        jax.random.PRNGKey(weights_seed))
    jax.block_until_ready(params)
    print(f"device {ident}; weights in {time.perf_counter() - t0:.1f}s",
          flush=True)
    rng = np.random.default_rng(args.seed)
    seqs = {lane_a: rng.integers(3, cfg.vocab_size, a_len + steps),
            lane_b: rng.integers(3, cfg.vocab_size, b_len + steps)}
    other = rng.integers(3, cfg.vocab_size, before)
    # scattered tables, not 1, 2, 3; the two lanes' blocks interleave
    tables = np.zeros((lanes, table_w), np.int32)
    for i, lane in enumerate(seqs):
        need = -(-len(seqs[lane]) // bs)
        tables[lane, :need] = 1 + i + 2 * np.arange(need)
    num_blocks = 2 + 2 * table_w

    kv = tuple(jnp.zeros(s, d) for s, d in zip(
        cohere2.kv_cache_shapes(cfg, num_blocks, bs, lanes=lanes),
        cohere2.kv_cache_dtypes(cfg)))
    # the weights are an argument: a closure would bake them into the
    # program as constants
    prefill = jax.jit(lambda kv, w, *a, **k: cohere2.prefill_packed(
        w, cfg, kv, *a, **k), donate_argnums=(0,))
    decode = jax.jit(lambda kv, w, *a, **k: cohere2.decode(
        w, cfg, kv, *a, **k), donate_argnums=(0,))

    def feed(kv, lane, seq, pos, n):
        """One packed stream of one segment row, as the planner builds
        it: the bucket's tail padded, the table cut to a power of two
        of the blocks touched."""
        bucket = next(b for b in buckets if b >= n)
        width = 1
        while width < -(-(pos + n) // bs):
            width *= 2
        width = min(width, table_w)
        t = np.zeros(bucket, np.int32)
        t[:n] = seq[pos:pos + n]
        p = np.zeros(bucket, np.int32)
        p[:n] = pos + np.arange(n)
        return prefill(
            kv, params, jnp.asarray(t), jnp.asarray(p),
            jnp.zeros(bucket, jnp.int32), jnp.asarray(tables[lane:lane + 1,
                                                             :width]),
            jnp.asarray([n - 1], jnp.int32), jnp.asarray(np.arange(bucket)
                                                         < n),
            lanes=jnp.asarray([lane], jnp.int32))

    rows = {lane: {} for lane in seqs}
    _, kv = feed(kv, lane_a, other, 0, before)          # the lane's past
    for lane, n_prompt in ((lane_a, a_len), (lane_b, b_len)):
        pos = 0
        while pos < n_prompt:
            n = min(chunk, n_prompt - pos)
            logits, kv = feed(kv, lane, seqs[lane], pos, n)
            pos += n
            rows[lane][pos - 1] = np.asarray(logits[0], np.float32)
    print(f"prefill done at {time.perf_counter() - t0:.1f}s", flush=True)

    def on_lanes(values, dtype=np.int32):
        a = np.zeros((lanes,) + np.shape(next(iter(values.values()))),
                     dtype)
        for lane, v in values.items():
            a[lane] = v
        return jnp.asarray(a)

    valid = on_lanes({lane: True for lane in seqs}, bool)
    start = {lane_a: a_len, lane_b: b_len}
    for i in range(steps):
        at = {lane: start[lane] + i for lane in seqs}
        logits, kv = decode(
            kv, params, on_lanes({ln: seqs[ln][at[ln]] for ln in seqs}),
            on_lanes(at), jnp.asarray(tables), on_lanes(at), valid=valid)
        for lane in seqs:
            rows[lane][at[lane]] = np.asarray(logits[lane], np.float32)
    counters = np.asarray(kv[4]).tolist()
    del kv
    print(f"program done at {time.perf_counter() - t0:.1f}s; counters "
          f"{dict(zip(cohere2.KV_COUNTERS, counters))}", flush=True)

    def reference(lane, leave_out=""):
        at = sorted(rows[lane])
        x = klass.hidden_states(params, cfg, seqs[lane].tolist(), leave_out)
        logits = klass.head_logits(params, cfg, x[jnp.asarray(at)])
        return dict(zip(at, np.asarray(logits)))

    def read(got, want):
        shares = [float(np.abs(got[p] - want[p]).max()
                        / (want[p].max() - want[p].min()))
                  for p in sorted(got)]
        return {"positions": len(shares),
                "median": float(np.median(shares)),
                "quartiles": [float(np.percentile(shares, q))
                              for q in (25, 75)],
                "worst": max(shares),
                "argmax_agree": int(sum(
                    int(got[p].argmax() == want[p].argmax())
                    for p in got))}

    def layer_alone(kind, detail):
        """One layer of `kind` alone over B's positions on a random
        normed input -> {"reference": e, "without": e}: the relative
        error of the program's output rows (every chunk's last 64 rows
        and the decode steps) against the reference's layer, as
        published and with `detail` left out."""
        from dynamo_tpu.ops.packed_prefill import (
            packed_prefill_attention,
            write_packed_kv,
        )
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode,
            write_token_kv,
        )
        from dynamo_tpu.ops.window_attention import (
            ring_blocks,
            ring_decode_table,
            ring_table,
            window_prefill_flash,
        )
        li = cfg.layers_of(kind)[0]
        layer = params["layers"][li]
        total = b_len + steps
        h = jax.random.normal(jax.random.PRNGKey(args.seed % (1 << 31)),
                              (total, cfg.d_model)).astype(cfg.dtype)
        window, W = cfg.sliding_window, ring_blocks(cfg.sliding_window, bs)
        member = 2 if kind == cohere2.WINDOW else 0
        shape = cohere2.kv_cache_shapes(cfg, num_blocks, bs,
                                        lanes=lanes)[member]
        cache = tuple(jnp.zeros((1,) + shape[1:], cfg.dtype)
                      for _ in range(2))
        table = jnp.asarray(tables[lane_b:lane_b + 1])
        lane1 = jnp.asarray([lane_b], jnp.int32)
        rings = ring_table(lane1, W, table_w)

        # the layer's weights are an argument (a closure would bake
        # them into every program as constants)
        @jax.jit
        def chunk_fn(layer, cache, h, pos, n):
            T = h.shape[0]
            positions = pos + jnp.arange(T, dtype=jnp.int32)
            valid = jnp.arange(T) < n
            seg = jnp.zeros(T, jnp.int32)
            q, k, v = cohere2._qkv(layer, cfg, kind, h, positions)
            if kind == cohere2.WINDOW:
                attn = window_prefill_flash(q, k, v, *cache, 0, lane1, seg,
                                            positions, valid, window)
                cache = write_packed_kv(*cache, 0, k, v, rings, seg,
                                        positions, valid)
            else:
                cache = write_packed_kv(*cache, 0, k, v, table, seg,
                                        positions, valid)
                attn = packed_prefill_attention(q, *cache, 0, table, seg,
                                                positions, valid)
            return cache, cohere2._attn_out(layer, attn)

        @jax.jit
        def step_fn(layer, cache, h, pos):
            lanes_pos = jnp.zeros(lanes, jnp.int32).at[lane_b].set(pos)
            ok = jnp.arange(lanes) == lane_b
            hs = jnp.zeros((lanes, cfg.d_model), cfg.dtype).at[lane_b].set(h)
            q, k, v = cohere2._qkv(layer, cfg, kind, hs, lanes_pos)
            if kind == cohere2.WINDOW:
                tab = jnp.where(ok[:, None], ring_table(
                    jnp.arange(lanes, dtype=jnp.int32), W, table_w), 0)
                cache = write_token_kv(*cache, 0, k, v, tab, lanes_pos,
                                       resident=resident, valid=ok)
                w_table, w_lens, w_lo = ring_decode_table(lanes_pos, ok,
                                                          window, bs)
                attn = paged_attention_decode(q, *cache, 0, w_table, w_lens,
                                              kv_lo=w_lo)
            else:
                cache = write_token_kv(*cache, 0, k, v,
                                       jnp.asarray(tables), lanes_pos,
                                       resident=resident, valid=ok)
                attn = paged_attention_decode(
                    q, *cache, 0, jnp.asarray(tables),
                    jnp.where(ok, lanes_pos + 1, 0))
            return cache, cohere2._attn_out(layer, attn)[lane_b]

        from dynamo_tpu.ops.paged_attention import (
            PALLAS_IMPLS,
            resolve_decode_impl,
        )
        resident = resolve_decode_impl(
            "auto", jax.default_backend(), bs, cfg.head_dim,
            cfg.dtype) in PALLAS_IMPLS
        got, pos, tail = {}, 0, min(64, chunk)
        while pos < b_len:
            n = min(chunk, b_len - pos)
            bucket = next(b for b in buckets if b >= n)
            rows_h = jnp.zeros((bucket, cfg.d_model), cfg.dtype) \
                .at[:n].set(h[pos:pos + n])
            cache, y = chunk_fn(layer, cache, rows_h, jnp.int32(pos),
                                jnp.int32(n))
            for r in range(max(n - tail, 0), n):
                got[pos + r] = y[r]
            pos += n
        for p in range(b_len, total):
            cache, y = step_fn(layer, cache, h[p], jnp.int32(p))
            got[p] = y
        at = sorted(got)
        mine = jnp.stack([got[p] for p in at]).astype(jnp.float32)
        F32 = jnp.float32

        def want(layer, h, leave_out):
            T = total
            hf = h.astype(F32)
            heads = lambda w, n: (hf @ w.astype(F32)).reshape(
                T, n, cfg.head_dim)
            q, k, v = (heads(layer["wq"], cfg.n_heads),
                       heads(layer["wk"], cfg.n_kv_heads),
                       heads(layer["wv"], cfg.n_kv_heads))
            if kind == cohere2.WINDOW or leave_out == "nope":
                q = klass._rope(q, jnp.arange(T), cfg.rope_theta)
                k = klass._rope(k, jnp.arange(T), cfg.rope_theta)
            win = window if kind == cohere2.WINDOW \
                and leave_out != "window" else 0
            a = klass._attention(cfg, q, k, v, win)[jnp.asarray(at)]
            return a.reshape(len(at), -1) @ layer["wo"].astype(F32)

        out = {}
        with jax.default_matmul_precision("highest"):
            for name, leave_out in (("reference", ""), ("without", detail)):
                w = jax.jit(lambda lp, h, lo=leave_out: want(lp, h, lo))(
                    layer, h)
                out[name] = float(jnp.linalg.norm(mine - w)
                                  / jnp.linalg.norm(w))
        return out

    def experts_alone():
        """One layer's experts alone on a random normed input of 2048
        rows -> the relative error of the program's routed + averaged
        shared output against the reference's, as published and with the
        shared experts' sum not divided by their number."""
        layer = params["layers"][0]
        hf = jax.random.normal(jax.random.PRNGKey(1 + args.seed % (1 << 31)),
                               (chunk, cfg.d_model), jnp.float32)
        hf = hf.astype(cfg.dtype).astype(jnp.float32)   # both read the same
        mine = jax.jit(lambda lp, hf: cohere2._experts(
            lp, cfg, hf, hf.astype(cfg.dtype), None)[0])(layer, hf)

        def want(lp, hf, mean):
            w, ids = klass._route(cfg, lp, hf)
            return klass._routed(cfg, lp, hf, w, ids) \
                + klass._shared(cfg, lp, hf, mean)

        out = {}
        with jax.default_matmul_precision("highest"):
            for name, mean in (("reference", True), ("without", False)):
                w = jax.jit(lambda lp, hf, m=mean: want(lp, hf, m))(layer,
                                                                    hf)
                out[name] = float(jnp.linalg.norm(mine - w)
                                  / jnp.linalg.norm(w))
        return out

    refs = {lane: reference(lane) for lane in seqs}
    print(f"reference done at {time.perf_counter() - t0:.1f}s", flush=True)
    both = {(ln, p): r for ln in seqs for p, r in rows[ln].items()}
    want = {(ln, p): r for ln in seqs for p, r in refs[ln].items()}
    out = {"config": args.config, "device": ident,
           "weights_seed": weights_seed, "limit_median": TOL_LOGITS, "limit_layer": TOL_LAYER,
           "program": read(both, want),
           "program_by_lane": {str(ln): read(rows[ln], refs[ln])
                               for ln in seqs}}
    out["layers_alone"] = {
        "window": layer_alone(cohere2.WINDOW, "window"),
        "global": layer_alone(cohere2.GLOBAL, "nope"),
        "experts": experts_alone()}
    print(f"layers alone at {time.perf_counter() - t0:.1f}s: "
          f"{json.dumps(out['layers_alone'])}", flush=True)
    alone_ok = all(r["reference"] <= TOL_LAYER < r["without"]
                   for r in out["layers_alone"].values())
    out["ok"] = bool(out["program"]["median"] <= TOL_LOGITS and alone_ok)
    if not args.skip_omissions:
        out["left_out"] = {}
        for d in OMISSIONS:
            out["left_out"][d] = read(rows[lane_b], reference(lane_b, d))
            print(f"without {d} at {time.perf_counter() - t0:.1f}s: "
                  f"{json.dumps(out['left_out'][d])}", flush=True)
        out["omissions_fail"] = all(
            r["median"] > TOL_LOGITS for r in out["left_out"].values())
        out["ok"] = bool(out["ok"] and out["omissions_fail"])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
