#!/usr/bin/env python3
"""Engine-path LOGITS against the float32 reference for the gated
short-convolution family (models/lfm2.py, ops/gated_conv.py), at the
configuration's own widths and the long-context cell's engine sizes, on
the chip.

    python3 benchmark/chip_logits_lfm2.py [--skip-controls]

What `lib/correct.py` (264 positions: ONE prefill program, no tail ever
carried) cannot reach.  A prompt of 6400 tokens through the family's own
`prefill_packed` in the cell's 2048-token chunks (2048 x 3, then 256
padded to the 512-token bucket: every conv layer's tail carried three
times, a chunk shorter than its bucket, the packed Pallas read at
64-wide heads over 16, 32 and 50 blocks and the scan on the last), on a
lane that held another sequence before (no program clears a lane); then
24 teacher-forced decode steps from position 6400 through the cache (the
paged Pallas read at 64 wide, the visited experts), in an 8-lane step in
which another lane decodes a sequence of its own.  (ISSUE 55 asked for 8
steps; 24 make the median over the positions steadier at 4 ms a step.)

Checked: every chunk's last position and the 24 decode positions (28),
as max |program - reference| over the vocabulary as a share of the
position's logit range (max - min): the median over the positions, the
quartiles, the worst, and how often the argmax agrees.

With random weights a token's fourth and fifth expert score lie a hair
apart, a pick flips against the float32 reference at bf16-level noise,
and every later position is read through what the flip moved (PERF.md
section 7t): a comparison of logits then reads which picks flipped, not
the arithmetic, and cannot tell this program from one computed in
bfloat16 throughout.  So the program runs TWICE: once choosing its own
experts (`program_free`: printed, not judged), and once ROUTED BY THE
REFERENCE'S CHOICE (`moe_forced_picks` in every expert layer's tree,
models/moe.py `ds_router`; the weights of the chosen are still the
program's own scores).  The second run is judged, against the same
reference, and so are three CONTROLS routed by the same choice, each of
which must read over a limit: the reference with a SiLU behind the
convolution (the repo's other convolutions' habit), without the gate
behind it, and the reference computed in bfloat16 (the precision below
the one stated: a bfloat16 stream and bfloat16 sums where the program
keeps float32).

`--weights-seeds a,b,c` repeats all of that under other draws of the
weights in the one process (the limits stand between the LARGEST sound
reading and the SMALLEST control reading over the seeds tried, PERF.md
section 6, PR 55).

The parts ALONE: both Pallas reads at 64-wide heads against their jnp
forms on random operands (the packed read of a 2048-token chunk over
contexts of 0, 8192 and 22528 tokens; the decode read of 8 lanes at
8-25k tokens), |kernel - jnp| / |jnp| held to `TOL_READ`, and each
one's milliseconds as 20 dependent calls in one program; the operator's
two forms are timed the same way (not judged: tier-1 holds them to the
reference in float32).

Exits 1 where, under any seed, the routed program reads over
`TOL_MEDIAN` or `TOL_WORST`, a control under both, or a read alone over
`TOL_READ`.  Without a TPU it fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import spec  # noqa: E402

# Limits over the 28 positions, every run ROUTED BY THE REFERENCE'S
# CHOICE, at published widths and 9 layers under `weights_seed` 23 / 24
# / 25 (my chip runs, PR 55; PERF.md section 6 has the table).  Each
# stands between the LARGEST reading of the sound program and the
# SMALLEST of the control it tells from it, over the three seeds:
#   TOL_MEDIAN    the program 0.0103 - 0.0110, the bfloat16 reference
#                 0.0157 - 0.0162 (a SiLU behind the convolution 0.637 -
#                 0.664, no gate behind it 0.710 - 0.745)
#   TOL_WORST     the program 0.0124 - 0.0140, the bfloat16 reference
#                 0.0198 - 0.0206 (the other two 0.742 - 0.795, 0.813 -
#                 0.840)
#                 The bfloat16 reference stands only 1.4 - 1.5 x over the
#                 program, not 3 x: the program's matmul operands ARE
#                 bfloat16 (what the configuration states), and their
#                 rounding is two thirds of an all-bfloat16
#                 computation's distance; each side spreads by 7 % over
#                 the seeds, so a limit a fifth over the one and a
#                 seventh under the other tells them apart.  (Choosing
#                 its own experts the program read a median of 0.013 -
#                 0.036 and a worst of 0.031 - 0.075: which picks
#                 flipped.)
#   TOL_READ      a read alone, |kernel - jnp| / |jnp|: the packed read
#                 1.5e-5 - 1.7e-5 at contexts 0 / 8192 / 22528, the
#                 decode read 2.9e-3; unrelated outputs would read
#                 sqrt 2.  (lib/correct.py allows an emitted token
#                 0.04.)
TOL_MEDIAN = 0.0133
TOL_WORST = 0.0168
TOL_READ = 0.02
CELL = "lfm2-24b-a2b.longctx-closed"
PROMPT, CHUNK, STEPS = 6400, 2048, 24
BEFORE = 100            # tokens of the sequence that held the lane before


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--skip-controls", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: walks the script only")
    ap.add_argument("--seed", type=int, default=20261004)
    ap.add_argument("--weights-seeds", default="",
                    help="comma-separated, in place of the configuration's "
                         "engine.weights_seed")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.runtime.device import device_identity, require_tpu

    ident = device_identity() if args.rehearse else require_tpu()
    cell = spec.load_cell(CELL)
    config = cell["config"]                 # with the cell's engine sizes
    klass = spec.model_class(config)
    cfg = klass.program_config(source_keys(config, args.rehearse),
                               cell["config_entry"]["name"])
    sizes = dict(config["engine"])
    prompt, chunk, steps, before = PROMPT, CHUNK, STEPS, BEFORE
    if args.rehearse:
        sizes.update(config["rehearse"]["engine"])
        prompt, chunk, steps, before = 100, 32, 4, 20
    buckets = list(sizes["prefill_buckets"])
    bs, lanes = int(sizes["block_size"]), int(sizes["max_num_seqs"])
    table_w, pool = int(sizes["max_blocks_per_seq"]), int(sizes["num_blocks"])
    k_picks = cfg.experts_per_token
    lane, other_lane = 2, lanes - 1
    t0 = time.perf_counter()
    impl = resolve_decode_impl(cfg.attn_impl, ident["platform"], bs,
                               cfg.head_dim, cfg.dtype)
    print(f"device {ident}; decode read {impl}", flush=True)
    rng = np.random.default_rng(args.seed)
    toks = rng.integers(3, cfg.vocab_size, prompt + steps)
    past = rng.integers(3, cfg.vocab_size, before)
    o_toks = rng.integers(3, cfg.vocab_size, 20 + steps)
    # scattered blocks, not 1, 2, 3; the other lane's behind them
    need = -(-(prompt + steps) // bs)
    tables = np.zeros((lanes, table_w), np.int32)
    tables[lane, :need] = 1 + 2 * np.arange(need)
    tables[other_lane, 0] = 2 * need + 2
    assert tables.max() < pool and need <= table_w

    # the weights are an argument: a closure would bake them into the
    # program as constants
    prefill = jax.jit(lambda kv, w, *a, **k: lfm2.prefill_packed(
        w, cfg, kv, *a, **k), donate_argnums=(0,))
    decode = jax.jit(lambda kv, w, *a, **k: lfm2.decode(
        w, cfg, kv, *a, **k), donate_argnums=(0,))

    def on_lanes(a, b, dtype=np.int32):
        out = np.zeros(lanes, dtype)
        out[lane], out[other_lane] = a, b
        return jnp.asarray(out)

    def program(params, picks=None):
        """The prompt and the decode steps through the cache -> (logits a
        checked position, the device counters).  `picks`, a list a layer
        of the experts [prompt + steps, k] to route the checked
        sequence by (None at a dense layer), or None: the program's own
        choice."""
        some = np.tile(np.arange(k_picks, dtype=np.int32), (1, 1))

        def routed(rows_of):
            """`params` whose expert layers carry `rows_of(a layer's
            picks)` as the choice; rows of other sequences and padding
            any k distinct experts."""
            if picks is None:
                return params
            return dict(params, layers=[
                layer if ids is None else dict(
                    layer, moe_forced_picks=jnp.asarray(rows_of(ids)))
                for layer, ids in zip(params["layers"], picks)])

        def feed(kv, ln, seq, pos, n, judged=False):
            """One packed stream of one segment row, as the planner
            builds it: the bucket's tail padded, the table cut to a power
            of two of the blocks touched."""
            bucket = next(b for b in buckets if b >= n)
            width = 1
            while width < -(-(pos + n) // bs):
                width *= 2
            width = min(width, table_w)
            t, p = np.zeros(bucket, np.int32), np.zeros(bucket, np.int32)
            t[:n], p[:n] = seq[pos:pos + n], pos + np.arange(n)

            def rows_of(ids):
                out = np.repeat(some, bucket, 0)
                if judged:
                    out[:n] = ids[pos:pos + n]
                return out

            return prefill(
                kv, routed(rows_of), jnp.asarray(t), jnp.asarray(p),
                jnp.zeros(bucket, jnp.int32),
                jnp.asarray(tables[ln:ln + 1, :width]),
                jnp.asarray([n - 1], jnp.int32),
                jnp.asarray(np.arange(bucket) < n),
                lanes=jnp.asarray([ln], jnp.int32))

        kv = tuple(jnp.zeros(s, d) for s, d in zip(
            lfm2.kv_cache_shapes(cfg, pool, bs, lanes=lanes),
            lfm2.kv_cache_dtypes(cfg)))
        rows = {}
        _, kv = feed(kv, lane, past, 0, before)         # the lane's past
        _, kv = feed(kv, other_lane, o_toks, 0, 20)
        pos = 0
        while pos < prompt:
            n = min(chunk, prompt - pos)
            logits, kv = feed(kv, lane, toks, pos, n, judged=True)
            pos += n
            rows[pos - 1] = np.asarray(logits[0], np.float32)
        valid = on_lanes(True, True, bool)
        for i in range(steps):
            at = on_lanes(prompt + i, 20 + i)

            def rows_of(ids, i=i):
                out = np.repeat(some, lanes, 0)
                out[lane] = ids[prompt + i]
                return out

            logits, kv = decode(
                kv, routed(rows_of),
                on_lanes(toks[prompt + i], o_toks[20 + i]), at,
                jnp.asarray(tables), at, valid=valid)
            rows[prompt + i] = np.asarray(logits[lane], np.float32)
        return rows, dict(zip(lfm2.KV_COUNTERS, np.asarray(kv[-1]).tolist()))

    def read(rows, want):
        shares = [float(np.abs(rows[p] - want[p]).max()
                        / (want[p].max() - want[p].min()))
                  for p in sorted(rows)]
        return {"median": float(np.median(shares)),
                "quartiles": [float(np.percentile(shares, q))
                              for q in (25, 75)],
                "worst": max(shares),
                "argmax_agree": int(sum(
                    int(rows[p].argmax() == want[p].argmax())
                    for p in rows))}

    def within(r):
        return bool(r["median"] <= TOL_MEDIAN and r["worst"] <= TOL_WORST)

    held = {}       # one draw of the weights at a time fits the chip

    def one_seed(weights_seed):
        t1 = time.perf_counter()
        held.clear()
        params = held["params"] = jax.jit(lambda key: lfm2.init_params(cfg, key))(
            jax.random.PRNGKey(weights_seed))
        jax.block_until_ready(params)
        free, counters = program(params)
        at = sorted(free)
        print(f"weights seed {weights_seed}: the program on its own "
              f"choice done at {time.perf_counter() - t1:.1f}s; counters "
              f"{counters}", flush=True)

        def reference(leave_out="", **kw):
            got = klass.reference_forward(params, cfg, toks.tolist(),
                                          leave_out, at=at, **kw)
            if kw.get("return_picks"):
                return dict(zip(at, np.asarray(got[0]))), got[1]
            return dict(zip(at, np.asarray(got)))

        want, picks = reference(return_picks=True)
        picks = [np.asarray(ids, np.int32) if ids.shape[1] else None
                 for ids in picks]
        out = {"weights_seed": weights_seed,
               "program_free": read(free, want)}
        del free
        routed, _ = program(params, picks)
        out["program"] = read(routed, want)
        out["ok"] = within(out["program"])
        print(f"  routed by the reference's choice at "
              f"{time.perf_counter() - t1:.1f}s: {json.dumps(out)}",
              flush=True)
        if not args.skip_controls:
            out["controls"] = {
                "conv_silu": read(reference("conv_silu", picks=picks), want),
                "no_conv_gate_out": read(
                    reference("no_conv_gate_out", picks=picks), want),
                "bf16_reference": read(reference(
                    compute_dtype=jnp.bfloat16, picks=picks), want),
            }
            out["controls_fail"] = {k: not within(r)
                                    for k, r in out["controls"].items()}
            out["ok"] = bool(out["ok"]
                             and all(out["controls_fail"].values()))
        print(f"  seed {weights_seed} done at "
              f"{time.perf_counter() - t1:.1f}s: {json.dumps(out)}",
              flush=True)
        return out

    def parts_alone():
        """Both reads at this head width, kernel against jnp form, and
        the operator's two forms: errors and milliseconds."""
        from dynamo_tpu.ops.gated_conv import (gated_conv_packed,
                                               gated_conv_step, packed_rows)
        from dynamo_tpu.ops.packed_prefill import packed_prefill_attention
        from dynamo_tpu.ops.paged_attention import paged_attention_decode

        kernel = "pallas_interpret" if args.rehearse else "pallas"
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 8)
        nkv, nh, hd, d = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim, \
            cfg.d_model
        T, N = (32, 2) if args.rehearse else (CHUNK, 20)
        kc, vc = (jax.random.normal(k, (2, nkv, pool, hd, bs), cfg.dtype)
                  for k in ks[:2])
        err = lambda a, b: float(
            jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
            / jnp.linalg.norm(b.astype(jnp.float32)))

        def ms(fn, x, *rest):
            """`fn(x, *rest)` N times, each call's x the last one's
            result bent back in, in ONE program."""
            many = jax.jit(lambda x, *r: jax.lax.fori_loop(
                0, N, lambda _, x: x + (1e-6 * fn(x, *r)).astype(x.dtype),
                x))
            jax.block_until_ready(many(x, *rest))
            t = time.perf_counter()
            jax.block_until_ready(many(x, *rest))
            return (time.perf_counter() - t) / N * 1e3

        got = {"packed_read": {}, "decode_read": {}, "operator_ms": {}}
        q = jax.random.normal(ks[2], (T, nh, hd), cfg.dtype)
        table = jnp.asarray(1 + np.arange(table_w, dtype=np.int32))[None]
        stream = (jnp.zeros(T, jnp.int32), jnp.ones(T, bool))
        for ctx in ((0, 32) if args.rehearse else (0, 8192, 22528)):
            pos = ctx + jnp.arange(T, dtype=jnp.int32)
            # the pools are ARGUMENTS of the timed program: closed over
            # they would be constants of the executable
            read = lambda q, kc, vc, impl=kernel, pos=pos: \
                packed_prefill_attention(q, kc, vc, 1, table, stream[0],
                                         pos, stream[1], impl=impl)
            got["packed_read"][ctx] = {
                "error": err(read(q, kc, vc), read(q, kc, vc, "xla")),
                "kernel_ms": ms(read, q, kc, vc)}
        q1 = jax.random.normal(ks[3], (lanes, nh, hd), cfg.dtype)
        tabs = jnp.asarray(1 + (np.arange(lanes * table_w, dtype=np.int32)
                                % (pool - 1)).reshape(lanes, table_w))
        lens = jnp.asarray(np.linspace(
            bs, min(25000, table_w * bs), lanes).astype(np.int32))
        dec = lambda q, kc, vc, impl=kernel: paged_attention_decode(
            q, kc, vc, 1, tabs, lens, impl=impl)
        got["decode_read"] = {
            "error": err(dec(q1, kc, vc), dec(q1, kc, vc, "jnp")),
            "kernel_ms": ms(dec, q1, kc, vc)}
        b, c, u = (jax.random.normal(k, (T, d), jnp.float32)
                   for k in ks[4:7])
        w = held["params"]["layers"][0]["conv_w"]
        rws = packed_rows(*stream, 1)
        start = jnp.zeros((1, cfg.conv_width - 1, d), cfg.dtype)
        got["operator_ms"] = {
            "packed": ms(lambda u: gated_conv_packed(
                b, c, u, w, rws, start)[0], u),
            "step": ms(lambda u: gated_conv_step(
                b[:lanes], c[:lanes], u, w,
                jnp.zeros((lanes, cfg.conv_width - 1, d), cfg.dtype))[0],
                u[:lanes])}
        return got

    seeds = [int(x) for x in args.weights_seeds.split(",") if x] \
        or [int(sizes["weights_seed"])]
    runs = []
    for weights_seed in seeds:
        runs.append(one_seed(weights_seed))
    parts = parts_alone()
    print(f"parts alone: {json.dumps(parts)}", flush=True)
    reads = [r["error"] for r in parts["packed_read"].values()]
    reads.append(parts["decode_read"]["error"])
    out = {"config": cfg.name, "device": ident,
           "limits": {"median": TOL_MEDIAN, "worst": TOL_WORST,
                      "read_alone": TOL_READ},
           "parts_alone": parts, "runs": runs,
           "ok": bool(all(r["ok"] for r in runs)
                      and max(reads) <= TOL_READ)}
    print(f"done at {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
