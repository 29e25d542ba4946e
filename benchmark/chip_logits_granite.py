#!/usr/bin/env python3
"""Engine-path LOGITS and the state-space STATE itself against the
float32 reference, for the dense Mamba-2 hybrid
(models/granite_hybrid.py: every layer a mixer and a gated MLP under muP
multipliers), at the PUBLISHED widths and full depth (40 layers) and the
cell's sizes, on the chip.

    python3 benchmark/chip_logits_granite.py [--details [a,b]]

What `lib/correct.py` (264 positions, ONE prefill program from zeros, an
idle engine) cannot reach: the cell's cache (64 lanes, tables of 45
blocks, the cell's pool) and the programs the cell times.  A prompt of
4576 tokens is prefilled as three programs (2048 from zeros on a lane
that held another sequence before, 2048 from a CARRIED state, then 480
padded to 512: a chunk of the scan cut by the prompt's end); then 64
teacher-forced decode steps from position 4576 across the block boundary
at 4608, in a 64-lane step in which eight other lanes decode sequences
of their own, four of them only for the first half (a lane finishing
mid-way) and four only for the second (one joining).  The cache is then
freed: the reference's float32 layers do not fit beside 7.4 GiB of it.

Printed: the largest and the median |program - reference| as a share of
the position's logit range (max - min) over the three chunk ends and the
64 decode positions, and the state's own relative error
|S - S_ref|_F / |S_ref|_F in the first and the last Mamba layer, after
the prompt and after the decode steps.

The configuration states a float32 state, and the limit on the state has
to tell it from the precision below THROUGH THE PROGRAM.  With the
weights as `init_params` draws them most heads forget within a few
tokens, a rounding of the state is gone with them, and a bfloat16 state
read like the float32 one (0.0028 / 0.0036 beside 0.0023 / 0.0031, the
first build of this script: PERF.md section 6, PR 57).  So this script,
which draws the weights, puts the WATCHED first Mamba layer's `dt_bias`
and `a_log` in the slow-decay regime (`slow_first_layer`: dt about 0.01,
A in -(0.1 ... 1), the regime in which a recurrence drifts; the layer's
input is the embedding itself, so nothing upstream of it moves), and the
program is ALWAYS run a second time with `state_dtype` bfloat16 (the
cache member, `mamba2.mixer_prefill`'s put and the 64-lane decode step
all take the dtype from the member): that run has to FAIL `within`, by
`TOL_STATE` on the first layer's state after the 64 steps.

With `--details` the same comparison with each published detail left out
of the reference (both residual multipliers, the embedding multiplier, a
score scale of 1/8 for 1/64, the logits' division, a rotary, an untied
head, norm-before-gate; `--details rope,untied_head` for some of them),
each of which must FAIL the logits limit.  A ROTARY is not seen with the
weights as drawn (scores a sixty-fourth of q . k are a nearly flat
softmax over thousands of keys, and what four such layers add is under
bf16 noise), so it is judged on weights whose attention is SHARP
(`sharp_attention`: every attention layer's `wq` times 32, scores of
standard deviation about 4, a few keys a query): the program is run on
them and has to stay within `TOL_SHARP` of the reference on the same
weights, and the reference with a rotary has to lie outside it.

The SCAN ALONE is always run besides: the chunked program and the decode
step AS THE CELL RUNS IT (`ops/lane_state.lanes_step` over a member of
64 lanes, which is `ssd_lanes_step`'s kernel for a float32 member and
the jnp step for any other, nine lanes busy) at the configuration's head
shapes (ONE group for 64 heads, chunks of 256) on float32 inputs whose
decay is slow, the prompt in its three calls and 64 steps, against the
token recurrence at the highest precision; with no bf16 activations
beside it the limit is five times tighter than the program's
(`TOL_SCAN`), and a bfloat16 member must fail it too.  Exits 1 where a
reading passes its limit or a control passes.  Without a TPU it fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import spec  # noqa: E402

# Limits, each between two readings at published widths and all 40 layers
# (my chip run, PR 57, call `p57r_a`; PERF.md section 6 has the table).
# No router here: no pick can flip, every one of the 67 positions agrees
# on the argmax, the worst position reads 0.021; the MEDIAN is judged all
# the same, as the other state families' scripts do.
#   TOL_LOGITS   median share of the range: the program read 0.0105
#                (quartiles 0.0091 / 0.0119); the smallest left-out
#                multiplier 0.0193 (a score scale of 1/8 for 1/64: four
#                attention layers of forty, whose softmax over scores a
#                sixty-fourth of q . k is nearly flat with the weights as
#                drawn), norm-before-gate 0.35, the logits' division
#                0.53, both residual multipliers 0.68, the embedding's
#                0.75, an untied head 0.81.  (correct.py allows an
#                emitted token 0.04.)
#   TOL_STATE    the first Mamba layer's state under slow decay: the
#                program read 0.00090 after the prompt and 0.00087 after
#                the 64 steps (bf16 activations into a float32 state:
#                independent roundings average out over the hundred
#                tokens it holds); with a bfloat16 state 0.0020 and
#                0.0183 (64 roundings that do not decay: the steps carry
#                the verdict, the prompt's three roundings do not);
#                without the embedding multiplier 0.021.  The limit is
#                their geometric mean, 4.6 x from each (0.006, set from
#                the prediction before the run, passed the same).
#   TOL_SHARP    the logits on weights whose attention is sharp (`wq`
#                x 32): the program read 0.0128 (sharper scores carry
#                more of q's and k's bf16 rounding), the reference with
#                a ROTARY 0.265; with the weights as drawn a rotary read
#                0.0114 beside 0.0112 (the first build) and was not seen.
#   TOL_SCAN     the scan alone under slow decay, float32 inputs: 3.8e-4
#                (state) and 5.0e-5 (reads) with the float32 member
#                through the kernel at 64 lanes, 1.2e-2 and 1.1e-3 with a
#                bfloat16 member (the jnp step); the limit is nearest
#                their geometric mean.
TOL_LOGITS = 0.015
TOL_STATE = 0.004
TOL_SHARP = 0.04
TOL_SCAN = 2e-3
SLOW_DT_BIAS = -5.0     # softplus(-5 + N(0, 1)): dt about 0.01
SLOW_A = (0.1, 1.0)     # A = -uniform: dt A in about (-0.02, 0)
SHARP = 32.0            # wq's factor under `sharp_attention`
PROMPT, CHUNKS, STEPS = 4576, (2048, 2048, 480), 64
BEFORE = 100            # tokens of the sequence that held the lane before
OTHERS = 8              # other lanes that decode beside the watched one


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="granite-4.0-h-micro")
    ap.add_argument("--cell", default="granite-4.0-h-micro.longgen-closed")
    ap.add_argument("--details", nargs="?", const="all", default="",
                    help="all, or a comma list of the reference's DETAILS")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: walks the script only")
    ap.add_argument("--seed", type=int, default=20260930)
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import granite_hybrid as gh
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.runtime.device import device_identity, require_tpu

    ident = device_identity() if args.rehearse else require_tpu()
    config = spec.load_cell(args.cell)["config"]      # with the cell's sizes
    klass = spec.model_class(config)
    cfg = klass.program_config(source_keys(config, args.rehearse),
                               args.config)
    sizes = dict(config["engine"])
    prompt, chunks, steps, before = PROMPT, CHUNKS, STEPS, BEFORE
    if args.rehearse:
        sizes.update(config["rehearse"]["engine"])
        prompt, chunks, steps, before = 76, (32, 32, 12), 64, 20
    bs, lanes = int(sizes["block_size"]), int(sizes["max_num_seqs"])
    table_w, pool = int(sizes["max_blocks_per_seq"]), int(sizes["num_blocks"])
    # "auto" as the engine resolves it for this cache on this platform
    cfg = dataclasses.replace(cfg, attn_impl=resolve_decode_impl(
        cfg.attn_impl, ident["platform"], bs, cfg.head_dim, cfg.dtype))
    t0 = time.perf_counter()
    params = jax.jit(lambda key: gh.init_params(cfg, key))(
        jax.random.PRNGKey(int(sizes["weights_seed"])))
    mamba = cfg.layers_of("mamba")
    assert mamba[0] == 0 and cfg.period[0] == "mamba", cfg.period

    def slow_first_layer(params):
        """The watched first Mamba layer (period 0 of the period's
        position 0) forgets slowly; every other leaf is the draw's."""
        at0 = dict(params["layers"][0])
        H = cfg.ssm_heads
        a = jax.random.uniform(jax.random.PRNGKey(args.seed % (1 << 31)),
                               (H,), jnp.float32, *SLOW_A)
        at0["dt_bias"] = at0["dt_bias"].at[0].set(SLOW_DT_BIAS)
        at0["a_log"] = at0["a_log"].at[0].set(jnp.log(a))
        return {**params, "layers": [at0] + list(params["layers"][1:])}

    def sharp_attention(params):
        """Every attention layer's scores `SHARP` times as large (the
        other leaves are shared, not copied)."""
        return {**params, "layers": [
            {**layer, "wq": (layer["wq"].astype(jnp.float32) * SHARP
                             ).astype(layer["wq"].dtype)}
            if kind != "mamba" else layer
            for layer, kind in zip(params["layers"], cfg.period)]}

    params = slow_first_layer(params)
    jax.block_until_ready(params)
    total = prompt + steps
    need = -(-total // bs)
    n_others = min(OTHERS, (lanes - 1) // 2 * 2)
    assert need <= table_w and n_others >= 2, (need, table_w, lanes)
    lane = lanes // 2
    print(f"device {ident}; attn {cfg.attn_impl}; {lanes} lanes x "
          f"{table_w} blocks of {pool}; weights in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    rng = np.random.default_rng(args.seed)
    toks = rng.integers(3, cfg.vocab_size, total)
    past = rng.integers(3, cfg.vocab_size, before)
    # scattered blocks, not 1, 2, 3; the other lanes take one block each
    # behind them (prompts of 20, at most `steps` more)
    table = np.zeros(table_w, np.int32)
    table[:need] = 1 + 2 * np.arange(need)
    free = [ln for ln in range(lanes) if ln != lane]
    others = free[::len(free) // n_others][:n_others]
    o_toks = rng.integers(3, cfg.vocab_size, (n_others, 20 + steps))
    o_tables = np.zeros((n_others, table_w), np.int32)
    o_tables[:, 0] = 2 * need + 2 + np.arange(n_others)
    o_tables[:, 1] = 2 * need + 2 + n_others + np.arange(n_others)
    assert o_tables.max() < pool
    watched = {"first": 0, "last": len(mamba) - 1}   # index into the pool

    def program(cfg, params):
        """-> ({position: logits}, {after: {which: state}})."""
        kv = tuple(jnp.zeros(s, d) for s, d in zip(
            gh.kv_cache_shapes(cfg, pool, bs, lanes=lanes),
            gh.kv_cache_dtypes(cfg)))
        # the weights are an argument: a closure would bake them into
        # the program as constants
        prefill = jax.jit(lambda kv, w, *a, **k: gh.prefill(
            w, cfg, kv, *a, **k), donate_argnums=(0,))
        decode = jax.jit(lambda kv, w, *a, **k: gh.decode(
            w, cfg, kv, *a, **k), donate_argnums=(0,))

        def feed(kv, seq, pos, chunk, tab, ln):
            bucket = max(1 << (chunk - 1).bit_length(), 16)
            t = np.zeros(bucket, np.int32)
            t[:chunk] = seq[pos:pos + chunk]
            return prefill(
                kv, params, jnp.asarray(t),
                jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
                jnp.asarray(tab), jnp.int32(pos), jnp.int32(chunk),
                lanes=jnp.int32(ln))

        _, kv = feed(kv, past, 0, before, table, lane)   # the lane's past
        for i, o in enumerate(others):
            _, kv = feed(kv, o_toks[i], 0, 20, o_tables[i], o)
        rows, states, pos = {}, {}, 0
        for chunk in chunks:
            logits, kv = feed(kv, toks, pos, chunk, table, lane)
            pos += chunk
            rows[pos - 1] = np.asarray(logits, np.float32)
        grab = lambda: {w: np.asarray(kv[2][i, lane], np.float32)
                        for w, i in watched.items()}
        states["prompt"] = grab()
        tables = np.zeros((lanes, table_w), np.int32)
        tables[lane] = table
        for i, o in enumerate(others):
            tables[o] = o_tables[i]
        o_pos = np.full(n_others, 20)
        for j, p in enumerate(range(prompt, total)):
            tok, cur = np.zeros(lanes, np.int32), np.zeros(lanes, np.int32)
            valid = np.zeros(lanes, bool)
            tok[lane], cur[lane], valid[lane] = toks[p], p, True
            # the first four step in the first half only (they finish),
            # the last four in the second half only (they join)
            for i, o in enumerate(others):
                if (i < n_others // 2) == (j < steps // 2):
                    tok[o], cur[o] = o_toks[i][o_pos[i]], o_pos[i]
                    valid[o] = True
                    o_pos[i] += 1
            logits, kv = decode(kv, params, jnp.asarray(tok),
                                jnp.asarray(cur), jnp.asarray(tables),
                                jnp.asarray(cur), valid=jnp.asarray(valid))
            rows[p] = np.asarray(logits[lane], np.float32)
        states["decode"] = grab()
        # the reference's float32 layers do not fit beside the cache
        for member in kv:
            member.delete()
        return rows, states

    def reference(params, leave_out=""):
        """A left-out detail is read at the end only (one forward)."""
        at = sorted(rows)
        pick = lambda S: {w: np.asarray(S[mamba[i]])
                          for w, i in watched.items()}
        logits, S_end = klass.reference_forward(
            params, cfg, toks.tolist(), leave_out, at=at)
        states = {"decode": pick(S_end)}
        if not leave_out:
            _, S_prompt = klass.reference_forward(
                params, cfg, toks[:prompt].tolist(), at=[prompt - 1])
            states["prompt"] = pick(S_prompt)
        return dict(zip(at, np.asarray(logits))), states

    def read(rows, states, ref_rows, ref_states):
        far = lambda x: float(x) if np.isfinite(x) else float("inf")
        shares = [far(np.abs(rows[p] - ref_rows[p]).max()
                      / (ref_rows[p].max() - ref_rows[p].min()))
                  for p in sorted(rows)]
        out = {"logits_worst": max(shares),
               "logits_median": float(np.median(shares)),
               "logits_quartiles": [float(np.percentile(shares, q))
                                    for q in (25, 75)]}
        for after in ref_states:
            for w in watched:
                a, b = states[after][w], ref_states[after][w]
                out[f"state_{w}.{after}"] = far(
                    np.linalg.norm(a - b) / np.linalg.norm(b))
        return out

    def within(r, tol=TOL_LOGITS):
        return bool(r["logits_median"] <= tol
                    and max(r["state_first.prompt"],
                            r["state_first.decode"]) <= TOL_STATE)

    def scan_alone(state_dtype):
        """-> the scan's own error from the token recurrence: the state
        after prompt and steps, the 64 steps' reads.  The steps run as
        the cell's do: `lanes_step` over a member of `lanes` lanes, the
        watched lane busy beside `n_others` whose inputs are their own."""
        from functools import partial

        from dynamo_tpu.ops.lane_state import (
            lanes_plan,
            lanes_step,
            resolve_state_impl,
        )
        from dynamo_tpu.ops.pallas_lane_state import ssd_lanes_step
        from dynamo_tpu.ops.ssm import ssd_chunked, ssd_step
        H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 6)

        def draw(key, lead):
            k = jax.random.split(key, 4)
            return (jax.random.normal(k[0], (*lead, H, P)),
                    0.02 * jax.random.uniform(k[1], (*lead, H)),
                    jax.random.normal(k[2], (*lead, G, N)) / np.sqrt(N),
                    jax.random.normal(k[3], (*lead, G, N)))

        x, dt, b, c = draw(ks[0], (total,))        # the watched lane's
        # the steps' rows are lanes: every lane its own inputs, the
        # watched lane the sequence's
        by_lane = [v.at[:, lane].set(w[prompt:])
                   for v, w in zip(draw(ks[1], (steps, lanes)),
                                   (x, dt, b, c))]
        a = -jax.random.uniform(ks[2], (H,), minval=SLOW_A[0],
                                maxval=SLOW_A[1])
        d_skip = jnp.ones((H,), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want_y, want_S = jax.jit(lambda *v: klass.token_recurrence(
                *v, jnp.zeros((H, P, N), jnp.float32)))(x, dt, a, b, c,
                                                        d_skip)
        chunked = jax.jit(lambda S, x, dt, b, c: ssd_chunked(
            x, dt, a, b, c, d_skip, S.astype(jnp.float32),
            chunk=cfg.ssm_chunk))
        S, pos = jnp.zeros((H, P, N), state_dtype), 0
        for chunk in chunks:
            cut = lambda v: v[pos:pos + chunk]
            _, S = chunked(S, cut(x), cut(dt), cut(b), cut(c))
            S, pos = S.astype(state_dtype), pos + chunk
        # a member of one layer, the watched lane's entry the prompt's
        s_impl = resolve_state_impl(cfg.attn_impl, ident["platform"], P, N,
                                    state_dtype)
        busy = np.zeros(lanes, bool)
        busy[[lane] + list(others)] = True
        member = jax.random.normal(ks[3], (1, lanes, H, P, N)).astype(
            state_dtype).at[0, lane].set(S)

        @partial(jax.jit, donate_argnums=(0,))
        def step(member, x, dt, b, c):
            rule = (x, dt, a, b, c, d_skip)
            return lanes_step(
                member, 0, lanes_plan(jnp.asarray(busy), s_impl),
                partial(ssd_step, *rule), partial(ssd_lanes_step, *rule),
                s_impl)

        reads = []
        for t in range(steps):
            y, member = step(member, *(v[t] for v in by_lane))
            reads.append(y[lane])
        err = lambda u, v: float(jnp.linalg.norm(u.astype(jnp.float32) - v)
                                 / jnp.linalg.norm(v))
        return {"impl": s_impl, "state": err(member[0, lane], want_S),
                "reads": err(jnp.stack(reads), want_y[prompt:])}

    def stamp(what):
        print(f"{what} at {time.perf_counter() - t0:.1f}s", flush=True)

    rows, states = program(cfg, params)
    stamp("program done")
    # the precision below the stated one, THROUGH THE PROGRAM: it has to
    # fail (the reference is read after it: both caches are gone by then)
    low = program(dataclasses.replace(cfg, state_dtype=jnp.bfloat16), params)
    stamp("program with a bfloat16 state done")
    ref_rows, ref_states = reference(params)
    stamp("reference done")
    out = {"config": args.config, "device": ident,
           "limits": {"logits_median": TOL_LOGITS, "state_first": TOL_STATE,
                      "logits_median_sharp": TOL_SHARP,
                      "scan_alone": TOL_SCAN},
           "positions": len(rows),
           "program": read(rows, states, ref_rows, ref_states),
           "control_bf16_state": read(*low, ref_rows, ref_states),
           "argmax_agree": int(sum(
               int(rows[p].argmax() == ref_rows[p].argmax())
               for p in rows))}
    out["scan_alone"] = {"float32_state": scan_alone(jnp.float32),
                         "control_bf16_state": scan_alone(jnp.bfloat16)}
    numbers = lambda r: [v for v in r.values() if not isinstance(v, str)]
    out["controls_fail"] = bool(
        not within(out["control_bf16_state"])
        and out["scan_alone"]["control_bf16_state"]["state"] > TOL_SCAN)
    out["ok"] = bool(
        within(out["program"]) and out["controls_fail"]
        and max(numbers(out["scan_alone"]["float32_state"])) <= TOL_SCAN)
    if args.details:
        print(json.dumps(out), flush=True)          # the details take long
        out["left_out"] = {}
        details = (klass.DETAILS if args.details == "all"
                   else tuple(args.details.split(",")))
        for d in details:
            if d == "rope":                         # on sharp weights, below
                continue
            out["left_out"][d] = read(rows, states, *reference(params, d))
            stamp(f"without {d}: {json.dumps(out['left_out'][d])}")
        # every left-out detail has to be outside the logits' limit
        out["details_fail"] = all(
            r["logits_median"] > TOL_LOGITS for r in out["left_out"].values())
        if "rope" in details:
            sharp = sharp_attention(params)
            run = program(cfg, sharp)
            out["sharp_attention"] = {
                "program": read(*run, *reference(sharp)),
                "rope": read(*run, *reference(sharp, "rope"))}
            stamp(f"sharp attention: {json.dumps(out['sharp_attention'])}")
            out["details_fail"] = bool(
                out["details_fail"]
                and within(out["sharp_attention"]["program"], TOL_SHARP)
                and out["sharp_attention"]["rope"]["logits_median"]
                > TOL_SHARP)
        out["ok"] = bool(out["ok"] and out["details_fail"])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
