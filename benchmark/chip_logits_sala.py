#!/usr/bin/env python3
"""Engine-path LOGITS, chosen block sets and every layer's mixer read of
the configuration whose sparse layers choose BLOCKS of keys beside
lightning linear-attention layers (models/minicpm_sala.py), against the
class's float32 reference, at the configuration's own widths, on the
chip.

    python3 benchmark/chip_logits_sala.py [--config <name>] [--details]

`lib/correct.py` (which decides a cell's `correct`) checks 264
positions: under `dense_len` and inside one prefill program, so it sees
neither the choice nor a carried state.  Here a prompt of 12288 tokens
(1.5 dense_len at the published 8192) is prefilled through the family's
own program in six chunks of 2048 on a lane whose state was dirty, then
64 teacher-forced decode steps run from position 12288.  The queries
from 8192 on choose; the state is carried over five chunk edges and into
decode; compressed-key windows straddle every chunk edge, page edge and
the prefill/decode edge.  Printed:

  * a phase, the largest |program - reference| as a share of the
    position's logit range (max - min), and the MEDIAN of that share
    over all 70 positions against MEDIAN_TOL;
  * the chosen sets of the FIRST sparse layer (whose input both sides
    share up to bf16) for the last chunk's queries and the 64 decode
    tokens, through the ops over the cache the program filled: the share
    of (query, KV group) pairs whose sets are the reference's block for
    block, and the mean overlap of the rest (they see bf16 q and
    compressed keys only), against CHOSEN_TOL;
  * every layer's mixer read (the program's own `taps`) over the last
    chunk and over the chunk that ends at `dense_len` (whose queries
    attend everything by POSITION, though their prompt is longer): the
    RMS of the difference as a share of the read's own RMS, the worst
    layer against MIX_TOL;
  * with `--details`, the same with each `DETAILS` entry left out of the
    reference, which must then pass a limit, and the CONTROL: the
    reference itself over the same weights rounded to the nearest
    precision under the configuration's bf16 (a float8's 3 mantissa
    bits, activations still float32), which has to come out as not ok.

Exits 1 where a share passes its limit.  Without a TPU it fails;
`--rehearse` walks the script on the CPU at the `rehearse` widths (its
numbers mean nothing).
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

# Each limit lies between two readings on a v5e at the published widths,
# 12 layers, over three prompt seeds (20261002, 7, 2147483659; my chip
# runs, PR 47; PERF.md section 6 has every reading beside the command
# lines): the largest that the program reads (bf16 weights, activations
# and cache, the Pallas kernels, against the float32 reference) and the
# smallest that a wrong model reads: a left-out detail, or the control
# (the reference over weights rounded to a float8's mantissa).
#   median share over 70 positions: program 0.0121-0.0125 (the largest
#     position 0.0163-0.0172); smallest detail that this measure sees
#     0.0230 (max_pool; compressed_window 0.0232), control 0.121.
#     `dense_len` left out reads 0.0123 here: it moves only the prompt's
#     queries 4096-8191, so it is held by the mixer reads of the chunk
#     that ENDS at dense_len (below), not by this;
#   mixer read, RMS of the difference over RMS, the worst layer over the
#     last chunk and the chunk that ends at dense_len: program
#     0.118-0.120 (the second and third sparse layers, whose choice sees
#     bf16 activations; the first 0.053, a lightning layer 0.008-0.039);
#     control 0.432, smallest detail 0.500 (max_pool); logit_scale
#     leaves every mixer read alone and fails the median;
#   mean overlap of the first sparse layer's chosen sets with the
#     reference's: program 0.9979 on all three (87.2-87.5 % of (query,
#     group) pairs block for block, the rest 63 of 64); control 0.9749,
#     nearest detail 0.822 (max_pool).
MEDIAN_TOL = 0.017
MIX_TOL = 0.25
CHOSEN_TOL = 0.99          # least mean overlap of a (query, group)'s set
CHUNKS, STEPS = (2048,) * 6, 64


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="minicpm-sala-9b-12l")
    ap.add_argument("--details", default="",
                    help="'all' or a comma-separated list of DETAILS")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: walks the script only")
    ap.add_argument("--seed", type=int, default=20261002)
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.llama import _qkv, rms_norm
    from dynamo_tpu.ops import block_sparse_attention as bsa
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.runtime.device import device_identity, require_tpu

    ident = device_identity() if args.rehearse else require_tpu()
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(spec.REPO_ROOT, entry["file"])) as f:
        config = json.load(f)
    klass = spec.model_class(config)
    cfg = klass.program_config(source_keys(config, args.rehearse),
                               args.config)
    family = get_family(cfg)
    sizes = dict(config["engine"])
    chunks, steps = CHUNKS, STEPS
    if args.rehearse:
        sizes.update(config["rehearse"].get("engine", {}))
        chunks, steps = (32, 32, 32), 8
    bs = int(sizes["block_size"])
    # the engine's own resolution of "auto" (engine/core.py)
    cfg = dataclasses.replace(cfg, attn_impl=resolve_decode_impl(
        cfg.attn_impl, ident["platform"], bs, cfg.head_dim, cfg.dtype))
    t0 = time.perf_counter()
    params = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.PRNGKey(int(config["engine"]["weights_seed"])))
    jax.block_until_ready(params)
    prompt = sum(chunks)
    total = prompt + steps
    need = -(-total // bs)
    lanes, table_w, lane = 4, need + 2, 2
    # the lane's state starts DIRTY: a row from position 0 must zero it
    kv = tuple(jnp.ones(s, d) if i == 3 else jnp.zeros(s, d)
               for i, (s, d) in enumerate(zip(
                   family.kv_cache_shapes(cfg, 1 + 2 * table_w, bs,
                                          lanes=lanes),
                   family.kv_cache_dtypes(cfg))))
    print(f"device {ident}; weights in {time.perf_counter() - t0:.1f}s",
          flush=True)

    toks = np.random.default_rng(args.seed).integers(
        3, cfg.vocab_size, total)
    table = np.zeros(table_w, np.int32)
    table[:need] = 1 + 2 * np.arange(need)       # scattered, not 1, 2, 3

    # the weights are an argument: a closure would bake them into the
    # program as constants
    def chunk_with_taps(kv, w, tok, positions, tb, ctx, n, ln):
        taps = []
        logits, kv = family.prefill_batched(
            w, cfg, kv, tok[None], positions[None], tb[None], ctx[None],
            n[None], lanes=ln[None], taps=taps)
        return logits[0], kv, [t[0] for t in taps]

    prefill = jax.jit(chunk_with_taps, donate_argnums=(0,))
    decode = jax.jit(lambda kv, w, *a, **k: family.decode(
        w, cfg, kv, *a, **k), donate_argnums=(0,))
    rows, pos = {}, 0
    bucket = max(chunks)
    # the mixer reads of two chunks are kept: the last (every query
    # chooses, the state carried five times) and the one that ends at
    # dense_len (its queries attend everything, by POSITION)
    ends = np.cumsum(chunks)
    kept = {len(chunks) - 1, int(np.searchsorted(ends, cfg.dense_len))}
    mixes = {}
    for ci, chunk in enumerate(chunks):
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv, taps = prefill(
            kv, params, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk),
            jnp.int32(lane))
        if ci in kept:
            mixes[slice(pos, pos + chunk)] = [
                np.asarray(m, np.float32)[:chunk] for m in taps]
        pos += chunk
        rows[pos - 1] = np.asarray(logits, np.float32)
    del taps

    def on_lane(x, dtype=np.int32):
        a = np.zeros((lanes,) + np.shape(x), dtype)
        a[lane] = x
        return jnp.asarray(a)

    valid = on_lane(True, bool)
    for p in range(prompt, total):
        logits, kv = decode(kv, params, on_lane(toks[p]), on_lane(p),
                            on_lane(table), on_lane(p), valid=valid)
        rows[p] = np.asarray(logits[lane], np.float32)
    print(f"program done at {time.perf_counter() - t0:.1f}s", flush=True)

    # the first sparse layer's choice through the op itself, over the
    # compressed keys the program wrote: the last chunk's queries and
    # every decode token
    first = cfg.layers_of(1)[0]
    last = np.arange(prompt - chunks[-1], total)

    @jax.jit
    def choice(ck, embedding, layer, tok, positions):
        x = (embedding[tok].astype(jnp.float32) * cfg.scale_emb).astype(
            cfg.dtype)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, _, _ = _qkv(layer, cfg, h, None)
        return bsa.prefill_block_choice(
            q, ck, 0, jnp.asarray(table), positions,
            jnp.ones(tok.shape[0], bool), cfg.sizes)

    got_sel = None
    if first == 0:      # its input is the embedding on both sides
        got_sel = np.asarray(choice(
            kv[2], params["embedding"], params["layers"][0],
            jnp.asarray(toks[last]), jnp.asarray(last, jnp.int32)))
    del kv

    def shares(ref, ps, got=rows):
        return [float(np.abs(got[p] - ref[p]).max()
                      / (ref[p].max() - ref[p].min())) for p in ps]

    def rms(x):
        return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))

    at = sorted(rows)

    def read(tree, leave_out=""):
        """-> (logits by position, {chosen, mix shares}) of one
        reference forward."""
        taps = []
        logits = np.asarray(klass.reference_logits(
            tree, cfg, toks.tolist(), leave_out=leave_out, taps=taps,
            at=at))
        ref = dict(zip(at, logits))
        mix = {f"{span.start}-{span.stop}": [
            rms(m - np.asarray(t["mix"])[span])
            / max(rms(np.asarray(t["mix"])[span]), 1e-30)
            for m, t in zip(got, taps)] for span, got in mixes.items()}
        out = {"median_share": float(np.median(shares(ref, at))),
               "worst_share": max(shares(ref, at)),
               "mix_rms_share_by_layer": mix,
               "mix_rms_share": max(max(v) for v in mix.values())}
        if got_sel is not None:
            want = np.asarray(taps[0]["chosen"])[last]
            got = got_sel[:, :, :want.shape[-1]]
            same = (got == want).all(-1)
            overlap = (got & want).sum(-1) / np.maximum(want.sum(-1), 1)
            out["chosen"] = {
                "sets_equal_share": float(same.mean()),
                "mean_overlap_of_the_rest": float(
                    overlap[~same].mean()) if (~same).any() else 1.0,
                "mean_overlap": float(overlap.mean()),
                "choosing_queries": int(
                    (last + 1 > cfg.dense_len).sum())}
        return ref, out

    def ok(r):
        return bool(r["median_share"] <= MEDIAN_TOL
                    and r["mix_rms_share"] <= MIX_TOL
                    and r.get("chosen", {}).get("mean_overlap", 1.0)
                    >= CHOSEN_TOL)

    ref, out = read(params)
    boundary = -(-prompt // bs) * bs
    phases = {"prefill_chunk_ends": [c - 1 for c in np.cumsum(chunks)],
              "decode_in_block": list(range(prompt, min(boundary, total))),
              "decode_past_boundary": list(range(boundary, total))}
    out.update(
        config=args.config, device=ident, attn_impl=cfg.attn_impl,
        prompt_seed=args.seed, positions=len(rows),
        limits={"median": MEDIAN_TOL, "mix": MIX_TOL,
                "chosen_overlap": CHOSEN_TOL},
        share_of_range={k: max(shares(ref, ps))
                        for k, ps in phases.items() if ps},
        argmax_agree=int(sum(int(rows[p].argmax() == ref[p].argmax())
                             for p in rows)))
    out["ok"] = ok(out)
    print(f"reference done at {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps(out), flush=True)
    status = 0 if out["ok"] else 1
    details = klass.DETAILS if args.details == "all" \
        else tuple(d for d in args.details.split(",") if d)
    if details:
        out["left_out"] = {}
        for d in details:
            _, r = read(params, leave_out=d)
            r["ok"] = ok(r)
            out["left_out"][d] = r
            print(json.dumps({d: r}), flush=True)
            status |= int(r["ok"])      # a detail left out must fail
    if args.control:
        # the control: 3 explicit mantissa bits (float8 e4m3's), rounded
        # half up in magnitude on the float32 bit pattern; the result
        # fits the weights' own dtype, and the unrounded tree goes first
        def float8_mantissa(x):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                                jnp.uint32)
            bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
            return jax.lax.bitcast_convert_type(
                bits, jnp.float32).astype(x.dtype)

        round_tree = jax.jit(lambda t: jax.tree.map(float8_mantissa, t),
                             donate_argnums=0)
        layers = params.pop("layers")
        rounded = {k: round_tree(v) for k, v in params.items()}
        rounded["layers"] = [round_tree(lp) for lp in layers]
        del params, layers
        _, c = read(rounded)
        c["ok"] = ok(c)
        out["control_float8_weights"] = c
        print(json.dumps({"control_float8_weights": c}), flush=True)
        status |= int(c["ok"])
    print(json.dumps({"ok": status == 0, "program_ok": out["ok"]}),
          flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
