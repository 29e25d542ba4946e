#!/usr/bin/env python3
"""Engine-path LOGITS, chosen key sets and attention outputs of a
configuration whose attention chooses its keys, against the class's
float32 reference, at the configuration's own widths, on the chip.

    python3 benchmark/chip_logits_sparse.py [--config <name>] [--details]

`chip_logits.py`'s 300-token prompt never leaves the regime in which the
indexer keeps every key, and `lib/correct.py` (which decides a cell's
`correct`) checks 264 positions: neither can see the selection.  Here a
prompt of 6112 tokens (3 x topk at the published 2048) is prefilled
through the family's own program in chunks of 2048, 2048 and 2016, then
64 teacher-forced decode steps run from position 6112 across the block
boundary at 6144.  Printed:

  * a phase, the largest |program - reference| as a share of the
    position's logit range (max - min), and the MEDIAN of that share
    over all 67 positions against MEDIAN_TOL (the largest decides
    nothing: see the limits below);
  * layer 0 (whose input both sides share bit for bit), last chunk and
    one decode token: the share of the reference's chosen keys that the
    op chose too (and of the op's that the reference chose), and the
    op's attention output before Wo against the reference's: the RMS of
    the difference as a share of the output's own RMS, against
    ATTN_TOL (the chunk) and ATTN_TOKEN_TOL (the one token).  With
    random weights an average over thousands of values shrinks the
    attention output, so a dropped selection may hide inside a logit
    tolerance: this is where it shows.  (A key that bf16
    index scores swap at the threshold moves ONE query's output by
    about its softmax weight: the largest single element differs by
    several RMS between program and reference, which is why the share
    is of RMS to RMS and not of the largest element);
  * with `--details`, the same shares with each `DETAILS` entry left
    out of the reference, which must then disagree, and the CONTROL:
    the reference itself over the same weights rounded to the nearest
    precision under the configuration's bf16 (a float8's 3 mantissa
    bits, exponent untouched, activations still float32), read against
    the float32 reference by the same measures.  It has to come out as
    not ok: limits that a float8 model passed would hold nothing.

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

# Each limit lies between two readings on a v5e at the published
# widths, 12 layers, over three prompt seeds (20260928, 7, 2147483659;
# my chip runs, PR 33; PERF.md section 6): the largest that the program
# reads (bf16 weights, activations and cache, the Pallas kernels, against
# the float32 reference) and the smallest that a wrong model reads: a
# left-out detail, or the control (the reference over weights rounded to
# a float8's mantissa).
#   median share over 67 positions: program 0.0082-0.0088; smallest
#     detail 0.034-0.039 (the index key's LayerNorm), control
#     0.048-0.057;
#   layer-0 attention output of the last chunk's 2016 queries, RMS of
#     the difference over RMS: program 0.0585-0.0592; control 0.233,
#     smallest detail that touches attention 0.392-0.394 (q/k norm;
#     selection itself 0.73);
#   the same for ONE decode token, a single query's row: program 0.050,
#     0.058, 0.085, so its limit stands higher than the chunk's (0.1
#     would leave 18 % over the largest of three seeds).
# The LARGEST share over the positions is printed and decides nothing:
# the program read 0.024-0.041 (one decode position whose chosen set or
# routed expert bf16 moved) and the smallest detail 0.048-0.059: no
# limit has room on both sides.
MEDIAN_TOL = 0.02
ATTN_TOL = 0.1
ATTN_TOKEN_TOL = 0.15
CHUNKS, STEPS = (2048, 2048, 2016), 64


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="keye-vl-2.0-30b-a3b-12l-ep8")
    ap.add_argument("--details", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU: walks the script only")
    ap.add_argument("--seed", type=int, default=20260928)
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.llama import _qkv, rms_norm
    from dynamo_tpu.ops import sparse_attention as sa
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
        chunks, steps = (32, 32, 28), 8
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
    kv = tuple(jnp.zeros(s, d) for s, d in zip(
        family.kv_cache_shapes(cfg, 1 + 2 * table_w, bs),
        family.kv_cache_dtypes(cfg)))
    print(f"device {ident}; weights in {time.perf_counter() - t0:.1f}s",
          flush=True)

    toks = np.random.default_rng(args.seed).integers(
        3, cfg.vocab_size, total)
    table = np.zeros(table_w, np.int32)
    table[:need] = 1 + 2 * np.arange(need)       # scattered, not 1, 2, 3
    # the weights are an argument: a closure would bake them into the
    # program as constants
    prefill = jax.jit(lambda kv, w, *a: family.prefill(w, cfg, kv, *a),
                      donate_argnums=(0,))
    decode = jax.jit(lambda kv, w, *a, **k: family.decode(
        w, cfg, kv, *a, **k), donate_argnums=(0,))
    rows, pos = {}, 0
    bucket = max(chunks)
    for chunk in chunks:
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = prefill(
            kv, params, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk))
        pos += chunk
        rows[pos - 1] = np.asarray(logits, np.float32)

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

    # layer 0 through the ops themselves, over the cache the program
    # filled: the last chunk's queries and the last decode token
    topk = cfg.index_topk

    @jax.jit
    def layer0(kv, embedding, layer, tok, positions):
        x = embedding[tok].astype(cfg.dtype)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, _, _ = _qkv(layer, cfg, h, positions)
        qi, _, wi = family._index_proj(layer, cfg, h, positions)
        n = tok.shape[0]
        sel = sa.prefill_index_mask(
            qi, wi, kv[2], 0, jnp.asarray(table), jnp.ones(n, bool),
            positions, topk)
        attn = sa.sparse_prefill_attention(
            q, qi, wi, kv[0], kv[1], kv[2], 0, jnp.asarray(table)[None],
            jnp.zeros(n, jnp.int32), positions, jnp.ones(n, bool), topk)
        one = sa.sparse_decode_attention(
            q[-1:], qi[-1:], wi[-1:], kv[0], kv[1], kv[2], 0,
            jnp.asarray(table)[None], positions[-1:] + 1, topk,
            attn_impl=cfg.attn_impl)
        return sel, attn, one

    last = np.arange(prompt - chunks[-1], prompt)
    sel, attn, one = (np.asarray(a, np.float32) for a in layer0(
        kv, params["embedding"], params["layers"][0],
        jnp.asarray(toks[last]), jnp.asarray(last, jnp.int32)))

    def shares(ref, ps, got=rows):
        return [float(np.abs(got[p] - ref[p]).max()
                      / (ref[p].max() - ref[p].min())) for p in ps]

    def worst(ref, ps):
        return max(shares(ref, ps))

    def rms(x):
        return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))

    boundary = -(-prompt // bs) * bs
    phases = {"prefill_chunk_ends": [c - 1 for c in np.cumsum(chunks)],
              "decode_in_block": list(range(prompt, boundary)),
              "decode_past_boundary": list(range(boundary, total))}
    taps = []
    ref = np.asarray(klass.reference_logits(params, cfg, toks.tolist(),
                                            taps=taps))
    want_sel = np.asarray(taps[0]["chosen"])[last, :prompt]
    want_attn = np.asarray(taps[0]["attn"])[last]
    got_sel = sel[:, :prompt] > 0
    both = (got_sel & want_sel).sum()
    out = {
        "config": args.config, "device": ident,
        "median_tolerance": MEDIAN_TOL, "attn_tolerance": ATTN_TOL,
        "attn_token_tolerance": ATTN_TOKEN_TOL,
        "attn_impl": cfg.attn_impl, "prompt_seed": args.seed,
        "share_of_range": {k: worst(ref, ps) for k, ps in phases.items()},
        "median_share": float(np.median(shares(ref, list(rows)))),
        "argmax_agree": int(sum(int(rows[p].argmax() == ref[p].argmax())
                                for p in rows)), "positions": len(rows),
        "layer0": {
            "chosen_of_references": float(both / want_sel.sum()),
            "references_of_chosen": float(both / got_sel.sum()),
            "chosen_of_references_worst_query": float(
                ((got_sel & want_sel).sum(1) / want_sel.sum(1)).min()),
            "attn_rms_share": rms(attn - want_attn) / rms(want_attn),
            "attn_rms_share_decode":
                rms(one[0] - want_attn[-1]) / rms(want_attn[-1]),
            "attn_largest_element_share": float(
                np.abs(attn - want_attn).max() / rms(want_attn)),
        }}
    print(f"reference done at {time.perf_counter() - t0:.1f}s", flush=True)
    if args.details and hasattr(klass, "DETAILS"):
        out["left_out"], out["left_out_median"] = {}, {}
        out["left_out_attn"] = {}
        for d in klass.DETAILS:
            taps = []
            without = np.asarray(klass.reference_logits(
                params, cfg, toks.tolist(), leave_out=d, taps=taps))
            out["left_out"][d] = worst(without, list(rows))
            out["left_out_median"][d] = float(np.median(
                shares(without, list(rows))))
            out["left_out_attn"][d] = rms(
                attn - np.asarray(taps[0]["attn"])[last]) / rms(want_attn)
        print(json.dumps(out), flush=True)       # kept if the control dies

        # the control: 3 explicit mantissa bits (float8 e4m3's), rounded
        # half up in magnitude on the float32 bit pattern; the result
        # fits the weights' own dtype, and the unrounded tree goes first
        # (two trees and the reference's logits do not fit one chip)
        def float8_mantissa(x):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                                jnp.uint32)
            bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
            return jax.lax.bitcast_convert_type(
                bits, jnp.float32).astype(x.dtype)

        layers = params.pop("layers")
        rounded = {k: jax.jit(lambda t: jax.tree.map(float8_mantissa, t),
                              donate_argnums=0)(v)
                   for k, v in params.items()}
        rounded["layers"] = [
            jax.jit(lambda t: jax.tree.map(float8_mantissa, t),
                    donate_argnums=0)(lp) for lp in layers]
        del params, layers, kv
        taps = []
        low = np.asarray(klass.reference_logits(
            rounded, cfg, toks.tolist(), taps=taps))
        low_attn = np.asarray(taps[0]["attn"])[last]
        low_shares = shares(ref, list(rows), got=low)
        out["control_float8_weights"] = {
            "worst_share": max(low_shares),
            "median_share": float(np.median(low_shares)),
            "attn_rms_share": rms(low_attn - want_attn) / rms(want_attn)}
        c = out["control_float8_weights"]
        c["ok"] = bool(c["median_share"] <= MEDIAN_TOL
                       and c["attn_rms_share"] <= ATTN_TOL)
    out["ok"] = bool(
        out["median_share"] <= MEDIAN_TOL
        and out["layer0"]["attn_rms_share"] <= ATTN_TOL
        and out["layer0"]["attn_rms_share_decode"] <= ATTN_TOKEN_TOL)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
