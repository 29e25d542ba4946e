#!/usr/bin/env python3
"""How far expert picks that flip carry the gated short-convolution
family (models/lfm2.py) from its float32 reference, and what a draw of
the weights does about it: the study behind `models/lfm2.py`'s `EXPERT_OWN`
and benchmark/chip_logits_lfm2.py's forced picks (PERF.md section 6,
PR 55).  CPU, minutes; not a chip measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/study_lfm2_picks.py

At d 256 with the cell's 9-layer pattern, 64 experts, 4 picks, bf16
weights: the PROGRAM's logits (`_forward_packed`, one row of 264 tokens,
lib/correct.py's length) against `reference_forward` in float32, for
each variant of the draw and three weights seeds:

  share     median and worst over the positions of max |program -
            reference| / (max - min of the reference's logits)
  gap>.04   the share of positions whose program argmax lies more than
            lib/correct.py's TOL_RANGE_SHARE under the reference's
            largest logit, and 1 - (1 - that)^8: how often eight
            emitted tokens would fail `correct`
  flips     the share of (token, expert layer) whose four picks differ
            between the float32 reference and the same arithmetic in
            bfloat16

Variants: independent experts (EXPERT_OWN 1), the router's matrix x 4
and x 1/4 (the scale does NOT move the flips: noise and margin scale
together), EXPERT_OWN 0.25 / 0.1 / 0.04, and independent experts with
the reference's picks FORCED on the program (the arithmetic alone).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import lfm2 as ref
from dynamo_tpu.models import lfm2
from dynamo_tpu.models.llama import _logits

T, BS, TOL = 264, 16, 0.04
CFG = lfm2.Lfm2Config(
    name="study", vocab_size=8192, d_model=256, n_heads=4, n_kv_heads=2,
    head_dim=64, ffn_dim=1024, moe_ffn_dim=192, n_experts=64,
    experts_per_token=4)


def program_logits(params, toks):
    blocks = -(-T // BS)
    kv = tuple(jnp.zeros(s, d) for s, d in zip(
        lfm2.kv_cache_shapes(CFG, blocks + 1, BS, lanes=1),
        lfm2.kv_cache_dtypes(CFG)))
    x, _ = lfm2._forward_packed(
        params, CFG, kv, jnp.asarray(toks, jnp.int32),
        jnp.arange(T, dtype=jnp.int32), jnp.zeros(T, jnp.int32),
        jnp.asarray(1 + np.arange(blocks, dtype=np.int32))[None],
        jnp.ones(T, bool), jnp.zeros(1, jnp.int32))
    return np.asarray(_logits(params, CFG, x.astype(CFG.dtype)), np.float32)


def read(got, want):
    span = want.max(1) - want.min(1)
    share = np.abs(got - want).max(1) / span
    gap = (want.max(1) - want[np.arange(len(want)), got.argmax(1)]) / span
    over = float((gap > TOL).mean())
    return (f"share {np.median(share):.4f} / {share.max():.4f}  "
            f"gap>{TOL} {over:.3f} (of 8: {1 - (1 - over) ** 8:.2f})")


def main():
    variants = [("own 1 (independent)", 1.0, 1.0, False),
                ("own 1, router x 4", 1.0, 4.0, False),
                ("own 1, router / 4", 1.0, 0.25, False),
                ("own 0.25", 0.25, 1.0, False),
                ("own 0.1", 0.1, 1.0, False),
                ("own 0.04", 0.04, 1.0, False),
                ("own 1, picks forced", 1.0, 1.0, True),
                ("own 0.04, picks forced", 0.04, 1.0, True)]
    for name, own, gate, forced in variants:
        for seed in (23, 24, 25):
            lfm2.EXPERT_OWN = own
            params = lfm2.init_params(CFG, jax.random.PRNGKey(seed))
            for layer in params["layers"]:
                if "moe_gate" in layer:
                    layer["moe_gate"] = (layer["moe_gate"].astype(
                        jnp.float32) * gate).astype(CFG.dtype)
            toks = np.random.default_rng(seed).integers(
                3, CFG.vocab_size, T)
            want, picks = ref.reference_forward(
                params, CFG, toks.tolist(), return_picks=True)
            _, low = ref.reference_forward(
                params, CFG, toks.tolist(), compute_dtype=jnp.bfloat16,
                return_picks=True)
            flips = np.mean([
                (np.sort(np.asarray(a), 1) != np.sort(np.asarray(b), 1)
                 ).any(1).mean()
                for a, b in zip(picks, low) if a.shape[1]])
            if forced:
                params = dict(params, layers=[
                    dict(layer, moe_forced_picks=ids) if ids.shape[1]
                    else layer
                    for layer, ids in zip(params["layers"], picks)])
            got = program_logits(params, toks)
            print(f"{name:26s} seed {seed}: {read(got, np.asarray(want))}"
                  f"  flips {flips:.3f}", flush=True)


if __name__ == "__main__":
    main()
