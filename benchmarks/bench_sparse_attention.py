#!/usr/bin/env python3
"""Time the parts of ops/sparse_attention.py alone on the chip, at the
published Keye-VL-2.0 widths (32 query heads over 4 KV heads of 128, 16
index heads of 64, topk 2048, blocks of 128): decode for 8 lanes and a
prefill chunk of 2048 queries, with the index scores + selection, the
plain dense read and the XLA forms of the two kernels beside them.
(What a read of the chosen columns alone took in this layout: PERF.md
section 6, PR 33.)

    python3 benchmarks/bench_sparse_attention.py [--ctx 16384]

Prints one JSON line of milliseconds a call (device time by the host's
clock around block_until_ready, median of 10 after 3 warm calls).
Fails without a TPU.
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
    ap.add_argument("--ctx", type=int, default=16384)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import sparse_attention as sa
    from dynamo_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_pallas,
    )
    from dynamo_tpu.runtime.device import require_tpu

    ident = require_tpu()
    NH, NKV, HD, H, D, TOPK, BS = 32, 4, 128, 16, 64, 2048, 128
    B, W, NB, T = 8, 199, 1593, 2048
    key = jax.random.split(jax.random.PRNGKey(0), 12)
    bf = jnp.bfloat16
    k_c = jax.random.normal(key[0], (1, NKV, NB, HD, BS), bf)
    v_c = jax.random.normal(key[1], (1, NKV, NB, HD, BS), bf)
    ik_c = jax.random.normal(key[2], (1, 1, NB, D, BS), bf)
    tables = jnp.asarray(
        1 + np.arange(B * W, dtype=np.int32).reshape(B, W) % (NB - 1))
    lens = jnp.full((B,), args.ctx + 1, jnp.int32)
    q = jax.random.normal(key[3], (B, NH, HD), bf)
    qi = jax.random.normal(key[4], (B, H, D), bf)
    wi = jax.random.normal(key[5], (B, H), bf)

    def timed(fn, *a):
        fn = jax.jit(fn)
        for _ in range(3):
            jax.block_until_ready(fn(*a))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(statistics.median(ts), 4)

    out = {"device": ident, "ctx": args.ctx, "lanes": B}

    cache = (k_c, v_c, ik_c)
    out["decode_masked_ms"] = timed(
        lambda *a: sa.sparse_decode_attention(
            *a, 0, tables, lens, TOPK, attn_impl="pallas"),
        q, qi, wi, *cache)
    out["decode_index_select_ms"] = timed(
        lambda qi, wi, ik: sa.decode_index_mask(qi, wi, ik, 0, tables,
                                                lens, TOPK), qi, wi, ik_c)
    out["decode_index_scores_ms"] = timed(
        lambda qi, wi, ik: sa._index_pairs(
            qi[:, None], wi[:, None], ik[0, 0, tables]), qi, wi, ik_c)
    out["decode_dense_kernel_ms"] = timed(
        lambda q, k, v: paged_attention_decode_pallas(q, k, v, 0, tables,
                                                      lens), q, k_c, v_c)
    scores = jax.random.normal(key[6], (B, W * BS), jnp.float32)
    ok = jnp.arange(W * BS)[None, :] < lens[:, None]
    out["decode_select_ms"] = timed(
        lambda s: sa.topk_mask(s, ok, TOPK), scores)
    out["decode_select_xla_ms"] = timed(
        lambda s: sa.topk_mask(s, ok, TOPK, impl="xla"), scores)
    out["decode_select_lax_top_k_ms"] = timed(
        lambda s: jax.lax.top_k(jnp.where(ok, s, -jnp.inf), TOPK)[0][:, -1],
        scores)

    # one prefill chunk: 2048 queries at the end of a context of --ctx
    mb = min(W, 1 << (-(-args.ctx // BS) - 1).bit_length())
    table = tables[0, :mb][None]
    pos = jnp.arange(args.ctx - T, args.ctx, dtype=jnp.int32)
    seg, valid = jnp.zeros(T, jnp.int32), jnp.ones(T, bool)
    qp = jax.random.normal(key[7], (T, NH, HD), bf)
    qip = jax.random.normal(key[8], (T, H, D), bf)
    wip = jax.random.normal(key[9], (T, H), bf)

    out["prefill_table_blocks"] = int(mb)
    out["prefill_masked_ms"] = timed(
        lambda *a: sa.sparse_prefill_attention(
            *a, 0, table, seg, pos, valid, TOPK), qp, qip, wip, *cache)
    out["prefill_index_select_ms"] = timed(
        lambda qi, wi, ik: sa.prefill_index_mask(
            qi, wi, ik, 0, table[0], valid, pos, TOPK), qip, wip, ik_c)
    scores = jax.random.normal(key[10], (T, mb * BS), jnp.float32)
    okp = jnp.arange(mb * BS)[None, :] <= pos[:, None]
    out["prefill_select_ms"] = timed(
        lambda s: sa.topk_mask(s, okp, TOPK), scores)
    out["prefill_select_xla_ms"] = timed(
        lambda s: sa.topk_mask(s, okp, TOPK, impl="xla"), scores)
    sel = sa.topk_mask(scores, okp, TOPK)
    out["prefill_masked_flash_ms"] = timed(
        lambda q, k, v, sel: sa._masked_flash_pallas(q, k, v, 0, table[0],
                                                     sel), qp, k_c, v_c, sel)
    out["prefill_masked_flash_xla_ms"] = timed(
        lambda q, k, v, sel: sa._masked_flash(q, k, v, 0, table[0], sel, 8),
        qp, k_c, v_c, sel)
    out["prefill_flash_agree"] = float(jnp.abs(
        sa._masked_flash_pallas(qp, k_c, v_c, 0, table[0], sel)
        - sa._masked_flash(qp, k_c, v_c, 0, table[0], sel, 8)).max())
    out["select_agree"] = bool(jnp.array_equal(
        sel, sa.topk_mask(scores, okp, TOPK, impl="xla")))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
