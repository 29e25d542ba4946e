"""Per-phase prefill profiler: localize where the prefill phase's MXU
time goes on the bench geometry (llama-3b, bf16), the prefill analogue
of bench_decode_phases.py.

Round-5 verdict: prefill MFU is 0.098 and p50 TTFT flat at ~2.9s —
prefill ran as one jitted program per padded-length bucket per sequence,
mostly padding and serial dispatch.  This script times each phase of the
chunked-prefill pipeline separately on the real chip:

  packed      ONE packed program: S prompts' chunks concatenated into a
              padding-free stream with segment ids (the serving path,
              ops/packed_prefill.py).  `--impl` selects the attention
              implementation inside it — the masked XLA reference
              (S-fold attention FLOPs) or the Pallas tile-skip kernel
              (ops/pallas_packed_prefill.py) — and `--impl ab` runs
              BOTH and prints one JSON line with each variant's
              hand-counted est_mfu and analytic attention FLOPs.
  batched     the legacy padded multi-row program (every row padded to
              the packed total — what packing replaces)
  single      S serial B=1 bucket programs (the pre-round-6 path)
  attn        the packed causal-within-segment attention op alone
  kv_write    the packed K/V scatter alone
  weights     projection/MLP matmuls only (attention stubbed) — the
              MXU-streaming bound for the packed stream

and prints tokens/s plus achieved model FLOPs utilisation (MFU) per
phase against the v5e bf16 pin.

Run on the chip:  python benchmarks/bench_prefill_phases.py
CPU smoke:        python benchmarks/bench_prefill_phases.py --model tiny \
                      --tokens 64 --seqs 2 --ctx-blocks 4
"""

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dynamo_tpu.models import llama            # noqa: E402
from dynamo_tpu.ops import packed_prefill as pp  # noqa: E402
from dynamo_tpu.quant.kv import quantize_tokens  # noqa: E402
from dynamo_tpu.runtime.device import (  # noqa: E402
    device_identity,
    device_peaks,
    require_tpu,
)


def _sync(r):
    """Close timing with a device FETCH (see bench_decode_phases)."""
    leaf = jax.tree_util.tree_leaves(r)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def timeit(fn, n=4, warm=1):
    for _ in range(warm):
        r = fn()
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    _sync(r)
    return (time.perf_counter() - t0) / n


def _sweep_stream(T, width, rows, block):
    """One packed stream of `rows` equal runs that END near the table's
    last column (a prompt's last chunk; its first when T fills the
    table), every run starting inside a block where there is room."""
    chunk = T // rows
    start = max(0, width * block - chunk)
    start -= 37 if start >= 37 else 0
    seg = np.repeat(np.arange(rows, dtype=np.int32), chunk)
    pos = np.tile(start + np.arange(chunk, dtype=np.int32), rows)
    tables = 1 + np.arange(rows * width, dtype=np.int32).reshape(rows,
                                                                   width)
    return seg, pos, np.ones(T, bool), tables


def _quantize_cache(x):
    """[L, nkv, NB, hd, bs] -> (int8 cache, fp32 scales [L, nkv, NB, bs]):
    per-position quantization over hd, the serving convention
    (quant/kv.py)."""
    q8, sc = quantize_tokens(x.swapaxes(3, 4))
    return q8.swapaxes(3, 4), sc


def attn_sweep(args, cfg):
    """The packed attention op alone, one layer, over `--sweep`'s
    (tokens x table width [x rows]) shapes: the float32 XLA scan, the
    packed kernel at each of `--tiles` (token_block x chunk_cols; 0 =
    its own), ops/sparse_attention._masked_flash_pallas under an
    all-causal mask, and what "auto" resolves to.  One JSON line a
    shape, ms a layer, each form's largest difference from the first."""
    from dynamo_tpu.ops.pallas_packed_prefill import (
        packed_prefill_attention_pallas,
    )
    from dynamo_tpu.ops.sparse_attention import _masked_flash_pallas

    interpret = args.mode != "tpu"
    L, nkv, nh, hd, bs = 4, cfg.n_kv_heads, cfg.n_heads, cfg.head_dim, \
        args.block
    tiles = [tuple(int(x) for x in t.split("x"))
             for t in args.tiles.split(",") if t]
    rng = np.random.default_rng(0)
    for shape in args.sweep.split(","):
        T, width, rows = (tuple(int(x) for x in shape.split("x"))
                          + (1,))[:3]
        seg, pos, valid, tables = _sweep_stream(T, width, rows, bs)
        nb = 1 + rows * width
        kc, vc = (jnp.asarray(rng.standard_normal((L, nkv, nb, hd, bs)),
                              cfg.dtype) for _ in range(2))
        q0 = jnp.asarray(rng.standard_normal((T, nh, hd)), cfg.dtype)
        a = tuple(jnp.asarray(x) for x in (tables, seg, pos, valid))
        scales = {}
        if args.int8:
            (kc, ks), (vc, vs) = _quantize_cache(kc), _quantize_cache(vc)
            scales = dict(k_scale=ks, v_scale=vs)

        def masked(q, kc, vc, li, tables, seg, pos, valid):
            out = jnp.zeros(q.shape, jnp.float32)
            span = jnp.arange(tables.shape[1] * bs)
            for s in range(tables.shape[0]):
                own = (seg == s) & valid
                sel = own[:, None] & (span[None, :] <= pos[:, None])
                o = _masked_flash_pallas(q, kc, vc, li, tables[s], sel,
                                         interpret)
                out = jnp.where(own[:, None, None], o, out)
            return out.astype(q.dtype)

        forms = {
            "xla": lambda *x: pp.packed_prefill_attention(
                *x, impl="xla", **scales),
            "auto": lambda *x: pp.packed_prefill_attention(
                *x, impl="auto", **scales),
        }
        if not args.int8:       # the masked pass has no int8 form
            forms["masked"] = masked
        for tb, cc in tiles:
            forms[f"tile{tb}x{cc}"] = (
                lambda q, kc, vc, li, *r, tb=tb, cc=cc:
                packed_prefill_attention_pallas(
                    q, kc, vc, li, *r, token_block=tb, chunk_cols=cc,
                    interpret=interpret, **scales))
        forms = {k: f for k, f in forms.items()
                 if ("tile" if k.startswith("tile") else k)
                 in args.forms.split(",")}
        row = {"bench": "prefill_attn_sweep", "mode": args.mode,
               "model": args.model, "tokens": T, "width": width,
               "rows": rows, "int8": args.int8, "ms": {},
               "max_abs_err": {}}
        ref = None
        for name, form in forms.items():
            @jax.jit
            def chain(q, kc, vc, *r, form=form):
                for li in range(L):
                    o = form(q, kc, vc, li, *r)
                    q = (o.astype(jnp.float32) * 0.999).astype(q.dtype)
                return q

            one = jax.jit(lambda q, kc, vc, *r, form=form:
                          form(q, kc, vc, L - 1, *r))
            try:
                got = np.asarray(one(q0, kc, vc, *a), np.float32)
                ref = got if ref is None else ref
                row["max_abs_err"][name] = round(
                    float(np.abs(got - ref).max()), 5)
                row["ms"][name] = round(
                    timeit(lambda: chain(q0, kc, vc, *a), n=args.reps)
                    * 1e3 / L, 4)
            except Exception as e:  # a tile that does not compile
                row["ms"][name] = None
                row["max_abs_err"][name] = repr(e)[:200]
        print(json.dumps(row), flush=True)


def main():
    p = argparse.ArgumentParser(
        description="per-phase prefill profiler (see module docstring)")
    p.add_argument("phases", nargs="*",
                   help="phase tags: packed batched single attn kv_write "
                        "weights (default: all)")
    p.add_argument("--model", default="llama-3b")
    p.add_argument("--tokens", type=int, default=2048,
                   help="packed chunk budget (total stream tokens)")
    p.add_argument("--seqs", type=int, default=4,
                   help="co-scheduled prompts packed per dispatch")
    p.add_argument("--ctx-blocks", type=int, default=16,
                   help="block-table width per sequence")
    p.add_argument("--block", type=int, default=128)
    p.add_argument("--impl", default="xla",
                   choices=["xla", "pallas", "pallas_interpret", "ab"],
                   help="packed-attention impl for the `packed` phase; "
                        "`ab` runs the XLA reference AND the Pallas "
                        "tile-skip kernel (compiled in --mode tpu, "
                        "interpreted in --mode smoke) and prints both "
                        "variants' MFU in one JSON line")
    p.add_argument("--sweep", default="",
                   help="attention op alone over shapes: comma-separated "
                        "TOKENSxWIDTH[xROWS] (table width in blocks); "
                        "needs no weights, so any preset's widths fit")
    p.add_argument("--tiles", default="128x8",
                   help="--sweep: the tile-skip kernel's "
                        "token_block x chunk_cols variants, comma-separated")
    p.add_argument("--forms", default="xla,auto,masked,tile",
                   help="--sweep: which columns to time")
    p.add_argument("--int8", action="store_true",
                   help="--sweep: over an int8 cache with scale planes")
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--mode", default="tpu", choices=["tpu", "smoke"],
                   help="tpu (default): needs a TPU and fails without "
                        "one; interpret-mode kernels are an error.  "
                        "smoke: CPU run at toy size, rows labeled smoke")
    args = p.parse_args()
    if args.mode == "tpu":
        if args.impl == "pallas_interpret":
            p.error("--impl pallas_interpret is not a measurement; "
                    "use --mode smoke")
        device = require_tpu()
        # published bf16 peak of the chip JAX found (unknown kind: error)
        peak_tflops = device_peaks(device["kind"])["bf16_tflops"]
    else:
        device = device_identity()
        peak_tflops = None  # off-chip rows carry no MFU

    def mfu_of(flops, t):
        return (None if peak_tflops is None
                else flops / t / (peak_tflops * 1e12))

    print(f"device: {json.dumps(device)} mode={args.mode}")
    if args.sweep:
        return attn_sweep(args, llama.PRESETS[args.model])
    if args.seqs > args.tokens:
        p.error(f"--seqs ({args.seqs}) must be <= --tokens "
                f"({args.tokens})")
    if args.tokens % args.seqs:
        rounded = args.tokens - args.tokens % args.seqs
        print(f"note: rounding --tokens {args.tokens} -> {rounded} "
              f"(whole {rounded // args.seqs}-token chunks per sequence)")
        args.tokens = rounded
    cap = args.ctx_blocks * args.block
    if args.tokens // args.seqs > cap:
        # JAX clamps out-of-bounds table indices, so overflowing the
        # per-sequence KV capacity would silently time the wrong
        # computation instead of erroring
        p.error(f"per-sequence chunk ({args.tokens // args.seqs} tokens) "
                f"exceeds KV capacity --ctx-blocks*--block = {cap}")
    sel = set(args.phases)

    def want(tag):
        return not sel or tag in sel

    cfg = llama.PRESETS[args.model]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # exclude the embedding lookup and an untied lm_head (logits run on
    # last-token rows only)
    skip = sum(params[k].size for k in ("embedding", "lm_head")
               if k in params)
    flops_per_tok = 2 * (n_params - skip)

    S, T, BLOCK, MB = args.seqs, args.tokens, args.block, args.ctx_blocks
    chunk = T // S
    num_blocks = 1 + S * MB
    kv = tuple(
        jnp.zeros((cfg.n_layers, cfg.n_kv_heads, num_blocks,
                   cfg.head_dim, BLOCK), cfg.dtype)
        for _ in range(2)
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(3, cfg.vocab_size, T).astype(np.int32)
    seg_ids = np.repeat(np.arange(S, dtype=np.int32), chunk)
    positions = np.tile(np.arange(chunk, dtype=np.int32), S)
    valid = np.ones(T, bool)
    tables = np.zeros((S, MB), np.int32)
    for s in range(S):
        tables[s] = 1 + s * MB + np.arange(MB)
    last_idx = (np.arange(S, dtype=np.int32) + 1) * chunk - 1

    gf = flops_per_tok * T / 1e9
    print(f"{args.model}: {S} x {chunk}-token prompts packed to T={T}; "
          f"~{gf:.1f} GF matmul per dispatch")
    dev = {k: jnp.asarray(v) for k, v in dict(
        toks=toks, seg_ids=seg_ids, positions=positions, valid=valid,
        tables=tables, last_idx=last_idx).items()}

    def report(name, t, tokens, flops):
        mfu = mfu_of(flops, t)
        print(f"  {name:10s} {t*1e3:8.2f} ms   {tokens/t/1e3:8.1f} ktok/s"
              + (f"   MFU {mfu:5.3f}" if mfu is not None else ""))

    state = {"kv": kv}

    # --- packed: the serving path --------------------------------------
    if want("packed"):
        if args.impl == "ab":
            impls = ["xla", "pallas" if args.mode == "tpu"
                     else "pallas_interpret"]
        else:
            impls = [args.impl]
        # analytic attention FLOPs per layer: score + pv matmuls over
        # each token's segment context window (mb blocks wide).  The
        # XLA reference runs one masked pass PER SEGMENT over the WHOLE
        # stream — S-fold; the Pallas kernel's tile-skip visits only a
        # token's own segment — 1x (upper bound: tile-granular causal
        # frontier skips more).
        attn_base = 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim \
            * T * MB * BLOCK
        variants = {}
        for impl in impls:
            cfg_i = dataclasses.replace(cfg, packed_attn_impl=impl)

            @jax.jit
            def packed(params, kv, toks, positions, seg_ids, tables,
                       last_idx, valid, cfg_i=cfg_i):
                lg, kv = llama.prefill_packed(
                    params, cfg_i, kv, toks, positions, seg_ids, tables,
                    last_idx, valid)
                return lg, kv

            def run_packed(packed=packed):
                lg, state["kv"] = packed(
                    params, state["kv"], dev["toks"], dev["positions"],
                    dev["seg_ids"], dev["tables"], dev["last_idx"],
                    dev["valid"])
                return lg

            t = timeit(run_packed)
            est_flops = flops_per_tok * T
            est_mfu = mfu_of(est_flops, t)
            row = {
                "ms": round(t * 1e3, 3),
                "tok_per_s": round(T / t, 1),
                "est_flops": est_flops,
                "est_mfu": est_mfu and round(est_mfu, 4),
                "attn_flops_analytic": attn_base
                * (S if impl == "xla" else 1),
            }
            variants[impl] = row
            report(f"packed/{impl}", t, T, flops_per_tok * T)
        print(json.dumps({
            "bench": "prefill_phases",
            "mode": args.mode, "device": device,
            "model": args.model, "seqs": S,
            "tokens": T, "ctx_blocks": MB, "block": BLOCK,
            "peak_tflops": peak_tflops, "target_mfu": 0.4,
            "impls": variants,
        }))

    # --- batched: every row padded to the packed total -----------------
    if want("batched"):
        btoks = np.zeros((S, T), np.int32)
        bpos = np.zeros((S, T), np.int32)
        for s in range(S):
            btoks[s, :chunk] = toks[s * chunk:(s + 1) * chunk]
            bpos[s] = np.arange(T)
        true_lens = np.full(S, chunk, np.int32)

        @jax.jit
        def batched(params, kv, toks, pos, tables, ctx, tl):
            return llama.prefill_batched(params, cfg, kv, toks, pos,
                                         tables, ctx, tl)

        dd = (jnp.asarray(btoks), jnp.asarray(bpos), dev["tables"],
              jnp.zeros(S, jnp.int32), jnp.asarray(true_lens))

        def run_batched():
            lg, state["kv"] = batched(params, state["kv"], *dd)
            return lg
        # padded program computes S*T token rows for T real tokens
        report("batched", timeit(run_batched), T, flops_per_tok * T)

    # --- single: serial B=1 dispatches ---------------------------------
    if want("single"):
        @jax.jit
        def single(params, kv, toks, pos, table):
            return llama.prefill(params, cfg, kv, toks, pos, table,
                                 jnp.int32(0), jnp.int32(chunk))

        sd = [(jnp.asarray(toks[s * chunk:(s + 1) * chunk]),
               jnp.asarray(np.arange(chunk, dtype=np.int32)),
               jnp.asarray(tables[s])) for s in range(S)]

        def run_single():
            lg = None
            for s in range(S):
                lg, state["kv"] = single(params, state["kv"], *sd[s])
            return lg
        report("single", timeit(run_single), T, flops_per_tok * T)

    # --- packed attention op alone -------------------------------------
    if want("attn"):
        q0 = jnp.asarray(
            rng.standard_normal((T, cfg.n_heads, cfg.head_dim)), cfg.dtype)

        @jax.jit
        def attn(q, kc, vc, tables, seg_ids, positions, valid):
            for li in range(cfg.n_layers):
                o = pp.packed_prefill_attention(
                    q, kc, vc, li, tables, seg_ids, positions, valid)
                q = (o.astype(jnp.float32) * 0.999).astype(q.dtype)
            return q
        # attention flops: per token ~ 2 matmuls over its own context
        afl = 4 * cfg.n_layers * cfg.n_heads * cfg.head_dim \
            * float(np.sum(positions + 1))
        report("attn", timeit(lambda: attn(
            q0, state["kv"][0], state["kv"][1], dev["tables"],
            dev["seg_ids"], dev["positions"], dev["valid"])), T, afl)

    # --- packed kv scatter alone ---------------------------------------
    if want("kv_write"):
        kvec = jnp.asarray(
            rng.standard_normal((T, cfg.n_kv_heads, cfg.head_dim)),
            cfg.dtype)

        @jax.jit
        def wr(kv, kvec, tables, seg_ids, positions, valid):
            kc, vc = kv
            for li in range(cfg.n_layers):
                kc, vc = pp.write_packed_kv(kc, vc, li, kvec, kvec,
                                            tables, seg_ids, positions,
                                            valid)
            return kc, vc

        def run_wr():
            state["kv"] = wr(state["kv"], kvec, dev["tables"],
                             dev["seg_ids"], dev["positions"],
                             dev["valid"])
            return state["kv"][0]
        wfl = 2 * cfg.n_layers * T * cfg.n_kv_heads * cfg.head_dim * 2
        report("kv_write", timeit(run_wr), T, wfl)

    # --- weights only (attention stubbed) ------------------------------
    if want("weights"):
        @jax.jit
        def wonly(params, toks, positions):
            x = params["embedding"][toks].astype(cfg.dtype)
            for layer in params["layers"]:
                h = llama.rms_norm(x, layer["attn_norm"]["norm"],
                                   cfg.rms_eps)
                q, k, v = llama._qkv(layer, cfg, h, positions)
                a = q + k.repeat(cfg.n_heads // cfg.n_kv_heads, 1)
                x = x + llama._attn_out(layer, a.reshape(T, cfg.q_dim))
                h = llama.rms_norm(x, layer["mlp_norm"]["norm"],
                                   cfg.rms_eps)
                x = x + llama._mlp(layer, h)
            return llama._logits(params, cfg, x[-1])
        report("weights",
               timeit(lambda: wonly(params, dev["toks"],
                                    dev["positions"])),
               T, flops_per_tok * T)


if __name__ == "__main__":
    main()
