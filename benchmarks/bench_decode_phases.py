"""Per-phase decode profiler: localize where the decode step's HBM
bandwidth goes on the bench geometry (llama-3b, B=8, ctx=2048, bf16).

Round-4 verdict: the raw decode loop reaches only 0.55 of the HBM
roofline and nothing localizes the loss.  This script times each phase
of one fused decode burst separately on the real chip:

  full        decode_multi burst (the bench.py raw loop, per-step)
  weights     transformer matmuls only (attention stubbed out) — the
              weight-streaming bound
  attn[...]   the Pallas paged-attention op alone, 28 layers x K steps,
              for several blocks_per_chunk settings
  attn_jnp    the jnp (XLA gather) attention path for comparison
  kv_write    write_token_kv scatter alone, 28 layers x K steps
  sample      argmax over [B, vocab]

and prints a table with achieved GB/s per phase vs the chip's published
HBM peak (looked up by device_kind).

`--epilogue on|off|ab` additionally serves greedy requests through a
real JaxEngine with the fused sampling epilogue (ops/fused_sampling.py)
on/off and reports decode MBU from the same dynamo_engine_mbu{phase}
gauge the worker exports — the HBM-bound hypothesis is checked in the
same run that measures the fix, against the gauge the fleet watches.

Run on the chip:  python benchmarks/bench_decode_phases.py
"""

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# phase selection: e.g. `python bench_decode_phases.py attn kv_write`
# (populated from argv by the __main__ block; empty = all phases)
_SEL = set()
# fused-sampling A/B: None = skip; "on"/"off"/"ab" = which engine
# epilogue modes to serve (populated from --epilogue by __main__)
EPILOGUE = None


def want(tag: str) -> bool:
    return not _SEL or tag in _SEL

from dynamo_tpu.models import llama
from dynamo_tpu.ops import paged_attention as pa
from dynamo_tpu.ops.pallas_paged_attention import paged_attention_decode_pallas

MODEL = "llama-3b"
# K=64 fused steps per dispatch: every dispatch has a fixed host cost,
# so per-step numbers are noise unless each call carries enough on-chip
# work to amortize it (K chosen on an earlier set-up; to be measured
# again on today's)
B, CTX, BLOCK, K = 8, 2048, 128, 64
# the chip's published HBM peak by device_kind, set in __main__; 0 =
# unknown (any backend that is not a TPU), and then no share is printed
HBM_GBPS = 0.0
# KV storage dtype (--kv-dtype): "int8" stores quantized K/V + fp32
# scale planes (quant/kv.py) — half the KV bytes the decode read streams
KV_DTYPE = "bf16"


def _sync(r):
    """Force completion with a device FETCH: timing closes with an actual
    value read (one device->host round trip, amortized over the measured
    calls), which cannot return before execution finishes."""
    leaf = jax.tree_util.tree_leaves(r)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def timeit(fn, n=8, warm=2):
    for _ in range(warm):
        r = fn()
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    _sync(r)
    return (time.perf_counter() - t0) / n


def epilogue_report(modes):
    """Engine-level fused-sampling A/B (--epilogue): serve B greedy
    requests through a real JaxEngine per mode and report served
    tokens/s.  Greedy token streams must match between
    modes — the epilogue's byte-identity contract, re-proven here on
    the bench geometry."""
    import asyncio

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    max_blocks = CTX // BLOCK + 2

    async def run_mode(mode):
        eng = JaxEngine(EngineConfig(
            model=MODEL, block_size=BLOCK, num_blocks=B * max_blocks + 1,
            max_blocks_per_seq=max_blocks, max_num_seqs=B,
            kv_cache_dtype=KV_DTYPE, sampling_epilogue=mode, seed=0))
        eng.warmup_decode()
        rng = np.random.default_rng(0)
        prompt = [int(t) for t in rng.integers(3, 255, 64)]

        async def one(i):
            req = PreprocessedRequest(
                token_ids=prompt, request_id=f"ep-{mode}-{i}",
                sampling=SamplingOptions(temperature=0.0, seed=i),
                stop=StopConditions(max_tokens=K, ignore_eos=True))
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids)
            return toks

        t0 = time.perf_counter()
        outs = await asyncio.gather(*(one(i) for i in range(B)))
        dt = time.perf_counter() - t0
        await eng.close()
        return outs, sum(len(t) for t in outs) / dt

    print(f"epilogue A/B: {MODEL}, B={B}, {K} tokens/req, kv {KV_DTYPE}")
    results = {}
    for mode in modes:
        outs, tok_s = asyncio.run(run_mode(mode))
        results[mode] = (outs, tok_s)
        print(f"  epilogue[{mode:5s}] {tok_s:9.1f} tok/s")
    if "off" in results and "fused" in results:
        assert results["off"][0] == results["fused"][0], \
            "greedy token streams diverged between epilogue modes"
        ratio = results["fused"][1] / max(results["off"][1], 1e-9)
        print(f"  epilogue A/B: greedy streams identical; fused/off "
              f"tok/s ratio {ratio:.2f}")


def main():
    cfg = llama.PRESETS[MODEL]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    emb = params["embedding"].size

    max_blocks = CTX // BLOCK + 2
    num_blocks = B * max_blocks + 1
    quant = KV_DTYPE == "int8"
    kv = [
        jnp.zeros((cfg.n_layers, cfg.n_kv_heads, num_blocks,
                   cfg.head_dim, BLOCK),
                  jnp.int8 if quant else cfg.dtype)
        for _ in range(2)
    ]
    if quant:
        kv += [jnp.zeros((cfg.n_layers, cfg.n_kv_heads, num_blocks,
                          BLOCK), jnp.float32) for _ in range(2)]
    kv = tuple(kv)
    tables = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        tables[b] = 1 + b * max_blocks + np.arange(max_blocks)
    tables = jnp.asarray(tables)
    lens = jnp.full((B,), CTX, jnp.int32)
    rng = np.random.default_rng(0)
    tok0 = jnp.asarray(rng.integers(3, cfg.vocab_size, B, np.int32))
    q0 = jnp.asarray(
        rng.standard_normal((B, cfg.n_heads, cfg.head_dim)), cfg.dtype)

    L = cfg.n_layers
    # bytes/token/layer/head: 2*hd at bf16; hd int8 + 4B fp32 scale at int8
    per_head = (cfg.head_dim + 4) if quant else 2 * cfg.head_dim
    kv_gb = 2 * L * CTX * cfg.n_kv_heads * per_head * B / 1e9
    w_gb = (n_params - emb) * 2 / 1e9
    print(f"per-step traffic: weights {w_gb:.2f} GB + KV {kv_gb:.2f} GB"
          f" (kv dtype {KV_DTYPE})")
    rows = []

    def report(name, t_burst, gb_per_step):
        t = t_burst / K
        rows.append((name, t * 1e3, gb_per_step / t))
        print(f"  {name:16s} {t*1e3:7.2f} ms/step   "
              f"{gb_per_step / t:6.1f} GB/s"
              + (f"  ({gb_per_step / t / HBM_GBPS * 100:4.1f}% of pin)"
                 if HBM_GBPS else ""))

    # --- full burst (the raw loop) -------------------------------------
    def burst(params, kv, tokens, positions, tables, ctx_lens):
        toks, kv = llama.decode_multi(params, cfg, kv, tokens, positions,
                                      tables, ctx_lens, K)
        return toks[-1], kv
    step = jax.jit(burst, donate_argnums=(1,))
    state = {"kv": kv, "tok": tok0}

    if want("full"):
        def run_full():
            state["tok"], state["kv"] = step(
                params, state["kv"], state["tok"], lens, tables, lens)
            return state["tok"]
        report("full", timeit(run_full), w_gb + kv_gb)
        kv = state["kv"]  # the full burst DONATED the original buffers

    if want("full_jnp"):
        import dataclasses

        cfg_jnp = dataclasses.replace(cfg, attn_impl="jnp")

        def burst_jnp(params, kv, tokens, positions, tables, ctx_lens):
            toks, kv = llama.decode_multi(params, cfg_jnp, kv, tokens,
                                          positions, tables, ctx_lens, K)
            return toks[-1], kv
        stepj = jax.jit(burst_jnp, donate_argnums=(1,))

        def run_jnp():
            state["tok"], state["kv"] = stepj(
                params, state["kv"], state["tok"], lens, tables, lens)
            return state["tok"]
        report("full_jnp", timeit(run_jnp), w_gb + kv_gb)
        kv = state["kv"]

    # --- weights only (attention stubbed) ------------------------------
    def decode_noattn(params, tokens, positions):
        x = params["embedding"][tokens].astype(cfg.dtype)
        pos1 = positions[:, None]
        for layer in params["layers"]:
            h = llama.rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
            q, k, v = llama._qkv(layer, cfg, h[:, None, :], pos1)
            attn = q[:, 0] + k[:, 0].repeat(cfg.n_heads // cfg.n_kv_heads, 1)
            x = x + llama._attn_out(layer, attn.reshape(B, cfg.q_dim))
            h = llama.rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
            x = x + llama._mlp(layer, h)
        return llama._logits(params, cfg, x)

    if want("weights"):
        @jax.jit
        def wburst(params, tok, positions):
            def body(t, _):
                lg = decode_noattn(params, t, positions)
                return jnp.argmax(lg, -1).astype(jnp.int32), None
            t, _ = jax.lax.scan(body, tok, None, length=K)
            return t
        report("weights", timeit(lambda: wburst(params, tok0, lens)), w_gb)

    # --- attention only: pallas bpc sweep + debug splits + jnp ---------
    def attn_burst_fn(impl_bpc, debug=""):
        scales = kv[2:] if quant else (None, None)

        def one_step(q, kc, vc):
            for li in range(L):
                if impl_bpc == "jnp":
                    o = pa.paged_attention_decode_jnp(
                        q, kc, vc, li, tables, lens,
                        k_scale=scales[0], v_scale=scales[1])
                else:
                    o = paged_attention_decode_pallas(
                        q, kc, vc, li, tables, lens,
                        blocks_per_chunk=impl_bpc, debug_mode=debug)
                q = (o.astype(jnp.float32) * 0.999).astype(q.dtype)
            return q

        @jax.jit
        def aburst(q, kc, vc):
            def body(q, _):
                return one_step(q, kc, vc), None
            q, _ = jax.lax.scan(body, q, None, length=K)
            return q
        return aburst

    if want("attn") and quant:
        # the Pallas kernel has no int8 lane layout (see
        # ops/paged_attention.py): the quantized cache serves via the
        # jnp gather path — measure attn_jnp instead
        print("  attn_pallas      skipped: int8 cache has no pallas path")
    if want("attn") and not quant:
        for bpc in (4, 8):
            f = attn_burst_fn(bpc)
            report(f"attn_pallas[{bpc}]",
                   timeit(lambda: f(q0, kv[0], kv[1])), kv_gb)
        # NB: "compute_only" exists too but reads never-DMA'd VMEM (it
        # faulted the kernel on an earlier set-up); run it only by
        # explicit selection
        for debug in (("dma_only", "compute_only") if "attn_debug" in _SEL
                      else ("dma_only",)):
            f = attn_burst_fn(4, debug)
            report(f"attn[{debug}]",
                   timeit(lambda: f(q0, kv[0], kv[1])), kv_gb)
    if want("attn_jnp"):
        fj = attn_burst_fn("jnp")
        report("attn_jnp", timeit(lambda: fj(q0, kv[0], kv[1])), kv_gb)

    # --- official jax pallas paged attention, if importable ------------
    try:
        if not want("attn_jaxlib"):
            raise ImportError("skipped")
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as jax_paged,
        )

        # library layout: pages [nkv, total_pages, page, hd]
        kp = jnp.zeros((cfg.n_kv_heads, num_blocks, BLOCK, cfg.head_dim),
                       cfg.dtype)
        vp = jnp.zeros_like(kp)

        @jax.jit
        def jburst(q, kp, vp):
            def body(q, _):
                for _li in range(L):
                    o = jax_paged(q, kp, vp, lens, tables,
                                  pages_per_compute_block=4)
                    q = (o.astype(jnp.float32) * 0.999).astype(q.dtype)
                return q, None
            q, _ = jax.lax.scan(body, q, None, length=K)
            return q
        # one cache serves all layers here, so traffic per step is still
        # 28 gathers of the same pages = kv_gb equivalent
        report("attn_jaxlib", timeit(lambda: jburst(q0, kp, vp)), kv_gb)
        del kp, vp
    except Exception as e:  # pragma: no cover - probe
        print(f"  attn_jaxlib      unavailable: {type(e).__name__}: {e}")

    # --- kv write scatter only -----------------------------------------
    if want("kv_write"):
        kvec = jnp.asarray(
            rng.standard_normal((B, cfg.n_kv_heads, cfg.head_dim)),
            cfg.dtype)

        @partial(jax.jit, donate_argnums=(0,))
        def wr_burst(kv, kvec):
            def body(carry, _):
                for li in range(L):
                    if len(carry) == 4:
                        kc, vc, ks, vs = carry
                        carry = pa.write_token_kv(
                            kc, vc, li, kvec, kvec, tables, lens,
                            k_scale=ks, v_scale=vs)
                    else:
                        kc, vc = carry
                        carry = pa.write_token_kv(kc, vc, li, kvec, kvec,
                                                  tables, lens)
                return carry, None

            out, _ = jax.lax.scan(body, kv, None, length=K)
            return out
        wr_gb = 2 * L * B * cfg.n_kv_heads * per_head / 1e9
        state2 = {"kv": kv}

        def run_wr():
            state2["kv"] = wr_burst(state2["kv"], kvec)
            return state2["kv"][0]
        report("kv_write", timeit(run_wr), wr_gb)

    # --- sampling -------------------------------------------------------
    if want("sample"):
        logits = jnp.asarray(
            rng.standard_normal((B, cfg.vocab_size)), jnp.float32)

        @jax.jit
        def samp(lg):
            def body(c, _):
                return (jnp.argmax(lg + c[:, None], -1).astype(jnp.int32),
                        None)
            t, _ = jax.lax.scan(body, tok0, None, length=K)
            return t
        report("sample", timeit(lambda: samp(logits)),
               B * cfg.vocab_size * 4 / 1e9)


if __name__ == "__main__":
    p = argparse.ArgumentParser(
        description="per-phase decode profiler (see module docstring)")
    p.add_argument("phases", nargs="*",
                   help="phase tags to run: full full_jnp weights attn "
                        "attn_debug attn_jnp attn_jaxlib kv_write sample "
                        "(default: all)")
    p.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"],
                   help="KV storage dtype: int8 streams half the KV "
                        "bytes per decode step (quant/kv.py); the pallas "
                        "attn phases are skipped (no int8 kernel)")
    p.add_argument("--epilogue", default="", choices=["", "on", "off", "ab"],
                   help="fused sampling epilogue A/B through a real "
                        "JaxEngine: on = fused only, off = reference "
                        "only, ab = both + greedy byte-identity check; "
                        "reports served tokens/s")
    p.add_argument("--model", default=MODEL,
                   help="model preset for all phases (default llama-3b; "
                        "use tiny for a CPU smoke of --epilogue)")
    args = p.parse_args()
    from dynamo_tpu.runtime.device import device_identity, device_peaks

    _dev = device_identity()
    if _dev["platform"] == "tpu":
        HBM_GBPS = device_peaks(_dev["kind"])["hbm_gbps"]
    print(f"device: {_dev} HBM pin {HBM_GBPS or 'unknown'}")
    _SEL = set(args.phases)
    KV_DTYPE = args.kv_dtype
    MODEL = args.model
    # `epilogue` as a bare phase tag defaults to the full A/B; when the
    # epilogue is the only selection, the classic phases are skipped
    EPILOGUE = args.epilogue or ("ab" if "epilogue" in _SEL else None)
    _SEL.discard("epilogue")
    if not EPILOGUE or _SEL:
        main()
    if EPILOGUE:
        epilogue_report(
            {"on": ("fused",), "off": ("off",), "ab": ("off", "fused")}
            [EPILOGUE])
