"""Numbers from the profiler's trace of the middle of the window
(benchmark/lib/trace_reduce.py), combined with counts over the same
stretch and the roofline floors of benchmark/lib/roofline.py."""

from benchmark.lib.roofline import causal_attention_flops, decode_bytes
from benchmark.lib.stats import mean_live_context, overlap


def _module_seconds(ctx, kind):
    """Device seconds of the programs of one kind (decode | prefill), as
    benchmark/lib/trace_reduce.py classifies them."""
    tr = ctx.get("trace")
    if not tr:
        return None
    s = tr["kind_s"].get(kind, 0.0)
    return s if s > 0 else None


def _decode_steps(ctx):
    """Fused decode steps dispatched while the trace ran: sum of k over
    the engine's decode records (its forward-pass ring) in that stretch."""
    t0, t1 = (t + ctx["mono_offset"] for t in ctx["trace_window"])
    return sum(r["k"] for r in ctx["fpm"]
               if r["kind"] == "decode" and t0 <= r["t"] < t1)


def _traced(ctx, key):
    """The counter's growth while the trace ran; None where the program
    has no such counter."""
    a, b = ctx["trace_counters"]
    return b[key] - a.get(key, 0) if key in b else None


def idle_share(ctx):
    """100 * (1 - union of device-op intervals / traced stretch), both by
    the trace's own clock."""
    tr = ctx.get("trace")
    if not tr or not tr["extent_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["extent_s"])


def module_ms_per_decode_step(ctx, kind):
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    return s * 1e3 / steps if steps else None


def decode_hbm_share(ctx, kind):
    """100 * bytes the decode steps had to read / device time of the
    decode modules / peak HBM bytes/s.  The bytes: every weight outside
    the embedding and the routed experts once a step, an expert's
    matrices for each (step, layer, held expert) that a token visited
    (the growth of `moe_experts_visited.decode` over the stretch), and
    the live context's cache.  A configuration without experts reads
    the first and the last; one WITH experts whose program does not
    count the visits gives nothing to read: None, never the number that
    counts every expert."""
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    if not steps:
        return None
    r = ctx["roofline"]
    visited = (_traced(ctx, "moe_experts_visited.decode")
               if r["expert_bytes"] else 0)
    if visited is None:
        return None
    live = mean_live_context(ctx["records"], *ctx["trace_window"])
    need = decode_bytes(steps, visited, live,
                        dense_weight_bytes=r["dense_weight_bytes"],
                        expert_bytes=r["expert_bytes"],
                        kv_bytes_per_token=r["kv_bytes_per_token"])
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def _prefilled_tokens(ctx):
    a, b = ctx["trace_counters"]
    return b.get("prefill_tokens", 0) - a.get("prefill_tokens", 0)


def prefill_tokens_per_device_s(ctx, kind):
    s = _module_seconds(ctx, kind)
    n = _prefilled_tokens(ctx) if s is not None else 0
    return n / s if n else None


def prefill_mxu_share(ctx, kind):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill modules / peak bf16 FLOP/s.  Matmuls: tokens prefilled in the
    stretch x the per-token floor.  Attention: each request's causal
    total, by the share of its prefill (sent -> first token) that fell
    inside the stretch."""
    s = _module_seconds(ctx, kind)
    n = _prefilled_tokens(ctx) if s is not None else 0
    if not n:
        return None
    r = ctx["roofline"]
    t0, t1 = ctx["trace_window"]
    attn = 0.0
    for rec in ctx["records"]:
        if rec["sent_t"] is None or not rec["token_times"]:
            continue
        a, b = rec["sent_t"], rec["token_times"][0]
        if b > a:
            attn += (overlap(a, b, t0, t1) / (b - a)
                     * causal_attention_flops(rec["prompt_len"],
                                              r["attn_pair_flops"],
                                              r["n_layers"]))
    flops = n * r["matmul_flops_per_token"] + attn
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
