"""Roofline shares for a DENSE configuration of Mamba-2 state-space
layers beside GQA attention layers, a gated MLP in every layer and no
experts: the floors of benchmark/lib/ssm_floors.py with their expert
terms at zero (`readers/ssm_roofline.py` waits for `moe_*` counters that
a program without experts never has), fed from the program's counters
over the traced stretch, over the device time of the programs of one
kind.  A program without those counters gives nothing to read: None,
and the metric is left out."""

from benchmark.lib import ssm_floors
from benchmark.lib.stats import overlap
from benchmark.readers.device_trace import _decode_steps, _module_seconds
from benchmark.readers.moe_roofline import _traced
from benchmark.readers.sparse_roofline import _all_traced


def decode_hbm_share(ctx, kind, dense_weight_bytes, lane_step_bytes,
                     kv_token_bytes, attn_layers, block_size):
    """100 * bytes the decode steps had to move / device time of the
    decode programs / peak HBM bytes/s."""
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    grown = _all_traced(ctx, "ssm_lane_steps.decode",
                        "decode_attn_live_blocks") if steps else None
    if grown is None:
        return None
    lane_steps, live_blocks = grown
    need = ssm_floors.decode_bytes(
        steps, 0.0, lane_steps,
        ssm_floors.live_tokens(live_blocks, lane_steps, attn_layers,
                               block_size),
        dense_weight_bytes=dense_weight_bytes, expert_bytes=0.0,
        lane_step_bytes=lane_step_bytes, kv_token_bytes=kv_token_bytes)
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mxu_share(ctx, kind, dense_flops_per_token, ssm_layers,
                      scan_flops_per_token, attn_layers, attn_pair_flops):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill programs / peak bf16 FLOP/s.  Real tokens only: a bucket's
    padded rows are work the program adds, not work the prompt needs.
    The attention layers' pairs: each request's causal total, by the
    share of its prefill (sent -> first token) that fell inside the
    stretch."""
    s = _module_seconds(ctx, kind)
    tokens = _traced(ctx, "ssm_tokens.prefill") if s is not None else None
    if not tokens:
        return None
    t0, t1 = ctx["trace_window"]
    pairs = 0.0
    for rec in ctx["records"]:
        if rec["sent_t"] is None or not rec["token_times"]:
            continue
        a, b = rec["sent_t"], rec["token_times"][0]
        if b > a:
            pairs += (overlap(a, b, t0, t1) / (b - a)
                      * ssm_floors.causal_pairs(rec["prompt_len"]))
    flops = ssm_floors.prefill_flops(
        tokens, 0.0, pairs, dense_flops_per_token=dense_flops_per_token,
        pick_flops=0.0, ssm_layers=ssm_layers,
        scan_flops_per_token=scan_flops_per_token, attn_layers=attn_layers,
        attn_pair_flops=attn_pair_flops)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
