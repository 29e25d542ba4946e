"""Roofline shares for a configuration that holds a share of its experts
and mixes window and global attention layers: the floors of
benchmark/lib/moe_floors.py, fed from the program's counters over the
traced stretch, over the device time of the programs of one kind.  A
program without those counters (the parent of the PR that added them)
gives nothing to read: None, and the metric is left out."""

from benchmark.lib import moe_floors
from benchmark.lib.stats import overlap
from benchmark.readers.device_trace import (_decode_steps, _module_seconds,
                                            _traced)


def decode_hbm_share(ctx, kind, dense_weight_bytes, expert_bytes,
                     global_layers, window_layers, global_block_bytes,
                     window_block_bytes):
    """100 * bytes the decode steps had to read / device time of the
    decode programs / peak HBM bytes/s.  Blocks the masks need: global
    layers `kv_uniform_block_steps` (ceil((ctx + 1) / block) a lane and
    step), window layers what is left of `decode_attn_live_blocks`
    (which sums both kinds, a layer each)."""
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    visited = _traced(ctx, "moe_experts_visited.decode") if steps else None
    g_blocks = _traced(ctx, "kv_uniform_block_steps") if steps else None
    live = _traced(ctx, "decode_attn_live_blocks") if steps else None
    if visited is None or g_blocks is None or live is None:
        return None
    w_blocks = (live - global_layers * g_blocks) / max(window_layers, 1)
    need = moe_floors.decode_bytes(
        steps, visited, g_blocks, w_blocks,
        dense_weight_bytes=dense_weight_bytes, expert_bytes=expert_bytes,
        global_layers=global_layers, window_layers=window_layers,
        global_block_bytes=global_block_bytes,
        window_block_bytes=window_block_bytes)
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mxu_share(ctx, kind, dense_flops_per_token, pick_flops,
                      pair_flops, global_layers, window_layers, window):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill programs / peak bf16 FLOP/s.  Attention: each request's
    total by kind, by the share of its prefill (sent -> first token)
    that fell inside the stretch."""
    s = _module_seconds(ctx, kind)
    tokens = _traced(ctx, "prefill_tokens") if s is not None else None
    picks = _traced(ctx, "moe_picks_held.prefill") if tokens else None
    if not tokens or picks is None:
        return None
    t0, t1 = ctx["trace_window"]
    attn = 0.0
    for rec in ctx["records"]:
        if rec["sent_t"] is None or not rec["token_times"]:
            continue
        a, b = rec["sent_t"], rec["token_times"][0]
        if b > a:
            attn += (overlap(a, b, t0, t1) / (b - a)
                     * moe_floors.prompt_attention_flops(
                         rec["prompt_len"], pair_flops=pair_flops,
                         global_layers=global_layers,
                         window_layers=window_layers, window=window))
    flops = moe_floors.prefill_flops(
        tokens, picks, attn, dense_flops_per_token=dense_flops_per_token,
        pick_flops=pick_flops)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
