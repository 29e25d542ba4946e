"""Per-layer metrics of a configuration that generates by diffusion over
blocks: ratios of the program's pass counters over the traced stretch, and the
two roofline shares of benchmark/lib/diffusion_floors.py, fed from the
counters over the traced stretch, over the device time of the programs
of one kind.  A program without those counters (the parent of the PR
that added them) gives nothing to read: None, and the metric is left
out."""

from benchmark.lib import diffusion_floors
from benchmark.readers.device_trace import (_decode_steps, _module_seconds,
                                            _traced)


def ratio_of_traced(ctx, part, whole, scale=1.0):
    """scale * d(part) / d(whole) while the trace ran (the stretch in the
    middle of the window: a traced run reads its closing counters only
    after `stop_trace` returns, when the closed loop's lanes are already
    draining); None where there is no trace, the program lacks either
    counter or `whole` did not move."""
    if not ctx.get("trace_counters"):
        return None
    p, w = _traced(ctx, part), _traced(ctx, whole)
    return scale * p / w if p is not None and w else None


def _all_traced(ctx, *keys):
    grown = [_traced(ctx, k) for k in keys]
    return None if any(g is None for g in grown) else grown


def pass_hbm_share(ctx, kind, dense_weight_bytes, expert_bytes,
                   block_bytes):
    """100 * bytes the passes had to read / device time of the pass
    programs / peak HBM bytes/s.  Passes: the sum of k over the engine's
    decode records in the stretch (a burst's unit is a pass)."""
    s = _module_seconds(ctx, kind)
    passes = _decode_steps(ctx) if s is not None else 0
    grown = _all_traced(ctx, "diff_lane_passes",
                        "moe_experts_visited.decode",
                        "decode_attn_live_blocks") if passes else None
    if grown is None:
        return None
    need = diffusion_floors.pass_bytes(
        passes, grown[1], grown[2], dense_weight_bytes=dense_weight_bytes,
        expert_bytes=expert_bytes, block_bytes=block_bytes)
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mxu_share(ctx, kind, dense_flops_per_token, pick_flops, layers,
                      attn_pair_flops):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill programs / peak bf16 FLOP/s; the pairs are the program's own
    count of what its chunks attend under the block-causal mask."""
    s = _module_seconds(ctx, kind)
    grown = _all_traced(ctx, "prefill_tokens", "moe_picks_held.prefill",
                        "diff_pairs.prefill") if s is not None else None
    if grown is None or not grown[0]:
        return None
    flops = diffusion_floors.prefill_flops(
        *grown, dense_flops_per_token=dense_flops_per_token,
        pick_flops=pick_flops, layers=layers,
        attn_pair_flops=attn_pair_flops)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
