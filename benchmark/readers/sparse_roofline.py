"""Roofline shares for a configuration whose attention chooses its keys
and that holds a share of its experts: the floors of
benchmark/lib/sparse_floors.py, fed from the program's counters over the
traced stretch, over the device time of the programs of one kind.  A
program without those counters (the parent of the PR that added them)
gives nothing to read: None, and the metric is left out."""

from benchmark.lib import sparse_floors
from benchmark.readers.device_trace import _decode_steps, _module_seconds
from benchmark.readers.moe_roofline import _traced


def _all_traced(ctx, *keys):
    """The counters' growth while the trace ran, or None where the
    program lacks one of them."""
    grown = [_traced(ctx, k) for k in keys]
    return None if any(g is None for g in grown) else grown


def decode_hbm_share(ctx, kind, dense_weight_bytes, expert_bytes, layers,
                     index_key_bytes, kv_token_bytes):
    """100 * bytes the decode steps had to read / device time of the
    decode programs / peak HBM bytes/s."""
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    grown = _all_traced(ctx, "moe_experts_visited.decode",
                        "sparse_ctx_tokens.decode",
                        "sparse_selected_tokens.decode") if steps else None
    if grown is None:
        return None
    need = sparse_floors.decode_bytes(
        steps, *grown, dense_weight_bytes=dense_weight_bytes,
        expert_bytes=expert_bytes, layers=layers,
        index_key_bytes=index_key_bytes, kv_token_bytes=kv_token_bytes)
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mxu_share(ctx, kind, dense_flops_per_token, pick_flops, layers,
                      index_pair_flops, attn_pair_flops):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill programs / peak bf16 FLOP/s; the pairs are the program's own
    count of what its chunks scored and kept, by position."""
    s = _module_seconds(ctx, kind)
    grown = _all_traced(ctx, "prefill_tokens", "moe_picks_held.prefill",
                        "sparse_pairs_scored.prefill",
                        "sparse_pairs_attended.prefill") \
        if s is not None else None
    if grown is None or not grown[0]:
        return None
    flops = sparse_floors.prefill_flops(
        *grown, dense_flops_per_token=dense_flops_per_token,
        pick_flops=pick_flops, layers=layers,
        index_pair_flops=index_pair_flops, attn_pair_flops=attn_pair_flops)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
