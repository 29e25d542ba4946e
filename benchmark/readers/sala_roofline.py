"""Roofline shares for a configuration of block-selecting sparse
attention beside lightning linear-attention layers: the floors of
benchmark/lib/sala_floors.py, fed from the program's counters over the
traced stretch, over the device time of the programs of one kind.  A
program without those counters (the parent of the PR that added them)
gives nothing to read: None, and the metric is left out."""

from benchmark.lib import sala_floors
from benchmark.readers.device_trace import _decode_steps, _module_seconds
from benchmark.readers.sparse_roofline import _all_traced


def decode_hbm_share(ctx, kind, dense_weight_bytes, lane_step_bytes,
                     sparse_layers, kv_token_bytes, ck_bytes):
    """100 * bytes the decode steps had to move / device time of the
    decode programs / peak HBM bytes/s."""
    s = _module_seconds(ctx, kind)
    steps = _decode_steps(ctx) if s is not None else 0
    grown = _all_traced(ctx, "recurrent_lane_steps.decode",
                        "sala_used_tokens.decode",
                        "sala_scored_keys.decode") if steps else None
    if grown is None:
        return None
    need = sala_floors.decode_bytes(
        steps, *grown, dense_weight_bytes=dense_weight_bytes,
        lane_step_bytes=lane_step_bytes, sparse_layers=sparse_layers,
        kv_token_bytes=kv_token_bytes, ck_bytes=ck_bytes)
    return 100.0 * need / s / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mxu_share(ctx, kind, dense_flops_per_token, lightning_layers,
                      rule_flops_per_token, sparse_layers, attn_pair_flops,
                      score_pair_flops):
    """100 * FLOPs the prefilled tokens needed / device time of the
    prefill programs / peak bf16 FLOP/s; the pairs are the program's own
    count of what the equations attend and score, by position."""
    s = _module_seconds(ctx, kind)
    grown = _all_traced(ctx, "prefill_tokens",
                        "sala_pairs_attended.prefill",
                        "sala_pairs_scored.prefill") \
        if s is not None else None
    if grown is None or not grown[0]:
        return None
    flops = sala_floors.prefill_flops(
        *grown, dense_flops_per_token=dense_flops_per_token,
        lightning_layers=lightning_layers,
        rule_flops_per_token=rule_flops_per_token,
        sparse_layers=sparse_layers, attn_pair_flops=attn_pair_flops,
        score_pair_flops=score_pair_flops)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"] / ctx["chips"]
