"""The floors of a share-of-experts, two-kind-cache configuration
(benchmark/lib/moe_floors.py) and the readers over them
(benchmark/readers/moe_roofline.py) on hand-made inputs; and the metric
files' constants recomputed from the configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import moe_floors, spec
from benchmark.readers import moe_roofline

ARGS = dict(dense_weight_bytes=1000.0, expert_bytes=100.0, global_layers=2,
            window_layers=5, global_block_bytes=10.0,
            window_block_bytes=20.0)


def test_decode_bytes_by_hand():
    # 4 steps x 1000 + 7 visited x 100 + 30 block-steps x 2 layers x 10
    # + 8 block-steps x 5 layers x 20
    assert moe_floors.decode_bytes(4, 7, 30, 8, **ARGS) == \
        4000 + 700 + 600 + 800


@pytest.mark.parametrize("n,window,want", [
    (1, 128, 1), (3, 128, 6), (128, 128, 128 * 129 / 2),
    (130, 128, 128 * 129 / 2 + 2 * 128), (5, 2, 3 + 3 * 2)])
def test_window_pairs(n, window, want):
    assert moe_floors.window_pairs(n, window) == want
    # never more than the causal pairs, equal while the prompt fits
    assert moe_floors.window_pairs(n, window) <= moe_floors.causal_pairs(n)
    brute = sum(min(i + 1, window) for i in range(n))
    assert brute == want


def test_prefill_flops_by_hand():
    attn = moe_floors.prompt_attention_flops(
        4, pair_flops=8.0, global_layers=2, window_layers=3, window=2)
    # causal 10 pairs x 2 layers + window (1 + 2 + 2 + 2) x 3 layers
    assert attn == 8.0 * (2 * 10 + 3 * 7)
    assert moe_floors.prefill_flops(
        100, 30, attn, dense_flops_per_token=1e3, pick_flops=50.0) == \
        100e3 + 1500 + attn


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"prefill_tokens": 1000, "moe_experts_visited.decode": 10,
             "kv_uniform_block_steps": 100, "decode_attn_live_blocks": 300,
             "moe_picks_held.prefill": 50},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"prefill_tokens": 1000, "moe_experts_visited.decode": 17,
             "kv_uniform_block_steps": 130,
             # 2 global layers x 30 + 5 window layers x 8
             "decode_attn_live_blocks": 300 + 60 + 40,
             "moe_picks_held.prefill": 50})
    # 12 steps in the stretch: 12000 + 700 + 600 + 800 bytes in 0.02 s
    assert moe_roofline.decode_hbm_share(c, "decode", **ARGS) == \
        pytest.approx(100 * 14100 / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert moe_roofline.decode_hbm_share(old, "decode", **ARGS) is None
    assert moe_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **ARGS) is None
    assert moe_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **ARGS) is None


def test_prefill_mxu_share_reader():
    args = dict(dense_flops_per_token=1e3, pick_flops=50.0, pair_flops=8.0,
                global_layers=2, window_layers=3, window=2)
    rec = {"sent_t": 100.0, "token_times": [100.2], "prompt_len": 4}
    c = ctx({"prefill_tokens": 1100, "moe_picks_held.prefill": 80},
            records=[rec, {"sent_t": None, "token_times": [],
                           "prompt_len": 9}])
    # half of the request's prefill (100.0 - 100.2) lies in the stretch
    attn = 0.5 * 8.0 * (2 * 10 + 3 * 7)
    assert moe_roofline.prefill_mxu_share(c, "prefill", **args) == \
        pytest.approx(100 * (100e3 + 30 * 50 + attn) / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert moe_roofline.prefill_mxu_share(old, "prefill", **args) is None
    idle = ctx({"prefill_tokens": 1000, "moe_picks_held.prefill": 50})
    assert moe_roofline.prefill_mxu_share(idle, "prefill", **args) is None


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    moe_floors.constants gives for the configuration the metrics' cells
    run, and those are the arithmetic of its keys."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("moe_decode_hbm_share", "moe_prefill_mxu_share"):
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        for cell in by_name[name]["workloads"]:
            loaded = spec.load_cell(cell)
            want = moe_floors.constants(
                loaded["config"], loaded["config"]["engine"]["block_size"])
            for k, v in args.items():
                if k != "kind":
                    assert want[k] == v, (name, cell, k)
    hf = spec.load_cell("mimo-v2-flash.reason-closed")["config"]
    c = moe_floors.constants(hf, 128)
    # by hand, from the published widths (ISSUE 29, point 2), in M
    g = 50.33 + 3.15 + 2.10 + 33.55
    w = 50.33 + 6.29 + 4.19 + 33.55
    dense = 2 * g + 5 * w + 201.33 + 6 * 1.05
    assert c["dense_flops_per_token"] / 2e6 == pytest.approx(dense, 1e-3)
    assert c["dense_weight_bytes"] / 2e6 == pytest.approx(
        dense + 624.95, 1e-3)
    assert c["expert_bytes"] == 2 * 3 * 4096 * 2048
    assert c["global_block_bytes"] == 4 * 320 * 128 * 2
    assert c["window_block_bytes"] == 8 * 320 * 128 * 2
    assert c["pair_flops"] == 64 * 2 * 320
    assert (c["global_layers"], c["window_layers"], c["window"]) == \
        (2, 5, 128)
