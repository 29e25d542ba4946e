"""The floors of a configuration that generates by diffusion over blocks
(benchmark/lib/diffusion_floors.py) and the readers over them
(benchmark/readers/diffusion.py) on hand-made inputs; and the metric
files' constants recomputed from the configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import diffusion_floors, spec
from benchmark.readers import diffusion

PASS = dict(dense_weight_bytes=1000.0, expert_bytes=100.0,
            block_bytes=16.0)
PRE = dict(dense_flops_per_token=1e3, pick_flops=50.0, layers=3,
           attn_pair_flops=32.0)
CELL = "sdar-30b-a3b.reason-closed"


def test_pass_bytes_by_hand():
    # 4 passes x 1000 + 7 visited x 100 + 60 block reads x 16 B
    assert diffusion_floors.pass_bytes(4, 7, 60, **PASS) == \
        4000 + 700 + 960


def test_block_causal_pairs_by_hand():
    # 10 tokens, blocks of 4: 8 prefilled; tokens 0-3 see 4 keys, 4-7 see 8
    assert diffusion_floors.block_causal_pairs(10, 4) == 4 * 4 + 4 * 8
    assert diffusion_floors.block_causal_pairs(3, 4) == 0


def test_prefill_flops_by_hand():
    # 100 tokens x 1e3 + 30 picks x 50 + 3 layers x 900 pairs x 32
    assert diffusion_floors.prefill_flops(100, 30, 900, **PRE) == \
        100e3 + 1500 + 3 * 28800


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"prefill_tokens": 1000, "moe_experts_visited.decode": 10,
             "moe_picks_held.prefill": 50, "diff_lane_passes": 5,
             "decode_attn_live_blocks": 100, "diff_pairs.prefill": 1000},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_pass_hbm_share_reader():
    c = ctx({"moe_experts_visited.decode": 17, "diff_lane_passes": 90,
             "decode_attn_live_blocks": 160})
    # 12 passes in the stretch: 12000 + 700 + 60 x 16
    assert diffusion.pass_hbm_share(c, "decode", **PASS) == \
        pytest.approx(100 * (12700 + 960) / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert diffusion.pass_hbm_share(old, "decode", **PASS) is None
    assert diffusion.pass_hbm_share(dict(c, trace=None), "decode",
                                    **PASS) is None
    assert diffusion.pass_hbm_share(dict(c, fpm=[]), "decode",
                                    **PASS) is None


def test_prefill_mxu_share_reader():
    c = ctx({"prefill_tokens": 1100, "moe_picks_held.prefill": 80,
             "diff_pairs.prefill": 1900})
    assert diffusion.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * (100e3 + 1500 + 3 * 28800) / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert diffusion.prefill_mxu_share(old, "prefill", **PRE) is None
    idle = ctx({"prefill_tokens": 1000, "moe_picks_held.prefill": 50,
                "diff_pairs.prefill": 1000})
    assert diffusion.prefill_mxu_share(idle, "prefill", **PRE) is None


def test_the_ratios_read_nothing_from_a_program_without_the_counters():
    stretch = {"trace_counters": [
        {"diff_tokens_unmasked": 100, "diff_lane_passes": 50,
         "diff_commit_passes": 10, "diff_rows": 400},
        {"diff_tokens_unmasked": 900, "diff_lane_passes": 1050,
         "diff_commit_passes": 210, "diff_rows": 5400}]}
    for name, want in (("diff_tokens_per_pass", 0.8),
                       ("diff_commit_pass_share", 20.0),
                       ("diff_row_live_share", 80.0)):
        read = spec.metric_reader("layer_metrics", name)
        assert read(stretch) == pytest.approx(want)
        # the parent's counters, an untraced run, a stretch with no pass
        assert read({"trace_counters": [{"steps": 1}, {"steps": 9}]}) is None
        assert read({"trace": None}) is None
        assert read({"trace_counters": [stretch["trace_counters"][1]] * 2}
                    ) is None


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    diffusion_floors.constants gives for the configuration the metrics'
    cell runs, and those are the arithmetic of its keys (ISSUE 51)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = spec.load_cell(CELL)
    hf = cell["config"]
    want = diffusion_floors.constants(hf, hf["engine"]["block_size"])
    for name in ("diff_pass_hbm_share", "diff_prefill_mxu_share"):
        assert by_name[name]["workloads"] == [CELL]
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args) - {"kind"} <= set(want)
        for k, v in args.items():
            if k != "kind":
                assert want[k] == v, (name, k)
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           "diff_row_live_share.json")) as f:
        assert json.load(f)["args"]["scale"] == 100.0 * want["block_length"]
    # by hand, from the published widths, in M parameters a layer
    outside = 8.389 + 1.049 + 1.049 + 8.389 + 0.262
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(
        6 * outside, 1e-3)
    assert want["dense_weight_bytes"] / 2e6 == pytest.approx(
        6 * outside + 311.16, 1e-3)
    assert want["expert_bytes"] == want["pick_flops"] == 2 * 3 * 2048 * 768
    assert want["block_bytes"] == 2 * 4 * 128 * 128 * 2
    assert (want["layers"], want["attn_pair_flops"]) == (6, 16384)
    # every expert held, the file's keys as the catalog row has them
    assert hf["num_experts"] == 128 and "router_experts" not in hf
    # the reference's own count of a pair, and the program's of a chunk
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"]
    from dynamo_tpu.models import sdar

    whole = sum(sdar.prefill_token_counts(cfg, pos, 12)["diff_pairs.prefill"]
                for pos in (0, 12, 24))
    assert whole == diffusion_floors.block_causal_pairs(39, 4)
