"""The floors of a configuration whose attention chooses its keys
(benchmark/lib/sparse_floors.py) and the readers over them
(benchmark/readers/sparse_roofline.py) on hand-made inputs; and the
metric files' constants recomputed from the configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import sparse_floors, spec
from benchmark.readers import counters, sparse_roofline

DEC = dict(dense_weight_bytes=1000.0, expert_bytes=100.0, layers=3,
           index_key_bytes=2.0, kv_token_bytes=16.0)
PRE = dict(dense_flops_per_token=1e3, pick_flops=50.0, layers=3,
           index_pair_flops=4.0, attn_pair_flops=32.0)
CELL = "keye-vl-2.0.longctx-closed"


def test_decode_bytes_by_hand():
    # 4 steps x 1000 + 7 visited x 100 + 3 layers x (500 scored x 2 B
    # + 60 kept x 16 B)
    assert sparse_floors.decode_bytes(4, 7, 500, 60, **DEC) == \
        4000 + 700 + 3 * (1000 + 960)


def test_prefill_flops_by_hand():
    # 100 tokens x 1e3 + 30 picks x 50 + 3 layers x (5050 scored x 4
    # + 900 kept x 32)
    assert sparse_floors.prefill_flops(100, 30, 5050, 900, **PRE) == \
        100e3 + 1500 + 3 * (20200 + 28800)


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"prefill_tokens": 1000, "moe_experts_visited.decode": 10,
             "moe_picks_held.prefill": 50, "sparse_ctx_tokens.decode": 100,
             "sparse_selected_tokens.decode": 40,
             "sparse_pairs_scored.prefill": 1000,
             "sparse_pairs_attended.prefill": 500},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"moe_experts_visited.decode": 17,
             "sparse_ctx_tokens.decode": 600,
             "sparse_selected_tokens.decode": 100})
    # 12 steps in the stretch: 12000 + 700 + 3 x (500 x 2 + 60 x 16)
    assert sparse_roofline.decode_hbm_share(c, "decode", **DEC) == \
        pytest.approx(100 * (12700 + 5880) / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert sparse_roofline.decode_hbm_share(old, "decode", **DEC) is None
    assert sparse_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **DEC) is None
    assert sparse_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **DEC) is None


def test_prefill_mxu_share_reader():
    c = ctx({"prefill_tokens": 1100, "moe_picks_held.prefill": 80,
             "sparse_pairs_scored.prefill": 6050,
             "sparse_pairs_attended.prefill": 1400})
    assert sparse_roofline.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * (100e3 + 1500 + 3 * (20200 + 28800))
                      / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert sparse_roofline.prefill_mxu_share(old, "prefill", **PRE) is None
    idle = ctx({"prefill_tokens": 1000, "moe_picks_held.prefill": 50,
                "sparse_pairs_scored.prefill": 1000,
                "sparse_pairs_attended.prefill": 500})
    assert sparse_roofline.prefill_mxu_share(idle, "prefill", **PRE) is None


def test_counter_shares_read_nothing_from_a_program_without_them():
    window = {"counters_open": {"sparse_ctx_tokens.decode": 100,
                                "sparse_selected_tokens.decode": 40,
                                "sparse_read_tokens.decode": 100},
              "counters_close": {"sparse_ctx_tokens.decode": 1100,
                                 "sparse_selected_tokens.decode": 240,
                                 "sparse_read_tokens.decode": 1100}}
    for name, want in (("sparse_selected_share", 20.0),
                       ("sparse_read_share", 20.0)):
        read = spec.metric_reader("layer_metrics", name)
        assert read(window) == pytest.approx(want)
        assert read({"counters_open": {"steps": 1},
                     "counters_close": {"steps": 9}}) is None
    assert counters.share_of_deltas(
        window, "sparse_selected_tokens.decode",
        "sparse_selected_tokens.decode") == 100.0     # only the kept moved


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    sparse_floors.constants gives for the configuration the metrics'
    cells run, and those are the arithmetic of its keys (ISSUE 33,
    points 2 and 8)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    hf = spec.load_cell(CELL)["config"]
    want = sparse_floors.constants(hf)
    for name in ("sparse_decode_hbm_share", "sparse_prefill_mxu_share"):
        assert by_name[name]["workloads"] == [CELL]
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args) - {"kind"} <= set(want)
        for k, v in args.items():
            if k != "kind":
                assert want[k] == v, (name, k)
    # by hand, from the published widths, in M parameters a layer
    attention = 8.389 + 1.049 + 1.049 + 8.389
    indexer = 2.097 + 0.131 + 0.033
    outside = attention + indexer + 0.262
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(
        12 * outside, 1e-3)
    assert want["dense_weight_bytes"] / 2e6 == pytest.approx(
        12 * outside + 311.16, 1e-3)
    assert want["expert_bytes"] == 2 * 3 * 2048 * 768
    assert want["pick_flops"] == 2 * 3 * 2048 * 768
    assert (want["index_key_bytes"], want["kv_token_bytes"]) == (128, 2048)
    assert (want["index_pair_flops"], want["attn_pair_flops"]) == \
        (2048, 16384)
    assert want["layers"] == 12
    # the reference's own count of a pair agrees
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"]
    assert klass.index_pair_flops(cfg) == want["index_pair_flops"]


def test_the_cell_keeps_out_of_the_floors_that_miscount_it():
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in ("decode_hbm_share", "prefill_mxu_share",
                         "moe_decode_hbm_share", "moe_prefill_mxu_share",
                         "decode_attn_live_share", "kv_window_held_share"):
            assert CELL not in m["workloads"], m["name"]
