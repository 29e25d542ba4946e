"""The floors of a configuration of delta-rule linear-attention layers
beside latent attention (benchmark/lib/recurrent_floors.py) and the
readers over them (benchmark/readers/recurrent_roofline.py) on hand-made
inputs; and the metric files' constants recomputed from the
configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import recurrent_floors, spec
from benchmark.readers import recurrent_roofline

DEC = dict(dense_weight_bytes=1000.0, expert_bytes=100.0,
           lane_step_bytes=50.0, latent_token_bytes=6.0)
PRE = dict(dense_flops_per_token=1e3, pick_flops=50.0, kda_layers=5,
           rule_flops_per_token=20.0, mla_layers=2, attn_pair_flops=8.0)
CELL = "ling-3.0-flash.longgen-closed"


def test_decode_bytes_by_hand():
    # 4 steps x 1000 + 7 visited x 100 + 12 lane-steps x 50 + 300 live
    # latent tokens x 6 B
    assert recurrent_floors.decode_bytes(4, 7, 12, 300, **DEC) == \
        4000 + 700 + 600 + 1800


def test_live_latent_tokens_is_a_floor_of_the_blocks():
    # 12 lane-steps holding 40 blocks of 16 in each of 2 layers: at
    # least (40 - 12) whole blocks and one token in each lane's last
    assert recurrent_floors.live_latent_tokens(80, 12, 2, 16) == \
        28 * 16 + 12
    # a lane at ctx 16 (17 tokens live) holds 2 blocks: floor 17
    assert recurrent_floors.live_latent_tokens(2, 1, 1, 16) == 17


def test_chunk_rule_flops_by_hand():
    # one head, dk = dv = 4, chunks of 8: A 3.5 pairs x 8, B 4.5 x 8,
    # solve 3.5 x 16, three state products 3 x 32, B U 4.5 x 8
    assert recurrent_floors.chunk_rule_flops(1, 4, 4, 8) == \
        28 + 36 + 56 + 96 + 36
    # the token recurrence (decay, S'^T k, rank-one update, read: 7 dk dv
    # a head) needs less, on the vector unit: the chunk form is what can
    # run on the MXU
    assert 32 * 7 * 128 * 128 < recurrent_floors.chunk_rule_flops(
        32, 128, 128, 64)


def test_prefill_flops_by_hand():
    # 100 tokens x (1e3 + 5 x 20) + 30 picks x 50 + 2 layers x 5050
    # pairs x 8
    assert recurrent_floors.prefill_flops(100, 30, 5050, **PRE) == \
        100 * 1100 + 1500 + 2 * 5050 * 8
    assert recurrent_floors.causal_pairs(100) == 5050


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"moe_experts_visited.decode": 10, "moe_picks_held.prefill": 50,
             "recurrent_lane_steps.decode": 100,
             "decode_attn_live_blocks": 400,
             "recurrent_tokens.prefill": 1000},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"moe_experts_visited.decode": 17,
             "recurrent_lane_steps.decode": 136,
             "decode_attn_live_blocks": 560})
    # 12 steps in the stretch, 7 visited, 36 lane-steps; 160 blocks over
    # 2 layers = 80 a layer: (80 - 36) x 16 + 36 = 740 live tokens
    want = 12000 + 700 + 36 * 50 + 740 * 6
    assert recurrent_roofline.decode_hbm_share(
        c, "decode", mla_layers=2, block_size=16, **DEC) == \
        pytest.approx(100 * want / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    kw = dict(mla_layers=2, block_size=16, **DEC)
    assert recurrent_roofline.decode_hbm_share(old, "decode", **kw) is None
    assert recurrent_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **kw) is None
    assert recurrent_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **kw) is None


def test_prefill_mxu_share_reader():
    rec = {"sent_t": 100.0, "token_times": [100.2], "prompt_len": 100}
    c = ctx({"recurrent_tokens.prefill": 1100,
             "moe_picks_held.prefill": 80}, records=[rec])
    # half of the request's prefill fell inside the stretch
    want = 100 * 1100 + 1500 + 2 * 0.5 * 5050 * 8
    assert recurrent_roofline.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * want / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert recurrent_roofline.prefill_mxu_share(old, "prefill", **PRE) \
        is None
    idle = ctx({"recurrent_tokens.prefill": 1000,
                "moe_picks_held.prefill": 50})
    assert recurrent_roofline.prefill_mxu_share(idle, "prefill", **PRE) \
        is None


def test_counter_shares_read_nothing_from_a_program_without_them():
    window = {"counters_open": {"recurrent_lane_steps.decode": 100,
                                "recurrent_slot_steps.decode": 200,
                                "recurrent_tokens.prefill": 0,
                                "recurrent_carried_tokens.prefill": 0},
              "counters_close": {"recurrent_lane_steps.decode": 1060,
                                 "recurrent_slot_steps.decode": 1200,
                                 "recurrent_tokens.prefill": 4000,
                                 "recurrent_carried_tokens.prefill": 1800}}
    for name, want in (("recurrent_lane_share", 96.0),
                       ("prefill_carried_share", 45.0)):
        read = spec.metric_reader("layer_metrics", name)
        assert read(window) == pytest.approx(want)
        assert read({"counters_open": {"steps": 1},
                     "counters_close": {"steps": 9}}) is None


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    recurrent_floors.constants gives for the configuration the metrics'
    cell runs, and those are the arithmetic of its keys (ISSUE 35,
    points 2 and 8)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = spec.load_cell(CELL)
    hf = cell["config"]
    want = recurrent_floors.constants(hf, hf["engine"]["block_size"])
    for name in ("recurrent_decode_hbm_share",
                 "recurrent_prefill_mxu_share"):
        assert by_name[name]["workloads"] == [CELL]
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args) - {"kind"} <= set(want)
        for k, v in args.items():
            if k != "kind":
                assert want[k] == v, (name, k)
    # by hand, from the published widths, in M parameters a layer
    kda = 3 * 10.486 + 10.486 + 10.486 + 0.082 + 0.082
    mla = 15.729 + 1.475 + 4.194 + 10.486
    dense, router_shared = 47.186, 1.311 + 5.898
    outside = 10 * kda + 2 * mla + 2 * dense + 10 * router_shared
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(
        outside, 1e-3)
    assert want["dense_weight_bytes"] / 2e6 == pytest.approx(
        outside + 10 * 0.049 + 402.39, 1e-3)
    assert want["expert_bytes"] == 2 * 3 * 2560 * 768
    assert want["pick_flops"] == 2 * 3 * 2560 * 768
    # a lane-step: 10 layers x (4 MiB state read + written, the 3-token
    # tail of 12288 bf16 channels read + written)
    assert want["lane_step_bytes"] == 10 * 2 * (32 * 128 * 128 * 4
                                                + 3 * 12288 * 2)
    assert want["latent_token_bytes"] == 2 * 576 * 2
    assert (want["kda_layers"], want["mla_layers"]) == (10, 2)
    assert want["rule_flops_per_token"] == pytest.approx(4.452e6, 1e-3)
    # the reference's own count of an MLA pair agrees
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"] == 20480
    assert cfg.layers_of(1) == (5, 11) and cfg.held == (0, 16)
    # the program's own cache shapes are the configuration's
    from dynamo_tpu.models import ling
    shapes = ling.kv_cache_shapes(cfg, 2881, 128, lanes=64)
    assert shapes[2] == (10, 64, 32, 128, 128)
    assert shapes[3] == (10, 64, 3, 12288)


def test_the_cell_keeps_out_of_the_floors_that_miscount_it():
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in ("decode_hbm_share", "prefill_mxu_share",
                         "moe_decode_hbm_share", "moe_prefill_mxu_share",
                         "kv_window_held_share") \
                or m["name"].startswith("sparse_"):
            assert CELL not in m["workloads"], m["name"]
