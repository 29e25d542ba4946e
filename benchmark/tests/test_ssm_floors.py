"""The floors of a configuration of Mamba-2 state-space blocks beside
GQA attention and plain routed experts (benchmark/lib/ssm_floors.py) and
the readers over them (benchmark/readers/ssm_roofline.py) on hand-made
inputs; the metric files' constants recomputed from the configuration
file's keys; and that a floor never exceeds a kernel-free count of the
same work."""

import json
import os

import pytest

from benchmark.lib import spec, ssm_floors
from benchmark.readers import ssm_roofline

DEC = dict(dense_weight_bytes=1000.0, expert_bytes=100.0,
           lane_step_bytes=50.0, kv_token_bytes=6.0)
PRE = dict(dense_flops_per_token=1e3, pick_flops=50.0, ssm_layers=5,
           scan_flops_per_token=20.0, attn_layers=2, attn_pair_flops=8.0)
CELL = "nemotron-twotower.chat"


def test_decode_bytes_by_hand():
    # 4 steps x 1000 + 7 visited x 100 + 12 lane-steps x 50 + 300 live
    # K/V tokens x 6 B
    assert ssm_floors.decode_bytes(4, 7, 12, 300, **DEC) == \
        4000 + 700 + 600 + 1800
    # 12 lane-steps holding 40 blocks of 16 in each of 2 blocks of the
    # model: at least (40 - 12) whole blocks and one token in each last
    assert ssm_floors.live_tokens(80, 12, 2, 16) == 28 * 16 + 12


def test_scan_flops_is_the_cheaper_form_and_under_what_the_program_does():
    # one head of 4 over a state of 8, one group: the recurrence 5 x 32;
    # chunks of 2: 1.5 pairs x (16 + 8) + 4 x 32 = 164 -> the recurrence
    assert ssm_floors.scan_flops(1, 4, 8, 1, 2) == 160
    # four heads a group and a long state: C . B is shared by the heads
    # and the chunked form wins: 1.5 x (2 x 64 + 4 x 2) + 4 x 4 x 64 = 1228
    # against 4 x 5 x 64 = 1280
    assert ssm_floors.scan_flops(4, 1, 64, 1, 2) == 1228
    # at the published sizes the floor is the recurrence's 5 P N a head,
    # and under a kernel-free count of what the program's chunked form
    # multiplies: the WHOLE square of a chunk of 128 (C . B^T a group,
    # its product with x a head), the feed and the read of the state
    H, P, N, G, C = 64, 64, 128, 8, 128
    floor = ssm_floors.scan_flops(H, P, N, G, C)
    assert floor == H * 5 * P * N
    program = G * C * 2 * N + H * C * 2 * P + H * 4 * P * N
    assert floor < program
    # ... and over nothing: no form reads and feeds a state for less
    assert floor >= H * 4 * P * N


def test_prefill_flops_by_hand():
    # 100 tokens x (1e3 + 5 x 20) + 30 picks x 50 + 2 blocks x 5050
    # pairs x 8
    assert ssm_floors.prefill_flops(100, 30, 5050, **PRE) == \
        100 * 1100 + 1500 + 2 * 5050 * 8
    assert ssm_floors.causal_pairs(100) == 5050


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"moe_experts_visited.decode": 10, "moe_picks_held.prefill": 50,
             "ssm_lane_steps.decode": 100, "decode_attn_live_blocks": 400,
             "ssm_tokens.prefill": 1000},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"moe_experts_visited.decode": 17,
             "ssm_lane_steps.decode": 136,
             "decode_attn_live_blocks": 560})
    # 12 steps in the stretch, 7 visited, 36 lane-steps; 160 blocks over
    # 2 attention blocks = 80 each: (80 - 36) x 16 + 36 = 740 live tokens
    want = 12000 + 700 + 36 * 50 + 740 * 6
    kw = dict(attn_layers=2, block_size=16, **DEC)
    assert ssm_roofline.decode_hbm_share(c, "decode", **kw) == \
        pytest.approx(100 * want / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert ssm_roofline.decode_hbm_share(old, "decode", **kw) is None
    assert ssm_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **kw) is None
    assert ssm_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **kw) is None


def test_prefill_mxu_share_reader():
    rec = {"sent_t": 100.0, "token_times": [100.2], "prompt_len": 100}
    c = ctx({"ssm_tokens.prefill": 1100, "moe_picks_held.prefill": 80},
            records=[rec])
    # half of the request's prefill fell inside the stretch
    want = 100 * 1100 + 1500 + 2 * 0.5 * 5050 * 8
    assert ssm_roofline.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * want / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert ssm_roofline.prefill_mxu_share(old, "prefill", **PRE) is None
    idle = ctx({"ssm_tokens.prefill": 1000, "moe_picks_held.prefill": 50})
    assert ssm_roofline.prefill_mxu_share(idle, "prefill", **PRE) is None


def test_counter_shares_read_nothing_from_a_program_without_them():
    window = {"counters_open": {"ssm_lane_steps.decode": 100,
                                "ssm_slot_steps.decode": 200,
                                "ssm_tokens.prefill": 0,
                                "ssm_pad_tokens.prefill": 0},
              "counters_close": {"ssm_lane_steps.decode": 1060,
                                 "ssm_slot_steps.decode": 1200,
                                 "ssm_tokens.prefill": 3000,
                                 "ssm_pad_tokens.prefill": 1000}}
    for name, want in (("ssm_lane_share", 96.0), ("ssm_pad_share", 25.0)):
        read = spec.metric_reader("layer_metrics", name)
        assert read(window) == pytest.approx(want)
        assert read({"counters_open": {"steps": 1},
                     "counters_close": {"steps": 9}}) is None


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    ssm_floors.constants gives for the configuration the metrics' cell
    runs, and those are the arithmetic of its keys (ISSUE 40, points 2
    and 8)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = spec.load_cell(CELL)
    hf = cell["config"]
    want = ssm_floors.constants(hf, hf["engine"]["block_size"])
    for name in ("ssm_decode_hbm_share", "ssm_prefill_mxu_share"):
        assert by_name[name]["workloads"] == [CELL]
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args) - {"kind"} <= set(want)
        for k, v in args.items():
            if k != "kind":
                assert want[k] == v, (name, k)
    # by hand, from the published widths, in M parameters a block
    mamba = 27.697 + 11.010            # in_proj 2688 x 10304, out_proj
    attn = 11.010 + 1.376 + 11.010
    router_shared = 0.344 + 19.956
    outside = 12 * mamba + 4 * attn + 11 * router_shared
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(
        outside, 1e-3)
    assert want["dense_weight_bytes"] / 2e6 == pytest.approx(
        outside + 12 * 0.0307 + 352.32, 1e-3)
    # a plain expert: two matrices
    assert want["expert_bytes"] == 2 * 2 * 2688 * 1856
    assert want["pick_flops"] == 2 * 2 * 2688 * 1856
    # a lane-step: 12 blocks x (2 MiB state read + written, the 3-token
    # tail of 6144 bf16 channels read + written)
    assert want["lane_step_bytes"] == 12 * 2 * (64 * 64 * 128 * 4
                                                + 3 * 6144 * 2)
    assert want["kv_token_bytes"] == 4 * 1024
    assert (want["ssm_layers"], want["attn_layers"]) == (12, 4)
    assert want["scan_flops_per_token"] == 64 * 5 * 64 * 128
    # the reference's own count of an attention pair agrees
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"] == 16384
    assert cfg.layers_of("*") == (5, 12, 19, 26) and cfg.held == (0, 16)
    # the program's own cache shapes are the configuration's
    from dynamo_tpu.models import nemotron_h
    e = hf["engine"]
    shapes = nemotron_h.kv_cache_shapes(cfg, e["num_blocks"],
                                        e["block_size"],
                                        lanes=e["max_num_seqs"])
    assert shapes[0] == (4, 2, 1281, 128, 128)
    assert shapes[2] == (12, 64, 64, 64, 128)
    assert shapes[3] == (12, 64, 3, 6144)


def test_the_cell_keeps_out_of_the_floors_that_miscount_it():
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in ("decode_hbm_share", "prefill_mxu_share",
                         "moe_decode_hbm_share", "moe_prefill_mxu_share",
                         "kv_window_held_share") \
                or m["name"].startswith(("sparse_", "recurrent_")):
            assert CELL not in m["workloads"], m["name"]
