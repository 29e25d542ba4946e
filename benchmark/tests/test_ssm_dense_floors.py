"""The floors of a DENSE configuration of Mamba-2 state-space layers
beside GQA attention, a gated MLP in every layer and no experts
(benchmark/lib/ssm_dense_floors.py: the constants; the floors are
lib/ssm_floors.py's with the expert terms at zero) and the readers over
them (benchmark/readers/ssm_dense_roofline.py) on hand-made inputs; the
metric files' constants recomputed from the configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import spec, ssm_dense_floors, ssm_floors
from benchmark.readers import ssm_dense_roofline, ssm_roofline

DEC = dict(dense_weight_bytes=1000.0, lane_step_bytes=50.0,
           kv_token_bytes=6.0)
PRE = dict(dense_flops_per_token=1e3, ssm_layers=5,
           scan_flops_per_token=20.0, attn_layers=2, attn_pair_flops=8.0)
CELL = "granite-4.0-h-micro.longgen-closed"
ROOFS = ("ssm_dense_decode_hbm_share", "ssm_dense_prefill_mxu_share")


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"ssm_lane_steps.decode": 100, "decode_attn_live_blocks": 400,
             "ssm_tokens.prefill": 1000},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"ssm_lane_steps.decode": 136, "decode_attn_live_blocks": 560})
    # 12 steps in the stretch, no expert, 36 lane-steps; 160 blocks over
    # 2 attention layers = 80 each: (80 - 36) x 16 + 36 = 740 live tokens
    want = 12000 + 36 * 50 + 740 * 6
    kw = dict(attn_layers=2, block_size=16, **DEC)
    assert ssm_dense_roofline.decode_hbm_share(c, "decode", **kw) == \
        pytest.approx(100 * want / 0.02 / 1e6)
    # the same floor as the expert family's reader with nothing visited
    both = ctx({"ssm_lane_steps.decode": 136, "decode_attn_live_blocks": 560,
                "moe_experts_visited.decode": 0})
    both["trace_counters"][0]["moe_experts_visited.decode"] = 0
    assert ssm_roofline.decode_hbm_share(
        both, "decode", expert_bytes=100.0, **kw) == pytest.approx(
            100 * want / 0.02 / 1e6)
    # ... which reads nothing from a program without expert counters
    assert ssm_roofline.decode_hbm_share(
        c, "decode", expert_bytes=100.0, **kw) is None
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert ssm_dense_roofline.decode_hbm_share(old, "decode", **kw) is None
    assert ssm_dense_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **kw) is None
    assert ssm_dense_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **kw) is None


def test_prefill_mxu_share_reader():
    rec = {"sent_t": 100.0, "token_times": [100.2], "prompt_len": 100}
    c = ctx({"ssm_tokens.prefill": 1100}, records=[rec])
    # 100 tokens x (1e3 + 5 x 20); half of the request's prefill fell
    # inside the stretch: 2 layers x 0.5 x 5050 pairs x 8
    want = 100 * 1100 + 2 * 0.5 * 5050 * 8
    assert ssm_dense_roofline.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * want / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    assert ssm_dense_roofline.prefill_mxu_share(old, "prefill",
                                                **PRE) is None
    idle = ctx({"ssm_tokens.prefill": 1000})
    assert ssm_dense_roofline.prefill_mxu_share(idle, "prefill",
                                                **PRE) is None


def test_carried_share_reads_nothing_from_a_program_without_it():
    read = spec.metric_reader("layer_metrics", "ssm_carried_share")
    window = {"counters_open": {"ssm_tokens.prefill": 1000,
                                "ssm_carried_tokens.prefill": 100},
              "counters_close": {"ssm_tokens.prefill": 3000,
                                 "ssm_carried_tokens.prefill": 600}}
    assert read(window) == pytest.approx(25.0)
    assert read({"counters_open": {"steps": 1},
                 "counters_close": {"steps": 9}}) is None


def _args(name):
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    ssm_dense_floors.constants gives for the configuration the metrics'
    cell runs, and those are the arithmetic of its keys (ISSUE 57)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    hf = spec.load_cell(CELL)["config"]
    want = ssm_dense_floors.constants(hf, hf["engine"]["block_size"])
    for name in ROOFS + ("ssm_carried_share",):
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert CELL in e2e[m["moves"]]["workloads"]
    for name in ROOFS:
        d = _args(name)
        assert d["reader"].startswith("ssm_dense_roofline.")
        for k, v in d["args"].items():
            if k != "kind":
                assert want[k] == v, (name, k)
    assert _args("ssm_carried_share") == {
        "reader": "counters.share_of_deltas",
        "args": {"part": "ssm_carried_tokens.prefill",
                 "whole": "ssm_tokens.prefill"}}
    # by hand, from the published widths, in M parameters a layer
    mamba = 17.433 + 8.389             # in_proj 2048 x 8512, out_proj
    attn = 4.194 + 2 * 1.049 + 4.194
    mlp = 33.554 + 16.777              # 2048 x 16384 and 8192 x 2048
    mats = 36 * mamba + 4 * attn + 40 * mlp
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(mats, 1e-3)
    # every weight once, the tied head as a matrix: 6.38 GB
    assert want["dense_weight_bytes"] / 1e9 == pytest.approx(6.383, 1e-3)
    assert want["dense_weight_bytes"] > 2e6 * (mats + 205.52)
    # a lane-step: 36 layers x (2 MiB state read + written, the 3-token
    # tail of 4352 bf16 channels read + written) = 2 x 76.44 MB
    assert want["lane_step_bytes"] == 36 * 2 * (64 * 64 * 128 * 4
                                                + 3 * 4352 * 2)
    assert want["lane_step_bytes"] / 2e6 == pytest.approx(76.44, 1e-3)
    assert want["kv_token_bytes"] == 8192
    assert (want["ssm_layers"], want["attn_layers"]) == (36, 4)
    # one group for 64 heads: the floor is still the recurrence's 5 P N
    assert want["scan_flops_per_token"] == 64 * 5 * 64 * 128 \
        == ssm_floors.scan_flops(64, 64, 128, 1, 256)
    # at 64 lanes a step's state bytes pass its weight bytes
    assert 64 * want["lane_step_bytes"] > want["dense_weight_bytes"]


def test_constants_are_the_programs_own():
    """The program's parameter tree and cache shapes at the configuration
    file's keys give the same bytes and FLOPs."""
    import jax

    from benchmark.lib import roofline
    from dynamo_tpu.models import granite_hybrid as gh

    hf = spec.load_cell(CELL)["config"]
    want = ssm_dense_floors.constants(hf, hf["engine"]["block_size"])
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"] == 8192
    tree = jax.eval_shape(lambda: gh.init_params(cfg,
                                                 jax.random.PRNGKey(0)))
    dense, expert = roofline.weight_parts(tree)
    head = cfg.vocab_size * cfg.d_model * 2
    assert expert == 0.0 and dense + head == want["dense_weight_bytes"]
    # roofline.py counts every leaf of two axes or more, and the tree's
    # leaves are stacked over the periods: the taps and the vectors count
    # there as matrices, 0.05 % of the FLOPs; the floor leaves them out
    extra = roofline.matmul_flops_per_token(tree, 0) \
        - want["dense_flops_per_token"]
    assert 0 < extra < 1e-3 * want["dense_flops_per_token"]
    e = hf["engine"]
    shapes = gh.kv_cache_shapes(cfg, e["num_blocks"], e["block_size"],
                                lanes=e["max_num_seqs"])
    assert shapes[2] == (36, 64, 64, 64, 128)
    assert shapes[3] == (36, 64, 3 * 4352)
    assert roofline.kv_bytes_per_token(
        gh.kv_cache_shapes(cfg, 1, 128)[:2], 128, 2) \
        == want["kv_token_bytes"]
    state, tail = (4 * 64 * 64 * 128, 2 * 3 * 4352)
    assert want["lane_step_bytes"] == 36 * 2 * (state + tail)


def test_the_cell_keeps_out_of_the_floors_that_miscount_it():
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in ("decode_hbm_share", "prefill_mxu_share",
                         "ssm_decode_hbm_share", "ssm_prefill_mxu_share",
                         "kv_window_held_share") \
                or m["name"].startswith(("sparse_", "recurrent_", "moe_",
                                         "sala_", "conv_", "diff_",
                                         "swa_")):
            assert CELL not in m["workloads"], m["name"]
