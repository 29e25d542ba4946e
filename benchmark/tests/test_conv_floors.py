"""The constants of the gated short-convolution + GQA + routed-experts
floors (benchmark/lib/conv_floors.py) for the `lfm2_moe` configuration:
the metric files' `args` recomputed from the configuration file's keys,
the keys' arithmetic by hand, the program's own parameter tree and cache
shapes against them, and the lists the cell joins."""

import json
import os

import pytest

from benchmark.lib import conv_floors, moe_floors, roofline, spec

CELL = "lfm2-24b-a2b.longctx-closed"
FILES = ("conv_moe_decode_hbm_share", "conv_moe_prefill_mxu_share")
SHARES = {"conv_carried_share": ("conv_carried_tokens.prefill",
                                 "conv_tokens.prefill"),
          "conv_lane_share": ("conv_lane_steps.decode",
                              "conv_slot_steps.decode")}


def _args(name):
    with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_metric_files_hold_the_configurations_constants():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    loaded = spec.load_cell(CELL)
    want = conv_floors.constants(
        loaded["config"], loaded["config"]["engine"]["block_size"])
    for name in FILES:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "output_tok_per_s"
        d = _args(name)
        assert d["reader"].startswith("moe_roofline.")
        for k, v in d["args"].items():
            if k != "kind":
                assert want[k] == v, (name, k)
    for name, (part, whole) in SHARES.items():
        assert by_name[name]["workloads"] == [CELL]
        assert _args(name) == {"reader": "counters.share_of_deltas",
                               "args": {"part": part, "whole": whole}}


def test_constants_by_hand():
    """From the published widths (ISSUE 55), in parameters: a conv mixer
    2048 x 6144 + 2048 x 2048 + 3 x 2048, an attention mixer 2 x 2048 x
    2048 + 2 x 2048 x 512, the dense feed-forward 3 x 2048 x 11776, a
    router 2048 x 64, an expert 3 x 2048 x 1536; 7 conv and 2 attention
    layers, 1 dense and 8 expert layers; the tied head of 65536."""
    c = conv_floors.constants(spec.load_cell(CELL)["config"], 128)
    conv, attn = 16_783_360, 10_485_760
    matrices = 7 * conv + 2 * attn + 72_351_744 + 8 * 131_072
    assert matrices == 211_855_360
    assert c["dense_flops_per_token"] == 2 * matrices == 423_710_720
    # float32 vectors: 19 norms of 2048, 4 of 64, 8 choice biases of 64
    vectors = 19 * 2048 + 4 * 64 + 8 * 64
    assert c["dense_weight_bytes"] == \
        (matrices + 134_217_728) * 2 + vectors * 4 == 692_304_896
    assert c["expert_bytes"] == c["pick_flops"] == 18_874_368
    assert c["global_block_bytes"] == 262_144       # 4096 B a token
    assert c["pair_flops"] == 8192
    assert (c["global_layers"], c["window_layers"], c["window"]) == (2, 0, 0)
    # no window layer: the accepted floors' window terms are nothing
    assert moe_floors.prompt_attention_flops(
        1000, pair_flops=8192, global_layers=2, window_layers=0,
        window=0) == 8192 * 2 * 1000 * 1001 / 2
    assert moe_floors.decode_bytes(
        3, 5, 7, 0.0, dense_weight_bytes=100.0, expert_bytes=10.0,
        global_layers=2, window_layers=0, global_block_bytes=4.0,
        window_block_bytes=0.0) == 300 + 50 + 7 * 2 * 4


def test_the_programs_own_tree_and_cache_are_the_constants():
    """The parameter tree the program builds for the configuration (by
    shape, nothing allocated) and its cache shapes against the floors'
    constants: the same bytes, the same experts, the same block."""
    import jax

    from dynamo_tpu.models import lfm2

    hf = spec.load_cell(CELL)["config"]
    c = conv_floors.constants(hf, hf["engine"]["block_size"])
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    shapes = jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))
    dense, expert = roofline.weight_parts(shapes)
    # weight_parts leaves the embedding out (a lookup); here it is also
    # the head, which a decode step reads whole
    assert dense + 65536 * 2048 * 2 == c["dense_weight_bytes"]
    assert expert == c["expert_bytes"]
    assert roofline.matmul_flops_per_token(shapes, 4) == pytest.approx(
        c["dense_flops_per_token"] + 8 * 4 * c["pick_flops"], rel=1e-9)
    assert klass.attn_pair_flops(cfg) == c["pair_flops"]
    e = hf["engine"]
    kv = lfm2.kv_cache_shapes(cfg, e["num_blocks"], e["block_size"],
                              lanes=e["max_num_seqs"])
    assert kv[0] == (2, 8, 1593, 64, 128) and kv[2] == (7, 8, 2, 2048)
    assert roofline.kv_bytes_per_token(
        lfm2.kv_cache_shapes(cfg, 1, 128), 128, 2) * 128 \
        == 2 * c["global_block_bytes"] + 7 * 2 * 2048 * 2
    # every held expert of every expert layer: 4.83 GB x 2
    total = roofline.weight_bytes_per_step(shapes)
    assert total == dense + 8 * 64 * expert
    assert 10.0e9 < total + 65536 * 2048 * 2 < 10.4e9


def test_a_cut_of_another_depth_keeps_the_ratio():
    hf = dict(spec.load_cell(CELL)["config"])
    period = ["conv", "conv", "full_attention", "conv"]
    hf.update(num_hidden_layers=12, layer_types=period * 3,
              num_dense_layers=2)
    c = conv_floors.constants(hf, 128)
    assert (c["global_layers"], c["window_layers"]) == (3, 0)
    assert c["dense_flops_per_token"] == 2 * (
        9 * 16_783_360 + 3 * 10_485_760 + 2 * 72_351_744 + 10 * 131_072)
    with pytest.raises(ValueError):
        conv_floors.constants(dict(hf, num_hidden_layers=7), 128)


def test_the_cell_joins_the_long_prompt_lists_and_no_other_floors():
    bench = spec.load_benchmark()
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in e2e["output_tok_per_s"]
    assert all(CELL not in (cells or ()) for name, cells in e2e.items()
               if name not in ("output_tok_per_s", "setup_s"))
    joined = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined == set(FILES) | set(SHARES) | {
        "window_compiles.doc", "kv_preemptions.doc",
        "prefill_dev_tok_per_s", "device_idle_share.doc",
        "sched_host_ms_per_step.doc", "device_wait_share.doc",
        "step_hop_ms_per_step.doc", "window_pause_ms.doc"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "output_tok_per_s", m["name"]
