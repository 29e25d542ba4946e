"""The floors of a configuration of block-selecting sparse attention
beside lightning linear-attention layers (benchmark/lib/sala_floors.py)
and the readers over them (benchmark/readers/sala_roofline.py) on
hand-made inputs; and the metric files' constants recomputed from the
configuration file's keys."""

import json
import os

import pytest

from benchmark.lib import sala_floors, spec
from benchmark.readers import counters, sala_roofline

DEC = dict(dense_weight_bytes=1000.0, lane_step_bytes=50.0,
           sparse_layers=3, kv_token_bytes=16.0, ck_bytes=8.0)
PRE = dict(dense_flops_per_token=1e3, lightning_layers=9,
           rule_flops_per_token=10.0, sparse_layers=3,
           attn_pair_flops=32.0, score_pair_flops=4.0)
CELL = "minicpm-sala.longdoc-closed"
NEW = ("sala_decode_hbm_share", "sala_prefill_mxu_share",
       "sala_selected_share", "sala_read_share", "sala_prefill_pair_share")


def test_decode_bytes_by_hand():
    # 4 steps x 1000 + 7 lane-steps x 50 + 3 layers x (60 used x 16 B
    # + 500 scored x 8 B)
    assert sala_floors.decode_bytes(4, 7, 60, 500, **DEC) == \
        4000 + 350 + 3 * (960 + 4000)


def test_prefill_flops_by_hand():
    # 100 tokens x (1e3 + 9 x 10) + 3 layers x (900 kept x 32
    # + 5050 scored x 4)
    assert sala_floors.prefill_flops(100, 900, 5050, **PRE) == \
        100 * 1090 + 3 * (28800 + 20200)


def test_chunk_rule_flops_by_hand():
    # a head: (128 + 1) / 2 x 2 x (128 + 128) in the chunk, 2 x 2 x 128
    # x 128 with the state
    assert sala_floors.chunk_rule_flops(32, 128, 128, 128) == \
        32 * (129 * 256 + 65536)


def ctx(counters_close, **over):
    base = {
        "trace": {"kind_s": {"decode": 0.02, "prefill": 0.01}},
        "trace_window": (100.0, 100.1), "mono_offset": 0.0, "chips": 1,
        "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                {"kind": "decode", "k": 4, "t": 100.05},
                {"kind": "decode", "k": 8, "t": 99.0}],
        "trace_counters": [
            {"prefill_tokens": 1000, "recurrent_lane_steps.decode": 10,
             "sala_used_tokens.decode": 40, "sala_scored_keys.decode": 100,
             "sala_pairs_attended.prefill": 500,
             "sala_pairs_scored.prefill": 1000},
            counters_close],
        "records": [], "peaks": {"hbm_bytes_per_s": 1e6,
                                 "bf16_flops": 1e9},
    }
    base.update(over)
    return base


def test_decode_hbm_share_reader():
    c = ctx({"recurrent_lane_steps.decode": 17,
             "sala_used_tokens.decode": 100,
             "sala_scored_keys.decode": 600})
    # 12 steps in the stretch: 12000 + 7 x 50 + 3 x (60 x 16 + 500 x 8)
    assert sala_roofline.decode_hbm_share(c, "decode", **DEC) == \
        pytest.approx(100 * (12000 + 350 + 14880) / 0.02 / 1e6)
    # a program without the counters gives nothing, and does not raise
    old = ctx({"prefill_tokens": 3000})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert sala_roofline.decode_hbm_share(old, "decode", **DEC) is None
    assert sala_roofline.decode_hbm_share(
        dict(c, trace=None), "decode", **DEC) is None
    assert sala_roofline.decode_hbm_share(
        dict(c, fpm=[]), "decode", **DEC) is None


def test_prefill_mxu_share_reader():
    c = ctx({"prefill_tokens": 1100, "sala_pairs_attended.prefill": 1400,
             "sala_pairs_scored.prefill": 6050})
    assert sala_roofline.prefill_mxu_share(c, "prefill", **PRE) == \
        pytest.approx(100 * (100 * 1090 + 3 * (28800 + 20200))
                      / 0.01 / 1e9)
    old = ctx({"prefill_tokens": 1100})
    old["trace_counters"][0] = {"prefill_tokens": 1000}
    assert sala_roofline.prefill_mxu_share(old, "prefill", **PRE) is None
    idle = ctx({"prefill_tokens": 1000, "sala_pairs_attended.prefill": 500,
                "sala_pairs_scored.prefill": 1000})
    assert sala_roofline.prefill_mxu_share(idle, "prefill", **PRE) is None


@pytest.mark.parametrize("name,want", [("sala_selected_share", 20.0),
                                       ("sala_read_share", 50.0),
                                       ("sala_prefill_pair_share", 25.0)])
def test_counter_shares_read_nothing_from_a_program_without_them(name, want):
    window = {
        "counters_open": {
            "sala_ctx_blocks.decode": 100, "sala_kept_blocks.decode": 40,
            "sala_used_tokens.decode": 10, "sala_read_tokens.decode": 100,
            "sala_pairs_attended.prefill": 0,
            "sala_pairs_computed.prefill": 0},
        "counters_close": {
            "sala_ctx_blocks.decode": 1100, "sala_kept_blocks.decode": 240,
            "sala_used_tokens.decode": 510, "sala_read_tokens.decode": 1100,
            "sala_pairs_attended.prefill": 250,
            "sala_pairs_computed.prefill": 1000}}
    read = spec.metric_reader("layer_metrics", name)
    assert read(window) == pytest.approx(want)
    assert read({"counters_open": {"steps": 1},
                 "counters_close": {"steps": 9}}) is None
    assert counters.share_of_deltas(window, "sala_kept_blocks.decode",
                                    "sala_kept_blocks.decode") == 100.0


def test_metric_files_hold_the_configurations_constants():
    """The args of the two roofline metric files are what
    sala_floors.constants gives for the configuration the metrics' cell
    runs, and those are the arithmetic of its keys (ISSUE 47, point 6)."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    hf = spec.load_cell(CELL)["config"]
    want = sala_floors.constants(hf)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "output_tok_per_s"
    for name in NEW[:2]:
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert set(args) - {"kind"} <= set(want)
        for k, v in args.items():
            if k != "kind":
                assert want[k] == v, (name, k)
    # by hand, from the published widths, in M parameters a layer
    mlp = 3 * 67.109
    lightning = 5 * 16.777 + mlp
    sparse = 16.777 + 2 * 1.0486 + 2 * 16.777 + mlp
    assert (want["lightning_layers"], want["sparse_layers"]) == (9, 3)
    assert want["dense_flops_per_token"] / 2e6 == pytest.approx(
        9 * lightning + 3 * sparse, 1e-4)
    assert want["dense_flops_per_token"] == pytest.approx(6.656e9, 1e-3)
    assert want["dense_weight_bytes"] / 2e6 == pytest.approx(
        9 * lightning + 3 * sparse + 300.84, 1e-4)
    assert want["dense_weight_bytes"] == pytest.approx(7.258e9, 1e-3)
    assert want["lane_step_bytes"] == 9 * 2 * 2097152
    assert (want["kv_token_bytes"], want["ck_bytes"]) == (1024, 512)
    assert (want["attn_pair_flops"], want["score_pair_flops"]) == \
        (16384, 8192)
    assert want["rule_flops_per_token"] == 32 * (129 * 256 + 65536)
    # the reference's own count of a pair agrees, and the program's tree
    # is the one the constants assume
    klass = spec.model_class(hf)
    cfg = klass.program_config(
        {k: v for k, v in hf.items() if k not in ("engine", "rehearse")},
        "t")
    assert klass.attn_pair_flops(cfg) == want["attn_pair_flops"]
    assert klass.score_pair_flops(cfg) == want["score_pair_flops"]
    assert cfg.layer_kinds == (1, 0, 0, 0, 0, 0, 0, 1, 1, 0, 0, 0)
    assert cfg.residual_depth == 32 and cfg.sizes == (32, 16, 64, 1, 2048,
                                                      64, 8192)


def test_the_programs_tree_is_what_the_floors_count():
    """`lib/roofline.describe` walks the family's tree as it stands (no
    expert leaves; `log_decay` is no matrix; `kv_cache_shapes` without
    `lanes=`), at the rehearsal widths, and its weight bytes and matmul
    FLOPs are `constants` of the same keys."""
    import jax

    from benchmark.lib import roofline
    from benchmark.lib.model import source_keys
    from dynamo_tpu.models import get_family

    config = spec.load_cell(CELL)["config"]
    hf = source_keys(config, rehearse=True)
    klass = spec.model_class(config)
    cfg = klass.program_config(hf, "t")
    family = get_family(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    d = roofline.describe(params, cfg, family, 16, klass.attn_pair_flops(cfg))
    want = sala_floors.constants(hf)
    assert d["expert_bytes"] == 0.0
    assert d["matmul_flops_per_token"] == want["dense_flops_per_token"]
    # the decay table and the norms' vectors ride along: small
    assert 0 <= d["dense_weight_bytes"] - want["dense_weight_bytes"] < 4096


def test_the_cell_keeps_out_of_the_floors_that_miscount_it():
    bench = spec.load_benchmark()
    out = ("decode_hbm_share", "prefill_mxu_share", "decode_attn_live_share")
    for m in bench["per_layer"]:
        if m["name"] in out or m["name"].startswith(
                ("moe_", "sparse_", "recurrent_", "ssm_", "swa_")):
            assert CELL not in m["workloads"], m["name"]
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in e2e["output_tok_per_s"]
    assert CELL not in e2e["tpot_p95_ms"]
    joined = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert sorted(joined) == sorted(NEW + (
        "window_compiles.doc", "kv_preemptions.doc", "prefill_dev_tok_per_s",
        "device_idle_share.doc", "sched_host_ms_per_step.doc",
        "device_wait_share.doc"))


def test_traffic_and_cell_are_the_issues():
    cell = spec.load_cell(CELL)
    mix, eng = cell["mix"], cell["config"]["engine"]
    assert (mix["loop"], mix["clients"], mix["preroll_s"]) == \
        ("closed", 8, 30.0)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 16384,
                                    "max": 49152}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 768}
    assert eng["max_blocks_per_seq"] == 391 == -(-(49152 + 768) // 128) + 1
    assert eng["num_blocks"] == 8 * 391 + 1
    assert (eng["max_num_seqs"], eng["max_prefill_seqs"]) == (8, 1)
    assert eng["prefill_buckets"] == [512, 1024, 2048]
    assert cell["workload"]["chips"] == 1
    assert len(cell["workload"]["why"]) <= 200
    # the catalog row's keys at their published values but the two cut
    hf = cell["config"]
    assert set(hf["reduced"]) == {"num_hidden_layers", "mixer_types"}
    assert (hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"],
            hf["num_attention_heads"], hf["num_key_value_heads"],
            hf["head_dim"]) == (4096, 16384, 73448, 32, 2, 128)
    assert (hf["scale_emb"], hf["scale_depth"], hf["dim_model_base"],
            hf["mup_denominator"]) == (12, 1.4, 256, 32)
