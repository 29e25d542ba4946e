"""The constants of the window + global, share-of-experts floors for a
`cohere2_moe` configuration (benchmark/lib/swa_floors.py): the metric
files' `args` recomputed from the configuration file's keys, the keys'
arithmetic by hand, and the two pair counters of the program
(`prefill_token_counts`) against the floors' own pair counts."""

import json
import os

import pytest

from benchmark.lib import moe_floors, spec, swa_floors

CELL = "command-a-plus.longctx-closed"
FILES = ("swa_moe_decode_hbm_share", "swa_moe_prefill_mxu_share")


def test_metric_files_hold_the_configurations_constants():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in FILES:
        with open(os.path.join(spec.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            args = json.load(f)["args"]
        assert by_name[name]["workloads"] == [CELL]
        for cell in by_name[name]["workloads"]:
            loaded = spec.load_cell(cell)
            want = swa_floors.constants(
                loaded["config"], loaded["config"]["engine"]["block_size"])
            for k, v in args.items():
                if k != "kind":
                    assert want[k] == v, (name, cell, k)


def test_constants_by_hand():
    """From the published widths (ISSUE 42), in parameters: attention
    4096 x 16384 + 2 x 4096 x 1024 + 16384 x 4096, shared experts
    3 x 4096 x 16384, router 4096 x 128; 4 layers; the tied head of
    32768; five float32 norms."""
    hf = spec.load_cell(CELL)["config"]
    c = swa_floors.constants(hf, 128)
    layer = 142_606_336 + 201_326_592 + 524_288
    assert layer == 344_457_216
    assert c["dense_flops_per_token"] == 2 * 4 * layer == 2_755_657_728
    assert c["dense_weight_bytes"] == 3_024_093_184 + 5 * 4096 * 4
    assert c["expert_bytes"] == c["pick_flops"] == 100_663_296
    assert c["global_block_bytes"] == c["window_block_bytes"] == 524_288
    assert c["pair_flops"] == 65_536
    assert (c["global_layers"], c["window_layers"], c["window"]) == \
        (1, 3, 4096)


def test_a_cut_of_another_depth_keeps_the_ratio():
    hf = dict(spec.load_cell(CELL)["config"])
    hf["num_hidden_layers"] = 8
    hf["layer_types"] = hf["layer_types"] * 2
    c = swa_floors.constants(hf, 128)
    assert (c["global_layers"], c["window_layers"]) == (2, 6)
    assert c["dense_flops_per_token"] == 2 * 2_755_657_728
    with pytest.raises(ValueError):
        swa_floors.constants(dict(hf, num_hidden_layers=7), 128)


@pytest.mark.parametrize("pos,chunk", [(0, 100), (0, 5000), (4000, 2048),
                                       (16384, 2048)])
def test_program_pair_counters_are_the_floors_pairs(pos, chunk):
    """attn_pairs_window.prefill / attn_pairs_global.prefill over the
    chunks of a prompt add up to moe_floors.window_pairs / causal_pairs
    of the prompt: the counters and the roofline count the same pairs."""
    from benchmark.reference import cohere2 as ref
    from dynamo_tpu.models import cohere2

    cfg = ref.program_config(spec.load_cell(CELL)["config"], "x")
    got = cohere2.prefill_token_counts(cfg, pos, chunk, 0)
    n = pos + chunk
    assert got["attn_pairs_global.prefill"] == \
        moe_floors.causal_pairs(n) - moe_floors.causal_pairs(pos)
    assert got["attn_pairs_window.prefill"] == \
        moe_floors.window_pairs(n, 4096) - moe_floors.window_pairs(pos, 4096)
    assert got["prefill_window_kernel_tokens"] == 0     # no bucket named
