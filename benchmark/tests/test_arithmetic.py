"""The yardstick's arithmetic on hand-made inputs.  Run with
`python -m pytest benchmark/tests -q` (not part of the repo's tier-1)."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import roofline, spec, stats, traffic
from benchmark.lib import gen_closed, gen_open
from benchmark.lib.correct import logit_gaps
from benchmark.lib.trace_reduce import (decode_names_from_probe, kind_of,
                                        merge, module_name, reduce_events)
from benchmark.readers import counters, device_trace, latency, throughput

HERE = os.path.dirname(os.path.abspath(__file__))


def rec(due, sent, times, max_tokens=None, prompt_len=100, error=None,
        end=None):
    times = [float(t) for t in times]
    return {"due_t": due, "sent_t": sent, "token_times": times,
            "tokens": [5] * len(times), "prompt_len": prompt_len,
            "max_tokens": len(times) if max_tokens is None else max_tokens,
            "error": error, "end_t": (times[-1] if times else None)
            if end is None else end}


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (95, 3.85),
                                    (100, 4.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(
        np.percentile([1, 2, 3, 4], q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_mean_spacing_and_none_for_one_token():
    assert stats.tpot_ms(1.0, 1.9, 10) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_latencies_run_from_due_not_sent():
    lat = stats.request_latencies(rec(10.0, 10.2, [10.5, 10.6, 10.7]))
    assert lat["ttft_ms"] == pytest.approx(500.0)
    assert lat["late_ms"] == pytest.approx(200.0)
    assert lat["tpot_ms"] == pytest.approx(100.0)


def test_window_split():
    rs = [rec(-1.0, -1.0, [0.5]),                    # pre-roll: nowhere
          rec(1.0, 1.0, [2.0, 3.0]),                 # ok
          rec(2.0, 2.0, [3.0], max_tokens=4),        # short: failed
          rec(3.0, 3.0, [4.0], error="boom"),        # failed
          rec(9.0, 9.0, [9.5, 10.5]),                # ends after close
          rec(9.5, 9.5, [], end=None)]               # never answered
    rs[-1]["end_t"] = None
    c = stats.counted(rs, 0.0, 10.0)
    assert [len(c[k]) for k in ("ok", "failed", "inflight")] == [1, 2, 2]
    assert stats.tokens_in_window(rs, 0.0, 10.0) == 6


def test_mean_live_context():
    # one request decoding over the whole stretch: context 101 -> 111
    r = rec(0.0, 0.0, np.linspace(1.0, 2.0, 11), prompt_len=100)
    assert stats.mean_live_context([r], 1.0, 2.0) == pytest.approx(106.0)
    # half the stretch: half the time-average
    assert stats.mean_live_context([r], 1.0, 3.0) == pytest.approx(53.0)
    assert stats.mean_live_context([r], 5.0, 6.0) == 0.0


def test_merge_and_module_name():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert module_name("jit__decode_multi_impl(123)") == \
        "jit__decode_multi_impl"
    probe = {"devices": {"d": {"ops": [], "modules": [
        ("jit_squeeze(1)", 0.0, 10.0), ("jit__unknown(5)", 20.0, 9e6),
        ("jit__unknown(7)", 1e7, 4e6), ("jit__unknown(8)", 2e7, 8e6),
        ("jit__unknown(7)", 3e7, 4e6)]}}, "host": []}
    names = decode_names_from_probe(probe)
    assert names == ["jit__unknown(7)", "jit__unknown(8)"]
    assert kind_of("jit__unknown(8)", names) == "decode"
    assert kind_of("jit__unknown(5)", names) == "prefill"
    assert kind_of("jit_prefill_packed(3)", names) == "prefill"
    assert kind_of("jit_squeeze(1)", names) == "other"


def hand_trace():
    ms = 1e6
    return {"devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 0 * ms, 10 * ms), ("fusion.2", 5 * ms, 10 * ms),
                ("copy.3", 40 * ms, 20 * ms), ("fusion.1", 80 * ms, 20 * ms)],
        "modules": [("jit__unknown(7)", 0 * ms, 15 * ms),
                    ("jit__unknown(9)", 40 * ms, 20 * ms),
                    ("jit__unknown(8)", 80 * ms, 20 * ms)]}},
        "host": [("sched", 14 * ms, 30 * ms), ("tiny", 20 * ms, 1 * ms),
                 ("fetch", 59 * ms, 22 * ms), ("tail", 99 * ms, 11 * ms)]}


DECODE = ["jit__unknown(7)", "jit__unknown(8)"]


def test_reduce_hand_trace():
    r = reduce_events(hand_trace(), DECODE)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["module_s"]["jit__unknown"] == pytest.approx(0.055)
    assert r["kind_s"] == {"decode": pytest.approx(0.035),
                           "prefill": pytest.approx(0.020), "other": 0.0}
    assert r["kind_n"]["decode"] == 2
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    # the gap after the last op counts, up to the last host event
    assert dict(map(tuple, r["idle_gaps"])) == {
        "sched": pytest.approx(0.025), "fetch": pytest.approx(0.020),
        "tail": pytest.approx(0.010)}
    assert reduce_events({"devices": {}, "host": []}) == {}


def test_trace_readers_on_hand_trace():
    ctx = {"trace": reduce_events(hand_trace(), DECODE),
           "trace_window": (100.0, 100.1),
           "mono_offset": 0.0, "chips": 1,
           "fpm": [{"kind": "decode", "k": 8, "t": 100.01},
                   {"kind": "decode", "k": 4, "t": 100.05},
                   {"kind": "decode", "k": 8, "t": 99.0},
                   {"kind": "prefill", "tokens": 9, "t": 100.02}],
           "trace_counters": [{"prefill_tokens": 1000},
                              {"prefill_tokens": 3000}],
           "records": [], "peaks": {"hbm_bytes_per_s": 1e9,
                                    "bf16_flops": 1e12},
           "roofline": {"weight_bytes": 1e6, "dense_weight_bytes": 1e6,
                        "expert_bytes": 0.0, "kv_bytes_per_token": 10.0,
                        "matmul_flops_per_token": 1e6,
                        "attn_pair_flops": 8.0, "n_layers": 2}}
    assert device_trace.idle_share(ctx) == pytest.approx(50.0)
    assert device_trace.module_ms_per_decode_step(
        ctx, "decode") == pytest.approx(35.0 / 12)
    # 12 steps x 1e6 bytes / 0.035 s / 1e9 B/s
    assert device_trace.decode_hbm_share(ctx, "decode") == pytest.approx(
        100 * 12e6 / 0.035 / 1e9)
    assert device_trace.prefill_tokens_per_device_s(
        ctx, "prefill") == pytest.approx(2000 / 0.020)
    assert device_trace.prefill_mxu_share(ctx, "prefill") == pytest.approx(
        100 * 2000 * 1e6 / 0.020 / 1e12)
    assert device_trace.idle_share({"trace": None}) is None
    assert device_trace.module_ms_per_decode_step(
        dict(ctx, fpm=[]), "decode") is None


def test_recorded_trace():
    """A cut of the first traced chip run (PR 23): the reduction finds
    the device plane's lines and the engine's module names."""
    path = os.path.join(HERE, "recorded_trace.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with open(path) as f:
        d = json.load(f)
    r = reduce_events(d["events"], d["decode_names"])
    assert r["busy_s"] == pytest.approx(d["expect"]["busy_s"])
    for group in ("module_s", "kind_s"):
        for k, v in d["expect"][group].items():
            assert r[group][k] == pytest.approx(v)
    assert r["kind_s"]["decode"] > 0


def test_roofline_counts():
    bf = np.dtype("float16")      # 2 bytes, stands in for bf16
    params = {"embedding": np.zeros((100, 8), bf),
              "lm_head": np.zeros((8, 100), bf),
              "final_norm": {"norm": np.zeros((8,), np.float32)},
              "layers": [{"wq": np.zeros((8, 8), bf),
                          "attn_norm": {"norm": np.zeros((8,), np.float32)},
                          "moe_gate": np.zeros((8, 4), bf),
                          "moe_w_up": np.zeros((4, 8, 16), bf)}]}
    assert roofline.weight_bytes_per_step(params) == \
        (800 + 64 + 32 + 512) * 2 + 8 * 4 * 2
    # wq 64 + gate 32 + 2 of 4 experts x 128
    assert roofline.matmul_flops_per_token(params, 2) == 2 * (64 + 32 + 256)
    assert roofline.kv_bytes_per_token([(2, 1, 1, 8, 16)] * 2, 16, 2) == 64
    # the two parts: all but embedding and the expert stack; one expert
    dense, expert = roofline.weight_parts(params)
    assert dense == (800 + 64 + 32) * 2 + 8 * 4 * 2
    assert expert == 8 * 16 * 2
    assert dense + 4 * expert == roofline.weight_bytes_per_step(params)
    assert roofline.decode_bytes(
        1, 0, 10.0, dense_weight_bytes=1000.0, expert_bytes=0.0,
        kv_bytes_per_token=64.0) == 1640.0
    assert roofline.causal_attention_flops(3, 8.0, 2) == 8 * 2 * 6


def test_counter_and_latency_readers():
    ctx = {"counters_open": {"steps": 10, "cont_bursts": 2, "preemptions": 1},
           "counters_close": {"steps": 30, "cont_bursts": 7, "preemptions": 1},
           "window": (100.0, 110.0), "mono_offset": 5.0, "chips": 1,
           "compile_events": [{"t": 104.0}, {"t": 106.0}, {"t": 116.0}],
           "records": [rec(101.0, 101.0, [102.0, 103.0])], "setup_s": 50.0}
    ctx["counted"] = stats.counted(ctx["records"], *ctx["window"])
    assert counters.share_of_deltas(ctx, "cont_bursts", "steps") == 25.0
    assert counters.delta(ctx, "preemptions") == 0.0
    assert counters.compiles_in_window(ctx) == 1.0
    assert latency.percentile_of(ctx, "ttft_ms", 50) == pytest.approx(1000.0)
    assert throughput.output_tokens_per_s(ctx) == pytest.approx(0.2)
    assert throughput.setup_seconds(ctx) == 50.0
    assert latency.percentile_of(dict(ctx, counted={"ok": []}),
                                 "ttft_ms", 50) is None


def test_logit_gaps():
    logits = np.zeros((5, 4))
    logits[2] = [0.0, 4.0, 3.0, -4.0]       # predicts emitted[0]
    logits[3] = [1.0, 0.0, 0.0, 0.0]
    assert logit_gaps(logits, 3, [1, 0]) == [0.0, 0.0]
    assert logit_gaps(logits, 3, [2, 1]) == [pytest.approx(1 / 8), 1.0]


MIX_OPEN = {"loop": "open", "rate_rps": 5.0, "preroll_s": 2.0,
            "sizes_seed": 1,
            "prompt_tokens": {"dist": "lognormal", "median": 320,
                              "sigma": 0.9, "min": 32, "max": 2048},
            "output_tokens": {"dist": "uniform", "min": 8, "max": 16}}


def test_every_seed_offers_the_same_work_in_the_same_order():
    a = gen_open.build(MIX_OPEN, 20.0, 1)
    b = gen_open.build(MIX_OPEN, 20.0, 2 ** 31 + 11)
    win = lambda rows: [r for r in rows if r.due >= 0]  # noqa: E731
    assert len(win(a)) == len(win(b)) == 100
    assert len(a) - len(win(a)) == 10
    sizes = lambda rows: sorted((r.prompt_len, r.max_tokens)  # noqa: E731
                                for r in rows)
    assert sizes(win(a)) == sizes(win(b))
    assert [(r.prompt_len, r.max_tokens, r.due) for r in a] == \
        [(r.prompt_len, r.max_tokens, r.due) for r in b]
    assert all(0 <= r.due < 20.0 for r in win(a))
    assert all(32 <= r.prompt_len <= 2048 and 8 <= r.max_tokens <= 16
               for r in a)
    assert traffic.prompt_tokens(a[0], 1000) == traffic.prompt_tokens(
        gen_open.build(MIX_OPEN, 20.0, 1)[0], 1000)
    assert traffic.prompt_tokens(a[0], 1000) != traffic.prompt_tokens(
        b[0], 1000)
    assert all(3 <= t < 1000 for t in traffic.prompt_tokens(a[0], 1000))


def test_closed_pool_is_the_same_multiset():
    mix = dict(MIX_OPEN, loop="closed", clients=8, pool_per_s=2.0)
    a, b = gen_closed.build(mix, 10.0, 3), gen_closed.build(mix, 10.0, 4)
    assert len(a) == len(b) == 24
    assert [r.prompt_len for r in a] == [r.prompt_len for r in b]
    assert traffic.prompt_tokens(a[0], 99) != traffic.prompt_tokens(b[0], 99)


def test_benchmark_json_points_at_files():
    bench = spec.load_benchmark()
    for wl in bench["workloads"]:
        cell = spec.load_cell(wl["name"])
        assert spec.loop_module(cell["mix"]).build
        assert spec.model_class(cell["config"]).reference_logits
        if cell["mix"]["loop"] == "open":
            assert cell["mix"]["rate_rps"] > 0
        for group, gdir in (("end_to_end", "e2e_metrics"),
                            ("per_layer", "layer_metrics")):
            ms = spec.cell_metrics(wl["name"], group)
            assert ms
            for m in ms:
                assert callable(spec.metric_reader(gdir, m["name"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        cells = m.get("workloads") or [w["name"] for w in bench["workloads"]]
        moved = e2e[m["moves"]]
        assert all(c in (moved.get("workloads") or cells) for c in cells)


SIX = [100.0, 101.0, 99.0, 102.0, 98.0, 120.0]


@pytest.mark.parametrize("fn,values,want", [
    # statistics.quantiles (exclusive): q1 = 98.75, q3 = 106.5 of six
    (stats.spread_iqr, SIX, (106.5 - 98.75) / 100.5),
    (stats.spread_iqr, SIX[:5], (101.5 - 98.5) / 100.0),
    (stats.spread_range, SIX, 22.0 / 100.5),
    (stats.spread_range, SIX[:5], 4.0 / 100.0),
    (stats.spread_range, [5.0, 5.0, 5.0], 0.0),
])
def test_spreads_are_shares_of_the_median(fn, values, want):
    assert fn(values) == pytest.approx(want)


@pytest.mark.parametrize("values,want", [
    (SIX, SIX[:5]),                           # the far run goes
    ([1.0, 2.0, 3.0], [2.0, 3.0]),            # a tie: the first goes
    ([10.0, 50.0, 11.0, 12.0], [10.0, 11.0, 12.0]),
])
def test_without_farthest_drops_one_run(values, want):
    assert stats.without_farthest(values) == want
    assert len(values) == len(want) + 1       # the input is not changed


def test_spread_iqr_is_wider_than_numpys_quartiles():
    q1, q3 = np.percentile(SIX, [25, 75])
    assert stats.spread_iqr(SIX) > (q3 - q1) / np.median(SIX)


def test_records_round_trip_and_counter_deltas(tmp_path):
    from benchmark.lib import records

    t0 = 50.0
    recs = [dict(rec(t0 + 1.0, t0 + 1.001, [t0 + 1.5, t0 + 1.6]), index=0),
            dict(rec(t0 + 2.0, t0 + 2.0, [t0 + 9.5, t0 + 10.5]), index=1)]
    ctx = {"window": (t0, t0 + 10.0), "mono_offset": 1000.0, "drained": 1,
           "records": recs,
           "counters_open": {"steps": 10, "cont_bursts": 1},
           "counters_close": {"steps": 30, "cont_bursts": 6,
                              "preemptions": 2},
           "compile_events": [{"t": 1000.0 + t0 + 3.0, "family": "prefill"},
                              {"t": 1000.0 + t0 - 3.0, "family": "decode"}],
           "fpm_close": [{"t": 1000.0 + t0 + 1.2, "kind": "decode", "k": 8,
                          "lanes": 3, "gap_s": 0.1, "xla_flops": 1e9}],
           "host": {"process_cpu_s": 1.0}}
    deltas = records.counter_deltas(ctx)
    assert (deltas["steps"], deltas["cont_bursts"], deltas["preemptions"],
            deltas["prefill_tokens"], deltas["window_compiles"]) == (
                20, 5, 2, 0, 1.0)
    path = str(tmp_path / "set" / "cell.s7.json.gz")
    assert records.path("", "cell", 7) == ""
    assert path == records.path(str(tmp_path / "set"), "cell", 7)
    records.dump(ctx, path, {"seed": 7, "counters": deltas})
    back = records.load(path)
    assert back["seed"] == 7 and back["window"] == (0.0, 10.0)
    assert [len(back["counted"][k]) for k in ("ok", "failed", "inflight")
            ] == [1, 0, 1]
    assert latency.percentile_of(back, "ttft_ms", 50) == pytest.approx(500.0)
    assert back["fpm"] == [{"t": pytest.approx(1.2), "kind": "decode",
                            "k": 8, "lanes": 3, "gap_s": 0.1}]
    assert back["compile_events"][0]["t"] == pytest.approx(3.0)


def test_gc_pauses_counts_collections_while_installed():
    import gc

    from benchmark.lib.records import GcPauses

    with GcPauses() as pauses:
        gc.collect()
        assert pauses in gc.callbacks
    assert pauses not in gc.callbacks
    s = pauses.summary(0.0)
    assert s["count"][2] == 1 and s["ms"][2] > 0.0
    assert s["longest_ms"] <= sum(s["ms"]) + 1e-3


def test_stall_watch_dumps_stacks_once_a_stall(capfd):
    import time

    from benchmark.lib.records import StallWatch

    steps, waiting = [0], [True]
    with StallWatch(lambda: steps[0], lambda: waiting[0],
                    after_s=0.3) as watch:
        time.sleep(1.0)               # no step, requests waiting: a stall
        steps[0] += 1                 # the step ends it
        waiting[0] = False
        time.sleep(0.9)               # no step, nothing waiting: no stall
    assert len(watch.stalls) == 1 and 0.3 < watch.stalls[0][1] < 1.6
    err = capfd.readouterr().err
    assert err.count("no scheduler step for") == 1
    assert "stall-watch" in err or "Thread" in err
