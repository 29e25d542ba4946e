"""benchmark/readers/request_life.py on hand-made observations, the seven
metric files that read a request's life (PR 38) on a `ctx` of an older
program, and `--rehearse --trace 1` through a chat cell and a closed-loop
cell with the new entries."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import spec
from benchmark.readers import request_life

OPEN = {"req_stage_s.wake": 0.2, "req_stage_s.lane": 0.0,
        "req_stage_s.turn": 0.1, "req_stage_n": 10, "req_ahead_steps": 100,
        "req_stage_s.join": 1.0, "req_join_n": 10,
        "req_stage_s.decode": 5.0, "req_decode_tokens": 500,
        "host_s.emit": 0.5, "host_n.step": 1000}
CLOSE = {"req_stage_s.wake": 0.5, "req_stage_s.lane": 0.02,
         "req_stage_s.turn": 0.6, "req_stage_n": 20, "req_ahead_steps": 260,
         "req_stage_s.join": 2.8, "req_join_n": 19,
         "req_stage_s.decode": 17.0, "req_decode_tokens": 1500,
         "host_s.emit": 0.8, "host_n.step": 1300}
WANT = {"step_wake_mean_ms": 30.0, "lane_wait_mean_ms": 2.0,
        "turn_wait_mean_ms": 50.0, "prefill_ahead_steps_mean": 16.0,
        "join_delay_mean_ms": 200.0, "decode_gap_mean_ms": 12.0,
        "emit_host_ms_per_step": 1.0}
# what PR 36's program counted: the stages to the first token and the
# host's phases, nothing after the first token and no split of the queue
PARENT = {"req_stage_s.queue": 1.0, "req_stage_s.prefill": 2.0,
          "req_stage_s.emit": 0.5, "req_stage_n": 10, "steps": 10}
CHAT = ["mistral-7b.chat", "moonlight-16b.chat"]
FOUR = CHAT + ["mimo-v2-flash.reason-closed", "ling-3.0-flash.longgen-closed"]
TWIN = ".ttft_p50"


def ctx(open_=OPEN, close=CLOSE):
    return {"counters_open": open_, "counters_close": close, "trace": None}


def test_mean_of_deltas_is_one_difference_over_another():
    assert request_life.mean_of_deltas(
        ctx(), "req_ahead_steps", "req_stage_n") == pytest.approx(16.0)
    assert request_life.mean_of_deltas(
        ctx(), "req_stage_s.join", "req_join_n", scale=1e3) \
        == pytest.approx(200.0)


@pytest.mark.parametrize("total,count", [
    ("req_ahead_steps", "req_stage_n"), ("req_stage_s.join", "req_join_n"),
    ("req_stage_s.decode", "req_decode_tokens"),
    ("host_s.emit", "host_n.step")])
def test_mean_of_deltas_is_none_without_a_count_or_a_counter(total, count):
    assert request_life.mean_of_deltas(ctx(close=OPEN), total, count) is None
    for gone in (total, count):      # the program has no such counter
        close = {k: v for k, v in CLOSE.items() if k != gone}
        assert request_life.mean_of_deltas(
            ctx(close=close), total, count) is None
    # the count is there (PR 36's req_stage_n moved), the total is not
    assert request_life.mean_of_deltas(
        ctx(open_={}, close=PARENT), "req_ahead_steps", "req_stage_n") is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_metric_file_names_a_reader_that_runs(name):
    read = spec.metric_reader("layer_metrics", name)
    assert read(ctx()) == pytest.approx(WANT[name])
    # an older program: nothing to read, and no exception.  PR 36's has
    # `req_stage_n` and no split of the queue: None there, not 0 ms
    assert read(ctx(open_={"steps": 10}, close={"steps": 20})) is None
    assert read(ctx(open_={}, close=PARENT)) is None
    assert read(ctx(close=OPEN)) is None


def test_the_declaration_lists_the_seven_with_their_cells():
    by_name = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    to_first_token = {"step_wake_mean_ms": "ttft_p50_ms",
                      "lane_wait_mean_ms": "ttft_p95_ms",
                      "turn_wait_mean_ms": "ttft_p50_ms",
                      "prefill_ahead_steps_mean": "ttft_p50_ms"}
    for name in WANT:
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"]) == (
            "scheduler", "program_counter", "lower")
        assert m["moves"] == to_first_token.get(name, "tpot_p95_ms")
        # a list gains the cells that later PRs add; a cell that does not
        # report the moved metric end to end reads the quantity under the
        # entry's `.ttft_p50` twin (PR 43: Mistral's ttft_p95_ms)
        twin = by_name.get(name + TWIN, {"workloads": []})
        assert set(CHAT if name in to_first_token else FOUR) <= set(
            m["workloads"] + twin["workloads"])
        assert not set(m["workloads"]) & set(twin["workloads"])
    # the three waits are the queue's, in the queue's cells
    assert set(CHAT) <= set(by_name["queue_wait_mean_ms"]["workloads"])


def test_a_split_entry_reads_what_its_original_reads():
    """Where a cell's tail is no end-to-end metric (PR 43, Mistral chat:
    one burst more or less ahead of three requests decides it), the
    per-layer entries that moved the tail are split: the twin has the
    original's reader and arguments, moves the median, and no cell is in
    both; the tail itself stays readable per layer."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    twins = [n for n in by_name if n.endswith(TWIN)]
    assert len(twins) == 4
    for n in twins:
        orig, twin = by_name[n[:-len(TWIN)]], by_name[n]
        assert (orig["moves"], twin["moves"]) == ("ttft_p95_ms",
                                                  "ttft_p50_ms")
        assert {k: orig[k] for k in ("unit", "better", "source", "layer")} \
            == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        assert spec._json(os.path.join(
            spec.BENCH_DIR, "layer_metrics", n + ".json")) == spec._json(
                os.path.join(spec.BENCH_DIR, "layer_metrics",
                             n[:-len(TWIN)] + ".json"))
        for cell in twin["workloads"]:
            assert cell not in e2e["ttft_p95_ms"]["workloads"]
            assert cell in e2e["ttft_p50_ms"]["workloads"]
    tail = by_name["ttft_tail_p95_ms"]
    assert tail["workloads"] == ["mistral-7b.chat"]
    assert spec._json(os.path.join(
        spec.BENCH_DIR, "layer_metrics", "ttft_tail_p95_ms.json")) \
        == spec._json(os.path.join(spec.BENCH_DIR, "e2e_metrics",
                                   "ttft_p95_ms.json"))


@pytest.mark.parametrize("cell,names", [
    ("mistral-7b.chat", sorted(WANT)),
    ("mimo-v2-flash.reason-closed",
     ["decode_gap_mean_ms", "emit_host_ms_per_step", "join_delay_mean_ms"])])
def test_rehearsal_reads_the_new_entries(cell, names):
    """`--rehearse --trace 1` (CPU, tiny widths, no measurement) walks the
    cell's per-layer readers: every new entry the cell lists reads a
    number, and on the chat cell the three waits add up to the queue."""
    r = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         cell, "--rehearse", "--seconds", "6", "--trace", "1"],
        cwd=spec.REPO_ROOT, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 3, (r.stdout[-2000:], r.stderr[-2000:])
    tag = "rehearsal result (CPU, tiny widths, not a measurement): "
    line, = [ln for ln in r.stdout.splitlines() if tag in ln]
    metrics = json.loads(line.split(tag, 1)[1])["metrics"]
    new = {n: m["value"] for n in WANT
           for m in [metrics.get(n) or metrics.get(n + TWIN)] if m}
    assert sorted(new) == names
    assert all(v >= 0.0 for v in new.values())
    if "step_wake_mean_ms" in new:
        assert (new["step_wake_mean_ms"] + new["lane_wait_mean_ms"]
                + new["turn_wait_mean_ms"]) == pytest.approx(
                    metrics["queue_wait_mean_ms"]["value"], rel=1e-6)
