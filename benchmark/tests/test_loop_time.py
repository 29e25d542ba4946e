"""benchmark/readers/loop_time.py on hand-made observations: the three
readers over the stretch from the window's opening to the traced
stretch's end, the five metric files that name them (PR 53) on the `ctx`
of an older program and of an untraced run, and `--rehearse --trace 1`
through a chat cell and a closed-loop cell with the new entries."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import spec
from benchmark.readers import loop_time

OPEN = {"host_s.idle": 1.0, "host_n.idle": 10, "host_s.hop": 0.2,
        "host_n.hop": 900, "host_n.step": 1000, "host_s.pause": 0.0,
        "host_n.pause": 0}
# the end of the traced stretch, 25 s after the window opened
AT_TRACE_END = {"host_s.idle": 4.5, "host_n.idle": 60, "host_s.hop": 0.7,
                "host_n.hop": 2800, "host_n.step": 3000,
                "host_s.pause": 1.75, "host_n.pause": 1}
# the window's close: the profile was written in between (a pause of its
# own making, which no reader here may count)
CLOSE = {"host_s.idle": 6.0, "host_n.idle": 90, "host_s.hop": 9.9,
         "host_n.hop": 4800, "host_n.step": 5000, "host_s.pause": 9.75,
         "host_n.pause": 3}
WANT = {"engine_empty_share": 14.0, "step_hop_ms_per_step": 0.25,
        "window_pause_ms": 1750.0}
DOC = ".doc"
CHAT = ["mistral-7b.chat", "moonlight-16b.chat", "nemotron-twotower.chat"]
# what PR 52's program counted: the steps and their phases, nothing of
# the loop outside a step
PARENT = {"host_s.step": 20.0, "host_n.step": 3000,
          "host_s.device_wait": 17.0, "steps": 3000}


def ctx(at_end=AT_TRACE_END, traced=True):
    c = {"counters_open": OPEN, "counters_close": CLOSE, "trace": None,
         "window": (100.0, 145.0)}
    if traced:
        c["trace_window"] = [120.0, 125.0]
        c["trace_counters"] = [dict(OPEN), at_end]
    return c


def test_the_readers_stop_where_the_traced_stretch_ends():
    assert loop_time.share_of_stretch(ctx(), "host_s.idle") \
        == pytest.approx(100.0 * 3.5 / 25.0)
    assert loop_time.mean_over_stretch(
        ctx(), "host_s.hop", "host_n.step", scale=1e3) \
        == pytest.approx(1e3 * 0.5 / 2000)
    assert loop_time.grown_over_stretch(ctx(), "host_s.pause", scale=1e3) \
        == pytest.approx(1750.0)
    # a stretch without a pause reads 0, not None: the counter is there
    still = dict(AT_TRACE_END, **{"host_s.pause": 0.0})
    assert loop_time.grown_over_stretch(ctx(still), "host_s.pause") == 0.0
    # no step in the stretch: no mean
    assert loop_time.mean_over_stretch(
        ctx(dict(AT_TRACE_END, **{"host_n.step": 1000})), "host_s.hop",
        "host_n.step") is None


@pytest.mark.parametrize("name", sorted(WANT) + [
    n + DOC for n in ("step_hop_ms_per_step", "window_pause_ms")])
def test_each_new_metric_file_names_a_reader_that_runs(name):
    read = spec.metric_reader("layer_metrics", name)
    assert read(ctx()) == pytest.approx(WANT[name.removesuffix(DOC)])
    # an older program has none of the counters: nothing to read there,
    # and no exception (`host_n.step` alone does not make a hop of 0 ms)
    assert read(ctx(PARENT)) is None
    # an untraced run has no such stretch; nor one whose session never
    # reached its end
    assert read(ctx(traced=False)) is None
    assert read(ctx(None)) is None


def test_the_declaration_lists_the_five_with_their_cells():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in list(WANT) + ["step_hop_ms_per_step" + DOC,
                              "window_pause_ms" + DOC]:
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"]) == (
            "scheduler", "program_counter", "lower")
        # every listed cell reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
    assert by_name["engine_empty_share"]["moves"] == "ttft_p50_ms"
    assert set(CHAT) <= set(by_name["engine_empty_share"]["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    for name in ("step_hop_ms_per_step", "window_pause_ms"):
        m, twin = by_name[name], by_name[name + DOC]
        assert (m["moves"], twin["moves"]) == ("tpot_p95_ms",
                                               "output_tok_per_s")
        # a twin has the original's reader and arguments, and between
        # them every cell reads the quantity once
        assert spec._json(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json")) == spec._json(
                os.path.join(spec.BENCH_DIR, "layer_metrics",
                             name + DOC + ".json"))
        assert not set(m["workloads"]) & set(twin["workloads"])
        assert set(m["workloads"]) | set(twin["workloads"]) == cells


@pytest.mark.parametrize("cell,names", [
    ("mistral-7b.chat", sorted(WANT)),
    ("mistral-7b.doc-closed",
     ["step_hop_ms_per_step" + DOC, "window_pause_ms" + DOC])])
def test_rehearsal_reads_the_new_entries(cell, names):
    """`--rehearse --trace 1` (CPU, tiny widths, no measurement) walks the
    cell's per-layer readers: every new entry the cell lists reads a
    number."""
    r = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         cell, "--rehearse", "--seconds", "6", "--trace", "1"],
        cwd=spec.REPO_ROOT, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 3, (r.stdout[-2000:], r.stderr[-2000:])
    tag = "rehearsal result (CPU, tiny widths, not a measurement): "
    line, = [ln for ln in r.stdout.splitlines() if tag in ln]
    metrics = json.loads(line.split(tag, 1)[1])["metrics"]
    new = {n: metrics[n]["value"] for n in names}
    assert all(v >= 0.0 for v in new.values())
    if "engine_empty_share" in new:
        assert new["engine_empty_share"] <= 100.0
