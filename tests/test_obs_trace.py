"""Timeline tracing plane (dynamo_tpu/obs): zero-cost-off span tracer,
Chrome trace export, flight recorder, cross-process trace stitching,
and the gap-attribution report."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time
import uuid

import aiohttp
import pytest

from dynamo_tpu import chaos, obs
from dynamo_tpu.mocker import MockEngine, MockEngineArgs, MockerWorker
from dynamo_tpu.obs.report import report_paths
from dynamo_tpu.protocols import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fresh_runtime() -> DistributedRuntime:
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    return DistributedRuntime(config=cfg, cluster_id=uuid.uuid4().hex)


@pytest.fixture(autouse=True)
def _no_tracer_leak():
    """A test that installs a tracer must not leak it into the next."""
    yield
    tr = obs.tracer()
    if tr is not None:
        tr.uninstall()
    assert obs.tracer() is None


# --------------------- zero-cost-off (the chaos-style None check) ----------


def test_disabled_helpers_are_noops():
    assert obs.tracer() is None and not obs.enabled()
    # begin() returns the shared 0.0 constant — no float allocated per
    # call on the hot loop (same zero-cost-off bar as chaos.hit's one
    # global None check)
    assert obs.begin() == 0.0
    assert obs.begin() is obs.begin()
    # end() with a disabled-start handle is a no-op even if a tracer
    # appears mid-span
    obs.end("step", 0.0, anything=1)
    with obs.Tracer() as tr:
        obs.end("step", 0.0, anything=1)  # began disabled: still dropped
        assert len(tr.spans) == 0
    # span() hands back one process-wide no-op context manager
    # dynlint: disable=DYN006 synthetic kinds: this tests tracer mechanics, not the span vocabulary
    assert obs.span("a") is obs.span("b")
    # dynlint: disable=DYN006 synthetic kinds: this tests tracer mechanics, not the span vocabulary
    with obs.span("a"):
        pass
    assert obs.flight_dump("nope") is None


def test_mock_engine_bit_identical_with_tracing_on():
    """The spans-disabled path must not change behavior — and enabling
    it must not either: same seed, same tokens, traced or not."""

    async def run_once(traced: bool):
        eng = MockEngine(MockEngineArgs(
            model_name="m", block_size=4, base_step_s=0.0,
            prefill_s_per_token=0.0, decode_s_per_seq=0.0))
        req = PreprocessedRequest(
            token_ids=list(range(40)), request_id="same-rid",
            stop=StopConditions(max_tokens=32, ignore_eos=True))
        toks = []
        tr = obs.Tracer().install() if traced else None
        try:
            async for out in eng.generate(req):
                toks.extend(out.token_ids)
        finally:
            if tr is not None:
                tr.uninstall()
            await eng.close()
        return toks, (set(s[0] for s in tr.spans) if tr else set())

    async def main():
        plain, _ = await run_once(False)
        traced, kinds = await run_once(True)
        assert plain == traced and len(plain) == 32
        # the mocker emits the engine vocabulary so the timeline plane is
        # exercised CPU-only
        assert {"step", "sched", "device_wait",
                "decode_dispatch", "prefill_dispatch"} <= kinds

    asyncio.run(main())


# --------------------- chrome trace export ---------------------------------


def test_chrome_trace_roundtrips_with_monotonic_ts_per_track():
    tr = obs.Tracer(service="t", ring=256)
    with tr:
        with obs.span("step", track="sched:x", active=2):
            with obs.span("sched", track="sched:x"):
                time.sleep(0.002)
            time.sleep(0.001)

        def other_thread():
            t0 = obs.begin()
            time.sleep(0.001)
            obs.end("detok", t0, tokens=3)

        th = threading.Thread(target=other_thread, name="loop-thread")
        th.start()
        th.join()
    doc = json.loads(json.dumps(tr.chrome_trace()))  # round-trip
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"sched:x", "loop-thread"} <= set(names.values())
    by_tid = {}
    for e in xs:
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    for tss in by_tid.values():
        assert tss == sorted(tss)  # monotonic start ts per track
    # nesting survived: the step span covers its sched child
    step = next(e for e in xs if e["name"] == "step")
    sched = next(e for e in xs if e["name"] == "sched")
    assert step["ts"] <= sched["ts"]
    assert step["ts"] + step["dur"] >= sched["ts"] + sched["dur"]
    assert step["args"]["active"] == 2
    assert next(e for e in xs if e["name"] == "detok")["args"]["tokens"] == 3


def test_ring_bounds_the_recorder():
    tr = obs.Tracer(ring=32)
    now = time.monotonic()
    for i in range(100):
        tr.record("k", now, now + 1e-6, {"i": i})
    assert len(tr.spans) == 32
    assert tr.spans[0][4]["i"] == 68  # oldest spans fell off


def test_span_histogram_on_metrics_hierarchy():
    from dynamo_tpu.runtime.metrics import MetricsHierarchy

    m = MetricsHierarchy(component="backend")
    tr = obs.Tracer().bind_metrics(m)
    with tr:
        t0 = obs.begin()
        obs.end("decode_dispatch", t0)
    text = m.render().decode()
    assert 'dynamo_trace_span_seconds_count{' in text
    assert 'kind="decode_dispatch"' in text


# --------------------- flight recorder -------------------------------------


def test_flight_recorder_fires_on_engine_step_chaos(tmp_path):
    """An injected engine.step fault must leave a valid Chrome-trace
    flight dump of the spans that led up to it (PR 4's fault plane tied
    to a post-mortem timeline)."""

    async def main():
        eng = MockEngine(MockEngineArgs(
            model_name="m", block_size=4, base_step_s=0.0))
        req = PreprocessedRequest(
            token_ids=list(range(12)), request_id="r1",
            stop=StopConditions(max_tokens=64, ignore_eos=True))
        plane = chaos.ChaosPlane(seed=3)
        plane.rule("engine.step", "fail", after=3, times=1)
        errored = False
        with plane:
            async for out in eng.generate(req):
                if out.finish_reason == "error":
                    errored = True
        await eng.close()
        assert errored and plane.fired("engine.step") == 1

    tr = obs.Tracer(out_path=str(tmp_path / "trace.json")).install()
    try:
        asyncio.run(main())
        assert tr.flight_dumps, "flight recorder did not fire"
        path = tr.flight_dumps[0]
        assert os.path.basename(path).startswith(
            "dynflight-chaos.engine.step-")
        doc = json.load(open(path))
        kinds = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert "step" in kinds  # the pre-fault timeline is in the dump
    finally:
        tr.uninstall()


def test_flight_recorder_rate_limited(tmp_path):
    tr = obs.Tracer(out_path=str(tmp_path / "t.json"))
    with tr:
        now = time.monotonic()
        tr.record("step", now, now)
        assert tr.flight_dump("storm") is not None
        assert tr.flight_dump("storm") is None  # within cooldown
        assert tr.flight_dump("other") is not None  # distinct reason


# --------------------- report: gap attribution ------------------------------


def _synthetic_engine_trace(tmp_path):
    """10 steps of 10ms: 2ms sched, 3ms decode_dispatch wrapping 2ms
    device_wait, 1ms sample; 4ms of the step unattributed; 2ms idle
    between steps.  Wall = 118ms (last idle gap not included)."""
    tr = obs.Tracer(service="synth", out_path=str(tmp_path / "synth.json"))
    base = time.monotonic()
    for i in range(10):
        t0 = base + i * 0.012
        tr.record("sched", t0, t0 + 0.002, None, None, "sched:eng")
        tr.record("device_wait", t0 + 0.003, t0 + 0.005, None, None,
                  "sched:eng")
        tr.record("decode_dispatch", t0 + 0.002, t0 + 0.005,
                  {"cont": i % 2 == 0, "k": 4, "lanes": 2}, None,
                  "sched:eng")
        tr.record("sample", t0 + 0.005, t0 + 0.006, None, None, "sched:eng")
        tr.record("step", t0, t0 + 0.010, None, None, "sched:eng")
    return tr.dump()


def test_report_partition_sums_to_wall(tmp_path):
    path = _synthetic_engine_trace(tmp_path)
    rep = report_paths([path])
    gap = rep["gap"]
    # the named phases + idle partition the engine wall time (±1% — the
    # acceptance bar; here it is exact by construction)
    assert abs(sum(gap["wall_fractions"].values()) - 1.0) < 0.01
    assert gap["engine_wall_s"] == pytest.approx(0.118, rel=0.01)
    assert gap["cont_burst_frac"] == 0.5
    # per-phase self time: decode_dispatch is 3ms with 2ms of
    # device_wait nested inside -> 1ms self per step
    assert gap["wall_fractions"]["device_wait"] == pytest.approx(
        0.020 / 0.118, abs=0.01)
    assert gap["wall_fractions"]["decode_dispatch"] == pytest.approx(
        0.010 / 0.118, abs=0.01)
    assert gap["wall_fractions"]["step_other"] == pytest.approx(
        0.040 / 0.118, abs=0.01)
    assert gap["wall_fractions"]["idle"] == pytest.approx(
        0.018 / 0.118, abs=0.02)
    assert gap["sched_overhead_frac"] == pytest.approx(
        0.060 / 0.118, abs=0.02)
    assert rep["kinds"]["decode_dispatch"]["count"] == 10
    assert rep["kinds"]["step"]["p95_ms"] == pytest.approx(10.0, rel=0.01)


def test_report_zero_duration_span_does_not_swallow_track(tmp_path):
    """A zero-width span (coarse clock) must not become a ghost entry
    in the self-time sweep that eats the track's unattributed time."""
    tr = obs.Tracer(service="z", out_path=str(tmp_path / "z.json"))
    base = time.monotonic()
    tr.record("step", base, base + 0.100, None, None, "sched:eng")
    tr.record("sched", base, base, None, None, "sched:eng")  # dur 0
    tr.record("decode_dispatch", base + 0.010, base + 0.030, None, None,
              "sched:eng")
    gap = report_paths([tr.dump()])["gap"]
    assert gap["wall_fractions"].get("sched", 0.0) == 0.0
    assert gap["wall_fractions"]["step_other"] == pytest.approx(0.8,
                                                                abs=0.01)
    assert abs(sum(gap["wall_fractions"].values()) - 1.0) < 0.01


def test_report_cli_runs_on_fixture(tmp_path):
    path = _synthetic_engine_trace(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.obs.report", path,
         "--indent", "0"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout)
    assert abs(sum(rep["gap"]["wall_fractions"].values()) - 1.0) < 0.01


def test_report_on_live_mocker_run(tmp_path):
    """End to end on a real (simulated) serving run: spans recorded by
    the mocker engine reduce to a partition that covers ≥95% of wall."""

    async def main():
        eng = MockEngine(MockEngineArgs(
            model_name="m", block_size=4, base_step_s=0.002))
        reqs = [PreprocessedRequest(
            token_ids=list(range(30 + i)), request_id=f"r{i}",
            stop=StopConditions(max_tokens=20, ignore_eos=True))
            for i in range(3)]

        async def drive(req):
            async for _ in eng.generate(req):
                pass

        await asyncio.gather(*(drive(r) for r in reqs))
        await eng.close()

    tr = obs.Tracer(out_path=str(tmp_path / "live.json")).install()
    try:
        asyncio.run(main())
        path = tr.dump()
    finally:
        tr.uninstall()
    gap = report_paths([path])["gap"]
    named = sum(v for k, v in gap["wall_fractions"].items() if k != "idle")
    assert named >= 0.95  # phases explain ≥95% of engine wall time
    assert abs(sum(gap["wall_fractions"].values()) - 1.0) < 0.01


# --------------------- cross-process trace stitching ------------------------


async def test_frontend_worker_trace_id_stitching(tmp_path, monkeypatch):
    """With tracing enabled and NO inbound traceparent, the frontend
    mints a trace_id; the request_end record, the frontend `request`
    span, and the worker's `worker_request` span all share it."""
    from dynamo_tpu.frontend import HttpService, ModelManager, ModelWatcher

    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("DYN_REQUEST_TRACE", "1")
    monkeypatch.setenv("DYN_REQUEST_TRACE_FILE_PATH", str(trace_file))
    tr = obs.Tracer().install()
    rt = await fresh_runtime().start()
    worker = await MockerWorker(rt, MockEngineArgs(
        model_name="stitch-model", block_size=4, base_step_s=0.0005,
        prefill_s_per_token=0.0, decode_s_per_seq=0.0)).start()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    service = await HttpService(rt, manager, host="127.0.0.1",
                                port=0).start()
    port = service._runner.addresses[0][1]
    for _ in range(100):
        if manager.get("stitch-model"):
            break
        await asyncio.sleep(0.02)
    try:
        async with aiohttp.ClientSession() as s:
            body = {"model": "stitch-model",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 4, "ignore_eos": True}
            async with s.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                              json=body) as r:
                assert r.status == 200
        rec = json.loads(trace_file.read_text().strip().splitlines()[-1])
        tid = rec["trace"]["trace_id"]
        assert tid and len(tid) == 32
        spans = list(tr.spans)
        req_span = next(s for s in spans if s[0] == "request")
        wrk_span = next(s for s in spans if s[0] == "worker_request")
        assert req_span[5] == tid
        assert wrk_span[5] == tid  # worker joined via the annotation
        assert wrk_span[4]["tokens"] == 4
        # the MDC advertises the capability while tracing is on
        assert worker.card.runtime_config.get("tracing") is True
    finally:
        tr.uninstall()
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


# --------------------- request_end on error paths ---------------------------


async def test_request_end_emitted_on_drain_abort(tmp_path, monkeypatch):
    """A drain-abort with no migration budget must still emit the
    request_end record, error field populated with the drain marker."""
    from dynamo_tpu.frontend import HttpService, ModelManager, ModelWatcher

    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("DYN_REQUEST_TRACE", "1")
    monkeypatch.setenv("DYN_REQUEST_TRACE_FILE_PATH", str(trace_file))
    rt = await fresh_runtime().start()
    worker = await MockerWorker(rt, MockEngineArgs(
        model_name="drain-model", block_size=4, base_step_s=0.01)).start()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    service = await HttpService(rt, manager, host="127.0.0.1",
                                port=0).start()
    port = service._runner.addresses[0][1]
    for _ in range(100):
        if manager.get("drain-model"):
            break
        await asyncio.sleep(0.02)
    try:
        async with aiohttp.ClientSession() as s:
            body = {"model": "drain-model",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 500, "ignore_eos": True, "stream": True}

            async def request_task():
                async with s.post(
                    f"http://127.0.0.1:{port}/v1/chat/completions",
                    json=body,
                ) as r:
                    assert r.status == 200
                    return await r.read()

            task = asyncio.create_task(request_task())
            await asyncio.sleep(0.15)  # stream under way
            await worker.drain(deadline_s=0.05)
            await task
        recs = [json.loads(x) for x in
                trace_file.read_text().strip().splitlines()]
        assert len(recs) == 1  # finish() is idempotent: exactly one
        assert "worker draining" in recs[0]["request"]["error"]
    finally:
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


async def test_request_end_emitted_on_worker_death(tmp_path, monkeypatch):
    """Migration budget exhausted (limit 0, worker dies mid-decode):
    request_end carries the death marker instead of vanishing."""
    from dynamo_tpu.frontend import HttpService, ModelManager, ModelWatcher

    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("DYN_REQUEST_TRACE", "1")
    monkeypatch.setenv("DYN_REQUEST_TRACE_FILE_PATH", str(trace_file))
    rt = await fresh_runtime().start()
    worker = await MockerWorker(rt, MockEngineArgs(
        model_name="dead-model", block_size=4, base_step_s=0.0005,
        fail_after_tokens=3)).start()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    service = await HttpService(rt, manager, host="127.0.0.1",
                                port=0).start()
    port = service._runner.addresses[0][1]
    for _ in range(100):
        if manager.get("dead-model"):
            break
        await asyncio.sleep(0.02)
    try:
        async with aiohttp.ClientSession() as s:
            body = {"model": "dead-model",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 64, "ignore_eos": True}
            async with s.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                              json=body) as r:
                assert r.status == 500
        recs = [json.loads(x) for x in
                trace_file.read_text().strip().splitlines()]
        assert len(recs) == 1
        assert "connection lost" in recs[0]["request"]["error"]
    finally:
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


def test_on_dispatch_counts_same_instance_redispatch():
    """A token-replay that lands back on the SAME instance (avoid set
    relaxed) is still a migration the record must count."""
    from dynamo_tpu.frontend.request_trace import RequestTracker

    tr = RequestTracker(request_id="r", model="m")
    tr.on_dispatch(7)
    tr.on_dispatch(7)  # re-dispatch to the same worker
    tr.on_dispatch(7)
    rec = tr.finish(error="died twice, same worker revived")
    assert rec["request"]["migrations"] == 2
    assert rec["request"]["worker"]["decode_worker_id"] == 7


def test_finish_is_idempotent():
    from dynamo_tpu.frontend.request_trace import (
        RequestTracker, TraceConfig, TraceSink)

    class CountingSink(TraceSink):
        def __init__(self):
            super().__init__(TraceConfig(enabled=True, sinks=()))
            self.n = 0

        def emit(self, record):
            self.n += 1

    sink = CountingSink()
    tr = RequestTracker(request_id="r", model="m", sink=sink)
    first = tr.finish(finish_reason="stop")
    second = tr.finish(error="late teardown exception")
    assert first is second and sink.n == 1
    assert "error" not in first["request"]  # the clean record won


# --------------------- FPM aggregates on /metrics ---------------------------


def test_fpm_window_decode_tokens_per_s():
    from dynamo_tpu.planner.metrics import FpmWindow

    fw = FpmWindow()
    for _ in range(10):
        # 4 tokens x 2 lanes per 10ms gap -> 800 tok/s
        fw.add(1, {"kind": "decode", "k": 4, "lanes": 2, "gap_s": 0.01})
    fw.add(1, {"kind": "decode", "k": 4, "lanes": 2, "gap_s": 0.0})  # idle
    assert fw.decode_tokens_per_s() == pytest.approx(800.0)
    assert fw.decode_itl_s() == pytest.approx(0.01 / 4)


async def test_worker_exports_fpm_gauges_on_metrics():
    """The mocker worker (same path as the JAX worker) surfaces FPM
    aggregates as gauges: a spec-decoding run leaves
    dynamo_engine_spec_acceptance on /metrics."""
    rt = await fresh_runtime().start()
    worker = await MockerWorker(rt, MockEngineArgs(
        model_name="fpm-model", block_size=4, base_step_s=0.0005,
        speculative={"k": 4, "acceptance": 0.7})).start()
    client = await (rt.namespace("dynamo").component("mocker")
                    .endpoint("generate").client()).start()
    await client.wait_for_instances()
    req = PreprocessedRequest(
        token_ids=list(range(16)), request_id="r1",
        stop=StopConditions(max_tokens=24, ignore_eos=True))
    async for _ in client.generate(req.to_dict()):
        pass
    text = ""
    for _ in range(40):  # wait out a load-loop tick
        await asyncio.sleep(0.1)
        text = rt.metrics.render().decode()
        if "dynamo_engine_spec_acceptance" in text:
            break
    assert "dynamo_engine_spec_acceptance" in text
    await client.close()
    await worker.close()
    await rt.shutdown()


def test_trace_id_from_annotations():
    tid = "0af7651916cd43dd8448eb211c80319c"
    assert obs.trace_id_from_annotations(
        [f"traceparent:00-{tid}-b7ad6b7169203331-01"]) == tid
    assert obs.trace_id_from_annotations(["traceparent:junk"]) is None
    assert obs.trace_id_from_annotations([]) is None
    assert obs.trace_id_from_annotations(None) is None
