"""SLO plane (obs/slo.py): per-request latency histograms fed from
RequestTracker.finish, terminal-outcome accounting, goodput + burn-rate
windows, the chaos-injected breach path, the planner's SloObserver feed,
the scrape contract, and log<->trace correlation."""

import asyncio
import json
import logging
import time
import uuid

import aiohttp
import jax.numpy as jnp
import pytest

from dynamo_tpu import chaos, obs
from dynamo_tpu.frontend import HttpService, ModelManager, ModelWatcher
from dynamo_tpu.frontend.request_trace import RequestTracker
from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
from dynamo_tpu.obs.slo import SloConfig, SloPlane
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig
from dynamo_tpu.runtime.metrics import MetricsHierarchy


def fresh_runtime() -> DistributedRuntime:
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    return DistributedRuntime(config=cfg, cluster_id=uuid.uuid4().hex)


async def start_stack(rt, model="slo-model", slo=None, **engine_kw):
    args = MockEngineArgs(model_name=model, block_size=4,
                          base_step_s=0.0005, prefill_s_per_token=0.0,
                          decode_s_per_seq=0.0, **engine_kw)
    worker = await MockerWorker(rt, args).start()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager).start()
    service = await HttpService(rt, manager, host="127.0.0.1", port=0,
                                slo=slo).start()
    port = service._runner.addresses[0][1]
    for _ in range(100):
        if manager.get(model):
            break
        await asyncio.sleep(0.02)
    return worker, watcher, service, port


async def chat(port, model, max_tokens=4, stream=False):
    async with aiohttp.ClientSession() as s:
        body = {"model": model,
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": max_tokens, "ignore_eos": True,
                "stream": stream}
        async with s.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                          json=body) as r:
            return r.status, await r.read()


async def scrape(port):
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{port}/metrics") as r:
            return await r.text()


def metric_value(text, prefix, **labels):
    """Sum of samples whose line starts with `prefix` and contains all
    label pairs."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if not line.startswith(prefix + "{"):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


# --------------------- unit: goodput / burn / outcomes ----------------------


def test_slo_plane_goodput_burn_and_outcome_labels():
    m = MetricsHierarchy(component="frontend")
    plane = SloPlane(m, SloConfig(ttft_ms=50.0, objective=0.99,
                                  windows_s=(60.0, 300.0)))

    def run(ttft_sleep_s=None, error=None):
        t = RequestTracker(request_id=uuid.uuid4().hex, model="m",
                           slo=plane)
        t.on_dispatch(1)
        if error is None:
            if ttft_sleep_s:
                time.sleep(ttft_sleep_s)
            t.on_tokens(2)
            t.finish(finish_reason="stop")
        else:
            t.finish(error=error)
        return t

    run()                       # fast: good
    run(ttft_sleep_s=0.08)      # ok but TTFT 80ms > 50ms: breach (ttft)
    run(error="connection lost (worker died)")  # no token at all
    plane.refresh()  # per-finish refreshes are throttled; scrapes force
    text = m.render().decode()
    # TTFT histogram saw ONLY the two token-producing requests
    assert metric_value(text, "dynamo_frontend_ttft_seconds_count",
                        model="m") == 2.0
    # e2e + finished count ALL three, split by outcome
    assert metric_value(text, "dynamo_frontend_e2e_seconds_count",
                        outcome="ok") == 2.0
    assert metric_value(text, "dynamo_frontend_e2e_seconds_count",
                        outcome="no_first_token") == 1.0
    assert metric_value(text, "dynamo_frontend_requests_finished_total",
                        outcome="no_first_token") == 1.0
    assert metric_value(text, "dynamo_frontend_slo_breach_total",
                        reason="ttft") == 1.0
    assert metric_value(text, "dynamo_frontend_slo_breach_total",
                        reason="no_first_token") == 1.0
    # goodput 1/3; burn = (2/3) / (1 - 0.99)
    assert plane.goodput() == pytest.approx(1 / 3)
    burns = plane.burn_rates()
    assert burns[60.0] == pytest.approx((2 / 3) / 0.01, rel=1e-6)
    assert burns[300.0] == burns[60.0]  # same requests in both windows
    for line in text.splitlines():
        if line.startswith("dynamo_frontend_slo_goodput{"):
            assert float(line.rsplit(" ", 1)[1]) == pytest.approx(1 / 3)
    # queue time was recorded from the first dispatch
    assert metric_value(text, "dynamo_frontend_queue_seconds_count",
                        model="m") == 3.0


def test_slo_plane_without_targets_is_histogram_only():
    m = MetricsHierarchy(component="frontend")
    plane = SloPlane(m, SloConfig())
    t = RequestTracker(request_id="r", model="m", slo=plane)
    t.on_tokens(1)
    rec = t.finish(finish_reason="stop")
    assert rec["request"]["outcome"] == "ok"
    text = m.render().decode()
    assert "dynamo_frontend_e2e_seconds_count" in text
    assert "dynamo_frontend_slo_goodput" not in text
    assert plane.goodput() is None


def test_tracker_record_outcome_and_queue_fields():
    t = RequestTracker(request_id="r", model="m")
    t.on_dispatch(7)
    rec = t.finish(error="worker draining")
    assert rec["request"]["outcome"] == "no_first_token"
    assert rec["request"]["queue_ms"] >= 0.0
    t2 = RequestTracker(request_id="r2", model="m")
    t2.on_dispatch(7)
    t2.on_tokens(3)
    rec2 = t2.finish(error="connection lost mid-stream")
    assert rec2["request"]["outcome"] == "error"
    t3 = RequestTracker(request_id="r3", model="m")
    rec3 = t3.finish(error="preprocessing failed")
    assert rec3["request"]["outcome"] == "no_first_token"
    assert "queue_ms" not in rec3["request"]  # never dispatched


def test_queue_time_ends_at_prefill_hop_not_decode_dispatch():
    """Disagg: the prefill hop is the FIRST worker dispatch — the
    pipeline marks it before maybe_prefill, so queue_ms must not absorb
    a slow remote prefill as phantom admission wait."""
    t = RequestTracker(request_id="r", model="m")
    t.mark_dispatching()   # pipeline: request leaves for the prefill hop
    time.sleep(0.05)       # the remote prefill runs...
    t.on_dispatch(3)       # ...then the decode dispatch happens
    t.on_tokens(1)
    rec = t.finish(finish_reason="stop")
    assert rec["request"]["queue_ms"] < 25.0  # excludes the 50ms prefill


def test_burn_rate_windows_age_out():
    m = MetricsHierarchy(component="frontend")
    plane = SloPlane(m, SloConfig(ttft_ms=50.0,
                                  windows_s=(0.05, 10.0)))
    plane._finished.append((time.monotonic(), False))  # one bad request
    assert plane.burn_rates()[0.05] > 0.0
    plane.refresh()
    assert metric_value(m.render().decode(),
                        "dynamo_frontend_slo_goodput") == 0.0
    # past the short window AND the window-scan cache TTL (0.2s)
    time.sleep(0.25)
    burns = plane.burn_rates()
    # aged out of the short window, still burning in the long one
    assert 0.05 not in burns
    assert burns[10.0] > 0.0
    # a refresh after aging must ROLL the gauges past the breach: the
    # empty short window reads no-breach, not the frozen last value
    plane.refresh()
    text = m.render().decode()
    assert metric_value(text, "dynamo_frontend_slo_goodput") == 1.0
    assert metric_value(text, "dynamo_frontend_slo_burn_rate",
                        window="0s") == 0.0  # int(0.05) == 0
    assert metric_value(text, "dynamo_frontend_slo_burn_rate",
                        window="10s") > 0.0


# --------------------- e2e: histograms + injected breach --------------------


# timing-sensitive: asserts a real 80ms TTFT target holds on the fast
# path — the slow-callback gate's debug-mode overhead breaches it flakily
@pytest.mark.allow_slow_callbacks
async def test_frontend_exports_slo_surface_and_chaos_breach():
    """The acceptance path: a CPU-only mocker+frontend run exports the
    TTFT/e2e/queue histograms and a goodput gauge that RESPONDS to an
    injected breach — chaos-delayed frames push goodput below 1.0."""
    rt = await fresh_runtime().start()
    worker, watcher, service, port = await start_stack(
        rt, slo=SloConfig(ttft_ms=80.0, publish_interval_s=0.1))
    try:
        status, _ = await chat(port, "slo-model")  # fast: good
        assert status == 200
        text = await scrape(port)
        assert metric_value(text, "dynamo_frontend_slo_goodput") == 1.0

        # delay every response frame well past the TTFT target
        plane = chaos.ChaosPlane(seed=5).rule(
            "request_plane.frame", "delay", delay_s=0.15, times=2)
        with plane:
            status, _ = await chat(port, "slo-model")
            assert status == 200
        assert plane.fired() >= 1
        text = await scrape(port)
        assert metric_value(text, "dynamo_frontend_ttft_seconds_count",
                            model="slo-model") == 2.0
        assert metric_value(text, "dynamo_frontend_e2e_seconds_count",
                            outcome="ok") == 2.0
        assert metric_value(text, "dynamo_frontend_queue_seconds_count",
                            model="slo-model") == 2.0
        goodput = metric_value(text, "dynamo_frontend_slo_goodput")
        assert goodput == pytest.approx(0.5)
        assert metric_value(text, "dynamo_frontend_slo_burn_rate",
                            window="60s") == pytest.approx(0.5 / 0.01)
        assert metric_value(text, "dynamo_frontend_slo_breach_total",
                            reason="ttft") == 1.0

        # ...and the planner-facing feed carries the same breach
        from dynamo_tpu.planner.metrics import SloObserver

        slo_obs = await SloObserver(rt, "dynamo").start()
        agg = None
        for _ in range(40):
            await asyncio.sleep(0.05)
            agg = slo_obs.aggregate()
            if agg is not None:
                break
        assert agg is not None and agg["goodput"] == pytest.approx(0.5)
        assert agg["max_burn"] == pytest.approx(50.0, rel=0.01)
        await slo_obs.close()
    finally:
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


async def test_dispatch_fail_counts_without_polluting_ttft(tmp_path,
                                                           monkeypatch):
    """The chaos dispatch-fail seam: a request that never produces a
    first token (migration budget 0) must land in the e2e/goodput
    denominators under outcome=no_first_token while the TTFT histogram
    stays empty — and its request_end record says why."""
    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("DYN_REQUEST_TRACE", "1")
    monkeypatch.setenv("DYN_REQUEST_TRACE_FILE_PATH", str(trace_file))
    rt = await fresh_runtime().start()
    worker, watcher, service, port = await start_stack(
        rt, model="df-model", slo=SloConfig(ttft_ms=1000.0))
    try:
        plane = chaos.ChaosPlane(seed=9).rule(
            "request_plane.dispatch", "fail", times=1,
            error="connection lost (chaos: dispatch)")
        with plane:
            status, _ = await chat(port, "df-model")
        assert status == 500 and plane.fired() == 1
        text = await scrape(port)
        assert metric_value(text, "dynamo_frontend_ttft_seconds_count",
                            model="df-model") is None  # no sample at all
        assert metric_value(text, "dynamo_frontend_e2e_seconds_count",
                            outcome="no_first_token") == 1.0
        assert metric_value(text, "dynamo_frontend_slo_goodput") == 0.0
        rec = json.loads(trace_file.read_text().strip().splitlines()[-1])
        assert rec["request"]["outcome"] == "no_first_token"
        assert "connection lost" in rec["request"]["error"]
    finally:
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


# --------------------- scrape contract --------------------------------------


def _assert_scrape_contract(text: str) -> int:
    """Every exported family parses and is dynamo_-prefixed — the
    lint-style gate that fails on any future unprefixed metric — and
    none claims a utilisation of the chip: that is the device trace's to
    say, not a host clock's."""
    from prometheus_client.parser import text_string_to_metric_families

    families = list(text_string_to_metric_families(text))
    assert families, "empty scrape"
    bad = [f.name for f in families if not f.name.startswith("dynamo_")]
    assert not bad, f"unprefixed metric families exported: {bad}"
    util = [f.name for f in families
            if f.name.endswith(("mfu", "mbu"))]
    assert not util, f"utilisation gauges from host-clock gaps: {util}"
    return len(families)


async def test_scrape_contract_frontend_and_mocker():
    rt = await fresh_runtime().start()
    worker, watcher, service, port = await start_stack(
        rt, model="scrape-model", slo=SloConfig(ttft_ms=1000.0))
    try:
        await chat(port, "scrape-model")
        await asyncio.sleep(0.4)  # a mocker load-loop tick
        text = await scrape(port)
        n = _assert_scrape_contract(text)
        assert n > 10  # frontend + worker families on one registry
    finally:
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


# real JAX engine in an async body: -O0 compiles dwarf the 200ms
# loop gate (see conftest); mocker-based tests here stay gated
@pytest.mark.allow_slow_callbacks
async def test_scrape_contract_jax_worker():
    """The JAX engine worker's /metrics surface (engine gauges, compile
    histogram, occupancy, FPM aggregates) honors the same contract."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.worker import JaxEngineWorker
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    tiny = LlamaConfig(name="tiny32", vocab_size=256, d_model=64,
                       n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                       ffn_dim=128, dtype=jnp.float32)
    rt = await fresh_runtime().start()
    worker = await JaxEngineWorker(rt, EngineConfig(
        model_config=tiny, block_size=4, num_blocks=64,
        max_blocks_per_seq=16, max_num_seqs=2,
        prefill_buckets=(8, 16, 32), seed=7,
    )).start()
    client = await (rt.namespace("dynamo").component("backend")
                    .endpoint("generate").client()).start()
    await client.wait_for_instances()
    try:
        req = PreprocessedRequest(
            token_ids=list(range(3, 25)), request_id="r1",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=4, ignore_eos=True))
        async for _ in client.generate(req.to_dict()):
            pass
        text = ""
        for _ in range(40):  # wait out a 0.5s load-loop tick
            await asyncio.sleep(0.1)
            text = rt.metrics.render().decode()
            if "dynamo_engine_compile_seconds" in text:
                break
        _assert_scrape_contract(text)
        # the compile and occupancy families are on the surface
        assert 'dynamo_engine_compile_seconds_count{' in text
        assert 'family="prefill_packed"' in text
        assert 'dynamo_engine_kv_blocks_used{' in text
        assert 'tier="g1"' in text
    finally:
        await client.close()
        await worker.close()
        await rt.shutdown()


# --------------------- log<->trace correlation ------------------------------


async def test_log_lines_join_spans_and_record_on_trace_id(tmp_path,
                                                           monkeypatch):
    """With tracing on, a request's frontend+worker log records carry
    the same trace_id as its spans and its request_end record — the
    three observability surfaces join on one key."""
    from dynamo_tpu.runtime.logging import TraceIdFilter

    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("DYN_REQUEST_TRACE", "1")
    monkeypatch.setenv("DYN_REQUEST_TRACE_FILE_PATH", str(trace_file))

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    cap = Capture()
    cap.addFilter(TraceIdFilter())
    logging.getLogger().addHandler(cap)
    wlog = logging.getLogger("dynamo_tpu.mocker.worker")
    old_level = wlog.level
    wlog.setLevel(logging.INFO)  # pytest's root default is WARNING
    tr = obs.Tracer().install()
    rt = await fresh_runtime().start()
    worker, watcher, service, port = await start_stack(rt,
                                                       model="join-model")
    try:
        status, _ = await chat(port, "join-model")
        assert status == 200
        rec = json.loads(trace_file.read_text().strip().splitlines()[-1])
        tid = rec["trace"]["trace_id"]
        served = [r for r in records
                  if r.getMessage() == "request served"]
        assert served, "worker served-log line missing"
        assert getattr(served[-1], "trace_id", None) == tid
        # the worker span shares the id too (PR 6 contract still holds)
        wrk = next(s for s in tr.spans if s[0] == "worker_request")
        assert wrk[5] == tid
    finally:
        logging.getLogger().removeHandler(cap)
        wlog.setLevel(old_level)
        tr.uninstall()
        await service.close()
        await watcher.close()
        await worker.close()
        await rt.shutdown()


def test_trace_id_filter_respects_explicit_extra():
    from dynamo_tpu.runtime.logging import TraceIdFilter

    f = TraceIdFilter()
    rec = logging.LogRecord("x", logging.INFO, "f.py", 1, "m", (), None)
    tok = obs.bind_trace_id("a" * 32)
    try:
        assert f.filter(rec) and rec.trace_id == "a" * 32
        rec2 = logging.LogRecord("x", logging.INFO, "f.py", 1, "m", (),
                                 None)
        rec2.trace_id = "explicit"
        f.filter(rec2)
        assert rec2.trace_id == "explicit"  # extra= wins over context
    finally:
        obs.unbind_trace_id(tok)
    rec3 = logging.LogRecord("x", logging.INFO, "f.py", 1, "m", (), None)
    f.filter(rec3)
    assert not hasattr(rec3, "trace_id")  # nothing bound: no stamp


# --------------------- planner SloObserver ----------------------------------


async def test_slo_observer_aggregates_and_expires():
    from dynamo_tpu.planner.metrics import SloObserver

    rt = await fresh_runtime().start()
    slo_obs = await SloObserver(rt, "dynamo", stale_after_s=0.3).start()
    try:
        agg = None
        for _ in range(40):
            # republish until the subscription is attached and both
            # samples landed (subscribe() attaches asynchronously)
            await rt.event_plane.publish("slo_metrics.dynamo", {
                "frontend_id": 1, "goodput": 0.9,
                "burn": {"60s": 10.0, "300s": 2.0}, "requests": 30})
            await rt.event_plane.publish("slo_metrics.dynamo", {
                "frontend_id": 2, "goodput": 0.5,
                "burn": {"60s": 50.0}, "requests": 10})
            await asyncio.sleep(0.02)
            agg = slo_obs.aggregate()
            if agg is not None and agg["frontends"] == 2:
                break
        assert agg["frontends"] == 2 and agg["requests"] == 40
        # request-weighted: (0.9*30 + 0.5*10) / 40
        assert agg["goodput"] == pytest.approx(0.8)
        assert agg["max_burn"] == 50.0
        await asyncio.sleep(0.4)
        assert slo_obs.aggregate() is None  # stale frontends expire
    finally:
        await slo_obs.close()
        await rt.shutdown()
