"""Fleet introspection plane: the per-process /debug/state + /debug/profile
admin surface (DYN_ADMIN_TOKEN-gated, both worker types + frontend), the
discovery-driven fleet aggregator (obs/fleet.py) with its stale/unreachable
degradation, the dynamo_fleet_* scrape contract, and the planner's
fleet-signal diag."""

import asyncio
import json
import os
import select
import signal
import socket
import subprocess
import sys
import time
import uuid

import aiohttp
import pytest

from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
from dynamo_tpu.obs import fleet as obs_fleet
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig
from dynamo_tpu.runtime.metrics import MetricsHierarchy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOKEN = "fleet-test-token"


def fresh_runtime(**cfg_kw) -> DistributedRuntime:
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc",
                        **cfg_kw)
    return DistributedRuntime(config=cfg, cluster_id=uuid.uuid4().hex)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def admin_get(url: str, token=TOKEN):
    headers = {"X-Dyn-Admin-Token": token} if token else {}
    async with aiohttp.ClientSession() as s:
        async with s.get(url, headers=headers) as r:
            body = await r.read()
            try:
                return r.status, json.loads(body)
            except json.JSONDecodeError:
                return r.status, body


# --------------------- per-process debug surface -----------------------------


async def test_debug_state_token_gated_and_dumps_mocker_state():
    """/debug/state: 401 without/with-wrong token, full dump with the
    right one — scheduler seqs, KV occupancy, drain status, effective
    config, compile stats — for the mocker worker type."""
    rt = await fresh_runtime(system_port=-1, admin_token=TOKEN).start()
    assert rt.system_address, "ephemeral system port must be advertised"
    worker = await MockerWorker(
        rt, MockEngineArgs(model_name="m", block_size=4,
                           base_step_s=0.0005)).start()
    url = f"http://{rt.system_address}/debug/state"
    try:
        status, _ = await admin_get(url, token=None)
        assert status == 401
        status, _ = await admin_get(url, token="wrong")
        assert status == 401
        status, state = await admin_get(url)
        assert status == 200
        assert state["worker_id"] == rt.worker_id
        assert state["config"]["admin_token"] == "***"  # never leaked
        src = state["sources"][f"worker:{worker.served.instance_id}"]
        assert src["kind"] == "mocker"
        assert src["instance_id"] == worker.served.instance_id
        assert src["draining"] is False
        assert src["kv"]["g1"]["capacity"] > 0
        assert "slots" in src and "waiting" in src
        assert "compile" in src and "config" in src
        # drain status flows through live
        worker.engine.draining = True
        _, state2 = await admin_get(url)
        assert state2["sources"][
            f"worker:{worker.served.instance_id}"]["draining"] is True
        # flight-recorder tail: off by default, spans when tracing is on
        assert state2["flight"]["enabled"] is False
        from dynamo_tpu import obs

        tr = obs.Tracer().install()
        try:
            t0 = obs.begin()
            obs.end("step", t0, track="sched:test")
            _, state3 = await admin_get(url + "?spans=8")
            assert state3["flight"]["enabled"] is True
            kinds = [s["kind"] for s in state3["flight"]["spans"]]
            assert "step" in kinds
        finally:
            tr.uninstall()
    finally:
        await worker.close()
        await rt.shutdown()
    # close() must unregister the debug source
    assert not rt.debug_sources


async def test_debug_state_without_admin_token_is_403():
    """Fail closed: no DYN_ADMIN_TOKEN on the process means the admin
    surface stays off (403 explains why), while /health /metrics serve."""
    rt = await fresh_runtime(system_port=-1).start()
    try:
        base = f"http://{rt.system_address}"
        status, body = await admin_get(f"{base}/debug/state", token="x")
        assert status == 403 and "DYN_ADMIN_TOKEN" in body["error"]
        status, _ = await admin_get(f"{base}/debug/profile", token="x")
        assert status == 403
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/health") as r:
                assert r.status == 200
            async with s.get(f"{base}/metrics") as r:
                assert r.status == 200
    finally:
        await rt.shutdown()


async def test_debug_profile_captures_trace_and_memory(tmp_path,
                                                       monkeypatch):
    """/debug/profile: a time-bounded jax.profiler capture + device
    memory snapshot land under DYN_PROFILE_DIR; CPU-safe."""
    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    rt = await fresh_runtime(system_port=-1, admin_token=TOKEN).start()
    try:
        url = f"http://{rt.system_address}/debug/profile?duration_s=0.1"
        status, prof = await admin_get(url)
        assert status == 200
        assert prof["status"] == "ok", prof
        assert prof["backend"] == "cpu"
        assert os.path.isdir(prof["trace_dir"])
        if "memory_profile" in prof:
            assert os.path.exists(prof["memory_profile"])
        # bad duration is a 400, not a crash
        status, _ = await admin_get(
            f"http://{rt.system_address}/debug/profile?duration_s=nan2",
            token=TOKEN)
        assert status == 400
    finally:
        await rt.shutdown()


# real JAX engine in an async body: -O0 compiles dwarf the slow-callback
# gate (see conftest)
@pytest.mark.allow_slow_callbacks
async def test_debug_state_jax_worker():
    """The JAX engine worker serves the same /debug/state contract:
    engine kind, per-tier KV occupancy, slots, compile stats."""
    import jax.numpy as jnp

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
    rt = await fresh_runtime(system_port=-1, admin_token=TOKEN).start()
    worker = await JaxEngineWorker(rt, EngineConfig(
        model_config=tiny, block_size=4, num_blocks=64,
        max_blocks_per_seq=16, max_num_seqs=2,
        prefill_buckets=(8, 16, 32), seed=7)).start()
    client = await (rt.namespace("dynamo").component("backend")
                    .endpoint("generate").client()).start()
    await client.wait_for_instances()
    try:
        req = PreprocessedRequest(
            token_ids=list(range(3, 20)), request_id="r1",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=4, ignore_eos=True))
        async for _ in client.generate(req.to_dict()):
            pass
        status, state = await admin_get(
            f"http://{rt.system_address}/debug/state")
        assert status == 200
        src = state["sources"][f"worker:{worker.served.instance_id}"]
        assert src["kind"] == "engine"
        assert src["kv"]["g1"]["capacity"] == 63  # block 0 is garbage
        assert src["kv"]["g1"]["used"] + src["kv"]["g1"]["free"] == 63
        assert src["engine_metrics"]["requests"] == 1
        assert src["config"]["total_kv_blocks"] == 64
        assert isinstance(src["slots"], list)
        assert src["compile"]["total"] >= 0
    finally:
        await client.close()
        await worker.close()
        await rt.shutdown()


# --------------------- aggregator: reduction + gauges ------------------------


def _mk_state(iid, toks=0, active=0, itl_p95=0.0, free=90, cap=100,
              draining=False, serving_compiles=0):
    return {
        "kind": "mocker", "instance_id": iid, "active_seqs": active,
        "tokens_in_flight": toks, "itl_p95_s": itl_p95,
        "kv": {"g1": {"used": cap - free, "free": free, "capacity": cap}},
        "kv_usage": (cap - free) / cap, "draining": draining,
        "compile": {"total": serving_compiles,
                    "serving": serving_compiles,
                    "families": ({"decode": {"count": serving_compiles,
                                             "seconds": 0.1,
                                             "serving": serving_compiles}}
                                 if serving_compiles else {})},
    }


def test_summarize_states_imbalance_straggler_headroom():
    states = [
        _mk_state(1, toks=300, active=6, itl_p95=0.010, free=10, cap=100),
        _mk_state(2, toks=100, active=2, itl_p95=0.050, free=80, cap=100,
                  serving_compiles=3),
        _mk_state(3, toks=200, active=4, itl_p95=0.012, free=50, cap=100,
                  draining=True),
    ]
    s = obs_fleet.summarize_states(states, stale=1, unreachable=2)
    assert s["workers"] == 6 and s["live"] == 3
    assert s["stale"] == 1 and s["unreachable"] == 2
    assert s["imbalance"] == pytest.approx(300 / 200)
    # median itl_p95 = 0.012; worker 2 at 0.050 > 2x median
    assert s["stragglers"] == [2] and s["straggler_count"] == 1
    assert s["kv_headroom_min"] == pytest.approx(0.10)
    assert s["serving_compile_hotspots"] == {"decode": 3}
    assert s["draining"] == 1
    assert s["tokens_in_flight"]["max"] == 300
    # goodput spread across frontends
    s2 = obs_fleet.summarize_states(states, frontend_states=[
        {"slo": {"goodput": 0.9}}, {"slo": {"goodput": 0.5}}])
    assert s2["goodput"]["spread"] == pytest.approx(0.4)
    # a partially-scraped worker folds its data into the reduction but
    # counts under stale, not live — worker counts stay disjoint
    s3 = obs_fleet.summarize_states(
        states[:2], stale=1, stale_states=[states[2]])
    assert s3["workers"] == 3 and s3["live"] == 2 and s3["stale"] == 1
    assert s3["draining"] == 1          # the stale worker's drain flag
    assert s3["tokens_in_flight"]["total"] == 600  # its load counted


def test_fleet_gauges_scrape_contract():
    """Every dynamo_fleet_* family parses with the prometheus parser,
    is dynamo_-prefixed, and per-instance families carry a `worker`
    label; labels of departed workers are removed on re-export."""
    from prometheus_client.parser import text_string_to_metric_families

    def view(iid, state="live", dbg=True):
        return obs_fleet.WorkerView(
            worker_id=iid, kind="mocker", namespace="dynamo",
            component="backend", endpoint="generate", address="h:1",
            system_addr="h:2", state=state,
            debug=_mk_state(iid, toks=10 * iid, active=iid,
                            itl_p95=0.01) if dbg else None)

    snap = obs_fleet.FleetSnapshot(
        ts_unix=0.0,
        workers=[view(1), view(2), view(3, "unreachable", dbg=False)],
        frontends=[],
        summary=obs_fleet.summarize_states(
            [_mk_state(1, toks=10), _mk_state(2, toks=20)],
            unreachable=1))
    m = MetricsHierarchy(namespace="dynamo", component="fleet")
    prev = obs_fleet.export_fleet_gauges(m, snap)
    assert prev == {"1", "2", "3"}
    text = m.render().decode()
    families = list(text_string_to_metric_families(text))
    assert families
    bad = [f.name for f in families if not f.name.startswith("dynamo_")]
    assert not bad, bad
    fleet_fams = {f.name: f for f in families
                  if f.name.startswith("dynamo_fleet_")}
    assert set(obs_fleet.PER_WORKER_FAMILIES) <= set(fleet_fams)
    for name in obs_fleet.PER_WORKER_FAMILIES:
        for sample in fleet_fams[name].samples:
            assert "worker" in sample.labels, (name, sample)
    # the unreachable worker exports up=0 and nothing else
    ups = {s.labels["worker"]: s.value
           for s in fleet_fams["dynamo_fleet_up"].samples}
    assert ups == {"1": 1.0, "2": 1.0, "3": 0.0}
    assert {s.labels["state"]: s.value
            for s in fleet_fams["dynamo_fleet_workers"].samples} == {
        "live": 2.0, "stale": 0.0, "unreachable": 1.0, "draining": 0.0,
        "quarantined": 0.0}
    # worker 3 leaves the fleet: its labels must not freeze in place
    snap2 = obs_fleet.FleetSnapshot(
        ts_unix=1.0, workers=[view(1), view(2)], frontends=[],
        summary=obs_fleet.summarize_states(
            [_mk_state(1, toks=10), _mk_state(2, toks=20)],
            frontend_states=[{"slo": {"goodput": 0.8}},
                             {"slo": {"goodput": 0.6}}]))
    obs_fleet.export_fleet_gauges(m, snap2, prev)
    text2 = m.render().decode()
    assert 'worker="3"' not in text2
    assert 'worker="1"' in text2
    assert "dynamo_fleet_goodput_spread" in text2
    # all frontends gone: the goodput gauges must not freeze their last
    # value into future scrapes
    snap3 = obs_fleet.FleetSnapshot(
        ts_unix=2.0, workers=[view(1), view(2)], frontends=[],
        summary=obs_fleet.summarize_states(
            [_mk_state(1, toks=10), _mk_state(2, toks=20)]))
    obs_fleet.export_fleet_gauges(m, snap3, {"1", "2"})
    # the HELP/TYPE declarations survive; the SAMPLES must not
    text3 = m.render().decode()
    assert not [ln for ln in text3.splitlines()
                if ln.startswith(("dynamo_fleet_goodput_spread{",
                                  "dynamo_fleet_goodput_min{"))]


async def test_scrape_4xx_fails_fast_without_retry():
    """A 401/403 scrape (wrong admin token) is deterministic: it must
    fail the surface on the FIRST attempt, not re-hit every worker
    under the retry policy on every snapshot."""
    from aiohttp import ClientSession, web

    hits = {"n": 0}

    async def unauthorized(request):
        hits["n"] += 1
        return web.json_response({"error": "unauthorized"}, status=401)

    app = web.Application()
    app.router.add_get("/debug/state", unauthorized)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = runner.addresses[0][1]
    try:
        async with ClientSession() as session:
            with pytest.raises(obs_fleet.PermanentScrapeError):
                await obs_fleet._fetch(
                    session, f"http://127.0.0.1:{port}/debug/state", {},
                    timeout_s=2.0)
        assert hits["n"] == 1
    finally:
        await runner.cleanup()


# --------------------- planner diag ------------------------------------------


class _StaticConnector:
    def __init__(self, n):
        self.n = n

    async def current_replicas(self):
        return self.n

    async def scale(self, n):
        self.n = n
        return n


async def test_planner_diag_carries_fleet_signals_after_skewed_burst():
    """Two mocker workers on one runtime; a skewed burst parks load on
    worker A only.  The FleetObserver's merged scrape shows the
    imbalance, and the planner tick folds it into diag — the inputs
    ROADMAP item 4's controller and item 2's cost function read."""
    from dynamo_tpu.planner import Planner, PlannerConfig
    from dynamo_tpu.protocols import PreprocessedRequest, StopConditions

    rt = await fresh_runtime(system_port=-1, admin_token=TOKEN).start()
    args = MockEngineArgs(model_name="m", block_size=4, base_step_s=0.002,
                          decode_s_per_seq=0.0005)
    w1 = await MockerWorker(rt, args).start()
    w2 = await MockerWorker(rt, args).start()
    fleet = obs_fleet.FleetObserver(runtime=rt, token=TOKEN,
                                    interval_s=60.0)  # manual refresh
    planner = Planner(rt, "dynamo", "mocker",
                      _StaticConnector(2),
                      PlannerConfig(target_active_per_replica=100.0),
                      fleet=fleet)
    await planner.observer.start()

    async def consume(gen):
        async for _ in gen:
            pass

    burst = []
    try:
        # skewed burst: all streams pinned to worker A's engine
        for i in range(4):
            req = PreprocessedRequest(
                token_ids=list(range(16)), request_id=f"r{i}",
                stop=StopConditions(max_tokens=200, ignore_eos=True))
            burst.append(asyncio.create_task(
                consume(w1.engine.generate(req))))
        # wait until A is visibly loaded and B idle, and the load
        # observer has samples (tick holds without them)
        for _ in range(100):
            await asyncio.sleep(0.05)
            if (w1.engine.num_active_seqs >= 3
                    and len(planner.observer.samples) >= 2):
                break
        snap = await fleet.refresh()
        assert snap.summary["live"] == 2
        assert snap.summary["imbalance"] > 1.5, snap.summary
        await planner.tick()
        assert planner.last_diag["fleet_imbalance"] > 1.5
        assert planner.last_diag["fleet_straggler"] >= 0
        assert 0.0 <= planner.last_diag["fleet_kv_headroom"] <= 1.0
        # the fleet gauges rode the runtime registry too
        text = rt.metrics.render().decode()
        assert "dynamo_fleet_load_imbalance" in text
    finally:
        for t in burst:
            t.cancel()
        await asyncio.gather(*burst, return_exceptions=True)
        await planner.close()
        await fleet.close()
        await w1.close()
        await w2.close()
        await rt.shutdown()


async def test_read_only_file_discovery_never_reaps(tmp_path):
    """Live-drive regression: the fleet CLI launched with a mismatched
    (shorter) DYN_LEASE_TTL used to REAP the fleet's live lease files —
    heartbeats only utime existing paths, so a reaped key never came
    back.  A read_only observer may hide entries past its own TTL but
    must never unlink them."""
    from dynamo_tpu.runtime.discovery import INSTANCE_PREFIX, FileDiscovery

    key = INSTANCE_PREFIX + "/ns/c/e/1"
    owner = FileDiscovery(str(tmp_path), ttl_s=60.0)
    observer = FileDiscovery(str(tmp_path), ttl_s=0.01, read_only=True)
    try:
        await owner.put(key, {"x": 1})
        await asyncio.sleep(0.05)  # older than the observer's TTL
        assert await observer.get_prefix(INSTANCE_PREFIX) == {}
        # ...hidden from the observer, but NOT deleted for the owner
        assert key in await owner.get_prefix(INSTANCE_PREFIX)
    finally:
        await observer.close()
        await owner.close()


# --------------------- e2e: 2-process fleet over file discovery --------------


def _wait_line(proc, needle: str, deadline_s: float) -> str:
    """Read stdout lines until `needle` appears (select-paced so a dead
    process can't block the suite)."""
    t_end = time.monotonic() + deadline_s
    buf = ""
    while time.monotonic() < t_end:
        if proc.poll() is not None:
            break
        r, _, _ = select.select([proc.stdout], [], [], 0.25)
        if not r:
            continue
        line = proc.stdout.readline()
        buf += line
        if needle in line:
            return line
    raise AssertionError(
        f"{needle!r} not seen (rc={proc.poll()}):\n{buf}\n"
        f"stderr: {proc.stderr.read() if proc.poll() is not None else ''}")


def test_fleet_e2e_two_process_mockers_and_frontend(tmp_path):
    """Acceptance path: a real 2-process mocker fleet + frontend over
    file discovery.  `python -m dynamo_tpu.obs.fleet --json` returns one
    merged snapshot with per-worker KV occupancy, load, and health;
    /debug/state enforces DYN_ADMIN_TOKEN on a real worker process; a
    SIGSTOP'd worker degrades to `unreachable` without failing the
    snapshot."""
    disco_root = str(tmp_path / "disco")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        DYN_DISCOVERY_BACKEND="file", DYN_DISCOVERY_PATH=disco_root,
        DYN_ADMIN_TOKEN=TOKEN,
        # long lease TTL: a SIGSTOP'd worker must stay IN discovery
        # (scrape-unreachable), not expire out of the snapshot
        DYN_LEASE_TTL="120",
    )
    sys_ports = [free_port(), free_port(), free_port()]
    procs = []
    try:
        for port in sys_ports[:2]:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.mocker",
                 "--component", "backend", "--block-size", "4"],
                env=dict(env, DYN_SYSTEM_PORT=str(port)),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, cwd=REPO))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.frontend",
             "--host", "127.0.0.1", "--port", str(free_port())],
            env=dict(env, DYN_SYSTEM_PORT=str(sys_ports[2])),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO))
        for proc in procs:
            _wait_line(proc, "ready", 90.0)

        # -- the CLI the acceptance criterion names -----------------------
        r = subprocess.run(
            [sys.executable, "-m", "dynamo_tpu.obs.fleet", "--json"],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        snap = json.loads(r.stdout)
        workers = snap["workers"]
        assert len(workers) == 2, workers
        assert all(w["state"] == "live" for w in workers), workers
        for w in workers:
            assert w["debug"]["kv"]["g1"]["capacity"] > 0  # KV occupancy
            assert "active_seqs" in w["debug"]              # load
            assert w["debug"]["draining"] is False          # health
        assert snap["summary"]["live"] == 2
        assert len(snap["frontends"]) == 1
        assert snap["frontends"][0]["debug"]["kind"] == "frontend"

        # -- token enforcement against a real worker process --------------
        async def check_auth():
            base = f"http://127.0.0.1:{sys_ports[0]}"
            st, _ = await admin_get(f"{base}/debug/state", token=None)
            assert st == 401
            st, state = await admin_get(f"{base}/debug/state")
            assert st == 200
            assert any(s.get("kind") == "mocker"
                       for s in state["sources"].values())
            # a mocker never imported JAX and holds no device: the
            # capture is refused rather than starting a backend there
            # (which would reach for a chip some worker holds)
            st, prof = await admin_get(
                f"{base}/debug/profile?duration_s=0.1")
            assert st == 200 and prof["status"] == "unavailable"
            assert "holds no device" in prof["error"]

        asyncio.run(check_auth())

        # -- SIGSTOP degradation ------------------------------------------
        procs[0].send_signal(signal.SIGSTOP)
        time.sleep(0.2)

        async def stopped_snapshot():
            from dynamo_tpu.runtime.discovery import FileDiscovery

            disco = FileDiscovery(disco_root, ttl_s=120.0)
            try:
                return await obs_fleet.snapshot(disco, token=TOKEN,
                                                timeout_s=0.5)
            finally:
                await disco.close()

        snap2 = asyncio.run(stopped_snapshot())
        states = sorted(w.state for w in snap2.workers)
        assert states == ["live", "unreachable"], states
        assert snap2.summary["unreachable"] == 1
        assert snap2.summary["live"] == 1
        procs[0].send_signal(signal.SIGCONT)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGCONT)
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
