"""SLA planner: profiler sweep, perf-model interpolation/inversion, and
the PROPOSE loop holding latency targets (ref planner-design.md
"Throughput-Based Scaling": predict traffic -> invert perf model under
TTFT/ITL SLAs -> replica targets)."""

import pytest
import asyncio
import math
import uuid

from dynamo_tpu.mocker import MockEngine, MockEngineArgs
from dynamo_tpu.planner import PerfModel, Planner, PlannerConfig, make_predictor
from dynamo_tpu.planner.metrics import AggregateLoad, LoadObserver
from dynamo_tpu.profiler import PerfPoint, PerfProfile, profile_engine
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig


def synthetic_profile(base=0.002, per_seq=0.001, prefill_per_tok=0.00002):
    """Profile of a linear-timing engine (the mocker's model): ITL grows
    with concurrency, TTFT with ISL and queueing."""
    prof = PerfProfile(model_name="synth")
    for isl in (128, 512):
        for c in (1, 2, 4, 8, 16):
            itl = base + per_seq * c
            ttft = (base + prefill_per_tok * isl) * (1 + 0.3 * (c - 1))
            prof.points.append(PerfPoint(
                isl=isl, osl=32, concurrency=c,
                ttft_p50_s=ttft * 0.9, ttft_p95_s=ttft,
                itl_mean_s=itl * 0.95, itl_p95_s=itl,
                req_per_s=c / (ttft + 32 * itl),
                output_tok_per_s=32 * c / (ttft + 32 * itl),
            ))
    return prof


# ----------------------------- profiler ----------------------------------


# profiler sweep: CPU-bound host math runs in the test coroutine —
# borderline against the loop gate under suite load (harness cost,
# not a serving path)
@pytest.mark.allow_slow_callbacks
async def test_profile_mock_engine_latency_surface():
    """The sweep recovers the mocker's polynomial timing model: ITL rises
    with concurrency, TTFT rises with ISL."""
    engine = MockEngine(MockEngineArgs(
        base_step_s=0.001, prefill_s_per_token=0.00002,
        decode_s_per_seq=0.0005, max_batch_tokens=512,
    ))
    try:
        prof = await profile_engine(
            engine, model_name="mock", isls=(32, 256), osl=8,
            concurrencies=(1, 8), rounds=2,
        )
    finally:
        await engine.close()
    assert len(prof.points) == 4
    by = {(p.isl, p.concurrency): p for p in prof.points}
    # ITL at c=8 must exceed c=1 (decode_s_per_seq dominates)
    assert by[(32, 8)].itl_mean_s > by[(32, 1)].itl_mean_s
    # TTFT at isl=256 must exceed isl=32 at the same concurrency
    assert by[(256, 1)].ttft_p95_s > by[(32, 1)].ttft_p95_s
    # round-trip through JSON preserves the surface
    prof2 = PerfProfile.from_json(prof.to_json())
    assert prof2.points[0].itl_mean_s == prof.points[0].itl_mean_s


# ---------------------------- perf model ----------------------------------


def test_perf_model_interpolation_and_inversion():
    pm = PerfModel(synthetic_profile())
    # interpolation between grid points: itl(6) between itl(4) and itl(8)
    assert pm.itl(4) < pm.itl(6) < pm.itl(8)
    # inversion: target 0.007 = base+per_seq*5 -> capacity ~5 seqs
    cap = pm.max_active_for_itl(0.007)
    assert 4.0 <= cap <= 6.0, cap
    # extrapolation past the grid: target beyond c=16 still inverts
    assert pm.max_active_for_itl(0.030) > 16.0
    # unattainable ITL floors at 0.5 (over-provision, never div-zero)
    assert pm.max_active_for_itl(0.0001) == 0.5
    # TTFT rate capacity: looser target admits more throughput
    tight = pm.max_rps_for_ttft(128, 0.003)
    loose = pm.max_rps_for_ttft(128, 0.02)
    assert loose >= tight > 0
    # ISL interpolation: TTFT at 300 sits between the 128 and 512 curves
    assert pm.ttft(128, 1) < pm.ttft(300, 1) < pm.ttft(512, 1)


def test_perf_model_conservative_on_noisy_profile():
    """A p95 outlier mid-grid (1-core measurement noise) must not let
    linear extrapolation invent infinite capacity past the grid — found
    live: planner refused to scale because itl(32) extrapolated negative."""
    prof = PerfProfile(model_name="noisy")
    for c, itl in ((1, 0.0034), (4, 0.1249), (8, 0.0062)):
        prof.points.append(PerfPoint(isl=64, osl=8, concurrency=c,
                                     ttft_p95_s=0.01, itl_p95_s=itl,
                                     itl_mean_s=itl, req_per_s=c * 10.0))
    pm = PerfModel(prof)
    # beyond the grid the estimate never drops below the last sample
    assert pm.itl(32) >= 0.0062
    # capacity under a 4ms target stops at the first violation (~1)
    assert pm.max_active_for_itl(0.004) < 1.5


def test_perf_model_online_correction():
    pm = PerfModel(synthetic_profile())
    base_est = pm.itl(4)
    # hardware consistently 2x slower than the stale profile
    for _ in range(50):
        pm.observe_itl(4, base_est * 2.0)
    assert 1.7 <= pm.itl_correction <= 2.1
    # corrected estimate halves the capacity at the same target
    assert pm.max_active_for_itl(0.007) < 4.0
    # correction is clamped against pathological samples
    for _ in range(100):
        pm.observe_itl(4, 100.0)
    assert pm.itl_correction <= 4.0


# ----------------------------- planner -----------------------------------


class _FakeConnector:
    def __init__(self, replicas=1):
        self.replicas = replicas
        self.calls = []

    async def current_replicas(self):
        return self.replicas

    async def scale(self, n):
        self.calls.append(n)
        self.replicas = n
        return n


class _FakeObserver:
    def __init__(self):
        self.load = None

    async def start(self):
        return self

    async def close(self):
        pass

    def aggregate(self):
        return self.load


def _sla_planner(cfg, conn, pm):
    p = Planner.__new__(Planner)
    p.config = cfg
    p.connector = conn
    p.observer = _FakeObserver()
    p.fpm = None
    p.slo = None
    p._storm_warned = 0
    p.predictor = make_predictor("constant")
    p.rate_predictor = make_predictor("constant")
    p.perf_model = pm
    p._task = None
    p._last_action_t = 0.0
    p._low_ticks = 0
    p.decisions = []
    return p


async def test_sla_planner_holds_itl_slo_on_ramp():
    """Ramping active sequences: replicas grow so per-replica concurrency
    stays within the perf model's ITL capacity."""
    pm = PerfModel(synthetic_profile())
    cfg = PlannerConfig(mode="sla", itl_target_s=0.007, cooldown_s=0.0,
                        min_replicas=1, max_replicas=8, max_step=8,
                        down_stable_ticks=1)
    conn = _FakeConnector(replicas=1)
    p = _sla_planner(cfg, conn, pm)
    cap = pm.max_active_for_itl(0.007)

    for active in (4, 10, 22, 38):
        p.observer.load = AggregateLoad(workers=conn.replicas,
                                        active_seqs=active,
                                        mean_kv_usage=0.2, mean_isl=128)
        p.predictor = make_predictor("constant")
        await p.tick()
        want = math.ceil(active / cap)
        assert conn.replicas == min(want, 8), (active, conn.replicas)
        # the SLO holds at the applied fleet size
        assert pm.itl(active / conn.replicas) <= 0.007 * 1.05

    # drain scales back down to min
    p.observer.load = AggregateLoad(workers=conn.replicas, active_seqs=0,
                                    mean_kv_usage=0.0)
    p.predictor = make_predictor("constant")
    p.rate_predictor = make_predictor("constant")
    for _ in range(8):
        await p.tick()
    assert conn.replicas == 1


async def test_sla_planner_ttft_bound_scales_on_arrival_rate():
    """Low active count but high arrival rate: the TTFT/rate bound must
    drive scaling even when the ITL bound is satisfied."""
    pm = PerfModel(synthetic_profile())
    cfg = PlannerConfig(mode="sla", itl_target_s=0.02,
                        ttft_target_s=0.004, cooldown_s=0.0,
                        min_replicas=1, max_replicas=16, max_step=16)
    conn = _FakeConnector(replicas=1)
    p = _sla_planner(cfg, conn, pm)
    rps_cap = pm.max_rps_for_ttft(128, 0.004)
    p.observer.load = AggregateLoad(workers=1, active_seqs=2,
                                    mean_kv_usage=0.1, req_per_s=rps_cap * 5,
                                    mean_isl=128)
    applied = await p.tick()
    assert applied == math.ceil(5.0), applied  # 5x one replica's capacity


def test_sla_mode_requires_perf_model():
    try:
        Planner(None, "ns", "c", _FakeConnector(),
                PlannerConfig(mode="sla", itl_target_s=0.01))
        raise AssertionError("sla mode without perf model must raise")
    except ValueError:
        pass


# ----------------------------- observer -----------------------------------


async def test_observer_differentiates_counters_into_rates():
    """Cumulative requests/prompt-token counters become windowed arrival
    rate and mean ISL; counter resets (worker restart) are discarded."""
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex).start()
    obs = await LoadObserver(rt, "dynamo", "backend",
                             rate_window_s=30.0).start()
    subj = "load_metrics.dynamo.backend"
    # 20 requests of 256 tokens over the sample stream
    for i in range(5):
        await rt.event_plane.publish(subj, {
            "worker_id": 1, "active_seqs": 4, "kv_usage": 0.3,
            "requests_total": i * 5, "prompt_tokens_total": i * 5 * 256,
            "itl_ema_s": 0.004,
        })
        await asyncio.sleep(0.05)
    agg = obs.aggregate()
    assert agg.req_per_s > 0
    assert abs(agg.mean_isl - 256) < 1e-6
    assert abs(agg.mean_itl_s - 0.004) < 1e-9

    # reset: counters go backwards -> window discarded, no negative rates
    await rt.event_plane.publish(subj, {
        "worker_id": 1, "active_seqs": 0, "kv_usage": 0.0,
        "requests_total": 2, "prompt_tokens_total": 512,
    })
    await asyncio.sleep(0.05)
    assert obs.aggregate().req_per_s >= 0.0
    await obs.close()
    await rt.shutdown()


# ------------------------------- e2e --------------------------------------


async def test_sla_planner_e2e_profile_then_plan_mocker():
    """The full bootstrap chain on CPU: profile the mocker, build the perf
    model, and verify the SLA proposer sizes a fleet for a load the
    load-mode constant would get wrong."""
    engine = MockEngine(MockEngineArgs(
        base_step_s=0.001, prefill_s_per_token=0.00001,
        decode_s_per_seq=0.0005,
    ))
    try:
        prof = await profile_engine(engine, isls=(64,), osl=8,
                                    concurrencies=(1, 4, 16), rounds=2)
    finally:
        await engine.close()
    pm = PerfModel(prof)

    # target just above the c=4 ITL: capacity lands in [4, 16)
    target = pm.itl(4) * 1.2
    cap = pm.max_active_for_itl(target)
    assert 4.0 <= cap <= 16.0, (target, cap)

    cfg = PlannerConfig(mode="sla", itl_target_s=target, cooldown_s=0.0,
                        min_replicas=1, max_replicas=8, max_step=8)
    conn = _FakeConnector(replicas=1)
    p = _sla_planner(cfg, conn, pm)
    p.observer.load = AggregateLoad(workers=1, active_seqs=32,
                                    mean_kv_usage=0.2, mean_isl=64)
    applied = await p.tick()
    assert applied == min(8, math.ceil(32 / cap))


# ------------------------------- FPM --------------------------------------


async def test_fpm_observer_derives_itl_and_prefill_rate():
    """The FpmObserver turns per-program dispatch records into a fleet
    decode ITL (gap per fused step) and a prefill token rate."""
    from dynamo_tpu.planner.metrics import FpmObserver

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex).start()
    obs = await FpmObserver(rt, "dynamo", "backend").start()
    await asyncio.sleep(0.05)  # let the subscription attach
    subj = "fpm.dynamo.backend"
    # 16-step bursts dispatched every 64ms -> 4ms per token-step
    await rt.event_plane.publish(subj, {"worker_id": 1, "steps": [
        {"t": i * 0.064, "kind": "decode", "k": 16, "lanes": 8,
         "gap_s": 0.064} for i in range(10)
    ]})
    # two prefill programs ~0.1s apart totalling 4096 tokens
    await rt.event_plane.publish(subj, {"worker_id": 1, "steps": [
        {"t": 0.0, "kind": "prefill", "rows": 2, "tokens": 2048},
    ]})
    await asyncio.sleep(0.1)
    await rt.event_plane.publish(subj, {"worker_id": 1, "steps": [
        {"t": 0.1, "kind": "prefill", "rows": 2, "tokens": 2048},
    ]})
    await asyncio.sleep(0.05)
    assert abs(obs.decode_itl_s() - 0.004) < 1e-6
    rate = obs.prefill_tokens_per_s()
    assert rate > 0  # window spans the two publishes
    await obs.close()
    await rt.shutdown()


# real JAX engine in an async body: -O0 compiles dwarf the 200ms
# loop gate (see conftest); mocker-based tests here stay gated
@pytest.mark.allow_slow_callbacks
async def test_fpm_prefill_queue_depth_and_single_record_rate():
    """The chunked-prefill FPM fields flow end-to-end: records produced
    by the ENGINE's own _fpm_prefill (gap/tokens/queue_depth) publish
    onto the event plane and aggregate through the FpmObserver into
    the prefill token rate and chunk-queue depth; and a window holding
    a SINGLE prefill record reports a nonzero token rate
    (tokens/window_s floor) instead of 0.0."""
    import time as _time

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.planner.metrics import FpmObserver

    import jax.numpy as jnp
    tiny = LlamaConfig(name="tiny32", vocab_size=64, d_model=16,
                       n_layers=1, n_heads=2, n_kv_heads=1, head_dim=8,
                       ffn_dim=32, dtype=jnp.float32)
    eng = JaxEngine(EngineConfig(model_config=tiny, block_size=4,
                                 num_blocks=8, max_blocks_per_seq=4,
                                 max_num_seqs=2, prefill_buckets=(8,)))
    # two dispatch records in quick succession: the second carries a
    # real gap
    eng._fpm_prefill(rows=1, tokens=8, bucket=8, packed=True)
    _time.sleep(0.01)
    eng._fpm_prefill(rows=2, tokens=16, bucket=16, packed=True)
    recs = [r for r in eng.fpm if r["kind"] == "prefill"]
    await eng.close()
    assert recs[-1]["gap_s"] > 0.0 and recs[-1]["tokens"] == 16
    assert "queue_depth" in recs[-1]

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex).start()
    obs = await FpmObserver(rt, "dynamo", "backend",
                            window_s=20.0).start()
    await asyncio.sleep(0.05)
    subj = "fpm.dynamo.backend"
    await rt.event_plane.publish(subj, {"worker_id": 1, "steps": recs})
    # a second worker publishes a single-record window for the rate
    # fallback
    await rt.event_plane.publish(subj, {"worker_id": 2, "steps": [
        {"t": 5.0, "kind": "prefill", "rows": 1, "tokens": 4096,
         "gap_s": 0.5, "queue_depth": 3},
    ]})
    await asyncio.sleep(0.05)
    # worker 1's two records: 24 tokens over twice their 10 ms span
    assert obs.prefill_tokens_per_s() > 4096 / 20.0 + 24 / 0.1
    # worker 2's single record: rate floors at tokens/window_s, not 0.0
    assert obs.prefill_tokens_per_s() > 4096 / 20.0 - 1e-6
    # fleet chunk-queue depth sums each worker's latest record
    depth = obs.prefill_queue_depth()
    assert depth == recs[-1]["queue_depth"] + 3
    await obs.close()
    await rt.shutdown()


async def test_sla_planner_consumes_live_fpm_stream():
    """End-to-end: FPM records published on the event plane reach the SLA
    planner's perf-model regression (the correction moves toward the
    measured ITL, and the tick diagnostics carry fpm_itl_s)."""
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex).start()
    pm = PerfModel(synthetic_profile())
    pcfg = PlannerConfig(mode="sla", itl_target_s=0.007, cooldown_s=0.0,
                         min_replicas=1, max_replicas=8, max_step=8,
                         consume_fpm=True)
    conn = _FakeConnector(replicas=1)
    p = Planner(rt, "dynamo", "backend", conn, config=pcfg, perf_model=pm)
    await p.start()
    await asyncio.sleep(0.05)  # let the subscriptions attach
    try:
        # the model predicts ~6ms at c=4; the live fleet measures 12ms
        await rt.event_plane.publish("fpm.dynamo.backend", {
            "worker_id": 7, "steps": [
                {"t": i * 0.2, "kind": "decode", "k": 16, "lanes": 4,
                 "gap_s": 0.192} for i in range(8)
            ]})
        await rt.event_plane.publish(
            "load_metrics.dynamo.backend",
            {"worker_id": 7, "active_seqs": 4, "kv_usage": 0.2,
             "requests_total": 10, "prompt_tokens_total": 1280,
             "itl_ema_s": 0.001})  # the coarse EMA disagrees; FPM wins
        await asyncio.sleep(0.1)
        before = pm.itl_correction
        await p.tick()
        assert pm.itl_correction > before  # corrected UP toward 12ms
        assert p.fpm is not None
        assert abs(p.fpm.decode_itl_s() - 0.012) < 1e-6
    finally:
        await p.close()
        await rt.shutdown()
