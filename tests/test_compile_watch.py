"""Compile watchdog (obs/compile_watch.py): per-family compile
observations on the real JAX engine (each program lowered once, by its
compile), the closed key sets of the FPM records, mid-serving flight
dumps, worker gauge export, mocker parity, and the planner's
recompile-storm diag."""

import asyncio
import os
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real-JAX-engine tests: XLA compiles (seconds at tier-1's -O0) and
# device work run inside the async test bodies, so the conftest's 200ms
# event-loop slow-callback gate (DYN004's runtime twin) cannot hold
# here; mocker/frontend/router fleets keep it armed.
pytestmark = pytest.mark.allow_slow_callbacks


from dynamo_tpu import obs
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.obs.compile_watch import (
    COMPILE_KIND,
    CompileWatch,
    WatchedProgram,
    observe_compile_records,
)
from dynamo_tpu.planner.metrics import FpmWindow
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = LlamaConfig(name="tiny32", vocab_size=256, d_model=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                   dtype=jnp.float32)


def make_engine(**kw):
    defaults = dict(model_config=TINY, block_size=4, num_blocks=256,
                    max_blocks_per_seq=32, max_num_seqs=4,
                    prefill_buckets=(8, 16, 32, 64), seed=7)
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


async def serve_one(eng, i, n_prompt=32, max_tokens=4):
    req = PreprocessedRequest(
        token_ids=[(i * 37 + j) % 200 + 3 for j in range(n_prompt)],
        request_id=f"r{i}",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True))
    toks = []
    async for out in eng.generate(req):
        toks.extend(out.token_ids)
    return toks


# --------------------- WatchedProgram unit ---------------------------------


def test_watched_program_counts_shapes():
    watch = CompileWatch()
    wp = watch.wrap(jax.jit(lambda x: jnp.tanh(x) @ x.T), "toy",
                    tokens_of=lambda a: a[0].shape[0])
    wp(np.ones((8, 8), np.float32))
    assert watch.counts == {"toy": 1}
    wp(np.ones((8, 8), np.float32))  # steady state: no new compile
    assert watch.counts == {"toy": 1}
    wp(np.ones((16, 16), np.float32))  # new shape: a second executable
    assert watch.counts == {"toy": 2}
    # the event's `tokens` is the dispatch site's key for the variant
    assert [ev["tokens"] for ev in watch.events] == [8, 16]
    # None passes through untouched (config-gated program families)
    assert watch.wrap(None, "absent") is None


def test_watch_sink_and_serving_flag():
    recs = []
    serving = {"on": False}
    watch = CompileWatch(sink=recs.append, serving=lambda: serving["on"])
    wp = watch.wrap(jax.jit(lambda x: x * 2), "toy")
    wp(np.ones((4,), np.float32))
    serving["on"] = True
    wp(np.ones((8,), np.float32))
    assert [r["serving"] for r in recs] == [False, True]
    assert all(r["kind"] == COMPILE_KIND and r["seconds"] >= 0.0
               for r in recs)
    assert watch.serving_compiles == 1


def test_unwatchable_jit_program_is_refused():
    """A jit product without the compile counter must fail at wrap time
    (else "zero mid-serving compiles" would read 0 for ever); a plain
    callable — a test stand-in — passes through unwatched."""
    watch = CompileWatch()

    class LowersButUncounted:
        def lower(self, *a):
            raise AssertionError("never reached")

        def __call__(self, *a):
            return a

    with pytest.raises(TypeError, match="_cache_size"):
        watch.wrap(LowersButUncounted(), "decode")
    stand_in = watch.wrap(lambda x: x + 1, "toy")
    assert stand_in(1) == 2 and watch.counts == {}


# --------------------- JAX engine end-to-end --------------------------------


async def test_engine_compile_observation_per_program_family(tmp_path):
    """Serving one request must leave >=1 compile observation for every
    program family it dispatched (packed prefill + fused decode), each
    keyed by its token bucket, a compile span on the engine track, and — having landed mid-serving with no warmup — a flight
    dump."""
    tr = obs.Tracer(out_path=str(tmp_path / "t.json")).install()
    try:
        eng = make_engine()
        toks = await serve_one(eng, 0)
        assert len(toks) == 4
        counts = eng.compile_watch.counts
        assert counts.get("prefill_packed", 0) >= 1, counts
        assert (counts.get("decode_multi", 0) >= 1
                or counts.get("decode", 0) >= 1), counts
        comp = [r for r in eng.fpm if r.get("kind") == COMPILE_KIND]
        families = {r["family"] for r in comp}
        assert {"prefill_packed"} <= families
        assert all(r["tokens"] in (8, 16, 32, 64) for r in comp
                   if r["family"] == "prefill_packed")
        # compile spans landed on the engine's logical track
        spans = [s for s in tr.spans if s[0] == COMPILE_KIND]
        assert spans and all(s[3].startswith("sched:") for s in spans)
        # mid-serving (no warmup, request in flight) => flight recorder
        assert any("compile-" in p for p in tr.flight_dumps)
        await eng.close()
    finally:
        tr.uninstall()


async def test_warmup_compiles_are_not_serving(tmp_path):
    """warmup_decode's compiles happen with no active sequences: they
    must be counted but NOT flagged mid-serving (no flight dump)."""
    tr = obs.Tracer(out_path=str(tmp_path / "t.json")).install()
    try:
        eng = make_engine()
        eng.warmup_decode()
        comp = [r for r in eng.fpm if r.get("kind") == COMPILE_KIND]
        assert comp, "warmup compiled nothing?"
        assert all(not r["serving"] for r in comp)
        assert not any("compile-" in p for p in tr.flight_dumps)
        await eng.close()
    finally:
        tr.uninstall()


class _CountsLower:
    """Stand-in for a watched jit product that counts `lower` calls (a
    dispatch goes through the product's C++ call path, never through
    this attribute; JAX caches the trace, so counting calls of the
    family function would not see a second lowering)."""

    def __init__(self, fn, family, calls):
        self.fn, self.family, self.calls = fn, family, calls

    def __call__(self, *args):
        return self.fn(*args)

    def _cache_size(self):
        return self.fn._cache_size()

    def lower(self, *args, **kw):
        self.calls.append(self.family)
        return self.fn.lower(*args, **kw)


def _watched(eng):
    for v in vars(eng).values():
        for wp in (v.values() if isinstance(v, dict) else (v,)):
            if isinstance(wp, WatchedProgram):
                yield wp


async def test_a_compile_lowers_its_program_once():
    """Warm-up and a first request compile every program they dispatch;
    nothing lowers a watched program a second time beside its compile."""
    eng = make_engine()
    calls = []
    n = 0
    for wp in _watched(eng):
        wp.fn = _CountsLower(wp.fn, wp.family, calls)
        n += 1
    assert n >= 4, "found too few watched programs to stand in for"
    eng.warmup_decode()
    await serve_one(eng, 0)
    counts = dict(eng.compile_watch.counts)
    await eng.close()
    assert counts.get("prefill_packed", 0) >= 1, counts
    assert counts.get("decode_multi", 0) >= 1, counts
    assert calls == [], f"lowered again after their compile: {calls}"


FPM_KEYS = {
    "prefill": {"t", "kind", "rows", "tokens", "bucket", "packed",
                "gap_s", "queue_depth"},
    "decode": {"t", "kind", "k", "lanes", "gap_s"},
    "spec_verify": {"t", "kind", "lanes", "proposed", "accepted",
                    "tokens", "gap_s"},
    COMPILE_KIND: {"t", "kind", "family", "seconds", "tokens", "serving"},
}


@pytest.fixture(scope="module")
def spec_engine_records():
    """The FPM ring of one real engine that prefilled, decoded and
    speculated."""
    async def run():
        eng = make_engine(spec_decode="ngram", spec_k=2)
        # the n-gram proposer engages on repetition in prompt + output.
        # The first probe (1 generated token) hits only if that token
        # already occurs in the prompt — which depends on the tiny
        # random model's greedy continuation, and that moved with the
        # JAX version (it now opens with a token outside the prompt).  A
        # miss re-probes after SPEC_PROBE_MIN = 8 more tokens, so the
        # request must outlive that probe plus the decode bursts already
        # in flight: 48 tokens, by which point a greedy tiny-model
        # stream has entered a cycle.
        req = PreprocessedRequest(
            token_ids=[5, 6, 7, 8] * 8, request_id="rep",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=48, ignore_eos=True))
        async for _ in eng.generate(req):
            pass
        for i in range(2):
            await serve_one(eng, i + 10)
        recs = list(eng.fpm)
        await eng.close()
        return recs

    return asyncio.run(run())


def test_decode_and_spec_records_carry_lanes_and_acceptance(
        spec_engine_records):
    """Decode records carry (lanes, k, gap) — what FpmWindow's ITL and
    token rate and the benchmark's `decode_step_ms` read; spec-verify
    records carry proposed/accepted for the acceptance rate."""
    recs = spec_engine_records
    dec = [r for r in recs if r.get("kind") == "decode"]
    assert dec and all(r["lanes"] >= 1 and r["k"] >= 1
                       and 0.0 <= r["gap_s"] <= 1.0 for r in dec)
    spec = [r for r in recs if r.get("kind") == "spec_verify"]
    assert spec, "speculation never engaged"
    assert all(0 <= r["accepted"] <= r["proposed"] for r in spec)
    fw = FpmWindow()
    for r in recs:
        fw.add(1, r)
    assert fw.decode_tokens_per_s() > 0.0
    assert fw.spec_acceptance() is not None


@pytest.mark.parametrize("kind", sorted(FPM_KEYS))
def test_fpm_record_keys_are_closed(spec_engine_records, kind):
    """A real engine's records carry exactly the documented keys: a key
    added for one reader is a key every consumer of the stream (the
    planner, the workers' gauges, `benchmark/`) has to know about."""
    recs = [r for r in spec_engine_records if r.get("kind") == kind]
    assert recs, f"the engine emitted no {kind} record"
    for r in recs:
        assert set(r) == FPM_KEYS[kind], r


async def test_guided_topk_compile_is_watched():
    """The guided top-M program's 8-14s mid-serving fork is the compile
    the watchdog exists for: _guided_step's lazy init must go through
    the wrapped _topk_jit, not a raw jax.jit that escapes observation."""
    eng = make_engine(max_num_seqs=2)
    schema = {"type": "object",
              "properties": {"unit": {"enum": ["c", "f"]}}}
    req = PreprocessedRequest(
        token_ids=list(range(7, 19)), request_id="g1",
        sampling=SamplingOptions(temperature=0.0, guided_json=schema),
        stop=StopConditions(max_tokens=24))
    async for _ in eng.generate(req):
        pass
    assert eng.compile_watch.counts.get("decode_topk", 0) >= 1, \
        eng.compile_watch.counts
    await eng.close()


# --------------------- engine KV occupancy ----------------------------------


async def test_engine_kv_occupancy_tiers():
    eng = make_engine(host_cache_blocks=8)
    occ0 = eng.kv_occupancy()
    assert occ0["g1"]["capacity"] == 255  # block 0 is the garbage block
    assert occ0["g1"]["used"] == 0
    assert "g2" in occ0 and occ0["g2"]["capacity"] == 8
    await serve_one(eng, 0)
    occ = eng.kv_occupancy()
    assert occ["g1"]["used"] > 0
    assert occ["g1"]["used"] + occ["g1"]["free"] == occ["g1"]["capacity"]
    await eng.close()


# --------------------- mocker parity ----------------------------------------


async def test_mock_engine_emits_compile_and_dispatch_records():
    from dynamo_tpu.mocker import MockEngine, MockEngineArgs

    eng = MockEngine(MockEngineArgs(
        model_name="m", block_size=4, base_step_s=0.0005))
    # two sequential requests: the second's prefill dispatch has a
    # plausible (>0) gap
    for i in (1, 2):
        req = PreprocessedRequest(
            token_ids=list(range(3, 40)), request_id=f"r{i}",
            stop=StopConditions(max_tokens=24, ignore_eos=True))
        async for _ in eng.generate(req):
            pass
    await eng.close()
    recs = list(eng.fpm)
    comp = [r for r in recs if r.get("kind") == COMPILE_KIND]
    assert {r["family"] for r in comp} == {"prefill", "decode"}
    assert all(not r["serving"] for r in comp)  # first-dispatch = warmup
    assert [r["family"] for r in comp].count("prefill") == 1  # once each
    dec = [r for r in recs if r.get("kind") == "decode"]
    pre = [r for r in recs if r.get("kind") == "prefill"]
    assert dec and pre
    # the JAX engine's record shapes, minus what only it knows
    assert all(set(r) == FPM_KEYS[COMPILE_KIND] for r in comp)
    assert all(set(r) == FPM_KEYS["decode"] for r in dec)
    assert all(set(r) == FPM_KEYS["prefill"] - {"packed"} for r in pre)
    fw = FpmWindow()
    for r in recs:
        fw.add(1, r)
    assert fw.decode_tokens_per_s() > 0.0
    assert fw.prefill_tokens_per_s() > 0.0


async def test_mock_engine_recompile_storm_records():
    from dynamo_tpu.mocker import MockEngine, MockEngineArgs

    eng = MockEngine(MockEngineArgs(
        model_name="m", block_size=4, base_step_s=0.0,
        sim_recompile_every=5))
    req = PreprocessedRequest(
        token_ids=list(range(3, 20)), request_id="r1",
        stop=StopConditions(max_tokens=30, ignore_eos=True))
    async for _ in eng.generate(req):
        pass
    await eng.close()
    storm = [r for r in eng.fpm
             if r.get("kind") == COMPILE_KIND and r.get("serving")]
    assert storm, "sim_recompile_every emitted no mid-serving compiles"


# --------------------- worker /metrics export -------------------------------


async def test_mocker_worker_exports_compile_and_occupancy_gauges():
    from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    rt = await DistributedRuntime(
        config=RuntimeConfig(discovery_backend="mem",
                             event_plane="inproc"),
        cluster_id=uuid.uuid4().hex).start()
    worker = await MockerWorker(rt, MockEngineArgs(
        model_name="roof-model", block_size=4,
        base_step_s=0.0005)).start()
    client = await (rt.namespace("dynamo").component("mocker")
                    .endpoint("generate").client()).start()
    await client.wait_for_instances()
    req = PreprocessedRequest(
        token_ids=list(range(3, 40)), request_id="r1",
        stop=StopConditions(max_tokens=24, ignore_eos=True))
    async for _ in client.generate(req.to_dict()):
        pass
    text = ""
    for _ in range(40):  # wait out a load-loop tick
        await asyncio.sleep(0.1)
        text = rt.metrics.render().decode()
        if "dynamo_engine_compile_seconds" in text \
                and "dynamo_engine_kv_blocks_used" in text:
            break
    assert 'dynamo_engine_compile_seconds_count{' in text
    assert 'family="prefill"' in text and 'family="decode"' in text
    assert "dynamo_engine_compiles_total" in text
    assert "dynamo_engine_decode_tokens_per_s" in text
    assert 'dynamo_engine_kv_blocks_used{' in text
    assert 'tier="g1"' in text
    assert "dynamo_engine_kv_blocks_capacity" in text
    await client.close()
    await worker.close()
    await rt.shutdown()


def test_observe_compile_records_histogram_math():
    from dynamo_tpu.runtime.metrics import MetricsHierarchy

    m = MetricsHierarchy(component="backend")
    observe_compile_records(m, [
        {"kind": COMPILE_KIND, "family": "decode", "seconds": 12.0,
         "serving": True},
        {"kind": COMPILE_KIND, "family": "decode", "seconds": 0.5},
        {"kind": "decode", "gap_s": 0.01},  # non-compile: ignored
    ])
    text = m.render().decode()
    # 12s must land in a real bucket, not only +Inf (buckets reach 60s)
    assert 'dynamo_engine_compile_seconds_bucket{' in text
    assert 'le="20.0"' in text
    for line in text.splitlines():
        if line.startswith("dynamo_engine_compiles_total{"):
            assert float(line.rsplit(" ", 1)[1]) == 2.0
        if line.startswith("dynamo_engine_serving_compiles_total{"):
            assert float(line.rsplit(" ", 1)[1]) == 1.0


# --------------------- planner storm diag -----------------------------------


def test_fpm_window_compile_stats_and_planner_storm_diag():
    fw = FpmWindow()
    fw.add(1, {"kind": COMPILE_KIND, "family": "decode", "seconds": 9.0,
               "serving": True})
    fw.add(1, {"kind": COMPILE_KIND, "family": "prefill_packed",
               "seconds": 2.0, "serving": False})
    stats = fw.compile_stats()
    assert stats["total"] == 2 and stats["serving"] == 1
    assert stats["families"]["decode"]["seconds"] == 9.0

    # the SLA tick diag surfaces the storm (planner/_propose_sla)
    import test_sla_planner as tsp
    from dynamo_tpu.planner.metrics import AggregateLoad
    from dynamo_tpu.planner.perf_model import PerfModel
    from dynamo_tpu.planner.planner import PlannerConfig

    p = tsp._sla_planner(
        PlannerConfig(mode="sla", itl_target_s=0.01),
        tsp._FakeConnector(), PerfModel(tsp.synthetic_profile()))
    p.fpm = fw
    diag = {}
    p._propose_sla(AggregateLoad(workers=1, active_seqs=4,
                                 mean_kv_usage=0.1, mean_isl=128),
                   4.0, diag)
    assert diag["compiles"]["decode"]["count"] == 1
    assert diag["recompile_storm"]["serving_compiles"] == 1
    assert "decode" in diag["recompile_storm"]["families"]


# --------------------- KVBM manager occupancy -------------------------------


def test_kvbm_manager_occupancy(tmp_path):
    from dynamo_tpu.kvbm.manager import TieredKvManager

    mgr = TieredKvManager(host_blocks=2, disk_dir=str(tmp_path),
                          disk_blocks=4)
    blk = (np.ones((2, 4), np.float16), np.ones((2, 4), np.float16))
    for h in (11, 22, 33):  # 3 blocks into a 2-block G2: one demotes
        mgr.offload(h, *blk)
    occ = mgr.occupancy()
    assert occ["g2"]["used"] == 2 and occ["g2"]["capacity"] == 2
    assert occ["g2"]["free"] == 0
    assert occ["g3"]["used"] == 1 and occ["g3"]["capacity"] == 4
    mgr.close()
