"""KV router tests: indexer semantics (Python + C++ cross-check), selector,
slot manager, and KV-routing e2e against mocker workers."""

import asyncio
import os
import random
import uuid

import pytest

from dynamo_tpu.router.indexer import PyKvIndexer
from dynamo_tpu.router.selector import (
    DefaultWorkerSelector,
    KvRouterConfig,
    WorkerState,
)
from dynamo_tpu.router.sequences import ActiveSequences


def H(i: int) -> int:
    return (i << 70) | (i * 2654435761 + 17)


def make_indexers():
    out = [PyKvIndexer()]
    try:
        from dynamo_tpu.router.native_indexer import NativeKvIndexer

        out.append(NativeKvIndexer())
    except ImportError:
        pass
    return out


def test_native_indexer_available():
    """The C++ indexer must be built in this repo (make -C native)."""
    from dynamo_tpu.router.native_indexer import NativeKvIndexer  # noqa: F401


def test_indexer_semantics_match():
    """Python and C++ indexers agree on randomized event sequences."""
    indexers = make_indexers()
    assert len(indexers) == 2, "native indexer missing"
    rng = random.Random(42)
    workers = [11, 22, 33, 44]
    universe = [H(i) for i in range(200)]
    for step in range(300):
        op = rng.random()
        w = rng.choice(workers)
        if op < 0.6:
            start = rng.randrange(0, 150)
            chunk = universe[start : start + rng.randrange(1, 20)]
            for ix in indexers:
                ix.apply_stored(w, chunk)
        elif op < 0.9:
            start = rng.randrange(0, 180)
            chunk = universe[start : start + rng.randrange(1, 10)]
            for ix in indexers:
                ix.apply_removed(w, chunk)
        else:
            for ix in indexers:
                ix.remove_worker(w)
        if step % 10 == 0:
            q_start = rng.randrange(0, 100)
            q = universe[q_start : q_start + rng.randrange(1, 40)]
            results = [ix.find_matches(q) for ix in indexers]
            assert results[0] == results[1], f"divergence at step {step}"
    assert indexers[0].num_blocks == indexers[1].num_blocks


def test_indexer_prefix_walk():
    ix = PyKvIndexer()
    hs = [H(i) for i in range(8)]
    ix.apply_stored(1, hs[:6])
    ix.apply_stored(2, hs[:3])
    ix.apply_stored(3, hs[2:5])  # no prefix from 0 -> no overlap
    m = ix.find_matches(hs)
    assert m == {1: 6, 2: 3}
    # a hole stops everyone
    ix.apply_removed(1, [hs[1]])
    m = ix.find_matches(hs)
    assert m == {1: 1, 2: 3}


def test_selector_prefers_overlap_and_load():
    sel = DefaultWorkerSelector(KvRouterConfig(temperature=0.0))
    states = {1: WorkerState(active_blocks=0), 2: WorkerState(active_blocks=0)}
    # worker 2 has 8 of 10 blocks cached -> cheaper
    assert sel.select([1, 2], 10, {2: 8}, states) == 2
    # ...unless it's heavily loaded
    states[2].active_blocks = 100
    assert sel.select([1, 2], 10, {2: 8}, states) == 1
    # avoid set wins over cost
    assert sel.select([1, 2], 10, {2: 8}, states, avoid={1}) == 2
    # busy-KV threshold pushes a worker to last resort
    states[2].active_blocks = 0
    states[2].kv_usage = 0.99
    assert sel.select([1, 2], 10, {2: 8}, states) == 1


def test_active_sequences_accounting():
    from dynamo_tpu.router.sequences import PREFILL_WEIGHT as W

    seqs = ActiveSequences()
    seqs.add_request("r1", 1, blocks=10, overlap_blocks=4)
    seqs.add_request("r2", 1, blocks=5, overlap_blocks=0)
    seqs.add_request("r3", 2, blocks=7, overlap_blocks=7)
    # worker 1: decode 15, pending prefill 6+5; worker 2: full overlap
    assert seqs.active_blocks(1) == 15 + W * 11
    assert seqs.active_blocks(2) == 7
    assert seqs.active_requests(1) == 2
    # prefill completion drops the prefill charge, keeps the KV charge
    seqs.mark_prefill_completed("r1")
    assert seqs.active_blocks(1) == 15 + W * 5
    seqs.mark_prefill_completed("r1")  # idempotent
    assert seqs.active_blocks(1) == 15 + W * 5
    seqs.free("r1")
    assert seqs.active_blocks(1) == 5 + W * 5
    seqs.free("r2")  # freed before prefill done: both charges released
    assert seqs.active_blocks(1) == 0
    seqs.remove_worker(2)
    assert seqs.active_blocks(2) == 0
    assert seqs.active_requests() == 0


# ---------------------------------------------------------------------------
# e2e: KV-aware routing across mocker workers
# ---------------------------------------------------------------------------


async def test_kv_routing_e2e_prefers_warm_worker():
    """Warm a prefix on one worker; KV-routed repeats must go there."""
    from dynamo_tpu.frontend import ModelManager, ModelWatcher
    from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
    from dynamo_tpu.protocols import PreprocessedRequest, StopConditions
    from dynamo_tpu.router.kv_router import make_kv_route_factory
    from dynamo_tpu.runtime import (
        DistributedRuntime,
        RouterMode,
        RuntimeConfig,
    )

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex
    ).start()
    args = MockEngineArgs(model_name="m", block_size=4, base_step_s=0.0005,
                          prefill_s_per_token=0.0, decode_s_per_seq=0.0)
    w1 = await MockerWorker(rt, args).start()
    w2 = await MockerWorker(rt, args).start()

    manager = ModelManager()
    watcher = await ModelWatcher(
        rt, manager, router_mode=RouterMode.KV,
        make_route=make_kv_route_factory(rt),
    ).start()
    for _ in range(100):
        if manager.get("m"):
            break
        await asyncio.sleep(0.02)
    pipeline = manager.get("m")
    await pipeline.client.wait_for_instances()
    for _ in range(100):
        if len(pipeline.client.instances) == 2:
            break
        await asyncio.sleep(0.02)

    prompt = list(range(40))  # 10 blocks

    def req(rid):
        return PreprocessedRequest(
            token_ids=prompt, request_id=rid,
            stop=StopConditions(max_tokens=2, ignore_eos=True),
        )

    # warm worker 1 directly
    async for _ in pipeline.client.generate(
        req("warm").to_dict(), instance_id=w1.served.instance_id
    ):
        pass
    # let the KV events land in the router's indexer
    router = pipeline.migration.route
    for _ in range(100):
        if router.indexer.worker_block_count(w1.served.instance_id) >= 10:
            break
        await asyncio.sleep(0.02)
    assert router.indexer.worker_block_count(w1.served.instance_id) >= 10

    # KV-routed requests with the same prefix must pick the warm worker
    for i in range(4):
        picked = await router.pick(req(f"route{i}"))
        router.complete(f"route{i}")
        assert picked == w1.served.instance_id

    # a totally different prompt should balance by load, not stick to w1
    cold = PreprocessedRequest(
        token_ids=list(range(500, 540)), request_id="cold",
        stop=StopConditions(max_tokens=2, ignore_eos=True),
    )
    # load w1 with fake in-flight requests
    for i in range(4):
        router.sequences.add_request(f"fake{i}", w1.served.instance_id, 20, 0)
    picked = await router.pick(cold)
    assert picked == w2.served.instance_id

    await watcher.close()
    await w1.close()
    await w2.close()
    await rt.shutdown()


async def test_kv_router_event_gap_recovery():
    """Drop an event on the floor; the router recovers via replay endpoint."""
    from dynamo_tpu.protocols import PreprocessedRequest
    from dynamo_tpu.router.events import KvEventPublisher
    from dynamo_tpu.router.kv_router import KvRouter
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex
    ).start()
    comp = rt.namespace("ns").component("w")
    pub = KvEventPublisher(rt, "ns", "w", worker_id=7)
    await comp.endpoint("kv_events_replay").serve_endpoint(
        pub.replay_handler, instance_id=7
    )
    gen_client = await comp.endpoint("generate").client().start()
    router = await KvRouter(rt, "ns", "w", gen_client, block_size=4).start()
    await asyncio.sleep(0.05)

    hs = [H(i) for i in range(10)]
    await pub.stored(hs[:3])          # event 0: delivered
    ev1 = pub._mk("stored", hs[3:6], None, "g1")  # event 1: NOT published
    await pub.stored(hs[6:10])        # event 2: delivered -> gap detected
    for _ in range(100):
        if router.indexer.worker_block_count(7) >= 10:
            break
        await asyncio.sleep(0.02)
    assert router.indexer.worker_block_count(7) == 10
    m = router.indexer.find_matches(hs)
    assert m == {7: 10}

    await router.close()
    await gen_client.close()
    await rt.shutdown()


async def test_kv_router_late_join_full_replay():
    """A router that subscribes AFTER a worker has been publishing must
    replay events 0..N-1 on its first observed event, or blocks stored
    before subscription stay invisible to routing (ADVICE r1, medium)."""
    from dynamo_tpu.router.events import KvEventPublisher
    from dynamo_tpu.router.kv_router import KvRouter
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex
    ).start()
    comp = rt.namespace("ns").component("w")
    pub = KvEventPublisher(rt, "ns", "w", worker_id=9)
    await comp.endpoint("kv_events_replay").serve_endpoint(
        pub.replay_handler, instance_id=9
    )
    hs = [H(i) for i in range(8)]
    # events 0 and 1 happen before any router exists
    await pub.stored(hs[:3])
    await pub.stored(hs[3:5])
    await asyncio.sleep(0.05)

    gen_client = await comp.endpoint("generate").client().start()
    router = await KvRouter(rt, "ns", "w", gen_client, block_size=4).start()
    await asyncio.sleep(0.05)
    # first event the late router sees has event_id=2 -> full replay from 0
    await pub.stored(hs[5:8])
    for _ in range(100):
        if router.indexer.worker_block_count(9) >= 8:
            break
        await asyncio.sleep(0.02)
    assert router.indexer.worker_block_count(9) == 8
    assert router.indexer.find_matches(hs) == {9: 8}

    await router.close()
    await gen_client.close()
    await rt.shutdown()


async def test_router_replica_sync_converges():
    """Two router replicas over one fleet: each router's slot manager must
    reflect the OTHER router's in-flight picks (add / prefill_done / free),
    or multi-frontend deployments dogpile workers."""
    from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
    from dynamo_tpu.protocols import PreprocessedRequest, StopConditions
    from dynamo_tpu.router.kv_router import KvRouter
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex
    ).start()
    args = MockEngineArgs(model_name="m", block_size=4, base_step_s=0.0005,
                          prefill_s_per_token=0.0, decode_s_per_seq=0.0)
    w1 = await MockerWorker(rt, args).start()
    wid = w1.served.instance_id
    comp = rt.namespace("dynamo").component("mocker")
    cA = await comp.endpoint("generate").client().start()
    cB = await comp.endpoint("generate").client().start()
    rA = await KvRouter(rt, "dynamo", "mocker", cA, block_size=4).start()
    rB = await KvRouter(rt, "dynamo", "mocker", cB, block_size=4).start()
    await cA.wait_for_instances()
    await cB.wait_for_instances()

    req = PreprocessedRequest(
        token_ids=list(range(40)), request_id="r1",
        stop=StopConditions(max_tokens=8, ignore_eos=True),
    )
    picked = await rA.pick(req)
    assert picked == wid
    # B must learn about A's in-flight request via replica sync
    for _ in range(100):
        if rB.sequences.active_blocks(wid) > 0:
            break
        await asyncio.sleep(0.02)
    assert rB.sequences.active_blocks(wid) == rA.sequences.active_blocks(wid)
    assert rB.sequences.active_requests(wid) == 1

    rA.mark_prefill_completed("r1")
    for _ in range(100):
        if rB.sequences.active_blocks(wid) == rA.sequences.active_blocks(wid) \
                and rB.sequences._reqs.get(f"r1@{rA.sync.router_id}") is not None \
                and rB.sequences._reqs[f"r1@{rA.sync.router_id}"].prefill_done:
            break
        await asyncio.sleep(0.02)
    assert rB.sequences._reqs[f"r1@{rA.sync.router_id}"].prefill_done

    rA.complete("r1")
    for _ in range(100):
        if rB.sequences.active_requests(wid) == 0:
            break
        await asyncio.sleep(0.02)
    assert rB.sequences.active_blocks(wid) == 0.0

    await rA.close()
    await rB.close()
    await cA.close()
    await cB.close()
    await w1.close()
    await rt.shutdown()


def test_selector_tiebreak_not_herded():
    """Independent selector replicas must not break cost ties identically
    (shared constant seed == thundering herd across frontends)."""
    workers = list(range(8))
    seqs = []
    for _ in range(2):
        sel = DefaultWorkerSelector(KvRouterConfig())
        seqs.append([
            sel.select(workers, 4, {}, {}) for _ in range(64)
        ])
    assert seqs[0] != seqs[1], "replicas picked identical tie-break sequences"


async def test_dp_ranks_are_distinct_routing_targets():
    """One worker with dp_size=2: the router must treat each rank as its
    own target — a warmed prefix routes repeats to the SAME rank (overlap
    credit is per rank, the caches are disjoint), and a cold request under
    load lands on the other rank (ref WorkerWithDpRank, selector.rs:33)."""
    from dynamo_tpu.mocker import MockEngineArgs, MockerWorker
    from dynamo_tpu.protocols import PreprocessedRequest, StopConditions
    from dynamo_tpu.router.kv_router import KvRouter
    from dynamo_tpu.router.targets import target_id
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    rt = await DistributedRuntime(
        config=cfg, cluster_id=uuid.uuid4().hex
    ).start()
    args = MockEngineArgs(model_name="m", block_size=4, dp_size=2,
                          base_step_s=0.0005, prefill_s_per_token=0.0,
                          decode_s_per_seq=0.0)
    w = await MockerWorker(rt, args).start()
    wid = w.served.instance_id
    comp = rt.namespace("dynamo").component("mocker")
    client = await comp.endpoint("generate").client().start()
    # seeded tie-break: cold requests are exact cost TIES between the
    # two ranks, and the default OS-entropy seed made "all 6 land on
    # one rank" a ~3% full-run flake — a fixed seed keeps the spread
    # assertion deterministic (KvRouterConfig documents test seeding)
    router = await KvRouter(rt, "dynamo", "mocker", client,
                            block_size=4,
                            config=KvRouterConfig(seed=7)).start()
    await client.wait_for_instances()
    # both ranks visible as targets (load metrics carry per-rank state)
    for _ in range(200):
        if len(router.targets.targets_of(wid)) == 2:
            break
        await asyncio.sleep(0.02)
    assert len(router.targets.targets_of(wid)) == 2

    async def serve(req):
        picked = await router.pick(req)
        assert picked == wid
        async for item in client.generate(req.to_dict(),
                                          instance_id=picked):
            pass
        router.complete(req.request_id)
        return req.dp_rank

    # warm a prefix: whatever rank it lands on must attract the repeat
    prompt = list(range(64))
    r1 = await serve(PreprocessedRequest(
        token_ids=prompt, request_id="a1",
        stop=StopConditions(max_tokens=4, ignore_eos=True)))
    # wait for the stored events of that rank's engine to index
    tid = target_id(wid, r1)
    for _ in range(200):
        if router.indexer.find_matches(
                __import__("dynamo_tpu.tokens", fromlist=["x"])
                .compute_block_hashes_for_request(prompt, 4)).get(tid):
            break
        await asyncio.sleep(0.02)
    r2 = await serve(PreprocessedRequest(
        token_ids=prompt, request_id="a2",
        stop=StopConditions(max_tokens=4, ignore_eos=True)))
    assert r2 == r1, "repeat did not follow its rank's warm prefix"

    # distinct prompts spread across ranks (load balancing over targets)
    ranks = set()
    for i in range(6):
        ranks.add(await serve(PreprocessedRequest(
            token_ids=list(range(100 + 40 * i, 140 + 40 * i)),
            request_id=f"b{i}",
            stop=StopConditions(max_tokens=4, ignore_eos=True))))
    assert ranks == {0, 1}, f"cold requests never spread: {ranks}"

    # each rank's engine actually served requests (the worker dispatched
    # by request.dp_rank)
    served = [e.metrics["requests"] for e in w.engines]
    assert all(n > 0 for n in served), served

    await router.close()
    await client.close()
    await w.close()
    await rt.shutdown()


# ----------------------- fleet prefix cache: tiered index -----------------------


def make_tiered_indexers():
    from dynamo_tpu.router.tiered_index import TieredKvIndexer

    return [TieredKvIndexer(base) for base in make_indexers()]


def test_tiered_indexer_parity_on_tier_ingestion():
    """Python- and C++-backed tiered indexers agree on randomized
    PER-TIER event streams: the union view (base membership is derived
    from local-tier residency) and the tiered overlap query both match,
    so the py/native parity the classic tests pin carries over to the
    fleet-prefix-cache ingestion path."""
    idx = make_tiered_indexers()
    assert len(idx) == 2, "native indexer missing"
    rng = random.Random(7)
    workers = [11, 22, 33]
    universe = [H(i) for i in range(160)]
    tiers = ["g1", "g1", "g2", "g3", "g4"]
    for step in range(400):
        op = rng.random()
        w = rng.choice(workers)
        tier = rng.choice(tiers)
        if op < 0.55:
            start = rng.randrange(0, 120)
            chunk = universe[start:start + rng.randrange(1, 16)]
            for ix in idx:
                ix.apply_stored(w, chunk, tier=tier)
        elif op < 0.85:
            start = rng.randrange(0, 150)
            chunk = universe[start:start + rng.randrange(1, 8)]
            for ix in idx:
                ix.apply_removed(w, chunk, tier=tier)
        elif op < 0.95:
            for ix in idx:
                ix.remove_worker(w)
        else:
            for ix in idx:
                ix.clear_worker(w)
        if step % 10 == 0:
            start = rng.randrange(0, 100)
            q = universe[start:start + rng.randrange(1, 40)]
            assert idx[0].find_matches(q) == idx[1].find_matches(q), \
                f"union divergence at step {step}"
            assert (idx[0].find_matches_tiered(q, workers)
                    == idx[1].find_matches_tiered(q, workers)), \
                f"tiered divergence at step {step}"
    assert idx[0].g4_blocks == idx[1].g4_blocks


def test_tiered_index_g4_scores_for_every_candidate():
    """G4 ownership is fleet-wide: the shared store's blobs extend ANY
    candidate's leading run, the sweeper need not be the spiller to
    remove one, and blobs outlive their spiller (remove_worker) but not
    a resync clear of the worker they are attributed to."""
    from dynamo_tpu.router.tiered_index import TieredKvIndexer

    ix = TieredKvIndexer(PyKvIndexer())
    hs = [H(i) for i in range(5)]
    ix.apply_stored(1, hs[:2], tier="g1")
    ix.apply_stored(1, hs[:4], tier="g4")  # spilled copies of the head
    m = ix.find_matches_tiered(hs, [1, 2, 3])
    assert m[1] == {"g1": 2, "g4": 2}  # own g1 is the cheaper source
    assert m[2] == {"g4": 4} and m[3] == {"g4": 4}
    # the union view stays local-tiers-only: only the spiller appears
    assert ix.find_matches(hs) == {1: 2}
    # a sweeper that never stored the blob removes it fleet-wide
    ix.apply_removed(99, [hs[2]], tier="g4")
    assert ix.find_matches_tiered(hs, [2])[2] == {"g4": 2}
    # the spiller dying keeps its G4 blobs onboardable...
    ix.remove_worker(1)
    assert ix.find_matches_tiered(hs, [2])[2] == {"g4": 2}
    # ...but a resync clear drops the worker's attributed blobs
    ix2 = TieredKvIndexer(PyKvIndexer())
    ix2.apply_stored(1, hs[:4], tier="g4")
    ix2.clear_worker(1)
    assert ix2.g4_blocks == 0
    assert ix2.find_matches_tiered(hs, [2]) == {}


def test_spilled_block_no_longer_free_g1_hit():
    """Regression for the tier-blind overlap inflation: a block the
    worker offloaded out of HBM used to keep scoring as a FREE G1 hit
    for its spiller (the union index never saw the demotion, so routing
    chased overlap that would be re-onboarded at real cost).  With
    per-tier events it must downgrade to a priced g4 hit, and the
    selector must prefer genuine HBM residency on another worker."""
    from dynamo_tpu.router.tiered_index import TieredKvIndexer

    ix = TieredKvIndexer(PyKvIndexer())
    hs = [H(i) for i in range(8)]
    # worker 1 computed the prefix, then demoted all of it down to G4
    ix.apply_stored(1, hs, tier="g1")
    ix.apply_stored(1, hs, tier="g4")
    ix.apply_removed(1, hs, tier="g1")
    # worker 2 holds the same prefix hot in HBM
    ix.apply_stored(2, hs, tier="g1")
    tiers = ix.find_matches_tiered(hs, [1, 2])
    assert tiers[1] == {"g4": 8}, "spilled run still counted as g1"
    assert tiers[2] == {"g1": 8}
    sel = DefaultWorkerSelector(KvRouterConfig(temperature=0.0, seed=0))
    states = {1: WorkerState(), 2: WorkerState()}
    overlaps = {w: sum(c.values()) for w, c in tiers.items()}
    assert sel.select([1, 2], 8, overlaps, states,
                      tier_overlaps=tiers) == 2


def test_selector_tier_pricing():
    import pytest as _pytest

    sel = DefaultWorkerSelector(KvRouterConfig(temperature=0.0, seed=0))
    tiers = {1: {"g4": 8}, 2: {"g1": 8}}
    states = {1: WorkerState(), 2: WorkerState()}
    choice, logits = sel.select_verbose([1, 2], 10, {}, states,
                                        tier_overlaps=tiers)
    assert choice == 2
    assert logits[2] == _pytest.approx(2.0)  # pure-g1 = classic formula
    assert logits[1] == _pytest.approx(2 + 8 * 0.7)  # default g4 cost
    # measured tier costs from load_metrics override the defaults
    states[1].tier_costs = {"g4": 0.05}
    _, logits = sel.select_verbose([1, 2], 10, {}, states,
                                   tier_overlaps=tiers)
    assert logits[1] == _pytest.approx(2 + 8 * 0.05)
    # cheap-enough onboarding beats a busier g1 holder
    states[2].active_blocks = 10
    assert sel.select([1, 2], 10, {}, states, tier_overlaps=tiers) == 1
    # onboarding is never priced above recompute (cap at 1.0)
    states[1].tier_costs = {"g4": 9.0}
    _, logits = sel.select_verbose([1, 2], 10, {}, states,
                                   tier_overlaps=tiers)
    assert logits[1] == _pytest.approx(2 + 8 * 1.0)


def test_compute_tier_costs_from_the_token_rate():
    from dynamo_tpu.router.tiered_index import (
        DEFAULT_TIER_COSTS,
        compute_tier_costs,
    )

    # recompute_s = 16 tok / 500 tok/s = 32 ms/block; a 32 MB block over
    # a 1 GB/s shared FS is ALSO 32 ms -> cost 1.0
    costs = compute_tier_costs(500.0, bytes_per_block=32e6,
                               block_tokens=16, tier_bw={"g4": 1e9})
    assert costs["g1"] == 0.0
    assert costs["g4"] == pytest.approx(1.0, abs=0.01)
    # g2 at the default 8 GB/s staging rate: 4 ms onboard -> 0.125
    assert costs["g2"] == pytest.approx(0.125, abs=0.01)
    # a worker that has not prefilled falls back to the static defaults
    assert compute_tier_costs(None, 32e6, 16) == DEFAULT_TIER_COSTS
    assert compute_tier_costs(0.0, 32e6, 16) == DEFAULT_TIER_COSTS


@pytest.mark.parametrize("tok_rate", [500.0, 12707.0, None])
def test_tier_costs_follow_the_token_rate(tok_rate):
    """The costs are what the formula over FLOPs gave for the same token
    rate (block_tokens x flops_per_token / flops_per_s with
    flops_per_token = flops_per_s / tokens_per_s: the FLOPs cancel),
    whatever the model's FLOPs a token; defaults while the rate is
    unknown."""
    from dynamo_tpu.router.tiered_index import (
        DEFAULT_TIER_BW,
        DEFAULT_TIER_COSTS,
        compute_tier_costs,
    )

    bytes_per_block, block_tokens = 2 * 16 * 8 * 128 * 128 * 2, 128
    costs = compute_tier_costs(tok_rate, bytes_per_block, block_tokens)
    if tok_rate is None:
        assert costs == DEFAULT_TIER_COSTS
        return
    for flops_per_token in (2e9, 7.5e9):
        flops_per_s = flops_per_token * tok_rate
        recompute_s = block_tokens * flops_per_token / flops_per_s
        for tier, bw in DEFAULT_TIER_BW.items():
            assert costs[tier] == pytest.approx(
                bytes_per_block / bw / recompute_s, abs=1e-4)
    assert costs["g1"] == 0.0
