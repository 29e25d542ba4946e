"""Overlapped-scheduler composition suite (ROADMAP item 3 / PR 11).

The contract under test: `overlap_scheduling=True` (pipelined decode,
deferred prefill first-token readback, adaptive decode fusion,
enqueue-ahead spans) is **greedy byte-identical** to the lockstep sync
mode across the composition matrix — mixed prefill/decode arrivals,
mid-stream cancellation, drain_abort, chaos-seeded step delays — plus
the scheduler-policy properties themselves: adaptive fusion ramps up a
decode-only stretch and de-fuses within one step of a new arrival,
serving steady state triggers ZERO recompiles (the packed-prefill
committed-KV executable fork regression), and SLA-aware admission
shrinks prefill chunks under SLO burn.

Everything here runs CPU-only (JAX_PLATFORMS=cpu) in tier-1 — the
`overlap` marker exists so the mode's smoke can be selected explicitly.
"""

import asyncio

import jax.numpy as jnp
import pytest

# real-JAX-engine tests: XLA compiles and device work run inside the
# async bodies; the conftest slow-callback gate cannot hold here (same
# opt-out as tests/test_engine.py)
pytestmark = [pytest.mark.overlap, pytest.mark.allow_slow_callbacks]

from dynamo_tpu import chaos
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.protocols import (
    DRAIN_ABORT,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

FP32 = LlamaConfig(name="tiny32", vocab_size=256, d_model=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                   dtype=jnp.float32)


def engine(**kw):
    defaults = dict(model_config=FP32, block_size=4, num_blocks=128,
                    max_blocks_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(8, 16, 32, 64), seed=7)
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def greedy_req(tokens, n, rid, seed=0):
    return PreprocessedRequest(
        token_ids=tokens, request_id=rid,
        sampling=SamplingOptions(temperature=0.0, seed=seed),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )


async def collect(eng, req, token=None):
    toks = []
    async for out in eng.generate(req, token=token):
        if out.finish_reason == "error":
            raise RuntimeError(out.error)
        toks.extend(out.token_ids)
    return toks


PROMPTS = [
    list(range(7, 20)),            # 13 tokens
    list(range(40, 49)),           # 9 tokens
    list(range(7, 15)),            # shares a 2-block prefix with [0]
]


async def _staggered_run(overlap: bool, tag: str, stagger_s=0.2,
                         n_tokens=14, **cfg):
    """Three requests arriving mid-each-other's decode: the mixed
    prefill/decode regime the overlapped scheduler reorders most."""
    eng = engine(overlap_scheduling=overlap, **cfg)

    async def one(i, delay):
        await asyncio.sleep(delay)
        return await collect(
            eng, greedy_req(PROMPTS[i], n_tokens, f"{tag}-r{i}"))

    outs = await asyncio.gather(*[
        one(i, i * stagger_s) for i in range(len(PROMPTS))])
    metrics = dict(eng.metrics)
    await eng.close()
    return outs, metrics


async def test_greedy_byte_identity_mixed_arrivals():
    """The headline contract: overlapped scheduling is greedy
    byte-identical to lockstep sync under staggered mixed
    prefill/decode arrivals (deferred first tokens, pipelined bursts,
    adaptive fusion and all)."""
    sync_outs, _ = await _staggered_run(False, "sync")
    over_outs, m = await _staggered_run(True, "over")
    assert over_outs == sync_outs
    # (whether a pure continuation burst engaged is timing-dependent on
    # a -O0 CPU; test_engine's continuation test pins that path — here
    # the contract is the byte identity above)
    assert m["decode_tokens"] > 0


async def test_byte_identity_mid_stream_cancellation():
    """Cancelling one stream mid-decode (token-level teardown racing
    in-flight bursts AND a possibly-deferred first token) must not
    perturb the surviving streams in either mode."""
    from dynamo_tpu.runtime import CancellationToken

    async def run(overlap: bool, tag: str):
        eng = engine(overlap_scheduling=overlap)
        token = CancellationToken()
        victim = greedy_req(list(range(20, 32)), 10_000, f"{tag}-victim")
        got = []

        async def consume():
            async for out in eng.generate(victim, token=token):
                got.append(out)

        vtask = asyncio.create_task(consume())

        async def survivor():
            await asyncio.sleep(0.15)
            return await collect(
                eng, greedy_req(PROMPTS[0], 16, f"{tag}-live"))

        stask = asyncio.create_task(survivor())
        await asyncio.sleep(0.6)
        token.stop()
        await asyncio.wait_for(vtask, timeout=30)
        toks = await asyncio.wait_for(stask, timeout=60)
        assert got[-1].finish_reason == "cancelled"
        # the cancelled slot's teardown frees its blocks on a later step
        for _ in range(600):
            if all(s is None for s in eng._slots) and not eng.waiting:
                break
            await asyncio.sleep(0.05)
        assert all(s is None for s in eng._slots)
        await eng.close()
        return toks

    sync_toks = await run(False, "sync")
    over_toks = await run(True, "over")
    assert over_toks == sync_toks


async def test_drain_abort_mid_overlap():
    """drain_abort with unread bursts + deferred first tokens in flight:
    every stream errors with the migratable DRAIN_ABORT marker, emitted
    tokens are a prefix of the fault-free stream, nothing hangs or
    leaks."""
    # fault-free reference
    ref, _ = await _staggered_run(True, "ref", stagger_s=0.05,
                                  n_tokens=64)

    eng = engine(overlap_scheduling=True)
    streams = {i: [] for i in range(len(PROMPTS))}
    errors = {}

    async def one(i):
        await asyncio.sleep(i * 0.05)
        async for out in eng.generate(
                greedy_req(PROMPTS[i], 64, f"drain-r{i}")):
            if out.finish_reason == "error":
                errors[i] = out.error
                return
            streams[i].extend(out.token_ids)

    tasks = [asyncio.create_task(one(i)) for i in range(len(PROMPTS))]
    await asyncio.sleep(0.8)
    eng.drain_abort()
    await asyncio.wait_for(asyncio.gather(*tasks), timeout=30)
    assert errors, "drain_abort aborted nothing in flight"
    for i, err in errors.items():
        assert DRAIN_ABORT in err
    for i, toks in streams.items():
        assert toks == ref[i][:len(toks)], \
            f"stream {i} diverged from the fault-free prefix"
    await eng.close()


async def test_byte_identity_under_chaos_step_delays():
    """Seeded chaos delays on the engine.step seam jitter the arrival/
    step phase alignment (different fusion ramps, different pipeline
    occupancy) — output must not care, in either mode."""
    plane = chaos.ChaosPlane(seed=23).rule(
        "engine.step", "delay", delay_s=0.02, p=0.25)
    with plane:
        chaos_outs, _ = await _staggered_run(True, "chaos")
    plain_outs, _ = await _staggered_run(True, "plain")
    sync_outs, _ = await _staggered_run(False, "syncref")
    assert chaos_outs == plain_outs == sync_outs


async def test_adaptive_fusion_ramps_and_defuses_on_arrival():
    """A decode-only stretch must ramp the burst size to the full
    decode_fused_steps; a new arrival must de-fuse the NEXT dispatched
    burst to the interleave size (within one step), then re-ramp."""
    eng = engine(overlap_scheduling=True, decode_fused_steps=8,
                 max_num_seqs=2, block_size=16, prefill_buckets=(16, 32))
    r1 = greedy_req(list(range(7, 20)), 80, "ramp-r1")

    async def second():
        await asyncio.sleep(1.0)  # land mid r1's decode-only stretch
        mark = len(eng.fpm)
        toks = await collect(eng, greedy_req(list(range(40, 49)), 8,
                                             "ramp-r2"))
        return mark, toks

    t2 = asyncio.create_task(second())
    toks1 = await collect(eng, r1)
    mark, toks2 = await t2
    assert len(toks1) == 80 and len(toks2) == 8
    recs = list(eng.fpm)
    decode_ks = [r["k"] for r in recs if r["kind"] == "decode"]
    assert max(decode_ks) == 8, "ramp never reached full fusion"
    assert 4 in decode_ks, "interleave rung never dispatched"
    # de-fuse within one step: find r2's prefill dispatch; the decode
    # burst dispatched in that same step (right after it) must be short
    pre_idx = [i for i, r in enumerate(recs)
               if r["kind"] == "prefill" and i >= mark]
    assert pre_idx, "second request's prefill not recorded"
    after = [r["k"] for r in recs[pre_idx[0]:] if r["kind"] == "decode"]
    assert after and after[0] <= JaxEngine.INTERLEAVE_BURST, \
        f"burst after arrival was k={after[0] if after else None}"
    await eng.close()


async def _ladder_run(overlap, fused, lanes, n_long, tag, tmp_path):
    """`n_long` long requests (160 and 60 tokens) on `lanes` lanes, to
    their ends.  Returns the token streams, every dispatched decode
    burst as (k, k_held, lanes decoding) in order, and the counters."""
    from dynamo_tpu import obs

    eng = engine(overlap_scheduling=overlap, decode_fused_steps=fused,
                 max_num_seqs=lanes, block_size=16,
                 prefill_buckets=(16, 32))
    with obs.Tracer(out_path=str(tmp_path / f"{tag}.json")) as tr:
        outs = await asyncio.wait_for(asyncio.gather(*[
            collect(eng, greedy_req(PROMPTS[i], n, f"{tag}-r{i}"))
            for i, n in enumerate([160, 60][:n_long])]), 180.0)
    bursts = [(a["k"], a["k_held"], a["lanes"])
              for name, _, _, _, a, *_ in tr.spans
              if name == "decode_dispatch"]
    m = dict(eng.metrics)
    await eng.close()
    return outs, bursts, m


LADDER_CASES = {
    # name: (decode_fused_steps, lanes, long requests)
    "a-two-share-a-free-lane-hold-at-4": (8, 3, 2),
    "a-single-stream-ramps-as-before": (8, 2, 1),
    "b-full-lanes-ramp-to-8": (8, 2, 2),
    "c-a-finish-brings-4-back": (8, 2, 2),
    "d-cap-1-untouched": (1, 3, 2),
    "d-cap-4-untouched": (4, 3, 2),
    "e-streams-as-lockstep-shared": (8, 3, 2),
    "e-streams-as-lockstep-full": (8, 2, 2),
    "e-streams-as-lockstep-single": (8, 2, 1),
}


@pytest.mark.parametrize("case", list(LADDER_CASES))
async def test_burst_ladder_holds_while_a_lane_stands_free(case, tmp_path):
    """_fused_k's rule (PR 50): in a decode-only stretch of an engine
    that requests share, the ladder climbs only while NO lane is free.
    With one free the burst stays at the interleave rung (it is what
    would stand ahead of the next arrival's first chunk) and is counted
    held, also after one of the two has finished; with every lane taken
    the ramp reaches decode_fused_steps and nothing is held; a single
    stream ramps as it always did; a cap at or under the interleave
    rung leaves nothing to hold; the greedy streams are the lockstep
    mode's either way."""
    fused, lanes, n_long = LADDER_CASES[case]
    outs, bursts, m = await _ladder_run(True, fused, lanes, n_long, case,
                                        tmp_path)
    only, held = m["decode_only_bursts"], m["decode_held_bursts"]
    assert [len(o) for o in outs] == [160, 60][:n_long]
    assert held == sum(h for _, h, _ in bursts) <= only <= len(bursts)
    # every lane decoding: no lane is free, nothing may be held
    full = [(k, h) for k, h, busy in bursts if busy == lanes]
    assert not any(h for _, h in full)
    if case.startswith("a-two"):
        assert {k for k, _, _ in bursts} == {4}
        assert held == only > 40        # the 100 tokens r0 decodes alone
        # the benchmark's reader of the two counters; a parent that
        # lacks them reports nothing and does not raise
        from benchmark.lib import spec
        read = spec.metric_reader("layer_metrics", "burst_held_share")
        assert read({"counters_open": {}, "counters_close": m}) == 100.0
        assert read({"counters_open": {}, "counters_close": {}}) is None
    elif case.startswith("a-single"):
        assert max(k for k, _, _ in bursts) == 8, "one stream never ramped"
        assert only > 0 and held == 0
    elif case.startswith("b-"):
        assert max(k for k, _ in full) == 8, "full lanes never ramped"
    elif case.startswith("c-"):
        # the bursts dispatched after the last one over both lanes: the
        # short request's finish has been read back, its lane is free
        last_full = max(i for i, b in enumerate(bursts) if b[2] == 2)
        tail = bursts[last_full + 1:]
        assert bursts[last_full][0] == 8 and len(tail) > 10
        assert all(k == 4 and h for k, h, _ in tail), tail
    elif case.startswith("d-"):
        # (a cap of 1 has no fused program: no branch of the rule runs)
        assert {k for k, _, _ in bursts} == {fused}
        assert held == 0 and (only > 0) == (fused > 1)
    else:
        ref, _, _ = await _ladder_run(False, fused, lanes, n_long,
                                      case + "-sync", tmp_path)
        assert outs == ref


async def test_serving_steady_state_zero_recompiles():
    """The compile-watchdog acceptance gate: once warmup + the first
    request have compiled every shape serving reaches, further traffic
    of the same shape triggers ZERO compiles — in particular
    prefill_packed compiles exactly once per bucket (the
    committed-vs-uncommitted KV executable fork regression: without
    pinned kv out_shardings, the SECOND packed dispatch after any
    decode recompiled the same bucket)."""
    eng = engine(overlap_scheduling=True)
    await asyncio.to_thread(eng.warmup_decode)
    await collect(eng, greedy_req([5, 9, 13, 2, 7, 11, 3, 1, 8, 20],
                                  24, "warm-r0"))
    counts_after_first = dict(eng.compile_watch.counts)
    assert counts_after_first.get("prefill_packed", 0) == 1
    # same prompt length, different tokens (no prefix hit: differs at 0)
    await collect(eng, greedy_req([6, 10, 14, 3, 8, 12, 4, 2, 9, 21],
                                  24, "warm-r1"))
    await collect(eng, greedy_req([9, 13, 17, 6, 11, 15, 7, 5, 12, 24],
                                  24, "warm-r2"))
    assert dict(eng.compile_watch.counts) == counts_after_first, \
        "steady-state serving recompiled an already-served shape"
    await eng.close()


async def test_slo_yield_shrinks_prefill_chunks_under_burn():
    """SLA-aware admission: with the SLO plane reporting a burn above
    threshold while decodes are live, prefill dispatches yield chunk
    budget (smaller tokens-per-dispatch) and the yield is counted."""

    async def run(burn):
        eng = engine(overlap_scheduling=True, slo_yield_burn=1.0,
                     max_num_seqs=2, num_blocks=256,
                     max_blocks_per_seq=32, block_size=4,
                     prefill_buckets=(8, 16),
                     prefill_chunk_tokens=64)
        if burn:
            eng.set_slo_burn(burn)

        async def long_prompt():
            await asyncio.sleep(0.4)  # arrive while r1 decodes
            return await collect(
                eng, greedy_req(list(range(1, 81)), 2, "slo-long"))

        t2 = asyncio.create_task(long_prompt())
        toks1 = await collect(eng, greedy_req(PROMPTS[0], 48, "slo-r1"))
        toks2 = await t2
        chunks = [r["tokens"] for r in eng.fpm
                  if r["kind"] == "prefill" and r["rows"] == 1
                  and r["tokens"] > 1]
        yields = eng.metrics.get("slo_yield_steps", 0)
        await eng.close()
        return toks1, toks2, max(chunks, default=0), yields

    toks1a, toks2a, max_free, y0 = await run(0.0)
    toks1b, toks2b, max_burn, y1 = await run(8.0)
    assert y0 == 0 and y1 > 0
    # burn=8 vs threshold 1.0 scales the ~62-token budget by 1/8 ->
    # floored near the smallest bucket; the free run keeps big chunks
    assert max_burn < max_free, (max_burn, max_free)
    # and yielding never changes WHAT is generated, only when
    assert (toks1a, toks2a) == (toks1b, toks2b)


async def test_spec_decode_byte_identity_across_modes():
    """Speculative decoding composed with the overlapped scheduler:
    token streams stay byte-identical to sync mode (spec engagement
    cadence may differ — the pipeline coarsens collapsed-slot probes —
    but rejection sampling preserves the greedy stream regardless)."""
    repeat = [5, 9, 13, 2] * 6

    async def run(overlap):
        eng = engine(overlap_scheduling=overlap, spec_decode="ngram",
                     spec_k=4, max_blocks_per_seq=32)
        toks = await collect(eng, greedy_req(repeat, 48, "spec-ov"))
        await eng.close()
        return toks

    assert await run(True) == await run(False)


async def test_guided_disagg_parks_cleanly_under_overlap():
    """A guided + disagg-prefill request defers its first-token readback
    like any other completing prefill; the guided step must NOT touch
    the slot during that one deferred step (a constrained decode there
    would write KV past the prompt and corrupt the parked prompt_len
    the decode side pulls — the review-pass finding)."""
    from dynamo_tpu.protocols.llm import DISAGG_ANNOTATION

    schema = {"type": "object",
              "properties": {"city": {"type": "string"}}}
    prompt = list(range(7, 19))

    async def run(overlap):
        eng = engine(overlap_scheduling=overlap)
        req = PreprocessedRequest(
            token_ids=prompt, request_id=f"gd-{overlap}",
            sampling=SamplingOptions(temperature=0.0,
                                     guided_json=schema),
            stop=StopConditions(max_tokens=32, ignore_eos=True),
            annotations=[DISAGG_ANNOTATION],
        )
        outs = []
        async for out in eng.generate(req):
            outs.append(out)
        parked = dict(eng._parked)
        await eng.close()
        return outs, parked

    for overlap in (False, True):
        outs, parked = await run(overlap)
        # exactly one output: the park finish with transfer params
        assert len(outs) == 1 and outs[0].finish_reason == "stop"
        params = outs[0].kv_transfer_params
        assert params is not None
        assert params["prompt_len"] == len(prompt), \
            f"overlap={overlap}: parked prompt_len corrupted"
        (rid, p), = parked.items()
        assert p.prompt_len == len(prompt)


async def test_mocker_overlap_byte_identity_and_cont_bursts():
    """The mocker's overlap sim: identical token streams either mode,
    and the overlapped run emits fused continuation decode dispatches
    (the bench gap line's cont_burst_frac source)."""
    from dynamo_tpu import obs
    from dynamo_tpu.mocker import MockEngineArgs
    from dynamo_tpu.mocker.engine import MockEngine

    async def run(overlap):
        eng = MockEngine(MockEngineArgs(
            model_name="m", block_size=4, base_step_s=0.0,
            prefill_s_per_token=0.0, decode_s_per_seq=0.0,
            overlap_scheduling=overlap, decode_fused_steps=8))
        req = PreprocessedRequest(
            token_ids=list(range(40)), request_id="same-rid",
            stop=StopConditions(max_tokens=48, ignore_eos=True))
        toks = []
        tr = obs.Tracer().install()
        try:
            async for out in eng.generate(req):
                toks.extend(out.token_ids)
        finally:
            tr.uninstall()
            await eng.close()
        decodes = [s for s in tr.spans if s[0] == "decode_dispatch"]
        return toks, decodes

    sync_toks, sync_d = await run(False)
    over_toks, over_d = await run(True)
    assert over_toks == sync_toks and len(over_toks) == 48
    assert all((s[4] or {}).get("k", 1) == 1 for s in sync_d)
    over_ks = [(s[4] or {}).get("k", 1) for s in over_d]
    over_cont = [(s[4] or {}).get("cont") for s in over_d]
    assert max(over_ks) == 8, "overlap sim never fused"
    assert any(over_cont), "overlap sim never marked a continuation"
    # fused bursts amortize dispatches: strictly fewer of them
    assert len(over_d) < len(sync_d)


@pytest.mark.parametrize("lanes, n_req, top",
                         [(64, 2, 4), (64, 1, 8), (1, 1, 8), (2, 2, 8)],
                         ids=["two-of-64", "single-stream", "one-lane-full",
                              "two-lanes-full"])
async def test_mocker_burst_ladder_follows_the_engine(lanes, n_req, top):
    """The mocker mirrors _fused_k (PR 50): while sequences share the
    engine and fewer run than it has lanes every burst is the interleave
    one; with the lanes full, and for a single stream, the ramp reaches
    decode_fused_steps."""
    from dynamo_tpu.mocker import MockEngineArgs
    from dynamo_tpu.mocker.engine import MockEngine

    eng = MockEngine(MockEngineArgs(
        model_name="m", block_size=4, base_step_s=0.0,
        prefill_s_per_token=0.0, decode_s_per_seq=0.0,
        overlap_scheduling=True, decode_fused_steps=8,
        max_num_seqs=lanes))

    async def one(i):
        req = PreprocessedRequest(
            token_ids=list(range(i, 40 + i)), request_id=f"ladder-{i}",
            stop=StopConditions(max_tokens=64, ignore_eos=True))
        return [t async for out in eng.generate(req)
                for t in out.token_ids]

    outs = await asyncio.gather(*[one(i) for i in range(n_req)])
    ks = [r["k"] for r in eng.fpm if r["kind"] == "decode"]
    await eng.close()
    assert [len(o) for o in outs] == [64] * n_req
    assert max(ks) == top and min(ks) >= 4, ks


async def _until(cond, what, timeout=60.0):
    t0 = asyncio.get_running_loop().time()
    while not cond():
        assert asyncio.get_running_loop().time() - t0 < timeout, what
        await asyncio.sleep(0.005)


async def test_close_with_bursts_in_flight_returns():
    """close() while fused bursts are dispatched and unread: it waits
    out the step and what the step dispatched (`_drain_device`), leaves
    nothing in flight, the stream ends in the worker-engine-error marker
    and a second close() is harmless."""
    eng = engine(overlap_scheduling=True)
    outs = []

    async def run():
        async for out in eng.generate(greedy_req(PROMPTS[0], 400, "inflight")):
            outs.append(out)

    task = asyncio.create_task(run())
    await _until(lambda: len(eng._inflight) > 0 and outs,
                 "no burst was ever in flight")
    await asyncio.wait_for(eng.close(), 60.0)
    await asyncio.wait_for(task, 10.0)
    assert not eng._inflight and not eng._pending_first
    assert outs[-1].finish_reason == "error"
    assert "worker engine error" in outs[-1].error
    await asyncio.wait_for(eng.close(), 10.0)


async def test_close_after_a_failed_step_returns():
    """A step that raises kills the loop with bursts still unread:
    later requests fail fast, and close() then returns all the same
    (the drain swallows what a failed program left behind)."""
    eng = engine(overlap_scheduling=True)
    plane = chaos.ChaosPlane(seed=3).rule(
        "engine.step", "fail", after=4, times=1,
        error="worker engine error: chaos crash on step N")
    with plane:
        with pytest.raises(RuntimeError, match="worker engine error"):
            await collect(eng, greedy_req(PROMPTS[0], 400, "crash"))
    assert plane.fired() == 1
    with pytest.raises(RuntimeError, match="worker engine error"):
        await collect(eng, greedy_req(PROMPTS[1], 2, "post-crash"))
    await asyncio.wait_for(eng.close(), 60.0)
    assert not eng._inflight and not eng._pending_first


class _NeverReady:
    """A dispatched burst that says it is not ready until it is read, so
    that `ahead_steps` counts everything in flight: a property of the
    schedule, not of the CPU's speed."""

    def __init__(self, arr):
        self.arr = arr

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        return np.asarray(self.arr)


@pytest.mark.parametrize("lanes", ["free", "full"])
@pytest.mark.parametrize("depth", [None, 4], ids=["default", "depth4"])
async def test_pipeline_depth_bounds_what_stands_ahead_of_a_prefill(
        depth, lanes):
    """A step leaves `decode_pipeline_depth` bursts in flight (2 by
    default: the one that runs and one behind it; still honoured where
    set) and never more; the oldest is read back BEFORE admission, so in
    a decode-only stretch a first chunk goes out behind depth - 1 bursts
    at most.  A single stream has ramped, so the FIRST request to join
    it stands behind full bursts; from then on the engine is shared
    and, with a lane FREE, those bursts are held at the interleave rung
    (_fused_k, PR 50): 4 steps by default where the ramp left 8.  With
    every lane taken the ramp reaches decode_fused_steps, an arrival
    waits for a lane, and what stands ahead of its chunk is under
    depth - 1 full bursts: by default under the decode_fused_steps +
    INTERLEAVE_BURST the order of the step promises."""
    assert EngineConfig().decode_pipeline_depth == 2
    cfg = {} if depth is None else {"decode_pipeline_depth": depth}
    depth = depth or 2
    free = lanes == "free"
    eng = engine(overlap_scheduling=True, decode_fused_steps=8,
                 block_size=16, prefill_buckets=(16, 32),
                 max_blocks_per_seq=32, max_num_seqs=4 if free else 2,
                 **cfg)
    fused, short = eng.config.decode_fused_steps, JaxEngine.INTERLEAVE_BURST
    build, step, stamp = eng._build_burst, eng._sched_step, \
        eng._stamp_dispatch
    left_in_flight, firsts = [], []

    def build_unready(active, k):
        burst, cont = build(active, k)
        return _NeverReady(burst), cont

    def step_then_look():
        step()
        left_in_flight.append(len(eng._inflight))

    def stamp_and_keep(slots):
        firsts.extend(s for s in slots if s.dispatched_t == 0.0)
        stamp(slots)

    eng._build_burst, eng._sched_step = build_unready, step_then_look
    eng._stamp_dispatch = stamp_and_keep

    def arrival(i, n):
        return asyncio.create_task(collect(eng, greedy_req(
            list(range(40 + i, 49 + i)), n, f"depth-r{i + 1}")))

    async def stretch(k, what):
        # `depth` bursts of k steps since now: all in flight are such
        mark = len(eng.fpm)
        await _until(lambda: sum(r["kind"] == "decode" and r["k"] == k
                                 for r in list(eng.fpm)[mark:]) >= depth,
                     what)

    n_long = 200 if free else 440          # r0 outlasts every arrival
    long_one = asyncio.create_task(collect(
        eng, greedy_req(list(range(7, 20)), n_long, "depth-r0")))
    if free:
        for i in range(3):
            # each arrival lands in a decode-only stretch of r0 alone:
            # the first meets a single stream at full fusion, the
            # others an engine that has been shared since
            if i == 0:
                await stretch(fused, "one stream never reached full fusion")
            else:
                await stretch(short, "no stretch at the interleave rung")
            assert len(await arrival(i, 8)) == 8
        n_first = 4
    else:
        # r0 and one more hold both lanes; each arrival is sent into a
        # stretch at full fusion and waits for that other's lane
        holder = arrival(0, 80)
        for i in range(1, 4):
            await stretch(fused, "the ramp never reached full fusion")
            nxt = arrival(i, 80 if i < 3 else 8)
            assert len(await holder) == 80
            holder = nxt
        assert len(await holder) == 8
        n_first = 5
    assert len(await long_one) == n_long
    assert max(left_in_flight) == depth
    ahead = [s.ahead_steps for s in firsts]
    assert len(ahead) == n_first and ahead[0] == 0   # r0: an idle engine
    assert 0 < max(ahead[1:]) <= (depth - 1) * fused
    if free:
        assert 0 < max(ahead[2:]) <= (depth - 1) * short
    if depth == 2:
        assert max(ahead) <= fused + short
    await eng.close()
