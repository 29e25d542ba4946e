"""One timeline (obs.PhaseClock, engine/core.py): the scheduler's phases
as always-on counters, as `dyn.<kind>` TraceMes on the profiler's clock
and as ring spans; the request-stage counters; the programs' names.
CPU, tiny widths, no wall-clock thresholds: every comparison is between
numbers of one run."""

import asyncio
import glob
import json
import logging
import threading
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.allow_slow_callbacks

from dynamo_tpu import obs
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.obs.compile_watch import (
    PROGRAM_PREFIX,
    CompileWatch,
    WatchedProgram,
)
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from test_overlap import _until as until  # noqa: E402 (shared helper)

TINY = LlamaConfig(name="tiny32", vocab_size=256, d_model=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                   dtype=jnp.float32)

PREFILL_FAMILIES = {"prefill", "prefill_batched", "prefill_packed",
                    "prefill_ring", "draft_prefill"}


def make_engine(**kw):
    defaults = dict(model_config=TINY, block_size=4, num_blocks=256,
                    max_blocks_per_seq=32, max_num_seqs=4,
                    prefill_buckets=(8, 16, 32, 64), seed=7)
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def request(i, n_prompt=32, max_tokens=8):
    return PreprocessedRequest(
        token_ids=[(i * 37 + j) % 200 + 3 for j in range(n_prompt)],
        request_id=f"r{i}",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True))


async def serve(eng, i, **kw):
    toks = []
    async for out in eng.generate(request(i, **kw)):
        toks.extend(out.token_ids)
    return toks


async def serve_mix(eng):
    """Four requests at once (one prompt of several chunks), then one
    alone: prefill, interleaved and decode-only steps."""
    outs = await asyncio.gather(
        serve(eng, 1), serve(eng, 2, n_prompt=100, max_tokens=12),
        serve(eng, 3, n_prompt=9), serve(eng, 4, max_tokens=20))
    return outs + [await serve(eng, 5, n_prompt=17)]


async def settled(eng):
    """Wait until the engine stands still: nothing queued, active or in
    flight, no step running, and the loop has counted the last step (it
    counts a step after the step's thread returned)."""
    for _ in range(400):
        idle = not (eng.waiting or eng._inflight
                    or any(s is not None for s in eng._slots))
        if idle and eng._step_lock.acquire(blocking=False):
            eng._step_lock.release()
            if eng.metrics["steps"] == eng.metrics["host_n.step"]:
                return
        await asyncio.sleep(0.01)
    raise AssertionError("the scheduler loop never went idle")


async def stands_empty(eng):
    """Wait until the scheduler loop waits under its `idle` phase."""
    await until(lambda: any(ph.kind == "idle" for ph in eng._phase.open),
                "the scheduler loop never stood empty")


def profiler_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def dyn_events(trace_dir):
    """[[(name, start_ns, end_ns, stats)]]: the `dyn.*` events of every
    thread line that holds any, on the host plane of the one trace under
    `trace_dir`.  Every Python thread's line is named "python", and the
    steps run on whichever pool thread `asyncio.to_thread` picked: under
    load that is more than one."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("dyn.")]
            if evs:
                out.append(evs)
    return out


# ------------------------- the phase clock alone ---------------------------


def fake_clock(monkeypatch, ticks, thread_cpu=(0.0,), process_cpu=(0.0,)):
    """`ticks` are the reads of the wall clock; the CPU clocks read their
    values in turn and then keep the last."""

    def reads(values):
        it = iter(values)
        return lambda: next(it, values[-1])

    it = iter(ticks)
    monkeypatch.setattr(obs, "time", types.SimpleNamespace(
        monotonic=lambda: next(it), thread_time=reads(thread_cpu),
        process_time=reads(process_cpu)))


def test_phase_clock_counts_self_time_and_whole_steps(monkeypatch):
    """A kind's seconds are those spent in no nested phase, `step`'s are
    whole steps: the kinds partition the steps' wall time."""
    m = {}
    clock = obs.PhaseClock(m, "sched:t")
    # step 0..10; sched 1..3; decode_dispatch 3..9 holding device_wait 4..8
    fake_clock(monkeypatch, [0.0, 1.0, 3.0, 3.0, 4.0, 8.0, 9.0, 10.0])
    with clock("step"):
        with clock("sched"):
            pass
        with clock("decode_dispatch"):
            with clock("device_wait", what="burst_fetch"):
                pass
    assert m["host_s.step"] == 10.0 and m["host_n.step"] == 1
    assert m["host_s.sched"] == 2.0
    assert m["host_s.decode_dispatch"] == 2.0
    assert m["host_s.device_wait"] == 4.0
    assert m["host_n.decode_dispatch"] == m["host_n.device_wait"] == 1
    assert not clock.open
    # every step phase has its keys from the start, at zero
    assert m["host_s.emit"] == 0.0 and m["host_n.spec_dispatch"] == 0


def test_phase_clock_ring_spans_attrs_and_off_ring():
    m = {}
    clock = obs.PhaseClock(m, "sched:t")
    with obs.Tracer() as tr:
        with clock("decode_dispatch") as ph:
            ph.set(k=4, lanes=2)
        with clock("decode_dispatch") as ph:
            ph.off_ring()            # nothing was dispatched
        with clock("device_wait", what="burst_fetch"):
            pass
    assert [(s[0], s[3], s[4]) for s in tr.spans] == [
        ("decode_dispatch", "sched:t", {"k": 4, "lanes": 2}),
        ("device_wait", "sched:t", {"what": "burst_fetch"})]
    assert m["host_n.decode_dispatch"] == 2   # the time counts either way


def test_phase_clock_closes_on_exception():
    m = {}
    clock = obs.PhaseClock(m)
    with pytest.raises(RuntimeError):
        with clock("step"):
            with clock("sched"):
                raise RuntimeError("boom")
    assert not clock.open
    assert m["host_n.step"] == 1 and m["host_n.sched"] == 1


# ------------------------- the engine's phases -----------------------------


@pytest.fixture(scope="module")
def profiler_capture(tmp_path_factory):
    """The `dyn.*` events of one jax.profiler session over a mix, an
    empty engine and one more request; no Tracer and no environment
    variable is involved."""
    trace_dir = tmp_path_factory.mktemp("capture")

    async def main():
        eng = make_engine()
        await serve(eng, 0)                      # compile outside the trace
        await settled(eng)       # a step open now would orphan its phases
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=profiler_options())
        try:
            await serve_mix(eng)
            await stands_empty(eng)      # an `idle` that opens in the session
            await serve(eng, 6)          # and is closed in it
            await settled(eng)
        finally:
            jax.profiler.stop_trace()
        await eng.close()

    assert obs.tracer() is None
    asyncio.run(main())
    lines = dyn_events(str(trace_dir))
    assert lines, "no dyn.* event on /host:CPU"
    return lines


def with_steps(lines, has):
    return [evs for evs in lines
            if any(n == "dyn.step" for n, _, _, _ in evs) == has]


def test_engine_phases_on_the_profilers_clock(profiler_capture):
    """Under a jax.profiler session the host plane holds `dyn.step` with
    the phases nested inside it, carrying their attributes."""
    lines = with_steps(profiler_capture, True)
    assert lines
    kinds = set()
    for evs in lines:
        steps = [(a, b) for n, a, b, _ in evs if n == "dyn.step"]
        first, last = min(a for a, _ in steps), max(b for _, b in steps)
        for name, a, b, stats in evs:
            kinds.add(name)
            if name == "dyn.step":
                assert "active" in stats and "waiting" in stats
                continue
            # the engine makes no between-step scheduler call here, so
            # every phase lies inside one step of its own thread's line
            # (a step cut by the session's edge keeps only its phases)
            if first <= a and b <= last:
                assert any(s0 <= a and b <= s1 for s0, s1 in steps), name
            if name == "dyn.decode_dispatch" and stats:
                assert {"k", "lanes", "cont"} <= set(stats)
            if name == "dyn.device_wait":
                assert stats["what"] in ("burst_fetch", "prefill_first")
    assert {"dyn.step", "dyn.prefill_dispatch", "dyn.decode_dispatch",
            "dyn.device_wait", "dyn.emit"} <= kinds
    # a request's first chunk says what stood ahead of it on the device
    # (r2's later chunks do not): six requests, at most six programs
    firsts = [stats for evs in lines for name, _, _, stats in evs
              if name == "dyn.prefill_dispatch" and "ahead_steps" in stats]
    assert 1 <= len(firsts) <= 6
    assert all(int(st["ahead_steps"]) >= int(st["ahead_bursts"]) >= 0
               for st in firsts)
    assert kinds & {"dyn.sched", "dyn.enqueue_ahead"}


def test_idle_and_hop_on_the_profilers_clock(profiler_capture):
    """The loop's own two kinds are TraceMes of the event-loop thread:
    `dyn.idle` over an empty-engine wait, `dyn.hop` from a step's return
    to the next step's call; no step of any thread overlaps either and
    they do not overlap each other."""
    loop_lines = with_steps(profiler_capture, False)
    assert len(loop_lines) == 1                  # one event-loop thread
    mine = sorted((a, b, n) for n, a, b, _ in loop_lines[0])
    assert {n for _, _, n in mine} == {"dyn.idle", "dyn.hop"}
    steps = [(a, b) for evs in with_steps(profiler_capture, True)
             for n, a, b, _ in evs if n == "dyn.step"]
    for a, b, name in mine:
        assert all(s1 <= a or b <= s0 for s0, s1 in steps), name
    for (_, b0, _), (a1, _, _) in zip(mine, mine[1:]):
        assert b0 <= a1
    # more steps follow one another than follow an idle wait
    n_idle = sum(1 for _, _, n in mine if n == "dyn.idle")
    assert 1 <= n_idle < len(mine) - n_idle <= len(steps)


def test_no_session_no_tracer_records_nothing(monkeypatch):
    """Profiler off and no Tracer: no TraceMe is built and no span
    recorded (either would raise here); only the counters move."""

    def refuse(*a, **kw):
        raise AssertionError("a span was recorded with tracing off")

    class NoTraceMe:
        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)
        __new__ = refuse

    monkeypatch.setattr(obs.Tracer, "record", refuse)

    async def main():
        eng = make_engine()
        assert eng._phase.trace_me is jax.profiler.TraceAnnotation
        eng._phase.trace_me = NoTraceMe
        outs = await serve_mix(eng)
        await settled(eng)
        m = dict(eng.metrics)
        await eng.close()
        return outs, m

    assert obs.tracer() is None
    outs, m = asyncio.run(main())
    assert [len(o) for o in outs] == [8, 12, 8, 20, 8]
    assert m["host_n.step"] == m["steps"] > 0
    assert m["req_stage_n"] == 5


def test_phase_seconds_partition_the_steps():
    """Sum of the phases' seconds == `host_s.step` within 2 % (what is
    left is the glue between phases) and never above it; `host_n.step`
    counts the loop's steps."""

    async def main():
        eng = make_engine()
        await serve_mix(eng)
        await settled(eng)
        m = dict(eng.metrics)
        await eng.close()
        return m

    m = asyncio.run(main())
    assert m["host_n.step"] == m["steps"]
    whole = m["host_s.step"]
    # the loop's kinds and the pauses' sum are not parts of a step
    parts = sum(v for k, v in m.items() if k.startswith("host_s.")
                and k[7:] not in ("step", "idle", "hop", "pause"))
    assert whole > 0
    assert 0.98 * whole <= parts <= whole * (1 + 1e-9)
    for kind in ("prefill_dispatch", "decode_dispatch", "device_wait",
                 "emit"):
        assert m[f"host_n.{kind}"] > 0 and m[f"host_s.{kind}"] > 0


# ------------------------- the loop's own kinds ------------------------------


def loop_seconds(m):
    return m["host_s.step"] + m["host_s.hop"] + m["host_s.idle"]


def test_step_hop_and_idle_tile_the_loops_wall_time():
    """`step` + `hop` + `idle` is the scheduler loop's wall time: from the
    opening of the idle wait that the first enqueue ends to the opening
    of the one that follows the last finish frame, over three rounds of
    requests with an empty engine between them, the three counters grow
    by that stretch within 2 % (or 2 ms) and never by more.  What is
    missing is the loop's way into and out of an idle wait, which no
    phase owns."""

    async def main():
        eng = make_engine()
        await serve(eng, 0)                      # the compiles
        await stands_empty(eng)
        t0, m0 = eng._phase.open[-1].t0, dict(eng.metrics)
        for i in (1, 2, 3):
            await asyncio.gather(serve(eng, i, max_tokens=64),
                                 serve(eng, i + 3, n_prompt=9,
                                       max_tokens=40))
            await stands_empty(eng)
        t1, m1 = eng._phase.open[-1].t0, dict(eng.metrics)
        await eng.close()
        return t1 - t0, m0, m1

    wall, m0, m1 = asyncio.run(main())
    assert m1["host_n.idle"] - m0["host_n.idle"] == 3
    hops = m1["host_n.hop"] - m0["host_n.hop"]
    steps = m1["host_n.step"] - m0["host_n.step"]
    assert 0 < hops == steps - 3         # every step but a round's first
    parts = loop_seconds(m1) - loop_seconds(m0)
    assert parts <= wall * (1 + 1e-9)
    assert wall - parts <= max(0.02 * wall, 0.002), (wall, parts)
    assert m1["host_n.pause"] == 0 and not m1["host_s.pause"]


def test_hop_runs_from_step_to_step_and_not_across_an_idle_wait(monkeypatch):
    """The hop's counter is the step thread's: from the clock read that
    closed one `step` to the one that opens the next; an idle wait in
    between is `idle` and no hop, and `with clock("hop")`, the event-loop
    thread's line of it, adds nothing to the counter."""
    m = {}
    clock = obs.PhaseClock(m, "sched:t")
    assert m["host_s.idle"] == m["host_s.hop"] == m["host_s.pause"] == 0.0
    assert m["host_n.idle"] == m["host_n.hop"] == m["host_n.pause"] == 0
    # step 0..1, the loop's line of the hop 1.1..1.2, step 1.25..2,
    # idle 2.5..7, step 7.5..8
    fake_clock(monkeypatch, [0.0, 1.0, 1.1, 1.2, 1.25, 2.0, 2.5, 7.0,
                             7.5, 8.0])
    with clock("step"):
        pass
    with clock("hop"):
        pass
    with clock("step"):
        pass
    with clock("idle"):
        pass
    with clock("step"):
        pass
    assert m["host_s.step"] == 2.25 and m["host_n.step"] == 3
    assert m["host_s.hop"] == 0.25 and m["host_n.hop"] == 1
    assert m["host_s.idle"] == 4.5 and m["host_n.idle"] == 1
    # an idle wait of any length is no pause
    assert m["host_n.pause"] == 0 and not clock.pauses


def test_idle_and_hop_record_nothing_without_a_listener(monkeypatch):
    """Profiler off and no Tracer: an engine that empties twice opens no
    TraceMe and records no span for `idle` or `hop` either (either would
    raise here); their counters move."""

    def refuse(*a, **kw):
        raise AssertionError("a span was recorded with tracing off")

    class NoTraceMe:
        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)
        __new__ = refuse

    monkeypatch.setattr(obs.Tracer, "record", refuse)

    async def main():
        eng = make_engine()
        eng._phase.trace_me = NoTraceMe
        for i in (0, 1):
            assert len(await serve(eng, i)) == 8
            await stands_empty(eng)
        assert len(await serve(eng, 2)) == 8
        await settled(eng)
        m = dict(eng.metrics)
        await eng.close()
        return m

    assert obs.tracer() is None
    m = asyncio.run(main())
    assert m["host_n.idle"] >= 2 and m["host_s.idle"] > 0.0
    assert m["host_n.hop"] > 0 and m["host_s.hop"] > 0.0
    assert m["host_n.step"] == m["steps"]


# ------------------------- a pause names itself ------------------------------


def pause_lines(caplog):
    return [json.loads(r.getMessage().split(" ", 1)[1])
            for r in caplog.records
            if r.name == "dynamo_tpu.obs" and r.getMessage().startswith(
                "pause ")]


@pytest.mark.parametrize("ready", [3, 0], ids=["chip_ran_on", "chip_stood"])
def test_a_long_phase_names_itself(monkeypatch, caplog, ready):
    """A `device_wait` of 2 s inside a step: one record with the closed
    key set, `host_n.pause` counts it, `ready_behind` is what the engine's
    callback says of the bursts in flight, the two CPU clocks are read
    across the wait (a `burst_fetch` reads them as it opens), and the
    log gets one line.  The step around it, as long, is no pause."""
    m = {}
    clock = obs.PhaseClock(m, "sched:t", behind=lambda: (3, ready))
    # step 0..3 holding device_wait 0.5..2.5 and emit 2.5..2.75
    fake_clock(monkeypatch, [0.0, 0.5, 2.5, 2.5, 2.75, 3.0],
               thread_cpu=(10.0, 10.001), process_cpu=(50.0, 51.5))
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.obs"):
        with clock("step"):
            with clock("device_wait", k=8, what="burst_fetch"):
                pass
            with clock("emit", k=8, what="burst"):
                pass
    rec, = clock.pauses
    assert tuple(rec) == obs.PAUSE_KEYS
    assert rec == {"t": 2.5, "kind": "device_wait", "what": "burst_fetch",
                   "seconds": 2.0, "k": 8, "inflight": 3,
                   "ready_behind": ready, "step_thread_cpu_s": 0.001,
                   "step_process_cpu_s": 1.5}
    assert m["host_n.pause"] == 1 and m["host_s.pause"] == 2.0
    assert pause_lines(caplog) == [rec]


def test_a_wait_behind_several_programs_is_held_to_the_limit_for_each(
        monkeypatch):
    """A first token waits behind every chunk of its prompt: 2 s behind
    six programs is no pause, 3.5 s is."""
    m = {}
    clock = obs.PhaseClock(m, "sched:t")
    fake_clock(monkeypatch, [0.0, 2.0, 10.0, 13.5])
    with clock("device_wait", what="prefill_first", programs=6):
        pass
    assert not clock.pauses and m["host_n.pause"] == 0
    with clock("device_wait", what="prefill_first", programs=6):
        pass
    rec, = clock.pauses
    assert tuple(rec) == obs.PAUSE_KEYS and rec["seconds"] == 3.5


def test_a_first_token_wait_says_how_many_programs_it_stands_behind(
        tmp_path):
    """The engine's `prefill_first` waits carry `programs`, the chunk
    programs of the longest prompt they complete."""

    async def main():
        eng = make_engine(prefill_chunk_tokens=48)
        seen = watch_stages(eng)
        # a compile under a Tracer leaves a flight dump beside `out_path`
        with obs.Tracer(out_path=str(tmp_path / "trace.json")) as tr:
            await asyncio.gather(serve(eng, 1),
                                 serve(eng, 2, n_prompt=100))
            await settled(eng)
        await eng.close()
        waits = [s[4] for s in tr.spans if s[0] == "device_wait"
                 and s[4]["what"] == "prefill_first"]
        return waits, {slot.request.request_id: slot.prefill_chunks
                       for slot, _ in seen}

    waits, chunks = asyncio.run(main())
    assert all(set(a) == {"what", "programs"} for a in waits)
    # the two prompts share a 48-token program, so r1 completes before r2
    assert chunks["r2"] > chunks["r1"] >= 1
    assert [a["programs"] for a in waits] == [chunks["r1"], chunks["r2"]]


def test_a_long_hop_names_itself(monkeypatch, caplog):
    """A hop is held to the same limit where it is added; it has no
    `what`, no `k` and no CPU clocks (nobody read them as it began)."""
    m = {}
    clock = obs.PhaseClock(m, "sched:t", behind=lambda: (2, 2))
    fake_clock(monkeypatch, [0.0, 1.0, 2.75, 3.0])
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.obs"):
        with clock("step"):
            pass
        with clock("step"):
            pass
    rec, = clock.pauses
    assert tuple(rec) == obs.PAUSE_KEYS
    assert rec == {"t": 2.75, "kind": "hop", "what": "", "seconds": 1.75,
                   "k": 0, "inflight": 2, "ready_behind": 2,
                   "step_thread_cpu_s": None, "step_process_cpu_s": None}
    assert m["host_s.hop"] == m["host_s.pause"] == 1.75
    assert m["host_n.pause"] == 1 and pause_lines(caplog) == [rec]


def test_a_compile_inside_the_phase_is_no_pause(monkeypatch, caplog):
    """A phase that waited for a first compile is named by the compile
    watch's event already: no record, no count, no line; the same phase
    with the event outside its span is a pause."""
    m = {}
    compiles = [{"t": 1.5, "kind": "compile", "family": "prefill",
                 "seconds": 1.4}]
    clock = obs.PhaseClock(m, "sched:t", compiles=compiles)
    fake_clock(monkeypatch, [0.0, 2.0, 3.0, 5.0])
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.obs"):
        with clock("prefill_dispatch", rows=1):
            pass
        assert not clock.pauses and m["host_n.pause"] == 0
        assert not pause_lines(caplog)
        with clock("prefill_dispatch", rows=1):
            pass
    rec, = clock.pauses
    assert (rec["kind"], rec["seconds"], rec["inflight"]) == (
        "prefill_dispatch", 2.0, 0)
    assert rec["step_thread_cpu_s"] is None      # not a wait that reads it
    assert m["host_n.pause"] == 1 and len(pause_lines(caplog)) == 1


@pytest.mark.parametrize("ready", [True, False],
                         ids=["chip_ran_on", "chip_stood"])
def test_engine_pause_record_asks_the_bursts_in_flight(monkeypatch, ready):
    """The engine's callback: `inflight` is the bursts it holds when the
    phase closes, `ready_behind` those whose tokens are ready."""
    monkeypatch.setattr(obs, "PAUSE_S", 0.0)
    eng = make_engine()
    assert eng.pauses is eng._phase.pauses and not eng.pauses
    eng._inflight.extend({"burst": _Burst(ready), "k": 8, "lanes": {}}
                         for _ in range(2))
    with eng._phase("device_wait", k=4, what="burst_fetch"):
        pass
    rec, = eng.pauses
    assert tuple(rec) == obs.PAUSE_KEYS
    assert (rec["kind"], rec["what"], rec["k"]) == (
        "device_wait", "burst_fetch", 4)
    assert rec["inflight"] == 2
    assert rec["ready_behind"] == (2 if ready else 0)
    assert eng.metrics["host_n.pause"] == 1
    eng._inflight.clear()


def test_pause_records_of_a_served_mix_are_closed(monkeypatch, caplog):
    """With the limit at 0 every phase and hop of a real run that holds
    no compile is a pause: each record has the closed key set, the
    counter counts them and the log has a line for each; the record of a
    `burst_fetch` or `prefill_first` carries the CPU seconds across it,
    every other carries none; the streams are what they are without
    (`plain_outputs`' lengths)."""
    monkeypatch.setattr(obs, "PAUSE_S", 0.0)

    async def main():
        eng = make_engine()
        outs = await serve_mix(eng)
        await settled(eng)
        pauses, m = list(eng.pauses), dict(eng.metrics)
        await eng.close()
        return outs, pauses, m

    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.obs"):
        outs, pauses, m = asyncio.run(main())
    assert [len(o) for o in outs] == [8, 12, 8, 20, 8]
    lines = pause_lines(caplog)
    assert m["host_n.pause"] == len(lines) > 0
    assert pauses == lines[-64:]                 # the deque's bound
    kinds = set()
    for rec in lines:
        assert tuple(rec) == obs.PAUSE_KEYS
        kinds.add((rec["kind"], rec["what"]))
        assert rec["seconds"] >= 0.0
        assert 0 <= rec["ready_behind"] <= rec["inflight"]
        if rec["kind"] == "hop":
            assert (rec["what"], rec["k"]) == ("", 0)
        if rec["kind"] == "device_wait":
            assert rec["what"] in obs.CPU_TIMED_WAITS
            assert rec["step_thread_cpu_s"] >= 0.0
            assert rec["step_process_cpu_s"] >= 0.0
        else:
            assert rec["step_thread_cpu_s"] is None
            assert rec["step_process_cpu_s"] is None
        if rec["what"] == "burst_fetch":
            assert rec["k"] >= 1
    assert {("hop", ""), ("device_wait", "burst_fetch"),
            ("device_wait", "prefill_first"), ("emit", "burst")} <= kinds
    assert not kinds & {("step", ""), ("idle", "")}


# ------------------------- request stages ----------------------------------


def watch_stages(eng):
    """Record, for every first frame, the slot and what it added to the
    counters (the adds run one after another on the event loop)."""
    seen = []
    inner = eng._emit_first
    keys = ("req_stage_s.queue", "req_stage_s.prefill", "req_stage_s.emit",
            "req_stage_n", "req_stage_s.wake", "req_stage_s.lane",
            "req_stage_s.turn", "req_ahead_steps")

    def emit_first(slot, out):
        before = [eng.metrics[k] for k in keys]
        inner(slot, out)
        seen.append((slot, [eng.metrics[k] - b
                            for k, b in zip(keys, before)]))

    eng._emit_first = emit_first
    return seen


def check_stages(eng, seen, n_first_tokens):
    m = eng.metrics
    assert m["req_stage_n"] == len(seen) == n_first_tokens
    rids = [slot.request.request_id for slot, _ in seen]
    assert len(set(rids)) == len(rids)          # once a request
    for slot, (queue, prefill, emit, n, wake, lane, turn, ahead) in seen:
        assert n == 1
        assert min(queue, prefill, emit) >= 0.0
        assert slot.enqueued_t <= slot.dispatched_t <= slot.first_token_t
        assert queue + prefill == pytest.approx(
            slot.first_token_t - slot.enqueued_t, abs=1e-9)
        # the queue is three waits, each stamped once
        assert min(wake, lane, turn) >= 0.0
        assert slot.enqueued_t <= slot.seen_t <= slot.admitted_t \
            <= slot.dispatched_t
        assert wake + lane + turn == pytest.approx(queue, abs=1e-9)
        assert ahead == slot.ahead_steps >= 0
    for i, key in enumerate(("queue", "prefill", "emit")):
        assert m[f"req_stage_s.{key}"] == pytest.approx(
            sum(adds[i] for _, adds in seen), abs=1e-9)
    for i, key in enumerate(("wake", "lane", "turn"), start=4):
        assert m[f"req_stage_s.{key}"] == pytest.approx(
            sum(adds[i] for _, adds in seen), abs=1e-9)
    assert m["req_stage_s.wake"] + m["req_stage_s.lane"] \
        + m["req_stage_s.turn"] == pytest.approx(m["req_stage_s.queue"],
                                                 abs=1e-9)
    assert m["req_ahead_steps"] == sum(adds[7] for _, adds in seen)


def check_life(eng, seen, outs):
    """After the first token: `join` over the requests that got a second
    token, `decode` over the tokens after the second of those that ran
    to their end (`outs`: their streams, all complete), and the stages
    add up to the request's life in the engine."""
    m = eng.metrics
    slots = [slot for slot, _ in seen]
    assert len(slots) == len(outs)
    assert m["req_join_n"] == sum(1 for o in outs if len(o) >= 2)
    assert m["req_decode_tokens"] == sum(max(len(o) - 2, 0) for o in outs)
    join = decode = 0.0
    for slot in slots:
        assert slot.finished
        done_t = slot.last_push_t            # the finish frame's clock read
        if slot.generated >= 2:
            assert slot.first_token_t <= slot.second_token_t <= done_t
            join += slot.second_token_t - slot.first_token_t
        else:
            assert slot.second_token_t == 0.0
        if slot.generated >= 3:
            decode += done_t - slot.second_token_t
        if slot.generated >= 2:
            # queue + prefill + join + decode: enqueue to finish
            assert sum(t1 - t0 for t0, t1 in (
                (slot.enqueued_t, slot.dispatched_t),
                (slot.dispatched_t, slot.first_token_t),
                (slot.first_token_t, slot.second_token_t),
                (slot.second_token_t, done_t))) == pytest.approx(
                    done_t - slot.enqueued_t, abs=1e-9)
    assert m["req_stage_s.join"] == pytest.approx(join, abs=1e-9)
    assert m["req_stage_s.decode"] == pytest.approx(decode, abs=1e-9)


def test_request_stages_sum_to_the_engines_ttft():
    async def main():
        eng = make_engine(prefill_chunk_tokens=48)
        seen = watch_stages(eng)
        await serve_mix(eng)
        check_stages(eng, seen, 5)
        multi = next(s for s, _ in seen if s.request.request_id == "r2")
        assert multi.prefill_chunks > 1          # the 100-token prompt
        await eng.close()

    asyncio.run(main())


def test_request_stages_count_a_preempted_request_once():
    async def main():
        # 3 x (8 prompt blocks + 6 of output) do not fit 30 blocks
        eng = make_engine(num_blocks=30)
        seen = watch_stages(eng)
        outs = await asyncio.gather(
            *[serve(eng, i, max_tokens=24) for i in range(3)])
        assert [len(o) for o in outs] == [24, 24, 24]
        assert eng.metrics["preemptions"] >= 1
        check_stages(eng, seen, 3)
        await eng.close()

    asyncio.run(main())


def test_request_stages_leave_out_a_request_cancelled_in_the_queue():
    async def main():
        eng = make_engine(max_num_seqs=1)
        seen = watch_stages(eng)
        first = asyncio.create_task(serve(eng, 0, max_tokens=48))
        while not seen:                          # r0 holds the one slot
            await asyncio.sleep(0.005)
        waiting = asyncio.create_task(serve(eng, 1))
        while not eng.waiting:
            await asyncio.sleep(0.005)
        waiting.cancel()
        with pytest.raises(asyncio.CancelledError):
            await waiting
        assert len(await first) == 48
        await serve(eng, 2)
        check_stages(eng, seen, 2)               # r0 and r2, not r1
        assert eng.metrics["requests"] == 3
        await eng.close()

    asyncio.run(main())


def test_request_stage_spans_share_the_request_id(tmp_path):
    async def main():
        eng = make_engine()
        # a compile under a Tracer leaves a flight dump beside `out_path`
        with obs.Tracer(out_path=str(tmp_path / "trace.json")) as tr:
            await asyncio.gather(serve(eng, 1), serve(eng, 2))
            await settled(eng)
        await eng.close()
        return list(tr.spans)

    spans = asyncio.run(main())
    for rid in ("r1", "r2"):
        mine = [s for s in spans if s[3] == f"req:{rid}"]
        # the first frame's six, in order (one event-loop call); join
        # and decode come from the scheduler's thread where they close
        kinds = [s[0] for s in mine]
        assert sorted(kinds) == sorted(obs.REQUEST_STAGES)
        first_frame = [k for k in kinds if k not in ("req_join",
                                                     "req_decode")]
        assert first_frame == list(obs.REQUEST_STAGES[:6])
        assert kinds.index("req_join") < kinds.index("req_decode")
        assert all(s[4] == {"request_id": rid} for s in mine)
        at = {s[0]: (s[1], s[2]) for s in mine}
        # one after another: queue -> prefill -> emit
        assert at["req_queue"][1] == at["req_prefill"][0]
        assert at["req_prefill"][1] == at["req_emit"][0]
        # the three waits tile the queue: wake -> lane -> turn
        assert at["req_wake"][0] == at["req_queue"][0]
        assert at["req_wake"][1] == at["req_lane"][0]
        assert at["req_lane"][1] == at["req_turn"][0]
        assert at["req_turn"][1] == at["req_queue"][1]
        # and after the first token: join -> decode
        assert at["req_join"][0] == at["req_prefill"][1]
        assert at["req_join"][1] == at["req_decode"][0]
        assert at["req_decode"][1] >= at["req_decode"][0]
    kinds = {s[0] for s in spans}
    assert {"step", "prefill_dispatch", "decode_dispatch", "device_wait",
            "emit"} <= kinds


# ------------------------- the queue's three waits --------------------------


@pytest.mark.parametrize("lanes", [4, 1], ids=["free_lane", "no_free_lane"])
def test_queue_waits_sum_to_the_queue(lanes):
    """wake + lane + turn is queue for every request (check_stages), with
    a lane free at arrival and with the three sharing one."""

    async def main():
        eng = make_engine(max_num_seqs=lanes, prefill_chunk_tokens=48)
        seen = watch_stages(eng)
        outs = await asyncio.gather(
            serve(eng, 1), serve(eng, 2, n_prompt=100, max_tokens=12),
            serve(eng, 3, n_prompt=9))
        await settled(eng)
        check_stages(eng, seen, 3)
        check_life(eng, seen, [
            outs[int(slot.request.request_id[1:]) - 1] for slot, _ in seen])
        await eng.close()

    asyncio.run(main())


def test_a_taken_lane_is_a_lane_wait():
    """One lane, held by a decoding request: the next one is seen while it
    waits and admitted only when the lane is free; the holder itself was
    seen and admitted in one pass (one clock read: lane wait 0)."""

    async def main():
        eng = make_engine(max_num_seqs=1)
        seen = watch_stages(eng)
        first = asyncio.create_task(serve(eng, 0, max_tokens=48))
        while not seen:                          # r0 holds the one lane
            await asyncio.sleep(0.005)
        await serve(eng, 1)
        assert len(await first) == 48
        check_stages(eng, seen, 2)
        (r0, a0), (r1, a1) = seen
        assert a0[5] == 0.0                      # r0's lane wait
        assert r1.seen_t < r0.last_push_t <= r1.admitted_t
        assert a1[5] == r1.admitted_t - r1.seen_t > 0.0
        await eng.close()

    asyncio.run(main())


def test_a_prompt_behind_anothers_chunks_is_a_turn_wait():
    """One prefill program a step: a request that has its lane waits for
    its turn behind the chunks of the prompt before it."""

    async def main():
        eng = make_engine(max_prefill_seqs=1, prefill_chunk_tokens=48)
        seen = watch_stages(eng)
        await asyncio.gather(serve(eng, 0, n_prompt=100), serve(eng, 1))
        check_stages(eng, seen, 2)
        by_id = {slot.request.request_id: (slot, adds)
                 for slot, adds in seen}
        (r0, a0), (r1, a1) = by_id["r0"], by_id["r1"]
        assert r0.prefill_chunks > 1
        assert a0[5] == a1[5] == 0.0             # lanes were free
        # r1 had its lane before r0's prompt was done, and its first
        # chunk went out after r0's first token was in hand
        assert r1.admitted_t < r0.first_token_t <= r1.dispatched_t
        assert a1[6] == r1.dispatched_t - r1.admitted_t > 0.0
        await eng.close()

    asyncio.run(main())


class _Burst:
    """A dispatched burst's tokens as the engine holds them: `is_ready()`
    and, where a test hands it a real burst and a gate, a read-back that
    waits for the gate (and says so on `blocked`)."""

    def __init__(self, ready, arr=None, gate=None, blocked=None):
        self.ready, self.arr = ready, arr
        self.gate, self.blocked = gate, blocked

    def is_ready(self):
        if self.gate is None:
            return self.ready
        return self.gate.is_set() and self.arr.is_ready()

    def __array__(self, dtype=None, copy=None):
        if not self.gate.is_set():
            self.blocked.set()
            assert self.gate.wait(60.0), "the test never opened the gate"
        return np.asarray(self.arr)


def test_ahead_steps_count_the_bursts_that_are_not_ready():
    """`_stamp_dispatch` on a first chunk: the `k` of every dispatched
    burst whose tokens are not ready, once a request; the open
    prefill_dispatch phase carries steps and bursts."""
    eng = make_engine()
    eng._inflight.extend([
        {"burst": _Burst(True), "k": 8, "lanes": {}},
        {"burst": _Burst(False), "k": 4, "lanes": {}},
        {"burst": _Burst(False), "k": 8, "lanes": {}}])
    a, b, c = (types.SimpleNamespace(dispatched_t=0.0, ahead_steps=0)
               for _ in range(3))
    eng._stamp_dispatch((a,))
    assert a.ahead_steps == 12 and a.dispatched_t > 0.0
    t_a = a.dispatched_t
    eng._inflight.append({"burst": _Burst(False), "k": 8, "lanes": {}})
    with eng._phase("prefill_dispatch", rows=2) as ph:
        eng._stamp_dispatch((a, b))              # a's second chunk, b's first
    assert (a.ahead_steps, a.dispatched_t) == (12, t_a)
    assert b.ahead_steps == 20
    assert ph.attrs == {"rows": 2, "ahead_steps": 20, "ahead_bursts": 3}
    with eng._phase("prefill_dispatch") as ph:
        eng._stamp_dispatch((a, b))              # later chunks only
    assert ph.attrs is None
    for e in eng._inflight:
        e["burst"].ready = True
    eng._stamp_dispatch((c,))
    assert c.ahead_steps == 0 and c.dispatched_t > 0.0
    eng._inflight.clear()


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlapped", "lockstep"])
def test_ahead_steps_ride_the_first_frame(overlap):
    """`req_ahead_steps` is what the slots carried (check_stages), whole
    steps of real bursts; lockstep mode has no burst in flight at a
    prefill dispatch and reads 0."""

    async def main():
        eng = make_engine(overlap_scheduling=overlap)
        seen = watch_stages(eng)
        await serve_mix(eng)
        check_stages(eng, seen, 5)
        m = dict(eng.metrics)
        await eng.close()
        return m

    m = asyncio.run(main())
    assert isinstance(m["req_ahead_steps"], int)
    if not overlap:
        assert m["req_ahead_steps"] == 0


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlapped", "lockstep"])
def test_an_arrival_during_the_read_back_goes_out_in_that_step(overlap):
    """Read back first, admit after: a request that arrives while the
    scheduler thread stands in a burst's read-back is seen and admitted
    by the admission pass of THAT step, its first chunk is dispatched
    before the step's decode burst, and the burst is an interleave one;
    `req_admitted_after_wait` counts it and neither the request that
    came to an idle engine before it nor the one after it.  Lockstep
    reads back after its dispatch: the same arrival waits for the next
    step and the counter stays 0.  The three waits still sum to the
    queue, request by request (check_stages)."""

    async def main():
        eng = make_engine(overlap_scheduling=overlap, max_blocks_per_seq=200)
        gate, blocked = threading.Event(), threading.Event()
        gate.set()
        build, step = eng._build_burst, eng._sched_step
        marks = []            # len(fpm) at the start of each step

        def held_burst(active, k):
            burst, cont = build(active, k)
            return _Burst(False, burst, gate, blocked), cont

        def marked_step():
            marks.append(len(eng.fpm))
            step()

        eng._build_burst, eng._sched_step = held_burst, marked_step
        seen = watch_stages(eng)
        first = asyncio.create_task(serve(eng, 0, max_tokens=600))
        await until(lambda: eng.metrics["decode_tokens"] >= 40,
                    "r0 never reached a decode-only stretch")
        gate.clear()
        await until(blocked.is_set, "no read-back ever met the shut gate")
        at = len(marks) - 1                      # the step that stands
        assert eng.metrics["req_admitted_after_wait"] == 0
        second = asyncio.create_task(serve(eng, 1))
        await until(lambda: len(eng.waiting) == 1, "r1 never queued")
        r1 = eng.waiting[0]
        assert r1.seen_t == 0.0                  # nobody has looked yet
        gate.set()
        assert len(await second) == 8
        first.cancel()
        with pytest.raises(asyncio.CancelledError):
            await first
        await settled(eng)       # r0's last bursts read back, not in wait
        await serve(eng, 2)                      # to an idle engine again
        await settled(eng)
        check_stages(eng, seen, 3)
        recs = list(eng.fpm)
        in_step = [[(r["kind"], r.get("k")) for r in recs[a:b]]
                   for a, b in zip(marks[at:at + 2], marks[at + 1:at + 3])]
        assert r1.seen_t == r1.admitted_t > 0.0  # seen and admitted at once
        if overlap:
            assert in_step[0] == [("prefill", None),
                                  ("decode", JaxEngine.INTERLEAVE_BURST)]
            assert r1.ahead_steps <= eng.config.decode_fused_steps
            assert eng.metrics["req_admitted_after_wait"] == 1
        else:
            # the burst went out before its read; the chunk a step later
            assert [kind for kind, _ in in_step[0]] == ["decode"]
            assert in_step[1][0] == ("prefill", None)
            assert r1.ahead_steps == 0
            assert eng.metrics["req_admitted_after_wait"] == 0
        await eng.close()

    asyncio.run(main())


# ------------------------- after the first token ----------------------------


@pytest.mark.parametrize("max_tokens", [1, 2, 3])
def test_join_needs_a_second_token_and_decode_a_third(max_tokens):
    async def main():
        eng = make_engine()
        seen = watch_stages(eng)
        out = await serve(eng, 0, max_tokens=max_tokens)
        await settled(eng)
        check_stages(eng, seen, 1)
        check_life(eng, seen, [out])
        m = dict(eng.metrics)
        await eng.close()
        return out, m

    out, m = asyncio.run(main())
    assert len(out) == max_tokens
    assert m["req_join_n"] == (1 if max_tokens >= 2 else 0)
    assert (m["req_stage_s.join"] > 0.0) == (max_tokens >= 2)
    assert m["req_decode_tokens"] == max(max_tokens - 2, 0)
    assert (m["req_stage_s.decode"] > 0.0) == (max_tokens >= 3)


def test_decode_tokens_are_the_tokens_after_the_second():
    """Over a mix: `req_decode_tokens` = sum of (generated - 2), and each
    request's stages add up to its life, enqueue to finish frame."""

    async def main():
        eng = make_engine(prefill_chunk_tokens=48)
        seen = watch_stages(eng)
        outs = await serve_mix(eng)
        await settled(eng)
        check_stages(eng, seen, 5)
        by_id = {f"r{i + 1}": o for i, o in enumerate(outs)}
        check_life(eng, seen, [by_id[slot.request.request_id]
                               for slot, _ in seen])
        assert eng.metrics["req_decode_tokens"] == 6 + 10 + 6 + 18 + 6
        await eng.close()

    asyncio.run(main())


def test_life_stages_count_a_preempted_request_once():
    async def main():
        # 3 x (8 prompt blocks + 6 of output) do not fit 30 blocks
        eng = make_engine(num_blocks=30)
        seen = watch_stages(eng)
        outs = await asyncio.gather(
            *[serve(eng, i, max_tokens=24) for i in range(3)])
        await settled(eng)
        assert eng.metrics["preemptions"] >= 1
        check_stages(eng, seen, 3)
        check_life(eng, seen, [outs[int(slot.request.request_id[1:])]
                               for slot, _ in seen])
        assert eng.metrics["req_join_n"] == 3
        assert eng.metrics["req_decode_tokens"] == 3 * 22
        await eng.close()

    asyncio.run(main())


def test_life_stages_leave_out_a_request_cancelled_in_the_queue():
    async def main():
        eng = make_engine(max_num_seqs=1)
        seen = watch_stages(eng)
        first = asyncio.create_task(serve(eng, 0, max_tokens=48))
        while not seen:                          # r0 holds the one slot
            await asyncio.sleep(0.005)
        waiting = asyncio.create_task(serve(eng, 1))
        while not eng.waiting:
            await asyncio.sleep(0.005)
        waiting.cancel()
        with pytest.raises(asyncio.CancelledError):
            await waiting
        outs = [await first, await serve(eng, 2)]
        await settled(eng)
        check_stages(eng, seen, 2)               # r0 and r2, not r1
        check_life(eng, seen, outs)
        assert eng.metrics["req_join_n"] == 2
        assert eng.metrics["req_decode_tokens"] == 46 + 6
        await eng.close()

    asyncio.run(main())


# ------------------------- names --------------------------------------------


def watched_programs(eng):
    for value in vars(eng).values():
        for wp in (value.values() if isinstance(value, dict) else [value]):
            if isinstance(wp, WatchedProgram):
                yield wp


def test_named_gives_the_jit_module_its_family_name():
    fn = CompileWatch.named(partial(lambda a, x: x * a, 2.0), "decode_multi")
    assert fn.__name__ == "dyn_decode_multi"
    text = jax.jit(fn).lower(jnp.ones(3)).as_text()
    assert "module @jit_dyn_decode_multi" in text
    assert float(jax.jit(fn)(jnp.ones(3))[0]) == 2.0


@pytest.mark.parametrize("extra", [{}, {"spec_decode": "ngram"}],
                         ids=["plain", "spec"])
def test_every_watched_program_is_named_after_its_family(extra):
    async def main():
        eng = make_engine(**extra)
        eng._topk_jit()
        eng._topk_wide_jit()
        await serve(eng, 0)
        programs = list(watched_programs(eng))
        events = [dict(e) for e in eng.compile_watch.events]
        await eng.close()
        return programs, events

    programs, events = asyncio.run(main())
    families = {wp.family for wp in programs}
    assert {"decode", "decode_multi", "prefill", "prefill_batched",
            "prefill_packed", "inject", "gather", "decode_topk",
            "decode_topk_wide"} <= families
    for wp in programs:
        assert wp.fn.__name__ == PROGRAM_PREFIX + wp.family
        # trace reductions call a module prefill by this word alone
        assert ("prefill" in wp.family) == (wp.family in PREFILL_FAMILIES)
    assert events and all(e["family"] in families for e in events)


@pytest.fixture(scope="module")
def plain_outputs():
    async def main():
        eng = make_engine()
        outs = await serve_mix(eng)
        await eng.close()
        return outs

    return asyncio.run(main())


@pytest.mark.parametrize("sink", ["tracer", "profiler", "both"])
def test_jax_engine_bit_identical_with_spans_on(sink, plain_outputs,
                                                tmp_path):
    """Greedy outputs do not depend on who listens to the phases (the
    JAX-engine twin of test_mock_engine_bit_identical_with_tracing_on)."""

    async def main():
        eng = make_engine()
        tr = (obs.Tracer(out_path=str(tmp_path / "trace.json")).install()
              if sink != "profiler" else None)
        if sink != "tracer":
            jax.profiler.start_trace(str(tmp_path),
                                     profiler_options=profiler_options())
        try:
            outs = await serve_mix(eng)
        finally:
            if sink != "tracer":
                jax.profiler.stop_trace()
            if tr is not None:
                tr.uninstall()
        await eng.close()
        return outs, tr

    outs, tr = asyncio.run(main())
    assert outs == plain_outputs
    if tr is not None:
        assert {"step", "decode_dispatch", *obs.REQUEST_STAGES} <= {
            s[0] for s in tr.spans}
