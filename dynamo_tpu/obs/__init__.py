"""Timeline tracing plane: span-attributed engine steps + flight recorder.

The FPM deque records per-dispatch aggregates, but no record decomposes
a scheduler step into host-schedule / device-wait / sample / detokenize /
frame-egress time, and nothing stitches a request's journey across
frontend → router → prefill worker → disagg pull → decode worker.
This module is that decomposition: named spans on every engine phase
and stamps along a request's life, exported three ways, reduced to
ROADMAP item-3's scoreboard by :mod:`dynamo_tpu.obs.report`.

Design:

  * **One call site per engine phase, three sinks** (`PhaseClock`, one
    per engine: ``with engine._phase("device_wait", what=...)``).
    (1) Counters, always on: ``engine.metrics["host_s.<kind>"]`` gains
    the phase's self seconds (those spent in no phase nested inside
    it; ``host_s.step`` counts whole steps, so the kinds partition the
    steps' wall time) and ``["host_n.<kind>"]`` gains 1.  They ride
    wherever ``dict(engine.metrics)`` already goes.  (2) The profiler's
    clock, whenever a ``jax.profiler`` session is live (the benchmark's
    ``--trace 1`` run, an operator's ``/debug/profile`` capture): a
    TraceMe named ``dyn.<kind>`` with the phase's attributes, on the
    thread's line of ``/host:CPU`` — the same axis as ``XLA Modules``
    and ``XLA Ops``, no environment variable.  (3) The ring, when a
    `Tracer` is installed.  With no session and no `Tracer` a phase is
    two clock reads, two dict adds, one small handle and one float
    compare against ``PAUSE_S``: no lock, no span record, nothing per
    token.

  * **The loop's whole wall time.**  Three kinds tile the scheduler
    loop's wall time (engine/core.py `_loop`; tier-1: within 2 %):
    ``step``; ``hop``, from one step's end to the next step's start
    while the engine did not go empty in between; ``idle``, the
    empty-engine wait.  ``host_s.hop`` is taken on the step's own
    thread from the two clock reads that close one ``step`` and open
    the next (both thread hand-offs and the event loop's backlog are
    inside it; no clock read of its own) and counts whole, like
    ``step``: the scheduler calls drained between two steps keep their
    own phases and are not subtracted.  The event-loop thread cannot
    see a hop's beginning (its ``to_thread`` future resolves behind
    whatever the loop had queued), so its ``with _phase("hop")`` adds
    nothing to the counter and is the ``dyn.hop`` TraceMe / ring span
    of the part it sees: the one place where counter and event differ.
    What the three leave out is the loop's way into and out of an idle
    wait.  Cost with no listener: two dict adds a step for ``hop``, one
    handle an idle wait.

  * **A pause names itself.**  A phase of any kind but ``step`` and
    ``idle``, or a hop, that outlasts ``PAUSE_S`` (0.5 s) adds itself to
    ``host_s.pause`` / ``host_n.pause``, appends one record to
    ``engine.pauses`` (a deque of 64; keys ``PAUSE_KEYS``), logs one
    ``pause {json}`` warning and asks the flight recorder for the ring.
    The record says what stood behind the wait at the moment it ended:
    ``inflight`` bursts dispatched and ``ready_behind`` of them ready
    (the engine's callback; after a ``burst_fetch``, which pops the
    OLDEST burst, all ready = the chip ran on and the host was late to
    hear of it, none ready = the chip itself stood; after a
    ``prefill_first`` the bursts were dispatched AHEAD of the prompt and
    prove nothing; that wait carries ``programs``, the prompt's chunk
    programs, and is held to ``PAUSE_S`` for each), and the step thread's and the process's CPU seconds
    across the wait (near 0 of both across a 2 s wait = asleep in the
    runtime; the wait's length in thread CPU = spinning or holding the
    interpreter; process far above thread = another thread was busy: a
    compile, the collector, a profile being written).  The two clocks
    are read where a ``device_wait`` of ``what`` ``burst_fetch`` or
    ``prefill_first`` opens (``CPU_TIMED_WAITS``) and a record of any
    other kind carries None: ``time.thread_time()`` +
    ``time.process_time()`` cost 12.0 us a pair on the chip's host
    (0.5 us on a plain Linux; PERF.md section 6, PR 53), too much for
    every step's opening.  A phase
    whose span holds a compile event is no pause: the compile watch has
    named that wait.  Cost with no listener: that pair of reads a
    ``burst_fetch`` / ``prefill_first`` (about one a step), one float
    compare a phase.

  * **Request stages, always on.**  Eight stamps a request, each set
    once where the work happens (engine/core.py): enqueued; seen by the
    scheduler thread (the first ``_admit_waiting`` pass that finds it
    waiting); admitted (it has its lane and blocks); first prefill
    chunk dispatched; first token in the host's hands; first frame put
    on the stream by the event loop; second token (the lane's first out
    of a decode burst); finish frame.  Summed into
    ``req_stage_s.queue`` / ``.prefill`` / ``.emit`` (seconds) over
    ``req_stage_n`` (requests whose first token was emitted), whose sum
    is the engine's time to first token exactly; ``req_stage_s.wake`` +
    ``.lane`` + ``.turn``, the three waits that ARE the queue, request
    by request; ``req_ahead_steps``, the decode steps dispatched and
    not yet ready on the device when the first chunk went out (a step
    reads back FIRST and admits AFTER, and leaves one burst behind the
    one that runs: a first chunk stands behind 8 steps where it stood
    behind 16-28; ``req_admitted_after_wait`` counts the requests that
    arrived during a step's read-back and were admitted by that same
    step; PERF.md section 6, PR 39);
    ``req_stage_s.join`` (first to second token) over ``req_join_n``;
    ``req_stage_s.decode`` (second token to finish) over
    ``req_decode_tokens`` (the tokens after the second of the requests
    that ran to their end).  What it costs with no listener: an
    admission pass reads the clock once more when it finds a new
    request or admits one (two reads a request at most), a first-chunk
    dispatch asks up to ``decode_pipeline_depth`` in-flight bursts
    ``is_ready()`` (0.3 us a call on a v5e: PERF.md section 6, PR 38),
    a token costs one integer compare and no clock read of its own.  Under a `Tracer` the stages are also ring spans
    (``REQUEST_STAGES``) on the track ``req:<request_id>``; they cross
    threads, so they are not TraceMes.  A profiler capture gets
    ``ahead_steps`` / ``ahead_bursts`` on the ``dyn.prefill_dispatch``
    event of a request's first chunk.

  * **Module-global None check when the ring is off.**  The helpers for
    everything that is not an engine phase (`begin()`, `end()`,
    `span()`: frontend, workers, pulls, the mocker) start with
    ``if _TRACER is None`` and allocate NOTHING on that branch:
    `begin()` returns the shared float ``0.0``, `span()` returns one
    process-wide no-op context manager.

  * **Thread-safe ring.**  Spans append to a bounded deque from both
    the scheduler thread and the event loop; the ring IS the flight
    recorder — `flight_dump()` snapshots the last N spans when a chaos
    seam fires or a drain/abort/migration triggers, so a post-mortem
    always has the timeline that led up to the fault.

  * **Logical tracks.**  A span records the current thread name unless
    the caller pins a `track`.  Engine steps pin ``sched:<engine-id>``
    (the step runs on whichever pool thread `asyncio.to_thread` picked,
    but it is ONE logical timeline — the step lock serializes it), so
    the report's innermost-span attribution sees a well-nested track.

  * **Cross-process stitching.**  Request-scoped spans carry the
    `trace_id` the frontend minted (or received via W3C `traceparent`)
    and propagated through request annotations
    (frontend/request_trace.py) — one trace_id joins the frontend's
    `request_end` record, its `request` span, and every worker's
    `worker_request` / pull spans for that request.

Span vocabulary (kind — where — what the time is):

  step             engine _sched_step / mocker _step: one scheduler
                   iteration end to end
  hop              engine _loop: from a step's return to the next
                   step's call while the engine did not go empty (the
                   event-loop thread's line; the ``host_s.hop`` counter
                   runs from step end to step start on the step's own
                   thread and holds the hand-offs too)
  idle             engine _loop: the empty engine waits to be woken
                   (on the event-loop thread; never a pause)
  sched            host scheduling: cancellations, KVBM offload sweep,
                   admission (allocation + prefix match) — emitted only
                   when the device had nothing in flight (the host time
                   the device actually waited on)
  enqueue_ahead    the same host scheduling/dispatch-build work when it
                   runs WHILE the device is still executing in-flight
                   work (overlap_scheduling): the overlapped scheduler's
                   step-N+1 build during step N.  Counted as its own
                   phase so the wall partition stays exact, and excluded
                   from the report's sched_overhead_frac — the device
                   never waited on it
  prefill_dispatch building + dispatching one prefill program (packed /
                   batched / B=1 / ring), including its FPM accounting;
                   a request's first chunk adds ``ahead_steps`` /
                   ``ahead_bursts`` (decode work dispatched before it
                   and not ready yet)
  decode_dispatch  building + dispatching one decode burst; attrs carry
                   ``cont`` (device-resident continuation vs full
                   upload), ``k``, ``lanes``
  device_wait      host blocked on a device fetch (burst readback,
                   prefill first-token sync, KVBM gather); a first-token
                   sync carries ``programs``, the chunk programs of the
                   prompt it stands behind; on the mocker, the simulated
                   device step sleep
  spec_dispatch    proposing drafts and dispatching one packed
                   spec-verify program
  sample           host-side token acceptance: spec-decode rejection
                   sampling, guided-decoding candidate selection
  emit             applying one read-back program's tokens: stream
                   frames, finishes, block commits (``what``: burst |
                   prefill_first)
  audit            the KV ledger's reconciliation sweep inside a step
  req_queue / req_prefill / req_emit
                   one request's stages to its first token (ring only,
                   track ``req:<request_id>``, attr ``request_id``):
                   enqueued -> first prefill chunk dispatched -> first
                   token in hand -> first frame on the stream
  req_wake / req_lane / req_turn
                   the three waits inside ``req_queue``, end to end:
                   enqueued -> seen by the scheduler thread -> lane and
                   blocks in hand -> first chunk dispatched (same track)
  req_join / req_decode
                   first token -> second token (the lane's first out of
                   a decode burst) -> finish frame; recorded where they
                   close, a one-token request has neither (same track)
  detok            incremental detokenization of one engine output
  frame_egress     writing one SSE frame to the client socket
  request          frontend: one HTTP request end to end (trace_id)
  worker_request   worker: serving one generate() stream (trace_id)
  kv_pull          decode engine: one whole disagg KV pull
  disagg_open/disagg_chunk
                   receiver-paced pull ops on the wire (tier 3)
  kvbm_offload     one batched G1→G2 offload sweep
  kvbm_onboard     one G2/G3/G4→G1 onboard scatter

What needs what: the counters and the ``dyn.*`` phases in a profiler
capture need nothing.  ``DYN_TRACE=1`` is needed only for the ring: the
Chrome dump, the flight recorder, ``dynamo_trace_span_seconds`` and the
per-request spans.

Env vocabulary (the request-trace config style):

    DYN_TRACE=1            install a process tracer at main() startup
    DYN_TRACE_OUT=path     Chrome trace JSON dump target; ``{pid}``
                           expands so multi-process fleets don't
                           clobber each other; dumped at exit and by
                           the flight recorder (sibling files)
    DYN_TRACE_RING=N       ring capacity in spans (default 16384)

Load a dump in Perfetto (https://ui.perfetto.dev) or chrome://tracing;
`python -m dynamo_tpu.obs.report <dump...>` reduces it to the
gap-attribution numbers.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

logger = logging.getLogger(__name__)

DEFAULT_RING = 16384

# span kinds the engine-step partition is scored on (report.py groups
# everything else under its own name); kept here so engine, mocker and
# report agree on the vocabulary
STEP_PHASES = ("sched", "enqueue_ahead", "prefill_dispatch",
               "decode_dispatch", "spec_dispatch", "device_wait", "sample",
               "emit", "audit")

# the scheduler loop's time outside a step (engine/core.py _loop): the
# empty-engine wait and the way from one step to the next; with `step`
# they tile the loop's wall time
LOOP_PHASES = ("idle", "hop")

# a phase (any kind but `step` and `idle`) or a hop that took longer names
# itself (PhaseClock.pause).  Every cell's longest honest wait for ONE
# program is under 0.3 s (a 2048-token prefill 155-230 ms, a burst of
# passes 104 ms), the pauses on record are 1.6-3.8 s; a wait that says it
# stood behind several `programs` is held to this many seconds for each
# (a 12288-token prompt sent to an idle engine waits 0.5-1.4 s for its
# first token, honestly: PERF.md section 6, PR 53)
PAUSE_S = 0.5

# the `device_wait` kinds (`what`) that read the thread's and the
# process's CPU clocks as they open, so that a pause there can say whether
# the thread slept or span: the two waits a step makes as a rule.  Not
# every step or phase: the pair costs 12 us on the chip's host (PERF.md
# section 6, PR 53; 0.5 us on a plain Linux)
CPU_TIMED_WAITS = ("burst_fetch", "prefill_first")

# the keys of one `engine.pauses` record, a closed set (PhaseClock.pause
# fills them in this order)
PAUSE_KEYS = ("t", "kind", "what", "seconds", "k", "inflight",
              "ready_behind", "step_thread_cpu_s", "step_process_cpu_s")

# the stages of one request's life (engine/core.py _emit_first,
# _push_token): queue -> prefill -> emit are its time to first token,
# wake -> lane -> turn the three waits inside the queue, join and decode
# what follows the first token; ring spans only, one track per request id
REQUEST_STAGES = ("req_queue", "req_wake", "req_lane", "req_turn",
                  "req_prefill", "req_emit", "req_join", "req_decode")

# THE canonical span vocabulary (the docstring table above, plus the
# compile watchdog's span): every obs.span()/obs.end() call site names
# one of these, and the DYN006 lint (lint/rules.py) checks the literals
# statically — a typo'd kind would otherwise produce an orphan span the
# report buckets under its own name and no dashboard ever joins on.
# Extend this set and the docstring table together when adding a kind.
SPAN_KINDS = frozenset(STEP_PHASES + LOOP_PHASES
                       + REQUEST_STAGES) | frozenset({
    "step",
    "detok",
    "frame_egress",
    "request",
    "worker_request",
    "kv_pull",
    "disagg_open",
    "disagg_chunk",
    "kvbm_offload",
    "kvbm_onboard",
    "compile",  # obs/compile_watch.py COMPILE_KIND
})

# ---------------------------------------------------------------------------
# span record: a plain tuple, cheapest thing that can ride a deque
#   (kind, t0, t1, track, attrs|None, trace_id|None)
SpanTuple = Tuple[str, float, float, str, Optional[dict], Optional[str]]


class Tracer:
    """A bounded in-process span ring with Chrome-trace export.

    Install process-globally with ``with tracer:`` (or
    install()/uninstall()); the module helpers are no-ops while no
    tracer is installed."""

    def __init__(self, service: str = "dynamo", ring: int = DEFAULT_RING,
                 out_path: Optional[str] = None):
        self.service = service
        self.spans: "deque[SpanTuple]" = deque(maxlen=max(16, ring))
        self.out_path = out_path
        # monotonic epoch for ts=0, plus the unix time it corresponds to
        # so dumps from different processes can be coarsely aligned
        self._t0 = time.monotonic()
        self._epoch_unix_ms = time.time() * 1000.0
        self._lock = threading.Lock()
        self._metrics = None
        # flight-recorder rate limit: one dump per reason per cooldown
        self._flight_last: Dict[str, float] = {}
        self.flight_cooldown_s = 1.0
        self.flight_dumps: List[str] = []  # paths written (post-mortems)

    # -- recording --------------------------------------------------------
    def record(self, kind: str, t0: float, t1: float,
               attrs: Optional[dict] = None, trace_id: Optional[str] = None,
               track: Optional[str] = None) -> None:
        span = (kind, t0, t1,
                track or threading.current_thread().name, attrs, trace_id)
        with self._lock:
            self.spans.append(span)
        m = self._metrics
        if m is not None:
            try:
                m.observe("dynamo_trace_span_seconds", t1 - t0, kind=kind)
            except Exception:  # observability must never take down serving
                logger.warning("trace span metric failed", exc_info=True)
                self._metrics = None

    def bind_metrics(self, metrics) -> "Tracer":
        """Register the per-span-kind duration histogram on a
        MetricsHierarchy so `/metrics` on the system status server
        exposes phase latencies next to the engine gauges."""
        metrics.histogram(
            "dynamo_trace_span_seconds",
            "duration of timeline-tracer spans by kind", ("kind",),
            buckets=(1e-5, 1e-4, 2.5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2,
                     2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 5.0))
        self._metrics = metrics
        return self

    # -- chrome trace export ----------------------------------------------
    def chrome_trace(self, spans=None) -> Dict[str, Any]:
        """Chrome trace-format JSON (Perfetto/chrome://tracing loadable):
        one "X" complete event per span, one metadata event per track,
        events sorted by start ts."""
        with self._lock:
            spans = list(self.spans) if spans is None else list(spans)
        pid = os.getpid()
        tids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"{self.service}:{pid}"},
        }]
        rows: List[Dict[str, Any]] = []
        for kind, t0, t1, track, attrs, trace_id in spans:
            tid = tids.get(track)
            if tid is None:
                tid = tids[track] = len(tids) + 1
                events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": track},
                })
            args: Dict[str, Any] = dict(attrs) if attrs else {}
            if trace_id is not None:
                args["trace_id"] = trace_id
            rows.append({
                "name": kind, "cat": "dynamo", "ph": "X", "pid": pid,
                "tid": tid,
                "ts": round((t0 - self._t0) * 1e6, 3),
                "dur": round(max(t1 - t0, 0.0) * 1e6, 3),
                "args": args,
            })
        # sorted by start time: nested spans were appended at their END,
        # so ring order is t1 order — viewers and the report both want
        # per-track monotonic start ts
        rows.sort(key=lambda e: e["ts"])
        return {
            "traceEvents": events + rows,
            "displayTimeUnit": "ms",
            "otherData": {
                "service": self.service,
                "pid": pid,
                "epoch_unix_ms": round(self._epoch_unix_ms, 3),
            },
        }

    def resolve_out_path(self) -> Optional[str]:
        if not self.out_path:
            return None
        return self.out_path.replace("{pid}", str(os.getpid()))

    def dump(self, path: Optional[str] = None) -> Optional[str]:
        """Write the ring as Chrome trace JSON; returns the path (None
        when no target is configured)."""
        path = path or self.resolve_out_path()
        if path is None:
            return None
        try:
            with open(path, "w") as f:
                json.dump(self.chrome_trace(), f)
        except OSError:
            logger.warning("trace dump to %r failed", path, exc_info=True)
            return None
        return path

    def flight_dump(self, reason: str) -> Optional[str]:
        """Flight recorder: dump the last-N-spans ring next to the
        configured trace output (or the cwd) when a fault fires.
        Rate-limited per reason so a storm of injected frame drops
        doesn't grind serving into file I/O."""
        now = time.monotonic()
        last = self._flight_last.get(reason, 0.0)
        if now - last < self.flight_cooldown_s:
            return None
        self._flight_last[reason] = now
        safe = "".join(c if (c.isalnum() or c in "._-") else "-"
                       for c in reason)
        base = self.resolve_out_path()
        d = os.path.dirname(base) if base else "."
        path = os.path.join(d or ".",
                            f"dynflight-{safe}-{os.getpid()}.json")
        out = self.dump(path)
        if out is not None:
            self.flight_dumps.append(out)
            logger.warning("flight recorder dumped %d spans to %s (%s)",
                           len(self.spans), out, reason)
        return out

    # -- install ----------------------------------------------------------
    def install(self) -> "Tracer":
        global _TRACER
        _TRACER = self
        return self

    def uninstall(self) -> None:
        global _TRACER
        if _TRACER is self:
            _TRACER = None

    def __enter__(self) -> "Tracer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


_TRACER: Optional[Tracer] = None

# the forensics plane's hop vocabulary, re-exported here so call sites
# (and the DYN012 lint) address it as ``obs.HOP_KINDS`` — the same
# one-registry pattern as SPAN_KINDS above (forensics.py is stdlib-only,
# so this import stays cheap for the lint's registry load)
from .forensics import HOP_KINDS  # noqa: E402


def tracer() -> Optional[Tracer]:
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


# -- hot-path helpers --------------------------------------------------------
# begin()/end() is the zero-allocation pair for the scheduler loop: the
# disabled branch returns the shared 0.0 and end() drops a 0.0 handle even
# if a tracer appeared mid-span (a span must never report a bogus start).


def begin() -> float:
    """Span start handle: a monotonic timestamp, or 0.0 when disabled."""
    return time.monotonic() if _TRACER is not None else 0.0


def end(kind: str, t0: float, track: Optional[str] = None,
        trace_id: Optional[str] = None, **attrs) -> None:
    """Record [t0, now) as one span.  No-op when disabled or when the
    span began disabled (t0 == 0.0)."""
    tr = _TRACER
    if tr is None or t0 == 0.0:
        return
    tr.record(kind, t0, time.monotonic(), attrs or None, trace_id, track)


class _NullSpan:
    """Shared no-op context manager: span() allocates nothing when
    tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("kind", "track", "trace_id", "attrs", "_t0")

    def __init__(self, kind: str, track: Optional[str],
                 trace_id: Optional[str], attrs: Optional[dict]):
        self.kind = kind
        self.track = track
        self.trace_id = trace_id
        self.attrs = attrs
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        tr = _TRACER
        if tr is not None and self._t0:
            tr.record(self.kind, self._t0, time.monotonic(), self.attrs,
                      self.trace_id, self.track)
        return False


def span(kind: str, track: Optional[str] = None,
         trace_id: Optional[str] = None, **attrs):
    """Context-manager span for non-hot paths (frontend, pulls, KVBM).
    Returns the shared no-op when tracing is disabled."""
    if _TRACER is None:
        return _NULL_SPAN
    return _Span(kind, track, trace_id, attrs or None)


# -- engine phases: one call site, three sinks --------------------------------
# (the module docstring's first design point: counter always, `dyn.<kind>`
# TraceMe while a jax.profiler session is live, ring span under a Tracer)


class _Phase:
    """One open phase (the handle `with clock(kind) as ph` yields)."""

    __slots__ = ("clock", "kind", "attrs", "ring", "t0", "child_s", "tm",
                 "cpu0")

    def __init__(self, clock: "PhaseClock", kind: str,
                 attrs: Optional[dict]):
        self.clock = clock
        self.kind = kind
        self.attrs = attrs
        self.ring = True
        self.child_s = 0.0
        self.tm = None
        self.cpu0 = None

    def set(self, **attrs) -> None:
        """Attributes known only once the phase's work is done (a burst's
        `k`, a dispatch's row count)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        if self.tm is not None:
            self.tm.set_metadata(**attrs)

    def off_ring(self) -> None:
        """The phase ran but dispatched nothing: its time still counts,
        the ring gets no span (a span there stands for one dispatch)."""
        self.ring = False

    def __enter__(self) -> "_Phase":
        clock = self.clock
        if clock.trace_me.is_enabled():      # a profiler session is live
            self.tm = clock.trace_me("dyn." + self.kind,
                                     **(self.attrs or {}))
            self.tm.__enter__()
        clock.open.append(self)
        self.t0 = t0 = time.monotonic()
        kind = self.kind
        if kind == "step":
            clock.step_opens(t0)
        elif kind == "idle":
            clock.left_t = 0.0           # an idle wait is not a hop
        elif kind == "device_wait" and self.attrs \
                and self.attrs.get("what") in CPU_TIMED_WAITS:
            self.cpu0 = (time.thread_time(), time.process_time())
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        clock = self.clock
        dur = t1 - self.t0
        clock.open.pop()
        if clock.open:
            clock.open[-1].child_s += dur
        kind = self.kind
        if kind == "hop":
            # the event-loop thread's line of a hop: an event only; the
            # counter is the step thread's, end of step to start of step
            # (PhaseClock.step_opens)
            own = 0.0
        else:
            # `step`, like `hop`, counts whole; every other kind its self
            # time (the seconds in no phase nested inside it), so the
            # kinds partition the steps' wall time
            if kind == "step":
                own = dur
                clock.left_t = t1
            else:
                own = dur - self.child_s
            m = clock.metrics
            ks, kn = clock.keys[kind]
            m[ks] = m.get(ks, 0.0) + own
            m[kn] = m.get(kn, 0) + 1
        if self.tm is not None:
            self.tm.__exit__(*exc)
        tr = _TRACER
        if tr is not None and self.ring:
            tr.record(kind, self.t0, t1, self.attrs, None, clock.track)
        if own > PAUSE_S and kind != "step" and kind != "idle":
            clock.pause(kind, self.attrs, self.t0, t1, own, self.cpu0)
        return False


class PhaseClock:
    """An engine's phase timer: ``with clock("device_wait", what=...)``.
    One per engine.  The stack of open phases needs no lock: a step and
    the scheduler calls between steps run on one pool thread at a time
    (the step lock serializes them), and ``idle`` and the event-loop
    thread's line of ``hop`` are opened by the scheduler loop itself
    while no step is in flight, when it is the only opener.

    ``step``, ``hop`` and ``idle`` tile the loop's wall time (the module
    docstring's third design point).  The ``hop`` COUNTER runs on the
    step's own thread from the clock read that closed one ``step`` to
    the one that opens the next (`step_opens`); ``with clock("hop")`` on
    the event-loop thread adds nothing to it and is the ``dyn.hop``
    TraceMe / ring span of the part that thread sees (a TraceMe cannot
    cross threads): the one place where counter and event differ.

    `behind()` -> (programs in flight, those whose result is ready) and
    `compiles` (the compile watch's event ring) are the engine's; they
    are asked only when a phase ran longer than ``PAUSE_S`` (`pause`)."""

    __slots__ = ("metrics", "track", "open", "keys", "trace_me", "left_t",
                 "behind", "compiles", "pauses")

    def __init__(self, metrics: dict, track: Optional[str] = None,
                 behind: Optional[Callable[[], Tuple[int, int]]] = None,
                 compiles: Optional[Iterable[dict]] = None):
        self.metrics = metrics
        self.track = track
        self.open: List[_Phase] = []
        self.keys = {k: (f"host_s.{k}", f"host_n.{k}") for k in SPAN_KINDS}
        # imported here: obs is also loaded by processes that never
        # touch JAX (frontend, lint), an engine always has
        from jax.profiler import TraceAnnotation

        self.trace_me = TraceAnnotation
        self.left_t = 0.0     # when the last step closed; 0.0 after idle
        self.behind = behind
        self.compiles = compiles if compiles is not None else ()
        self.pauses: "deque[dict]" = deque(maxlen=64)
        for kind in ("step",) + STEP_PHASES + LOOP_PHASES + ("pause",):
            metrics.setdefault(f"host_s.{kind}", 0.0)
            metrics.setdefault(f"host_n.{kind}", 0)

    def __call__(self, kind: str, **attrs) -> _Phase:
        return _Phase(self, kind, attrs or None)

    def step_opens(self, t0: float) -> None:
        """A `step` opens at `t0`: close the hop that led to it (none
        after an idle wait or before the first step)."""
        left = self.left_t
        if left:
            hop = t0 - left
            m = self.metrics
            m["host_s.hop"] += hop
            m["host_n.hop"] += 1
            if hop > PAUSE_S:
                self.pause("hop", None, left, t0, hop)

    def pause(self, kind: str, attrs: Optional[dict], t0: float, t1: float,
              seconds: float,
              cpu0: Optional[Tuple[float, float]] = None) -> None:
        """A phase (or a hop) of more than ``PAUSE_S``: count it, keep a
        record with the closed key set ``PAUSE_KEYS``, say so once in the
        log and dump the ring.  Not where a compile event lies inside
        its span: that wait has a name already.  Nor where the phase
        says that it stood behind several `programs` by construction (a
        long prompt's first token behind all of its chunks) and took
        less than ``PAUSE_S`` for each.  `cpu0`: the thread's and the
        process's CPU clocks at the phase's opening, where it read them
        (``CPU_TIMED_WAITS``)."""
        attrs = attrs or {}
        if seconds <= PAUSE_S * attrs.get("programs", 1) or any(
                t0 <= e["t"] <= t1 for e in list(self.compiles)):
            return
        m = self.metrics
        m["host_s.pause"] += seconds
        m["host_n.pause"] += 1
        inflight, ready = self.behind() if self.behind is not None else (0, 0)
        thread_s = process_s = None
        if cpu0 is not None:     # closed on the thread that opened it
            thread_s = round(time.thread_time() - cpu0[0], 6)
            process_s = round(time.process_time() - cpu0[1], 6)
        rec = dict(zip(PAUSE_KEYS, (
            t1, kind, attrs.get("what", ""), round(seconds, 6),
            attrs.get("k", 0), inflight, ready, thread_s, process_s)))
        self.pauses.append(rec)
        logger.warning("pause %s", json.dumps(rec))
        flight_dump("pause")


def flight_dump(reason: str) -> Optional[str]:
    """Module-level flight-recorder trigger (chaos seams, drain/abort,
    migration); no-op when tracing is disabled."""
    tr = _TRACER
    if tr is None:
        return None
    return tr.flight_dump(reason)


# -- log<->trace correlation -------------------------------------------------
# The frontend binds the request's trace_id for the duration of its
# handler task; workers bind it around one generate() stream.  The
# logging filter (runtime/logging.py TraceIdFilter) stamps it onto every
# record emitted inside that context, so a request's log lines join its
# spans and request_end record on one id.  ContextVars follow asyncio
# task context, so concurrent requests never see each other's ids.
from contextvars import ContextVar as _ContextVar

_TRACE_ID_VAR: "_ContextVar[Optional[str]]" = _ContextVar(
    "dyn_trace_id", default=None)


def bind_trace_id(trace_id: Optional[str]):
    """Bind `trace_id` to the current (task) context for log
    correlation; None is a no-op.  Returns a reset token (or None)."""
    if trace_id is None:
        return None
    return _TRACE_ID_VAR.set(trace_id)


def unbind_trace_id(token) -> None:
    if token is not None:
        _TRACE_ID_VAR.reset(token)


def current_trace_id() -> Optional[str]:
    return _TRACE_ID_VAR.get()


def trace_id_from_annotations(annotations) -> Optional[str]:
    """The trace_id the frontend propagated via a
    ``traceparent:00-<trace>-<span>-01`` request annotation — how worker
    spans join the frontend's trace."""
    for a in annotations or ():
        if a.startswith("traceparent:"):
            parts = a.split(":", 1)[1].split("-")
            if len(parts) == 4 and len(parts[1]) == 32:
                return parts[1].lower()
    return None


def install_from_env() -> Optional[Tracer]:
    """Process-entry hook (engine/mocker/frontend mains): install a
    tracer when DYN_TRACE is set, dumping to DYN_TRACE_OUT at exit."""
    if os.environ.get("DYN_TRACE", "").lower() not in ("1", "true", "yes",
                                                       "on"):
        return None
    try:
        ring = int(os.environ.get("DYN_TRACE_RING", str(DEFAULT_RING)))
    except ValueError:
        ring = DEFAULT_RING
    tr = Tracer(ring=ring,
                out_path=os.environ.get("DYN_TRACE_OUT") or None).install()
    if tr.out_path:
        atexit.register(tr.dump)
    logger.info("timeline tracing enabled (ring=%d, out=%s)",
                ring, tr.out_path)
    return tr


__all__ = [
    "CPU_TIMED_WAITS",
    "DEFAULT_RING",
    "HOP_KINDS",
    "LOOP_PHASES",
    "PAUSE_KEYS",
    "PAUSE_S",
    "PhaseClock",
    "REQUEST_STAGES",
    "SPAN_KINDS",
    "STEP_PHASES",
    "Tracer",
    "begin",
    "bind_trace_id",
    "current_trace_id",
    "enabled",
    "end",
    "flight_dump",
    "install_from_env",
    "span",
    "trace_id_from_annotations",
    "tracer",
    "unbind_trace_id",
]
