"""Compile watchdog for the engine's jit dispatch sites.

The engine's own comments record *measured* 8-14s guided-fork compiles
landing mid-serving with zero telemetry — an invisible latency cliff
that no span, metric, or FPM record could attribute.  This module makes
every XLA compile an observed event.

Mechanism (a program is lowered once, by its compile; no steady-state
cost):

  * ``WatchedProgram`` wraps a ``jax.jit`` callable.  Per call it reads
    the pjit C++ cache size before and after — a growth means THIS call
    traced+compiled a new executable, and the call's wall time is the
    compile time (jit dispatch is async; only a compiling call blocks).
    Steady-state overhead is two cache-size reads and two clock reads
    per dispatch — nanoseconds next to the descriptor uploads the
    dispatch already does.  Unlike the span tracer there is no off
    switch: an unobserved mid-serving compile is exactly the blind spot
    this exists to close, and the steady-state cost is negligible.

  * Every compile emits: a ``compile`` span on the engine's logical
    track (Perfetto shows the cliff in the timeline), a ``compile`` FPM
    record (``family``, ``seconds``, ``tokens``, ``serving``) the worker
    turns into ``dynamo_engine_compile_seconds{family}`` and the
    planner's recompile-storm diag, and — when the compile landed
    **mid-serving** (active sequences exist; warmup compiles don't) — a
    flight-recorder snapshot plus a warning, because a steady-state
    recompile means a shape leaked past warmup.

How fast a compiled program runs is the device trace's to say
(`benchmark/`, PERF.md §3), not this module's.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

# one place defines the compile FPM record's kind string; engine, mocker,
# workers, FpmWindow and the report all join on it
COMPILE_KIND = "compile"

# a watched program is jitted as `dyn_<family>`, so a profiler trace's
# `XLA Modules` read `jit_dyn_<family>(<fingerprint>)`.  Only the prefill
# families' names may contain "prefill": trace reductions classify a
# module as prefill by that word.
PROGRAM_PREFIX = "dyn_"


class WatchedProgram:
    """One jit callable under the watchdog.  Call syntax is unchanged."""

    __slots__ = ("fn", "family", "watch", "tokens_of", "_counted")

    def __init__(self, fn, family: str, watch: "CompileWatch",
                 tokens_of: Optional[Callable] = None):
        # a jit product (anything that lowers) MUST expose the compile
        # counter: without it every compile would pass unseen and "zero
        # mid-serving compiles" would read 0 for ever.  Plain callables
        # (test stand-ins) have neither attribute and pass unwatched.
        self._counted = hasattr(fn, "_cache_size")
        if not self._counted and hasattr(fn, "lower"):
            raise TypeError(
                f"jit program {family!r} has no _cache_size(): this JAX "
                "cannot be compile-watched; refusing to serve unwatched")
        self.fn = fn
        self.family = family
        self.watch = watch
        # tokens_of(args) -> int key grouping compiled variants (e.g. the
        # prefill bucket = the token array's padded length); None = one
        # fixed shape per program (decode: always [max_num_seqs])
        self.tokens_of = tokens_of

    def __call__(self, *args):
        fn = self.fn
        if not self._counted:
            return fn(*args)
        n0 = fn._cache_size()
        t0 = time.monotonic()
        out = fn(*args)
        if fn._cache_size() > n0:
            self.watch.on_compile(self, time.monotonic() - t0, args)
        return out

    def lower(self, *args, **kw):
        return self.fn.lower(*args, **kw)


class CompileWatch:
    """Per-engine compile observer: counts/times every compile per
    program family."""

    def __init__(self, sink: Optional[Callable[[dict], None]] = None,
                 track: Optional[str] = None,
                 serving: Optional[Callable[[], bool]] = None):
        self.sink = sink          # fpm ring append (engine.fpm.append)
        self.track = track        # obs logical track for compile spans
        self._serving = serving or (lambda: False)
        self.counts: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.serving_compiles = 0
        self.events: deque = deque(maxlen=256)

    @contextlib.contextmanager
    def warming(self):
        """Compiles inside this block are warm-up, whatever `serving`
        says: the worker's warm-up request occupies a slot like a real
        one, and must not count as a mid-serving stall."""
        prev, self._serving = self._serving, (lambda: False)
        try:
            yield
        finally:
            self._serving = prev

    @staticmethod
    def named(fn, family: str):
        """`fn` under the name ``dyn_<family>``, to hand to ``jax.jit``
        inside `wrap(...)`: jit names a program after its function, and
        a ``functools.partial`` has no name (`jit__unknown`).  The name
        is part of the compile-cache key."""
        out = functools.partial(fn)
        out.__name__ = out.__qualname__ = PROGRAM_PREFIX + family
        return out

    def wrap(self, fn, family: str,
             tokens_of: Optional[Callable] = None):
        """Wrap one jit callable; None passes through (families gated off
        for this worker keep their `is None` checks working)."""
        if fn is None:
            return None
        return WatchedProgram(fn, family, self, tokens_of)

    def on_compile(self, wp: WatchedProgram, seconds: float,
                   args: Tuple[Any, ...]) -> None:
        t1 = time.monotonic()
        family = wp.family
        serving = bool(self._serving())
        key = 0
        if wp.tokens_of is not None:
            try:
                key = int(wp.tokens_of(args))
            except Exception:
                key = 0
        self.counts[family] = self.counts.get(family, 0) + 1
        self.seconds[family] = self.seconds.get(family, 0.0) + seconds
        if serving:
            self.serving_compiles += 1
        ev = {
            "t": t1, "kind": COMPILE_KIND, "family": family,
            "seconds": round(seconds, 6), "tokens": key,
            "serving": serving,
        }
        self.events.append(ev)
        if self.sink is not None:
            self.sink(dict(ev))
        from . import flight_dump, tracer

        tr = tracer()
        if tr is not None:
            tr.record(COMPILE_KIND, t1 - seconds, t1,
                      {k: v for k, v in ev.items()
                       if k not in ("t", "kind")},
                      None, self.track)
        if serving:
            # a compile the warmup didn't cover landed while requests
            # were in flight: every active stream just stalled behind it
            logger.warning(
                "XLA compile of %r (%d tokens) landed mid-serving: "
                "%.2fs stall", family, key, seconds)
            flight_dump(f"compile-{family}")


# compiles range from ms (CPU test programs) to 8-14s (measured TPU
# guided forks); the default prometheus buckets top out at 10s
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   20.0, 60.0)


def observe_compile_records(metrics, records) -> None:
    """Fold a drained FPM batch's compile records onto a worker's
    /metrics: the dynamo_engine_compile_seconds{family} histogram and
    compile counters.  Shared by the JAX and mocker workers so both
    export the same families (the plane stays tier-1 testable
    CPU-only)."""
    hist = None
    for rec in records:
        if rec.get("kind") != COMPILE_KIND:
            continue
        if hist is None:
            hist = metrics.histogram(
                "dynamo_engine_compile_seconds",
                "XLA compile wall time per program family", ("family",),
                buckets=COMPILE_BUCKETS)
        family = str(rec.get("family", ""))
        hist.labels(**metrics.labels, family=family).observe(
            float(rec.get("seconds", 0.0)))
        metrics.inc("dynamo_engine_compiles_total", 1.0,
                    "XLA compiles per program family", family=family)
        if rec.get("serving"):
            metrics.inc("dynamo_engine_serving_compiles_total", 1.0,
                        "compiles that landed while requests were "
                        "in flight (each one is a serving stall)",
                        family=family)
