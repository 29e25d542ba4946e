"""Test config: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): everything below the
hardware layer is testable with no accelerator.  Multi-chip sharding tests run
against 8 virtual CPU devices; the chip itself is exercised by chip_smoke.py,
and tests/test_tpu_compile.py compiles the kernels for a described v5e.

`JAX_PLATFORMS` is the one way to choose a backend; it must be set before
jax is imported anywhere.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# tests write throwaway checkpoints under tmp paths; populating the global
# tmpfs weight cache for them would grow /dev/shm forever (explicit cache
# tests point DYN_WEIGHT_CACHE_DIR at a tmp dir instead)
os.environ.setdefault("DYN_WEIGHT_CACHE", "0")
# Tests keep JAX's persistent compilation cache OFF, for themselves and
# for every process they spawn (the engine entry point would otherwise
# fill the checkout's .jax_cache/ with tiny -O0 CPU programs, and a
# described-topology compile in tests/test_tpu_compile.py can be written
# to a cache but not read back without a chip).  The suite's wall clock
# is kept inside its envelope by compiling at -O0 instead (below).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# tier-1 runs tiny models where XLA optimization buys nothing but compile
# time (~1/3 of suite wall clock); correctness assertions (greedy token
# equality, leader/follower bit-identity) compare within-run outputs, so
# the pass-pipeline level does not affect them
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# build the native library from source if absent (it is not committed):
# the native indexer is the promoted DEFAULT when built, so tier-1 must
# exercise it whenever a toolchain exists.  No toolchain degrades
# gracefully to the pure-Python indexer (tests/test_native_build.py
# skips its native half); a PRESENT toolchain whose build fails is
# surfaced loudly instead of silently testing the fallback forever.
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_native_so = os.path.join(_repo_root, "native", "libdynamo_native.so")
if not os.path.exists(_native_so):
    import shutil
    import subprocess

    if shutil.which("make") and (shutil.which("c++") or
                                 shutil.which("g++") or
                                 shutil.which("clang++")):
        try:
            _build = subprocess.run(
                ["make", "-C", os.path.join(_repo_root, "native")],
                capture_output=True, text=True, timeout=120)
            if _build.returncode != 0:
                sys.stderr.write(
                    "conftest: native indexer build FAILED (tests fall "
                    "back to the pure-Python indexer):\n"
                    + _build.stdout[-1000:] + _build.stderr[-1000:]
                    + "\n")
        except (OSError, subprocess.TimeoutExpired) as e:
            sys.stderr.write(f"conftest: native indexer build errored: "
                             f"{e}\n")
    # else: no toolchain — pure-Python indexer serves tier-1

import asyncio
import gc
import inspect
import logging
import warnings

import pytest

# Runtime twin of the DYN004 lint (dynamo_tpu/lint): asyncio debug mode
# times every callback, and any callback holding the event loop longer
# than this fails the test with the offending callback named (the lint
# catches time.sleep/open()/.result() lexically; this catches the
# blocking work static analysis can't see — a jit compile or device
# fetch that snuck onto the loop instead of asyncio.to_thread).  Debug
# mode's expensive half is the source-traceback capture on every
# Task/Handle creation — stubbed to empty below so the suite keeps its
# wall-clock envelope while the slow-callback timer stays armed.
# The design bound is 200ms; tier-1 arms at 500ms because the suite runs
# several xdist workers over shared cores — under full-suite load,
# innocent 0.25-0.45s scheduler-noise slices cross 200ms
# nondeterministically (different tests each run), while the bug class
# this exists for (sync sleeps, mid-serving compiles, device fetches on
# the loop) blocks for ≥0.5s when real.  Tune with DYN_TEST_SLOW_CB_S.
SLOW_CALLBACK_S = float(os.environ.get("DYN_TEST_SLOW_CB_S", "0.5"))
asyncio.format_helpers.extract_stack = lambda *a, **k: []  # type: ignore


class _SlowCallbackCapture(logging.Handler):
    """Collects asyncio's 'Executing <Handle ...> took N seconds'
    warnings for the duration of one test — but only when the named
    culprit is THIS repo's code holding the loop.  A warning whose
    running-at frame is stdlib (e.g. selector_events.py accepting a
    connection) is a major-GC pause or scheduler stall attributed to
    whatever callback it interrupted: real to the wall clock, but not
    actionable by the test under judgment (observed: a 1.1s gen-2
    collection of the JAX heap billed to _accept_connection2)."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.slow: list = []
        self._repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if "took" in msg and "Executing" in msg and self._repo in msg:
            self.slow.append(msg)


@pytest.fixture(autouse=True, scope="module")
def _freeze_longlived_heap():
    """Move each module's surviving heap out of the cyclic collector.

    The suite's long-lived object graph (jit caches, compiled
    executables, module state) grows to millions of objects; a gen-2
    collection over it takes 1-2s on this box and lands wherever the
    allocator happens to trip threshold2 — including mid-event-loop,
    where the slow-callback gate above bills the pause to whichever
    innocent repo-code callback it interrupted (the PR 10-documented
    once-per-full-run flake: a different async test each time).  At
    every module boundary we collect once OUTSIDE any event loop (the
    previous module's cyclic garbage goes here, where a pause judges
    nothing) and FREEZE the survivors into the permanent generation, so
    later collections scan only the current module's young objects —
    mid-test gen-2 pauses stay small, and each boundary collect stays
    cheap because everything older is already frozen.  Refcounting
    still frees frozen objects; only cycle detection skips them, and
    anything cyclic-dead was collected the moment before its freeze.

    Caveat: a cycle formed LATER through a frozen object (a frozen
    registry mutated by a subsequent module's test) is never
    collectable for the rest of the run — acceptable because tests
    build their own fixtures rather than mutating other modules'
    state, and full-suite RSS held steady across the validation runs;
    if suite RSS ever creeps, add a periodic gc.unfreeze()+collect
    here instead of removing the fixture."""
    gc.collect()
    gc.freeze()
    yield


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async-test support (pytest-asyncio is not in the image),
    plus tier-1-wide leak detection: a test that exits with pending
    asyncio tasks (something it started and never cancelled/awaited) or
    that leaves never-awaited coroutines behind FAILS.  Leaked tasks are
    how wedged-worker bugs hide — a canary loop or pull task that
    outlives its test would be silently destroyed with the loop.

    Tasks the test's own teardown already cancelled are given a few loop
    cycles to retire before the check, so `task.cancel()` without an
    await (the common close() idiom) does not false-positive.  A test
    that legitimately abandons tasks can opt out with
    `@pytest.mark.allow_task_leaks`."""
    fn = pyfuncitem.obj
    if not inspect.iscoroutinefunction(fn):
        return None
    kwargs = {
        name: pyfuncitem.funcargs[name]
        for name in pyfuncitem._fixtureinfo.argnames
    }
    leaked: list = []
    slow_capture = _SlowCallbackCapture()
    # the opt-out disables debug mode itself, not just the verdict:
    # debug's per-callback timing is real overhead, and the tests that
    # opt out (real-JAX-engine bodies, timing-sensitive SLO assertions)
    # are exactly the ones that overhead distorts
    gate_on = pyfuncitem.get_closest_marker("allow_slow_callbacks") is None

    async def runner():
        me = asyncio.current_task()
        loop = asyncio.get_running_loop()
        if gate_on:
            # arm the slow-callback watchdog: debug mode is what makes
            # the event loop time its callbacks at all (extract_stack
            # stubbed above keeps it cheap)
            loop.set_debug(True)
            loop.slow_callback_duration = SLOW_CALLBACK_S
            logging.getLogger("asyncio").addHandler(slow_capture)
        try:
            # the body runs as its OWN task: since Python 3.12 wait_for
            # awaits a bare coroutine inline, so the body's first segment
            # would run inside the very callback that armed debug mode
            # above — which the loop had already decided not to time —
            # and a slow callback would be billed to runner(), not to the
            # test coroutine the failure message should name
            await asyncio.wait_for(asyncio.create_task(fn(**kwargs)),
                                   timeout=120)
        finally:
            # let tasks cancelled-but-not-reaped by the test's teardown
            # retire before judging what is genuinely leaked; a short
            # real-time grace covers teardown paths that need wall clock
            # (aiohttp connection handlers after server cleanup, nested
            # cancellation chains)
            import time as _time

            deadline = _time.monotonic() + 0.75
            while _time.monotonic() < deadline:
                await asyncio.sleep(0)
                if all(t.done() for t in asyncio.all_tasks()
                       if t is not me):
                    break
                await asyncio.sleep(0.02)
            pending = [t for t in asyncio.all_tasks()
                       if t is not me and not t.done()]
            leaked.extend(pending)
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            logging.getLogger("asyncio").removeHandler(slow_capture)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        asyncio.run(runner())
        # never-awaited coroutines surface their RuntimeWarning when the
        # object dies: refcounting catches the common case the moment the
        # test's frames unwind, a young-generation pass catches the
        # cycle-trapped rest.  (A FULL gc.collect() here would walk the
        # whole JAX heap after every async test — tens of ms each, minutes
        # across the suite.)
        gc.collect(1)
    if leaked and not pyfuncitem.get_closest_marker("allow_task_leaks"):
        pytest.fail(
            "test leaked pending asyncio tasks (start it, own it): "
            + ", ".join(repr(t) for t in leaked[:8]), pytrace=False)
    never_awaited = [w for w in caught
                     if "was never awaited" in str(w.message)]
    if never_awaited:
        pytest.fail(
            "test left never-awaited coroutines: "
            + ", ".join(str(w.message) for w in never_awaited[:8]),
            pytrace=False)
    if slow_capture.slow and gate_on:
        pytest.fail(
            f"test blocked the event loop > {SLOW_CALLBACK_S:.1f}s "
            "(every concurrent stream stalls behind a blocking "
            "callback; move the work to asyncio.to_thread, or opt out "
            "with @pytest.mark.allow_slow_callbacks): "
            + "; ".join(slow_capture.slow[:4]), pytrace=False)
    return True
