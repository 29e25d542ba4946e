"""What one window observed, kept for a look after the run
(`--keep-records <dir>`): the client's per-request records, the window,
the counters at its edges, compile events and the engine's forward-pass
ring.  Times are seconds from the opening of the window (the ring's and
the compile events' clock is time.monotonic, shifted here onto the
client's).  `load` gives back a `ctx` the host-clock readers accept."""

from __future__ import annotations

import faulthandler
import gc
import gzip
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List

from .stats import counted

COUNTERS = ("steps", "cont_bursts", "preemptions", "prefill_tokens",
            "prefill_steps", "decode_tokens", "requests")


class GcPauses:
    """The collector's pauses while installed (`gc.callbacks`), summed by
    generation: the client, the engine's scheduler and the collector share
    one interpreter, so a long collection is a stall of all three."""

    def __init__(self) -> None:
        self.count: List[int] = [0, 0, 0]
        self.seconds: List[float] = [0.0, 0.0, 0.0]
        self.longest: List[float] = [0.0, 0.0]     # [seconds, started at]
        self._t = 0.0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t = now
            return
        g = info["generation"]
        self.count[g] += 1
        self.seconds[g] += now - self._t
        if now - self._t > self.longest[0]:
            self.longest = [now - self._t, self._t]

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def summary(self, t0: float) -> Dict[str, Any]:
        return {"count": self.count,
                "ms": [round(s * 1e3, 3) for s in self.seconds],
                "longest_ms": round(self.longest[0] * 1e3, 3),
                "longest_at_s": round(self.longest[1] - t0, 3)}


class StallWatch:
    """A thread that looks every quarter second whether the engine has
    finished a scheduler step; where it has not for `after_s` although
    requests are waiting, every thread's stack goes to stderr, once a
    stall, so that the run says where the program stood.  `stalls` holds
    [began (perf_counter), seconds] of each (PERF.md section 6, PR 25: one
    run in eighteen stood 3.8 s with no compile event)."""

    def __init__(self, steps: Callable[[], int],
                 waiting: Callable[[], bool], after_s: float = 1.5) -> None:
        self.steps, self.waiting, self.after_s = steps, waiting, after_s
        self.stalls: List[List[float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stall-watch")

    def _run(self) -> None:
        seen, since, dumped = self.steps(), time.perf_counter(), False
        while not self._stop.wait(0.25):
            now, n = time.perf_counter(), self.steps()
            if n != seen or not self.waiting():
                if dumped:
                    self.stalls[-1][1] = now - since
                seen, since, dumped = n, now, False
            elif now - since > self.after_s and not dumped:
                dumped = True
                self.stalls.append([since, now - since])
                print(f"no scheduler step for {now - since:.2f} s with "
                      f"requests waiting; threads:", file=sys.stderr)
                faulthandler.dump_traceback(file=sys.stderr,
                                            all_threads=True)

    def __enter__(self) -> "StallWatch":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


def counter_deltas(ctx: Dict[str, Any]) -> Dict[str, float]:
    """Deltas over the window of the counters the per-layer readers use,
    the compile events that fell inside it, and what the host did
    meanwhile (this process's CPU seconds, load, collector pauses)."""
    from ..readers.counters import compiles_in_window

    a, b = ctx["counters_open"], ctx["counters_close"]
    out = {k: b.get(k, 0) - a.get(k, 0) for k in COUNTERS}
    out["window_compiles"] = compiles_in_window(ctx)
    out["host"] = ctx["host"]
    return out


def path(directory: str, workload: str, seed: int, window: str = "") -> str:
    """Where `--keep-records <directory>` puts one window ("" for none)."""
    return directory and os.path.join(
        directory, f"{workload}.s{seed}{window}.json.gz")


def dump(ctx: Dict[str, Any], path: str, meta: Dict[str, Any]) -> None:
    """`meta` carries what `ctx` does not: the seed, the window's length
    and rate, and the `counters` already worked out for the log."""
    t0, t1 = ctx["window"]
    rel = lambda t: None if t is None else round(t - t0, 6)  # noqa: E731
    mono = lambda t: round(t - ctx["mono_offset"] - t0, 6)  # noqa: E731
    doc = {
        **meta,
        "window": [0.0, round(t1 - t0, 6)],
        "drained": ctx["drained"],
        "records": [{
            "index": r["index"], "prompt_len": r["prompt_len"],
            "max_tokens": r["max_tokens"], "error": r["error"],
            "due_t": rel(r["due_t"]), "sent_t": rel(r["sent_t"]),
            "end_t": rel(r["end_t"]), "n_tokens": len(r["tokens"]),
            "token_times": [rel(t) for t in r["token_times"]],
        } for r in ctx["records"]],
        "compile_events": [{**e, "t": mono(e["t"])}
                           for e in ctx["compile_events"]],
        "fpm": [{**{k: v for k, v in r.items()
                    if k in ("kind", "k", "lanes", "gap_s", "rows",
                             "tokens", "bucket", "queue_depth")},
                 "t": mono(r["t"])} for r in ctx["fpm_close"]],
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    doc["window"] = tuple(doc["window"])
    doc["counted"] = counted(doc["records"], *doc["window"])
    return doc
