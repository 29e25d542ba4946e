"""From the profiler's `.xplane.pb` to numbers: the smallest reduction
that gives a busy/idle share, device time by XLA module, the device ops
that took most time and the longest idle gaps by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded
trace kept as JSON (benchmark/tests/): `load_xplane` flattens the file
into plain lists, `reduce_events` does the arithmetic on those.

What a TPU trace looks like (looked at by hand, PR 23, jax 0.9.0 on a
v5e): one plane per chip named `/device:TPU:<n>` whose line `XLA Modules`
has one event per executed program, named `jit_<function>(<fingerprint>)`,
and whose line `XLA Ops` has one event per HLO op inside them; `/host:CPU`
has one line per host thread with the runtime's TraceMe events.  All
lines share one clock (nanoseconds from the start of the session).

The engine jits `functools.partial` objects, so every one of its programs
is named `jit__unknown` and only the fingerprint tells them apart.  A
program is therefore classified by a probe: one request traced alone
before the window runs one prefill program and then only decode programs
(`decode_names_from_probe`); in the window a module with one of those
names is decode, another module of the same anonymous base name (or with
"prefill" in its name, once the program names its jits) is prefill, and
the rest is other.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 160

Event = Tuple[str, float, float]      # name, start_ns, duration_ns


def load_xplane(path: str) -> Dict[str, Any]:
    """{"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "host": [Event]} from one .xplane.pb (read with JAX alone)."""
    from jax.profiler import ProfileData

    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    # an op's name is its whole HLO text: keep its head
                    dev[key] = [(e.name[:NAME_CHARS], float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.duration_ns > 0)
    return out


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def module_name(event_name: str) -> str:
    """`jit__decode_multi_impl(1234)` -> `jit__decode_multi_impl`."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _top(totals: Dict[str, float], n: int) -> List[List[Any]]:
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def decode_names_from_probe(ev: Dict[str, Any],
                            min_ns: float = 1e6) -> List[str]:
    """Module names (with fingerprint) of the decode programs, from the
    trace of ONE request served alone: its first module longer than
    `min_ns` is the prefill, every later one is decode."""
    names: List[str] = []
    for dev in ev["devices"].values():
        big = [e for e in sorted(dev["modules"], key=lambda e: e[1])
               if e[2] >= min_ns]
        names.extend(n for n, _, _ in big[1:] if n != big[0][0])
    return sorted(set(names))


def kind_of(name: str, decode_names: Sequence[str]) -> str:
    if name in decode_names:
        return "decode"
    if "prefill" in name or module_name(name) in {
            module_name(n) for n in decode_names}:
        return "prefill"
    return "other"


def reduce_events(ev: Dict[str, Any], decode_names: Sequence[str] = (),
                  top: int = 10) -> Dict[str, Any]:
    """Busy seconds (union of device-op intervals, averaged over the
    chips), seconds and runs by module and by kind of program, top ops,
    and idle gaps (those before the first and after the last device op
    included, up to the first and last event of any line).  A device
    whose ops line is empty falls back to its modules line."""
    devices = ev["devices"]
    if not devices:
        return {}
    kind_s = {"decode": 0.0, "prefill": 0.0, "other": 0.0}
    kind_n = {"decode": 0, "prefill": 0, "other": 0}
    busy, span_lo, span_hi = 0.0, None, None
    mod_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    merged_first: List[Tuple[float, float]] = []
    for i, dev in enumerate(devices.values()):
        src = dev["ops"] or dev["modules"]
        merged = merge([(s, s + d) for _, s, d in src])
        if i == 0:
            merged_first = merged
        busy += sum(b - a for a, b in merged)
        if merged:
            span_lo = merged[0][0] if span_lo is None \
                else min(span_lo, merged[0][0])
            span_hi = merged[-1][1] if span_hi is None \
                else max(span_hi, merged[-1][1])
        for name, _, d in dev["modules"]:
            m = module_name(name)
            mod_s[m] = mod_s.get(m, 0.0) + d
            kind = kind_of(name, decode_names)
            kind_s[kind] += d
            kind_n[kind] += 1
        for name, _, d in dev["ops"]:
            op_s[name] = op_s.get(name, 0.0) + d
    n = len(devices)
    host = ev["host"]
    extent = 0.0
    if merged_first:
        lo = min([span_lo] + [s for _, s, _ in host])
        hi = max([span_hi] + [s + d for _, s, d in host])
        extent = hi - lo
        merged_first = [(lo, lo)] + merged_first + [(hi, hi)]
    gaps = sorted(((b0 - a1, a1, b0) for (_, a1), (b0, _)
                   in zip(merged_first, merged_first[1:]) if b0 > a1),
                  reverse=True)
    gap_s: Dict[str, float] = {}
    if host:
        import numpy as np

        h0 = np.array([s for _, s, _ in host])
        h1 = h0 + np.array([d for _, _, d in host])
    for length, a, b in gaps[:50]:
        best = "(no host event)"
        if host:
            # the host event that covers most of the gap names it; among
            # equals the shortest says most
            ov = np.minimum(b, h1) - np.maximum(a, h0)
            top_ov = ov.max()
            if top_ov > 0:
                cand = np.flatnonzero(ov >= top_ov * 0.999)
                best = host[int(cand[np.argmin((h1 - h0)[cand])])][0]
        gap_s[best] = gap_s.get(best, 0.0) + length
    return {
        "busy_s": busy / n / 1e9,
        # the traced stretch by the trace's own clock: first to last event
        # of any line, so busy_s can never pass it
        "extent_s": extent / 1e9,
        "module_s": {k: v / n / 1e9 for k, v in mod_s.items()},
        "kind_s": {k: v / n / 1e9 for k, v in kind_s.items()},
        "kind_n": {k: v / n for k, v in kind_n.items()},
        "device_ops": _top({k: v / n / 1e9 for k, v in op_s.items()}, top),
        "idle_gaps": _top({k: v / 1e9 for k, v in gap_s.items()}, top),
    }


def cut_for_tests(ev: Dict[str, Any], decode_names: Sequence[str] = (),
                  seconds: float = 0.25,
                  host_max: int = 400) -> Dict[str, Any]:
    """A short stretch from the middle of a trace, with what
    `reduce_events` makes of it: small enough to keep in the repository
    (benchmark/tests/recorded_trace.json)."""
    starts = [e[1] for d in ev["devices"].values() for e in d["ops"]]
    if not starts:
        return {"events": {"devices": {}, "host": []}, "decode_names": [],
                "expect": {}}
    lo, hi = min(starts), max(starts)
    a = (lo + hi) / 2.0
    b = a + seconds * 1e9
    keep = lambda evs: [  # noqa: E731  (events clipped to the stretch)
        (n, max(s, a), min(s + d, b) - max(s, a))
        for n, s, d in evs if s < b and s + d > a]
    cut = {"devices": {k: {"ops": keep(d["ops"]),
                           "modules": keep(d["modules"])}
                       for k, d in ev["devices"].items()},
           "host": keep(ev["host"])[:host_max]}
    r = reduce_events(cut, decode_names)
    return {"events": cut, "decode_names": list(decode_names),
            "expect": {k: r.get(k) for k in ("busy_s", "module_s",
                                             "kind_s")}}
