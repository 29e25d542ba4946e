"""Latency arithmetic on plain numbers (no JAX, no program imports)."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100) with linear interpolation between the
    closest ranks (numpy's default).  Empty input is an error: a metric
    with no sample is left out by its reader, never reported as 0."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def tpot_ms(first_t: float, last_t: float, n_tokens: int) -> Optional[float]:
    """Time per output token of ONE request: (last - first)/(n - 1).
    Tokens leave the engine in fused bursts, so per-gap percentiles
    degenerate (many gaps of ~0 and one long one); the request's mean
    spacing is what a client feels.  None when n < 2."""
    if n_tokens < 2:
        return None
    return (last_t - first_t) * 1e3 / (n_tokens - 1)


def request_latencies(rec: Dict) -> Dict[str, Optional[float]]:
    """ttft from when the request was DUE (not sent: a stalled sender's
    wait is the user's wait), tpot as above, lateness = sent - due."""
    times = rec["token_times"]
    return {
        "ttft_ms": (times[0] - rec["due_t"]) * 1e3 if times else None,
        "tpot_ms": (tpot_ms(times[0], times[-1], len(times))
                    if times else None),
        "late_ms": (rec["sent_t"] - rec["due_t"]) * 1e3,
    }


def counted(records: List[Dict], t0: float, t1: float) -> Dict[str, List]:
    """Split the window's requests.  `ok`: due in [t0, t1), finished
    inside the window with exactly max_tokens tokens.  `failed`: due in
    the window and ended in an error, short or long.  `inflight`: due in
    the window, still running when it closed — drained, not counted.
    Requests due before t0 (pre-roll) are in none of them."""
    ok, failed, inflight = [], [], []
    for r in records:
        if not (t0 <= r["due_t"] < t1):
            continue
        if r["end_t"] is None or r["end_t"] > t1:
            inflight.append(r)
        elif r["error"] or len(r["token_times"]) != r["max_tokens"]:
            failed.append(r)
        else:
            ok.append(r)
    return {"ok": ok, "failed": failed, "inflight": inflight}


def tokens_in_window(records: List[Dict], t0: float, t1: float) -> int:
    """Output tokens whose arrival time falls inside [t0, t1), whatever
    request they belong to (a rate is over all the work of the window)."""
    return sum(1 for r in records for t in r["token_times"] if t0 <= t < t1)


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def mean_live_context(records: List[Dict], t0: float, t1: float) -> float:
    """Time-average over [t0, t1) of the summed context (prompt + tokens
    so far) of the requests that were decoding: what a decode step's
    attention has to read.  A request decodes from its first token to
    its last; its context grows linearly between them."""
    if t1 <= t0:
        return 0.0
    total = 0.0
    for r in records:
        times = r["token_times"]
        if len(times) < 2:
            continue
        a, b = times[0], times[-1]
        lo, hi = max(a, t0), min(b, t1)
        if hi <= lo or b <= a:
            continue
        n = len(times)
        ctx = lambda t: r["prompt_len"] + 1 + (n - 1) * (t - a) / (b - a)  # noqa: E731
        total += (ctx(lo) + ctx(hi)) / 2.0 * (hi - lo)
    return total / (t1 - t0)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The values but the one farthest from their median (the first such
    in order where two are equally far)."""
    v = list(values)
    m = statistics.median(v)
    v.pop(max(range(len(v)), key=lambda i: abs(v[i] - m)))
    return v


def spread_iqr(values: Sequence[float]) -> float:
    """The spread a bound is set from: third quartile less first, as
    `statistics.quantiles(values, n=4)` gives them (numpy's lie closer
    together), as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_range(values: Sequence[float]) -> float:
    """Largest less smallest, as a share of the median."""
    return (max(values) - min(values)) / statistics.median(values)
