"""Host-clock latencies of the requests counted in the window."""

from benchmark.lib.stats import percentile, request_latencies


def percentile_of(ctx, field, q):
    """q-th percentile of `field` (ttft_ms | tpot_ms | late_ms) over the
    requests that were due and finished inside the window."""
    vals = [v for v in (request_latencies(r)[field]
                        for r in ctx["counted"]["ok"]) if v is not None]
    return percentile(vals, q) if vals else None
