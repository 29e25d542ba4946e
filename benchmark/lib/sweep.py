"""`--sweep r1,r2,...`: one set-up, then the cell's mix offered at each
rate for `--seconds` (after its pre-roll).  Prints a table; used once per
fixed-rate cell to find the highest rate the system sustains without a
growing backlog.  The cell then runs at about four fifths of it."""

from __future__ import annotations

from ..readers.counters import compiles_in_window
from ..readers.latency import percentile_of
from . import records
from .stats import counted, tokens_in_window


async def sweep(engine, cfg, mix, args, measure, log) -> None:
    log("rate_rps  ok  failed  in_flight_at_close  ttft_p50_ms  ttft_p95_ms"
        "  tpot_p95_ms  late_p95_ms  out_tok_per_s  compiles_in_window")
    for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
        ctx = await measure(engine, cfg, {**mix, "rate_rps": rate},
                            args.seconds, args.seed + i,
                            keep_records=records.path(
                                args.keep_records, args.workload,
                                args.seed + i, f".w{i}"))
        t0, t1 = ctx["window"]
        c = ctx["counted"] = counted(ctx["records"], t0, t1)
        col = lambda k, q: percentile_of(ctx, k, q) or -1.0  # noqa: E731
        log(f"{rate:8.2f} {len(c['ok']):4d} {len(c['failed']):4d} "
            f"{len(c['inflight']):6d} {col('ttft_ms', 50):12.1f} "
            f"{col('ttft_ms', 95):12.1f} {col('tpot_ms', 95):12.2f} "
            f"{col('late_ms', 95):10.2f} "
            f"{tokens_in_window(ctx['records'], t0, t1) / (t1 - t0):10.1f} "
            f"{compiles_in_window(ctx):4.0f}")
        await engine.clear_kv_blocks()
