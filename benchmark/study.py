#!/usr/bin/env python3
"""A noise study of one cell: sets of runs as the check makes them, and
what they spread by.  Never imports JAX, so the runs it starts get the chip.

    python3 benchmark/study.py runs --workload <cell> --runs 6 --seed0 <n> \\
        --seconds 45 --out chiprun_out/study/<label>
        one process a run, seeds seed0 .. seed0+runs-1, each with
        --keep-records; stdout, stderr, result lines and records land in --out
    python3 benchmark/study.py table <dir> [<dir> ...]
        every end-to-end metric's values, median and spreads, a set a row;
        a directory without result lines (the windows of one `--sweep`
        process) has its latency metrics read again from the kept records
    python3 benchmark/study.py requests <dir> [<dir> ...]
        per window: counters, bursts by k, waits; and how alike the same
        request's TTFT is from window to window

The spreads are those of benchmark/lib/stats.py: `iqr` is what a bound is
set from, `range` is largest less smallest; `-1` leaves out the run
farthest from the median.  All are shares of the median.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import records, spec  # noqa: E402
from benchmark.lib.stats import (  # noqa: E402
    request_latencies,
    spread_iqr,
    spread_range,
    without_farthest,
)

RUN = os.path.join(spec.BENCH_DIR, "run.py")


def runs(args) -> int:
    args.out = os.path.abspath(args.out)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "results.jsonl"), "a") as results:
        for seed in range(args.seed0, args.seed0 + args.runs):
            cmd = [sys.executable, RUN, "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(args.seconds),
                   "--trace", "0", "--keep-records", args.out]
            base = os.path.join(args.out, f"{args.workload}.s{seed}")
            with open(base + ".out", "w") as out, \
                    open(base + ".err", "w") as err:
                rc = subprocess.run(cmd, stdout=out, stderr=err,
                                    cwd=spec.REPO_ROOT).returncode
            with open(base + ".out") as f:
                last = f.read().strip().rsplit("\n", 1)[-1]
            print(f"seed {seed} rc {rc}: {last}", flush=True)
            if rc == 0:
                results.write(json.dumps({"seed": seed, **json.loads(last)})
                              + "\n")
                results.flush()
    table([args.out])
    return 0


def set_metrics(d: str) -> Dict[str, List[float]]:
    """metric -> one value a window, from the result lines where the
    directory has them, otherwise from the records by the cell's readers."""
    path = os.path.join(d, "results.jsonl")
    out: Dict[str, List[float]] = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                for k, m in json.loads(line)["metrics"].items():
                    out.setdefault(k, []).append(m["value"])
        return out
    for p in sorted(glob.glob(os.path.join(d, "*.json.gz"))):
        ctx = records.load(p)
        cell = os.path.basename(p).rsplit(".s", 1)[0]
        for m in spec.cell_metrics(cell, "end_to_end"):
            if m["source"] != "host_clock" or m["name"] == "setup_s":
                continue
            v = spec.metric_reader("e2e_metrics", m["name"])(ctx)
            if v is not None:
                out.setdefault(m["name"], []).append(v)
    return out


def table(dirs: List[str]) -> int:
    print("set | metric | n | median | iqr | iqr-1 | range | range-1 | values")
    for d in dirs:
        for name, v in set_metrics(d).items():
            if len(v) < 3:
                print(f"{d} | {name} | {len(v)} | too few runs | {v}")
                continue
            less = without_farthest(v)
            print(f"{d} | {name} | {len(v)} | {statistics.median(v):.4f} | "
                  f"{spread_iqr(v):.4f} | {spread_iqr(less):.4f} | "
                  f"{spread_range(v):.4f} | {spread_range(less):.4f} | "
                  + " ".join(f"{x:.2f}" for x in v))
    return 0


def _window_row(ctx: Dict[str, Any]) -> Dict[str, Any]:
    t0, t1 = ctx["window"]
    dec = [r for r in ctx["fpm"] if r["kind"] == "decode" and t0 <= r["t"] < t1]
    pre = [r for r in ctx["fpm"] if r["kind"] == "prefill" and t0 <= r["t"] < t1]
    by_k: Dict[int, List[float]] = {}
    for r in dec:
        by_k.setdefault(r["k"], []).append(r["gap_s"])
    ok = ctx["counted"]["ok"]
    lat = [request_latencies(r) for r in ok]
    # decode bursts dispatched between a request's sending and its first
    # token: the queue it waited behind
    waits = [sum(1 for r in dec if q["sent_t"] <= r["t"] < q["token_times"][0])
             for q in ok]
    return {
        "ok": len(ok), "inflight": len(ctx["counted"]["inflight"]),
        "ttft_p50": statistics.median(x["ttft_ms"] for x in lat),
        "late_max": max(x["late_ms"] for x in lat),
        "counters": ctx["counters"],
        "bursts_by_k": {k: len(g) for k, g in sorted(by_k.items())},
        "gap_ms_by_k": {k: round(statistics.median(g) * 1e3, 2)
                        for k, g in sorted(by_k.items())},
        "gap_ms_max": round(max(r["gap_s"] for r in dec) * 1e3, 1),
        "prefills": len(pre),
        "bursts_waited_mean": round(statistics.mean(waits), 3),
        "bursts_waited_hist": {w: waits.count(w) for w in sorted(set(waits))},
    }


def requests(dirs: List[str]) -> int:
    ttft: Dict[str, Dict[int, float]] = {}
    for d in dirs:
        for p in sorted(glob.glob(os.path.join(d, "*.json.gz"))):
            ctx = records.load(p)
            print(f"{p}: {json.dumps(_window_row(ctx))}")
            ttft[p] = {r["index"]: request_latencies(r)["ttft_ms"]
                       for r in ctx["counted"]["ok"]}
    if len(ttft) < 2:
        return 0
    # the same index is the same request (sizes and due time) in every
    # window of a cell: how far does ITS ttft move from window to window?
    common = set.intersection(*[set(t) for t in ttft.values()])
    med = {i: statistics.median(t[i] for t in ttft.values()) for i in common}
    print(f"{len(common)} requests counted in every window")
    for p, t in ttft.items():
        diff = sorted(t[i] - med[i] for i in common)
        q1, q2, q3 = statistics.quantiles(diff, n=4)
        print(f"{p}: own ttft less the request's median over windows, ms: "
              f"min {diff[0]:.1f} q1 {q1:.1f} median {q2:.1f} q3 {q3:.1f} "
              f"max {diff[-1]:.1f}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("runs")
    r.add_argument("--workload", required=True)
    r.add_argument("--runs", type=int, default=6)
    r.add_argument("--seed0", type=int, required=True)
    r.add_argument("--seconds", type=float,
                   default=float(spec.load_benchmark()["run_seconds"]))
    r.add_argument("--out", required=True)
    for name in ("table", "requests"):
        sub.add_parser(name).add_argument("dirs", nargs="+")
    args = ap.parse_args()
    if args.cmd == "runs":
        return runs(args)
    return {"table": table, "requests": requests}[args.cmd](args.dirs)


if __name__ == "__main__":
    sys.exit(main())
