"""Bench round driver: one command cashes in a whole round.

Round r07 hardens the cache fabric r06 built: every persisted/
transferred KV block now carries a crc32 footer, checksum failures
quarantine the blob and fall back to recompute, and per-tier circuit
breakers bound how much a failing shared mount can cost.  The kernel/
serving benches carry over from r06:

  prefill   bench_prefill_phases.py --impl ab packed
            gate[tpu]: packed-Pallas est MFU >= 0.4
  kv_quant  bench_kv_quant.py (dtype x impl decode rows)
            gate[tpu]: int8-Pallas decode tok/s >= bf16-Pallas
  serving   bench_serving.py --overlap ab
            gate[tpu]: zero mid-serving compiles
            (dynamo_engine_serving_compiles_total stays 0)

plus the benches that emit their own gated line, adopted verbatim
(indexer, global_router, prefix_fleet, and — new this round —
chaos_cache, the KV-integrity A/B: byte-identical serving under
injected G4 corruption + stalls, every corruption attributed in the
ledger, breaker tripped, p90 TTFT bounded by recompute).

Each bench contributes ONE summary JSON line to stdout:

  {"bench": ..., "round": "r07", "mode": "smoke"|"tpu",
   "gates": [{"name", "target", "value", "status"}...], "result": {...}}

`--mode smoke` runs every bench end to end off-chip at smoke scale (tiny
model, interpret-mode kernels, mocker serving) so the driver is tier-1
testable — rows are labeled mode=smoke and every gate reports
status=skipped_smoke instead of pass/fail.  `--mode tpu` enforces the
gates (any fail exits nonzero) and needs a chip: a child process asks JAX
for the device first and the round stops with an error unless it is a
TPU.  The mode is never guessed, and this parent never imports JAX — a
chip belongs to one process at a time, and every bench is a child that
takes it in its turn.

    python benchmarks/run_round.py --mode smoke|tpu [--only ...]
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")

ROUND = "r07"
TARGET_PREFILL_MFU = 0.4

# per-bench argv at each scale: smoke keeps every bench CPU-runnable
# in seconds (tiny geometry, interpret kernels, short mocker trace);
# tpu is the serving geometry the round's numbers are quoted at
BENCH_ARGS = {
    "prefill": {
        "script": "bench_prefill_phases.py",
        "smoke": ["packed", "--impl", "ab", "--mode", "smoke",
                  "--model", "tiny", "--tokens", "64", "--seqs", "2",
                  "--ctx-blocks", "4", "--block", "16"],
        "tpu": ["packed", "--impl", "ab", "--mode", "tpu"],
    },
    "kv_quant": {
        "script": "bench_kv_quant.py",
        "smoke": ["--mode", "smoke", "--batch", "2", "--ctx", "64",
                  "--steps", "4", "--iters", "1", "--parity-seqs", "1"],
        "tpu": ["--mode", "tpu", "--model", "llama-3b", "--ctx", "2048",
                "--block", "128", "--batch", "8", "--steps", "32"],
    },
    "serving": {
        "script": "bench_serving.py",
        "smoke": ["--overlap", "ab", "--requests", "16", "--rate", "32",
                  "--speedup", "4"],
        "tpu": ["--overlap", "ab"],
    },
    "indexer": {
        "script": "bench_indexer.py",
        "smoke": ["--mode", "smoke", "--events", "4000",
                  "--queries", "4000", "--parity-ops", "500"],
        "tpu": ["--mode", "tpu"],
    },
    "global_router": {
        "script": "bench_global_router.py",
        "smoke": ["--mode", "smoke"],
        "tpu": ["--mode", "tpu"],
    },
    "prefix_fleet": {
        "script": "bench_prefix_fleet.py",
        "smoke": ["--mode", "smoke"],
        "tpu": ["--mode", "tpu"],
    },
    "chaos_cache": {
        "script": "bench_chaos_cache.py",
        "smoke": ["--mode", "smoke"],
        "tpu": ["--mode", "tpu"],
    },
}


def probe_device(timeout_s: float = 300.0) -> dict:
    """Ask a CHILD what JAX's default backend is (this parent stays off
    JAX so the benches it spawns can each take the chip).  The child has
    exited — and released the chip — before the first bench starts.  A
    probe that fails is an error, never a mode."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json\n"
         "from dynamo_tpu.runtime.device import device_identity\n"
         "print(json.dumps(device_identity()))"],
        capture_output=True, text=True, timeout=timeout_s,
        env={**os.environ, "PYTHONPATH": REPO})
    if proc.returncode != 0:
        raise RuntimeError(
            "device probe failed (rc=%d):\n%s" % (
                proc.returncode, proc.stderr[-2000:]))
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_bench(name: str, argv, timeout_s: float):
    """Subprocess one bench and parse its stdout JSON lines."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, name), *argv],
        capture_output=True, text=True, timeout=timeout_s,
        env={**os.environ, "PYTHONPATH": REPO})
    lines = []
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            try:
                lines.append(json.loads(ln))
            except ValueError:
                pass
    return proc, lines


def gate(name: str, target: str, value, ok, enforced: bool) -> dict:
    """One acceptance-gate row: in tpu mode pass/fail (fail flunks the
    round), in smoke mode the gate is still PRESENT in the JSON but
    labeled skipped — interpret-mode/mocker numbers must never
    satisfy (or flunk) a chip bar."""
    if not enforced:
        status = "skipped_smoke"
    elif value is None:
        status = "fail_missing"
    else:
        status = "pass" if ok else "fail"
    return {"name": name, "target": target, "value": value,
            "status": status}


def eval_prefill(lines, enforced):
    row = next((l for l in lines if l.get("bench") == "prefill_phases"),
               None)
    impls = (row or {}).get("impls", {})
    pal = impls.get("pallas") or impls.get("pallas_interpret") or {}
    mfu = pal.get("est_mfu")
    gates = [gate("prefill_pallas_mfu", f">= {TARGET_PREFILL_MFU}", mfu,
                  mfu is not None and mfu >= TARGET_PREFILL_MFU,
                  enforced)]
    return gates, row


def eval_kv_quant(lines, enforced):
    row = next((l for l in lines if l.get("bench") == "kv_quant"), None)
    tok = {}
    for r in (row or {}).get("decode", {}).get("rows", []):
        tok[(r["kv_dtype"], r["attn_impl"])] = r["tok_s"]
    pallas = (row or {}).get("decode", {}).get("pallas_impl", "pallas")
    i8, b16 = tok.get(("int8", pallas)), tok.get(("bf16", pallas))
    val = (None if i8 is None or b16 is None
           else round(i8 / max(b16, 1e-9), 3))
    gates = [gate("int8_pallas_ge_bf16", "tok/s ratio >= 1.0", val,
                  val is not None and val >= 1.0, enforced)]
    return gates, row


def eval_serving(lines, enforced):
    # one driver line summarizes BOTH overlap modes: keep the overlap
    # row (the serving configuration) as the headline result and gate
    # on mid-serving compiles across every topology row
    rows = [l for l in lines if "compiles" in l]
    compiles = sum(sum(l["compiles"].get("serving", {}).values())
                   for l in rows)
    gates = [gate("zero_mid_serving_compiles", "== 0",
                  compiles if rows else None,
                  bool(rows) and compiles == 0, enforced)]
    head = next((l for l in reversed(rows)
                 if "overlap" in l.get("config", "")), None)
    return gates, head or (rows[-1] if rows else None)


def eval_gated_line(bench_name):
    """Benches that emit their own gated line (indexer, global_router,
    prefix_fleet, chaos_cache): adopt their gates verbatim —
    enforcement already followed the --mode flag the driver passed
    down."""
    def _eval(lines, enforced):
        row = next((l for l in lines if l.get("bench") == bench_name),
                   None)
        if row is None:
            return [gate(f"{bench_name}_summary_line", "present", None,
                         False, True)], None
        return row.get("gates", []), row.get("result")
    return _eval


EVALS = {"prefill": eval_prefill, "kv_quant": eval_kv_quant,
         "serving": eval_serving,
         "indexer": eval_gated_line("indexer"),
         "global_router": eval_gated_line("global_router"),
         "prefix_fleet": eval_gated_line("prefix_fleet"),
         "chaos_cache": eval_gated_line("chaos_cache")}


def main() -> int:
    p = argparse.ArgumentParser(
        description="one-shot bench round driver (see module docstring)")
    p.add_argument("--mode", required=True, choices=["smoke", "tpu"],
                   help="tpu = serving geometry on a chip, gates "
                        "enforced, an error without a TPU; smoke = tiny "
                        "geometry off-chip, gates skipped")
    p.add_argument("--only", nargs="*", choices=sorted(BENCH_ARGS),
                   default=None,
                   help="run a subset of the round's benches")
    p.add_argument("--timeout-s", type=float, default=1800.0,
                   help="per-bench subprocess timeout")
    args = p.parse_args()

    mode = args.mode
    enforced = mode == "tpu"
    if enforced:
        device = probe_device()
        if device["platform"] != "tpu":
            sys.stderr.write(
                f"--mode tpu needs a TPU; JAX's default backend here is "
                f"{device}.  Smoke rows are never reported under "
                "--mode tpu: run --mode smoke off-chip.\n")
            return 2
        print(json.dumps({"round": ROUND, "mode": mode,
                          "device": device}), flush=True)
    failed = []
    for bench in (args.only or sorted(BENCH_ARGS)):
        spec = BENCH_ARGS[bench]
        proc, lines = run_bench(spec["script"], spec[mode],
                                args.timeout_s)
        gates, result = EVALS[bench](lines, enforced)
        if proc.returncode != 0:
            # the bench's own in-process asserts (parity, capacity,
            # int8>=bf16) count as round gates too
            gates.append({"name": "bench_exit", "target": "rc == 0",
                          "value": proc.returncode, "status": "fail"})
            sys.stderr.write(proc.stdout[-2000:] +
                             proc.stderr[-2000:] + "\n")
        print(json.dumps({
            "bench": bench, "round": ROUND, "mode": mode,
            "gates": gates,
            **({"result": result} if result is not None else {}),
        }), flush=True)
        failed += [g["name"] for g in gates if g["status"].
                   startswith("fail")]
    if failed:
        sys.stderr.write(f"round {ROUND} gate failures: "
                         f"{', '.join(failed)}\n")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
