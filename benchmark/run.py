#!/usr/bin/env python3
"""One cell of BENCHMARK.json, in one process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

builds the cell's engine (random weights from the configuration's fixed
weights seed), warms up, checks the engine against the class's float32
reference, clears the KV blocks, measures for `--seconds`, and prints one
JSON object as the last line of its output.  `--seed` seeds the traffic
only.  Without a TPU it exits non-zero and prints no result.

    --sweep RATES   one set-up, then each offered rate of the cell's mix
                    for --seconds each; prints a table, no result line
    --keep-records DIR  write each window's per-request records, counter
                    deltas and the engine's step records to
                    DIR/<cell>.s<seed>.json.gz (benchmark/lib/records.py)
    --rehearse      tiny widths on the CPU to walk the control flow;
                    prints no result line and always exits 3

Every run logs the window's counter deltas on stderr, and every thread's
stack where the engine stands still, so that an untraced run that reads far
off leaves something to say why.

Which files make up a cell is in benchmark/README.md.  This file holds
no cell, configuration, mix or metric name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import spec  # noqa: E402

TRACE_SECONDS = 5.0
OUT_DIR = os.path.join(spec.REPO_ROOT, ".bench_out")


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_PROCESS:8.2f}s] {msg}", flush=True)


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # per-call Python tracing slows the host
    opts.enable_hlo_proto = False
    return opts


async def measure(engine, cfg, mix, seconds: float, seed: int,
                  trace_dir: str = "", keep_records: str = "") -> dict:
    """Pre-roll, then one window of `seconds`; with `trace_dir`, the
    profiler runs over TRACE_SECONDS in the middle of it.  Returns the
    observations the metric readers work from; with `keep_records` (a
    file) they are also written there."""
    import jax

    from benchmark.lib.client import Client, settle
    from benchmark.lib.records import (GcPauses, StallWatch, counter_deltas,
                                       dump)

    loop_kind = spec.loop_module(mix)
    rows = loop_kind.build(mix, seconds, seed)
    client = Client(engine, cfg.vocab_size, f"s{seed}")
    t_open = time.perf_counter() + float(mix.get("preroll_s", 0.0))
    t_close = t_open + seconds
    mono_off = time.monotonic() - time.perf_counter()
    watch = StallWatch(
        lambda: engine.metrics.get("steps", 0),
        lambda: any(r["end_t"] is None for r in list(client.records)))
    with GcPauses() as pauses, watch:      # over the pre-roll too
        driver = asyncio.create_task(
            loop_kind.drive(rows, client, t_open, t_close, mix))
        await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
        ctx = {"counters_open": dict(engine.metrics), "trace": None}
        cpu_open = time.process_time()
        if trace_dir:
            span = min(TRACE_SECONDS, seconds / 2.0)
            await asyncio.sleep(max(
                0.0, t_open + (seconds - span) / 2.0 - time.perf_counter()))
            await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                                    profiler_options=_trace_options())
            ctx["trace_window"] = [time.perf_counter(), None]
            ctx["trace_counters"] = [dict(engine.metrics), None]
            await asyncio.sleep(span)
            ctx["trace_window"][1] = time.perf_counter()
            ctx["trace_counters"][1] = dict(engine.metrics)
            ctx["fpm"] = [dict(r) for r in list(engine.fpm)]
            await asyncio.to_thread(jax.profiler.stop_trace)
        tasks = await driver
    ctx["host"] = {"process_cpu_s": round(time.process_time() - cpu_open, 3),
                   "loadavg_1m": os.getloadavg()[0],
                   "gc": pauses.summary(t_open),
                   "stalls": [[round(t - t_open, 3), round(d, 3)]
                              for t, d in watch.stalls]}
    ctx["counters_close"] = dict(engine.metrics)
    ctx["fpm_close"] = [dict(r) for r in list(engine.fpm)]
    ctx["drained"] = await settle(tasks)
    ctx.update(records=client.records, window=(t_open, t_close),
               mono_offset=mono_off, seconds=seconds,
               compile_events=[dict(e) for e in engine.compile_watch.events])
    deltas = counter_deltas(ctx)
    print(f"counters seed {seed}: {json.dumps(deltas)}", file=sys.stderr,
          flush=True)
    if keep_records:
        dump(ctx, keep_records, {"seed": seed, "seconds": seconds,
                                 "rate_rps": mix.get("rate_rps"),
                                 "counters": deltas})
    return ctx


def _xplane_events(trace_dir: str):
    from benchmark.lib.trace_reduce import load_xplane

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return (load_xplane(files[0]), files[0]) if files else (None, "")


async def decode_probe(engine, cfg, trace_dir: str) -> list:
    """Trace two short requests, the second sent while the first decodes:
    every program that ran, but the one that ran first (the prefill, which
    both share), is a decode program: the full burst of a decode-only
    stretch and the short burst that interleaves with a pending prefill.
    (The engine's jits are anonymous: benchmark/lib/trace_reduce.py.)"""
    import jax

    from benchmark.lib.client import Client
    from benchmark.lib.trace_reduce import decode_names_from_probe
    from benchmark.lib.traffic import Row

    shutil.rmtree(trace_dir, ignore_errors=True)
    await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                            profiler_options=_trace_options())
    client = Client(engine, cfg.vocab_size, "probe")
    first = asyncio.create_task(client.request(
        Row(index=0, prompt_len=64, max_tokens=48, seed=7), 0.0))
    while not first.done() and (
            not client.records or len(client.records[0]["tokens"]) < 8):
        await asyncio.sleep(0.01)
    await client.request(Row(index=1, prompt_len=64, max_tokens=24, seed=7),
                         0.0)
    await first
    await asyncio.to_thread(jax.profiler.stop_trace)
    events, _ = await asyncio.to_thread(_xplane_events, trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    for dev in (events or {"devices": {}})["devices"].values():
        log("probe modules (name, ms): " + json.dumps(
            [[n, round(d / 1e6, 2)] for n, _, d in dev["modules"]
             if d >= 1e5]))
    return decode_names_from_probe(events) if events else []


def reduce_trace(trace_dir: str, decode_names, keep: str = "") -> dict:
    from benchmark.lib.trace_reduce import cut_for_tests, reduce_events

    events, path = _xplane_events(trace_dir)
    if events is None:
        return {}
    reduced = reduce_events(events, decode_names)
    if keep:      # a first look by hand, and the cut the tests keep
        os.makedirs(keep, exist_ok=True)
        if os.path.getsize(path) < 40 << 20:
            shutil.copy(path, keep)
        with open(os.path.join(keep, "recorded_trace.json"), "w") as f:
            json.dump(cut_for_tests(events, decode_names), f)
        with open(os.path.join(keep, "reduced.json"), "w") as f:
            json.dump(reduced, f, indent=1)
    return reduced


def device_block(ident: dict, ctx: dict) -> dict:
    import jax

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    dev = {"platform": ident["platform"], "kind": ident["kind"],
           "count": ident["count"], "memory_peak_bytes": int(peak)}
    tr = ctx.get("trace")
    if tr:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["extent_s"]
    return dev


async def run(args) -> int:
    from benchmark.lib import correct, records, roofline, warmup
    from benchmark.lib.model import build_engine
    from benchmark.lib.peaks import device_peaks
    from benchmark.lib.stats import counted
    from dynamo_tpu.models import get_family
    from dynamo_tpu.runtime.device import (
        device_identity,
        enable_compile_cache,
        require_tpu,
    )

    cell = spec.load_cell(args.workload)
    mix, config = cell["mix"], cell["config"]
    ident = device_identity() if args.rehearse else require_tpu()
    if ident["count"] < cell["workload"]["chips"]:
        raise RuntimeError(f"cell needs {cell['workload']['chips']} chips, "
                           f"JAX sees {ident['count']}")
    peaks = None if args.rehearse else device_peaks(ident["kind"])
    log(f"device {ident}; compile cache "
        f"{'off' if args.rehearse else enable_compile_cache()}")
    if args.rehearse:
        mix = {**mix, **config["rehearse"].get("mix", {})}

    engine, cfg = build_engine(config, cell["config_entry"]["name"],
                               args.rehearse)
    log("engine built")
    engine.warmup_decode()
    log(f"decode programs warm: {dict(engine.compile_watch.counts)}")
    warm = await warmup.warm_prefill(engine, cfg.vocab_size, mix, log)
    klass = spec.model_class(config)
    check = await correct.check_engine(engine, cfg, klass.reference_logits)
    log(f"correct check: {json.dumps(check)}")
    await engine.clear_kv_blocks()
    roof = roofline.describe(engine.params, cfg, get_family(cfg),
                             engine.config.block_size,
                             klass.attn_pair_flops(cfg))

    if args.sweep:
        from benchmark.lib.sweep import sweep

        await sweep(engine, cfg, mix, args, measure, log)
        await engine.close()
        return 0

    trace_dir, decode_names = "", []
    if args.trace:
        trace_dir = os.path.join(OUT_DIR, "trace", args.workload)
        decode_names = await decode_probe(engine, cfg, trace_dir + ".probe")
        log(f"decode programs by the probe: {decode_names}")
        await engine.clear_kv_blocks()
    t_measure = time.perf_counter()
    ctx = await measure(engine, cfg, mix, args.seconds, args.seed, trace_dir,
                        records.path(args.keep_records, args.workload,
                                     args.seed))
    await engine.close()
    ctx["setup_s"] = ctx["window"][0] - T_PROCESS
    if trace_dir:
        ctx["trace"] = await asyncio.to_thread(
            reduce_trace, trace_dir, decode_names, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.update(roofline=roof, peaks=peaks, chips=cell["workload"]["chips"],
               counted=counted(ctx["records"], *ctx["window"]))
    c = ctx["counted"]
    log(f"window {args.seconds}s after {t_measure - T_PROCESS:.1f}s: "
        f"{len(c['ok'])} ok, {len(c['failed'])} failed, {len(c['inflight'])} "
        f"in flight at close (drained {ctx['drained']} tasks), warm-up "
        f"rounds {warm['rounds']}")

    group, gdir = (("per_layer", "layer_metrics") if args.trace
                   else ("end_to_end", "e2e_metrics"))
    metrics = {}
    for m in spec.cell_metrics(args.workload, group):
        value = spec.metric_reader(gdir, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(check["ok"]
                        and correct.requests_well_formed(ctx["records"])),
        "attempted": len(c["ok"]) + len(c["failed"]),
        "failed": len(c["failed"]),
        "metrics": metrics,
        "device": device_block(ident, ctx),
    }
    if ctx.get("trace"):
        result["breakdown"] = {k: ctx["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    if args.rehearse:
        log("rehearsal result (CPU, tiny widths, not a measurement): "
            + json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (requests/s)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default="",
                    help="directory to copy the raw .xplane.pb into")
    ap.add_argument("--keep-records", default="",
                    help="directory for each window's records")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.seconds is None:
        args.seconds = float(spec.load_benchmark()["run_seconds"])
    return asyncio.run(run(args))


if __name__ == "__main__":
    sys.exit(main())
