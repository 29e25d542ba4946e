"""Serving latency benchmark: trace replay against in-proc mocker clusters.

CPU-only (no accelerator): the mocker's timing model simulates engine step
latency, so this measures ORCHESTRATION quality — routing, admission,
disagg hand-off — as TTFT/ITL percentiles and goodput, the same metric set
as the reference's router benchmarks (benchmarks/router/README.md:4-46).

Runs two topologies over the same synthesized trace and prints one JSON
report line per config:

  * agg     — N aggregated mocker workers, round-robin routing
  * disagg  — prefill fleet + decode fleet behind the PrefillOrchestrator

    python benchmarks/bench_serving.py [--requests 200] [--rate 16]
"""

import argparse
import asyncio
import json
import sys
import uuid

sys.path.insert(0, ".")

from dynamo_tpu.disagg.prefill_router import (  # noqa: E402
    ConditionalDisaggConfig,
    PrefillOrchestrator,
)
from dynamo_tpu.loadgen import replay, synthesize  # noqa: E402
from dynamo_tpu.mocker import MockEngineArgs, MockerWorker  # noqa: E402
from dynamo_tpu.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime import (  # noqa: E402
    DistributedRuntime,
    RuntimeConfig,
)

BLOCK = 16


def fresh_runtime():
    cfg = RuntimeConfig(discovery_backend="mem", event_plane="inproc")
    return DistributedRuntime(config=cfg, cluster_id=uuid.uuid4().hex)


def engine_args(role="both", overlap=True, fused=8, ledger=None):
    return MockEngineArgs(model_name="bench", block_size=BLOCK,
                          num_blocks=8192, speedup_ratio=1.0, role=role,
                          overlap_scheduling=overlap,
                          decode_fused_steps=fused,
                          kv_ledger=ledger)


class RunTrace:
    """Per-topology span recording: each bench run gets its own Tracer
    (service tagged with the config label, so merged dumps keep their
    tracks distinct) and reduces its own timeline to the obs.report gap
    block — sched_overhead/device_wait/idle/enqueue_ahead fractions and
    cont_burst_frac land in the run's JSON line next to the latency
    numbers they explain."""

    def __init__(self, label: str, out_path: str = ""):
        import os

        from dynamo_tpu import obs

        path = ""
        if out_path:
            # split on the BASENAME only: a dotted directory component
            # (/runs/2026.08/trace) must not become the split point
            root, ext = os.path.splitext(out_path)
            path = f"{root}.{label}{ext or '.json'}"
        self.tracer = obs.Tracer(service=f"bench-{label}",
                                 ring=8 * obs.DEFAULT_RING,
                                 out_path=path or None)
        self.path = path

    def __enter__(self):
        self.tracer.install()
        return self

    def __exit__(self, *exc):
        self.tracer.uninstall()
        return False

    def gap(self):
        from dynamo_tpu.obs.report import events_of_doc, report

        if self.path:
            self.path = self.tracer.dump() or ""
        return report(events_of_doc(self.tracer.chrome_trace()))["gap"]


class ForensicCapture:
    """Frontend-analogue forensics over the worker-contract stream: a
    RequestTracker per replayed request records the hop timeline
    (dispatched → first_token → decode_stall → finish) and the worker's
    forensic stamps, feeding a ForensicsPlane — so the bench exercises
    the always-on plane end to end and its JSON line carries the `tail`
    block.  Token streams are captured in BOTH modes (identical capture
    cost on either side of the A/B), so `--forensics ab` can assert the
    plane changes nothing about what clients see."""

    def __init__(self, enabled: bool, metrics=None):
        from dynamo_tpu.obs.forensics import ForensicsPlane

        self.enabled = enabled
        self.plane = ForensicsPlane(metrics) if enabled else None
        self.streams: dict = {}  # request_id -> [token ids]

    def wrap(self, client_fn, pass_tracker=False):
        from dynamo_tpu.frontend.request_trace import RequestTracker

        async def wrapped(req_dict):
            rid = req_dict.get("request_id", "")
            toks = self.streams.setdefault(rid, [])
            tracker = None
            if self.enabled:
                tracker = RequestTracker(
                    request_id=rid, model="bench", forensics=self.plane,
                    input_tokens=len(req_dict.get("token_ids") or ()))
                tracker.on_dispatch(None)
            finish = None
            # pass_tracker: a composite client (disagg orchestration)
            # records its own prefill_open/prefill_done hops, exactly
            # like the real frontend pipeline brackets maybe_prefill
            stream = (client_fn(req_dict, tracker=tracker) if pass_tracker
                      else client_fn(req_dict))
            async for item in stream:
                ids = item.get("token_ids") or ()
                toks.extend(ids)
                if tracker is not None:
                    stamp = (item.get("metrics") or {}).get("forensic")
                    if stamp is not None:
                        tracker.on_worker_stamp(stamp)
                    tracker.on_tokens(len(ids))
                    finish = item.get("finish_reason") or finish
                yield item
            if tracker is not None:
                tracker.finish(finish_reason=finish)

        return wrapped

    def tail_block(self, rt):
        """The bench JSON `tail` block: realized-overlap rate read back
        off the run's own metrics registry with the real parser (the
        fleet/compiles-block idiom), plus the worst retained exemplar's
        exact phase partition — the reservoir IS the tail, so its worst
        entry is the p99+ autopsy."""
        if self.plane is None:
            return None
        from prometheus_client.parser import text_string_to_metric_families

        out = dict(self.plane.counts())
        for fam in text_string_to_metric_families(
                rt.metrics.render().decode()):
            if fam.name == "dynamo_frontend_realized_overlap_ratio":
                out["realized_overlap_ratio"] = round(
                    fam.samples[0].value, 4)
        worst = self.plane.worst("ttft")
        if worst is not None:
            out["p99_ttft_ms"] = round(worst.ttft_ms or 0.0, 3)
            out["p99_partition"] = {p: round(v, 3) for p, v in
                                    worst.partition.items()}
        return out


async def sample_fleet_peaks(workers, stop: asyncio.Event, peaks: dict):
    """Track the fleet-plane headline AT PEAK while the replay runs:
    worst load imbalance, worst straggler count, minimum KV headroom —
    sampled from the same per-worker debug states obs.fleet scrapes,
    reduced by the same summarize_states."""
    from dynamo_tpu.obs.fleet import summarize_states

    while not stop.is_set():
        s = summarize_states([w.debug_state() for w in workers])
        peaks["imbalance"] = max(peaks.get("imbalance", 1.0),
                                 s["imbalance"])
        peaks["stragglers"] = max(peaks.get("stragglers", 0),
                                  s["straggler_count"])
        peaks["kv_headroom_min"] = min(peaks.get("kv_headroom_min", 1.0),
                                       s["kv_headroom_min"])
        peaks["_last"] = s
        try:
            await asyncio.wait_for(stop.wait(), 0.05)
        except asyncio.TimeoutError:
            pass


async def collect_fleet(rt, workers, peaks: dict):
    """`fleet` block for the bench JSON: export the peak-annotated
    summary through the fleet gauge surface (obs/fleet.py), then read
    the numbers back off the run's own registry with the prometheus
    parser — the same families a production scrape of a fleet exporter
    would see."""
    import time

    from prometheus_client.parser import text_string_to_metric_families

    from dynamo_tpu.obs.fleet import FleetSnapshot, export_fleet_gauges, \
        summarize_states

    summary = peaks.get("_last") or summarize_states(
        [w.debug_state() for w in workers])
    summary["imbalance"] = peaks.get("imbalance", summary["imbalance"])
    summary["straggler_count"] = peaks.get("stragglers",
                                           summary["straggler_count"])
    summary["kv_headroom_min"] = peaks.get("kv_headroom_min",
                                           summary["kv_headroom_min"])
    export_fleet_gauges(
        rt.metrics.scoped(component="fleet"),
        FleetSnapshot(ts_unix=time.time(), workers=[], frontends=[],
                      summary=summary))
    out = {}
    for fam in text_string_to_metric_families(rt.metrics.render().decode()):
        if fam.name == "dynamo_fleet_load_imbalance":
            out["imbalance"] = round(fam.samples[0].value, 4)
        elif fam.name == "dynamo_fleet_straggler_workers":
            out["stragglers"] = int(fam.samples[0].value)
        elif fam.name == "dynamo_fleet_kv_headroom_min":
            out["kv_headroom_min"] = round(fam.samples[0].value, 4)
    return out


def collect_kv_ledger(workers):
    """`kv_ledger` entry for the bench JSON `fleet` block: run each
    worker's ON-DEMAND ledger audit (the /debug/kv path) after the
    replay and reduce with the fleet's own rollup — a clean bench run
    must reconcile exactly (violations_total == 0), which is the
    acceptance gate --kv-ledger ab asserts."""
    from dynamo_tpu.obs.fleet import reduce_kv_ledgers

    rollup = reduce_kv_ledgers([w.kv_debug() for w in workers])
    if rollup is None:
        return {}
    return {"kv_ledger": {
        "violations_total": rollup["violations_total"],
        "violations": rollup["violations"],
        "occupancy": rollup["occupancy"],
    }}


async def collect_compiles(rt):
    """Scrape the run's worker counters (one load-loop tick after the
    replay) into the bench JSON's compiles block: compile counts per
    program family — the same names a production Prometheus would
    scrape, parsed with the same parser."""
    from prometheus_client.parser import text_string_to_metric_families

    await asyncio.sleep(0.4)  # let the workers' 0.25s load loops tick
    out = {"total": {}, "serving": {}}
    for fam in text_string_to_metric_families(
            rt.metrics.render().decode()):
        if fam.name in ("dynamo_engine_compiles",
                        "dynamo_engine_serving_compiles"):
            # serving_compiles = compiles that landed with requests in
            # flight (obs/compile_watch.py): each one is a serving
            # stall, and the bench round's zero-mid-serving gate reads
            # this block
            key_out = ("total" if fam.name == "dynamo_engine_compiles"
                       else "serving")
            for s in fam.samples:
                if not s.name.endswith("_total"):
                    continue
                key = s.labels.get("family", "")
                out[key_out][key] = out[key_out].get(key, 0) + int(s.value)
    return out


async def bench_agg(rows, n_workers, args, overlap=True, label="agg",
                    forensics=True, ledger=None):
    rt = await fresh_runtime().start()
    workers = [
        await MockerWorker(rt, engine_args(overlap=overlap,
                                           ledger=ledger),
                           component="backend").start()
        for _ in range(n_workers)
    ]
    client = await (rt.namespace("dynamo").component("backend")
                    .endpoint("generate").client()).start()
    await client.wait_for_instances()
    cap = ForensicCapture(forensics,
                          rt.metrics.scoped(component="frontend"))
    stop, peaks = asyncio.Event(), {}
    sampler = asyncio.create_task(sample_fleet_peaks(workers, stop, peaks))
    with RunTrace(label, args.trace_out) as rtrace:
        try:
            report = await replay(cap.wrap(client.generate), rows,
                                  block_size=BLOCK, speedup=args.speedup)
        finally:
            stop.set()
            await sampler
        compiles = await collect_compiles(rt)
    gap = rtrace.gap()
    fleet = await collect_fleet(rt, workers, peaks)
    fleet.update(collect_kv_ledger(workers))
    tail = cap.tail_block(rt)
    await client.close()
    for w in workers:
        await w.close()
    await rt.shutdown()
    return report, compiles, fleet, gap, rtrace.path, tail, cap


async def bench_disagg(rows, n_prefill, n_decode, args, overlap=True,
                       label="disagg", forensics=True, ledger=None):
    rt = await fresh_runtime().start()
    prefills = [
        await MockerWorker(rt, engine_args("prefill", overlap=overlap,
                                           ledger=ledger),
                           component="prefill").start()
        for _ in range(n_prefill)
    ]
    decodes = [
        await MockerWorker(rt, engine_args("decode", overlap=overlap,
                                           ledger=ledger),
                           component="backend").start()
        for _ in range(n_decode)
    ]
    pclient = await (rt.namespace("dynamo").component("prefill")
                     .endpoint("generate").client()).start()
    dclient = await (rt.namespace("dynamo").component("backend")
                     .endpoint("generate").client()).start()
    await pclient.wait_for_instances()
    await dclient.wait_for_instances()
    orch = PrefillOrchestrator(
        pclient, ConditionalDisaggConfig(always_remote=True))

    async def client_fn(req_dict, tracker=None):
        import time as _time

        t_hop = _time.monotonic()
        routed = await orch.maybe_prefill(
            PreprocessedRequest.from_dict(req_dict))
        if tracker is not None and routed.disaggregated_params:
            # same bracketing as the frontend pipeline: the remote
            # prefill IS the first dispatch, and first_token after the
            # decode dispatch partitions as `transfer`
            tracker.hop("prefill_open", at=t_hop)
            tracker.hop("prefill_done")
            tracker.mark_dispatching(at=t_hop)
        async for item in dclient.generate(routed.to_dict()):
            yield item

    cap = ForensicCapture(forensics,
                          rt.metrics.scoped(component="frontend"))
    stop, peaks = asyncio.Event(), {}
    sampler = asyncio.create_task(
        sample_fleet_peaks(prefills + decodes, stop, peaks))
    with RunTrace(label, args.trace_out) as rtrace:
        try:
            report = await replay(cap.wrap(client_fn, pass_tracker=True),
                                  rows,
                                  block_size=BLOCK, speedup=args.speedup)
        finally:
            stop.set()
            await sampler
        compiles = await collect_compiles(rt)
    gap = rtrace.gap()
    fleet = await collect_fleet(rt, prefills + decodes, peaks)
    fleet.update(collect_kv_ledger(prefills + decodes))
    tail = cap.tail_block(rt)
    await orch.close()
    await pclient.close()
    await dclient.close()
    for w in prefills + decodes:
        await w.close()
    await rt.shutdown()
    return report, compiles, fleet, gap, rtrace.path, tail, cap


async def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=100)
    p.add_argument("--rate", type=float, default=16.0)
    p.add_argument("--input-len", type=int, default=384)
    p.add_argument("--output-len", type=int, default=24)
    p.add_argument("--prefix-groups", type=int, default=8)
    p.add_argument("--speedup", type=float, default=1.0)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--slo-ttft", type=float, default=2.0)
    p.add_argument("--slo-itl", type=float, default=0.025)
    # ms-denominated aliases matching the frontend's --slo-* flags
    # (obs/slo.py); when given they override the seconds-based knobs
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT SLO target in ms (overrides --slo-ttft; "
                        "same convention as the frontend's flag)")
    p.add_argument("--slo-itl-ms", type=float, default=None,
                   help="mean-ITL SLO target in ms (overrides "
                        "--slo-itl)")
    p.add_argument("--trace-out", default="",
                   help="dump each topology's Perfetto-loadable Chrome "
                        "trace to PATH with the config label inserted "
                        "before the extension, and print a merged "
                        "obs.report gap-attribution line (the per-run "
                        "gap fracs are in every JSON line regardless)")
    p.add_argument("--overlap", choices=["on", "off", "ab"], default="on",
                   help="scheduler mode for the mocker engines: "
                        "overlapped (default), lockstep sync, or 'ab' — "
                        "run every topology in BOTH modes so the "
                        "overlapped scheduler's win is measurable in "
                        "one invocation")
    p.add_argument("--forensics", choices=["on", "off", "ab"],
                   default="on",
                   help="per-request forensics plane "
                        "(obs/forensics.py): on (default — every JSON "
                        "line carries a `tail` block), off, or 'ab' — "
                        "run the agg topology with the plane off then "
                        "on over the SAME trace, assert byte-identical "
                        "token streams, and print a forensics_ab line "
                        "with the measured throughput overhead "
                        "(target <1%%)")
    p.add_argument("--kv-ledger", choices=["on", "off", "ab"],
                   default="on",
                   help="KV block-lifecycle ledger + auditor "
                        "(obs/kv_ledger.py): on (default — every JSON "
                        "line's `fleet` block carries the post-run "
                        "audit rollup, which must reconcile clean), "
                        "off, or 'ab' — run the agg topology with the "
                        "plane off then on over the SAME trace, assert "
                        "byte-identical token streams AND a clean "
                        "audit, and print a kv_ledger_ab line with the "
                        "measured throughput overhead (target <1%%)")
    # kernel-impl bookkeeping for round scoreboards: the mocker's timing
    # model dispatches no real kernels, so these flags only STAMP the
    # settings a paired on-chip run used into every JSON line (the
    # `impls` block), keeping r06 rows self-describing next to rows from
    # the real engine.  Choices mirror ops/paged_attention.DECODE_IMPLS,
    # ops/packed_prefill.PACKED_IMPLS and ops/fused_sampling
    # .EPILOGUE_MODES as literals — importing those modules would pull
    # jax into this deliberately jax-free bench (tests pin the parity).
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "pallas", "pallas_interpret", "jnp",
                            "jnp_bf16"],
                   help="decode attention impl stamped into the JSON "
                        "`impls` block")
    p.add_argument("--packed-attn-impl", default="auto",
                   choices=["auto", "xla", "pallas", "pallas_interpret"],
                   help="packed-prefill impl stamped into the JSON "
                        "`impls` block")
    p.add_argument("--sampling-epilogue", default="off",
                   choices=["off", "fused"],
                   help="sampling epilogue mode stamped into the JSON "
                        "`impls` block")
    args = p.parse_args()

    rows = synthesize(args.requests, rate_rps=args.rate,
                      input_len=args.input_len, output_len=args.output_len,
                      block_size=BLOCK, prefix_groups=args.prefix_groups,
                      seed=11)
    slo_ttft_s = (args.slo_ttft_ms / 1000.0
                  if args.slo_ttft_ms is not None else args.slo_ttft)
    slo_itl_s = (args.slo_itl_ms / 1000.0
                 if args.slo_itl_ms is not None else args.slo_itl)

    # the headline gap-report fracs every JSON line carries (the
    # item-3 scoreboard: sched_overhead -> ~0 and cont_burst -> 1 is
    # what the overlapped scheduler is FOR; the rest partitions where
    # the remaining wall time goes)
    GAP_KEYS = ("sched_overhead_frac", "enqueue_ahead_frac",
                "device_wait_frac", "idle_frac", "cont_burst_frac")

    def line(config, summary, compiles, fleet, gap, tail=None):
        # stable bench JSON schema: the `slo` block mirrors the
        # frontend SLO plane's vocabulary (targets + goodput fraction),
        # `compiles` the workers' compile counters (total and
        # mid-serving, per family), `fleet` the obs.fleet headline
        # at peak (imbalance, straggler count, min KV headroom), and
        # `gap` the obs.report wall partition of this run's own engine
        # tracks — a scoreboard diff across rounds reads the same
        # numbers a live scrape/trace would
        gp = summary.get("goodput", {})
        total = summary.get("requests", 0)
        return json.dumps({
            "config": config, **summary,
            # effective kernel/epilogue settings for this row (mocker =
            # simulated step timing; the settings describe the paired
            # on-chip configuration a round scoreboard lines this row
            # up against)
            "impls": {
                "engine": "mocker",
                "attn_impl": args.attn_impl,
                "packed_attn_impl": args.packed_attn_impl,
                "sampling_epilogue": args.sampling_epilogue,
            },
            "slo": {
                "ttft_s": slo_ttft_s, "itl_s": slo_itl_s,
                "goodput": (round(gp.get("good_requests", 0) / total, 4)
                            if total else None),
                "good_rps": gp.get("good_rps"),
            },
            "compiles": compiles,
            "fleet": fleet,
            "gap": {k: gap[k] for k in GAP_KEYS if k in gap},
            # tail-forensics block (obs/forensics.py via the replay's
            # per-request trackers): worst retained exemplar's exact
            # phase partition + the realized-overlap rate, read back
            # off the run's own registry
            **({"tail": tail} if tail is not None else {}),
        })

    if args.kv_ledger == "ab":
        # A/B smoke: the SAME trace against the agg topology with the
        # ledger off then on.  The ledger is pure accounting — the
        # token streams must be byte-identical (hard assert), the ON
        # run's post-run audit must reconcile exactly (0 violations),
        # and the throughput delta is the always-on overhead (target
        # <1%; open-loop arrivals keep the rate comparison stable)
        await bench_agg(rows[: min(len(rows), 8)], args.workers, args,
                        label="agg-kvledger-warmup", ledger=True)
        off, *_rest_off, cap_off = await bench_agg(
            rows, args.workers, args, label="agg-kvledger-off",
            ledger=False)
        on, _compiles, fleet_on, _gap, _path, _tail, cap_on = await bench_agg(
            rows, args.workers, args, label="agg-kvledger-on",
            ledger=True)
        s_off = off.summary(slo_ttft_s, slo_itl_s)
        s_on = on.summary(slo_ttft_s, slo_itl_s)
        tps_off = s_off["output_tokens_per_s"]
        tps_on = s_on["output_tokens_per_s"]
        overhead = (1.0 - tps_on / tps_off) if tps_off else 0.0
        identical = cap_off.streams == cap_on.streams
        kvl = fleet_on.get("kv_ledger") or {}
        print(json.dumps({
            "config": "kv_ledger_ab",
            "streams_identical": identical,
            "tok_s_off": tps_off, "tok_s_on": tps_on,
            "overhead_frac": round(overhead, 4),
            "overhead_target_frac": 0.01,
            "overhead_ok": overhead < 0.01,
            "violations_total": kvl.get("violations_total"),
            "kv_ledger": kvl,
        }))
        if not identical:
            raise SystemExit(
                "kv ledger changed the token streams — it must be pure "
                "accounting")
        if kvl.get("violations_total", 0) != 0:
            raise SystemExit(
                f"kv ledger audit did not reconcile clean: "
                f"{kvl.get('violations')}")
        return

    if args.forensics == "ab":
        # A/B smoke: the SAME trace against the agg topology with the
        # plane off then on.  The plane is pure observation — the token
        # streams must be byte-identical (hard assert), and the
        # throughput delta is the always-on overhead (target <1%; the
        # open-loop arrival schedule makes the rate comparison stable)
        # throwaway warmup so the first measured run doesn't eat the
        # process's import/infra cold start and bias the comparison
        await bench_agg(rows[: min(len(rows), 8)], args.workers, args,
                        label="agg-forensics-warmup", forensics=True)
        off, *_rest_off, cap_off = await bench_agg(
            rows, args.workers, args, label="agg-forensics-off",
            forensics=False)
        on, _compiles, _fleet, _gap, _path, tail, cap_on = await bench_agg(
            rows, args.workers, args, label="agg-forensics-on",
            forensics=True)
        s_off = off.summary(slo_ttft_s, slo_itl_s)
        s_on = on.summary(slo_ttft_s, slo_itl_s)
        tps_off = s_off["output_tokens_per_s"]
        tps_on = s_on["output_tokens_per_s"]
        overhead = (1.0 - tps_on / tps_off) if tps_off else 0.0
        identical = cap_off.streams == cap_on.streams
        print(json.dumps({
            "config": "forensics_ab",
            "streams_identical": identical,
            "tok_s_off": tps_off, "tok_s_on": tps_on,
            "overhead_frac": round(overhead, 4),
            "overhead_target_frac": 0.01,
            "overhead_ok": overhead < 0.01,
            "tail": tail,
        }))
        if not identical:
            raise SystemExit(
                "forensics plane changed the token streams — it must be "
                "pure observation")
        return

    modes = {"on": [(True, "overlap")], "off": [(False, "sync")],
             "ab": [(False, "sync"), (True, "overlap")]}[args.overlap]
    forensics_on = args.forensics == "on"
    # on = follow DYN_KV_LEDGER (default-on); off pins the plane off
    ledger = None if args.kv_ledger == "on" else False
    np_, nd = max(1, args.workers // 2), max(1, args.workers // 2)
    trace_paths = []
    for ov, tag in modes:
        suffix = f"-{tag}" if args.overlap == "ab" else ""
        label = f"agg-{args.workers}w{suffix}"
        agg, compiles, fleet, gap, path, tail, _cap = await bench_agg(
            rows, args.workers, args, overlap=ov, label=label,
            forensics=forensics_on, ledger=ledger)
        trace_paths.append(path)
        print(line(label, agg.summary(slo_ttft_s, slo_itl_s), compiles,
                   fleet, gap, tail))
        label = f"disagg-{np_}p{nd}d{suffix}"
        dis, compiles, fleet, gap, path, tail, _cap = await bench_disagg(
            rows, np_, nd, args, overlap=ov, label=label,
            forensics=forensics_on, ledger=ledger)
        trace_paths.append(path)
        print(line(label, dis.summary(slo_ttft_s, slo_itl_s), compiles,
                   fleet, gap, tail))

    if args.trace_out:
        from dynamo_tpu.obs.report import report_paths

        paths = [p for p in trace_paths if p]
        if not paths:
            print(json.dumps({"config": "trace",
                              "error": f"trace dump to "
                                       f"{args.trace_out!r} failed"}))
        else:
            print(json.dumps({"config": "trace", "trace_out": paths,
                              **report_paths(paths)["gap"]}))


if __name__ == "__main__":
    asyncio.run(main())
