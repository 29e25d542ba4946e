"""Importability + argparse smoke for every benchmarks/bench_*.py.

The benchmarks run only on real TPU hardware, so nothing in CI executed
them and import-time drift (renamed ops, moved modules, jax API skew)
went unnoticed until someone sat down at a chip.  `--help` forces the
full module import plus argument parsing and must exit 0 in a few
seconds on CPU — cheap enough for tier-1, and it catches exactly the
drift class that cost round 5 a bench session."""

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHES = sorted(glob.glob(os.path.join(REPO, "benchmarks",
                                        "bench_*.py")))


def test_benchmarks_discovered():
    # the glob must see the suite; an empty list would vacuously pass
    assert len(BENCHES) >= 7, BENCHES
    names = {os.path.basename(p) for p in BENCHES}
    assert "bench_kv_quant.py" in names


def test_lint_cli_help_exits_zero():
    """The dynlint CLI rides the same drift gate as the benches: --help
    forces the full module import and argparse wiring (the --json
    contract itself is covered in tests/test_lint.py)."""
    r = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", "--help"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "--json" in r.stdout and "--baseline" in r.stdout


@pytest.mark.parametrize(
    "path", BENCHES, ids=[os.path.basename(p) for p in BENCHES])
def test_bench_help_exits_zero(path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, path, "--help"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "usage" in r.stdout.lower()
    if os.path.basename(path) == "bench_prefill_phases.py":
        # the attention-impl A/B mode (Pallas tile-skip kernel vs the
        # masked XLA reference, one JSON line with both variants' MFU)
        assert "--impl" in r.stdout
    if os.path.basename(path) == "bench_serving.py":
        # the timeline-tracing hook (obs/): --trace-out records the run
        # and prints the gap-attribution line
        assert "--trace-out" in r.stdout
        # SLO plane flags (obs/slo.py vocabulary, ms like the frontend)
        assert "--slo-ttft-ms" in r.stdout
        assert "--slo-itl-ms" in r.stdout
        # forensics plane A/B hook (obs/forensics.py)
        assert "--forensics" in r.stdout
        # KV-accounting plane A/B hook (obs/kv_ledger.py)
        assert "--kv-ledger" in r.stdout


def test_bench_serving_json_carries_slo_and_compiles_blocks():
    """The bench JSON schema's `slo` + `compiles` blocks must actually
    serialize from a (tiny, sped-up) run: the scoreboard the rounds are
    diffed on, not just flags in --help."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_serving.py"),
         "--requests", "8", "--rate", "40", "--speedup", "20",
         "--workers", "2", "--slo-ttft-ms", "2000", "--slo-itl-ms", "25"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    reps = [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]
    configs = {rep["config"] for rep in reps}
    assert any(c.startswith("agg-") for c in configs), configs
    assert any(c.startswith("disagg-") for c in configs), configs
    for rep in reps:
        if rep["config"] == "trace":
            continue
        # ms flags override the seconds-based defaults
        assert rep["slo"]["ttft_s"] == 2.0
        assert rep["slo"]["itl_s"] == 0.025
        assert 0.0 <= rep["slo"]["goodput"] <= 1.0
        # the mocker sim compiled prefill+decode, none of it mid-serving
        assert set(rep["compiles"]) == {"total", "serving"}
        assert rep["compiles"]["total"].get("prefill", 0) >= 1
        assert rep["compiles"]["total"].get("decode", 0) >= 1
        assert not any(rep["compiles"]["serving"].values())
        # fleet block (obs/fleet.py): peak imbalance / straggler count /
        # min KV headroom scraped back off the run's own registry
        fleet = rep["fleet"]
        assert fleet["imbalance"] >= 1.0
        assert fleet["stragglers"] >= 0
        assert 0.0 <= fleet["kv_headroom_min"] <= 1.0
        # tail-forensics block (obs/forensics.py, plane on by default):
        # worst retained exemplar's EXACT phase partition + the
        # realized-overlap rate read off the run's own registry
        tail = rep["tail"]
        assert tail["exemplars"] >= 1
        part = tail["p99_partition"]
        assert set(part) == {"queue", "route", "prefill", "transfer",
                             "decode", "stall"}
        # the pre-first-token phases sum to the exemplar's TTFT (the
        # partition's exactness property, visible in the bench block)
        pre = (part["queue"] + part["route"] + part["prefill"]
               + part["transfer"])
        assert abs(pre - tail["p99_ttft_ms"]) <= 0.02 * pre + 0.02


def test_bench_serving_kv_ledger_ab_streams_identical_and_clean():
    """--kv-ledger ab: the always-on accounting plane must be pure
    observation — byte-identical token streams with it on vs off (hard
    assert inside the bench) AND a post-run audit that reconciles
    exactly (0 violations, also a hard assert inside the bench).  What
    the plane costs is the chip's to show: `overhead_frac` is a ratio of
    two wall-clock rates of a 12-request run, which under the suite's
    six workers says nothing (0.62 once, ROADMAP D0), so no bound on it
    is held here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_serving.py"),
         "--requests", "12", "--rate", "50", "--input-len", "64",
         "--output-len", "8", "--speedup", "4", "--kv-ledger", "ab"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    (rep,) = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith("{")]
    assert rep["config"] == "kv_ledger_ab"
    assert rep["streams_identical"] is True
    assert rep["violations_total"] == 0
    assert rep["overhead_target_frac"] == 0.01
    assert "overhead_frac" in rep
    assert rep["kv_ledger"]["occupancy"]["g1"]["prefix_cached"] >= 0


def test_bench_serving_forensics_ab_streams_identical():
    """--forensics ab: the always-on plane must be pure observation —
    byte-identical token streams with it on vs off (hard assert inside
    the bench) and the worst exemplars kept.  What the plane costs is
    the chip's to show: `overhead_frac` is a ratio of two wall-clock
    rates of a 12-request run, which under the suite's six workers says
    nothing (the one failing case of the driver's run on PR 52), so no
    bound on it is held here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_serving.py"),
         "--requests", "12", "--rate", "50", "--input-len", "64",
         "--output-len", "8", "--speedup", "4", "--forensics", "ab"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    (rep,) = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith("{")]
    assert rep["config"] == "forensics_ab"
    assert rep["streams_identical"] is True
    assert rep["overhead_target_frac"] == 0.01
    assert "overhead_frac" in rep
    assert rep["tail"]["exemplars"] >= 1


def test_bench_global_router_smoke_closed_loop():
    """The PR 18 mega-fleet closed loop at smoke scale runs IN tier-1
    (seconds on CPU): 2 pools x 3 replica-sync'd frontends x mocker
    workers, with the correctness gates — byte-identity vs the
    single-frontend baseline and both pool classes routed — enforced
    even in smoke mode (the bench exits 1 on failure), and the
    latency/staleness measurement surfaces present per JSON line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_global_router.py"),
         "--mode", "smoke"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    (rep,) = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith("{")]
    status = {g["name"]: g["status"] for g in rep["gates"]}
    assert status["grouter_byte_identity"] == "pass"
    assert status["grouter_pools_routed"] == "pass"
    res = rep["result"]
    assert res["byte_identical"] is True and res["empty_streams"] == 0
    assert res["route_latency"]["count"] == res["streams"]
    # per-replica staleness + decision counts reported for every pool's
    # frontend tier (the replica-sync health surfaces)
    for pool in res["staleness"].values():
        assert len(pool["replicas"]) >= 3
        assert sum(r_["decisions"]
                   for r_ in pool["replicas"].values()) > 0


def test_bench_prefix_fleet_smoke_closed_loop():
    """The ISSUE-19 fleet-prefix-cache A/B at smoke scale runs IN
    tier-1 (seconds on CPU): warm fleet -> junk churn demotes prefixes
    into the shared G4 store -> a cold worker in a fresh namespace
    onboards them.  The mechanism gates — byte identity across arms,
    store populated, cold onboarding from G4, router-visible G4
    blocks, clean ledger audits — are enforced even in smoke mode (the
    bench exits 1 on failure); only the TTFT-penalty chip bars are
    skipped."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_prefix_fleet.py"),
         "--mode", "smoke"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    (rep,) = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith("{")]
    status = {g["name"]: g["status"] for g in rep["gates"]}
    assert status["prefix_fleet_byte_identity"] == "pass"
    assert status["prefix_fleet_store_populated"] == "pass"
    assert status["prefix_fleet_cold_onboard_g4"] == "pass"
    assert status["prefix_fleet_router_g4_visible"] == "pass"
    assert status["prefix_fleet_ledger_audit"] == "pass"
    assert status["prefix_fleet_cold_start_penalty"] == "skipped_smoke"
    res = rep["result"]
    g4, ctl = res["g4"], res["control"]
    # the cold worker really onboarded from the shared store, and the
    # control arm really had no tier ladder to lean on
    assert g4["cold_onboards"]["g4"] > 0 and g4["store_blobs"] > 0
    assert ctl["cold_onboards"]["g4"] == 0 and ctl["store_blobs"] == 0
    # G4 residency verdicts surface on the cold worker's /debug/kv
    assert sum(g4["cold_g4_residency"]["residency"].values()) > 0
    # even unenforced, the smoke-scale penalty must point the right
    # way: onboarding strictly cheaper than the control's recompute
    assert g4["cold_start_penalty"] < ctl["cold_start_penalty"]


def test_bench_chaos_cache_smoke_closed_loop():
    """The ISSUE-20 KV-integrity A/B at smoke scale runs IN tier-1
    (seconds on CPU): warm fleet -> junk churn spills prefixes into the
    shared G4 store -> the measure wave re-onboards them, once healthy
    and once under injected corruption + stalls.  The mechanism gates —
    byte identity across arms, store populated, real G4 onboarding,
    stall/breaker observation, 1:1 corrupt attribution, clean ledger
    audits — are enforced even in smoke mode (the bench exits 1 on
    failure); only the p90-TTFT-ratio chip bar is skipped."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_chaos_cache.py"),
         "--mode", "smoke"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    (rep,) = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith("{")]
    status = {g["name"]: g["status"] for g in rep["gates"]}
    assert status["chaos_cache_byte_identity"] == "pass"
    assert status["chaos_cache_store_populated"] == "pass"
    assert status["chaos_cache_control_onboard_g4"] == "pass"
    assert status["chaos_cache_stall_observed"] == "pass"
    assert status["chaos_cache_corrupt_attributed"] == "pass"
    assert status["chaos_cache_ledger_audit"] == "pass"
    assert status["chaos_cache_p90_ttft_ratio"] == "skipped_smoke"
    res = rep["result"]
    cha, ctl = res["chaos"], res["control"]
    # every materialized corruption quarantined AND attributed; the
    # healthy arm saw none of either
    hi = cha["integrity"]
    assert hi["quarantined"] > 0
    assert hi["ledger_corrupt_g4"] == hi["quarantined"]
    assert hi["breaker_trips"] > 0 and hi["timeouts"] > 0
    ci = ctl["integrity"]
    assert ci["quarantined"] == 0 and ci["breaker_trips"] == 0


def test_run_round_help_exits_zero():
    """benchmarks/run_round.py is not matched by the bench_*.py glob
    above, so it gets its own drift gate: --help must import the driver
    and exit 0, with the round's mode/subset knobs wired."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "run_round.py"), "--help"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "--mode" in r.stdout and "--only" in r.stdout


@pytest.mark.slow
def test_run_round_smoke_emits_gated_json_per_bench():
    """The round driver end to end at smoke scale: one JSON line per
    bench, every line labeled mode=smoke, and every TPU acceptance gate
    PRESENT but skipped (interpret/mocker numbers must never satisfy a
    chip bar).  This is the r07 cash-in path minus the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "run_round.py"), "--mode", "smoke"],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    lines = [json.loads(line) for line in r.stdout.splitlines()
             if line.startswith("{")]
    by_bench = {rep["bench"]: rep for rep in lines}
    assert set(by_bench) == {"prefill", "kv_quant", "serving",
                             "indexer", "global_router",
                             "prefix_fleet", "chaos_cache"}
    gate_names = set()
    for rep in by_bench.values():
        assert rep["round"] == "r07"
        assert rep["mode"] == "smoke"
        assert rep["gates"], rep
        for g in rep["gates"]:
            # chip bars are skipped at smoke scale; correctness bars
            # (indexer parity, grouter byte-identity/pool coverage)
            # are enforced in EVERY mode and must pass
            assert g["status"] in ("skipped_smoke", "pass"), g
            gate_names.add(g["name"])
        assert "result" in rep
    assert gate_names >= {"prefill_pallas_mfu", "int8_pallas_ge_bf16",
                          "zero_mid_serving_compiles",
                          "indexer_events_per_s", "indexer_query_p99_us",
                          "grouter_byte_identity",
                          "grouter_pools_routed",
                          "grouter_route_p99_ms",
                          "grouter_staleness_spread",
                          "prefix_fleet_byte_identity",
                          "prefix_fleet_cold_onboard_g4",
                          "prefix_fleet_cold_start_penalty",
                          "chaos_cache_byte_identity",
                          "chaos_cache_corrupt_attributed",
                          "chaos_cache_p90_ttft_ratio"}
    # the correctness bars really ran
    assert {g["name"]: g["status"]
            for g in by_bench["global_router"]["gates"]
            }["grouter_byte_identity"] == "pass"
    # the per-bench results carry the round's measurement surfaces
    assert "pallas_interpret" in by_bench["prefill"]["result"]["impls"]
    rows = by_bench["kv_quant"]["result"]["decode"]["rows"]
    assert {(r_["kv_dtype"], r_["attn_impl"]) for r_ in rows} >= {
        ("bf16", "pallas_interpret"), ("int8", "pallas_interpret")}
    assert by_bench["serving"]["result"]["impls"]["engine"] == "mocker"


def test_run_round_only_subset_and_impl_flag_vocab():
    """--only serving keeps the driver to one bench, and the serving
    bench's impl-stamp flag vocabulary (kept as literals so the mocker
    bench stays jax-free) must still cover the canonical impl tuples —
    the parity the bench's comment promises."""
    from dynamo_tpu.ops.fused_sampling import EPILOGUE_MODES
    from dynamo_tpu.ops.packed_prefill import PACKED_IMPLS
    from dynamo_tpu.ops.paged_attention import DECODE_IMPLS

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_serving.py"), "--help"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0
    for impl in (*PACKED_IMPLS, *DECODE_IMPLS, *EPILOGUE_MODES):
        assert impl in r.stdout, f"--help missing impl choice {impl!r}"
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "run_round.py"), "--mode", "smoke",
         "--only", "serving"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r2.returncode == 0, (r2.stdout[-2000:], r2.stderr[-2000:])
    lines = [json.loads(line) for line in r2.stdout.splitlines()
             if line.startswith("{")]
    assert [rep["bench"] for rep in lines] == ["serving"]


def test_bench_planner_loop_ab_closed_beats_static():
    """bench_planner_loop --policy ab at smoke scale: the closed loop
    must hold the latency targets with FEWER worker-seconds than static
    max-provisioning and zero errors — the bench itself exits 1 when
    the verdict fails, so the returncode is the acceptance gate.  The
    swing is shortened (10s) but keeps the 10× trough→peak ratio; the
    latency targets are generous because CI CPUs carry suite-parallel
    contention."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_planner_loop.py"),
         "--policy", "ab", "--duration-s", "10", "--rate-low", "0.4",
         "--rate-high", "4.0", "--max-replicas", "3",
         "--slo-ttft-ms", "2000", "--slo-itl-ms", "500"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    lines = [json.loads(line) for line in r.stdout.splitlines()
             if line.startswith("{")]
    by_cfg = {}
    for rep in lines:
        by_cfg.setdefault(rep["config"], []).append(rep)
    (v,) = by_cfg["planner_loop_ab"]
    assert v["ok"] is True, v
    assert v["closed_worker_seconds"] < v["static_worker_seconds"]
    closed = next(r for r in by_cfg["planner_loop"]
                  if r["policy"] == "closed")
    assert closed["errors"] == 0
    # the loop actually moved: at least one scale action happened
    assert sum(closed.get("actions", {}).values()) >= 1, closed
