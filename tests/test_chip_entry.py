"""The chip entry points must fail — never fall back — without a chip.

`chip_smoke.py` and `benchmarks/run_round.py --mode tpu` are what a later
session trusts as proof that the system ran on a TPU, so the one thing
tier-1 (which has no TPU) can pin is that neither can report success here,
and that their parents stay off JAX while children need the chip."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, timeout=300, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, capture_output=True, text=True,
        timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_without_chip_fails_and_never_says_ok():
    """JAX held to the CPU: the worker reports platform=cpu, the script
    stops there, exits non-zero and prints no `"ok": true`."""
    r = _run(["chip_smoke.py"])
    assert r.returncode not in (0, 3), (r.stdout[-2000:], r.stderr[-2000:])
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
    # it failed at the device line, before any request was served
    assert "worker device" in r.stdout and "/v1/models" not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program (the driver's third run): an
    ImportError, a non-zero exit and no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_parent_stays_off_jax():
    """Importing the script (what its parent process does before it
    spawns anything) must not import JAX."""
    r = _run(["-c", "import sys, chip_smoke; "
                    "print('jax' in sys.modules)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


def test_run_round_tpu_mode_without_chip_is_an_error():
    """--mode tpu never degrades to smoke rows: no TPU is an error
    before the first bench runs, and the mode is never guessed."""
    script = os.path.join(REPO, "benchmarks", "run_round.py")
    r = _run([script, "--mode", "tpu"], PYTHONPATH=REPO)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"bench"' not in r.stdout and "smoke" not in r.stdout
    # no --mode at all is a usage error, not auto-detection
    r2 = _run([script], PYTHONPATH=REPO)
    assert r2.returncode == 2 and "--mode" in r2.stderr
    # the parent decides without importing JAX
    r3 = _run(["-c", "import sys; sys.argv=['run_round']; "
                     "sys.path.insert(0, 'benchmarks'); import run_round; "
                     "print('jax' in sys.modules)"], PYTHONPATH=REPO)
    assert r3.returncode == 0 and r3.stdout.strip() == "False", r3.stderr


@pytest.mark.parametrize("bench", ["bench_prefill_phases.py",
                                   "bench_kv_quant.py"])
def test_kernel_bench_tpu_mode_without_chip_is_an_error(bench):
    """The kernel benches default to --mode tpu, where a missing TPU is
    an error and interpret mode is never a row."""
    r = _run([os.path.join(REPO, "benchmarks", bench)], PYTHONPATH=REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "pallas_interpret" not in r.stdout


def test_bench_py_without_chip_is_an_error():
    r = _run([os.path.join(REPO, "bench.py")], PYTHONPATH=REPO)
    assert r.returncode != 0 and "no TPU" in r.stderr
    assert '"value"' not in r.stdout


def test_compile_cache_placement(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset,
    the cache lives at one fixed path inside the checkout."""
    import jax

    from dynamo_tpu.runtime import device

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(device.CACHE_ENV, "/somewhere/else")
        assert device.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(device.CACHE_ENV)
        fixed = os.path.join(REPO, ".jax_cache")
        assert device.compile_cache_dir() == fixed
        assert device.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_peaks_unknown_kind_is_an_error():
    from dynamo_tpu.runtime.device import device_peaks, require_tpu

    assert device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(RuntimeError, match="no published peaks"):
        device_peaks("cpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        require_tpu()


@pytest.mark.parametrize("cell", ["mimo-v2-flash.reason-closed",
                                  "keye-vl-2.0.longctx-closed"])
def test_new_cell_rehearses_on_the_cpu(cell):
    """`benchmark/run.py --workload <cell> --rehearse`, for the cells
    that came with a family of their own, walks the cell's whole
    control flow at the
    configuration's tiny `rehearse` widths (files found, shapes agree,
    warm-up, the `correct` check against the float32 reference, a
    window, the readers), always exits 3 and prints no result line.
    Its own limit: 120 s."""
    import json

    r = _run([os.path.join("benchmark", "run.py"), "--workload", cell,
              "--rehearse", "--seconds", "5"], timeout=120)
    assert r.returncode == 3, (r.stdout[-2000:], r.stderr[-2000:])
    tag = "rehearsal result (CPU, tiny widths, not a measurement): "
    lines = [ln for ln in r.stdout.splitlines() if tag in ln]
    assert len(lines) == 1
    result = json.loads(lines[0].split(tag, 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    # the long-context cell stays out of `tpot_p95_ms` (PERF.md section 2)
    assert {"output_tok_per_s", "setup_s"} <= set(result["metrics"])
    assert ("tpot_p95_ms" in result["metrics"]) == cell.startswith("mimo")
    check = [ln for ln in r.stdout.splitlines() if "correct check: " in ln]
    assert json.loads(check[0].split("correct check: ", 1)[1])["ok"] is True
    # no result line: the last line is the log's, not a JSON object
    assert not r.stdout.rstrip().splitlines()[-1].startswith("{")
