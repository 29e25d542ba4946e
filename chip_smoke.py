"""chip_smoke.py — the quickest proof that the serving path runs on the chip.

    python chip_smoke.py             one TPU chip: llama-3b, full depth/width
    python chip_smoke.py --chips 4   four chips:   llama-8b at tp=4 (only)

One chip (the default, what the driver runs):

  serve     `python -m dynamo_tpu.engine --model llama-3b` (owns the chip)
            + `python -m dynamo_tpu.frontend --router-mode kv`, file
            discovery between them, HTTP from this script: /v1/models, a
            streamed chat completion, concurrent completions of ~300-2000
            prompt tokens x 64 output tokens, one greedy prompt twice
            (same text), then SIGTERM and exit code 0 from both.
  numerics  after the worker is gone, a child run in turn: the COMPILED
            Pallas decode and packed-prefill kernels against their XLA
            references at llama-3b widths (bf16 and int8), and packed
            "auto" at the doc cell's shape (2048 tokens, one row, table
            width 50, Mistral's widths) against the scan; the latent
            (MLA) decode kernel against its jnp body at Ling's and
            Moonlight's shapes; JaxEngine
            serving one request cold and again as a prefix-cache hit
            (first-token logits of the engine's own two prefills within
            a bf16 tolerance); and JaxEngine with attn_impl /
            packed_attn_impl = "pallas" against the default impls.

Four chips (`--chips 4`, run by hand): the same worker + frontend path
with `--model llama-8b --tp 4`, a check that parameters and KV sit about
a quarter on each device, and tp=4 against tp=1 at the same widths cut to
8 layers in one process.  No other phase.

This parent never imports JAX: a chip belongs to one process at a time,
and the children take it in turn.  The device in the last line is what
the worker — the process that held the chip — reported.  Any phase that
fails exits non-zero; without a TPU the script never prints `"ok": true`.
`--rehearse` walks the same control flow off-chip (tiny model, interpret
kernels) and always exits 3.

Times printed here are one run on the named device, not a benchmark.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# jax-free import (checked below); absent in a directory that holds only
# this script, which is then an ImportError and a non-zero exit
from dynamo_tpu.runtime.device import CACHE_ENV, compile_cache_dir  # noqa: E402

ADMIN_TOKEN = "chip-smoke"
OUT_DIR = os.path.join(REPO, "chiprun_out", "smoke")
BUDGET_S = 1150.0  # the driver allows 1200 s, compilation included

# numerics tolerances (stated, bf16): attention outputs are convex
# combinations of N(0,1) values, so |out| <= ~1 and bf16 rounding of
# operands/outputs is ~2^-8; logits are compared relative to their range
KERNEL_ATOL = 0.05
LOGITS_REL_TOL = 0.05


class SmokeFailure(Exception):
    pass


def model_for(args) -> str:
    """The one model each way of running serves; not an option, so the
    result line cannot be earned at a smaller width."""
    if args.rehearse:
        return "tiny"
    return "llama-8b" if args.chips > 1 else "llama-3b"


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


class Child:
    """One child process with its merged output captured line by line."""

    def __init__(self, name: str, argv: list, env: dict):
        self.name = name
        self.lines: list = []
        self._cv = threading.Condition()
        os.makedirs(OUT_DIR, exist_ok=True)
        self._log = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, start_new_session=True)
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self._log.write(line)
            self._log.flush()
            with self._cv:
                self.lines.append(line.rstrip("\n"))
                self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def wait_line(self, prefix: str, timeout: float) -> str:
        """First output line starting with `prefix`; fails if the child
        exits or the time runs out first."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cv:
            while True:
                for line in self.lines[seen:]:
                    if line.startswith(prefix):
                        return line
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._reader.is_alive():
                    raise SmokeFailure(
                        f"{self.name} exited rc={self.proc.returncode} "
                        f"before printing {prefix!r}:\n" + self.tail())
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        f"{self.name}: no {prefix!r} line within "
                        f"{timeout:.0f}s:\n" + self.tail())
                self._cv.wait(min(left, 1.0))

    def tail(self, n: int = 30) -> str:
        return "\n".join(f"    {self.name}| {ln}" for ln in self.lines[-n:])

    def terminate(self, timeout: float) -> int:
        """SIGTERM, wait, return the exit code (SIGKILL after timeout)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(
                f"{self.name} did not exit within {timeout:.0f}s of "
                "SIGTERM:\n" + self.tail())
        self._reader.join(5)
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(10)
        self._log.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_native() -> str:
    """Build native/libdynamo_native.so from the committed source (the
    .so is not committed, and a stale one on disk is not what git would
    ship).  Returns the indexer the frontend is pinned to."""
    have = shutil.which("make") and (shutil.which("c++") or
                                     shutil.which("g++") or
                                     shutil.which("clang++"))
    if not have:
        say("native: no toolchain (make + c++) -> python indexer")
        return "py"
    r = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "native")],
                       capture_output=True, text=True, timeout=300)
    check(r.returncode == 0,
          "native indexer build failed with a toolchain present:\n"
          + r.stdout[-2000:] + r.stderr[-2000:])
    return "native"


# ---------------------------------------------------------------------------
# HTTP (stdlib only)
# ---------------------------------------------------------------------------


def http_json(method: str, url: str, body=None, headers=None,
              timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode()[-2000:]}


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def stream_chat(base: str, model: str, n: int) -> dict:
    """One streamed chat completion; returns counts from the SSE frames."""
    req = urllib.request.Request(
        f"{base}/v1/chat/completions", method="POST",
        headers={"Content-Type": "application/json"},
        data=json.dumps({
            "model": model, "stream": True, "max_tokens": n,
            "temperature": 0.0, "ignore_eos": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user",
                          "content": "Say something about TPUs."}],
        }).encode())
    frames = deltas = 0
    done = False
    finish = usage = None
    with urllib.request.urlopen(req, timeout=600) as r:
        check(r.status == 200, f"chat stream answered {r.status}")
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[len("data:"):].strip()
            if payload == "[DONE]":
                done = True
                break
            frames += 1
            obj = json.loads(payload)
            usage = obj.get("usage") or usage
            for ch in obj.get("choices", []):
                if (ch.get("delta") or {}).get("content"):
                    deltas += 1
                finish = ch.get("finish_reason") or finish
    check(done, "chat stream never sent [DONE]")
    check(deltas > 0, "chat stream carried no content delta")
    check(usage is not None and usage.get("completion_tokens") == n,
          f"chat stream usage {usage} != {n} completion tokens")
    return {"frames": frames, "content_deltas": deltas, "finish": finish,
            "completion_tokens": usage["completion_tokens"]}


def make_prompt(rng: random.Random, n_chars: int) -> str:
    """ASCII text of exactly n_chars: the worker's mock tokenizer is one
    token per byte, so this is n_chars prompt tokens."""
    words = []
    size = 0
    while size < n_chars:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                    for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[:n_chars]


def completion(base: str, model: str, prompt: str, n: int,
               delay: float = 0.0) -> dict:
    if delay:
        time.sleep(delay)
    t0 = time.monotonic()
    status, body = http_json("POST", f"{base}/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": n,
        "temperature": 0.0, "ignore_eos": True})
    check(status == 200, f"completion answered {status}: {body}")
    usage = body.get("usage") or {}
    check(usage.get("completion_tokens") == n,
          f"completion returned {usage} for max_tokens={n}")
    check(usage.get("prompt_tokens") == len(prompt),
          f"prompt_tokens {usage.get('prompt_tokens')} != {len(prompt)}")
    return {"prompt_tokens": usage["prompt_tokens"],
            "completion_tokens": usage["completion_tokens"],
            "text": body["choices"][0]["text"],
            "seconds": round(time.monotonic() - t0, 3)}


def metric_total(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
    return total


# ---------------------------------------------------------------------------
# phase: serve (worker + frontend over HTTP)
# ---------------------------------------------------------------------------


def phase_serve(args, env: dict, children: list, deadline: float) -> dict:
    t_phase = time.monotonic()
    cluster = tempfile.mkdtemp(prefix="chip-smoke-cluster-")
    wport, fsys, fport = free_port(), free_port(), free_port()
    base_env = {**env, "DYN_DISCOVERY_BACKEND": "file",
                "DYN_DISCOVERY_PATH": cluster,
                "DYN_ADMIN_TOKEN": ADMIN_TOKEN}
    model = model_for(args)
    worker_argv = [sys.executable, "-m", "dynamo_tpu.engine",
                   "--model", model]
    if args.chips > 1:
        worker_argv += ["--tp", str(args.chips)]
    say(f"worker: {' '.join(worker_argv[1:])}")
    worker = Child("worker", worker_argv,
                   {**base_env, "DYN_SYSTEM_PORT": str(wport)})
    children.append(worker)
    # `device {"platform": ..., "kind": ..., "count": ..., ...}`
    reported = json.loads(worker.wait_line("device ", 300)[len("device "):])
    device = {k: reported[k] for k in ("platform", "kind", "count")}
    say(f"worker device: {json.dumps(device)}")
    if not args.rehearse:
        check(device["platform"] == "tpu",
              f"no TPU: the worker's backend is {device['platform']!r}")
        check(device["count"] == args.chips,
              f"worker sees {device['count']} devices, wanted "
              f"{args.chips}")
    # while the worker warms up (make does not touch the chip)
    indexer = build_native()
    say(f"native indexer built from source -> DYN_INDEXER={indexer}")
    worker.wait_line("ready instance_id=",
                     max(60.0, deadline - time.monotonic() - 240))
    t_ready = time.monotonic()
    say(f"worker ready after {t_ready - t_phase:.1f}s "
        "(init + warm-up compiles; one run, not a benchmark)")

    frontend = Child("frontend", [
        sys.executable, "-m", "dynamo_tpu.frontend", "--port", str(fport),
        "--router-mode", "kv"],
        {**base_env, "DYN_SYSTEM_PORT": str(fsys), "DYN_INDEXER": indexer})
    children.append(frontend)
    frontend.wait_line("ready port=", 120)
    base = f"http://127.0.0.1:{fport}"
    auth = {"X-Dyn-Admin-Token": ADMIN_TOKEN}

    # /v1/models (the watcher needs a discovery poll to see the card)
    names: list = []
    t_end = time.monotonic() + 60
    while time.monotonic() < t_end:
        status, body = http_json("GET", f"{base}/v1/models")
        check(status == 200, f"/v1/models answered {status}")
        names = [m["id"] for m in body.get("data", [])]
        if model in names:
            break
        time.sleep(0.5)
    check(model in names, f"/v1/models never listed {model!r}: {names}")
    say(f"/v1/models: {names}")

    status, wstate = http_json(
        "GET", f"http://127.0.0.1:{wport}/debug/state", headers=auth)
    check(status == 200, f"worker /debug/state answered {status}")
    wsrc = next(s for s in wstate["sources"].values()
                if s.get("kind") == "engine")
    impls = {k: wsrc["config"].get(k) for k in
             ("attn_impl", "packed_attn_impl", "sampling_epilogue",
              "kv_cache_dtype", "overlap_scheduling", "prefill_packed")}
    say(f"engine resolved impls: {json.dumps(impls)} "
        "(attn: 'auto' as resolved for this worker, the Pallas kernel on "
        "a TPU with 128-token blocks; packed 'auto' is resolved a program "
        "by the stream's length: the Pallas kernel on a TPU from 1024 "
        "tokens up, the XLA scan under that)")
    warm = wsrc["compile_watch"]
    say(f"after warm-up: compiles={json.dumps(warm['counts'])} "
        f"seconds={json.dumps(warm['seconds'])} "
        f"serving_compiles={warm['serving_compiles']}")

    t0 = time.monotonic()
    chat = stream_chat(base, model, 16 if args.rehearse else 32)
    say(f"streamed chat: {json.dumps(chat)} "
        f"in {time.monotonic() - t0:.1f}s")

    # concurrent completions, staggered so later prefills meet running
    # decodes (packed prefill, mixed steps, fused decode bursts)
    rng = random.Random(args.seed)
    if args.rehearse:
        lens, n_out = [40, 90, 150, 200], 12
    elif args.chips > 1:
        lens, n_out = [300, 900, 1500, 2000], 64
    else:
        lens, n_out = [300, 700, 1100, 1500, 1900, 2000], 64
    prompts = [make_prompt(rng, n) for n in lens]
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = [pool.submit(completion, base, model, p, n_out, 0.4 * i)
                for i, p in enumerate(prompts)]
        results = [f.result() for f in futs]
    say("concurrent completions: " + json.dumps(
        [{k: r[k] for k in ("prompt_tokens", "completion_tokens",
                            "seconds")} for r in results])
        + f" wall={time.monotonic() - t0:.1f}s")

    # the same greedy prompt twice.  Shorter than one KV block, so no
    # prefix block is cached and both runs take the very same programs:
    # identical text is demanded.
    twin = make_prompt(rng, 100)  # the worker's block size is 128
    a = completion(base, model, twin, n_out)
    b = completion(base, model, twin, n_out)
    check(a["text"] == b["text"],
          "the same greedy prompt gave different text twice:\n"
          f"  1: {a['text'][:200]!r}\n  2: {b['text'][:200]!r}")
    say(f"greedy determinism: {len(twin)}-token prompt, 2 x {n_out} "
        f"tokens identical ({a['seconds']}s, {b['seconds']}s)")
    # ...and a long one twice: the second run hits the prefix cache and
    # prefills only the tail — another program shape over the same
    # cached K/V, so bf16 near-ties of a random-weight model may flip a
    # greedy token (second chip run: they did, at word 4).  Agreement
    # over HTTP is reported, not demanded; the numerics phase repeats a
    # request on one JaxEngine and bounds the first-token logits of the
    # engine's own prefix-hit prefill against its cold one.
    long_twin = make_prompt(rng, lens[1])
    a = completion(base, model, long_twin, n_out)
    b = completion(base, model, long_twin, n_out)
    wa, wb = a["text"].split(), b["text"].split()
    same = next((i for i, (x, y) in enumerate(zip(wa, wb)) if x != y),
                min(len(wa), len(wb)))
    say(f"prefix-cache hit: {len(long_twin)}-token prompt twice, leading "
        f"words equal {same}/{min(len(wa), len(wb))} "
        f"({a['seconds']}s cold, {b['seconds']}s cached)")

    status, wstate = http_json(
        "GET", f"http://127.0.0.1:{wport}/debug/state", headers=auth)
    check(status == 200, f"worker /debug/state answered {status}")
    wsrc = next(s for s in wstate["sources"].values()
                if s.get("kind") == "engine")
    cw, em, dev = wsrc["compile_watch"], wsrc["engine_metrics"], wsrc["device"]
    metrics_text = http_text(f"http://127.0.0.1:{wport}/metrics")
    serving_total = metric_total(
        metrics_text, "dynamo_engine_serving_compiles_total")
    say(f"after serving: compiles={json.dumps(cw['counts'])} "
        f"dynamo_engine_serving_compiles_total={serving_total:g} "
        f"(compile_watch.serving_compiles={cw['serving_compiles']})")
    say("engine counters: " + json.dumps({k: em.get(k) for k in (
        "requests", "steps", "prefill_steps", "prefill_tokens",
        "decode_tokens", "cont_bursts", "cache_hit_tokens",
        "preemptions")}))
    check(cw["counts"].get("prefill_packed", 0) > 0,
          "packed prefill never ran (no prefill_packed compile)")
    check(cw["counts"].get("decode_multi", 0) > 0,
          "no fused decode program was ever compiled")
    check(em.get("cont_bursts", 0) > 0,
          "no fused continuation burst ran")
    if not args.rehearse:  # toy prompts are shorter than a block
        check(em.get("cache_hit_tokens", 0) >= len(long_twin) // 128 * 128,
              f"the repeated prompt hit no prefix cache: "
              f"cache_hit_tokens={em.get('cache_hit_tokens')}")
    expect_out = (len(prompts) + 4) * n_out + chat["completion_tokens"]
    check(em.get("decode_tokens", 0) + em.get("requests", 0) >= expect_out,
          f"engine counted {em.get('decode_tokens')} decode tokens over "
          f"{em.get('requests')} requests, expected >= {expect_out} total")

    per_dev = dev["per_device"]
    for d in per_dev:
        ms = d.get("memory_stats") or {}
        say(f"device {d['id']}: param_bytes={d['param_bytes']} "
            f"kv_bytes={d['kv_bytes']} "
            f"bytes_in_use={ms.get('bytes_in_use')} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')} "
            f"bytes_limit={ms.get('bytes_limit')}")
    check(len(per_dev) == args.chips,
          f"the mesh holds {len(per_dev)} devices, wanted {args.chips}")
    if args.chips > 1:
        for key in ("param_bytes", "kv_bytes"):
            vals = [d[key] for d in per_dev]
            share = [v / max(sum(vals), 1) for v in vals]
            say(f"{key} share per device: "
                + ", ".join(f"{s:.3f}" for s in share))
            check(all(abs(s - 1 / args.chips) < 0.05 for s in share),
                  f"{key} is not spread evenly over the mesh: {vals}")

    status, fstate = http_json(
        "GET", f"http://127.0.0.1:{fsys}/debug/state", headers=auth)
    check(status == 200, f"frontend /debug/state answered {status}")
    fsrc = next(s for s in fstate["sources"].values()
                if s.get("kind") == "frontend")
    served_by = {m: r.get("indexer_impl")
                 for m, r in (fsrc.get("router") or {}).items()}
    say(f"kv router indexer: {json.dumps(served_by)} (pinned {indexer})")
    check(served_by.get(model) == indexer,
          f"the frontend's indexer is {served_by}, expected {indexer}")
    # a frontend holds no device: it must refuse to profile one
    status, prof = http_json(
        "GET", f"http://127.0.0.1:{fsys}/debug/profile?duration_s=0.1",
        headers=auth)
    check(status == 200 and prof.get("status") == "unavailable",
          f"frontend /debug/profile did not refuse: {status} {prof}")

    t0 = time.monotonic()
    rc_w = worker.terminate(90)
    rc_f = frontend.terminate(60)
    say(f"SIGTERM drain: worker rc={rc_w} frontend rc={rc_f} "
        f"in {time.monotonic() - t0:.1f}s")
    check(rc_w == 0, f"worker exited rc={rc_w}:\n" + worker.tail())
    check(rc_f == 0, f"frontend exited rc={rc_f}:\n" + frontend.tail())
    shutil.rmtree(cluster, ignore_errors=True)
    say(f"phase serve: {time.monotonic() - t_phase:.1f}s "
        f"(worker ready {t_ready - t_phase:.1f}s)")
    return device


# ---------------------------------------------------------------------------
# phases that touch JAX: run as a child, in turn, after the worker exited
# ---------------------------------------------------------------------------


def run_jax_phase(phase: str, args, env: dict, children: list,
                  deadline: float) -> dict:
    t0 = time.monotonic()
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--chips", str(args.chips), "--seed", str(args.seed)]
    if args.rehearse:
        argv.append("--rehearse")
    child = Child(phase, argv, env)
    children.append(child)
    line = child.wait_line("RESULT ", max(30.0, deadline - time.monotonic()))
    try:
        rc = child.proc.wait(120)  # it exits by itself after RESULT
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {phase} printed its result but did "
                           "not exit:\n" + child.tail())
    for ln in child.lines:
        if ln.startswith("smoke:"):
            print(ln, flush=True)
    check(rc == 0, f"phase {phase} exited rc={rc}:\n" + child.tail())
    result = json.loads(line[len("RESULT "):])
    say(f"phase {phase}: {time.monotonic() - t0:.1f}s")
    return result


def _widths(args):
    """(nkv, nh, hd, bs, max_blocks, T, S): llama-3b's own on the chip,
    toy in rehearsal (the interpreter is slow)."""
    if args.rehearse:
        return 2, 4, 16, 4, 8, 32, 4
    from dynamo_tpu.models import llama

    cfg = llama.PRESETS["llama-3b"]
    return cfg.n_kv_heads, cfg.n_heads, cfg.head_dim, 128, 16, 2048, 4


def _quantize_cache(x):
    """[L, nkv, NB, hd, bs] float -> (int8 cache, fp32 scales
    [L, nkv, NB, bs]): per-position quantization over hd, the serving
    convention (quant/kv.py)."""
    from dynamo_tpu.quant.kv import quantize_tokens

    q8, sc = quantize_tokens(x.transpose(0, 1, 2, 4, 3))
    return q8.transpose(0, 1, 2, 4, 3), sc


def check_kernels(args, pallas: str) -> dict:
    """Compiled Pallas kernels vs their XLA references, uneven lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.packed_prefill import packed_prefill_attention
    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode,
        paged_attention_decode_jnp,
    )

    nkv, nh, hd, bs, mb, T, S = _widths(args)
    rng = np.random.default_rng(args.seed)
    out = {}

    def cache(num_blocks):
        shape = (2, nkv, num_blocks, hd, bs)
        return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
                jnp.asarray(rng.standard_normal(shape), jnp.float32))

    def compare(name, ref_name, what, kf, vf, run, mask=None,
                impl=pallas):
        """run(kc, vc, impl=..., **scales) on a bf16 and an int8 copy of
        the same cache: the impl named against the reference impl."""
        for tag in ("bf16", "int8"):
            if tag == "int8":
                (kc, ks), (vc, vs) = (_quantize_cache(kf),
                                      _quantize_cache(vf))
                scales = dict(k_scale=ks, v_scale=vs)
            else:
                kc, vc = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
                scales = {}
            ref = run(kc, vc, None, **scales).astype(jnp.float32)
            got = run(kc, vc, impl, **scales).astype(jnp.float32)
            diff = jnp.abs(got - ref)
            if mask is not None:
                diff = jnp.where(mask, diff, 0.0)
            err = float(jnp.max(diff))
            check(bool(jnp.all(jnp.isfinite(got))),
                  f"{name} kernel ({tag}) produced non-finite values")
            check(err <= KERNEL_ATOL,
                  f"{name} kernel ({tag}) differs from {ref_name} by "
                  f"{err:.4f} > {KERNEL_ATOL}")
            out[f"{name}_{tag}_max_abs_err"] = round(err, 5)
            say(f"{name} kernel {impl} vs {ref_name}, {tag}, {what}: "
                f"max|err|={err:.5f} (atol {KERNEL_ATOL})")

    # -- decode: B sequences, uneven kv_lens incl. partial / single block
    full = mb * bs
    kv_lens = np.asarray([full, max(full - 49, 1), full // 2 + 1, full // 2,
                          full // 4 + 1, bs + 1, max(bs // 3, 1), 1],
                         np.int32)
    B = len(kv_lens)
    nb = 1 + B * mb
    kf, vf = cache(nb)
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.bfloat16)
    tables = np.zeros((B, mb), np.int32)
    perm = rng.permutation(nb - 1) + 1
    for b in range(B):
        used = -(-int(kv_lens[b]) // bs)
        tables[b, :used] = perm[b * mb:b * mb + used]
    tables, lens = jnp.asarray(tables), jnp.asarray(kv_lens)

    def decode(kc, vc, impl, **scales):
        if impl is None:  # the fp32-upcast jnp reference
            return paged_attention_decode_jnp(q, kc, vc, 1, tables, lens,
                                              **scales)
        return paged_attention_decode(q, kc, vc, 1, tables, lens,
                                      impl=impl, **scales)

    compare("decode", "the jnp path", f"B={B} nkv={nkv} nh={nh} hd={hd} "
            f"kv_lens={kv_lens.tolist()}", kf, vf, decode)

    # -- packed prefill: S segments with cached prefixes, padded tail
    share = T // S
    seg_len = [max(share - 17, 1), max(share // 2, 1),
               share + min(9, share // 2), max(share // 3, 1)][:S]
    ctx0 = [0, bs, 2 * bs + 5, 0][:S]
    for s in range(S):  # keep every segment inside its table
        seg_len[s] = min(seg_len[s], mb * bs - ctx0[s])
    seg_ids = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    at = 0
    for s in range(S):
        n = seg_len[s]
        seg_ids[at:at + n] = s
        pos[at:at + n] = ctx0[s] + np.arange(n)
        valid[at:at + n] = True
        at += n
    nb = 1 + S * mb
    kf, vf = cache(nb)
    ptab = (1 + np.arange(S * mb, dtype=np.int32)).reshape(S, mb)
    qp = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.bfloat16)
    a = (jnp.asarray(ptab), jnp.asarray(seg_ids), jnp.asarray(pos),
         jnp.asarray(valid))

    def packed(kc, vc, impl, **scales):
        return packed_prefill_attention(qp, kc, vc, 1, *a,
                                        impl=impl or "xla", **scales)

    compare("packed", "the xla impl",
            f"T={T} S={S} seg_len={seg_len} ctx0={ctx0}", kf, vf, packed,
            mask=jnp.asarray(valid)[:, None, None])

    # -- the doc cell's last chunk (`mistral-7b.doc-closed`): ONE row of
    # T tokens at the end of a 48-block context under the cell's table
    # width of 50, Mistral's widths, run as whatever "auto" resolves to
    # here (ops/packed_prefill.resolve_packed_impl) against the scan
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl

    if not args.rehearse:
        nkv, nh, mb = 8, 32, 50
    start = max(0, (mb - 2) * bs - T)
    kf, vf = cache(1 + mb)
    qp = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.bfloat16)
    a = (jnp.asarray(1 + np.arange(mb, dtype=np.int32))[None],
         jnp.zeros(T, jnp.int32),
         jnp.asarray(start + np.arange(T, dtype=np.int32)),
         jnp.ones(T, bool))
    out["packed_auto_impl"] = {
        tag: resolve_packed_impl("auto", jax.default_backend(), bs, hd, dt,
                                 T, nh // nkv)
        for tag, dt in (("bf16", jnp.bfloat16), ("int8", jnp.int8))}
    compare("packed_auto", "the xla impl",
            f"T={T} one row at {start}.. width {mb} nkv={nkv} nh={nh}, "
            f"auto = {json.dumps(out['packed_auto_impl'])}", kf, vf,
            packed, impl="auto")
    out.update(check_mla_kernel(args, pallas, rng))
    jax.clear_caches()
    return out


def check_mla_kernel(args, pallas: str, rng) -> dict:
    """The compiled latent kernels (ops/pallas_mla_attention.py): the
    prefill read against the jnp form (`_check_mla_prefill`), and the
    decode kernel
    against the gathering jnp body at the two cells' shapes (Ling's 32
    heads over 64 lanes x 45 blocks, Moonlight's 16 over 16 x 20; R 512,
    rope key 64, blocks of 128): lanes of unequal length, contexts
    ending mid-block, idle lanes, tables far wider than what is live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.mla_attention import mla_decode_attention
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    shapes = {"ling": (32, 64, 45), "moonlight": (16, 16, 20)}
    R, dr, dv, bs = 512, 64, 128, 128
    if args.rehearse:
        shapes = {"ling": (4, 6, 5), "moonlight": (2, 3, 4)}
        R, dr, dv, bs = 32, 16, 16, 8
    out = {"mla_auto_impl": resolve_decode_impl(
        "auto", jax.default_backend(), bs, (R, dr), jnp.bfloat16)}
    for name, (nh, B, mb) in shapes.items():
        nb = 1 + B * mb
        kv_lens = rng.integers(1, mb * bs + 1, B).astype(np.int32)
        kv_lens[:3] = [mb * bs, 0, bs + 1]      # full, idle, mid-block
        tables = np.zeros((B, mb), np.int32)
        perm = rng.permutation(nb - 1) + 1
        for b in range(B):
            used = -(-int(kv_lens[b]) // bs)
            tables[b, :used] = perm[b * mb:b * mb + used]
        a = [jnp.asarray(rng.standard_normal(s), jnp.bfloat16) for s in (
            (B, nh, R), (B, nh, dr), (2, 1, nb, R, bs), (2, 1, nb, dr, bs))]
        w_uv = jnp.asarray(rng.standard_normal((nh, R, dv)) / R ** 0.5,
                           jnp.bfloat16)
        run = lambda impl: mla_decode_attention(
            *a, 1, jnp.asarray(tables), jnp.asarray(kv_lens), w_uv,
            (128 + dr) ** -0.5, impl=impl).astype(jnp.float32)
        got, ref = run(pallas), run("jnp")
        live = jnp.asarray(kv_lens > 0)[:, None, None]
        err = float(jnp.max(jnp.where(live, jnp.abs(got - ref), 0.0)))
        check(bool(jnp.all(jnp.isfinite(got))),
              f"mla decode kernel ({name}) produced non-finite values")
        check(float(jnp.max(jnp.abs(got[1]))) == 0.0,
              f"mla decode kernel ({name}): an idle lane's output is not 0")
        check(err <= KERNEL_ATOL,
              f"mla decode kernel ({name}) differs from the jnp body by "
              f"{err:.4f} > {KERNEL_ATOL}")
        out[f"mla_decode_{name}_max_abs_err"] = round(err, 5)
        say(f"mla decode kernel {pallas} vs the jnp body, {name}: nh={nh} "
            f"B={B} table={mb}, {int((-(-kv_lens // bs)).sum())} live "
            f"blocks: max|err|={err:.5f} (atol {KERNEL_ATOL})")
        out[f"mla_prefill_{name}_max_abs_err"] = _check_mla_prefill(
            name, nh, mb, (R, dr, dv, bs), pallas, rng)
    return out


def _check_mla_prefill(name, nh, mb, widths, pallas: str, rng) -> float:
    """The compiled latent PREFILL kernel (one flash pass over the
    pool's live blocks, `mla_prefill_flash`) against the jnp form a row,
    over what `mla_write_rows` wrote: a row carried from mid-block with
    a padded tail, a fresh row that fills its bucket, a row with nothing
    valid."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.mla_attention import (
        mla_prefill_attention,
        mla_prefill_flash,
        mla_write_rows,
    )

    R, dr, dv, bs = widths
    dn = dv
    T = min(4 * bs, (mb - 2) * bs)
    rows = [(bs + bs // 2 + 3, max(T - 17, 1)), (0, T), (bs, 0)]
    S = len(rows)
    nb = 1 + S * mb
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.bfloat16)
    pools = normal(2, 1, nb, R, bs), normal(2, 1, nb, dr, bs)
    qn, qr = normal(S, T, nh, dn), normal(S, T, nh, dr)
    c, kr = normal(S, T, R), normal(S, T, dr)
    w_uk, w_uv = (jnp.asarray(rng.standard_normal((nh, R, d)) / R ** 0.5,
                              jnp.bfloat16) for d in (dn, dv))
    tables = jnp.asarray((1 + rng.permutation(nb - 1)).reshape(S, mb),
                         jnp.int32)
    ctx = jnp.asarray([r[0] for r in rows], jnp.int32)
    true = jnp.asarray([r[1] for r in rows], jnp.int32)
    pools = jax.jit(mla_write_rows)(*pools, 1, c, kr, tables, ctx, true)
    ref = jax.jit(jax.vmap(
        lambda a, b, cb, krb, tb, cl, tl: mla_prefill_attention(
            a, b, cb, krb, *pools, 1, tb, cl, tl, w_uk, w_uv)))(
        qn, qr, c, kr, tables, ctx, true).astype(jnp.float32)
    got = jax.jit(lambda *a: mla_prefill_flash(
        *a, interpret=pallas == "pallas_interpret"))(
        qn, qr, *pools, 1, tables, ctx, true, w_uk, w_uv).astype(jnp.float32)
    real = (jnp.arange(T)[None, :] < true[:, None])[..., None, None]
    err = float(jnp.max(jnp.where(real, jnp.abs(got - ref), 0.0)))
    check(bool(jnp.all(jnp.isfinite(got))),
          f"mla prefill kernel ({name}) produced non-finite values")
    check(float(jnp.max(jnp.where(real, 0.0, jnp.abs(got)))) == 0.0,
          f"mla prefill kernel ({name}): a row's padding is not 0")
    check(err <= KERNEL_ATOL,
          f"mla prefill kernel ({name}) differs from the jnp form by "
          f"{err:.4f} > {KERNEL_ATOL}")
    say(f"mla prefill kernel {pallas} vs the jnp form, {name}: nh={nh} "
        f"T={T} rows={rows} table={mb}: max|err|={err:.5f} "
        f"(atol {KERNEL_ATOL})")
    return round(err, 5)


def _engine_probe(engine_cfg, prompt_ids, n_out: int):
    """Build a JaxEngine and serve the same greedy request twice through
    generate(): cold, then again, so that the engine's own prefix cache
    holds the leading full blocks and only the tail is prefilled.

    First-token logits come from the engine's own path: `step_sink` (the
    hook the multi-host leader broadcasts steps from) hands over each
    packed-prefill step's host arrays just before the engine's jit call,
    and the same `family.prefill_packed` runs there on exactly those
    arrays over the engine's own parameters and block tables and a copy
    of its KV pool as it stands, so the engine's state is untouched.
    Returns (cold tokens, hit tokens, cold logits, hit logits)."""
    import asyncio
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import JaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    bs, n = engine_cfg.block_size, len(prompt_ids)
    # what a prefix hit may reuse: full blocks, never the last token
    cut = (n - 1) // bs * bs
    check(cut > 0, f"probe prompt of {n} tokens holds no full block")
    steps: list = []  # (first position, first-token logits) per prefill step

    async def run():
        eng = JaxEngine(engine_cfg)
        prefill = jax.jit(
            lambda p, kv, *a: eng.family.prefill_packed(
                p, eng.model_cfg, kv, *a, mesh=eng.mesh),
            donate_argnums=(1,))

        def sink(kind, a):
            if kind != "prefill_packed":
                return
            # donated like the engine's own program, so on a copy
            kv = tuple(jnp.copy(x) for x in eng.kv)
            logits, _ = prefill(eng.params, kv, *(
                jnp.asarray(a[k]) for k in (
                    "toks", "positions", "seg_ids", "tables", "last_idx",
                    "valid")))
            steps.append((int(a["positions"][0]),
                          np.asarray(logits[0], np.float32)))

        eng.step_sink = sink

        async def serve(tag):
            del steps[:]
            toks: list = []
            async for out in eng.generate(PreprocessedRequest(
                    token_ids=list(prompt_ids),
                    request_id=f"smoke-probe-{tag}",
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=n_out,
                                        ignore_eos=True))):
                check(out.error is None, f"engine error: {out.error}")
                toks.extend(out.token_ids)
            check(len(toks) == n_out,
                  f"engine returned {len(toks)} tokens, wanted {n_out}")
            check(bool(steps), f"no packed prefill step ran ({tag})")
            start = steps[0][0]
            return toks, start, steps[-1][1]  # the completing step's row

        t_cold, at_cold, l_cold = await serve("cold")
        hits0 = eng.metrics["cache_hit_tokens"]
        t_hit, at_hit, l_hit = await serve("hit")
        hit_tokens = eng.metrics["cache_hit_tokens"] - hits0
        await eng.close()
        check(at_cold == 0 and at_hit == cut and hit_tokens == cut,
              f"the repeat was no prefix hit of {cut} tokens: prefill "
              f"started at {at_cold} then {at_hit}, "
              f"cache_hit_tokens +{hit_tokens}")
        return t_cold, t_hit, l_cold, l_hit

    out = asyncio.run(run())
    gc.collect()
    jax.clear_caches()
    return out


def _near_argmax(logits, token: int, what: str) -> None:
    """The token the engine emitted first is the argmax of the logits
    taken at its step sink, up to the stated tolerance (ties in bf16)."""
    import numpy as np

    gap = float(np.max(logits) - logits[token])
    check(gap <= LOGITS_REL_TOL * float(np.max(np.abs(logits))),
          f"{what}: the engine's first token {token} is {gap:.4f} below "
          "the best logit taken at its own prefill step")


def _compare_logits(a, b, what: str) -> dict:
    import numpy as np

    check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
          f"{what}: non-finite prefill logits")
    scale = float(np.max(np.abs(a)))
    rel = float(np.max(np.abs(a - b))) / max(scale, 1e-9)
    check(rel <= LOGITS_REL_TOL,
          f"{what}: prefill logits differ by {rel:.4f} of their range "
          f"(> {LOGITS_REL_TOL})")
    return {"logits_rel_err": round(rel, 5), "logits_absmax": round(scale, 3),
            "argmax_equal": bool(int(np.argmax(a)) == int(np.argmax(b)))}


def phase_numerics(args) -> dict:
    """Child process, one chip: kernels vs references, then JaxEngine
    with the Pallas impls vs the default impls."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.runtime.device import (
        device_identity,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    dev = device_identity() if args.rehearse else require_tpu()
    pallas = "pallas_interpret" if args.rehearse else "pallas"
    out = {"device": dev, **check_kernels(args, pallas)}

    rng = random.Random(args.seed + 1)
    n_prompt, n_out = (40, 8) if args.rehearse else (200, 16)
    prompt = [rng.randrange(3, 259) for _ in range(n_prompt)]
    small = dict(block_size=4, num_blocks=64, max_blocks_per_seq=32,
                 prefill_buckets=(16, 32, 64)) if args.rehearse else {}
    model = model_for(args)
    probes = {}
    for tag, impl_kw in (("default", {}),
                         (pallas, dict(attn_impl=pallas,
                                       packed_attn_impl=pallas))):
        t_cold, t_hit, cold, hit = probes[tag] = _engine_probe(
            EngineConfig(model=model, seed=args.seed, **impl_kw, **small),
            prompt, n_out)
        _near_argmax(cold, t_cold[0], f"{tag} impls, cold")
        _near_argmax(hit, t_hit[0], f"{tag} impls, prefix hit")
        c = _compare_logits(cold, hit, f"prefix-hit vs cold ({tag})")
        same = sum(int(x == y) for x, y in zip(t_cold, t_hit))
        say(f"JaxEngine {model} {tag} impls, the same request cold then "
            f"as a prefix-cache hit (engine's own path): first-token "
            f"logits rel err {c['logits_rel_err']} of range (tol "
            f"{LOGITS_REL_TOL}), argmax equal {c['argmax_equal']}, "
            f"greedy tokens agree {same}/{n_out}")
        out[f"prefix_hit_rel_err_{tag}"] = c["logits_rel_err"]
        out[f"prefix_hit_greedy_agree_{tag}"] = f"{same}/{n_out}"
    t_def, _, l_def, _ = probes["default"]
    t_pal, _, l_pal, _ = probes[pallas]
    agree = sum(int(x == y) for x, y in zip(t_def, t_pal))
    cmp = _compare_logits(l_def, l_pal, f"{pallas} vs default impls")
    say(f"JaxEngine {model} attn/packed={pallas} vs default: "
        f"prefill logits rel err {cmp['logits_rel_err']} of range "
        f"(tol {LOGITS_REL_TOL}, |logit|max {cmp['logits_absmax']}), "
        f"greedy tokens agree {agree}/{n_out}")
    out.update(cmp, greedy_agree=f"{agree}/{n_out}")
    return out


def phase_tp_compare(args) -> dict:
    """Child process, four chips: llama-8b widths cut to 8 layers, same
    seed, tp=1 against tp=N in one process."""
    import dataclasses

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.runtime.device import (
        device_identity,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    dev = device_identity() if args.rehearse else require_tpu()
    check(dev["count"] >= args.chips,
          f"{dev['count']} devices, wanted {args.chips}")
    if args.rehearse:
        mcfg = dataclasses.replace(llama.PRESETS["tiny"], n_layers=2)
        small = dict(block_size=4, num_blocks=64, max_blocks_per_seq=32,
                     prefill_buckets=(16, 32, 64))
        n_prompt, n_out = 40, 8
    else:
        mcfg = dataclasses.replace(llama.PRESETS[model_for(args)],
                                   n_layers=8)
        small = {}
        n_prompt, n_out = 200, 16
    rng = random.Random(args.seed + 2)
    prompt = [rng.randrange(3, 259) for _ in range(n_prompt)]
    t1, _, l1, _ = _engine_probe(
        EngineConfig(model_config=mcfg, seed=args.seed, tp=1, **small),
        prompt, n_out)
    tn, _, ln, _ = _engine_probe(
        EngineConfig(model_config=mcfg, seed=args.seed, tp=args.chips,
                     **small), prompt, n_out)
    agree = sum(int(x == y) for x, y in zip(t1, tn))
    cmp = _compare_logits(l1, ln, f"tp={args.chips} vs tp=1")
    say(f"{mcfg.name} x{mcfg.n_layers} layers, tp={args.chips} vs tp=1: "
        f"prefill logits rel err {cmp['logits_rel_err']} of range "
        f"(tol {LOGITS_REL_TOL}), greedy tokens agree {agree}/{n_out}")
    return {"device": dev, **cmp, "greedy_agree": f"{agree}/{n_out}"}


# ---------------------------------------------------------------------------


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4 = only the llama-8b tp=4 path and its tp=1 "
                        "comparison (run by hand; the driver runs 1)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="walk the control flow off-chip at toy size "
                        "(interpret kernels); never a pass: exits 3")
    p.add_argument("--phase", default="",
                   choices=["", "numerics", "tp_compare"],
                   help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.phase:  # a child that takes the chip in its turn
        fn = {"numerics": phase_numerics,
              "tp_compare": phase_tp_compare}[args.phase]
        print("RESULT " + json.dumps(fn(args)), flush=True)
        return 0

    check("jax" not in sys.modules,
          "the parent imported JAX; it must stay off the chip")
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    env = dict(os.environ)
    # one compile cache for every process of this run: where the
    # variable is set it is used as is, else the fixed in-checkout path
    env[CACHE_ENV] = compile_cache_dir()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips > 1:
            env.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={args.chips}")
    origin = ("from " + CACHE_ENV if os.environ.get(CACHE_ENV)
              else "fixed path in the checkout")
    say(f"compile cache: {env[CACHE_ENV]} ({origin})")
    children: list = []
    try:
        device = phase_serve(args, env, children, deadline)
        second = run_jax_phase(
            "tp_compare" if args.chips > 1 else "numerics",
            args, env, children, deadline)
        check(args.rehearse or second["device"] == device,
              f"the phases saw different devices: {device} vs "
              f"{second['device']}")
        check(args.rehearse or device["platform"] == "tpu", "not a TPU")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for c in children:
            c.kill()
    say(f"total {time.monotonic() - t_start:.1f}s "
        "(one run on the device below, not a benchmark)")
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}), flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
