"""dynlint tests: per-rule fixtures (a minimal bad snippet that must be
flagged + a good/suppressed snippet that must pass), the PR 7 raw-jit
guided-topk regression fixture verbatim, suppression-reason enforcement,
baseline semantics, the repo-wide tier-1 gate, and the CLI --json smoke.

Note on fixtures containing suppression comments: the suppression parser
is line-based (comments don't survive ast), so a reasonless
``dynlint: disable`` written literally inside a fixture string would be
parsed out of THIS file too and fail the repo gate — those fixtures are
built by concatenation instead.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from dynamo_tpu import lint
from dynamo_tpu.lint.core import canon_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_RULES = tuple(f"DYN{i:03d}" for i in range(1, 15))


def run(src, path="dynamo_tpu/engine/snippet.py", rules=None):
    return lint.run_source(textwrap.dedent(src), path, rules=rules)


def rule_ids(findings):
    return sorted({f.rule for f in findings})


def test_registry_has_all_rules():
    assert set(ALL_RULES) <= set(lint.RULES)
    for r in lint.RULES.values():
        assert r.title and r.bug  # README table sources


def test_canon_path_is_invocation_invariant():
    assert canon_path("/root/repo/dynamo_tpu/engine/core.py") \
        == "dynamo_tpu/engine/core.py"
    assert canon_path("./tests/test_lint.py") == "tests/test_lint.py"
    assert canon_path("dynamo_tpu/lint/core.py") == "dynamo_tpu/lint/core.py"


# --------------------------- DYN001: raw jit ----------------------------

# the PR 7 headline blind spot, verbatim: _guided_step's duplicate lazy
# top-k init went through a raw jax.jit that bypassed the watchdog — the
# measured 8-14s guided-fork compile would have landed mid-serving with
# zero telemetry.  Re-introducing this exact code must be DYN001.
PR7_GUIDED_TOPK_BYPASS = """
import jax
from functools import partial

class JaxEngine:
    def _guided_step(self, e):
        if getattr(self, "_jit_decode_topk", None) is None:
            self._jit_decode_topk = jax.jit(
                partial(self._decode_topk_impl, self.family,
                        self.model_cfg, self.mesh, self.GUIDED_TOPM),
                donate_argnums=(1,),
            )
        return self._jit_decode_topk
"""


def test_dyn001_flags_pr7_guided_topk_bypass():
    findings = run(PR7_GUIDED_TOPK_BYPASS, path="dynamo_tpu/engine/core.py")
    assert rule_ids(findings) == ["DYN001"]
    assert len(findings) == 1
    assert findings[0].line == 8


def test_dyn001_wrapped_form_passes():
    findings = run("""
        import jax
        from functools import partial

        class JaxEngine:
            def _topk_jit(self):
                if getattr(self, "_jit_decode_topk", None) is None:
                    self._jit_decode_topk = self.compile_watch.wrap(jax.jit(
                        partial(self._decode_topk_impl, self.family,
                                self.model_cfg, self.mesh, self.GUIDED_TOPM),
                        donate_argnums=(1,),
                    ), "decode_topk")
                return self._jit_decode_topk
        """, path="dynamo_tpu/engine/core.py")
    assert findings == []


def test_dyn001_bare_jit_import_and_decorator_partial():
    findings = run("""
        from functools import partial
        from jax import jit

        @partial(jit, static_argnames=("n",))
        def f(x, n):
            return x * n
        """, path="dynamo_tpu/ops/snippet.py")
    assert rule_ids(findings) == ["DYN001"]
    # a LOCAL helper called jit is not jax's
    assert run("""
        def jit(f):
            return f

        g = jit(lambda x: x)
        """, path="dynamo_tpu/ops/snippet.py") == []


def test_dyn001_scope():
    src = "import jax\nf = jax.jit(lambda x: x)\n"
    # the watchdog module itself is the allowlist
    assert run(src, path="dynamo_tpu/obs/compile_watch.py") == []
    # tests/benchmarks are out of scope for this rule
    assert run(src, path="tests/test_x.py") == []


# --------------------------- DYN002: hash() -----------------------------

def test_dyn002_hash_for_identity():
    bad = run("seed = hash(request_id)\n",
              path="dynamo_tpu/mocker/engine.py")
    assert rule_ids(bad) == ["DYN002"]
    good = run("""
        import zlib
        seed = zlib.crc32(request_id.encode())
        """, path="dynamo_tpu/mocker/engine.py")
    assert good == []
    # method .hash() is not the builtin
    assert run("h = obj.hash()\n", path="dynamo_tpu/mocker/engine.py") == []


# --------------------------- DYN003: metric prefix ----------------------

def test_dyn003_unprefixed_metric_family():
    bad = run('m.inc("requests_total", 1.0)\n',
              path="dynamo_tpu/frontend/service.py")
    assert rule_ids(bad) == ["DYN003"]
    bad2 = run("""
        from prometheus_client import Counter
        c = Counter("frontend_requests", "doc")
        """, path="dynamo_tpu/frontend/service.py")
    assert rule_ids(bad2) == ["DYN003"]
    good = run('m.inc("dynamo_frontend_requests_total", 1.0)\n',
               path="dynamo_tpu/frontend/service.py")
    assert good == []
    # .observe() on non-metric objects (non-name strings, numbers) pass
    assert run('hist.labels(family="x").observe(1.0)\n',
               path="dynamo_tpu/obs/slo.py") == []
    assert run('tid = self.targets.observe(w, 0)\n',
               path="dynamo_tpu/router/kv_router.py") == []


# --------------------------- DYN004: blocking in async ------------------

def test_dyn004_blocking_calls_in_async_def():
    bad = run("""
        import time

        async def handler(req):
            time.sleep(0.5)
            with open("/tmp/x") as f:
                data = f.read()
            return fut.result()
        """, path="dynamo_tpu/frontend/service.py")
    assert rule_ids(bad) == ["DYN004"]
    assert len(bad) == 3
    good = run("""
        import asyncio, time

        async def handler(req):
            await asyncio.sleep(0.5)
            data = await asyncio.to_thread(read_file, "/tmp/x")
            return await fut

        def sync_helper():
            time.sleep(0.5)  # runs in a thread, not on the loop

        async def offload():
            def work():
                with open("/tmp/x") as f:
                    return f.read()
            return await asyncio.to_thread(work)
        """, path="dynamo_tpu/frontend/service.py")
    assert good == []


# --------------------------- DYN005: discarded task ---------------------

def test_dyn005_discarded_task():
    bad = run("""
        import asyncio

        async def go():
            asyncio.create_task(pump())
            asyncio.ensure_future(drain())
        """, path="dynamo_tpu/router/kv_router.py")
    assert rule_ids(bad) == ["DYN005"]
    assert len(bad) == 2
    good = run("""
        import asyncio

        async def go(self):
            t = asyncio.create_task(pump())
            self._tasks.add(t)
            t.add_done_callback(self._tasks.discard)
            await asyncio.ensure_future(drain())
        """, path="dynamo_tpu/router/kv_router.py")
    assert good == []


# --------------------------- DYN006: registries -------------------------

def test_dyn006_seam_and_span_literals():
    bad = run("""
        from dynamo_tpu import chaos, obs

        async def step(self):
            await chaos.ahit("engine.stpe", key="x")
            chaos.hit("engine.step2")
            with obs.span("decode_dispatcher"):
                pass
            obs.end("sched_", 0.0)
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN006"]
    assert len(bad) == 4
    good = run("""
        from dynamo_tpu import chaos, obs

        async def step(self):
            await chaos.ahit("engine.step", key="x")
            with obs.span("decode_dispatch"):
                pass
            obs.end("sched", 0.0)
        """, path="dynamo_tpu/engine/core.py")
    assert good == []


def test_dyn006_phase_kind_literals():
    """`<engine>._phase(kind)` (obs.PhaseClock) names a registered kind
    like obs.span()/obs.end() do."""
    bad = run("""
        class JaxEngine:
            def _sched_step(self):
                with self._phase("schedd"):
                    pass
                with self._phase("emit_", what="burst"):
                    pass
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN006"]
    assert len(bad) == 2
    good = run("""
        class JaxEngine:
            def _sched_step(self):
                with self._phase("step"):
                    with self._phase("emit", what="burst"):
                        pass
                    with self._phase("spec_dispatch") as ph:
                        ph.off_ring()
        """, path="dynamo_tpu/engine/core.py")
    assert good == []


def test_dyn006_rule_scenario_literals():
    bad = run("""
        plane = chaos.ChaosPlane(seed=1).rule("request_plane.framez",
                                              "truncate", times=1)
        """, path="tests/test_chaos.py")
    assert rule_ids(bad) == ["DYN006"]
    good = run("""
        plane = chaos.ChaosPlane(seed=1).rule("request_plane.frame",
                                              "truncate", times=1)
        other.rule("not-a-seam", "whatever")  # not a chaos action: not ours
        """, path="tests/test_chaos.py")
    assert good == []


def test_registries_are_canonical():
    from dynamo_tpu import chaos, obs
    from dynamo_tpu.obs.compile_watch import COMPILE_KIND

    assert set(obs.STEP_PHASES) <= obs.SPAN_KINDS
    assert COMPILE_KIND in obs.SPAN_KINDS
    assert "engine.step" in chaos.SEAMS
    # forensics hop vocabulary (obs/forensics.py, DYN012's registry)
    from dynamo_tpu.obs.forensics import PHASES

    assert {"received", "routed", "dispatched", "prefill_open",
            "prefill_done", "worker_stamp", "first_token",
            "decode_stall", "finish"} == set(obs.HOP_KINDS)
    assert set(PHASES) == {"queue", "route", "prefill", "transfer",
                           "decode", "stall"}


# --------------------------- DYN007: inline markers ---------------------

def test_dyn007_inline_drain_marker():
    from dynamo_tpu.protocols import DRAIN_REJECT

    bad = run(f"""
        async def generate(self, req):
            yield Output(error={DRAIN_REJECT!r})
        """, path="dynamo_tpu/mocker/engine.py")
    assert rule_ids(bad) == ["DYN007"]
    good = run("""
        from ..protocols import DRAIN_REJECT

        async def generate(self, req):
            yield Output(error=DRAIN_REJECT)
        """, path="dynamo_tpu/mocker/engine.py")
    assert good == []
    # the defining module is the allowlist
    assert run(f"DRAIN_REJECT = {DRAIN_REJECT!r}\n",
               path="dynamo_tpu/protocols/llm.py") == []


# --------------------------- DYN008: swallowed cancellation -------------

def test_dyn008_bare_except_in_async():
    bad = run("""
        async def pump(self):
            try:
                await self.once()
            except BaseException:
                log.warning("oops")
        """, path="dynamo_tpu/runtime/component.py")
    assert rule_ids(bad) == ["DYN008"]
    bad2 = run("""
        async def pump(self):
            try:
                await self.once()
            except:
                pass
        """, path="dynamo_tpu/runtime/component.py")
    assert rule_ids(bad2) == ["DYN008"]
    good = run("""
        async def pump(self):
            try:
                await self.once()
            except BaseException:
                self.cleanup()
                raise
            try:
                await self.twice()
            except Exception:
                log.warning("oops")  # CancelledError passes through
        """, path="dynamo_tpu/runtime/component.py")
    assert good == []


# --------------------------- DYN009: kv arity ---------------------------

def test_dyn009_fixed_arity_kv_destructure():
    bad = run("""
        def write(kv_cache, blk):
            k, v = kv_cache
            return k, v
        """, path="dynamo_tpu/models/llama.py")
    assert rule_ids(bad) == ["DYN009"]
    good = run("""
        def write(kv_cache, blk):
            if len(kv_cache) == 4:
                k, v, ks, vs = kv_cache
            else:
                k, v = kv_cache
            return k, v
        """, path="dynamo_tpu/models/llama.py")
    assert good == []
    # out-of-scope modules (runtime kv pairs, not KV caches) pass
    assert run("k, v = kv\n", path="dynamo_tpu/runtime/kube.py") == []


# --------------------------- DYN010: print ------------------------------

def test_dyn010_print_in_library():
    bad = run('print("served")\n', path="dynamo_tpu/router/kv_router.py")
    assert rule_ids(bad) == ["DYN010"]
    assert run('print("usage: ...")\n',
               path="dynamo_tpu/engine/__main__.py") == []
    assert run('print("report")\n', path="dynamo_tpu/obs/report.py") == []


# ------------------- DYN011: blocking sync in hot path ------------------

def test_dyn011_unattributed_asarray_in_hot_path():
    bad = run("""
        import numpy as np

        class JaxEngine:
            def _process_oldest_burst(self):
                e = self._inflight.popleft()
                arr = np.asarray(e["burst"])
                return arr
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN011"]
    assert len(bad) == 1


def test_dyn011_device_wait_span_idiom_passes():
    good = run("""
        import numpy as np
        from dynamo_tpu import obs

        class JaxEngine:
            def _process_oldest_burst(self):
                e = self._inflight.popleft()
                t_obs = obs.begin()
                arr = np.asarray(e["burst"])
                obs.end("device_wait", t_obs, track=self._obs_track,
                        what="burst_fetch")
                return arr
        """, path="dynamo_tpu/engine/core.py")
    assert good == []


def test_dyn011_device_wait_phase_passes_other_phases_do_not():
    good = run("""
        import numpy as np

        class JaxEngine:
            def _process_oldest_burst(self):
                e = self._inflight.popleft()
                with self._phase("device_wait", k=e["k"],
                                 what="burst_fetch"):
                    arr = np.asarray(e["burst"])
                return arr
        """, path="dynamo_tpu/engine/core.py")
    assert good == []
    bad = run("""
        import numpy as np

        class JaxEngine:
            def _process_oldest_burst(self):
                e = self._inflight.popleft()
                with self._phase("emit", what="burst"):
                    arr = np.asarray(e["burst"])
                return arr
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN011"]


def test_dyn011_item_and_block_until_ready_flagged():
    bad = run("""
        class JaxEngine:
            def _sched_step(self, tok, kv):
                a = tok.item()
                tok.block_until_ready()
                return a
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN011"]
    assert len(bad) == 2


def test_dyn011_scope_and_exemptions():
    # pre-serving warmup and the follower's lockstep replay are exempt
    assert run("""
        import numpy as np
        import jax

        class JaxEngine:
            def warmup_decode(self):
                jax.block_until_ready(self.kv)

            def apply_step(self, kind, a):
                return np.asarray(a["toks"])
        """, path="dynamo_tpu/engine/core.py") == []
    # only the engine core is the hot path; other modules are governed
    # by their own rules (DYN004 covers the event loop)
    assert run("import numpy as np\nx = np.asarray(y)\n",
               path="dynamo_tpu/kvbm/pools.py") == []


def test_dyn011_suppression_with_reason():
    src = ("import numpy as np\n"
           "def _dispatch_decode(a):\n"
           "    # dynlint: disable=DYN011 host-side numpy descriptor\n"
           "    return np.asarray(a['temps'])\n")
    assert lint.run_source(src, "dynamo_tpu/engine/core.py") == []


# ------------------- DYN012: forensics hop registry ---------------------

def test_dyn012_hop_literals():
    bad = run("""
        def on_dispatch(self, iid):
            self.hop("dispatchd", worker=iid)
            tracker.hop("prefil_open")
        """, path="dynamo_tpu/frontend/request_trace.py")
    assert rule_ids(bad) == ["DYN012"]
    assert len(bad) == 2
    good = run("""
        def on_dispatch(self, iid):
            self.hop("dispatched", worker=iid)
            tracker.hop("prefill_open", at=t0)
            tracker.hop(kind_variable)  # non-literal: not judged
        """, path="dynamo_tpu/frontend/request_trace.py")
    assert good == []


def test_dyn012_applies_in_tests_and_suppresses():
    bad = run("""
        tr.hop("first_tokn")
        """, path="tests/test_forensics.py")
    assert rule_ids(bad) == ["DYN012"]
    src = ('tr.hop("first_tokn")  '
           "# dynlint: disable=DYN012 the negative-test literal\n")
    assert lint.run_source(src, "tests/test_forensics.py") == []


# ------------------- DYN013: allocator/pool book mutation ---------------

def test_dyn013_flags_book_mutations_outside_defining_module():
    bad = run("""
        def steal(allocator, sim, pool, bid, h):
            allocator._free.append(bid)          # free-list mutation
            allocator._block_ref[bid] = 2        # subscript store
            allocator._block_ref[bid] += 1       # augassign
            del allocator._seq_blocks["s"]       # del
            allocator._lru.pop(h, None)          # mutating method
            sim._ref.update({h: 1})              # sim books
            pool._order.clear()                  # pool manifest
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN013"]
    assert len(bad) == 7


def test_dyn013_reads_pass_and_defining_modules_exempt():
    good = run("""
        def audit(allocator):
            free_list = list(allocator._free)    # read-only copy
            rc = dict(allocator._block_ref)
            n = len(allocator._seq_blocks)
            return free_list, rc, n
        """, path="dynamo_tpu/obs/kv_ledger.py")
    assert good == []
    # the defining modules mutate their own books by definition
    owner = run("""
        def free(self, bid):
            self._block_ref.pop(bid, None)
            self._free.append(bid)
        """, path="dynamo_tpu/engine/block_allocator.py")
    assert owner == []


def test_dyn013_applies_in_tests_and_suppresses():
    bad = run("""
        def test_corrupt(a):
            a._free.append(3)
        """, path="tests/test_something.py")
    assert rule_ids(bad) == ["DYN013"]
    src = ("a._free.append(3)  "
           "# dynlint: disable=DYN013 seeding the fault the auditor must catch\n")
    assert lint.run_source(src, "tests/test_something.py") == []


# ------------------- DYN014: raw npz of block payloads -------------------

def test_dyn014_flags_raw_npz_outside_sanctioned_helpers():
    bad = run("""
        import numpy as np

        def restore(path, arrays):
            np.savez(path, **arrays)             # skips the crc stamp
            blob = np.load(path)                 # skips the verify
            np.savez_compressed(path, **arrays)
            return blob
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(bad) == ["DYN014"]
    assert len(bad) == 3


def test_dyn014_sanctioned_modules_and_tests_exempt():
    src = """
        import numpy as np

        def _load_block(path):
            return np.load(path)
        """
    # kvbm/pools.py IS the checksummed helper layer
    assert run(src, path="dynamo_tpu/kvbm/pools.py") == []
    # multimodal decodes media tensors, not KV block payloads
    assert run(src, path="dynamo_tpu/multimodal/encoder.py") == []
    # tests craft corrupt/legacy blobs on purpose — out of scope
    assert run(src, path="tests/test_kv_integrity.py") == []


def test_dyn014_suppresses_with_reason():
    src = ("blob = np.load(path)  "
           "# dynlint: disable=DYN014 reading a non-block npz artifact\n")
    assert lint.run_source(src, "dynamo_tpu/engine/core.py") == []


# --------------------------- suppressions -------------------------------

def test_suppression_with_reason_is_honored():
    findings = run("""
        seed = hash(rid)  # dynlint: disable=DYN002 single-process dict key, never crosses a boundary
        """, path="dynamo_tpu/mocker/engine.py")
    assert findings == []


def test_suppression_standalone_line_covers_next_line():
    findings = run("""
        # dynlint: disable=DYN002 single-process dict key, never crosses a boundary
        seed = hash(rid)
        """, path="dynamo_tpu/mocker/engine.py")
    assert findings == []


def test_suppression_reason_is_mandatory():
    # built by concatenation so THIS file's line-based suppression scan
    # does not see a reasonless disable (see module docstring)
    src = "seed = hash(rid)  # dynlint: " + "disable=DYN002\n"
    findings = lint.run_source(src, "dynamo_tpu/mocker/engine.py")
    ids = rule_ids(findings)
    assert "DYN000" in ids    # the reasonless suppression is a finding
    assert "DYN002" in ids    # and it does NOT suppress


def test_dyn008_tuple_except_clause():
    """`except (OSError, BaseException)` swallows CancelledError just
    like the bare form."""
    bad = run("""
        async def pump(self):
            try:
                await self.once()
            except (OSError, BaseException):
                pass
        """, path="dynamo_tpu/runtime/component.py")
    assert rule_ids(bad) == ["DYN008"]
    good = run("""
        async def pump(self):
            try:
                await self.once()
            except (OSError, ValueError):
                pass
        """, path="dynamo_tpu/runtime/component.py")
    assert good == []


def test_stacked_standalone_suppressions_anchor_on_code_line():
    """Two standalone disables above one flagged line both target the
    code, not each other."""
    findings = run("""
        import jax
        # dynlint: disable=DYN002 fixture: first of a stack
        # dynlint: disable=DYN001 fixture: second of a stack
        x = jax.jit(hash(f))
        """, path="dynamo_tpu/engine/core.py")
    assert findings == []


def test_trailing_suppression_on_continuation_line():
    """A suppression on any physical line of a multiline statement
    covers findings anywhere on that statement."""
    findings = run("""
        import jax
        y = jax.jit(
            fn)  # dynlint: disable=DYN001 fixture: comment on the continuation line
        """, path="dynamo_tpu/engine/core.py")
    assert findings == []


def test_suppression_only_covers_named_rule():
    findings = run("""
        import time

        async def f():
            time.sleep(hash("x"))  # dynlint: disable=DYN002 fixture: only DYN002 is waived
        """, path="dynamo_tpu/engine/core.py")
    assert rule_ids(findings) == ["DYN004"]


def test_unused_suppression_is_flagged():
    """Dead disables must not accumulate: a suppression whose target
    line no longer produces the named finding is itself DYN000 (the
    suppression analogue of the baseline stale-entry rule)."""
    src = ("import zlib\n"
           "seed = zlib.crc32(rid)  # dynlint: " +
           "disable=DYN002 fixed long ago, comment left behind\n")
    findings = lint.run_source(src, "dynamo_tpu/mocker/engine.py")
    assert rule_ids(findings) == ["DYN000"]
    assert "unused" in findings[0].message
    # rule-restricted runs skip the check: suppressions for unselected
    # rules are not "unused", they are out of scope
    assert lint.run_source(src, "dynamo_tpu/mocker/engine.py",
                           rules=["DYN004"]) == []


def test_suppression_inside_string_literal_is_not_parsed():
    """The parser reads real COMMENT tokens, so suppression-shaped text
    in a string (fixtures, docs) neither suppresses nor counts as an
    unused disable."""
    src = ('FIXTURE = """\n'
           'seed = hash(rid)  # dynlint: disable=DYN002 inside a string\n'
           '"""\n'
           "seed = hash(rid)\n")
    findings = lint.run_source(src, "dynamo_tpu/mocker/engine.py")
    assert rule_ids(findings) == ["DYN002"]  # real call flagged, no DYN000


# --------------------------- baseline -----------------------------------

def test_baseline_grandfathers_and_goes_stale(tmp_path):
    pkg = tmp_path / "dynamo_tpu" / "mocker"
    pkg.mkdir(parents=True)
    mod = pkg / "engine.py"
    mod.write_text("seed = hash(rid)\n")

    res = lint.run_paths([str(tmp_path)])
    assert rule_ids(res.findings) == ["DYN002"]

    base = tmp_path / "dynlint.baseline"
    base.write_text(lint.render_baseline(res.findings))
    res2 = lint.run_paths([str(tmp_path)], baseline_path=str(base))
    assert res2.ok and res2.findings == [] and len(res2.baselined) == 1

    # fixing the finding strands the baseline entry -> the gate fails
    # until the stale line is deleted (the baseline only shrinks)
    mod.write_text("import zlib\nseed = zlib.crc32(rid)\n")
    res3 = lint.run_paths([str(tmp_path)], baseline_path=str(base))
    assert res3.findings == [] and len(res3.stale_baseline) == 1
    assert not res3.ok


def test_restricted_runs_do_not_false_stale(tmp_path):
    """A --rule or path-subset run cannot re-produce unrelated baseline
    entries; reporting them stale would tell the developer to delete
    still-valid lines."""
    pkg = tmp_path / "dynamo_tpu" / "mocker"
    pkg.mkdir(parents=True)
    (pkg / "engine.py").write_text("seed = hash(rid)\n")
    other = tmp_path / "dynamo_tpu" / "router"
    other.mkdir()
    (other / "r.py").write_text('print("x")\n')

    res = lint.run_paths([str(tmp_path)])
    base = tmp_path / "dynlint.baseline"
    base.write_text(lint.render_baseline(res.findings))

    # rule-restricted: the DYN010 entry is out of scope, not stale
    r1 = lint.run_paths([str(tmp_path)], baseline_path=str(base),
                        rules=["DYN002"])
    assert r1.ok and r1.stale_baseline == []
    # path-subset: the un-linted router/ entry is out of scope too
    r2 = lint.run_paths([str(pkg)], baseline_path=str(base))
    assert r2.ok and r2.stale_baseline == []


def test_baseline_never_launders_suppression_hygiene(tmp_path):
    """DYN000 (reasonless/dead disables) is neither written by
    --write-baseline nor honored if hand-added: the reason-mandatory
    contract cannot be grandfathered away."""
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import time\ntime.sleep(1)  # dynlint: " + "disable=DYN004\n")
    res = lint.run_paths([str(tmp_path)])
    assert "DYN000" in rule_ids(res.findings)
    rendered = lint.render_baseline(res.findings)
    assert "DYN000" not in rendered          # never written
    base = tmp_path / "b.txt"
    base.write_text(rendered + "".join(
        f.key + "\n" for f in res.findings if f.rule == "DYN000"))
    res2 = lint.run_paths([str(tmp_path)], baseline_path=str(base))
    assert "DYN000" in rule_ids(res2.findings)  # hand-added key ignored


def test_missing_path_is_an_error_not_a_green_gate(tmp_path):
    res = lint.run_paths([str(tmp_path / "no_such_dir")])
    assert not res.ok and res.files == 0
    assert "no Python files" in res.errors[0]


def test_deleted_file_baseline_entry_goes_stale(tmp_path):
    """An entry for a file that no longer exists under the linted roots
    must go stale — a lingering key would grandfather a later
    identically-keyed regression in a re-created file."""
    pkg = tmp_path / "dynamo_tpu" / "mocker"
    pkg.mkdir(parents=True)
    mod = pkg / "engine.py"
    mod.write_text("seed = hash(rid)\n")
    keeper = tmp_path / "dynamo_tpu" / "ok.py"
    keeper.write_text("x = 1\n")
    root = str(tmp_path / "dynamo_tpu")

    res = lint.run_paths([root])
    base = tmp_path / "dynlint.baseline"
    base.write_text(lint.render_baseline(res.findings))
    mod.unlink()
    res2 = lint.run_paths([root], baseline_path=str(base))
    assert res2.stale_baseline and not res2.ok


def test_overlapping_path_args_lint_each_file_once(tmp_path):
    """`dynlint dynamo_tpu dynamo_tpu/mocker` must not lint a file
    twice: the duplicate finding would escape the baseline's multiset
    matching and turn a green gate red."""
    pkg = tmp_path / "dynamo_tpu" / "mocker"
    pkg.mkdir(parents=True)
    (pkg / "engine.py").write_text("seed = hash(rid)\n")
    root = str(tmp_path / "dynamo_tpu")

    res = lint.run_paths([root, str(pkg)])
    assert res.files == 1 and len(res.findings) == 1
    base = tmp_path / "b.txt"
    base.write_text(lint.render_baseline(res.findings))
    res2 = lint.run_paths([root, str(pkg)], baseline_path=str(base))
    assert res2.ok, [f.render() for f in res2.findings]


def test_stale_verdict_is_invocation_spelling_invariant(tmp_path):
    """`dynlint <root>` and `dynlint <root>/dynamo_tpu` must agree that
    a deleted file's entry is stale: an unmarked enclosing root covers
    every namespace its walk produced files in."""
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "ok.py").write_text("x = 1\n")
    base = tmp_path / "b.txt"
    base.write_text(lint.render_baseline([lint.Finding(
        rule="DYN002", path="dynamo_tpu/deleted.py", line=1,
        message="m", snippet="seed = hash(x)")]))
    # enclosing unmarked root (the `dynlint .` spelling)
    r1 = lint.run_paths([str(tmp_path)], baseline_path=str(base))
    # marker root (the `dynlint dynamo_tpu` spelling)
    r2 = lint.run_paths([str(pkg)], baseline_path=str(base))
    assert r1.stale_baseline == r2.stale_baseline != []


def test_write_baseline_path_subset_preserves_other_entries(tmp_path):
    """--write-baseline over a path subset regenerates only that
    subtree's entries; out-of-scope ones survive verbatim."""
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text("seed = hash(rid)\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_x.py").write_text("import asyncio\n\n\nasync def f():\n"
                                    "    asyncio.create_task(g())\n")
    base = tmp_path / "dynlint.baseline"
    full = lint.run_paths([str(pkg), str(tdir)])
    base.write_text(lint.render_baseline(full.findings))

    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", str(pkg),
         "--write-baseline", "--baseline", str(base)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "kept 1 out-of-scope" in out.stdout
    content = base.read_text()
    assert "DYN005|tests/test_x.py" in content  # preserved
    res = lint.run_paths([str(pkg), str(tdir)], baseline_path=str(base))
    assert res.ok, [f.render() for f in res.findings]


def test_write_baseline_refuses_rule_subset(tmp_path):
    """Regenerating the baseline from a rule subset would silently drop
    every other rule's grandfathered entries."""
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text("seed = hash(rid)\n")
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", str(pkg),
         "--rule", "DYN002", "--write-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "--write-baseline cannot be combined" in out.stderr


# --------------------------- the tier-1 gate ----------------------------

def test_repo_is_lint_clean():
    """THE gate: the full rule set over dynamo_tpu/ + tests/ must report
    zero new findings (suppressed-with-reason and baselined are clean),
    zero stale baseline entries, zero parse failures.  A PR that
    introduces any PR-1..7 bug-class regression fails here."""
    res = lint.run_paths(
        [os.path.join(REPO, "dynamo_tpu"), os.path.join(REPO, "tests")],
        baseline_path=os.path.join(REPO, "dynlint.baseline"))
    assert res.files > 150
    assert not res.errors, res.errors
    assert not res.findings, "new dynlint findings:\n" + "\n".join(
        f.render() for f in res.findings)
    assert not res.stale_baseline, (
        "stale dynlint baseline entries (fixed findings must leave "
        "dynlint.baseline):\n" + "\n".join(res.stale_baseline))


def test_every_suppression_in_repo_names_a_reason():
    """Reason enforcement over the real tree, not just fixtures: DYN000
    would surface in the gate above, but assert it directly so the
    failure message is unambiguous."""
    res = lint.run_paths(
        [os.path.join(REPO, "dynamo_tpu"), os.path.join(REPO, "tests")])
    assert not [f for f in res.findings if f.rule == "DYN000"]


# --------------------------- runtime twin (conftest gate) ---------------

def test_slow_callback_gate_fails_blocking_async_test():
    """DYN004's runtime twin end-to-end: a test that blocks the event
    loop past the armed threshold must FAIL with the offending callback
    named.  Runs a throwaway test file under the real tests/ conftest in
    a subprocess (the gate lives there), so this exercises the exact
    mechanism — armed at the 200ms design bound via DYN_TEST_SLOW_CB_S
    to stay well clear of the blocking sleep."""
    path = os.path.join(REPO, "tests", f"test_tmp_slowgate_{os.getpid()}.py")
    with open(path, "w") as f:
        f.write(textwrap.dedent("""
            import time

            async def test_blocks_the_loop():
                time.sleep(0.8)  # lint-exempt: tests/ are out of DYN004 scope; the GATE must catch it
        """))
    try:
        out = subprocess.run(
            [sys.executable, "-m", "pytest", path, "-q",
             "-p", "no:cacheprovider"],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     DYN_TEST_SLOW_CB_S="0.2"))
        assert out.returncode == 1, out.stdout[-2000:]
        assert "blocked the event loop" in out.stdout
        assert "test_blocks_the_loop" in out.stdout  # culprit named
    finally:
        os.unlink(path)


# --------------------------- CLI ----------------------------------------

def test_cli_json_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", "dynamo_tpu/lint",
         "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    data = json.loads(out.stdout)
    assert data["ok"] is True
    assert data["files"] >= 5
    assert isinstance(data["findings"], list)
    assert "stale_baseline" in data


def test_cli_flags_finding_with_exit_1(tmp_path):
    pkg = tmp_path / "dynamo_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("seed = hash(rid)\n")
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", str(pkg), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    data = json.loads(out.stdout)
    assert [f["rule"] for f in data["findings"]] == ["DYN002"]


def test_cli_list_rules():
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.lint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    for rid in ALL_RULES:
        assert rid in out.stdout
