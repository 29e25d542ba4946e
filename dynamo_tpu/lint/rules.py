"""dynlint rules DYN001–DYN014: each one encodes a bug this repo really
shipped (the PR it came from is named per rule), turning a
found-late-by-review-or-live-fleet failure into a permanently-enforced
invariant.  The README "Static analysis" table is generated from the
``bug`` strings below.

Scoping: rules carry a path predicate.  ``dynamo_tpu/`` is library code
under full enforcement; ``tests/`` gets the rules whose bug class lives
in tests too (task leaks, seam/span typos, marker literals, swallowed
cancellation); CLI entrypoints (``__main__.py``, report/profiler) are
exempt from the print rule because printing is their job.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .core import Finding, Module, dotted, register, str_arg, terminal


def _in_pkg(path: str) -> bool:
    return path.startswith("dynamo_tpu/")


def _in_pkg_or_tests(path: str) -> bool:
    return path.startswith(("dynamo_tpu/", "tests/"))


def _walk_async_body(fn: ast.AsyncFunctionDef) -> Iterable[ast.AST]:
    """Nodes that execute ON THE EVENT LOOP inside this async def:
    descends expressions and control flow but not nested function defs
    (those are callbacks/executor targets, judged where they run)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# DYN001 — raw jax.jit / pjit outside the compile watchdog
# ---------------------------------------------------------------------------

_JIT_BASES = {"jax.jit", "jit", "pjit", "jax.experimental.pjit.pjit"}


@register(
    "DYN001",
    "raw jax.jit/pjit outside compile-watch wrapping",
    "PR 7: guided decoding's duplicate lazy top-k init went through a raw "
    "jax.jit that bypassed the compile watchdog — the measured 8-14s "
    "mid-serving guided-fork stall would have stayed invisible",
    applies=lambda p: _in_pkg(p) and p != "dynamo_tpu/obs/compile_watch.py"
    and not p.startswith("dynamo_tpu/lint/"))
def raw_jit(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        d = dotted(node)
        if d not in _JIT_BASES:
            continue
        # bare-name matches must actually come from jax; `jit`/`pjit`
        # defined locally (a helper named jit) is not our business
        if isinstance(node, ast.Name) and not _imported_from_jax(mod,
                                                                 node.id):
            continue
        # references that are themselves the attr of a longer chain
        # (e.g. the `jax.jit` inside `jax.jit.lower`) are covered by the
        # outer node; only judge the full chain
        parent = mod.parent(node)
        if isinstance(parent, ast.Attribute):
            continue
        if _under_wrap_call(mod, node):
            continue
        yield mod.finding(
            "DYN001", node,
            "raw jax.jit/pjit: route it through "
            "obs/compile_watch.CompileWatch.wrap(...) so a mid-serving "
            "compile is observed (the PR 7 guided-topk blind spot)")


def _imported_from_jax(mod: Module, name: str) -> bool:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.split(".")[0] == "jax":
            if any(a.asname == name or (a.asname is None and a.name == name)
                   for a in node.names):
                return True
    return False


def _under_wrap_call(mod: Module, node: ast.AST) -> bool:
    """True when the jit reference is an argument (at any depth) of a
    ``<watch>.wrap(...)`` call — the sanctioned way to create one."""
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.Call) and terminal(anc.func) == "wrap":
            return True
    return False


# ---------------------------------------------------------------------------
# DYN002 — builtin hash() for identity
# ---------------------------------------------------------------------------

@register(
    "DYN002",
    "builtin hash() used for identity",
    "PR 4: the mocker's position-addressed token stream seeded from "
    "hash(request_id) — PYTHONHASHSEED randomizes it per process, so "
    "cross-process token-replay migration regenerated a different suffix; "
    "fixed to zlib.crc32",
    applies=_in_pkg)
def builtin_hash(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "hash":
            yield mod.finding(
                "DYN002", node,
                "builtin hash() is PYTHONHASHSEED-randomized per process "
                "— any value that crosses a process boundary (seeds, "
                "replay identity, cache keys) must use zlib.crc32 or "
                "tokens/hashing instead")


# ---------------------------------------------------------------------------
# DYN003 — metric family without the dynamo_ prefix
# ---------------------------------------------------------------------------

_METRIC_METHODS = {"counter", "gauge", "histogram", "inc", "observe",
                   "set", "set_gauge"}
_METRIC_CTORS = {"Counter", "Gauge", "Histogram", "Summary"}


@register(
    "DYN003",
    "metric family defined without the dynamo_ prefix",
    "PR 7: the scrape-contract test asserts every exported family is "
    "dynamo_-prefixed at runtime; this is its static twin, catching the "
    "definition site before a worker ever serves /metrics (PR 10 widened "
    "it to MetricsHierarchy.set so the fleet aggregator's dynamo_fleet_* "
    "gauge definitions are in scope)",
    applies=_in_pkg_or_tests)
def metric_prefix(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = None
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _METRIC_METHODS:
            name = str_arg(node)
        elif terminal(node.func) in _METRIC_CTORS:
            name = str_arg(node)
        if name is None:
            continue
        # only judge strings that are plausibly prometheus family names
        # (.observe()/.inc() on non-metric objects take arbitrary args)
        if not name.replace("_", "").islower() or " " in name \
                or not name[:1].isalpha():
            continue
        if not name.startswith("dynamo_"):
            yield mod.finding(
                "DYN003", node,
                f"metric family {name!r} must carry the dynamo_ prefix "
                "(scrape contract: every exported family aggregates "
                "under one namespace)")


# ---------------------------------------------------------------------------
# DYN004 — blocking call lexically inside async def
# ---------------------------------------------------------------------------

_BLOCKING_DOTTED = {
    "time.sleep", "os.system",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output",
}


@register(
    "DYN004",
    "blocking call inside async def",
    "PR 7 class: the engine moved every device wait behind "
    "asyncio.to_thread because one synchronous fetch on the event loop "
    "stalls every live stream's frame egress at once",
    applies=_in_pkg)
def blocking_in_async(mod: Module) -> Iterable[Finding]:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for node in _walk_async_body(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            t = terminal(node.func)
            msg = None
            if d in _BLOCKING_DOTTED:
                msg = f"{d}() blocks the event loop"
            elif isinstance(node.func, ast.Name) and node.func.id == "open":
                msg = ("sync file I/O on the event loop: open/read/write "
                       "via run_in_executor (or aiofiles-style helpers)")
            elif t == "block_until_ready":
                msg = ("block_until_ready() parks the loop on a device "
                       "sync; fetch via asyncio.to_thread")
            elif t == "result" and isinstance(node.func, ast.Attribute) \
                    and not node.args and not node.keywords:
                msg = (".result() on a future blocks (or raises "
                       "InvalidState); await it, or suppress with the "
                       "reason the future is known-done")
            if msg:
                yield mod.finding(
                    "DYN004", node,
                    f"{msg} — inside `async def {fn.name}` every "
                    "concurrent request stalls behind it")


# ---------------------------------------------------------------------------
# DYN005 — fire-and-forget task
# ---------------------------------------------------------------------------

@register(
    "DYN005",
    "asyncio task created and discarded",
    "PR 4: leaked tasks are how wedged-worker bugs hide — the conftest "
    "gate catches them at runtime per test; this catches the discarded "
    "reference at the creation site, library-wide",
    applies=_in_pkg_or_tests)
def discarded_task(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        t = terminal(call.func)
        if t not in ("create_task", "ensure_future"):
            continue
        yield mod.finding(
            "DYN005", call,
            f"{t}(...) result discarded: the event loop holds only a "
            "weak reference — the task can be garbage-collected "
            "mid-flight and its exceptions are never observed; keep a "
            "reference (owner set + done-callback discard) or await it")


# ---------------------------------------------------------------------------
# DYN006 — seam / span-kind literal not in the central registry
# ---------------------------------------------------------------------------

def _registries():
    from .. import chaos, obs

    return chaos.SEAMS, set(chaos.ACTIONS), obs.SPAN_KINDS


@register(
    "DYN006",
    "chaos-seam / span-kind literal not in the central registry",
    "PR 4/6 class: a typo'd seam name is a chaos rule that silently never "
    "fires and a typo'd span kind is an orphan timeline row; "
    "chaos.SEAMS / obs.SPAN_KINDS are the single source of truth",
    applies=_in_pkg_or_tests)
def registry_literals(mod: Module) -> Iterable[Finding]:
    seams, actions, span_kinds = _registries()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        t = terminal(node.func)
        if d in ("chaos.hit", "chaos.ahit"):
            seam = str_arg(node)
            if seam is not None and seam not in seams:
                yield mod.finding(
                    "DYN006", node,
                    f"seam {seam!r} is not in chaos.SEAMS — this hit() "
                    "can never be targeted by a rule; register the seam "
                    "or fix the typo")
        elif t == "rule":
            seam, action = str_arg(node, 0), str_arg(node, 1)
            if seam is not None and action in actions \
                    and seam not in seams:
                yield mod.finding(
                    "DYN006", node,
                    f"seam {seam!r} is not in chaos.SEAMS — a rule on an "
                    "unregistered seam silently never fires")
        elif d in ("obs.span", "obs.end") or t == "_phase":
            # `<engine>._phase(kind, ...)` is an obs.PhaseClock call
            kind = str_arg(node)
            if kind is not None and kind not in span_kinds:
                yield mod.finding(
                    "DYN006", node,
                    f"span kind {kind!r} is not in obs.SPAN_KINDS — the "
                    "report and dashboards join on the registered "
                    "vocabulary; add the kind there or fix the typo")


# ---------------------------------------------------------------------------
# DYN007 — protocol marker literal written inline
# ---------------------------------------------------------------------------

def _drain_markers():
    from ..protocols import llm

    return {llm.DRAIN_REJECT: "protocols.DRAIN_REJECT",
            llm.DRAIN_ABORT: "protocols.DRAIN_ABORT"}


@register(
    "DYN007",
    "protocol marker literal inlined instead of imported",
    "PR 4: the drain markers were duplicated as string literals in both "
    "engines — a reword in one would silently break real-engine "
    "token-replay migration while mocker tests stayed green",
    applies=lambda p: _in_pkg_or_tests(p)
    and p != "dynamo_tpu/protocols/llm.py")
def inline_marker(mod: Module) -> Iterable[Finding]:
    markers = _drain_markers()
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)):
            continue
        v = node.value
        name = markers.get(v)
        # dynlint: disable=DYN007 the rule's own prefix check, not an inline marker
        if name is None and v.startswith("worker draining:"):
            name = "protocols.DRAIN_REJECT/DRAIN_ABORT"
        if name is not None:
            yield mod.finding(
                "DYN007", node,
                f"inline copy of a protocol marker: import {name} — "
                "migratable-error classification substring-matches the "
                "canonical text, a reworded copy breaks it silently")


# ---------------------------------------------------------------------------
# DYN008 — swallowing cancellation in async code
# ---------------------------------------------------------------------------

@register(
    "DYN008",
    "bare except / except BaseException in async def without re-raise",
    "PR 4 class: a handler that eats CancelledError turns cooperative "
    "cancellation into a wedged task — exactly the shutdown/drain hangs "
    "the chaos suite exists to catch",
    applies=_in_pkg_or_tests)
def swallowed_cancellation(mod: Module) -> Iterable[Finding]:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for node in _walk_async_body(fn):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not _catches_base_exception(node.type):
                continue
            if any(isinstance(n, ast.Raise)
                   for b in node.body for n in ast.walk(b)):
                continue
            what = ("bare `except:`" if node.type is None
                    else "`except BaseException`")
            yield mod.finding(
                "DYN008", node,
                f"{what} inside `async def {fn.name}` swallows "
                "CancelledError: the task can no longer be cancelled "
                "(wedged drains/shutdowns); re-raise, or catch Exception")


def _catches_base_exception(type_node) -> bool:
    """True for bare ``except:``, ``except BaseException`` and a tuple
    clause containing it (``except (OSError, BaseException)`` swallows
    CancelledError just the same)."""
    if type_node is None:
        return True
    if isinstance(type_node, ast.Tuple):
        return any(terminal(e) == "BaseException" for e in type_node.elts)
    return terminal(type_node) == "BaseException"


# ---------------------------------------------------------------------------
# DYN009 — KV tuple destructured at fixed arity 2
# ---------------------------------------------------------------------------

_KV_NAMES = {"kv", "kv_cache", "kv_pages", "kv_tuple"}


def _kv_name(node: ast.AST):
    t = terminal(node)
    if t is None:
        return None
    if t in _KV_NAMES or t.endswith("_kv"):
        return t
    return None


@register(
    "DYN009",
    "KV cache tuple destructured at fixed arity 2",
    "PR 3: the int8 cache rides as a (k, v, k_scale, v_scale) 4-tuple "
    "through the same pytree as the bf16 (k, v) 2-tuple; an unguarded "
    "`k, v = kv` silently drops the scale planes (or raises) the first "
    "time an int8 cache reaches it",
    applies=lambda p: p.startswith((
        "dynamo_tpu/engine/", "dynamo_tpu/ops/", "dynamo_tpu/models/",
        "dynamo_tpu/kvbm/", "dynamo_tpu/disagg/", "dynamo_tpu/quant/",
        "dynamo_tpu/mocker/", "dynamo_tpu/spec/")))
def kv_fixed_arity(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Tuple) and len(tgt.elts) == 2
                and all(isinstance(e, ast.Name) for e in tgt.elts)):
            continue
        name = _kv_name(node.value)
        if name is None:
            continue
        fn = mod.enclosing_function(node)
        if fn is not None and _has_len_guard(fn, name):
            continue
        yield mod.finding(
            "DYN009", node,
            f"`{tgt.elts[0].id}, {tgt.elts[1].id} = {name}` assumes the "
            "bf16 2-tuple: int8 caches are (k, v, k_scale, v_scale) — "
            "guard on len() (quant/kv.py unpack_kv) or handle both "
            "arities")


def _has_len_guard(fn: ast.AST, name: str) -> bool:
    """The enclosing function tests len(<name>) somewhere — the
    quant/kv.py unpack idiom — so the 2-arity branch is deliberate."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "len" and len(node.args) == 1 \
                and terminal(node.args[0]) == name:
            return True
    return False


# ---------------------------------------------------------------------------
# DYN011 — blocking device sync in the scheduler hot path outside a
# device_wait span
# ---------------------------------------------------------------------------

# the scheduler hot path: every function in engine/core.py except the
# ones that run before serving or replay a leader's lockstep stream
_DYN011_EXEMPT_FNS = {"warmup_decode", "_init_kv_cache", "apply_step"}


def _dyn011_candidates(mod: Module):
    """Calls that force a host<->device synchronization: np.asarray(...)
    (the engine's canonical fetch), .block_until_ready(), .item()."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        t = terminal(node.func)
        if d in ("np.asarray", "numpy.asarray"):
            yield node, "np.asarray(...)"
        elif t == "block_until_ready":
            yield node, ".block_until_ready()"
        elif t == "item" and isinstance(node.func, ast.Attribute) \
                and not node.args and not node.keywords:
            yield node, ".item()"


def _stmt_of(mod: Module, node: ast.AST) -> ast.stmt:
    """The innermost statement containing `node`."""
    stmt = node
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.stmt):
            stmt = anc
            break
    return stmt


def _body_of(mod: Module, stmt: ast.stmt):
    """The statement list `stmt` sits in (its parent's matching block)."""
    parent = mod.parent(stmt)
    if parent is None:
        return None
    for field in ("body", "orelse", "finalbody"):
        block = getattr(parent, field, None)
        if isinstance(block, list) and stmt in block:
            return block
    return None


def _in_device_wait_span(mod: Module, node: ast.AST) -> bool:
    """True when the call sits inside the sanctioned phase

        with self._phase("device_wait", what=...):
            <the blocking fetch>

    (an obs.PhaseClock phase: counter, profiler TraceMe, ring), or
    follows the older pair in its OWN statement block:

        t = obs.begin()
        <the blocking fetch>
        obs.end("device_wait", t, ...)

    i.e. an obs.begin() assignment somewhere before it and an
    obs.end("device_wait", ...) somewhere after it, both at the same
    block depth — so the fetch's wall time is attributed to the
    device_wait phase the gap report scores."""
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.With) and any(
                isinstance(it.context_expr, ast.Call)
                and terminal(it.context_expr.func) == "_phase"
                and str_arg(it.context_expr) == "device_wait"
                for it in anc.items):
            return True
    stmt = _stmt_of(mod, node)
    block = _body_of(mod, stmt)
    if block is None:
        return False
    idx = block.index(stmt)
    begin_before = any(
        isinstance(s, ast.Assign) and isinstance(s.value, ast.Call)
        and dotted(s.value.func) == "obs.begin"
        for s in block[:idx])
    end_after = any(
        isinstance(s, ast.Expr) and isinstance(s.value, ast.Call)
        and dotted(s.value.func) == "obs.end"
        and str_arg(s.value) == "device_wait"
        for s in block[idx + 1:])
    return begin_before and end_after


@register(
    "DYN011",
    "blocking device sync in the scheduler hot path outside a "
    "device_wait span",
    "PR 11 class: the overlapped scheduler only works if the hot path's "
    "sole blocking points are the deliberate, span-attributed readbacks "
    "— one stray np.asarray/.item()/block_until_ready silently "
    "re-serializes host and device AND the stall is invisible to the "
    "gap report that exists to catch it",
    applies=lambda p: p == "dynamo_tpu/engine/core.py")
def blocking_sync_in_hot_path(mod: Module) -> Iterable[Finding]:
    for node, what in _dyn011_candidates(mod):
        fn = mod.enclosing_function(node)
        if fn is not None and fn.name in _DYN011_EXEMPT_FNS:
            continue
        if _in_device_wait_span(mod, node):
            continue
        yield mod.finding(
            "DYN011", node,
            f"{what} in the scheduler hot path forces a device sync "
            "outside a device_wait span: wrap it in `with "
            "self._phase(\"device_wait\", what=...)` so the stall is "
            "attributed (and deliberate), or move the readback behind "
            "the overlap machinery (_pending_first / _inflight)")


# ---------------------------------------------------------------------------
# DYN010 — print() in library code
# ---------------------------------------------------------------------------

_PRINT_OK = (
    "__main__.py",                 # CLI entrypoints print by design
    "dynamo_tpu/obs/report.py",    # report CLIs
    "dynamo_tpu/obs/fleet.py",     # fleet snapshot CLI
    "dynamo_tpu/profiler/",
    "dynamo_tpu/loadgen/",
    "dynamo_tpu/lint/cli.py",      # the lint's own CLI output
)


def _print_applies(path: str) -> bool:
    if not _in_pkg(path):
        return False
    return not any(path.endswith(s) or path.startswith(s)
                   for s in _PRINT_OK)


@register(
    "DYN010",
    "print() in library code",
    "observability-plane class: a print bypasses runtime/logging — no "
    "level, no trace_id stamp (PR 7's log<->span join), invisible to "
    "log-based alerting; workers' stdout is not a log pipeline",
    applies=_print_applies)
def print_in_library(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            yield mod.finding(
                "DYN010", node,
                "print() in library code: use runtime/logging (levels, "
                "TraceIdFilter correlation) — stdout is not scraped")


# ---------------------------------------------------------------------------
# DYN012 — forensics hop-kind literal not in the central registry
# ---------------------------------------------------------------------------

def _hop_kinds():
    from .. import obs

    return obs.HOP_KINDS


@register(
    "DYN012",
    "forensics hop-kind literal not in obs.HOP_KINDS",
    "forensics-plane twin of DYN006: a typo'd hop name would be an orphan "
    "timeline row the phase partition and the tail autopsy silently never "
    "join on; obs.HOP_KINDS is the single source of truth",
    applies=_in_pkg_or_tests)
def hop_literals(mod: Module) -> Iterable[Finding]:
    kinds = _hop_kinds()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or terminal(node.func) != "hop":
            continue
        kind = str_arg(node)
        if kind is not None and kind not in kinds:
            yield mod.finding(
                "DYN012", node,
                f"hop kind {kind!r} is not in obs.HOP_KINDS — the exact "
                "phase partition and the tail autopsy join on the "
                "registered vocabulary; register the kind (and its "
                "docstring-table row) or fix the typo")


# ---------------------------------------------------------------------------
# DYN013 — allocator/pool book mutation outside the defining module
# ---------------------------------------------------------------------------

# the ledgered private books: BlockAllocator's refcount/free-list/hash
# maps, the KVBM pools' manifests, and the mocker sim's hash books —
# each mutable ONLY inside its defining module, where every transition
# is mirrored onto the KV ledger (obs/kv_ledger.py)
_BOOK_ATTRS = {
    "_block_ref", "_hash_to_block", "_block_hash", "_seq_blocks",
    "_free", "_lru",            # engine/block_allocator.py
    "_blocks", "_order",        # kvbm/pools.py
    "_ref", "_seq_full", "_seq_partial",  # mocker/kv_cache_sim.py
}
_BOOK_MODULES = (
    "dynamo_tpu/engine/block_allocator.py",
    "dynamo_tpu/kvbm/pools.py",
    "dynamo_tpu/mocker/kv_cache_sim.py",
)
_MUTATORS = {
    "append", "pop", "popitem", "clear", "insert", "extend", "remove",
    "update", "setdefault", "move_to_end", "add", "discard",
}


def _book_attr(node: ast.AST):
    """The `x._book` Attribute inside `node` being written through, if
    any: the node itself, or the value of a Subscript store target."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and node.attr in _BOOK_ATTRS:
        return node
    return None


@register(
    "DYN013",
    "allocator/pool book mutated outside its defining module",
    "kv-ledger plane (obs/kv_ledger.py): the ledger mirrors every "
    "BlockAllocator/pool/sim book transition at its definition site — a "
    "mutation anywhere else is invisible to the books and IS the silent "
    "leak/double-free/orphan class the auditor exists to catch",
    applies=lambda p: _in_pkg_or_tests(p) and p not in _BOOK_MODULES)
def book_mutation(mod: Module) -> Iterable[Finding]:
    def _flag(attr_node: ast.AST, how: str):
        return mod.finding(
            "DYN013", attr_node,
            f"{how} of `{attr_node.attr}` outside its defining module: "
            "the KV ledger mirrors these books at their definition "
            "sites only (engine/block_allocator.py, kvbm/pools.py, "
            "mocker/kv_cache_sim.py) — mutate through the owning "
            "class's API, or the accounting drifts silently")

    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                a = _book_attr(t)
                if a is not None:
                    yield _flag(a, "assignment")
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                a = _book_attr(t)
                if a is not None:
                    yield _flag(a, "del")
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS:
            a = _book_attr(node.func.value)
            if a is not None:
                yield _flag(a, f".{node.func.attr}()")


# ---------------------------------------------------------------------------
# DYN014 — raw np.load/np.savez of KV block payloads
# ---------------------------------------------------------------------------

_NPZ_CALLS = {
    "np.load", "numpy.load", "np.savez", "numpy.savez",
    "np.savez_compressed", "numpy.savez_compressed",
}
# the sanctioned readers/writers live in kvbm/pools.py (_save_block /
# _load_block / read_block_file, the only code allowed to touch the npz
# layer directly — it is what stamps and verifies the crc32 footer);
# multimodal/encoder.py decodes MEDIA tensors from the wire, not KV
# block payloads, so the checksummed-block contract does not apply
_NPZ_EXEMPT = (
    "dynamo_tpu/kvbm/pools.py",
    "dynamo_tpu/multimodal/encoder.py",
)


@register(
    "DYN014",
    "raw np.load/np.savez outside the checksummed block helpers",
    "PR 20: persisted/transferred KV blocks carry a crc32 footer verified "
    "at every tier-crossing consume — a direct np.load/np.savez of a "
    "block payload bypasses both the stamp and the verify, re-creating "
    "the unchecksummed blobs the integrity plane exists to retire",
    applies=lambda p: _in_pkg(p) and p not in _NPZ_EXEMPT)
def raw_npz(mod: Module) -> Iterable[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d not in _NPZ_CALLS:
            continue
        yield mod.finding(
            "DYN014", node,
            f"direct {d}() of a block payload bypasses the crc32 "
            "stamp/verify: persist through kvbm/pools._save_block and "
            "consume through _load_block/read_block_file (+verify_block) "
            "so a corrupt blob quarantines instead of serving bytes")
