"""Per-process system status server: /health /live /metrics + the
token-gated admin debug surface /debug/state, /debug/requests,
/debug/kv and /debug/profile.

Ref: lib/runtime/src/system_status_server.rs:159-222 for the health
trio.  The debug surface is the per-process half of the fleet
introspection plane (obs/fleet.py): `/debug/state` is a JSON dump of
everything a live incident needs that pre-aggregated gauges can't give
(scheduler slots, in-flight request ids, KV occupancy per tier, drain
and canary status, compile-watch family stats, effective config, the
flight-recorder's last-N spans), and `/debug/profile` captures a
time-bounded `jax.profiler` trace plus a device-memory (HBM breakdown)
snapshot on demand.

Exposure model: the server binds `host` (default 0.0.0.0 so k8s probes
and Prometheus can reach it) — /health, /live and /metrics carry no
secrets and stay open, while every /debug/* route requires the
DYN_ADMIN_TOKEN shared secret (constant-time compare; no token
configured = 403, fail closed).  Workers/frontends register callables
via `DistributedRuntime.register_debug_source`, so the dump reflects
whatever serves in this process without the server knowing any
engine's shape.
"""

from __future__ import annotations

import functools
import hmac
import inspect
import json
import logging
import os
import sys
import time
from dataclasses import asdict
from typing import TYPE_CHECKING, Optional

from aiohttp import web

if TYPE_CHECKING:
    from .distributed import DistributedRuntime

logger = logging.getLogger(__name__)

# profiler capture bounds: long enough for a few scheduler steps on a
# busy fleet, short enough that an operator can't wedge a worker behind
# an hour-long trace
PROFILE_MIN_S = 0.05
PROFILE_MAX_S = 60.0

# /debug/state flight-recorder tail: enough spans to see the steps that
# led up to an incident without shipping the whole 16k ring per scrape
DEFAULT_FLIGHT_SPANS = 64
MAX_FLIGHT_SPANS = 4096


class SystemStatusServer:
    def __init__(self, runtime: "DistributedRuntime", port: int,
                 host: str = "0.0.0.0"):
        self.runtime = runtime
        self.host = host
        self.port = port
        self.bound_port: Optional[int] = None  # actual port once started
        self._runner = None
        self._started_t = time.monotonic()
        import asyncio

        self._profile_lock = asyncio.Lock()

    # -- open routes ------------------------------------------------------
    async def _health(self, request: web.Request) -> web.Response:
        shutting_down = self.runtime.root_token.is_stopped()
        canaries_ok = self.runtime.system_health.healthy
        healthy = not shutting_down and canaries_ok
        status = ("shutting_down" if shutting_down
                  else "healthy" if canaries_ok else "unhealthy")
        return web.json_response(
            {"status": status,
             "worker_id": self.runtime.worker_id,
             "endpoints": self.runtime.system_health.statuses()},
            status=200 if healthy else 503,
        )

    async def _live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(body=self.runtime.metrics.render(),
                            content_type="text/plain")

    # -- admin gate -------------------------------------------------------
    def _authorize(self, request: web.Request) -> Optional[web.Response]:
        """None = authorized; else the error response.  The token rides
        `Authorization: Bearer <tok>` or `X-Dyn-Admin-Token`."""
        token = self.runtime.config.admin_token
        if not token:
            return web.json_response(
                {"error": "admin surface disabled: set DYN_ADMIN_TOKEN "
                          "on this process to enable /debug/*"},
                status=403)
        given = request.headers.get("X-Dyn-Admin-Token", "")
        if not given:
            auth = request.headers.get("Authorization", "")
            if auth.startswith("Bearer "):
                given = auth[len("Bearer "):]
        if not hmac.compare_digest(given.encode(), token.encode()):
            return web.json_response({"error": "unauthorized"}, status=401)
        return None

    # -- /debug/state -----------------------------------------------------
    async def _debug_state(self, request: web.Request) -> web.Response:
        err = self._authorize(request)
        if err is not None:
            return err
        try:
            n_spans = int(request.query.get("spans", DEFAULT_FLIGHT_SPANS))
        except ValueError:
            n_spans = DEFAULT_FLIGHT_SPANS
        n_spans = max(0, min(n_spans, MAX_FLIGHT_SPANS))
        rt = self.runtime
        cfg = asdict(rt.config)
        cfg["admin_token"] = "***" if cfg.get("admin_token") else ""
        sources = {}
        for name, fn in list(rt.debug_sources.items()):
            try:
                v = fn()
                if inspect.isawaitable(v):
                    v = await v
                sources[name] = v
            except Exception as e:  # a broken source must not kill the dump
                logger.warning("debug source %s failed", name, exc_info=True)
                sources[name] = {"error": f"{type(e).__name__}: {e}"}
        state = {
            "worker_id": rt.worker_id,
            "pid": os.getpid(),
            "ts_unix": time.time(),
            "uptime_s": round(time.monotonic() - self._started_t, 3),
            "health": {
                "shutting_down": rt.root_token.is_stopped(),
                "healthy": rt.system_health.healthy,
                "endpoints": rt.system_health.statuses(),
            },
            "config": cfg,
            "sources": sources,
            "flight": self._flight_tail(n_spans),
        }
        # sources can carry non-JSON leaves (numpy scalars, enums);
        # degrade them to repr instead of 500ing the whole dump
        body = json.dumps(state, default=repr)
        return web.Response(body=body.encode(),
                            content_type="application/json")

    @staticmethod
    def _flight_tail(n: int) -> dict:
        """Last-N spans of the in-process flight recorder (obs/), plus
        any post-mortem dumps it already wrote.  Empty when tracing is
        off — the dump stays valid, just without a timeline."""
        from .. import obs

        tr = obs.tracer()
        if tr is None or n == 0:
            return {"enabled": tr is not None, "spans": []}
        with tr._lock:
            tail = list(tr.spans)[-n:]
        now = time.monotonic()
        return {
            "enabled": True,
            "dumps": list(tr.flight_dumps),
            "spans": [
                {"kind": kind, "age_s": round(now - t1, 4),
                 "dur_ms": round((t1 - t0) * 1e3, 3), "track": track,
                 **({"attrs": attrs} if attrs else {}),
                 **({"trace_id": trace_id} if trace_id else {})}
                for kind, t0, t1, track, attrs, trace_id in tail
            ],
        }

    @staticmethod
    async def _merge_sources(registry: dict, what: str) -> dict:
        """Collect one registry's source callables (sync or async) into
        a name->dump dict; a broken source degrades to an error entry
        instead of killing the whole dump."""
        sources = {}
        for name, fn in list(registry.items()):
            try:
                v = fn()
                if inspect.isawaitable(v):
                    v = await v
                sources[name] = v
            except Exception as e:  # a broken source must not kill the dump
                logger.warning("%s source %s failed", what, name,
                               exc_info=True)
                sources[name] = {"error": f"{type(e).__name__}: {e}"}
        return sources

    # -- /debug/requests --------------------------------------------------
    async def _debug_requests(self, request: web.Request) -> web.Response:
        """Tail-latency forensics dump (obs/forensics.py): the retained
        slowest-K request timelines + every SLO breach with its pinned
        span snapshot, per registered source.  Token-gated exactly like
        /debug/state — timelines are metadata, never payload, but they
        still carry request ids and worker placements."""
        err = self._authorize(request)
        if err is not None:
            return err
        rt = self.runtime
        body = json.dumps({
            "worker_id": rt.worker_id,
            "pid": os.getpid(),
            "ts_unix": time.time(),
            "sources": await self._merge_sources(rt.forensics_sources,
                                                 "forensics"),
        }, default=repr)
        return web.Response(body=body.encode(),
                            content_type="application/json")

    # -- /debug/kv --------------------------------------------------------
    async def _debug_kv(self, request: web.Request) -> web.Response:
        """KV-accounting dump (obs/kv_ledger.py): per registered worker
        source, the block-lifecycle ledger's attribution (per-tier
        occupancy by state + fragmentation), violation totals, and a
        fresh ON-DEMAND reconciliation sweep — which is why the payload
        gets its own route instead of riding a /debug/state scrape.
        Token-gated exactly like the other /debug/* surfaces."""
        err = self._authorize(request)
        if err is not None:
            return err
        rt = self.runtime
        body = json.dumps({
            "worker_id": rt.worker_id,
            "pid": os.getpid(),
            "ts_unix": time.time(),
            "sources": await self._merge_sources(rt.kv_sources, "kv"),
        }, default=repr)
        return web.Response(body=body.encode(),
                            content_type="application/json")

    # -- /debug/profile ---------------------------------------------------
    async def _debug_profile(self, request: web.Request) -> web.Response:
        """On-demand, time-bounded `jax.profiler` capture + a device
        memory (HBM breakdown) snapshot.  One capture at a time per
        process (409 while busy); no-op-safe on CPU and on processes
        where the profiler is unavailable (status "unavailable", never
        a 500 — an incident tool must not add incidents).  Refused
        (status "unavailable") in a process that holds no engine."""
        err = self._authorize(request)
        if err is not None:
            return err
        import math

        try:
            duration_s = float(request.query.get("duration_s", "1.0"))
        except ValueError:
            duration_s = float("nan")
        if not math.isfinite(duration_s):
            return web.json_response(
                {"error": "duration_s must be a finite number"}, status=400)
        duration_s = min(max(duration_s, PROFILE_MIN_S), PROFILE_MAX_S)
        if self._profile_lock.locked():
            return web.json_response(
                {"error": "a profiler capture is already running"},
                status=409)
        if "jax" not in sys.modules:
            # a process that never imported JAX holds no engine and no
            # device (frontend, router, planner, mocker).  Importing it
            # here would start a backend in THIS process and reach for
            # the chip the worker next door holds — refuse instead.
            return web.json_response({
                "worker_id": self.runtime.worker_id, "pid": os.getpid(),
                "status": "unavailable",
                "error": "this process holds no device (JAX was never "
                         "imported here); ask the worker that owns the "
                         "chip"})
        import asyncio
        import tempfile

        async with self._profile_lock:
            out_dir = os.environ.get("DYN_PROFILE_DIR") or tempfile.mkdtemp(
                prefix=f"dynprof-{os.getpid()}-")
            result: dict = {"worker_id": self.runtime.worker_id,
                            "pid": os.getpid(),
                            "duration_s": duration_s,
                            "out_dir": out_dir}
            trace_dir = os.path.join(
                out_dir, f"trace-{int(time.time())}-{os.getpid()}")
            try:
                import jax

                result["backend"] = jax.default_backend()
                await asyncio.to_thread(
                    functools.partial(os.makedirs, trace_dir, exist_ok=True))
                await asyncio.to_thread(jax.profiler.start_trace, trace_dir)
                try:
                    await asyncio.sleep(duration_s)
                finally:
                    await asyncio.to_thread(jax.profiler.stop_trace)
                result["status"] = "ok"
                result["trace_dir"] = trace_dir
            except Exception as e:
                logger.warning("profiler trace capture failed",
                               exc_info=True)
                result["status"] = "unavailable"
                result["error"] = f"{type(e).__name__}: {e}"
            try:
                import jax

                mem_path = os.path.join(
                    out_dir, f"memory-{int(time.time())}-{os.getpid()}.prof")
                await asyncio.to_thread(
                    jax.profiler.save_device_memory_profile, mem_path)
                result["memory_profile"] = mem_path
            except Exception as e:
                result["memory_profile_error"] = f"{type(e).__name__}: {e}"
            return web.json_response(result)

    async def start(self) -> None:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/debug/state", self._debug_state)
        app.router.add_get("/debug/requests", self._debug_requests)
        app.router.add_get("/debug/kv", self._debug_kv)
        app.router.add_get("/debug/profile", self._debug_profile)
        app.router.add_post("/debug/profile", self._debug_profile)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        # port 0 = ephemeral: record what the OS picked so the runtime
        # can advertise a scrapeable address in discovery metadata
        self.bound_port = self._runner.addresses[0][1]

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
