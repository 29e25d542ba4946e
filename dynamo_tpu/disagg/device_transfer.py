"""Tier-2 device-to-device KV transfer via the JAX transfer server.

jax.experimental.transfer ("DCN cross slice transfer") moves device
arrays between separate JAX processes: the sender parks arrays under a
uuid (`TransferServer.await_pull`), the receiver connects to the
sender's advertised address and pulls them into ITS OWN devices/sharding
(`TransferConnection.pull`).  This is the closest TPU analogue of the
reference's NIXL RDMA pull (docs/design-docs/kvbm-design.md:171-230):
payload bytes never transit the request plane — only per-chunk METADATA
(the uuid) does.

Availability is probed once per process: the API needs PJRT support
(CreateBuffersForAsyncHostToDevice); where it is missing (e.g. some
plugin backends) every helper degrades to "unavailable" and callers fall
back to the host-staged tier.  Capability is advertised in the kv_pull
header (`transfer_addr`), so mixed fleets negotiate per-pull.
"""

from __future__ import annotations

import itertools
import logging
import threading
from typing import Any, Dict, Optional, Tuple

from .transfer import RequestPlanePullSource

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_server = None
_server_failed = False
_uuid_counter = itertools.count(1)


def get_transfer_server():
    """The process-wide transfer server, started lazily; None when the
    backend does not support it OR when not explicitly enabled.

    OPT-IN via DYN_KV_TRANSFER_SERVER=1: the in-process loopback probe
    below cannot prove the backend's CROSS-process bulk transport works,
    and a cross-process pull that aborts the SENDER (seen on an earlier
    set-up; not tried on today's) would kill a prefill worker — far
    worse than a host-staged copy.  Deployments on backends with
    known-good DCN transfer enable it explicitly."""
    global _server, _server_failed
    import os

    if os.environ.get("DYN_KV_TRANSFER_SERVER", "0").lower() not in (
            "1", "true", "yes", "on"):
        return None
    with _lock:
        if _server is not None or _server_failed:
            return _server
        try:
            import jax
            from jax.experimental import transfer

            client = jax.devices()[0].client
            srv = transfer.start_transfer_server(client)
            # probe a real round-trip: some backends construct the server
            # but fail on pull (UNIMPLEMENTED PJRT hooks)
            import numpy as np

            x = jax.device_put(np.zeros(8, np.float32))
            uid = next(_uuid_counter)
            srv.await_pull(uid, [x])
            conn = srv.connect(srv.address())
            out = conn.pull(uid, [jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding)])
            np.asarray(out[0])
            _server = srv
            logger.info("jax transfer server at %s", srv.address())
        except Exception as e:  # pragma: no cover - backend-dependent
            logger.info("jax transfer server unavailable (%s); "
                        "device-to-device pulls fall back to host staging",
                        e)
            _server_failed = True
        return _server


def next_uuid() -> int:
    return next(_uuid_counter)


class SenderChunkRegistry:
    """Sender-side refs for chunks parked in the transfer server.

    await_pull gives no completion signal, so the arrays must stay
    referenced until the receiver has pulled them.  The registry keeps AT
    MOST ONE outstanding chunk per request (the receiver is paced: it
    pulls chunk i before asking for i+1, so registering i+1 proves i is
    consumed) and drops everything for a request on close or TTL sweep
    (a receiver that dies mid-pull must not pin device memory forever —
    the worker sweeps from its load loop)."""

    def __init__(self):
        import time

        self._now = time.monotonic
        self._parked: Dict[str, Tuple[int, Any, float]] = {}

    def park(self, request_id: str, uuid: int, arrays) -> None:
        self._parked[request_id] = (uuid, arrays, self._now())

    def release(self, request_id: str) -> None:
        self._parked.pop(request_id, None)

    def sweep(self, max_age_s: float = 120.0) -> int:
        """Drop refs whose receiver never finished; mirrors the engine's
        parked-KV TTL."""
        cutoff = self._now() - max_age_s
        stale = [r for r, (_, _, t) in self._parked.items() if t < cutoff]
        for r in stale:
            del self._parked[r]
        return len(stale)

    def __len__(self) -> int:
        return len(self._parked)


class NegotiatedPullSource(RequestPlanePullSource):
    """Receiver pull source that negotiates tier 2 per pull.

    Opens over the request plane like the host-staged tier (the base
    class); if the sender's header advertises a transfer server AND this
    process has one too, chunk payloads switch to device-to-device pulls
    (the chunk RPC carries only a uuid); otherwise chunks arrive as host
    byte frames — so mixed fleets (e.g. a backend whose PJRT lacks
    transfer support talking to one that has it) always interoperate."""

    def __init__(self, client, params: Dict[str, Any],
                 device: Any = None, allow_transfer: bool = True):
        """device: the jax device pulled chunks land on (the receiving
        engine's first mesh device).  The wire format is canonically
        SINGLE-shard — the transfer server requires identical shard
        structure on both ends (probed empirically), and prefill TP never
        needs to match decode TP here, so each side reshards locally over
        ICI (sender: gather to one device; receiver: inject device_puts
        onto its own sharding).  A matched-topology multi-stream fast
        path is a future optimization."""
        super().__init__(client, params)
        self.device = device
        self.allow_transfer = allow_transfer and device is not None
        self._conn = None

    @property
    def device_resident(self) -> bool:
        """True once tier 2 is negotiated: chunks land as device arrays,
        so the receiver can size chunks for the device path."""
        return self._conn is not None

    async def open(self) -> Dict[str, Any]:
        header = await super().open()
        addr = header.get("transfer_addr")
        if addr and self.allow_transfer:
            srv = get_transfer_server()
            if srv is not None:
                try:
                    self._conn = srv.connect(addr)
                    logger.info("kv pull %s: device-to-device via "
                                "transfer server %s",
                                self.params["request_id"], addr)
                except Exception:
                    logger.warning("transfer server connect to %s failed; "
                                   "host-staged fallback", addr,
                                   exc_info=True)
                    self._conn = None
        return header

    async def chunk(self, b0: int, n: int):
        if self._conn is None:
            return await self._host_chunk(b0, n)
        try:
            return await self._device_chunk(b0, n)
        except Exception:
            # a failed device pull (connection torn down mid-stream, PJRT
            # quirk) degrades the REST of this pull to host frames
            logger.warning("device-to-device chunk [%d,%d) failed; "
                           "host-staged fallback", b0, b0 + n,
                           exc_info=True)
            self._conn = None
            return await self._host_chunk(b0, n)

    async def _host_chunk(self, b0: int, n: int):
        return await RequestPlanePullSource.chunk(self, b0, n)

    async def _device_chunk(self, b0: int, n: int):
        import asyncio

        import jax

        from .transfer import _np_dtype

        reply = await self._call({
            "op": "chunk", "request_id": self.params["request_id"],
            "start": int(b0), "count": int(n), "via": "transfer",
        })
        if "uuid" not in reply:
            raise RuntimeError("sender refused transfer-server chunk")
        uuid = int(reply["uuid"])
        lo = self.layout
        dt = _np_dtype(lo.dtype)
        sh = jax.sharding.SingleDeviceSharding(self.device)
        sds = [
            jax.ShapeDtypeStruct(
                (lo.num_layers, n, lo.block_size, lo.kv_heads,
                 lo.head_dim), dt, sharding=sh),
            jax.ShapeDtypeStruct(
                (lo.num_layers, n, lo.block_size, lo.kv_heads, lo.hd_v),
                dt, sharding=sh),
        ]
        if lo.scales:
            # int8 payload: the sender parked fp32 scale planes too
            sshape = (lo.num_layers, n, lo.block_size, lo.kv_heads)
            import numpy as np

            sds += [jax.ShapeDtypeStruct(sshape, np.float32, sharding=sh)
                    for _ in range(2)]
        # conn.pull blocks on the wire; keep the event loop free
        out = await asyncio.to_thread(self._conn.pull, uuid, sds)
        return tuple(out)
