"""Multi-host SPMD serving: one routing identity per N-host slice.

The reference's workers are one-process-one-GPU with NCCL underneath; a
TPU slice is different: ONE jit program spans N hosts (jax.distributed),
every process must execute the SAME sequence of jit calls, and only the
slice — not each host — is a meaningful routing target (SURVEY §7 hard
part 3).  This module maps that model onto the worker contract:

  * MultihostContext — who am I in the slice.  Detected from
    jax.process_index()/process_count() (overridable via DYN_MH_RANK /
    DYN_MH_WORLD for tests and non-jax transports).
  * Leader gating — ONLY process 0 registers the model card and serves
    the generate/clear/kv_* endpoints, so the router sees one instance
    per slice.  Followers hold the same weights/KV shards and execute
    the same programs, but have no network identity.
  * StepBroadcaster / StepFollower — the leader's scheduler publishes an
    ordered stream of step descriptors (kind + host batch arrays) on the
    event plane; followers replay them call-for-call, keeping every
    process's jit sequence identical.  Step kinds span the whole compute
    surface: prefill (single/batched/packed/ring), decode (full/multi/
    continuation), guided top-M, speculative verification (spec_verify),
    KV gather/inject, lora_write, and embed — see engine/core.py
    apply_step.  Sequence numbers make gaps loud:
    a follower that misses a step CANNOT continue (its next collective
    would deadlock or corrupt), so it raises instead of resubscribing.

What is validated where: protocol ordering/gating is tested single-host
(tests/test_multihost.py, two engine replicas standing in for two host
shards); the XLA side (jax.distributed.initialize + global arrays) needs
real multi-host hardware and is intentionally a thin, documented seam —
`initialize()` below.
"""

from __future__ import annotations

import asyncio
import logging
import os
from dataclasses import dataclass
from typing import AsyncIterator, Dict, List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """jax.distributed.initialize with env fallbacks (JAX's own
    COORDINATOR_ADDRESS etc. still apply).  Call before first jax use on
    every host of the slice."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=num_processes,
        process_id=process_id,
    )


@dataclass(frozen=True)
class MultihostContext:
    rank: int = 0
    world: int = 1

    @property
    def is_leader(self) -> bool:
        return self.rank == 0

    @classmethod
    def detect(cls) -> "MultihostContext":
        """DYN_MH_RANK/DYN_MH_WORLD override (tests, pre-init tooling);
        otherwise whatever jax.distributed reports."""
        if "DYN_MH_RANK" in os.environ:
            return cls(rank=int(os.environ["DYN_MH_RANK"]),
                       world=int(os.environ.get("DYN_MH_WORLD", "1")))
        import jax

        # initializes the backend if nothing has yet; a backend that
        # cannot start is an error here, not a silent single-host default
        return cls(rank=jax.process_index(), world=jax.process_count())


def step_subject(namespace: str, component: str, instance_id: int) -> str:
    return f"mh_step.{namespace}.{component}.{instance_id}"


def _pack(arrays: Dict[str, np.ndarray]) -> Dict[str, dict]:
    return {
        k: {"b": np.ascontiguousarray(a).tobytes(),
            "shape": list(a.shape), "dtype": a.dtype.name}
        for k, a in arrays.items()
    }


def _unpack(wire: Dict[str, dict]) -> Dict[str, np.ndarray]:
    return {
        k: np.frombuffer(d["b"], dtype=np.dtype(d["dtype"]))
        .reshape(d["shape"])
        for k, d in wire.items()
    }


def ready_subject(namespace: str, component: str, instance_id: int) -> str:
    return f"mh_ready.{namespace}.{component}.{instance_id}"


class StepBroadcaster:
    """Leader side: ordered step-descriptor stream for the slice.

    Synchronous enqueue (call from the scheduler thread via the loop, like
    KV events) + single-writer publish keeps wire order equal to execution
    order.  A publish that still fails after retries is FATAL (via
    on_fatal): dropping one frame would turn into a permanent sequence gap
    that kills every follower while the leader keeps serving — the slice
    must restart together instead."""

    def __init__(self, runtime, namespace: str, component: str,
                 instance_id: int, on_fatal=None):
        self.runtime = runtime
        self.subject = step_subject(namespace, component, instance_id)
        self._seq = 0
        self._outbox: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self.on_fatal = on_fatal

    async def start(self) -> "StepBroadcaster":
        self._task = asyncio.create_task(self._drain())
        return self

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()

    async def hello(self) -> None:
        """Barrier probe: a sentinel (seq -1) on the step subject.  A
        follower acks the barrier only after receiving one — proof positive
        its subscription is attached to THIS broadcaster's stream, with no
        assumptions about pub/sub join timing.  Safe to publish directly
        (not via the outbox): hellos happen strictly before the barrier
        passes and steps strictly after, so they never interleave."""
        await self.runtime.event_plane.publish(
            self.subject, {"seq": -1, "kind": "__hello__", "meta": {},
                           "arrays": {}})

    def publish_step(self, kind: str,
                     arrays: Optional[Dict[str, np.ndarray]] = None,
                     meta: Optional[dict] = None) -> int:
        seq = self._seq
        self._seq += 1
        self._outbox.put_nowait({
            "seq": seq, "kind": kind, "meta": meta or {},
            "arrays": _pack(arrays or {}),
        })
        return seq

    async def _drain(self) -> None:
        try:
            while True:
                msg = await self._outbox.get()
                for attempt in range(3):
                    try:
                        await self.runtime.event_plane.publish(
                            self.subject, msg)
                        break
                    except Exception:
                        logger.warning("step broadcast attempt %d failed",
                                       attempt + 1, exc_info=True)
                        await asyncio.sleep(0.05 * (attempt + 1))
                else:
                    logger.critical(
                        "step %s unpublishable; slice is broken — leader "
                        "must restart", msg.get("seq"))
                    if self.on_fatal is not None:
                        self.on_fatal()
                    return
        except asyncio.CancelledError:
            pass


class StepGapError(RuntimeError):
    """A follower missed a step: its jit sequence has diverged from the
    slice and it must crash-restart (collectives would hang otherwise)."""


class StepFollower:
    """Follower side: yields (kind, arrays, meta) strictly in order."""

    def __init__(self, runtime, namespace: str, component: str,
                 instance_id: int):
        self.runtime = runtime
        self.subject = step_subject(namespace, component, instance_id)
        self._cancel = asyncio.Event()
        self._next = 0
        #: pulsed on every hello sentinel received from the leader.  A
        #: hello in hand proves this follower's subscription is attached to
        #: the leader's stream, so acking the barrier after one can never
        #: leave step 0 published into the void (permanent StepGapError).
        self.hello = asyncio.Event()

    async def steps(self) -> AsyncIterator[Tuple[str, Dict[str, np.ndarray],
                                                 dict]]:
        async for _subj, msg in self.runtime.event_plane.subscribe(
            self.subject, cancel=self._cancel
        ):
            seq = msg.get("seq")
            if seq == -1:  # barrier probe, not a step
                self.hello.set()
                continue
            if seq != self._next:
                raise StepGapError(
                    f"expected step {self._next}, got {seq}: this follower "
                    "has diverged from the slice and must restart"
                )
            self._next += 1
            yield msg["kind"], _unpack(msg["arrays"]), msg.get("meta", {})

    def stop(self) -> None:
        self._cancel.set()
