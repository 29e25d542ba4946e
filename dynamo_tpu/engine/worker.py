"""JAX engine worker: serves the engine under the standard worker contract.

Same contract as the mocker worker (ref model:
components/src/dynamo/vllm/worker_factory.py): generate / clear_kv_blocks /
kv_events_replay endpoints, MDC publication, KV events, periodic load
metrics.  The router cannot tell a JAX engine from a simulated one — which is
the point of the contract.
"""

from __future__ import annotations

import asyncio
import logging
import os
from typing import Optional

import jax
import numpy as np

from .. import obs
from ..protocols import LLMEngineOutput, ModelDeploymentCard, PreprocessedRequest
from ..protocols.model_card import deregister_model, register_model
from ..router.events import KvEventPublisher
from ..runtime import DistributedRuntime
from ..runtime.discovery import new_instance_id
from .config import EngineConfig
from .core import JaxEngine

logger = logging.getLogger(__name__)

LOAD_SUBJECT_PREFIX = "load_metrics"


class JaxEngineWorker:
    def __init__(self, runtime: DistributedRuntime, config: EngineConfig,
                 namespace: str = "dynamo", component: str = "backend",
                 migration_limit: int = 3,
                 tokenizer_cfg: Optional[dict] = None,
                 params=None, mh=None, slice_id: int = 0):
        """mh: MultihostContext for N-host SPMD slices (default: detect).
        Only the slice leader (rank 0) registers the model and serves
        endpoints — ONE routing identity per slice; followers replay the
        leader's broadcast step stream (parallel/multihost.py).  slice_id
        disambiguates multiple slices of one component (xPyD)."""
        from ..parallel.multihost import MultihostContext

        self.runtime = runtime
        self.config = config
        self.namespace = namespace
        self.component = component
        self.migration_limit = migration_limit
        self.mh = mh or MultihostContext.detect()
        self.slice_id = slice_id
        self._broadcaster = None
        self._follower = None
        self._follower_task = None
        self._chat_template: Optional[str] = None
        if tokenizer_cfg is None:
            if config.model_path:
                import os

                from ..models.loader import load_chat_template

                eos_ids = config.resolve_eos_ids()
                # ship the tokenizer as an inline blob so frontends on
                # other hosts can build it (a worker-local path would not
                # resolve there)
                tok_json = os.path.join(config.model_path, "tokenizer.json")
                with open(tok_json) as f:
                    tokenizer_cfg = {
                        "type": "hf", "json": f.read(),
                        "eos_id": eos_ids[0] if eos_ids else None,
                    }
                self._chat_template = load_chat_template(config.model_path)
            else:
                tokenizer_cfg = {
                    "type": "mock",
                    "vocab_size": config.resolve_model().vocab_size,
                }
        self.tokenizer_cfg = tokenizer_cfg
        self._params = params
        self.engine: Optional[JaxEngine] = None
        self.publisher: Optional[KvEventPublisher] = None
        self.served = None
        self._aux_served = []
        self._load_task: Optional[asyncio.Task] = None
        # local FPM aggregation window: the load loop feeds it, and the
        # /debug/state dump reads compile-family stats and ITL p95 off
        # it between ticks (fleet straggler detection input)
        from ..planner.metrics import FpmWindow

        self._fpm_window = FpmWindow()
        self._debug_source_name: Optional[str] = None
        self._resident_bytes = None  # (params, kv) bytes per device id

    @property
    def card(self) -> ModelDeploymentCard:
        m = self.config.resolve_model()
        return ModelDeploymentCard(
            name=self.config.served_name,
            namespace=self.namespace,
            component=self.component,
            endpoint="generate",
            tokenizer=self.tokenizer_cfg,
            chat_template=self._chat_template,
            context_length=min(m.max_context, self.config.max_context),
            kv_cache_block_size=self.config.block_size,
            migration_limit=self.migration_limit,
            runtime_config={
                "total_kv_blocks": self.config.num_blocks,
                "max_num_seqs": self.config.max_num_seqs,
                "model_preset": self.config.model,
                "tp": self.config.tp,
                "dp": self.config.dp,
                "role": self.config.role,
                # EFFECTIVE KV storage dtype (quant/kv.py): the engine
                # may fall back to bf16 for families without a quantized
                # path (MLA), and routers/planners must see what is
                # actually served — e.g. the planner warns when an ITL
                # profile measured at one dtype steers a worker at the
                # other (planner/perf_model.py)
                "kv_cache_dtype": (self.engine.kv_dtype
                                   if self.engine is not None
                                   else self.config.kv_cache_dtype),
                # chunked-prefill scheduling knobs (engine/prefill.py):
                # routers/planners can see each worker's chunk budget
                "prefill_chunk_tokens": self.config.chunk_budget,
                "prefill_packed": self.config.prefill_packed,
                # EFFECTIVE attention impls (engine-level overrides
                # applied to the model config, and the decode impl's
                # "auto" RESOLVED for this worker's platform and cache:
                # ops/paged_attention.resolve_decode_impl): a fleet
                # debugger sees which workers run the Pallas kernels vs
                # the XLA reference paths without reading worker flags
                "attn_impl": (self.engine.model_cfg.attn_impl
                              if self.engine is not None
                              else (self.config.attn_impl or "auto")),
                "packed_attn_impl": (
                    getattr(self.engine.model_cfg, "packed_attn_impl",
                            "auto")
                    if self.engine is not None
                    else (self.config.packed_attn_impl or "auto")),
                # EFFECTIVE fused-sampling epilogue mode (engine-level
                # resolution: MLA families fall back to "off"), same
                # fleet-visibility contract as the attn impls
                "sampling_epilogue": (self.engine.sampling_epilogue
                                      if self.engine is not None
                                      else self.config.sampling_epilogue),
                # overlapped scheduler (engine/core.py): whether this
                # worker pipelines host scheduling behind device
                # execution — sync-mode workers show distinctly worse
                # served/raw ratios, and a fleet debugger should see the
                # mode without reading worker flags
                "overlap_scheduling": self.config.overlap_scheduling,
                # speculative decoding (spec/): planners/routers see the
                # proposer and max draft length; live acceptance rides
                # the FPM stream (spec_verify records).  Gated on the
                # ENGINE's state, not the raw config: an MLA family
                # silently falls back to plain decode and must not
                # advertise a capability it doesn't serve
                **({"speculative": {"proposer": self.config.spec_decode,
                                    "k": self.config.spec_k}}
                   if self.engine is not None and self.engine.spec_enabled
                   else {}),
                **({"reasoning_parser": self.config.reasoning_parser}
                   if self.config.reasoning_parser else {}),
                # timeline tracing capability (obs/): planners/routers
                # can see which workers will emit spans for a trace_id
                **({"tracing": True} if obs.enabled() else {}),
            },
        )

    async def start(self) -> "JaxEngineWorker":
        rt = self.runtime
        if not self.mh.is_leader:
            return await self._start_follower()
        instance_id = new_instance_id()
        self.publisher = KvEventPublisher(
            rt, self.namespace, self.component, worker_id=instance_id
        )
        step_sink = None
        if self.mh.world > 1:
            from ..parallel.multihost import StepBroadcaster, ready_subject

            # all KV-mutating paths ride the step stream (prefill/decode,
            # KVBM gather/inject, disagg inject) — followers replay the
            # full jit sequence, so tiers and disagg roles compose with
            # multi-host (the north-star topology)
            self._broadcaster = await StepBroadcaster(
                rt, self.namespace, self.component, self.slice_id,
                on_fatal=rt.root_token.kill,
            ).start()
            loop = asyncio.get_running_loop()
            bc = self._broadcaster

            def step_sink(kind, arrays):
                # scheduler thread -> loop thread; FIFO preserves exec order
                loop.call_soon_threadsafe(bc.publish_step, kind, arrays)

            # startup barrier: serve only after every follower has ACKED A
            # HELLO SENTINEL received on the step subject itself — proof
            # its subscription is attached to this leader's stream (a step
            # published to nobody is a permanent gap).  Hellos repeat while
            # collecting, so followers re-ack for a restarted leader too.
            ready_ranks: set = {0}
            barrier = asyncio.Event()

            async def collect_ready():
                cancel = asyncio.Event()
                async for _s, msg in rt.event_plane.subscribe(
                    ready_subject(self.namespace, self.component,
                                  self.slice_id),
                    cancel=cancel,
                ):
                    ready_ranks.add(int(msg.get("rank", -1)))
                    if len(ready_ranks) >= self.mh.world:
                        barrier.set()
                        cancel.set()
                        return

            async def hello_loop():
                # hellos repeat anyway, so a transiently failing publish
                # (e.g. a FileDiscovery write under zmq) just costs a beat —
                # but it must not silently kill the loop, or the barrier
                # times out blaming the followers
                while not barrier.is_set():
                    try:
                        await bc.hello()
                    except Exception:
                        logger.warning("barrier hello publish failed",
                                       exc_info=True)
                    await asyncio.sleep(0.2)

            collector = asyncio.create_task(collect_ready())
            heller = asyncio.create_task(hello_loop())
            try:
                await asyncio.wait_for(
                    barrier.wait(),
                    float(os.environ.get("DYN_MH_BARRIER_TIMEOUT_S", "60")),
                )
            except asyncio.TimeoutError:
                collector.cancel()
                raise RuntimeError(
                    f"multi-host barrier timeout: followers ready "
                    f"{sorted(ready_ranks)} of world {self.mh.world}"
                )
            finally:
                heller.cancel()

        def kv_event_sink(stored, removed, tier="g1"):
            # synchronous enqueue on the loop thread: event ids are assigned
            # in mutation order and a single drain task publishes FIFO.
            # `tier` is the tier of the mutation that made the block enter
            # (stored) or fully leave (removed) the worker — events are
            # already netted across tiers by the engine's consolidator.
            self.publisher.enqueue_batch(stored=stored, removed=removed,
                                         tier=tier)

        self.engine = JaxEngine(
            self.config, params=self._params,
            kv_event_sink=kv_event_sink,
            # the leader pulls over the request plane; the injected blocks
            # then ride the step stream to the slice's followers
            kv_pull_fn=self._kv_pull,
            step_sink=step_sink,
        )
        self.engine.transfer_identity = {
            "instance_id": instance_id,
            "namespace": self.namespace,
            "component": self.component,
        }
        # guided decoding validates candidate text with the MODEL'S
        # tokenizer (engine falls back to the byte mock only for mock
        # cards — where the frontend uses the same mock)
        from ..frontend.tokenizer import tokenizer_from_mdc

        try:
            self.engine.guided_codec = tokenizer_from_mdc(
                self.tokenizer_cfg)
        except Exception:
            logger.warning("guided codec unavailable; guided decoding "
                           "will use the byte fallback", exc_info=True)
        self._pull_clients = {}
        from ..disagg.device_transfer import SenderChunkRegistry

        self._chunk_refs = SenderChunkRegistry()
        self._broker_id: Optional[int] = None

        async def generate_handler(payload, ctx):
            request = PreprocessedRequest.from_dict(payload)
            ntok = 0
            # log<->trace correlation: every log record this worker
            # emits while serving the stream carries the propagated
            # trace_id (runtime/logging.py TraceIdFilter)
            bind_tok = obs.bind_trace_id(
                obs.trace_id_from_annotations(request.annotations))
            # worker-side request span: stitches to the frontend's
            # `request` span and request_end record via the propagated
            # trace_id (obs cross-process stitching)
            t_obs = obs.begin()
            try:
                async for out in self.engine.generate(request,
                                                      token=ctx.token):
                    ntok += len(out.token_ids)
                    yield out.to_dict()
            finally:
                obs.end("worker_request", t_obs,
                        trace_id=obs.trace_id_from_annotations(
                            request.annotations) if t_obs else None,
                        request_id=request.request_id, tokens=ntok)
                # trace join: the frontend's traceparent annotation makes
                # this worker's structured log line greppable by trace_id
                tp = next((a.split(":", 1)[1] for a in request.annotations
                           if a.startswith("traceparent:")), None)
                if tp is not None:
                    logger.info("request served", extra={
                        "request_id": request.request_id,
                        "traceparent": tp, "output_tokens": ntok})
                obs.unbind_trace_id(bind_tok)

        async def clear_handler(payload, ctx):
            n = await self.engine.clear_kv_blocks()
            yield {"cleared_blocks": n}

        async def kvbm_pull_handler(payload, ctx):
            """Cross-worker G2 pull (kvbm/remote.py): stream this worker's
            host-tier copies of the requested block run; a None hash marks
            where the run broke (peer eviction)."""
            from ..kvbm.remote import encode_block

            hashes = list(payload.get("hashes", []))[:128]
            blocks = await self.engine.read_host_blocks(hashes)
            for h, *arrays in blocks:
                yield encode_block(h, *arrays)
            if len(blocks) < len(hashes):
                yield {"h": None}

        async def kv_pull_handler(payload, ctx):
            """Receiver-paced pull ops (disagg/transfer.py wire protocol):
            open -> header, chunk -> one gathered slab (host bytes, or a
            transfer-server uuid when the receiver asks via=transfer),
            close -> release.  Each chunk is ONE scheduler op on this
            engine, so prefill/decode for other requests interleave with
            the extraction instead of stalling behind a whole-prompt
            gather."""
            from ..disagg.transfer import encode_chunk_frame, make_header

            op = payload.get("op")
            rid = payload["request_id"]
            if op == "open":
                n_blocks, prompt_len = await self.engine.parked_info(rid)
                layout = self.engine.kv_wire_layout(n_blocks)
                yield make_header(prompt_len, layout,
                                  transfer_addr=self._transfer_addr())
            elif op == "chunk":
                b0 = int(payload["start"])
                n = int(payload["count"])
                if payload.get("via") == "transfer" \
                        and self._transfer_addr() is not None:
                    from ..disagg import device_transfer

                    arrs = await self.engine.extract_parked_chunk(
                        rid, b0, n, to_host=False)
                    # canonical single-shard wire form (the server needs
                    # identical shard structure on both ends); the
                    # tp-gather onto one device rides ICI.  int8 caches
                    # park 4 arrays (data + scale planes).
                    dev = self.engine.mesh.devices.flat[0]
                    arrs = tuple(jax.device_put(a, dev) for a in arrs)
                    uid = device_transfer.next_uuid()
                    device_transfer.get_transfer_server().await_pull(
                        uid, list(arrs))
                    # ref held until the next chunk/close (receiver pacing
                    # proves consumption) so the arrays outlive the pull
                    self._chunk_refs.park(rid, uid, arrs)
                    yield {"uuid": uid}
                else:
                    arrs = await self.engine.extract_parked_chunk(
                        rid, b0, n)
                    yield encode_chunk_frame(b0, *arrs)
            elif op == "close":
                self._chunk_refs.release(rid)
                await self.engine.release_parked(rid)
                yield {}
            else:
                raise ValueError(f"unknown kv_pull op {op!r}")

        comp = rt.namespace(self.namespace).component(self.component)
        from ..protocols.llm import CANARY_GENERATE_PAYLOAD

        if self.config.warmup and self.mh.world == 1:
            # compile all decode variants BEFORE any endpoint is served:
            # serving arms the health-check canary (30s idle, 10s to
            # answer), and at serving widths the warm-up compiles outlast
            # both — the canary then times out behind them, the endpoint
            # turns NOT READY and the discovery lease is withdrawn before
            # the worker ever said `ready` (first chip run, llama-3b).
            # The canary's own request is warmed too, so the first idle
            # probe does not sit behind a prefill compile either.
            # Multi-host slices skip it: warmup dispatches are collective
            # programs the followers would never replay (they only run
            # what arrives on the step stream), so a leader-side warmup
            # would hang the slice's collective schedule.
            await asyncio.to_thread(self.engine.warmup_decode)
            with self.engine.compile_watch.warming():
                async for _ in self.engine.generate(
                        PreprocessedRequest.from_dict(
                            {**CANARY_GENERATE_PAYLOAD,
                             "request_id": "warmup-canary"})):
                    pass
        self.served = await comp.endpoint("generate").serve_endpoint(
            generate_handler,
            metadata={"model": self.config.served_name},
            instance_id=instance_id,
            health_check_payload=CANARY_GENERATE_PAYLOAD,
        )
        self._aux_served = [
            await comp.endpoint("clear_kv_blocks").serve_endpoint(
                clear_handler, instance_id=instance_id),
            await comp.endpoint("kv_events_replay").serve_endpoint(
                self.publisher.replay_handler, instance_id=instance_id),
            await comp.endpoint("kv_pull").serve_endpoint(
                kv_pull_handler, instance_id=instance_id),
        ]
        if self.engine.kvbm is not None and self.config.kvbm_remote:
            from ..kvbm.remote import RemoteBlockIndex, RemoteKvbmPuller

            self._aux_served.append(
                await comp.endpoint("kvbm_pull").serve_endpoint(
                    kvbm_pull_handler, instance_id=instance_id))
            self._kvbm_index = await RemoteBlockIndex(
                rt, self.namespace, self.component, instance_id).start()
            self._kvbm_pull_client = await (
                comp.endpoint("kvbm_pull").client().start())
            puller = RemoteKvbmPuller(
                self._kvbm_index, self._kvbm_pull_client,
                max_blocks=self.config.kvbm_remote_max_blocks,
            )
            # corrupt pulled frames attribute like every other tier's
            # corruptions (ledger kind `corrupt`, tier="remote") and the
            # index marks the serving peer suspect
            puller.on_corruption = self.engine._note_kv_corruption
            self.engine.remote_kvbm_fetch = puller.fetch_run
        if self.engine.supports_embedding:
            # embed rides the step broadcast like every other collective
            # program, so multi-host slices serve it too
            async def embed_handler(payload, ctx):
                vec = await self.engine.embed(payload["token_ids"])
                yield {"embedding": vec.tolist(), "dim": int(vec.shape[0])}

            self._aux_served.append(
                await comp.endpoint("embed").serve_endpoint(
                    embed_handler, instance_id=instance_id))
        # tier-1 d2d: co-resident engines pull device-to-device through
        # the process broker (single-host slices only — followers need the
        # payload on the step stream as host bytes).  Registered only once
        # every endpoint is up, so a failed start never leaks a
        # half-initialized engine into the process-global registry.
        from ..disagg import broker

        broker.register_engine(instance_id, self.engine)
        self._broker_id = instance_id
        await register_model(rt, self.card, instance_id)
        self._load_task = asyncio.create_task(self._load_loop())
        # SLA-aware admission input (engine/core.py set_slo_burn): feed
        # the frontends' published SLO burn rate (obs/slo.py
        # SloPlane.publish -> slo_metrics.{ns}) into the engine, where a
        # sustained burn makes prefill chunks yield budget to decode.
        # Stale signals decay engine-side (slo_burn_stale_s), so a
        # frontend restart or a disabled SLO plane is harmless.
        self._slo_cancel = asyncio.Event()
        self._slo_task = asyncio.create_task(self._slo_loop())
        # fleet introspection: this worker's live state on /debug/state
        self._debug_source_name = f"worker:{instance_id}"
        rt.register_debug_source(self._debug_source_name, self.debug_state)
        # KV-accounting plane: the block-lifecycle ledger's attribution
        # + an on-demand audit on /debug/kv (obs/kv_ledger.py)
        self._kv_source_name = f"kv:{instance_id}"
        rt.register_kv_source(self._kv_source_name, self.kv_debug)
        logger.info("jax engine worker %d serving %s (tp=%d)",
                    instance_id, self.config.served_name, self.config.tp)
        return self

    async def kv_debug(self) -> dict:
        """/debug/kv source: the ledger dump with a FRESH reconciliation
        sweep (audit on demand — the third cadence next to
        request-finish and idle-tick)."""
        eng = self.engine
        base = {
            "kind": "engine",
            "instance_id": (self.served.instance_id
                            if self.served is not None else None),
            "namespace": self.namespace,
            "component": self.component,
        }
        if eng is None or eng.kv_ledger is None:
            return {**base, "schema": "dynamo.kv_ledger.v1",
                    "enabled": False}
        audit = await eng.audit_kv()
        out = {**base, **eng.kv_ledger.dump(), "audit": audit,
               "kv": eng.kv_occupancy()}
        if eng.kvbm is not None:
            # degraded-mode picture: breaker state per tier + the
            # manager's I/O/quarantine counters (obs/fleet.py folds
            # tier_state across workers into the fleet summary)
            out["tier_state"] = eng.kvbm.tier_states()
            out["kvbm_stats"] = dict(eng.kvbm.stats)
            out["integrity"] = {
                f"{tier}:{action}": n
                for (tier, action), n in
                eng.kv_integrity_counters().items()}
        if (eng.kvbm is not None and eng.kvbm.g4 is not None
                and eng.kvbm.breaker.state("g4") != "open"):
            # G4 residency picture: blob count + this worker's lineage
            # verdicts over a bounded sample (the sweep applies the same
            # policy; here it's read-only for the fleet aggregator)
            from ..kvbm.residency import LineageResidency

            try:
                keys = []
                for h in eng.kvbm.g4.keys():
                    keys.append(h)
                    if len(keys) >= 2048:
                        break
                res = LineageResidency(eng.kv_ledger, pool=eng.kvbm.g4)
                out["g4"] = {"blobs_sampled": len(keys),
                             "residency": res.verdicts(keys)}
            except OSError:
                pass  # shared dir raced a sweep; next scrape reads it
        return out

    def debug_state(self) -> dict:
        """Live scheduler/KV/drain snapshot for /debug/state and the
        fleet aggregator (obs/fleet.py).  Read-only over structures the
        scheduler thread mutates — copies first, tolerates a torn read
        (a debug dump must never take the step lock)."""
        eng = self.engine
        if eng is None:
            return {"kind": "engine", "role": "follower",
                    "rank": self.mh.rank}
        slots = []
        for s in list(eng._slots):
            if s is None:
                continue
            slots.append({
                "request_id": s.request.request_id,
                "prompt_len": s.prompt_len,
                "generated": s.generated,
                "prefilling": s.prefilling,
                "pulling": s.pulling,
                "inflight": s.inflight,
                "cached_tokens": s.cached_tokens,
            })
        waiting = [s.request.request_id for s in list(eng.waiting)]
        fw = self._fpm_window
        return {
            "kind": "engine",
            "instance_id": (self.served.instance_id
                            if self.served is not None else None),
            "namespace": self.namespace,
            "component": self.component,
            "model": self.config.served_name,
            "role": self.config.role,
            "draining": eng.draining,
            "active_seqs": eng.num_active_seqs,
            "waiting": waiting,
            "slots": slots,
            "tokens_in_flight": sum(
                s["prompt_len"] + s["generated"] for s in slots),
            "kv": eng.kv_occupancy(),
            "kv_usage": eng.kv_usage(),
            "kv_cache_dtype": eng.kv_dtype,
            "itl_ema_s": eng.itl_ema_s,
            "itl_p95_s": fw.decode_itl_p95_s(),
            "compile": fw.compile_stats(),
            # cumulative since start (the FPM window above forgets):
            # per-family compile counts/seconds and the mid-serving total
            "compile_watch": {
                "counts": dict(eng.compile_watch.counts),
                "seconds": {k: round(v, 3) for k, v in
                            eng.compile_watch.seconds.items()},
                "serving_compiles": eng.compile_watch.serving_compiles,
            },
            "device": self._device_state(),
            "engine_metrics": dict(eng.metrics),
            "config": dict(self.card.runtime_config),
        }

    def _device_state(self) -> dict:
        """The mesh as the process that holds it sees it: identity,
        per-device allocator stats (None where the backend keeps none,
        e.g. CPU) and the bytes of parameter / KV shards resident on
        each device — the evidence that a tp mesh really spread the
        model instead of leaving it on the first chip."""
        from ..runtime.device import device_identity

        eng = self.engine
        devs = list(eng.mesh.devices.flat)
        if self._resident_bytes is None:
            # placement is fixed at init: walk the trees once, not on
            # every /debug/state scrape
            def resident(tree) -> dict:
                out = {d.id: 0 for d in devs}
                for leaf in jax.tree_util.tree_leaves(tree):
                    for sh in leaf.addressable_shards:
                        out[sh.device.id] += sh.data.nbytes
                return out

            self._resident_bytes = (resident(eng.params), resident(eng.kv))
        pbytes, kbytes = self._resident_bytes
        return {
            **device_identity(),
            "mesh": {k: int(v) for k, v in eng.mesh.shape.items()},
            "per_device": [
                {"id": d.id, "param_bytes": pbytes[d.id],
                 "kv_bytes": kbytes[d.id],
                 "memory_stats": d.memory_stats()}
                for d in devs
            ],
        }

    async def _start_follower(self) -> "JaxEngineWorker":
        """Follower process of an N-host slice: hold the same engine state
        (local weight/KV shards), replay the leader's step stream, expose
        NO network identity.  A step gap is fatal by design — the process
        must restart to rejoin the slice's collective schedule, so replay
        failure kills this runtime's root token (the process exits)."""
        from ..parallel.multihost import StepFollower, ready_subject

        # Followers hold no KVBM tiers: their self.kv evolves purely from
        # the replayed stream (onboard/pull payloads arrive as inject
        # steps), and pools would fight over the same disk dir on shared
        # hosts.  dataclasses.replace keeps the compute config identical.
        from dataclasses import replace as _dc_replace

        fcfg = _dc_replace(self.config, host_cache_blocks=0,
                           disk_cache_dir=None, disk_cache_blocks=0)
        self.engine = JaxEngine(fcfg, params=self._params)
        self._follower = StepFollower(
            self.runtime, self.namespace, self.component, self.slice_id
        )

        async def replay():
            async for kind, arrays, _meta in self._follower.steps():
                self.engine.apply_step(kind, arrays)

        self._follower_task = asyncio.create_task(replay())

        def on_done(task: asyncio.Task) -> None:
            if task.cancelled():
                return
            exc = task.exception()
            if exc is not None:
                logger.critical(
                    "follower rank %d replay died (%s); restarting is the "
                    "only way to rejoin the slice", self.mh.rank, exc,
                )
                self.runtime.root_token.kill()

        self._follower_task.add_done_callback(on_done)

        async def announce():
            # barrier ack: one ack per hello sentinel.  A hello in hand
            # proves our step subscription is attached to the leader's
            # stream, so the leader can never pass the barrier and publish
            # step 0 into the void.  Hellos stop once the barrier passes
            # (no steady-state event noise) and resume from a restarted
            # leader — whose step 0 then crash-restarts us via StepGapError,
            # which is how a slice rejoins.
            subject = ready_subject(self.namespace, self.component,
                                    self.slice_id)
            try:
                while True:
                    await self._follower.hello.wait()
                    self._follower.hello.clear()
                    try:
                        await self.runtime.event_plane.publish(
                            subject, {"rank": self.mh.rank})
                    except Exception:
                        # hellos repeat; a dropped ack self-heals next beat
                        logger.warning("barrier ack publish failed",
                                       exc_info=True)
            except asyncio.CancelledError:
                pass

        self._announce_task = asyncio.create_task(announce())
        logger.info("follower rank %d/%d replaying %s/%s slice %d",
                    self.mh.rank, self.mh.world, self.namespace,
                    self.component, self.slice_id)
        return self

    def _transfer_addr(self) -> Optional[str]:
        """Advertise the tier-2 transfer server: single-host slices only
        (a multi-host slice's gathered chunk is distributed across
        processes; one process cannot serve it) and only when the backend
        supports it."""
        if self.mh.world > 1:
            return None
        from ..disagg.device_transfer import get_transfer_server

        srv = get_transfer_server()
        return srv.address() if srv is not None else None

    async def _kv_pull(self, params: dict):
        """Decode-side pull source, best tier first (disagg/transfer.py):

        1. same process  -> broker source: chunks stay device-resident
           (device_put across meshes = the ICI move)
        2. cross process -> negotiated request-plane source: payload via
           the jax transfer server when both ends have one (DCN
           device-to-device), else host-staged byte frames
        3. host-staged frames — the always-correct fallback.

        Multi-host slices always take host-staged frames: followers
        replay inject steps with the payload riding the step stream.
        The sender's header layout is validated by the engine against its
        own geometry — tp/dp may differ freely (inject reshards via
        GSPMD)."""
        single_host = self.mh.world == 1
        if single_host:
            from ..disagg import broker

            src_engine = broker.lookup_engine(params["instance_id"])
            if src_engine is not None and src_engine is not self.engine:
                return broker.LocalEnginePullSource(
                    src_engine, params["request_id"])
        ns = params.get("namespace", self.namespace)
        comp = params.get("component", self.component)
        key = (ns, comp)
        client = self._pull_clients.get(key)
        if client is None:
            ep = (self.runtime.namespace(ns).component(comp)
                  .endpoint("kv_pull"))
            client = await ep.client().start()
            await client.wait_for_instances()
            self._pull_clients[key] = client
        from ..disagg.device_transfer import NegotiatedPullSource

        return NegotiatedPullSource(
            client, params,
            device=self.engine.mesh.devices.flat[0],
            allow_transfer=single_host,
        )

    async def _slo_loop(self) -> None:
        """Fold every frontend SLO summary into the engine's burn signal
        (worst window wins — the same reduction the planner's
        SloObserver applies)."""
        from ..obs.slo import SLO_SUBJECT_PREFIX

        subject = f"{SLO_SUBJECT_PREFIX}.{self.namespace}"
        try:
            async for subj, payload in self.runtime.event_plane.subscribe(
                subject, cancel=self._slo_cancel
            ):
                if subj != subject or self.engine is None:
                    continue
                try:
                    burns = payload.get("burn")
                    self.engine.set_slo_burn(
                        max((float(v) for v in burns.values()),
                            default=0.0)
                        if isinstance(burns, dict) else 0.0)
                except Exception:
                    # one malformed event (non-dict payload included)
                    # must not kill the feed task — a dead subscription
                    # silently disables SLA-aware admission for the
                    # worker's whole lifetime
                    logger.warning("malformed slo payload: %r",
                                   payload, exc_info=True)
        except asyncio.CancelledError:
            pass

    async def _load_loop(self) -> None:
        subject = f"{LOAD_SUBJECT_PREFIX}.{self.namespace}.{self.component}"
        fpm_subject = f"fpm.{self.namespace}.{self.component}"
        # local /metrics surface (system-status server): queue depth,
        # active sequences, KV pressure per worker
        m = self.runtime.metrics.scoped(component=self.component)
        tr = obs.tracer()
        if tr is not None:
            # per-span-kind duration histograms on this worker's
            # /metrics, next to the engine gauges
            tr.bind_metrics(m)
        # local FPM aggregation: the same derivations the planner's
        # FpmObserver runs fleet-wide, fed from this worker's own ring
        # BEFORE it ships — so a bare `/metrics` scrape sees the
        # headline engine numbers without a planner in the deployment
        # (and /debug/state reads compile stats + ITL p95 off the same
        # window)
        fw = self._fpm_window
        from ..router.tiered_index import compute_tier_costs

        ticks = 0
        tier_costs = None
        while True:
            await asyncio.sleep(0.5)
            ticks += 1
            if self.engine is None or self.served is None:
                continue
            # forward-pass metrics stream (ref fpm_publisher.rs): drain
            # the engine's per-program ring onto the event plane — the
            # planner's online perf regression input
            steps = []
            while self.engine.fpm and len(steps) < 512:
                steps.append(self.engine.fpm.popleft())
            for rec in steps:
                fw.add(self.served.instance_id, rec)
            # compile watchdog records -> per-family compile histogram,
            # then the shared gauge surface (planner/metrics.py
            # export_engine_gauges): headline FPM aggregates, KV
            # occupancy per tier — ONE definition for both workers, so
            # mocker /metrics parity can't drift
            from ..obs.compile_watch import observe_compile_records
            from ..planner.metrics import export_engine_gauges

            observe_compile_records(m, steps)
            export_engine_gauges(
                m, fw, occupancy=self.engine.kv_occupancy(),
                kv_ledger=self.engine.kv_ledger)
            if steps:
                try:
                    await self.runtime.event_plane.publish(fpm_subject, {
                        "worker_id": self.served.instance_id,
                        "steps": steps,
                    })
                except Exception:
                    logger.warning("fpm publish failed", exc_info=True)
            # tier-2 sender refs whose receiver died mid-pull (mirrors the
            # engine's parked-KV TTL)
            self._chunk_refs.sweep(self.engine.parked_ttl_s)
            # per-tier onboard costs for the router's tiered selector:
            # this worker's prefill token rate against the cache's
            # per-block payload bytes.  Recomputed each tick — the rate
            # converges as the window fills; the selector falls back to
            # defaults until the first publish.
            tok_rate = fw.prefill_tokens_per_s()
            if tok_rate > 0.0:
                tier_costs = compute_tier_costs(
                    tok_rate, self.engine.kv_block_bytes(),
                    self.config.block_size)
            # degraded-mode plane: fold circuit-breaker states into the
            # advertised costs (a non-closed tier is priced AT recompute
            # so the selector stops steering traffic toward its blocks)
            # and export the breaker + integrity-failure gauges
            if self.engine.kvbm is not None:
                from ..kvbm import breaker as kvbm_breaker
                from ..router.tiered_index import degraded_tier_costs

                states = self.engine.kvbm.tier_states()
                tier_costs = degraded_tier_costs(tier_costs, states)
                for tier, st in states.items():
                    m.set("dynamo_kvbm_tier_state",
                          float(kvbm_breaker.NUMERIC.get(st, 0)),
                          "KV tier circuit-breaker state "
                          "(0=closed, 1=half_open, 2=open)", tier=tier)
            for (tier, action), n in \
                    self.engine.kv_integrity_counters().items():
                m.set("dynamo_kv_integrity_failures_total", float(n),
                      "checksum quarantines and deadline/breaker I/O "
                      "failures across the KV cache fabric",
                      tier=tier, action=action)
            # lineage-driven G4 GC on a slow cadence (~30s): the shared
            # store is swept by every mounted worker; hot lineages get
            # their TTL renewed, dead ones reap early
            if ticks % 60 == 0:
                try:
                    await self.engine.sweep_kvbm_g4()
                except Exception:
                    logger.warning("g4 sweep failed", exc_info=True)
            await self.runtime.event_plane.publish(subject, {
                "worker_id": self.served.instance_id,
                "active_seqs": self.engine.num_active_seqs,
                "kv_usage": self.engine.kv_usage(),
                "kv_total_blocks": self.config.num_blocks,
                **({"kv_tier_costs": tier_costs} if tier_costs else {}),
                # effective KV dtype: the planner checks live workers
                # against the perf profile's dtype tag
                "kv_cache_dtype": self.engine.kv_dtype,
                "engine_metrics": dict(self.engine.metrics),
                # stable SLA-planner contract (planner/metrics.py
                # differentiates these; engine_metrics above is an
                # unversioned debug dump that happens to overlap)
                "requests_total": self.engine.metrics["requests"],
                "prompt_tokens_total": self.engine.metrics["prompt_tokens"],
                "itl_ema_s": self.engine.itl_ema_s,
            })
            m.set("dynamo_engine_active_seqs", self.engine.num_active_seqs)
            m.set("dynamo_engine_waiting_seqs", len(self.engine.waiting))
            m.set("dynamo_engine_kv_usage", self.engine.kv_usage())
            m.set("dynamo_engine_itl_ema_seconds", self.engine.itl_ema_s)

    async def drain(self, deadline_s: float = 5.0) -> None:
        """Graceful drain (SIGTERM path): withdraw this worker's routing
        identity from discovery, reject new work with the migratable
        "worker draining" marker, let in-flight requests finish until the
        deadline, then drain_abort() the rest so the frontend's
        token-replay migration moves them to surviving workers with no
        client-visible failure.  Only this worker's keys are deleted —
        co-resident workers on the same runtime keep serving.

        Followers of a multi-host slice have no routing identity and
        nothing to drain (the leader's drain stops the step stream)."""
        import time

        from .. import chaos

        if not self.mh.is_leader or self.engine is None:
            return
        # chaos: a worker that ignores drain (wedge) — the planner
        # connector's bounded wait escalates to stop, and migration
        # completes the in-flight streams on survivors
        await chaos.ahit("worker.drain", key=str(
            self.served.instance_id if self.served is not None else ""))
        self.engine.draining = True
        if self.served is not None:
            logger.warning("draining jax engine worker %d (deadline %.1fs)",
                           self.served.instance_id, deadline_s)
            await deregister_model(self.runtime, self.card,
                                   self.served.instance_id)
            await self.runtime.discovery.delete(self.served.instance.key())
        t0 = time.monotonic()
        while (self.engine.num_active_seqs
               and time.monotonic() - t0 < deadline_s):
            await asyncio.sleep(0.02)
        self.engine.drain_abort()

    async def close(self) -> None:
        if self._debug_source_name is not None:
            self.runtime.unregister_debug_source(self._debug_source_name)
            self._debug_source_name = None
        if getattr(self, "_kv_source_name", None) is not None:
            self.runtime.unregister_kv_source(self._kv_source_name)
            self._kv_source_name = None
        if getattr(self, "_broker_id", None) is not None:
            from ..disagg import broker

            broker.deregister_engine(self._broker_id)
        for client in getattr(self, "_pull_clients", {}).values():
            await client.close()
        if getattr(self, "_kvbm_index", None) is not None:
            await self._kvbm_index.close()
        if getattr(self, "_kvbm_pull_client", None) is not None:
            await self._kvbm_pull_client.close()
        if self._follower is not None:
            self._follower.stop()
        if self._follower_task is not None:
            self._follower_task.cancel()
        if getattr(self, "_announce_task", None) is not None:
            self._announce_task.cancel()
        if self._broadcaster is not None:
            await self._broadcaster.close()
        if self._load_task is not None:
            self._load_task.cancel()
        if getattr(self, "_slo_task", None) is not None:
            self._slo_cancel.set()
            self._slo_task.cancel()
        if self.engine is not None:
            await self.engine.close()
        if self.served is not None:
            await deregister_model(self.runtime, self.card,
                                   self.served.instance_id)
        for served in self._aux_served:
            await served.shutdown()
        if self.served is not None:
            await self.served.shutdown()
