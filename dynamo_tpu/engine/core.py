"""The JAX engine core: continuous batching over a paged KV cache.

This is the component the reference does NOT have — it delegates token
generation to vLLM/SGLang/TRT-LLM (SURVEY.md §7 scope delta).  Design, for
XLA's compile-once/execute-many model:

  * two jitted programs: `prefill` (per padded-length bucket, one sequence)
    and `decode` (fixed batch = max_num_seqs, inactive slots masked to the
    garbage block).  No data-dependent shapes ever reach XLA.
  * the KV cache is donated through every step, so updates are in-place in
    HBM; only sampled token ids (B int32) cross back to the host per step.
  * host-side scheduler (this file) admits requests, manages the block
    allocator and PLH bookkeeping, streams tokens, and publishes KV events —
    mirroring the vLLM-scheduler behaviors the mocker simulates.
  * prefix-cache hits skip prefill compute for matched blocks: the prefill
    program attends to cached context through the block table (unified
    chunked-prefill/prefix-reuse path, ops/paged_attention.py).
"""

from __future__ import annotations

import asyncio
import dataclasses
import inspect
import logging
import zlib
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from types import SimpleNamespace
from typing import Any, AsyncIterator, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import chaos, obs
from ..models import get_family
from ..models.moe import moe_form
from ..parallel.mesh import MeshConfig, make_mesh, shard_params
from ..protocols import (
    DRAIN_ABORT,
    DRAIN_REJECT,
    LLMEngineOutput,
    PreprocessedRequest,
)
from ..quant.kv import is_quantized
from ..runtime.retry import PULL_POLICY, call_with_retry
from ..tokens import TokenBlockSequence, request_salt
from .block_allocator import BlockAllocator
from .config import EngineConfig
from ..ops.fused_sampling import fused_greedy_tokens, fused_sample_tokens
from ..ops.packed_prefill import resolve_packed_impl
from ..ops.paged_attention import PALLAS_IMPLS, resolve_decode_impl
from .sampler import greedy_tokens, sample_block_tokens, sample_tokens

logger = logging.getLogger(__name__)


def _set_result_safe(fut: asyncio.Future, value) -> None:
    if not fut.done():
        fut.set_result(value)


def _pow2_len(n: int) -> int:
    """Next power of two >= n (shape-bucketing for jit)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _pow2_ids(block_ids) -> np.ndarray:
    """Block ids zero-padded to _pow2_len: bounds the number of distinct
    shapes reaching jit (one recompile per bucket), and padded ids target
    the reserved garbage block 0, so gathers read junk the host slices off
    and scatters write harmlessly."""
    n = len(block_ids)
    out = np.zeros(_pow2_len(n), np.int32)
    out[:n] = block_ids
    return out


@dataclass
class _Slot:
    index: int
    request: PreprocessedRequest
    seq: TokenBlockSequence
    out_q: asyncio.Queue
    block_table: np.ndarray  # [max_blocks_per_seq] int32
    ctx_len: int = 0         # tokens materialized in the cache
    prompt_len: int = 0      # fixed at admit (seq grows as tokens append)
    prefill_pos: int = 0     # next prompt position to compute (< prompt_len
    #                          while the slot is still prefilling)
    last_token: int = 0
    generated: int = 0
    committed_blocks: int = 0
    sampling_seed: int = 0
    finished: bool = False
    cancel_requested: bool = False
    cached_tokens: int = 0   # prefix-cache reuse (for metrics)
    lora_idx: int = 0        # adapter bank slot (0 = no adapter)
    enqueued_t: float = 0.0
    # forensics plane (obs/forensics.py): waiting-queue position at
    # enqueue and prefill chunk count, stamped back to the frontend on
    # the stream's first-token/finish frames (`forensic` metrics block)
    queue_pos: int = 0
    prefill_chunks: int = 0
    # request stages (metrics req_stage_*), each stamp set once (a
    # preempted request's replay keeps its first): enqueued_t -> seen_t
    # (the first _admit_waiting pass that finds it waiting) ->
    # admitted_t (it gets its lane and blocks) -> dispatched_t (its
    # first prefill chunk is dispatched) -> first_token_t (the first
    # token is in the host's hands; its frame is put on the stream by
    # the event loop, _emit_first stamps and sums) -> second_token_t
    # (the first token of a decode burst) -> the finish frame
    seen_t: float = 0.0
    admitted_t: float = 0.0
    dispatched_t: float = 0.0
    first_token_t: float = 0.0
    second_token_t: float = 0.0
    # decode steps dispatched and not yet ready on the device when the
    # first chunk was dispatched (_stamp_dispatch, metric req_ahead_steps)
    ahead_steps: int = 0
    first_sent: bool = False  # the first frame is on its way to the loop
    last_push_t: float = 0.0  # previous streamed-token time (ITL EMA)
    # a family that generates by blocks (models/__init__.py
    # `GEN_BLOCK`): the block length; 0 for one token a step
    gen_block: int = 0

    @property
    def prefill_end(self) -> int:
        """Where this slot's prefill ends: the prompt's end, or for a
        family that generates by blocks the last multiple of the block
        length in it (the rest enters the first block unmasked)."""
        if self.gen_block:
            return self.prompt_len - self.prompt_len % self.gen_block
        return self.prompt_len

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < self.prefill_end
    # disaggregation
    disagg_prefill: bool = False       # prefill-only; park KV for pulling
    # decode side of a disagg pull: the slot sits admitted-but-idle while
    # the pull task streams chunk injects into its blocks (prefill and
    # decode skip it until the pull finalizes or falls back)
    pulling: bool = False
    admitted: Optional[asyncio.Event] = None  # set (loop thread) on admit
    # decode pipelining (decode_pipeline_depth): tokens the device has
    # already decoded for this slot but the host has not yet read back
    inflight: int = 0
    # bumped on preemption so stale in-flight bursts are discarded
    epoch: int = 0
    # overlapped scheduling: the prompt is fully prefilled but the first
    # sampled token is still riding _pending_first (deferred readback) —
    # decode/spec skip the slot until the next step's flush emits it
    awaiting_first: bool = False
    # guided decoding (guided/json_prefix.py): constrained slots step
    # one token at a time through the top-M candidate path instead of
    # joining fused batch bursts
    guide: Optional[Any] = None
    guided_out: List[int] = field(default_factory=list)
    # speculative decoding (spec/): adaptive draft length (-1 = take the
    # engine default on first attempt; 0 = collapsed to plain decode),
    # acceptance-rate EMA (seeded with a neutral 0.5 prior on first
    # attempt), the generated-token count at which a collapsed/pipelined
    # slot next probes, and the number of leading positions whose
    # DRAFT-model KV matches the real sequence
    spec_k_cur: int = -1
    spec_accept_ema: float = -1.0
    spec_probe_at: int = 0
    spec_backoff: int = 0
    draft_pos: int = 0


@dataclass
class _Parked:
    """A finished disagg prefill whose KV awaits pulling by decode."""

    seq_id: str
    block_ids: list
    prompt_len: int
    expires_t: float


class JaxEngine:
    def __init__(self, config: EngineConfig, params=None, mesh=None,
                 kv_event_sink=None, kv_pull_fn=None, step_sink=None):
        """kv_event_sink: optional callable(stored, removed) -> awaitable,
        invoked with PLH batches as the cache mutates.
        kv_pull_fn: optional async callable(disaggregated_params) ->
        (k, v, prompt_len) pulling a remote prefill's KV blocks (set by the
        worker; the engine stays transport-agnostic).
        step_sink: optional callable(kind, {name: np.ndarray}) invoked with
        every compute step's host inputs BEFORE the jit call — the
        multi-host leader broadcasts these so follower processes replay an
        identical jit sequence (parallel/multihost.py).  Covers prefill
        (single/batched/packed/ring), decode (full/multi/continuation),
        guided top-M, spec_verify, gather/inject, lora_write, and embed;
        followers require kvbm/disagg off and the n-gram proposer only
        (draft-model speculation is single-host in v1)."""
        self.config = config
        self.model_cfg = config.resolve_model()
        self.family = get_family(self.model_cfg)
        # a family whose pools are addressed by lane takes each prefill
        # row's lane; one whose cache ends in device-side counts names
        # them (models/__init__.py)
        self._lane_addressed = bool(
            getattr(self.family, "KV_LANE_ADDRESSED", False))
        self._kv_counters = tuple(getattr(self.family, "KV_COUNTERS", ()))
        self._kv_counters_seen = np.zeros(len(self._kv_counters), np.int64)
        # a family that generates by blocks of positions says so by
        # `GEN_BLOCK` (models/__init__.py): a decode burst's unit is
        # then a PASS over every busy lane's block and a lane's state
        # between bursts lives on the device
        gen_block = getattr(self.family, "GEN_BLOCK", None)
        self._gen_block = int(gen_block(self.model_cfg)) if gen_block else 0
        # attention-impl overrides (ops/paged_attention.py +
        # ops/pallas_packed_prefill.py): the engine-level knobs replace
        # the resolved model config's fields so deployments pick the
        # kernel per worker (--attn-impl/--packed-attn-impl) without a
        # custom model_config.  "" keeps the family's default.  A knob
        # the family would silently ignore is a loud config error — the
        # MDC advertises the EFFECTIVE impl and must never claim a
        # kernel the worker doesn't run: MLA's absorbed read has no
        # "jnp_bf16" form (family SUPPORTED_ATTN_IMPLS) and no
        # packed_attn_impl (no packed path / field).
        from ..ops.packed_prefill import PACKED_IMPLS
        from ..ops.paged_attention import DECODE_IMPLS

        impl_over = {}
        if config.attn_impl:
            supported = getattr(self.family, "SUPPORTED_ATTN_IMPLS",
                                DECODE_IMPLS)
            if config.attn_impl not in supported:
                raise ValueError(
                    f"attn_impl for model family "
                    f"{type(self.model_cfg).__name__} must be one of "
                    f"{' | '.join(supported)}, got {config.attn_impl!r}")
            impl_over["attn_impl"] = config.attn_impl
        if config.packed_attn_impl:
            if config.packed_attn_impl not in PACKED_IMPLS:
                raise ValueError(
                    f"packed_attn_impl must be "
                    f"{' | '.join(PACKED_IMPLS)}, "
                    f"got {config.packed_attn_impl!r}")
            if "packed_attn_impl" not in {
                    f.name for f in dataclasses.fields(self.model_cfg)}:
                raise ValueError(
                    f"model family {type(self.model_cfg).__name__} has "
                    f"no packed_attn_impl knob (MLA has no packed "
                    f"prefill path)")
            impl_over["packed_attn_impl"] = config.packed_attn_impl
        if impl_over:
            self.model_cfg = dataclasses.replace(self.model_cfg,
                                                 **impl_over)
        # fused sampling/top-k epilogue (ops/fused_sampling.py): resolve
        # the EFFECTIVE mode like the attn impls and kv dtype — families
        # without the hidden-state decode surface (MLA) fall back to
        # "off" with a warning instead of failing the worker, and the
        # MDC advertises the effective mode so a worker never claims an
        # epilogue it does not run
        from ..ops.fused_sampling import EPILOGUE_MODES
        if config.sampling_epilogue not in EPILOGUE_MODES:
            raise ValueError(
                f"sampling_epilogue must be "
                f"{' | '.join(EPILOGUE_MODES)}, "
                f"got {config.sampling_epilogue!r}")
        self.sampling_epilogue = config.sampling_epilogue
        if self.sampling_epilogue == "fused" and not (
                hasattr(self.family, "decode_hidden")
                and hasattr(self.family, "unembed_weight")
                and hasattr(self.family, "decode_multi_hidden")):
            logger.warning(
                "model family %r has no hidden-state decode surface; "
                "sampling_epilogue falls back to off",
                type(self.model_cfg).__name__)
            self.sampling_epilogue = "off"
        self.mesh = mesh if mesh is not None else make_mesh(
            MeshConfig(dp=config.dp, tp=config.tp, sp=config.sp)
        )
        self.kv_event_sink = kv_event_sink
        self._sink_takes_tier = False
        if kv_event_sink is not None:
            try:
                sink_params = list(
                    inspect.signature(kv_event_sink).parameters.values()
                )
                kinds = inspect.Parameter
                self._sink_takes_tier = (
                    sum(p.kind in (kinds.POSITIONAL_ONLY,
                                   kinds.POSITIONAL_OR_KEYWORD)
                        for p in sink_params) >= 3
                    or any(p.kind == kinds.VAR_POSITIONAL
                           for p in sink_params)
                )
            except (TypeError, ValueError):
                pass
        self.kv_pull_fn = kv_pull_fn
        self.step_sink = step_sink
        self.eos_ids = frozenset(config.resolve_eos_ids())
        # KV-cache quantization (quant/kv.py): resolve the EFFECTIVE
        # dtype — families without a quantized path (MLA) fall back to
        # bf16, the same precedent as the MLA packed-prefill/spec
        # fallbacks — then size the block pool: with a kv_hbm_gb budget
        # the block count derives from bytes-per-block, so int8 yields
        # ~2x blocks for the same HBM instead of the same count at half
        # the memory.  config.num_blocks is updated in place so the
        # allocator, block tables, MDC, and load metrics all agree.
        if config.kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bf16' | 'int8', "
                f"got {config.kv_cache_dtype!r}")
        self.kv_dtype = config.kv_cache_dtype
        if self.kv_dtype == "int8" \
                and not hasattr(self.family, "kv_cache_scale_shapes"):
            logger.warning(
                "model family %r has no quantized KV path; "
                "kv_cache_dtype falls back to bf16", self.model_cfg.name)
            self.kv_dtype = "bf16"
        self._family_gaps(kv_pull_fn)
        # "auto" decode attention becomes what it means for this cache on
        # this mesh's platform (ops/paged_attention.resolve_decode_impl):
        # the step programs, the MDC and the decode_attn_* counters all
        # name the impl that runs.  The rule reads the heights of the
        # cache's block planes: `head_dim`, or what a config whose paged
        # members are MLA's says they are (`mla_plane_heights`).
        if self.model_cfg.attn_impl == "auto":
            self.model_cfg = dataclasses.replace(
                self.model_cfg, attn_impl=resolve_decode_impl(
                    "auto", self.mesh.devices.flat[0].platform,
                    config.block_size,
                    getattr(self.model_cfg, "mla_plane_heights", None)
                    or self.model_cfg.head_dim,
                    jnp.int8 if self.kv_dtype == "int8"
                    else self.model_cfg.dtype))
        if config.kv_hbm_gb > 0:
            from ..quant.kv import blocks_for_hbm_budget

            config.num_blocks = blocks_for_hbm_budget(
                self.family, self.model_cfg, config.block_size,
                self.kv_dtype, int(config.kv_hbm_gb * 1e9))
        # KV block-lifecycle ledger (obs/kv_ledger.py): an independent
        # set of books recorded at the allocator's own mutation sites,
        # reconciled by the invariant auditor on request finish / idle
        # tick / on demand (/debug/kv).  None when DYN_KV_LEDGER=0 (or
        # config.kv_ledger=False) — every hook is then one pointer
        # compare, the obs-plane zero-cost-off contract.
        from ..obs.kv_ledger import KvLedger, ledger_enabled

        self.kv_ledger: Optional[KvLedger] = (
            KvLedger() if ledger_enabled(config.kv_ledger) else None)
        self.allocator = BlockAllocator(
            config.num_blocks, config.enable_prefix_caching,
            ledger=self.kv_ledger,
        )
        # KVBM tiers: router-visible events for ALL tiers are netted through
        # the consolidator, so a block offloaded to G2 survives G1 eviction
        # in the router's view (kvbm/consolidator.py)
        from ..kvbm import KvEventConsolidator, TieredKvManager

        self._consolidator = KvEventConsolidator()
        self.kvbm: Optional[TieredKvManager] = None
        if config.disk_cache_dir and config.host_cache_blocks <= 0:
            raise ValueError(
                "disk_cache_dir (G3) requires host_cache_blocks > 0: the "
                "disk tier is fed only by demotion from the host tier"
            )
        if config.disk_cache_dir and config.disk_cache_blocks <= 0:
            raise ValueError(
                "disk_cache_dir (G3) requires disk_cache_blocks > 0"
            )
        if config.object_store_dir and config.host_cache_blocks <= 0:
            raise ValueError(
                "object_store_dir (G4) requires host_cache_blocks > 0: the "
                "object tier is fed by demotion down the tier ladder")
        if config.host_cache_blocks > 0:
            self.kvbm = TieredKvManager(
                config.host_cache_blocks,
                disk_dir=config.disk_cache_dir,
                disk_blocks=config.disk_cache_blocks,
                object_dir=config.object_store_dir,
                object_ttl_s=config.object_store_ttl_s,
                io_deadline_s=config.kv_io_deadline_s,
                breaker_threshold=config.kv_breaker_threshold,
                breaker_cooldown_s=config.kv_breaker_cooldown_s,
            )
            self.kvbm.on_corruption = self._note_kv_corruption
        # (tier, action) -> count for
        # dynamo_kv_integrity_failures_total; quarantines land here via
        # _note_kv_corruption (g3/g4/remote/disagg), timeouts/errors are
        # merged in from the manager's I/O stats at export time
        self.kv_integrity: Dict[Tuple[str, str], int] = {}
        # cross-worker G2 pull (kvbm/remote.py): installed by the worker;
        # async callable(hashes) -> [(h, k, v), ...]
        self.remote_kvbm_fetch = None
        self._offload_watermark = (
            config.offload_watermark_blocks or config.num_blocks // 4
        )

        # LoRA: stacked adapter bank + name->slot registry (lora/bank.py).
        # Slot 0 is the all-zeros no-adapter slot; adapters load lazily
        # from lora_dir on first request and evict LRU among slots not
        # referenced by active sequences.
        self.lora_bank = None
        self._lora_slots: Dict[str, int] = {}   # name -> bank slot (>=1)
        self._lora_lru: List[str] = []          # LRU order, oldest first
        self._lora_pins: Dict[int, int] = {}    # slot -> resolved-not-
        #                                         yet-enqueued requests
        self._lora_source = None
        if config.lora_max_adapters > 0:
            if "lora_bank" not in inspect.signature(
                    self.family.prefill).parameters:
                raise ValueError(
                    f"model family {self.model_cfg.name!r} does not "
                    "support LoRA serving")
            from ..lora.bank import empty_bank
            from ..lora.source import LocalLoraSource

            mc = self.model_cfg
            self.lora_bank = empty_bank(
                mc.n_layers, config.lora_max_adapters + 1,
                config.lora_rank, mc.d_model, mc.q_dim, mc.kv_dim,
                dtype=mc.dtype)
            if config.lora_dir:
                self._lora_source = LocalLoraSource(config.lora_dir)

        with self.mesh:
            if params is None and config.model_path:
                from ..models.loader import load_params

                # already placed shard-by-shard onto the mesh
                self.params = load_params(
                    config.model_path, self.model_cfg, mesh=self.mesh
                )
            else:
                if params is None:
                    # born sharded, one layer at a time (and waited for,
                    # so the host cannot run ahead of the frees): a
                    # model that needs the whole mesh (llama-8b at tp=4)
                    # never fits whole on the first chip
                    params = self.family.init_params(
                        self.model_cfg, jax.random.PRNGKey(config.seed),
                        # dynlint: disable=DYN011 init-time wait, before any scheduler exists
                        place=lambda tree: jax.block_until_ready(
                            shard_params(tree, self.mesh)),
                    )
                self.params = shard_params(params, self.mesh)
            self.kv = self._init_kv_cache()
        # routed-expert layers as the host knows them, for the moe_*
        # counters: (layers that route, picks a token, experts held a
        # layer — the `moe_w_*` stacks' length, the router may be wider)
        moe = [lp["moe_w_up"]
               for lp in (self.params.get("layers", ())
                          if isinstance(self.params, dict) else ())
               if isinstance(lp, dict) and "moe_w_up" in lp]
        self._moe = (len(moe), getattr(self.model_cfg,
                                       "experts_per_token", 0),
                     moe[0].shape[0] if moe else 0)
        if moe:
            # a traced program cannot see how its arguments are laid
            # out: the devices the expert stacks are split over, read
            # off the arrays as placed (moe.moe_dispatch_form)
            self.model_cfg = dataclasses.replace(
                self.model_cfg, expert_shards=moe[0].shape[0]
                // moe[0].sharding.shard_shape(moe[0].shape)[0])

        # pinned output shardings for every KV-returning program: XLA is
        # otherwise free to pick a DIFFERENT (equivalent) sharding for a
        # program's kv output than the cache was initialized with, and the
        # C++ dispatch cache keys on input sharding — so the next program
        # that consumed the drifted kv forked its executable (the
        # committed-vs-uncommitted packed-prefill fork the PR 7 watchdog
        # measured at 8-14s mid-serving on TPU).  Pinning the kv outputs
        # to the canonical cache shardings (and the small host-bound
        # outputs to replicated) makes every program's kv round-trip
        # sharding-stable: one executable per shape, period.
        self._rep_sharding = NamedSharding(self.mesh, P())
        kv_specs = list(self.family.kv_cache_specs())
        if self.kv_dtype == "int8":
            kv_specs += list(self.family.kv_cache_scale_specs())
        self._kv_shardings = tuple(
            NamedSharding(self.mesh, spec) for spec in kv_specs)

        # compile watchdog (obs/compile_watch.py) is constructed FIRST
        # so every jit below is a WatchedProgram from the moment it
        # exists — a compile (warmup or the mid-serving kind the guided
        # fork measured at 8-14s) is counted, timed and span-recorded.
        # Wrap-at-definition is the DYN001 lint invariant: a raw jax.jit
        # that dispatches unwatched cannot be written here without a
        # suppression.  Wrapper overhead per dispatch is two C++
        # cache-size reads.
        from ..obs.compile_watch import CompileWatch

        # timeline tracing (obs/): steps run on whatever pool thread
        # asyncio.to_thread picked, but the step lock serializes them —
        # pin every step-phase span (and compile spans) to ONE logical
        # track per engine so the report's innermost-span attribution
        # sees a well-nested timeline (co-resident engines in one
        # process stay distinct)
        self._obs_track = f"sched:{id(self):x}"
        self.compile_watch = CompileWatch(
            sink=lambda rec: self.fpm.append(rec),
            track=self._obs_track,
            serving=lambda: any(s is not None for s in self._slots),
        )
        w = self.compile_watch
        # every program is jitted under its watch family's name
        # (w.named): the profiler's modules, compile events and the
        # benchmark's module_s read `jit_dyn_<family>`
        _toks2 = lambda a: a[2].shape[-1]           # noqa: E731
        _toks2_total = lambda a: int(               # noqa: E731
            np.prod(a[2].shape))
        # out_shardings pytrees: kv pinned canonical, everything else
        # replicated (token/descriptor outputs are [B]-sized and host
        # bound — see the _kv_shardings rationale above)
        rep = self._rep_sharding
        kvsh = self._kv_shardings
        _decode_out = (rep, kvsh, rep, rep, rep)
        _prefill_out = (rep, kvsh)
        # decode variants: {greedy: jitted} — an all-greedy batch takes the
        # argmax specialization (sampling machinery measurably costs on
        # large vocabs even top-k-capped)
        # donate kv + the advancing descriptor arrays (positions/ctx/steps
        # are returned advanced for the next burst's continuation)
        # the sampling epilogue is a static, init-time property of the
        # decode programs (identical on every host — followers replay
        # the leader's step stream through the same partials), NOT a
        # per-dispatch key: the (greedy, k) program families and their
        # pinned out_shardings are unchanged, so the zero-recompile
        # steady state carries over
        _ep = self.sampling_epilogue == "fused"
        # a family that generates by blocks runs its pass program in
        # the decode programs' place, one pass where they run one step
        _donate = (1, 5, 7, 9)
        if self._gen_block:
            _decode_out, _donate = (rep, kvsh, rep), (1,)
        self._jit_decode = {
            g: w.wrap(jax.jit(
                w.named(partial(self._denoise_impl, self.family,
                                self.model_cfg, self.mesh, g, 1, _ep)
                        if self._gen_block else
                        partial(self._decode_impl, self.family,
                                self.model_cfg, self.mesh, g, _ep),
                        "decode"),
                donate_argnums=_donate,
                out_shardings=_decode_out,
            ), "decode")
            for g in (False, True)
        }
        self._jit_prefill = w.wrap(jax.jit(
            w.named(partial(self._prefill_impl, self.family,
                            self.model_cfg, mesh=self.mesh), "prefill"),
            donate_argnums=(1,),
            out_shardings=_prefill_out,
        ), "prefill", _toks2)
        self._jit_prefill_batched = w.wrap(jax.jit(
            w.named(partial(self._prefill_batched_impl, self.family,
                            self.model_cfg, mesh=self.mesh),
                    "prefill_batched"),
            donate_argnums=(1,),
            out_shardings=_prefill_out,
        ), "prefill_batched", _toks2_total)
        # packed chunked prefill (engine/prefill.py planner +
        # ops/packed_prefill.py): the padding-free multi-sequence path.
        # Gated off for families without prefill_packed (MLA).
        self._packed_prefill_ok = (
            config.prefill_packed and hasattr(self.family, "prefill_packed"))
        # the jit must exist whenever the FAMILY supports packing, even
        # with packing config-disabled on this worker: a multi-host
        # follower replays whatever step kinds its leader broadcasts,
        # including prefill_packed
        self._jit_prefill_packed = None
        if hasattr(self.family, "prefill_packed"):
            self._jit_prefill_packed = w.wrap(jax.jit(
                w.named(partial(self._prefill_packed_impl, self.family,
                                self.model_cfg, self.mesh),
                        "prefill_packed"),
                donate_argnums=(1,),
                out_shardings=_prefill_out,
            ), "prefill_packed", _toks2)
        # speculative decoding (spec/): like prefill_packed, the verify
        # jit exists whenever the FAMILY supports it — a multi-host
        # follower replays whatever step kinds its leader broadcasts,
        # spec_verify included, regardless of this worker's own config
        self._jit_spec_verify = None
        if hasattr(self.family, "spec_verify_packed"):
            self._jit_spec_verify = w.wrap(jax.jit(
                w.named(partial(self._spec_verify_impl, self.family,
                                self.model_cfg, self.mesh), "spec_verify"),
                donate_argnums=(1,),
                out_shardings=(rep, rep, rep, kvsh),
            ), "spec_verify", _toks2)
        self.proposer = None
        self._spec_ok = False
        if config.spec_decode != "off":
            if config.spec_decode not in ("ngram", "draft"):
                raise ValueError(
                    f"spec_decode must be 'off' | 'ngram' | 'draft', "
                    f"got {config.spec_decode!r}")
            if self._jit_spec_verify is None:
                # MLA families have no packed verify path in v1: serve
                # plain decode instead of failing the worker
                logger.warning(
                    "model family %r has no spec_verify_packed; "
                    "speculative decoding disabled (plain decode)",
                    self.model_cfg.name)
            else:
                if config.spec_decode == "draft" and step_sink is not None:
                    raise ValueError(
                        "draft-model speculation is single-host in v1 "
                        "(draft programs do not ride the step stream); "
                        "use spec_decode='ngram' on multi-host slices")
                from ..spec import make_proposer

                # the draft model's own prefill/propose programs are jit
                # dispatch sites like any other: watched, so a draft
                # recompile mid-serving is as visible as a target one
                self.proposer = make_proposer(config, self.mesh,
                                              compile_watch=w)
                self._spec_ok = True
        # slot indexes that speculated this scheduler step (they emitted
        # synchronously and must skip the pipelined decode dispatch)
        self._specced: frozenset = frozenset()
        self._fpm_last_spec_t = 0.0
        # sequence-parallel ring prefill: long-context path for prompts
        # beyond the largest bucket when the mesh has an sp axis
        self._jit_prefill_ring = None
        if config.sp > 1 and hasattr(self.family, "prefill_ring"):
            self._jit_prefill_ring = w.wrap(jax.jit(
                w.named(partial(self._prefill_ring_impl, self.family,
                                self.model_cfg, self.mesh), "prefill_ring"),
                donate_argnums=(1,),
                out_shardings=_prefill_out,
            ), "prefill_ring", _toks2)
        self._jit_inject = w.wrap(
            jax.jit(w.named(self._inject_impl, "inject"),
                    donate_argnums=(0,), out_shardings=kvsh), "inject",
            lambda a: a[3].shape[0])
        self._jit_gather = w.wrap(
            jax.jit(w.named(self._gather_impl, "gather")), "gather",
            lambda a: a[1].shape[0])
        # fused decode: one compiled variant per (greedy, k) ladder rung
        # (adaptive fusion ramps k through _fuse_ladder; a fixed
        # num_steps program dispatched at a smaller accounting k would
        # waste (num_steps - k)/num_steps of every interleave burst's
        # decode compute).  All rungs are warmed by warmup_decode.
        self._jit_decode_multi = None
        if config.decode_fused_steps > 1:
            self._jit_decode_multi = {
                (g, k): w.wrap(jax.jit(
                    w.named(partial(self._denoise_impl
                                    if self._gen_block
                                    else self._decode_multi_impl,
                                    self.family,
                                    self.model_cfg, self.mesh, g, k, _ep),
                            "decode_multi"),
                    donate_argnums=_donate,
                    out_shardings=_decode_out,
                ), "decode_multi")
                for g in (False, True)
                for k in self._fuse_ladder()[1:]
            }

        # continuation decode (steady state): the burst descriptor lives on
        # device and advances INSIDE the decode program (advance=k), so an
        # unchanged-membership burst uploads nothing — the full path
        # uploads ~12 arrays per burst, each paying the host->device hop
        # (the round-3 scheduler-overhead finding).  _dev_desc is the
        # device descriptor pack of the last dispatched burst; _last_desc
        # the leader's host mirror used to prove the next burst is a pure
        # continuation of it.
        self._dev_desc: Optional[Dict[str, Any]] = None
        self._last_desc: Optional[Dict[str, Any]] = None
        self._desc_sharding = NamedSharding(self.mesh, P())
        self._adv_consts: Dict[int, Any] = {}

        self.waiting: List[_Slot] = []
        self._sched_calls: List[tuple] = []  # (fn, future) run between steps
        # async KV-event sink dispatches in flight: the loop only holds a
        # weak ref to a task, so fire-and-forget publications could be
        # gc'd mid-flight with their exceptions never observed (DYN005)
        self._event_tasks: set = set()
        self._parked: Dict[str, _Parked] = {}
        self.parked_ttl_s = 120.0
        # identity advertised in kv_transfer_params (set by the worker)
        self.transfer_identity: Dict[str, Any] = {}
        self._qlock = threading.Lock()  # guards `waiting` across threads
        self._step_lock = threading.Lock()  # held for each _sched_step run
        self._slots: List[Optional[_Slot]] = [None] * config.max_num_seqs
        # decode pipelining (decode_pipeline_depth): dispatched-but-unread
        # bursts + the device-resident token chain feeding the next burst
        self._inflight: deque = deque()
        self._chain_tokens = None          # device [B] last burst's output
        self._chain_owner: List[Optional[Tuple[str, int]]] = \
            [None] * config.max_num_seqs   # (seq_id, epoch) per lane
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._loop_ref: Optional[asyncio.AbstractEventLoop] = None
        self._closed = False
        # graceful drain (engine/worker.py drain()): set to reject new
        # requests with the migratable "worker draining" marker
        self.draining = False
        self.metrics: Dict[str, Any] = {
            "steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
            "cache_hit_tokens": 0, "preemptions": 0,
            "requests": 0, "prompt_tokens": 0,
            # a request's time to its first token by stage, summed over
            # the requests whose first token was emitted (seconds; see
            # _Slot.dispatched_t and _emit_first).  wake + lane + turn
            # is queue, a request at a time; req_ahead_steps is the
            # decode steps that stood ahead of its first prefill chunk
            # on the device (_stamp_dispatch)
            "req_stage_s.queue": 0.0, "req_stage_s.prefill": 0.0,
            "req_stage_s.emit": 0.0, "req_stage_n": 0,
            "req_stage_s.wake": 0.0, "req_stage_s.lane": 0.0,
            "req_stage_s.turn": 0.0, "req_ahead_steps": 0,
            # requests that arrived while a step's read-back was waiting
            # and were seen by the admission pass of that same step
            # (_read_back_first): over req_stage_n, the share that
            # "read back first, admit after" served a step sooner
            "req_admitted_after_wait": 0,
            # decode bursts dispatched with their size from _fused_k's
            # decode-only branch, and those of them it held at the
            # interleave rung because a lane stood free (PR 50): the
            # share says how often the rule engaged
            "decode_only_bursts": 0, "decode_held_bursts": 0,
            # after the first token (_push_token): first -> second token
            # over the requests that got a second; second -> finish over
            # the tokens after the second of the requests that finished
            "req_stage_s.join": 0.0, "req_join_n": 0,
            "req_stage_s.decode": 0.0, "req_decode_tokens": 0,
            # decode attention, in cache blocks per layer summed over
            # decode steps: what the active lanes' contexts hold, and
            # what the impl that runs reads for them (_count_decode_attn)
            "decode_attn_live_blocks": 0, "decode_attn_read_blocks": 0,
            # routed experts: picks = tokens x routing layers x experts
            # a token, by phase; expert slots = held experts x routing
            # layers a decode step (the denominator of how many of them
            # a step's tokens visit).  A family that holds a share of
            # its experts counts on the device which picks fell on a
            # held expert and how many held experts a step visited
            # (its KV_COUNTERS: moe_picks_held.*, moe_experts_visited.*)
            "moe_picks.prefill": 0, "moe_picks.decode": 0,
            "moe_expert_slots.decode": 0,
            # the expert slots of decode steps whose program took the
            # dropless dispatch's visited form (moe.moe_form: by
            # shape), which reads the visited experts only;
            # / moe_expert_slots.decode says how often it engaged
            "moe_visited_form_slots.decode": 0,
            # prompt tokens whose program took the dropless dispatch's
            # grouped form (moe.moe_form: by shape);
            # / prefill_tokens says how often it engaged
            "moe_grouped_tokens.prefill": 0,
            # prompt tokens whose packed program ran its attention in
            # the Pallas kernel (ops/packed_prefill.resolve_packed_impl:
            # by platform, cache and the stream's length);
            # / prefill_tokens says how often it engaged
            "prefill_attn_kernel_tokens": 0,
        }
        for name in self._kv_counters:
            self.metrics[name] = 0
        if self._gen_block:
            # generation by blocks: lane passes = busy lanes summed over
            # passes, of them the commit passes; the tokens and blocks
            # that lost their last mask; the rows the pass programs ran
            # (passes x lanes x block length)
            self.metrics.update({
                "diff_lane_passes": 0, "diff_commit_passes": 0,
                "diff_tokens_unmasked": 0, "diff_blocks_done": 0,
                "diff_rows": 0})
        # a family whose layers differ in what they read counts its own
        # decode reads, and one whose attention chooses its keys its
        # prefill pairs too, from the host's positions; an empty burst
        # or chunk names the counters (mimo.py: kv_window_block_steps
        # against kv_uniform_block_steps; keye.py: sparse_*)
        self._prefill_counts = getattr(self.family, "prefill_token_counts",
                                       None)
        if hasattr(self.family, "decode_block_counts"):
            self.metrics.update(self._decode_counts(np.zeros(0, np.int64),
                                                    0))
        if self._prefill_counts is not None:
            self.metrics.update(self._prefill_counts(self.model_cfg, 0, 0, 0))
        # the scheduler thread's phases: counters host_s.<kind> /
        # host_n.<kind> always, `dyn.<kind>` on the profiler's clock
        # while a session is live, ring spans under a Tracer (obs/)
        self._phase = obs.PhaseClock(self.metrics, self._obs_track,
                                     behind=self._bursts_behind,
                                     compiles=self.compile_watch.events)
        # the phases that outlasted obs.PAUSE_S, newest last, each saying
        # what stood behind it on the device when it ended
        self.pauses = self._phase.pauses
        self.itl_ema_s = 0.0  # streamed inter-token latency (SLA planner)
        # forward-pass metrics stream (ref fpm_publisher.rs:1-10 /
        # instrumented_scheduler.py): one record per dispatched program —
        # decode bursts carry (lanes, fused k, gap since the previous
        # decode dispatch), prefill programs carry (rows, chunk tokens).
        # The worker drains this ring onto the event plane; the SLA
        # planner regresses its perf model on it online.
        self.fpm: deque = deque(maxlen=4096)
        self._fpm_last_decode_t = 0.0
        self._fpm_last_prefill_t = 0.0
        # overlapped scheduling (config.overlap_scheduling): deferred
        # prefill first-token readbacks — each entry holds one dispatch's
        # sampled-token device array plus the completing slots awaiting
        # it; flushed (ONE device_wait) at the top of the next step,
        # while this step's programs execute behind it
        self._overlap = bool(config.overlap_scheduling)
        self._pending_first: List[dict] = []
        # dispatched bursts a step leaves behind it; lockstep is 1 and
        # drains after its dispatch whatever the field says
        self._depth = max(1, config.decode_pipeline_depth) \
            if self._overlap else 1
        # adaptive decode fusion: consecutive decode-only steps (the
        # fusion ladder's ramp clock); reset on arrivals/cancellations
        self._decode_only_run = 0
        # whether the request admitted last joined others on their
        # lanes (_admit_waiting): _fused_k holds the ladder then
        self._shared = False
        # SLA-aware admission: worst SLO burn rate the worker last fed
        # us (obs/slo.py via the worker's slo_metrics subscription) and
        # when — stale signals decay to 0 (_effective_slo_burn)
        self._slo_burn = 0.0
        self._slo_burn_t = 0.0

    # -- cache ------------------------------------------------------------
    def _family_gaps(self, kv_pull_fn) -> None:
        """What the family says it does not carry yet (`UNSUPPORTED`),
        held against this worker's configuration: a feature that only
        costs speed falls back with a warning, as MLA's gaps do; one
        whose absence would change answers or lose state refuses the
        configuration.  (int8 cache, speculation, LoRA, ring and packed
        prefill are decided where each is set up, by what functions the
        family has.)"""
        gaps = getattr(self.family, "UNSUPPORTED", ())
        c = self.config
        name = type(self.model_cfg).__name__
        if "prefix_caching" in gaps and c.enable_prefix_caching:
            logger.warning(
                "model family %s cannot reuse a cached prefix (its "
                "lane-addressed layers' state at the boundary is not "
                "kept); prefix caching is off", name)
            c.enable_prefix_caching = False
        refused = [what for what, asked in (
            ("tp", c.tp > 1),
            ("kvbm", c.host_cache_blocks > 0 or bool(c.disk_cache_dir)
             or bool(c.object_store_dir)),
            ("disagg", kv_pull_fn is not None),
        ) if asked and what in gaps]
        if refused:
            raise ValueError(
                f"model family {name} does not carry "
                f"{', '.join(refused)} yet")

    def _init_kv_cache(self):
        m = self.model_cfg
        c = self.config
        # family-owned layout: GQA (k, v) or MLA (latent, rope-key) pair,
        # both in the head-major transposed block layout; a family with
        # more than one kind of layer has more members (models/__init__
        # .py), and one whose pools are addressed by lane sizes them by
        # the lanes.  An int8 cache (self.kv_dtype, quant/kv.py) adds
        # fp32 scale planes as members 3 and 4 of the tuple, sharded
        # with the same tp split.
        dtype = jnp.int8 if self.kv_dtype == "int8" else m.dtype
        lane_kw = ({"lanes": c.max_num_seqs}
                   if getattr(self.family, "KV_LANE_ADDRESSED", False)
                   else {})
        shapes = self.family.kv_cache_shapes(
            m, c.num_blocks, c.block_size, **lane_kw)
        dtypes = (self.family.kv_cache_dtypes(m)
                  if hasattr(self.family, "kv_cache_dtypes")
                  else (dtype,) * len(shapes))
        kv = tuple(
            # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
            jax.jit(partial(jnp.zeros, shape, dt),
                    out_shardings=NamedSharding(self.mesh, spec))()
            for shape, dt, spec in zip(shapes, dtypes,
                                       self.family.kv_cache_specs()))
        if self.kv_dtype != "int8":
            return kv
        ks_shape, vs_shape = self.family.kv_cache_scale_shapes(
            m, c.num_blocks, c.block_size)
        ks_spec, vs_spec = self.family.kv_cache_scale_specs()
        # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
        ks = jax.jit(partial(jnp.zeros, ks_shape, jnp.float32),
                     out_shardings=NamedSharding(self.mesh, ks_spec))()
        # dynlint: disable=DYN001 one-shot sharded-zeros allocation at init, never dispatched while serving
        vs = jax.jit(partial(jnp.zeros, vs_shape, jnp.float32),
                     out_shardings=NamedSharding(self.mesh, vs_spec))()
        return kv + (ks, vs)

    # -- jitted programs --------------------------------------------------
    @staticmethod
    def _decode_impl(family, model_cfg, mesh, greedy, epilogue, params,
                     kv, chain, use_chain, tokens, positions, block_tables,
                     ctx_lens, seeds, steps, temps, top_ks, top_ps, valid,
                     advance, lora_bank=None, lidx=None):
        """chain/use_chain: device-resident token chaining — lanes whose
        previous burst is still unread take their input token from the
        prior burst's on-device output instead of a host round-trip.
        `greedy` is a static specialization: an all-greedy batch skips the
        sampling machinery (sampler.py greedy_tokens).  `epilogue` is the
        static fused-sampling choice (ops/fused_sampling.py): the decode
        trunk stops at the final-norm hidden and the projection streams
        tile-by-tile into the sampler statistics, so [B, vocab] logits
        never materialize — byte-identical at greedy to the reference
        path below, which stays as the off-mode fallback.

        `advance` (traced scalar) is the continuation clock: steady-state
        bursts re-dispatch the PREVIOUS device descriptor with advance=k
        instead of uploading fresh positions/ctx/steps — the advanced
        arrays are returned for the next burst.  One program serves both
        modes, so donated KV never crosses programs (a separate
        continuation program made XLA re-lay the multi-GB cache on every
        transition — measured at seconds per full burst)."""
        positions = positions + advance
        ctx_lens = ctx_lens + advance
        steps = steps + advance
        tokens = jnp.where(use_chain, chain, tokens)
        lora_kw = ({"lora_bank": lora_bank, "adapter_idx": lidx}
                   if lora_bank is not None else {})
        if epilogue:
            h, kv = family.decode_hidden(
                params, model_cfg, kv, tokens, positions, block_tables,
                ctx_lens, valid=valid, mesh=mesh, **lora_kw,
            )
            uw = family.unembed_weight(params, model_cfg)
            if greedy:
                next_tokens = fused_greedy_tokens(h, uw)
            else:
                next_tokens = fused_sample_tokens(h, uw, seeds, steps,
                                                  temps, top_ks, top_ps)
        else:
            logits, kv = family.decode(
                params, model_cfg, kv, tokens, positions, block_tables,
                ctx_lens, valid=valid, mesh=mesh, **lora_kw,
            )
            if greedy:
                next_tokens = greedy_tokens(logits)
            else:
                next_tokens = sample_tokens(logits, seeds, steps, temps,
                                            top_ks, top_ps)
        # [1, B]: burst-shaped like multi
        return (JaxEngine._with_counters(family, next_tokens[None], kv),
                kv, positions, ctx_lens, steps)

    @staticmethod
    def _decode_multi_impl(family, model_cfg, mesh, greedy, num_steps,
                           epilogue, params, kv, chain, use_chain, tokens,
                           positions, block_tables, ctx_lens, seeds, steps,
                           temps, top_ks, top_ps, valid, advance,
                           lora_bank=None, lidx=None):
        """num_steps fused decode steps (family decode_multi); sampling
        streams stay per-token identical to the single-step path (seed
        folded with the running step counter).  `epilogue`/`advance`: see
        _decode_impl — with the epilogue the scan body samples from the
        final-norm hidden (family decode_multi_hidden), so no step of the
        burst materializes logits."""
        positions = positions + advance
        ctx_lens = ctx_lens + advance
        steps = steps + advance
        tokens = jnp.where(use_chain, chain, tokens)
        lora_kw = ({"lora_bank": lora_bank, "adapter_idx": lidx}
                   if lora_bank is not None else {})
        if epilogue:
            uw = family.unembed_weight(params, model_cfg)
            if greedy:
                def sample_fn(h, step_idx):
                    return fused_greedy_tokens(h, uw)
            else:
                def sample_fn(h, step_idx):
                    return fused_sample_tokens(h, uw, seeds,
                                               steps + step_idx, temps,
                                               top_ks, top_ps)

            burst, kv = family.decode_multi_hidden(
                params, model_cfg, kv, tokens, positions, block_tables,
                ctx_lens, num_steps, sample_fn, valid=valid, mesh=mesh,
                **lora_kw,
            )
            return (JaxEngine._with_counters(family, burst, kv), kv,
                    positions, ctx_lens, steps)
        if greedy:
            sample_fn = None  # decode_multi defaults to argmax
        else:
            def sample_fn(logits, step_idx):
                return sample_tokens(logits, seeds, steps + step_idx,
                                     temps, top_ks, top_ps)

        burst, kv = family.decode_multi(
            params, model_cfg, kv, tokens, positions, block_tables,
            ctx_lens, num_steps, sample_fn, valid=valid, mesh=mesh,
            **lora_kw,
        )
        return (JaxEngine._with_counters(family, burst, kv), kv,
                positions, ctx_lens, steps)

    @staticmethod
    def _denoise_impl(family, model_cfg, mesh, greedy, num_steps, epilogue,
                      params, kv, chain, use_chain, tokens, positions,
                      block_tables, ctx_lens, seeds, steps, temps, top_ks,
                      top_ps, valid, advance, lora_bank=None, lidx=None):
        """`num_steps` fused PASSES of a family that generates by blocks
        (family denoise_multi), in `_decode_multi_impl`'s place and under
        its signature, so that dispatch, continuation and warm-up are the
        decode programs' own.  `chain` is every lane's state after the
        last burst (tokens, mask flags, block start, step:
        `lane_state_width` int32 columns), `tokens` the state the host
        gives a lane that joins, `use_chain` which of the two a lane
        takes.  How far a lane advances is data, so `positions`,
        `ctx_lens`, `steps` and `advance` say nothing here: a
        continuation uploads nothing because the state IS the chain.
        Returns (burst [num_steps * B + 1 (+ counters), lanes]: row
        j * B + b the token at position b of the block that lost its
        last mask in pass j, -1 elsewhere, then each lane's block start
        after the burst; cache; the lanes' state)."""
        state = jnp.where(use_chain[:, None], chain, tokens)
        if greedy:
            sample_fn = None   # argmax and its probability
        else:
            passes = model_cfg.denoising_steps + 1

            def sample_fn(logits, pos, stp):
                return sample_block_tokens(
                    logits, seeds, pos * passes + stp[:, None], temps,
                    top_ks, top_ps)

        outs, state, kv = family.denoise_multi(
            params, model_cfg, kv, state, block_tables, num_steps,
            sample_fn, valid=valid, mesh=mesh)
        start = family.unpack_lane_state(model_cfg, state)[2]
        burst = jnp.concatenate(
            [outs.transpose(0, 2, 1).reshape(-1, outs.shape[1]),
             start[None]])
        return JaxEngine._with_counters(family, burst, kv), kv, state

    @staticmethod
    def _with_counters(family, burst, kv):
        """A family whose cache ends in a vector of device-side counts
        (`KV_COUNTERS`) sends it home beside the tokens, one row a
        count under the burst's k rows: the fetch of the burst is the
        only one a decode step makes."""
        n = len(getattr(family, "KV_COUNTERS", ()))
        if not n:
            return burst
        return jnp.concatenate(
            [burst, jnp.broadcast_to(kv[-1][:, None],
                                     (n, burst.shape[1]))])

    @staticmethod
    def _inject_impl(kv, kb, vb, ids, ksb=None, vsb=None):
        """Scatter pulled KV blocks into the cache (ids padded with 0 write
        harmlessly into the garbage block).

        kb/vb arrive in the UNIVERSAL transfer layout [L, nb, bs, nkv, hd]
        (stable on the wire regardless of either engine's physical layout)
        and are permuted into the head-major block layout here — the TPU
        analogue of the reference's universal_to_block kernel
        (lib/kvbm-kernels/cuda/tensor_kernels.cu:192).  For an int8 cache
        the fp32 scale planes ride as ksb/vsb [L, nb, bs, nkv] and
        scatter into the sibling scale arrays — the quantized
        representation moves verbatim (bit-exact scales, half the
        payload bytes), never dequantizing en route."""
        if len(kv) == 4:
            k, v, ks, vs = kv
        else:
            k, v = kv
            ks = vs = None
        kb = jnp.transpose(kb, (0, 3, 1, 4, 2))  # -> [L, nkv, nb, hd, bs]
        vb = jnp.transpose(vb, (0, 3, 1, 4, 2))
        k = k.at[:, :, ids].set(kb.astype(k.dtype))
        v = v.at[:, :, ids].set(vb.astype(v.dtype))
        if ks is None:
            return (k, v)
        ksb = jnp.transpose(ksb, (0, 3, 1, 2))   # -> [L, nkv, nb, bs]
        vsb = jnp.transpose(vsb, (0, 3, 1, 2))
        ks = ks.at[:, :, ids].set(ksb.astype(ks.dtype))
        vs = vs.at[:, :, ids].set(vsb.astype(vs.dtype))
        return (k, v, ks, vs)

    @staticmethod
    def _gather_impl(kv, ids):
        """Gather blocks out of the cache into the universal transfer layout
        [L, nb, bs, nkv, hd] (block_to_universal analogue,
        lib/kvbm-kernels/cuda/tensor_kernels.cu:151).  Padded ids read the
        garbage block; the host slices them off.  An int8 cache returns
        (kb, vb, ksb, vsb) with the scale planes in [L, nb, bs, nkv]."""
        if len(kv) == 4:
            k, v, ks, vs = kv
        else:
            k, v = kv
            ks = None
        kb = jnp.transpose(k[:, :, ids], (0, 2, 4, 1, 3))
        vb = jnp.transpose(v[:, :, ids], (0, 2, 4, 1, 3))
        if ks is None:
            return kb, vb
        ksb = jnp.transpose(ks[:, :, ids], (0, 2, 3, 1))
        vsb = jnp.transpose(vs[:, :, ids], (0, 2, 3, 1))
        return kb, vb, ksb, vsb

    @staticmethod
    def _prefill_impl(family, model_cfg, params, kv, tokens, positions,
                      block_table, ctx_len, true_len, seed, temp, top_k,
                      top_p, lora_bank=None, lidx=None, lanes=None,
                      mesh=None):
        """`lanes`: the scheduler's lane of the sequence, for a family
        whose pools are addressed by lane (`KV_LANE_ADDRESSED`).  `mesh`
        rides to a family whose prefill read is a kernel that runs per
        shard under tp (models/deepseek.py), where the signature takes
        it."""
        lora_kw = ({"lora_bank": lora_bank, "adapter_idx": lidx}
                   if lora_bank is not None else {})
        if lanes is not None:
            lora_kw["lanes"] = lanes
        if "mesh" in inspect.signature(family.prefill).parameters:
            lora_kw["mesh"] = mesh
        logits, kv = family.prefill(
            params, model_cfg, kv, tokens, positions, block_table,
            ctx_len, true_len, **lora_kw,
        )
        tok = sample_tokens(
            logits[None], seed[None], jnp.zeros((1,), jnp.int32),
            temp[None], top_k[None], top_p[None],
        )[0]
        return tok, kv

    @staticmethod
    def _prefill_ring_impl(family, model_cfg, mesh, params, kv, toks,
                           positions, block_table, true_len, seed, temp,
                           top_k, top_p):
        """One-shot sequence-parallel prefill + first-token sample (the
        sp analogue of _prefill_impl; ring attention shards the O(T^2)
        attention over the mesh's sp axis)."""
        logits, kv = family.prefill_ring(
            params, model_cfg, kv, toks, positions, block_table,
            true_len, mesh=mesh,
        )
        tok = sample_tokens(
            logits[None], seed[None], jnp.zeros((1,), jnp.int32),
            temp[None], top_k[None], top_p[None],
        )[0]
        return tok, kv

    @staticmethod
    def _prefill_batched_impl(family, model_cfg, params, kv, toks,
                              positions, tables, ctx_lens, true_lens,
                              seeds, temps, top_ks, top_ps,
                              lora_bank=None, lidx=None, lanes=None,
                              mesh=None):
        """Multi-sequence chunked prefill (family prefill_batched):
        concurrent arrivals share one program instead of serializing B=1
        chunks.  First tokens are sampled per row; rows whose prompt is not
        finished this chunk have their sample discarded by the host.
        `mesh` as for `_prefill_impl`."""
        lora_kw = ({"lora_bank": lora_bank, "adapter_idx": lidx}
                   if lora_bank is not None else {})
        if lanes is not None:
            lora_kw["lanes"] = lanes
        if "mesh" in inspect.signature(family.prefill_batched).parameters:
            lora_kw["mesh"] = mesh
        logits, kv = family.prefill_batched(
            params, model_cfg, kv, toks, positions, tables,
            ctx_lens, true_lens, **lora_kw,
        )
        tok = sample_tokens(
            logits, seeds, jnp.zeros(seeds.shape, jnp.int32), temps,
            top_ks, top_ps,
        )
        return tok, kv

    @staticmethod
    def _prefill_packed_impl(family, model_cfg, mesh, params, kv, toks,
                             positions, seg_ids, tables, last_idx, valid,
                             seeds, temps, top_ks, top_ps,
                             lora_bank=None, lidx=None, lanes=None):
        """Packed multi-sequence chunked prefill (family prefill_packed):
        co-scheduled prompts/chunks run as ONE padding-free token stream
        with segment ids.  First tokens are sampled per segment row; rows
        whose prompt is not finished this chunk have their sample
        discarded by the host.  `mesh` rides to the attention op for the
        Pallas packed kernel's tp shard_map (like _decode_impl)."""
        lora_kw = ({"lora_bank": lora_bank, "adapter_idx": lidx}
                   if lora_bank is not None else {})
        if lanes is not None:
            lora_kw["lanes"] = lanes
        logits, kv = family.prefill_packed(
            params, model_cfg, kv, toks, positions, seg_ids, tables,
            last_idx, valid, mesh=mesh, **lora_kw,
        )
        tok = sample_tokens(
            logits, seeds, jnp.zeros(seeds.shape, jnp.int32), temps,
            top_ks, top_ps,
        )
        return tok, kv

    @staticmethod
    def _spec_verify_impl(family, model_cfg, mesh, params, kv, toks,
                          positions, seg_ids, tables, valid, temps_t):
        """Packed multi-token verification (spec/): every speculating
        sequence's row [last_token, d1..dk] scored in ONE padding-free
        segment-id program (family spec_verify_packed over
        ops/packed_prefill.py), draft-position KV written in place.
        Returns per-position top-CAP candidate ids + temperature-scaled
        logits and the full-vocab logsumexp of the scaled logits — the
        exact ingredients of sampler.py's masked-window categorical, so
        the host-side acceptance test (sampler.spec_accept_tokens) draws
        against the true target distribution."""
        from .sampler import CAP

        logits, kv = family.spec_verify_packed(
            params, model_cfg, kv, toks, positions, seg_ids, tables,
            valid, mesh=mesh,
        )
        scaled = logits / jnp.maximum(temps_t, 1e-6)[:, None]
        vals, ids = jax.lax.top_k(scaled, CAP)
        lse = jax.scipy.special.logsumexp(scaled, axis=-1)
        return ids, vals, lse, kv

    def apply_step(self, kind: str, a: Dict[str, np.ndarray]) -> None:
        """Multi-host follower: execute one broadcast step descriptor —
        the exact jit call the leader ran, on this process's local shards
        (parallel/multihost.py).  Sampled tokens are discarded; only the
        KV/weights state evolution matters on followers."""
        # lora args mirror the leader's calls exactly: when the bank
        # exists both sides pass (bank, lidx) — a one-sided lora arg would
        # compile a DIFFERENT program and desynchronize the collective
        # schedule
        if kind == "prefill_batch":
            lora = ((self.lora_bank, jnp.asarray(a["lidx"]))
                    if self.lora_bank is not None else (None, None))
            _, self.kv = self._jit_prefill_batched(
                self.params, self.kv,
                jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
                jnp.asarray(a["tables"]), jnp.asarray(a["ctx_lens"]),
                jnp.asarray(a["true_lens"]), jnp.asarray(a["seeds"]),
                jnp.asarray(a["temps"]), jnp.asarray(a["top_ks"]),
                jnp.asarray(a["top_ps"]), *lora,
                jnp.asarray(a["lanes"]) if "lanes" in a else None,
            )
        elif kind == "prefill_packed":
            lora = ((self.lora_bank, jnp.asarray(a["lidx"]))
                    if self.lora_bank is not None else (None, None))
            _, self.kv = self._jit_prefill_packed(
                self.params, self.kv,
                jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
                jnp.asarray(a["seg_ids"]), jnp.asarray(a["tables"]),
                jnp.asarray(a["last_idx"]), jnp.asarray(a["valid"]),
                jnp.asarray(a["seeds"]), jnp.asarray(a["temps"]),
                jnp.asarray(a["top_ks"]), jnp.asarray(a["top_ps"]), *lora,
                jnp.asarray(a["lanes"]) if "lanes" in a else None,
            )
        elif kind == "prefill":
            lora = ((self.lora_bank, jnp.int32(a["lidx"]))
                    if self.lora_bank is not None else (None, None))
            _, self.kv = self._jit_prefill(
                self.params, self.kv,
                jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
                jnp.asarray(a["block_table"]),
                jnp.int32(a["pos"]), jnp.int32(a["chunk"]),
                jnp.int32(a["seed"]), jnp.float32(a["temp"]),
                jnp.int32(a["top_k"]), jnp.float32(a["top_p"]), *lora,
                jnp.int32(a["lanes"]) if "lanes" in a else None,
            )
        elif kind == "decode_topk":
            # guided candidate step: same collective program, result is
            # the leader's to consume
            _, _, self.kv = self._topk_jit()(
                self.params, self.kv, jnp.asarray(a["tokens"]),
                jnp.asarray(a["positions"]), jnp.asarray(a["tables"]),
                jnp.asarray(a["ctx_lens"]), jnp.asarray(a["valid"]),
            )
        elif kind == "decode_topk_wide":
            # widened-M retry: the position's KV rewrite is value-identical
            _, _, self.kv = self._topk_wide_jit()(
                self.params, self.kv, jnp.asarray(a["tokens"]),
                jnp.asarray(a["positions"]), jnp.asarray(a["tables"]),
                jnp.asarray(a["ctx_lens"]), jnp.asarray(a["valid"]),
            )
        elif kind == "spec_verify":
            # speculative verification: the acceptance decision is the
            # leader's; followers only need the identical KV evolution
            _, _, _, self.kv = self._jit_spec_verify(
                self.params, self.kv,
                jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
                jnp.asarray(a["seg_ids"]), jnp.asarray(a["tables"]),
                jnp.asarray(a["valid"]), jnp.asarray(a["temps_t"]),
            )
        elif kind == "prefill_ring":
            _, self.kv = self._jit_prefill_ring(
                self.params, self.kv, jnp.asarray(a["toks"]),
                jnp.asarray(a["positions"]),
                jnp.asarray(a["block_table"]),
                jnp.int32(a["true_len"]), jnp.int32(a["seed"]),
                jnp.float32(a["temp"]), jnp.int32(a["top_k"]),
                jnp.float32(a["top_p"]),
            )
        elif kind == "lora_write":
            from ..lora.bank import write_adapter

            tensors = {k: v for k, v in a.items() if k != "slot"}
            self.lora_bank = write_adapter(self.lora_bank, int(a["slot"]),
                                           tensors)
        elif kind == "embed":
            # read-only, but a collective program every process must run
            self._run_embed(np.asarray(a["toks"]), int(a["true_len"]))
        elif kind in ("decode", "decode_multi"):
            # _dispatch_decode keeps the follower's device token chain
            # symmetric with the leader's (use_chain lanes resolve to the
            # follower's own previous burst, which is value-identical).
            # Adaptive fusion: the leader's burst size rides the
            # descriptor (falling back to the full fusion for streams
            # from pre-adaptive leaders) — the follower must dispatch the
            # SAME (greedy, k) program or the collective schedule forks.
            k = (int(a.get("k", self.config.decode_fused_steps))
                 if kind == "decode_multi" else 1)
            self._dispatch_decode(k, a)
        elif kind == "decode_cont":
            # continuation bursts ship no arrays: the follower's own
            # device pack (persisted by its preceding full decode replay)
            # advances in-program, exactly like the leader's
            self._dispatch_decode_cont(int(a["k"]), int(a["advance"]),
                                       bool(int(a["greedy"])))
        elif kind == "gather":
            # read-only, but still a collective program every process of
            # the slice must execute (KVBM offload, parked-KV extraction);
            # the result is the leader's to consume
            self._jit_gather(self.kv, jnp.asarray(a["ids"]))
        elif kind == "inject":
            # KVBM onboard or disagg KV pull: payload rides the stream, so
            # followers need no tiers/transport of their own (int8 caches
            # add the ksb/vsb scale planes to the same descriptor)
            scales = ([jnp.asarray(a["ksb"]), jnp.asarray(a["vsb"])]
                      if "ksb" in a else [])
            self.kv = self._jit_inject(
                self.kv, jnp.asarray(a["kb"]), jnp.asarray(a["vb"]),
                jnp.asarray(a["ids"]), *scales,
            )
        else:
            raise ValueError(f"unknown step kind {kind!r}")

    def warmup_decode(self) -> None:
        """Compile every decode-program variant serving can reach — both
        burst sizes (k=1 interleaves with prefill, k=fused in steady
        state), greedy and sampled, full and continuation dispatch — so
        no first-request or mid-serving burst ever eats a 10s+ XLA
        compile (measured: a (greedy, k=1) variant compiling inside the
        serving window cost more than all other scheduler overhead
        combined).  Runs on the caller's thread; call before serving
        traffic (worker startup / bench warm phase).  Prefill buckets
        are NOT warmed here (one per bucket is admission-driven and the
        first request pays exactly one).

        Holds _step_lock for the whole dispatch+restore section: a
        request that reaches a live engine while warmup is still
        compiling starts the scheduler loop — an unlocked _sched_step
        then reads self.kv between two warmup dispatches that have
        already donated it (observed as "Array has been deleted" in
        _prefill_packed and a permanently dead loop).  Under the lock
        that step simply waits out warmup and sees a consistent engine.
        (The worker warms up before it serves any endpoint, so its
        health-check canary cannot be that request.)"""
        B = self.config.max_num_seqs
        zero = {
            "tokens": np.zeros(self._chain_shape(), np.int32),
            "use_chain": np.zeros(B, bool),
            "positions": np.zeros(B, np.int32),
            "tables": np.zeros((B, self.config.max_blocks_per_seq),
                               np.int32),
            "ctx_lens": np.ones(B, np.int32),
            "seeds": np.zeros(B, np.int32),
            "steps": np.ones(B, np.int32),
            "top_ks": np.zeros(B, np.int32),
            "top_ps": np.ones(B, np.float32),
            "valid": np.zeros(B, bool),  # nothing real decodes
        }
        if self.lora_bank is not None:
            zero["lidx"] = np.zeros(B, np.int32)
        # every fusion-ladder rung (adaptive bursts ramp through all of
        # them) — a rung missing here is a mid-serving compile later
        ks = self._fuse_ladder()
        with self._step_lock:
            chain0, desc0, last0 = (self._chain_tokens, self._dev_desc,
                                    self._last_desc)
            for greedy in (True, False):
                a = dict(zero, temps=np.full(
                    B, 0.0 if greedy else 0.7, np.float32))
                for k in ks:
                    self._dispatch_decode(k, a)
                    self._dispatch_decode_cont(k, k, greedy)
            jax.block_until_ready(self.kv)
            # warmup bursts wrote nothing (valid all-false) but did
            # advance the chain/descriptor state machinery: reset it
            self._chain_tokens, self._dev_desc, self._last_desc = (
                chain0, desc0, last0)

    # -- request entry ----------------------------------------------------
    def start(self) -> None:
        if self._task is None:
            self._loop_ref = asyncio.get_running_loop()
            self._task = asyncio.create_task(self._loop())

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._task is not None:
            self._task.cancel()
            self._task = None
        self._fail_all_streams()
        # quiesce: a cancelled loop task does not stop a _sched_step
        # already running in its thread (it may be mid-write into the G3
        # dir whose ownership kvbm.close() releases), and what it
        # dispatched is still on the device
        await asyncio.to_thread(self._drain_device)
        if self.kvbm is not None:
            self.kvbm.close()

    def _drain_device(self) -> None:
        """Wait out the running step and everything it dispatched, then
        drop the unread bursts and deferred first-token readbacks (their
        streams are dead).  Each holds a device-to-host copy that starts
        when its program ends; a process that tore the runtime down
        under such a copy died in it (SIGSEGV in CopyToLiteralAsync
        after the result line, with seconds of 2048-token prefill
        programs queued ahead of the last burst: my chip run, PR 33)."""
        with self._step_lock:
            pending = [e["burst"] for e in self._inflight] \
                + [e["tok"] for e in self._pending_first]
            self._inflight.clear()
            self._pending_first.clear()
            for arr in pending:
                try:
                    # dynlint: disable=DYN011 shutdown, after the last step: the wait is the point
                    np.asarray(arr)
                except Exception:  # noqa: BLE001 a failed program's output
                    pass
            # dynlint: disable=DYN011 shutdown, after the last step: the wait is the point
            jax.block_until_ready(self.kv)

    def _fail_all_streams(
        self,
        error: str = "worker engine error: engine loop failed or shut down",
    ) -> None:
        """Terminate every in-flight stream (shutdown or loop crash)."""
        err = LLMEngineOutput(finish_reason="error", error=error)
        with self._qlock:
            stuck = list(self.waiting) + [
                s for s in self._slots if s is not None
            ]
            self.waiting.clear()
        for slot in stuck:
            if not slot.finished:
                slot.finished = True
                # finished=True makes the consumer's teardown skip the
                # cancel request, so ask for it here: if the scheduler is
                # still alive (drain_abort — the loop keeps running),
                # _process_cancellations reaps the slot and frees its KV
                # blocks; a process that stays up after a drain RPC must
                # not leak every aborted slot.  On the loop-crash path
                # nobody processes this, which is moot — close() tears
                # the whole cache down.
                slot.cancel_requested = True
                slot.out_q.put_nowait(err)

    def drain_abort(self) -> None:
        """Graceful-drain deadline: error every in-flight stream with
        the migratable "worker draining" marker so the frontend replays
        each request (token-replay migration) on a surviving worker
        with no client-visible failure."""
        self.draining = True
        # flight recorder: the last N spans are the timeline that led to
        # the abort — dump them before the streams are torn down
        obs.flight_dump("drain_abort")
        self._fail_all_streams(error=DRAIN_ABORT)
        self._wake.set()

    def set_slo_burn(self, burn: float) -> None:
        """SLA-aware admission input: the worst SLO error-budget burn
        rate the frontends currently report (obs/slo.py burn_rates; fed
        by the worker's slo_metrics subscription).  Any-thread safe (two
        atomic float stores); consumed by _prefill_dispatch, where a
        burn above config.slo_yield_burn makes prefill chunks yield
        budget to decode until ITL recovers."""
        self._slo_burn = float(burn)
        self._slo_burn_t = time.monotonic()

    def _effective_slo_burn(self) -> float:
        """The last reported burn, or 0.0 once it has gone stale (a dead
        frontend / disabled SLO plane must not throttle prefill
        forever)."""
        if time.monotonic() - self._slo_burn_t > \
                self.config.slo_burn_stale_s:
            return 0.0
        return self._slo_burn

    @property
    def num_active_seqs(self) -> int:
        return sum(s is not None for s in self._slots) + len(self.waiting)

    def kv_usage(self) -> float:
        return self.allocator.usage()

    def kv_occupancy(self) -> Dict[str, Dict[str, int]]:
        """Block occupancy per storage tier, for the worker's /metrics
        gauges: g1 = the HBM allocator (id 0 is the garbage block, so
        capacity is num_blocks - 1), g2..g4 = the KVBM tiers when
        enabled (kvbm/manager.py occupancy)."""
        a = self.allocator
        usable = a.num_blocks - 1
        out: Dict[str, Dict[str, int]] = {"g1": {
            "used": usable - a.num_free, "free": a.num_free,
            "capacity": usable, "evictable": a.num_evictable,
        }}
        if self.kvbm is not None:
            out.update(self.kvbm.occupancy())
        return out

    def kv_block_bytes(self) -> int:
        """Host-tier bytes one block's payload moves when onboarded
        (all cache components, per physical block) — the numerator of
        the worker's published per-tier onboard costs."""
        try:
            return int(sum(a.nbytes for a in self.kv)
                       // max(1, self.config.num_blocks))
        except Exception:
            return 0

    async def sweep_kvbm_g4(self) -> int:
        """One lineage-driven GC pass over the shared G4 store (called
        from the worker's load loop on a slow cadence, never from the
        scheduler thread — the sweep lists a shared directory).  Hot
        lineages get their TTL clock renewed, dead lineages reap early,
        the rest age by TTL (kvbm/residency.py).  Reaped hashes are
        folded through the consolidator ON the scheduler thread so the
        engine's cross-tier books drop them too — a later re-spill of
        the same hash must re-emit stored(g4) or routers never re-learn
        the blob."""
        if self.kvbm is None or self.kvbm.g4 is None:
            return 0
        if self.kvbm.breaker.state("g4") == "open":
            # the tier is dark: hammering a dead mount from the sweep
            # only delays the half-open probe's clean read
            return 0
        from ..kvbm.residency import LineageResidency

        res = LineageResidency(self.kv_ledger, pool=self.kvbm.g4)
        try:
            swept = await asyncio.to_thread(self.kvbm.g4.sweep, None, res)
        except OSError:
            logger.warning("G4 residency sweep failed", exc_info=True)
            return 0
        if swept:
            def emit() -> int:
                self._emit_tier_events([([], list(swept), "g4")])
                return len(swept)

            await self._call_on_scheduler(emit)
        return len(swept)

    # -- KV integrity (checksummed cache fabric) ---------------------------
    def _note_kv_corruption(self, tier: str, h: Optional[int]) -> None:
        """One checksum-failed consume anywhere in the fabric (G3 pool,
        G4 object store, remote pull, disagg frame): count it for
        dynamo_kv_integrity_failures_total{tier,action="quarantine"} and
        attribute it in the KV ledger (violation kind `corrupt`, flight
        snapshot on each tier's first).  The caller already quarantined
        the bytes and degraded to a miss — serving falls back to
        recompute with byte-identical output, so this hook is purely
        forensic and must never raise."""
        try:
            key = (tier, "quarantine")
            self.kv_integrity[key] = self.kv_integrity.get(key, 0) + 1
            if self.kv_ledger is not None:
                self.kv_ledger.corruption(tier, h)
        except Exception:
            logger.warning("kv corruption attribution failed",
                           exc_info=True)

    def kv_integrity_counters(self) -> Dict[Tuple[str, str], int]:
        """(tier, action) -> count rows for the integrity-failure
        counter: quarantines recorded here + the KVBM manager's I/O
        timeouts/errors."""
        out = dict(self.kv_integrity)
        if self.kvbm is not None:
            for k, v in self.kvbm.io_failure_counters().items():
                out[k] = out.get(k, 0) + v
        return out

    # -- KV ledger audit (obs/kv_ledger.py) --------------------------------
    def _audit_ledger_locked(self, where: str = "step") -> dict:
        """One reconciliation sweep: the ledger's books vs the
        allocator's free-list/refcounts, the scheduler's live slot
        view, and the KVBM pool manifests.  Caller holds _step_lock
        (or IS the step)."""
        led = self.kv_ledger
        if led is None:
            return {}
        live = [self._seq_id(s) for s in self._slots if s is not None]
        with self._qlock:
            live += [self._seq_id(s) for s in self.waiting]
        parked = [p.seq_id for p in self._parked.values()]
        viol = led.audit_allocator(self.allocator, live, parked)
        viol += led.audit_kvbm(self.kvbm)
        return led.finish_audit(viol, where=where)

    def _audit_ledger(self, where: str = "on_demand") -> dict:
        with self._step_lock:
            if self._closed:
                return {}
            return self._audit_ledger_locked(where)

    async def audit_kv(self) -> dict:
        """On-demand reconciliation (the /debug/kv handler's entry
        point); safe on an idle engine — takes the step lock off the
        event loop."""
        if self.kv_ledger is None:
            return {}
        return await asyncio.to_thread(self._audit_ledger)

    @property
    def spec_enabled(self) -> bool:
        """Speculative decoding actually active: the config asked for it
        AND the family supports packed verification (MLA falls back to
        plain decode in v1) — what the worker should advertise, which
        the raw config value alone cannot tell."""
        return self._spec_ok

    async def generate(
        self, request: PreprocessedRequest, token=None
    ) -> AsyncIterator[LLMEngineOutput]:
        self.start()
        if self.draining:
            # reject before admission with the migratable marker: the
            # router may still dispatch here in the window between lease
            # withdrawal and its watch converging
            yield LLMEngineOutput(finish_reason="error", error=DRAIN_REJECT)
            return
        if self._task is not None and self._task.done():
            # the scheduler loop died (crash injection or a real bug):
            # fail fast instead of parking the request forever — the
            # marker classifies as migratable so the frontend replays it
            yield LLMEngineOutput(
                finish_reason="error",
                error="worker engine error: engine loop crashed",
            )
            return
        if len(request.token_ids) >= self.config.max_context:
            yield LLMEngineOutput(
                finish_reason="error",
                error=f"prompt is {len(request.token_ids)} tokens; engine "
                      f"max_context is {self.config.max_context}",
            )
            return
        if self._gen_block:
            refused = self._gen_block_refusal(request)
            if refused:
                yield LLMEngineOutput(finish_reason="error", error=refused)
                return
        # after validation: rejected requests cost no engine work and must
        # not inflate the SLA planner's arrival rate / mean ISL
        self.metrics["requests"] += 1
        self.metrics["prompt_tokens"] += len(request.token_ids)
        dp = request.disaggregated_params
        want_pull = dp is not None and dp.get("engine") == "jax"
        if want_pull and self.kv_pull_fn is None:
            logger.warning("disaggregated_params but no kv_pull_fn; "
                           "falling back to local prefill")
            want_pull = False
        if self.kvbm is not None and self.remote_kvbm_fetch is not None:
            try:
                await self._remote_prefetch(request)
            except Exception:
                # remote warm-up is an optimization; local prefill is the
                # always-correct fallback
                logger.warning("remote KVBM prefetch failed for %s",
                               request.request_id, exc_info=True)
        lora_idx = 0
        if request.lora_name:
            if self.lora_bank is None:
                # serving the base model labeled as the adapter would be
                # silently wrong output; fail loud so the frontend
                # migrates / surfaces it
                yield LLMEngineOutput(
                    finish_reason="error",
                    error=f"lora adapter {request.lora_name!r} requested "
                          "but this worker has LoRA disabled "
                          "(lora_max_adapters=0)",
                )
                return
            try:
                lora_idx = await self._resolve_lora(request.lora_name)
            except Exception as e:
                yield LLMEngineOutput(
                    finish_reason="error",
                    error=f"lora adapter {request.lora_name!r}: {e}",
                )
                return
        slot = _Slot(
            index=-1,
            request=request,
            seq=TokenBlockSequence(
                request.token_ids, self.config.block_size,
                salt=request_salt(request.lora_name,
                                  request.media_hashes),
            ),
            out_q=asyncio.Queue(),
            block_table=np.zeros(self.config.max_blocks_per_seq, np.int32),
            sampling_seed=(
                request.sampling.seed
                if request.sampling.seed is not None
                # stable across processes (unlike hash(): PYTHONHASHSEED)
                # so a replayed/migrated request samples the same stream
                else zlib.crc32(request.request_id.encode()) & 0x7FFFFFFF
            ),
            lora_idx=lora_idx,
            enqueued_t=time.monotonic(),
            gen_block=self._gen_block,
        )
        from ..protocols.llm import DISAGG_ANNOTATION

        slot.disagg_prefill = DISAGG_ANNOTATION in (request.annotations or [])
        if request.sampling.guided_json is not None:
            from ..guided import JsonSchemaGuide

            slot.guide = JsonSchemaGuide(request.sampling.guided_json)
        pull_task = None
        if want_pull:
            slot.pulling = True
            slot.admitted = asyncio.Event()
        if self.kv_ledger is not None:
            # ledger tape entries for this sequence join the request's
            # distributed trace (frontend-minted traceparent annotation)
            self.kv_ledger.bind_seq(
                request.request_id,
                obs.trace_id_from_annotations(request.annotations))
        with self._qlock:
            slot.queue_pos = len(self.waiting)
            self.waiting.append(slot)
        if lora_idx:
            # enqueued: the waiting/_slots scan now holds the reference
            self._lora_pins[lora_idx] -= 1
        self._wake.set()
        if want_pull:
            # streaming pull: chunk injects interleave with decode steps;
            # on any failure the slot falls back to local prefill
            pull_task = asyncio.create_task(self._stream_pull(slot, dp))
        from ..runtime.aio import CANCELLED, next_or_cancel

        try:
            while True:
                item = await next_or_cancel(
                    slot.out_q,
                    token.stopped_event if token is not None else None,
                )
                if item is CANCELLED:
                    slot.cancel_requested = True
                    self._wake.set()
                    yield LLMEngineOutput(finish_reason="cancelled")
                    return
                yield item
                if item.finish_reason is not None:
                    return
        finally:
            if pull_task is not None and not pull_task.done():
                pull_task.cancel()
            if not slot.finished:
                # actual teardown happens on the scheduler thread
                slot.cancel_requested = True
                self._wake.set()

    def _gen_block_refusal(self, request: PreprocessedRequest
                           ) -> Optional[str]:
        """What a family that generates by blocks cannot serve yet, said
        at admission: each needs ONE distribution a token, and a block's
        positions are filled out of order over several passes."""
        from ..protocols.llm import DISAGG_ANNOTATION

        sa = request.sampling
        what = [name for name, asked in (
            ("guided decoding", sa.guided_json is not None),
            ("frequency / presence penalties",
             bool(sa.frequency_penalty or sa.presence_penalty)),
            ("disaggregated prefill",
             DISAGG_ANNOTATION in (request.annotations or [])
             or request.disaggregated_params is not None),
        ) if asked]
        if not what:
            return None
        return (f"model family {type(self.model_cfg).__name__} generates "
                f"by blocks of {self._gen_block} positions and does not "
                f"carry {', '.join(what)} yet")

    def _process_cancellations(self) -> None:
        """Runs on the scheduler thread at the top of every step."""
        with self._qlock:
            for slot in list(self.waiting):
                if slot.cancel_requested:
                    self.waiting.remove(slot)
                    slot.finished = True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.cancel_requested:
                slot.finished = True
                self._slots[i] = None
                self._emit_events(self.allocator.free(self._seq_id(slot)))
                # membership changed mid-stretch: de-fuse so the freed
                # lane's capacity returns to useful work within a short
                # burst (adaptive fusion ramps back up afterwards)
                self._decode_only_run = 0

    def _seq_id(self, slot: _Slot) -> str:
        return slot.request.request_id

    def _emit_events(self, res, tier: str = "g1") -> None:
        """Thread-safe KV event emission (called from the scheduler thread).

        Mutations are first folded through the cross-tier consolidator so
        routers see net PER-TIER residency (stored on entering a tier,
        removed on leaving it — duplicate same-tier mutations net out; the
        tier-aware index derives union ownership router-side).  The sink
        may be synchronous (preferred: enqueue +
        serialized publish, see KvEventPublisher.enqueue_batch) or an async
        callable.  Either way it is invoked on the loop thread via
        call_soon_threadsafe, whose FIFO callback ordering keeps wire order
        equal to mutation order."""
        if res is None:
            return
        stored = list(getattr(res, "stored", []))
        removed = list(getattr(res, "removed", []))
        if not (stored or removed):
            return
        if tier != "g1" and self.kv_ledger is not None:
            # KVBM tier membership for the ledger auditor (pre-netting:
            # the ledger reconciles per-tier against the pool manifests;
            # g1 transitions are recorded inside the allocator itself)
            self.kv_ledger.tier_batch(stored, removed, tier)
        # G1 evictions of blocks that were offloaded must not drop the G2/G3
        # copy — the consolidator handles the netting; the pools themselves
        # only drop on their own capacity pressure.
        net_stored, net_removed, _ = self._consolidator.apply(
            stored, removed, tier
        )
        self._dispatch_events(net_stored, net_removed, tier)

    def _emit_tier_events(self, batches) -> None:
        """Emit [(stored, removed, tier), ...] batches from the KVBM manager
        (already per-tier; still netted through the consolidator)."""
        for stored, removed, tier in batches:
            self._emit_events(
                SimpleNamespace(stored=stored, removed=removed), tier=tier
            )

    def _dispatch_events(self, stored, removed, tier: str) -> None:
        if self.kv_event_sink is None or not (stored or removed):
            return
        sink = self.kv_event_sink
        takes_tier = self._sink_takes_tier

        def call():
            return sink(stored, removed, tier) if takes_tier \
                else sink(stored, removed)

        def dispatch():
            r = call()
            if inspect.isawaitable(r):
                from ..runtime.aio import spawn_retained

                spawn_retained(r, self._event_tasks)

        if self._loop_ref is not None:
            self._loop_ref.call_soon_threadsafe(dispatch)
        else:
            # pre-start only (no loop yet): nothing is routing yet, so an
            # async sink's events can be dropped safely
            r = call()
            if inspect.isawaitable(r):
                r.close()

    async def _resolve_lora(self, name: str) -> int:
        """Map an adapter name to its bank slot, lazily loading from
        lora_dir on first use.  Eviction is LRU among adapters not
        referenced by any active/waiting sequence OR pinned by a resolved
        request that hasn't enqueued yet (the pin closes the window where
        an eviction could silently swap the adapter under a request).
        All registry mutations run on the scheduler thread; the file load
        runs in an executor so streams never stall on it.
        Ref: lora/cache.rs + controller.rs, collapsed into lazy
        load-on-first-request (routing.py explains why no load RPCs)."""

        def lookup() -> Optional[int]:
            idx = self._lora_slots.get(name)
            if idx is not None:
                self._lora_lru.remove(name)
                self._lora_lru.append(name)
                self._lora_pins[idx] = self._lora_pins.get(idx, 0) + 1
            return idx

        idx = await self._call_on_scheduler(lookup)
        if idx is not None:
            return idx
        if self._lora_source is None:
            raise ValueError("unknown adapter (engine has no lora_dir)")
        loop = asyncio.get_running_loop()
        adapter = await loop.run_in_executor(
            None,
            lambda: self._lora_source.load(
                name, self.model_cfg.n_layers
            ).padded_to(self.config.lora_rank))

        def install() -> int:
            existing = self._lora_slots.get(name)
            if existing is not None:  # raced with another request
                self._lora_pins[existing] = \
                    self._lora_pins.get(existing, 0) + 1
                return existing
            in_use = {s.lora_idx for s in self._slots if s is not None}
            with self._qlock:
                in_use |= {s.lora_idx for s in self.waiting}
            in_use |= {i for i, c in self._lora_pins.items() if c > 0}
            free = (set(range(1, self.config.lora_max_adapters + 1))
                    - set(self._lora_slots.values()))
            if free:
                slot = min(free)
            else:
                victim = next(
                    (n for n in self._lora_lru
                     if self._lora_slots[n] not in in_use), None)
                if victim is None:
                    raise RuntimeError(
                        "all adapter slots are referenced by active "
                        "sequences; raise lora_max_adapters")
                slot = self._lora_slots.pop(victim)
                self._lora_lru.remove(victim)
            from ..lora.bank import write_adapter

            if self.step_sink is not None:
                # bank mutations ride the step stream: followers apply the
                # same write so every process's adapter bank (a jit input)
                # stays bit-identical with the leader's
                self.step_sink("lora_write", {
                    "slot": np.int32(slot),
                    # dynlint: disable=DYN011 adapter tensors are host-loaded numpy, not device arrays
                    **{k: np.asarray(v) for k, v in
                       adapter.tensors.items()},
                })
            self.lora_bank = write_adapter(self.lora_bank, slot,
                                           adapter.tensors)
            self._lora_slots[name] = slot
            self._lora_lru.append(name)
            self._lora_pins[slot] = self._lora_pins.get(slot, 0) + 1
            logger.info("lora adapter %r loaded into slot %d (rank %d)",
                        name, slot, adapter.rank)
            return slot

        return await self._call_on_scheduler(install)

    def _call_on_scheduler(self, fn) -> asyncio.Future:
        """Run `fn()` between scheduler steps (the allocator and KV cache are
        owned by the scheduler; cross-thread access would race donation)."""
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._sched_calls.append((fn, fut))
        self._wake.set()
        if self._task is None or self._task.done():
            # no live loop to drain for us (unstarted, crashed, or closed)
            self._drain_sched_calls()
        return fut

    def _drain_sched_calls(self) -> None:
        while self._sched_calls:
            fn, fut = self._sched_calls.pop(0)
            try:
                result = fn()
            except Exception as e:  # surface to the caller
                err = e

                def set_exc(f=fut, err=err):
                    if not f.done():
                        f.set_exception(err)

                if self._loop_ref is not None:
                    self._loop_ref.call_soon_threadsafe(set_exc)
                else:
                    set_exc()
            else:
                if self._loop_ref is not None:
                    self._loop_ref.call_soon_threadsafe(
                        _set_result_safe, fut, result
                    )
                else:
                    _set_result_safe(fut, result)

    @property
    def supports_embedding(self) -> bool:
        return hasattr(self.family, "embed_text")

    async def embed(self, token_ids: List[int]) -> np.ndarray:
        """Pooled text embedding (family embed_text), bucketed like
        prefill so repeat lengths hit the jit cache."""
        if not self.supports_embedding:
            raise RuntimeError(
                f"model family {self.family.__name__} has no embed_text")
        if len(token_ids) > self.config.prefill_buckets[-1]:
            raise ValueError(
                f"input is {len(token_ids)} tokens; embedding max is "
                f"{self.config.prefill_buckets[-1]}")
        bucket = self._bucket_for(len(token_ids))
        toks = np.zeros(bucket, np.int32)
        toks[: len(token_ids)] = token_ids
        true_len = len(token_ids)

        def run():
            if self.step_sink is not None:
                # a collective program every process of the slice must
                # execute — embed rides the step stream like everything
                # else (the result is the leader's to consume)
                self.step_sink("embed", {"toks": toks,
                                         "true_len": np.int32(true_len)})
            return self._run_embed(toks, true_len)

        self.start()
        return await self._call_on_scheduler(run)

    def _run_embed(self, toks: np.ndarray, true_len: int) -> np.ndarray:
        jit = getattr(self, "_jit_embed", None)
        if jit is None:
            w = self.compile_watch
            jit = self._jit_embed = w.wrap(jax.jit(
                w.named(partial(self.family.embed_text, self.params,
                                self.model_cfg), "embed")), "embed",
                tokens_of=lambda a: a[0].shape[0])
        with self.mesh:
            vec = jit(jnp.asarray(toks), jnp.int32(true_len))
            with self._phase("device_wait", what="embed_fetch"):
                out = np.asarray(vec, np.float32)
            return out

    async def clear_kv_blocks(self) -> int:
        """Drop the reusable prefix cache (active sequences keep theirs)."""
        def do_clear():
            removed = self.allocator.clear_cached()
            # emit from the scheduler thread so these removals stay ordered
            # against stores from the next step (a later stored(H) for a
            # re-admitted prefix must reach the wire after this removed(H))
            self._emit_events(SimpleNamespace(stored=[], removed=removed))
            if self.kvbm is not None:
                self._emit_tier_events(self.kvbm.clear())
            return removed

        removed = await self._call_on_scheduler(do_clear)
        return len(removed)

    # -- disaggregation: parked prefills + KV extraction -------------------
    def kv_wire_layout(self, n_blocks: int = 0):
        """This engine's KvLayout for wire headers/validation, derived from
        its OWN cache arrays (family-agnostic: GQA k==v shapes, MLA
        latent/rope-key pair with different head dims)."""
        from ..disagg.transfer import KvLayout

        k_cache, v_cache = self.kv[0], self.kv[1]
        return KvLayout(
            num_layers=k_cache.shape[0], num_blocks=n_blocks,
            block_size=self.config.block_size,
            kv_heads=k_cache.shape[1], head_dim=k_cache.shape[3],
            dtype=np.dtype(k_cache.dtype).name,
            tp=self.config.tp, dp=self.config.dp,
            head_dim_v=(v_cache.shape[3]
                        if v_cache.shape[3] != k_cache.shape[3] else 0),
            scales=is_quantized(self.kv),
        )

    def universal_shardings(self):
        """Per-component NamedShardings for universal-layout chunks on
        this engine's mesh: the cache's head-axis sharding moved to the
        universal head axis (data [L, nb, bs, nkv, hd]; int8 scale
        planes [L, nb, bs, nkv]).  Device-resident pulls land chunks
        here so inject consumes them without a host bounce.  Tuple arity
        matches the cache's (2 or 4)."""
        k_spec, v_spec = self.family.kv_cache_specs()
        # cache layout [L, H, NB, HD, BS] -> universal [L, NB, BS, H, HD];
        # MLA families use an empty spec (replicated latent cache)
        kh = k_spec[1] if len(k_spec) > 1 else None
        vh = v_spec[1] if len(v_spec) > 1 else None
        out = [NamedSharding(self.mesh, P(None, None, None, kh, None)),
               NamedSharding(self.mesh, P(None, None, None, vh, None))]
        if is_quantized(self.kv):
            out += [NamedSharding(self.mesh, P(None, None, None, kh)),
                    NamedSharding(self.mesh, P(None, None, None, vh))]
        return tuple(out)

    async def parked_info(self, request_id: str):
        """(n_blocks, prompt_len) of a parked prefill (pull 'open' op)."""

        def info():
            parked = self._parked.get(request_id)
            if parked is None:
                raise KeyError(f"no parked KV for request {request_id!r}")
            return len(parked.block_ids), parked.prompt_len

        return await self._call_on_scheduler(info)

    async def extract_parked_chunk(self, request_id: str, start: int,
                                   count: int, *, to_host: bool = True):
        """Gather blocks [start, start+count) of a parked prefill in the
        universal transfer layout — ONE scheduler op per chunk, so decode
        bursts interleave with a long extraction instead of stalling
        behind a whole-prompt gather (the round-3 ITL-spike finding).

        to_host=False keeps the gathered chunk device-resident for the
        device-to-device tiers (broker / transfer server)."""

        def gather():
            parked = self._parked.get(request_id)
            if parked is None:
                raise KeyError(f"no parked KV for request {request_id!r}")
            chunk_ids = parked.block_ids[start:start + count]
            if len(chunk_ids) != count:
                raise ValueError(
                    f"chunk [{start},{start + count}) out of range for "
                    f"{len(parked.block_ids)} parked blocks")
            ids = _pow2_ids(chunk_ids)
            if self.step_sink is not None:
                # reads are collective programs too: every process of the
                # slice must execute the same gather or it hangs
                self.step_sink("gather", {"ids": ids})
            arrs = self._jit_gather(self.kv, jnp.asarray(ids))
            # axis 1 is the block axis for every component (data AND the
            # int8 scale planes): slice the pow2 padding off uniformly
            arrs = tuple(a[:, :count] for a in arrs)
            if to_host:
                with self._phase("device_wait", what="parked_extract"):
                    out = tuple(np.asarray(a) for a in arrs)
                return out
            return arrs

        return await self._call_on_scheduler(gather)

    async def release_parked(self, request_id: str) -> None:
        def release():
            parked = self._parked.pop(request_id, None)
            if parked is not None:
                if self.kv_ledger is not None:
                    self.kv_ledger.unpark(parked.seq_id)
                self._emit_events(self.allocator.free(parked.seq_id))

        await self._call_on_scheduler(release)

    def _reap_parked(self) -> None:
        now = time.monotonic()
        for rid in [r for r, p in self._parked.items()
                    if now > p.expires_t]:
            logger.warning("parked KV for %s expired unpulled", rid)
            parked = self._parked.pop(rid)
            if self.kv_ledger is not None:
                self.kv_ledger.unpark(parked.seq_id)
            self._emit_events(self.allocator.free(parked.seq_id))

    # -- scheduler loop ---------------------------------------------------
    async def _loop(self) -> None:
        """The scheduler loop, on the event-loop thread.  Its wall time
        is tiled by three phases (obs.PhaseClock): `step` (on a pool
        thread), `hop` from a step's end to the next step's start, and
        `idle` while the engine stands empty."""
        try:
            work = await self._step_work()
            while not self._closed:
                if not work:
                    # nothing below yields between `_step_work`'s look at
                    # the queue and this clear: an enqueue cannot fall
                    # between them
                    self._wake.clear()
                    if not self._sched_calls:
                        await self._stand_empty()
                    work = await self._step_work()
                    continue
                await asyncio.to_thread(self._sched_step)
                # this thread's line of the hop (the counter is taken on
                # the step's thread: PhaseClock.step_opens)
                with self._phase("hop"):
                    self.metrics["steps"] += 1
                    await asyncio.sleep(0)  # yield to the event loop
                    work = await self._step_work()
        except asyncio.CancelledError:
            pass
        except Exception:
            logger.exception("engine loop crashed")
            obs.flight_dump("engine_crash")
            self._fail_all_streams()
            raise

    async def _step_work(self) -> bool:
        """Between two steps: run the queued scheduler calls, reap
        expired parked KV, and say whether a step has anything to do."""
        if self._sched_calls:
            # heavy calls (KV gathers) run off the event loop; no
            # scheduler step is in flight while we await this
            await asyncio.to_thread(self._drain_sched_calls)
        self._reap_parked()
        # a slot mid-pull has no step work of its own (its chunk
        # injects arrive as sched_calls, which set _wake): don't
        # hot-spin the step loop on its behalf — EXCEPT when its
        # cancellation is pending, which needs one step to reap
        # it (_process_cancellations); without that carve-out a
        # request cancelled mid-pull on an otherwise idle worker
        # held its KV blocks until unrelated traffic arrived
        return (any(s is not None
                    and (not s.pulling or s.cancel_requested)
                    for s in self._slots)
                or bool(self._inflight) or bool(self.waiting))

    async def _stand_empty(self) -> None:
        """The empty engine waits to be woken, under `idle`."""
        if self.kv_ledger is not None \
                and self.kv_ledger.audit_due(5.0):
            # idle-tick reconciliation: an idle worker's
            # books still get swept (leaks hide best in
            # caches nobody is touching)
            await asyncio.to_thread(self._audit_ledger, "idle")
        with self._phase("idle"):
            if self._parked:
                # wake periodically so the parked-KV TTL reaper runs
                # even on an otherwise idle worker
                try:
                    await asyncio.wait_for(self._wake.wait(), 5.0)
                except asyncio.TimeoutError:
                    pass
            else:
                await self._wake.wait()

    def _sched_step(self) -> None:
        """One scheduler iteration, entirely on the worker thread.

        The order (overlapped mode): READ BACK FIRST, ADMIT AFTER.  The
        step begins with what it must wait for (_read_back_first: the
        oldest burst at or over the pipeline depth, the previous step's
        deferred first tokens) and only then looks at what is new:
        cancellations, admission, one prefill program, the decode burst.
        A request that arrived while the thread was blocked is so
        admitted, and its first chunk dispatched, in the step in which
        the wait ends, AHEAD of that step's decode burst, which
        _fused_k then keeps at INTERLEAVE_BURST.  With admission before
        the wait (the order until PR 39) it was seen a step later and
        its chunk stood behind one more burst.  Lockstep mode has
        nothing in flight at the top of a step and is untouched.

        vLLM-style interleaving: admit any number of waiting requests
        (allocation only), run at most ONE budget-capped prefill chunk, then
        a decode step for every slot past prefill — so a long prompt never
        stalls active decodes for more than one chunk's compute
        (the head-of-line blocking the round-1 verdict called out).

        _step_lock lets close() wait out an in-flight step (cancelling the
        loop task does not stop an already-running thread) before releasing
        resources a step may be mid-write on, e.g. the G3 cache dir.  The
        _closed check under the lock closes the remaining window: a step
        whose thread started but had not yet acquired the lock when close()
        swept through must not touch the released resources."""
        with self._step_lock:
            if self._closed:
                return
            # chaos seam: crash ("fail") or wedge the scheduler on step
            # N — the loop's crash handler fails all streams with the
            # migratable worker-engine-error marker; a wedge is caught
            # by the canary (health_check.py)
            chaos.hit("engine.step", key=self.config.served_name)
            # phases (obs.PhaseClock): one `step` covering the iteration
            # and, inside it, phases that together cover its wall time —
            # `sched` over the host-only scheduling work, then the
            # dispatch phases, each opened where its work is.
            # Overlapped mode: when unread bursts are in flight the
            # device is still executing them, so this host scheduling
            # work is OVERLAPPED, not overhead — it reports as
            # `enqueue_ahead` (report.py excludes it from
            # sched_overhead_frac; the wall partition stays exact).
            with self._phase("step") as step:
                waited_from = self._read_back_first()
                overlapped = self._overlap and bool(self._inflight)
                with self._phase("enqueue_ahead" if overlapped
                                 else "sched"):
                    self._process_cancellations()
                    self._maybe_offload()
                    self._admit_waiting(waited_from)
                self._prefill_step()
                self._guided_step()
                self._spec_step()
                if any(s is not None and not s.prefilling
                       and not s.awaiting_first for s in self._slots):
                    self._decode_step()
                elif self._inflight:
                    # no dispatchable decode work: flush the pipeline
                    # tail so trailing tokens/finishes are delivered
                    # promptly
                    self._drain_inflight()
                led = self.kv_ledger
                if led is not None and led.audit_due():
                    # reconciliation sweep on the finish cadence (a
                    # request freed its blocks since the last audit) —
                    # the books are checked while the leak is one request
                    # old, not one incident old
                    with self._phase("audit"):
                        self._audit_ledger_locked("step")
                if step.tm is not None or obs.enabled():
                    # attrs are only worth computing when someone reads
                    step.set(active=sum(1 for s in self._slots
                                        if s is not None),
                             waiting=len(self.waiting))

    def _read_back_first(self) -> float:
        """The top of an overlapped step: make the blocking reads the
        step would make anyway, before anything new is looked at, in
        the order the device finishes the work.  The bursts at or over
        the pipeline depth, where a lane is past its prompt (a step
        whose lanes are all prefilling drains after its dispatch, as
        ever); then the previous step's deferred first tokens; at depth
        1 the one burst in flight went out AFTER that prefill and is
        read after it.  So one burst is left running (depth 2) while
        the host emits, admits and dispatches, and whatever arrived
        during the wait is met by this step's admission pass.  Returns
        the clock at which the wait began, 0.0 where there was nothing
        to wait for (always, in lockstep mode)."""
        depth = self._depth
        bursts = len(self._inflight) >= depth and any(
            s is not None and not s.prefilling for s in self._slots)
        if not (bursts or self._pending_first):
            return 0.0
        waited_from = time.monotonic()
        while bursts and len(self._inflight) >= max(depth, 2):
            self._process_oldest_burst()
        self._flush_pending_first()
        while bursts and len(self._inflight) >= depth:
            self._process_oldest_burst()
        return waited_from

    # -- distributed KVBM (kvbm/remote.py) ---------------------------------
    async def _remote_prefetch(self, request: PreprocessedRequest) -> None:
        """Pull this prompt's missing leading blocks from a peer's host
        cache and stage them into the LOCAL G2, so admission's existing
        G2 onboarding path finds them — no scheduler-thread changes.
        Racy local-presence checks are safe: the worst case is pulling a
        block that arrived locally meanwhile (the stage skips it)."""
        from ..tokens import compute_block_hashes_for_request

        hashes = compute_block_hashes_for_request(
            request.token_ids, self.config.block_size,
            lora_name=request.lora_name,
            media_hashes=request.media_hashes,
        )
        start = 0
        while start < len(hashes) and hashes[start] in self.kvbm:
            start += 1
        if start >= len(hashes):
            return
        blocks = await self.remote_kvbm_fetch(hashes[start:])
        if not blocks:
            return

        def stage() -> int:
            n = 0
            arity = len(self.kv)
            for h, *arrays in blocks:
                if h in self.kvbm:
                    continue
                if len(arrays) != arity:
                    # peer runs the other cache dtype (mixed fleet): its
                    # payload cannot scatter into this cache — skip, the
                    # leading-run contract makes the tail unusable too
                    break
                self._emit_tier_events(self.kvbm.offload(h, *arrays))
                n += 1
            return n

        staged = await self._call_on_scheduler(stage)
        if staged:
            self.metrics["remote_onboarded"] = (
                self.metrics.get("remote_onboarded", 0) + staged)
            logger.info("staged %d remote KV blocks for %s", staged,
                        request.request_id)

    def read_host_blocks(self, hashes: List[int]):
        """Serve a peer's pull: fetch each block from the local tiers
        (promoting to G2 — a peer pulling it marks the prefix hot) until
        the first miss.  Runs between scheduler steps."""

        def read():
            out = []
            for h in hashes:
                blk, events, _src = self.kvbm.fetch(h) \
                    if self.kvbm is not None else (None, [], None)
                self._emit_tier_events(events)
                if blk is None:
                    break
                out.append((h, *blk))
            return out

        return self._call_on_scheduler(read)

    # -- KVBM offload/onboard ----------------------------------------------
    def _maybe_offload(self) -> None:
        """Copy the coldest evictable HBM blocks to the G2 host tier before
        eviction pressure destroys them.  One batched gather per step; the
        blocks stay live in G1 (offload is a copy, not a move), so there is
        no correctness window."""
        if self.kvbm is None or self.allocator.num_free >= self._offload_watermark:
            return
        cands = self.allocator.coldest_evictable(
            self.config.offload_batch, exclude=self.kvbm.offload_skip,
            scan_limit=4 * self.config.offload_batch + 64,
        )
        if not cands:
            return
        with self._phase("kvbm_offload", blocks=len(cands)):
            ids = _pow2_ids([bid for _, bid in cands])
            if self.step_sink is not None:
                self.step_sink("gather", {"ids": ids})
            with self._phase("device_wait", what="offload_gather"):
                arrs = [np.asarray(a)
                        for a in self._jit_gather(self.kv, jnp.asarray(ids))]
            for i, (h, _) in enumerate(cands):
                # contiguous copies: a [:, i] view would pin the whole
                # gathered batch buffer in host RAM for as long as any one
                # block lives.  int8 caches offload (k, v, k_scale,
                # v_scale) per block — half the host-tier bytes, scales
                # bit-exact (kvbm/pools.py)
                self._emit_tier_events(self.kvbm.offload(
                    h, *(np.ascontiguousarray(a[:, i]) for a in arrs)))

    def _try_onboard(self, slot: _Slot, hit: int, cap_blocks: int) -> int:
        """Extend a G1 prefix hit with blocks onboarded from G2/G3/G4:
        scatter their payloads into the freshly allocated HBM blocks
        instead of recomputing prefill.  match_run (and the fetch walk)
        reach through the shared object store, so a cold worker under
        shared-prefix load onboards the fleet's history — the G4 path the
        tiered router prices and routes to.  Returns the number of blocks
        onboarded."""
        if self.kvbm is None:
            return 0
        hashes = slot.seq.block_hashes
        run = self.kvbm.match_run(hashes[hit:cap_blocks])
        if run == 0:
            return 0
        with self._phase("kvbm_onboard") as ph:
            n = self._onboard_run(slot, hit, run, ph)
            if n == 0:
                ph.off_ring()
            return n

    def _onboard_run(self, slot: _Slot, hit: int, run: int, ph) -> int:
        """Fetch and scatter the `run` blocks after `hit` (see
        _try_onboard; `ph` is its kvbm_onboard phase)."""
        hashes = slot.seq.block_hashes
        block_ids = self.allocator.seq_block_ids(self._seq_id(slot))
        arity = len(self.kv)
        comps: List[list] = [[] for _ in range(arity)]
        ids = []
        by_tier: Dict[str, int] = {}
        for i in range(hit, hit + run):
            blk, events, src = self.kvbm.fetch(hashes[i])
            self._emit_tier_events(events)
            if blk is None:  # dropped from the pool mid-walk
                break
            if len(blk) != arity:
                # a block staged from a peer running the OTHER cache
                # dtype (mixed fleet): scatter-without-scales would be
                # silent corruption — treat as a miss and recompute
                logger.warning(
                    "KVBM block %x has %d payload arrays but the cache "
                    "expects %d (kv dtype mismatch); recomputing",
                    hashes[i], len(blk), arity)
                break
            for c, arr in zip(comps, blk):
                c.append(arr)
            ids.append(block_ids[i])
            if src is not None:
                by_tier[src] = by_tier.get(src, 0) + 1
                if self.kv_ledger is not None:
                    self.kv_ledger.onboard(hashes[i], src,
                                           seq=self._seq_id(slot))
        if not ids:
            return 0
        n = len(ids)
        ids_arr = _pow2_ids(ids)
        bucket = len(ids_arr)
        stacked = []
        for c in comps:
            pad = [(0, 0), (0, bucket - n)] + [(0, 0)] * (c[0].ndim - 1)
            stacked.append(np.pad(np.stack(c, axis=1), pad))
        if self.step_sink is not None:
            # onboard payloads ride the wire so followers need no KVBM
            # tiers of their own — their self.kv evolves from the stream
            desc = {"kb": stacked[0], "vb": stacked[1], "ids": ids_arr}
            if arity == 4:
                desc["ksb"], desc["vsb"] = stacked[2], stacked[3]
            self.step_sink("inject", desc)
        self.kv = self._jit_inject(
            self.kv, *(jnp.asarray(a) for a in stacked[:2]),
            jnp.asarray(ids_arr), *(jnp.asarray(a) for a in stacked[2:])
        )
        for src, cnt in by_tier.items():
            key = f"kv_onboard_{src}"
            self.metrics[key] = self.metrics.get(key, 0) + cnt
        ph.set(blocks=n, tokens=n * self.config.block_size,
               **{f"from_{s}": c for s, c in by_tier.items()})
        return n

    # -- prefill ----------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return self.config.prefill_buckets[-1]

    def _admit_waiting(self, waited_from: float = 0.0) -> None:
        """Move waiting requests into free slots (block allocation + prefix
        cache lookup; no model compute).  Request stage stamps: `seen_t`
        on every request this pass is the first to find waiting,
        `admitted_t` where one gets its lane and blocks; the pass reads
        the clock once, and again only where a request arrived after
        that read.  `waited_from` is when this step's blocking
        read-back began (_read_back_first; 0.0: it made none): a request
        enqueued since then arrived while the thread was blocked, and
        `req_admitted_after_wait` counts it."""
        now = 0.0
        while True:
            with self._qlock:
                if not self.waiting:
                    return
                if self.waiting[-1].seen_t == 0.0:
                    # arrivals append and a preempted request goes to
                    # the front seen: the unseen ones are the tail
                    now = time.monotonic()
                    for s in reversed(self.waiting):
                        if s.seen_t != 0.0:
                            break
                        s.seen_t = now
                        if waited_from and s.enqueued_t >= waited_from:
                            self.metrics["req_admitted_after_wait"] += 1
                free_idx = next(
                    (i for i, s in enumerate(self._slots) if s is None), None
                )
                if free_idx is None:
                    return
                slot = self.waiting[0]
                c = self.config
                prompt_len = len(slot.seq)
                hashes = slot.seq.block_hashes
                # never reuse the whole prompt: the last token must be
                # computed to produce first-token logits
                cap_blocks = max(0, (prompt_len - 1) // c.block_size)
                res = self.allocator.allocate(
                    self._seq_id(slot), hashes[:cap_blocks],
                    slot.seq.num_blocks,
                )
                if res is None:
                    return  # capacity: stay in queue (FIFO)
                self.waiting.pop(0)
            self._emit_events(res)
            # a request that joins others shares the engine; one that
            # comes to an empty engine is a single stream (_fused_k)
            self._shared = any(s is not None for s in self._slots)
            slot.index = free_idx
            self._slots[free_idx] = slot
            if slot.admitted_t == 0.0:
                now = now or time.monotonic()
                slot.admitted_t = now
            bids = res.block_ids
            slot.block_table[: len(bids)] = bids
            slot.committed_blocks = res.cached_blocks
            # extend the G1 hit with G2/G3 onboarding (KV scattered back
            # into HBM instead of recomputed)
            onboarded = self._try_onboard(slot, res.cached_blocks, cap_blocks)
            for i in range(res.cached_blocks, res.cached_blocks + onboarded):
                cres = self.allocator.commit_block(
                    self._seq_id(slot), i, slot.seq.block_hashes[i]
                )
                self._emit_events(cres)
                slot.committed_blocks = i + 1
            total_cached = res.cached_blocks + onboarded
            cached_tokens = total_cached * c.block_size
            slot.cached_tokens = cached_tokens
            self.metrics["cache_hit_tokens"] += cached_tokens
            if onboarded:
                self.metrics["onboarded_tokens"] = (
                    self.metrics.get("onboarded_tokens", 0)
                    + onboarded * c.block_size
                )
            slot.ctx_len = cached_tokens
            slot.prompt_len = prompt_len
            slot.prefill_pos = cached_tokens

            # disagg decode: wake the pull task now that blocks exist; the
            # slot idles (prefill/decode skip it) while chunk injects
            # stream in between steps
            if slot.pulling and slot.admitted is not None \
                    and self._loop_ref is not None:
                self._loop_ref.call_soon_threadsafe(slot.admitted.set)

    def _prefill_step(self) -> None:
        """Run prefill chunks for up to max_prefill_seqs prefilling slots
        (earliest-enqueued first) in ONE program, the step's total token
        count capped near the chunk budget (chunks + one decode token per
        active slot).  Default path: PACKED chunked prefill — every
        co-scheduled chunk concatenates into one padding-free token
        stream with segment ids (engine/prefill.py planner).  Families
        without prefill_packed fall back to the padded B=1 / batched
        programs; cold long prompts on an sp mesh still take the one-shot
        ring program."""
        pslots = sorted(
            (s for s in self._slots
             if s is not None and s.prefilling and not s.pulling),
            key=lambda s: s.enqueued_t,
        )[: self.config.max_prefill_seqs]
        if not pslots:
            return
        with self._phase("prefill_dispatch", rows=len(pslots)) as ph:
            before = self.metrics["prefill_tokens"]
            self._prefill_dispatch(pslots)
            # obs.report's fleet_prefix_cache prices a token of prefill
            # from this span
            ph.set(tokens=self.metrics["prefill_tokens"] - before)

    def _prefill_dispatch(self, pslots) -> None:
        """Route this step's prefilling slots to one program (see
        _prefill_step; split out so the dispatch span covers every
        path)."""
        c = self.config
        self.metrics["prefill_steps"] = \
            self.metrics.get("prefill_steps", 0) + 1
        decoding = sum(
            1 for s in self._slots if s is not None and not s.prefilling
        )
        budget = max(c.chunk_budget - decoding, c.prefill_buckets[0])
        # SLA-aware admission (the PR 1 mixed-scheduling loop closed
        # against the PR 7 SLO plane): when the frontier burn rate says
        # the ITL/TTFT error budget is burning faster than allowed AND
        # decodes are live, prefill yields chunk budget to decode —
        # scaled by threshold/burn, floored at the smallest bucket so
        # prefill always advances (no livelock, TTFT degrades gradually
        # instead of decode ITL collapsing).
        if c.slo_yield_burn > 0 and decoding:
            burn = self._effective_slo_burn()
            if burn > c.slo_yield_burn:
                budget = max(int(budget * c.slo_yield_burn / burn),
                             c.prefill_buckets[0])
                self.metrics["slo_yield_steps"] = \
                    self.metrics.get("slo_yield_steps", 0) + 1
        if len(pslots) == 1 and self._ring_eligible(pslots[0]):
            # long-context path (see _prefill_one's rationale)
            self._prefill_ring_one(pslots[0])
            return
        if self._packed_prefill_ok:
            self._prefill_packed_step(pslots, budget)
            return
        if len(pslots) == 1:
            self._prefill_one(pslots[0], budget)
            return

        # Equal budget shares, NO donation of leftovers: every row pads to
        # the largest chunk's bucket, so letting one row grow past the
        # share would multiply the whole batch's padded compute (n×bucket)
        # far beyond the budget that bounds decode ITL.  When the budget is
        # too tight to give every row the minimum bucket, batch FEWER slots
        # this step (earliest first) rather than multiplying the floor by
        # n — total compute stays ≤ n·bucket(share) ≤ ~2·budget either way.
        n = max(1, min(len(pslots), budget // c.prefill_buckets[0]))
        pslots = pslots[:n]
        if n == 1:
            self._prefill_one(pslots[0], budget)
            return
        share = max(budget // n, c.prefill_buckets[0])
        chunks = [self._whole_blocks(min(c.prefill_buckets[-1], share,
                                         s.prefill_end - s.prefill_pos))
                  for s in pslots]

        bucket = self._bucket_for(max(chunks))
        Bp = _pow2_len(n)
        toks = np.zeros((Bp, bucket), np.int32)
        positions = np.zeros((Bp, bucket), np.int32)
        tables = np.zeros((Bp, c.max_blocks_per_seq), np.int32)
        ctx_lens = np.zeros(Bp, np.int32)
        true_lens = np.zeros(Bp, np.int32)
        seeds = np.zeros(Bp, np.int32)
        temps = np.zeros(Bp, np.float32)
        top_ks = np.zeros(Bp, np.int32)
        top_ps = np.ones(Bp, np.float32)
        for i, (slot, chunk) in enumerate(zip(pslots, chunks)):
            pos = slot.prefill_pos
            toks[i, :chunk] = slot.seq.tokens[pos: pos + chunk]
            positions[i] = pos + np.arange(bucket, dtype=np.int32)
            tables[i] = slot.block_table
            ctx_lens[i] = pos
            true_lens[i] = chunk
            s = slot.request.sampling
            seeds[i] = slot.sampling_seed
            temps[i] = s.temperature
            top_ks[i] = s.top_k
            top_ps[i] = s.top_p
        lidx = np.zeros(Bp, np.int32)
        lanes = np.zeros(Bp, np.int32)
        for i, (slot, _) in enumerate(zip(pslots, chunks)):
            lidx[i] = slot.lora_idx
            lanes[i] = slot.index
        if self.step_sink is not None:
            self.step_sink("prefill_batch", {
                "toks": toks, "positions": positions,
                "tables": tables, "ctx_lens": ctx_lens,
                "true_lens": true_lens, "seeds": seeds, "temps": temps,
                "top_ks": top_ks, "top_ps": top_ps,
                **({"lidx": lidx} if self.lora_bank is not None else {}),
                **({"lanes": lanes} if self._lane_addressed else {}),
            })
        self._stamp_dispatch(pslots)
        tok, self.kv = self._jit_prefill_batched(
            self.params, self.kv,
            jnp.asarray(toks), jnp.asarray(positions), jnp.asarray(tables),
            jnp.asarray(ctx_lens), jnp.asarray(true_lens),
            jnp.asarray(seeds), jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), self.lora_bank,
            jnp.asarray(lidx) if self.lora_bank is not None else None,
            jnp.asarray(lanes) if self._lane_addressed else None,
        )
        self._fpm_prefill(
            rows=n, tokens=int(sum(chunks)), bucket=bucket,
            completing=sum(1 for s, ch in zip(pslots, chunks)
                           if s.prefill_pos + ch >= s.prefill_end))
        # the sampled tokens matter ONLY when some row completes its
        # prompt this chunk (np.asarray is a blocking device round trip;
        # intermediate chunks discard the sample, so they never pay it);
        # overlap mode defers even that fetch one step
        need = self._completing_rows(pslots, chunks)
        firsts = (self._prefill_samples(
            tok, [(s, i) for i, s in need.items()]) if need else None)
        for i, (slot, chunk) in enumerate(zip(pslots, chunks)):
            if i in need:
                first = int(firsts[i]) if firsts is not None else None
            else:
                first = -1
            self._finish_prefill_chunk(slot, chunk, first, bucket)

    def _whole_blocks(self, chunk: int) -> int:
        """A prefill chunk of a family that generates by blocks ends on
        a multiple of the block length, so that every key a query may
        see (to its block's end) is written by its own program or an
        earlier one; anyone else's chunk is as it was."""
        gb = self._gen_block
        return chunk - chunk % gb if gb else chunk

    def _moe_grouped(self, tokens: int) -> bool:
        """Whether a program whose expert layers see `tokens` rows takes
        the dropless dispatch's grouped form: the rule the traced code
        applies (moe.moe_form), asked from the host."""
        return self._moe[0] > 0 \
            and moe_form(self.model_cfg, tokens) == "grouped"

    def _prefill_attn_kernel(self, bucket: int) -> bool:
        """Whether a packed prefill program of `bucket` tokens runs its
        attention in the Pallas kernel: the rule the traced code applies
        (ops/packed_prefill.resolve_packed_impl), asked from the host."""
        m = self.model_cfg
        impl = getattr(m, "packed_attn_impl", None)
        return impl is not None and resolve_packed_impl(
            impl, self.mesh.devices.flat[0].platform,
            self.config.block_size, m.head_dim,
            jnp.int8 if self.kv_dtype == "int8" else m.dtype, bucket,
            m.n_heads // m.n_kv_heads) in PALLAS_IMPLS

    def _fpm_prefill(self, rows: int, tokens: int, bucket: int,
                     packed: bool = False, completing: int = 0) -> None:
        """One FPM record per prefill program — the inputs the SLA
        planner's FpmObserver turns into prefill rate and pressure.

        Beyond (rows, tokens, bucket) the record carries:

        - gap_s: dispatch-to-dispatch gap (the decode records'
          convention).  The gap spans everything between two prefill
          dispatches — interleaved decode steps included — and jit
          dispatch is async: host time, not device time.
        - queue_depth: waiting + still-prefilling slots, MINUS the
          `completing` slots whose prompt this very dispatch finishes —
          the burst's final record must read 0, or the observer reports
          phantom pressure for a full window after the fleet goes
          idle."""
        now = time.monotonic()
        gap = (now - self._fpm_last_prefill_t
               if self._fpm_last_prefill_t else 0.0)
        if gap > 1.0:
            gap = 0.0  # idle stretch, not prefill latency: mark unknown
        # len() of a list is an atomic read; the exact depth is advisory
        # (this runs before _finish_prefill_chunk flips .prefilling, so
        # completing slots still count — subtract them)
        depth = max(0, len(self.waiting) + sum(
            1 for s in self._slots if s is not None and s.prefilling)
            - completing)
        self.fpm.append({
            "t": now, "kind": "prefill", "rows": rows, "tokens": tokens,
            "bucket": bucket, "packed": packed, "gap_s": gap,
            "queue_depth": depth,
        })
        self._fpm_last_prefill_t = now
        # the expert layers see the program's rows flattened: a packed
        # stream or one row is `bucket` long, co-batched rows pad to a
        # power of two of them (moe.moe_rows)
        if self._moe_grouped(bucket if packed
                             else _pow2_len(rows) * bucket):
            self.metrics["moe_grouped_tokens.prefill"] += tokens
        if packed and self._prefill_attn_kernel(bucket):
            self.metrics["prefill_attn_kernel_tokens"] += tokens

    def _prefill_packed_step(self, pslots, budget: int) -> None:
        """One packed prefill dispatch: the planner water-fills the token
        budget across the prefilling slots and concatenates their chunks
        (including prefix-cache-hit tails, which start at prefill_pos >
        0) into a single padding-free stream — one program, one shape
        family, no per-row bucket padding."""
        from .prefill import plan_packed_prefill

        c = self.config
        plan = plan_packed_prefill(
            pslots, budget, block_size=c.block_size,
            max_blocks_per_seq=c.max_blocks_per_seq,
            min_bucket=c.prefill_buckets[0],
            with_lora=self.lora_bank is not None,
            align=self._gen_block,
        )
        if plan is None:
            return
        a = plan.arrays
        if self._lane_addressed:
            # each segment row's lane (unused rows: lane 0, no tokens)
            a["lanes"] = np.zeros(len(a["last_idx"]), np.int32)
            a["lanes"][:len(plan.slots)] = [s.index for s in plan.slots]
        if self.step_sink is not None:
            self.step_sink("prefill_packed", dict(a))
        self._stamp_dispatch(plan.slots)
        tok, self.kv = self._jit_prefill_packed(
            self.params, self.kv,
            jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
            jnp.asarray(a["seg_ids"]), jnp.asarray(a["tables"]),
            jnp.asarray(a["last_idx"]), jnp.asarray(a["valid"]),
            jnp.asarray(a["seeds"]), jnp.asarray(a["temps"]),
            jnp.asarray(a["top_ks"]), jnp.asarray(a["top_ps"]),
            self.lora_bank,
            jnp.asarray(a["lidx"]) if self.lora_bank is not None else None,
            jnp.asarray(a["lanes"]) if self._lane_addressed else None,
        )
        self._fpm_prefill(
            rows=len(plan.slots), tokens=plan.tokens, bucket=plan.bucket,
            packed=True,
            completing=sum(1 for s, ch in zip(plan.slots, plan.chunks)
                           if s.prefill_pos + ch >= s.prefill_end))
        # token fetch only when some segment completes its prompt this
        # chunk (see _prefill_step: intermediate chunks discard the
        # sample); overlap mode defers the readback one step
        need = self._completing_rows(plan.slots, plan.chunks)
        firsts = (self._prefill_samples(
            tok, [(s, i) for i, s in need.items()]) if need else None)
        for i, (slot, chunk) in enumerate(zip(plan.slots, plan.chunks)):
            if i in need:
                first = int(firsts[i]) if firsts is not None else None
            else:
                first = -1
            self._finish_prefill_chunk(slot, chunk, first, plan.bucket)

    def _ring_eligible(self, slot: "_Slot") -> bool:
        """A cold (prefill_pos == 0), non-LoRA prompt longer than the
        largest bucket takes the one-shot sequence-parallel ring program
        when the mesh has one — one predicate for both the packed
        scheduler and the padded fallback, so they can never route the
        same slot differently."""
        return (self._jit_prefill_ring is not None
                and slot.prefill_pos == 0
                and slot.prompt_len > self.config.prefill_buckets[-1]
                and slot.lora_idx == 0)

    def _prefill_one(self, slot: "_Slot", budget: int) -> None:
        """The B=1 chunk program (single prefilling slot)."""
        c = self.config
        if self._ring_eligible(slot):
            # long-context path: one sequence-parallel program computes
            # the whole prompt with ring attention — the O(T^2) FLOPs
            # shard over sp devices instead of chunk-serializing on each.
            # Trade-off vs chunking: decode stalls for this ONE program
            # (not per chunk), but the sp-way split makes it short.
            self._prefill_ring_one(slot)
            return
        pos = slot.prefill_pos
        chunk = self._whole_blocks(min(c.prefill_buckets[-1], budget,
                                       slot.prefill_end - pos))
        bucket = self._bucket_for(chunk)
        toks = np.zeros(bucket, np.int32)
        toks[:chunk] = slot.seq.tokens[pos: pos + chunk]
        positions = pos + np.arange(bucket, dtype=np.int32)
        s = slot.request.sampling
        if self.step_sink is not None:
            # copy: the sink crosses to the loop thread while the scheduler
            # keeps mutating the slot's live table (grow/release)
            self.step_sink("prefill", {
                "toks": toks, "positions": positions,
                "block_table": slot.block_table.copy(),
                "pos": np.int32(pos), "chunk": np.int32(chunk),
                "seed": np.int32(slot.sampling_seed),
                "temp": np.float32(s.temperature),
                "top_k": np.int32(s.top_k), "top_p": np.float32(s.top_p),
                **({"lidx": np.int32(slot.lora_idx)}
                   if self.lora_bank is not None else {}),
                **({"lanes": np.int32(slot.index)}
                   if self._lane_addressed else {}),
            })
        self._stamp_dispatch((slot,))
        tok, self.kv = self._jit_prefill(
            self.params, self.kv,
            jnp.asarray(toks), jnp.asarray(positions),
            jnp.asarray(slot.block_table),
            jnp.int32(pos), jnp.int32(chunk),
            jnp.int32(slot.sampling_seed),
            jnp.float32(s.temperature), jnp.int32(s.top_k),
            jnp.float32(s.top_p), self.lora_bank,
            jnp.int32(slot.lora_idx) if self.lora_bank is not None
            else None,
            jnp.int32(slot.index) if self._lane_addressed else None,
        )
        self._fpm_prefill(
            rows=1, tokens=int(chunk), bucket=bucket,
            completing=int(slot.prefill_pos + chunk >= slot.prefill_end))
        # token fetch only on the completing chunk (see _prefill_step:
        # intermediate chunks discard the sample); deferred in overlap
        if pos + chunk >= slot.prompt_len and not self._gen_block \
                and (slot.guide is None or slot.disagg_prefill):
            arr = self._prefill_samples(tok, [(slot, 0)])
            first = int(arr) if arr is not None else None
        else:
            first = -1
        self._finish_prefill_chunk(slot, chunk, first, bucket)

    def _prefill_ring_one(self, slot: "_Slot") -> None:
        """Whole-prompt sequence-parallel prefill (see _prefill_one)."""
        c = self.config
        T = slot.prompt_len
        # pad to a pow2 multiple of (sp * smallest bucket): T must divide
        # by sp for the ring, and pow2 rounding bounds distinct shapes
        g = c.sp * c.prefill_buckets[0]
        T_pad = _pow2_len(-(-T // g)) * g
        toks = np.zeros(T_pad, np.int32)
        toks[:T] = slot.seq.tokens[:T]
        positions = np.arange(T_pad, dtype=np.int32)
        s = slot.request.sampling
        if self.step_sink is not None:
            self.step_sink("prefill_ring", {
                "toks": toks, "positions": positions,
                "block_table": slot.block_table.copy(),
                "true_len": np.int32(T),
                "seed": np.int32(slot.sampling_seed),
                "temp": np.float32(s.temperature),
                "top_k": np.int32(s.top_k), "top_p": np.float32(s.top_p),
            })
        self._stamp_dispatch((slot,))
        tok, self.kv = self._jit_prefill_ring(
            self.params, self.kv, jnp.asarray(toks),
            jnp.asarray(positions), jnp.asarray(slot.block_table),
            jnp.int32(T), jnp.int32(slot.sampling_seed),
            jnp.float32(s.temperature), jnp.int32(s.top_k),
            jnp.float32(s.top_p),
        )
        self.metrics["ring_prefills"] = \
            self.metrics.get("ring_prefills", 0) + 1
        if slot.guide is None or slot.disagg_prefill:
            arr = self._prefill_samples(tok, [(slot, 0)])
            first = int(arr) if arr is not None else None
        else:
            first = -1
        self._finish_prefill_chunk(slot, T, first)

    def _stamp_dispatch(self, slots) -> None:
        """Request stage stamp: the first prefill chunk of these slots'
        requests is about to be dispatched (one clock read a program;
        a preempted request's replay keeps its first stamp).  Such a
        request also takes with it how much decode work stood ahead of
        its chunk on the device: the steps (sum of `k`) and the count of
        the dispatched bursts whose tokens are not ready yet
        (`is_ready()`, which does not block).  The burst the device is
        running at that moment counts WHOLE, however far it has come, so
        the reading is high by half a burst on average; lockstep mode
        (`overlap_scheduling` off) has no burst in flight here and reads
        0.  The open `prefill_dispatch` phase gets both as attributes."""
        now = time.monotonic()
        first = [s for s in slots if s.dispatched_t == 0.0]
        if not first:
            return
        unready = [e["k"] for e in self._inflight
                   if not e["burst"].is_ready()]
        steps = sum(unready)
        for s in first:
            s.dispatched_t = now
            s.ahead_steps = steps
        open_ = self._phase.open
        if open_ and open_[-1].kind == "prefill_dispatch":
            open_[-1].set(ahead_steps=steps, ahead_bursts=len(unready))

    def _completing_rows(self, slots, chunks) -> Dict[int, "_Slot"]:
        """{program row -> slot} of slots whose prompt completes this
        chunk AND whose first sampled token is actually consumed
        (guided non-disagg completions discard the unconstrained sample
        and re-derive it in the guided step, so they never cost a
        fetch)."""
        if self._gen_block:
            return {}   # a prefill yields no token: the first block does
        return {
            i: s for i, (s, ch) in enumerate(zip(slots, chunks))
            if s.prefill_pos + ch >= s.prompt_len
            and (s.guide is None or s.disagg_prefill)
        }

    def _prefill_samples(self, tok, entries):
        """Completing slots' sampled first tokens, one program's worth.

        Sync mode: blocking fetch now (the lockstep reference path).
        Overlap mode: start the device->host copy and DEFER the read one
        step (_pending_first; _flush_pending_first at the top of the
        next step emits them) — the dispatching step never blocks on its
        own program, so the device_wait only ever pays for work the
        device had a full step to finish.  Returns the host array, or
        None when deferred.  `entries` is [(slot, program row)].

        The wait carries `programs`, the chunk programs of the longest
        prompt it completes: a prompt that came to an idle engine has
        them all queued ahead of this read, so the wait is honestly that
        many programs long (obs.PhaseClock.pause scales its limit)."""
        programs = 1 + max((s.prefill_chunks for s, _ in entries), default=0)
        if self._overlap:
            try:
                tok.copy_to_host_async()
            except AttributeError:  # non-jax stand-ins in tests
                pass
            ents = []
            for slot, row in entries:
                slot.awaiting_first = True
                ents.append((slot, (self._seq_id(slot), slot.epoch), row))
            self._pending_first.append({"tok": tok, "entries": ents,
                                        "programs": programs})
            return None
        with self._phase("device_wait", what="prefill_first",
                         programs=programs):
            arr = np.asarray(tok)
        return arr

    def _flush_pending_first(self) -> None:
        """Overlap mode: read back the PREVIOUS step's deferred prefill
        first tokens (one blocking fetch for everything deferred, at the
        top of the step: _read_back_first) and emit or park them.
        Entries whose slot finished, cancelled, or preempted since
        dispatch are discarded — the same (seq_id, epoch) identity check
        the in-flight decode bursts use."""
        if not self._pending_first:
            return
        pending, self._pending_first = self._pending_first, []
        with self._phase("device_wait", what="prefill_first",
                         programs=max(e["programs"] for e in pending)):
            arrs = [np.asarray(e["tok"]) for e in pending]
        with self._phase("emit", what="prefill_first"):
            for e, arr in zip(pending, arrs):
                flat = np.atleast_1d(arr)
                for slot, ident, row in e["entries"]:
                    slot.awaiting_first = False
                    if slot.finished or slot.index < 0 \
                            or self._slots[slot.index] is not slot \
                            or (self._seq_id(slot), slot.epoch) != ident:
                        continue
                    self._complete_prefill(slot, int(flat[row]))

    def _finish_prefill_chunk(self, slot: "_Slot", chunk: int,
                              first: Optional[int],
                              bucket: int = 0) -> None:
        """Advance a slot past a completed chunk.  `first` is the prompt's
        sampled first token when it completes this chunk; -1 marks a
        non-completing chunk (or a guided completion, which discards the
        sample); None marks a completed prompt whose token readback is
        deferred (_pending_first — the flush completes it next step).
        `bucket`: the rows the chunk was padded to where its program pads
        a row, a packed program's whole stream (0 for a ring program), for
        the family's counts."""
        self.metrics["prefill_tokens"] += chunk
        self.metrics["moe_picks.prefill"] += \
            chunk * self._moe[0] * self._moe[1]
        if self._prefill_counts is not None:
            for name, n in self._prefill_counts(
                    self.model_cfg, slot.prefill_pos, chunk,
                    bucket).items():
                self.metrics[name] += n
        slot.prefill_pos += chunk
        slot.prefill_chunks += 1
        slot.ctx_len = slot.prefill_pos
        # register blocks this chunk completed (registration is deferred to
        # materialization, so commit must track prefill progress chunkwise)
        self._commit_full_blocks(slot)
        if slot.prefilling:
            return  # more chunks to go; decode runs in between
        if self._gen_block:
            # no token comes of a prefill: the lane starts in its first
            # block, which holds the prompt's last tokens unmasked, and
            # first_token_t is stamped where that block is emitted
            return
        if slot.guide is not None and not slot.disagg_prefill:
            # constrained output: discard the unconstrained sample and
            # re-derive the first token's logits in the guided step by
            # re-running the last prompt position (its KV rewrite is
            # value-identical)
            self._stamp_first_token(slot)
            slot.ctx_len = slot.prompt_len - 1
            slot.last_token = slot.seq.tokens[slot.prompt_len - 1]
            return
        if first is None:
            return  # awaiting_first; the next step's flush completes it
        self._complete_prefill(slot, first)

    def _complete_prefill(self, slot: "_Slot", first: int) -> None:
        """Prompt fully materialized and first token in hand: emit it (or
        park the KV for disagg pull)."""
        self._stamp_first_token(slot)
        if slot.disagg_prefill:
            self._park_prefilled(slot, first)
            return
        self._push_token(slot, first)

    @staticmethod
    def _stamp_first_token(slot: "_Slot") -> None:
        """Request stage stamp: the first token is in the host's hands
        (a preempted request's replay keeps its first stamp, so `ttft_s`
        and the stages stay the first token's)."""
        if slot.first_token_t == 0.0:
            slot.first_token_t = time.monotonic()

    async def _stream_pull(self, slot: _Slot, dp: Dict[str, Any]) -> None:
        """Decode-side streaming pull: inject the prefill's KV chunk by
        chunk, each chunk one scheduler op, so decode bursts for OTHER
        slots run in between (no whole-prompt stall; host memory bounded
        by two chunks — the injecting one plus one prefetch in flight).
        Any failure falls back to local prefill — the slot's blocks are
        already allocated and prefill_pos still points at the cached
        prefix."""
        src = None
        t0 = time.monotonic()
        rid = slot.request.request_id
        t_obs = obs.begin()
        tid_obs = (obs.trace_id_from_annotations(slot.request.annotations)
                   if t_obs else None)

        async def pull_chunk(b0: int, n: int):
            # unified retry (runtime/retry.py): a transiently failing
            # chunk op (peer hiccup, injected fault) is retried with
            # jittered backoff before the whole pull gives up and falls
            # back to local prefill.  The chaos seam sits INSIDE the
            # retried call so `times=1` rules are absorbed by a retry
            # while unlimited rules exhaust it.
            async def once():
                await chaos.ahit("disagg.pull.chunk", key=f"{rid}:{b0}")
                return await src.chunk(b0, n)

            return await call_with_retry(
                once, PULL_POLICY,
                on_retry=lambda a, e: logger.warning(
                    "kv pull chunk [%d,%d) for %s failed (attempt %d): "
                    "%s", b0, b0 + n, rid, a, e),
            )

        try:
            await slot.admitted.wait()
            if slot.finished or slot.cancel_requested:
                return
            src = await self.kv_pull_fn(dp)
            header = await call_with_retry(src.open, PULL_POLICY)
            from ..disagg.transfer import KvLayout

            layout = KvLayout.from_dict(header["layout"])
            layout.check_compatible(self.kv_wire_layout())
            prompt_len = slot.prompt_len
            if int(header["prompt_len"]) != prompt_len:
                raise ValueError(
                    f"prefill parked {header['prompt_len']} tokens but the "
                    f"decode request has {prompt_len}")
            bs = self.config.block_size
            n_blocks = (prompt_len + bs - 1) // bs
            if layout.num_blocks != n_blocks:
                raise ValueError(
                    f"prefill parked {layout.num_blocks} blocks; decode "
                    f"needs {n_blocks}")
            # skip blocks the local prefix cache / KVBM already
            # materialized at admission — pull only the missing tail
            start = slot.cached_tokens // bs
            per = layout.blocks_per_chunk(self.config.transfer_chunk_bytes)
            if getattr(src, "device_resident", False):
                # device tiers: the chunk bound protects HOST memory, which
                # device-resident chunks never touch — 8x chunks cut the
                # scheduler-op round trips that dominated round-4's
                # 0.24 GB/s tier-1 pull
                per *= 8
            spans = [(b0, min(per, n_blocks - b0))
                     for b0 in range(start, n_blocks, per)]
            pulled = 0
            # pipelined: chunk i+1 is in flight on the SOURCE while chunk
            # i injects on this engine's scheduler (receiver-paced, one
            # outstanding prefetch — the sender registry holds one chunk)
            nxt = (asyncio.ensure_future(pull_chunk(*spans[0]))
                   if spans else None)
            try:
                for idx, (b0, n) in enumerate(spans):
                    if slot.finished or slot.cancel_requested:
                        return
                    arrs = await nxt
                    nxt = (asyncio.ensure_future(
                        pull_chunk(*spans[idx + 1]))
                        if idx + 1 < len(spans) else None)
                    await self._call_on_scheduler(
                        partial(self._inject_pulled_chunk, slot, b0, n,
                                arrs))
                    if isinstance(arrs[0], np.ndarray):
                        nbytes = sum(a.nbytes for a in arrs)
                        self.metrics["pull_host_chunk_bytes_max"] = max(
                            self.metrics.get("pull_host_chunk_bytes_max",
                                             0),
                            nbytes)
                    pulled += n
            finally:
                if nxt is not None:
                    nxt.cancel()  # no-op if already done
                    try:
                        await nxt
                    except asyncio.CancelledError:
                        # suppress only the prefetch future's OWN
                        # cancellation; re-raise when the pull TASK is
                        # being externally cancelled — either the
                        # prefetch ended uncancelled (the error must be
                        # ours), or (py3.11+) current_task reports a
                        # cancel that arrived while we awaited the
                        # self-cancelled prefetch — so the metrics/
                        # finish code below stops running after cancel
                        # instead of racing the teardown
                        cur = asyncio.current_task()
                        if not nxt.cancelled() or (
                                cur is not None
                                and getattr(cur, "cancelling",
                                            lambda: 0)() > 0):
                            raise
                    except Exception:
                        pass
            self.metrics["pull_blocks"] = (
                self.metrics.get("pull_blocks", 0) + pulled)
            self.metrics["pull_seconds"] = (
                self.metrics.get("pull_seconds", 0.0)
                + (time.monotonic() - t0))
            await self._call_on_scheduler(
                partial(self._finish_pull, slot, dp.get("first_token")))
        except asyncio.CancelledError:
            raise
        except Exception:
            logger.warning("KV pull failed for %s; local prefill fallback",
                           slot.request.request_id, exc_info=True)

            def fallback():
                slot.pulling = False  # prefill path picks the slot up

            try:
                await self._call_on_scheduler(fallback)
            except Exception:
                pass
            self._wake.set()
        finally:
            obs.end("kv_pull", t_obs, request_id=rid, trace_id=tid_obs)
            if src is not None:
                try:
                    await src.close()
                except Exception:
                    pass

    def _inject_pulled_chunk(self, slot: _Slot, b0: int, n: int,
                             arrs) -> None:
        """Scheduler op: scatter one pulled chunk into the slot's blocks.

        `arrs` is (kb, vb) — plus (ksb, vsb) scale planes for an int8
        cache — numpy (host-staged tier) or device arrays (broker /
        transfer-server tiers).  Device chunks are re-laid onto this
        engine's own universal sharding first — with a different source
        mesh that device_put IS the ICI device-to-device move."""
        if slot.finished or slot.cancel_requested:
            return  # blocks may already be freed; drop the chunk
        if len(arrs) != len(self.kv):
            raise ValueError(
                f"pulled chunk has {len(arrs)} payload arrays but the "
                f"cache expects {len(self.kv)} (kv dtype mismatch)")
        block_ids = self.allocator.seq_block_ids(
            self._seq_id(slot))[b0:b0 + n]
        if len(block_ids) != n:
            raise ValueError(f"slot lost blocks [{b0},{b0 + n}) mid-pull")
        ids = _pow2_ids(block_ids)
        bucket = len(ids)
        if isinstance(arrs[0], np.ndarray):
            padded = [np.pad(a, ((0, 0), (0, bucket - n))
                             + ((0, 0),) * (a.ndim - 2)) for a in arrs]
        else:
            shardings = self.universal_shardings()
            arrs = [jax.device_put(a, sh) for a, sh in zip(arrs, shardings)]
            padded = [jnp.pad(a, ((0, 0), (0, bucket - n))
                              + ((0, 0),) * (a.ndim - 2)) for a in arrs]
        if self.step_sink is not None:
            # the pulled KV rides the step stream to the slice's followers
            # (device-resident tiers are gated off for multi-host slices,
            # so the padded chunks are host bytes here)
            # dynlint: disable=DYN011 multi-host pulls are host-staged frames (device tiers gated off); these are numpy already
            desc = {"kb": np.asarray(padded[0]), "vb": np.asarray(padded[1]),
                    "ids": ids}
            if len(padded) == 4:
                # dynlint: disable=DYN011 same host-staged frame (scale planes)
                desc["ksb"] = np.asarray(padded[2])
                # dynlint: disable=DYN011 same host-staged frame (scale planes)
                desc["vsb"] = np.asarray(padded[3])
            self.step_sink("inject", desc)
        self.kv = self._jit_inject(
            self.kv, *(jnp.asarray(a) for a in padded[:2]),
            jnp.asarray(ids), *(jnp.asarray(a) for a in padded[2:])
        )

    def _finish_pull(self, slot: _Slot, first: Optional[int]) -> None:
        """Scheduler op: all chunks landed — commit the blocks and emit the
        first token (recomputing it if the transfer metadata lacked it)."""
        if slot.finished or slot.cancel_requested:
            return
        prompt_len = slot.prompt_len
        slot.ctx_len = prompt_len
        slot.prefill_pos = prompt_len
        slot.cached_tokens = prompt_len  # skipped compute entirely
        slot.pulling = False
        self._commit_full_blocks(slot)
        # a pulled prompt has no prefill dispatch of its own: its queue
        # stage ends where the pulled KV is whole, with no program of its
        # own behind the bursts on the device (ahead_steps stays 0)
        if slot.dispatched_t == 0.0:
            slot.dispatched_t = time.monotonic()
        self._stamp_first_token(slot)
        if slot.guide is not None:
            # constrained output served via disagg: the prefill worker
            # sampled its first token UNCONSTRAINED (it parks before the
            # guided branch runs), so pushing it would stream a stray
            # token ahead of the JSON document.  Mirror the aggregated
            # guided branch instead: rewind to the last prompt position
            # and let _guided_step re-derive the first token under the
            # constraint (the position's KV rewrite is value-identical).
            self.metrics["cache_hit_tokens"] += prompt_len
            slot.ctx_len = prompt_len - 1
            slot.last_token = slot.seq.tokens[prompt_len - 1]
            return
        if first is None:
            # transfer metadata lacked the first token: recompute from the
            # last prompt position (cache already holds prompt[:-1])
            table_dev = jnp.asarray(slot.block_table)
            s = slot.request.sampling
            toks = np.zeros(self.config.prefill_buckets[0], np.int32)
            toks[0] = slot.seq.tokens[-1]
            positions = (prompt_len - 1) + np.arange(
                self.config.prefill_buckets[0], dtype=np.int32)
            if self.step_sink is not None:
                self.step_sink("prefill", {
                    "toks": toks, "positions": positions,
                    "block_table": slot.block_table.copy(),
                    "pos": np.int32(prompt_len - 1), "chunk": np.int32(1),
                    "seed": np.int32(slot.sampling_seed),
                    "temp": np.float32(s.temperature),
                    "top_k": np.int32(s.top_k),
                    "top_p": np.float32(s.top_p),
                    **({"lidx": np.int32(slot.lora_idx)}
                       if self.lora_bank is not None else {}),
                })
            tok, self.kv = self._jit_prefill(
                self.params, self.kv, jnp.asarray(toks),
                jnp.asarray(positions), table_dev,
                jnp.int32(prompt_len - 1), jnp.int32(1),
                jnp.int32(slot.sampling_seed), jnp.float32(s.temperature),
                jnp.int32(s.top_k), jnp.float32(s.top_p),
                self.lora_bank,
                jnp.int32(slot.lora_idx) if self.lora_bank is not None
                else None,
            )
            first = int(tok)
        self.metrics["cache_hit_tokens"] += prompt_len
        self._push_token(slot, int(first))

    def _park_prefilled(self, slot: _Slot, first_token: int) -> None:
        """Disagg prefill done: keep the KV, hand back transfer metadata."""
        from ..disagg.transfer import make_transfer_params

        seq_id = self._seq_id(slot)
        rid = slot.request.request_id
        self._parked[rid] = _Parked(
            seq_id=seq_id,
            block_ids=list(self.allocator.seq_block_ids(seq_id)),
            prompt_len=slot.ctx_len,
            expires_t=time.monotonic() + self.parked_ttl_s,
        )
        if self.kv_ledger is not None:
            # attribution: this sequence's blocks are now
            # pinned-by-transfer, awaiting the decode side's pull
            self.kv_ledger.park(seq_id)
        slot.finished = True
        if slot.index >= 0:
            self._slots[slot.index] = None
            slot.index = -1
        params = make_transfer_params(
            instance_id=self.transfer_identity.get("instance_id", 0),
            request_id=rid,
            prompt_len=self._parked[rid].prompt_len,
            first_token=first_token,
            block_size=self.config.block_size,
            num_layers=self.model_cfg.n_layers,
        )
        params.update({k: v for k, v in self.transfer_identity.items()
                       if k != "instance_id"})
        out = LLMEngineOutput(
            token_ids=[first_token], finish_reason="stop",
            kv_transfer_params=params,
            metrics={"ttft_s": slot.first_token_t - slot.enqueued_t,
                     # disagg one-shot: the prefill hop's own realized
                     # reuse/queue facts ride its single frame
                     "forensic": self._forensic(slot)},
        )
        self._send(slot, out)

    # -- speculative decoding (spec/) --------------------------------------
    def _spec_step(self) -> None:
        """One speculation round: propose up to k draft tokens per
        eligible slot (n-gram prompt lookup or the draft model), score
        all speculating slots' rows in ONE packed spec_verify program
        (segment-id causal attention over the paged cache — the chunked
        prefill machinery re-aimed at decode), then accept the longest
        distribution-preserving prefix host-side (sampler.py
        spec_accept_tokens) and roll the rejected tail's block growth
        back through the allocator.

        Slots that speculate this step skip the pipelined decode
        dispatch (their emission is synchronous — the verify fetch IS
        the step); everything else decodes as usual, so speculating and
        plain sequences mix freely in one scheduler step under the same
        token budget.  Guided/JSON-constrained slots, LoRA slots, and
        mid-pull disagg slots never speculate.  A slot whose acceptance
        EMA collapsed to k=0 rides the (faster, pipelined) plain decode
        path and re-probes every spec_probe_interval generated tokens —
        a probe is the only time the pipeline is drained on its behalf,
        which is what bounds the near-zero-acceptance regression."""
        self._specced = frozenset()
        if not self._spec_ok:
            return
        c = self.config
        cands = [s for s in self._slots
                 if s is not None and not s.prefilling and not s.pulling
                 and not s.awaiting_first  # first token still deferred
                 and not s.finished and s.guide is None
                 and s.lora_idx == 0]
        if not cands:
            return
        with self._phase("spec_dispatch") as ph:
            if not self._spec_round(cands, ph):
                ph.off_ring()

    def _spec_round(self, cands, ph) -> bool:
        """_spec_step's round over its candidate slots; False when no
        verify program was dispatched."""
        c = self.config
        rows = []
        budget = c.chunk_budget
        for s in cands:
            # an earlier candidate's probe drain can finish/preempt LATER
            # slots of this stale snapshot (same hazard as _decode_step's
            # grow loop): re-check before touching the allocator
            if s.finished or self._slots[s.index] is not s:
                continue
            if s.spec_k_cur < 0:
                s.spec_k_cur = c.spec_k
                s.spec_backoff = min(self.SPEC_PROBE_MIN,
                                     c.spec_probe_interval)
                # neutral prior: collapse needs a few rounds of real
                # rejection evidence, not one unlucky first verify
                s.spec_accept_ema = 0.5
            if (s.spec_k_cur == 0 or s.inflight > 0) \
                    and s.generated < s.spec_probe_at:
                continue
            if budget <= 1:
                # budget exhausted BEFORE the drain below: a probe
                # skipped here costs nothing and stays due next step —
                # draining first would flush the decode pipeline every
                # step for a probe that then never runs
                break
            if s.inflight > 0:
                # probe of a slot sitting in the pipelined decode path:
                # its latest tokens are device-side, so the proposer
                # would see a stale tail — drain first
                self._drain_inflight()
                if s.finished or self._slots[s.index] is not s \
                        or s.inflight:
                    continue
            k = max(1, s.spec_k_cur)
            # cap by table capacity (verify touches positions
            # [ctx, ctx+k]) and the step's remaining token budget
            k = min(k, c.max_context - 1 - s.ctx_len, budget - 1)
            k = self._spec_grow(s, k) if k > 0 else 0
            if k <= 0:
                self._spec_feedback(s, 0, 0)
                continue
            drafts = list(self.proposer.propose(
                s.seq.tokens, k, ctx=s.ctx_len, draft_pos=s.draft_pos,
                block_table=s.block_table))[:k]
            if not drafts:
                # nothing to try: a miss for the EMA; trim the
                # speculative growth and let plain decode take the slot
                self._spec_feedback(s, 0, 0)
                self._spec_trim(s)
                continue
            budget -= len(drafts) + 1
            rows.append((s, drafts))
        if not rows:
            return False
        from ..spec import plan_spec_verify

        plan = plan_spec_verify(
            rows, block_size=c.block_size,
            max_blocks_per_seq=c.max_blocks_per_seq,
        )
        a = plan.arrays
        if self.step_sink is not None:
            self.step_sink("spec_verify", dict(a))
        ids, vals, lse, self.kv = self._jit_spec_verify(
            self.params, self.kv,
            jnp.asarray(a["toks"]), jnp.asarray(a["positions"]),
            jnp.asarray(a["seg_ids"]), jnp.asarray(a["tables"]),
            jnp.asarray(a["valid"]), jnp.asarray(a["temps_t"]),
        )
        with self._phase("device_wait", what="spec_verify_fetch"):
            ids = np.asarray(ids)
            vals = np.asarray(vals)
            lse = np.asarray(lse)
        from .sampler import spec_accept_tokens

        proposed_total = accepted_total = 0
        specced = set()
        with self._phase("sample", what="spec_accept",
                         lanes=len(plan.rows)):
            for (s, drafts), off in zip(plan.rows, plan.offsets):
                n = len(drafts) + 1
                sm = s.request.sampling
                # host-side rng stream keyed (seed, position): replayed or
                # migrated requests re-draw identically, like the device
                # sampler's fold_in(seed, step)
                rng = np.random.default_rng(
                    (s.sampling_seed * 0x9E3779B1 + s.generated + 1)
                    & 0xFFFFFFFF)
                accepted, emitted = spec_accept_tokens(
                    ids[off:off + n], vals[off:off + n], lse[off:off + n],
                    drafts, greedy=sm.temperature <= 0.0, top_k=sm.top_k,
                    top_p=sm.top_p, rng=rng)
                proposed_total += len(drafts)
                accepted_total += accepted
                self._spec_feedback(s, accepted, len(drafts))
                specced.add(s.index)
                # the device token chain no longer feeds this lane: its true
                # last_token is now a host-side spec emission, so a later
                # decode burst must neither chain it nor treat the lane as a
                # pure continuation of the pre-spec descriptor
                self._chain_owner[s.index] = None
                ctx0 = s.ctx_len
                for tok in emitted:
                    s.ctx_len += 1
                    self.metrics["decode_tokens"] += 1
                    self._push_token(s, int(tok))
                    if s.finished:
                        break
                # the draft cache matches the real sequence through the
                # accepted prefix (the propose pass wrote draft KV for its k
                # INPUT positions [ctx0, ctx0+k-1]; the rejected tail is
                # overwritten on the next round).  Capped at ctx0+k: after
                # FULL acceptance the last draft token's own KV was never a
                # decode input, so that position must be re-prefilled
                s.draft_pos = min(s.ctx_len, ctx0 + len(drafts))
                if not s.finished:
                    self._spec_trim(s)
        self._specced = frozenset(specced)
        self.metrics["spec_steps"] = self.metrics.get("spec_steps", 0) + 1
        self.metrics["spec_proposed"] = \
            self.metrics.get("spec_proposed", 0) + proposed_total
        self.metrics["spec_accepted"] = \
            self.metrics.get("spec_accepted", 0) + accepted_total
        now = time.monotonic()
        gap = (now - self._fpm_last_spec_t
               if self._fpm_last_spec_t else 0.0)
        if gap > 1.0:
            gap = 0.0  # idle stretch, not verify latency: mark unknown
        # one FPM record per verify dispatch: the acceptance-rate input
        # FpmObserver.spec_acceptance aggregates for the SLA planner
        self.fpm.append({
            "t": now, "kind": "spec_verify", "lanes": len(plan.rows),
            "proposed": proposed_total, "accepted": accepted_total,
            "tokens": plan.tokens, "gap_s": gap,
        })
        self._fpm_last_spec_t = now
        return True

    def _spec_grow(self, s: _Slot, k: int) -> int:
        """Grow s's block table to cover verify positions [ctx, ctx+k];
        under allocation pressure shrink k to what the table already
        covers (0 = no speculation this step — plain decode handles the
        base position, preempting if even that fails)."""
        c = self.config
        bs = c.block_size
        nblocks = int(np.count_nonzero(s.block_table))
        while nblocks * bs <= s.ctx_len + k:
            if nblocks >= c.max_blocks_per_seq:
                break
            grow = self.allocator.append_block(self._seq_id(s))
            self._emit_events(grow)
            if grow.block_id is None:
                break
            s.block_table[nblocks] = grow.block_id
            nblocks += 1
        return min(k, nblocks * bs - 1 - s.ctx_len)

    def _spec_trim(self, s: _Slot) -> None:
        """Roll back speculative block growth: trailing blocks beyond the
        materialized context — the rejected drafts' KV slots — return to
        the allocator, so free-block accounting matches plain decode."""
        keep = max(-(-s.ctx_len // self.config.block_size), 1)
        res = self.allocator.trim_blocks(self._seq_id(s), keep)
        self._emit_events(res)
        s.block_table[keep:] = 0

    #: first re-probe distance (generated tokens); failed probes back
    #: off exponentially up to spec_probe_interval, so repetition that
    #: emerges mid-stream is discovered within ~8 tokens while a
    #: hopeless stream pays a pipeline drain only at 8/16/32/... marks
    SPEC_PROBE_MIN = 8

    def _spec_feedback(self, s: _Slot, accepted: int,
                       proposed: int) -> None:
        """Fold one speculation outcome into the slot's adaptivity
        state.  A proposer MISS (proposed == 0) carries no acceptance
        evidence — it was free if the slot wasn't pipelined — but
        re-attempting on a pipelined slot costs a drain, so misses only
        push the probe clock with exponential backoff.  VERIFIED rounds
        update the acceptance EMA: high acceptance runs the full spec_k,
        middling halves it, and an EMA below spec_accept_min collapses
        the slot to 0 (plain pipelined decode) until a probe fires."""
        c = self.config
        if proposed <= 0:
            s.spec_probe_at = s.generated + s.spec_backoff
            s.spec_backoff = min(s.spec_backoff * 2, c.spec_probe_interval)
            return
        rate = accepted / proposed
        s.spec_accept_ema = 0.7 * s.spec_accept_ema + 0.3 * rate
        if s.spec_accept_ema < c.spec_accept_min:
            s.spec_k_cur = 0
            s.spec_probe_at = s.generated + s.spec_backoff
            s.spec_backoff = min(s.spec_backoff * 2, c.spec_probe_interval)
        else:
            s.spec_backoff = min(self.SPEC_PROBE_MIN, c.spec_probe_interval)
            s.spec_k_cur = c.spec_k if s.spec_accept_ema >= 0.5 \
                else max(1, c.spec_k // 2)

    # -- decode -----------------------------------------------------------
    # decode burst size while prefill/admission work is pending, and
    # (PR 50) in a decode-only stretch while a lane stands free: single
    # stepping bounds how long a chunk waits behind decode, but every
    # dispatch has a fixed host cost — at burst 1 that interleave tax
    # can dominate the prefill phase.  A burst of 4 amortizes the
    # dispatch 4x while holding a prefill chunk back ~3 extra steps.
    # What a burst costs at its edges, f = 2 t(4) - t(8) of a burst
    # alone under the profiler (PERF.md section 5, PR 50): 2.55 ms on
    # Mistral-7B's 9.98 ms step (42.48 / 82.41 ms), 0.43 ms on
    # Moonlight's 2.86 (11.85 / 23.27), 0.4 ms on Nemotron's 4.45
    # (18.2 / 36.0) — so 4-step bursts cost f / 8 = 0.05-0.32 ms a
    # token over 8-step ones, and stand 4 steps less ahead of the next
    # arrival's first chunk.
    INTERLEAVE_BURST = 4

    def _fuse_ladder(self) -> List[int]:
        """The decode-burst sizes adaptive fusion can dispatch, ascending:
        1, then INTERLEAVE_BURST doubling up to decode_fused_steps.  One
        compiled (greedy, k) variant exists per rung (built at __init__,
        warmed by warmup_decode) — the ladder is the closed set of shapes
        serving can reach, so a ramp can never compile mid-serving."""
        fused = self.config.decode_fused_steps
        ladder = [1]
        k = min(self.INTERLEAVE_BURST, fused)
        while k > ladder[-1]:
            ladder.append(k)
            k = min(k * 2, fused)
        return ladder

    def _fused_k(self) -> Tuple[int, Optional[bool]]:
        """Decode-burst size for this step (the adaptive fusion policy)
        and, where the decode-only branch sized it, whether that branch
        HELD it at the interleave rung (None where pending work did).

        Pending admissions or prefill chunks run between SHORT decode
        bursts (chunked-prefill interleaving — a full burst would hold
        them back k steps): any pending work de-fuses to the interleave
        burst and resets the ramp.  In a decode-only stretch the burst
        ramps up the fusion ladder one rung per step — but (PR 50) not
        while a lane stands free on an engine that requests SHARE.
        With a lane free (the test _admit_waiting makes) the next
        arrival would be admitted in the very step that sees it, and
        the burst queued now is what stands ahead of its first chunk on
        the in-order device: it stays at the interleave rung and the
        ramp does not advance.  With every lane taken an arrival waits
        for a lane whatever the burst's length, so steady state reaches
        full decode_fused_steps within log2 steps (throughput), as
        before.  So does a SINGLE STREAM: a request that came to an
        empty engine ramps as it always did until a second one joins it
        (`_shared`, set at every admission: another request held a
        lane); one user of a server keeps full bursts, and a probe of
        one request still meets every rung's program."""
        c = self.config
        if self._jit_decode_multi is None:
            return 1, None
        short = min(self.INTERLEAVE_BURST, c.decode_fused_steps)
        if (self.waiting
                or any(s is not None and (s.prefilling or s.awaiting_first)
                       for s in self._slots)):
            self._decode_only_run = 0
            return short, None
        if self._shared and any(s is None for s in self._slots):
            return short, short < c.decode_fused_steps
        k = min(short << self._decode_only_run, c.decode_fused_steps)
        self._decode_only_run = min(self._decode_only_run + 1, 16)
        return k, False

    def _decode_step(self) -> None:
        """Grow the active slots' block tables, then build and dispatch
        one decode burst: one `decode_dispatch` phase (on the ring only
        when a burst went out).  The bursts at or over the pipeline
        depth were read back at the top of the step
        (_read_back_first)."""
        with self._phase("decode_dispatch") as ph:
            sent = self._decode_burst(ph)
            if not sent:
                ph.off_ring()
        if sent and not self._overlap:
            # lockstep reference mode: block on the burst and emit now
            self._drain_inflight()

    def _decode_burst(self, ph) -> bool:
        """_decode_step's body; False when nothing was dispatched.

        A step leaves `decode_pipeline_depth` bursts dispatched and
        unread: the one the device runs and, at the default of 2, ONE
        queued behind it, which is what hides the host's step (PERF.md
        section 6, PR 39: the depth sweep).  The oldest of them was read
        back at the top of the step, before admission
        (_read_back_first); the loop here holds the bound where that
        read was not due then (no lane was past its prompt, and a guided
        one left it in this very step).  Sync mode
        (overlap_scheduling=False) is lockstep: depth 1 and a drain
        right after dispatch, so tokens emit the step they were
        computed — the byte-identity reference the overlap tests pin."""
        c = self.config
        while len(self._inflight) >= self._depth:
            self._process_oldest_burst()
        k, held = self._fused_k()
        # slots that speculated this step already emitted synchronously
        # (engine/_spec_step); dispatching them again would double-step.
        # awaiting_first slots have no last_token yet (deferred prefill
        # readback) — they join decode the step after their flush.
        active = [s for s in self._slots
                  if s is not None and not s.prefilling
                  and not s.awaiting_first
                  and s.guide is None and s.index not in self._specced]
        if not active:
            return False
        # Every active slot MUST have a block for its next device position
        # ctx_len + inflight (preempt if even that fails); blocks for the
        # rest of the burst are speculative — under allocation pressure
        # degrade to k=1 instead of preempting a sequence for blocks it
        # won't need for k-1 more steps.
        for slot in active:
            # an intra-loop drain (below) can finish LATER slots of this
            # stale snapshot: growing a freed sequence would KeyError
            if slot.finished or self._slots[slot.index] is not slot:
                continue
            nblocks = int(np.count_nonzero(slot.block_table))
            if self._burst_reach(slot, 1) >= nblocks * c.block_size:
                if nblocks >= c.max_blocks_per_seq:
                    # capacity: the in-flight tokens already reach the end
                    # of the table — drain so the length-finish fires
                    # before any further dispatch for this slot
                    self._drain_inflight()
                    return False
                grow = self.allocator.append_block(self._seq_id(slot))
                self._emit_events(grow)
                if grow.block_id is None:
                    # drain first: processing may finish the slot or free
                    # enough blocks to retry; preemption is the last resort
                    self._drain_inflight()
                    if slot.finished or self._slots[slot.index] is not slot:
                        continue
                    grow = self.allocator.append_block(self._seq_id(slot))
                    self._emit_events(grow)
                    if grow.block_id is None:
                        self._preempt(slot)
                        continue
                slot.block_table[nblocks] = grow.block_id
                nblocks += 1
            while k > 1 and self._burst_reach(slot, k) \
                    >= nblocks * c.block_size:
                if nblocks >= c.max_blocks_per_seq:
                    # table is full: burst positions past it would clamp to
                    # the last column and overwrite that block's KV — run
                    # single-step and let _finish_reason handle capacity
                    k = 1
                    break
                grow = self.allocator.append_block(self._seq_id(slot))
                self._emit_events(grow)
                if grow.block_id is None:
                    k = 1  # pressure: this step runs single-step
                    break
                slot.block_table[nblocks] = grow.block_id
                nblocks += 1

        active = [s for s in self._slots
                  if s is not None and not s.prefilling
                  and not s.awaiting_first
                  and s.guide is None and s.index not in self._specced]
        if not active:
            return False

        # building + enqueuing the NEXT burst is host work; with unread
        # bursts in flight the device is still executing, so it is the
        # overlapped enqueue-ahead phase, not scheduler overhead (obs
        # vocabulary: `enqueue_ahead`, nested inside decode_dispatch so
        # innermost-span attribution keeps the wall partition exact).
        if self._overlap and self._inflight:
            with self._phase("enqueue_ahead", k=k):
                burst, cont_burst = self._build_burst(active, k)
        else:
            burst, cont_burst = self._build_burst(active, k)
        lanes = {}
        for s in active:
            s.inflight += k
            lanes[s.index] = (self._seq_id(s), s.epoch)
            self._chain_owner[s.index] = lanes[s.index]
        self._inflight.append({"burst": burst, "k": k, "lanes": lanes})
        if held is not None:
            self.metrics["decode_only_bursts"] += 1
            self.metrics["decode_held_bursts"] += held
        ph.set(cont=cont_burst, k=k, k_held=bool(held), lanes=len(active))
        return True

    def _burst_reach(self, slot: _Slot, k: int) -> int:
        """The last position a burst of `k` dispatched now may write for
        this slot, which its block table has to cover.  One token a
        step: the k positions after what is in flight.  Generation by
        blocks: a burst's unit is a pass and how far a lane advances is
        data; a block takes two passes at least (the one that unmasks
        the last of it and its commit), so the passes in flight and
        these k end at most (inflight + k) div 2 blocks on, inside the
        block after."""
        gb = self._gen_block
        if gb:
            return slot.ctx_len + gb * ((slot.inflight + k) // 2 + 1) - 1
        return slot.ctx_len + slot.inflight + k - 1

    def _chain_shape(self) -> tuple:
        """The device chain's shape: a token a lane, or a lane's whole
        state where the family generates by blocks."""
        B = self.config.max_num_seqs
        if self._gen_block:
            return (B, self.family.lane_state_width(self.model_cfg))
        return (B,)

    def _build_burst(self, active, k: int):
        """Build the descriptor of one decode burst over `active` and
        dispatch it (a device-resident continuation where provable).
        Returns (the unread burst [k, B], whether it was a
        continuation)."""
        c = self.config
        B = c.max_num_seqs
        # NOTE on buffer reuse: these descriptor arrays CANNOT be pooled /
        # double-buffered in place — jax.device_put may alias numpy memory
        # zero-copy (it does on CPU), continuation bursts keep the aliased
        # device descriptor live indefinitely, and the step sink hands the
        # same arrays to the loop thread.  Fresh arrays per full dispatch
        # are the double buffer: the previous generation stays pinned by
        # the in-flight burst while this one is built.
        gb = self._gen_block
        tokens = np.zeros(self._chain_shape(), np.int32)
        use_chain = np.zeros(B, bool)
        positions = np.zeros(B, np.int32)
        ctx_lens = np.zeros(B, np.int32)
        tables = np.zeros((B, c.max_blocks_per_seq), np.int32)
        seeds = np.zeros(B, np.int32)
        steps = np.zeros(B, np.int32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        valid = np.zeros(B, bool)  # padding rows pick no expert
        for s in active:
            i = s.index
            # a lane whose previous burst is unread takes its input token
            # from the device chain; host last_token would be k steps stale
            use_chain[i] = (
                self._chain_tokens is not None
                and self._chain_owner[i] == (self._seq_id(s), s.epoch)
                and (s.inflight > 0 or gb > 0)
            )
            if gb:
                # generation by blocks: a lane's state stays on the
                # device from its first burst on (a block half unmasked
                # is nowhere else); where a lane stands is data, so the
                # three clocks stay 0
                if not use_chain[i]:
                    # a lane that joins: its block starts at ctx_len
                    # (what is committed) and holds what is known
                    # beyond it, the prompt's last P mod B tokens
                    tokens[i] = self.family.new_lane_state(
                        self.model_cfg, s.ctx_len,
                        s.seq.tokens[s.ctx_len: s.ctx_len + gb])
            else:
                tokens[i] = s.last_token
                positions[i] = s.ctx_len + s.inflight
                ctx_lens[i] = s.ctx_len + s.inflight
                steps[i] = s.generated + s.inflight + 1
            tables[i] = s.block_table
            seeds[i] = s.sampling_seed
            temps[i] = s.request.sampling.temperature
            top_ks[i] = s.request.sampling.top_k
            top_ps[i] = s.request.sampling.top_p
            valid[i] = True

        # ONE descriptor for both the step stream and the local dispatch —
        # a key added to one but not the other would silently desynchronize
        # follower replay from the leader
        a = {
            "tokens": tokens, "use_chain": use_chain,
            "positions": positions, "tables": tables, "ctx_lens": ctx_lens,
            "seeds": seeds, "steps": steps, "temps": temps,
            "top_ks": top_ks, "top_ps": top_ps, "valid": valid,
        }
        if self.lora_bank is not None:
            lidx = np.zeros(B, np.int32)
            for s in active:
                lidx[s.index] = s.lora_idx
            a["lidx"] = lidx
        self._count_decode_attn(
            np.array([s.ctx_len for s in active], np.int32) if gb
            else ctx_lens[valid], k)
        cont_burst = self._is_continuation(a, active, k)
        if cont_burst:
            # steady state: nothing changed but the clock — advance the
            # device-resident descriptor in-program, upload nothing
            prev = self._last_desc
            adv = self._clock_advance(prev)
            greedy = bool(np.all(a["temps"] <= 0.0))
            if self.step_sink is not None:
                self.step_sink("decode_cont", {
                    "k": np.int32(k), "advance": np.int32(adv),
                    "greedy": np.int32(greedy),
                })
            burst = self._dispatch_decode_cont(k, adv, greedy)
            for name in ("positions", "ctx_lens", "steps"):
                prev[name] = prev[name] + adv
            prev["k"] = k
            self.metrics["cont_bursts"] = \
                self.metrics.get("cont_bursts", 0) + 1
        else:
            if self.step_sink is not None:
                # adaptive fusion: the burst size rides the descriptor so
                # followers dispatch the identical (greedy, k) program
                self.step_sink(
                    "decode_multi" if k > 1 else "decode",
                    {**a, "k": np.int32(k)} if k > 1 else a)
            burst = self._dispatch_decode(k, a)
            self._last_desc = {**a, "k": k}
            self._last_desc.pop("tokens", None)
            self._last_desc.pop("use_chain", None)
        # start the device->host copy NOW so the fetch in
        # _process_oldest_burst (>= 1 iteration later) finds the data
        # already local — a fresh fetch pays the full device->host
        # transfer latency even after compute finished
        try:
            burst.copy_to_host_async()
        except AttributeError:  # non-jax stand-ins in tests
            pass
        return burst, cont_burst

    def _decode_counts(self, ctx, k: int) -> Dict[str, int]:
        return self.family.decode_block_counts(
            self.model_cfg, ctx, k, self.config.block_size,
            self.config.max_num_seqs, self.config.max_blocks_per_seq,
            self.model_cfg.attn_impl)

    def _count_decode_attn(self, ctx, k: int):
        """How far decode attention's reads follow the live context, for
        a burst of `k` steps over active lanes holding `ctx` tokens:
        live = k x sum ceil((ctx + 1) / block_size); read = the blocks
        the impl moves by construction — every lane's whole table width
        per step for the gathering jnp paths, each step's live blocks
        for the Pallas kernels (GQA's and the latent cache's)."""
        bs = self.config.block_size
        layers, picks, held = self._moe
        # a pass runs a block's positions a lane where a step runs one
        rows = self._gen_block or 1
        self.metrics["moe_picks.decode"] += \
            k * len(ctx) * rows * layers * picks
        self.metrics["moe_expert_slots.decode"] += k * layers * held
        if layers and moe_form(self.model_cfg,
                               self.config.max_num_seqs * rows) == "visited":
            self.metrics["moe_visited_form_slots.decode"] += \
                k * layers * held
        if hasattr(self.family, "decode_block_counts"):
            # more than one kind of layer, or keys that are chosen: the
            # family counts its own
            for name, n in self._decode_counts(ctx, k).items():
                self.metrics[name] += n
            return
        self.metrics["decode_attn_live_blocks"] += \
            k * int(np.sum(-(-(ctx + 1) // bs)))
        if self.model_cfg.attn_impl in PALLAS_IMPLS:
            steps = ctx[:, None] + 1 + np.arange(k)[None, :]
            read = int(np.sum(-(-steps // bs)))
        else:
            read = k * self.config.max_num_seqs \
                * self.config.max_blocks_per_seq
        self.metrics["decode_attn_read_blocks"] += read

    GUIDED_TOPM = 32
    GUIDED_TOPM_WIDE = 256

    @staticmethod
    def _decode_topk_impl(family, model_cfg, mesh, m, params, kv, tokens,
                          positions, tables, ctx_lens, valid):
        """One decode step returning the top-M candidate ids + logits for
        every lane (guided decoding samples on HOST from this candidate
        set instead of shipping a 128k-vocab mask per token)."""
        logits, kv = family.decode(
            params, model_cfg, kv, tokens, positions, tables, ctx_lens,
            valid=valid, mesh=mesh,
        )
        vals, ids = jax.lax.top_k(logits.astype(jnp.float32), m)
        return ids, vals, kv

    def _topk_jit(self):
        """ONE lazy-init site for the guided top-M program — leader and
        follower must compile the identical collective program."""
        if getattr(self, "_jit_decode_topk", None) is None:
            w = self.compile_watch
            self._jit_decode_topk = w.wrap(jax.jit(
                w.named(partial(self._decode_topk_impl, self.family,
                                self.model_cfg, self.mesh,
                                self.GUIDED_TOPM), "decode_topk"),
                donate_argnums=(1,),
            ), "decode_topk")
        return self._jit_decode_topk

    def _topk_wide_jit(self):
        """Widened-M retry program (GUIDED_TOPM_WIDE candidates): compiled
        lazily on the first time a guided slot's top-M set has no valid
        continuation, before giving up and force-closing the document."""
        if getattr(self, "_jit_decode_topk_wide", None) is None:
            w = self.compile_watch
            self._jit_decode_topk_wide = w.wrap(jax.jit(
                w.named(partial(self._decode_topk_impl, self.family,
                                self.model_cfg, self.mesh,
                                self.GUIDED_TOPM_WIDE), "decode_topk_wide"),
                donate_argnums=(1,),
            ), "decode_topk_wide")
        return self._jit_decode_topk_wide

    def _guided_codec(self):
        """Token<->text codec for guided decoding; workers install the
        model's real tokenizer, presets fall back to the same mock
        byte tokenizer their model cards advertise."""
        codec = getattr(self, "guided_codec", None)
        if codec is None:
            from ..frontend.tokenizer import MockTokenizer

            codec = self.guided_codec = MockTokenizer(
                self.model_cfg.vocab_size)
        return codec

    def _guided_step(self) -> None:
        """One constrained token for every guided slot (guide != None).

        Each slot steps alone through the top-M program: candidates are
        tried in sampled order (deterministic gumbel over the top-M
        logits) and the first whose decoded text keeps the output a
        valid JSON prefix wins; EOS is admissible only once the document
        is complete.  When no candidate fits — or the token budget is
        about to run out mid-document — the canonical completion closes
        the document, so the response is ALWAYS schema-valid."""
        # awaiting_first: a guided+disagg slot defers its first-token
        # readback like any parked-to-be prefill (its completion PARKS
        # the KV at the next flush) — stepping it here meanwhile would
        # write a constrained token's KV past the prompt and corrupt
        # the parked prompt_len the decode side pulls
        gslots = [s for s in self._slots
                  if s is not None and not s.prefilling
                  and not s.awaiting_first
                  and s.guide is not None and not s.finished]
        if not gslots:
            return
        with self._phase("sample", what="guided", lanes=len(gslots)):
            # ONE init site (_topk_jit): a duplicate raw jax.jit here
            # would bypass the compile watchdog's wrapper — the guided
            # fork's 8-14s mid-serving compile is exactly what it must see
            self._topk_jit()
            self._guided_round(gslots, self._guided_codec())

    def _guided_round(self, gslots, codec) -> None:
        """One constrained token for each of `gslots` (see
        _guided_step)."""
        c = self.config
        B = c.max_num_seqs
        for slot in gslots:
            # block for the next position (no burst speculation needed)
            nblocks = int(np.count_nonzero(slot.block_table))
            if slot.ctx_len >= nblocks * c.block_size:
                if nblocks >= c.max_blocks_per_seq:
                    self._guided_finish(slot, codec, forced=True)
                    continue
                grow = self.allocator.append_block(self._seq_id(slot))
                self._emit_events(grow)
                if grow.block_id is None:
                    self._preempt(slot)
                    continue
                slot.block_table[nblocks] = grow.block_id
            a = {
                "tokens": np.zeros(B, np.int32),
                "positions": np.zeros(B, np.int32),
                "tables": np.zeros((B, c.max_blocks_per_seq), np.int32),
                "ctx_lens": np.zeros(B, np.int32),
                "valid": np.zeros(B, bool),
            }
            i = slot.index
            a["tokens"][i] = slot.last_token
            a["positions"][i] = slot.ctx_len
            a["ctx_lens"][i] = slot.ctx_len
            a["tables"][i] = slot.block_table
            a["valid"][i] = True
            if self.step_sink is not None:
                self.step_sink("decode_topk", a)
            ids, vals, self.kv = self._jit_decode_topk(
                self.params, self.kv, jnp.asarray(a["tokens"]),
                jnp.asarray(a["positions"]), jnp.asarray(a["tables"]),
                jnp.asarray(a["ctx_lens"]), jnp.asarray(a["valid"]),
            )
            slot.ctx_len += 1  # this step's KV write is in the cache
            s = slot.request.sampling
            text = codec.decode(slot.guided_out)

            def choose(cand_ids, cand_logits):
                if s.temperature <= 0.0:
                    order = np.argsort(-cand_logits)
                else:
                    g = np.random.default_rng(
                        (slot.sampling_seed + slot.generated)
                        & 0xFFFFFFFF).gumbel(size=cand_logits.shape)
                    order = np.argsort(-(cand_logits / s.temperature + g))
                for j in order:
                    tok = int(cand_ids[j])
                    if tok in self.eos_ids:
                        if slot.guide.done(text):
                            return ("eos", tok)
                        continue
                    if slot.guide.ok(codec.decode(slot.guided_out + [tok])):
                        return ("tok", tok)
                return None

            with self._phase("device_wait", what="guided_fetch"):
                cand_ids = np.asarray(ids[i])
                cand_vals = np.asarray(vals[i])
            chosen = choose(cand_ids, cand_vals)
            if chosen is None:
                # nothing in the top-M set extends the document: retry
                # once with a widened candidate set before giving up —
                # an uncooperative model may still have a valid token in
                # the tail of its distribution (the step re-runs the
                # same position; its KV rewrite is value-identical)
                self.metrics["guided_widened_retries"] = \
                    self.metrics.get("guided_widened_retries", 0) + 1
                if self.step_sink is not None:
                    self.step_sink("decode_topk_wide", a)
                wids, wvals, self.kv = self._topk_wide_jit()(
                    self.params, self.kv, jnp.asarray(a["tokens"]),
                    jnp.asarray(a["positions"]), jnp.asarray(a["tables"]),
                    jnp.asarray(a["ctx_lens"]), jnp.asarray(a["valid"]),
                )
                with self._phase("device_wait", what="guided_fetch"):
                    wid_i, wval_i = np.asarray(wids[i]), np.asarray(wvals[i])
                chosen = choose(wid_i, wval_i)
            if chosen is None:
                # even the widened set has no valid continuation: close
                # the document canonically (and say so in the response)
                self._guided_finish(slot, codec, forced=True)
                continue
            kind, tok = chosen
            if kind == "eos":
                self._guided_emit(slot, tok, "stop")
                continue
            slot.guided_out.append(tok)
            done = slot.guide.done(codec.decode(slot.guided_out))
            self._guided_emit(slot, tok, "stop" if done else None)
            if not slot.finished \
                    and slot.generated >= slot.request.stop.max_tokens:
                # budget exhausted mid-document: schema validity beats
                # the token budget — close canonically (a few tokens
                # over) instead of emitting truncated invalid JSON
                self._guided_finish(slot, codec, forced=True)

    def _guided_emit(self, slot: _Slot, tok: int,
                     finish: Optional[str]) -> None:
        """Stream one guided token with an EXPLICIT finish decision (the
        generic _finish_reason would truncate at max_tokens mid-document;
        the guided path closes the document instead)."""
        now = time.monotonic()
        if slot.last_push_t > 0.0:
            gap = now - slot.last_push_t
            self.itl_ema_s = gap if self.itl_ema_s == 0.0 \
                else 0.95 * self.itl_ema_s + 0.05 * gap
        slot.last_push_t = now
        slot.seq.append(tok)
        slot.last_token = tok
        slot.generated += 1
        self.metrics["decode_tokens"] += 1
        self._commit_full_blocks(slot)
        out = LLMEngineOutput(
            token_ids=[tok], finish_reason=finish,
            # same first/finish forensic stamping as _push_token
            metrics=({"forensic": self._forensic(slot)}
                     if (finish is not None or slot.generated == 1)
                     else None),
        )
        self._send(slot, out)
        if finish is not None:
            slot.finished = True
            if slot.index >= 0:
                self._slots[slot.index] = None
                slot.index = -1
            self._emit_events(self.allocator.free(self._seq_id(slot)))

    def _guided_finish(self, slot: _Slot, codec,
                       forced: bool = False) -> None:
        """Emit the canonical completion closing the document and finish
        the stream.  A non-empty completion means the engine, not the
        model, wrote the document's tail — surfaced per request in the
        final chunk's metrics (`guided_forced_close_tokens`) so clients
        can tell schema-valid-but-model-independent output from a real
        completion (the reference's token-mask approach cannot emit an
        invalid token in the first place; the top-M rescoring design
        trades that guarantee for TPU-side simplicity and must report
        when the trade bites)."""
        text = codec.decode(slot.guided_out)
        try:
            completion = slot.guide.complete(text)
        except ValueError:
            completion = ""
        toks = codec.encode(completion) if completion else []
        slot.guided_out.extend(toks)
        metrics: Dict[str, Any] = {"forensic": self._forensic(slot)}
        if toks or forced:
            self.metrics["guided_forced_closes"] = \
                self.metrics.get("guided_forced_closes", 0) + 1
            metrics["guided_forced_close_tokens"] = len(toks)
        out = LLMEngineOutput(token_ids=list(toks), finish_reason="stop",
                              metrics=metrics)
        self._send(slot, out)
        slot.finished = True
        if slot.index >= 0:
            self._slots[slot.index] = None
            slot.index = -1
        self._emit_events(self.allocator.free(self._seq_id(slot)))

    def _dispatch_decode(self, k: int, a: Dict[str, np.ndarray]):
        """Dispatch one full decode burst (shared by the scheduler and the
        multihost follower replay, so chain state stays symmetric).
        Returns the UNREAD burst device array [k, B], updates the
        device-side token chain, and persists the descriptor as the
        device pack continuations advance from (advance=0 here: the host
        arrays are already current)."""
        # dynlint: disable=DYN011 a["temps"] is the host-side numpy descriptor, not a device array
        greedy = bool(np.all(np.asarray(a["temps"]) <= 0.0))
        chain = self._chain_tokens
        if chain is None:
            chain = jax.device_put(
                jnp.zeros(self._chain_shape(), jnp.int32),
                self._desc_sharding)
        # COMMITTED uploads: continuation bursts feed the program's own
        # (committed) outputs back in, and a committed-vs-uncommitted
        # split on the same avals forks the jit cache — the fork's
        # compile then lands mid-serving (seconds per fork at serving
        # widths)
        sh = self._desc_sharding
        dd = {
            name: jax.device_put(a[name], sh)
            for name in ("tokens", "use_chain", "positions", "tables",
                         "ctx_lens", "seeds", "steps", "temps", "top_ks",
                         "top_ps", "valid")
        }
        dd["lidx"] = (jax.device_put(a["lidx"], sh) if "lidx" in a
                      else None)
        return self._run_decode(k, greedy, dd, chain, advance=0)

    def _dispatch_decode_cont(self, k: int, advance: int, greedy: bool):
        """Dispatch a continuation burst from the persisted device pack —
        zero host->device array uploads (the descriptor advances inside
        the SAME compiled program, advance=k).  Shared by the scheduler
        and follower replay (followers hold their own _dev_desc from
        replaying the preceding full burst).  All lanes chain (the host
        proved every active lane's last token is the device chain's)."""
        dd = self._dev_desc
        if dd.get("_all_chain") is None:
            dd["_all_chain"] = jax.device_put(
                jnp.ones((self.config.max_num_seqs,), bool),
                self._desc_sharding)
        dd = dict(dd, use_chain=dd["_all_chain"])
        self._dev_desc = dd
        return self._run_decode(k, greedy, dd, self._chain_tokens,
                                advance=advance)

    def _run_decode(self, k: int, greedy: bool, dd: Dict[str, Any],
                    chain, advance: int):
        # committed per-value device constants for the advance clock: a
        # raw python int is an UnspecifiedValue in the jit cache key and
        # forks the executable (see _dispatch_decode)
        adv = self._adv_consts.get(advance)
        if adv is None:
            adv = self._adv_consts[advance] = jax.device_put(
                jnp.int32(advance), self._desc_sharding)
        args = (
            self.params, self.kv, chain, dd["use_chain"], dd["tokens"],
            dd["positions"], dd["tables"], dd["ctx_lens"], dd["seeds"],
            dd["steps"], dd["temps"], dd["top_ks"], dd["top_ps"],
            dd["valid"], adv,
            self.lora_bank, dd["lidx"],
        )
        fn = self._jit_decode_multi[(greedy, k)] if k > 1 \
            else self._jit_decode[greedy]
        if self._gen_block:
            # generation by blocks: the chain is every lane's state
            burst, self.kv, self._chain_tokens = fn(*args)
        else:
            burst, self.kv, pos, ctx, steps = fn(*args)
            dd["positions"], dd["ctx_lens"], dd["steps"] = pos, ctx, steps
            self._chain_tokens = burst[k - 1]
        self._dev_desc = dd
        now = time.monotonic()
        gap = (now - self._fpm_last_decode_t
               if self._fpm_last_decode_t else 0.0)
        if gap > 1.0:
            gap = 0.0  # idle period, not decode latency: mark unknown
        self.fpm.append({
            "t": now, "kind": "decode", "k": k,
            "lanes": sum(1 for s in self._slots
                         if s is not None and not s.prefilling),
            # dispatch-to-dispatch gap: with the pipeline saturated this
            # IS the burst's wall time (k tokens per lane per gap);
            # 0.0 = unknown (first burst after an idle stretch)
            "gap_s": gap,
        })
        self._fpm_last_decode_t = now
        return burst

    def _clock_advance(self, prev: Dict[str, Any]) -> int:
        """What a continuation adds to the last burst's positions,
        context lengths and sampling steps: its k, a token a step.
        Generation by blocks: 0, where a lane stands rides the chain
        and the three clocks stay as they were uploaded."""
        return 0 if self._gen_block else prev["k"]

    def _is_continuation(self, a: Dict[str, np.ndarray], active,
                         k: int) -> bool:
        """True when this burst is provably the pure continuation of the
        last one: same k, same membership/tables/sampling, every lane's
        input token available in the device chain, and positions/steps
        exactly one advance ahead — so the device pack can evolve in
        place.  Requiring k == prev k keeps the compiled-variant set at
        (greedy, k) pairs the warm-up already hits; a k transition
        (prefill interleaving) takes the full path instead of compiling a
        fresh program mid-serving."""
        prev = self._last_desc
        if prev is None or self._dev_desc is None \
                or self._chain_tokens is None or k != prev["k"]:
            return False
        for s in active:
            if self._chain_owner[s.index] != (self._seq_id(s), s.epoch):
                return False
        m = a["valid"]
        adv = self._clock_advance(prev)
        return (
            np.array_equal(a["valid"], prev["valid"])
            and ("lidx" in a) == (prev.get("lidx") is not None)
            and np.array_equal(a["positions"][m], prev["positions"][m] + adv)
            and np.array_equal(a["ctx_lens"][m], prev["ctx_lens"][m] + adv)
            and np.array_equal(a["steps"][m], prev["steps"][m] + adv)
            and np.array_equal(a["tables"][m], prev["tables"][m])
            and np.array_equal(a["seeds"][m], prev["seeds"][m])
            and np.array_equal(a["temps"][m], prev["temps"][m])
            and np.array_equal(a["top_ks"][m], prev["top_ks"][m])
            and np.array_equal(a["top_ps"][m], prev["top_ps"][m])
            and ("lidx" not in a
                 or np.array_equal(a["lidx"][m], prev["lidx"][m]))
        )

    def _process_oldest_burst(self) -> None:
        """Read back the oldest dispatched burst and apply it: stream
        tokens, advance ctx, commit blocks, detect finishes.  Lanes whose
        slot finished/preempted/cancelled since dispatch are discarded
        (their KV writes went to blocks that are never committed past the
        finish, or to since-freed blocks that device program order
        guarantees were overwritten only by later dispatches)."""
        e = self._inflight.popleft()
        with self._phase("device_wait", k=e["k"], what="burst_fetch"):
            arr = np.asarray(e["burst"])  # [k (+ counters), B]
        # rows of tokens: a token a step, or a block's positions a pass
        # and the lanes' block starts under them
        gb = self._gen_block
        n_rows = e["k"] * gb + 1 if gb else e["k"]
        if self._kv_counters:
            # the rows under the tokens: running int32 totals on the
            # device; what they grew by (modulo the wrap) is added
            now = arr[n_rows:, 0].astype(np.int64)
            grew = (now - self._kv_counters_seen) % (1 << 32)
            self._kv_counters_seen = now
            for name, n in zip(self._kv_counters, grew):
                self.metrics[name] = self.metrics.get(name, 0) + int(n)
        with self._phase("emit", k=e["k"], what="burst") as emit:
            done0 = self.metrics.get("diff_blocks_done", 0)
            for i, ident in e["lanes"].items():
                s = self._slots[i] if i < len(self._slots) else None
                if s is None or (self._seq_id(s), s.epoch) != ident \
                        or s.finished:
                    continue
                s.inflight -= e["k"]
                if gb:
                    self._emit_blocks(s, arr[:n_rows, i], e["k"])
                    continue
                for j in range(e["k"]):
                    s.ctx_len += 1
                    self.metrics["decode_tokens"] += 1
                    self._push_token(s, int(arr[j, i]))
                    if s.finished:
                        # mid-burst finish: trailing sampled tokens
                        # discarded (their KV writes landed in this slot's
                        # own blocks, which are never committed past the
                        # finish ctx_len)
                        break
            if gb:
                # a burst of passes: the rows its program ran (counted
                # here, where the lane passes are, so that the two move
                # together) and the blocks that lost their last mask
                self.metrics["diff_rows"] += \
                    e["k"] * self.config.max_num_seqs * gb
                emit.set(blocks_out=self.metrics["diff_blocks_done"] - done0)

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._process_oldest_burst()

    def _bursts_behind(self) -> Tuple[int, int]:
        """(bursts in flight, those whose tokens are ready): asked when a
        phase ends that outlasted obs.PAUSE_S.  The device runs programs
        in order and a burst is tens of milliseconds of it, so after a
        `burst_fetch`, which popped the OLDEST burst, all of them ready
        says the chip ran on while the host waited, none ready that the
        chip itself stood."""
        bursts = [e["burst"] for e in self._inflight]
        return len(bursts), sum(1 for b in bursts if b.is_ready())

    def _commit_full_blocks(self, slot: _Slot) -> None:
        """Register newly-completed full blocks under their PLH.

        A block is only committed once every one of its tokens' K/V is
        materialized in the cache (covered by ctx_len).  The sampled token
        that *completes* a block has its K/V written on the NEXT decode
        step, so that block commits one step later; if the request finishes,
        is cancelled, or is preempted first, the trailing block is never
        registered — otherwise a later prompt could prefix-match a block
        whose final position holds zeros."""
        materialized = slot.ctx_len // self.config.block_size
        limit = min(slot.seq.num_full_blocks, materialized)
        while slot.committed_blocks < limit:
            idx = slot.committed_blocks
            h = slot.seq.block_hashes[idx]
            res = self.allocator.commit_block(self._seq_id(slot), idx, h)
            self._emit_events(res)
            slot.committed_blocks += 1

    def _forensic(self, slot: _Slot) -> Dict[str, Any]:
        """Worker-side forensic facts for the stream's first-token and
        finish frames (frontend/request_trace.py on_worker_stamp):
        REALIZED prefix-cache reuse (what this worker actually served
        from cache — the router's prediction-staleness feedback), the
        slot's waiting-queue position at enqueue, and step counts.
        Wire-safe scalars only; a handful of bytes on two frames per
        request is the plane's whole stream overhead."""
        return {
            "cached_tokens": slot.cached_tokens,
            "queue_pos": slot.queue_pos,
            "prefill_chunks": slot.prefill_chunks,
            "generated": slot.generated,
        }

    def _emit_blocks(self, s: _Slot, col: np.ndarray, k: int) -> None:
        """One lane's share of a burst of `k` passes of a family that
        generates by blocks: col = k x B rows of tokens (the block that
        lost its last mask in that pass, -1 elsewhere) and the lane's
        block start after the burst.  A block is emitted where its last
        mask goes (its commit pass is the next one), as ONE frame of the
        tokens that were not known before, truncated at `max_tokens`
        and at a stop token; what is committed (`ctx_len`) comes home
        with the burst, so prefix caching and a preemption's replay see
        committed blocks only.  Counts lane passes (a busy lane runs
        every pass of a burst, to the one that finishes it), the commit
        passes among them, and the tokens and blocks unmasked."""
        gb = self._gen_block
        m = self.metrics
        # a block emitted by the last burst's last pass commits first
        commits = int(len(s.seq) - s.ctx_len >= gb)
        for j in range(k):
            blk = col[j * gb:(j + 1) * gb]
            if blk[0] < 0:
                continue
            known = len(s.seq) % gb   # the prompt's tail: first block only
            new = [int(t) for t in blk[known:]]
            m["diff_blocks_done"] += 1
            m["diff_tokens_unmasked"] += len(new)
            self._stamp_first_token(s)
            before = s.generated
            self._push_tokens(s, new)
            m["decode_tokens"] += s.generated - before
            if s.finished:
                m["diff_lane_passes"] += j + 1
                m["diff_commit_passes"] += commits
                return
            commits += 1
        start = int(col[k * gb])
        m["diff_lane_passes"] += k
        m["diff_commit_passes"] += (start - s.ctx_len) // gb
        s.ctx_len = start
        self._commit_full_blocks(s)

    def _push_token(self, slot: _Slot, tok: int) -> None:
        """Append a generated token, stream it, handle finish."""
        self._push_tokens(slot, (tok,))

    def _push_tokens(self, slot: _Slot, toks) -> None:
        """Append generated tokens, stream them as ONE frame, handle
        finish: a token a step for most families, a block's tokens for
        one that generates by blocks (cut after the token that
        finishes the request)."""
        now = time.monotonic()
        if slot.last_push_t > 0.0:
            # per-slot gap EMA; burst-internal ~0 gaps and between-burst
            # step gaps average out to the true mean inter-token latency
            gap = (now - slot.last_push_t) / len(toks)
            self.itl_ema_s = gap if self.itl_ema_s == 0.0 \
                else 0.95 * self.itl_ema_s + 0.05 * gap
        slot.last_push_t = now
        first_frame = slot.generated == 0
        finish, sent = None, []
        for tok in toks:
            slot.seq.append(tok)
            slot.last_token = tok
            slot.generated += 1
            sent.append(tok)
            if slot.generated == 2:
                # request stage: the lane's first token out of a decode
                # burst (`generated` never goes back: once a request)
                slot.second_token_t = now
                self.metrics["req_stage_s.join"] += \
                    now - slot.first_token_t
                self.metrics["req_join_n"] += 1
                self._stage_spans(
                    slot, ("req_join", slot.first_token_t, now))
            finish = self._finish_reason(slot, tok)
            if finish:
                break
        self._commit_full_blocks(slot)
        # forensic stamp on the FIRST token frame and the finish frame
        # (frontend RequestTracker.on_worker_stamp): realized prefix
        # reuse lands with the first token — when the router's
        # predicted-vs-realized feedback wants it — and the finish
        # frame's step counts supersede it as the record's truth
        if finish:
            if slot.generated > 2:
                # request stage: second token -> finish, over the tokens
                # that came after the second
                self.metrics["req_stage_s.decode"] += \
                    now - slot.second_token_t
                self.metrics["req_decode_tokens"] += slot.generated - 2
                self._stage_spans(
                    slot, ("req_decode", slot.second_token_t, now))
            metrics = {"kv_usage": self.kv_usage(),
                       "cached_tokens": slot.cached_tokens,
                       "ttft_s": slot.first_token_t - slot.enqueued_t,
                       "forensic": self._forensic(slot)}
        elif first_frame:
            metrics = {"forensic": self._forensic(slot)}
        else:
            metrics = None
        out = LLMEngineOutput(
            token_ids=sent,
            finish_reason=finish,
            metrics=metrics,
        )
        if self._loop_ref is not None:
            self._send(slot, out)
        if finish is not None:
            slot.finished = True
            if slot.index >= 0:
                self._slots[slot.index] = None
            self._emit_events(self.allocator.free(self._seq_id(slot)))

    def _send(self, slot: _Slot, out: LLMEngineOutput) -> None:
        """Hand one frame to the request's stream, which the event loop
        owns.  The request's first frame goes through _emit_first, which
        closes the request's stages where the frame reaches the stream."""
        put = slot.out_q.put_nowait
        if not slot.first_sent:
            slot.first_sent = True
            put = partial(self._emit_first, slot)
        if self._loop_ref is not None:
            self._loop_ref.call_soon_threadsafe(put, out)
        else:
            put(out)

    def _emit_first(self, slot: _Slot, out: LLMEngineOutput) -> None:
        """On the event loop: put the request's first frame on its stream
        and add its stages to `req_stage_s.*` (seconds): queue, prefill
        and emit, whose sum is the engine's time to first token, enqueue
        to stream; and the queue's three waits, wake (for the scheduler
        thread to come round) + lane (for a lane and blocks) + turn (for
        its turn at a prefill program) = queue.  A stage the request
        skipped (a pulled prompt has no dispatch of its own) adds 0.
        Under a Tracer the stages are also ring spans on the request's
        own track."""
        slot.out_q.put_nowait(out)
        now = time.monotonic()
        t_first = slot.first_token_t or now
        t_disp = slot.dispatched_t or t_first
        t_seen = slot.seen_t or slot.enqueued_t
        t_adm = slot.admitted_t or t_seen
        m = self.metrics
        m["req_stage_s.queue"] += t_disp - slot.enqueued_t
        m["req_stage_s.wake"] += t_seen - slot.enqueued_t
        m["req_stage_s.lane"] += t_adm - t_seen
        m["req_stage_s.turn"] += t_disp - t_adm
        m["req_ahead_steps"] += slot.ahead_steps
        m["req_stage_s.prefill"] += t_first - t_disp
        m["req_stage_s.emit"] += now - t_first
        m["req_stage_n"] += 1
        self._stage_spans(
            slot, ("req_queue", slot.enqueued_t, t_disp),
            ("req_wake", slot.enqueued_t, t_seen),
            ("req_lane", t_seen, t_adm), ("req_turn", t_adm, t_disp),
            ("req_prefill", t_disp, t_first), ("req_emit", t_first, now))

    @staticmethod
    def _stage_spans(slot: _Slot, *spans) -> None:
        """Under a Tracer: request stages (kind, t0, t1) as ring spans on
        the request's own track (they cross threads, so they are not
        TraceMes)."""
        tr = obs.tracer()
        if tr is None:
            return
        rid = slot.request.request_id
        tid = obs.trace_id_from_annotations(slot.request.annotations)
        for kind, t0, t1 in spans:
            tr.record(kind, t0, t1, {"request_id": rid}, tid, f"req:{rid}")

    def _preempt(self, slot: _Slot) -> None:
        """KV OOM: drop the slot's blocks and re-enqueue with full replay."""
        self.metrics["preemptions"] += 1
        self._slots[slot.index] = None
        self._emit_events(self.allocator.free(self._seq_id(slot)))
        slot.index = -1
        slot.ctx_len = 0
        slot.prefill_pos = 0
        slot.prompt_len = 0
        slot.committed_blocks = 0
        slot.block_table[:] = 0
        # stale in-flight bursts for this slot must be discarded on
        # processing (its lanes are keyed by (seq_id, epoch))
        slot.epoch += 1
        slot.inflight = 0
        # the draft-model cache for the freed blocks is stale: replay
        # re-prefills the draft from position 0 (spec/draft.py)
        slot.draft_pos = 0
        with self._qlock:
            self.waiting.insert(0, slot)

    def _finish_reason(self, slot: _Slot, tok: int) -> Optional[str]:
        st = slot.request.stop
        if not st.ignore_eos and tok in self.eos_ids:
            return "stop"
        if tok in (st.stop_token_ids or []):
            return "stop"
        if slot.generated >= st.max_tokens:
            return "length"
        if slot.ctx_len + 1 >= self.config.max_context:
            return "length"
        gb = self._gen_block
        if gb and len(slot.seq) + gb > min(
                self.config.max_context,
                self.config.max_blocks_per_seq * self.config.block_size):
            return "length"   # no room for one more block
        return None
