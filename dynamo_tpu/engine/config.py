"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

from ..disagg.transfer import DEFAULT_CHUNK_BYTES
from ..models import PRESETS
from ..parallel.mesh import MeshConfig


@dataclass
class EngineConfig:
    model: str = "tiny"  # preset name (models.PRESETS, all families)
    model_config: Optional[object] = None  # LlamaConfig | DeepseekConfig
    model_name: str = ""  # served model name; defaults to preset name
    # local HF checkpoint dir (config.json + *.safetensors + tokenizer);
    # when set it overrides `model` and the engine serves real weights
    model_path: str = ""

    # paged KV cache.  Default block_size is 128 (lane-aligned) so the
    # Pallas decode kernel's auto-dispatch engages on TPU; CPU/test configs
    # pass smaller blocks and take the jnp path.
    block_size: int = 128         # tokens per block == PLH hashing block size
    num_blocks: int = 128         # physical blocks (id 0 is garbage)
    max_blocks_per_seq: int = 64  # max context = block_size * this
    enable_prefix_caching: bool = True
    # KV cache storage dtype (quant/kv.py): "bf16" stores the model dtype
    # (the pre-quantization behavior, byte-identical); "int8" stores
    # symmetric per-(layer, kv_head, block, position) quantized K/V with
    # fp32 scale planes riding as sibling arrays — roughly half the HBM
    # bytes per token, so the decode read streams half the traffic and a
    # fixed budget holds ~1.9x the blocks.  Families without a quantized
    # path (MLA) auto-fall back to bf16 with a warning, following the
    # MLA/MoE fallback precedent; the worker MDC advertises the EFFECTIVE
    # dtype.  Quantized payloads ride disagg transfer and the KVBM tiers
    # as int8 + scales (half the wire/host bytes too).
    kv_cache_dtype: str = "bf16"
    # KV HBM budget in GB: when > 0, num_blocks is DERIVED from the
    # bytes-per-block of the resolved model at the effective
    # kv_cache_dtype (quant/kv.py blocks_for_hbm_budget), so switching
    # bf16 -> int8 at a fixed budget yields ~2x blocks instead of the
    # same block count at half the memory.  0 keeps num_blocks as given.
    kv_hbm_gb: float = 0.0
    # KV block-lifecycle ledger + invariant auditor (obs/kv_ledger.py):
    # None = follow DYN_KV_LEDGER (always-on by default, "0" disables);
    # True/False pins the plane per engine — bench_serving's
    # --kv-ledger ab uses this to A/B the overhead in one invocation.
    kv_ledger: Optional[bool] = None

    # batching
    max_num_seqs: int = 8

    # decode burst: fuse this many decode steps into ONE compiled program
    # (lax.scan) when no prefill/admission work is pending.  Fusing
    # amortizes the fixed per-dispatch host cost k-fold at the cost of
    # k-token output bursts and up to k-1 wasted steps when a sequence
    # finishes mid-burst.  1 disables.  With decode_pipeline_depth 2 the
    # one queued burst of 8 is what a new request's first chunk stands
    # behind in a decode-only stretch (6.0-6.1 steps on average on the
    # chip, PERF.md section 6, PR 39); a shorter rung while few lanes
    # decode is ROADMAP S2's next step, not measured yet.
    decode_fused_steps: int = 8
    # decode output pipelining: keep up to depth-1 dispatched bursts
    # UNREAD while the next one runs, chaining sampled ids on device — the
    # host fetch of burst N then overlaps bursts N+1..N+depth-1's compute
    # instead of stalling on a device sync every burst.  Emission and
    # stop detection lag by up to (depth-1)*decode_fused_steps tokens
    # (overshoot is discarded, same as a mid-burst finish).  1 = fetch
    # synchronously every burst.  Depth d gives the async device->host
    # copy d-1 burst intervals to land before the host reads it.  The
    # order of a step is READ BACK FIRST, ADMIT AFTER (core._sched_step):
    # the oldest burst at or over the depth is read before admission, so
    # depth - 1 bursts stand ahead of a new request's first chunk.
    # Measured on a TPU v5e with that order in (PERF.md section 6, PR 39,
    # depth 1 / 2 / 3 / 4): mistral-7b.chat ttft_p50_ms 62 / 134 / 204 /
    # 286; ling-3.0-flash.longgen-closed, the host's longest step (31-41
    # ms), output_tok_per_s 2348 / 2927 / 2919 / 2907 with the chip
    # 0.03 % idle at 2 and at 3.  2 is the smallest depth at which no
    # closed loop idles or loses throughput: ONE burst behind the one
    # that runs hides the host's step, three do not hide it better and
    # each costs a new request decode_fused_steps steps of waiting.
    # Depth 1 pays the host's step between bursts (-20 % on Ling).
    # Only effective with overlap_scheduling on; sync mode is lockstep
    # (depth 1 and drain-after-dispatch) regardless of this value.
    decode_pipeline_depth: int = 2
    # overlapped scheduler (the ROADMAP item-3 refactor): while step N's
    # programs execute on device, the host schedules and enqueues step
    # N+1 — decode bursts pipeline to decode_pipeline_depth, a completing
    # prefill chunk's first-token readback is DEFERRED one step (the
    # device_wait then pays only for the previous step's work, and
    # streaming emission is one step late for exactly that first token),
    # and host scheduling done while the device is busy is attributed to
    # the `enqueue_ahead` span instead of `sched` (obs/report.py keeps
    # the wall partition exact; sched_overhead_frac counts only host
    # time the device actually waited on).  False = lockstep reference
    # mode: schedule -> dispatch -> block on device -> emit, greedy
    # byte-identical to overlapped mode by construction (the test matrix
    # in tests/test_overlap.py asserts it, including cancellation, chaos
    # and drain).
    overlap_scheduling: bool = True
    # SLA-aware admission (closes the PR 1 mixed-scheduling loop against
    # the PR 7 SLO plane): when the frontend-published error-budget burn
    # rate (obs/slo.py; worst window, fed to the engine by the worker's
    # slo_metrics subscription) exceeds this threshold while decodes are
    # active, the per-step prefill chunk budget is scaled down by
    # threshold/burn (floored at the smallest prefill bucket) — prefill
    # chunks yield to decode until ITL recovers.  0 disables.
    slo_yield_burn: float = 1.0
    # a burn signal older than this is ignored (frontend gone / SLO
    # plane off must not keep throttling prefill forever)
    slo_burn_stale_s: float = 10.0
    prefill_buckets: Tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048)
    # per-scheduler-step token budget: one prefill chunk is capped to
    # max_batch_tokens minus one token per decoding slot, so decode ITL is
    # bounded by a single chunk's compute (vLLM chunked-prefill semantics)
    max_batch_tokens: int = 2048
    # concurrent-arrival prefill: up to this many prefilling sequences run
    # their chunks in ONE batched program per scheduler step (the token
    # budget is split across them).  Short prompts that would each waste
    # most of max_batch_tokens fill it together, so TTFT under queue depth
    # does not serialize.  1 disables batching (always the B=1 program).
    max_prefill_seqs: int = 4
    # packed chunked prefill (engine/prefill.py + ops/packed_prefill.py):
    # co-scheduled prompts/chunks concatenate into one padding-free token
    # stream with segment ids instead of padding each row to a bucket.
    # Auto-falls back to the padded paths for families without
    # prefill_packed (MLA).
    prefill_packed: bool = True
    # chunk budget for one packed prefill dispatch (the chunk-budget knob:
    # bounds how long a prefill program can hold decode back, so decode
    # ITL during a prefill burst is capped by one chunk's compute).
    # 0 = use max_batch_tokens.
    prefill_chunk_tokens: int = 0
    # decode attention impl override ("" = keep the model family's
    # default): "auto" | "pallas" | "pallas_interpret" | "jnp" |
    # "jnp_bf16" — the ops/paged_attention.py dispatch.  Every choice
    # accepts int8 caches (the Pallas kernel dequantizes in-kernel);
    # "pallas_interpret" exists for CPU testing.  Replaces the resolved
    # model config's attn_impl field, so a preset model can take the
    # kernel per worker without a custom model_config.
    attn_impl: str = ""
    # packed-prefill attention impl override ("" = family default):
    # "auto" (ops/packed_prefill.resolve_packed_impl: the kernel on a
    # TPU from 1024 tokens a program, the scan elsewhere) | "xla" (the float32 scan, S-fold attention
    # FLOPs) | "pallas"/"pallas_interpret" (the tile-skip kernel,
    # ops/pallas_packed_prefill.py).  Also selects the impl for
    # spec_verify, which rides the same packed path.
    packed_attn_impl: str = ""
    # fused sampling/top-k epilogue (ops/fused_sampling.py): "fused"
    # streams the decode final projection in vocab tiles and emits only
    # sampled token ids — the [B, vocab] fp32 logits tensor never
    # round-trips HBM on the decode / fused-decode-ladder paths (byte-
    # identical at greedy, distribution-identical seeded sampling).
    # "off" keeps the reference path (materialized logits ->
    # engine/sampler.py), which remains the fallback for families
    # without a hidden-state decode surface (MLA) — those fall back
    # with a warning, like the int8-KV precedent, and the worker MDC
    # advertises the EFFECTIVE mode.
    sampling_epilogue: str = "off"

    # speculative decoding (spec/): emit more than one ACCEPTED token per
    # weight/KV pass once decode is memory-bandwidth-bound.  "ngram" is
    # the zero-weight prompt-lookup proposer (drafts from the sequence's
    # own history; free when it doesn't match); "draft" runs a second,
    # smaller model on the same mesh (greedy k-step drafts via fused
    # decode_multi; single-host slices only in v1).  Verification scores
    # all speculating sequences' drafts in ONE packed segment-id program
    # (spec_verify, reusing ops/packed_prefill.py attention) and accepts
    # via rejection sampling that provably preserves the decode sampler's
    # distribution — greedy output is token-identical to plain decode.
    # Guided/JSON-constrained requests, LoRA sequences, and MLA families
    # always fall back to plain decode.  "off" disables.
    spec_decode: str = "off"
    # max draft tokens per speculation round.  The effective per-sequence
    # draft length adapts BELOW this via an acceptance-rate EMA — down to
    # 0 (= plain pipelined decode) when speculation stops paying, with a
    # probe every spec_probe_interval generated tokens to re-engage.
    spec_k: int = 4
    # n-gram proposer: suffix lengths tried for the history match,
    # longest (strongest signal) first
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # draft model, first match wins: explicit config object (tests) >
    # HF checkpoint dir > preset name.  Vocab must equal the target's.
    spec_draft_config: Optional[object] = None
    spec_draft_model_path: str = ""
    spec_draft_model: str = ""
    # acceptance EMA below this collapses the sequence to plain decode
    spec_accept_min: float = 0.15
    # MAX probe distance (generated tokens) for collapsed/missing slots:
    # failed probes back off exponentially from 8 up to this cap.  Each
    # probe on a pipelined slot costs one pipeline drain + one proposer
    # attempt, so the cap bounds the near-zero-acceptance regression
    # (< 2%) while mid-stream repetition is still discovered quickly.
    spec_probe_interval: int = 64

    # KVBM tiers (kvbm/): 0 disables the G2 host cache.  When enabled, the
    # scheduler offloads the coldest evictable HBM blocks to host DRAM once
    # free blocks fall below offload_watermark_blocks (one batched
    # device→host gather per step), and onboards G2/G3 prefix hits at
    # admission instead of recomputing prefill.
    host_cache_blocks: int = 0
    disk_cache_dir: Optional[str] = None   # G3; needs disk_cache_blocks > 0
    disk_cache_blocks: int = 0
    # G4 cluster-shared object store (kvbm/object_store.py): demotions
    # that would otherwise drop spill here; any worker onboards them
    object_store_dir: Optional[str] = None
    object_store_ttl_s: Optional[float] = None
    # cross-worker G2 pull (kvbm/remote.py): prefetch missing prefix
    # blocks from a peer's host cache at admission time
    kvbm_remote: bool = True
    kvbm_remote_max_blocks: int = 64
    offload_watermark_blocks: int = 0      # 0 = num_blocks // 4
    offload_batch: int = 16                # max blocks gathered per step
    # KV integrity / degraded modes (kvbm/object_io.py, kvbm/breaker.py):
    # every G4 op the serving path issues is awaited at most
    # kv_io_deadline_s on a dedicated I/O thread; kv_breaker_threshold
    # consecutive per-tier failures trip that tier's circuit breaker
    # open (priced as recompute in the advertised kv_tier_costs) until a
    # half-open probe succeeds after kv_breaker_cooldown_s
    kv_io_deadline_s: float = 0.25
    kv_breaker_threshold: int = 3
    kv_breaker_cooldown_s: float = 30.0

    # disagg KV transfer: bound on one wire frame's K+V payload bytes
    # (disagg/transfer.py chunk sizing)
    transfer_chunk_bytes: int = DEFAULT_CHUNK_BYTES

    # LoRA serving (lora/): 0 disables.  max_adapters counts usable slots
    # (slot 0 is reserved for "no adapter"); adapters load lazily from
    # lora_dir (shared PEFT checkpoint tree) on first request and evict
    # LRU.  Ranks are padded to lora_rank; larger ranks are rejected.
    lora_max_adapters: int = 0
    lora_rank: int = 16
    lora_dir: Optional[str] = None

    # parallelism.  sp > 1 enables sequence-parallel ring-attention
    # prefill for prompts beyond the largest prefill bucket (the
    # long-context path; ops/ring_attention.py) — dp*tp*sp must divide
    # the device count
    dp: int = 1
    tp: int = 1
    sp: int = 1

    # disaggregation role: "both" serves agg traffic; "prefill" workers run
    # prefill-only hops and park KV; "decode" workers pull and decode
    role: str = "both"

    # compile every decode-program variant before serving traffic
    # (core.py warmup_decode) — on by the CLI worker/bench; default off so
    # short-lived test engines skip the extra compiles
    warmup: bool = False

    # None = resolve from the checkpoint's config.json (model_path) or 2
    eos_token_id: Optional[int] = None
    # output parsing advertised in the MDC: frontends split <think> spans
    # into reasoning_content when set (e.g. "deepseek_r1")
    reasoning_parser: str = ""
    seed: int = 0

    def resolve_model(self):
        if self.model_config is not None:
            return self.model_config
        if self.model_path:
            from .loader_cache import cached_hf_config

            return cached_hf_config(self.model_path)
        if self.model not in PRESETS:
            raise ValueError(
                f"unknown model preset {self.model!r}; have {sorted(PRESETS)}"
            )
        return PRESETS[self.model]

    @property
    def served_name(self) -> str:
        return self.model_name or self.resolve_model().name

    @property
    def max_context(self) -> int:
        return self.block_size * self.max_blocks_per_seq

    @property
    def chunk_budget(self) -> int:
        """Effective per-step prefill token budget."""
        return self.prefill_chunk_tokens or self.max_batch_tokens

    def resolve_eos_ids(self) -> Tuple[int, ...]:
        """Stop-token set: explicit override > checkpoint config > default.
        The checkpoint path reuses cached_hf_config (one config.json parse
        per path, same error surface as resolve_model)."""
        if self.eos_token_id is not None:
            return (self.eos_token_id,)
        return self.resolve_model().eos_token_ids
