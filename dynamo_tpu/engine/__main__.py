"""`python -m dynamo_tpu.engine` — run a JAX engine worker.

The TPU-native equivalent of `python -m dynamo.vllm`
(ref: components/src/dynamo/vllm/main.py:114).

The backend is whatever JAX's default is; `JAX_PLATFORMS` is the one way
to choose another (e.g. `JAX_PLATFORMS=cpu` with
`XLA_FLAGS=--xla_force_host_platform_device_count=8` for a virtual mesh).
This process takes the chip and keeps it until it exits: nothing that
needs the chip may run beside it.
"""

import argparse
import asyncio
import json
import os

from .. import obs
from ..runtime import DistributedRuntime
from ..runtime.device import device_identity, enable_compile_cache
from ..runtime.logging import setup_logging
from .config import EngineConfig
from .worker import JaxEngineWorker


def build_args() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("dynamo_tpu.engine")
    p.add_argument("--model", default="tiny", help="model preset name")
    p.add_argument("--model-path", default="",
                   help="local HF checkpoint dir (overrides --model)")
    p.add_argument("--model-name", default="", help="served model name")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--block-size", type=int, default=128)
    p.add_argument("--num-blocks", type=int, default=128)
    p.add_argument("--max-blocks-per-seq", type=int, default=64)
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--no-prefix-caching", action="store_true")
    p.add_argument("--kv-cache-dtype", default="bf16",
                   choices=["bf16", "int8"],
                   help="KV storage dtype (quant/kv.py): int8 halves KV "
                        "bytes/token and ~doubles blocks per HBM budget; "
                        "MLA families fall back to bf16")
    p.add_argument("--kv-hbm-gb", type=float, default=0.0,
                   help="KV HBM budget in GB: derive --num-blocks from "
                        "bytes-per-block at the effective kv dtype "
                        "(0 = use --num-blocks as given)")
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="chunked-prefill token budget per scheduler step "
                        "(bounds decode ITL during prefill bursts); "
                        "0 = max_batch_tokens")
    from ..ops.packed_prefill import PACKED_IMPLS
    from ..ops.paged_attention import DECODE_IMPLS

    p.add_argument("--attn-impl", default="",
                   choices=["", *DECODE_IMPLS],
                   help="decode attention impl (ops/paged_attention.py):"
                        " pallas = hand-tiled DMA kernel (int8 caches "
                        "dequantize in-kernel), jnp/jnp_bf16 = XLA "
                        "gather paths; default keeps the model family's "
                        "choice")
    p.add_argument("--packed-attn-impl", default="",
                   choices=["", *PACKED_IMPLS],
                   help="packed-prefill attention impl "
                        "(ops/pallas_packed_prefill.py): pallas = "
                        "segment-aware tile-skip kernel (no S-fold "
                        "attention overhead), xla = float32 scan; "
                        "default keeps the model family's choice "
                        "(auto: by platform, cache and stream length)")
    from ..ops.fused_sampling import EPILOGUE_MODES

    p.add_argument("--sampling-epilogue", default="off",
                   choices=list(EPILOGUE_MODES),
                   help="fused sampling/top-k epilogue "
                        "(ops/fused_sampling.py): fused = stream the "
                        "decode final projection in vocab tiles and "
                        "emit only token ids (no [B, vocab] logits in "
                        "HBM; byte-identical at greedy); off = the "
                        "reference materialize-then-sample path; "
                        "families without a hidden-state decode "
                        "surface (MLA) fall back to off")
    p.add_argument("--no-packed-prefill", action="store_true",
                   help="disable packed chunked prefill (use the padded "
                        "per-row programs)")
    p.add_argument("--host-cache-blocks", type=int, default=0,
                   help="G2 host-DRAM KV cache capacity (blocks); 0 off")
    p.add_argument("--offload-watermark-blocks", type=int, default=0,
                   help="offload coldest HBM blocks to G2 once free blocks "
                        "fall below this (0 = num_blocks/4); raise toward "
                        "num_blocks so allocation bursts can't evict a "
                        "block before the offload pass copies it")
    p.add_argument("--disk-cache-dir", default="",
                   help="G3 disk KV cache directory")
    p.add_argument("--disk-cache-blocks", type=int, default=0)
    p.add_argument("--object-store-dir",
                   default=os.environ.get("DYN_KVBM_OBJECT_DIR", ""),
                   help="G4 cluster-shared object store (shared FS path; "
                        "defaults to $DYN_KVBM_OBJECT_DIR)")
    p.add_argument("--kv-io-deadline-s", type=float, default=0.25,
                   help="per-op deadline for shared-FS (G4) KV I/O on the "
                        "dedicated I/O thread; a wedged mount is a bounded "
                        "timeout off the scheduler path")
    p.add_argument("--kv-breaker-threshold", type=int, default=3,
                   help="consecutive tier failures that trip the tier's "
                        "circuit breaker open (tier skipped and priced at "
                        "recompute until a half-open probe succeeds)")
    p.add_argument("--kv-breaker-cooldown-s", type=float, default=30.0,
                   help="seconds an open tier breaker waits before "
                        "admitting one half-open probe op")
    p.add_argument("--no-kvbm-remote", action="store_true",
                   help="disable cross-worker G2 pull")
    p.add_argument("--migration-limit", type=int, default=3)
    p.add_argument("--no-warmup", action="store_true",
                   help="skip decode-variant precompilation at startup")
    p.add_argument("--role", default="both",
                   choices=["both", "prefill", "decode"])
    p.add_argument("--reasoning-parser", default="",
                   help="advertise a reasoning parser (e.g. deepseek_r1) "
                        "so frontends split <think> spans")
    p.add_argument("--lora-dir", default=os.environ.get("DYN_LORA_PATH", ""),
                   help="PEFT adapter tree (lora/source.py); empty = off")
    p.add_argument("--lora-max-adapters", type=int, default=4)
    p.add_argument("--lora-rank", type=int, default=16)
    p.add_argument("--spec-decode", default="off",
                   choices=["off", "ngram", "draft"],
                   help="speculative decoding proposer (spec/): ngram = "
                        "zero-weight prompt lookup; draft = a second "
                        "model on the same mesh (single-host v1)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens per speculation round "
                        "(per-sequence acceptance EMA adapts below this)")
    p.add_argument("--spec-draft-model", default="",
                   help="draft model preset for --spec-decode draft")
    p.add_argument("--spec-draft-model-path", default="",
                   help="draft HF checkpoint dir (overrides the preset)")
    p.add_argument("--drain-deadline-s", type=float, default=5.0,
                   help="SIGTERM grace: in-flight requests get this long "
                        "to finish before the rest error with the "
                        "migratable 'worker draining' marker and replay "
                        "on surviving workers")
    p.add_argument("--no-overlap-scheduling", action="store_true",
                   help="lockstep reference scheduler (schedule -> "
                        "dispatch -> block -> emit) instead of the "
                        "overlapped default; greedy output is "
                        "byte-identical, served throughput is not")
    p.add_argument("--slo-yield-burn", type=float, default=1.0,
                   help="SLA-aware admission: prefill chunks yield "
                        "budget to decode while the frontend-published "
                        "SLO burn rate exceeds this (0 disables)")
    return p


async def main() -> None:
    setup_logging()
    # timeline tracing (obs/): DYN_TRACE=1 installs the process
    # tracer; DYN_TRACE_OUT gets a Chrome trace dump at exit
    obs.install_from_env()
    args = build_args().parse_args()
    # before the first compile: persistent compile cache at
    # $JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout directory
    cache_dir = enable_compile_cache()
    # the device as THIS process (the one that holds it) sees it —
    # launchers read this line instead of touching JAX themselves
    print("device " + json.dumps(
        {**device_identity(), "compile_cache": cache_dir}), flush=True)
    config = EngineConfig(
        model=args.model,
        model_path=args.model_path,
        model_name=args.model_name,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        max_blocks_per_seq=args.max_blocks_per_seq,
        max_num_seqs=args.max_num_seqs,
        tp=args.tp,
        dp=args.dp,
        enable_prefix_caching=not args.no_prefix_caching,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_hbm_gb=args.kv_hbm_gb,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        prefill_packed=not args.no_packed_prefill,
        attn_impl=args.attn_impl,
        packed_attn_impl=args.packed_attn_impl,
        sampling_epilogue=args.sampling_epilogue,
        host_cache_blocks=args.host_cache_blocks,
        offload_watermark_blocks=args.offload_watermark_blocks,
        disk_cache_dir=args.disk_cache_dir or None,
        disk_cache_blocks=args.disk_cache_blocks,
        object_store_dir=args.object_store_dir or None,
        kv_io_deadline_s=args.kv_io_deadline_s,
        kv_breaker_threshold=args.kv_breaker_threshold,
        kv_breaker_cooldown_s=args.kv_breaker_cooldown_s,
        kvbm_remote=not args.no_kvbm_remote,
        role=args.role,
        warmup=not args.no_warmup,
        reasoning_parser=args.reasoning_parser,
        lora_dir=args.lora_dir or None,
        lora_max_adapters=(args.lora_max_adapters if args.lora_dir else 0),
        lora_rank=args.lora_rank,
        spec_decode=args.spec_decode,
        spec_k=args.spec_k,
        spec_draft_model=args.spec_draft_model,
        spec_draft_model_path=args.spec_draft_model_path,
        overlap_scheduling=not args.no_overlap_scheduling,
        slo_yield_burn=args.slo_yield_burn,
    )
    rt = await DistributedRuntime.detached().start()
    worker = await JaxEngineWorker(
        rt, config, namespace=args.namespace, component=args.component,
        migration_limit=args.migration_limit,
    ).start()

    async def drain_worker() -> None:
        # graceful SIGTERM: withdraw the lease, finish/migrate in-flight
        # requests (engine/worker.py drain()), then exit — even if a
        # drain step fails, the process must still come down
        try:
            await worker.drain(args.drain_deadline_s)
        finally:
            rt.root_token.kill()

    from ..runtime.aio import install_drain_handler

    install_drain_handler(drain_worker)
    if worker.served is not None:
        print(f"ready instance_id={worker.served.instance_id}", flush=True)
    else:  # multihost follower: no routing identity, replay only
        print(f"ready follower rank={worker.mh.rank}/{worker.mh.world}",
              flush=True)
    try:
        await rt.root_token.wait_killed()
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    await worker.close()
    await rt.shutdown()


if __name__ == "__main__":
    asyncio.run(main())
