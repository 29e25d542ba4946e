"""Llama-family decoder as functional JAX code over a paged KV cache.

Covers the dense families in BASELINE.md configs (Llama-3 8B/70B, Qwen3
dense via qk_norm).  Pure functions over a params pytree — no Module
framework — so pjit/GSPMD shardings (parallel/mesh.py) and donation apply
cleanly.  Forward passes read/write KV through the paged cache ops in
ops/paged_attention.py; everything is static-shape for XLA.

Weights are bf16 by default (MXU-native); activations bf16 with fp32 for
norms/softmax accumulation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple  # noqa: F401 (Tuple in cfg)

import jax
import jax.numpy as jnp

from ..ops.packed_prefill import packed_prefill_attention, write_packed_kv
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    paged_prefill_attention,
    resolve_decode_impl,
    write_prompt_kv,
    write_prompt_kv_batched,
    write_token_kv,
)
from ..quant.kv import unpack_kv
from .common import burst_scan
from .moe import experts_held  # benchmark/reference/keye.py reads it from here
from .moe import moe_dispatch, moe_rows, softmax_router


@dataclass(frozen=True)
class LlamaConfig:
    name: str = "tiny"
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 64
    ffn_dim: int = 1408
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    qk_norm: bool = False  # Qwen3-style per-head q/k RMSNorm
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    # decode attention path: "auto" | "pallas" | "pallas_interpret" |
    # "jnp" | "jnp_bf16" (ops/paged_attention.py rationale; every
    # choice accepts int8 caches — the Pallas kernel dequantizes
    # in-kernel)
    attn_impl: str = "auto"
    # packed-prefill attention path: "auto" (decided a program by
    # ops/packed_prefill.resolve_packed_impl: platform, cache, stream
    # length) | "xla" (the float32 scan) | "pallas"/"pallas_interpret"
    # (the tile-skip kernel, ops/pallas_packed_prefill.py)
    packed_attn_impl: str = "auto"
    # stop-token set (instruct checkpoints often declare several, e.g.
    # llama-3's <|end_of_text|> and <|eot_id|>)
    eos_token_ids: Tuple[int, ...] = (2,)
    # MoE (Mixtral-family): 0 experts = dense MLP; the expert layer is
    # models/moe.py's
    n_experts: int = 0
    experts_per_token: int = 2
    # devices the moe_w_* stacks are split over (parallel/mesh.py shards
    # them over "tp"); resolved by the engine from the mesh it placed the
    # parameters on, like attn_impl "auto" — a traced program cannot see
    # how its arguments are laid out
    expert_shards: int = 1

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def kv_cache_shapes(cfg: "LlamaConfig", num_blocks: int,
                    block_size: int) -> tuple:
    """(k, v) cache shapes in the head-major transposed block layout
    (ops/paged_attention.py)."""
    shape = (cfg.n_layers, cfg.n_kv_heads, num_blocks, cfg.head_dim,
             block_size)
    return shape, shape


def kv_cache_specs() -> tuple:
    """kv_heads sharded over tp (parallel/mesh.py kv_cache_spec)."""
    from ..parallel.mesh import kv_cache_spec

    return kv_cache_spec(), kv_cache_spec()


def kv_cache_scale_shapes(cfg: "LlamaConfig", num_blocks: int,
                          block_size: int) -> tuple:
    """(k_scale, v_scale) shapes for an int8 cache (quant/kv.py): one
    fp32 scale per (layer, kv_head, block, position), sibling to the
    paged cache.  The presence of this function is what marks a family
    as supporting `kv_cache_dtype="int8"` — families without it (MLA)
    auto-fall back to bf16 in the engine."""
    shape = (cfg.n_layers, cfg.n_kv_heads, num_blocks, block_size)
    return shape, shape


def kv_cache_scale_specs() -> tuple:
    """Scale planes shard with the cache (parallel/mesh.py
    kv_scale_spec: kv_heads over tp)."""
    from ..parallel.mesh import kv_scale_spec

    return kv_scale_spec(), kv_scale_spec()


# (k, v, k_scale | None, v_scale | None) from either cache arity —
# the shared tuple convention lives in quant/kv.py
_unpack_kv = unpack_kv


def _write_kv(fn, kv_cache, layer, *args):
    """Dispatch a cache write through `fn` (a write_* op from
    ops/paged_attention.py or ops/packed_prefill.py), threading the
    quantization scales when the cache is int8.  Returns the new cache
    tuple in the input's arity."""
    if len(kv_cache) == 4:
        k, v, ks, vs = kv_cache
        return fn(k, v, layer, *args, k_scale=ks, v_scale=vs)
    k, v = kv_cache
    return fn(k, v, layer, *args)


def prefill_ring(
    params: Dict[str, Any],
    cfg: "LlamaConfig",
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T_pad] int32 (one sequence, padded)
    positions: jax.Array,      # [T_pad] int32, absolute positions
    block_table: jax.Array,    # [max_blocks] int32
    true_len: jax.Array,       # scalar int32: valid tokens
    mesh=None,
):
    """Sequence-parallel COLD prefill: attention FLOPs shard over the
    mesh's sp axis via ring attention (ops/ring_attention.py) instead of
    running the whole O(T^2) prompt on every device — the long-context
    path for prompts beyond the chunked-prefill buckets (SURVEY §5: the
    reference's engines own this; here it is native).

    One-shot (ctx_len=0, no prefix reuse — a partially cached long prompt
    falls back to chunked prefill).  Causality alone isolates the padded
    tail: valid queries only attend to j <= i < true_len, and
    write_prompt_kv masks the padded KV writes.  Returns
    (logits at the last valid position, updated kv_cache)."""
    from ..ops.ring_attention import ring_attention

    zero = jnp.int32(0)
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [T, d]
    T = x.shape[0]
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions)
        kv_cache = _write_kv(write_prompt_kv, kv_cache, li, k, v,
                             block_table, zero, true_len)
        attn = ring_attention(q[None], k[None], v[None], mesh,
                              head_axis="tp")[0]
        x = x + _attn_out(layer, attn.reshape(T, cfg.q_dim))
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ffn(layer, cfg, h, valid=jnp.arange(T) < true_len)
    last = jnp.maximum(true_len - 1, 0)
    logits = _logits(params, cfg, x[last])
    return logits, kv_cache


PRESETS: Dict[str, LlamaConfig] = {
    # test-scale
    "tiny": LlamaConfig(),
    "tiny-gqa": LlamaConfig(name="tiny-gqa", n_heads=8, n_kv_heads=2),
    # benchmark-scale (single v5e chip fits ~1-2B bf16 + KV)
    "llama-1b": LlamaConfig(
        name="llama-1b", vocab_size=128256, d_model=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, head_dim=64, ffn_dim=8192,
        max_context=131072,
    ),
    # largest public-architecture config that fits ONE v5e chip (16G HBM)
    # with a serving KV cache: ~3.2B bf16 = ~6.4G weights (Llama-3.2-3B
    # geometry); the single-chip north-star bench model
    "llama-3b": LlamaConfig(
        name="llama-3b", vocab_size=128256, d_model=3072, n_layers=28,
        n_heads=24, n_kv_heads=8, head_dim=128, ffn_dim=8192,
        max_context=131072,
    ),
    # target configs (multi-chip; shapes from the public architectures)
    "llama-8b": LlamaConfig(
        name="llama-8b", vocab_size=128256, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
        max_context=131072,
    ),
    "llama-70b": LlamaConfig(
        name="llama-70b", vocab_size=128256, d_model=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, head_dim=128, ffn_dim=28672,
        max_context=131072,
    ),
    "qwen3-32b": LlamaConfig(
        name="qwen3-32b", vocab_size=151936, d_model=5120, n_layers=64,
        n_heads=64, n_kv_heads=8, head_dim=128, ffn_dim=25600,
        qk_norm=True, rope_theta=1000000.0, max_context=40960,
    ),
    # MoE family
    "tiny-moe": LlamaConfig(
        name="tiny-moe", vocab_size=256, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
        n_experts=4, experts_per_token=2,
    ),
    "mixtral-8x7b": LlamaConfig(
        name="mixtral-8x7b", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, head_dim=128, ffn_dim=14336,
        rope_theta=1000000.0, max_context=32768,
        n_experts=8, experts_per_token=2,
    ),
}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: LlamaConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree (weight loading fills the same tree).

    `place` is applied to the top-level leaves and to each layer's dict
    as soon as it exists (the engine passes shard_params over its mesh):
    a model that needs the whole mesh is then never whole on the default
    device — only one layer at a time is.  Values do not depend on it."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype
        )

    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
        "final_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (cfg.d_model, cfg.vocab_size))
    params = place(params)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 8)
        layer = {
            "attn_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
            "wq": dense(k[0], (cfg.d_model, cfg.q_dim)),
            "wk": dense(k[1], (cfg.d_model, cfg.kv_dim)),
            "wv": dense(k[2], (cfg.d_model, cfg.kv_dim)),
            "wo": dense(k[3], (cfg.q_dim, cfg.d_model)),
        }
        if cfg.n_experts > 0:
            E = cfg.n_experts
            layer["moe_gate"] = dense(k[4], (cfg.d_model, E))
            layer["moe_w_gate"] = dense(k[5], (E, cfg.d_model, cfg.ffn_dim),
                                        scale=1.0 / math.sqrt(cfg.d_model))
            layer["moe_w_up"] = dense(k[6], (E, cfg.d_model, cfg.ffn_dim),
                                      scale=1.0 / math.sqrt(cfg.d_model))
            layer["moe_w_down"] = dense(k[7], (E, cfg.ffn_dim, cfg.d_model),
                                        scale=1.0 / math.sqrt(cfg.ffn_dim))
        else:
            layer["w_gate"] = dense(k[4], (cfg.d_model, cfg.ffn_dim))
            layer["w_up"] = dense(k[5], (cfg.d_model, cfg.ffn_dim))
            layer["w_down"] = dense(k[6], (cfg.ffn_dim, cfg.d_model))
        if cfg.qk_norm:
            layer["q_norm"] = {"norm": jnp.ones((cfg.head_dim,), jnp.float32)}
            layer["k_norm"] = {"norm": jnp.ones((cfg.head_dim,), jnp.float32)}
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: [..., seq, heads, head_dim], positions: [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# `dyn.*` named scopes (here, in deepseek.py, ops/ and engine/sampler.py)
# are op metadata only: a profiler groups device ops by layer part
@jax.named_scope("dyn.attn_qkv")
def _qkv(layer, cfg: LlamaConfig, x: jax.Array, positions: jax.Array,
         lora=None):
    """x: [..., seq, d_model] -> q [..., seq, nh, hd], k/v [..., seq, nkv, hd].

    `lora`: optional (bank_layer, adapter_idx) — batched low-rank deltas
    added to the projections (lora/bank.py); slot 0 is zeros so mixed
    base/adapter batches share this program.  `positions` None: a family
    whose attention layers carry no rotary (models/nemotron_h.py)."""
    *lead, seq, _ = x.shape
    zq = x @ layer["wq"]
    zk = x @ layer["wk"]
    zv = x @ layer["wv"]
    if lora is not None:
        from ..lora.bank import lora_delta

        bl, idx = lora
        zq = zq + lora_delta(x, bl["A_q"], bl["B_q"], idx)
        zk = zk + lora_delta(x, bl["A_k"], bl["B_k"], idx)
        zv = zv + lora_delta(x, bl["A_v"], bl["B_v"], idx)
    q = zq.reshape(*lead, seq, cfg.n_heads, cfg.head_dim)
    k = zk.reshape(*lead, seq, cfg.n_kv_heads, cfg.head_dim)
    v = zv.reshape(*lead, seq, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"]["norm"], cfg.rms_eps)
        k = rms_norm(k, layer["k_norm"]["norm"], cfg.rms_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("dyn.attn_out")
def _attn_out(layer, attn_flat: jax.Array, lora=None) -> jax.Array:
    o = attn_flat @ layer["wo"]
    if lora is not None:
        from ..lora.bank import lora_delta

        bl, idx = lora
        o = o + lora_delta(attn_flat, bl["A_o"], bl["B_o"], idx)
    return o


def _lora_ctx(lora_bank, adapter_idx, li):
    """Per-layer LoRA context for _qkv/_attn_out, or None when disabled."""
    if lora_bank is None or adapter_idx is None:
        return None
    from ..lora.bank import bank_layer

    return bank_layer(lora_bank, li), adapter_idx


@jax.named_scope("dyn.mlp")
def _mlp(layer, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) @ layer[
        "w_down"
    ]


def _ffn(layer, cfg: LlamaConfig, x: jax.Array,
         valid: Optional[jax.Array] = None) -> jax.Array:
    """Dense or routed MLP over [..., d] (leading dims flattened for MoE)."""
    if cfg.n_experts <= 0:
        return _mlp(layer, x)
    lead = x.shape[:-1]
    if valid is not None:
        valid = valid.reshape(-1)
    flat = x.reshape(-1, x.shape[-1])
    top_w, top_e = softmax_router(layer, cfg, flat)
    out = moe_dispatch(layer, cfg, flat, top_w, top_e, valid)
    return out.reshape(*lead, x.shape[-1])


@jax.named_scope("dyn.lm_head")
def _logits(params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        return (x @ params["embedding"].T).astype(jnp.float32)
    return (x @ params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# prefill: T_new prompt tokens attend to cached context + themselves (causal)
# ---------------------------------------------------------------------------


def prefill(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T_pad] int32 (one sequence, padded)
    positions: jax.Array,      # [T_pad] int32, absolute positions
    block_table: jax.Array,    # [max_blocks] int32, physical block ids
    ctx_len: jax.Array,        # scalar int32: tokens already cached (prefix)
    true_len: jax.Array,       # scalar int32: valid tokens in token_ids
    lora_bank=None,            # stacked adapter bank (lora/bank.py)
    adapter_idx=None,          # scalar int32: this sequence's bank slot
):
    """Run the prompt (or a prefill chunk) through the model.

    Supports prefix-cache hits and chunked prefill uniformly: the new tokens
    attend to `ctx_len` cached tokens (read via the block table) plus
    themselves causally.  Writes the new tokens' K/V into the paged cache.
    Returns (logits_at_last_valid [vocab], updated kv_cache).
    """
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [T, d]
    for li, layer in enumerate(params["layers"]):
        lctx = _lora_ctx(lora_bank, adapter_idx, li)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions, lora=lctx)
        kv_cache = _write_kv(write_prompt_kv, kv_cache, li, k, v,
                             block_table, ctx_len, true_len)
        k_cache, v_cache, ks, vs = _unpack_kv(kv_cache)
        attn = paged_prefill_attention(
            q, k, v, k_cache, v_cache, li, block_table, ctx_len, true_len,
            k_scale=ks, v_scale=vs,
        )
        x = x + _attn_out(layer, attn.reshape(x.shape[0], cfg.q_dim),
                          lora=lctx)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ffn(layer, cfg, h,
                     valid=jnp.arange(x.shape[0]) < true_len)
    last = jnp.maximum(true_len - 1, 0)
    logits = _logits(params, cfg, x[last])
    return logits, kv_cache


def prefill_batched(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [Bp, T_pad] int32 (chunk per sequence)
    positions: jax.Array,      # [Bp, T_pad] int32, absolute positions
    block_tables: jax.Array,   # [Bp, max_blocks] int32
    ctx_lens: jax.Array,       # [Bp] int32: tokens already cached per seq
    true_lens: jax.Array,      # [Bp] int32: valid tokens per row
    lora_bank=None,            # stacked adapter bank (lora/bank.py)
    adapter_idx=None,          # [Bp] int32: bank slot per sequence
):
    """Multi-sequence chunked prefill: Bp sequences' chunks in ONE program.

    The MXU-utilization answer to concurrent arrivals (round-2 verdict weak
    #3: one B=1 chunk per scheduler step collapses TTFT under queue depth):
    short prompts that would each waste most of the token budget fill it
    together instead.  Semantically identical to running `prefill` per row
    — KV writes are a flat scatter over disjoint block sets, attention is
    vmapped per sequence over the shared cache (reads are masked to each
    row's own ctx/table), and padding rows (true_len 0) write only the
    garbage block.  Returns (logits [Bp, vocab] at each row's last valid
    token, updated kv_cache).
    """
    Bp, T = token_ids.shape
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [Bp, T, d]
    valid = jnp.arange(T)[None, :] < true_lens[:, None]   # [Bp, T]
    for li, layer in enumerate(params["layers"]):
        lctx = _lora_ctx(lora_bank, adapter_idx, li)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions, lora=lctx)  # [Bp,T,nh,hd]
        kv_cache = _write_kv(write_prompt_kv_batched, kv_cache, li, k, v,
                             block_tables, ctx_lens, true_lens)
        k_cache, v_cache, ks, vs = _unpack_kv(kv_cache)
        attn = jax.vmap(
            lambda qb, kb, vb, tb, cl, tl: paged_prefill_attention(
                qb, kb, vb, k_cache, v_cache, li, tb, cl, tl,
                k_scale=ks, v_scale=vs,
            )
        )(q, k, v, block_tables, ctx_lens, true_lens)
        x = x + _attn_out(layer, attn.reshape(Bp, T, cfg.q_dim), lora=lctx)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        if cfg.n_experts > 0:
            x = x + moe_rows(partial(_ffn, layer, cfg), h, valid)
        else:
            x = x + _ffn(layer, cfg, h, valid=valid)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last]  # [Bp, d]
    logits = _logits(params, cfg, xl)
    return logits, kv_cache


def prefill_packed(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T] int32 packed stream (tail padded)
    positions: jax.Array,      # [T] int32 absolute position per token
    seg_ids: jax.Array,        # [T] int32 segment row per token
    block_tables: jax.Array,   # [S, mb] int32 per-segment block tables
    last_idx: jax.Array,       # [S] int32 packed index of each segment's
    #                            last token this chunk (0 for unused rows)
    valid: jax.Array,          # [T] bool: False on the padded tail
    lora_bank=None,            # stacked adapter bank (lora/bank.py)
    adapter_idx=None,          # [T] int32: bank slot PER TOKEN
    mesh=None,                 # required for the Pallas path under tp>1
):
    """Packed multi-sequence prefill: several prompts' chunks (or
    prefix-hit tails) run as ONE padding-free token stream with segment
    ids (ops/packed_prefill.py) — the path that replaces the padded
    per-row batched program.  Semantically identical to running `prefill`
    per sequence: K/V written into each token's own blocks, attention is
    causal-within-segment over each segment's paged context.

    Contract: each segment row's tokens are ONE run of the stream at
    consecutive positions, rows in rising order, the padded tail last
    (ops/packed_prefill.check_packed_stream, which plan_packed_prefill
    holds its arrays to).  The K/V write sizes itself by that; a stream
    in another order loses columns without an error.

    Returns (logits [S, vocab] at each segment's last packed token,
    updated kv_cache)."""
    x, kv_cache = _packed_forward(
        params, cfg, kv_cache, token_ids, positions, seg_ids,
        block_tables, valid, lora_bank, adapter_idx, mesh=mesh,
    )
    xl = x[last_idx]  # [S, d]
    logits = _logits(params, cfg, xl)
    return logits, kv_cache


def _packed_forward(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T] int32 packed stream (tail padded)
    positions: jax.Array,      # [T] int32 absolute position per token
    seg_ids: jax.Array,        # [T] int32 segment row per token
    block_tables: jax.Array,   # [S, mb] int32 per-segment block tables
    valid: jax.Array,          # [T] bool: False on the padded tail
    lora_bank=None,
    adapter_idx=None,
    mesh=None,                 # required for the Pallas path under tp>1
):
    """Shared packed-stream transformer body (prefill_packed and
    spec_verify_packed): K/V written into each token's own blocks, whole
    planes in the pool's resident layout, then causal-within-segment
    attention over each segment's paged context.
    Returns (final hidden states [T, d], updated kv_cache)."""
    T = token_ids.shape[0]
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [T, d]
    for li, layer in enumerate(params["layers"]):
        lctx = _lora_ctx(lora_bank, adapter_idx, li)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions, lora=lctx)  # [T, nh, hd]
        kv_cache = _write_kv(write_packed_kv, kv_cache, li, k, v,
                             block_tables, seg_ids, positions, valid)
        k_cache, v_cache, ks, vs = _unpack_kv(kv_cache)
        attn = packed_prefill_attention(
            q, k_cache, v_cache, li, block_tables, seg_ids, positions,
            valid, impl=cfg.packed_attn_impl, k_scale=ks, v_scale=vs,
            mesh=mesh,
        )
        x = x + _attn_out(layer, attn.reshape(T, cfg.q_dim), lora=lctx)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ffn(layer, cfg, h, valid=valid)
    return x, kv_cache


def spec_verify_packed(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T] int32 packed verify stream
    positions: jax.Array,      # [T] int32 absolute position per token
    seg_ids: jax.Array,        # [T] int32 segment row per token
    block_tables: jax.Array,   # [S, mb] int32 per-segment block tables
    valid: jax.Array,          # [T] bool: False on the padded tail
    mesh=None,                 # required for the Pallas path under tp>1
):
    """Speculative-decoding verification (spec/): each speculating
    sequence's row [last_token, d1..dk] runs through the SAME packed
    segment-id path as chunked prefill — K/V for every draft position is
    written in place (accepted prefixes keep theirs; rejected tails are
    overwritten when the sequence actually reaches those positions) —
    but logits come back for EVERY packed position, since verification
    needs the target's next-token distribution after each draft prefix.
    The stream keeps `prefill_packed`'s contract: one run of consecutive
    positions a row, rows in order (plan_spec_verify checks its arrays).
    Returns (logits [T, vocab], updated kv_cache)."""
    x, kv_cache = _packed_forward(
        params, cfg, kv_cache, token_ids, positions, seg_ids,
        block_tables, valid, mesh=mesh,
    )
    return _logits(params, cfg, x), kv_cache


def embed_text(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    token_ids: jax.Array,   # [T_pad] int32
    true_len: jax.Array,    # scalar int32: valid tokens
) -> jax.Array:
    """Pooled text embedding: dense causal forward (no paging), final
    norm, mean-pool over valid positions, L2-normalize.  Serves the
    /v1/embeddings route (ref: the reference's embeddings route family,
    lib/llm/src/http/service/openai.rs) — any generative checkpoint
    doubles as a pooled embedder, vLLM's `embed` task semantics."""
    T = token_ids.shape[0]
    positions = jnp.arange(T)
    valid = positions < true_len
    x = params["embedding"][token_ids].astype(cfg.dtype)
    for layer in params["layers"]:
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions)
        group = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(k, group, axis=1)
        vr = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("ihd,jhd->hij", q.astype(jnp.float32),
                       kr.astype(jnp.float32)) / jnp.sqrt(
            jnp.float32(cfg.head_dim))
        causal = jnp.tril(jnp.ones((T, T), bool)) & valid[None, :]
        s = jnp.where(causal[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hij,jhd->ihd", p, vr.astype(jnp.float32))
        x = x + o.reshape(T, cfg.q_dim).astype(cfg.dtype) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ffn(layer, cfg, h, valid=valid)
    x = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    w = valid.astype(jnp.float32)[:, None]
    pooled = (x.astype(jnp.float32) * w).sum(0) / jnp.maximum(w.sum(), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


# ---------------------------------------------------------------------------
# decode: one token per active slot, batched
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [B] int32, last sampled token per slot
    positions: jax.Array,      # [B] int32
    block_tables: jax.Array,   # [B, max_blocks] int32
    ctx_lens: jax.Array,       # [B] int32, tokens in cache BEFORE this step
    valid: Optional[jax.Array] = None,  # [B] bool: active (non-padding) slots
    mesh=None,                 # required for the Pallas path under tp>1
    lora_bank=None,            # stacked adapter bank (lora/bank.py)
    adapter_idx=None,          # [B] int32: bank slot per slot
):
    """One decode step for B slots.  Writes each token's K/V, attends over
    the paged context, returns (logits [B, vocab], updated kv_cache)."""
    x, kv_cache = _decode_trunk(params, cfg, kv_cache, token_ids,
                                positions, block_tables, ctx_lens,
                                valid=valid, mesh=mesh,
                                lora_bank=lora_bank,
                                adapter_idx=adapter_idx)
    logits = _logits(params, cfg, x)  # [B, vocab]
    return logits, kv_cache


def _decode_trunk(params, cfg, kv_cache, token_ids, positions,
                  block_tables, ctx_lens, valid=None, mesh=None,
                  lora_bank=None, adapter_idx=None):
    """The decode layer stack shared by decode (-> _logits) and
    decode_hidden (-> final norm only, for the fused sampling epilogue).
    Returns (pre-final-norm hidden [B, d], updated kv_cache)."""
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [B, d]
    pos1 = positions[:, None]  # [B, 1] for rope
    # what "auto" means for this cache here (TPU + lane-aligned blocks:
    # the Pallas kernel).  The kernel reads the pool in its resident
    # layout, so the token's K/V is written in that layout too, and an
    # idle lane (valid False) claims no context: it reads nothing.
    pool = kv_cache[0]
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               pool.shape[4], pool.shape[3], pool.dtype)
    write_token = partial(write_token_kv, resident=impl in PALLAS_IMPLS,
                          valid=valid)
    kv_lens = ctx_lens + 1
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    for li, layer in enumerate(params["layers"]):
        lctx = _lora_ctx(lora_bank, adapter_idx, li)
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h[:, None, :], pos1, lora=lctx)
        kv_cache = _write_kv(write_token, kv_cache, li, k[:, 0],
                             v[:, 0], block_tables, ctx_lens)
        k_cache, v_cache, ks, vs = _unpack_kv(kv_cache)
        attn = paged_attention_decode(
            q[:, 0], k_cache, v_cache, li, block_tables, kv_lens,
            impl=impl, mesh=mesh, k_scale=ks, v_scale=vs,
        )  # [B, nh, hd]
        x = x + _attn_out(layer, attn.reshape(x.shape[0], cfg.q_dim),
                          lora=lctx)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ffn(layer, cfg, h, valid=valid)
    return x, kv_cache


def decode_hidden(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [B] int32
    positions: jax.Array,      # [B] int32
    block_tables: jax.Array,   # [B, max_blocks] int32
    ctx_lens: jax.Array,       # [B] int32
    valid: Optional[jax.Array] = None,
    mesh=None,
    lora_bank=None,
    adapter_idx=None,
):
    """decode minus the final projection: returns (final-norm hidden
    [B, d] in cfg.dtype, updated kv_cache).  The fused sampling
    epilogue (ops/fused_sampling.py) contracts the hidden against
    unembed_weight tile-by-tile, so [B, vocab] logits never
    materialize in HBM; `_logits` is exactly
    `(this_hidden @ unembed_weight).astype(fp32)`, which is what the
    epilogue's byte-identity contract rides on."""
    x, kv_cache = _decode_trunk(params, cfg, kv_cache, token_ids,
                                positions, block_tables, ctx_lens,
                                valid=valid, mesh=mesh,
                                lora_bank=lora_bank,
                                adapter_idx=adapter_idx)
    return rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps), kv_cache


def unembed_weight(params, cfg: LlamaConfig) -> jax.Array:
    """[d, vocab] final-projection matrix — the operand _logits
    contracts the final-norm hidden with (embedding.T when tied)."""
    if cfg.tie_embeddings:
        return params["embedding"].T
    return params["lm_head"]


def decode_multi(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [B] int32
    positions: jax.Array,      # [B] int32
    block_tables: jax.Array,   # [B, max_blocks] int32
    ctx_lens: jax.Array,       # [B] int32
    num_steps: int,
    sample_fn=None,            # (logits [B,V], step_idx) -> tokens [B]
    valid: Optional[jax.Array] = None,  # [B] bool: active slots
    mesh=None,                 # required for the Pallas path under tp>1
    lora_bank=None,            # stacked adapter bank (lora/bank.py)
    adapter_idx=None,          # [B] int32: bank slot per slot
):
    """`num_steps` fused decode steps in ONE compiled program
    (common.burst_scan: why, and what the caller pre-allocates).

    Returns (tokens [num_steps, B], updated kv_cache)."""
    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh, lora_bank=lora_bank,
                      adapter_idx=adapter_idx)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)


def decode_multi_hidden(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [B] int32
    positions: jax.Array,      # [B] int32
    block_tables: jax.Array,   # [B, max_blocks] int32
    ctx_lens: jax.Array,       # [B] int32
    num_steps: int,
    sample_fn,                 # (hidden [B,d], step_idx) -> tokens [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
    lora_bank=None,
    adapter_idx=None,
):
    """decode_multi with the fused sampling epilogue: the scan body
    hands `sample_fn` the final-norm HIDDEN state instead of logits, so
    no [B, vocab] tensor exists anywhere in the fused burst — the
    epilogue reduces each step's projection tile-by-tile
    (ops/fused_sampling.py).  Same chaining/position bookkeeping as
    decode_multi; callers pre-allocate blocks for [ctx, ctx+num_steps).

    Returns (tokens [num_steps, B], updated kv_cache)."""
    def step(kv, tokens, pos, cls):
        return decode_hidden(params, cfg, kv, tokens, pos, block_tables,
                             cls, valid=valid, mesh=mesh,
                             lora_bank=lora_bank, adapter_idx=adapter_idx)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
