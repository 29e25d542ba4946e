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
    # MoE (Mixtral-family): 0 experts = dense MLP.  Experts shard over the
    # "tp" mesh axis (EP reuses tp, parallel/mesh.py moe_w_* rules).
    # Dispatch modes:
    #   "dense"    — DROPLESS and batch-invariant (same token -> same
    #                output regardless of chunking/co-batch), which prefix
    #                caching and greedy determinism rely on.  The FORM is
    #                the program's choice by shape (moe_dispatch_form):
    #                few tokens (every decode step, the prefill buckets
    #                to 256 tokens) multiply every token with every
    #                VISITED expert and mask the combine, in one kernel
    #                that reads no expert nobody picked — the weights'
    #                read is the cost there; prompt-sized inputs sort
    #                their picks by expert and multiply each token with
    #                its own experts only; what lies between multiplies
    #                every token with every held expert.
    #   "capacity" — GShard capacity dispatch: tokens over an expert's
    #                C = ceil(T*k/E * capacity_factor) are dropped.  k/E
    #                of the FLOPs, but outputs vary with batch shape; use
    #                for throughput-oriented long-prefill deployments.
    n_experts: int = 0
    experts_per_token: int = 2
    moe_dispatch: str = "dense"
    moe_capacity_factor: float = 1.25
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


@jax.named_scope("dyn.moe_router")
def _moe_router(layer, cfg: LlamaConfig, x: jax.Array):
    """Top-k routing: returns (weights [T,k] softmaxed, expert ids [T,k]).

    topk-then-softmax == HF Mixtral's softmax-topk-renormalize (softmax of
    the selected logits), verified against transformers in
    tests/test_loader.py."""
    router = (x.astype(jnp.float32) @ layer["moe_gate"].astype(jnp.float32))
    top_w, top_e = jax.lax.top_k(router, cfg.experts_per_token)
    return jax.nn.softmax(top_w, axis=-1), top_e


def experts_held(cfg) -> Tuple[int, int]:
    """(first, count) of the routed experts whose weights this program
    holds, out of the `cfg.n_experts` the router scores: all of them
    unless the config says otherwise (`experts_held`, a chip's share of
    an expert-parallel deployment)."""
    return getattr(cfg, "experts_held", None) or (0, cfg.n_experts)


def relu2(h: jax.Array) -> jax.Array:
    """relu(h)^2, the activation of a plain (non-gated) expert."""
    return jnp.square(jax.nn.relu(h))


def _expert_hidden(layer, cfg, mm) -> jax.Array:
    """A routed expert's hidden activations in the form the family's
    configuration gives it, for all three dispatches: `mm(w)` multiplies
    the dispatch's rows with an expert matrix stack.  GATED (the default:
    `cfg.expert_gated` absent or True) is act(x Wgate) * (x Wup), three
    matrices an expert; PLAIN is act(x Wup), two, and the layer has no
    `moe_w_gate`.  `cfg.expert_act` is the activation, a function (SiLU
    where absent: SwiGLU)."""
    act = getattr(cfg, "expert_act", jax.nn.silu)
    if getattr(cfg, "expert_gated", True):
        return act(mm(layer["moe_w_gate"])) * mm(layer["moe_w_up"])
    return act(mm(layer["moe_w_up"]))


def _held_picks(cfg, top_e: jax.Array, valid: Optional[jax.Array]):
    """(on [T, k] bool: the picks of valid rows that fall on a held
    expert; seen [held] bool: the held experts such a pick visits)."""
    first, count = experts_held(cfg)
    on = (top_e >= first) & (top_e < first + count)
    if valid is not None:
        on = on & valid[:, None]
    seen = jnp.zeros((count,), bool).at[
        jnp.where(on, top_e - first, count)].set(True, mode="drop")
    return on, seen


def moe_held_counts(cfg, top_e: jax.Array, valid: Optional[jax.Array]):
    """(picks that fell on a held expert, held experts with a token), two
    int32 scalars over the valid rows of top_e [T, k]: what a family
    that holds a share of its experts counts on the device
    (`KV_COUNTERS`)."""
    on, seen = _held_picks(cfg, top_e, valid)
    return jnp.sum(on, dtype=jnp.int32), jnp.sum(seen, dtype=jnp.int32)


def _combine_weights(cfg, top_w: jax.Array, top_e: jax.Array,
                     valid: Optional[jax.Array]) -> jax.Array:
    """[T, held] in cfg.dtype: a row's routing weight for each held
    expert, 0 where it did not pick it and for a row `valid` masks."""
    T, E = top_e.shape[0], cfg.n_experts
    wmat = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], top_e
    ].set(top_w)                                       # [T, E]
    if valid is not None:
        wmat = wmat * valid.astype(jnp.float32)[:, None]
    first, count = experts_held(cfg)
    if count != E:
        wmat = wmat[:, first:first + count]            # the held columns
    return wmat.astype(cfg.dtype)


@jax.named_scope("dyn.moe_dispatch")
def moe_dispatch_dense(layer, cfg: LlamaConfig, x: jax.Array,
                       top_w: jax.Array, top_e: jax.Array,
                       valid: Optional[jax.Array] = None) -> jax.Array:
    """Dropless masked-dense MoE dispatch for precomputed routing
    (top_w/top_e [T, k]): all experts compute all tokens, the router
    matrix masks the combine.  Batch-invariant by construction.

    With experts sharded over tp, the expert einsums run local to each
    shard and the final combine reduces over the expert axis (one psum on
    the way out) — no dispatch tensors, no all-to-all.

    `cfg.n_experts` is the ROUTER's width; the `moe_w_*` stacks hold the
    experts `experts_held(cfg)` names.  A chip's share of a wider
    deployment computes the held experts' part for the tokens routed to
    them and adds nothing for the rest; with all experts held this is
    the one code path there was."""
    wmat = _combine_weights(cfg, top_w, top_e, valid)
    h = _expert_hidden(layer, cfg,
                       lambda w: jnp.einsum("td,edf->etf", x, w))
    eout = jnp.einsum("etf,efd->etd", h, layer["moe_w_down"])
    return jnp.einsum("etd,te->td", eout, wmat)


@jax.named_scope("dyn.moe_dispatch")
def moe_dispatch_capacity(layer, cfg: LlamaConfig, x: jax.Array,
                          top_w: jax.Array, top_e: jax.Array,
                          valid: Optional[jax.Array] = None) -> jax.Array:
    """Top-k routed expert MLP for precomputed routing, GShard
    capacity-dispatch formulation.

    x [T, d] -> [T, d].  Every step is a static-shape einsum so GSPMD can
    shard the expert axis (EP over the "tp" mesh axis via the moe_w_* rules
    in parallel/mesh.py) and insert the dispatch/combine all-to-alls —
    the TPU-native expression of the reference's EP path (SURVEY §2.4).
    Tokens past an expert's capacity C = ceil(T*k/E * capacity_factor) are
    dropped (their residual stream passes through), the standard
    inference-time overflow policy.

    `valid` [T] bool masks batch-padding rows OUT of dispatch entirely:
    the serving engine decodes a fixed batch whose inactive slots all embed
    token 0, route identically, and would otherwise eat the real tokens'
    expert capacity."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = max(1, math.ceil(T * k / E * cfg.moe_capacity_factor))

    e_flat = top_e.reshape(-1)                         # [T*k]
    w_flat = top_w.reshape(-1)
    first, count = experts_held(cfg)
    if count != E:
        # a share of the experts (moe_dispatch_dense): a pick of an
        # expert held elsewhere is an all-zero row, claims no capacity
        # and combines to nothing; C stays the deployment's per expert
        e_flat, E = jnp.clip(e_flat - first, -1, count), count
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # [Tk, E]
    if valid is not None:
        onehot = onehot * jnp.repeat(valid.astype(jnp.int32), k)[:, None]
    # each (token, slot)'s position within its expert's capacity buffer;
    # masked rows have all-zero onehot so they claim no position, and
    # one_hot(pos, C) zeroes any row with pos >= C (capacity drop)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, e_flat[:, None], axis=1
    )[:, 0]                                            # [Tk]
    # dispatch [Tk, E, C]: one-hot (expert, slot) placement
    disp = onehot.astype(jnp.float32)[:, :, None] \
        * jax.nn.one_hot(pos, C, dtype=jnp.float32)[:, None, :]
    comb = disp * w_flat[:, None, None]                # combine weights

    x_rep = jnp.repeat(x, k, axis=0)                   # [Tk, d]
    ein = jnp.einsum("sec,sd->ecd", disp.astype(cfg.dtype), x_rep)
    h = _expert_hidden(layer, cfg,
                       lambda w: jnp.einsum("ecd,edf->ecf", ein, w))
    eout = jnp.einsum("ecf,efd->ecd", h, layer["moe_w_down"])
    out = jnp.einsum("sec,ecd->sd", comb.astype(cfg.dtype), eout)
    return out.reshape(T, k, d).sum(axis=1)


# rows of one m-tile of the grouped matmul: a group that is not empty
# pays up to one tile of rows it does not have (moe_dispatch_form)
_GMM_TILE_M = 128


# rows up to which a program's experts take the visited form: the
# weights' read is the cost there (moe_dispatch_form)
_VISITED_MAX_ROWS = 256


def moe_dispatch_form(tokens: int, k: int, held: int, routed: int,
                      shards: int = 1) -> str:
    """Which form the dropless dispatch takes for a program of `tokens`
    rows: "visited", "dense" or "grouped".  They differ in the experts
    whose weights they read and in the rows they multiply: dense reads
    every HELD expert's weights and multiplies tokens x held rows;
    grouped reads the visited experts' and multiplies the picks that
    fall on a held expert (about tokens x k x held / routed) plus up to
    one m-tile a group; visited reads the visited experts' and
    multiplies tokens x visited rows.  Visited up to
    `_VISITED_MAX_ROWS` rows, where the weights' read is the cost
    (every decode step, the prefill buckets to 256 tokens); grouped
    where dense would multiply at least twice as many rows; dense
    between.

    One expert layer on a TPU v5e, ms, dense / grouped (my chip runs,
    PR 32): Moonlight's widths (64 experts of 2048 x 1408, top 6) T 512:
    3.51 / 1.94, 2048: 16.88 / 3.06; MiMo's (16 of 256 held, 4096 x
    2048, top 8) 512: 2.48 / 1.48, 2048: 10.92 / 3.20.

    The same at each expert cell's decode rows by the held experts
    visited, ms, dense / grouped / visited, and the visited form's share
    of 819 GB/s on the visited experts' bytes (my chip runs, PR 44,
    benchmarks/bench_moe_decode.py; the kernel at `f_tile`'s width):
      Moonlight, 16 rows, 64 held:  6: 1.485 / 0.182 / 0.160 (79 %)
        16: 1.474 / 0.410 / 0.381 (89 %)  32: 1.479 / 0.772 / 0.749
        (90 %)  64: 1.485 / 1.518 / 1.488 (91 %)
      MiMo, 32 rows, 16 held:  1: 1.102 / 0.112 / 0.080 (77 %)
        4: 1.105 / 0.316 / 0.289 (85 %)  8: 1.105 / 0.598 / 0.560 (88 %)
        16: 1.108 / 1.146 / 1.110 (89 %)
      Keye, 8 rows, 16 of 2048 x 768:  1: 0.211 / 0.149 / 0.018 (66 %)
        4: 0.205 / 0.169 / 0.054 (85 %)  8: 0.204 / 0.191 / 0.103 (90 %)
        16: 0.212 / 0.243 / 0.212 (87 %)
      Ling, 64 rows, 16 of 2560 x 768:  1: 0.250 / 0.137 / 0.016 (88 %)
        4: 0.261 / 0.188 / 0.076 (76 %)  8: 0.261 / 0.243 / 0.143 (80 %)
        16: 0.266 / 0.348 / 0.272 (85 %)
      Nemotron, 64 rows, 16 of 2688 x 1856, two matrices:  1: 0.437 /
        0.115 / 0.041 (59 %)  4: 0.432 / 0.211 / 0.117 (83 %)  8: 0.433 /
        0.382 / 0.225 (87 %)  16: 0.433 / 0.645 / 0.441 (88 %)
      Command A+, 8 rows, 16 of 4096 x 4096:  1: 2.162 / 0.168 / 0.157
        (78 %)  4: 2.156 / 0.601 / 0.553 (89 %)  8: 2.165 / 1.166 / 1.090
        (90 %)  16: 2.166 / 2.285 / 2.150 (91 %)
    The dense form's time does not depend on what was visited; the
    grouped form reads the visited experts too but pays three calls and
    a sort a layer (Keye 27 %, Ling 31 %, Nemotron 46 % of the bound at 4
    of 16), so it is not decode's form.  With every expert visited the
    visited form is the dense form's time to 2.5 %, and at 128 and 256
    rows, picks drawn evenly, it is the faster one: Moonlight 128: 1.537
    / 1.613 / 1.489, 256: 1.724 / 1.719 / 1.524; MiMo 128: 1.141 / 1.068
    / 0.996, 256: 1.343 / 1.323 / 1.177; Nemotron 128: 0.429 / 0.619 /
    0.441, 256: 0.507 / 0.680 / 0.479.  Hence the bound.

    Stacks split over devices (`shards` > 1) keep the dense form: its
    einsums run local to each shard under GSPMD, the kernels would have
    the stacks gathered to every device first."""
    if shards > 1:
        return "dense"
    if tokens <= _VISITED_MAX_ROWS:
        return "visited"
    dense_rows = tokens * held
    grouped_rows = tokens * k * held // routed + held * _GMM_TILE_M
    return "grouped" if dense_rows >= 2 * grouped_rows else "dense"


def _gmm_tiling(kdim: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the Pallas grouped matmul for an expert matrix
    [kdim, n]: the whole contraction in one step where a row tile and
    a weight tile of 6 MiB together allow (the kernel holds two of each
    in 16 MiB of scoped VMEM beside the output tile and its fp32
    accumulator), n halved until they do.  Timed on the chip (PR 32):
    Moonlight's (128, 2048, 1408) / (128, 1408, 2048) and MiMo's
    (128, 4096, 512) / (128, 2048, 1024) are within 3 % of the best of
    six a shape; tm 64 or 256 changes nothing."""
    tm, tk, tn = _GMM_TILE_M, kdim, n

    def over():
        return (tm + tn) * tk * itemsize > 6 << 20

    while over() and tn > 128:
        tn = max(128, tn // 2 // 128 * 128)
    while over() and tk > 128:
        tk = max(128, tk // 2 // 128 * 128)
    return tm, tk, tn


def _grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
    """lhs [m, k] with its rows sorted by group, rhs [g, k, n],
    group_sizes [g] -> [m, n]: group i's rows times rhs[i], fp32
    accumulation, an empty group costs nothing.  Rows past the last
    group are undefined; the caller masks them.

    A platform rule, not a choice: on the TPU the Pallas kernel (megablox
    `gmm`, which visits only the m-tiles that hold rows), elsewhere
    `jax.lax.ragged_dot`, its twin for the CPU.  What XLA makes of
    `ragged_dot` on the chip was timed too: 2.2-2.8 x the kernel's time
    at 2048 tokens and slower than the dense form under 1024 (PR 32)."""
    def tpu(lhs, rhs, group_sizes):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        m = lhs.shape[0]
        lhs = jnp.pad(lhs, ((0, -m % _GMM_TILE_M), (0, 0)))
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                   tiling=_gmm_tiling(rhs.shape[1], rhs.shape[2],
                                      rhs.dtype.itemsize))[:m]

    def xla(lhs, rhs, group_sizes):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(lhs.dtype)

    return jax.lax.platform_dependent(lhs, rhs, group_sizes,
                                      tpu=tpu, default=xla)


@jax.named_scope("dyn.moe_dispatch")
def moe_dispatch_grouped(layer, cfg: LlamaConfig, x: jax.Array,
                         top_w: jax.Array, top_e: jax.Array,
                         valid: Optional[jax.Array] = None) -> jax.Array:
    """The dropless dispatch's form for prompt-sized inputs
    (moe_dispatch_dense's contract and mathematics): the (token, pick)
    pairs sorted by expert, the expert's matmuls (three, or two where it
    is plain: `_expert_hidden`) grouped over the sorted rows (a row
    meets its own expert's matrices only), the k results of a token
    gathered back and summed in pick order.

    A pick of an expert held elsewhere (`experts_held`) and every pick
    of a row `valid` masks out sort behind the held groups, belong to no
    group and are never multiplied.  A row's result depends on no other
    row: a matmul row by row, and a sum over its own k picks in a fixed
    order."""
    T, d = x.shape
    k = top_e.shape[1]
    first, count = experts_held(cfg)
    local = top_e.reshape(-1) - first                  # [T*k]
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & jnp.repeat(valid, k)
    group = jnp.where(held, local, count)              # count = no group
    order = jnp.argsort(group, stable=True)
    place = jnp.argsort(order)                         # pair -> sorted row
    sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    xs = x[order // k]                                 # [T*k, d]
    h = _expert_hidden(layer, cfg,
                       lambda w: _grouped_matmul(xs, w, sizes))
    ys = _grouped_matmul(h, layer["moe_w_down"], sizes)
    held = held.reshape(T, k)
    y = jnp.where(held[..., None], ys[place].reshape(T, k, d), 0)
    w = jnp.where(held, top_w, 0).astype(cfg.dtype)
    return jnp.einsum("tkd,tk->td", y, w)


@partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit, static_argnames=("cfg", "tile", "interpret"))
@jax.named_scope("dyn.moe_dispatch")
def _visited(stacks, cfg, x, top_w, top_e, valid, tile, interpret):
    """moe_dispatch_visited over a layer's expert stacks alone, jitted
    with the (hashable) config static: a program traces and lowers the
    form ONCE for its expert layers and not once a layer.  A Pallas
    call site costs about 60 ms of tracing: 11 layers x 10 programs
    were 6 s of `setup_s` on the state-space cell (my chip runs,
    PR 44)."""
    from ..ops.pallas_moe_visited import moe_visited, visited_plan

    def tpu(stacks, x, top_w, top_e, valid):
        ids, n = visited_plan(_held_picks(cfg, top_e, valid)[1])
        return moe_visited(
            stacks, lambda refs, mm: _expert_hidden(refs, cfg, mm), x,
            _combine_weights(cfg, top_w, top_e, valid), ids, n,
            tile=tile, interpret=interpret)

    def xla(stacks, x, top_w, top_e, valid):
        return moe_dispatch_dense(stacks, cfg, x, top_w, top_e, valid)

    if interpret:
        return tpu(stacks, x, top_w, top_e, valid)
    return jax.lax.platform_dependent(stacks, x, top_w, top_e, valid,
                                      tpu=tpu, default=xla)


def moe_dispatch_visited(layer, cfg: LlamaConfig, x: jax.Array,
                         top_w: jax.Array, top_e: jax.Array,
                         valid: Optional[jax.Array] = None, *,
                         tile: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """The dropless dispatch's form for decode-sized inputs
    (moe_dispatch_dense's contract and mathematics, its rounding points
    too): every row through every VISITED expert, the combine weight
    deciding, and an expert no valid row picked is never read.  One
    Pallas call a layer (ops/pallas_moe_visited.py) that walks the
    visited experts' ids; batch-invariant as the dense form is.

    A platform rule, not a choice: the kernel on the TPU, the dense
    einsums elsewhere (the same results over every held expert).
    `interpret` runs the kernel under the interpreter, for the tests;
    `tile` is the kernel's hidden tile where it is not its own choice
    (benchmarks/bench_moe_decode.py)."""
    stacks = {k: w for k, w in layer.items() if k.startswith("moe_w_")}
    return _visited(stacks, cfg, x, top_w, top_e, valid, tile, interpret)


def moe_form(cfg, tokens: int) -> str:
    """What a program of `tokens` rows runs for its routed experts:
    "capacity", or the dropless dispatch's "visited", "dense" or
    "grouped" form.  The one rule, asked by the traced code and by the
    engine's counters."""
    if cfg.moe_dispatch == "capacity":
        return "capacity"
    if cfg.moe_dispatch != "dense":
        raise ValueError(
            f"moe_dispatch must be 'dense' or 'capacity', "
            f"got {cfg.moe_dispatch!r}"
        )
    return moe_dispatch_form(tokens, cfg.experts_per_token,
                             experts_held(cfg)[1], cfg.n_experts,
                             cfg.expert_shards)


def moe_dispatch(layer, cfg, x: jax.Array, top_w: jax.Array,
                 top_e: jax.Array,
                 valid: Optional[jax.Array] = None) -> jax.Array:
    """Routed experts for precomputed routing, x [T, d] -> [T, d]: the
    one entry point of every family with experts.  `cfg.moe_dispatch`
    says WHAT is computed ("dense": dropless; "capacity": GShard's
    drop); the dropless FORM follows the program's shape (moe_form),
    which the caller cannot set."""
    dispatch = {"capacity": moe_dispatch_capacity,
                "grouped": moe_dispatch_grouped,
                "visited": moe_dispatch_visited,
                "dense": moe_dispatch_dense}[moe_form(cfg, x.shape[0])]
    return dispatch(layer, cfg, x, top_w, top_e, valid)


def moe_rows(fn, cfg, h: jax.Array, valid: jax.Array):
    """A family's routed FFN `fn(x [T, d], valid [T])` over co-batched
    prefill rows h [Bp, T, d].  A dropless dispatch has no pools to keep
    apart and a row's result depends on no other row: the rows run
    flattened, as one program-sized input.  Capacity dispatch keeps a
    pool a row (co-scheduled requests must not capacity-drop each
    other's tokens): vmapped."""
    if cfg.moe_dispatch == "capacity":
        return jax.vmap(fn)(h, valid)
    Bp, T = h.shape[:2]
    out = fn(h.reshape(Bp * T, -1), valid.reshape(Bp * T))
    return jax.tree_util.tree_map(
        lambda o: o.reshape(Bp, T, *o.shape[1:]) if o.ndim else o, out)


def _ffn(layer, cfg: LlamaConfig, x: jax.Array,
         valid: Optional[jax.Array] = None) -> jax.Array:
    """Dense or routed MLP over [..., d] (leading dims flattened for MoE)."""
    if cfg.n_experts <= 0:
        return _mlp(layer, x)
    lead = x.shape[:-1]
    if valid is not None:
        valid = valid.reshape(-1)
    flat = x.reshape(-1, x.shape[-1])
    top_w, top_e = _moe_router(layer, cfg, flat)
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
        # padding tokens past true_len must not eat MoE expert capacity
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
            x = x + moe_rows(partial(_ffn, layer, cfg), cfg, h, valid)
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

    NOTE: capacity-dispatch MoE is NOT packed-safe (segments would share
    one expert-capacity pool and capacity-drop each other's tokens); the
    engine routes those configs to the per-row batched program instead.

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
    """`num_steps` fused decode steps in ONE compiled program (lax.scan).

    The serving hot loop's dominant off-roofline cost on this platform is
    per-dispatch overhead (each jit call round-trips the host); fusing k
    steps amortizes it k-fold — the on-device generate loop every
    production TPU serving stack runs.  Sampled ids chain on device; block
    tables are fixed across the burst, so callers must pre-allocate blocks
    covering positions [ctx, ctx + num_steps).

    Returns (tokens [num_steps, B], updated kv_cache)."""
    if sample_fn is None:
        def sample_fn(logits, _):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def body(carry, step_idx):
        tokens, kv, pos, cls = carry
        logits, kv = decode(params, cfg, kv, tokens, pos, block_tables, cls,
                            valid=valid, mesh=mesh, lora_bank=lora_bank,
                            adapter_idx=adapter_idx)
        nt = sample_fn(logits, step_idx).astype(jnp.int32)
        return (nt, kv, pos + 1, cls + 1), nt

    (_, kv_cache, _, _), toks = jax.lax.scan(
        body, (token_ids, kv_cache, positions, ctx_lens),
        jnp.arange(num_steps), length=num_steps,
    )
    return toks, kv_cache


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

    def body(carry, step_idx):
        tokens, kv, pos, cls = carry
        h, kv = decode_hidden(params, cfg, kv, tokens, pos, block_tables,
                              cls, valid=valid, mesh=mesh,
                              lora_bank=lora_bank,
                              adapter_idx=adapter_idx)
        nt = sample_fn(h, step_idx).astype(jnp.int32)
        return (nt, kv, pos + 1, cls + 1), nt

    (_, kv_cache, _, _), toks = jax.lax.scan(
        body, (token_ids, kv_cache, positions, ctx_lens),
        jnp.arange(num_steps), length=num_steps,
    )
    return toks, kv_cache
