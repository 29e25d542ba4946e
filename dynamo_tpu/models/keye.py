"""GQA decoder whose every layer CHOOSES its keys: a lightning indexer
scores all earlier tokens, attention reads the `index_topk` best (the
Keye-VL-2.0-30B-A3B language model: DeepSeek-sparse-attention indexer
over Qwen3-MoE-style layers), over routed experts held as a share.
Functional JAX, same contract as the other family modules.

Per layer (all alike):
  * q, k, v as models/llama.py `_qkv` makes them: `n_heads` query heads
    and `n_kv_heads` KV heads of `head_dim`, per-head RMSNorm on q and k
    (`qk_norm`), rotary on every dimension, base `rope_theta`;
  * indexer: qI = h WqI as `index_heads` x `index_head_dim`, ONE index
    key kI = LayerNorm(h WkI) a token, rotary on both at the same base,
    w = h Ww, one weight an index head; I[t, s] = sum_j w[t, j]
    relu(qI[t, j] . kI[s]) in float32; S_t = the `index_topk` tokens
    s <= t with the largest I[t, s], ties to the lower index
    (ops/sparse_attention.py);
  * softmax attention over S_t only, one set for all heads;
  * FFN: softmax router over `n_experts`, the top `experts_per_token`
    renormalised (moe.py `softmax_router`), SwiGLU experts `moe_ffn_dim`
    wide of which this program holds `experts_held` = (first, count)
    (moe.py `moe_dispatch`: the visited form for a decode step, grouped
    for a prompt-sized chunk); what the absent experts would add is left
    out.

Cache (models/__init__.py): four members, (k, v, index keys,
counters).  The first three are paged by the sequence's ONE block
table, each [L, heads, blocks, width, block_size] (K and V `n_kv_heads`
x `head_dim`, the index keys 1 x `index_head_dim`); every program that
writes a token's K and V writes its index key beside them, whole planes
in the resident layout (`write_token_members`, `write_packed_members`).
A block reused by prefix caching, or recomputed after a preemption,
brings its index keys with it.  `counters` as models/mimo.py's.

Every prefill entry point runs ONE body, the packed stream's
(`prefill_packed`; `prefill` and `prefill_batched` lay their padded
rows out as a stream whose rows are the segments).

Not carried yet (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): int8 cache, speculation, LoRA, ring prefill, KVBM
offload / onboard and disagg transfer of a three-member cache, tp > 1.
The vision tower is not modelled: text in, text out.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.paged_attention import resolve_decode_impl
from ..ops.sparse_attention import (
    sparse_decode_attention,
    sparse_prefill_attention,
    write_packed_members,
    write_token_members,
)
from .common import burst_scan, prefill_one_row
from .llama import _attn_out, _logits, _qkv, rms_norm, rope
from .moe import (
    experts_held,
    moe_dispatch,
    moe_held_counts,
    softmax_router,
)


@dataclass(frozen=True)
class KeyeConfig:
    name: str = "tiny-keye"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    moe_ffn_dim: int = 32
    index_heads: int = 4
    index_head_dim: int = 8
    index_topk: int = 16
    n_experts: int = 16           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    qk_norm: bool = True
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    # how decode reads K and V under the mask (paged_attention.py)
    attn_impl: str = "auto"
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        first, count = experts_held(self)
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


# what the engine must not promise for this family (engine/core.py
# _family_gaps refuses the configuration; int8 cache, speculation, LoRA
# and ring prefill fall back where each is set up, by what functions
# the family has)
UNSUPPORTED = ("kv_int8", "speculation", "lora", "ring_prefill", "kvbm",
               "disagg", "tp")

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

PRESETS: Dict[str, KeyeConfig] = {
    "tiny-keye": KeyeConfig(),
    # the published shapes (Kwai-Keye/Keye-VL-2.0-30B-A3B config.json);
    # one chip holds a share of it (benchmark/configs/)
    "keye-vl-2.0-30b-a3b": KeyeConfig(
        name="keye-vl-2.0-30b-a3b", vocab_size=151936, d_model=2048,
        n_layers=48, n_heads=32, n_kv_heads=4, head_dim=128,
        moe_ffn_dim=768, index_heads=16, index_head_dim=64,
        index_topk=2048, n_experts=128, experts_per_token=8,
        max_context=262144,
    ),
}


# ---------------------------------------------------------------------------
# cache spec and host-side counts (consumed by the engine via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: KeyeConfig, num_blocks: int,
                    block_size: int) -> Tuple[tuple, ...]:
    """(k, v, index keys, counters); the first three share the block
    table."""
    kv = (cfg.n_layers, cfg.n_kv_heads, num_blocks, cfg.head_dim,
          block_size)
    return (kv, kv,
            (cfg.n_layers, 1, num_blocks, cfg.index_head_dim, block_size),
            (len(KV_COUNTERS),))


def kv_cache_dtypes(cfg: KeyeConfig) -> Tuple[Any, ...]:
    return (cfg.dtype,) * 3 + (jnp.int32,)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 4


def decode_block_counts(cfg: KeyeConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens, in TOKENS a layer summed over steps and
    lanes (engine/core.py _count_decode_attn): the index keys a layer
    must score (ctx + 1), the tokens its indexer keeps, and the tokens
    whose K and V decode moves: the whole context, read under a mask
    (ops/sparse_attention.py; a read of the kept ones alone would count
    them here).  `decode_attn_*` (blocks that a dense read needs) has
    no meaning here and is not fed."""
    live = ctx[:, None] + 1 + np.arange(k)[None, :]
    return {
        "sparse_ctx_tokens.decode": int(live.sum()),
        "sparse_selected_tokens.decode":
            int(np.minimum(live, cfg.index_topk).sum()),
        "sparse_read_tokens.decode": int(live.sum()),
    }


def prefill_token_counts(cfg: KeyeConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos`, in (query, key) pairs a layer: scored by the
    indexer (position + 1 a token) and kept for attention.  (`bucket`,
    a padded program's rows, is the contract's: pairs do not pad.)"""
    seen = pos + 1 + np.arange(chunk, dtype=np.int64)
    return {
        "sparse_pairs_scored.prefill": int(seen.sum()),
        "sparse_pairs_attended.prefill":
            int(np.minimum(seen, cfg.index_topk).sum()),
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: KeyeConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    The norms' weights are random around 1 (and the index key's
    LayerNorm has a random bias) so that leaving one out changes the
    answer."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

    def near_one(key, n):
        return 1.0 + 0.25 * jax.random.normal(key, (n,), jnp.float32)

    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (cfg.d_model, cfg.vocab_size))
    params = place(params)
    d, f = cfg.d_model, cfg.moe_ffn_dim
    H, D = cfg.index_heads, cfg.index_head_dim
    held = experts_held(cfg)[1]
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 14)
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "wq": dense(k[0], (d, cfg.q_dim)),
            "wk": dense(k[1], (d, cfg.kv_dim)),
            "wv": dense(k[2], (d, cfg.kv_dim)),
            "wo": dense(k[3], (cfg.q_dim, d)),
            "wq_index": dense(k[4], (d, H * D)),
            "wk_index": dense(k[5], (d, D)),
            "ww_index": dense(k[6], (d, H)),
            "k_index_norm": {
                "weight": near_one(k[7], D),
                "bias": 0.25 * jax.random.normal(k[8], (D,), jnp.float32)},
            "moe_gate": dense(k[9], (d, cfg.n_experts)),
            "moe_w_gate": dense(k[10], (held, d, f),
                                scale=1.0 / math.sqrt(d)),
            "moe_w_up": dense(k[11], (held, d, f),
                              scale=1.0 / math.sqrt(d)),
            "moe_w_down": dense(k[12], (held, f, d),
                                scale=1.0 / math.sqrt(f)),
        }
        if cfg.qk_norm:
            kq, kk = jax.random.split(k[13])
            layer["q_norm"] = {"norm": near_one(kq, cfg.head_dim)}
            layer["k_norm"] = {"norm": near_one(kk, cfg.head_dim)}
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * weight + bias).astype(
        x.dtype)


@jax.named_scope("dyn.attn_index")
def _index_proj(layer, cfg: KeyeConfig, x: jax.Array,
                positions: jax.Array):
    """x [..., seq, d] -> the indexer's queries qI [..., seq, H, D], its
    one key a token kI [..., seq, 1, D] (what the cache holds) and its
    head weights w [..., seq, H]."""
    *lead, seq, _ = x.shape
    qi = (x @ layer["wq_index"]).reshape(
        *lead, seq, cfg.index_heads, cfg.index_head_dim)
    ki = layer_norm(x @ layer["wk_index"], layer["k_index_norm"]["weight"],
                    layer["k_index_norm"]["bias"], cfg.rms_eps)
    ki = ki.reshape(*lead, seq, 1, cfg.index_head_dim)
    return (rope(qi, positions, cfg.rope_theta),
            rope(ki, positions, cfg.rope_theta),
            x @ layer["ww_index"])


def _ffn(layer, cfg: KeyeConfig, x: jax.Array,
         valid: Optional[jax.Array]):
    """x [T, d] -> (out [T, d], picks on held experts, held experts with
    a token), the two counts over valid rows."""
    top_w, top_e = softmax_router(layer, cfg, x)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid)
    return (out,) + moe_held_counts(cfg, top_e, valid)


# ---------------------------------------------------------------------------
# prefill: one body, the packed stream's
# ---------------------------------------------------------------------------


def _packed_forward(params, cfg: KeyeConfig, kv_cache, token_ids, positions,
                    seg_ids, block_tables, valid):
    """token_ids, positions, seg_ids, valid [T] (ops/packed_prefill.py's
    stream), block_tables [S, mb] -> (hidden [T, d], cache).  K, V and
    index keys of the chunk are written first; attention then reads
    everything through the block table."""
    k_c, v_c, ik_c, counters = kv_cache
    T = token_ids.shape[0]
    x = params["embedding"][token_ids].astype(cfg.dtype)
    picks = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions)
        qi, ki, wi = _index_proj(layer, cfg, h, positions)
        k_c, v_c, ik_c = write_packed_members(
            (k_c, v_c, ik_c), li, (k, v, ki), block_tables, seg_ids,
            positions, valid)
        attn = sparse_prefill_attention(
            q, qi, wi, k_c, v_c, ik_c, li, block_tables, seg_ids,
            positions, valid, cfg.index_topk)
        x = x + _attn_out(layer, attn.reshape(T, cfg.q_dim))
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, _ = _ffn(layer, cfg, h, valid)
        x = x + out
        picks = picks + n_on
    return x, (k_c, v_c, ik_c, counters.at[0].add(picks))


def prefill_packed(params, cfg: KeyeConfig, kv_cache, token_ids, positions,
                   seg_ids, block_tables, last_idx, valid, mesh=None):
    """llama.prefill_packed's contract: several prompts' chunks as one
    padding-free stream.  -> (logits [S, vocab] at each segment's last
    packed token, cache)."""
    x, kv_cache = _packed_forward(params, cfg, kv_cache, token_ids,
                                  positions, seg_ids, block_tables, valid)
    return _logits(params, cfg, x[last_idx]), kv_cache


def prefill_batched(params, cfg: KeyeConfig, kv_cache, token_ids, positions,
                    block_tables, ctx_lens, true_lens):
    """llama.prefill_batched's contract ([Bp, T_pad] padded rows): the
    rows laid end to end are a packed stream whose segments are the
    rows, each one run at consecutive positions with its padding
    masked (`plan_packed_write` starts a plane at every change of
    `valid`, so padding between rows costs no plane)."""
    Bp, T = token_ids.shape
    valid = (jnp.arange(T)[None, :] < true_lens[:, None]).reshape(-1)
    seg_ids = jnp.repeat(jnp.arange(Bp, dtype=jnp.int32), T)
    x, kv_cache = _packed_forward(
        params, cfg, kv_cache, token_ids.reshape(-1), positions.reshape(-1),
        seg_ids, block_tables, valid)
    last = jnp.arange(Bp) * T + jnp.maximum(true_lens - 1, 0)
    return _logits(params, cfg, x[last]), kv_cache


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(params, cfg: KeyeConfig, kv_cache, token_ids, positions,
           block_tables, ctx_lens, valid: Optional[jax.Array] = None,
           mesh=None):
    """One decode step for B lanes (llama.decode's contract)."""
    k_c, v_c, ik_c, counters = kv_cache
    B = token_ids.shape[0]
    x = params["embedding"][token_ids].astype(cfg.dtype)
    pos1 = positions[:, None]
    attn_impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                                    k_c.shape[4], k_c.shape[3], k_c.dtype)
    kv_lens = ctx_lens + 1
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    picks = visited = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h[:, None, :], pos1)
        qi, ki, wi = _index_proj(layer, cfg, h[:, None, :], pos1)
        k_c, v_c, ik_c = write_token_members(
            (k_c, v_c, ik_c), li, (k[:, 0], v[:, 0], ki[:, 0]),
            block_tables, ctx_lens, valid)
        attn = sparse_decode_attention(
            q[:, 0], qi[:, 0], wi[:, 0], k_c, v_c, ik_c, li, block_tables,
            kv_lens, cfg.index_topk, attn_impl=attn_impl)
        x = x + _attn_out(layer, attn.reshape(B, cfg.q_dim))
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, n_seen = _ffn(layer, cfg, h, valid)
        x = x + out
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x), (k_c, v_c, ik_c, counters)


def decode_multi(params, cfg: KeyeConfig, kv_cache, token_ids, positions,
                 block_tables, ctx_lens, num_steps: int, sample_fn=None,
                 valid: Optional[jax.Array] = None, mesh=None):
    """num_steps fused decode steps (llama.decode_multi's contract)."""
    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
