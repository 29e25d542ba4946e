"""Hybrid window/global attention decoder over sparse experts held as a
share (the MiMo-V2-Flash architecture), functional JAX over a cache of
TWO kinds, same contract as models/llama.py and models/deepseek.py.

Per layer, by `cfg.layer_kinds[l]` (0 global, 1 window):
  * 64-style query heads of `head_dim` (192); K `head_dim` wide, V
    `v_head_dim` (128) wide; `n_kv_heads` KV heads in global layers,
    `swa_n_kv_heads` in window layers;
  * rotary embedding on the first `rotary_dim` dimensions of q and k
    (rotate-half pairing inside them), base `rope_theta` in global and
    `swa_rope_theta` in window layers; the rest passes through;
  * global layers: causal softmax over everything; window layers: only
    `0 <= i - j < sliding_window`, and (where `swa_sink`) one learned
    logit a head in the softmax denominator (`attn_sink`);
  * values scaled by `attn_value_scale` (applied to the output: the sum
    is linear in v);
  * FFN: dense SwiGLU where `moe_layers[l]` is 0, else DeepSeek routing
    (models/moe.py `ds_router`: sigmoid, choice bias, renormalised
    top-k) over `n_experts` router outputs, of which this program holds
    `experts_held` = (first, count) — the `moe_w_*` stacks are `count`
    long (models/moe.py `experts_held`).  What the absent experts
    would add is left out; the partial result goes on to the next layer.

Cache (the family contract in models/__init__.py): five members,
(k_global, v_global, k_window, v_window, counters).  The global pools
are paged by the block table like every other family's.  The window
pools are rings addressed by lane and position
(ops/window_attention.py): `ceil(window / block) + 1` blocks a lane
whatever the sequence's length, so `KV_LANE_ADDRESSED`: programs take
the scheduler's lane of each row (`lanes=`; decode rows ARE lanes).
`counters` is an int32 vector the programs add to (`KV_COUNTERS`); the
decode burst carries it home beside the tokens.

Decode attention: global layers through `paged_attention_decode` (the
Pallas kernel where `resolve_decode_impl` says so: live blocks only,
K and V of unequal width), window layers through the window-bounded jnp
read of the lane's own ring.

Not carried yet (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hit needs the window layers' state at
the boundary), int8 cache, speculation, LoRA, ring prefill, packed
prefill, KVBM offload / onboard and disagg transfer of a two-kind
cache, tp > 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    resolve_decode_impl,
    write_prompt_kv_batched,
    write_token_kv,
)
from ..ops.window_attention import (
    causal_prefill_attention,
    gather_ring_tail,
    ring_blocks,
    ring_pool_blocks,
    window_decode_attention,
    window_prefill_attention,
    write_ring_prompt,
    write_ring_token,
)
from .common import burst_scan, pool_index, prefill_one_row
from .llama import _logits, _mlp, rms_norm, rope
from .moe import ds_router, moe_dispatch, moe_held_counts, moe_rows

GLOBAL, WINDOW = 0, 1


@dataclass(frozen=True)
class MimoConfig:
    name: str = "tiny-mimo"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 24            # q and k
    v_head_dim: int = 16
    n_kv_heads: int = 2           # global layers
    swa_n_kv_heads: int = 4       # window layers
    layer_kinds: Tuple[int, ...] = (GLOBAL, WINDOW, WINDOW, GLOBAL)
    sliding_window: int = 16
    rotary_dim: int = 8
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    attn_value_scale: float = 0.707
    swa_sink: bool = True
    full_sink: bool = False
    ffn_dim: int = 128
    moe_ffn_dim: int = 32
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1)
    n_experts: int = 16           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    # models/moe.py ds_router reads these
    moe_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"       # of the GLOBAL layers' decode read
    eos_token_ids: Tuple[int, ...] = (2,)
    qk_norm: bool = False         # unused; uniform surface

    def __post_init__(self):
        if len(self.layer_kinds) != self.n_layers \
                or len(self.moe_layers) != self.n_layers:
            raise ValueError("layer_kinds and moe_layers need one entry a "
                             f"layer ({self.n_layers})")
        if self.sliding_window & (self.sliding_window - 1):
            raise ValueError("sliding_window must be a power of two (the "
                             "prefill tiles are windows)")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    def kv_heads(self, kind: int) -> int:
        return self.swa_n_kv_heads if kind == WINDOW else self.n_kv_heads

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    def _moe_layer(self, li: int) -> bool:
        return bool(self.moe_layers[li])


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "packed_prefill", "kvbm", "disagg", "tp")

# the window pools are addressed by lane: prefill programs take `lanes`
KV_LANE_ADDRESSED = True

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

PRESETS: Dict[str, MimoConfig] = {
    "tiny-mimo": MimoConfig(),
    # the published shapes (XiaomiMiMo/MiMo-V2-Flash config.json); one
    # chip holds a share of it (benchmark/configs/)
    "mimo-v2-flash": MimoConfig(
        name="mimo-v2-flash", vocab_size=152576, d_model=4096,
        n_layers=48, n_heads=64, head_dim=192, v_head_dim=128,
        n_kv_heads=4, swa_n_kv_heads=8,
        layer_kinds=tuple(int(i > 0 and i % 6 != 5) for i in range(48)),
        sliding_window=128, rotary_dim=64, rope_theta=5e6,
        swa_rope_theta=1e4, ffn_dim=16384, moe_ffn_dim=2048,
        moe_layers=(0,) + (1,) * 47, n_experts=256, experts_per_token=8,
        max_context=262144,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: MimoConfig, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(k_global, v_global, k_window, v_window, counters).  The global
    pools have `num_blocks` blocks; the window pools a ring a lane."""
    ng, nw = len(cfg.layers_of(GLOBAL)), len(cfg.layers_of(WINDOW))
    rb = ring_pool_blocks(lanes, cfg.sliding_window, block_size)
    return (
        (ng, cfg.n_kv_heads, num_blocks, cfg.head_dim, block_size),
        (ng, cfg.n_kv_heads, num_blocks, cfg.v_head_dim, block_size),
        (nw, cfg.swa_n_kv_heads, rb, cfg.head_dim, block_size),
        (nw, cfg.swa_n_kv_heads, rb, cfg.v_head_dim, block_size),
        (len(KV_COUNTERS),),
    )


def kv_cache_dtypes(cfg: MimoConfig) -> Tuple[Any, ...]:
    return (cfg.dtype,) * 4 + (jnp.int32,)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 5


def decode_block_counts(cfg: MimoConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens, in cache blocks summed over layers and
    steps (engine/core.py _count_decode_attn): `live` what the masks
    need, `read` what the impls move — global layers as every family
    (live blocks with the kernel, lanes x table width with the jnp
    gather), window layers the lane's ring.  And what the window pools
    hold for these lanes against what a uniform cache would."""
    ng, nw = len(cfg.layers_of(GLOBAL)), len(cfg.layers_of(WINDOW))
    W = ring_blocks(cfg.sliding_window, block_size)
    pos = ctx[:, None] + np.arange(k)[None, :]          # current token's
    full = -(-(pos + 1) // block_size)                  # blocks, uniform
    lo = np.maximum(pos - cfg.sliding_window + 1, 0)
    win = pos // block_size - lo // block_size + 1      # blocks the mask needs
    g_read = (int(full.sum()) if attn_impl in PALLAS_IMPLS
              else k * lanes * table_width)
    held = int(np.minimum(full, W).sum())
    return {
        "decode_attn_live_blocks": ng * int(full.sum()) + nw * int(win.sum()),
        "decode_attn_read_blocks": ng * g_read + nw * W * pos.size,
        "kv_window_block_steps": held,
        "kv_uniform_block_steps": int(full.sum()),
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: MimoConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

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
    held = cfg.held[1]
    layers = []
    for li, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[2 + li], 10)
        nkv = cfg.kv_heads(kind)
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "wq": dense(k[0], (d, cfg.q_dim)),
            "wk": dense(k[1], (d, nkv * cfg.head_dim)),
            "wv": dense(k[2], (d, nkv * cfg.v_head_dim)),
            "wo": dense(k[3], (cfg.n_heads * cfg.v_head_dim, d)),
        }
        if cfg.swa_sink if kind == WINDOW else cfg.full_sink:
            # a learned logit a head; random here so that leaving it
            # out changes the answer
            layer["attn_sink"] = jax.random.normal(
                k[4], (cfg.n_heads,), jnp.float32)
        if cfg._moe_layer(li):
            layer["moe_gate"] = dense(k[5], (d, cfg.n_experts))
            if cfg.moe_scoring == "sigmoid":
                layer["moe_gate_bias"] = jnp.zeros((cfg.n_experts,),
                                                   jnp.float32)
            layer["moe_w_gate"] = dense(k[6], (held, d, f),
                                        scale=1.0 / math.sqrt(d))
            layer["moe_w_up"] = dense(k[7], (held, d, f),
                                      scale=1.0 / math.sqrt(d))
            layer["moe_w_down"] = dense(k[8], (held, f, d),
                                        scale=1.0 / math.sqrt(f))
        else:
            layer["w_gate"] = dense(k[5], (d, cfg.ffn_dim))
            layer["w_up"] = dense(k[6], (d, cfg.ffn_dim))
            layer["w_down"] = dense(k[7], (cfg.ffn_dim, d))
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _partial_rope(x: jax.Array, positions: jax.Array, theta: float,
                  rotary_dim: int) -> jax.Array:
    """Rotate the first `rotary_dim` dimensions, pass the rest."""
    return jnp.concatenate(
        [rope(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
        axis=-1)


@jax.named_scope("dyn.attn_qkv")
def _qkv(layer, cfg: MimoConfig, kind: int, x: jax.Array,
         positions: jax.Array):
    """x [..., seq, d] -> q [..., seq, nh, hd], k [..., seq, nkv, hd],
    v [..., seq, nkv, hdv]; the layer kind picks nkv and the rope base."""
    *lead, seq, _ = x.shape
    nkv = cfg.kv_heads(kind)
    theta = cfg.swa_rope_theta if kind == WINDOW else cfg.rope_theta
    q = (x @ layer["wq"]).reshape(*lead, seq, cfg.n_heads, cfg.head_dim)
    k = (x @ layer["wk"]).reshape(*lead, seq, nkv, cfg.head_dim)
    v = (x @ layer["wv"]).reshape(*lead, seq, nkv, cfg.v_head_dim)
    q = _partial_rope(q, positions, theta, cfg.rotary_dim)
    k = _partial_rope(k, positions, theta, cfg.rotary_dim)
    return q, k, v


@jax.named_scope("dyn.attn_out")
def _attn_out(layer, cfg: MimoConfig, attn: jax.Array) -> jax.Array:
    """attn [..., nh, hdv] of UNSCALED values -> [..., d]."""
    flat = attn.reshape(*attn.shape[:-2], cfg.n_heads * cfg.v_head_dim)
    return (flat * jnp.asarray(cfg.attn_value_scale, flat.dtype)) \
        @ layer["wo"]


def _ffn(layer, cfg: MimoConfig, x: jax.Array,
         valid: Optional[jax.Array]):
    """x [T, d] -> (out [T, d], picks on held experts, held experts with
    a token), the two counts over valid rows (0, 0 for a dense layer)."""
    zero = jnp.zeros((), jnp.int32)
    if "moe_gate" not in layer:
        return _mlp(layer, x), zero, zero
    top_w, top_e = ds_router(layer, cfg, x)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid)
    return (out,) + moe_held_counts(cfg, top_e, valid)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_batched(
    params: Dict[str, Any],
    cfg: MimoConfig,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract),
    padded per row.  A window layer reads its ring's tail BEFORE the
    chunk is written (the chunk may overwrite the whole ring) and is
    written only what later queries can read; a global layer writes the
    chunk and then reads context and chunk from the pool together."""
    if lanes is None:
        raise ValueError("this family's window pools are addressed by "
                         "lane: prefill needs `lanes`")
    kg, vg, kw, vw, counters = kv_cache
    Bp, T = token_ids.shape
    W = ring_blocks(cfg.sliding_window, kw.shape[4])
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [Bp, T, d]
    valid = jnp.arange(T)[None, :] < true_lens[:, None]
    pool_li = pool_index(cfg)
    picks = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, kind, h, positions)
        if kind == WINDOW:
            tail = jax.vmap(lambda c, ln, cl: gather_ring_tail(
                c, pli, ln, cl, cfg.sliding_window, W), in_axes=(None, 0, 0))
            attn = jax.vmap(partial(
                window_prefill_attention, window=cfg.sliding_window,
                sink=layer.get("attn_sink")))(
                q, k, v, tail(kw, lanes, ctx_lens), tail(vw, lanes, ctx_lens),
                ctx_lens, true_lens)
            kw, vw = write_ring_prompt(kw, vw, pli, k, v, lanes, ctx_lens,
                                       true_lens, cfg.sliding_window)
        else:
            kg, vg = write_prompt_kv_batched(kg, vg, pli, k, v, block_tables,
                                             ctx_lens, true_lens)
            attn = jax.vmap(lambda qb, tb, cl, tl: causal_prefill_attention(
                qb, kg, vg, pli, tb, cl, tl))(
                q, block_tables, ctx_lens, true_lens)
        x = x + _attn_out(layer, cfg, attn)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, _ = moe_rows(partial(_ffn, layer, cfg), h, valid)
        x = x + out
        picks = picks + jnp.sum(n_on)
    counters = counters.at[0].add(picks)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last]
    return _logits(params, cfg, xl), (kg, vg, kw, vw, counters)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: MimoConfig,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    kg, vg, kw, vw, counters = kv_cache
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [B, d]
    pos1 = positions[:, None]
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               kg.shape[4], kg.shape[3], kg.dtype)
    write_global = partial(write_token_kv, resident=impl in PALLAS_IMPLS,
                           valid=valid)
    kv_lens = ctx_lens + 1
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    pool_li = pool_index(cfg)
    picks = visited = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, kind, h[:, None, :], pos1)
        if kind == WINDOW:
            kw, vw = write_ring_token(kw, vw, pli, k[:, 0], v[:, 0],
                                      ctx_lens, cfg.sliding_window, valid)
            attn = window_decode_attention(
                q[:, 0], kw, vw, pli, ctx_lens, valid, cfg.sliding_window,
                sink=layer.get("attn_sink"))
        else:
            kg, vg = write_global(kg, vg, pli, k[:, 0], v[:, 0],
                                  block_tables, ctx_lens)
            with jax.named_scope("dyn.attn_global"):
                attn = paged_attention_decode(
                    q[:, 0], kg, vg, pli, block_tables, kv_lens, impl=impl,
                    mesh=mesh)
        x = x + _attn_out(layer, cfg, attn)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, n_seen = _ffn(layer, cfg, h, valid)
        x = x + out
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x), (kg, vg, kw, vw, counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: MimoConfig,
    kv_cache,
    token_ids: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    ctx_lens: jax.Array,
    num_steps: int,
    sample_fn=None,
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    """num_steps fused decode steps (llama.decode_multi contract)."""
    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
