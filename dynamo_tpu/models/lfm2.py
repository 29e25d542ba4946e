"""Gated short-convolution layers beside a few GQA attention layers,
every layer followed by a feed-forward that is dense in the leading
layers and routed experts after them (the `lfm2_moe` architecture),
functional JAX over a cache of two kinds whose second kind is a
convolution's tail alone; same contract as the other families.

Layer i, by `cfg.layer_kinds[i]`:

    x += op_i(RMSNorm(x));  x += ffn_i(RMSNorm(x))

  * `conv` (ops/gated_conv.py): [B, C, u] = h W_in; g = B * u; a causal
    depthwise convolution of `conv_width` taps over g, no bias, NO
    activation; y = (C * conv) W_out.  What the layer remembers of a
    sequence is g's last `conv_width - 1` rows.
  * `attn`: models/llama.py's `_qkv` (q and k RMS-normed a head before
    rotary: `qk_norm`), `_attn_out`, and the paged GQA write and reads
    of ops/paged_attention.py and ops/packed_prefill.py, imported.
  * ffn, i < `n_dense_layers`: SwiGLU at `ffn_dim` (llama's `_mlp`).
    Else experts: models/moe.py's `ds_router` at one group (sigmoid, a
    bias for the CHOICE only, the chosen raw scores over their sum, x
    `routed_scaling_factor`; the published block adds 1e-6 to that sum,
    `ds_router` 1e-20: under 1e-6 relative in a weight) and
    `moe_dispatch` over the `experts_held`; no shared expert.

Final RMSNorm, the output head is the embedding.  The stream between
layers is float32 and the convolution's projections keep their float32
accumulator (bf16 operands), as models/nemotron_h.py does and for its
reason: the reference is float32 and a rounding a layer is most of a
bf16 program's distance from it.

Cache (the family contract in models/__init__.py): four members, (k, v,
conv tail, counters), the layer axes indexed by KIND (`pool_index`): k
and v are paged by the block table over the `attn` layers only; `tail`
[conv layers, lanes, conv_width - 1, d_model] in the weights' dtype is
addressed by LANE (`KV_LANE_ADDRESSED`) and is a STATE whose life
ops/lane_state.py keeps: zeros where a row starts at position 0, carried
between a prompt's prefill programs, untouched by a bucket's padding, by
a row of no tokens and by idle decode lanes, rebuilt by replay after a
preemption.  There is no float32 state: 8 KB a lane and layer at the
published width.

Prefill is PACKED (`prefill_packed`, the engine's main path): rows of
several sequences end to end in one stream; `prefill_batched` is the
same forward over padded rows.

Not carried (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hashed K/V block says nothing of the
tail at its end), int8 cache, speculation, LoRA, ring prefill, KVBM
offload / onboard, disagg transfer of a two-kind cache, tp > 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.gated_conv import (
    gated_conv_packed,
    gated_conv_step,
    packed_rows,
)
from ..ops.lane_state import lanes_keep, rows_put, rows_start, rows_target
from ..ops.packed_prefill import (
    packed_prefill_attention,
    resolve_packed_impl,
    write_packed_kv,
)
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    resolve_decode_impl,
    write_token_kv,
)
from .common import burst_scan, pool_index, prefill_one_row
from .llama import _attn_out, _logits, _mlp, _qkv, rms_norm
from .moe import ds_router, moe_dispatch, moe_held_counts

CONV, ATTN = "conv", "attn"


@dataclass(frozen=True)
class Lfm2Config:
    name: str = "tiny-lfm2"
    vocab_size: int = 256
    d_model: int = 64
    layer_kinds: Tuple[str, ...] = (CONV, ATTN, CONV, CONV, CONV, ATTN,
                                    CONV, CONV, CONV)
    conv_width: int = 3           # taps; the tail keeps conv_width - 1
    # attention (models/llama.py _qkv reads these)
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    qk_norm: bool = True
    rope_theta: float = 1e6
    # feed-forward: dense in the first n_dense_layers, experts after
    n_dense_layers: int = 1
    ffn_dim: int = 96
    moe_ffn_dim: int = 32
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
    tie_embeddings: bool = True
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"         # the attention layers' decode read
    packed_attn_impl: str = "auto"  # their prefill read
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        odd = set(self.layer_kinds) - {CONV, ATTN}
        if odd or not self.layer_kinds:
            raise ValueError(f"layer_kinds {self.layer_kinds}: a layer is "
                             f"{CONV!r} or {ATTN!r}; {sorted(odd)} is not "
                             "modelled")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads do not split into their groups, or "
                             "rotary pairs lack an even head_dim")
        if self.conv_width < 2:
            raise ValueError("a convolution of one tap keeps no tail")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {self.n_dense_layers} "
                             f"outside the {self.n_layers} layers")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "kvbm", "disagg", "tp")

# the tail is addressed by lane: prefill programs take `lanes`
KV_LANE_ADDRESSED = True

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

PRESETS: Dict[str, Lfm2Config] = {
    "tiny-lfm2": Lfm2Config(),
    # the published shapes (LiquidAI/LFM2-24B-A2B config.json, model_type
    # lfm2_moe); one chip holds its first layers (benchmark/configs/)
    "lfm2-24b-a2b": Lfm2Config(
        name="lfm2-24b-a2b", vocab_size=65536, d_model=2048,
        layer_kinds=(CONV, CONV, ATTN, CONV) * 10, conv_width=3,
        n_heads=32, n_kv_heads=8, head_dim=64, rope_theta=1e6,
        n_dense_layers=2, ffn_dim=11776, moe_ffn_dim=1536, n_experts=64,
        experts_per_token=4, max_context=128000,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: Lfm2Config, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(k, v, conv tail, counters).  The paged pools have `num_blocks`
    blocks and the attention layers only; the tail has one entry a lane
    and conv layer."""
    pool = (len(cfg.layers_of(ATTN)), cfg.n_kv_heads, num_blocks,
            cfg.head_dim, block_size)
    return (pool, pool,
            (len(cfg.layers_of(CONV)), lanes, cfg.conv_width - 1,
             cfg.d_model),
            (len(KV_COUNTERS),))


def kv_cache_dtypes(cfg: Lfm2Config) -> Tuple[Any, ...]:
    return (cfg.dtype, cfg.dtype, cfg.dtype, jnp.int32)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 4


def decode_block_counts(cfg: Lfm2Config, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens (engine/core.py _count_decode_attn).  The
    attention layers' cache blocks, summed over layers and steps: `live`
    what the mask needs, `read` what the impl that runs moves (the
    kernel each step's live blocks, the gathering read every lane's
    whole table); `kv_uniform_block_steps` the same blocks for ONE
    layer, what a cache on every layer would hold a layer.  And the
    tails: each active lane moves one tail a conv layer a step, out of
    the `lanes` slots a step's program runs over (the step is jnp over
    every slot, a select keeping the idle ones: `state_moved` is the
    slots)."""
    na, nc = len(cfg.layers_of(ATTN)), len(cfg.layers_of(CONV))
    live = int((-(-(ctx[:, None] + 1 + np.arange(k)[None, :])
                  // block_size)).sum())
    read = live if attn_impl in PALLAS_IMPLS else k * lanes * table_width
    return {
        "decode_attn_live_blocks": na * live,
        "decode_attn_read_blocks": na * read,
        "kv_uniform_block_steps": live,
        "conv_lane_steps.decode": k * len(ctx),
        "conv_slot_steps.decode": k * lanes,
        "state_live_lane_steps.decode": nc * k * len(ctx),
        "state_moved_lane_steps.decode": nc * k * lanes,
    }


def prefill_token_counts(cfg: Lfm2Config, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos` in a program of `bucket` tokens: tokens through the
    convolution, those of them in a chunk that began from a carried
    tail; the tokens the attention layers' prefill read took, and those
    whose program ran it in the kernel (the rule the traced read
    applies, ops/packed_prefill.resolve_packed_impl, asked about the
    engine's default pool as nemotron_h.prefill_token_counts asks)."""
    gqa = len(cfg.layers_of(ATTN)) * chunk
    kernel = resolve_packed_impl(
        cfg.packed_attn_impl, jax.default_backend(), 128, cfg.head_dim,
        cfg.dtype, bucket, cfg.n_heads // cfg.n_kv_heads) in PALLAS_IMPLS
    return {
        "conv_tokens.prefill": chunk,
        "conv_carried_tokens.prefill": chunk if pos > 0 else 0,
        "gqa_prefill_tokens.prefill": gqa,
        "gqa_prefill_kernel_tokens.prefill": gqa if kernel else 0,
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


# the share of a routed expert's variance that is its own; the rest is
# one feed-forward common to the layer's experts (init_params; tier-1
# and benchmarks/study_lfm2_picks.py set it to 1: independent experts)
EXPERT_OWN = 0.04


def init_params(cfg: Lfm2Config, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    Only the held experts' stacks are built; the router keeps
    `n_experts` outputs.  What a trained model has and zeros or ones
    would hide is random: the norms' weights around 1, the taps, and the
    router's choice bias at a scale that flips some choices.

    A layer's experts are drawn as an UPCYCLED layer's (one dense
    feed-forward copied into every expert, each then departing from it):
    sqrt(1 - EXPERT_OWN) of a matrix common to the layer + sqrt(
    EXPERT_OWN) of the expert's own, every entry still N(0, 1 / fan_in).
    Independent experts (EXPERT_OWN 1) make `correct` a coin: with 4
    picks of 64 and no shared expert the fourth and fifth score lie a
    hair apart whatever the router's scale, bf16-level noise flips one
    token-layer in twelve against the float32 reference, and a flip
    swaps a quarter of the layer's feed-forward for an unrelated
    function, which the cubic conv layers behind it amplify (PERF.md
    section 6, PR 55; benchmarks/study_lfm2_picks.py).  Shapes, bytes,
    FLOPs and every expert's load are what they were: the router does
    not read the experts."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

    def experts(key, shape):
        own = jax.random.normal(key, shape, jnp.float32)
        common = jax.random.normal(jax.random.fold_in(key, 7), shape[1:],
                                   jnp.float32)
        w = math.sqrt(1.0 - EXPERT_OWN) * common \
            + math.sqrt(EXPERT_OWN) * own
        return (w / math.sqrt(shape[-2])).astype(cfg.dtype)

    def norm(key, n=None):
        return {"norm": 1.0 + 0.1 * jax.random.normal(
            key, (n or cfg.d_model,), jnp.float32)}

    if not cfg.tie_embeddings:
        raise ValueError("the family's output head is its embedding")
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = place({
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": norm(keys[1]),
    })
    d, f, held = cfg.d_model, cfg.moe_ffn_dim, cfg.held[1]
    layers = []
    for li, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[2 + li], 12)
        layer: Dict[str, Any] = {"op_norm": norm(k[0]),
                                 "ffn_norm": norm(k[1])}
        if kind == CONV:
            layer.update({
                # B | C | u side by side: one matmul
                "w_in": dense(k[2], (d, 3 * d)),
                "conv_w": (jax.random.normal(
                    k[3], (cfg.conv_width, d), jnp.float32) * 0.5
                    ).astype(cfg.dtype),
                "w_out": dense(k[4], (d, d)),
            })
        else:
            layer.update({
                "wq": dense(k[2], (d, cfg.q_dim)),
                "wk": dense(k[3], (d, cfg.kv_dim)),
                "wv": dense(k[4], (d, cfg.kv_dim)),
                "wo": dense(k[5], (cfg.q_dim, d)),
                "q_norm": norm(k[6], cfg.head_dim),
                "k_norm": norm(k[7], cfg.head_dim),
            })
        if li < cfg.n_dense_layers:
            layer.update({"w_gate": dense(k[8], (d, cfg.ffn_dim)),
                          "w_up": dense(k[9], (d, cfg.ffn_dim)),
                          "w_down": dense(k[10], (cfg.ffn_dim, d))})
        else:
            layer.update({
                "moe_gate": dense(k[8], (d, cfg.n_experts)),
                "moe_gate_bias": 0.05 * jax.random.normal(
                    k[11], (cfg.n_experts,), jnp.float32),
                "moe_w_gate": experts(k[9], (held, d, f)),
                "moe_w_up": experts(k[10], (held, d, f)),
                "moe_w_down": experts(jax.random.fold_in(k[10], 1),
                                      (held, f, d)),
            })
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """a @ w with the accumulator kept: operands in the weights' dtype,
    the result float32 (what the MXU sums in anyway)."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


@jax.named_scope("dyn.conv_proj")
def _conv_in(layer, cfg: Lfm2Config, h: jax.Array):
    """h [..., d] float32 -> the thirds B, C, u [..., d], float32."""
    bcu = _mm(h.astype(cfg.dtype), layer["w_in"])
    d = cfg.d_model
    return bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]


@jax.named_scope("dyn.conv_proj")
def _conv_out(layer, cfg: Lfm2Config, y: jax.Array) -> jax.Array:
    return _mm(y.astype(cfg.dtype), layer["w_out"])


def _ffn(layer, cfg: Lfm2Config, h: jax.Array,
         valid: Optional[jax.Array]):
    """The layer's feed-forward over the normed stream h [T, d] float32
    -> (out [T, d], picks on held experts, held experts with a token):
    the counts over valid rows, zeros for a dense layer.  The router
    reads the stream unrounded and at the highest precision (a [T, d] x
    [d, 64] product: nothing beside the experts): with random weights
    the fourth and the fifth choice lie a hair apart, and a pick that
    flips against the float32 reference moves every later token through
    the tails (PERF.md section 7t)."""
    x = h.astype(cfg.dtype)
    if "moe_gate" not in layer:
        zero = jnp.zeros((), jnp.int32)
        return _mlp(layer, x), zero, zero
    with jax.default_matmul_precision("highest"):
        top_w, top_e = ds_router(layer, cfg, h)
    return (moe_dispatch(layer, cfg, x, top_w, top_e, valid),) \
        + moe_held_counts(cfg, top_e, valid)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def _forward_packed(params, cfg: Lfm2Config, kv_cache, token_ids,
                    positions, seg_ids, block_tables, valid, lanes):
    """A packed stream through every layer (ops/packed_prefill.py's
    contract: a segment row is one run of the stream at consecutive
    positions).  A conv layer takes each row's tail from its lane (zeros
    where the row's first position is 0) and puts back the tail the row
    leaves; an attention layer writes the chunk's K and V and then reads
    context and chunk from the pool together.  -> (x [T, d], cache)."""
    if lanes is None:
        raise ValueError("this family's tails are addressed by lane: "
                         "prefill needs `lanes`")
    k_cache, v_cache, tail, counters = kv_cache
    rows = packed_rows(seg_ids, valid, block_tables.shape[0])
    fresh = positions[rows.first] == 0
    put = rows_target(lanes, rows.n, tail.shape[1])
    x = params["embedding"][token_ids].astype(jnp.float32)    # [T, d]
    pool_li = pool_index(cfg)
    picks = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        # the float32 stream, normed; an operator casts it for its matmuls
        h = rms_norm(x, layer["op_norm"]["norm"], cfg.rms_eps)
        if kind == CONV:
            y, left = gated_conv_packed(
                *_conv_in(layer, cfg, h), layer["conv_w"], rows,
                rows_start(tail, pli, lanes, fresh))
            tail = rows_put(tail, pli, put, left)
            x = x + _conv_out(layer, cfg, y)
        else:
            q, k, v = _qkv(layer, cfg, h.astype(cfg.dtype), positions)
            k_cache, v_cache = write_packed_kv(
                k_cache, v_cache, pli, k, v, block_tables, seg_ids,
                positions, valid)
            attn = packed_prefill_attention(
                q, k_cache, v_cache, pli, block_tables, seg_ids, positions,
                valid, impl=cfg.packed_attn_impl)
            x = x + _attn_out(layer, attn.reshape(-1, cfg.q_dim))
        out, n_on, _ = _ffn(
            layer, cfg, rms_norm(x, layer["ffn_norm"]["norm"], cfg.rms_eps),
            valid)
        x = x + out
        picks = picks + n_on
    return x, (k_cache, v_cache, tail, counters.at[0].add(picks))


def prefill_packed(
    params: Dict[str, Any],
    cfg: Lfm2Config,
    kv_cache,
    token_ids: jax.Array,      # [T] int32 packed stream (tail padded)
    positions: jax.Array,      # [T] int32 absolute position per token
    seg_ids: jax.Array,        # [T] int32 segment row per token
    block_tables: jax.Array,   # [S, mb] int32 per-segment block tables
    last_idx: jax.Array,       # [S] packed index of each row's last token
    valid: jax.Array,          # [T] bool: False on the padded tail
    mesh=None,
    lanes: jax.Array = None,   # [S] the scheduler's lane of each row
):
    """Packed multi-sequence chunked prefill (llama.prefill_packed's
    contract, and `lanes`).  -> (logits [S, vocab], cache)."""
    x, kv_cache = _forward_packed(params, cfg, kv_cache, token_ids,
                                  positions, seg_ids, block_tables, valid,
                                  lanes)
    return _logits(params, cfg, x[last_idx].astype(cfg.dtype)), kv_cache


def prefill_batched(
    params: Dict[str, Any],
    cfg: Lfm2Config,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill, padded per row
    (llama.prefill_batched's contract): the rows laid end to end are a
    packed stream whose padding lies between the runs
    (cohere2.prefill_batched)."""
    Bp, T = token_ids.shape
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    seg = jnp.broadcast_to(jnp.arange(Bp, dtype=jnp.int32)[:, None],
                           (Bp, T))
    x, kv_cache = _forward_packed(
        params, cfg, kv_cache, token_ids.reshape(-1),
        (ctx_lens[:, None] + idx).reshape(-1), seg.reshape(-1),
        block_tables, (idx < true_lens[:, None]).reshape(-1), lanes)
    last = jnp.arange(Bp) * T + jnp.maximum(true_lens - 1, 0)
    return _logits(params, cfg, x[last].astype(cfg.dtype)), kv_cache


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: Lfm2Config,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    """One token a lane (rows ARE lanes); a lane that is not `valid`
    keeps its tails as they were and writes its K and V nowhere."""
    k_cache, v_cache, tail, counters = kv_cache
    x = params["embedding"][token_ids].astype(jnp.float32)  # [B, d]
    B = x.shape[0]
    live = jnp.ones((B,), bool) if valid is None else valid
    # llama._decode_trunk's plan for the paged members
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype)
    kv_lens = jnp.where(live, ctx_lens + 1, 0)
    pool_li = pool_index(cfg)
    picks = visited = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["op_norm"]["norm"], cfg.rms_eps)
        if kind == CONV:
            y, t1 = gated_conv_step(*_conv_in(layer, cfg, h),
                                    layer["conv_w"], tail[pli])
            tail = tail.at[pli].set(lanes_keep(live, t1, tail[pli]))
            x = x + _conv_out(layer, cfg, y)
        else:
            q, k, v = _qkv(layer, cfg, h[:, None, :].astype(cfg.dtype),
                           positions[:, None])
            k_cache, v_cache = write_token_kv(
                k_cache, v_cache, pli, k[:, 0], v[:, 0], block_tables,
                ctx_lens, resident=impl in PALLAS_IMPLS, valid=valid)
            attn = paged_attention_decode(
                q[:, 0], k_cache, v_cache, pli, block_tables, kv_lens,
                impl=impl, mesh=mesh)
            x = x + _attn_out(layer, attn.reshape(B, cfg.q_dim))
        out, n_on, n_seen = _ffn(
            layer, cfg, rms_norm(x, layer["ffn_norm"]["norm"], cfg.rms_eps),
            valid)
        x = x + out
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x.astype(cfg.dtype)), (
        k_cache, v_cache, tail, counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: Lfm2Config,
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
