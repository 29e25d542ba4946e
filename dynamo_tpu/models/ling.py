"""Delta-rule linear attention (KDA) layers with one latent-attention
(MLA) layer a period, over group-routed experts held as a share (the
Ling-3.0-flash architecture), functional JAX over a cache of TWO kinds
whose second kind is not keys; same contract as the other families.

Per layer l, by `cfg.layer_kinds[l]` (0 KDA, 1 MLA; MLA where
(l + 1) % mla_period == 0):
  * KDA (ops/delta_attention.py): q~, k~, v~ = h Wq, h Wk, h Wv, each
    n_heads x head_dim wide; a causal depthwise convolution of
    `conv_width` over time on every channel, then SiLU; q and k
    L2-normalised a head; beta = sigmoid(h Wb) a head; a decay a CHANNEL
    log a = kda_lower_bound * sigmoid(exp(A_log) * (h Wf + dt_bias));
    the head's state S (head_dim x head_dim, float32) is decayed,
    corrected by the delta rule and read with q / sqrt(head_dim); the
    read is RMS-normed a head, gated by sigmoid(h Wg) a head and goes
    through Wo.  No rotary: the decay carries position.
  * MLA: models/deepseek.py's `_q_proj`, `_kv_latent`, `_absorb_q`,
    `mla_decode_plan`, `mla_prefill_plan` and ops/mla_attention.py,
    imported: a latent and a shared rope key a token, absorbed decode
    (the Pallas latent kernel where `cfg.attn_impl` resolves to it, jnp
    elsewhere) and a prefill read that is one flash kernel over the
    pool's live blocks from the 512-token bucket up where the decode
    kernel runs (`_mla_prefill`, `mla_q_block` queries a pass over the
    whole table, elsewhere).
  * FFN: dense SwiGLU below `first_k_dense`, else DeepSeek routing
    (moe.py `ds_router`: sigmoid, choice bias, group-limited top-k,
    renormalised, scaled) over `n_experts` router outputs of which this
    program holds `experts_held` = (first, count), plus one shared
    SwiGLU.  What the absent experts would add is left out; the partial
    result goes on to the next layer.

Cache (the family contract in models/__init__.py): five members,
(latent, rope key, state, conv tail, counters).  The first two are
paged by the block table over the MLA layers only.  `state`
[kda layers, lanes, heads, head_dim, head_dim] float32 and `tail`
[kda layers, lanes, conv_width - 1, 3 x heads x head_dim] are addressed
by LANE (`KV_LANE_ADDRESSED`) and are a STATE, not a ring: nothing
overwrites them by position, so the programs keep their life:

    zeroed     a row whose first position is 0 starts from zeros,
               whatever the lane held (no clearing program)
    carried    chunk n + 1 of a prompt starts from what chunk n left
    untouched  by a bucket's padding (beta 0, log a 0, tail cut at the
               last real token), by a co-batched row of no tokens and
               by the idle lanes of a decode burst
    rebuilt    a preempted sequence is replayed from position 0

Not carried yet (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hashed latent block says nothing of
the state at its end: needs snapshots), int8 cache, speculation, LoRA,
ring and packed prefill, KVBM offload / onboard, disagg transfer and
migration of a state, tp > 1.  Non-zero SwiGLU limits are refused by
the config.  The vision tower and the multi-token-prediction module are
not modelled: text in, text out.
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

from ..ops.delta_attention import (
    kda_chunked,
    kda_gates,
    kda_step,
    l2norm,
    short_conv,
    short_conv_step,
)
from ..ops.lane_state import (
    lanes_keep,
    lanes_plan,
    lanes_step,
    resolve_chunk_impl,
    resolve_state_impl,
    rows_put,
    rows_start,
    rows_target,
)
from ..ops.mla_attention import (
    MLA_DECODE_IMPLS,
    mla_decode_attention,
    mla_prefill_attention,
)
from ..ops.paged_attention import PALLAS_IMPLS
from ..ops.pallas_chunk_state import kda_chunk_rows
from ..ops.pallas_lane_state import kda_lanes_step
from .common import burst_scan, pool_index, prefill_one_row
from .deepseek import (
    _absorb_q,
    _kv_latent,
    _q_proj,
    mla_decode_plan,
    mla_prefill_impl,
    mla_prefill_plan,
)
from .llama import _logits, _mlp, rms_norm
from .moe import ds_router, moe_dispatch, moe_held_counts, moe_rows

KDA, MLA = 0, 1


@dataclass(frozen=True)
class LingConfig:
    name: str = "tiny-ling"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 6
    n_heads: int = 4
    head_dim: int = 16            # KDA: q, k and v a head
    mla_period: int = 6           # layer l is MLA where (l + 1) % period == 0
    conv_width: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 8            # tokens a chunk of the chunked rule
    state_dtype: Any = jnp.float32
    # MLA (models/deepseek.py reads these)
    q_lora_rank: int = 0
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    mla_q_block: int = 512        # queries a pass of the MLA prefill read
    # FFN
    ffn_dim: int = 128
    moe_ffn_dim: int = 32
    shared_ffn_dim: int = 32
    first_k_dense: int = 2
    n_experts: int = 32           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    swiglu_limits: Tuple[float, ...] = ()   # a layer; non-zero is refused
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    # models/moe.py ds_router reads these
    moe_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 4
    topk_group: int = 2
    routed_scaling_factor: float = 2.5
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"       # the MLA layers' read: MLA_DECODE_IMPLS;
                                  # by its own conditions the state's step
    eos_token_ids: Tuple[int, ...] = (2,)
    qk_norm: bool = False         # unused; uniform surface

    def __post_init__(self):
        if any(self.swiglu_limits):
            raise ValueError(
                "a non-zero SwiGLU limit (expert_swiglu_limit_list / "
                "share_expert_swiglu_limit_list) is not modelled: "
                f"{self.swiglu_limits}")
        # ops/delta_attention.py: a sub-chunk's decay factors lie within
        # exp(+-sub/2 x |lower bound|) and must stay inside float32
        if max(self.kda_chunk // 4, 1) / 2 * abs(self.kda_lower_bound) > 80:
            raise ValueError(
                f"kda_chunk {self.kda_chunk} at kda_lower_bound "
                f"{self.kda_lower_bound}: a sub-chunk's decay leaves "
                "float32")
        if self.n_experts % self.n_group \
                or self.topk_group > self.n_group:
            raise ValueError(f"{self.n_experts} experts do not split into "
                             f"{self.n_group} groups ({self.topk_group} "
                             "kept)")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def layer_kinds(self) -> Tuple[int, ...]:
        return tuple(int((i + 1) % self.mla_period == 0)
                     for i in range(self.n_layers))

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def kda_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mla_plane_heights(self) -> Tuple[int, int]:
        """DeepseekConfig's: what `resolve_decode_impl` reads for the
        paged members (`head_dim` here is the KDA layers')."""
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.qk_head_dim

    def _moe_layer(self, li: int) -> bool:
        return li >= self.first_k_dense


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "packed_prefill", "kvbm", "disagg", "tp")

# the state and the tail are addressed by lane: prefill takes `lanes`
KV_LANE_ADDRESSED = True

# what the MLA layers' absorbed decode read can be told to be
SUPPORTED_ATTN_IMPLS = MLA_DECODE_IMPLS

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

PRESETS: Dict[str, LingConfig] = {
    "tiny-ling": LingConfig(),
    # the published shapes (inclusionAI/Ling-3.0-flash-VL config.json,
    # the language model); one chip holds a share of it
    # (benchmark/configs/)
    "ling-3.0-flash": LingConfig(
        name="ling-3.0-flash", vocab_size=157184, d_model=2560,
        n_layers=42, n_heads=32, head_dim=128, mla_period=6,
        kda_chunk=64, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_dim=6144,
        moe_ffn_dim=768, shared_ffn_dim=768, first_k_dense=2,
        n_experts=512, experts_per_token=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, rope_theta=6e6, max_context=131072,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: LingConfig, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(latent, rope key, state, conv tail, counters).  The paged pools
    have `num_blocks` blocks and the MLA layers only; state and tail
    have one entry a lane and KDA layer."""
    nm, nk = len(cfg.layers_of(MLA)), len(cfg.layers_of(KDA))
    return (
        (nm, 1, num_blocks, cfg.kv_lora_rank, block_size),
        (nm, 1, num_blocks, cfg.qk_rope_head_dim, block_size),
        (nk, lanes, cfg.n_heads, cfg.head_dim, cfg.head_dim),
        (nk, lanes, cfg.conv_width - 1, 3 * cfg.kda_dim),
        (len(KV_COUNTERS),),
    )


def kv_cache_dtypes(cfg: LingConfig) -> Tuple[Any, ...]:
    return (cfg.dtype, cfg.dtype, cfg.state_dtype, cfg.dtype, jnp.int32)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 5


def decode_block_counts(cfg: LingConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens (engine/core.py _count_decode_attn).  The
    MLA layers' cache blocks, summed over layers and steps: `live` what
    the mask needs, `read` what the impl that runs moves (the kernel each
    step's live blocks, the gathering read every lane's whole table).
    And the state pool's lanes: each active lane moves
    one state a KDA layer a step, out of `lanes` slots that a step's
    program runs over; `state_live` those lane steps over the KDA
    layers, `state_moved` the lanes whose state the step that runs moves
    (`state_impl`: the kernel the busy ones, the jnp step every slot)."""
    nm, nk = len(cfg.layers_of(MLA)), len(cfg.layers_of(KDA))
    live = int((-(-(ctx[:, None] + 1 + np.arange(k)[None, :])
                  // block_size)).sum())
    read = live if attn_impl in PALLAS_IMPLS else k * lanes * table_width
    return {
        "decode_attn_live_blocks": nm * live,
        "decode_attn_read_blocks": nm * read,
        "recurrent_lane_steps.decode": k * len(ctx),
        "recurrent_slot_steps.decode": k * lanes,
        "state_live_lane_steps.decode": nk * k * len(ctx),
        "state_moved_lane_steps.decode": nk * k * (
            len(ctx) if state_impl(cfg, attn_impl) in PALLAS_IMPLS
            else lanes),
    }


def state_impl(cfg: LingConfig, attn_impl: str) -> str:
    """The impl of the state's decode step under `attn_impl`, by the
    state's own conditions (ops/lane_state.resolve_state_impl), asked by
    the traced step and by the host's counts alike."""
    return resolve_state_impl(attn_impl, jax.default_backend(),
                              cfg.head_dim, cfg.head_dim, cfg.state_dtype)


def chunk_impl(cfg: LingConfig, attn_impl: str, tokens: int) -> str:
    """The impl of the chunked rule over a prefill row of `tokens`
    tokens (the program's bucket) under `attn_impl`, by the shape's own
    conditions (ops/lane_state.resolve_chunk_impl), asked by the traced
    program and by the host's counts alike."""
    return resolve_chunk_impl(attn_impl, jax.default_backend(), tokens,
                              cfg.kda_chunk, cfg.head_dim, cfg.head_dim,
                              cfg.state_dtype, unit=2 * cfg.kda_chunk,
                              sub=max(cfg.kda_chunk // 4, 1))


def prefill_token_counts(cfg: LingConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos` in a program of `bucket` rows: tokens through the
    chunked rule, those of them in a program that started from a carried
    state, rows that started from zeros; and the same tokens a KDA
    layer, with those of them whose program ran the rule in
    ops/pallas_chunk_state.py's kernel (`chunk_impl` of the bucket);
    and the same tokens an MLA layer, with those of them whose program
    ran the prefill read in ops/pallas_mla_attention.py's kernel
    (`mla_prefill_impl` of the bucket)."""
    nk, nm = len(cfg.layers_of(KDA)), len(cfg.layers_of(MLA))
    kernel = chunk_impl(cfg, cfg.attn_impl, bucket) in PALLAS_IMPLS
    flash = mla_prefill_impl(cfg, bucket) in PALLAS_IMPLS
    return {
        "recurrent_tokens.prefill": chunk,
        "recurrent_carried_tokens.prefill": chunk if pos > 0 else 0,
        "recurrent_resets": int(chunk > 0 and pos == 0),
        "state_chunk_tokens.prefill": nk * chunk,
        "state_chunk_kernel_tokens.prefill": nk * chunk if kernel else 0,
        "mla_prefill_tokens.prefill": nm * chunk,
        "mla_prefill_kernel_tokens.prefill": nm * chunk if flash else 0,
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: LingConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    The gates' parameters (A_log, dt_bias, the convolution, the read's
    norm) are random so that leaving one out changes the answer."""

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
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    f, held = cfg.moe_ffn_dim, cfg.held[1]
    layers = []
    for li, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[2 + li], 20)
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((d,), jnp.float32)},
        }
        if kind == KDA:
            layer.update({
                # q, k, v side by side: one matmul, one convolution
                "wqkv": dense(k[0], (d, 3 * cfg.kda_dim)),
                "conv_w": (jax.random.normal(
                    k[1], (cfg.conv_width, 3 * cfg.kda_dim), jnp.float32)
                    * 0.5).astype(cfg.dtype),
                "wf": dense(k[2], (d, cfg.kda_dim)),
                "wb": dense(k[3], (d, H)),
                "wg": dense(k[4], (d, H)),
                "a_log": jax.random.uniform(k[5], (H,), jnp.float32,
                                            -1.0, 1.0),
                "dt_bias": jax.random.normal(k[6], (H, hd), jnp.float32),
                "o_norm": {"norm": 1.0 + 0.1 * jax.random.normal(
                    k[7], (hd,), jnp.float32)},
                "wo": dense(k[8], (cfg.kda_dim, d)),
            })
        else:
            layer.update({
                "wq": dense(k[0], (d, cfg.q_dim)),
                "wkv_a": dense(k[1], (d, R + dr)),
                "kv_a_norm": {"norm": jnp.ones((R,), jnp.float32)},
                "w_uk": dense(k[2], (H, R, cfg.qk_nope_head_dim),
                              scale=1.0 / math.sqrt(R)),
                "w_uv": dense(k[3], (H, R, cfg.v_head_dim),
                              scale=1.0 / math.sqrt(R)),
                "wo": dense(k[4], (H * cfg.v_head_dim, d)),
            })
        if cfg._moe_layer(li):
            layer["moe_gate"] = dense(k[9], (d, cfg.n_experts))
            layer["moe_gate_bias"] = jnp.zeros((cfg.n_experts,),
                                               jnp.float32)
            layer["moe_w_gate"] = dense(k[10], (held, d, f),
                                        scale=1.0 / math.sqrt(d))
            layer["moe_w_up"] = dense(k[11], (held, d, f),
                                      scale=1.0 / math.sqrt(d))
            layer["moe_w_down"] = dense(k[12], (held, f, d),
                                        scale=1.0 / math.sqrt(f))
            layer["shared"] = {
                "w_gate": dense(k[13], (d, cfg.shared_ffn_dim)),
                "w_up": dense(k[14], (d, cfg.shared_ffn_dim)),
                "w_down": dense(k[15], (cfg.shared_ffn_dim, d)),
            }
        else:
            layer["w_gate"] = dense(k[9], (d, cfg.ffn_dim))
            layer["w_up"] = dense(k[10], (d, cfg.ffn_dim))
            layer["w_down"] = dense(k[11], (cfg.ffn_dim, d))
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.attn_qkv")
def _kda_proj(layer, h: jax.Array):
    """h [..., d] -> (q~ k~ v~ side by side [..., 3 H hd], the decay's
    and beta's projections, the output gate's)."""
    return (h @ layer["wqkv"], h @ layer["wf"], h @ layer["wb"],
            h @ layer["wg"])


def _kda_heads(cfg: LingConfig, c: jax.Array):
    """The convolved channels [..., 3 H hd] -> q, k (L2-normed a head)
    and v, each [..., H, hd] float32."""
    q, k, v = (x.reshape(*x.shape[:-1], cfg.n_heads, cfg.head_dim)
               for x in jnp.split(c, 3, axis=-1))
    return l2norm(q), l2norm(k), v


@jax.named_scope("dyn.attn_out")
def _kda_out(layer, cfg: LingConfig, o: jax.Array, g: jax.Array):
    """o [..., H, hd] float32 the rule's read, g [..., H] the gate's
    projection -> [..., d]."""
    o = rms_norm(o, layer["o_norm"]["norm"], cfg.rms_eps)
    o = o * jax.nn.sigmoid(g.astype(jnp.float32))[..., None]
    return o.reshape(*o.shape[:-2], cfg.kda_dim).astype(cfg.dtype) \
        @ layer["wo"]


def _ffn(layer, cfg: LingConfig, x: jax.Array,
         valid: Optional[jax.Array]):
    """x [T, d] -> (out [T, d], picks on held experts, held experts with
    a token), the two counts over valid rows (0, 0 for a dense layer)."""
    zero = jnp.zeros((), jnp.int32)
    if "moe_gate" not in layer:
        return _mlp(layer, x), zero, zero
    top_w, top_e = ds_router(layer, cfg, x)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid) \
        + _mlp(layer["shared"], x)
    return (out,) + moe_held_counts(cfg, top_e, valid)


def _mla_prefill(layer, cfg: LingConfig, q_nope, q_rope, c, kr, c_cache,
                 kr_cache, pli, table, ctx_len, true_len):
    """The jnp form of the MLA layers' prefill read (the CPU, the
    buckets under `mla_prefill_impl`'s floor; the kernel form is
    ops/mla_attention.mla_prefill_flash): one row's chunk [T, ...] over
    latents ALREADY written to the
    pool, `mla_q_block` queries a pass: a pass reads the context (the
    cache up to its first query) and its own block's latents, so the
    score block is [q_block, heads, table + q_block] whatever T is."""
    T = q_nope.shape[0]
    qb = min(cfg.mla_q_block, T)
    if T % qb:
        raise ValueError(f"a chunk of {T} tokens does not split into "
                         f"query blocks of {qb}")
    blocks = lambda x: x.reshape(T // qb, qb, *x.shape[1:])

    def one(args):
        qn, qr, cb, krb, i = args
        return mla_prefill_attention(
            qn, qr, cb, krb, c_cache, kr_cache, pli, table,
            ctx_len + i * qb, jnp.clip(true_len - i * qb, 0, qb),
            layer["w_uk"], layer["w_uv"])

    out = jax.lax.map(one, (blocks(q_nope), blocks(q_rope), blocks(c),
                            blocks(kr), jnp.arange(T // qb)))
    return out.reshape(T, *out.shape[2:])


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_batched(
    params: Dict[str, Any],
    cfg: LingConfig,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract),
    padded per row.  A KDA layer takes each row's state and tail from
    its lane (zeros where the row starts at position 0), runs the
    chunked rule with padding switched off (beta 0, log a 0) and puts
    both back; a row of no tokens writes nothing."""
    if lanes is None:
        raise ValueError("this family's state is addressed by lane: "
                         "prefill needs `lanes`")
    c_cache, kr_cache, state, tail, counters = kv_cache
    Bp, T = token_ids.shape
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [Bp, T, d]
    valid = jnp.arange(T)[None, :] < true_lens[:, None]
    fresh = ctx_lens == 0
    put = rows_target(lanes, true_lens, state.shape[1])
    scale = 1.0 / math.sqrt(cfg.head_dim)
    pool_li = pool_index(cfg)
    picks = jnp.zeros((), jnp.int32)
    c_impl = chunk_impl(cfg, cfg.attn_impl, T)
    rule = dict(scale=scale, chunk=cfg.kda_chunk,
                sub=max(cfg.kda_chunk // 4, 1))

    def mla_jnp(layer, pli, q_nope, q_rope, c, kr, c_cache, kr_cache,
                *rows):
        return jax.vmap(
            lambda qn, qr, cb, krb, tb, cl, tl: _mla_prefill(
                layer, cfg, qn, qr, cb, krb, c_cache, kr_cache, pli,
                tb, cl, tl))(q_nope, q_rope, c, kr, *rows)

    # (no mesh: tp > 1 is not carried by this family)
    mla_write, mla_read = mla_prefill_plan(cfg, c_cache, kr_cache, T, None,
                                           mla_jnp)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        if kind == KDA:
            qkv, f, b, g = _kda_proj(layer, h)
            t0 = rows_start(tail, pli, lanes, fresh)
            s0 = rows_start(state, pli, lanes, fresh).astype(jnp.float32)
            conv, t1 = jax.vmap(short_conv, in_axes=(0, 0, None, 0))(
                qkv, t0, layer["conv_w"], true_lens)
            q, k, v = _kda_heads(cfg, conv)
            log_a, beta = kda_gates(
                f.reshape(Bp, T, cfg.n_heads, cfg.head_dim), b,
                layer["a_log"], layer["dt_bias"], cfg.kda_lower_bound)
            log_a = jnp.where(valid[..., None, None], log_a, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
            if c_impl in PALLAS_IMPLS:
                o, s1 = kda_chunk_rows(
                    q, k, v, log_a, beta, s0, **rule,
                    interpret=c_impl == "pallas_interpret")
            else:
                o, s1 = jax.vmap(partial(kda_chunked, **rule))(
                    q, k, v, log_a, beta, s0)
            state = rows_put(state, pli, put, s1)
            tail = rows_put(tail, pli, put, t1)
            x = x + _kda_out(layer, cfg, o, g)
        else:
            q_nope, q_rope = _q_proj(layer, cfg, h, positions)
            c, kr = _kv_latent(layer, cfg, h, positions)
            c_cache, kr_cache = mla_write(
                c_cache, kr_cache, pli, c, kr, block_tables, ctx_lens,
                true_lens)
            with jax.named_scope("dyn.attn_mla"):
                attn = mla_read(
                    layer, pli, q_nope, q_rope, c, kr, c_cache, kr_cache,
                    block_tables, ctx_lens, true_lens)
            with jax.named_scope("dyn.attn_out"):
                x = x + attn.reshape(Bp, T, -1) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, _ = moe_rows(partial(_ffn, layer, cfg), h, valid)
        x = x + out
        picks = picks + jnp.sum(n_on)
    counters = counters.at[0].add(picks)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last]
    return _logits(params, cfg, xl), (c_cache, kr_cache, state, tail,
                                      counters)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: LingConfig,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
    state_plan=None,           # decode_multi's: `lanes_plan`, once a burst
):
    """One token a lane.  A KDA layer reads and writes the live lanes'
    state where it lies (rows ARE lanes: `lanes_step`); a lane that is
    not `valid` keeps state and tail as they were."""
    c_cache, kr_cache, state, tail, counters = kv_cache
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [B, d]
    B = x.shape[0]
    pos1 = positions[:, None]
    live = jnp.ones((B,), bool) if valid is None else valid
    mla_scale = 1.0 / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    scale = 1.0 / math.sqrt(cfg.head_dim)
    pool_li = pool_index(cfg)
    picks = visited = jnp.zeros((), jnp.int32)
    impl, kv_lens, write_token = mla_decode_plan(
        cfg, c_cache, kr_cache, ctx_lens, valid, mesh)
    s_impl = state_impl(cfg, cfg.attn_impl)
    if state_plan is None:
        state_plan = lanes_plan(live, s_impl)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        if kind == KDA:
            qkv, f, b, g = _kda_proj(layer, h)
            conv, t1 = short_conv_step(qkv, tail[pli], layer["conv_w"])
            q, k, v = _kda_heads(cfg, conv)
            log_a, beta = kda_gates(
                f.reshape(B, cfg.n_heads, cfg.head_dim), b,
                layer["a_log"], layer["dt_bias"], cfg.kda_lower_bound)
            rule = (q, k, v, log_a, beta)
            o, state = lanes_step(
                state, pli, state_plan,
                partial(kda_step, *rule, scale=scale),
                partial(kda_lanes_step, *rule, scale=scale), s_impl)
            tail = tail.at[pli].set(lanes_keep(live, t1, tail[pli]))
            x = x + _kda_out(layer, cfg, o, g)
        else:
            q_nope, q_rope = _q_proj(layer, cfg, h[:, None, :], pos1)
            c, kr = _kv_latent(layer, cfg, h[:, None, :], pos1)
            c_cache, kr_cache = write_token(
                c_cache, kr_cache, pli, c[:, 0][:, None, :],
                kr[:, 0][:, None, :], block_tables, ctx_lens)
            q_abs = _absorb_q(layer, q_nope[:, 0])       # [B, nh, R]
            with jax.named_scope("dyn.attn_mla"):
                attn = mla_decode_attention(
                    q_abs, q_rope[:, 0], c_cache, kr_cache, pli,
                    block_tables, kv_lens, layer["w_uv"], mla_scale,
                    impl=impl, mesh=mesh)
            with jax.named_scope("dyn.attn_out"):
                x = x + attn.reshape(B, -1) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, n_seen = _ffn(layer, cfg, h, valid)
        x = x + out
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x), (c_cache, kr_cache, state, tail,
                                     counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: LingConfig,
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
    # the busy lanes are the burst's: compacted once, outside the scan
    plan = None if valid is None else lanes_plan(
        valid, state_impl(cfg, cfg.attn_impl))

    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh, state_plan=plan)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
