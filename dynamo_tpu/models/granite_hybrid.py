"""Mamba-2 layers beside a few NoPE GQA attention layers, EVERY layer a
mixer followed by a gated MLP, under muP multipliers (the dense member
of the `granitemoehybrid` architecture), functional JAX over a cache of
two kinds whose second kind is not keys; same contract as the other
families.

    x_0 = embedding_multiplier * E[token]
    layer i, kind `cfg.layer_kinds[i]`:
        x += residual_multiplier * mixer_i(RMSNorm(x))
        [g | u] = W_in RMSNorm(x)
        x += residual_multiplier * W_out (silu(g) * u)
    logits = RMSNorm(x) E^T / logits_scaling          (the head is E, tied)

  * `mamba`: models/mamba2.py's mixer, the one models/nemotron_h.py
    calls, here with ONE group: every head reads the same B and C and the
    gated norm runs over all the inner channels.
  * `attention`: models/llama.py's `_qkv` / `_attn_out` and the paged GQA
    write and reads of ops/paged_attention.py and ops/packed_prefill.py,
    imported.  NO rotary (`position_embedding_type` "nope").  The scores
    are q . k * attention_multiplier, which is NOT 1 / sqrt(head_dim):
    the reads keep their 1 / sqrt(head_dim) and the layer scales q by
    `q_scale` = attention_multiplier * sqrt(head_dim) before them, a
    power of two at the published widths (1/64 * 8 = 1/8: exact in
    bfloat16), a float32 product rounded once anywhere else.
  * the MLP: ONE input matrix [d, 2 f] whose first half is the gate
    (`shared_intermediate_size`; the experts' `intermediate_size` is
    unused without experts) and one output matrix.
The stream between layers is float32 and every projection keeps its
float32 accumulator (bf16 operands), as models/nemotron_h.py does and
for its reason: eighty sublayers would round the stream eighty times.

The weights are STACKED BY PERIOD: `cfg.period` is the shortest run of
kinds that `layer_kinds` repeats (the published model: 5 Mamba-2, 1
attention, 4 Mamba-2, four times) and the parameter tree holds one layer
a position of the period, every leaf stacked over the periods
([periods, ...]); a cache member's layer is period x (layers of the kind
a period) + its index inside the period (`_over_periods`).  The PREFILL
compiles one period and is a `lax.scan` over the periods (the layer
index traced: every op here takes it, the kernels read it from SMEM);
the DECODE step goes over STATIC slices of the stacks, unrolled: scanned,
XLA copied a period's weights out of the stacks every iteration (1.24
GiB of temporaries for 0.09: the weights moved three times a step;
compiled for a described v5e).  With all 40 layers unrolled over their
own weights a cold start took 548 s on the chip (131 s to compile the
weights' draw, 87 the decode programs, 193 the seven prefill buckets)
against the 205-450 s of the other cells; stacked, 333-353 s, and the
prefill is 17 % faster (unrolled it rematerialised under 13.3 GiB of
arguments) (my chip runs, PR 57).  A layer list with no repeat is one
period.

Cache (the family contract in models/__init__.py): four members, (k, v,
state, conv tail), their layer axes indexed by KIND: k
and v are paged by the block table over the attention layers only;
`state` [Mamba layers, lanes, heads, head_dim, state] float32 and `tail`
[Mamba layers, lanes, (conv_width - 1) x conv channels] (a lane's rows
end to end: mamba2.state_shapes says why) are addressed by
LANE (`KV_LANE_ADDRESSED`) and are a STATE whose life ops/lane_state.py
keeps.  At the published widths and 64 lanes `state` is 36 x 64 x 2 MiB =
4.83e9 bytes in one array, the largest member the engine holds: it is stepped
and put where it lies, never copied (tests/test_tpu_compile.py).  There
are no device-side counts: nothing here is held as a share.

Not carried (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hashed K/V block says nothing of the
state at its end), int8 cache, speculation, LoRA, ring and packed
prefill, KVBM offload / onboard, disagg transfer and migration of a
state, tp > 1.  Refused by the configuration (`from_hf`,
`__post_init__`): routed or shared experts beside the mixer
(`num_local_experts` > 0), a rotary, biases on the projections, a
convolution without its bias, groups that do not divide the heads, a
sliding window, another norm or activation.
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

from ..ops.lane_state import lanes_plan, rows_target
from ..ops.packed_prefill import packed_prefill_attention, write_packed_kv
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    resolve_decode_impl,
    write_token_kv,
)
from . import mamba2
from .common import burst_scan, prefill_one_row
from .llama import _attn_out, _qkv, rms_norm
from .mamba2 import Mamba2Dims, mm

MAMBA, ATTN = "mamba", "attention"

# one published period: five Mamba layers, one attention, four Mamba
PERIOD = (MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4


@dataclass(frozen=True)
class GraniteHybridConfig:
    name: str = "tiny-granite-hybrid"
    vocab_size: int = 256
    d_model: int = 64
    layer_kinds: Tuple[str, ...] = PERIOD
    # Mamba-2 (models/mamba2.py)
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_state: int = 16
    ssm_groups: int = 1           # ONE B and C for every head
    conv_width: int = 4
    ssm_chunk: int = 8            # tokens a chunk of the chunked form
    state_dtype: Any = jnp.float32
    # attention (models/llama.py _qkv reads these)
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    qk_norm: bool = False
    rope_theta: float = 10000.0   # a carried key: no rotary is applied
    ffn_dim: int = 128            # the gated MLP's (shared_intermediate_size)
    # muP
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0625      # 1 / head_dim, not 1 / sqrt
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0               # a division
    rms_eps: float = 1e-5
    tie_embeddings: bool = True
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"       # the GQA layers' decode read and, by
                                  # its own conditions, the state's step
    packed_attn_impl: str = "auto"  # the GQA layers' prefill read
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        odd = set(self.layer_kinds) - {MAMBA, ATTN}
        if odd or not self.layer_kinds:
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: a layer is {MAMBA!r} or "
                f"{ATTN!r}; {sorted(odd)} is not modelled")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"mamba_n_groups {self.ssm_groups} does not divide the "
                f"{self.ssm_heads} heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads do not split over the KV heads")
        if not self.tie_embeddings:
            raise ValueError("the family's output head is its embedding")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that `layer_kinds` repeats."""
        kinds, n = self.layer_kinds, len(self.layer_kinds)
        return next(kinds[:p] for p in range(1, n + 1)
                    if n % p == 0 and kinds[:p] * (n // p) == kinds)

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.period)

    @property
    def ssm(self) -> Mamba2Dims:
        """The Mamba-2 mixer's widths (models/mamba2.py)."""
        return Mamba2Dims(
            heads=self.ssm_heads, head_dim=self.ssm_head_dim,
            state=self.ssm_state, groups=self.ssm_groups,
            conv_width=self.conv_width, chunk=self.ssm_chunk,
            eps=self.rms_eps, dtype=self.dtype,
            state_dtype=self.state_dtype)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def q_scale(self) -> float:
        """What q is multiplied by so that the reads' 1 / sqrt(head_dim)
        makes the scores q . k * attention_multiplier."""
        return self.attention_multiplier * math.sqrt(self.head_dim)


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "packed_prefill", "kvbm", "disagg", "tp")

# the state and the tail are addressed by lane: prefill takes `lanes`
KV_LANE_ADDRESSED = True

PRESETS: Dict[str, GraniteHybridConfig] = {
    "tiny-granite-hybrid": GraniteHybridConfig(),
    # the published shapes (ibm-granite/granite-4.0-h-micro config.json,
    # model_type granitemoehybrid, num_local_experts 0); one chip holds
    # all of it
    "granite-4.0-h-micro": GraniteHybridConfig(
        name="granite-4.0-h-micro", vocab_size=100352, d_model=2048,
        layer_kinds=PERIOD * 4, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, ssm_groups=1, conv_width=4, ssm_chunk=256,
        n_heads=32, n_kv_heads=8, head_dim=64, ffn_dim=8192,
        embedding_multiplier=12.0, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=8.0, rms_eps=1e-5,
        max_context=131072,
    ),
}


def from_hf(hf: Dict[str, Any], name: str) -> GraniteHybridConfig:
    """A `granitemoehybrid` config.json's keys -> the program's config;
    what the program does not model is refused here, by key."""
    kinds = tuple(hf["layer_types"])
    if len(kinds) != hf["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(kinds)} layers, "
                         f"num_hidden_layers {hf['num_hidden_layers']}")
    for key, got, want in (
            ("model_type", hf.get("model_type", "granitemoehybrid"),
             "granitemoehybrid"),
            # experts (routed, with their shared expert) beside the
            # mixer are the family's other members
            ("num_local_experts", hf.get("num_local_experts", 0), 0),
            ("position_embedding_type",
             hf.get("position_embedding_type", "nope"), "nope"),
            ("rope_scaling", hf.get("rope_scaling"), None),
            ("sliding_window", hf.get("sliding_window"), None),
            ("attention_bias", hf.get("attention_bias", False), False),
            ("mamba_proj_bias", hf.get("mamba_proj_bias", False), False),
            ("mamba_conv_bias", hf.get("mamba_conv_bias", True), True),
            ("hidden_act", hf.get("hidden_act", "silu"), "silu"),
            ("normalization_function",
             hf.get("normalization_function", "rmsnorm"), "rmsnorm"),
            ("tie_word_embeddings", hf.get("tie_word_embeddings", True),
             True),
            ("time_step_limit", tuple(hf.get("time_step_limit",
                                             (0.0, float("inf")))),
             (0.0, float("inf")))):
        if got != want:
            raise ValueError(f"{key} = {got!r} is not modelled (only "
                             f"{want!r})")
    d, H, hd = hf["hidden_size"], hf["mamba_n_heads"], hf["mamba_d_head"]
    if hf["mamba_expand"] * d != H * hd:
        raise ValueError(
            f"mamba_expand {hf['mamba_expand']} x hidden_size {d} is not "
            f"mamba_n_heads {H} x mamba_d_head {hd}")
    heads = hf["num_attention_heads"]
    return GraniteHybridConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=d,
        layer_kinds=kinds, ssm_heads=H, ssm_head_dim=hd,
        ssm_state=hf["mamba_d_state"], ssm_groups=hf["mamba_n_groups"],
        conv_width=hf["mamba_d_conv"], ssm_chunk=hf["mamba_chunk_size"],
        n_heads=heads, n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or d // heads,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        ffn_dim=hf["shared_intermediate_size"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        rms_eps=hf["rms_norm_eps"],
        max_context=hf["max_position_embeddings"],
    )


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: GraniteHybridConfig, num_blocks: int,
                    block_size: int, lanes: int = 1) -> Tuple[tuple, ...]:
    """(k, v, state, conv tail).  The paged pools have `num_blocks`
    blocks and the attention layers only; state and tail have one entry
    a lane and Mamba layer, the tail's rows end to end."""
    pool = (len(cfg.layers_of(ATTN)), cfg.n_kv_heads, num_blocks,
            cfg.head_dim, block_size)
    return (pool, pool) + mamba2.state_shapes(
        cfg.ssm, len(cfg.layers_of(MAMBA)), lanes, flat_tail=True)


def kv_cache_dtypes(cfg: GraniteHybridConfig) -> Tuple[Any, ...]:
    return (cfg.dtype, cfg.dtype, cfg.state_dtype, cfg.dtype)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 4


def state_impl(cfg: GraniteHybridConfig, attn_impl: str) -> str:
    """The impl of the state's decode step under `attn_impl`
    (mamba2.state_impl), asked by the traced step and by the host's
    counts alike."""
    return mamba2.state_impl(cfg.ssm, attn_impl)


def decode_block_counts(cfg: GraniteHybridConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """mamba2.decode_counts over this family's layers, under
    nemotron_h's counter names: its counter-only metric files read
    them."""
    return mamba2.decode_counts(
        cfg.ssm, len(cfg.layers_of(ATTN)), len(cfg.layers_of(MAMBA)), ctx,
        k, block_size, lanes, table_width, attn_impl)


def prefill_token_counts(cfg: GraniteHybridConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """mamba2.prefill_counts over this family's attention layers."""
    return mamba2.prefill_counts(cfg, len(cfg.layers_of(ATTN)), pos, chunk,
                                 bucket)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: GraniteHybridConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    `params["layers"]` is one layer a position of `cfg.period`, every
    leaf [periods, ...].  The
    norms' weights are random around 1 so that a norm left out or moved
    changes the answer; a Mamba layer's parameters are
    mamba2.init_mixer's."""
    dense = mamba2.dense_init(cfg.dtype)

    def norm(key):
        return {"norm": 1.0 + 0.1 * jax.random.normal(
            key, (cfg.d_model,), jnp.float32)}

    period = cfg.period
    keys = jax.random.split(key, len(period) + 2)
    params: Dict[str, Any] = place({
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": norm(keys[1]),
    })
    d, f = cfg.d_model, cfg.ffn_dim

    def one(kind, key):
        k = jax.random.split(key, 12)
        layer: Dict[str, Any] = {
            "norm": norm(k[8]), "mlp_norm": norm(k[9]),
            # gate | up side by side: one matmul
            "mlp_in": dense(k[10], (d, 2 * f)),
            "mlp_out": dense(k[11], (f, d)),
        }
        if kind == MAMBA:
            layer.update(mamba2.init_mixer(cfg.ssm, d, k, dense))
        else:
            layer.update({
                "wq": dense(k[0], (d, cfg.q_dim)),
                "wk": dense(k[1], (d, cfg.kv_dim)),
                "wv": dense(k[2], (d, cfg.kv_dim)),
                "wo": dense(k[3], (cfg.q_dim, d)),
            })
        return layer

    # one layer a position of the period, its leaves stacked over the
    # periods: ONE draw a position, whatever the depth
    params["layers"] = [
        place(jax.vmap(partial(one, kind))(
            jax.random.split(keys[2 + j], cfg.n_periods)))
        for j, kind in enumerate(period)]
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _embed(params, cfg: GraniteHybridConfig, token_ids: jax.Array):
    return params["embedding"][token_ids].astype(jnp.float32) \
        * cfg.embedding_multiplier


def _scaled_q(cfg: GraniteHybridConfig, q: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * cfg.q_scale).astype(q.dtype)


@jax.named_scope("dyn.mlp")
def _gated_mlp(layer, cfg: GraniteHybridConfig, x: jax.Array) -> jax.Array:
    """x [..., d] float32 the stream -> [..., d] float32."""
    h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
    gu = mm(h.astype(cfg.dtype), layer["mlp_in"])
    g, u = gu[..., :cfg.ffn_dim], gu[..., cfg.ffn_dim:]
    return mm((jax.nn.silu(g) * u).astype(cfg.dtype), layer["mlp_out"])


@jax.named_scope("dyn.lm_head")
def _logits(params, cfg: GraniteHybridConfig, x: jax.Array) -> jax.Array:
    """x [..., d] float32 -> logits float32, the accumulator kept."""
    h = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    return mm(h.astype(cfg.dtype), params["embedding"].T) \
        / cfg.logits_scaling


def _over_periods(params, cfg: GraniteHybridConfig, carry, layer_fn,
                  unrolled: bool = False):
    """The layers, a period at a time: a `lax.scan` over the periods
    whose body is the period unrolled, or with `unrolled` a Python loop
    over static slices of the stacked weights (the module's docstring
    says which program takes which, and why).  `layer_fn(carry, layer,
    kind, pli) -> carry` is one layer: `layer` its parameters, `pli` its
    index in its kind's cache members = period x (layers of the kind a
    period) + its index inside the period (traced under the scan)."""
    period = cfg.period
    a_period = {kind: period.count(kind) for kind in set(period)}
    within = [period[:j].count(kind) for j, kind in enumerate(period)]

    def body(carry, xs):
        layers, p = xs
        for layer, kind, j in zip(layers, period, within):
            carry = layer_fn(carry, layer, kind, p * a_period[kind] + j)
        return carry, None

    if unrolled:
        for p in range(cfg.n_periods):
            carry, _ = body(carry, (jax.tree_util.tree_map(
                lambda a: a[p], params["layers"]), p))
        return carry
    return jax.lax.scan(
        body, carry,
        (params["layers"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))[0]


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_batched(
    params: Dict[str, Any],
    cfg: GraniteHybridConfig,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]; unused: no rotary
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract),
    padded per row, as nemotron_h.prefill_batched: a Mamba layer is
    mamba2.mixer_prefill; an attention layer writes the rows' K and V to
    the pool and reads them, and the cached context, back through
    ops/packed_prefill.py (the rows laid end to end are a packed stream
    whose segments are the rows)."""
    if lanes is None:
        raise ValueError("this family's state is addressed by lane: "
                         "prefill needs `lanes`")
    k_cache, v_cache, state, tail = kv_cache
    Bp, T = token_ids.shape
    x = _embed(params, cfg, token_ids)                     # [Bp, T, d]
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = idx < true_lens[:, None]
    # the packed stream (tables, segment rows, positions, valid), the
    # same for every attention layer; no rotary here: a position says
    # where a token's K and V go and how far its query sees
    stream = (block_tables, jnp.repeat(jnp.arange(Bp, dtype=jnp.int32), T),
              (ctx_lens[:, None] + idx).reshape(-1), valid.reshape(-1))
    fresh = ctx_lens == 0
    put = rows_target(lanes, true_lens, state.shape[1])
    res = cfg.residual_multiplier

    def layer_fn(carry, layer, kind, pli):
        x, k_cache, v_cache, state, tail = carry
        # the float32 stream, normed; a mixer casts it for its matmuls
        h = rms_norm(x, layer["norm"]["norm"], cfg.rms_eps)
        if kind == MAMBA:
            y, state, tail = mamba2.mixer_prefill(
                layer, cfg.ssm, h, state, tail, pli, lanes, fresh, put,
                valid, true_lens, hold_start=True)
        else:
            q, k, v = _qkv(layer, cfg,
                           h.astype(cfg.dtype).reshape(Bp * T, -1), None)
            k_cache, v_cache = write_packed_kv(
                k_cache, v_cache, pli, k, v, *stream)
            attn = packed_prefill_attention(
                _scaled_q(cfg, q), k_cache, v_cache, pli, *stream,
                impl=cfg.packed_attn_impl)
            y = _attn_out(layer, attn.reshape(Bp, T, cfg.q_dim))
        x = x + res * y
        x = x + res * _gated_mlp(layer, cfg, x)
        return x, k_cache, v_cache, state, tail

    x, k_cache, v_cache, state, tail = _over_periods(
        params, cfg, (x, k_cache, v_cache, state, tail), layer_fn)
    last = jnp.maximum(true_lens - 1, 0)
    return _logits(params, cfg, x[jnp.arange(Bp), last]), (
        k_cache, v_cache, state, tail)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: GraniteHybridConfig,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]; unused: no rotary
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
    state_plan=None,           # decode_multi's: `lanes_plan`, once a burst
):
    """One token a lane.  A Mamba layer reads and writes the live lanes'
    state where it lies (mamba2.mixer_decode); a lane that is not
    `valid` keeps state and tail as they were."""
    k_cache, v_cache, state, tail = kv_cache
    x = _embed(params, cfg, token_ids)                     # [B, d]
    B = x.shape[0]
    live = jnp.ones((B,), bool) if valid is None else valid
    # llama._decode_trunk's plan for the paged members
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype)
    write_token = partial(write_token_kv, resident=impl in PALLAS_IMPLS,
                          valid=valid)
    kv_lens = jnp.where(live, ctx_lens + 1, 0)
    s_impl = state_impl(cfg, cfg.attn_impl)
    if state_plan is None:
        state_plan = lanes_plan(live, s_impl)
    res = cfg.residual_multiplier

    def layer_fn(carry, layer, kind, pli):
        x, k_cache, v_cache, state, tail = carry
        h = rms_norm(x, layer["norm"]["norm"], cfg.rms_eps)
        if kind == MAMBA:
            y, state, tail = mamba2.mixer_decode(
                layer, cfg.ssm, h, state, tail, pli, state_plan, s_impl,
                live)
        else:
            q, k, v = _qkv(layer, cfg, h[:, None, :].astype(cfg.dtype), None)
            k_cache, v_cache = write_token(
                k_cache, v_cache, pli, k[:, 0], v[:, 0], block_tables,
                ctx_lens)
            attn = paged_attention_decode(
                _scaled_q(cfg, q[:, 0]), k_cache, v_cache, pli,
                block_tables, kv_lens, impl=impl, mesh=mesh)
            y = _attn_out(layer, attn.reshape(B, cfg.q_dim))
        x = x + res * y
        x = x + res * _gated_mlp(layer, cfg, x)
        return x, k_cache, v_cache, state, tail

    x, k_cache, v_cache, state, tail = _over_periods(
        params, cfg, (x, k_cache, v_cache, state, tail), layer_fn,
        unrolled=True)
    return _logits(params, cfg, x), (k_cache, v_cache, state, tail)


def decode_multi(
    params: Dict[str, Any],
    cfg: GraniteHybridConfig,
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
