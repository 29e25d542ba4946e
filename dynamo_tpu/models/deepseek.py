"""DeepSeek-family decoder: MLA attention + DeepSeekMoE, functional JAX
over the paged latent cache (BASELINE config 4: DeepSeek-R1 disagg).

Ref role: the reference serves DeepSeek-R1 via vLLM/SGLang recipes
(/root/reference/recipes/deepseek-r1/, docs/benchmarks/deepseek-v3-2-
wideep-routing.mdx); this module is the TPU-native model itself, same
functional contract as models/llama.py (prefill / prefill_batched /
decode / decode_multi over a paged cache) so the serving engine treats
both families uniformly through models.get_family().

Architecture (DeepSeek V2/V3 lineage):
  * MLA: queries optionally LoRA-compressed (q_lora_rank), KV compressed
    to a kv_lora_rank latent + a decoupled shared RoPE key; the paged
    cache stores (latent, rope-key) pairs — ops/mla_attention.py.
  * DeepSeekMoE: first_k_dense dense layers, then MoE layers with
    n_shared_experts always-on dense experts plus top-k routed experts
    (models/moe.py's router and dispatch, scaled by routed_scaling_factor).

Decode runs the weight-absorbed MLA formulation (never materializes
per-head K/V), its read in the Pallas latent kernel where
`cfg.attn_impl` resolves to it (a TPU with 128-token blocks: the live
blocks only, from the pool where it lies) and in jnp elsewhere; prefill
up-projects K and V a key tile at a time inside one flash kernel over
the pool's live blocks where `mla_prefill_plan` says so (the same
caches, a bucket of 512 tokens or more), and for the whole table in jnp
elsewhere.  YaRN long-context scaling
is not implemented (rope_theta covers the tested ranges).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.mla_attention import (
    MLA_DECODE_IMPLS,
    mla_decode_attention,
    mla_prefill_attention,
    mla_prefill_flash,
    mla_write_rows,
    mla_write_token,
    resolve_mla_prefill_impl,
)
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    resolve_decode_impl,
    write_prompt_kv_batched,
    write_token_kv,
)
from .common import burst_scan
from .llama import _logits, _mlp, rms_norm, rope
from .moe import ds_router, moe_dispatch, moe_rows


@dataclass(frozen=True)
class DeepseekConfig:
    name: str = "tiny-mla"
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    # MLA dims
    q_lora_rank: int = 0          # 0 = full query projection (V2-Lite)
    kv_lora_rank: int = 64        # R: latent cache dim per token
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16    # dr: shared rope key dim per token
    v_head_dim: int = 32
    # FFN / DeepSeekMoE
    ffn_dim: int = 1408           # dense layers
    moe_ffn_dim: int = 0          # per-expert hidden (0 -> ffn_dim)
    n_experts: int = 0            # 0 = all layers dense
    experts_per_token: int = 2
    n_shared_experts: int = 0     # always-on experts (hidden = n * moe_ffn)
    first_k_dense: int = 1        # leading dense layers before MoE starts
    routed_scaling_factor: float = 1.0
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    # router semantics (HF DeepseekV3TopkRouter / V2 MoEGate):
    #   V2 lineage: softmax scores, plain top-k, no renorm
    #   V3 lineage: sigmoid scores + e_score_correction_bias for CHOICE
    #   (weights stay raw scores), group-limited top-k, renormalized
    moe_scoring: str = "softmax"  # "softmax" | "sigmoid"
    norm_topk_prob: bool = False
    n_group: int = 1              # expert groups for group-limited top-k
    topk_group: int = 1           # groups kept
    # misc
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"       # the absorbed decode read: MLA_DECODE_IMPLS
    eos_token_ids: Tuple[int, ...] = (2,)
    qk_norm: bool = False         # unused; uniform surface with LlamaConfig

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mla_plane_heights(self) -> Tuple[int, int]:
        """The rows of a cached block's planes, latent and rope key:
        what `resolve_decode_impl` reads as `head_dim` for this cache."""
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    # callers that resolve "auto" from `cfg.head_dim`, as for the GQA
    # families (engine/core.py, benchmark/chip_logits.py), get the same
    head_dim = mla_plane_heights

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.qk_head_dim

    def _moe_layer(self, li: int) -> bool:
        return self.n_experts > 0 and li >= self.first_k_dense


# what the absorbed decode read can be told to be (there is no
# "jnp_bf16" form of it); the engine rejects any other --attn-impl
# loudly against this set
SUPPORTED_ATTN_IMPLS = MLA_DECODE_IMPLS

PRESETS: Dict[str, DeepseekConfig] = {
    # test-scale
    "tiny-mla": DeepseekConfig(),
    "tiny-mla-moe": DeepseekConfig(
        name="tiny-mla-moe", vocab_size=256, d_model=64, n_layers=3,
        n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, ffn_dim=128, moe_ffn_dim=64,
        n_experts=4, experts_per_token=2, n_shared_experts=1,
        first_k_dense=1,
    ),
    # public architecture shapes
    "deepseek-v2-lite": DeepseekConfig(
        name="deepseek-v2-lite", vocab_size=102400, d_model=2048,
        n_layers=27, n_heads=16, q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        ffn_dim=10944, moe_ffn_dim=1408, n_experts=64,
        experts_per_token=6, n_shared_experts=2, first_k_dense=1,
        routed_scaling_factor=1.0, rope_theta=10000.0,
        max_context=163840,
    ),
    # BASELINE config 4 (DeepSeek-R1 == V3 architecture)
    "deepseek-r1": DeepseekConfig(
        name="deepseek-r1", vocab_size=129280, d_model=7168,
        n_layers=61, n_heads=128, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        ffn_dim=18432, moe_ffn_dim=2048, n_experts=256,
        experts_per_token=8, n_shared_experts=1, first_k_dense=3,
        routed_scaling_factor=2.5, moe_scoring="sigmoid",
        norm_topk_prob=True, n_group=8, topk_group=4,
        rope_theta=10000.0, max_context=163840,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: DeepseekConfig, num_blocks: int,
                    block_size: int) -> Tuple[tuple, tuple]:
    """(latent cache, rope-key cache) in the shared head-major layout with
    nkv=1 — every block op (scatter/gather/offload/transfer) reuses it.

    NOTE: this family deliberately has NO kv_cache_scale_shapes — the MLA
    latent is already a ~4x compression of the per-head K/V and the
    weight-absorbed decode consumes it inside matmuls where per-position
    int8 scales don't factor out cleanly, so `kv_cache_dtype="int8"`
    auto-falls back to bf16 here (engine/core.py, same precedent as the
    MLA packed-prefill and spec-decode fallbacks)."""
    return (
        (cfg.n_layers, 1, num_blocks, cfg.kv_lora_rank, block_size),
        (cfg.n_layers, 1, num_blocks, cfg.qk_rope_head_dim, block_size),
    )


def kv_cache_specs() -> Tuple[P, P]:
    """Latent caches are REPLICATED under tp (there is no kv-head axis to
    shard; heads shard via w_uk/w_uv/wq_b instead).  At R+dr bytes/token
    the replicated cache is still ~nkv*2*hd/(R+dr) smaller per chip than a
    sharded GQA cache for the big configs."""
    return (P(), P())


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: DeepseekConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params
    (applied to the top-level leaves and to each layer as it exists)."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype
        )

    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (cfg.d_model, cfg.vocab_size))
    params = place(params)
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 13)
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
            "wkv_a": dense(k[0], (cfg.d_model, R + dr)),
            "kv_a_norm": {"norm": jnp.ones((R,), jnp.float32)},
            "w_uk": dense(k[1], (cfg.n_heads, R, dn),
                          scale=1.0 / math.sqrt(R)),
            "w_uv": dense(k[2], (cfg.n_heads, R, dv),
                          scale=1.0 / math.sqrt(R)),
            "wo": dense(k[3], (cfg.n_heads * dv, cfg.d_model)),
        }
        if cfg.q_lora_rank > 0:
            layer["wq_a"] = dense(k[4], (cfg.d_model, cfg.q_lora_rank))
            layer["q_a_norm"] = {
                "norm": jnp.ones((cfg.q_lora_rank,), jnp.float32)}
            layer["wq_b"] = dense(k[5], (cfg.q_lora_rank, cfg.q_dim))
        else:
            layer["wq"] = dense(k[4], (cfg.d_model, cfg.q_dim))
        if cfg._moe_layer(li):
            E = cfg.n_experts
            f = cfg.moe_ffn_dim or cfg.ffn_dim
            layer["moe_gate"] = dense(k[6], (cfg.d_model, E))
            if cfg.moe_scoring == "sigmoid":
                # V3 lineage: choice-bias buffer (loaded from checkpoints)
                layer["moe_gate_bias"] = jnp.zeros((E,), jnp.float32)
            layer["moe_w_gate"] = dense(k[7], (E, cfg.d_model, f),
                                        scale=1.0 / math.sqrt(cfg.d_model))
            layer["moe_w_up"] = dense(k[8], (E, cfg.d_model, f),
                                      scale=1.0 / math.sqrt(cfg.d_model))
            layer["moe_w_down"] = dense(k[9], (E, f, cfg.d_model),
                                        scale=1.0 / math.sqrt(f))
            if cfg.n_shared_experts > 0:
                sf = cfg.n_shared_experts * f
                layer["shared"] = {
                    "w_gate": dense(k[10], (cfg.d_model, sf)),
                    "w_up": dense(k[11], (cfg.d_model, sf)),
                    "w_down": dense(k[12], (sf, cfg.d_model)),
                }
        else:
            layer["w_gate"] = dense(k[6], (cfg.d_model, cfg.ffn_dim))
            layer["w_up"] = dense(k[7], (cfg.d_model, cfg.ffn_dim))
            layer["w_down"] = dense(k[8], (cfg.ffn_dim, cfg.d_model))
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.attn_qkv")
def _q_proj(layer, cfg: DeepseekConfig, x: jax.Array,
            positions: jax.Array):
    """x [..., T, d] -> (q_nope [..., T, nh, dn], q_rope [..., T, nh, dr],
    rope applied to the rope part)."""
    *lead, T, _ = x.shape
    if cfg.q_lora_rank > 0:
        q = rms_norm(x @ layer["wq_a"], layer["q_a_norm"]["norm"],
                     cfg.rms_eps) @ layer["wq_b"]
    else:
        q = x @ layer["wq"]
    q = q.reshape(*lead, T, cfg.n_heads, cfg.qk_head_dim)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


@jax.named_scope("dyn.attn_qkv")
def _kv_latent(layer, cfg: DeepseekConfig, x: jax.Array,
               positions: jax.Array):
    """x [..., T, d] -> (c [..., T, R] normed latent, kr [..., T, dr]
    rope-applied shared key)."""
    R = cfg.kv_lora_rank
    kv = x @ layer["wkv_a"]                      # [..., T, R+dr]
    c = rms_norm(kv[..., :R], layer["kv_a_norm"]["norm"], cfg.rms_eps)
    kr = rope(kv[..., None, R:], positions, cfg.rope_theta)[..., 0, :]
    return c, kr


def _ds_ffn(layer, cfg: DeepseekConfig, x: jax.Array,
            valid: Optional[jax.Array] = None) -> jax.Array:
    """Dense layer, or DeepSeekMoE = shared experts + routed experts
    (models/moe.py's DeepSeek routing and dispatch over the moe_* keys)."""
    if "moe_gate" not in layer:
        return _mlp(layer, x)
    top_w, top_e = ds_router(layer, cfg, x)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid)
    if "shared" in layer:
        out = out + _mlp(layer["shared"], x)
    return out


@jax.named_scope("dyn.attn_qkv")
def _absorb_q(layer, q_nope: jax.Array) -> jax.Array:
    """q_nope [..., nh, dn] @ w_uk^T -> absorbed query [..., nh, R]."""
    return jnp.einsum("...hd,hrd->...hr", q_nope.astype(jnp.float32),
                      layer["w_uk"].astype(jnp.float32)).astype(q_nope.dtype)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def mla_prefill_impl(cfg, tokens: int, block_size: int = 1,
                     cache_dtype=None) -> str:
    """The impl of the MLA layers' prefill read in a program whose rows
    are `tokens` long, under `cfg.attn_impl`
    (ops/mla_attention.resolve_mla_prefill_impl), asked by the traced
    program about its cache and by the host's counts alike
    (models/ling.py too).  The host has no cache to show: it asks about
    the impl the engine has made of "auto" for its cache at start-up,
    which the rule takes as given; an "auto" that nobody resolved is the
    jnp form there."""
    return resolve_mla_prefill_impl(
        cfg.attn_impl, jax.default_backend(), block_size,
        cfg.mla_plane_heights, cache_dtype or cfg.dtype, tokens)


def mla_prefill_plan(cfg, c_cache, kr_cache, tokens: int, mesh, jnp_read):
    """What a prefill program's MLA layers share (models/ling.py too):
    -> (write, read).  `write(c_cache, kr_cache, li, c, kr, tables,
    ctx_lens, true_lens)` puts the rows' chunks [S, T, .] into the
    pools; `read(layer, li, q_nope, q_rope, c, kr, c_cache, kr_cache,
    tables, ctx_lens, true_lens)` -> [S, T, nh, dv] attends them and the
    cached context.  Where the program's bucket takes the kernel
    (`mla_prefill_impl`) the read is one flash pass over the pool's
    live blocks, elsewhere `jnp_read`, the family's jnp form.  Where the
    cache's reads are kernels at SOME bucket (the decode read's rule)
    the chunk is written as whole planes in the resident layout, in the
    buckets whose read stays jnp too: the column scatter would have XLA
    relay the pool around every kernel call; elsewhere the scatter, and
    the program is the one it was."""
    bs = c_cache.shape[4]
    planes = resolve_decode_impl(
        cfg.attn_impl, jax.default_backend(), bs,
        (c_cache.shape[3], kr_cache.shape[3]), c_cache.dtype) in PALLAS_IMPLS
    impl = mla_prefill_impl(cfg, tokens, bs, c_cache.dtype)

    def scatter(c_cache, kr_cache, li, c, kr, *where):
        return write_prompt_kv_batched(
            c_cache, kr_cache, li, c[:, :, None, :], kr[:, :, None, :],
            *where)

    def flash(layer, li, q_nope, q_rope, c, kr, c_cache, kr_cache, *where):
        # (the chunk's own c and kr are in the pools already)
        return mla_prefill_flash(
            q_nope, q_rope, c_cache, kr_cache, li, *where, layer["w_uk"],
            layer["w_uv"], mesh=mesh, interpret=impl == "pallas_interpret")

    return (mla_write_rows if planes else scatter,
            flash if impl in PALLAS_IMPLS else jnp_read)


def _mla_prefill_jnp(layer, li, q_nope, q_rope, c, kr, c_cache, kr_cache,
                     block_tables, ctx_lens, true_lens):
    """The jnp form a row: `mla_prefill_plan`'s `jnp_read` here."""
    return jax.vmap(
        lambda qn, qr, cb, krb, tb, cl, tl: mla_prefill_attention(
            qn, qr, cb, krb, c_cache, kr_cache, li, tb, cl, tl,
            layer["w_uk"], layer["w_uv"])
    )(q_nope, q_rope, c, kr, block_tables, ctx_lens, true_lens)


def prefill_token_counts(cfg: DeepseekConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos` in a program of `bucket` rows: the tokens an MLA
    layer's prefill read took, and those of them whose program ran it
    in the kernel (`mla_prefill_impl` of the bucket)."""
    kernel = mla_prefill_impl(cfg, bucket) in PALLAS_IMPLS
    return {
        "mla_prefill_tokens.prefill": cfg.n_layers * chunk,
        "mla_prefill_kernel_tokens.prefill":
            cfg.n_layers * chunk if kernel else 0,
    }


def prefill(
    params: Dict[str, Any],
    cfg: DeepseekConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [T_pad] int32
    positions: jax.Array,      # [T_pad] int32
    block_table: jax.Array,    # [max_blocks] int32
    ctx_len: jax.Array,
    true_len: jax.Array,
    mesh=None,                 # the kernel's, under tp > 1
):
    """Same contract as llama.prefill; cache pair = (latent, rope key)."""
    # dynlint: disable=DYN009 MLA latent cache is bf16-only by design (no int8 scale shapes); the engine forces the bf16 fallback for this family
    c_cache, kr_cache = kv_cache
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [T, d]
    T = x.shape[0]
    write, read = mla_prefill_plan(cfg, c_cache, kr_cache, T, mesh,
                                   _mla_prefill_jnp)
    row = (block_table[None], ctx_len[None], true_len[None])
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q_nope, q_rope = _q_proj(layer, cfg, h, positions)
        c, kr = _kv_latent(layer, cfg, h, positions)
        c_cache, kr_cache = write(c_cache, kr_cache, li, c[None], kr[None],
                                  *row)
        attn = read(layer, li, q_nope[None], q_rope[None], c[None],
                    kr[None], c_cache, kr_cache, *row)[0]
        x = x + attn.reshape(T, -1) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ds_ffn(layer, cfg, h,
                        valid=jnp.arange(T) < true_len)
    last = jnp.maximum(true_len - 1, 0)
    return _logits(params, cfg, x[last]), (c_cache, kr_cache)


def prefill_batched(
    params: Dict[str, Any],
    cfg: DeepseekConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    mesh=None,
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract)."""
    # dynlint: disable=DYN009 MLA latent cache is bf16-only by design (no int8 scale shapes); the engine forces the bf16 fallback for this family
    c_cache, kr_cache = kv_cache
    Bp, T = token_ids.shape
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [Bp, T, d]
    valid = jnp.arange(T)[None, :] < true_lens[:, None]
    write, read = mla_prefill_plan(cfg, c_cache, kr_cache, T, mesh,
                                   _mla_prefill_jnp)
    rows = (block_tables, ctx_lens, true_lens)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q_nope, q_rope = _q_proj(layer, cfg, h, positions)
        c, kr = _kv_latent(layer, cfg, h, positions)
        c_cache, kr_cache = write(c_cache, kr_cache, li, c, kr, *rows)
        attn = read(layer, li, q_nope, q_rope, c, kr, c_cache, kr_cache,
                    *rows)
        x = x + attn.reshape(Bp, T, -1) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + moe_rows(partial(_ds_ffn, layer, cfg), h, valid)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last]
    return _logits(params, cfg, xl), (c_cache, kr_cache)


# ---------------------------------------------------------------------------
# decode (weight-absorbed)
# ---------------------------------------------------------------------------


def mla_decode_plan(cfg, c_cache, kr_cache, ctx_lens, valid, mesh):
    """What a decode step's MLA layers share (models/ling.py too):
    -> (impl, kv_lens, write_token).  `impl` is what `cfg.attn_impl`
    means for this cache here (a TPU with lane-aligned blocks: the
    Pallas kernel).  The kernel reads the pools where they lie, so
    beside it the token's column is written in the resident layout too,
    by its own kernel (`mla_write_token`; with the flat scatter XLA lays
    the pool out one way for the write and another for the custom call,
    and copies it every step), and an idle lane (valid False) claims no
    context: it reads nothing."""
    impl = resolve_decode_impl(
        cfg.attn_impl, jax.default_backend(), c_cache.shape[4],
        (c_cache.shape[3], kr_cache.shape[3]), c_cache.dtype)
    kv_lens = ctx_lens + 1
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    if impl in PALLAS_IMPLS:
        write = partial(mla_write_token, valid=valid, mesh=mesh,
                        interpret=impl == "pallas_interpret")
    else:
        write = write_token_kv
    return impl, kv_lens, write


def decode(
    params: Dict[str, Any],
    cfg: DeepseekConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
    token_ids: jax.Array,      # [B]
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,                 # the kernel's, under tp > 1
):
    # dynlint: disable=DYN009 MLA latent cache is bf16-only by design (no int8 scale shapes); the engine forces the bf16 fallback for this family
    c_cache, kr_cache = kv_cache
    x = params["embedding"][token_ids].astype(cfg.dtype)  # [B, d]
    B = x.shape[0]
    pos1 = positions[:, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    impl, kv_lens, write_token = mla_decode_plan(
        cfg, c_cache, kr_cache, ctx_lens, valid, mesh)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q_nope, q_rope = _q_proj(layer, cfg, h[:, None, :], pos1)
        c, kr = _kv_latent(layer, cfg, h[:, None, :], pos1)
        c_cache, kr_cache = write_token(
            c_cache, kr_cache, li, c[:, 0][:, None, :],
            kr[:, 0][:, None, :], block_tables, ctx_lens,
        )
        q_abs = _absorb_q(layer, q_nope[:, 0])           # [B, nh, R]
        attn = mla_decode_attention(
            q_abs, q_rope[:, 0], c_cache, kr_cache, li,
            block_tables, kv_lens, layer["w_uv"], scale,
            impl=impl, mesh=mesh,
        )                                                # [B, nh, dv]
        x = x + attn.reshape(B, -1) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ds_ffn(layer, cfg, h, valid=valid)
    return _logits(params, cfg, x), (c_cache, kr_cache)


def decode_multi(
    params: Dict[str, Any],
    cfg: DeepseekConfig,
    kv_cache: Tuple[jax.Array, jax.Array],
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
