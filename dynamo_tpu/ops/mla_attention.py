"""Paged Multi-head Latent Attention (MLA) ops — the DeepSeek-family
attention over a compressed latent KV cache.

Ref role: the reference serves DeepSeek-R1/V3 through vLLM/SGLang MLA
kernels (recipes/deepseek-r1/, docs/benchmarks/deepseek-v3-2-wideep-
routing.mdx); this is the TPU-native equivalent built on the same paged
layout as ops/paged_attention.py.

MLA caches, per token, a LATENT pair instead of per-head K/V:
    c    [R]   compressed KV latent (R = kv_lora_rank, e.g. 512)
    k_R  [dr]  decoupled RoPE key (dr = qk_rope_head_dim, e.g. 64)
an ~order-of-magnitude smaller cache than GQA for the same model — the
property that makes DeepSeek long-context serving cheap.  The caches
reuse the head-major transposed block layout with nkv=1:
    c_cache  [L, 1, nblocks, R,  bs]
    kr_cache [L, 1, nblocks, dr, bs]
so every existing block op (write/scatter/gather, KVBM offload, disagg
transfer) works unchanged on MLA engines.

Decode uses the WEIGHT-ABSORBED formulation: per head
    score_t = q_nope·(W_UK c_t) + q_rope·k_R_t
            = (q_nope W_UK^T)·c_t + q_rope·k_R_t
so the per-head key is never materialized — queries are absorbed into
latent space ([B, nh, R]) and attention runs directly against the cache;
the context vector (sum_t p_t c_t) is up-projected once by W_UV.  Prefill
materializes per-head K/V for the chunk+context (the standard non-absorbed
path: better MXU shapes for long chunks, and it runs once per prompt).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather_latent(cache: jax.Array, layer: int,
                   block_table: jax.Array) -> jax.Array:
    """[L,1,nb,R,bs] + [max_blocks] -> [S, R] (S = max_blocks*bs)."""
    g = cache[layer, 0][block_table]         # [mb, R, bs]
    mb, R, bs = g.shape
    return g.swapaxes(1, 2).reshape(mb * bs, R)


@jax.named_scope("dyn.attention")
def mla_prefill_attention(
    q_nope: jax.Array,    # [T, nh, dn]  (no rope)
    q_rope: jax.Array,    # [T, nh, dr]  (rope applied)
    c: jax.Array,         # [T, R]   this chunk's latents (normed)
    kr: jax.Array,        # [T, dr]  this chunk's rope keys (rope applied)
    c_cache: jax.Array,
    kr_cache: jax.Array,
    layer: int,
    block_table: jax.Array,  # [max_blocks]
    ctx_len: jax.Array,      # cached tokens this chunk attends to
    true_len: jax.Array,     # valid tokens in the chunk
    w_uk: jax.Array,      # [nh, R, dn]
    w_uv: jax.Array,      # [nh, R, dv]
) -> jax.Array:
    """Chunk tokens attend to (cached context) ++ (chunk, causally).
    Returns [T, nh, dv].  Cached context is up-projected from latents —
    identical math to having cached full K/V, at R+dr bytes/token."""
    T, nh, dn = q_nope.shape
    dr = q_rope.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(dn + dr))

    c_ctx = _gather_latent(c_cache, layer, block_table)    # [S, R]
    kr_ctx = _gather_latent(kr_cache, layer, block_table)  # [S, dr]
    S = c_ctx.shape[0]
    c_all = jnp.concatenate([c_ctx.astype(jnp.float32),
                             c.astype(jnp.float32)], axis=0)   # [S+T, R]
    kr_all = jnp.concatenate([kr_ctx.astype(jnp.float32),
                              kr.astype(jnp.float32)], axis=0)  # [S+T, dr]

    k_nope = jnp.einsum("sr,hrd->hsd", c_all,
                        w_uk.astype(jnp.float32))          # [nh, S+T, dn]
    v_all = jnp.einsum("sr,hrd->hsd", c_all,
                       w_uv.astype(jnp.float32))           # [nh, S+T, dv]

    s = jnp.einsum("thd,hsd->ths", q_nope.astype(jnp.float32), k_nope)
    s = s + jnp.einsum("thd,sd->ths", q_rope.astype(jnp.float32), kr_all)
    s = s * scale                                          # [T, nh, S+T]

    i = jnp.arange(T)[:, None, None]
    j = jnp.arange(S + T)[None, None, :]
    # context part: j < ctx_len; self part: causal within valid chunk
    mask = jnp.where(j < S, j < ctx_len,
                     ((j - S) <= i) & ((j - S) < true_len))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("ths,hsd->thd", p, v_all)             # [T, nh, dv]
    return out.astype(q_nope.dtype)


@jax.named_scope("dyn.attention")
def mla_decode_attention(
    q_abs: jax.Array,     # [B, nh, R]  absorbed queries (q_nope @ w_uk^T)
    q_rope: jax.Array,    # [B, nh, dr]
    c_cache: jax.Array,
    kr_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [B, max_blocks]
    kv_lens: jax.Array,       # [B] valid tokens (incl. the one just written)
    w_uv: jax.Array,      # [nh, R, dv]
    scale: jax.Array | float,
) -> jax.Array:
    """One decode step over the latent cache, weight-absorbed.
    Returns [B, nh, dv]."""

    def one(qa, qr, table, kvlen):
        c_ctx = _gather_latent(c_cache, layer, table)      # [S, R]
        kr_ctx = _gather_latent(kr_cache, layer, table)    # [S, dr]
        s = jnp.einsum("hr,sr->hs", qa.astype(jnp.float32),
                       c_ctx.astype(jnp.float32))
        s = s + jnp.einsum("hd,sd->hs", qr.astype(jnp.float32),
                           kr_ctx.astype(jnp.float32))
        s = s * scale
        mask = (jnp.arange(c_ctx.shape[0]) < kvlen)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)                     # [nh, S]
        ctx = jnp.einsum("hs,sr->hr", p, c_ctx.astype(jnp.float32))
        return jnp.einsum("hr,hrd->hd", ctx, w_uv.astype(jnp.float32))

    out = jax.vmap(one)(q_abs, q_rope, block_tables, kv_lens)
    return out.astype(q_abs.dtype)
