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

The decode read has two forms and `mla_decode_attention` dispatches
between them as paged_attention_decode does for GQA: the jnp body
gathers every lane's whole table width, upcasts it to fp32 and masks
afterwards (exact test numerics; CPU, block_size 16, fp32 caches); the
Pallas kernel (ops/pallas_mla_attention.py) DMAs each lane's LIVE
blocks from the pool where it lies and reads the latent once for scores
and values.  "auto" is paged_attention's `resolve_decode_impl`, asked
about both cache members' plane heights (R and dr): the rule has one
home.  Beside the kernel the step's token is written by a kernel too
(`mla_write_token`), so that custom calls are the pools' only users
inside a decode step and the pools stay in HBM.  One layer-call on a
v5e, device time (my chip run, PR 36; PERF.md section 6 has the table):
Ling's 32 heads, 64 lanes x 45 blocks with 22 a lane live, jnp 1.24 ms
alone (3.7 ms inside the step, with the pool's slice and relayout),
kernel 0.36 ms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import (
    NEG_INF,
    PALLAS_IMPLS,
    kernel_tp_call,
    resolve_decode_impl,
)

# the impls the absorbed decode read has (the families' SUPPORTED_ATTN_IMPLS)
MLA_DECODE_IMPLS = ("auto", "pallas", "pallas_interpret", "jnp")


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


def _tp(mesh) -> int:
    return 1 if mesh is None else int(mesh.shape.get("tp", 1))


def _mla_decode_jnp(q_abs, q_rope, c_cache, kr_cache, layer,
                    block_tables, kv_lens, scale):
    """The gathering read: [B, nh, R] latent-space context in fp32."""

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
        return jnp.einsum("hs,sr->hr", p, c_ctx.astype(jnp.float32))

    return jax.vmap(one)(q_abs, q_rope, block_tables, kv_lens)


def _mla_decode_pallas(q_abs, q_rope, c_cache, kr_cache, layer,
                       block_tables, kv_lens, scale, *, mesh, interpret):
    """The kernel, per head shard under `shard_map` where the mesh has a
    tp axis: the latent pools are replicated there and the heads shard
    through w_uk / w_uv (models/deepseek.py kv_cache_specs), so a shard
    reads the whole pool for its own heads and nothing crosses shards
    (left to GSPMD the custom call would gather the heads instead)."""
    from jax.sharding import PartitionSpec as P

    from .pallas_mla_attention import mla_decode_pallas

    def local(qa, qr, cc, krc, tables, lens):
        return mla_decode_pallas(qa, qr, cc, krc, layer, tables, lens,
                                 scale, interpret=interpret)

    args = [q_abs, q_rope, c_cache, kr_cache, block_tables, kv_lens]
    if _tp(mesh) == 1:
        return local(*args)
    return kernel_tp_call(
        mesh, local, args,
        [P(None, "tp", None), P(None, "tp", None), P(), P(),
         P(None, None), P(None)])


def _replicated_under_tp(mesh, local, args):
    """`local(*args)` as it stands, or once a shard under `shard_map`
    with everything replicated where the mesh has a tp axis (a custom
    call is not GSPMD's to partition)."""
    if _tp(mesh) == 1:
        return local(*args)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return shard_map(local, mesh=mesh, in_specs=(P(),) * len(args),
                     out_specs=P(), check_vma=False)(*args)


@jax.named_scope("dyn.kv_write")
def mla_write_token(
    c_cache: jax.Array,
    kr_cache: jax.Array,
    layer: int,
    c: jax.Array,             # [B, 1, R]  (write_token_kv's nkv = 1 form)
    kr: jax.Array,            # [B, 1, dr]
    block_tables: jax.Array,  # [B, max_blocks]
    ctx_lens: jax.Array,      # [B] position to write (== current length)
    *,
    valid: jax.Array = None,  # [B] bool: the lanes that write (None: all)
    mesh=None,
    interpret: bool = False,
):
    """The decode step's token write beside the latent kernel: the
    cells `write_token_kv(resident=True, valid=valid)` would set, set by
    a Pallas kernel (pallas_mla_attention.mla_write_token_pallas says
    why a kernel).  Under tp every shard writes its replica."""
    from .pallas_mla_attention import mla_write_token_pallas

    bs = c_cache.shape[4]
    B = c.shape[0]
    blocks = block_tables[jnp.arange(B), ctx_lens // bs]
    if valid is None:
        valid = jnp.ones((B,), bool)
    return _replicated_under_tp(
        mesh,
        lambda *a: tuple(mla_write_token_pallas(*a, interpret=interpret)),
        (c_cache, kr_cache, jnp.int32(layer), c[:, 0], kr[:, 0], blocks,
         ctx_lens % bs, valid))


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
    impl: str = "auto",
    mesh=None,
) -> jax.Array:
    """One decode step over the latent cache, weight-absorbed.
    Returns [B, nh, dv].

    impl: "auto" (`resolve_decode_impl` on the default backend),
    "pallas", "pallas_interpret" (the kernel under the interpreter: CPU
    testing) or "jnp".  kv_lens 0 marks a lane with nothing to attend:
    the kernel reads nothing for it and returns 0, the jnp body a
    finite, unused average.  mesh: the engine's, for the kernel under
    tp > 1 (`_mla_decode_pallas`)."""
    impl = resolve_decode_impl(
        impl, jax.default_backend(), c_cache.shape[4],
        (c_cache.shape[3], kr_cache.shape[3]), c_cache.dtype)
    if impl in PALLAS_IMPLS:
        ctx = _mla_decode_pallas(
            q_abs, q_rope, c_cache, kr_cache, layer, block_tables,
            kv_lens, scale, mesh=mesh,
            interpret=impl == "pallas_interpret")
    elif impl == "jnp":
        ctx = _mla_decode_jnp(q_abs, q_rope, c_cache, kr_cache, layer,
                              block_tables, kv_lens, scale)
    else:
        raise ValueError(f"unknown MLA decode impl {impl!r}; expected "
                         + " | ".join(MLA_DECODE_IMPLS))
    out = jnp.einsum("bhr,hrd->bhd", ctx, w_uv.astype(jnp.float32))
    return out.astype(q_abs.dtype)
