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
MATERIALISES per-head K/V (the standard non-absorbed path: 640 FLOP a
(query, key) pair and head against the absorbed form's 2176), in two
forms.  The jnp form (`mla_prefill_attention`) gathers a row's WHOLE
table, up-projects K and V for every position of it and passes
[T, heads, table + T] float32 scores through HBM, whatever the context:
the CPU's path, block_size 16, float32 caches and the short buckets.
The Pallas kernel (`mla_prefill_flash` ->
pallas_mla_attention.mla_prefill_pallas, PR 49) is one flash pass over
the LIVE blocks of the pool, the chunk's own latents among them: a key
tile is DMA'd from the pool where it lies by the table's block ids and
up-projected a head at a time in VMEM, scores, running max, sum and
accumulator never leave VMEM, and tiles above a query tile's frontier
are skipped.  `resolve_mla_prefill_impl` picks, from what the code can
observe (platform, block, cache dtype, the program's bucket).  Beside a
kernel that reads the pool as it lies the chunk is written as whole
planes in the resident layout (`mla_write_rows`): the flat column
scatter has XLA relay the whole pool to the scatter's layout and back
every layer.  One layer-call on a v5e, op alone (PERF.md section 6,
PR 49): Ling's 32 heads, a 2048-token chunk over a 45-block table, the
jnp form 12.5 ms at every context, the kernel 1.03 ms at context 0 and
2.16 at 2048; Moonlight's 16 heads over 20 blocks 1.85 against 0.52.

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


# the bucket from which the prefill read is the kernel where the decode
# read is.  By time alone the kernel is no slower from 32 tokens up
# (PERF.md section 6, PR 49: under 512 it buys 0.05 ms a layer on
# Moonlight, and Ling's short buckets hold 0.5 % of its tokens); with
# the kernel at the 256 bucket `moonlight-16b.chat`'s `correct` (one
# fixed 256-token prompt that follows one expert's pick, ROADMAP Y0)
# left the float32 reference's trajectory at its fifth token (PR 48:
# gap 0.074 against a limit of 0.04, the jnp form's gaps there 0.031 to
# 0.038), with float32-accurate K and V as with bf16.  The floor goes
# lower when that check is a median over positions.
MLA_PREFILL_KERNEL_MIN_TOKENS = 512


def resolve_mla_prefill_impl(impl: str, platform: str, block_size: int,
                             plane_heights, cache_dtype,
                             tokens: int) -> str:
    """What `impl` (a family's `attn_impl`) means for the PREFILL read
    of a program whose rows are `tokens` long (its bucket): the twin of
    `resolve_decode_impl` / `resolve_packed_impl`, and like them the one
    place it is decided, from what the code can observe.  -> "pallas" |
    "pallas_interpret" | "jnp".

    The kernel where the decode kernel runs ("auto" asks
    `resolve_decode_impl` about this cache: a TPU, 128-token blocks, a
    bf16 cache; "pallas" is what the engine has made of "auto" by the
    time a program is traced, or an explicit choice) for a bucket of
    MLA_PREFILL_KERNEL_MIN_TOKENS or more; "pallas_interpret" runs it
    under the interpreter at every bucket (CPU tests); the jnp form on
    the CPU, block 16, float32 caches, an explicit "jnp" and the
    shorter buckets."""
    if impl == "auto":
        impl = resolve_decode_impl(impl, platform, block_size, plane_heights,
                                   cache_dtype)
    if impl == "pallas" and tokens < MLA_PREFILL_KERNEL_MIN_TOKENS:
        return "jnp"
    return impl


@jax.named_scope("dyn.kv_write")
def mla_write_rows(
    c_cache: jax.Array,
    kr_cache: jax.Array,
    layer: int,
    c: jax.Array,             # [S, T, R]  the rows' latents (normed)
    kr: jax.Array,            # [S, T, dr] and rope keys
    block_tables: jax.Array,  # [S, max_blocks]
    ctx_lens: jax.Array,      # [S] tokens cached before each row
    true_lens: jax.Array,     # [S] real tokens of each row
):
    """The rows' chunks into both pools as WHOLE planes in the resident
    layout: the cells `write_prompt_kv_batched` sets, set by
    `packed_prefill.write_packed_kv` over the padded rows laid end to
    end (a packed stream: a row a segment, its padding invalid).  For a
    cache whose reads are kernels: beside a custom call that reads the
    pool as it lies, the flat column scatter has XLA relay the whole
    pool to the scatter's layout and back every layer (1.5 ms a layer on
    Moonlight's 537 MB pool, PR 48)."""
    from .packed_prefill import write_packed_kv

    S, T = c.shape[:2]
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    rows = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None], (S, T))
    return write_packed_kv(
        c_cache, kr_cache, layer, c.reshape(S * T, 1, -1),
        kr.reshape(S * T, 1, -1), block_tables, rows.reshape(-1),
        (ctx_lens[:, None] + t).reshape(-1),
        (t < true_lens[:, None]).reshape(-1))


@jax.named_scope("dyn.attention")
def mla_prefill_flash(
    q_nope: jax.Array,        # [S, T, nh, dn]
    q_rope: jax.Array,        # [S, T, nh, dr]
    c_cache: jax.Array,       # the rows' chunks ALREADY written
    kr_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [S, max_blocks]
    ctx_lens: jax.Array,      # [S]
    true_lens: jax.Array,     # [S]
    w_uk: jax.Array,          # [nh, R, dn]
    w_uv: jax.Array,          # [nh, R, dv]
    *,
    mesh=None,
    interpret: bool = False,
) -> jax.Array:
    """`mla_prefill_attention` for every row of a program at once, as
    the kernel (pallas_mla_attention.mla_prefill_pallas): query t of row
    s attends its table's positions [0, ctx[s] + t].  Returns
    [S, T, nh, dv]; a row's padding returns 0.  Per head shard under
    `shard_map` where the mesh has a tp axis, like the decode read
    (`_mla_decode_pallas`): the pools are replicated and the heads
    shard through w_uk / w_uv."""
    from jax.sharding import PartitionSpec as P

    from .pallas_mla_attention import mla_prefill_pallas

    # traced, so the kernel (a jit of its own) is traced and lowered
    # once a program, not once a layer
    layer = jnp.int32(layer)
    if _tp(mesh) == 1:
        return mla_prefill_pallas(
            q_nope, q_rope, c_cache, kr_cache, layer, block_tables,
            ctx_lens, true_lens, w_uk, w_uv, interpret=interpret)
    # `kernel_tp_call` shards [tokens, heads, width]: the rows laid end
    # to end on the way in and out
    S, T = q_nope.shape[:2]
    flat = lambda x: x.reshape(S * T, *x.shape[2:])

    def local(qn, qr, cc, krc, tables, ctx, true, uk, uv):
        rows = lambda x: x.reshape(S, T, *x.shape[1:])
        return flat(mla_prefill_pallas(
            rows(qn), rows(qr), cc, krc, layer, tables, ctx, true, uk, uv,
            interpret=interpret))

    out = kernel_tp_call(
        mesh, local,
        [flat(q_nope), flat(q_rope), c_cache, kr_cache, block_tables,
         ctx_lens, true_lens, w_uk, w_uv],
        [P(None, "tp", None), P(None, "tp", None), P(), P(), P(None, None),
         P(None), P(None), P("tp", None, None), P("tp", None, None)])
    return out.reshape(S, T, *out.shape[1:])


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
