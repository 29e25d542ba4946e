"""Paged KV-cache attention ops.

The TPU replacement for the reference's only first-party GPU kernels
(lib/kvbm-kernels/cuda/tensor_kernels.cu — block gather/scatter) plus the
paged attention the reference delegates to vLLM/TRT-LLM.

Cache layout (per tensor): [n_layers, n_kv_heads, num_blocks, head_dim,
block_size] — HEAD-MAJOR with TRANSPOSED blocks.  Head-major: one
(head, block) slab is contiguous, so the Pallas decode kernel DMAs blocks
by physical id as whole planes, and the tp sharding over kv_heads
(parallel/mesh.py:kv_cache_spec) splits the cache into contiguous
per-shard slabs.  Transposed ([hd, bs] instead of [bs, hd]): block_size is
the TPU lane dimension, so with block_size a multiple of 128 the DMA slabs
are lane-aligned for ANY head_dim (64-dim models included) and the
kernel's two matmuls hit the MXU without in-kernel transposes.

Conventions:
  * physical block 0 is the GARBAGE block: inactive slots' writes land there
    and are never read; allocators hand out ids >= 1.
  * all shapes are static; sequence validity is carried by ctx_len/true_len
    scalars and enforced with masks, so XLA compiles one program per bucket.

These are the jnp reference implementations — numerically exact, fully
fused-able by XLA.  ops/pallas_paged_attention.py is the hand-tiled
Pallas decode kernel; the two are interchangeable and cross-checked in
tests/test_paged_attention.py.  `paged_attention_decode` dispatches
between them: "auto" is decided by `resolve_decode_impl` from what the
code can observe — the Pallas kernel on a TPU backend with lane-aligned
blocks, the jnp path elsewhere (CPU, block_size 16).  The jnp path
gathers the whole table width for every lane and ("jnp") upcasts it to
fp32; the kernel DMAs the live blocks in the cache's dtype.  One
layer-call on a v5e at Mistral-7B widths (device time, my chip run,
PR 28): 16 lanes x 20 blocks with 26 blocks live, jnp 1079 us, jnp_bf16
915 us, kernel 24-31 us; 6 lanes x 50 blocks with 204 live, 721 us
against 149 us.  "jnp_bf16" keeps matmul operands in the cache dtype
with fp32 accumulation; "jnp" upcasts to fp32 for exact test numerics.

Int8 KV quantization (quant/kv.py, engine `kv_cache_dtype="int8"`):
every write function takes optional `k_scale`/`v_scale` sibling arrays
[L, nkv, num_blocks, block_size] fp32 — when passed, the incoming K/V
quantize per (token, head) on the way into the cache and the scale
scatters with the same index math, and the function returns a 4-tuple.
EVERY read impl supports int8:

  * "jnp" / "jnp_bf16" — the int8 block gather is what
    streams from HBM; dequantization happens on the gathered context
    (`_gather_ctx`), upcast to fp32 ("jnp") or bf16 ("jnp_bf16", keeping
    the MXU operands 16-bit with fp32 accumulation).
  * "pallas" / "pallas_interpret" ("auto" on a TPU) — in-kernel
    dequant: the kernel DMAs int8 blocks plus their [nkv, bs] fp32 scale rows into VMEM and
    fuses the scale multiply into the chunk consume (query-dtype MXU
    operands, fp32 softmax/accumulate) — int8's halved HBM traffic
    happens inside the fast path (pallas_paged_attention.py docstring
    has the VMEM layout).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..quant.kv import quantize_tokens

NEG_INF = -1e30

# the decode dispatch's impl vocabulary — the single source of truth the
# engine's --attn-impl validation and CLI choices reference (a new impl
# added here is automatically accepted end-to-end)
DECODE_IMPLS = ("auto", "pallas", "pallas_interpret", "jnp", "jnp_bf16")


# ---------------------------------------------------------------------------
# cache writes (block scatter)
# ---------------------------------------------------------------------------


def _store_kv(k_cache, v_cache, layer, k, v, blocks, offsets,
              k_scale, v_scale):
    """Shared scatter tail for every write site: data at
    [layer, :, blocks, :, offsets] (advanced dims front — the target
    reads [T, nkv, hd], exactly the token-major layout k/v arrive in),
    and for an int8 cache the per-(token, head) fp32 scales at
    [layer, :, blocks, offsets] (target [T, nkv]) with the SAME
    blocks/offsets, so data and scale can never disagree on placement.
    Returns the cache tuple in the caller's arity."""
    if k_scale is not None:
        k, ks = quantize_tokens(k)
        v, vs = quantize_tokens(v)
        k_scale = k_scale.at[layer, :, blocks, offsets].set(ks, mode="drop")
        v_scale = v_scale.at[layer, :, blocks, offsets].set(vs, mode="drop")
    k_cache = k_cache.at[layer, :, blocks, :, offsets].set(
        k.astype(k_cache.dtype), mode="drop"
    )
    v_cache = v_cache.at[layer, :, blocks, :, offsets].set(
        v.astype(v_cache.dtype), mode="drop"
    )
    if k_scale is not None:
        return k_cache, v_cache, k_scale, v_scale
    return k_cache, v_cache


@jax.named_scope("dyn.kv_write")
def write_prompt_kv(
    k_cache: jax.Array,  # [L, nkv, nblocks, hd, bs]
    v_cache: jax.Array,
    layer: int,
    k: jax.Array,        # [T, nkv, hd] new tokens' keys
    v: jax.Array,
    block_table: jax.Array,  # [max_blocks] int32
    ctx_len: jax.Array,      # scalar: tokens already in cache
    true_len: jax.Array,     # scalar: valid entries of k/v
    k_scale: jax.Array = None,  # [L, nkv, nblocks, bs] fp32 (int8 cache)
    v_scale: jax.Array = None,
) -> Tuple[jax.Array, ...]:
    T = k.shape[0]
    bs = k_cache.shape[4]
    pos = ctx_len + jnp.arange(T, dtype=jnp.int32)  # absolute positions
    blocks = block_table[pos // bs]                 # [T]
    offsets = pos % bs
    valid = jnp.arange(T) < true_len
    # invalid rows scatter to the garbage block
    blocks = jnp.where(valid, blocks, 0)
    return _store_kv(k_cache, v_cache, layer, k, v, blocks, offsets,
                     k_scale, v_scale)


@jax.named_scope("dyn.kv_write")
def write_prompt_kv_batched(
    k_cache: jax.Array,       # [L, nkv, nblocks, hd, bs]
    v_cache: jax.Array,
    layer: int,
    k: jax.Array,             # [Bp, T, nkv, hd] chunk keys per sequence
    v: jax.Array,
    block_tables: jax.Array,  # [Bp, max_blocks] int32
    ctx_lens: jax.Array,      # [Bp] tokens already in cache per sequence
    true_lens: jax.Array,     # [Bp] valid entries of each row of k/v
    k_scale: jax.Array = None,  # [L, nkv, nblocks, bs] fp32 (int8 cache)
    v_scale: jax.Array = None,
) -> Tuple[jax.Array, ...]:
    """Multi-sequence chunk scatter: Bp sequences' prefill chunks written in
    one flat scatter (sequences own disjoint blocks, so rows never collide;
    invalid/padding rows land in the garbage block)."""
    Bp, T = k.shape[:2]
    bs = k_cache.shape[4]
    pos = ctx_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    blocks = jnp.take_along_axis(block_tables, pos // bs, axis=1)  # [Bp, T]
    offsets = pos % bs
    valid = jnp.arange(T)[None, :] < true_lens[:, None]
    blocks = jnp.where(valid, blocks, 0)
    bf = blocks.reshape(-1)
    of = offsets.reshape(-1)
    kf = k.reshape(Bp * T, *k.shape[2:])
    vf = v.reshape(Bp * T, *v.shape[2:])
    return _store_kv(k_cache, v_cache, layer, kf, vf, bf, of,
                     k_scale, v_scale)


# dynlint: disable=DYN001 op-level jit: reached only inside the engine's watched decode programs; `layer` is traced, so one trace serves every layer of a program
@jax.jit
def _store_columns(caches, layer, xs, blocks, offsets, valid):
    """Write each valid lane's column — xs[j][b] ([nkv, hd], or [nkv]
    for a scale plane) into column offsets[b] of block blocks[b] of
    caches[j] — one lane at a time, as a read-modify-write of that
    block's whole [nkv, hd, bs] planes: the pools keep the layout they
    are resident in.  A column alone (the flat scatter of `_store_kv`,
    or a `dynamic_update_slice` of one column) makes XLA's TPU compiler
    lay the pool out with the updated window (hd, nkv) minor-most,
    {3,1,4,2,0}; next to a reader that needs the resident {4,3,2,1,0}
    (the Pallas decode kernel DMAs [hd, bs] planes) that is a copy of
    the whole pool per layer per step (compiled for a described v5e,
    PR 28).  In the resident layout a token's column is spread over
    every tile of its planes, so whole planes are what any writer has
    to move: 4 us a lane and tensor on a v5e (my chip run, PR 28), so
    the loop runs over the valid lanes only.  Its sibling for a packed
    prefill stream, where a block's plane takes up to bs tokens at
    once, is packed_prefill._store_planes (PR 30)."""
    bs = caches[0].shape[-1]
    zero = jnp.int32(0)
    lanes = jnp.argsort(~valid)   # stable: the valid lanes first
    col = jnp.arange(bs, dtype=jnp.int32)

    def body(i, cs):
        b = lanes[i]
        out = []
        for c, x in zip(cs, xs):
            at = (layer, zero, blocks[b]) + (zero,) * (c.ndim - 3)
            plane = jax.lax.dynamic_slice(
                c, at, (1, c.shape[1], 1) + c.shape[3:])
            new = x[b].astype(c.dtype)[None, :, None, ..., None]
            out.append(jax.lax.dynamic_update_slice(
                c, jnp.where(col == offsets[b], new, plane), at))
        return tuple(out)

    return jax.lax.fori_loop(0, jnp.sum(valid, dtype=jnp.int32), body,
                             tuple(caches))


@jax.named_scope("dyn.kv_write")
def write_token_kv(
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    k: jax.Array,            # [B, nkv, hd]
    v: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks]
    ctx_lens: jax.Array,      # [B] position to write (== current length)
    k_scale: jax.Array = None,  # [L, nkv, nblocks, bs] fp32 (int8 cache)
    v_scale: jax.Array = None,
    resident: bool = False,
    valid: jax.Array = None,    # [B] bool, read by the resident write
) -> Tuple[jax.Array, ...]:
    """`resident=True` writes lane by lane in the pool's resident layout
    (`_store_columns`), and only the lanes `valid` marks (None: all):
    what the decode step uses beside the Pallas kernel.  False keeps the
    one flat scatter the XLA gather path is laid out for, idle lanes
    landing in the garbage block.  A valid lane's cells get the same
    values either way."""
    bs = k_cache.shape[4]
    B = k.shape[0]
    blocks = block_tables[jnp.arange(B), ctx_lens // bs]  # [B]
    offsets = ctx_lens % bs
    if not resident:
        return _store_kv(k_cache, v_cache, layer, k, v, blocks, offsets,
                         k_scale, v_scale)
    caches, xs = (k_cache, v_cache), (k, v)
    if k_scale is not None:
        k, ks = quantize_tokens(k)
        v, vs = quantize_tokens(v)
        caches, xs = caches + (k_scale, v_scale), (k, v, ks, vs)
    if valid is None:
        valid = jnp.ones((B,), bool)
    return _store_columns(caches, jnp.int32(layer), xs, blocks, offsets,
                          valid)


# ---------------------------------------------------------------------------
# attention reads
# ---------------------------------------------------------------------------


def _gather_ctx(cache: jax.Array, layer: int, block_table: jax.Array,
                scale: jax.Array = None, dtype=None) -> jax.Array:
    """[L,nkv,nb,hd,bs] + [max_blocks] -> [nkv, max_blocks*bs, hd].

    `scale` [L, nkv, nb, bs] dequantizes an int8 cache on the gathered
    context (quant/kv.py): the int8 gather is what streams from HBM;
    the upcast target is `dtype` (bf16 for the jnp_bf16 fast path) or
    fp32 when unset."""
    g = cache[layer][:, block_table]  # [nkv, max_blocks, hd, bs]
    nkv, mb, hd, bs = g.shape
    g = g.swapaxes(2, 3).reshape(nkv, mb * bs, hd)
    if scale is not None:
        s = scale[layer][:, block_table].reshape(nkv, mb * bs)
        g = g.astype(jnp.float32) * s[..., None]
        if dtype is not None:
            g = g.astype(dtype)
    return g


def _gqa_scores(q: jax.Array, k: jax.Array,
                native_dtype: bool = False) -> jax.Array:
    """q [.., nh, hd] x k [nkv, S, hd] -> scores [.., nh, S] with GQA.

    native_dtype=True feeds the MXU the storage dtype (bf16) with fp32
    accumulation instead of upcasting operands — the decode fast path."""
    nh = q.shape[-2]
    nkv = k.shape[0]
    group = nh // nkv
    qg = q.reshape(*q.shape[:-2], nkv, group, q.shape[-1])
    if native_dtype:
        return jnp.einsum(
            "...kgh,ksh->...kgs", qg, k,
            preferred_element_type=jnp.float32,
        ).reshape(*q.shape[:-2], nh, k.shape[1])
    s = jnp.einsum("...kgh,ksh->...kgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s.reshape(*q.shape[:-2], nh, k.shape[1])


def _gqa_out(p: jax.Array, v: jax.Array,
             native_dtype: bool = False) -> jax.Array:
    """p [.., nh, S] x v [nkv, S, hd] -> out [.., nh, hd]."""
    nh = p.shape[-2]
    nkv = v.shape[0]
    group = nh // nkv
    pg = p.reshape(*p.shape[:-2], nkv, group, p.shape[-1])
    if native_dtype:
        o = jnp.einsum("...kgs,ksh->...kgh", pg.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("...kgs,ksh->...kgh", pg, v.astype(jnp.float32))
    return o.reshape(*p.shape[:-2], nh, v.shape[-1])


@jax.named_scope("dyn.attention")
def paged_prefill_attention(
    q: jax.Array,        # [T, nh, hd] (rope applied)
    k: jax.Array,        # [T, nkv, hd] this chunk's keys
    v: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_table: jax.Array,
    ctx_len: jax.Array,   # cached tokens this chunk attends to
    true_len: jax.Array,  # valid tokens in the chunk
    k_scale: jax.Array = None,  # int8 cache: dequant scales (quant/kv.py)
    v_scale: jax.Array = None,
) -> jax.Array:
    """Chunk tokens attend to (cached context) ++ (chunk, causally).

    One code path serves plain prefill (ctx_len=0), prefix-cache hits and
    chunked prefill (ctx_len>0) — the unified form that lets the engine reuse
    blocks the router already counted as overlap.  The chunk's own K/V
    attend at full precision (they arrive fresh from the projection);
    only the cached context dequantizes on an int8 cache.
    """
    T, nh, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    k_ctx = _gather_ctx(k_cache, layer, block_table, k_scale)  # [nkv,S,hd]
    v_ctx = _gather_ctx(v_cache, layer, block_table, v_scale)
    S = k_ctx.shape[1]
    k_hm = k.swapaxes(0, 1)  # head-major [nkv, T, hd]
    v_hm = v.swapaxes(0, 1)

    s_ctx = _gqa_scores(q, k_ctx) * scale            # [T, nh, S]
    ctx_mask = (jnp.arange(S) < ctx_len)[None, None, :]
    s_ctx = jnp.where(ctx_mask, s_ctx, NEG_INF)

    s_self = _gqa_scores(q, k_hm) * scale            # [T, nh, T]
    i = jnp.arange(T)[:, None, None]
    j = jnp.arange(T)[None, None, :]
    causal = (j <= i) & (j < true_len)
    s_self = jnp.where(causal, s_self, NEG_INF)

    s = jnp.concatenate([s_ctx, s_self], axis=-1)    # [T, nh, S+T]
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_out(p[..., :S], v_ctx) + _gqa_out(p[..., S:], v_hm)
    return out.astype(q.dtype)


def paged_attention_decode_jnp(
    q: jax.Array,            # [B, nh, hd]
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [B, max_blocks]
    kv_lens: jax.Array,       # [B] valid tokens (incl. the one just written)
    native_dtype: bool = False,
    k_scale: jax.Array = None,  # int8 cache: dequant scales (quant/kv.py)
    v_scale: jax.Array = None,
    kv_lo: jax.Array = None,    # [B] first table position a lane attends
) -> jax.Array:
    """XLA path: the block gather feeds the einsums directly (fused by
    XLA — no explicit DMA kernel).  native_dtype=True keeps matmul
    operands in the cache dtype (bf16) with fp32 accumulation; False
    upcasts to fp32 (exact reference numerics for tests).  An int8 cache
    dequantizes on the gather — to bf16 under native_dtype (operands
    stay 16-bit for the MXU), else to fp32."""
    B, nh, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    deq_dtype = jnp.bfloat16 if native_dtype else None

    def one(qb, table, kvlen, lo=None):
        kb = _gather_ctx(k_cache, layer, table, k_scale, deq_dtype)
        vb = _gather_ctx(v_cache, layer, table, v_scale, deq_dtype)
        s = _gqa_scores(qb, kb, native_dtype) * scale   # [nh, S]
        at = jnp.arange(kb.shape[1])
        mask = (at < kvlen)[None, :]
        if lo is not None:
            mask = mask & (at >= lo)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_out(p, vb, native_dtype)     # [nh, hd]

    bounds = () if kv_lo is None else (kv_lo,)
    out = jax.vmap(one)(q, block_tables, kv_lens, *bounds)
    return out.astype(q.dtype)


def kernel_tp_call(mesh, local, args, specs, k_scale=None, v_scale=None):
    """shard_map scaffolding shared by the Pallas decode and
    packed-prefill kernels under tensor parallelism.

    The kernels are custom calls GSPMD cannot partition (left alone,
    XLA all-gathers the whole kv_heads-sharded cache per layer per
    step — the exact fallback this replaces).  Under shard_map each tp
    shard runs `local` on its LOCAL kv-head slice; GQA head grouping
    is kv-major and contiguous, so a kv head's entire query group
    lives on the same shard and the op needs zero cross-shard
    communication — the row-parallel wo matmul downstream performs the
    usual psum.  An int8 cache's scale planes shard with the cache
    (kv_heads over tp, parallel/mesh.py kv_scale_spec) so each shard
    dequantizes its own slab in-kernel; when scales are passed they
    are appended to `args` and `local` receives them as its trailing
    *scales.  Everything left unmentioned in a spec is replicated
    (tables/lengths/stream metadata — the engine's host-array
    inputs)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    args = list(args)
    specs = list(specs)
    if k_scale is not None:
        args += [k_scale, v_scale]
        specs += [P(None, "tp", None, None), P(None, "tp", None, None)]
    return shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=P(None, "tp", None),
        # pallas_call's out_shape carries no varying-mesh-axes annotation,
        # so the vma checker cannot see through it
        check_vma=False,
    )(*args)


def _decode_pallas_tp(q, k_cache, v_cache, layer, block_tables, kv_lens,
                      *, mesh, interpret, k_scale=None, v_scale=None):
    """Pallas decode under tensor parallelism (kernel_tp_call)."""
    from jax.sharding import PartitionSpec as P

    from .pallas_paged_attention import paged_attention_decode_pallas

    quantized = k_scale is not None

    def local(q, kc, vc, tables, lens, *scales):
        ks, vs = scales if quantized else (None, None)
        return paged_attention_decode_pallas(
            q, kc, vc, layer, tables, lens, interpret=interpret,
            k_scale=ks, v_scale=vs,
        )

    return kernel_tp_call(
        mesh, local,
        [q, k_cache, v_cache, block_tables, kv_lens],
        [P(None, "tp", None), P(None, "tp", None, None, None),
         P(None, "tp", None, None, None), P(None, None), P(None)],
        k_scale=k_scale, v_scale=v_scale,
    )


PALLAS_IMPLS = ("pallas", "pallas_interpret")


def resolve_decode_impl(impl: str, platform: str, block_size: int,
                        head_dim, cache_dtype) -> str:
    """What `impl` means for this cache on this platform: the one place
    "auto" is decided, from what the code can observe.  An explicit impl
    is returned as given.

    "auto" is the Pallas kernel where it can run as written — a TPU
    backend, block_size a multiple of 128 (the lane dimension of the
    [hd, bs] block planes it DMAs), head_dim a whole number of sublane
    tiles for the cache dtype (16 rows bf16, 32 int8), a bf16 or int8
    cache — and the jnp path everywhere else (CPU, block_size 16, fp32
    caches).  `head_dim` is the plane's height, or a tuple of them where
    the cache's members differ (an MLA cache's latent and rope key,
    ops/mla_attention.py): each has to be whole tiles.  The kernel moves the live context's bytes in the cache's
    dtype straight from the pool; the jnp path gathers lanes x table
    width and upcasts to fp32.  On `mistral-7b.chat` (16 lanes x 20
    blocks, ~25 blocks live) that is a decode step of 31.7 ms with jnp
    (ledger, PR 27) against 10.55 ms with the kernel (my chip run,
    PR 28; PERF.md section 6)."""
    if impl != "auto":
        return impl
    dt = jnp.dtype(cache_dtype)
    heights = head_dim if isinstance(head_dim, tuple) else (head_dim,)
    if (platform == "tpu" and block_size % 128 == 0
            and dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.int8))
            and all(h % (32 // dt.itemsize) == 0 for h in heights)):
        return "pallas"
    return "jnp"


@jax.named_scope("dyn.attention")
def paged_attention_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    impl: str = "auto",
    mesh=None,
    k_scale: jax.Array = None,
    v_scale: jax.Array = None,
    kv_lo: jax.Array = None,
) -> jax.Array:
    """Single-token batched paged attention (the decode hot loop).

    impl: "auto" (`resolve_decode_impl` on the default backend: the
    Pallas kernel on a TPU, the jnp path elsewhere), "pallas",
    "pallas_interpret" (kernel under the interpreter — CPU testing),
    "jnp" (fp32-upcast operands: exact reference numerics for tests), or
    "jnp_bf16" (operands stay in the cache dtype, fp32 accumulation).

    kv_lens: valid positions per lane including the token just written;
    0 marks a lane with nothing to attend (the kernel reads nothing for
    it and returns 0; the jnp path returns a finite, unused average).

    mesh: required for the Pallas path when the kv cache is tensor-parallel
    (kv_heads sharded over a "tp" axis) — the kernel then runs under
    shard_map per shard.  Without a mesh, the kernel under tp>1 would hit
    GSPMD's unpartitionable-custom-call all-gather, so callers serving
    multi-chip must pass their mesh (the engine does).

    k_scale/v_scale: an int8 cache's dequant scales (quant/kv.py).
    Every impl consumes them natively — the jnp paths dequantize on
    the gather, the Pallas kernel DMAs int8 blocks + scale rows and
    fuses the multiply in VMEM (module docstring's support matrix).

    kv_lo: [B] a LOWER bound beside kv_lens: a lane attends the table's
    positions kv_lo <= pos < kv_lens.  What a window layer's ring needs
    when it is handed over as a table, oldest live block first
    (ops/window_attention.ring_decode_table): the cells of that block
    that have left the window are masked.  Both impls take it; without
    it every caller's program is the one it was.  Not carried under tp.
    """
    tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
    impl = resolve_decode_impl(impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype)
    if impl in PALLAS_IMPLS:
        interpret = impl == "pallas_interpret"
        if tp > 1:
            if kv_lo is not None:
                raise NotImplementedError("kv_lo under tp > 1")
            return _decode_pallas_tp(
                q, k_cache, v_cache, layer, block_tables, kv_lens,
                mesh=mesh, interpret=interpret,
                k_scale=k_scale, v_scale=v_scale,
            )
        from .pallas_paged_attention import paged_attention_decode_pallas

        return paged_attention_decode_pallas(
            q, k_cache, v_cache, layer, block_tables, kv_lens,
            interpret=interpret, k_scale=k_scale, v_scale=v_scale,
            kv_lo=kv_lo,
        )
    if impl not in ("jnp", "jnp_bf16"):
        raise ValueError(
            f"unknown attention impl {impl!r}; expected "
            + " | ".join(DECODE_IMPLS)
        )
    return paged_attention_decode_jnp(
        q, k_cache, v_cache, layer, block_tables, kv_lens,
        native_dtype=(impl == "jnp_bf16"),
        k_scale=k_scale, v_scale=v_scale, kv_lo=kv_lo,
    )
