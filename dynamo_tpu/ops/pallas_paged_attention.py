"""Pallas TPU paged-attention decode kernel.

The hand-tiled fast path for the decode hot loop — the TPU counterpart of
the reference's only first-party GPU kernels (the block gather/copy family
in lib/kvbm-kernels/cuda/tensor_kernels.cu:151,192,494): where the CUDA
kernels permute paged blocks through a universal layout, on TPU the same
block-gather problem is fused INTO attention — each sequence's scattered
KV blocks are DMA'd from HBM into VMEM by physical block id and consumed
by an online-softmax accumulation without ever materializing a gathered
context tensor in HBM (which is what the jnp fallback in
paged_attention.py makes XLA do — double traffic through HBM).

Layout: the cache stores TRANSPOSED blocks, [n_kv, num_blocks, head_dim,
block_size] per layer (paged_attention.py docstring).  block_size is the
lane dimension, so with block_size a multiple of 128:
  * every per-block DMA ([nkv, hd, bs] — one strided descriptor covering
    all heads) is lane-aligned for ANY head_dim;
  * scores q[g,hd] @ k[hd,S] and the p@v contraction are MXU-shaped with
    no in-kernel reshapes or lane-splits.

Structure:
  * grid = (batch,), sequential; block tables, kv lengths and the chunk
    chain's planes (each row's slot phase and successor) ride scalar
    prefetch (SMEM).
  * the pools go in WHOLE, [L, nkv, num_blocks, hd, bs] in HBM in their
    resident layout, and the DMA descriptor indexes layer and block: a
    slice taken outside the kernel is materialized by XLA for a custom
    call (one layer's pool copied per layer per step).
  * KV is consumed in chunks of up to `bpc` physical blocks DMA'd into
    [nkv, hd, S=bpc*bs] VMEM buffers, double-buffered; only the blocks
    that hold live positions are copied, and a lane with kv_len 0 has
    no chunk at all.  The prefetch chain CROSSES grid steps (the last
    chunk of a sequence prefetches chunk 0 of the next sequence that
    has one) — the DMA engines never drain between sequences.
  * compute per chunk is TWO batched bf16 dot_generals with fp32
    accumulation ([nkv, g, hd] @ [nkv, hd, S] and the p@v contraction)
    plus one online-softmax update on [nkv, g, S].

Measured on a v5e at Mistral-7B widths, one layer-call, device time (my
chip run, PR 28): 26 live blocks (13.6 MB) over 6 of 16 lanes in
24-31 us (435 GB/s and up); 204 live blocks (107 MB) over 6 lanes in
149 us (718 GB/s of the chip's 819).  The same kernel as it stood
before PR 28 (layer sliced outside, every chunk whole, idle lanes
reading the garbage block): 604 us and 675 us.

Int8 KV caches (quant/kv.py) are consumed natively: alongside each
[nkv, hd, bs] int8 block the kernel DMAs the block's [nkv, bs] fp32
scale row (the per-position scale planes that ride the cache as
sibling arrays) into [2, nkv, S] VMEM buffers on two extra semaphore
lanes, and the chunk consume fuses the dequantizing multiply —
int8 elements stream from HBM (half the bandwidth of bf16, +4 bytes
per position of scale), the MXU sees query-dtype operands (bf16 on
the serving path), softmax/accumulation stay fp32.  This is what lets
quantization's bandwidth win compound with the fast attention path
instead of routing around it (the pre-PR-12 jnp-gather fallback).

Padded table entries point at physical block 0 (the garbage block) and
are masked by position, so shapes stay static.  Numerics match
paged_attention.paged_attention_decode_jnp to bf16 matmul tolerance
(fp32 softmax and accumulation); tests/test_paged_attention.py and
tests/test_packed_pallas.py cross-check the two (int8 included) in
interpret mode on the CPU; tests/test_tpu_compile.py compiles the kernel
for a described v5e, and chip_smoke.py checks the compiled kernel
against the jnp path on the chip.

`make_chunk_dma`, `make_chunk_chain` and `chunk_chain_planes` are the
chunk contract's one definition site; two kernels stand on it: this one
and the latent decode kernel of the MLA families
(pallas_mla_attention.py: the same chain over a latent and a rope-key
pool, other matmuls).  (The packed-prefill kernel left it in PR 34 for
BlockSpec-pipelined tiles.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def make_chunk_dma(tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem, *,
                   bpc, bs, ks_hbm=None, vs_hbm=None, ks_buf=None,
                   vs_buf=None, layer=None, live_blocks=None):
    """The chunk DMA contract shared by the decode kernels (this file's
    and pallas_mla_attention.py's, where k/v are the latent and the rope
    key, nkv = 1): (start, wait) closures moving up to `bpc` physical blocks
    into a double-buffered VMEM chunk — one strided descriptor per block
    per tensor ([nkv, hd, bs], all heads, landing at the block's offset
    in the chunk buffer), and for an int8 cache the block's [nkv, bs]
    fp32 scale rows on two extra semaphore lanes (`sem` is [slots, 2]
    bf16 / [slots, 4] int8).  Both closures take (row, c, slot) where
    `row` indexes tables_ref's first axis (the sequence).  One
    definition site keeps the two kernels' DMA contracts — descriptor
    shapes, semaphore pairing, scale lanes — from drifting.

    `layer` (a scalar, static or read from SMEM): the HBM refs are the
    WHOLE pools [L, nkv, nb, hd, bs] (scales [L, nkv, nb, bs]) and the
    descriptor indexes the layer, so the caller never slices the pool
    outside the kernel (XLA materializes such a slice for a custom
    call: a copy of one layer's pool per call).  None: the refs are one
    layer's [nkv, nb, hd, bs].

    `live_blocks(row, c)` -> traced count of chunk c's blocks that hold
    live positions: block i is copied (and waited on) only when
    i < live, so a chunk moves the bytes of its live blocks and the
    rest of the buffer keeps what it held (the consumer masks those
    positions and must keep them finite).  Block 0 of a chunk that is
    fetched at all is always live.  None: all `bpc` blocks."""
    quantized = ks_hbm is not None

    def src(hbm, pid):
        return hbm.at[:, pid] if layer is None else hbm.at[layer, :, pid]

    def _each(row, c, slot, op):
        live = None if live_blocks is None else live_blocks(row, c)
        for i in range(bpc):
            def block(i=i):
                pid = tables_ref[row, c * bpc + i]
                op(pltpu.make_async_copy(
                    src(k_hbm, pid),
                    k_buf.at[slot, :, :, pl.ds(i * bs, bs)],
                    sem.at[slot, 0]))
                op(pltpu.make_async_copy(
                    src(v_hbm, pid),
                    v_buf.at[slot, :, :, pl.ds(i * bs, bs)],
                    sem.at[slot, 1]))
                if quantized:
                    op(pltpu.make_async_copy(
                        src(ks_hbm, pid),
                        ks_buf.at[slot, :, pl.ds(i * bs, bs)],
                        sem.at[slot, 2]))
                    op(pltpu.make_async_copy(
                        src(vs_hbm, pid),
                        vs_buf.at[slot, :, pl.ds(i * bs, bs)],
                        sem.at[slot, 3]))

            if live is None or i == 0:
                block()
            else:
                pl.when(i < live)(block)

    def start(row, c, slot):
        _each(row, c, slot, lambda dma: dma.start())

    def wait(row, c, slot):
        _each(row, c, slot, lambda dma: dma.wait())

    return start, wait


def make_chunk_chain(start_chunk, wait_chunk):
    """Global never-drain slot phase over a make_chunk_dma pair — the
    scheme both kernels share: every chunk fetched anywhere in the
    launch occupies one position `base + c` in a single global phase
    sequence, its VMEM slot is `(base + c) % 2`, and each chunk's
    consume loop prefetches the NEXT phase's chunk (this row's next
    chunk, or chunk 0 of `next_row` — the next active row, possibly in
    a later grid step) into the opposite slot before waiting on its
    own.  Only the launch's globally first fetch (`base == 0`) is ever
    un-overlapped; the DMA engines never drain across sequence, tile,
    or segment boundaries.

    `prime(row, nch, base)` issues that first fetch; `step(row, c, nch,
    base, next_row)` runs inside the chunk loop and returns the slot
    holding chunk `c` (next_row < 0 = nothing left to prefetch).  The
    caller supplies `base` (chunks consumed by all earlier rows) and
    `next_row`, both scalar-prefetch planes that `chunk_chain_planes`
    makes from kv_lens; the double-buffer
    safety argument is program order: phase p+1's slot was last read by
    phase p-1's consume, which completes before p's loop iteration
    issues p+1."""

    def prime(row, nch, base):
        @pl.when((nch > 0) & (base == 0))
        def _():
            start_chunk(row, 0, 0)

    def step(row, c, nch, base, next_row):
        slot = jax.lax.rem(base + c, 2)
        nxt = jax.lax.rem(base + c + 1, 2)

        # prefetch BEFORE waiting: next chunk of this row, or chunk 0
        # of the next active row (the cross-boundary chain)
        @pl.when(c + 1 < nch)
        def _():
            start_chunk(row, c + 1, nxt)

        @pl.when((c + 1 == nch) & (next_row >= 0))
        def _():
            start_chunk(next_row, 0, nxt)

        wait_chunk(row, c, slot)
        return slot

    return prime, step


def chunk_chain_planes(block_tables, kv_lens, bpc: int, bs: int):
    """What a decode launch hands make_chunk_dma / make_chunk_chain by
    scalar prefetch, from [B, max_blocks] tables and [B] lengths (valid
    positions incl. the current token; 0 = a lane with no chunk):
    (tables padded to whole chunks of `bpc` blocks, lengths clipped to
    the table, each row's slot phase, each row's successor)."""
    B, max_blocks = block_tables.shape
    pad = -max_blocks % bpc
    if pad:
        # padded entries are past every live position: never copied
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    S = bpc * bs
    # a lane never reads past its table, whatever length it claims
    kv_lens = jnp.clip(kv_lens, 0, max_blocks * bs).astype(jnp.int32)
    # the chunk chain's planes (make_chunk_chain): each row's slot phase
    # (chunks of all earlier rows) and its successor, the next row with
    # a chunk (suffix-min over row indices, -1 past the last)
    nch = -(-kv_lens // S)
    base = (jnp.cumsum(nch) - nch).astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    suf = jax.lax.cummin(jnp.where(nch > 0, rows, B)[::-1])[::-1]
    nxt = jnp.concatenate([suf[1:], jnp.full((1,), B, jnp.int32)])
    next_row = jnp.where(nxt < B, nxt, -1).astype(jnp.int32)
    return block_tables, kv_lens, base, next_row


def _decode_kernel(
    # scalar prefetch
    tables_ref,   # [B, n_chunks * bpc] int32 physical block ids
    kv_lens_ref,  # [B] int32 valid positions (incl. current token); 0 =
                  #   a lane with nothing to attend (no chunk, output 0)
    base_ref,     # [B] int32 chunks consumed by all earlier rows
    next_ref,     # [B] int32 next row with a chunk (-1 = none)
    layer_ref,    # [1] int32 the layer of the pool this call reads
    # `bounded` adds lo_ref [B] int32 as the LAST scalar prefetch: a
    # lane attends positions lo <= pos < kv_len of its table (a ring
    # handed over oldest block first: ops/window_attention.py).  Then
    # the inputs:
    #   q_ref   [1, nkv, group, hd] VMEM (this sequence's query)
    #   k_hbm   [L, nkv, num_blocks, hd, bs] ANY: the WHOLE pool,
    #   v_hbm     in HBM; the DMA descriptor picks layer and block
    #             (V may be another width: [.., hdv, bs], out hdv)
    # int8 caches add (ks_hbm, vs_hbm) [L, nkv, num_blocks, bs] fp32
    # ANY; `biased` adds bias_ref [1, n_chunks, 1, S] fp32 VMEM (this
    # sequence's per-position addend to the scores); then: o_ref
    # [1, nkv, group, hd] VMEM; scratch k_buf/v_buf
    # [2, nkv, hd, S] VMEM (+ks_buf/vs_buf [2, nkv, S] fp32), DMA
    # semaphores [2 slots, 2 (k/v) or 4 (+scales)]
    *rest,
    bpc: int,
    bs: int,
    quantized: bool = False,
    biased: bool = False,
    bounded: bool = False,
    debug_mode: str = "",  # "" | "dma_only" | "compute_only" (profiling)
):
    lo_ref = None
    if bounded:
        lo_ref, rest = rest[0], rest[1:]
    q_ref, k_hbm, v_hbm, *rest = rest
    bias_ref = None
    if biased:       # the last input, after the scales
        at = 2 if quantized else 0
        bias_ref, rest = rest[at], rest[:at] + rest[at + 1:]
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem) = rest
    else:
        (o_ref, k_buf, v_buf, sem) = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b = pl.program_id(0)
    nkv = k_hbm.shape[1]
    hdv = v_hbm.shape[3]
    S = bpc * bs  # positions per chunk
    kv_len = kv_lens_ref[b]
    kv_lo = lo_ref[b] if bounded else None
    n_chunks = pl.cdiv(kv_len, S)

    # the chunk DMA contract (descriptor shapes, semaphore pairing, int8
    # scale lanes) is shared with the latent decode kernel; here a
    # chunk moves only the blocks that hold live positions
    start_chunk, wait_chunk = make_chunk_dma(
        tables_ref, k_hbm, v_hbm, k_buf, v_buf, sem, bpc=bpc, bs=bs,
        ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf, vs_buf=vs_buf,
        layer=layer_ref[0],
        live_blocks=lambda row, c: pl.cdiv(kv_lens_ref[row] - c * S, bs))
    prime, chain_step = make_chunk_chain(start_chunk, wait_chunk)

    # Blocks past a chunk's live ones are never copied, so those lanes
    # of the buffers keep what they held: masked scores never read K,
    # but p (exactly 0 there) still multiplies V, and 0 * NaN is NaN.
    # Zero V (and its scales) once per launch, before the first DMA;
    # afterwards the buffers only ever hold cache data, which is finite.
    @pl.when(b == 0)
    def _():
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        if quantized:
            vs_buf[...] = jnp.zeros(vs_buf.shape, vs_buf.dtype)

    # slot phase = chunks consumed by earlier rows, and the next row
    # that has a chunk (both precomputed from kv_lens by the wrapper):
    # the launch's first active row primes the pipeline; afterwards
    # chunk 0 of a row was prefetched by the previous active row's last
    # chunk and the DMA chain never drains between sequences.  A row
    # with kv_len 0 has no chunk: it starts, waits on and reads nothing.
    base = base_ref[b]
    prime(b, n_chunks, base)
    next_row = next_ref[b]
    q = q_ref[0]     # [nkv, g, hd] bf16, pre-scaled
    g = q.shape[1]

    def body(c, carry):
        m, l, acc = carry
        if debug_mode == "compute_only":
            # profiling: every sequence reduces the primed buffer 0 (only
            # b==0/c==0 may wait — nothing ever signals the other grid
            # steps' semaphores, so waiting there would deadlock)
            slot = jnp.int32(0)

            @pl.when((c == 0) & (b == 0))
            def _():
                wait_chunk(0, 0, slot)
        else:
            slot = chain_step(b, c, n_chunks, base, next_row)
        if debug_mode == "dma_only":
            acc = acc + jnp.max(k_buf[slot].astype(jnp.float32)) \
                + jnp.max(v_buf[slot].astype(jnp.float32))
            return m, l, acc

        # scores [nkv, g, S]: ONE batched bf16 matmul for the whole chunk
        k = k_buf[slot]  # [nkv, hd, S]
        v = v_buf[slot]
        if quantized:
            # fused dequant on the chunk consume: int8 streamed from
            # HBM (half the traffic), per-position fp32 scale multiply
            # in VMEM, operands cast to the query dtype for the MXU
            # (bf16 on the serving path) with fp32 accumulation below
            k = (k.astype(jnp.float32)
                 * ks_buf[slot][:, None, :]).astype(q.dtype)
            v = (v.astype(jnp.float32)
                 * vs_buf[slot][:, None, :]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if biased:
            bias = bias_ref[0, c][None]                    # [1, 1, S]
            if g > 8 and g % 8 == 0:
                # the TPU compiler aborts on this add where a KV head's
                # group passes one float32 tile of sublanes (16 heads a
                # KV head: ops/block_sparse_attention.py; compiled for a
                # described v5e, PR 47): a tile of heads at a time
                s = jnp.concatenate([s[:, i:i + 8] + bias
                                     for i in range(0, g, 8)], axis=1)
            else:
                s = s + bias
        pos = c * S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        live = pos < kv_len
        if bounded:
            live = live & (pos >= kv_lo)
        s = jnp.where(live, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if bounded:
            # a chunk wholly under the bound leaves m_new at NEG_INF and
            # exp(0) = 1 for every pair that is out: zero them
            p = jnp.where(live, p, 0.0)
        l = l * alpha + jnp.sum(p, axis=2, keepdims=True)
        # out [nkv, g, hd]: p is cast to the operand dtype for the MXU
        # (standard flash practice; fp32 running accumulation keeps the
        # precision).  `v` is the dequantized chunk on an int8 cache.
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc = acc * alpha + pv
        return m_new, l, acc

    m0 = jnp.full((nkv, g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nkv, g, 1), jnp.float32)
    a0 = jnp.zeros((nkv, g, hdv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    # a row with no chunk has l == 0: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    # dynlint: disable=DYN001 kernel-level jit: engine dispatch reaches this inside already-watched programs; direct calls are bench/test-only
    jax.jit,
    static_argnames=("blocks_per_chunk", "interpret", "debug_mode"),
)
def paged_attention_decode_pallas(
    q: jax.Array,             # [B, nh, hd] (rope applied, NOT pre-scaled)
    k_cache: jax.Array,       # [L, nkv, num_blocks, hd, bs]
    v_cache: jax.Array,
    layer,                    # int scalar, traced: one trace and one
                              #   lowering serve every layer of a program
                              #   (a static layer cost 9 s of lowering a
                              #   16-layer decode program, paid on every
                              #   start, compile cache or not; PR 28)
    block_tables: jax.Array,  # [B, max_blocks] int32
    kv_lens: jax.Array,       # [B] int32, valid positions incl. current;
                              #   0 = idle lane (reads nothing, output 0)
    *,
    blocks_per_chunk: int | None = None,
    interpret: bool = False,
    debug_mode: str = "",
    k_scale: jax.Array = None,  # [L, nkv, num_blocks, bs] fp32 (int8)
    v_scale: jax.Array = None,
    bias: jax.Array = None,     # [B, max_blocks * bs] fp32, added to a
                                #   lane's scores position by position
                                #   (NEG_INF = a token left out:
                                #   ops/sparse_attention.py)
    kv_lo: jax.Array = None,    # [B] int32: a lane attends the table's
                                #   positions kv_lo <= pos < kv_lens (a
                                #   window layer's ring, oldest block
                                #   first: the stale cells of that block
                                #   are masked; blocks under the bound
                                #   are still moved, so the caller's
                                #   table starts at the oldest live one)
) -> jax.Array:
    """Drop-in fast path for paged_attention.paged_attention_decode.

    The pools go into the kernel whole and in their resident layout (the
    DMA descriptor indexes layer and block), and a lane moves only its
    live blocks: HBM traffic is the live context in the cache's dtype.

    With `k_scale`/`v_scale` (an int8 cache's per-position fp32 scale
    planes, quant/kv.py) the kernel DMAs int8 blocks plus their scale
    rows into VMEM and fuses the dequantizing multiply into the chunk
    consume — int8's halved HBM traffic lands inside the fast path."""
    B, nh, hd = q.shape
    _, nkv, _, _, bs = k_cache.shape
    hdv = v_cache.shape[3]    # V may be narrower than K (MLA-free GQA
    #                           families with unequal widths): out is hdv
    group = nh // nkv
    max_blocks = block_tables.shape[1]
    quantized = k_scale is not None

    # chunk of up to 8 blocks (S = 1024 lanes at bs=128): big enough that
    # the two per-chunk matmuls amortize their pipeline fills and DMA
    # descriptors stay few, small enough for double-buffered VMEM
    bpc = blocks_per_chunk or max(1, min(max_blocks, -(-1024 // bs)))
    n_chunks = -(-max_blocks // bpc)
    pad = n_chunks * bpc - max_blocks
    S = bpc * bs
    block_tables, kv_lens, base, next_row = chunk_chain_planes(
        block_tables, kv_lens, bpc, bs)

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(B, nkv, group, hd)

    inputs = [qg, k_cache, v_cache]
    in_specs = [
        pl.BlockSpec((1, nkv, group, hd),
                     lambda b, *refs: (b, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, nkv, hd, S), k_cache.dtype),
        pltpu.VMEM((2, nkv, hdv, S), v_cache.dtype),
    ]
    if quantized:
        inputs += [k_scale, v_scale]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((2, nkv, S), jnp.float32),
                    pltpu.VMEM((2, nkv, S), jnp.float32)]
    if bias is not None:
        # a sequence's whole bias rides VMEM (4 bytes a position), one
        # [1, S] row a chunk, indexed by the chunk loop
        inputs.append(jnp.pad(
            bias.astype(jnp.float32), ((0, 0), (0, pad * bs)),
            constant_values=NEG_INF).reshape(B, n_chunks, 1, S))
        in_specs.append(pl.BlockSpec((1, n_chunks, 1, S),
                                     lambda b, *refs: (b, 0, 0, 0)))
    scratch.append(pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)))
    bounded = kv_lo is not None
    # with no bound the call is the one it was: five scalar operands
    prefetch = [block_tables, kv_lens, base, next_row,
                jnp.asarray(layer, jnp.int32).reshape(1)]
    if bounded:
        prefetch.append(kv_lo.astype(jnp.int32))
    # bytes per context position per head: int8 streams 1-byte elements
    # plus one fp32 scale per (head, position)
    pos_bytes = ((hd + hdv) * k_cache.dtype.itemsize
                 + (8 if quantized else 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bpc=bpc, bs=bs,
                          quantized=quantized, biased=bias is not None,
                          bounded=bounded, debug_mode=debug_mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nkv, group, hdv),
                                   lambda b, *refs: (b, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, group, hdv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * nh * (hd + hdv) * max_blocks * bs,
            bytes_accessed=B * nkv * max_blocks * bs * pos_bytes,
            transcendentals=B * nh * max_blocks * bs,
        ),
        interpret=interpret,
    )(*prefetch, *inputs)
    return out.reshape(B, nh, hdv)
