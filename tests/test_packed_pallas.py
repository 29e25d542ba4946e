"""Pallas kernel unification suite (ROADMAP item 1 / PR 12).

Two kernels under test, both interpret-mode on CPU (the same code path
compiles on TPU):

  * the packed-prefill tile-skip kernel
    (ops/pallas_packed_prefill.py) vs the XLA masked reference
    (ops/packed_prefill.py) across segment layouts — uneven lengths,
    prefix-cache committed KV, spec_verify-shaped k+1 rows, int8
    caches, tp sharding;
  * the paged-attention decode kernel's in-kernel int8 dequant
    (ops/pallas_paged_attention.py) vs the jnp gather path.

Plus the engine-level contracts: greedy byte-identity at
impl=pallas_interpret with kv_cache_dtype=int8 (overlap scheduling
ON), the zero-recompile steady state with the kernels in the watched
families, and the --attn-impl config/CLI plumbing.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real-JAX-engine tests: XLA compiles (seconds at tier-1's -O0) and
# device work run inside the async test bodies, so the conftest's
# event-loop slow-callback gate (DYN004's runtime twin) cannot hold
# here; mocker/frontend/router fleets keep it armed.
pytestmark = pytest.mark.allow_slow_callbacks

from dynamo_tpu.ops.packed_prefill import (
    packed_prefill_attention,
    write_packed_kv,
)
from dynamo_tpu.ops.paged_attention import (
    paged_attention_decode_jnp,
    write_prompt_kv,
)
from dynamo_tpu.ops.pallas_packed_prefill import (
    packed_prefill_attention_pallas,
)
from dynamo_tpu.ops.pallas_paged_attention import (
    paged_attention_decode_pallas,
)


def _packed_case(rng, lens, *, nkv=2, group=2, hd=16, bs=4, mb=8, L=2,
                 bucket=None, ctx0=None, dtype=jnp.float32, int8=False):
    """Build one packed-stream case: per-segment chunk lengths `lens`
    (0 = unused row), optional committed prefix lengths `ctx0` already
    in cache before the chunk, KV written through the real write ops so
    int8 cases round-trip the quantizer exactly like serving."""
    S = len(lens)
    nh = nkv * group
    num_blocks = 1 + S * mb
    ctx0 = ctx0 or [0] * S
    T = sum(lens)
    bucket = bucket or T
    pad = bucket - T
    seg_ids = np.concatenate(
        [np.full(n, i, np.int32) for i, n in enumerate(lens)]
        + [np.zeros(pad, np.int32)])
    positions = np.concatenate(
        [c + np.arange(n, dtype=np.int32) for c, n in zip(ctx0, lens)]
        + [np.zeros(pad, np.int32)])
    valid = np.concatenate([np.ones(T, bool), np.zeros(pad, bool)])
    tables = np.zeros((S, mb), np.int32)
    perm = rng.permutation(num_blocks - 1) + 1
    for s in range(S):
        tables[s] = perm[s * mb:(s + 1) * mb]
    tables = jnp.asarray(tables)
    seg_ids = jnp.asarray(seg_ids)
    positions = jnp.asarray(positions)
    valid = jnp.asarray(valid)

    cache_shape = (L, nkv, num_blocks, hd, bs)
    if int8:
        kc = jnp.zeros(cache_shape, jnp.int8)
        vc = jnp.zeros(cache_shape, jnp.int8)
        ks = jnp.zeros((L, nkv, num_blocks, bs), jnp.float32)
        vs = jnp.zeros((L, nkv, num_blocks, bs), jnp.float32)
    else:
        kc = jnp.asarray(rng.standard_normal(cache_shape), dtype)
        vc = jnp.asarray(rng.standard_normal(cache_shape), dtype)
        ks = vs = None

    # committed prefixes first (prefix-cache hits): written through the
    # prompt write op, exactly as a previous chunk would have
    for li in range(L):
        for s, c in enumerate(ctx0):
            if c == 0:
                continue
            kp = jnp.asarray(rng.standard_normal((c, nkv, hd)), dtype)
            vp = jnp.asarray(rng.standard_normal((c, nkv, hd)), dtype)
            out = write_prompt_kv(kc, vc, li, kp, vp, tables[s],
                                  jnp.int32(0), jnp.int32(c),
                                  k_scale=ks, v_scale=vs)
            kc, vc, ks, vs = out if len(out) == 4 else (*out, None, None)
        kch = jnp.asarray(rng.standard_normal((bucket, nkv, hd)), dtype)
        vch = jnp.asarray(rng.standard_normal((bucket, nkv, hd)), dtype)
        out = write_packed_kv(kc, vc, li, kch, vch, tables, seg_ids,
                              positions, valid, k_scale=ks, v_scale=vs)
        kc, vc, ks, vs = out if len(out) == 4 else (*out, None, None)
    q = jnp.asarray(rng.standard_normal((bucket, nh, hd)), dtype)
    return q, kc, vc, ks, vs, tables, seg_ids, positions, valid


def _assert_packed_parity(case, L=2, **pallas_kw):
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = case
    # parity on the LAST layer only: the layer index selects a cache
    # slice (the kernel body is layer-independent), and every extra
    # layer is a second interpret-mode trace+compile of tier-1 wall
    # clock; li=L-1 keeps the non-zero-offset slicing under test
    for li in (L - 1,):
        ref = packed_prefill_attention(
            q, kc, vc, li, tables, seg_ids, positions, valid,
            impl="xla", k_scale=ks, v_scale=vs)
        out = packed_prefill_attention_pallas(
            q, kc, vc, li, tables, seg_ids, positions, valid,
            interpret=True, k_scale=ks, v_scale=vs, **pallas_kw)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lens,bucket", [
    ([7, 1, 12, 4], 32),       # uneven lengths + padded tail
    ([16, 16], 32),            # balanced, no tail
    ([3], 8),                  # single segment
    ([5, 0, 9, 0], 16),        # unused segment rows (waterfill leftovers)
])
def test_packed_pallas_matches_xla_segment_layouts(lens, bucket):
    """Tile-skip kernel vs the masked XLA reference across the segment
    layouts the packing planner actually produces."""
    rng = np.random.default_rng(0)
    case = _packed_case(rng, lens, bucket=bucket)
    _assert_packed_parity(case)


def test_packed_pallas_multi_tile_and_chunking():
    """Small token_block + chunk_cols force the tile grid and the
    double-buffered context chunk loop through many iterations, with a
    segment boundary landing mid-tile."""
    rng = np.random.default_rng(1)
    case = _packed_case(rng, [11, 9, 6], bucket=32)
    _assert_packed_parity(case, token_block=8, chunk_cols=2)


def test_packed_pallas_committed_prefix():
    """Prefix-cache hits: chunk tokens at positions ctx0.. attend to the
    committed KV written by earlier chunks through the block table."""
    rng = np.random.default_rng(2)
    case = _packed_case(rng, [6, 10], ctx0=[5, 13], mb=8, bucket=16)
    _assert_packed_parity(case, token_block=8, chunk_cols=2)


def test_packed_pallas_spec_verify_rows():
    """spec_verify's layout: S rows of k+1 tokens each at large committed
    positions (the draft window riding a long context)."""
    rng = np.random.default_rng(3)
    k = 4
    case = _packed_case(rng, [k + 1] * 3, ctx0=[17, 9, 26], mb=8,
                        bucket=16)
    _assert_packed_parity(case)


def test_packed_pallas_int8_dequant():
    """Int8 cache: the kernel's fused in-VMEM dequant must match the
    XLA reference's gather-side dequant on the same quantized cache
    (both read the identical int8+scale planes)."""
    rng = np.random.default_rng(4)
    case = _packed_case(rng, [7, 1, 12, 4], bucket=32, int8=True,
                        ctx0=[3, 0, 0, 5])
    _assert_packed_parity(case, token_block=8, chunk_cols=2)


def test_packed_pallas_bf16_tolerance():
    rng = np.random.default_rng(5)
    case = _packed_case(rng, [9, 7], bucket=16, dtype=jnp.bfloat16)
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = case
    ref = packed_prefill_attention(
        q, kc, vc, 0, tables, seg_ids, positions, valid, impl="xla")
    out = packed_prefill_attention_pallas(
        q, kc, vc, 0, tables, seg_ids, positions, valid, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=0.05, atol=0.05)


def test_packed_pallas_tp_sharded_matches_xla():
    """The packed kernel under shard_map over a tp>1 mesh (each shard
    owning its kv-head slice) must match the unsharded XLA reference —
    the path multi-chip packed prefill takes at impl=pallas."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(6)
    case = _packed_case(rng, [7, 9], nkv=4, group=2, bucket=16)
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = case
    ref = packed_prefill_attention(
        q, kc, vc, 0, tables, seg_ids, positions, valid, impl="xla")
    mesh = make_mesh(MeshConfig(dp=2, tp=4))  # 8 virtual CPU devices
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = NamedSharding(mesh, P(None, "tp", None, None, None))
    with mesh:
        kc_s = jax.device_put(kc, spec)
        vc_s = jax.device_put(vc, spec)
        out = jax.jit(
            lambda q_, kc_, vc_, t_, s_, p_, v_: packed_prefill_attention(
                q_, kc_, vc_, 0, t_, s_, p_, v_,
                impl="pallas_interpret", mesh=mesh)
        )(q, kc_s, vc_s, tables, seg_ids, positions, valid)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# what "auto" means (resolve_packed_impl) and the kernel at its boundaries
# ---------------------------------------------------------------------------

_BF16, _I8, _F32 = jnp.bfloat16, jnp.int8, jnp.float32


@pytest.mark.parametrize("platform,bs,hd,dtype,tokens,group,want", [
    # the buckets that carry the doc cell's tokens, and the chat
    # cell's long prompts: the kernel
    ("tpu", 128, 128, _BF16, 2048, 4, "pallas"),
    ("tpu", 128, 128, _BF16, 1024, 4, "pallas"),
    ("tpu", 256, 128, _I8, 1024, 4, "pallas"),
    ("tpu", 128, 256, _I8, 4096, 4, "pallas"),
    # under the measured length: the scan (the short buckets,
    # speculative verification's rows of k + 1 tokens)
    ("tpu", 128, 128, _BF16, 512, 4, "xla"),
    ("tpu", 128, 128, _BF16, 128, 4, "xla"),
    ("tpu", 128, 128, _BF16, 32, 4, "xla"),
    ("tpu", 128, 128, _I8, 5, 4, "xla"),
    # where the kernel cannot run as written
    ("cpu", 128, 128, _BF16, 2048, 4, "xla"),
    ("gpu", 128, 128, _BF16, 2048, 4, "xla"),
    ("tpu", 16, 128, _BF16, 2048, 4, "xla"),
    # a body's query tile that is not whole vregs: 64-wide heads one a
    # KV head (an even number of them is: LFM2's 4; 32 a KV head go 4 a
    # body)
    ("tpu", 128, 64, _BF16, 2048, 1, "xla"),
    ("tpu", 128, 64, _BF16, 2048, 4, "pallas"),
    ("tpu", 128, 64, _BF16, 2048, 32, "pallas"),
    ("tpu", 128, 64, _BF16, 512, 4, "xla"),
    ("tpu", 128, 128, _F32, 2048, 4, "xla"),
    ("tpu", 64, 128, _I8, 2048, 4, "xla"),
])
def test_resolve_packed_impl_auto(platform, bs, hd, dtype, tokens, group,
                                  want):
    """`auto` is decided in one place from platform, cache and the
    stream's length; an explicit impl is returned as given whatever the
    rest says."""
    from dynamo_tpu.ops.packed_prefill import (
        PACKED_IMPLS,
        resolve_packed_impl,
    )

    assert resolve_packed_impl("auto", platform, bs, hd, dtype, tokens,
                               group) == want
    for impl in PACKED_IMPLS[1:]:
        assert resolve_packed_impl(impl, platform, bs, hd, dtype, tokens,
                                   group) == impl


def test_auto_off_the_chip_is_the_float32_scan():
    """On this backend `auto` traces the scan: the same values as "xla",
    bit for bit."""
    rng = np.random.default_rng(11)
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = _packed_case(
        rng, [9, 7], bucket=16)
    a, b = (packed_prefill_attention(q, kc, vc, 1, tables, seg_ids,
                                     positions, valid, impl=impl)
            for impl in ("auto", "xla"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="packed-prefill impl"):
        packed_prefill_attention(q, kc, vc, 1, tables, seg_ids, positions,
                                 valid, impl="triton")


@pytest.mark.parametrize("lens,ctx0,mb,bucket,tiles", [
    # a chunk that starts and ends inside a block (block 4): 13..26
    ([14], [13], 8, 16, dict(token_block=8, chunk_cols=2)),
    # a table padded to the key tile: 7 columns, tiles of 4
    ([21], [3], 7, 32, dict(token_block=8, chunk_cols=4)),
    # a stream with a padded tail longer than a query tile
    ([9], [0], 8, 32, dict(token_block=8, chunk_cols=2)),
    # four segment rows, two of them sharing a query tile
    ([12, 4, 9, 7], [0, 6, 0, 17], 8, 32,
     dict(token_block=16, chunk_cols=2)),
    # a committed prefix of many blocks under a short chunk: every key
    # tile but the last runs without a mask
    ([8], [23], 8, 8, dict(token_block=8, chunk_cols=1)),
    # the kernel's own tile sizes (larger than the stream)
    ([11, 5], [2, 9], 8, 16, {}),
])
def test_packed_kernel_at_the_boundaries(lens, ctx0, mb, bucket, tiles):
    """The kernel against the float32 scan where its tile arithmetic
    turns: block, key-tile and query-tile edges, the padded tail, rows
    that share a tile, and tiles that skip the mask."""
    rng = np.random.default_rng(12)
    case = _packed_case(rng, lens, ctx0=ctx0, mb=mb, bucket=bucket)
    _assert_packed_parity(case, **tiles)


def test_packed_kernel_runs_only_the_pairs_it_needs(monkeypatch):
    """The wrapper's plan, read off the scalar-prefetch values it hands
    the kernel: a query tile runs its own row's key tiles up to its
    frontier and no other; the tiles wholly under it carry the maskless
    flag."""
    from dynamo_tpu.ops import pallas_packed_prefill as ppk

    rng = np.random.default_rng(13)
    # two rows of 16 tokens at positions 0..15 and 16..31 (block 4):
    # query tiles of 8, key tiles of 2 blocks = 8 keys, 4 a row
    case = _packed_case(rng, [16, 16], ctx0=[0, 16], mb=8, bucket=32)
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = case
    seen = {}
    real = ppk.pl.pallas_call

    def spy(kernel, **kw):
        call = real(kernel, **kw)

        def run(layer, tables_, fetch, flags, *rest):
            seen["flags"], seen["fetch"] = flags, fetch
            return call(layer, tables_, fetch, flags, *rest)
        return run

    monkeypatch.setattr(ppk.pl, "pallas_call", spy)
    with jax.disable_jit():
        ppk.packed_prefill_attention_pallas.__wrapped__(
            q, kc, vc, 1, tables, seg_ids, positions, valid,
            token_block=8, chunk_cols=2, interpret=True)
    flags = np.asarray(seen["flags"]).reshape(4, 8)
    assert flags.tolist() == [
        [1, 0, 0, 0, 0, 0, 0, 0],      # row 0, positions 0..7
        [2, 1, 0, 0, 0, 0, 0, 0],      # row 0, 8..15
        [0, 0, 0, 0, 2, 2, 1, 0],      # row 1, 16..23
        [0, 0, 0, 0, 2, 2, 2, 1],      # row 1, 24..31
    ]
    fetch = np.asarray(seen["fetch"]).reshape(4, 8)
    # a skipped step names a tile that ran (or will): nothing to fetch
    assert fetch.tolist() == [
        [0, 0, 0, 0, 0, 0, 0, 0],
        [0, 1, 1, 1, 1, 1, 1, 1],
        [4, 4, 4, 4, 4, 5, 6, 6],
        [4, 4, 4, 4, 4, 5, 6, 7],
    ]


def _kernel_call(jaxpr):
    """The `pallas_call` equation of a traced kernel wrapper."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _kernel_call(sub)
            if found is not None:
                return found
    return None


# query heads a KV head -> heads a kernel body holds (`_group_heads`)
_GROUP_OF = {4: 4, 8: 8, 16: 4}


@pytest.mark.parametrize("form", ["plain", "lower", "int8"])
@pytest.mark.parametrize("G", sorted(_GROUP_OF))
def test_packed_kernel_takes_a_group_four_heads_at_a_time(G, form):
    """A body holds `GROUP_HEADS` query heads of a KV head where the
    group is larger than `GROUP_WHOLE` (the grid's first axis then walks
    the runs of heads, each the next column block of the stream): the same values bit for
    bit as ONE body over the whole group (`group_heads=G`), the scan's
    to bf16 tolerance, with a band (`lower`) and an int8 cache alike;
    the traced call shows the width, and to 8 heads a KV head it is the
    call it was."""
    rng = np.random.default_rng(20 + G)
    case = _packed_case(rng, [11, 9, 6], ctx0=[3, 0, 5], group=G, bucket=32,
                        int8=form == "int8",
                        dtype=jnp.float32 if form == "int8" else jnp.bfloat16)
    q, kc, vc, ks, vs, tables, seg_ids, positions, valid = case
    lower = jnp.maximum(positions - 6, 0) if form == "lower" else None
    args = (q, kc, vc, 1, tables, seg_ids, positions, valid)
    tiles = dict(token_block=8, chunk_cols=2, k_scale=ks, v_scale=vs,
                 lower=lower)
    out, whole = (np.asarray(packed_prefill_attention_pallas(
        *args, interpret=True, group_heads=g, **tiles), np.float32)
        for g in (0, G))
    np.testing.assert_array_equal(out, whole)
    ref = packed_prefill_attention(*args, impl="xla", k_scale=ks,
                                   v_scale=vs, lower=lower)
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.05)

    def traced(g):
        return jax.make_jaxpr(lambda *a: packed_prefill_attention_pallas(
            *a, group_heads=g, **tiles))(*args)

    Gk, hd, nkv = _GROUP_OF[G], q.shape[2], kc.shape[1]
    call = _kernel_call(traced(0).jaxpr)
    grid = call.params["grid_mapping"]
    assert grid.grid[0] == nkv * (G // Gk)
    q_block = grid.block_mappings[2].block_shape
    assert [d.block_size for d in q_block] == [8, Gk * hd]
    assert [v.aval.shape for v in call.outvars] == [(32, nkv * G * hd)]
    if Gk == G:
        assert str(traced(0)) == str(traced(G))
    with pytest.raises(ValueError, match="does not divide"):
        traced(3)


async def test_the_counter_says_how_often_the_kernel_engaged():
    """`prefill_attn_kernel_tokens` rises by a packed program's tokens
    where the rule the traced code applies names the kernel for its
    bucket — here an explicit impl, which the rule returns as given —
    and by nothing under `auto` off the chip; it is asked at dispatch,
    from the host."""
    from test_engine import collect, greedy_req

    from dynamo_tpu.engine import JaxEngine
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl

    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 20]
    small = dict(decode_fused_steps=1, num_blocks=64, max_blocks_per_seq=8)
    for impl, share in (("pallas_interpret", 1), ("", 0)):
        eng = JaxEngine(_engine_cfg(packed_attn_impl=impl, **small))
        try:
            assert eng.metrics["prefill_attn_kernel_tokens"] == 0
            await collect(eng, greedy_req(prompt, 2, f"k-{impl}"))
            assert eng.metrics["prefill_tokens"] == len(prompt)
            assert eng.metrics["prefill_attn_kernel_tokens"] \
                == share * len(prompt)
            rec = [r for r in eng.fpm if r["kind"] == "prefill"][-1]
            # the host's answer is the rule's, for the plan's bucket
            c, m = eng.config, eng.model_cfg
            assert eng._prefill_attn_kernel(rec["bucket"]) == (
                resolve_packed_impl(m.packed_attn_impl, "cpu",
                                    c.block_size, m.head_dim, m.dtype,
                                    rec["bucket"],
                                    m.n_heads // m.n_kv_heads) != "xla")
        finally:
            await eng.close()


# ---------------------------------------------------------------------------
# the packed write: whole planes in the resident layout vs the flat scatter
# ---------------------------------------------------------------------------


def _flat_scatter(kc, vc, layer, k, v, tables, seg_ids, positions, valid,
                  k_scale=None, v_scale=None):
    """`write_packed_kv` as it was until PR 30, kept as the plain
    reference: one flat column scatter, the padded tail into block 0."""
    from dynamo_tpu.ops.paged_attention import _store_kv

    bs = kc.shape[4]
    blocks = jnp.where(valid, tables[seg_ids, positions // bs], 0)
    return _store_kv(kc, vc, layer, k, v, blocks, positions % bs,
                     k_scale, v_scale)


# name -> (block size, stream length, [(table row, first position,
# tokens), ...] in stream order, table rows)
_WRITE_CASES = {
    "one-segment": (16, 64, [(0, 0, 40)], 1),
    "four-segments": (16, 64, [(0, 0, 7), (1, 0, 1), (2, 0, 12),
                               (3, 0, 20)], 4),
    "inside-one-block": (16, 8, [(0, 5, 6)], 1),
    "starts-and-ends-inside-blocks": (16, 32, [(0, 21, 30)], 1),
    "two-chunks-of-one-prompt": (16, 32, [(0, 0, 10), (0, 10, 9)], 2),
    "prefix-hit-tail": (16, 32, [(0, 32, 20), (1, 37, 6)], 2),
    "padded-tail-only-one-token": (16, 16, [(0, 15, 1)], 1),
    "unused-rows": (16, 32, [(0, 0, 5), (2, 0, 9)], 4),
    "spec-verify-rows": (16, 16, [(0, 15, 5), (1, 9, 5), (2, 30, 5)], 3),
    "every-segment-ends-a-block-and-starts-one": (
        16, 72, [(s, 15, 18) for s in range(4)], 4),
    "block-128": (128, 512, [(0, 100, 300)], 1),
    "block-128-four-segments": (128, 512, [(0, 0, 130), (1, 127, 3),
                                           (2, 256, 128), (3, 5, 200)], 4),
}


def _write_case(name, int8):
    from zlib import crc32

    bs, T, segs, S = _WRITE_CASES[name]
    rng = np.random.default_rng(crc32(name.encode()))
    L, nkv, hd, mb = 2, 2, 8, 6
    nb = 1 + S * mb
    tables = (1 + rng.permutation(nb - 1))[:S * mb].reshape(S, mb)
    seg_ids, positions = np.zeros(T, np.int32), np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    off = 0
    for i, (row, start, n) in enumerate(segs):
        # two chunks of one prompt are two segment rows over one table
        tables[i] = tables[row]
        seg_ids[off:off + n] = i
        positions[off:off + n] = start + np.arange(n)
        valid[off:off + n] = True
        off += n
    shape = (L, nkv, nb, hd, bs)
    if int8:
        kc, vc = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                  for _ in range(2))
        scales = tuple(jnp.asarray(rng.random((L, nkv, nb, bs)),
                                   jnp.float32) for _ in range(2))
    else:
        kc, vc = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                  for _ in range(2))
        scales = ()
    k, v = (jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.bfloat16)
            for _ in range(2))
    return ((kc, vc) + scales, k, v, jnp.asarray(tables, jnp.int32),
            jnp.asarray(seg_ids), jnp.asarray(positions),
            jnp.asarray(valid))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(_WRITE_CASES))
def test_write_packed_kv_matches_flat_scatter(name, int8):
    """The plane write puts into every cell of both pools (and both
    scale planes) what the flat scatter put there, bit for bit, and
    leaves every other cell outside the garbage block as it was: other
    layers, the rest of a half-filled block, blocks the chunk does not
    touch."""
    caches, k, v, tables, seg_ids, positions, valid = _write_case(
        name, int8)
    kw = dict(k_scale=caches[2], v_scale=caches[3]) if int8 else {}
    args = (caches[0], caches[1], 1, k, v, tables, seg_ids, positions,
            valid)
    ref = _flat_scatter(*args, **kw)
    out = write_packed_kv(*args, **kw)
    assert len(out) == len(ref) == len(caches)
    for c, o, r in zip(caches, out, ref):
        assert o.dtype == c.dtype and o.shape == c.shape
        assert np.array_equal(np.asarray(o[:, :, 1:], np.float32),
                              np.asarray(r[:, :, 1:], np.float32))
        # something was written, and only into layer 1
        assert not np.array_equal(np.asarray(o[1], np.float32),
                                  np.asarray(c[1], np.float32))
        assert np.array_equal(np.asarray(o[0], np.float32),
                              np.asarray(c[0], np.float32))


def _slot(start, n, bs, draft=0):
    """What the two planners read of an engine slot: a prompt of which
    `start` tokens are in the cache and `n` are left (prefill), or a
    context of `start` tokens (spec verify)."""
    from types import SimpleNamespace as NS

    return NS(prompt_len=start + n, prefill_pos=start, ctx_len=start,
              seq=NS(tokens=list(range(1, start + n + 1))), last_token=7,
              block_table=np.arange(1, 65, dtype=np.int32) + 64 * draft,
              sampling_seed=0, lora_idx=0,
              request=NS(sampling=NS(temperature=0.0, top_k=0, top_p=1.0)))


# name -> (planner, block size, [(tokens cached, tokens to run), ...],
# planes the stream starts, planes the plan holds)
_PLANNER_CASES = {
    # every row starts in a block's last column and ends in another's
    # first, and the stream fills its bucket: the plan is full
    "prefill-full-plan": ("prefill", 16, [(15, 18), (15, 18), (15, 18),
                                          (15, 10)], 11, 11),
    "prefill-three-rows-of-four": ("prefill", 16, [(0, 40), (21, 3),
                                                   (37, 6)], 5, 11),
    "prefill-one-token": ("prefill", 16, [(31, 1)], 1, 2),
    "prefill-doc-chunk": ("prefill", 128, [(2048 + 100, 2048)], 17, 17),
    "prefill-block-128-chat": ("prefill", 128, [(0, 320), (127, 130),
                                                (640, 62)], 7, 11),
    "spec-full-plan": ("spec", 16, [(15, 2)] * 8, 16, 16),
    "spec-three-rows-of-four": ("spec", 16, [(14, 5), (9, 5), (30, 5)],
                                5, 8),
    "spec-block-128": ("spec", 128, [(127, 4), (300, 4)], 3, 4),
}


@pytest.mark.parametrize("name", sorted(_PLANNER_CASES))
def test_planners_streams_fit_the_write_plan(name):
    """The write plan's static size holds for what the two planners
    really hand the program, at their bucketed stream length and row
    count: every token of the stream has its column in a plane, none is
    dropped, and each plane is the block its tokens' table names."""
    from dynamo_tpu.engine.prefill import plan_packed_prefill
    from dynamo_tpu.ops.packed_prefill import plan_packed_write
    from dynamo_tpu.spec.verify import plan_spec_verify

    planner, bs, rows, want_used, want_planes = _PLANNER_CASES[name]
    slots = [_slot(start, n, bs, draft=i)
             for i, (start, n) in enumerate(rows)]
    if planner == "prefill":
        a = plan_packed_prefill(
            slots, sum(n for _, n in rows), block_size=bs,
            max_blocks_per_seq=64, min_bucket=16, with_lora=False).arrays
    else:
        a = plan_spec_verify(
            [(s, [3] * (n - 1)) for s, (_, n) in zip(slots, rows)],
            block_size=bs, max_blocks_per_seq=64).arrays
    T = len(a["toks"])
    blocks, src, used = (np.asarray(x) for x in plan_packed_write(
        *(jnp.asarray(a[k]) for k in ("tables", "seg_ids", "positions",
                                      "valid")), bs))
    assert (int(used), len(blocks)) == (want_used, want_planes)
    real = np.flatnonzero(a["valid"])
    assert sorted(src[src < T]) == real.tolist()
    plane, col = np.nonzero(src < T)
    tok = src[plane, col]
    assert (plane < used).all()
    assert np.array_equal(col, a["positions"][tok] % bs)
    assert np.array_equal(
        blocks[plane],
        a["tables"][a["seg_ids"][tok], a["positions"][tok] // bs])


@pytest.mark.parametrize("seg_ids,positions,valid", [
    ([0, 1, 0, 0], [0, 0, 1, 0], [1, 1, 1, 0]),      # a row in two runs
    ([0, 0, 1, 0], [3, 5, 0, 0], [1, 1, 1, 0]),      # a gap in a row
    ([0, 0, 1, 0], [4, 3, 0, 0], [1, 1, 1, 0]),      # a row backwards
    ([1, 1, 0, 0], [3, 4, 0, 0], [1, 1, 1, 0]),      # rows out of order
    ([0, 0, 1, 0], [3, 4, 0, 0], [1, 0, 1, 1]),      # padding inside
    ([0, 0, 2, 0], [3, 4, 0, 0], [1, 1, 1, 0]),      # a row past the table
], ids=["two-runs", "gap", "backwards", "rows-out-of-order",
        "padding-inside", "row-past-the-table"])
def test_packed_stream_outside_its_contract_is_refused(seg_ids, positions,
                                                       valid):
    """The plane write, unlike the scatter it replaced, relies on each
    row being one run at consecutive positions: the host check the
    planners make refuses a stream that is not, where the device would
    drop its columns without a word."""
    from dynamo_tpu.ops.packed_prefill import check_packed_stream

    check_packed_stream(np.array([0, 0, 1, 0]), np.array([3, 4, 0, 0]),
                        np.array([1, 1, 1, 0], bool), 2)
    with pytest.raises(ValueError, match="outside its contract"):
        check_packed_stream(np.array(seg_ids), np.array(positions),
                            np.array(valid, bool), 2)


@pytest.mark.parametrize("int8", [False, True], ids=["fp32", "int8"])
def test_prefill_packed_logits_match_the_scatter_program(monkeypatch, int8):
    """`prefill_packed` end to end against the program it replaces (flat
    scatter, a layer sliced out of the pool before each gather): same
    logits, same cache, over two dispatches of which the second
    continues the first one's half-filled blocks."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import packed_prefill
    from dynamo_tpu.ops.paged_attention import _gather_ctx

    cfg = llama.LlamaConfig(
        name="tiny32", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=128, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    bs, nb, mb, S, T = 16, 13, 4, 2, 64
    rng = np.random.default_rng(11)
    tables = jnp.asarray(
        (1 + rng.permutation(nb - 1))[:S * mb].reshape(S, mb), jnp.int32)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, T), jnp.int32)
    shape = (cfg.n_layers, cfg.n_kv_heads, nb, cfg.head_dim, bs)

    def run():
        kv = tuple(jnp.zeros(shape, jnp.int8 if int8 else cfg.dtype)
                   for _ in range(2))
        if int8:
            kv += tuple(jnp.zeros(shape[:3] + (bs,), jnp.float32)
                        for _ in range(2))
        logits = []
        for starts, lens in (((0, 0), (37, 20)), ((37, 20), (9, 27))):
            pos, seg = np.zeros(T, np.int32), np.zeros(T, np.int32)
            val, last, off = np.zeros(T, bool), np.zeros(S, np.int32), 0
            for i, (st, n) in enumerate(zip(starts, lens)):
                pos[off:off + n] = st + np.arange(n)
                seg[off:off + n] = i
                val[off:off + n] = True
                last[i] = off + n - 1
                off += n
            lg, kv = llama.prefill_packed(
                params, cfg, kv, toks, jnp.asarray(pos),
                jnp.asarray(seg), tables, jnp.asarray(last),
                jnp.asarray(val))
            logits.append(np.asarray(lg))
        return logits, kv

    new_logits, new_kv = run()
    monkeypatch.setattr(llama, "write_packed_kv", _flat_scatter)
    monkeypatch.setattr(packed_prefill, "_gather_blocks", _gather_ctx)
    old_logits, old_kv = run()
    for a, b in zip(new_logits, old_logits):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    for a, b in zip(new_kv, old_kv):
        assert np.array_equal(np.asarray(a[:, :, 1:]),
                              np.asarray(b[:, :, 1:]))


# ---------------------------------------------------------------------------
# decode kernel: in-kernel int8 dequant
# ---------------------------------------------------------------------------


def _int8_decode_case(rng, kv_lens, *, nkv=2, group=2, hd=16, bs=4,
                      mb=6, L=2):
    B = len(kv_lens)
    nh = nkv * group
    num_blocks = 1 + B * mb
    kc = jnp.zeros((L, nkv, num_blocks, hd, bs), jnp.int8)
    vc = jnp.zeros((L, nkv, num_blocks, hd, bs), jnp.int8)
    ks = jnp.zeros((L, nkv, num_blocks, bs), jnp.float32)
    vs = jnp.zeros((L, nkv, num_blocks, bs), jnp.float32)
    tables = np.zeros((B, mb), np.int32)
    perm = rng.permutation(num_blocks - 1) + 1
    for b in range(B):
        tables[b] = perm[b * mb:(b + 1) * mb]
    tables = jnp.asarray(tables)
    for b in range(B):
        n = int(kv_lens[b])
        kt = jnp.asarray(rng.standard_normal((n, nkv, hd)), jnp.float32)
        vt = jnp.asarray(rng.standard_normal((n, nkv, hd)), jnp.float32)
        for li in range(L):
            kc, vc, ks, vs = write_prompt_kv(
                kc, vc, li, kt, vt, tables[b], jnp.int32(0),
                jnp.int32(n), k_scale=ks, v_scale=vs)
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    return q, kc, vc, ks, vs, tables, jnp.asarray(
        np.asarray(kv_lens, np.int32))


def test_int8_decode_pallas_matches_jnp():
    """In-kernel dequant vs the jnp gather path's dequant-on-gather, on
    the same quantized cache — uneven lengths incl. partial blocks, and
    blocks_per_chunk forced small so the double-buffered scale DMA loop
    runs several iterations."""
    rng = np.random.default_rng(7)
    q, kc, vc, ks, vs, tables, kv_lens = _int8_decode_case(
        rng, [17, 24, 5])
    # layer 1 only — same one-interpret-trace rationale as
    # _assert_packed_parity, non-zero layer offset kept under test
    for li in (1,):
        ref = paged_attention_decode_jnp(q, kc, vc, li, tables, kv_lens,
                                         k_scale=ks, v_scale=vs)
        out = paged_attention_decode_pallas(
            q, kc, vc, li, tables, kv_lens, interpret=True,
            k_scale=ks, v_scale=vs, blocks_per_chunk=2)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_int8_decode_pallas_tp_sharded_matches_jnp():
    """The int8 kernel under shard_map over tp>1: each shard DMAs and
    dequantizes its own cache+scale slab (kv_scale_spec sharding)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.paged_attention import paged_attention_decode
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(8)
    q, kc, vc, ks, vs, tables, kv_lens = _int8_decode_case(
        rng, [13, 7, 21], nkv=4)
    ref = paged_attention_decode_jnp(q, kc, vc, 1, tables, kv_lens,
                                     k_scale=ks, v_scale=vs)
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    cspec = NamedSharding(mesh, P(None, "tp", None, None, None))
    sspec = NamedSharding(mesh, P(None, "tp", None, None))
    with mesh:
        kc_s, vc_s = jax.device_put(kc, cspec), jax.device_put(vc, cspec)
        ks_s, vs_s = jax.device_put(ks, sspec), jax.device_put(vs, sspec)
        out = jax.jit(
            lambda q_, kc_, vc_, ks_, vs_, t_, l_: paged_attention_decode(
                q_, kc_, vc_, 1, t_, l_, impl="pallas_interpret",
                mesh=mesh, k_scale=ks_, v_scale=vs_)
        )(q, kc_s, vc_s, ks_s, vs_s, tables, kv_lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_int8_no_longer_reroutes_pallas_to_jnp():
    """The PR 3 caveat is dead: impl="pallas_interpret" with scales must
    run the KERNEL, not silently fall back to the gather path.  The
    kernel's online softmax reassociates differently from the one-shot
    softmax, so bit-identical output to the jnp path would itself be
    suspicious; instead pin the dispatch by breaking the kernel's
    input contract and seeing the kernel's own failure mode."""
    from dynamo_tpu.ops.paged_attention import paged_attention_decode

    rng = np.random.default_rng(9)
    q, kc, vc, ks, vs, tables, kv_lens = _int8_decode_case(rng, [9, 12])
    out = paged_attention_decode(q, kc, vc, 0, tables, kv_lens,
                                 impl="pallas_interpret",
                                 k_scale=ks, v_scale=vs)
    ref = paged_attention_decode_jnp(q, kc, vc, 0, tables, kv_lens,
                                     k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # and the result is NOT the bf16-operand fallback the old reroute
    # produced (jnp_bf16 quantizes operands to bf16; the kernel keeps
    # the query dtype fp32 here, so a max-abs-diff this small vs the
    # fp32 reference is only reachable through the kernel)
    bf16 = paged_attention_decode_jnp(q, kc, vc, 0, tables, kv_lens,
                                      native_dtype=True,
                                      k_scale=ks, v_scale=vs)
    assert float(jnp.max(jnp.abs(out - ref))) < \
        float(jnp.max(jnp.abs(bf16 - ref)))


# ---------------------------------------------------------------------------
# engine-level composition
# ---------------------------------------------------------------------------


def _engine_cfg(**kw):
    from test_engine import FP32 as _FP32

    from dynamo_tpu.engine import EngineConfig

    defaults = dict(model_config=_FP32, block_size=4, num_blocks=128,
                    max_blocks_per_seq=16, max_num_seqs=2,
                    prefill_buckets=(8, 16), seed=7)
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _greedy(cfg, prompt, n, rid):
    from test_engine import collect, greedy_req

    from dynamo_tpu.engine import JaxEngine

    eng = JaxEngine(cfg)
    toks = await collect(eng, greedy_req(list(prompt), n, rid))
    await eng.close()
    return toks


async def test_engine_greedy_int8_pallas_byte_identity():
    """The acceptance gate: greedy byte-identity at impl=pallas_interpret
    for BOTH kernels with kv_cache_dtype=int8 and overlap scheduling ON
    — quantization composes with the fast path end to end."""
    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 20]
    # 5 decode steps cross a block boundary (block_size=4) so identity
    # covers intra- and inter-block paging.  decode_fused_steps=1 and
    # the smaller table keep tier-1 wall clock sane: every fusion-ladder
    # rung is its own interpret-mode compile (~12s each on CPU, and the
    # trace cost scales with max_blocks_per_seq); identical settings on
    # both engines keep the comparison exact.
    wall = dict(kv_cache_dtype="int8", overlap_scheduling=True,
                decode_fused_steps=1, num_blocks=64, max_blocks_per_seq=8)
    ref = await _greedy(_engine_cfg(**wall), prompt, 5, "i8-jnp")
    pal = await _greedy(
        _engine_cfg(attn_impl="pallas_interpret",
                    packed_attn_impl="pallas_interpret", **wall),
        prompt, 5, "i8-pal")
    assert len(ref) == 5  # a crashed engine's empty stream is vacuous
    assert pal == ref


async def test_zero_recompiles_with_pallas_kernels():
    """The new kernels ride the watched compile families (prefill_packed
    / decode): warmup + the first request compile each shape ONCE, two
    more same-shape requests compile NOTHING — the PR 11 pinned
    out_shardings invariant holds with pallas_call in the programs and
    the int8 4-tuple riding donation."""
    from dynamo_tpu.engine import JaxEngine

    # decode_fused_steps=1 keeps the warmup to the single-step decode
    # program (each interpret-mode pallas compile costs seconds on CPU;
    # the family-count contract is identical)
    eng = JaxEngine(_engine_cfg(
        kv_cache_dtype="int8", attn_impl="pallas_interpret",
        packed_attn_impl="pallas_interpret", decode_fused_steps=1,
        num_blocks=64, max_blocks_per_seq=8))
    try:
        await asyncio.to_thread(eng.warmup_decode)
        from test_engine import collect, greedy_req

        # 4 tokens/request: the compile-family counts under judgment are
        # identical at any length ≥1, and every interpret-mode decode
        # step is seconds of tier-1 wall clock
        await collect(eng, greedy_req([5, 9, 13, 2, 7, 11, 3, 1, 8, 20],
                                      4, "pk-r0"))
        counts = dict(eng.compile_watch.counts)
        assert counts.get("prefill_packed", 0) == 1
        assert counts.get("decode", 0) >= 1
        await collect(eng, greedy_req([6, 10, 14, 3, 8, 12, 4, 2, 9, 21],
                                      4, "pk-r1"))
        await collect(eng, greedy_req([9, 13, 17, 6, 11, 15, 7, 5, 12, 24],
                                      4, "pk-r2"))
        assert dict(eng.compile_watch.counts) == counts, \
            "steady-state serving recompiled a pallas-kernel program"
    finally:
        await eng.close()


def test_engine_config_attn_impl_override_and_validation():
    """EngineConfig.attn_impl/packed_attn_impl replace the resolved
    model config's fields; junk values fail fast at engine init."""
    from dynamo_tpu.engine import JaxEngine

    eng = JaxEngine(_engine_cfg(attn_impl="jnp_bf16",
                                packed_attn_impl="xla"))
    assert eng.model_cfg.attn_impl == "jnp_bf16"
    assert eng.model_cfg.packed_attn_impl == "xla"
    with pytest.raises(ValueError, match="attn_impl"):
        JaxEngine(_engine_cfg(attn_impl="triton"))
    with pytest.raises(ValueError, match="packed_attn_impl"):
        JaxEngine(_engine_cfg(packed_attn_impl="cuda"))


def test_engine_cli_parses_attn_impl_flags():
    from dynamo_tpu.engine.__main__ import build_args

    a = build_args().parse_args(
        ["--attn-impl", "pallas", "--packed-attn-impl", "pallas"])
    assert a.attn_impl == "pallas"
    assert a.packed_attn_impl == "pallas"
    # default keeps the model family's choice
    d = build_args().parse_args([])
    assert d.attn_impl == "" and d.packed_attn_impl == ""
    with pytest.raises(SystemExit):
        build_args().parse_args(["--attn-impl", "triton"])


def test_mla_rejects_attn_impl_overrides():
    """MLA has no packed path, and its absorbed decode read has the
    impls ops/mla_attention.py dispatches (SUPPORTED_ATTN_IMPLS: no
    "jnp_bf16" form) — asking its worker for one it cannot run must be
    a config error, not a silent no-op the MDC then mis-advertises."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    def mla_cfg(**kw):
        return EngineConfig(model="tiny-mla", block_size=4,
                            num_blocks=32, max_blocks_per_seq=8, **kw)

    with pytest.raises(ValueError, match="packed_attn_impl"):
        JaxEngine(mla_cfg(packed_attn_impl="pallas_interpret"))
    with pytest.raises(ValueError, match="attn_impl"):
        JaxEngine(mla_cfg(attn_impl="jnp_bf16"))
    # what MLA runs passes through; "auto" names what it resolved to
    for ask, runs in (("jnp", "jnp"), ("pallas_interpret",
                                       "pallas_interpret"), ("", "jnp")):
        eng = JaxEngine(mla_cfg(attn_impl=ask))
        assert eng.model_cfg.attn_impl == runs
