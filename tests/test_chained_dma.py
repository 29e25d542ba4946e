"""Cross-tile/segment DMA chain parity (PR 17).

The decode kernel does not re-prime its double-buffered chunk DMA
chain at each row boundary: a global phase over the prefetched nchunks
plane (pallas_paged_attention.make_chunk_chain) keeps the chain
saturated across boundaries.  The packed-prefill kernel had the same
chain until PR 34; its blocks now come through Pallas's own pipeline
(a BlockSpec a block, indexed by the table from scalar prefetch), and a
skipped (query tile, key tile) step names the blocks already held — the
same layouts now exercise THAT handoff.  They are chosen so the HANDOFF itself is
what's exercised — the globally-first active pair not being (0, 0),
empty rows interleaved between active ones, boundaries landing mid-tile,
fully-padded tail tiles after the last chunk, single segments spanning
many tiles, committed prefix KV, and int8 scale lanes riding the same
chain.  All interpret-mode vs the XLA references; the existing
test_packed_pallas.py layouts stay untouched as the base contract.
Interpret-mode calls cost seconds each on CPU, so the stress variants
of already-covered handoffs carry the `slow` marker — tier-1 keeps one
layout per distinct mechanism (mid-tile boundaries, empty-row skip,
int8 scale lanes, uneven decode rows).
"""

import numpy as np
import pytest

# sibling-module reuse (the tests/ conftest puts tests/ on sys.path),
# same pattern test_packed_pallas.py uses for test_engine helpers
from test_packed_pallas import (
    _assert_packed_parity,
    _int8_decode_case,
    _packed_case,
)

from dynamo_tpu.ops.paged_attention import paged_attention_decode_jnp
from dynamo_tpu.ops.pallas_paged_attention import (
    paged_attention_decode_pallas,
)

pytestmark = pytest.mark.allow_slow_callbacks


@pytest.mark.parametrize("lens,bucket,kw", [
    # chunk_cols=1 maximizes chain length: every block is its own
    # chunk, every segment boundary is a chain handoff, and token_block
    # 8 puts several of those boundaries mid-tile
    ([5, 11, 3, 13], 32, dict(token_block=8, chunk_cols=1)),
    # leading + interleaved EMPTY rows: the prime must skip to the
    # first pair with work, and each handoff must skip the zero-chunk
    # rows (the next_seg suffix-scan), not stall on them
    ([0, 7, 0, 9, 0], 16, dict(token_block=8, chunk_cols=2)),
    # many tiny segments: a handoff at (nearly) every loop iteration
    # (slow: stress variant of the first layout; interpret-mode calls
    # cost seconds each on CPU and tier-1 has a wall-clock budget)
    pytest.param([2, 2, 2, 2, 2, 2, 2, 2], 16,
                 dict(token_block=4, chunk_cols=1),
                 marks=pytest.mark.slow),
    # one long segment over 4 token tiles: the chain crosses TILE
    # boundaries (same segment re-walked per tile) without draining
    pytest.param([29], 32, dict(token_block=8, chunk_cols=2),
                 marks=pytest.mark.slow),
    # short stream + fully padded tail tiles: the global chain must end
    # exactly at the last real chunk (no prefetch past the plane)
    pytest.param([3], 32, dict(token_block=8, chunk_cols=2),
                 marks=pytest.mark.slow),
])
def test_packed_chain_boundary_layouts(lens, bucket, kw):
    rng = np.random.default_rng(21)
    case = _packed_case(rng, lens, bucket=bucket)
    _assert_packed_parity(case, **kw)


@pytest.mark.slow
def test_packed_chain_committed_prefix_mid_tile():
    """Prefix-cache hits give segments different chunk counts for the
    same chunk length (ctx0 extends the context walk), so the chain's
    per-pair bases are uneven while segment boundaries land mid-tile."""
    rng = np.random.default_rng(22)
    case = _packed_case(rng, [6, 4, 6], ctx0=[13, 0, 5], mb=8,
                        bucket=16)
    _assert_packed_parity(case, token_block=8, chunk_cols=1)


def test_packed_chain_int8_scale_lanes():
    """Int8 cache: the k/v scale rows ride the SAME chained descriptors
    as the quantized blocks — a slot-phase bug would pair a block with
    the wrong scale row and the dequant would show it."""
    rng = np.random.default_rng(23)
    case = _packed_case(rng, [5, 0, 11, 7], bucket=32, int8=True,
                        ctx0=[2, 0, 0, 9])
    _assert_packed_parity(case, token_block=8, chunk_cols=1)


@pytest.mark.parametrize("kv_lens,bpc", [
    # uneven rows: the cross-row handoff happens at every row edge,
    # with chain phases that differ per row
    ([1, 24, 3], 2),
    # single-chunk rows between long ones: prime-once, immediate
    # handoff (slow: the uneven-rows layouts above/below already cross
    # every row edge; tier-1 wall-clock budget)
    pytest.param([24, 4, 24, 4], 2, marks=pytest.mark.slow),
    # chunk bigger than some rows' contexts: rows with n_chunks == 1
    # next to rows with several (slow: tier-1 wall-clock budget)
    pytest.param([17, 24, 5, 9], 3, marks=pytest.mark.slow),
])
def test_decode_chain_uneven_rows(kv_lens, bpc):
    """Decode kernel: the batch-dim chunk chain hands off row b -> b+1
    without draining; uneven kv_lens give each row a different chunk
    count (incl. partial last blocks)."""
    rng = np.random.default_rng(24)
    q, kc, vc, ks, vs, tables, lens = _int8_decode_case(rng, kv_lens)
    # layer 1 only — the layer index picks a cache slice, and a second
    # layer is a second interpret-mode trace (tier-1 wall-clock budget)
    for li in (1,):
        ref = paged_attention_decode_jnp(q, kc, vc, li, tables, lens,
                                         k_scale=ks, v_scale=vs)
        out = paged_attention_decode_pallas(
            q, kc, vc, li, tables, lens, interpret=True,
            k_scale=ks, v_scale=vs, blocks_per_chunk=bpc)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
