"""Window + NoPE-global attention beside routed and averaged shared
experts in one parallel block (models/cohere2.py): the paged path against
the plain float32 reference of benchmark/reference/cohere2.py, at tiny
widths on the CPU, and the two operands the paged pools' reads gained
for it (a lower bound a lane, a lower bound a query) against dense masks.

Window 16 over blocks of 16 (a ring of 2 blocks a lane), 8 query heads
over 2 KV heads of 16, 16 router outputs of which a share of 4 is held,
4 shared experts.  Everything is float32 here, so program and reference
differ by summation order only."""

import asyncio
import dataclasses
from functools import partial

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import cohere2 as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import cohere2, get_family
from dynamo_tpu.models.cohere2 import Cohere2Config
from dynamo_tpu.models.llama import rope
from dynamo_tpu.models.moe import moe_dispatch_dense, moe_dispatch_visited
from dynamo_tpu.ops.packed_prefill import packed_prefill_attention
from dynamo_tpu.ops.paged_attention import paged_attention_decode
from dynamo_tpu.ops.window_attention import (
    ring_blocks,
    ring_decode_table,
    ring_table,
    window_prefill_flash,
    write_ring_prompt,
    write_ring_token,
)
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = Cohere2Config(dtype=jnp.float32, experts_held=(4, 4))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation (flash
# chunks, blocked gathers), measured at 4e-7 on logits of magnitude 0.6
# (the tied head of a 0.02 embedding); 5e-6 leaves ten times that for
# another platform's reductions, is under what bfloat16 costs in the
# router (3e-5: the weights' own rounding; a flipped pick costs more) or
# in the norm (1e-3), and far under the smallest effect of a left-out
# detail (0.16, below)
TOL = 5e-6
TABLE_IDS = [3, 7, 9, 11, 13, 2, 5]
PREFILL = jax.jit(cohere2.prefill, static_argnums=(1,))
DECODE = jax.jit(cohere2.decode, static_argnums=(1,))


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES):
    return tuple(
        jnp.zeros(s, d) for s, d in zip(
            cohere2.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
            cohere2.kv_cache_dtypes(cfg)))


def _table():
    table = np.zeros(TABLE, np.int32)
    table[:7] = TABLE_IDS
    return table


def _lanes_of(lane, x, dtype=np.int32):
    a = np.zeros((LANES,) + np.shape(x), dtype)
    a[lane] = x
    return jnp.asarray(a)


def prefill_chunks(params, cfg, kv, toks, prompt_len, lane, bucket,
                   prefill=PREFILL):
    pos, table = 0, _table()
    while pos < prompt_len:
        chunk = min(bucket, prompt_len - pos)
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = prefill(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk),
            lanes=jnp.int32(lane))
        pos += chunk
    return np.asarray(logits), kv


def decode_steps(params, cfg, kv, toks, start, lane):
    out, valid = [], _lanes_of(lane, True, bool)
    for step in range(start, len(toks)):
        logits, kv = DECODE(
            params, cfg, kv, _lanes_of(lane, toks[step]),
            _lanes_of(lane, step), _lanes_of(lane, _table()),
            _lanes_of(lane, step), valid=valid)
        out.append(np.asarray(logits)[lane])
    return out, kv


@pytest.fixture(scope="module")
def model():
    params = cohere2.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 100)
    full = np.asarray(ref.reference_logits(params, TINY, toks.tolist()))
    return params, toks, full


@pytest.mark.parametrize("prompt_len,bucket", [
    (12, 16),      # inside the window: the band is the causal mask
    (50, 64),      # one shot over three windows: the band inside a chunk
    (70, 32),      # chunks of 32, 32, 6: the ring (2 blocks) wraps, the
                   #   second and third chunk read the tail the first left
    (70, 16),      # chunks of a window: every chunk reads a whole tail
])
def test_prefill_matches_reference_logits(model, prompt_len, bucket):
    params, toks, full = model
    assert ring_blocks(TINY.sliding_window, BS) == 2
    got, kv = prefill_chunks(params, TINY, fresh_cache(), toks,
                             prompt_len, 2, bucket)
    np.testing.assert_allclose(got, full[prompt_len - 1], rtol=0, atol=TOL)
    # the window pools hold a ring a lane and block 0, whatever the length
    assert kv[2].shape[2] == 1 + LANES * 2


def test_decode_through_the_cache_past_the_wrap(model):
    """70 prompt tokens, then 30 decode steps: positions 70..99 cross
    the block boundaries at 80 and 96 and wrap the ring twice."""
    params, toks, full = model
    _, kv = prefill_chunks(params, TINY, fresh_cache(), toks, 70, 2, 32)
    got, _ = decode_steps(params, TINY, kv, toks, 70, 2)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[70 + i], rtol=0, atol=TOL)


def test_a_reused_lane_and_an_idle_lane(model):
    """Lane 2 serves a first sequence, then a second one from position
    0 over what the first left in its ring and blocks; lane 1, idle
    through all of it (valid False, a table of zeros), keeps its ring
    bit for bit."""
    params, toks, full = model
    kv = fresh_cache()
    other = np.random.default_rng(5).integers(3, TINY.vocab_size, 90)
    marker = jnp.full_like(kv[2][:, :, 3:5], 7.0)      # lane 1's ring
    kv = kv[:2] + (kv[2].at[:, :, 3:5].set(marker),
                   kv[3].at[:, :, 3:5].set(marker)) + kv[4:]
    _, kv = prefill_chunks(params, TINY, kv, other, 60, 2, 32)
    _, kv = decode_steps(params, TINY, kv, other, 60, 2)
    got, kv = prefill_chunks(params, TINY, kv, toks, 40, 2, 32)
    np.testing.assert_allclose(got, full[39], rtol=0, atol=TOL)
    rows, kv = decode_steps(params, TINY, kv, toks[:60], 40, 2)
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, full[40 + i], rtol=0, atol=TOL)
    assert bool((kv[2][:, :, 3:5] == 7.0).all())
    assert bool((kv[3][:, :, 3:5] == 7.0).all())


def test_fused_burst_chains_the_reference_tokens(model):
    """decode_multi from position 60 for 8 steps (the boundary at 64
    lies inside the burst) chains the reference's own greedy tokens."""
    params, toks, _ = model
    _, kv = prefill_chunks(params, TINY, fresh_cache(), toks, 60, 2, 32)
    burst, _ = cohere2.decode_multi(
        params, TINY, kv, _lanes_of(2, toks[60]), _lanes_of(2, 60),
        _lanes_of(2, _table()), _lanes_of(2, 60), 8,
        valid=_lanes_of(2, True, bool))
    got = np.asarray(burst)[:, 2].tolist()
    logits = ref.reference_logits(params, TINY,
                                  toks[:61].tolist() + got[:-1])
    assert [int(jnp.argmax(logits[60 + i])) for i in range(8)] == got


def test_packed_stream_of_two_rows(model):
    """prefill_packed: row 0 continues a prompt at position 32 (a tail
    to read, lane 3), row 1 starts another at 0 (lane 0) behind it in
    one stream of 64 with a padded tail; each row's last logits are the
    reference's."""
    params, toks, full = model
    other = np.random.default_rng(6).integers(3, TINY.vocab_size, 20)
    want = np.asarray(ref.reference_logits(params, TINY, other.tolist()))
    kv = fresh_cache()
    _, kv = prefill_chunks(params, TINY, kv, toks, 32, 3, 32)
    n0, n1 = 25, 20
    stream = np.zeros(64, np.int32)
    stream[:n0], stream[n0:n0 + n1] = toks[32:32 + n0], other
    pos = np.zeros(64, np.int32)
    pos[:n0], pos[n0:n0 + n1] = 32 + np.arange(n0), np.arange(n1)
    seg = np.zeros(64, np.int32)
    seg[n0:n0 + n1] = 1
    valid = np.arange(64) < n0 + n1
    tables = np.zeros((2, TABLE), np.int32)
    tables[0] = _table()
    tables[1, :3] = [20, 21, 22]
    logits, _ = cohere2.prefill_packed(
        params, TINY, kv, jnp.asarray(stream), jnp.asarray(pos),
        jnp.asarray(seg), jnp.asarray(tables),
        jnp.asarray([n0 - 1, n0 + n1 - 1], jnp.int32), jnp.asarray(valid),
        lanes=jnp.asarray([3, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), full[32 + n0 - 1],
                               rtol=0, atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), want[n1 - 1],
                               rtol=0, atol=TOL)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of: the window's
    bound, rotary on the global layer (it has none), the shared experts'
    sum not divided by their number, the norm without its mean, and
    rotate-half pairs in place of interleaved ones.  Smallest effect
    measured: 0.16 (rotary on the one global layer), against TOL 5e-6."""
    params, toks, full = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    got, _ = prefill_chunks(params, TINY, fresh_cache(), toks, 70, 2, 32)
    assert float(np.abs(got - without[69]).max()) > 100 * TOL, detail
    assert float(np.abs(full - without).max()) > 100 * TOL


def test_rotary_pairs_are_interleaved():
    """rope_interleaved rotates (2i, 2i + 1) by the angle llama.rope
    gives the pair (i, i + hd / 2): the two are each other's
    permutation, and differ on the same input."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 16))
    pos = jnp.arange(5) + 7
    got = cohere2.rope_interleaved(x, pos, 5e4)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    half = rope(x[..., perm], pos, 5e4)          # de-interleave, rotate
    np.testing.assert_allclose(np.asarray(got[..., perm]),
                               np.asarray(half), atol=1e-6)
    assert float(jnp.abs(got - rope(x, pos, 5e4)).max()) > 0.1


@pytest.mark.parametrize("piece", ["norm", "router"])
def test_bfloat16_in_a_float32_piece_breaks_agreement(model, piece,
                                                      monkeypatch):
    """The norm and the router are float32 inside whatever the weights'
    dtype; the tolerance notices either computed in bfloat16."""
    params, toks, full = model
    bf = jnp.bfloat16
    if piece == "norm":
        real = cohere2.layer_norm
        monkeypatch.setattr(cohere2, "layer_norm", lambda x, w, eps: real(
            x.astype(bf).astype(jnp.float32), w.astype(bf), eps
        ).astype(bf).astype(jnp.float32))
    else:
        real = cohere2.ds_router
        monkeypatch.setattr(cohere2, "ds_router", lambda layer, cfg, x: real(
            {"moe_gate": layer["moe_gate"].astype(bf)}, cfg, x.astype(bf)))
    got, _ = prefill_chunks(params, TINY, fresh_cache(), toks, 70, 2, 32,
                            prefill=cohere2.prefill)   # traced anew
    assert float(np.abs(got - full[69]).max()) > 4 * TOL, piece


# the kernel's body on the CPU: the form a decode step takes on the chip
_visited = partial(moe_dispatch_visited, interpret=True)


@pytest.mark.parametrize("dispatch", [moe_dispatch_dense, _visited],
                         ids=["moe_dispatch_dense", "moe_dispatch_visited"])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """The routed parts of the four shares of 4 experts plus the shared
    experts counted ONCE add up to what the program gives with all 16
    held, and to the reference's uncut layer; a share alone equals the
    reference given the same share; the shared part is the mean of its
    four experts."""
    whole = dataclasses.replace(TINY, experts_held=None)
    params = cohere2.init_params(whole, jax.random.PRNGKey(3))
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (9, whole.d_model))
    w, ids = cohere2.ds_router(layer, whole, x)
    rw, rids = ref._route(whole, layer, x)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    shared = cohere2._shared(layer, whole, x)
    np.testing.assert_allclose(np.asarray(shared),
                               np.asarray(ref._shared(whole, layer, x)),
                               atol=1e-5)
    uncut = dispatch(layer, whole, x, w, ids) + shared
    np.testing.assert_allclose(
        np.asarray(uncut),
        np.asarray(ref._routed(whole, layer, x, w, ids)
                   + ref._shared(whole, layer, x)), atol=1e-5)
    total = shared
    for rank in range(4):
        cfg = dataclasses.replace(whole, experts_held=(4 * rank, 4))
        held = {k: (v[4 * rank:4 * rank + 4] if k.startswith("moe_w_")
                    else v) for k, v in layer.items()}
        part = dispatch(held, cfg, x, w, ids)
        np.testing.assert_allclose(
            np.asarray(part),
            np.asarray(ref._routed(cfg, held, x, w, ids)), atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_op_scopes_name_the_parts_of_a_block(program):
    """What a profiler groups device ops by: both programs carry the
    block's scopes, the two reads by kind and the shared experts'."""
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: cohere2.init_params(
        TINY, jax.random.PRNGKey(0)))
    kv = tuple(S(s, d) for s, d in zip(
        cohere2.kv_cache_shapes(TINY, 40, BS, lanes=LANES),
        cohere2.kv_cache_dtypes(TINY)))
    i32 = jnp.int32
    if program == "prefill":
        low = PREFILL.lower(
            params, TINY, kv, S((32,), i32), S((32,), i32),
            S((TABLE,), i32), S((), i32), S((), i32), lanes=S((), i32))
    else:
        low = DECODE.lower(
            params, TINY, kv, S((LANES,), i32), S((LANES,), i32),
            S((LANES, TABLE), i32), S((LANES,), i32),
            valid=S((LANES,), jnp.bool_))
    text = low.as_text(debug_info=True)
    for scope in ("dyn.attn_qkv", "dyn.kv_write", "dyn.attn_window",
                  "dyn.attn_global", "dyn.attn_out", "dyn.moe_router",
                  "dyn.moe_dispatch", "dyn.moe_shared", "dyn.lm_head"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# the operands the paged pools' reads gained, against dense masks
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v, seen):
    """q [T, nh, hd], k / v [S, nkv, hd], seen [T, S] -> [T, nh, hd]."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(jnp.float32(q.shape[2]))
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    return jnp.einsum("hij,jhd->ihd", p, v)


def _pool(key, blocks, nkv, hd, bs, dtype):
    return jax.random.normal(key, (2, nkv, blocks, hd, bs), dtype)


def _cells(pool, layer, table):
    """[L, nkv, nb, hd, bs] + [C] -> [C * bs, nkv, hd] in table order."""
    g = pool[layer][:, table]                     # [nkv, C, hd, bs]
    return g.transpose(1, 3, 0, 2).reshape(-1, g.shape[0], g.shape[2])


@pytest.mark.parametrize("impl,bs,hd,dtype", [
    ("jnp", 16, 16, jnp.float32),
    ("pallas_interpret", 128, 128, jnp.bfloat16),
])
def test_decode_lower_bound_against_a_dense_mask(impl, bs, hd, dtype):
    """paged_attention_decode with `kv_lo`: lane b attends its table's
    positions kv_lo[b] <= pos < kv_lens[b]; a lane of length 0 reads
    nothing; without the operand the answer is the unbounded one."""
    nkv, nh, B, width = 2, 4, 3, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    kc, vc = (_pool(k, 14, nkv, hd, bs, dtype) for k in keys[:2])
    q = jax.random.normal(keys[2], (B, nh, hd), dtype)
    tables = jnp.asarray([[3, 5, 7, 2], [9, 1, 4, 6], [8, 10, 11, 12]],
                         jnp.int32)
    lens = jnp.asarray([3 * bs + 5, 2 * bs, 0], jnp.int32)
    lo = jnp.asarray([bs - 3, 7, 0], jnp.int32)
    got = paged_attention_decode(q, kc, vc, 1, tables, lens, impl=impl,
                                 kv_lo=lo)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    at = jnp.arange(width * bs)
    for b in range(2):
        k = _cells(kc, 1, tables[b]).astype(jnp.float32)
        v = _cells(vc, 1, tables[b]).astype(jnp.float32)
        seen = ((at >= lo[b]) & (at < lens[b]))[None]
        want = _dense_attention(q[b:b + 1].astype(jnp.float32), k, v, seen)
        np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                   np.asarray(want[0]), atol=tol)
    free = paged_attention_decode(q, kc, vc, 1, tables, lens, impl=impl)
    assert float(jnp.abs(free[0].astype(jnp.float32)
                         - got[0].astype(jnp.float32)).max()) > 10 * tol


@pytest.mark.parametrize("impl,bs,hd,dtype,T", [
    ("xla", 16, 16, jnp.float32, 64),
    ("pallas_interpret", 128, 128, jnp.bfloat16, 512),
])
def test_packed_lower_bound_against_a_dense_mask(impl, bs, hd, dtype, T):
    """packed_prefill_attention with `lower`: a token attends its row's
    positions lower[t] <= pos <= positions[t]; two rows, a padded tail;
    the kernel's tile skip (tiles of 128 queries x 2 blocks here) leaves
    out whole tiles under the band and changes nothing."""
    nkv, nh, width = 2, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    kc, vc = (_pool(k, 20, nkv, hd, bs, dtype) for k in keys[:2])
    q = jax.random.normal(keys[2], (T, nh, hd), dtype)
    tables = jnp.asarray([np.arange(1, 9), np.arange(9, 17)], jnp.int32)
    n0 = T // 2 + 5
    n1 = T - n0 - 9
    seg = jnp.asarray([0] * n0 + [1] * (T - n0), jnp.int32)
    pos = jnp.concatenate([3 * bs + jnp.arange(n0), bs + jnp.arange(T - n0)])
    valid = jnp.arange(T) < n0 + n1
    lower = jnp.maximum(pos - 2 * bs - 3, 5)
    if impl != "xla":
        from dynamo_tpu.ops.pallas_packed_prefill import (
            packed_prefill_attention_pallas,
        )
        got = packed_prefill_attention_pallas(
            q, kc, vc, 1, tables, seg, pos, valid, interpret=True,
            lower=lower, token_block=128, chunk_cols=2)
    else:
        got = packed_prefill_attention(q, kc, vc, 1, tables, seg, pos,
                                       valid, impl=impl, lower=lower)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    at = jnp.arange(width * bs)
    for s, (a, b) in enumerate([(0, n0), (n0, n0 + n1)]):
        k = _cells(kc, 1, tables[s]).astype(jnp.float32)
        v = _cells(vc, 1, tables[s]).astype(jnp.float32)
        seen = (at[None] >= lower[a:b, None]) & (at[None] <= pos[a:b, None])
        want = _dense_attention(q[a:b].astype(jnp.float32), k, v, seen)
        np.testing.assert_allclose(np.asarray(got[a:b], np.float32),
                                   np.asarray(want), atol=tol)
    assert float(jnp.abs(got[n0 + n1:].astype(jnp.float32)).max()) == 0.0


def test_ring_as_a_table_writes_the_ring_ops_cells():
    """The ring seen as a block table of period W puts a prompt chunk
    and a decode token where write_ring_prompt / write_ring_token put
    them; ring_decode_table starts at the oldest live block."""
    from dynamo_tpu.ops.packed_prefill import write_packed_kv
    from dynamo_tpu.ops.paged_attention import write_token_kv

    window, nkv, hd, W = 16, 2, 8, 2
    shape = (1, nkv, 1 + LANES * W, hd, BS)
    k = jax.random.normal(jax.random.PRNGKey(0), (40, nkv, hd))
    zeros = jnp.zeros(shape)
    lanes, ctx, true = jnp.asarray([2]), jnp.asarray([23]), jnp.asarray([37])
    want, _ = write_ring_prompt(zeros, zeros, 0, k[None], k[None], lanes,
                                ctx, true, window)
    pos = 23 + jnp.arange(40)
    valid = jnp.arange(40) < 37
    kept = valid & (pos > 23 + 36 - window)
    got, _ = write_packed_kv(zeros, zeros, 0, k, k, ring_table(lanes, W, 8),
                             jnp.zeros(40, jnp.int32), pos, kept)
    np.testing.assert_array_equal(np.asarray(got[:, :, 1:]),
                                  np.asarray(want[:, :, 1:]))
    positions = jnp.asarray([5, 40, 63, 0])
    ok = jnp.asarray([True, True, True, False])
    want, _ = write_ring_token(zeros, zeros, 0, k[:4], k[:4], positions,
                               window, ok)
    rings = jnp.where(ok[:, None], ring_table(jnp.arange(4), W, 8), 0)
    got, _ = write_token_kv(zeros, zeros, 0, k[:4], k[:4], rings, positions)
    np.testing.assert_array_equal(np.asarray(got[:, :, 1:]),
                                  np.asarray(want[:, :, 1:]))
    table, lens, lo = ring_decode_table(positions, ok, window, BS)
    # lane 1 at position 40: live 25..40, oldest live block 1 (cells
    # 16..31) = ring block 1 + 1 * 2 + 1, then block 2 = ring block + 0
    assert table[1].tolist() == [4, 3]
    assert (int(lens[1]), int(lo[1])) == (40 + 1 - 16, 25 - 16)
    assert (int(lens[0]), int(lo[0])) == (6, 0) and int(lens[3]) == 0


def test_window_prefill_flash_against_a_dense_band():
    """[ring's tail || chunk] under the band = dense attention over the
    row's last `window` positions, for a row that continues at 37 (a
    tail that wraps the ring) and one that starts at 0 in the same
    stream, whatever else the rings hold."""
    window, nkv, nh, hd, W = 16, 2, 4, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    kr = jax.random.normal(keys[0], (2, nkv, 1 + LANES * W, hd, BS))
    vr = jax.random.normal(keys[1], (2, nkv, 1 + LANES * W, hd, BS))
    hist_k = jax.random.normal(keys[2], (37, nkv, hd))
    hist_v = hist_k * 0.5 + 1.0
    lanes = jnp.asarray([3, 1])
    kr, vr = write_ring_prompt(kr, vr, 1, hist_k[None], hist_v[None],
                               lanes[:1], jnp.asarray([0]),
                               jnp.asarray([37]), window)
    T, n0, n1 = 64, 30, 25
    q = jax.random.normal(keys[3], (T, nh, hd))
    k = jax.random.normal(keys[4], (T, nkv, hd))
    v = k * 0.25 - 1.0
    seg = jnp.asarray([0] * n0 + [1] * (T - n0), jnp.int32)
    pos = jnp.concatenate([37 + jnp.arange(n0), jnp.arange(T - n0)])
    valid = jnp.arange(T) < n0 + n1
    got = window_prefill_flash(q, k, v, kr, vr, 1, lanes, seg, pos, valid,
                               window)
    for (a, b), hk, hv in [((0, n0), hist_k, hist_v),
                           ((n0, n0 + n1), hist_k[:0], hist_v[:0])]:
        kk = jnp.concatenate([hk, k[a:b]])
        vv = jnp.concatenate([hv, v[a:b]])
        i = len(hk) + jnp.arange(b - a)
        dist = i[:, None] - jnp.arange(len(kk))[None]
        want = _dense_attention(q[a:b], kk, vv,
                                (dist >= 0) & (dist < window))
        np.testing.assert_allclose(np.asarray(got[a:b]), np.asarray(want),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _engine(**over):
    sizes = dict(model_config=TINY, block_size=BS, num_blocks=64,
                 max_blocks_per_seq=TABLE, max_num_seqs=LANES,
                 prefill_buckets=(16, 32), seed=7)
    sizes.update(over)
    return JaxEngine(EngineConfig(**sizes))


async def _generate(eng, rid, prompt, n):
    req = PreprocessedRequest(
        token_ids=prompt, request_id=rid,
        sampling=SamplingOptions(temperature=0.0, seed=0),
        stop=StopConditions(max_tokens=n, ignore_eos=True))
    toks = []
    async for out in eng.generate(req):
        assert not out.error, out.error
        toks.extend(out.token_ids)
    return toks


@pytest.mark.parametrize("packed", [True, False])
async def test_engine_serves_the_family_and_counts(packed):
    """JaxEngine end to end through get_family, by the packed planner
    (lanes ride with the stream) and by the padded rows: three requests
    at once emit the reference's greedy tokens; the window pools do not
    grow with length; the counters are fed."""
    eng = _engine(prefill_packed=packed)
    assert get_family(eng.model_cfg) is cohere2
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    lens = (50, 37, 70)
    prompts = [rng.integers(3, TINY.vocab_size, n).tolist() for n in lens]
    outs = await asyncio.gather(*[
        _generate(eng, f"r{i}", p, n)
        for i, (p, n) in enumerate(zip(prompts, (30, 20, 25)))])
    for p, toks in zip(prompts, outs):
        full = ref.reference_logits(eng.params, eng.model_cfg,
                                    p + toks[:-1])
        assert [int(jnp.argmax(full[len(p) - 1 + j]))
                for j in range(len(toks))] == toks
    m = eng.metrics
    assert eng.kv[2].shape[2] == 1 + LANES * 2
    # window layers hold at most 2 blocks a lane; a uniform cache up to 6
    assert 0 < m["kv_window_block_steps"] < 0.5 * m["kv_uniform_block_steps"]
    assert 0 < m["decode_attn_live_blocks"] <= m["decode_attn_read_blocks"]
    assert m["moe_picks.prefill"] == sum(lens) * TINY.n_layers * 4
    assert 0 < m["moe_picks_held.prefill"] < m["moe_picks.prefill"]
    assert 0 < m["moe_picks_held.decode"] < m["moe_picks.decode"]
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
    # one layer of each kind: sum of min(position + 1, 16) and of
    # position + 1 over the prompts' tokens
    assert m["attn_pairs_global.prefill"] == sum(n * (n + 1) // 2
                                                 for n in lens)
    assert m["attn_pairs_window.prefill"] == sum(
        136 + (n - 16) * 16 for n in lens)
    assert m["prefill_window_kernel_tokens"] == 0      # the CPU: the scan
    await eng.close()


async def test_preempted_sequence_resumes_with_the_same_tokens():
    """A pool too small for two long answers: one sequence is preempted,
    its lane's ring rewritten by the replay, and it emits what it emits
    alone."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, TINY.vocab_size, 40).tolist()
               for _ in range(2)]
    alone = _engine()
    want = [await _generate(alone, f"a{i}", p, 50)
            for i, p in enumerate(prompts)]
    await alone.close()
    tight = _engine(num_blocks=9)        # 8 usable: two x 6 do not fit
    got = await asyncio.gather(*[_generate(tight, f"t{i}", p, 50)
                                 for i, p in enumerate(prompts)])
    assert tight.metrics["preemptions"] > 0
    assert got == want
    await tight.close()


def test_host_counts_follow_the_resolved_impl():
    """decode_block_counts: with the kernel both kinds move their live
    blocks; the jnp gather moves every lane's table width and ring."""
    ctx = np.asarray([5, 40, 70])
    args = (TINY, ctx, 2, BS, LANES, TABLE)
    kern = cohere2.decode_block_counts(*args, "pallas")
    gath = cohere2.decode_block_counts(*args, "jnp")
    assert kern["decode_attn_live_blocks"] == kern["decode_attn_read_blocks"]
    assert gath["decode_attn_read_blocks"] == 2 * LANES * (TABLE + 3 * 2)
    assert kern["decode_attn_live_blocks"] == gath["decode_attn_live_blocks"]
    # positions 5, 6 | 40, 41 | 70, 71: uniform 1+1+3+3+5+5, rings 1+1+2+2+2+2
    assert kern["kv_uniform_block_steps"] == 18
    assert kern["kv_window_block_steps"] == 10
    empty = cohere2.prefill_token_counts(TINY, 0, 0, 0)
    assert set(empty) == {"attn_pairs_window.prefill",
                          "attn_pairs_global.prefill",
                          "prefill_window_kernel_tokens"}


def test_unsupported_features_refuse_or_fall_back():
    """tp > 1, KVBM tiers and a disagg pull refuse the configuration;
    int8 cache, speculation and prefix caching fall back (warned);
    LoRA refuses: no silently wrong answer on any of them.  Packed
    prefill is carried."""
    with pytest.raises(ValueError, match="does not carry tp"):
        _engine(tp=2)
    with pytest.raises(ValueError, match="does not carry kvbm"):
        _engine(host_cache_blocks=8)
    with pytest.raises(ValueError, match="does not carry disagg"):
        JaxEngine(EngineConfig(
            model_config=TINY, block_size=BS, num_blocks=16,
            max_blocks_per_seq=TABLE, max_num_seqs=LANES),
            kv_pull_fn=lambda p: None)
    with pytest.raises(ValueError, match="LoRA"):
        _engine(lora_max_adapters=2)
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert eng._packed_prefill_ok
    assert set(cohere2.UNSUPPORTED) == {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "kvbm", "disagg", "tp"}
