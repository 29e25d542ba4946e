"""Block-selecting sparse attention beside lightning linear-attention
layers under muP scaling (models/minicpm_sala.py,
ops/block_sparse_attention.py): the paged path against the plain float32
reference of benchmark/reference/minicpm_sala.py, at tiny widths on the
CPU.

d 64, 4 query heads over 2 KV heads of 16 (two groups of two), 4 layers
(sparse, lightning, lightning, sparse), pages of 16; compressed keys of
4 tokens every 2, blocks of 8, 4 chosen (the first and the two local
ones forced), `dense_len` 48 (six blocks: more than are chosen, so the
switch shows), chunks of 8 of the lightning rule.  Everything is float32
here, so program and reference differ by summation order only: the
reference is the token-by-token recurrence and a stable sort, the
program the chunked form and a bisection."""

import asyncio
import dataclasses

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import minicpm_sala as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import PRESETS, get_family
from dynamo_tpu.models import minicpm_sala as sala
from dynamo_tpu.models.llama import _qkv, rms_norm
from dynamo_tpu.ops import block_sparse_attention as bsa
from dynamo_tpu.ops.ssm import ssd_chunked, ssd_step
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = dataclasses.replace(PRESETS["tiny-sala"], dtype=jnp.float32,
                           dense_len=48)
PREFILL = jax.jit(sala.prefill, static_argnums=1)
PREFILL_BATCHED = jax.jit(sala.prefill_batched, static_argnums=1)
DECODE = jax.jit(sala.decode, static_argnums=1)
DECODE_MULTI = jax.jit(sala.decode_multi, static_argnums=(1, 7))
BS, LANES, TABLE = 16, 4, 10
PAGES = [3, 7, 9, 11, 13, 2, 5, 14, 6]
# float32 on both sides: what is left is the order of summation (the
# chunked rule against a scan over tokens, blocked gathers, an online
# softmax), measured at 1e-6 on logits of magnitude 0.7; 5e-5 leaves
# room for another platform's reductions and is two orders under the
# smallest effect of a left-out detail (below)
TOL = 5e-5
N = 130          # positions of the module's sequence: 17 blocks of 8


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES, dirty=True):
    """`dirty`: the state full of ones, as a lane that another sequence
    held would be (no program clears a lane)."""
    kv = [jnp.zeros(s, d) for s, d in zip(
        sala.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
        sala.kv_cache_dtypes(cfg))]
    if dirty:
        kv[3] = jnp.ones(kv[3].shape, kv[3].dtype)
    return tuple(kv)


def lanes_of(x, lane, dtype=np.int32):
    a = np.zeros((LANES,) + np.shape(x), dtype)
    a[lane] = x
    return jnp.asarray(a)


def the_table():
    table = np.zeros(TABLE, np.int32)
    table[:len(PAGES)] = PAGES
    return table


def prefill_chunks(params, cfg, toks, chunks, lane=2, bucket=None, kv=None):
    """The prompt through the family's own program, one chunk after the
    other (each padded to `bucket`) -> (last chunk's logits, cache)."""
    kv = fresh_cache(cfg) if kv is None else kv
    bucket = bucket or max(chunks)
    pos, logits = 0, None
    for chunk in chunks:
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = PREFILL(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(the_table()), jnp.int32(pos), jnp.int32(chunk),
            lanes=jnp.int32(lane))
        pos += chunk
    return np.asarray(logits), kv


def decode_steps(params, cfg, kv, toks, start, lane=2):
    """Teacher-forced decode of toks[start:] -> (logits a step, cache)."""
    out, valid = [], lanes_of(True, lane, bool)
    for p in range(start, len(toks)):
        logits, kv = DECODE(params, cfg, kv, lanes_of(toks[p], lane),
                            lanes_of(p, lane), lanes_of(the_table(), lane),
                            lanes_of(p, lane), valid=valid)
        out.append(np.asarray(logits)[lane])
    return out, kv


@pytest.fixture(scope="module")
def model():
    params = sala.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, N)
    taps = []
    full = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                           taps=taps))
    return params, toks, full, taps


def test_layer_pattern_is_the_published_one():
    big = PRESETS["minicpm-sala-9b"]
    assert big.layers_of(sala.SPARSE) == (0, 9, 16, 17, 22, 29, 30, 31)
    assert len(big.layers_of(sala.LIGHTNING)) == 24
    # the benchmark's cut: the published entries 9-20, 1 : 3
    cut = big.layer_kinds[9:21]
    assert cut == (1, 0, 0, 0, 0, 0, 0, 1, 1, 0, 0, 0)
    assert big.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(TINY, n_layers=5)
    with pytest.raises(ValueError, match="forced blocks"):
        sala.kv_cache_shapes(dataclasses.replace(TINY, topk=2), 4, BS)
    with pytest.raises(ValueError, match="whole"):
        sala.kv_cache_shapes(dataclasses.replace(TINY, kernel_size=3), 4,
                             BS)


def test_paged_path_matches_reference_logits(model):
    """A prompt of 101 tokens prefilled as 37 + 40 + 24 (three programs:
    chunk edges off the stride, the block and the page; the state
    carried twice; queries on both sides of `dense_len`), then 29 decode
    steps across two page boundaries, on a lane that was dirty."""
    params, toks, full, _ = model
    logits, kv = prefill_chunks(params, TINY, toks, (37, 40, 24), bucket=48)
    np.testing.assert_allclose(logits, full[100], rtol=0, atol=TOL)
    got, kv = decode_steps(params, TINY, kv, toks, 101)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[101 + i], rtol=0, atol=TOL)
    # the other lanes are as they were: ones
    assert float(jnp.abs(kv[3][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 3] - 1).max()) == 0.0
    # the device's counts grew: pages read in decode, pairs in prefill
    assert int(kv[4][0]) > 0 and int(kv[4][1]) > 0


def _program_choice(params, cfg, kv, toks, positions):
    """The first layer's chosen sets through the op, over the compressed
    keys the program wrote (its input is the embedding on both sides)."""
    layer = params["layers"][0]
    x = params["embedding"][jnp.asarray(toks[positions])] * cfg.scale_emb
    h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
    q, _, _ = _qkv(layer, cfg, h, None)
    return np.asarray(bsa.prefill_block_choice(
        q, kv[2], 0, jnp.asarray(the_table()),
        jnp.asarray(positions, jnp.int32),
        jnp.ones(len(positions), bool), cfg.sizes))


def test_chosen_sets_are_the_references(model):
    """Every query's set, a KV group, block for block (float32 on both
    sides: bisection on bit patterns against a stable sort): everything
    up to `dense_len` by POSITION, `topk` blocks past it with the first
    and the two local ones among them, and the two groups choosing
    differently."""
    params, toks, _, taps = model
    _, kv = prefill_chunks(params, TINY, toks, (48, 48, 34))
    positions = np.arange(N)
    got = _program_choice(params, TINY, kv, toks, positions)
    want = np.asarray(taps[0]["chosen"])
    assert (got[:, :, :want.shape[-1]] == want).all()
    assert not got[:, :, want.shape[-1]:].any()
    own = positions // 8
    n = got.sum(-1)
    dense = positions + 1 <= TINY.dense_len
    assert (n[dense] == (own[dense] + 1)[:, None]).all()       # attends all
    assert (n[~dense] == TINY.topk).all()
    sparse = np.flatnonzero(~dense)
    assert got[sparse, :, 0].all()                             # block 0
    for t in sparse:
        assert got[t, :, own[t]].all() and got[t, :, own[t] - 1].all()
    assert (got[sparse, 0] != got[sparse, 1]).any()


def _choose(q, ck_seq, t, sizes):
    t = jnp.asarray(t, jnp.int32)
    return np.asarray(bsa.choose_blocks(q, ck_seq, t,
                                        jnp.ones(t.shape, bool), sizes))


@pytest.mark.parametrize("case", ["ties", "few_blocks", "dense_switch",
                                  "invalid_row"])
def test_block_choice_by_hand(case):
    sizes = bsa.BlockSizes(4, 2, 8, 1, 16, 4, 32)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 16)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((64, 2, 16)), jnp.float32)
    if case == "ties":
        # equal compressed keys: every visible window scores alike, so
        # every block that touches one ties, and the lower index wins:
        # forced 0, 11, 12 and the first free block, 1
        got = _choose(q, jnp.zeros_like(ck), [100], sizes)
        assert np.flatnonzero(got[0, 0]).tolist() == [0, 1, 11, 12]
        assert (got[0, 0] == got[0, 1]).all()
    elif case == "few_blocks":
        # past dense_len with no more blocks than topk: attends all
        short = sizes._replace(dense_len=8)
        got = _choose(q, ck, [30], short)
        assert np.flatnonzero(got[0, 0]).tolist() == [0, 1, 2, 3]
    elif case == "dense_switch":
        got = _choose(jnp.tile(q, (2, 1, 1)), ck, [31, 32], sizes)
        assert got[0].sum(-1).tolist() == [4, 4]      # 4 blocks: all
        at = _choose(jnp.tile(q, (2, 1, 1)), ck, [63, 64],
                     sizes._replace(dense_len=64))
        assert at[0].sum(-1).tolist() == [8, 8]       # t + 1 = dense_len
        assert at[1].sum(-1).tolist() == [4, 4]
    else:
        got = bsa.choose_blocks(q, ck, jnp.asarray([100]),
                                jnp.asarray([False]), sizes)
        assert not np.asarray(got).any()


@pytest.mark.parametrize("chunks,bucket", [
    ((130,), 144), ((37, 40, 53), 64), ((16,) * 8 + (2,), 16),
    ((5, 7, 3, 33, 1, 81), 96)])
def test_any_chunking_gives_the_one_shot_result(model, chunks, bucket):
    """Chunk edges off the stride (2), the block (8) and the page (16),
    a chunk of one token: the last logits and the three paged members
    are the one-shot prefill's."""
    params, toks, full, _ = model
    want_logits, want = prefill_chunks(params, TINY, toks, (N,), bucket=144)
    logits, kv = prefill_chunks(params, TINY, toks, chunks, bucket=bucket)
    np.testing.assert_allclose(logits, full[N - 1], rtol=0, atol=TOL)
    np.testing.assert_allclose(logits, want_logits, rtol=0, atol=TOL)
    pages = jnp.asarray(PAGES)
    for m in (0, 1):
        np.testing.assert_allclose(np.asarray(kv[m][:, :, pages]),
                                   np.asarray(want[m][:, :, pages]),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(kv[2][:, pages]),
                               np.asarray(want[2][:, pages]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(kv[3][:, 2]),
                               np.asarray(want[3][:, 2]), atol=1e-4)


def test_compressed_keys_of_decode_are_prefills(model):
    """The compressed keys after a prefill of 130 tokens equal those
    after 21 prefilled and 109 decoded one step at a time (windows over
    the prefill/decode edge and every page edge), and both are the mean
    of the keys: the reference's."""
    params, toks, _, _ = model
    _, want = prefill_chunks(params, TINY, toks, (N,), bucket=144)
    _, kv = prefill_chunks(params, TINY, toks, (21,), bucket=32)
    _, kv = decode_steps(params, TINY, kv, toks, 21)
    pages = jnp.asarray(PAGES)
    a, b = (np.asarray(x[2][:, pages]) for x in (kv, want))
    # (the second sparse layer's keys carry the summation order of the
    # layers under it: the chunked rule against its steps)
    np.testing.assert_allclose(a[0], b[0], atol=1e-6)
    np.testing.assert_allclose(a, b, atol=2e-5)
    # by hand: layer 0, window i = mean(k[2 i : 2 i + 4]) at flat slot
    # i + 1; K of page p, head h is kv[0][0, h, p] as [hd, bs]
    k = np.concatenate([np.asarray(want[0][0, :, p]).transpose(2, 0, 1)
                        for p in PAGES])[:N]               # [N, nkv, hd]
    flat = b[0].reshape(-1, *b.shape[-2:])                 # [slots, nkv, hd]
    for i in (0, 5, 31, 63):
        np.testing.assert_allclose(flat[i + 1], k[2 * i:2 * i + 4].mean(0),
                                   atol=1e-6)


@pytest.mark.parametrize("chunk", [8, 128])
def test_lightning_chunk_equals_the_token_recurrence(chunk):
    """The family's operands through ops/ssm.py `ssd_chunked` (a constant
    log-decay a head, dt 1, one group a head) against the reference's
    recurrence, from a given state, with a padded tail that must leave
    the state alone; and `ssd_step` token by token gives the same."""
    rng = np.random.default_rng(3)
    T, H, hd = 70, 4, 16
    cfg = dataclasses.replace(TINY, lightning_chunk=chunk)
    q, k, v = (jnp.asarray(rng.standard_normal((T + 10, H, hd)),
                           jnp.float32) for _ in range(3))
    log_decay = sala.default_log_decay(1, H)[0]
    s0 = jnp.asarray(rng.standard_normal((H, hd, hd)), jnp.float32)
    live = jnp.arange(T + 10) < T
    o, s1 = ssd_chunked(*sala._rule(cfg, log_decay, q, k, v, live), s0,
                        chunk=chunk)
    # the reference's state is [dk, dv]; the program's [dv, dk]
    want_o, want_s = ref.token_recurrence(
        q[:T], k[:T], v[:T], log_decay, jnp.swapaxes(s0, 1, 2),
        1.0 / hd ** 0.5)
    np.testing.assert_allclose(np.asarray(o[:T]), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1),
                               np.asarray(jnp.swapaxes(want_s, 1, 2)),
                               atol=2e-5)
    s, outs = s0[None], []
    for t in range(T):
        y, s = ssd_step(*sala._rule(cfg, log_decay, q[t][None], k[t][None],
                                    v[t][None], jnp.ones((1,), bool)), s)
        outs.append(y[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s1), atol=2e-5)


def test_decay_table_is_the_lightning_convention():
    table = np.asarray(sala.default_log_decay(2, 32))
    assert table.shape == (2, 32)
    assert table[0, 0] == pytest.approx(-2 ** -0.25)
    assert table[1, 31] == pytest.approx(-2 ** -8)


def test_fused_burst_crosses_page_and_dense_len(model):
    """decode_multi from position 44 for 8 steps (`dense_len` at 48 and
    the page boundary at 48 inside the burst) chains the reference's own
    greedy tokens; idle lanes keep their state."""
    params, toks, _, _ = model
    _, kv = prefill_chunks(params, TINY, toks, (44,), bucket=48)
    got, kv = DECODE_MULTI(
        params, TINY, kv, lanes_of(toks[44], 2), lanes_of(44, 2),
        lanes_of(the_table(), 2), lanes_of(44, 2), 8,
        valid=lanes_of(True, 2, bool))
    burst = np.asarray(got)[:, 2]
    seq = toks[:45].tolist() + [int(t) for t in burst[:-1]]
    logits = ref.reference_logits(params, TINY, seq)
    assert [int(jnp.argmax(logits[44 + j])) for j in range(8)] \
        == [int(t) for t in burst]
    assert float(jnp.abs(kv[3][:, 1] - 1).max()) == 0.0


def test_padded_row_beside_a_full_one_and_an_empty_one(model):
    """prefill_batched: a row of 64 tokens, a row of 21 padded to 64 and
    two filler rows of none (lane 0, as the engine pads).  Both real
    rows agree with the reference; the short row goes on from where its
    21st token left; lane 0 keeps what it held."""
    params, toks, full, _ = model
    short = np.random.default_rng(6).integers(3, TINY.vocab_size, 70)
    full_s = np.asarray(ref.reference_logits(params, TINY, short.tolist()))
    kv = fresh_cache()
    rows = np.zeros((4, 64), np.int32)
    rows[0], rows[1, :21] = toks[:64], short[:21]
    tables = np.zeros((4, TABLE), np.int32)
    tables[0, :4], tables[1, :5] = [3, 7, 9, 11], [13, 2, 5, 14, 6]
    pos = np.tile(np.arange(64, dtype=np.int32), (4, 1))
    logits, kv = PREFILL_BATCHED(
        params, TINY, kv, jnp.asarray(rows), jnp.asarray(pos),
        jnp.asarray(tables), jnp.zeros(4, jnp.int32),
        jnp.asarray([64, 21, 0, 0], jnp.int32),
        lanes=jnp.asarray([2, 1, 0, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), full[63], atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), full_s[20], atol=TOL)
    assert float(jnp.abs(kv[3][:, 0] - 1).max()) == 0.0
    t = np.zeros(64, np.int32)
    t[:49] = short[21:]
    logits, kv = PREFILL(
        params, TINY, kv, jnp.asarray(t),
        jnp.asarray(21 + np.arange(64, dtype=np.int32)),
        jnp.asarray(tables[1]), jnp.int32(21), jnp.int32(49),
        lanes=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(logits), full_s[69], atol=TOL)


def test_two_lanes_of_different_length_in_one_step(model):
    """Lane 0 at position 30 (under `dense_len`) and lane 3 at 90 (past
    it) step together: each gets its own sequence's logits."""
    params, toks, full, _ = model
    other = np.random.default_rng(5).integers(3, TINY.vocab_size, 40)
    full_o = np.asarray(ref.reference_logits(params, TINY, other.tolist()))
    kv = fresh_cache()
    ta, tb = np.zeros(TABLE, np.int32), np.zeros(TABLE, np.int32)
    ta[:3], tb[:6] = [4, 8, 10], [1, 12, 15, 16, 17, 18]
    for seq, n, table, lane in ((other, 30, ta, 0), (toks, 90, tb, 3)):
        t = np.zeros(96, np.int32)
        t[:n] = seq[:n]
        _, kv = PREFILL(params, TINY, kv, jnp.asarray(t),
                        jnp.arange(96, dtype=jnp.int32), jnp.asarray(table),
                        jnp.int32(0), jnp.int32(n), lanes=jnp.int32(lane))
    tables = np.zeros((LANES, TABLE), np.int32)
    tables[0], tables[3] = ta, tb
    valid = jnp.asarray([True, False, False, True])
    for j in range(3):
        cur = np.array([30 + j, 0, 0, 90 + j], np.int32)
        tok = np.array([other[30 + j], 0, 0, toks[90 + j]], np.int32)
        logits, kv = DECODE(params, TINY, kv, jnp.asarray(tok),
                            jnp.asarray(cur), jnp.asarray(tables),
                            jnp.asarray(cur), valid=valid)
        np.testing.assert_allclose(np.asarray(logits[0]), full_o[30 + j],
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(logits[3]), full[90 + j],
                                   atol=TOL)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of DETAILS."""
    params, toks, full, _ = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    logits, kv = prefill_chunks(params, TINY, toks, (37, 40, 24), bucket=48)
    got, _ = decode_steps(params, TINY, kv, toks[:110], 101)
    worst = max(float(np.abs(row - without[100 + i]).max())
                for i, row in enumerate([logits] + got))
    assert worst > 100 * TOL, (detail, worst)


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_kernel_forms_give_the_jnp_forms_result(model, phase):
    """The chosen-page read through the paged pool's decode kernel (once
    a KV group over the pool seen as [layers x nkv, 1, ...]) and the
    flash pass under the block mask as its Pallas kernel, both under the
    interpreter, against the gathering and scanning forms."""
    params, toks, _, _ = model
    _, kv = prefill_chunks(params, TINY, toks, (N,), bucket=144)
    rng = np.random.default_rng(8)
    sizes = TINY.sizes
    if phase == "decode":
        q = jnp.asarray(rng.standard_normal((LANES, 4, 16)), jnp.float32)
        tables = jnp.zeros((LANES, TABLE), jnp.int32).at[1].set(the_table()) \
            .at[2].set(the_table())
        kv_lens = jnp.asarray([0, 40, 127, 0], jnp.int32)
        want, n_want = bsa.sparse_decode_attention(
            q, kv[0], kv[1], kv[2], 1, tables, kv_lens, sizes, "jnp")
        got, n_got = bsa.sparse_decode_attention(
            q, kv[0], kv[1], kv[2], 1, tables, kv_lens, sizes,
            "pallas_interpret")
        assert int(n_got) == int(n_want) > 0
        np.testing.assert_allclose(np.asarray(got)[1:3],
                                   np.asarray(want)[1:3], atol=1e-5)
    else:
        T = 64
        q = jnp.asarray(rng.standard_normal((1, T, 4, 16)), jnp.float32)
        args = (q, kv[0], kv[1], kv[2], 1, jnp.asarray(the_table())[None],
                jnp.asarray([66]), jnp.asarray([50]), sizes)
        want, n_want = bsa.sparse_prefill_attention(*args, "jnp")
        got, n_got = bsa.sparse_prefill_attention(*args, "pallas_interpret")
        np.testing.assert_allclose(np.asarray(got)[0, :50],
                                   np.asarray(want)[0, :50], atol=1e-5)
        assert 0 < int(n_got) <= int(n_want)


# the choice kernel against the jnp form: (sizes, pages of 16 tokens =
# 8 compressed keys = 2 blocks, first position, valid queries of 256).
# A key tile is 128 blocks = 1024 tokens, a query tile 128 queries.
_S = bsa.BlockSizes(4, 2, 8, 1, 16, 4, 300)
CHOICE_CASES = {
    # 784 slots in 196 blocks (the cell's 3128 / 782 in small: no
    # multiples of 128), both key tiles visited
    "odd_widths": (_S, 98, 1200, 256),
    # two windows of the next block touch a block; none does
    "pad_2": (bsa.BlockSizes(6, 2, 8, 1, 16, 4, 300), 98, 1200, 256),
    "pad_0": (bsa.BlockSizes(2, 2, 8, 1, 16, 4, 300), 98, 1200, 256),
    # dense_len falls inside the first query tile, inside the second
    "crosses_dense_len": (_S._replace(dense_len=1270), 98, 1200, 256),
    "second_tile_chooses": (_S._replace(dense_len=1400), 98, 1200, 256),
    # nothing scored: every block j <= t // block
    "all_dense": (_S._replace(dense_len=1456), 98, 1200, 256),
    "short_table": (_S._replace(dense_len=1568), 98, 1200, 256),
    # invalid rows at the chunk's end: inside a tile, a whole tile
    "invalid_rows": (_S, 98, 1200, 150),
    "invalid_tile": (_S, 98, 1200, 100),
    # the frontier (block 81 of 196) lies inside the first key tile; at
    # 1025 tokens it has just entered the second
    "frontier_in_tile": (_S, 98, 400, 256),
    "frontier_at_tile_edge": (_S, 98, 770, 256),
    # the first queries see no whole window at all
    "no_window": (_S._replace(dense_len=0), 98, 0, 256),
}


def _choice_inputs(case, dtype):
    sizes, pages, ctx, true = CHOICE_CASES[case]
    rng = np.random.default_rng(sorted(CHOICE_CASES).index(case))
    q = jnp.asarray(rng.standard_normal((256, 4, 16)), dtype)
    ck = jnp.asarray(rng.standard_normal((2, pages + 7, 8, 2, 16)), dtype)
    table = jnp.asarray(rng.permutation(pages + 7)[:pages], jnp.int32)
    return (sizes, q, ck, table, jnp.asarray(ctx + np.arange(256), jnp.int32),
            jnp.arange(256) < true)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CHOICE_CASES))
def test_choice_kernel_chooses_the_jnp_forms_sets(case, dtype):
    """`prefill_block_choice` through ops/pallas_block_choice.py's
    kernel under the interpreter: the chosen sets are the jnp form's,
    block for block, on random (so distinct) scores."""
    sizes, q, ck, table, pos, valid = _choice_inputs(case, dtype)
    want = np.asarray(bsa.prefill_block_choice(q, ck, 1, table, pos, valid,
                                               sizes))
    got = np.asarray(bsa.prefill_block_choice(q, ck, 1, table, pos, valid,
                                              sizes, "pallas_interpret"))
    assert got.shape == want.shape == (256, 2, table.shape[0] * 2)
    assert (got == want).all()
    t, ok = np.asarray(pos), np.asarray(valid)
    assert not got[~ok].any()
    dense = ok & (t + 1 <= sizes.dense_len)
    assert (got[dense].sum(-1) == (t[dense] // 8 + 1)[:, None]).all()
    past = ok & ~dense & (t // 8 + 1 > sizes.topk)
    assert (got[past].sum(-1) == sizes.topk).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CHOICE_CASES))
def test_choice_kernel_scores_are_the_jnp_forms(case, dtype):
    """P of every query that is read, to 1e-6 of its value (another
    order of the float32 sums; the same operands and rounding points),
    at a query tile of 32 and the rule's own; exactly 0 past a query's
    frontier on both sides, and for a tile nobody reads."""
    from dynamo_tpu.ops.pallas_block_choice import (
        block_scores_pallas,
        choice_tile,
    )

    sizes, q, ck, table, pos, valid = _choice_inputs(case, dtype)
    ck_seq = ck[1, table].reshape(-1, 2, 16)
    read = valid & (pos + 1 > sizes.dense_len)
    want = np.asarray(bsa.block_scores(q, ck_seq, pos, sizes))
    for tq in (32, 128, 0):
        got = np.asarray(block_scores_pallas(q, ck_seq, pos, read, sizes,
                                             tq=tq, interpret=True))
        assert got.shape == want.shape
        rows = np.asarray(read)
        np.testing.assert_allclose(got[rows], want[rows], rtol=1e-6,
                                   atol=1e-12)
        assert ((want[rows] == 0) == (got[rows] == 0)).all()
        tiles = rows.reshape(-1, tq or choice_tile(
            256, 2, 16, 4, want.shape[-1], q.dtype.itemsize)).any(1)
        assert not got.reshape(len(tiles), -1)[~tiles].any()


@pytest.mark.parametrize("rows", [0, 128])
def test_search_rows_a_step_leave_the_mask(rows):
    """`topk_mask`'s kernel at its own 16 rows a grid step and at the
    128 the prefill choice names for its short rows (300 rows of 782:
    a padded last step), under the interpreter, against the XLA loop;
    ties among them."""
    from dynamo_tpu.ops.sparse_attention import topk_mask

    rng = np.random.default_rng(4)
    scores = jnp.asarray(rng.integers(0, 40, (300, 782)) / 8.0, jnp.float32)
    ok = jnp.asarray(rng.random((300, 782)) < 0.8)
    want = np.asarray(topk_mask(scores, ok, 64, "xla"))
    got = np.asarray(topk_mask(scores, ok, 64, "pallas_interpret", rows))
    assert (got == want).all() and (want.sum(-1) == 64).all()


def test_prefill_program_runs_the_choice_kernel(model):
    """The family's own prefill program with the choice in the kernel
    (a bucket of 144 rows: a tile and a padded one) gives the
    reference's logits, and the rule is the resolved impl and the row
    count alone."""
    params, toks, full, _ = model
    cfg = dataclasses.replace(TINY, attn_impl="pallas_interpret")
    logits, _ = prefill_chunks(params, cfg, toks, (N,), bucket=144)
    np.testing.assert_allclose(logits, full[N - 1], rtol=0, atol=TOL)
    assert bsa.choice_impl("pallas", 2048) == "pallas"
    assert bsa.choice_impl("pallas", 128) == "pallas"
    assert bsa.choice_impl("pallas", 8) == "jnp"          # a decode step
    assert bsa.choice_impl("pallas_interpret", 64) == "jnp"
    assert bsa.choice_impl("jnp", 2048) == "jnp"
    pre = sala.prefill_token_counts(cfg, 40, 20, 144)
    assert pre["sala_choice_kernel_queries.prefill"] \
        == pre["sala_sparse_queries.prefill"] == 12
    assert sala.prefill_token_counts(cfg, 40, 20, 32)[
        "sala_choice_kernel_queries.prefill"] == 0


@pytest.mark.parametrize("ctx,k", [((10, 40, 47, 48, 100), 4), ((), 0)])
def test_host_counts_follow_the_equations(ctx, k):
    """decode_block_counts / prefill_token_counts against a loop over
    positions; an empty burst names the counters."""
    got = sala.decode_block_counts(TINY, np.asarray(ctx, np.int64), k, BS,
                                   LANES, TABLE, "jnp")
    blocks = kept = used = seen = 0
    for c in ctx:
        for t in range(c, c + k):
            nb = t // 8 + 1
            sparse = t + 1 > 48 and nb > 4
            blocks += nb
            kept += 4 if sparse else nb
            used += (4 * 8 - (7 - t % 8)) if sparse else t + 1
            seen += max((t - 3) // 2 + 1, 0) if t + 1 > 48 else 0
    assert got["sala_ctx_blocks.decode"] == blocks
    assert got["sala_kept_blocks.decode"] == kept
    assert got["sala_used_tokens.decode"] == used
    assert got["sala_scored_keys.decode"] == seen
    assert got["recurrent_lane_steps.decode"] == k * len(ctx)
    assert got["state_live_lane_steps.decode"] == 2 * k * len(ctx)
    assert got["state_moved_lane_steps.decode"] == 2 * k * LANES
    pre = sala.prefill_token_counts(TINY, 40, 20 if k else 0, 32)
    if k:
        assert pre["sala_dense_queries.prefill"] == 8
        assert pre["sala_sparse_queries.prefill"] == 12
        assert pre["sala_pairs_attended.prefill"] == sum(range(41, 49)) \
            + sum(32 - (7 - t % 8) for t in range(48, 60))
        assert pre["state_chunk_tokens.prefill"] == 40
        assert pre["state_chunk_kernel_tokens.prefill"] == 0
        assert pre["sala_choice_kernel_queries.prefill"] == 0   # jnp form
    else:
        assert set(pre) >= {"sala_pairs_scored.prefill",
                            "recurrent_tokens.prefill"}
        assert not any(pre.values())


def _engine(**over):
    sizes = dict(model_config=TINY, block_size=BS, num_blocks=64,
                 max_blocks_per_seq=TABLE, max_num_seqs=LANES,
                 prefill_buckets=(32,), seed=7)
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


async def test_engine_serves_the_family_and_counts():
    """JaxEngine end to end through get_family: five requests over four
    lanes (chunked prefill of up to five programs a prompt, fused
    bursts, lanes side by side, a lane REUSED by the fifth sequence
    with the fourth's state still in it and no clearing program) emit
    the reference's greedy tokens; the counters are fed, the device's
    among them."""
    eng = _engine()
    assert get_family(eng.model_cfg) is sala
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    sizes = ((100, 12), (37, 9), (130, 10), (20, 6), (75, 8))
    prompts = [rng.integers(3, TINY.vocab_size, n).tolist()
               for n, _ in sizes]
    outs = await asyncio.gather(*[
        _generate(eng, f"r{i}", p, n)
        for i, (p, (_, n)) in enumerate(zip(prompts, sizes))])
    for p, toks in zip(prompts, outs):
        full = ref.reference_logits(eng.params, eng.model_cfg,
                                    p + toks[:-1])
        assert [int(jnp.argmax(full[len(p) - 1 + j]))
                for j in range(len(toks))] == toks
    m = eng.metrics
    total = sum(n for n, _ in sizes)
    assert m["recurrent_tokens.prefill"] == total
    assert 0 < m["recurrent_carried_tokens.prefill"] < total
    assert m["recurrent_resets"] == 5
    assert m["sala_dense_queries.prefill"] \
        + m["sala_sparse_queries.prefill"] == total
    assert 0 < m["sala_pairs_attended.prefill"] \
        <= m["sala_pairs_computed.prefill"]
    assert 0 < m["sala_kept_blocks.decode"] < m["sala_ctx_blocks.decode"]
    assert 0 < m["sala_used_tokens.decode"] <= m["sala_read_tokens.decode"]
    assert m["sala_scored_keys.decode"] > 0
    assert 0 < m["recurrent_lane_steps.decode"] \
        <= m["recurrent_slot_steps.decode"]
    assert m["state_chunk_tokens.prefill"] == 2 * total
    await eng.close()


async def test_preempted_sequence_resumes_with_the_same_tokens():
    """A pool too small for two long answers: one sequence is preempted,
    its state and its three paged members rebuilt by the replay from
    position 0, and it emits what it emits alone."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, TINY.vocab_size, 60).tolist()
               for _ in range(2)]
    alone = _engine()
    want = [await _generate(alone, f"a{i}", p, 40)
            for i, p in enumerate(prompts)]
    await alone.close()
    tight = _engine(num_blocks=11)       # 10 usable: two x 7 do not fit
    got = await asyncio.gather(*[_generate(tight, f"t{i}", p, 40)
                                 for i, p in enumerate(prompts)])
    assert tight.metrics["preemptions"] > 0
    assert got == want
    await tight.close()


@pytest.mark.parametrize("what,over,match", [
    ("tp", dict(tp=2), "does not carry tp"),
    ("kvbm", dict(host_cache_blocks=8), "does not carry kvbm"),
    ("lora", dict(lora_max_adapters=2), "LoRA"),
])
def test_unsupported_features_refuse(what, over, match):
    assert what in sala.UNSUPPORTED
    with pytest.raises(ValueError, match=match):
        _engine(**over)


def test_unsupported_features_refuse_or_fall_back():
    """Prefix caching asked for is switched off with a warning (a reused
    block says nothing of the state at its end); a disagg pull refuses
    the configuration; int8 cache and speculation fall back: no silently
    wrong answer on any of `UNSUPPORTED`."""
    assert set(sala.UNSUPPORTED) == {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "packed_prefill", "kvbm", "disagg", "tp"}
    eng = _engine(enable_prefix_caching=True)
    assert not eng.config.enable_prefix_caching
    with pytest.raises(ValueError, match="does not carry disagg"):
        JaxEngine(EngineConfig(
            model_config=TINY, block_size=BS, num_blocks=16,
            max_blocks_per_seq=TABLE, max_num_seqs=LANES),
            kv_pull_fn=lambda p: None)
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert not hasattr(sala, "prefill_packed")
    assert not hasattr(sala, "prefill_ring")
