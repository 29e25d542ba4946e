"""Gated short-convolution layers beside GQA attention, a dense
feed-forward in the leading layer and routed experts after it
(models/lfm2.py, ops/gated_conv.py): the paged path against the plain
float32 reference of benchmark/reference/lfm2.py, at tiny widths on the
CPU.

d 64; 9 layers in the cell's own pattern (conv, attention, conv x 3,
attention, conv x 3; the first with a dense feed-forward, eight with 16
experts of which 4 are picked); 4 query heads over 2 KV heads of 16;
taps 3, so a lane's state is two rows of 64 a conv layer; block 16.
Everything is float32 here, so program and reference differ by
summation order only."""

import asyncio
import dataclasses

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import lfm2 as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import get_family, lfm2, moe
from dynamo_tpu.models.lfm2 import ATTN, CONV, Lfm2Config
from dynamo_tpu.ops import delta_attention, gated_conv
from dynamo_tpu.ops.packed_prefill import (
    packed_prefill_attention,
    resolve_packed_impl,
    write_packed_kv,
)
from dynamo_tpu.ops.paged_attention import paged_attention_decode
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = Lfm2Config(dtype=jnp.float32)
PREFILL = jax.jit(lfm2.prefill, static_argnums=1)
PACKED = jax.jit(lfm2.prefill_packed, static_argnums=1)
DECODE = jax.jit(lfm2.decode, static_argnums=1)
DECODE_MULTI = jax.jit(lfm2.decode_multi, static_argnums=(1, 7))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation
# (blocked gathers, the matmuls' shapes), measured at 1e-6 of the logit
# range; 1e-4 leaves room for another platform's reductions and is three
# orders under the smallest effect of a bent detail (0.05, below)
TOL = 1e-4


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES, dirty=False):
    """`dirty`: tails full of ones, as a lane that another sequence held
    would be (no program clears a lane)."""
    kv = [jnp.zeros(s, d) for s, d in zip(
        lfm2.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
        lfm2.kv_cache_dtypes(cfg))]
    if dirty:
        kv[2] = jnp.ones(kv[2].shape, kv[2].dtype)
    return tuple(kv)


def _table(first=1):
    t = np.zeros(TABLE, np.int32)
    t[:6] = first + 2 * np.arange(6)         # scattered, not 1, 2, 3
    return t


def lanes_of(x, lane, dtype=np.int32):
    a = np.zeros((LANES,) + np.shape(x), dtype)
    a[lane] = x
    return jnp.asarray(a)


def prefill_chunks(params, cfg, kv, toks, chunks, lane, table, pos=0):
    """The prompt in programs of `chunks` tokens, each padded to its
    bucket -> ({position: logits}, cache)."""
    rows = {}
    for n in chunks:
        bucket = max(16, 1 << (n - 1).bit_length())
        t = np.zeros(bucket, np.int32)
        t[:n] = toks[pos:pos + n]
        logits, kv = PREFILL(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(n),
            lanes=jnp.int32(lane))
        pos += n
        rows[pos - 1] = np.asarray(logits)
    return rows, kv


def decode_steps(params, cfg, kv, toks, start, lane, table):
    rows = {}
    for p in range(start, len(toks)):
        logits, kv = DECODE(
            params, cfg, kv, lanes_of(toks[p], lane), lanes_of(p, lane),
            lanes_of(table, lane), lanes_of(p, lane),
            valid=lanes_of(True, lane, bool))
        rows[p] = np.asarray(logits[lane])
    return rows, kv


def shares(rows, want):
    """|program - reference| as a share of the position's logit range."""
    return {p: float(np.abs(r - want[p]).max()
                     / (want[p].max() - want[p].min()))
            for p, r in rows.items()}


@pytest.fixture(scope="module")
def model():
    # independent experts (`EXPERT_OWN` 1): in float32 on the CPU no
    # pick flips, and a routing detail left out shows at its largest
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "EXPERT_OWN", 1.0)
        params = lfm2.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 46)
    want = np.asarray(ref.reference_logits(params, TINY, toks.tolist()))
    return params, toks, want


def test_layer_pattern_is_the_published_one():
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    assert big.n_layers == 40
    assert big.layers_of(ATTN) == tuple(range(2, 40, 4))
    assert len(big.layers_of(CONV)) == 30
    assert get_family(TINY) is lfm2
    # the tiny preset is the cell's cut: published layers 1-9
    assert TINY.layer_kinds == big.layer_kinds[1:10]
    shapes = lfm2.kv_cache_shapes(TINY, 40, BS, lanes=LANES)
    assert shapes == ((2, 2, 40, 16, BS),) * 2 + ((7, LANES, 2, 64), (3,))
    assert lfm2.kv_cache_dtypes(TINY)[2] == TINY.dtype   # no float32 state


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_paged_path_matches_reference_logits(model, impl):
    """Prefill in two programs (32, then 8 padded to 16), then six
    decode steps through the cache, on a lane that was dirty: logits,
    not tokens, against the reference's full forward."""
    params, toks, want = model
    cfg = dataclasses.replace(TINY, packed_attn_impl=impl)
    rows, kv = prefill_chunks(params, cfg, fresh_cache(dirty=True), toks,
                              (32, 8), 2, _table())
    more, _ = decode_steps(params, cfg, kv, toks, 40, 2, _table())
    rows.update(more)
    assert sorted(rows) == [31, 39] + list(range(40, 46))
    assert max(shares(rows, want).values()) < TOL


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The same comparison against the reference with one detail bent:
    each is off by 0.05 of the logit range or more where the program
    agrees to 1e-4, so the comparison would notice the program dropping
    it."""
    params, toks, want = model
    rows, kv = prefill_chunks(params, TINY, fresh_cache(), toks, (32, 8),
                              2, _table())
    more, _ = decode_steps(params, TINY, kv, toks, 40, 2, _table())
    rows.update(more)
    bent = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                           leave_out=detail))
    assert max(shares(rows, want).values()) < TOL
    assert float(np.median(list(shares(rows, bent).values()))) > 0.05


@pytest.mark.parametrize("chunks", [
    (13, 1, 2, 24),      # a chunk of one and of two: shorter than the tail
    (14, 2, 1, 23),
    (15, 1, 1, 1, 22),   # three single tokens in a row
    (3, 37),
])
def test_chunks_at_any_boundary_equal_one_chunk(model, chunks):
    """A prompt cut at every boundary mod 3 (the taps' width), chunks
    shorter than the tail among them, leaves the tails and gives the
    logits that one 40-token program does (to the summation order of
    differently shaped matmuls), and both are the reference's."""
    params, toks, want = model
    one, kv_one = prefill_chunks(params, TINY, fresh_cache(), toks, (40,),
                                 1, _table())
    cut, kv_cut = prefill_chunks(params, TINY, fresh_cache(dirty=True),
                                 toks, chunks, 1, _table())
    assert max(shares(cut, want).values()) < TOL
    np.testing.assert_allclose(cut[39], one[39], atol=1e-4)
    np.testing.assert_allclose(np.asarray(kv_cut[2][:, 1]),
                               np.asarray(kv_one[2][:, 1]), rtol=1e-5,
                               atol=1e-5)


def test_packed_stream_of_three_rows(model):
    """One stream: a row that starts its sequence (on a dirty lane), a
    row that continues a prompt (its tail carried from an earlier
    program) and a row of a single token that continues another: each
    gets the logits and leaves the tails it does alone."""
    params, toks, want = model
    rng = np.random.default_rng(5)
    b_toks = rng.integers(3, TINY.vocab_size, 30)
    c_toks = rng.integers(3, TINY.vocab_size, 12)
    want_b = np.asarray(ref.reference_logits(params, TINY, b_toks.tolist()))
    want_c = np.asarray(ref.reference_logits(params, TINY, c_toks.tolist()))
    tables = np.stack([_table(1), _table(2), _table(13), np.zeros(TABLE)]
                      ).astype(np.int32)
    kv = fresh_cache(dirty=True)
    # what came before: 17 tokens of B on lane 0, 11 of C on lane 3
    _, kv = prefill_chunks(params, TINY, kv, b_toks, (17,), 0, tables[1])
    _, kv = prefill_chunks(params, TINY, kv, c_toks, (11,), 3, tables[2])
    lens, starts = (21, 13, 1), (0, 17, 11)
    seqs = (toks, b_toks, c_toks)
    T = 64
    stream = np.zeros(T, np.int32)
    seg, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    at, last = 0, []
    for s, (n, p0) in enumerate(zip(lens, starts)):
        stream[at:at + n] = seqs[s][p0:p0 + n]
        seg[at:at + n], pos[at:at + n] = s, p0 + np.arange(n)
        valid[at:at + n] = True
        at += n
        last.append(at - 1)
    logits, kv = PACKED(
        params, TINY, kv, jnp.asarray(stream), jnp.asarray(pos),
        jnp.asarray(seg), jnp.asarray(tables),
        jnp.asarray(last + [0], jnp.int32), jnp.asarray(valid),
        lanes=jnp.asarray([2, 0, 3, 0], jnp.int32))   # row 3: no tokens
    got = np.asarray(logits)
    for row, ref_logits, p in ((0, want, 20), (1, want_b, 29),
                               (2, want_c, 11)):
        assert shares({p: got[row]}, ref_logits)[p] < TOL
    # the tails each row left are those of the row alone
    for lane, seq, n in ((2, toks, 21), (0, b_toks, 30), (3, c_toks, 12)):
        _, alone = prefill_chunks(params, TINY, fresh_cache(), seq, (n,),
                                  1, _table(1))
        np.testing.assert_allclose(np.asarray(kv[2][:, lane]),
                                   np.asarray(alone[2][:, 1]), atol=1e-5)
    # the lane nobody wrote (row 3 had no tokens and named lane 0, which
    # row 1 owns) is what it was
    assert np.array_equal(np.asarray(kv[2][:, 1]), np.ones((7, 2, 64)))


def test_idle_lanes_and_padding_leave_tails_bit_identical(model):
    """A decode burst over two of four lanes and a prefill program that
    is mostly padding: the tails of the lanes they do not own are bit
    for bit what they were, and a reused lane starts from zeros."""
    params, toks, want = model
    kv = fresh_cache(dirty=True)
    rows, kv = prefill_chunks(params, TINY, kv, toks, (32, 8), 2, _table())
    before = np.asarray(kv[2])
    assert np.array_equal(before[:, [0, 1, 3]], np.ones((7, 3, 2, 64)))
    valid = np.zeros(LANES, bool)
    valid[2] = True
    burst, kv = DECODE_MULTI(
        params, TINY, kv, lanes_of(toks[40], 2), lanes_of(40, 2),
        lanes_of(_table(), 2), lanes_of(40, 2), 5,
        None, jnp.asarray(valid))
    after = np.asarray(kv[2])
    assert np.array_equal(after[:, [0, 1, 3]], before[:, [0, 1, 3]])
    assert not np.array_equal(after[:, 2], before[:, 2])
    # the burst chains the reference's greedy tokens where they follow
    # the prompt's own continuation
    full = np.asarray(ref.reference_logits(
        params, TINY, toks[:41].tolist() + np.asarray(burst)[:-1, 2]
        .tolist()))
    assert [int(full[40 + j].argmax()) for j in range(5)] \
        == np.asarray(burst)[:, 2].tolist()


# ---------------------------------------------------------------------------
# the operator alone
# ---------------------------------------------------------------------------


def _old_causal_conv(x, tail, w, true_len, bias=None):
    """ops/delta_attention.py `causal_conv` as it stood before the taps
    were factored out (PR 54's tree), verbatim."""
    F32 = jnp.float32
    T, W = x.shape[0], w.shape[0]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    xf, wf = xx.astype(F32), w.astype(F32)
    c = sum(wf[j] * xf[j:j + T] for j in range(W))
    if bias is not None:
        c = c + bias.astype(F32)
    new_tail = jax.lax.dynamic_slice_in_dim(xx, true_len, W - 1, axis=0)
    return jax.nn.silu(c), new_tail.astype(tail.dtype)


def _old_causal_conv_step(x, tail, w, bias=None):
    F32 = jnp.float32
    xx = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    c = jnp.einsum("bwc,wc->bc", xx.astype(F32), w.astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    if bias is not None:
        c = c + bias.astype(F32)
    return jax.nn.silu(c), xx[:, 1:].astype(tail.dtype)


@pytest.mark.parametrize("bias", [False, True], ids=["ling", "nemotron"])
def test_the_other_families_convolutions_are_the_programs_they_were(bias):
    """Ling's (no bias) and Nemotron's (a bias) short convolutions after
    the taps were factored out: the same lowered program text, and the
    same bits, as the functions they were."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (24, 32), jnp.bfloat16)
    tail = jax.random.normal(ks[1], (3, 32), jnp.bfloat16)
    w = jax.random.normal(ks[2], (4, 32), jnp.bfloat16)
    b = jax.random.normal(ks[3], (32,), jnp.bfloat16) if bias else None
    n = jnp.int32(19)
    for new, old, args in (
            (delta_attention.causal_conv, _old_causal_conv,
             (x, tail, w, n, b)),
            (delta_attention.causal_conv_step, _old_causal_conv_step,
             (x[:5], jnp.broadcast_to(tail, (5, 3, 32)), w, b))):
        got, want = jax.jit(new)(*args), jax.jit(old)(*args)
        for g, v in zip(got, want):
            assert np.array_equal(np.asarray(g, np.float32),
                                  np.asarray(v, np.float32))
        text = lambda f: jax.jit(f).lower(*args).as_text().replace(
            f.__name__, "f")
        assert text(new) == text(old)


def test_gated_conv_packed_against_a_loop_over_rows():
    """The packed form over three rows (one fresh, one carried, one of a
    single token) and padding, against a plain loop a row over
    [tail || run]: the reads, and the tails left."""
    rng = np.random.default_rng(2)
    C, W, T = 8, 3, 16
    b, c, u = (jnp.asarray(rng.standard_normal((T, C)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((W, C)), jnp.float32)
    start = jnp.asarray(rng.standard_normal((4, W - 1, C)), jnp.float32)
    start = start.at[0].set(0.0)                     # row 0 is fresh
    lens = (6, 5, 1, 0)
    seg = np.concatenate([np.full(n, s) for s, n in enumerate(lens)]
                         + [np.zeros(T - sum(lens))]).astype(np.int32)
    valid = np.arange(T) < sum(lens)
    rows = gated_conv.packed_rows(jnp.asarray(seg), jnp.asarray(valid), 4)
    assert rows.n.tolist() == list(lens)
    assert rows.first.tolist()[:3] == [0, 6, 11]
    y, left = gated_conv.gated_conv_packed(b, c, u, w, rows, start)
    g = np.asarray(b * u)
    at = 0
    for s, n in enumerate(lens[:3]):
        run = np.concatenate([np.asarray(start[s]), g[at:at + n]])
        want = np.stack([sum(np.asarray(w[j]) * run[t + j]
                             for j in range(W)) for t in range(n)])
        np.testing.assert_allclose(np.asarray(y[at:at + n]),
                                   np.asarray(c[at:at + n]) * want,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(left[s]), run[-(W - 1):],
                                   rtol=1e-6)
        at += n
    assert np.array_equal(np.asarray(y[at:]), np.zeros((T - at, C)))
    # one token a lane is the packed form of rows of one token
    y1, t1 = gated_conv.gated_conv_step(b[:4], c[:4], u[:4], w, start)
    for s in range(4):
        run = np.concatenate([np.asarray(start[s]), g[s:s + 1]])
        np.testing.assert_allclose(
            np.asarray(y1[s]), np.asarray(c[s]) * sum(
                np.asarray(w[j]) * run[j] for j in range(W)),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(t1[s]), run[1:], rtol=1e-6)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_op_scopes_name_the_parts_of_a_layer(program):
    """The compiled programs carry the scopes a trace groups device ops
    by; `dyn.short_conv` is the operator's."""
    params = lfm2.init_params(TINY, jax.random.PRNGKey(0))
    kv = fresh_cache()
    if program == "prefill":
        low = PREFILL.lower(
            params, TINY, kv, jnp.zeros(16, jnp.int32),
            jnp.arange(16, dtype=jnp.int32), jnp.asarray(_table()),
            jnp.int32(0), jnp.int32(16), lanes=jnp.int32(0))
    else:
        low = DECODE.lower(
            params, TINY, kv, lanes_of(1, 0), lanes_of(0, 0),
            lanes_of(_table(), 0), lanes_of(0, 0))
    text = low.as_text(debug_info=True)
    for scope in ("dyn.short_conv", "dyn.conv_proj", "dyn.attn_qkv",
                  "dyn.attention", "dyn.moe_router", "dyn.moe_dispatch",
                  "dyn.mlp", "dyn.lm_head"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# 64-wide heads through both Pallas reads (interpreted) against their
# jnp forms; the rule that picks the kernel
# ---------------------------------------------------------------------------


def _pool(rng, L, nkv, blocks, hd, bs, dtype):
    return jnp.asarray(rng.standard_normal((L, nkv, blocks, hd, bs)), dtype)


def test_packed_read_at_64_wide_heads_against_the_scan():
    """The packed kernel at head_dim 64, 4 query heads a KV head (a body
    of 4 x 64 = 256 lanes), blocks of 128: a row continuing a cached
    context beside a fresh one, against the float32 scan."""
    rng = np.random.default_rng(3)
    nkv, G, hd, bs, T = 2, 4, 64, 128, 256
    kc, vc = (_pool(rng, 2, nkv, 9, hd, bs, jnp.bfloat16) for _ in "kv")
    tables = jnp.asarray([[2, 4, 6, 0], [1, 3, 0, 0]], jnp.int32)
    lens, starts = (150, 90), (200, 0)
    seg = np.concatenate([np.full(n, s) for s, n in enumerate(lens)]
                         + [np.zeros(T - sum(lens))]).astype(np.int32)
    pos = np.concatenate([p0 + np.arange(n) for n, p0 in zip(lens, starts)]
                         + [np.zeros(T - sum(lens))]).astype(np.int32)
    valid = np.arange(T) < sum(lens)
    k, v = (jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.bfloat16)
            for _ in "kv")
    stream = (tables, jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(valid))
    kc, vc = write_packed_kv(kc, vc, 1, k, v, *stream)
    q = jnp.asarray(rng.standard_normal((T, nkv * G, hd)), jnp.bfloat16)
    want = packed_prefill_attention(q, kc, vc, 1, *stream, impl="xla")
    got = packed_prefill_attention(q, kc, vc, 1, *stream,
                                   impl="pallas_interpret")
    n = sum(lens)
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               atol=2e-2, rtol=2e-2)


def test_decode_read_at_64_wide_heads_against_the_gather():
    rng = np.random.default_rng(4)
    nkv, G, hd, bs, B = 2, 4, 64, 128, 4
    kc, vc = (_pool(rng, 2, nkv, 9, hd, bs, jnp.bfloat16) for _ in "kv")
    tables = jnp.asarray([[2, 4, 6], [1, 3, 0], [5, 0, 0], [7, 8, 0]],
                         jnp.int32)
    kv_lens = jnp.asarray([300, 129, 0, 256], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, nkv * G, hd)), jnp.bfloat16)
    want = paged_attention_decode(q, kc, vc, 1, tables, kv_lens,
                                  impl="jnp")
    got = paged_attention_decode(q, kc, vc, 1, tables, kv_lens,
                                 impl="pallas_interpret")
    live = np.asarray(kv_lens) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=2e-2, rtol=2e-2)


def test_counts_follow_the_rules_the_reads_apply():
    """`prefill_token_counts` / `decode_block_counts` at the cell's cut:
    two reads a token; the kernel's tokens are those of a program whose
    bucket `resolve_packed_impl` gives the kernel (none on the CPU under
    "auto"); the decode read's blocks and the lanes' steps."""
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    cut = dataclasses.replace(big, layer_kinds=big.layer_kinds[1:10],
                              n_dense_layers=1)
    got = lfm2.prefill_token_counts(cut, 2048, 2048, 2048)
    assert got == {"conv_tokens.prefill": 2048,
                   "conv_carried_tokens.prefill": 2048,
                   "gqa_prefill_tokens.prefill": 2 * 2048,
                   "gqa_prefill_kernel_tokens.prefill": 0}
    assert lfm2.prefill_token_counts(cut, 0, 300, 512)[
        "conv_carried_tokens.prefill"] == 0
    got = lfm2.prefill_token_counts(
        dataclasses.replace(cut, packed_attn_impl="pallas"), 0, 200, 512)
    assert got["gqa_prefill_kernel_tokens.prefill"] == 2 * 200
    # on a TPU "auto" is the kernel at this head width from the bucket
    # that repays it, as at 128; an odd number of 64-wide heads a body
    # (a tile of half a vreg) keeps the scan
    assert [resolve_packed_impl("auto", "tpu", 128, cut.head_dim,
                                cut.dtype, t, g)
            for t, g in ((512, 4), (2048, 4), (2048, 1), (2048, 3),
                         (2048, 16))] \
        == ["xla", "pallas", "xla", "xla", "pallas"]
    ctx = np.asarray([127, 128, 5000])
    counts = lfm2.decode_block_counts(cut, ctx, 2, 128, 8, 199, "pallas")
    live = (1 + 2 + 40) + (2 + 2 + 40)
    assert counts == {
        "decode_attn_live_blocks": 2 * live,
        "decode_attn_read_blocks": 2 * live,
        "kv_uniform_block_steps": live,
        "conv_lane_steps.decode": 6, "conv_slot_steps.decode": 16,
        "state_live_lane_steps.decode": 42,
        "state_moved_lane_steps.decode": 112}
    assert lfm2.decode_block_counts(cut, ctx, 2, 128, 8, 199, "jnp")[
        "decode_attn_read_blocks"] == 2 * 2 * 8 * 199


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------


def test_router_is_the_published_block_to_a_millionth():
    """`ds_router` chooses by score + bias and weighs by the scores
    alone; it guards the division with DeepSeek's 1e-20 where the
    published `lfm2_moe` block has 1e-6: four sigmoid scores sum to
    about 2, so the weights differ by under 1e-6 relative, far under a
    bf16 weight's rounding (the configuration file's `assumed`)."""
    layer = lfm2.init_params(TINY, jax.random.PRNGKey(0))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    s = jax.nn.sigmoid(h @ layer["moe_gate"])
    ids = jax.lax.top_k(s + layer["moe_gate_bias"], 4)[1]
    picked = jnp.take_along_axis(s, ids, 1)
    w, e = moe.ds_router(layer, TINY, h)
    assert np.array_equal(np.asarray(e), np.asarray(ids))
    published = np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6))
    assert np.abs(np.asarray(w) / published - 1).max() < 1e-6


def test_forced_picks_route_by_the_witness_choice():
    """A layer that carries `moe_forced_picks` routes by them, the
    weights still the program's own scores there
    (benchmark/chip_logits_lfm2.py's witness); without the key the
    program is what it was."""
    layer = lfm2.init_params(TINY, jax.random.PRNGKey(0))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    s = jax.nn.sigmoid(h @ layer["moe_gate"])
    forced = jnp.asarray(np.random.default_rng(0).permuted(
        np.tile(np.arange(TINY.n_experts), (5, 1)), axis=1)[:, :4],
        jnp.int32)
    w, e = moe.ds_router({**layer, "moe_forced_picks": forced}, TINY, h)
    assert np.array_equal(np.asarray(e), np.asarray(forced))
    picked = jnp.take_along_axis(s, forced, 1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)),
        rtol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The guide's test that ties a share to the model (the cell holds
    all 64): one expert layer's output under `experts_held` = rank r of
    8, summed over the 8 ranks with each rank's slice of the stacks,
    is the layer that holds everything."""
    layer = lfm2.init_params(TINY, jax.random.PRNGKey(0))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(2), (12, 64), jnp.float32)
    whole, picks, seen = lfm2._ffn(layer, TINY, h, None)
    assert int(picks) == 12 * 4 and 0 < int(seen) <= 16
    total, held_picks = jnp.zeros_like(whole), 0
    for r in range(8):
        cfg = dataclasses.replace(TINY, experts_held=(2 * r, 2))
        share = {k: (v[2 * r:2 * r + 2] if k.startswith("moe_w_") else v)
                 for k, v in layer.items()}
        out, n_on, _ = lfm2._ffn(share, cfg, h, None)
        total, held_picks = total + out, held_picks + int(n_on)
    assert held_picks == 12 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# through the engine
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
    """JaxEngine end to end through get_family, on the packed path (the
    engine's default) and on the padded one: six requests over four
    lanes (prompts of two to three programs, fused bursts, lanes joining
    a running burst and finishing inside one, two lanes REUSED without a
    clearing program) emit the reference's greedy tokens; the counters
    are fed."""
    # a chunk budget of 32 tokens: the packed planner cuts a prompt
    # where the padded path's largest bucket does
    eng = _engine(prefill_packed=packed, prefill_chunk_tokens=32)
    assert get_family(eng.model_cfg) is lfm2
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    sizes = ((50, 30), (37, 9), (70, 25), (20, 12), (45, 16), (33, 7))
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
    assert m["conv_tokens.prefill"] == total
    # buckets of at most 32: every prompt but the 20-token one is carried
    assert 0 < m["conv_carried_tokens.prefill"] < total
    assert 0 < m["conv_lane_steps.decode"] <= m["conv_slot_steps.decode"]
    assert m["conv_slot_steps.decode"] % LANES == 0
    assert m["state_live_lane_steps.decode"] \
        == 7 * m["conv_lane_steps.decode"]
    assert m["state_moved_lane_steps.decode"] \
        == 7 * m["conv_slot_steps.decode"]
    assert 0 < m["decode_attn_live_blocks"] <= m["decode_attn_read_blocks"]
    assert m["decode_attn_live_blocks"] == 2 * m["kv_uniform_block_steps"]
    assert m["moe_picks.prefill"] == total * 8 * 4
    # every expert is held: every pick falls on a held one
    assert m["moe_picks_held.prefill"] == m["moe_picks.prefill"]
    assert m["moe_picks_held.decode"] == m["moe_picks.decode"] > 0
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
    assert m["moe_visited_form_slots.decode"] == m["moe_expert_slots.decode"]
    assert m["gqa_prefill_tokens.prefill"] == total * 2
    assert m["gqa_prefill_kernel_tokens.prefill"] == 0   # the CPU's auto
    await eng.close()


async def test_reused_lane_equals_the_sequence_alone():
    """One lane: the second sequence takes the lane the first one left
    (its tails still there) and emits what it emits alone."""
    rng = np.random.default_rng(3)
    a, b = (rng.integers(3, TINY.vocab_size, n).tolist() for n in (40, 33))
    alone = _engine(max_num_seqs=1)
    want = await _generate(alone, "b", b, 12)
    await alone.close()
    eng = _engine(max_num_seqs=1)
    await _generate(eng, "a", a, 9)
    assert float(jnp.abs(eng.kv[2]).max()) > 0          # the lane is dirty
    assert await _generate(eng, "b", b, 12) == want
    await eng.close()


async def test_preempted_sequence_resumes_with_the_same_tokens():
    """A pool too small for two long answers: one sequence is preempted,
    its tails rebuilt by the replay from position 0, and it emits what
    it emits alone."""
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


def test_unsupported_features_refuse_or_fall_back():
    """Prefix caching asked for is switched off with a warning (a reused
    K/V block says nothing of the tail at its end); tp > 1, KVBM tiers
    and a disagg pull refuse the configuration; int8 cache and
    speculation fall back; LoRA and a layer type the program does not
    know refuse: no silently wrong answer on any."""
    eng = _engine(enable_prefix_caching=True)
    assert not eng.config.enable_prefix_caching
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
    with pytest.raises(ValueError, match="not modelled"):
        dataclasses.replace(TINY, layer_kinds=(CONV, "mamba"))
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert set(lfm2.UNSUPPORTED) >= {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "kvbm", "disagg", "tp"}
    assert "packed_prefill" not in lfm2.UNSUPPORTED


def test_configuration_file_maps_onto_the_published_widths():
    """benchmark/configs' file through the reference's `program_config`:
    the published widths, published layers 1-9, one dense layer, all 64
    experts; a switch the program does not model is refused."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "lfm2-24b-a2b-9l.json")
    with open(path) as f:
        hf = json.load(f)
    cfg = ref.program_config(hf, "x")
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    assert cfg == dataclasses.replace(
        big, name="x", layer_kinds=big.layer_kinds[1:10], n_dense_layers=1)
    assert cfg.held == (0, 64) and cfg.head_dim == 64
    assert ref.attn_pair_flops(cfg) == 4.0 * 32 * 64
    assert sorted(hf["reduced"]) == ["layer_types", "num_dense_layers",
                                     "num_hidden_layers"]
    for key, bad in (("conv_bias", True), ("norm_topk_prob", False),
                     ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=key):
            ref.program_config({**hf, key: bad}, "x")
    with pytest.raises(ValueError, match="rope_type"):
        ref.program_config({**hf, "rope_parameters": {
            "rope_theta": 1e6, "rope_type": "yarn"}}, "x")
    with pytest.raises(ValueError, match="not modelled"):
        ref.program_config({**hf, "layer_types":
                            ["mamba"] + hf["layer_types"][1:]}, "x")
