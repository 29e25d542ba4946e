"""Attention over keys that a learned indexer chooses (models/keye.py,
ops/sparse_attention.py): the paged path against the plain float32
reference of benchmark/reference/keye.py, at tiny widths on the CPU.

topk 16, block 16, 4 index heads of 8, 8 query heads over 2 KV heads of
16, 16 router outputs of which a share of 4 is held.  Everything is
float32 here, so program and reference differ by summation order only,
and the chosen sets are equal token for token."""

import asyncio
import dataclasses

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import keye as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import get_family, keye
from dynamo_tpu.models.keye import KeyeConfig
from dynamo_tpu.models.moe import (
    moe_dispatch_dense,
    moe_dispatch_grouped,
    softmax_router,
)
from dynamo_tpu.ops import sparse_attention as sa
from dynamo_tpu.ops.paged_attention import (
    paged_attention_decode_jnp,
    paged_prefill_attention,
)
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = KeyeConfig(dtype=jnp.float32, experts_held=(4, 4))
BS, LANES, TABLE = 16, 4, 12
BLOCKS = [3, 7, 9, 11, 13, 2, 5, 17, 21, 19]
PROMPT, TOTAL = 100, 130      # 100 > 5 x topk: most keys are dropped
# float32 on both sides: what is left is the order of summation (chunked
# softmax, blocked index scores), measured at 2.5e-6 on logits of
# magnitude 3.7; 1e-4 leaves room for another platform's reductions and
# is four orders under the smallest effect of a left-out detail (2.1)
TOL = 1e-4


# one compile a shape: eager dispatch of a two-layer model op by op is
# most of what these tests would otherwise wait for
PREFILL = jax.jit(keye.prefill, static_argnums=(1,))
DECODE = jax.jit(keye.decode, static_argnums=(1,))
DECODE_MULTI = jax.jit(keye.decode_multi, static_argnums=(1, 7))


def fresh_cache(cfg=TINY, num_blocks=40):
    return tuple(jnp.zeros(s, d) for s, d in zip(
        keye.kv_cache_shapes(cfg, num_blocks, BS),
        keye.kv_cache_dtypes(cfg)))


def table_of(blocks=BLOCKS):
    t = np.zeros(TABLE, np.int32)
    t[:len(blocks)] = blocks
    return t


def prefilled(params, cfg, toks, prompt_len, table, kv=None, bucket=32):
    """Chunked prefill of toks[:prompt_len] (chunks of `bucket`) ->
    (logits at the last prompt position, cache)."""
    kv = fresh_cache(cfg) if kv is None else kv
    pos = 0
    while pos < prompt_len:
        chunk = min(bucket, prompt_len - pos)
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = PREFILL(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk))
        pos += chunk
    return np.asarray(logits), kv


def lanes_of(rows, dtype=np.int32):
    """{lane: value} -> [LANES, ...] array, zeros elsewhere."""
    first = next(iter(rows.values()))
    a = np.zeros((LANES,) + np.shape(first), dtype)
    for lane, x in rows.items():
        a[lane] = x
    return jnp.asarray(a)


@pytest.fixture(scope="module")
def model():
    params = keye.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, TOTAL)
    taps = []
    full = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                           taps=taps))
    return params, toks, full, taps


def test_paged_path_matches_reference_logits(model):
    """Prompt of 100 tokens prefilled in chunks of 32, 32, 32 and 4
    (three chunk boundaries, six block boundaries), then 30 decode steps
    through the cache across the block boundaries at 112 and 128."""
    params, toks, full, _ = model
    cfg = TINY
    table = table_of()
    logits, kv = prefilled(params, cfg, toks, PROMPT, table)
    np.testing.assert_allclose(logits, full[PROMPT - 1], rtol=0, atol=TOL)
    lane = 2
    for step in range(PROMPT, TOTAL):
        logits, kv = DECODE(
            params, cfg, kv, lanes_of({lane: toks[step]}),
            lanes_of({lane: step}), lanes_of({lane: table}),
            lanes_of({lane: step}), valid=lanes_of({lane: True}, bool))
        np.testing.assert_allclose(np.asarray(logits)[lane], full[step],
                                   rtol=0, atol=TOL)


def test_two_lanes_of_different_length_and_a_fused_burst(model):
    """Two sequences side by side, 100 and 59 tokens long (one over, one
    a few times topk), through one fused burst of 8 steps: the longer
    one's cells 108..115 cross the block boundary at 112, the shorter
    one's 59..66 the one at 64.  Each lane chains the reference's own
    greedy tokens."""
    params, toks, _, _ = model
    other = np.random.default_rng(5).integers(3, TINY.vocab_size, 60)
    ta, tb = table_of(), table_of([4, 6, 8, 10, 12])
    _, kv = prefilled(params, TINY, toks, 108, ta)
    _, kv = prefilled(params, TINY, other, 59, tb, kv=kv)
    got, _ = DECODE_MULTI(
        params, TINY, kv, lanes_of({1: toks[108], 3: other[59]}),
        lanes_of({1: 108, 3: 59}), lanes_of({1: ta, 3: tb}),
        lanes_of({1: 108, 3: 59}), 8,
        valid=lanes_of({1: True, 3: True}, bool))
    got = np.asarray(got)
    for lane, seq in ((1, toks[:109].tolist()), (3, other[:60].tolist())):
        burst = got[:, lane].tolist()
        logits = ref.reference_logits(params, TINY, seq + burst[:-1])
        assert [int(jnp.argmax(row)) for row in logits[len(seq) - 1:]] \
            == burst


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of: the selection
    itself, the relu inside the index score, the indexer's rotary, the
    LayerNorm on the index key, topk halved, the per-head q/k norm, the
    router's renormalisation.  Smallest effect measured: 2.1 (the
    router), against TOL 1e-4."""
    params, toks, full, _ = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    logits, _ = prefilled(params, TINY, toks, PROMPT, table_of())
    assert float(np.abs(logits - without[PROMPT - 1]).max()) > 100 * TOL
    assert float(np.abs(full - without)[PROMPT - 1:].max()) > 100 * TOL


def _layer_inputs(params, toks, upto):
    """Layer 0's projections for toks[:upto], as the program makes
    them, and a cache that holds them."""
    layer = params["layers"][0]
    pos = jnp.arange(upto)
    x = params["embedding"][jnp.asarray(toks[:upto])].astype(TINY.dtype)
    h = keye.rms_norm(x, layer["attn_norm"]["norm"], TINY.rms_eps)
    q, k, v = keye._qkv(layer, TINY, h, pos)
    qi, ki, wi = keye._index_proj(layer, TINY, h, pos)
    table = table_of()
    k_c, v_c, ik_c, _ = fresh_cache()
    k_c, v_c, ik_c = sa.write_packed_members(
        (k_c, v_c, ik_c), 0, (k, v, ki), jnp.asarray(table)[None],
        jnp.zeros(upto, jnp.int32), pos, jnp.ones(upto, bool))
    return q, k, v, qi, wi, (k_c, v_c, ik_c), table


def test_chosen_sets_equal_the_references_exactly(model):
    """Layer 0 (the only one whose input the two sides share bit for
    bit): the op's mask for every query of a 100-token chunk, and for a
    decode token at position 99, is the reference's chosen set."""
    params, toks, _, taps = model
    want = np.asarray(taps[0]["chosen"])[:PROMPT, :PROMPT]
    assert want[PROMPT - 1].sum() == TINY.index_topk
    q, _, _, qi, wi, (_, _, ik_c), table = _layer_inputs(params, toks,
                                                         PROMPT)
    sel = sa.prefill_index_mask(
        qi, wi, ik_c, 0, jnp.asarray(table), jnp.ones(PROMPT, bool),
        jnp.arange(PROMPT), TINY.index_topk)
    np.testing.assert_array_equal(np.asarray(sel)[:, :PROMPT], want)
    assert not np.asarray(sel)[:, PROMPT:].any()
    one = sa.decode_index_mask(
        qi[-1:], wi[-1:], ik_c, 0, jnp.asarray(table)[None],
        jnp.asarray([PROMPT]), TINY.index_topk)
    np.testing.assert_array_equal(np.asarray(one)[0, :PROMPT],
                                  want[PROMPT - 1])


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_topk_mask_ties_go_to_the_lower_index(impl):
    """Equal scores at the threshold: the lower indices are kept; -0.0
    ties with +0.0; a row of one value keeps its first k; a row with
    fewer than k candidates keeps them all; entries that are not
    candidates are never kept.  The XLA loop and the kernel (rows
    resident in VMEM; interpreted here) are the same search."""
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(6, 200)).astype(np.float32)
    scores[:, ::3] = np.round(scores[:, ::3], 1)
    scores[2, 10:40], scores[2, 50:60] = 0.0, -0.0
    scores[4] = 1.5
    ok = rng.random((6, 200)) < 0.8
    ok[3, 12:] = False
    ok[5] = False
    for k in (16, 64):
        got = np.asarray(sa.topk_mask(jnp.asarray(scores),
                                      jnp.asarray(ok), k, impl=impl))
        for r in range(6):
            cand = sorted(np.nonzero(ok[r])[0],
                          key=lambda i: (-scores[r, i], i))[:k]
            assert set(np.nonzero(got[r])[0]) == set(cand), (k, r)


@pytest.mark.parametrize("flash,attn_impl", [
    ("xla", "jnp"), ("pallas_interpret", "pallas_interpret")])
def test_a_context_within_topk_gets_dense_attention(model, flash,
                                                    attn_impl):
    """While t + 1 <= topk every key is chosen: the sparse op's output
    is dense attention's, to the rounding of a reordered float32 sum
    (the indexer is not skipped there: module docstring); through the
    XLA forms and through both kernels (interpreted here)."""
    params, toks, _, _ = model
    n = TINY.index_topk
    q, k, v, qi, wi, (k_c, v_c, ik_c), table = _layer_inputs(params, toks,
                                                             n)
    table = jnp.asarray(table)
    got = sa.sparse_prefill_attention(
        q, qi, wi, k_c, v_c, ik_c, 0, table[None], jnp.zeros(n, jnp.int32),
        jnp.arange(n), jnp.ones(n, bool), n, flash=flash)
    want = paged_prefill_attention(q, k, v, k_c, v_c, 0, table,
                                   jnp.int32(0), jnp.int32(n))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
    got = sa.sparse_decode_attention(
        q[-1:], qi[-1:], wi[-1:], k_c, v_c, ik_c, 0, table[None],
        jnp.asarray([n]), n, attn_impl=attn_impl)
    want = paged_attention_decode_jnp(q[-1:], k_c, v_c, 0, table[None],
                                      jnp.asarray([n]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_flash_kernel_under_a_mask_equals_the_scan(model):
    """A 100-token chunk over its own cache: the Pallas flash pass under
    the chosen-set mask (interpreted here) against the XLA scan."""
    params, toks, _, _ = model
    q, _, _, qi, wi, (k_c, v_c, ik_c), table = _layer_inputs(params, toks,
                                                             PROMPT)
    got, want = (sa.sparse_prefill_attention(
        q, qi, wi, k_c, v_c, ik_c, 0, jnp.asarray(table)[None],
        jnp.zeros(PROMPT, jnp.int32), jnp.arange(PROMPT),
        jnp.ones(PROMPT, bool), TINY.index_topk, flash=f)
        for f in ("pallas_interpret", "xla"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_masked_decode_through_the_kernel_equals_the_jnp_read(model):
    """The Pallas decode kernel under a per-token bias (interpreted
    here) against the jnp masked read, three lanes of 100, 37 and 0
    live tokens."""
    params, toks, _, _ = model
    q, _, _, qi, wi, (k_c, v_c, ik_c), table = _layer_inputs(params, toks,
                                                             PROMPT)
    rows = jnp.asarray([PROMPT - 1, 36, 5])
    tables = jnp.broadcast_to(jnp.asarray(table)[None], (3, TABLE))
    lens = jnp.asarray([PROMPT, 37, 0])
    got, want = (sa.sparse_decode_attention(
        q[rows], qi[rows], wi[rows], k_c, v_c, ik_c, 0, tables, lens,
        TINY.index_topk, attn_impl=a)
        for a in ("pallas_interpret", "jnp"))
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                               rtol=0, atol=1e-5)
    assert not np.asarray(got)[2].any()         # an idle lane reads nothing


@pytest.mark.parametrize("dispatch", [moe_dispatch_dense,
                                      moe_dispatch_grouped])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """The parts that the four shares of 4 experts give add up to what
    the program gives with all 16 held, and to the reference's uncut
    layer; a share alone equals the reference given the same share."""
    whole = dataclasses.replace(TINY, experts_held=None)
    layer = keye.init_params(whole, jax.random.PRNGKey(3))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (9, whole.d_model))
    w, ids = softmax_router(layer, whole, x)
    uncut = dispatch(layer, whole, x, w, ids)
    np.testing.assert_allclose(
        np.asarray(uncut), np.asarray(ref._routed(whole, layer, x, "")),
        atol=1e-5)
    total = 0.0
    for rank in range(4):
        cfg = dataclasses.replace(whole, experts_held=(4 * rank, 4))
        held = {k: (v[4 * rank:4 * rank + 4] if k.startswith("moe_w_")
                    else v) for k, v in layer.items()}
        part = dispatch(held, cfg, x, w, ids)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(ref._routed(cfg, held, x, "")),
            atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)


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


def _greedy(eng, prompt, toks):
    full = ref.reference_logits(eng.params, eng.model_cfg,
                                prompt + toks[:-1])
    return [int(jnp.argmax(full[len(prompt) - 1 + j]))
            for j in range(len(toks))]


async def test_engine_serves_the_family_and_counts():
    """JaxEngine end to end through get_family: three requests at once
    (packed chunked prefill, fused bursts, lanes side by side) emit the
    reference's greedy tokens; the sparse and expert counters are fed
    from the host's positions, the dense-read ones are not."""
    eng = _engine()
    assert get_family(eng.model_cfg) is keye
    assert eng._packed_prefill_ok and eng.config.enable_prefix_caching
    rng = np.random.default_rng(1)
    lens, outs_n = (90, 37, 120), (30, 20, 25)
    prompts = [rng.integers(3, TINY.vocab_size, n).tolist() for n in lens]
    outs = await asyncio.gather(*[
        _generate(eng, f"r{i}", p, n)
        for i, (p, n) in enumerate(zip(prompts, outs_n))])
    for p, toks in zip(prompts, outs):
        assert _greedy(eng, p, toks) == toks
    m = eng.metrics
    k = TINY.index_topk
    assert m["sparse_pairs_scored.prefill"] == sum(
        n * (n + 1) // 2 for n in lens)
    assert m["sparse_pairs_attended.prefill"] == sum(
        k * (k + 1) // 2 + (n - k) * k for n in lens)
    # every decode step of these lanes holds more than topk tokens
    assert m["sparse_selected_tokens.decode"] % k == 0
    assert m["sparse_read_tokens.decode"] == m["sparse_ctx_tokens.decode"] \
        > 2 * m["sparse_selected_tokens.decode"] > 0
    assert m["decode_attn_live_blocks"] == m["decode_attn_read_blocks"] == 0
    assert m["moe_picks.prefill"] == sum(lens) * TINY.n_layers * 4
    assert 0 < m["moe_picks_held.prefill"] < m["moe_picks.prefill"]
    assert 0 < m["moe_picks_held.decode"] < m["moe_picks.decode"]
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
    await eng.close()


async def test_a_reused_prefix_brings_its_index_keys():
    """The second request shares 80 tokens (five blocks) with the first:
    its prefill starts from the cached blocks, whose index keys the
    indexer reads, and it emits the reference's tokens."""
    eng = _engine()
    rng = np.random.default_rng(3)
    shared = rng.integers(3, TINY.vocab_size, 80).tolist()
    tails = [rng.integers(3, TINY.vocab_size, n).tolist() for n in (25, 33)]
    for i, tail in enumerate(tails):
        toks = await _generate(eng, f"p{i}", shared + tail, 12)
        assert _greedy(eng, shared + tail, toks) == toks
    assert eng.metrics["cache_hit_tokens"] == 80
    await eng.close()


async def test_preempted_sequence_resumes_with_the_same_tokens():
    """A pool too small for two long answers: one sequence is preempted,
    its blocks (index keys among them) rewritten by the replay, and it
    emits what it emits alone."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, TINY.vocab_size, 70).tolist()
               for _ in range(2)]
    alone = _engine()
    want = [await _generate(alone, f"a{i}", p, 50)
            for i, p in enumerate(prompts)]
    await alone.close()
    tight = _engine(num_blocks=13, enable_prefix_caching=False)
    got = await asyncio.gather(*[_generate(tight, f"t{i}", p, 50)
                                 for i, p in enumerate(prompts)])
    assert tight.metrics["preemptions"] > 0
    assert got == want
    await tight.close()


def test_unsupported_features_refuse_or_fall_back():
    """tp > 1, KVBM tiers and a disagg pull refuse the configuration;
    int8 cache and speculation fall back (warned); LoRA refuses: no
    silently wrong answer on any of them."""
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
    assert set(keye.UNSUPPORTED) >= {
        "kv_int8", "speculation", "lora", "ring_prefill", "kvbm", "disagg",
        "tp"}
