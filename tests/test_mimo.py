"""Window + global attention over experts held as a share (models/mimo.py):
the paged path against the plain float32 reference of
benchmark/reference/mimo.py, at tiny widths on the CPU.

Window 16, block 16, 8 query heads, 2 KV heads in global and 4 in window
layers, K 24 wide and V 16, rotary on 8 of 24 dimensions, 16 router
outputs of which a share of 4 is held.  Everything is float32 here, so
program and reference differ by summation order only."""

import asyncio
import dataclasses
from functools import partial

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mimo as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import get_family, mimo
from dynamo_tpu.models.mimo import MimoConfig
from dynamo_tpu.models.moe import moe_dispatch_dense, moe_dispatch_visited
from dynamo_tpu.ops.window_attention import ring_blocks
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = MimoConfig(dtype=jnp.float32, experts_held=(4, 4))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation (chunked
# softmax, blocked gathers), measured at 2.4e-6 on logits of magnitude 4;
# 1e-4 leaves room for another platform's reductions and is two orders
# under the smallest effect of a left-out detail (3e-2, below)
TOL = 1e-4


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES):
    return tuple(
        jnp.zeros(s, d) for s, d in zip(
            mimo.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
            mimo.kv_cache_dtypes(cfg)))


def paged_logits(params, cfg, toks, prompt_len, lane=2, bucket=32,
                 fused=0):
    """Logits at positions prompt_len - 1 .. len(toks) - 1 from the paged
    path: chunked prefill of the prompt (chunks of `bucket`), then
    teacher-forced decode through the cache; with `fused`, bursts of
    that many steps through decode_multi (which returns tokens: the
    argmax stands in for the logits there)."""
    kv = fresh_cache(cfg)
    table = np.zeros(TABLE, np.int32)
    table[:7] = [3, 7, 9, 11, 13, 2, 5]
    pos, out = 0, []
    while pos < prompt_len:
        chunk = min(bucket, prompt_len - pos)
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = mimo.prefill(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk),
            lanes=jnp.int32(lane))
        pos += chunk
    out.append(np.asarray(logits))

    def lanes_of(x, dtype=np.int32):
        a = np.zeros((LANES,) + np.shape(x), dtype)
        a[lane] = x
        return jnp.asarray(a)

    valid = lanes_of(True, bool)
    step = prompt_len
    while step < len(toks):
        args = (params, cfg, kv, lanes_of(toks[step]), lanes_of(step),
                lanes_of(table), lanes_of(step))
        if fused:
            # greedy chain from toks[step]: returns tokens [fused, B]
            got, kv = mimo.decode_multi(*args, fused, valid=valid)
            return out, np.asarray(got)[:, lane], kv
        logits, kv = mimo.decode(*args, valid=valid)
        out.append(np.asarray(logits)[lane])
        step += 1
    return out, None, kv


@pytest.fixture(scope="module")
def model():
    params = mimo.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 70)
    full = np.asarray(ref.reference_logits(params, TINY, toks.tolist()))
    return params, toks, full


def test_paged_path_matches_reference_logits(model):
    """Prompt of 50 tokens (over three windows: the ring of two blocks
    wraps) prefilled in chunks of 32 and 18, then 20 decode steps across
    the block boundary at 64; both layer kinds, both KV head counts."""
    params, toks, full = model
    assert ring_blocks(TINY.sliding_window, BS) == 2
    got, _, kv = paged_logits(params, TINY, toks, 50)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[49 + i], rtol=0, atol=TOL)
    # the window pools hold a ring a lane and block 0, whatever the length
    assert kv[2].shape[2] == 1 + LANES * 2


def test_fused_burst_crosses_block_boundary(model):
    """decode_multi from position 60 for 8 steps (cells 60..67: the
    boundary at 64 lies inside the burst) chains the reference's own
    greedy tokens."""
    params, toks, _ = model
    _, burst, _ = paged_logits(params, TINY, toks[:61], 60, fused=8)
    seq = toks[:61].tolist()
    for t in burst:
        logits = ref.reference_logits(params, TINY, seq)
        assert int(jnp.argmax(logits[-1])) == int(t)
        seq.append(int(t))


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of: the sink in the
    window layers' denominator, the value scale, the rotary split (64 of
    192 published; 8 of 24 here), the window layers' own rope base, the
    `i - j < window` bound.  Smallest effect measured: 3e-2 (the rope
    base), against TOL 1e-4."""
    params, toks, full = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    got, _, _ = paged_logits(params, TINY, toks, 50)
    worst = max(float(np.abs(row - without[49 + i]).max())
                for i, row in enumerate(got))
    assert worst > 100 * TOL, (detail, worst)
    assert float(np.abs(full - without).max()) > 100 * TOL


# the kernel's body on the CPU: the form a decode step takes on the chip
_visited = partial(moe_dispatch_visited, interpret=True)


@pytest.mark.parametrize("dispatch", [moe_dispatch_dense, _visited],
                         ids=["moe_dispatch_dense", "moe_dispatch_visited"])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """The parts that the four shares of 4 experts give add up to what
    the program gives with all 16 held, and to the reference's uncut
    layer; a share alone equals the reference given the same share."""
    whole = dataclasses.replace(TINY, experts_held=None)
    params = mimo.init_params(whole, jax.random.PRNGKey(3))
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (9, whole.d_model))
    w, ids = mimo.ds_router(layer, whole, x)
    uncut = dispatch(layer, whole, x, w, ids)
    np.testing.assert_allclose(
        np.asarray(uncut),
        np.asarray(ref._routed(whole, layer, x, w, ids)), atol=1e-5)
    total = 0.0
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


async def test_engine_serves_the_family_and_counts():
    """JaxEngine end to end through get_family: three requests at once
    (batched chunked prefill, fused bursts, lanes side by side) emit the
    reference's greedy tokens; the window pools do not grow with length;
    the counters are fed."""
    eng = _engine()
    assert get_family(eng.model_cfg) is mimo
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, TINY.vocab_size, n).tolist()
               for n in (50, 37, 70)]
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
    layers = sum(TINY.moe_layers)
    assert m["moe_picks.prefill"] == (50 + 37 + 70) * layers * 4
    assert 0 < m["moe_picks_held.prefill"] < m["moe_picks.prefill"]
    assert 0 < m["moe_picks_held.decode"] < m["moe_picks.decode"]
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
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


def test_unsupported_features_refuse_or_fall_back():
    """tp > 1, KVBM tiers and a disagg pull refuse the configuration;
    int8 cache, speculation and prefix caching fall back (warned);
    LoRA refuses: no silently wrong answer on any of them."""
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
    assert set(mimo.UNSUPPORTED) >= {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "kvbm", "disagg", "tp"}
