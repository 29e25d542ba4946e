"""Generation by diffusion over blocks (models/sdar.py, the engine's pass
bursts, `upper` in ops/packed_prefill.py) against the plain float32
reference of benchmark/reference/sdar.py, at tiny widths on the CPU.

d 64, 8 query heads over 2 KV heads of 16, 2 layers, 16 experts of
which a token picks 4, blocks of 4 positions filled over 4 passes,
pages of 16.  Everything is float32 here, so program and reference
differ by summation order only."""

import asyncio
import dataclasses

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import sdar as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import PRESETS, get_family, sdar
from dynamo_tpu.ops.packed_prefill import packed_prefill_attention
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = dataclasses.replace(PRESETS["tiny-sdar"], dtype=jnp.float32)
B = TINY.block_length
BS, LANES, TABLE = 16, 4, 8
PREFILL = jax.jit(sdar.prefill, static_argnums=1)
DENOISE = jax.jit(sdar.denoise, static_argnums=1)
DENOISE_MULTI = jax.jit(sdar.denoise_multi, static_argnums=(1, 5))
# float32 on both sides: summation order only (measured 4e-6 on logits
# of magnitude 3); two orders under a left-out detail's effect
TOL = 5e-5


def fresh_cache(cfg=TINY, num_blocks=24):
    return tuple(jnp.zeros(s, d) for s, d in zip(
        sdar.kv_cache_shapes(cfg, num_blocks, BS),
        sdar.kv_cache_dtypes(cfg)))


@pytest.fixture(scope="module")
def params():
    return sdar.init_params(TINY, jax.random.PRNGKey(0))


def _prefill_chunks(params, kv, tokens, table, chunk, cfg=TINY):
    """Chunked block-causal prefill of `tokens` (a multiple of B), the
    rows padded to 32."""
    pos = 0
    while pos < len(tokens):
        n = min(chunk, len(tokens) - pos)
        row = np.zeros(32, np.int32)
        row[:n] = tokens[pos:pos + n]
        _, kv = PREFILL(params, cfg, kv, jnp.asarray(row),
                        jnp.asarray(pos + np.arange(32), jnp.int32), table,
                        jnp.int32(pos), jnp.int32(n))
        pos += n
    return kv


# ---------------------------------------------------------------------------
# the programs against the reference's full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len,chunk", [
    (36, 12), (37, 8), (38, 32), (39, 12), (1, 8), (2, 8), (3, 8)])
def test_pass_logits_through_prefill_and_cache(params, prompt_len, chunk):
    """Pass logits through chunked prefill + cache equal the reference's
    full forward of the same state: all masked, some, clean; prompts of
    every length mod 4 and prompts with nothing to prefill."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, 250, prompt_len).tolist()
    p0 = prompt_len // B * B
    tail = prompt[p0:]
    table = jnp.asarray([3, 7, 9, 11, 13, 2, 5, 14], jnp.int32)
    kv = _prefill_chunks(params, fresh_cache(), prompt[:p0], table, chunk)
    for n_masked in (B - len(tail), max(0, 2 - len(tail)), 0):
        blk = rng.integers(0, 250, B).astype(np.int32)
        blk[:len(tail)] = tail
        msk = np.arange(B) >= B - n_masked
        want = ref.forward(params, TINY, prompt[:p0] + blk.tolist(),
                           [False] * p0 + msk.tolist(),
                           rows=slice(p0, p0 + B))
        got, _ = DENOISE(params, TINY, kv, jnp.asarray(blk)[None],
                         jnp.asarray(msk)[None], jnp.asarray([p0]),
                         table[None])
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=TOL)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_the_comparison_notices_a_left_out_detail(params, detail):
    """Each published detail moves the logits by far more than TOL."""
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 250, 22).tolist()
    flags = [False] * 20 + [True, True]
    whole = ref.forward(params, TINY, toks, flags)
    cut = ref.forward(params, TINY, toks, flags, leave_out=detail)
    assert float(jnp.abs(whole - cut).max()) > 100 * TOL


def test_the_mask_is_a_flag_not_an_id(params):
    """A block token equal to mask_token_id is a token: its logits are
    those of the clean state, not of a masked position; and a sampled
    token equal to it is transferred and emitted like any other."""
    m = TINY.mask_token_id
    table = jnp.asarray([1, 2, 3, 4, 5, 6, 7, 8], jnp.int32)
    prompt = [m, 5, m, 9, 11, m, 2, 4]
    kv = _prefill_chunks(params, fresh_cache(), prompt, table, 8)
    blk = np.asarray([m, 17, m, 3], np.int32)
    msk = np.asarray([False, False, False, True])
    got, _ = DENOISE(params, TINY, kv, jnp.asarray(blk)[None],
                     jnp.asarray(msk)[None], jnp.asarray([8]), table[None])
    want = ref.forward(params, TINY, prompt + blk.tolist(),
                       [False] * 8 + msk.tolist(), rows=slice(8, 12))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL)
    out = sdar.transfer(
        TINY, jnp.asarray(blk)[None], jnp.asarray(msk)[None],
        jnp.asarray([8]), jnp.asarray([3]), jnp.full((1, B), m),
        jnp.full((1, B), 0.5), jnp.asarray([True]))
    assert np.asarray(out[4]).tolist() == [[m, 17, m, m]]
    assert not np.asarray(out[1]).any()


@pytest.mark.parametrize("steps,threshold", [(4, 0.9), (4, 0.4), (2, 0.4),
                                              (1, 0.9)])
def test_transfer_is_the_reference_rule(steps, threshold):
    """`transfer` against the reference's `choose`, a lane at a time:
    random confidences with ties, every mask pattern, every step."""
    cfg = dataclasses.replace(TINY, denoising_steps=steps,
                              confidence_threshold=threshold)
    rng = np.random.default_rng(steps)
    L = 64
    conf = rng.choice([0.1, 0.3, 0.5, 0.7, 0.95], (L, B)).astype(np.float32)
    msk = rng.random((L, B)) < 0.6
    stp = rng.integers(0, steps, L)
    x0 = rng.integers(0, 250, (L, B))
    blk = rng.integers(0, 250, (L, B))
    pos = 4 * rng.integers(0, 9, L)
    valid = rng.random(L) < 0.8
    nb, nm, npos, nstp, out, n_thr = [np.asarray(a) for a in sdar.transfer(
        cfg, jnp.asarray(blk), jnp.asarray(msk), jnp.asarray(pos),
        jnp.asarray(stp), jnp.asarray(x0), jnp.asarray(conf),
        jnp.asarray(valid))]
    by_threshold = 0
    for i in range(L):
        if not valid[i]:
            assert (nb[i] == blk[i]).all() and (nm[i] == msk[i]).all()
            assert npos[i] == pos[i] and (out[i] == -1).all()
            continue
        if not msk[i].any():     # its commit pass
            assert npos[i] == pos[i] + B and nm[i].all() and nstp[i] == 0
            assert (out[i] == -1).all()
            continue
        take = ref.choose(cfg, conf[i], msk[i], int(stp[i]))
        high = msk[i] & (conf[i] > threshold)
        if high.sum() >= ref.n_transfer(cfg, int(stp[i])):
            by_threshold += int(take.sum())
        assert (nm[i] == (msk[i] & ~take)).all(), i
        assert (nb[i] == np.where(take, x0[i], blk[i])).all()
        assert npos[i] == pos[i] and nstp[i] == stp[i] + 1
        assert (out[i] == (nb[i] if not nm[i].any() else -1)).all()
    assert n_thr == by_threshold


def test_the_replay_equals_a_full_forward_a_pass(params):
    """`reference_logits` (clean keys once, every block's passes side by
    side) against the plain replay: one full forward a block and pass."""
    rng = np.random.default_rng(9)
    toks = rng.integers(0, 250, 18).tolist()      # 19 positions: 5 blocks
    got = np.asarray(ref.reference_logits(params, TINY, toks))
    assert got.shape == (18, TINY.vocab_size)
    n = len(toks)
    for b in range(1, 5):
        seq = toks[:b * B] + [0] * B
        flags = np.zeros(len(seq), bool)
        flags[b * B:] = True
        step = 0
        while flags.any():
            logits = np.asarray(ref.forward(
                params, TINY, seq, flags, rows=slice(b * B, b * B + B)))
            x0, conf = ref._confidence(logits)
            take = ref.choose(TINY, conf, flags[b * B:], step)
            for j in np.nonzero(take)[0]:
                p = b * B + j
                if p <= n:
                    np.testing.assert_allclose(got[p - 1], logits[j],
                                               atol=TOL)
                seq[p] = toks[p] if p < n else int(x0[j])
                flags[p] = False
            step += 1


def test_the_cache_after_generation_is_a_prefills(params):
    """What the passes leave in the cache for the committed blocks is
    what a block-causal prefill of prompt + output writes: a replay
    after a preemption, and a reused prefix, rebuild it exactly."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 250, 22).tolist()
    p0 = 20
    table = jnp.asarray([3, 7, 9, 11, 13, 2, 5, 14], jnp.int32)
    kv = _prefill_chunks(params, fresh_cache(), prompt[:p0], table, 12)
    state = sdar.new_lane_state(TINY, p0, prompt[p0:])[None]
    outs, state, kv = DENOISE_MULTI(params, TINY, kv, jnp.asarray(state),
                                    table[None], 16)
    outs = np.asarray(outs)[:, 0]
    blocks = [o for o in outs if o[0] >= 0]
    gen = [int(t) for o in blocks for t in o][2:]
    assert gen == ref.generate(params, TINY, prompt, len(gen))
    done = int(np.asarray(state)[0, 2 * B])       # committed positions
    assert done >= p0 + 2 * B
    seq = (prompt + gen)[:done]
    again = _prefill_chunks(params, fresh_cache(), seq, table, 8)
    for a, b in zip(kv[:2], again[:2]):
        a, b = np.asarray(a), np.asarray(b)
        for page in range(-(-done // BS)):
            live = min(BS, done - page * BS)
            blk = int(table[page])
            np.testing.assert_allclose(a[:, :, blk, :, :live],
                                       b[:, :, blk, :, :live], atol=1e-5)


# ---------------------------------------------------------------------------
# `upper`: the packed read's new operand
# ---------------------------------------------------------------------------


def _packed_inputs(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    nh, nkv, hd, nb = 4, 2, 16, 12
    lens = (21, 9)
    T = 32
    k_c = jnp.asarray(rng.standard_normal((1, nkv, nb, hd, BS)), dtype)
    v_c = jnp.asarray(rng.standard_normal((1, nkv, nb, hd, BS)), dtype)
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), dtype)
    seg = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    valid = np.zeros(T, bool)
    seg[:21], seg[21:30] = 0, 1
    pos[:21], pos[21:30] = 3 + np.arange(21), 8 + np.arange(9)
    valid[:30] = True
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    return q, k_c, v_c, tables, jnp.asarray(seg), jnp.asarray(pos), \
        jnp.asarray(valid), lens


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_upper_absent_is_the_program_it_was(impl):
    """Without `upper` the read is bit-equal to the causal one (`upper`
    = the positions themselves), with and without a window layer's
    `lower`: llama's and cohere2's inputs."""
    q, k_c, v_c, tables, seg, pos, valid, _ = _packed_inputs(
        dtype=jnp.float32 if impl == "xla" else jnp.bfloat16)
    for lower in (None, jnp.maximum(pos - 6, 0)):
        was = packed_prefill_attention(q, k_c, v_c, 0, tables, seg, pos,
                                       valid, impl=impl, lower=lower)
        now = packed_prefill_attention(q, k_c, v_c, 0, tables, seg, pos,
                                       valid, impl=impl, lower=lower,
                                       upper=pos)
        assert (np.asarray(was) == np.asarray(now)).all()


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_upper_is_a_dense_block_causal_mask(impl):
    """With `upper` = each query's block end the read equals a dense
    softmax under the block-causal mask, jnp and interpreted Pallas."""
    dt = jnp.float32 if impl == "xla" else jnp.bfloat16
    q, k_c, v_c, tables, seg, pos, valid, _ = _packed_inputs(1, dt)
    upper = pos // B * B + B - 1
    got = np.asarray(packed_prefill_attention(
        q, k_c, v_c, 0, tables, seg, pos, valid, impl=impl, upper=upper),
        np.float32)
    kf, vf = np.asarray(k_c, np.float32), np.asarray(v_c, np.float32)
    for t in np.nonzero(np.asarray(valid))[0]:
        pages = np.asarray(tables)[int(seg[t])]
        keys = np.concatenate([kf[0, :, p] for p in pages], axis=-1)
        vals = np.concatenate([vf[0, :, p] for p in pages], axis=-1)
        n = int(upper[t]) + 1
        for h in range(q.shape[1]):
            s = np.asarray(q[t, h], np.float32) @ keys[h // 2][:, :n] / 4.0
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vals[h // 2][:, :n].T
            np.testing.assert_allclose(
                got[t, h], want, atol=2e-5 if impl == "xla" else 3e-2)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _engine(cfg=TINY, **over):
    sizes = dict(model_config=cfg, block_size=BS, num_blocks=64,
                 max_blocks_per_seq=TABLE, max_num_seqs=LANES,
                 prefill_buckets=(16, 32), seed=7)
    sizes.update(over)
    return JaxEngine(EngineConfig(**sizes))


async def _generate(eng, rid, prompt, n, delay=0.0, frames=None, **sampling):
    await asyncio.sleep(delay)
    req = PreprocessedRequest(
        token_ids=prompt, request_id=rid,
        sampling=SamplingOptions(**{"temperature": 0.0, "seed": 0,
                                    **sampling}),
        stop=StopConditions(max_tokens=n, ignore_eos=True))
    toks = []
    async for out in eng.generate(req):
        assert not out.error, out.error
        toks.extend(out.token_ids)
        if frames is not None:
            frames.append(len(out.token_ids))
    return toks


def _median_confidence(params):
    """The median of the reference's first-pass confidences over a few
    blocks: a threshold at which lanes advance at different rates."""
    rng = np.random.default_rng(11)
    toks = rng.integers(0, 250, 24).tolist() + [0] * B
    flags = [False] * 24 + [True] * B
    _, conf = ref._confidence(np.asarray(ref.forward(
        params, TINY, toks, flags, rows=slice(24, 24 + B))))
    return float(np.median(conf))


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("rule", ["floor", "median"])
async def test_engine_tokens_are_the_reference_generators(params, rule,
                                                          overlap):
    """JaxEngine end to end through get_family: three lanes that join at
    different times, prompts of every length mod 4 and one with nothing
    to prefill, `max_tokens` not a multiple of 4, emit the reference
    generator's tokens under the floor rule (threshold 0.9: one token a
    pass) and under a threshold at the median confidence, where lanes
    advance at different rates inside one burst; a block is one frame;
    the counters add up."""
    thr = 0.9 if rule == "floor" else _median_confidence(params)
    cfg = dataclasses.replace(TINY, confidence_threshold=thr)
    eng = _engine(cfg, overlap_scheduling=overlap)
    assert get_family(eng.model_cfg) is sdar and eng._gen_block == B
    assert eng._packed_prefill_ok and eng.config.enable_prefix_caching
    rng = np.random.default_rng(3)
    lens, outs_n = (37, 18, 3, 24), (13, 10, 7, 9)
    prompts = [rng.integers(0, 250, n).tolist() for n in lens]
    frames = [[] for _ in lens]
    got = await asyncio.gather(*[
        _generate(eng, f"r{i}", p, n, 0.03 * i, frames[i])
        for i, (p, n) in enumerate(zip(prompts, outs_n))])
    denoise = blocks = unmasked = by_thr = 0
    for p, n, toks, fr in zip(prompts, outs_n, got, frames):
        st = {}
        assert toks == ref.generate(eng.params, cfg, p, n, stats=st)
        assert len(toks) == n
        tail = len(p) % B
        assert fr[0] == min(B - tail, n) and max(fr) <= B
        denoise += st["denoise_passes"]
        blocks += st["blocks"]
        unmasked += st["blocks"] * B - tail
        by_thr += st["threshold_transfers"]
    m = eng.metrics
    assert m["decode_tokens"] == sum(outs_n)
    # emitted + truncated; a request's last block is never committed
    assert m["diff_tokens_unmasked"] == unmasked >= sum(outs_n)
    assert m["diff_blocks_done"] == blocks
    assert m["diff_commit_passes"] == blocks - len(lens)
    assert m["diff_lane_passes"] == denoise + blocks - len(lens)
    assert m["diff_rows"] % (LANES * B) == 0 and m["diff_rows"] \
        >= m["diff_lane_passes"] * B
    assert m["prefill_tokens"] == sum(n // B * B for n in lens)
    if rule == "floor":
        assert by_thr == m["diff_threshold_transfers"] == 0
    else:
        # the device also counts passes a lane ran past its request's end
        assert 0 < by_thr <= m["diff_threshold_transfers"]
        assert m["diff_lane_passes"] < 5 * blocks
    assert m["moe_picks.decode"] == m["moe_picks_held.decode"] > 0
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"] \
        == m["moe_visited_form_slots.decode"]
    assert m["decode_attn_live_blocks"] > 0
    recs = [r for r in eng.fpm if r.get("kind") == "decode"]
    assert recs and all(set(r) == {"t", "kind", "k", "lanes", "gap_s"}
                        for r in recs)
    assert m["req_stage_n"] == len(lens)
    await eng.close()


async def test_mask_tokens_in_the_prompt_are_tokens(params):
    """A prompt whose tail (the first block's unmasked part) and body
    hold mask_token_id: kept, and the tokens are the reference's."""
    eng = _engine()
    m = TINY.mask_token_id
    prompt = [5, m, 9, m, 11, 2, m, 4, 8, m, m]     # tail m, m
    toks = await _generate(eng, "m", prompt, 6)
    assert toks == ref.generate(eng.params, TINY, prompt, 6)
    await eng.close()


async def test_a_reused_prefix_and_a_preempted_sequence(params):
    """Prefix caching sees committed blocks only and a hit's tail starts
    on a block boundary; a pool too small for two long answers preempts
    one sequence, whose replay through the block-causal prefill rebuilds
    its cache: both emit what they emit alone."""
    eng = _engine()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 250, 50).tolist()
    tails = [rng.integers(0, 250, n).tolist() for n in (9, 14)]
    for i, tail in enumerate(tails):
        toks = await _generate(eng, f"p{i}", shared + tail, 11)
        assert toks == ref.generate(eng.params, TINY, shared + tail, 11)
    assert eng.metrics["cache_hit_tokens"] == 48
    await eng.close()

    prompts = [rng.integers(0, 250, 40).tolist() for _ in range(2)]
    alone = _engine()
    want = [await _generate(alone, f"a{i}", p, 50)
            for i, p in enumerate(prompts)]
    await alone.close()
    tight = _engine(num_blocks=10, enable_prefix_caching=False)
    got = await asyncio.gather(*[_generate(tight, f"t{i}", p, 50)
                                 for i, p in enumerate(prompts)])
    assert tight.metrics["preemptions"] > 0
    assert got == want
    await tight.close()


async def test_a_sampled_request_is_reproducible(params):
    """Temperature, top-k and top-p go through engine/sampler.py's block
    sampler, one distribution a (lane, position): the same seed gives the
    same tokens, another seed others, and a greedy neighbour is not
    disturbed."""
    eng = _engine()
    prompt = list(range(3, 21))
    a = await _generate(eng, "s1", prompt, 10, temperature=0.9, top_k=20,
                        top_p=0.95, seed=5)
    b, g = await asyncio.gather(
        _generate(eng, "s2", prompt, 10, temperature=0.9, top_k=20,
                  top_p=0.95, seed=5),
        _generate(eng, "g", prompt, 10))
    c = await _generate(eng, "s3", prompt, 10, temperature=0.9, top_k=20,
                        top_p=0.95, seed=6)
    assert a == b and a != c and len(a) == 10
    assert g == ref.generate(eng.params, TINY, prompt, 10)
    await eng.close()


async def test_what_needs_one_distribution_a_token_is_refused():
    """Guided decoding, penalties and a disagg prefill are refused at
    admission with a clear error; tp > 1, KVBM tiers, a disagg pull and
    LoRA refuse the configuration; int8 cache and speculation fall back
    (warned): no silently wrong answer on any of them."""
    eng = _engine()
    for what, sampling in (
            ("guided decoding", SamplingOptions(
                temperature=0.0, guided_json={"type": "object"})),
            ("penalties", SamplingOptions(temperature=0.0,
                                          frequency_penalty=0.5))):
        req = PreprocessedRequest(token_ids=[1, 2, 3, 4, 5],
                                  request_id=what, sampling=sampling,
                                  stop=StopConditions(max_tokens=4))
        outs = [o async for o in eng.generate(req)]
        assert len(outs) == 1 and outs[0].finish_reason == "error"
        assert what in outs[0].error and "by blocks" in outs[0].error
    assert eng.metrics["requests"] == 0
    await eng.close()
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
    with pytest.raises(ValueError, match="multiple of"):
        _engine(block_size=6)
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert set(sdar.UNSUPPORTED) >= {
        "kv_int8", "speculation", "lora", "ring_prefill", "kvbm", "disagg",
        "tp", "guided", "penalties", "logprobs"}


@pytest.mark.parametrize("program", ["prefill", "passes"])
def test_op_scopes_name_the_parts_of_a_block(program):
    """What a profiler groups device ops by: both programs carry the
    layer's scopes, the pass burst the transfer rule's too."""
    S = jax.ShapeDtypeStruct
    prm = jax.eval_shape(lambda: sdar.init_params(TINY,
                                                  jax.random.PRNGKey(0)))
    kv = tuple(S(s, d) for s, d in zip(
        sdar.kv_cache_shapes(TINY, 24, BS), sdar.kv_cache_dtypes(TINY)))
    i32 = jnp.int32
    scopes = ["dyn.attn_qkv", "dyn.kv_write", "dyn.attention",
              "dyn.attn_out", "dyn.moe_router", "dyn.moe_dispatch",
              "dyn.lm_head"]
    if program == "prefill":
        low = PREFILL.lower(prm, TINY, kv, S((32,), i32), S((32,), i32),
                            S((TABLE,), i32), S((), i32), S((), i32))
    else:
        low = DENOISE_MULTI.lower(
            prm, TINY, kv, S((LANES, sdar.lane_state_width(TINY)), i32),
            S((LANES, TABLE), i32), 4)
        scopes.append("dyn.diff_transfer")
    text = low.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
