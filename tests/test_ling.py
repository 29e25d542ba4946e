"""Delta-rule linear attention (KDA) with one latent-attention layer a
period over group-routed experts held as a share (models/ling.py,
ops/delta_attention.py): the paged path against the plain float32
reference of benchmark/reference/ling.py, at tiny widths on the CPU.

d 64, 4 heads of 16, chunks of 8 tokens (sub-chunks of 2), block 16,
6 layers, one period of the published pattern (5 KDA, MLA at 5; two
leading dense layers, four expert layers), 32 router
outputs in 4 groups of 8 with 2 kept, a share of 8 held.  Everything is
float32 here, so program and reference differ by summation order only:
the reference is the token-by-token recurrence, the program the chunked
form with its triangular solve."""

import asyncio
import dataclasses
from functools import partial

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ling as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import get_family, ling
from dynamo_tpu.models.ling import LingConfig
from dynamo_tpu.models.moe import moe_dispatch_dense, moe_dispatch_visited
from dynamo_tpu.ops.delta_attention import kda_chunked, kda_step, l2norm
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = LingConfig(dtype=jnp.float32, experts_held=(0, 8), mla_q_block=16)
# the family's programs, compiled once a shape as the engine does
PREFILL = jax.jit(ling.prefill, static_argnums=1)
PREFILL_BATCHED = jax.jit(ling.prefill_batched, static_argnums=1)
DECODE = jax.jit(ling.decode, static_argnums=1)
DECODE_MULTI = jax.jit(ling.decode_multi, static_argnums=(1, 7))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation (the
# chunked rule's solve and matmuls against a scan over tokens, blocked
# gathers), measured at 6e-6 on logits of magnitude 3; 1e-4 leaves room
# for another platform's reductions and is two orders under the
# smallest effect of a left-out detail (below)
TOL = 1e-4


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES, dirty=False):
    """`dirty`: state and tail full of ones, as a lane that another
    sequence held would be (no program clears a lane)."""
    fill = jnp.ones if dirty else jnp.zeros
    kv = [jnp.zeros(s, d) for s, d in zip(
        ling.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
        ling.kv_cache_dtypes(cfg))]
    kv[2], kv[3] = fill(kv[2].shape, kv[2].dtype), fill(kv[3].shape,
                                                        kv[3].dtype)
    return tuple(kv)


def lanes_of(x, lane, dtype=np.int32):
    a = np.zeros((LANES,) + np.shape(x), dtype)
    a[lane] = x
    return jnp.asarray(a)


def paged_logits(params, cfg, toks, prompt_len, lane=2, bucket=32,
                 fused=0, kv=None):
    """Logits at positions prompt_len - 1 .. len(toks) - 1 from the paged
    path: chunked prefill of the prompt (chunks of `bucket`: the state is
    carried between them), then teacher-forced decode through the cache;
    with `fused`, one burst of that many steps through decode_multi
    (which returns tokens).  The lane starts DIRTY."""
    kv = fresh_cache(cfg, dirty=True) if kv is None else kv
    table = np.zeros(TABLE, np.int32)
    table[:7] = [3, 7, 9, 11, 13, 2, 5]
    pos, out = 0, []
    while pos < prompt_len:
        chunk = min(bucket, prompt_len - pos)
        t = np.zeros(bucket, np.int32)
        t[:chunk] = toks[pos:pos + chunk]
        logits, kv = PREFILL(
            params, cfg, kv, jnp.asarray(t),
            jnp.asarray(pos + np.arange(bucket, dtype=np.int32)),
            jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk),
            lanes=jnp.int32(lane))
        pos += chunk
    out.append(np.asarray(logits))
    valid = lanes_of(True, lane, bool)
    step = prompt_len
    while step < len(toks):
        args = (params, cfg, kv, lanes_of(toks[step], lane),
                lanes_of(step, lane), lanes_of(table, lane),
                lanes_of(step, lane))
        if fused:
            got, kv = DECODE_MULTI(*args, fused, valid=valid)
            return out, np.asarray(got)[:, lane], kv
        logits, kv = DECODE(*args, valid=valid)
        out.append(np.asarray(logits)[lane])
        step += 1
    return out, None, kv


@pytest.fixture(scope="module")
def model():
    params = ling.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 70)
    full, states = ref.reference_forward(params, TINY, toks.tolist())
    return params, toks, np.asarray(full), states


def test_layer_pattern_is_the_published_one():
    assert TINY.layers_of(ling.MLA) == (5,)
    assert TINY.layers_of(ling.KDA) == (0, 1, 2, 3, 4)
    big = ling.PRESETS["ling-3.0-flash"]
    assert big.layers_of(ling.MLA) == (5, 11, 17, 23, 29, 35, 41)


def test_paged_path_matches_reference_logits(model):
    """Prompt of 50 tokens prefilled as 32 + 18 (two programs: the state
    carried once, the second padded to its bucket, a chunk of the rule
    cut by the prompt's end), then 20 decode steps across the block
    boundary at 64, on a lane that was dirty; the state itself agrees."""
    params, toks, full, states = model
    got, _, kv = paged_logits(params, TINY, toks, 50)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[49 + i], rtol=0, atol=TOL)
    # after the last decode step the state has seen all 70 tokens
    for pli, li in enumerate(TINY.layers_of(ling.KDA)):
        np.testing.assert_allclose(np.asarray(kv[2][pli, 2]),
                                   np.asarray(states[li]), atol=TOL)
    # the other lanes are as they were: ones
    assert float(jnp.abs(kv[2][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 3] - 1).max()) == 0.0


def test_prompt_of_several_programs_carries_the_state(model):
    """Buckets of 16: a prompt of 50 is four programs, the state handed
    on three times and the convolution's tail with it."""
    params, toks, full, _ = model
    got, _, _ = paged_logits(params, TINY, toks[:52], 50, bucket=16)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[49 + i], rtol=0, atol=TOL)


def test_fused_burst_crosses_chunk_and_block_boundary(model):
    """decode_multi from position 60 for 8 steps (the block boundary at
    64 and a multiple of the rule's chunk inside the burst) chains the
    reference's own greedy tokens; idle lanes do not decay."""
    params, toks, _, _ = model
    _, burst, kv = paged_logits(params, TINY, toks[:61], 60, fused=8)
    # causal: one forward over prompt + burst gives every step's argmax
    seq = toks[:61].tolist() + [int(t) for t in burst[:-1]]
    logits = ref.reference_logits(params, TINY, seq)
    assert [int(jnp.argmax(logits[60 + j])) for j in range(8)] \
        == [int(t) for t in burst]
    assert float(jnp.abs(kv[2][:, 1] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 1] - 1).max()) == 0.0


def test_two_lanes_of_different_length_in_one_burst(model):
    """Lane 0 at position 20 and lane 3 at position 45 step together:
    each gets its own sequence's logits."""
    params, toks, full, _ = model
    other = np.random.default_rng(5).integers(3, TINY.vocab_size, 30)
    full_o = np.asarray(ref.reference_logits(params, TINY, other.tolist()))
    kv = fresh_cache(dirty=True)
    ta, tb = np.zeros(TABLE, np.int32), np.zeros(TABLE, np.int32)
    ta[:2], tb[:4] = [4, 6], [1, 8, 10, 12]
    for seq, n, table, lane in ((other, 20, ta, 0), (toks, 45, tb, 3)):
        for pos in range(0, n, 32):
            chunk = min(32, n - pos)
            t = np.zeros(32, np.int32)
            t[:chunk] = seq[pos:pos + chunk]
            _, kv = PREFILL(
                params, TINY, kv, jnp.asarray(t),
                jnp.asarray(pos + np.arange(32, dtype=np.int32)),
                jnp.asarray(table), jnp.int32(pos), jnp.int32(chunk),
                lanes=jnp.int32(lane))
    tables = np.zeros((LANES, TABLE), np.int32)
    tables[0], tables[3] = ta, tb
    valid = jnp.asarray([True, False, False, True])
    for j in range(3):
        cur = np.array([20 + j, 0, 0, 45 + j], np.int32)
        tok = np.array([other[20 + j], 0, 0, toks[45 + j]], np.int32)
        logits, kv = DECODE(params, TINY, kv, jnp.asarray(tok),
                                 jnp.asarray(cur), jnp.asarray(tables),
                                 jnp.asarray(cur), valid=valid)
        np.testing.assert_allclose(np.asarray(logits[0]), full_o[20 + j],
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(logits[3]), full[45 + j],
                                   atol=TOL)


def test_padded_row_beside_a_full_one(model):
    """prefill_batched: a row of 32 tokens, a row of 11 padded to 32 and
    a filler row of none (lane 0, as the engine pads).  Both real rows
    agree with the reference; the short row's state and tail are what
    its 11th token left (a later chunk continues from them); lane 0
    keeps what it held."""
    params, toks, full, _ = model
    short = np.random.default_rng(6).integers(3, TINY.vocab_size, 24)
    full_s = np.asarray(ref.reference_logits(params, TINY, short.tolist()))
    kv = fresh_cache(dirty=True)
    rows = np.zeros((4, 32), np.int32)
    rows[0], rows[1, :11] = toks[:32], short[:11]
    tables = np.zeros((4, TABLE), np.int32)
    tables[0, :3], tables[1, :2] = [3, 7, 9], [11, 13]
    pos = np.tile(np.arange(32, dtype=np.int32), (4, 1))
    logits, kv = PREFILL_BATCHED(
        params, TINY, kv, jnp.asarray(rows), jnp.asarray(pos),
        jnp.asarray(tables), jnp.zeros(4, jnp.int32),
        jnp.asarray([32, 11, 0, 0], jnp.int32),
        lanes=jnp.asarray([2, 1, 0, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), full[31], atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), full_s[10], atol=TOL)
    assert float(jnp.abs(kv[2][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 0] - 1).max()) == 0.0
    # the short row goes on from position 11 to 24
    t = np.zeros(32, np.int32)
    t[:13] = short[11:]
    logits, kv = PREFILL(
        params, TINY, kv, jnp.asarray(t),
        jnp.asarray(11 + np.arange(32, dtype=np.int32)),
        jnp.asarray(tables[1]), jnp.int32(11), jnp.int32(13),
        lanes=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(logits), full_s[23], atol=TOL)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of: the delta
    correction, the decay, its being a channel's and not a head's, its
    lower bound, the short convolution, the L2 norm of q and k, the
    head-wise output gate, the group limit of the routing, the routed
    scale, the MLA layers' rotary."""
    params, toks, full, _ = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    got, _, _ = paged_logits(params, TINY, toks, 50)
    worst = max(float(np.abs(row - without[49 + i]).max())
                for i, row in enumerate(got))
    assert worst > 100 * TOL, (detail, worst)
    assert float(np.abs(full - without).max()) > 100 * TOL


def _rule_inputs(case, T=200, H=4, dk=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = l2norm(jax.random.normal(ks[0], (T, H, dk)))
    k = l2norm(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (T, H)))
    log_a = {"mixed": -5 * jax.nn.sigmoid(
                 2 * jax.random.normal(ks[4], (T, H, dk))),
             "near_one": jnp.full((T, H, dk), -1e-6),
             "at_the_bound": jnp.full((T, H, dk), -5.0),
             "equal_keys": jnp.zeros((T, H, dk))}[case]
    if case == "equal_keys":
        q = k = jnp.broadcast_to(k[:1], k.shape)
        beta = jnp.full((T, H), 0.999)
    return q, k, v, log_a, beta, jax.random.normal(ks[5], (H, dk, dk))


@pytest.mark.parametrize("case", ["mixed", "near_one", "at_the_bound",
                                  "equal_keys"])
@pytest.mark.parametrize("chunk,sub", [(64, 16), (8, 2)])
def test_chunked_rule_equals_the_token_recurrence(case, chunk, sub):
    """200 tokens from a random state, the gates pinned at both ends of
    their range: a near 1 (nothing forgotten: the solve carries the
    whole chunk) and log a = -5 on every channel (320 nats a chunk of
    64: a factor exp(-cumsum) would overflow float32 at the 18th
    token); and every key the same with beta near 1 and no decay (A is
    then all ones under the diagonal: its powers grow to 1e22 before
    they cancel, forward substitution does not care).  1e-4 absolute on
    outputs of magnitude 1: float32 round-off through the 64-row
    substitution measured 2e-6."""
    args = _rule_inputs(case)
    want_o, want_S = ref.token_recurrence(*args, 0.25)
    got_o, got_S = kda_chunked(*args, 0.25, chunk=chunk, sub=sub)
    assert bool(jnp.isfinite(got_o).all())
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S),
                               atol=1e-4)


def test_steps_equal_the_chunked_rule():
    """kda_step T times is kda_chunked over T; a lane that is not valid
    keeps its state bit for bit."""
    q, k, v, log_a, beta, S0 = _rule_inputs("mixed", T=40)
    want_o, want_S = kda_chunked(q, k, v, log_a, beta, S0, 0.25)
    S = jnp.stack([S0, S0])
    valid = jnp.asarray([True, False])
    outs = []
    for t in range(40):
        two = lambda x: jnp.stack([x[t], x[t]])
        o, S = kda_step(two(q), two(k), two(v), two(log_a), two(beta), S,
                        0.25, valid)
        outs.append(o[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(want_o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(want_S),
                               atol=1e-5)
    assert bool((S[1] == S0).all())


# the kernel's body on the CPU: the form a decode step takes on the chip
_visited = partial(moe_dispatch_visited, interpret=True)


@pytest.mark.parametrize("dispatch", [moe_dispatch_dense, _visited],
                         ids=["moe_dispatch_dense", "moe_dispatch_visited"])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """Under group-limited routing, the parts that the four shares of 8
    experts give, with the shared expert (which every chip computes
    alike) counted once, add up to what the program gives with all 32
    held, and to the reference's uncut layer; a share alone equals the
    reference given the same share."""
    whole = dataclasses.replace(TINY, experts_held=None)
    params = ling.init_params(whole, jax.random.PRNGKey(3))
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(4), (9, whole.d_model))
    w, ids = ling.ds_router(layer, whole, x)
    rw, rids = ref._route(whole, layer, x, "")
    assert (np.asarray(ids) == np.asarray(rids)).all()
    # the group limit binds: plain top-4 of 32 chooses differently
    assert (np.asarray(ref._route(whole, layer, x, "group_limit")[1])
            != np.asarray(ids)).any()
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    shared = ling._mlp(layer["shared"], x)
    uncut = dispatch(layer, whole, x, w, ids) + shared
    np.testing.assert_allclose(
        np.asarray(uncut),
        np.asarray(ref._routed(whole, layer, x, w, ids) + shared),
        atol=1e-5)
    total = shared
    for rank in range(4):
        cfg = dataclasses.replace(whole, experts_held=(8 * rank, 8))
        held = {k: (v[8 * rank:8 * rank + 8] if k.startswith("moe_w_")
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
    """JaxEngine end to end through get_family: five requests over four
    lanes (batched chunked prefill, fused bursts, lanes side by side, a
    lane REUSED by the fifth sequence without a clearing program) emit
    the reference's greedy tokens; the counters are fed."""
    eng = _engine()
    assert get_family(eng.model_cfg) is ling
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    sizes = ((50, 30), (37, 20), (70, 25), (20, 12), (45, 16))
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
    # buckets of at most 32: every prompt but the 20-token one is carried
    assert 0 < m["recurrent_carried_tokens.prefill"] < total
    assert m["recurrent_resets"] == 5
    assert 0 < m["recurrent_lane_steps.decode"] \
        <= m["recurrent_slot_steps.decode"]
    assert 0 < m["decode_attn_live_blocks"] <= m["decode_attn_read_blocks"]
    layers = TINY.n_layers - TINY.first_k_dense
    assert m["moe_picks.prefill"] == total * layers * 4
    assert 0 < m["moe_picks_held.prefill"] < m["moe_picks.prefill"]
    assert 0 < m["moe_picks_held.decode"] < m["moe_picks.decode"]
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
    await eng.close()


async def test_reused_lane_equals_the_sequence_alone():
    """One lane: the second sequence takes the lane the first one left
    (its state and tail still there) and emits what it emits alone."""
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
    its state rebuilt by the replay from position 0, and it emits what
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


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
async def test_engine_counts_the_lanes_the_state_step_moves(impl):
    """`state_moved_lane_steps.decode` under both impls of the state's
    decode step (`state_impl`: the state's own conditions, asked from the
    host as the traced step asks them): the kernel moves the busy lanes
    of every state layer (= `state_live_lane_steps.decode`: the share
    reads 100 %), the jnp step every slot; the tokens are the
    reference's either way (three requests over four lanes: lanes join
    and finish inside bursts, one slot stays idle)."""
    eng = _engine(attn_impl=impl)
    assert ling.state_impl(eng.model_cfg, eng.model_cfg.attn_impl) == impl
    rng = np.random.default_rng(5)
    sizes = ((23, 14), (40, 9), (17, 20))
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
    m, layers = eng.metrics, len(TINY.layers_of(ling.KDA))
    assert layers > 0
    live, moved = (m[f"state_{x}_lane_steps.decode"]
                   for x in ("live", "moved"))
    assert 0 < live < layers * m["recurrent_slot_steps.decode"]
    assert moved == (live if impl == "pallas_interpret"
                     else layers * m["recurrent_slot_steps.decode"])
    await eng.close()


async def test_prefill_through_the_chunk_kernel_gives_the_jnp_tokens():
    """A prompt of two prefill programs (32 tokens from zeros, 18 more
    in a bucket of 32 from the CARRIED state) with the chunked rule in
    ops/pallas_chunk_state.py's kernel (`chunk_impl`: the bucket's own
    conditions, asked from the host as the traced program asks them)
    emits the tokens it emits under the jnp form, and
    `state_chunk_kernel_tokens.prefill` counts its tokens a KDA layer
    (all of `state_chunk_tokens.prefill`: the share reads 100 %); under
    the jnp form it stays 0."""
    prompt = np.random.default_rng(9).integers(
        3, TINY.vocab_size, 50).tolist()
    layers = len(TINY.layers_of(ling.KDA))
    got = {}
    for impl in ("jnp", "pallas_interpret"):
        eng = _engine(attn_impl=impl)
        assert ling.chunk_impl(eng.model_cfg, eng.model_cfg.attn_impl,
                               32) == impl
        assert ling.chunk_impl(eng.model_cfg, eng.model_cfg.attn_impl,
                               4) == "jnp"             # under one chunk
        got[impl] = await _generate(eng, "r", prompt, 12)
        m = eng.metrics
        assert m["recurrent_carried_tokens.prefill"] == 18   # two programs
        assert m["state_chunk_tokens.prefill"] == layers * 50
        assert m["state_chunk_kernel_tokens.prefill"] == (
            layers * 50 if impl == "pallas_interpret" else 0)
        await eng.close()
    assert got["pallas_interpret"] == got["jnp"]


def test_unsupported_features_refuse_or_fall_back():
    """Prefix caching asked for is refused at start-up (switched off,
    warned: a reused latent block says nothing of the state at its
    end); tp > 1, KVBM tiers and a disagg pull refuse the configuration;
    int8 cache and speculation fall back; LoRA and a non-zero SwiGLU
    limit refuse: no silently wrong answer on any of them."""
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
    with pytest.raises(ValueError, match="SwiGLU limit"):
        dataclasses.replace(TINY, swiglu_limits=(0.0,) * 11 + (4.0,))
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert set(ling.UNSUPPORTED) >= {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "packed_prefill", "kvbm", "disagg", "tp"}
