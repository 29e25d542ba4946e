"""Mamba-2 layers beside NoPE GQA attention, every layer a mixer AND a
gated MLP under muP multipliers (models/granite_hybrid.py, the mixer of
models/mamba2.py that models/nemotron_h.py shares): the paged path
against the plain float32 reference of
benchmark/reference/granitemoehybrid.py, at tiny widths on the CPU.

The widths keep the published SHAPE: ONE group of B and C for 4 heads of
8 over a state of 16, chunks of 8; 4 query heads over 2 KV heads of 16
with the scores scaled by 1/16 (1 / head_dim, not 1 / sqrt); an MLP of
128; TWO whole periods 5 M + A + 4 M = 20 layers; block 16.  Everything
is float32 here, so program and reference differ by summation order
only: the reference is the token-by-token recurrence, the program the
chunked form."""

import asyncio
import dataclasses
import json
import os
from functools import partial

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import granitemoehybrid as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import PRESETS, get_family
from dynamo_tpu.models import granite_hybrid as gh
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.granite_hybrid import ATTN, MAMBA, GraniteHybridConfig
from dynamo_tpu.ops.lane_state import lanes_plan, lanes_step
from dynamo_tpu.ops.pallas_lane_state import head_block_for, ssd_lanes_step
from dynamo_tpu.ops.ssm import ssd_step
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = GraniteHybridConfig(dtype=jnp.float32, layer_kinds=gh.PERIOD * 2)
# the family's programs, compiled once a shape as the engine does
PREFILL = jax.jit(gh.prefill, static_argnums=1)
PREFILL_BATCHED = jax.jit(gh.prefill_batched, static_argnums=1)
DECODE = jax.jit(gh.decode, static_argnums=1)
DECODE_MULTI = jax.jit(gh.decode_multi, static_argnums=(1, 7))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation (the
# chunked form's matmuls against a scan over tokens, blocked gathers),
# measured at 1e-7 on logits of magnitude 0.08 (normed stream x an
# embedding of 0.02, over logits_scaling 8); 2e-6 leaves room for
# another platform's reductions and is three orders under the smallest
# effect of a left-out detail (a rotary: 4.7e-3)
TOL = 2e-6
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "granite-4.0-h-micro.json")


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES, dirty=False):
    """`dirty`: state and tail full of ones, as a lane that another
    sequence held would be (no program clears a lane)."""
    fill = jnp.ones if dirty else jnp.zeros
    kv = [jnp.zeros(s, d) for s, d in zip(
        gh.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
        gh.kv_cache_dtypes(cfg))]
    kv[2], kv[3] = fill(kv[2].shape, kv[2].dtype), fill(kv[3].shape,
                                                        kv[3].dtype)
    return tuple(kv)


def lanes_of(x, lane, dtype=np.int32):
    a = np.zeros((LANES,) + np.shape(x), dtype)
    a[lane] = x
    return jnp.asarray(a)


def paged_logits(params, cfg, toks, prompt_len, lane=2, bucket=32,
                 fused=0):
    """Logits at positions prompt_len - 1 .. len(toks) - 1 from the paged
    path: chunked prefill of the prompt (chunks of `bucket`: the state is
    carried between them), then teacher-forced decode through the cache;
    with `fused`, one burst of that many steps through decode_multi
    (which returns tokens).  The lane starts DIRTY."""
    kv = fresh_cache(cfg, dirty=True)
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


def worst(got, full, start=49):
    return max(float(np.abs(row - full[start + i]).max())
               for i, row in enumerate(got))


@pytest.fixture(scope="module")
def model():
    params = gh.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 70)
    full, states = ref.reference_forward(params, TINY, toks.tolist())
    return params, toks, np.asarray(full), states


@pytest.fixture(scope="module")
def paged(model):
    """The paged path's logits at positions 49 .. 69 (prompt of 50 in two
    programs, 20 decode steps) and the cache after them."""
    params, toks, _, _ = model
    got, _, kv = paged_logits(params, TINY, toks, 50)
    return got, kv


def test_layers_are_the_published_periods():
    """Four periods of 5 Mamba-2, 1 attention, 4 Mamba-2; the cache's
    layer axes are indexed by kind; the state member at the cell's 64
    lanes is the 4.89 GB the configuration states."""
    big = PRESETS["granite-4.0-h-micro"]
    assert get_family(big) is gh and big.n_layers == 40
    assert big.layers_of(ATTN) == (5, 15, 25, 35)
    assert len(big.layers_of(MAMBA)) == 36
    assert big.ssm.conv_dim == 4352 and big.ssm.in_dim == 8512
    assert big.q_scale == 0.125         # 1/64 * sqrt(64): exact in bf16
    shapes = gh.kv_cache_shapes(big, 2881, 128, lanes=64)
    assert shapes == ((4, 8, 2881, 64, 128), (4, 8, 2881, 64, 128),
                      (36, 64, 64, 64, 128), (36, 64, 3 * 4352))
    assert gh.kv_cache_dtypes(big)[2] == jnp.float32
    assert 4 * np.prod(shapes[2]) == 4831838208 > 2 ** 32
    assert TINY.layers_of(ATTN) == (5, 15) and TINY.ssm_groups == 1


def test_the_period_is_the_shortest_repeat():
    """The programs compile one period and go over the periods (prefill
    in a `lax.scan`, decode unrolled over static slices of the stacked
    weights); a layer list with no repeat is one period."""
    assert TINY.period == gh.PERIOD and TINY.n_periods == 2
    big = PRESETS["granite-4.0-h-micro"]
    assert big.period == gh.PERIOD and big.n_periods == 4
    odd = dataclasses.replace(TINY, layer_kinds=(MAMBA, ATTN, MAMBA, MAMBA))
    assert odd.period == odd.layer_kinds and odd.n_periods == 1
    flat = dataclasses.replace(TINY, layer_kinds=(MAMBA,) * 3)
    assert flat.period == (MAMBA,) and flat.n_periods == 3
    params = gh.init_params(odd, jax.random.PRNGKey(1))
    assert [lp["norm"]["norm"].shape for lp in params["layers"]] \
        == [(1, 64)] * 4
    toks = np.random.default_rng(4).integers(3, odd.vocab_size, 40)
    full = np.asarray(ref.reference_logits(params, odd, toks.tolist()))
    got, _, _ = paged_logits(params, odd, toks, 36)
    assert worst(got, full, start=35) <= TOL


def test_paged_path_matches_reference_logits(model, paged):
    """Prompt of 50 tokens prefilled as 32 + 18 (two programs: the state
    carried once, the second padded to its bucket, a chunk of the scan
    cut by the prompt's end; the attention layers' second read crosses
    into the cached context), then 20 decode steps across the block
    boundary at 64, on a lane that was dirty (a reused lane starts from
    zeros); the state itself agrees; idle lanes keep state and tail."""
    _, _, full, states = model
    got, kv = paged
    assert len(got) == 21 and worst(got, full) <= TOL
    # after the last decode step the state has seen all 70 tokens
    for pli, li in enumerate(TINY.layers_of(MAMBA)):
        np.testing.assert_allclose(np.asarray(kv[2][pli, 2]),
                                   np.asarray(states[li]), atol=1e-5,
                                   rtol=1e-5)
    # the other lanes are as they were: ones
    for lane in (0, 1, 3):
        assert float(jnp.abs(kv[2][:, lane] - 1).max()) == 0.0
        assert float(jnp.abs(kv[3][:, lane] - 1).max()) == 0.0


def test_prompt_of_several_programs_carries_the_state(model):
    """Buckets of 16: a prompt of 50 is four programs, the state handed
    on three times and the convolution's tail with it; the attention
    layers read three programs' keys back from the pool."""
    params, toks, full, _ = model
    got, _, _ = paged_logits(params, TINY, toks[:52], 50, bucket=16)
    assert worst(got, full) <= TOL


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_both_forms_of_the_prefill_read_take_the_scaled_query(model, impl):
    """The attention layers' prefill read as the float32 flash scan and
    as the Pallas kernel under the interpreter: both see q already
    scaled by attention_multiplier * sqrt(head_dim)."""
    params, toks, full, _ = model
    cfg = dataclasses.replace(TINY, packed_attn_impl=impl)
    got, _, _ = paged_logits(params, cfg, toks[:51], 50)
    assert worst(got, full) <= TOL


def test_fused_burst_crosses_chunk_and_block_boundary(model):
    """decode_multi from position 60 for 8 steps (the block boundary at
    64 inside the burst) chains the reference's own greedy tokens; idle
    lanes keep state and tail bit for bit."""
    params, toks, _, _ = model
    _, burst, kv = paged_logits(params, TINY, toks[:61], 60, fused=8)
    seq = toks[:61].tolist() + [int(t) for t in burst[:-1]]
    logits = ref.reference_logits(params, TINY, seq)
    assert [int(jnp.argmax(logits[60 + j])) for j in range(8)] \
        == [int(t) for t in burst]
    assert float(jnp.abs(kv[2][:, 1] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 1] - 1).max()) == 0.0


def test_padded_row_beside_a_full_one(model):
    """prefill_batched: a row of 32 tokens, a row of 11 padded to 32 and
    filler rows of none (lane 0, as the engine pads).  Both real rows
    agree with the reference; the short row's state is what its 11th
    token left; lane 0 keeps what it held."""
    params, toks, full, _ = model
    short = np.random.default_rng(6).integers(3, TINY.vocab_size, 24)
    full_s, want = ref.reference_forward(params, TINY, short[:11].tolist())
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
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(full_s)[10], atol=TOL)
    assert float(jnp.abs(kv[2][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 0] - 1).max()) == 0.0
    for pli, li in enumerate(TINY.layers_of(MAMBA)):
        np.testing.assert_allclose(np.asarray(kv[2][pli, 1]),
                                   np.asarray(want[li]), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, paged,
                                                         detail):
    """The comparison is tight enough to notice each of: the residual
    multiplier (on both adds), the embedding multiplier, a score scale
    of 1 / sqrt(head_dim) (1/4 here, 1/8 published) for
    attention_multiplier (1/16 here, 1/64 published), the logits'
    division, a rotary that the attention does not have, a head that is
    not the embedding, and norm-then-gate for gate-then-norm."""
    params, toks, _, _ = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    assert worst(paged[0], without) > 100 * TOL, detail


def test_a_bfloat16_state_breaks_agreement(model):
    """The configuration states a float32 state: the same program with
    the state member held in bfloat16 between steps and programs is
    outside the tolerance."""
    params, toks, full, _ = model
    got, _, kv = paged_logits(
        params, dataclasses.replace(TINY, state_dtype=jnp.bfloat16), toks,
        50)
    assert kv[2].dtype == jnp.bfloat16
    assert worst(got, full) > 10 * TOL


@pytest.mark.parametrize("busy", [[1] * 6, [1, 0, 1, 1, 0, 1], [0] * 6])
def test_lane_kernel_at_one_group_of_sixty_four_heads(busy):
    """`ssd_lanes_step` at the published head shape (64 heads of 64 x
    128 in ONE group: the only legal head block is all 64 heads, exactly
    the kernel's block budget; B and C one row for every head) under the
    interpreter equals `ssd_step`; idle lanes and other layers are bit
    for bit what they were."""
    H, P, N, G, lanes = 64, 64, 128, 1, 6
    assert head_block_for(H, H // G, P, N) == 64
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    ops = (jax.random.normal(ks[0], (lanes, H, P)),
           jax.nn.softplus(jax.random.normal(ks[1], (lanes, H))),
           -jnp.exp(jax.random.normal(ks[2], (H,))),
           jax.random.normal(ks[3], (lanes, G, N)),
           jax.random.normal(ks[4], (lanes, G, N)),
           jax.random.normal(ks[5], (H,)))
    dirty = jax.random.normal(ks[6], (2, lanes, H, P, N))
    mask = np.asarray(busy, bool)
    step = lambda impl: lanes_step(
        dirty, 1, lanes_plan(jnp.asarray(mask), impl),
        partial(ssd_step, *ops), partial(ssd_lanes_step, *ops), impl)
    (ra, ma), (rb, mb) = step("jnp"), step("pallas_interpret")
    ma, mb, ra, rb = map(np.asarray, (ma, mb, ra, rb))
    np.testing.assert_allclose(mb[1][mask], ma[1][mask], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(rb[mask], ra[mask], rtol=2e-6, atol=2e-4)
    assert np.array_equal(mb[1][~mask], np.asarray(dirty)[1][~mask])
    assert np.array_equal(mb[0], np.asarray(dirty)[0])
    assert not rb[~mask].any()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_op_scopes_name_the_parts_of_a_layer(program):
    """What a profiler groups device ops by: the shared mixer's scopes,
    the gated MLP's and the attention reads'."""
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: gh.init_params(TINY,
                                                   jax.random.PRNGKey(0)))
    kv = tuple(S(s, d) for s, d in zip(
        gh.kv_cache_shapes(TINY, 40, BS, lanes=LANES),
        gh.kv_cache_dtypes(TINY)))
    i32 = jnp.int32
    if program == "prefill":
        low = PREFILL.lower(params, TINY, kv, S((32,), i32), S((32,), i32),
                            S((TABLE,), i32), S((), i32), S((), i32),
                            lanes=S((), i32))
    else:
        low = DECODE.lower(params, TINY, kv, S((LANES,), i32),
                           S((LANES,), i32), S((LANES, TABLE), i32),
                           S((LANES,), i32), valid=S((LANES,), jnp.bool_))
    text = low.as_text(debug_info=True)
    for scope in ("dyn.ssm_proj", "dyn.ssm_conv", "dyn.ssm_scan",
                  "dyn.ssm_gate", "dyn.attn_qkv", "dyn.attention",
                  "dyn.attn_out", "dyn.mlp", "dyn.lm_head"):
        assert scope in text, scope


def test_one_mixer_serves_both_families():
    """models/mamba2.py is the only place the Mamba-2 mixer is written:
    both families' configs hand it their widths, and neither module
    defines the mixer's parts."""
    from dynamo_tpu.models import mamba2

    assert isinstance(TINY.ssm, mamba2.Mamba2Dims)
    assert isinstance(nh.NemotronHConfig().ssm, mamba2.Mamba2Dims)
    for mod in (gh, nh):
        assert mod.mamba2 is mamba2
        for name in ("_ssm_in", "_ssm_heads", "_ssm_out", "ssd_chunked",
                     "ssd_step", "gated_group_norm"):
            assert not hasattr(mod, name), (mod.__name__, name)
    big = nh.PRESETS["nemotron-twotower-30b-a3b"]
    assert (big.ssm.groups, big.ssm.conv_dim) == (8, 6144)
    assert PRESETS["granite-4.0-h-micro"].ssm.groups == 1


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


def _greedy(eng, prompt, toks):
    full = ref.reference_logits(eng.params, eng.model_cfg,
                                prompt + toks[:-1])
    return [int(jnp.argmax(full[len(prompt) - 1 + j]))
            for j in range(len(toks))]


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
async def test_engine_serves_the_family_and_counts(impl):
    """JaxEngine end to end through get_family, under both impls of the
    state's decode step: six requests over four lanes (chunked prefill
    across two and three programs, fused bursts, lanes joining a running
    burst and finishing inside one, two lanes REUSED without a clearing
    program) emit the reference's greedy tokens; the counters are fed
    under Nemotron's names, so its counter-only metric files read them."""
    eng = _engine(attn_impl=impl)
    assert get_family(eng.model_cfg) is gh
    assert gh.state_impl(eng.model_cfg, eng.model_cfg.attn_impl) == impl
    assert not eng.config.enable_prefix_caching        # fell back, loudly
    rng = np.random.default_rng(1)
    sizes = ((50, 30), (37, 9), (70, 25), (20, 12), (45, 16), (33, 7))
    prompts = [rng.integers(3, TINY.vocab_size, n).tolist()
               for n, _ in sizes]
    outs = await asyncio.gather(*[
        _generate(eng, f"r{i}", p, n)
        for i, (p, (_, n)) in enumerate(zip(prompts, sizes))])
    for p, toks in zip(prompts, outs):
        assert _greedy(eng, p, toks) == toks
    m = eng.metrics
    total = sum(n for n, _ in sizes)
    assert m["ssm_tokens.prefill"] == total
    # buckets of at most 32: every prompt but the 20-token one is carried
    assert 0 < m["ssm_carried_tokens.prefill"] < total
    assert m["ssm_resets"] == 6
    assert 0 < m["ssm_pad_tokens.prefill"] < total
    assert (m["ssm_tokens.prefill"] + m["ssm_pad_tokens.prefill"]) % 16 == 0
    assert 0 < m["ssm_lane_steps.decode"] <= m["ssm_slot_steps.decode"]
    assert m["ssm_slot_steps.decode"] % LANES == 0
    assert 0 < m["decode_attn_live_blocks"] <= m["decode_attn_read_blocks"]
    assert m["gqa_prefill_tokens.prefill"] == total * 2
    assert m["gqa_prefill_kernel_tokens.prefill"] == 0
    layers = len(TINY.layers_of(MAMBA))
    live, moved = (m[f"state_{x}_lane_steps.decode"]
                   for x in ("live", "moved"))
    assert live == layers * m["ssm_lane_steps.decode"]
    assert moved == (live if impl == "pallas_interpret"
                     else layers * m["ssm_slot_steps.decode"])
    # no experts: nothing routed, no device-side count
    assert m["moe_picks.prefill"] == m["moe_picks.decode"] == 0
    assert not hasattr(gh, "KV_COUNTERS") and len(eng.kv) == 4
    await eng.close()


async def test_reused_lane_equals_the_sequence_alone():
    """One lane: the second sequence takes the lane the first one left
    (its state and tail still there) and emits what it emits alone."""
    rng = np.random.default_rng(3)
    a, b = (rng.integers(3, TINY.vocab_size, n).tolist() for n in (40, 33))
    alone = _engine(max_num_seqs=1)
    want = await _generate(alone, "b", b, 12)
    assert _greedy(alone, b, want) == want
    await alone.close()
    eng = _engine(max_num_seqs=1)
    await _generate(eng, "a", a, 9)
    assert float(jnp.abs(eng.kv[2]).max()) > 0          # the lane is dirty
    assert await _generate(eng, "b", b, 12) == want
    await eng.close()


async def test_preempted_sequence_resumes_with_the_same_tokens():
    """A pool too small for two long answers: one sequence is preempted,
    its state rebuilt by the replay from position 0, and it emits the
    reference's greedy tokens."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, TINY.vocab_size, 40).tolist()
               for _ in range(2)]
    tight = _engine(num_blocks=9)        # 8 usable: two x 6 do not fit
    got = await asyncio.gather(*[_generate(tight, f"t{i}", p, 50)
                                 for i, p in enumerate(prompts)])
    assert tight.metrics["preemptions"] > 0
    for p, toks in zip(prompts, got):
        assert _greedy(tight, p, toks) == toks
    await tight.close()


def test_unsupported_features_refuse_or_fall_back():
    """Prefix caching asked for is switched off with a warning; tp > 1,
    KVBM tiers and a disagg pull refuse the configuration; int8 cache
    and speculation fall back; LoRA refuses: no silently wrong answer."""
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
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert set(gh.UNSUPPORTED) >= {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "packed_prefill", "kvbm", "disagg", "tp"}


def test_catalog_config_gives_the_published_preset():
    """`from_hf` on the catalog's `config`, copied whole into the
    configuration file, is the published preset: 3191.4 M parameters,
    and as many as `init_params` makes."""
    with open(CONFIG_FILE) as f:
        hf = json.load(f)
    assert hf["reduced"] == {} and hf["num_hidden_layers"] == 40
    cfg = ref.program_config(hf, "granite-4.0-h-micro")
    assert cfg == PRESETS["granite-4.0-h-micro"]
    assert ref.attn_pair_flops(cfg) == 4.0 * 32 * 64
    shapes = jax.eval_shape(lambda: gh.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == 3191396096
    # one layer a position of the period, stacked over the four periods
    assert len(shapes["layers"]) == 10
    assert shapes["layers"][0]["w_in"].shape == (4, 2048, 8512)
    assert shapes["layers"][0]["mlp_in"].shape == (4, 2048, 16384)
    assert shapes["layers"][5]["wk"].shape == (4, 2048, 512)
    assert "lm_head" not in shapes


@pytest.mark.parametrize("key,bad", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("sliding_window", 4096),
    ("rope_scaling", {"type": "linear"}), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"),
    ("tie_word_embeddings", False), ("time_step_limit", [0.0, 0.1]),
    ("mamba_expand", 3), ("mamba_n_groups", 3), ("model_type", "granite"),
])
def test_what_is_not_modelled_is_refused(key, bad):
    with open(CONFIG_FILE) as f:
        hf = json.load(f)
    with pytest.raises(ValueError, match=key):
        ref.program_config({**hf, key: bad}, "x")


def test_layer_kinds_outside_the_two_are_refused():
    with pytest.raises(ValueError, match="not modelled"):
        dataclasses.replace(TINY, layer_kinds=(MAMBA, "moe"))
    with pytest.raises(ValueError, match="layer_types has"):
        gh.from_hf({"layer_types": ["mamba"], "num_hidden_layers": 2}, "x")
