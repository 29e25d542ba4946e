"""Mamba-2 state-space blocks beside GQA attention and plain routed
experts held as a share, one mixer a block (models/nemotron_h.py,
ops/ssm.py): the paged path against the plain float32 reference of
benchmark/reference/nemotron_h.py, at tiny widths on the CPU.

d 64; Mamba: 4 heads of 8 over a state of 16, 2 groups, chunks of 8;
attention: 4 query heads over 2 KV heads of 16; 16 router outputs, 4
picked, a share of 8 held; block 16; 7 blocks `MEM*EME`.  Everything is
float32 here, so program and reference differ by summation order only:
the reference is the token-by-token recurrence, the program the chunked
form."""

import asyncio
import dataclasses
from functools import partial

import pytest

pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import nemotron_h as ref
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import deepseek, get_family, keye, ling, mimo, moe
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.moe import (
    moe_dispatch_dense,
    moe_dispatch_grouped,
    moe_dispatch_visited,
)
from dynamo_tpu.models.nemotron_h import NemotronHConfig
from dynamo_tpu.ops.ssm import ssd_chunked, ssd_step
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

TINY = NemotronHConfig(dtype=jnp.float32, experts_held=(0, 8))
# the family's programs, compiled once a shape as the engine does
PREFILL = jax.jit(nh.prefill, static_argnums=1)
PREFILL_BATCHED = jax.jit(nh.prefill_batched, static_argnums=1)
DECODE = jax.jit(nh.decode, static_argnums=1)
DECODE_MULTI = jax.jit(nh.decode_multi, static_argnums=(1, 7))
BS, LANES, TABLE = 16, 4, 8
# float32 on both sides: what is left is the order of summation (the
# chunked form's matmuls against a scan over tokens, blocked gathers),
# measured at 4e-6 on logits of magnitude 4; 1e-4 leaves room for
# another platform's reductions and is four orders under the smallest
# effect of a left-out detail (1.7, below)
TOL = 1e-4
# the two forms of the attention blocks' prefill read (ops/packed_prefill.py):
# the float32 flash scan ("auto" on the CPU) and the Pallas kernel under
# the interpreter (it takes this block size and head_dim: blocks of 16,
# heads of 16, tests/test_packed_pallas.py)
PACKED_IMPLS = ("xla", "pallas_interpret")


def packed(impl, cfg=TINY):
    return dataclasses.replace(cfg, packed_attn_impl=impl)


def fresh_cache(cfg=TINY, num_blocks=40, lanes=LANES, dirty=False):
    """`dirty`: state and tail full of ones, as a lane that another
    sequence held would be (no program clears a lane)."""
    fill = jnp.ones if dirty else jnp.zeros
    kv = [jnp.zeros(s, d) for s, d in zip(
        nh.kv_cache_shapes(cfg, num_blocks, BS, lanes=lanes),
        nh.kv_cache_dtypes(cfg))]
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
    params = nh.init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(3, TINY.vocab_size, 70)
    full, states = ref.reference_forward(params, TINY, toks.tolist())
    return params, toks, np.asarray(full), states


def test_layer_pattern_is_the_published_one():
    """One mixer a block, by the pattern string; the cache's layer axes
    are indexed by kind."""
    assert TINY.layers_of("M") == (0, 2, 5) and TINY.layers_of("*") == (3,)
    assert TINY.kind_index == (0, 0, 1, 0, 1, 2, 2)
    big = nh.PRESETS["nemotron-twotower-30b-a3b"]
    assert big.n_layers == 52
    assert [len(big.layers_of(k)) for k in "M*E"] == [23, 6, 23]
    assert big.layers_of("*") == (5, 12, 19, 26, 33, 42)
    cut = dataclasses.replace(big, pattern=big.pattern[:27])
    assert [len(cut.layers_of(k)) for k in "M*E"] == [12, 4, 11]
    shapes = nh.kv_cache_shapes(cut, 1281, 128, lanes=64)
    assert shapes[0] == (4, 2, 1281, 128, 128)
    assert shapes[2] == (12, 64, 64, 64, 128)
    assert shapes[3] == (12, 64, 3, 6144)
    with pytest.raises(ValueError, match="not modelled"):
        dataclasses.replace(TINY, pattern="ME-*")


@pytest.mark.parametrize("impl", PACKED_IMPLS)
def test_paged_path_matches_reference_logits(model, impl):
    """Prompt of 50 tokens prefilled as 32 + 18 (two programs: the state
    carried once, the second padded to its bucket, a chunk of the scan
    cut by the prompt's end; the attention block's second read crosses
    into the cached context), then 20 decode steps across the block
    boundary at 64, on a lane that was dirty; the state itself agrees."""
    params, toks, full, states = model
    got, _, kv = paged_logits(params, packed(impl), toks, 50)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[49 + i], rtol=0, atol=TOL)
    # after the last decode step the state has seen all 70 tokens
    for pli, li in enumerate(TINY.layers_of("M")):
        np.testing.assert_allclose(np.asarray(kv[2][pli, 2]),
                                   np.asarray(states[li]), atol=TOL)
    # the other lanes are as they were: ones
    assert float(jnp.abs(kv[2][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 3] - 1).max()) == 0.0


def test_kernel_takes_the_heads_of_a_group_four_at_a_time():
    """16 query heads over 2 KV heads: the kernel holds 4 heads of each
    KV head a body (the op's own rule, `pallas_packed_prefill.
    _group_heads`; tests/test_packed_pallas.py reads the lowered call);
    the logits of a prompt in two programs (the second reads the first's
    keys from the pool) are the scan's, which takes all 16 at once, and
    the reference's."""
    cfg = dataclasses.replace(TINY, n_heads=16, pattern="M*E*")
    params = nh.init_params(cfg, jax.random.PRNGKey(1))
    toks = np.random.default_rng(2).integers(3, cfg.vocab_size, 50)
    want = np.asarray(ref.reference_logits(params, cfg, toks.tolist()))[49]
    for impl in PACKED_IMPLS:
        got, _, _ = paged_logits(params, packed(impl, cfg), toks, 50)
        np.testing.assert_allclose(got[0], want, rtol=0, atol=TOL)


@pytest.mark.parametrize("impl", PACKED_IMPLS)
def test_prompt_of_several_programs_carries_the_state(model, impl):
    """Buckets of 16: a prompt of 50 is four programs, the state handed
    on three times and the convolution's tail with it; the attention
    block reads three programs' keys back from the pool."""
    params, toks, full, _ = model
    got, _, _ = paged_logits(params, packed(impl), toks[:52], 50,
                             bucket=16)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, full[49 + i], rtol=0, atol=TOL)


def test_fused_burst_crosses_chunk_and_block_boundary(model):
    """decode_multi from position 60 for 8 steps (the block boundary at
    64 inside the burst) chains the reference's own greedy tokens; idle
    lanes keep state and tail bit for bit."""
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


@pytest.mark.parametrize("impl", PACKED_IMPLS)
def test_padded_row_beside_a_full_one(model, impl):
    """prefill_batched: a row of 32 tokens, a row of 11 padded to 32 and
    a filler row of none (lane 0, as the engine pads): a packed stream
    of four segments whose padding lies between the runs.  Both real rows
    agree with the reference; the short row's state and tail are what
    its 11th token left (a later chunk continues from them: 1.4 chunks
    of scan behind it changed nothing); lane 0 keeps what it held."""
    params, toks, full, _ = model
    cfg = packed(impl)
    short = np.random.default_rng(6).integers(3, TINY.vocab_size, 24)
    full_s = np.asarray(ref.reference_logits(params, TINY, short.tolist()))
    kv = fresh_cache(dirty=True)
    rows = np.zeros((4, 32), np.int32)
    rows[0], rows[1, :11] = toks[:32], short[:11]
    tables = np.zeros((4, TABLE), np.int32)
    tables[0, :3], tables[1, :2] = [3, 7, 9], [11, 13]
    pos = np.tile(np.arange(32, dtype=np.int32), (4, 1))
    logits, kv = PREFILL_BATCHED(
        params, cfg, kv, jnp.asarray(rows), jnp.asarray(pos),
        jnp.asarray(tables), jnp.zeros(4, jnp.int32),
        jnp.asarray([32, 11, 0, 0], jnp.int32),
        lanes=jnp.asarray([2, 1, 0, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), full[31], atol=TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), full_s[10], atol=TOL)
    assert float(jnp.abs(kv[2][:, 0] - 1).max()) == 0.0
    assert float(jnp.abs(kv[3][:, 0] - 1).max()) == 0.0
    _, want = ref.reference_forward(params, TINY, short[:11].tolist())
    for pli, li in enumerate(TINY.layers_of("M")):
        np.testing.assert_allclose(np.asarray(kv[2][pli, 1]),
                                   np.asarray(want[li]), atol=TOL)
    # the short row goes on from position 11 to 24
    t = np.zeros(32, np.int32)
    t[:13] = short[11:]
    logits, kv = PREFILL(
        params, cfg, kv, jnp.asarray(t),
        jnp.asarray(11 + np.arange(32, dtype=np.int32)),
        jnp.asarray(tables[1]), jnp.int32(11), jnp.int32(13),
        lanes=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(logits), full_s[23], atol=TOL)


@pytest.mark.parametrize("detail", ref.DETAILS)
def test_leaving_out_a_published_detail_breaks_agreement(model, detail):
    """The comparison is tight enough to notice each of: the decay, the
    time step's bias, the convolution, its bias, the D skip, the gate,
    gate-then-norm against norm-then-gate, the norm's groups, B and C
    being a group's, the square of the experts' ReLU, the routed scale,
    the shared expert, and a rotary that the attention does not have."""
    params, toks, full, _ = model
    without = np.asarray(ref.reference_logits(params, TINY, toks.tolist(),
                                              leave_out=detail))
    got, _, _ = paged_logits(params, TINY, toks, 50)
    worst = max(float(np.abs(row - without[49 + i]).max())
                for i, row in enumerate(got))
    assert worst > 100 * TOL, (detail, worst)
    assert float(np.abs(full - without).max()) > 100 * TOL


def _scan_inputs(case, T=200, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (T, H, P))
    b = jax.random.normal(ks[1], (T, G, N))
    c = jax.random.normal(ks[2], (T, G, N))
    a = -jnp.exp(jax.random.uniform(ks[3], (H,), minval=0.0, maxval=2.7))
    dt = {"mixed": jax.nn.softplus(jax.random.normal(ks[4], (T, H)) - 2),
          "near_one": jnp.full((T, H), 1e-7),
          "forgets_all": jnp.full((T, H), 30.0)}[case]
    d_skip = jax.random.normal(ks[5], (H,))
    return x, dt, a, b, c, d_skip, jax.random.normal(ks[6], (H, P, N))


@pytest.mark.parametrize("case", ["mixed", "near_one", "forgets_all"])
@pytest.mark.parametrize("chunk", [128, 8])
def test_chunked_scan_equals_the_token_recurrence(case, chunk):
    """200 tokens from a random state (one and a half chunks of 128,
    25 of 8), the time step pinned at both ends of its range: nothing
    forgotten (decay 1 - 1e-7 a token: the intra-chunk sum carries the
    whole chunk) and everything (dt A down to -450 a token: exp(l_t -
    l_s) underflows to 0, exp(-l_s) would overflow float32 at the first
    token).  2e-4 absolute on outputs of magnitude 10 (200 fed tokens
    summed): float32 round-off of a 128-term sum measured 4e-5."""
    args = _scan_inputs(case)
    want_y, want_S = ref.token_recurrence(*args)
    got_y, got_S = ssd_chunked(*args, chunk=chunk)
    assert bool(jnp.isfinite(got_y).all())
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S),
                               atol=2e-4)


def test_scan_over_two_programs_with_a_padded_bucket():
    """70 tokens as a program of 40 and one of 30 padded to its bucket
    of 32 (dt 0, x 0 on the two rows behind the end), chunks of 8: the
    second starts from what the first left, and the padding leaves the
    state as the 70th token left it."""
    x, dt, a, b, c, d_skip, S0 = _scan_inputs("mixed", T=70)
    want_y, want_S = ref.token_recurrence(x, dt, a, b, c, d_skip, S0)
    y1, S1 = ssd_chunked(x[:40], dt[:40], a, b[:40], c[:40], d_skip, S0,
                         chunk=8)
    pad = lambda v: jnp.pad(v[40:], ((0, 2),) + ((0, 0),) * (v.ndim - 1))
    y2, S2 = ssd_chunked(pad(x), pad(dt), a, pad(b), pad(c), d_skip, S1,
                         chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2[:30]])),
                               np.asarray(want_y), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(want_S),
                               atol=1e-4)


def test_steps_equal_the_chunked_scan():
    """ssd_step T times is ssd_chunked over T; a lane that is not valid
    keeps its state bit for bit."""
    x, dt, a, b, c, d_skip, S0 = _scan_inputs("mixed", T=40)
    want_y, want_S = ssd_chunked(x, dt, a, b, c, d_skip, S0, chunk=8)
    S = jnp.stack([S0, S0])
    valid = jnp.asarray([True, False])
    outs = []
    for t in range(40):
        two = lambda v: jnp.stack([v[t], v[t]])
        y, S = ssd_step(two(x), two(dt), a, two(b), two(c), d_skip, S,
                        valid)
        outs.append(y[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(want_y), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(want_S),
                               atol=1e-4)
    assert bool((S[1] == S0).all())


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_op_scopes_name_the_parts_of_a_block(program):
    """What a profiler groups device ops by: both programs carry the
    state-space block's four scopes beside the shared ones."""
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: nh.init_params(TINY,
                                                   jax.random.PRNGKey(0)))
    kv = tuple(S(s, d) for s, d in zip(
        nh.kv_cache_shapes(TINY, 40, BS, lanes=LANES),
        nh.kv_cache_dtypes(TINY)))
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
                  "dyn.attn_out", "dyn.moe_router", "dyn.moe_dispatch",
                  "dyn.mlp", "dyn.lm_head"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# the experts: a plain form in the shared dispatch
# ---------------------------------------------------------------------------


def _expert_layer(cfg, seed=3):
    params = nh.init_params(cfg, jax.random.PRNGKey(seed))
    return params["layers"][cfg.layers_of("E")[0]]


# the kernel's body on the CPU: the form a decode step takes on the chip
_visited = partial(moe_dispatch_visited, interpret=True)


@pytest.mark.parametrize("dispatch", [moe_dispatch_dense,
                                      moe_dispatch_grouped, _visited],
                         ids=["moe_dispatch_dense", "moe_dispatch_grouped",
                              "moe_dispatch_visited"])
def test_plain_experts_equal_a_loop_over_experts(dispatch):
    """`Wdown relu(x Wup)^2` through each form of moe.py's dispatch
    (the family's config says the expert is plain and names the
    activation) = the reference's loop, a token and an expert at a time,
    with a share held and a padded tail masked out."""
    cfg = dataclasses.replace(TINY, experts_held=(4, 8))
    layer = _expert_layer(cfg)
    assert "moe_w_gate" not in layer
    x = jax.random.normal(jax.random.PRNGKey(4), (37, cfg.d_model))
    valid = jnp.arange(37) < 30
    w, ids = nh.ds_router(layer, cfg, x)
    got = dispatch(layer, cfg, x, w, ids, valid)
    want = ref._routed(cfg, layer, x, w, ids) * valid[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    # and it is not the gated form's answer with a matrix missing: the
    # square matters
    lin = ref._routed(cfg, layer, x, w, ids, act=jax.nn.relu)
    assert float(jnp.abs(lin * valid[:, None] - got).max()) > 1e-2


GATED = {
    "deepseek": (deepseek, deepseek.PRESETS["tiny-mla-moe"]),
    "mimo": (mimo, mimo.MimoConfig()),
    "keye": (keye, keye.KeyeConfig()),
    "ling": (ling, ling.LingConfig()),
}


@pytest.mark.parametrize("family", sorted(GATED))
@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_gated_families_compute_what_they_did(family, form):
    """The four families whose experts are SwiGLU go through the edited
    dispatch and get, bit for bit, what the parent's three lines gave:
    silu(x Wgate) * (x Wup) with the products in that order, then
    Wdown (the config has no `expert_gated` / `expert_act`: the defaults
    are the gated SiLU form)."""
    module, cfg = GATED[family]
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    assert not hasattr(cfg, "expert_gated") and not hasattr(cfg,
                                                             "expert_act")
    params = module.init_params(cfg, jax.random.PRNGKey(1))
    layer = next(lp for lp in params["layers"] if "moe_w_gate" in lp)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, cfg.d_model))
    if form == "dense":
        mm = lambda w: jnp.einsum("td,edf->etf", x, w)
    else:
        sizes = jnp.asarray([5, 0, 7] + [0] * (layer["moe_w_up"].shape[0]
                                               - 3), jnp.int32)
        mm = lambda w: moe._grouped_matmul(x, w, sizes)
    parent = jax.nn.silu(mm(layer["moe_w_gate"])) * mm(layer["moe_w_up"])
    assert bool((moe._expert_hidden(layer, cfg, mm) == parent).all())


def test_expert_shares_add_up_to_the_uncut_layer():
    """The share test of the guide at 8 ranks, as the configuration's
    deployment: the parts that the eight shares of 2 experts give, with
    the shared expert (which every chip computes alike) counted once,
    add up to what the program gives with all 16 held, and to the
    reference's uncut layer; a share alone equals the reference given
    the same share."""
    whole = dataclasses.replace(TINY, experts_held=None)
    layer = _expert_layer(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (9, whole.d_model))
    w, ids = nh.ds_router(layer, whole, x)
    rw, rids = ref._route(whole, layer, x)
    assert (np.asarray(ids) == np.asarray(rids)).all()
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, atol=1e-5)
    shared = nh._plain_mlp(layer["shared"], whole, x)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    np.testing.assert_allclose(
        np.asarray(nh._experts(layer, whole, x, None)[0]),
        np.asarray(ref._experts(whole, layer, p32, x, "")), atol=1e-5)
    uncut = moe_dispatch_dense(layer, whole, x, w, ids) + shared
    total = shared
    for rank in range(8):
        cfg = dataclasses.replace(whole, experts_held=(2 * rank, 2))
        held = {k: (v[2 * rank:2 * rank + 2] if k.startswith("moe_w_")
                    else v) for k, v in layer.items()}
        part = moe_dispatch_dense(held, cfg, x, w, ids)
        np.testing.assert_allclose(
            np.asarray(part),
            np.asarray(ref._routed(cfg, held, x, w, ids)), atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
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


async def test_engine_serves_the_family_and_counts():
    """JaxEngine end to end through get_family: six requests over four
    lanes (chunked prefill across two and three programs, fused bursts,
    lanes joining a running burst and finishing inside one, two lanes
    REUSED without a clearing program) emit the reference's greedy
    tokens; the counters are fed."""
    eng = _engine()
    assert get_family(eng.model_cfg) is nh
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
    assert m["ssm_tokens.prefill"] == total
    # buckets of at most 32: every prompt but the 20-token one is carried
    assert 0 < m["ssm_carried_tokens.prefill"] < total
    assert m["ssm_resets"] == 6
    # every program's rows: what the buckets of 16 and 32 added
    assert 0 < m["ssm_pad_tokens.prefill"] < total
    assert (m["ssm_tokens.prefill"] + m["ssm_pad_tokens.prefill"]) % 16 == 0
    assert 0 < m["ssm_lane_steps.decode"] <= m["ssm_slot_steps.decode"]
    assert m["ssm_slot_steps.decode"] % LANES == 0
    assert 0 < m["decode_attn_live_blocks"] <= m["decode_attn_read_blocks"]
    layers = len(TINY.layers_of("E"))
    assert m["moe_picks.prefill"] == total * layers * 4
    assert 0 < m["moe_picks_held.prefill"] < m["moe_picks.prefill"]
    assert 0 < m["moe_picks_held.decode"] < m["moe_picks.decode"]
    assert 0 < m["moe_experts_visited.decode"] \
        <= m["moe_expert_slots.decode"]
    # every prompt token through every attention block's prefill read,
    # none of them in the kernel: the CPU's "auto" is the scan
    assert m["gqa_prefill_tokens.prefill"] \
        == total * len(TINY.layers_of("*"))
    assert m["gqa_prefill_kernel_tokens.prefill"] == 0
    await eng.close()


def test_prefill_counts_follow_the_rule_the_read_applies():
    """`prefill_token_counts` at the cell's cut (four attention blocks
    of heads of 128): four reads a token; the kernel's tokens are those
    of a program whose bucket `resolve_packed_impl` gives the kernel: no
    bucket on the CPU under "auto", every bucket under an explicit
    kernel, and on a TPU ("auto" asked about that platform) the buckets
    from KERNEL_MIN_TOKENS."""
    from dynamo_tpu.ops.packed_prefill import (
        KERNEL_MIN_TOKENS,
        resolve_packed_impl,
    )

    big = nh.PRESETS["nemotron-twotower-30b-a3b"]
    cut = dataclasses.replace(big, pattern=big.pattern[:27])
    assert cut.packed_attn_impl == "auto"
    for bucket in (256, 2048):
        got = nh.prefill_token_counts(cut, 128, 200, bucket)
        assert got["gqa_prefill_tokens.prefill"] == 4 * 200
        assert got["gqa_prefill_kernel_tokens.prefill"] == 0
        got = nh.prefill_token_counts(
            dataclasses.replace(cut, packed_attn_impl="pallas"), 0, 200,
            bucket)
        assert got["gqa_prefill_kernel_tokens.prefill"] == 4 * 200
    assert [resolve_packed_impl("auto", "tpu", 128, cut.head_dim,
                                cut.dtype, t, cut.n_heads // cut.n_kv_heads)
            for t in (KERNEL_MIN_TOKENS // 2, KERNEL_MIN_TOKENS)] \
        == ["xla", "pallas"]


async def test_engine_takes_the_shared_packed_impl_override():
    """`EngineConfig.packed_attn_impl` reaches this family's field as it
    reaches llama's, cohere2's and sdar's (engine/core.py): under the
    interpreted kernel the engine emits the reference's greedy tokens
    and counts every prompt token as the kernel's."""
    eng = _engine(packed_attn_impl="pallas_interpret")
    assert eng.model_cfg.packed_attn_impl == "pallas_interpret"
    p = np.random.default_rng(4).integers(3, TINY.vocab_size, 45).tolist()
    toks = await _generate(eng, "r", p, 6)
    full = ref.reference_logits(eng.params, eng.model_cfg, p + toks[:-1])
    assert [int(jnp.argmax(full[len(p) - 1 + j]))
            for j in range(len(toks))] == toks
    m = eng.metrics
    assert m["gqa_prefill_kernel_tokens.prefill"] \
        == m["gqa_prefill_tokens.prefill"] == 45
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
    assert nh.state_impl(eng.model_cfg, eng.model_cfg.attn_impl) == impl
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
    m, layers = eng.metrics, len(TINY.layers_of("M"))
    assert layers > 0
    live, moved = (m[f"state_{x}_lane_steps.decode"]
                   for x in ("live", "moved"))
    assert 0 < live < layers * m["ssm_slot_steps.decode"]
    assert moved == (live if impl == "pallas_interpret"
                     else layers * m["ssm_slot_steps.decode"])
    await eng.close()


def test_unsupported_features_refuse_or_fall_back():
    """Prefix caching asked for is refused at start-up (switched off,
    warned: a reused K/V block says nothing of the state at its end);
    tp > 1, KVBM tiers and a disagg pull refuse the configuration; int8
    cache and speculation fall back; LoRA, a plain-MLP block and
    group-limited routing refuse: no silently wrong answer on any."""
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
    with pytest.raises(ValueError, match="group-limited"):
        dataclasses.replace(TINY, n_group=2)
    eng = _engine(kv_cache_dtype="int8", spec_decode="ngram")
    assert eng.kv_dtype == "bf16" and not eng.spec_enabled
    assert set(nh.UNSUPPORTED) >= {
        "prefix_caching", "kv_int8", "speculation", "lora", "ring_prefill",
        "packed_prefill", "kvbm", "disagg", "tp"}


def test_configuration_file_maps_onto_the_published_widths():
    """benchmark/configs' file through the reference's `program_config`:
    the published widths, the first 27 blocks, 16 of 128 experts held as
    rank 0 of 8; a switch the program does not model is refused."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "nemotron-twotower-30b-a3b-27l-ep8.json")
    with open(path) as f:
        hf = json.load(f)
    cfg = ref.program_config(hf, "x")
    big = nh.PRESETS["nemotron-twotower-30b-a3b"]
    assert cfg == dataclasses.replace(
        big, name="x", pattern=big.pattern[:27], experts_held=(0, 16))
    assert ref.attn_pair_flops(cfg) == 4.0 * 32 * 128
    for key, bad in (("mlp_hidden_act", "silu"), ("use_conv_bias", False),
                     ("n_group", 2), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            ref.program_config({**hf, key: bad}, "x")
