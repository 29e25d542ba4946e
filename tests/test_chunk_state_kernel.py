"""ops/pallas_chunk_state.py under the Pallas interpreter on the CPU: the
shell (rows x head blocks x chunk groups, the state carried along a
row's groups) and the delta rule's body against the jnp form it
replaces (`kda_chunked`) AND against the token recurrence (`kda_step`, a
token at a time), the resolver's table and the tiling against its
budget.

tests/test_ling.py serves a prompt of two programs through the kernel;
tests/test_tpu_compile.py compiles it inside the family's prefill
program for a described v5e; on the chip benchmarks/bench_chunk_state.py
compares and times it.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import pallas_chunk_state as pcs
from dynamo_tpu.ops.delta_attention import kda_chunked, kda_step, l2norm
from dynamo_tpu.ops.lane_state import resolve_chunk_impl

F32 = jnp.float32
H, DK, DV, CHUNK, SUB, SCALE = 2, 16, 24, 16, 4, 0.25
RULE = dict(scale=SCALE, chunk=CHUNK, sub=SUB)
# float32 on both sides: what differs is the order inside the sums (the
# MXU's against XLA's einsums; a tree over a sub-chunk against a loop);
# measured 6e-7 against the jnp form and 2e-6 against the recurrence
TOL = 2e-5


def _row(key, T, decay=5.0, rows=1):
    ks = jax.random.split(key, 6)
    return (l2norm(jax.random.normal(ks[0], (rows, T, H, DK), F32)),
            l2norm(jax.random.normal(ks[1], (rows, T, H, DK), F32)),
            jax.random.normal(ks[2], (rows, T, H, DV), F32),
            -decay * jax.nn.sigmoid(jax.random.normal(ks[3],
                                                      (rows, T, H, DK))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (rows, T, H))),
            jax.random.normal(ks[5], (rows, H, DK, DV), F32))


def _kernel(*ops, **tiling):
    return pcs.kda_chunk_rows(*ops, **RULE, interpret=True, **tiling)


def _jnp(*ops):
    return jax.vmap(partial(kda_chunked, **RULE))(*ops)


def _tokens(q, k, v, log_a, beta, state):
    """The recurrence itself: `kda_step` a token, rows as its lanes."""
    def one(s, t):
        o, s = kda_step(*t, s, SCALE)
        return s, o
    s, o = jax.lax.scan(one, state, tuple(
        jnp.swapaxes(x, 0, 1) for x in (q, k, v, log_a, beta)))
    return jnp.swapaxes(o, 0, 1), s


def _far(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("start", ["zeros", "carried"])
@pytest.mark.parametrize("tokens,units", [(16, None), (48, None), (64, 1),
                                          (128, None), (128, 1)])
def test_kernel_equals_the_jnp_form_and_the_recurrence(tokens, units, start):
    """One chunk (half a pair: the other half is padding), an odd number
    of chunks, several, the largest test bucket at the tiling's own
    choice and a pair a step; from zeros and from a CARRIED state: reads
    and final state are the jnp form's and the token recurrence's to
    rounding."""
    *ops, state = _row(jax.random.PRNGKey(tokens), tokens)
    if start == "zeros":
        state = jnp.zeros_like(state)
    got = _kernel(*ops, state, units=units)
    assert got[0].shape == (1, tokens, H, DV) and got[0].dtype == F32
    for want in (_jnp(*ops, state), _tokens(*ops, state)):
        assert _far(got[0], want[0]) < TOL
        assert _far(got[1], want[1]) < TOL


def test_padding_leaves_the_state_of_the_last_real_token():
    """A row whose tail is padding (beta 0, log a 0): the state after it
    is the state after its last real token.  Whole chunks of padding
    change NOTHING: the row padded to 64 leaves bit for bit the state of
    the row padded to 48, and the 32 real tokens of a row padded to 64
    that of the 32-token row; a chunk that ends in padding leaves the
    jnp form's state (of the unpadded 40 tokens) to rounding, and the
    real tokens' reads are the unpadded row's."""
    q, k, v, log_a, beta, state = _row(jax.random.PRNGKey(7), 64)

    def padded(real, to):
        live = (jnp.arange(to) < real)[None, :, None]
        return (q[:, :to], k[:, :to], v[:, :to],
                jnp.where(live[..., None], log_a[:, :to], 0.0),
                jnp.where(live, beta[:, :to], 0.0), state)

    o64, s64 = _kernel(*padded(40, 64))
    o48, s48 = _kernel(*padded(40, 48))
    np.testing.assert_array_equal(np.asarray(s64), np.asarray(s48))
    np.testing.assert_array_equal(np.asarray(o64[:, :40]),
                                  np.asarray(o48[:, :40]))
    want_o, want_s = _jnp(*(x[:, :40] for x in padded(40, 40)[:5]), state)
    assert _far(s64, want_s) < TOL and _far(o64[:, :40], want_o) < TOL
    _, s32 = _kernel(*padded(32, 32))
    _, s32_in_64 = _kernel(*padded(32, 64))
    np.testing.assert_array_equal(np.asarray(s32_in_64), np.asarray(s32))


def test_two_rows_in_one_call_are_two_calls():
    """The row axis is the grid's first: each row's reads and state are
    bit for bit what the row gives alone (its own start state, its own
    operands), whatever the other row holds."""
    ops = _row(jax.random.PRNGKey(11), 64, rows=2)
    o, s = _kernel(*ops)
    for r in range(2):
        o1, s1 = _kernel(*(x[r:r + 1] for x in ops))
        np.testing.assert_array_equal(np.asarray(o[r]), np.asarray(o1[0]))
        np.testing.assert_array_equal(np.asarray(s[r]), np.asarray(s1[0]))
    both = _jnp(*ops)
    assert _far(o, both[0]) < TOL and _far(s, both[1]) < TOL


def test_strong_decay_stays_finite():
    """log a = -5 on every channel and token: 80 nats a chunk of 16 here
    (320 at the published chunk), where a factor exp(-G) alone would
    overflow; every exponent is a difference through a sub-chunk's
    middle, so reads and state are finite and the recurrence's."""
    q, k, v, _, beta, state = _row(jax.random.PRNGKey(13), 64)
    ops = (q, k, v, jnp.full(q.shape, -5.0, F32), beta, state)
    o, s = _kernel(*ops)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    want = _tokens(*ops)
    assert _far(o, want[0]) < TOL and _far(s, want[1]) < TOL


def test_equal_keys_with_beta_one_is_the_substitutions_hard_case():
    """Every key the same unit vector, beta 1, no decay: A is all ones
    below the diagonal, the powers of A grow as C^p / p! before they
    cancel and a product of (I + A^(2^j)) loses the answer; forward
    substitution inside the diagonal blocks and block rows after does
    not."""
    q, _, v, _, _, state = _row(jax.random.PRNGKey(17), 64)
    key = l2norm(jnp.ones((DK,), F32))
    ops = (q, jnp.broadcast_to(key, q.shape), v, jnp.zeros(q.shape, F32),
           jnp.ones(q.shape[:-1], F32), state)
    got, want = _kernel(*ops), _tokens(*ops)
    assert _far(got[0], want[0]) < TOL and _far(got[1], want[1]) < TOL


@pytest.mark.parametrize("impl,platform,tokens,dk,dv,dtype,sub,want", [
    ("auto", "tpu", 2048, 128, 128, F32, 16, "pallas"),
    ("auto", "tpu", 64, 128, 128, F32, 16, "pallas"),      # one chunk
    ("auto", "tpu", 32, 128, 128, F32, 16, "jnp"),         # under a chunk
    ("auto", "tpu", 96, 128, 128, F32, 16, "jnp"),         # not whole
    ("auto", "cpu", 2048, 128, 128, F32, 16, "jnp"),
    ("pallas", "cpu", 2048, 128, 128, F32, 16, "pallas"),  # described chip
    ("pallas", "tpu", 2048, 64, 128, F32, 16, "jnp"),      # dk under a tile
    ("pallas", "tpu", 2048, 128, 64, F32, 16, "jnp"),
    ("pallas", "tpu", 2048, 128, 128, jnp.bfloat16, 16, "jnp"),
    ("pallas", "tpu", 2048, 128, 128, F32, 12, "jnp"),     # no tree
    ("pallas_interpret", "cpu", 128, 16, 24, F32, 4, "pallas_interpret"),
    ("pallas_interpret", "cpu", 24, 16, 24, F32, 4, "jnp"),
    ("jnp", "tpu", 2048, 128, 128, F32, 16, "jnp"),
    ("jnp_bf16", "tpu", 2048, 128, 128, F32, 16, "jnp"),
])
def test_resolver_table(impl, platform, tokens, dk, dv, dtype, sub, want):
    chunk = 64 if dk == 128 or dv == 128 else 16
    assert resolve_chunk_impl(impl, platform, tokens, chunk, dk, dv, dtype,
                              unit=2 * chunk, sub=sub) == want
    # a chunk of 32: two of them are not a whole tile of rows
    if want == "pallas":
        assert resolve_chunk_impl(impl, platform, tokens, 32, dk, dv, dtype,
                                  unit=64, sub=8) == "jnp"


def test_tiling_fits_the_budget():
    """Head block and units a step from the shapes alone: up to 8
    (head, unit) problems a step, heads first; the head block divides
    the heads, the units divide the row, blocks (double-buffered) and
    products stay under the budget."""
    token_bytes, live = 4 * (3 * 128 + 2 * 128), 4 * 128 * 1664
    per = 2 * 128 * token_bytes + live
    for tokens in (128, 256, 384, 512, 2048):
        hb, units = pcs.chunk_tiling(tokens, 128, 32, token_bytes, live)
        assert (hb, units) == (8, 1)
        assert hb * units * per <= pcs._VMEM_BUDGET
    assert pcs.chunk_tiling(512, 128, 4, token_bytes, live) == (4, 2)
    assert pcs.chunk_tiling(512, 128, 6, token_bytes, live) == (6, 1)
    # products over the budget: fewer problems a step, down to one
    assert pcs.chunk_tiling(512, 128, 32, token_bytes, 9 << 20) == (2, 1)
    assert pcs.chunk_tiling(512, 128, 32, 1 << 20, live) == (1, 1)


def test_kernel_refuses_what_the_resolver_keeps_from_it():
    *ops, state = _row(jax.random.PRNGKey(19), 24)
    with pytest.raises(ValueError):
        _kernel(*ops, state)                    # not whole chunks
    *ops, state = _row(jax.random.PRNGKey(19), 32)
    with pytest.raises(ValueError):
        pcs.kda_chunk_rows(*ops, state.astype(jnp.bfloat16), **RULE,
                           interpret=True)
    with pytest.raises(ValueError):
        _kernel(*ops, state, units=2)           # one unit does not split
