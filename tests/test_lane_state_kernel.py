"""ops/pallas_lane_state.py under the Pallas interpreter on the CPU: the
shell (busy lanes only, in place, one layer of the member) and its two
bodies against the jnp steps they replace (`ssd_step`, `kda_step`), the
plan that compacts a burst's busy lanes, the resolver's table, and both
families' fused bursts through the kernel against the jnp bursts.

tests/test_tpu_compile.py compiles the same kernels inside both
families' decode bursts for a described v5e; on the chip
benchmarks/bench_state_step.py compares and times them.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.delta_attention import kda_step, l2norm
from dynamo_tpu.ops.lane_state import (
    lanes_plan,
    lanes_step,
    resolve_state_impl,
)
from dynamo_tpu.ops.pallas_lane_state import (
    head_block_for,
    kda_lanes_step,
    ssd_lanes_step,
)
from dynamo_tpu.ops.ssm import ssd_step

LAYERS, LANES, PLI = 3, 6, 1
F32 = jnp.float32

# which lanes are busy: none, one, all, scattered
MASKS = {
    "none": [0, 0, 0, 0, 0, 0],
    "one": [0, 0, 0, 1, 0, 0],
    "all": [1, 1, 1, 1, 1, 1],
    "scattered": [1, 0, 1, 1, 0, 1],
    "last": [0, 0, 0, 0, 0, 1],
}


def _rule(rule, key):
    """-> (member shape, jnp step, kernel step): one token a lane's
    operands as the families make them, at a small odd-free size."""
    ks = jax.random.split(key, 6)
    if rule == "ssd":
        H, P, N, G = 8, 8, 16, 2
        ops = (jax.random.normal(ks[0], (LANES, H, P), F32),
               jax.nn.softplus(jax.random.normal(ks[1], (LANES, H))),
               -jnp.exp(jax.random.normal(ks[2], (H,))),
               jax.random.normal(ks[3], (LANES, G, N), F32),
               jax.random.normal(ks[4], (LANES, G, N), F32),
               jax.random.normal(ks[5], (H,), F32))
        return ((LAYERS, LANES, H, P, N), partial(ssd_step, *ops),
                partial(ssd_lanes_step, *ops))
    H, dk, dv = 8, 16, 24
    ops = (l2norm(jax.random.normal(ks[0], (LANES, H, dk), F32)),
           l2norm(jax.random.normal(ks[1], (LANES, H, dk), F32)),
           jax.random.normal(ks[2], (LANES, H, dv), F32),
           -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (LANES, H, dk))),
           jax.nn.sigmoid(jax.random.normal(ks[4], (LANES, H))))
    return ((LAYERS, LANES, H, dk, dv), partial(kda_step, *ops, scale=0.25),
            partial(kda_lanes_step, *ops, scale=0.25))


@pytest.mark.parametrize("head_block", [None, 4])
@pytest.mark.parametrize("busy", sorted(MASKS))
@pytest.mark.parametrize("rule", ["ssd", "kda"])
def test_kernel_step_equals_the_jnp_step(rule, busy, head_block):
    """On a DIRTY member: the busy lanes' new state and read are the jnp
    step's to float32 rounding; every idle lane and every other layer is
    bit for bit what it was (with no lane busy: the whole member); an
    idle lane's read is 0."""
    shape, jnp_step, kernel_step = _rule(rule, jax.random.PRNGKey(3))
    mask = np.asarray(MASKS[busy], bool)
    valid = jnp.asarray(mask)
    dirty = jax.random.normal(jax.random.PRNGKey(4), shape, F32)
    if head_block:
        kernel_step = partial(kernel_step, head_block=head_block)
    step = lambda impl: lanes_step(dirty, PLI, lanes_plan(valid, impl),
                                   jnp_step, kernel_step, impl)
    (ra, ma), (rb, mb) = step("jnp"), step("pallas_interpret")
    ma, mb, ra, rb = map(np.asarray, (ma, mb, ra, rb))
    np.testing.assert_allclose(mb[PLI][mask], ma[PLI][mask], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(rb[mask], ra[mask], rtol=2e-6, atol=2e-5)
    assert np.array_equal(mb[PLI][~mask], np.asarray(dirty)[PLI][~mask])
    for other in (0, 2):
        assert np.array_equal(mb[other], np.asarray(dirty)[other])
    assert not rb[~mask].any()
    if mask.any():
        assert not np.array_equal(mb[PLI][mask], np.asarray(dirty)[PLI][mask])


@pytest.mark.parametrize("busy", sorted(MASKS))
def test_plan_puts_the_busy_lanes_first(busy):
    """Busy lanes first and in order, the tail repeating the last busy
    one (lane 0 where none is), and their number; the jnp step gets
    `valid` alone."""
    mask = np.asarray(MASKS[busy], bool)
    assert lanes_plan(jnp.asarray(mask), "jnp")[1:] == (None, None)
    plan = lanes_plan(jnp.asarray(mask), "pallas")
    n = int(mask.sum())
    live = np.asarray(plan.live_lanes).tolist()
    assert int(plan.n_live[0]) == n and plan.n_live.dtype == jnp.int32
    assert live[:n] == np.flatnonzero(mask).tolist()
    assert live[n:] == [live[n - 1] if n else 0] * (LANES - n)
    assert np.array_equal(np.asarray(plan.valid), mask)


@pytest.mark.parametrize("impl,platform,dk,dv,dtype,want", [
    ("auto", "tpu", 64, 128, jnp.float32, "pallas"),       # Nemotron's tile
    ("auto", "tpu", 128, 128, jnp.float32, "pallas"),      # Ling's
    ("pallas", "cpu", 64, 128, jnp.float32, "pallas"),     # the engine's
    ("auto", "cpu", 64, 128, jnp.float32, "jnp"),
    ("auto", "tpu", 64, 64, jnp.float32, "jnp"),           # half a lane tile
    ("auto", "tpu", 12, 128, jnp.float32, "jnp"),          # odd sublanes
    ("pallas", "tpu", 8, 16, jnp.float32, "jnp"),
    ("auto", "tpu", 64, 128, jnp.bfloat16, "jnp"),
    ("pallas_interpret", "cpu", 8, 16, jnp.float32, "pallas_interpret"),
    ("pallas_interpret", "cpu", 8, 16, jnp.bfloat16, "jnp"),
    ("jnp", "tpu", 64, 128, jnp.float32, "jnp"),           # the A/B
    ("jnp_bf16", "tpu", 64, 128, jnp.float32, "jnp"),
])
def test_resolver_table(impl, platform, dk, dv, dtype, want):
    assert resolve_state_impl(impl, platform, dk, dv, dtype) == want


def test_head_block_fits_the_budget():
    """Both cells' tiles take the lane whole (64 heads x 32 KB, 32 x
    64 KB: 2 MB in, 2 MB out, double-buffered 8 MB); a wider state is cut
    to whole groups."""
    assert head_block_for(64, 8, 64, 128) == 64
    assert head_block_for(32, 1, 128, 128) == 32
    assert head_block_for(64, 8, 128, 256) == 16
    assert head_block_for(8, 8, 256, 512) == 8


def _family(name):
    if name == "nemotron_h":
        from dynamo_tpu.models import nemotron_h as mod

        return mod, mod.NemotronHConfig(dtype=jnp.float32,
                                        experts_held=(0, 8))
    from dynamo_tpu.models import ling as mod

    return mod, mod.LingConfig(dtype=jnp.float32, experts_held=(0, 8),
                               mla_q_block=16)


@pytest.mark.parametrize("family", ["nemotron_h", "ling"])
def test_burst_through_the_kernel_equals_the_jnp_burst(family):
    """k fused steps of `decode_multi` (the plan made once, the member in
    the scan's carry) == k jnp steps: the live lanes' tokens, the state to
    rounding, the idle lane's state and tail bit for bit on a cache that
    was dirty."""
    mod, cfg = _family(family)
    params = mod.init_params(cfg, jax.random.PRNGKey(9))
    lanes, k = 4, 5
    kv = tuple(jnp.ones(s, d) if i in (2, 3) else jnp.zeros(s, d)
               for i, (s, d) in enumerate(zip(
                   mod.kv_cache_shapes(cfg, 32, 4, lanes=lanes),
                   mod.kv_cache_dtypes(cfg))))
    tokens = jnp.asarray([5, 9, 0, 17], jnp.int32)
    pos = jnp.asarray([0, 0, 0, 0], jnp.int32)
    tables = jnp.arange(lanes * 4, dtype=jnp.int32).reshape(lanes, 4) + 1
    valid = jnp.asarray([True, True, False, True])
    out = {}
    for impl in ("jnp", "pallas_interpret"):
        out[impl] = mod.decode_multi(
            params, dataclasses.replace(cfg, attn_impl=impl), kv, tokens,
            pos, tables, pos, k, valid=valid)
    (ta, kva), (tb, kvb) = out["jnp"], out["pallas_interpret"]
    live = np.asarray(valid)
    assert np.asarray(ta)[:, live].tolist() == np.asarray(tb)[:, live].tolist()
    np.testing.assert_allclose(np.asarray(kvb[2]), np.asarray(kva[2]),
                               rtol=1e-5, atol=1e-5)
    for member in (2, 3):
        assert float(jnp.abs(kvb[member][:, 2] - 1).max()) == 0.0
    assert not np.array_equal(np.asarray(kvb[2]), np.asarray(kv[2]))


@pytest.mark.parametrize("family", ["nemotron_h", "ling"])
def test_single_step_makes_its_own_plan(family):
    """`decode` without a burst's plan (the engine's guided top-M step)
    compacts `valid` itself and equals the jnp step."""
    mod, cfg = _family(family)
    params = mod.init_params(cfg, jax.random.PRNGKey(2))
    lanes = 3
    kv = tuple(jnp.ones(s, d) if i in (2, 3) else jnp.zeros(s, d)
               for i, (s, d) in enumerate(zip(
                   mod.kv_cache_shapes(cfg, 16, 4, lanes=lanes),
                   mod.kv_cache_dtypes(cfg))))
    tokens = jnp.asarray([3, 4, 5], jnp.int32)
    pos = jnp.zeros((lanes,), jnp.int32)
    tables = jnp.arange(lanes * 2, dtype=jnp.int32).reshape(lanes, 2) + 1
    valid = jnp.asarray([False, True, True])
    (la, kva), (lb, kvb) = (
        mod.decode(params, dataclasses.replace(cfg, attn_impl=impl), kv,
                   tokens, pos, tables, pos, valid=valid)
        for impl in ("jnp", "pallas_interpret"))
    np.testing.assert_allclose(np.asarray(lb)[1:], np.asarray(la)[1:],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kvb[2]), np.asarray(kva[2]),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(kvb[2][:, 0] - 1).max()) == 0.0
