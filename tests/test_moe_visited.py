"""The dropless dispatch's visited form (models/moe.py
`moe_dispatch_visited` over ops/pallas_moe_visited.py): the kernel under
the interpreter against `moe_dispatch_dense` and the float32 references'
one-token-at-a-time expert loops, over the cases the contract names; a
row's bits whatever else the step holds; the list the kernel walks; the
tile and the rule that picks the form from the shape; and the counter
that says it engaged."""

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_moe_grouped as tmg

from benchmark.reference import mimo as ref_mimo
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.moe import (
    moe_dispatch,
    moe_dispatch_dense,
    moe_dispatch_form,
    moe_dispatch_visited,
    relu2,
)
from dynamo_tpu.ops.pallas_moe_visited import (
    f_tile,
    stacks_lie_flipped,
    visited_plan,
)

pytestmark = pytest.mark.allow_slow_callbacks

D = tmg.D


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """What the dispatch reads of a family's configuration (hashable, as
    the families' own are: the visited form is jitted on it)."""
    n_experts: int
    experts_per_token: int
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    expert_gated: bool = True
    expert_act: Callable = jax.nn.silu

    @property
    def held(self):
        return self.experts_held or (0, self.n_experts)


def _case(name):
    """(cfg, layer, x, top_w, top_e, valid): test_moe_grouped's named
    cases, and the decode-shaped ones this form is for."""
    if name in tmg.CASES:
        return tmg._case(name)
    cfg, layer, x, top_w, top_e, valid = tmg._case("share_held")
    T = x.shape[0]
    if name == "idle_lanes":             # a decode step: few lanes busy
        valid = jnp.zeros(T, bool).at[jnp.array([3, 17, 18, 40])].set(True)
    elif name == "none_visited":         # a warm-up burst: no lane busy
        valid = jnp.zeros(T, bool)
    elif name == "all_visited":
        cfg, layer, x, top_w, top_e, valid = tmg._case("all_held")
        assert len(np.unique(top_e)) == cfg.n_experts
    elif name == "one_row":
        x, top_w, top_e, valid = x[:1], top_w[:1], top_e[:1], None
        top_e = top_e.at[0, 0].set(5)     # one of its picks is held
    elif name == "plain_relu2":          # two matrices an expert
        layer = {k: w for k, w in layer.items() if k != "moe_w_gate"}
        cfg = _Cfg(cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
                   expert_gated=False, expert_act=relu2)
    return cfg, layer, x, top_w, top_e, valid


def _reference(cfg, layer, x, top_w, top_e):
    """float32, a token at a time through its own held experts."""
    if "moe_w_gate" in layer:
        return ref_mimo._routed(cfg, layer, x, top_w, top_e)
    first, count = cfg.held
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(top_w[t]), np.asarray(top_e[t]) - first):
            if 0 <= e < count:
                h = np.square(np.maximum(
                    np.asarray(x[t]) @ np.asarray(layer["moe_w_up"][e]), 0))
                out[t] += w * (h @ np.asarray(layer["moe_w_down"][e]))
    return out


CASES = tmg.CASES + ["idle_lanes", "none_visited", "all_visited", "one_row",
                     "plain_relu2"]


@pytest.mark.parametrize("name", CASES)
def test_visited_is_the_dense_dispatch_and_the_reference(name):
    cfg, layer, x, top_w, top_e, valid = _case(name)
    got = jax.jit(lambda *a: moe_dispatch_visited(
        layer, cfg, *a, interpret=True))(x, top_w, top_e, valid)
    dense = moe_dispatch_dense(layer, cfg, x, top_w, top_e, valid)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)
    want = _reference(cfg, layer, x, top_w, top_e)
    if valid is not None:
        assert not np.any(np.asarray(got)[~np.asarray(valid)])
        want = jnp.where(valid[:, None], want, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if name != "none_visited":
        assert float(jnp.abs(want).max()) > 0.05  # something was computed


@pytest.mark.parametrize("dtype,tile", [
    (jnp.float32, 128), (jnp.float32, 256), (jnp.float32, None),
    (jnp.bfloat16, 128), (jnp.bfloat16, None)])
def test_the_hidden_width_in_tiles_is_the_same_sum(dtype, tile):
    """A hidden width of 512 in tiles of 128, 256 and whole: float32
    equals the dense form to rounding; bfloat16 keeps the dense form's
    rounding points, so the two lie within two of its steps."""
    E, k, T, F = 8, 2, 12, 512
    cfg = tmg.MimoConfig(dtype=dtype, n_experts=E, experts_per_token=k)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    layer = {"moe_w_gate": jax.random.normal(ks[0], (E, D, F)) * 0.2,
             "moe_w_up": jax.random.normal(ks[1], (E, D, F)) * 0.2,
             "moe_w_down": jax.random.normal(ks[2], (E, F, D)) * 0.2}
    layer = {n: w.astype(dtype) for n, w in layer.items()}
    x = jax.random.normal(ks[3], (T, D)).astype(dtype)
    top_w, top_e = tmg._picks(T, E, k, seed=5, avoid=(2, 6))
    valid = jnp.arange(T) != 4
    got = moe_dispatch_visited(layer, cfg, x, top_w, top_e, valid,
                               tile=tile, interpret=True)
    dense = moe_dispatch_dense(layer, cfg, x, top_w, top_e, valid)
    assert got.dtype == dtype and not np.any(np.asarray(got[4], np.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=tol, atol=tol * 4)


def test_a_row_does_not_depend_on_the_rows_or_the_experts_beside_it():
    """The same lane with the same picks gives the same bits whatever
    the other lanes hold, whichever OTHER experts they visit (the list
    the kernel walks changes under it), and wherever the lane sits."""
    cfg, layer, x, top_w, top_e, _ = _case("share_held")
    T, keep = x.shape[0], 9
    run = jax.jit(lambda *a: moe_dispatch_visited(layer, cfg, *a,
                                                  interpret=True))
    base = run(x, top_w, top_e, jnp.ones(T, bool))
    w2, e2 = tmg._picks(T, cfg.n_experts, cfg.experts_per_token, seed=99)
    x2 = jax.random.normal(jax.random.PRNGKey(8), x.shape, jnp.float32)
    rows = jnp.arange(T) < keep
    other = run(jnp.where(rows[:, None], x, x2),
                jnp.where(rows[:, None], top_w, w2),
                jnp.where(rows[:, None], top_e, e2),
                jnp.arange(T) < T - 5)
    assert jnp.array_equal(base[:keep], other[:keep])
    # alone in the step: only the kept rows' own experts are visited
    alone = run(x, top_w, top_e, rows)
    assert jnp.array_equal(base[:keep], alone[:keep])
    assert not np.any(np.asarray(alone[keep:]))
    # the kept rows moved to the end of the batch, the rest masked out
    tail = run(jnp.roll(x, -keep, 0), jnp.roll(top_w, -keep, 0),
               jnp.roll(top_e, -keep, 0), jnp.arange(T) >= T - keep)
    assert jnp.array_equal(base[:keep], tail[T - keep:])


@pytest.mark.parametrize("seen,ids,n", [
    ([0, 1, 0, 1, 1, 0], [1, 3, 4, 4, 4, 4], 3),
    ([1, 1, 1, 1], [0, 1, 2, 3], 4),
    ([0, 0, 0, 1], [3, 3, 3, 3], 1),
    ([0, 0, 0], [0, 0, 0], 0),
])
def test_the_list_the_kernel_walks(seen, ids, n):
    got_ids, got_n = visited_plan(jnp.asarray(seen, bool))
    assert got_ids.dtype == jnp.int32 and got_n.shape == (1,)
    assert got_ids.tolist() == ids and int(got_n[0]) == n


@pytest.mark.parametrize("d,f,matrices,tile,flipped", [
    (2048, 1408, 3, 1408, False),    # Moonlight: 11 x 128, whole
    (4096, 2048, 3, 1024, False),    # MiMo
    (2048, 768, 3, 768, False),      # Keye
    (2560, 768, 3, 768, False),      # Ling
    (2688, 1856, 2, 1856, True),     # Nemotron: 14.5 x 128, d minor
    (4096, 4096, 3, 1024, False),    # Command A+
    (4096, 14336, 3, 1024, False),   # Mixtral's: 112 x 128
    (32, 24, 3, 24, False),          # the tests' own
])
def test_the_tile_follows_the_widths(d, f, matrices, tile, flipped):
    assert f_tile(d, f, 2, matrices) == tile
    assert f % tile == 0
    assert stacks_lie_flipped(d, f) == flipped


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("tile", [None, 48, 16])
def test_a_stack_that_lies_flipped_is_read_as_it_lies(gated, tile):
    """A hidden width that is not whole lanes under a model width that
    is (Nemotron's 1856 under 2688): the kernel takes the [held, d, f]
    stacks transposed, tiles of [tf, d], and contracts over their minor
    axis; the same results."""
    E, k, T, d, f = 6, 2, 10, 128, 96
    assert stacks_lie_flipped(d, f)
    cfg = _Cfg(E, k, expert_gated=gated,
               expert_act=jax.nn.silu if gated else relu2)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    layer = {"moe_w_up": jax.random.normal(ks[1], (E, d, f)) * 0.2,
             "moe_w_down": jax.random.normal(ks[2], (E, f, d)) * 0.2}
    if gated:
        layer["moe_w_gate"] = jax.random.normal(ks[0], (E, d, f)) * 0.2
    x = jax.random.normal(ks[3], (T, d), jnp.float32)
    top_w, top_e = tmg._picks(T, E, k, seed=2, avoid=(4,))
    got = moe_dispatch_visited(layer, cfg, x, top_w, top_e, tile=tile,
                               interpret=True)
    dense = moe_dispatch_dense(layer, cfg, x, top_w, top_e)
    assert float(jnp.abs(dense).max()) > 0.05
    np.testing.assert_allclose(got, dense, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tokens,k,held,routed,shards,form", [
    # each expert cell's decode program: lanes, top k, held of routed
    (16, 6, 64, 64, 1, "visited"),       # moonlight-16b.chat
    (32, 8, 16, 256, 1, "visited"),      # mimo-v2-flash.reason-closed
    (8, 8, 16, 128, 1, "visited"),       # keye-vl-2.0.longctx-closed
    (64, 8, 16, 512, 1, "visited"),      # ling-3.0-flash.longgen-closed
    (64, 6, 16, 128, 1, "visited"),      # nemotron-twotower.chat
    (8, 8, 16, 128, 1, "visited"),       # command-a-plus.longctx-closed
    # one lane, and the prefill buckets to 256 tokens
    (1, 2, 8, 8, 1, "visited"), (32, 6, 64, 64, 1, "visited"),
    (128, 8, 16, 256, 1, "visited"), (256, 6, 16, 128, 1, "visited"),
    # past the bound the other two forms, as they were
    (257, 6, 64, 64, 1, "dense"), (384, 2, 8, 8, 1, "dense"),
    (512, 6, 64, 64, 1, "grouped"), (4096, 4, 4, 4, 1, "dense"),
    # stacks split over a mesh axis: the dense form, whatever the shape
    (16, 6, 64, 64, 4, "dense"), (64, 8, 16, 256, 2, "dense"),
])
def test_the_shape_picks_the_visited_form(tokens, k, held, routed, shards,
                                          form):
    assert moe_dispatch_form(tokens, k, held, routed, shards) == form


def test_the_one_entry_point_takes_it_and_runs_dense_off_the_tpu():
    """`moe_dispatch` of a decode-sized input traces the kernel beside
    the dense einsums (`platform_dependent`) and, on the CPU, returns
    the dense form's bits; split stacks never trace it."""
    cfg = LlamaConfig(d_model=D, ffn_dim=tmg.F, n_experts=8,
                      experts_per_token=2, dtype=jnp.float32)
    layer = tmg._stacks(8)
    top_w, top_e = tmg._picks(16, 8, 2, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, D), jnp.float32)
    valid = jnp.arange(16) < 5

    def text(cfg):
        return str(jax.make_jaxpr(lambda *a: moe_dispatch(layer, cfg, *a))(
            x, top_w, top_e, valid))

    assert "pallas_call" in text(cfg) and "platform_index" in text(cfg)
    assert "pallas_call" not in text(
        dataclasses.replace(cfg, expert_shards=2))
    got = jax.jit(lambda *a: moe_dispatch(layer, cfg, *a))(
        x, top_w, top_e, valid)
    assert jnp.array_equal(
        got, moe_dispatch_dense(layer, cfg, x, top_w, top_e, valid))


@pytest.mark.parametrize("lanes,engaged", [(2, True), (272, False)])
async def test_the_counter_says_whether_decode_took_the_visited_form(
        lanes, engaged):
    """`moe_visited_form_slots.decode` rises with
    `moe_expert_slots.decode` where the decode program's rows take the
    visited form and stays 0 where they do not; known at dispatch from
    the shape, no fetch."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    model = LlamaConfig(name="m8", vocab_size=64, d_model=32, n_layers=2,
                        n_heads=2, n_kv_heads=2, head_dim=16, ffn_dim=24,
                        n_experts=8, experts_per_token=2, dtype=jnp.float32)
    eng = JaxEngine(EngineConfig(
        model_config=model, block_size=16, num_blocks=2 * lanes + 8,
        max_blocks_per_seq=2, max_num_seqs=lanes, max_prefill_seqs=1,
        prefill_buckets=(32,), max_batch_tokens=32,
        enable_prefix_caching=False, seed=1))
    assert eng.metrics["moe_visited_form_slots.decode"] == 0
    req = PreprocessedRequest(
        token_ids=[3 + i for i in range(10)], request_id="r",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=6, ignore_eos=True))
    async for _ in eng.generate(req):
        pass
    slots = eng.metrics["moe_expert_slots.decode"]
    assert slots > 0 and slots % (2 * 8) == 0
    assert eng.metrics["moe_visited_form_slots.decode"] == \
        (slots if engaged else 0)
    await eng.close()
