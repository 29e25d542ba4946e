"""The dropless dispatch's grouped form (models/moe.py
`moe_dispatch_grouped`): the same mathematics as `moe_dispatch_dense` and
as the float32 references' one-token-at-a-time expert loops, over the
cases the contract names; a row's result whatever else is in the batch;
the rule that picks the form from the shape; and the counter that says
how often it engaged."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek as ref_ds
from benchmark.reference import mimo as ref_mimo
from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.moe import (
    moe_dispatch,
    moe_dispatch_dense,
    moe_dispatch_form,
    moe_dispatch_grouped,
)
from dynamo_tpu.models.mimo import MimoConfig

pytestmark = pytest.mark.allow_slow_callbacks

D, F = 32, 24


def _stacks(count, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "moe_w_gate": jax.random.normal(ks[0], (count, D, F)) * 0.3,
        "moe_w_up": jax.random.normal(ks[1], (count, D, F)) * 0.3,
        "moe_w_down": jax.random.normal(ks[2], (count, F, D)) * 0.3,
    }


def _picks(T, E, k, seed, avoid=()):
    """Distinct experts a token, none of `avoid`; weights that sum to 1."""
    rng = np.random.default_rng(seed)
    pool = np.array([e for e in range(E) if e not in avoid])
    top_e = np.stack([rng.choice(pool, k, replace=False) for _ in range(T)])
    top_w = rng.random((T, k)).astype(np.float32) + 0.1
    return jnp.asarray(top_w / top_w.sum(-1, keepdims=True)), \
        jnp.asarray(top_e.astype(np.int32))


def _case(name):
    """(cfg, layer, x, top_w, top_e, valid) of one named case."""
    T, E, k, held, valid, avoid = 48, 8, 2, None, None, ()
    if name == "share_held":             # picks fall outside the share
        E, k, held = 16, 4, (4, 4)
    elif name == "padded_tail":          # bucket padding masked out
        valid = jnp.arange(T) < 31
    elif name == "unpicked_expert":      # an empty group in the middle
        avoid = (3,)
    elif name == "ragged_T":             # no multiple of any tile
        T = 37
    elif name == "share_padded_ragged":  # all of it at once
        T, E, k, held, valid = 53, 16, 4, (8, 4), jnp.arange(53) < 40
    cfg = MimoConfig(dtype=jnp.float32, n_experts=E, experts_per_token=k,
                     experts_held=held)
    top_w, top_e = _picks(T, E, k, seed=len(name), avoid=avoid)
    if name == "one_expert":             # every token's first pick: one group
        top_e = top_e.at[:, 0].set(5).at[:, 1].set(
            jnp.where(top_e[:, 1] == 5, 6, top_e[:, 1]))
    x = jax.random.normal(jax.random.PRNGKey(7), (T, D), jnp.float32)
    return cfg, _stacks(cfg.held[1]), x, top_w, top_e, valid


CASES = ["all_held", "share_held", "padded_tail", "unpicked_expert",
         "one_expert", "ragged_T", "share_padded_ragged"]


@pytest.mark.parametrize("name", CASES)
def test_grouped_is_the_dense_dispatch_and_the_reference(name):
    cfg, layer, x, top_w, top_e, valid = _case(name)
    got = jax.jit(lambda *a: moe_dispatch_grouped(layer, cfg, *a))(
        x, top_w, top_e, valid)
    dense = moe_dispatch_dense(layer, cfg, x, top_w, top_e, valid)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)
    # the float32 references: each token through its own held experts,
    # one at a time (MiMo's knows a share; DeepSeek's holds them all)
    want = ref_mimo._routed(cfg, layer, x, top_w, top_e)
    if cfg.held[1] == cfg.n_experts:
        np.testing.assert_allclose(
            ref_ds._routed(layer, x, top_w, top_e), want,
            rtol=1e-5, atol=1e-5)
    if valid is not None:
        assert not np.any(np.asarray(got)[~np.asarray(valid)])
        want = jnp.where(valid[:, None], want, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.05      # something was computed


@pytest.mark.parametrize("form", [moe_dispatch_grouped, moe_dispatch_dense],
                         ids=["grouped", "dense"])
def test_a_row_does_not_depend_on_the_rows_beside_it(form):
    """Prefix reuse and chunked prefill rest on it: the same token with
    the same picks gives the same bits whatever else the program holds,
    and wherever its (token, pick) pairs land among the sorted rows."""
    cfg, layer, x, top_w, top_e, _ = _case("share_held")
    T, keep = x.shape[0], 9
    run = jax.jit(lambda *a: form(layer, cfg, *a))
    base = run(x, top_w, top_e, jnp.ones(T, bool))
    w2, e2 = _picks(T, cfg.n_experts, cfg.experts_per_token, seed=99)
    x2 = jax.random.normal(jax.random.PRNGKey(8), x.shape, jnp.float32)
    rows = jnp.arange(T) < keep
    other = run(jnp.where(rows[:, None], x, x2),
                jnp.where(rows[:, None], top_w, w2),
                jnp.where(rows[:, None], top_e, e2),
                jnp.arange(T) < T - 5)
    assert jnp.array_equal(base[:keep], other[:keep])
    # the kept rows moved to the end of the batch, the rest masked out
    tail = run(jnp.roll(x, -keep, 0), jnp.roll(top_w, -keep, 0),
               jnp.roll(top_e, -keep, 0), jnp.arange(T) >= T - keep)
    assert jnp.array_equal(base[:keep], tail[T - keep:])


@pytest.mark.parametrize("tokens,k,held,routed,shards,form", [
    # Moonlight: 64 of 64, top 6 — every decode step and the buckets
    # to 256 walk their visited experts (tests/test_moe_visited.py),
    # 512 and up group
    (16, 6, 64, 64, 1, "visited"), (128, 6, 64, 64, 1, "visited"),
    (256, 6, 64, 64, 1, "visited"), (512, 6, 64, 64, 1, "grouped"),
    (2048, 6, 64, 64, 1, "grouped"),
    # MiMo's cut: 16 of 256 held, top 8
    (32, 8, 16, 256, 1, "visited"), (256, 8, 16, 256, 1, "visited"),
    (512, 8, 16, 256, 1, "grouped"), (2048, 8, 16, 256, 1, "grouped"),
    # Mixtral-shaped: 8 experts, top 2
    (256, 2, 8, 8, 1, "visited"), (384, 2, 8, 8, 1, "dense"),
    (512, 2, 8, 8, 1, "grouped"),
    # as many picks as experts: dense multiplies nothing it need not
    (4096, 4, 4, 4, 1, "dense"),
    # stacks split over a mesh axis: the dense form, whatever the shape
    (2048, 6, 64, 64, 4, "dense"), (2048, 8, 16, 256, 2, "dense"),
])
def test_the_shape_picks_the_form(tokens, k, held, routed, shards, form):
    assert moe_dispatch_form(tokens, k, held, routed, shards) == form


def test_one_entry_point_picks_by_shape():
    """`moe_dispatch` is what every family with experts calls: the
    traced program of a prompt-sized input holds grouped matmuls, one
    between the two bounds the dense einsums alone (under it the visited
    form's kernel beside them: tests/test_moe_visited.py);
    `expert_shards` > 1 keeps dense."""
    cfg = LlamaConfig(d_model=D, ffn_dim=F, n_experts=8, experts_per_token=2,
                      dtype=jnp.float32)
    layer = _stacks(8)

    def prims(cfg, T):
        top_w, top_e = _picks(T, 8, 2, seed=T)
        x = jnp.zeros((T, D), jnp.float32)
        text = str(jax.make_jaxpr(
            lambda *a: moe_dispatch(layer, cfg, *a))(x, top_w, top_e))
        return "ragged_dot" in text or "pallas_call" in text

    assert prims(cfg, 512)
    assert not prims(cfg, 384)
    assert not prims(dataclasses.replace(cfg, expert_shards=4), 512)


def test_co_batched_rows_run_flattened_and_equal_their_own_programs():
    """A dropless dispatch has no pools to keep apart: prefill_batched
    hands the expert layer its rows flattened (two rows of 256 make a
    grouped-size input) and each row's logits equal its own program's."""
    cfg = LlamaConfig(name="m", vocab_size=64, d_model=32, n_layers=1,
                      n_heads=2, n_kv_heads=2, head_dim=16, ffn_dim=24,
                      n_experts=8, experts_per_token=2, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(4))
    bs, nb, mb, T = 16, 40, 16, 256
    shape = (cfg.n_layers, cfg.n_kv_heads, nb, cfg.head_dim, bs)
    rng = np.random.default_rng(8)
    prompts = rng.integers(1, cfg.vocab_size, (2, T)).astype(np.int32)
    tables = np.zeros((2, mb), np.int32)
    for i in range(2):
        tables[i] = 1 + i * mb + np.arange(mb)
    lens = np.array([T, 200], np.int32)
    kv = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    solo = []
    for i in range(2):
        lg, kv = llama.prefill(
            params, cfg, kv, jnp.asarray(prompts[i]),
            jnp.arange(T, dtype=jnp.int32), jnp.asarray(tables[i]),
            jnp.int32(0), jnp.int32(lens[i]))
        solo.append(np.asarray(lg))
    assert moe_dispatch_form(T, 2, 8, 8) == "visited"
    assert moe_dispatch_form(2 * T, 2, 8, 8) == "grouped"
    kv = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    blg, _ = llama.prefill_batched(
        params, cfg, kv, jnp.asarray(prompts),
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T)),
        jnp.asarray(tables), jnp.zeros(2, jnp.int32), jnp.asarray(lens))
    for i in range(2):
        np.testing.assert_allclose(np.asarray(blg[i]), solo[i],
                                   rtol=2e-5, atol=2e-5)


async def test_the_counter_says_how_often_the_grouped_form_engaged():
    """`moe_grouped_tokens.prefill` rises by a prompt's tokens where its
    bucket is a grouped-size program and by nothing for a small one;
    known at dispatch from the bucket, no fetch."""
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
        model_config=model, block_size=16, num_blocks=64,
        max_blocks_per_seq=24, max_num_seqs=2, max_prefill_seqs=1,
        prefill_buckets=(32, 512), max_batch_tokens=512,
        enable_prefix_caching=False, seed=1))
    assert eng.model_cfg.expert_shards == 1
    assert "moe_grouped_tokens.prefill" in eng.metrics

    async def run(rid, n):
        req = PreprocessedRequest(
            token_ids=[3 + i % 50 for i in range(n)], request_id=rid,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=2, ignore_eos=True))
        async for _ in eng.generate(req):
            pass

    await run("small", 20)
    assert eng.metrics["prefill_tokens"] == 20
    assert eng.metrics["moe_grouped_tokens.prefill"] == 0
    await run("large", 300)
    assert eng.metrics["prefill_tokens"] == 320
    assert eng.metrics["moe_grouped_tokens.prefill"] == 300
    assert eng.metrics["moe_picks.prefill"] == 320 * 2 * 2
    await eng.close()
