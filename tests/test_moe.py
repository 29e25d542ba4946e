"""MoE model family: routing math, EP sharding parity, and engine e2e
on the tiny-moe preset.

The EP check is the load-bearing one: expert weights shard over the tp mesh
axis (parallel/mesh.py moe_w_* rules) and the dispatch einsums must
produce identical outputs sharded vs unsharded."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real-JAX-engine tests: XLA compiles (seconds at tier-1's -O0) and
# device work run inside the async test bodies, so the conftest's 200ms
# event-loop slow-callback gate (DYN004's runtime twin) cannot hold
# here; mocker/frontend/router fleets keep it armed.
pytestmark = pytest.mark.allow_slow_callbacks


from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import LlamaConfig, PRESETS
from dynamo_tpu.models.moe import (
    moe_dispatch_dense,
    moe_dispatch_grouped,
    moe_dispatch_visited,
    softmax_router,
)


def _routed(dispatch):
    """Router + one form of the dispatch, x [T, d] -> [T, d]."""
    def run(layer, cfg, x):
        top_w, top_e = softmax_router(layer, cfg, x)
        return dispatch(layer, cfg, x, top_w, top_e)
    return run


_moe_mlp_dense = _routed(moe_dispatch_dense)
_moe_mlp_grouped = _routed(moe_dispatch_grouped)
# the kernel's body on the CPU: the form a decode step takes on the chip
_moe_mlp_visited = _routed(partial(moe_dispatch_visited, interpret=True))


def moe_cfg(**kw):
    base = dict(name="m", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                n_kv_heads=2, head_dim=16, ffn_dim=48, n_experts=4,
                experts_per_token=2, dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig(**base)


def expert_ffn(layer, e, x):
    """Reference per-expert FFN for one token."""
    g = jax.nn.silu(x @ layer["moe_w_gate"][e]) * (x @ layer["moe_w_up"][e])
    return g @ layer["moe_w_down"][e]


@pytest.mark.parametrize("impl", [_moe_mlp_dense, _moe_mlp_grouped,
                                  _moe_mlp_visited],
                         ids=["dense", "grouped", "visited"])
def test_moe_routes_to_topk_experts(impl):
    """Every form of the dispatch: output must equal the softmax-weighted sum of
    the top-k experts' FFN outputs, computed independently per token."""
    cfg = moe_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (5, cfg.d_model),
                          jnp.float32)
    out = impl(layer, cfg, x)

    router = x @ layer["moe_gate"]
    for t in range(x.shape[0]):
        top_w, top_e = jax.lax.top_k(router[t], cfg.experts_per_token)
        w = jax.nn.softmax(top_w)
        expect = sum(
            w[j] * expert_ffn(layer, int(top_e[j]), x[t])
            for j in range(cfg.experts_per_token)
        )
        np.testing.assert_allclose(np.asarray(out[t]), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", [_moe_mlp_dense], ids=["dense"])
def test_moe_ep_sharding_parity(impl):
    """Expert-parallel (experts sharded over tp) output == unsharded, in
    the form split stacks take (moe_dispatch_form: shards > 1)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh, shard_params

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = moe_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (8, cfg.d_model),
                          jnp.float32)
    ref = impl(layer, cfg, x)

    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    sharded = shard_params(params, mesh)["layers"][0]
    assert sharded["moe_w_gate"].sharding.spec == P("tp", None, None)
    with mesh:
        out = jax.jit(lambda l, x: impl(l, cfg, x))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


async def test_moe_prefix_cache_rerun_deterministic():
    """Regression (caught live, under a dispatch that dropped tokens over
    an expert's capacity and is gone for it): a rerun of the same prompt
    takes the cached-prefix + short-tail-prefill path, whose different
    chunk size changed the drops and produced DIFFERENT greedy output.
    The dispatch must be batch-invariant: identical tokens out, whatever
    the chunking."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    prompt = [3 + ord(c) for c in "hello mixture of experts"]
    for seed in (0, 7):
        cfg = EngineConfig(model="tiny-moe", block_size=4, num_blocks=64,
                           max_blocks_per_seq=16, max_num_seqs=2, seed=seed)
        eng = JaxEngine(cfg)

        async def run(rid):
            req = PreprocessedRequest(
                token_ids=list(prompt), request_id=rid,
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=8, ignore_eos=True),
            )
            toks = []
            async for o in eng.generate(req):
                toks.extend(o.token_ids)
            return toks

        first = await run("a")
        second = await run("b")
        assert second == first, f"seed {seed}: cache-path divergence"
        assert eng.metrics["cache_hit_tokens"] > 0
        await eng.close()


async def test_engine_serves_moe_preset():
    """tiny-moe end to end through the engine: deterministic greedy decode
    with prefill + fused decode, twice (prefix-cache second pass)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    cfg = EngineConfig(model="tiny-moe", block_size=4, num_blocks=32,
                       max_blocks_per_seq=8, max_num_seqs=2,
                       prefill_buckets=(8, 16), seed=3)
    eng = JaxEngine(cfg)

    async def run(rid):
        req = PreprocessedRequest(
            token_ids=list(range(5, 17)), request_id=rid,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        return toks

    first = await run("m1")
    assert len(first) == 6
    second = await run("m2")
    assert second == first
    assert eng.metrics["cache_hit_tokens"] > 0  # prefix cache engaged
    await eng.close()


def test_moe_preset_registered():
    assert PRESETS["tiny-moe"].n_experts == 4
    assert PRESETS["mixtral-8x7b"].n_experts == 8


def test_moe_batched_prefill_rows_get_their_own_logits():
    """Co-batched MoE rows give each row's own logits: prefill_batched
    runs its rows flattened through the one dispatch (moe_rows), and a
    row's result depends on no other row, so batched logits equal
    per-sequence prefill logits."""
    from dynamo_tpu.models.llama import init_params, prefill, prefill_batched

    cfg = moe_cfg(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(4))
    bs, nb, mb, T = 4, 64, 8, 16
    shape = (cfg.n_layers, cfg.n_kv_heads, nb, cfg.head_dim, bs)
    kv_a = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    kv_b = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))

    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab_size, T).astype(np.int32)
               for _ in range(2)]
    tables = np.zeros((2, mb), np.int32)
    for i in range(2):
        tables[i, : T // bs] = 1 + i * mb + np.arange(T // bs)

    solo = []
    for i in range(2):
        lg, kv_a = prefill(
            params, cfg, kv_a, jnp.asarray(prompts[i]),
            jnp.arange(T, dtype=jnp.int32), jnp.asarray(tables[i]),
            jnp.int32(0), jnp.int32(T),
        )
        solo.append(np.asarray(lg))

    blg, kv_b = prefill_batched(
        params, cfg, kv_b, jnp.asarray(np.stack(prompts)),
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T)),
        jnp.asarray(tables), jnp.zeros(2, jnp.int32),
        jnp.full((2,), T, jnp.int32),
    )
    for i in range(2):
        np.testing.assert_allclose(np.asarray(blg[i]), solo[i],
                                   rtol=2e-5, atol=2e-5)
