"""MLA / DeepSeek family: paged latent-cache attention vs a dense
non-absorbed oracle, chunked-prefill equivalence, fused decode, MoE with
shared experts, and the engine serving the family end-to-end.

Mirrors tests/test_engine.py's shape: an independent full-attention
reference implementation is ground truth for the paged + weight-absorbed
serving path."""


import pytest

# real-JAX-engine tests: XLA compiles (seconds at tier-1's -O0) and
# device work run inside the async test bodies, so the conftest's 200ms
# event-loop slow-callback gate (DYN004's runtime twin) cannot hold
# here; mocker/frontend/router fleets keep it armed.
pytestmark = pytest.mark.allow_slow_callbacks

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import get_family
from dynamo_tpu.models.deepseek import (
    DeepseekConfig,
    _ds_ffn,
    _kv_latent,
    _q_proj,
    decode,
    decode_multi,
    init_params,
    kv_cache_shapes,
    prefill,
    prefill_batched,
)
from dynamo_tpu.models.llama import rms_norm
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

MLA32 = DeepseekConfig(
    name="mla32", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, ffn_dim=128, dtype=jnp.float32,
)
MLA32_MOE = DeepseekConfig(
    name="mla32-moe", vocab_size=256, d_model=64, n_layers=3, n_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, ffn_dim=128, moe_ffn_dim=64, n_experts=4,
    experts_per_token=2, n_shared_experts=1, first_k_dense=1,
    routed_scaling_factor=1.5, dtype=jnp.float32,
)


def dense_mla_logits(params, cfg, token_ids):
    """Independent oracle: full-sequence MLA attention with per-head K/V
    MATERIALIZED (non-absorbed, no paging).  Shares only the projection
    helpers with the implementation under test."""
    T = len(token_ids)
    x = params["embedding"][jnp.asarray(token_ids)].astype(cfg.dtype)
    positions = jnp.arange(T)
    scale = 1.0 / np.sqrt(cfg.qk_head_dim)
    for layer in params["layers"]:
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q_nope, q_rope = _q_proj(layer, cfg, h, positions)  # [T,nh,*]
        c, kr = _kv_latent(layer, cfg, h, positions)        # [T,R],[T,dr]
        k_nope = jnp.einsum("tr,hrd->thd", c.astype(jnp.float32),
                            layer["w_uk"].astype(jnp.float32))
        v = jnp.einsum("tr,hrd->thd", c.astype(jnp.float32),
                       layer["w_uv"].astype(jnp.float32))
        q = jnp.concatenate(
            [q_nope.astype(jnp.float32), q_rope.astype(jnp.float32)], -1)
        k = jnp.concatenate(
            [k_nope,
             jnp.broadcast_to(kr.astype(jnp.float32)[:, None, :],
                              (T, cfg.n_heads, cfg.qk_rope_head_dim))], -1)
        s = jnp.einsum("ihd,jhd->hij", q, k) * scale
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hij,jhd->ihd", p, v)
        x = x + o.reshape(T, -1).astype(cfg.dtype) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + _ds_ffn(layer, cfg, h)
    x = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def fresh_cache(cfg, num_blocks=32, block_size=4):
    ks, vs = kv_cache_shapes(cfg, num_blocks, block_size)
    return jnp.zeros(ks, cfg.dtype), jnp.zeros(vs, cfg.dtype)


def rollout_paged(params, cfg, prompt, n_steps, chunks=None,
                  block_size=4):
    """Greedy autoregressive rollout through the paged prefill+decode path
    (optionally chunked prefill).  Returns generated tokens."""
    kv = fresh_cache(cfg, block_size=block_size)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]  # blocks 1..16
    chunks = chunks or [len(prompt)]
    pos = 0
    toks = []
    for ch in chunks:
        chunk = prompt[pos:pos + ch]
        logits, kv = prefill(
            params, cfg, kv, jnp.asarray(chunk, jnp.int32),
            jnp.arange(pos, pos + ch, dtype=jnp.int32), table[0],
            jnp.int32(pos), jnp.int32(ch),
        )
        pos += ch
    last = int(jnp.argmax(logits))
    toks.append(last)
    for _ in range(n_steps - 1):
        logits, kv = decode(
            params, cfg, kv, jnp.asarray([last], jnp.int32),
            jnp.asarray([pos], jnp.int32), table,
            jnp.asarray([pos], jnp.int32),
        )
        last = int(jnp.argmax(logits[0]))
        toks.append(last)
        pos += 1
    return toks


def oracle_rollout(params, cfg, prompt, n_steps):
    seq = list(prompt)
    out = []
    for _ in range(n_steps):
        logits = dense_mla_logits(params, cfg, seq)
        t = int(jnp.argmax(logits[-1]))
        out.append(t)
        seq.append(t)
    return out


def test_mla_paged_matches_dense_oracle():
    params = init_params(MLA32, jax.random.PRNGKey(3))
    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 20]  # crosses block boundary
    # 3 steps: every oracle step is a fresh dense shape — a fresh XLA
    # compile — so each extra step costs seconds of tier-1 wall clock
    got = rollout_paged(params, MLA32, prompt, 3)
    want = oracle_rollout(params, MLA32, prompt, 3)
    assert got == want


def test_mla_chunked_prefill_equivalence():
    """Prefill in 3 chunks (prefix-cache / chunked path, ctx_len>0) must
    generate identically to one-shot prefill."""
    params = init_params(MLA32, jax.random.PRNGKey(4))
    prompt = list(range(40, 52))  # 12 tokens
    one = rollout_paged(params, MLA32, prompt, 5)
    chunked = rollout_paged(params, MLA32, prompt, 5, chunks=[4, 4, 4])
    assert one == chunked


def test_mla_moe_paged_matches_dense_oracle():
    """DeepSeekMoE layers (shared + routed, scaled) through the paged
    path vs the oracle."""
    params = init_params(MLA32_MOE, jax.random.PRNGKey(5))
    prompt = [3, 17, 44, 9, 100, 55, 8]
    # 3 steps, same per-step oracle-compile rationale as above
    got = rollout_paged(params, MLA32_MOE, prompt, 3)
    want = oracle_rollout(params, MLA32_MOE, prompt, 3)
    assert got == want


def test_mla_decode_multi_matches_single_steps():
    params = init_params(MLA32, jax.random.PRNGKey(6))
    prompt = [10, 20, 30, 40, 50]
    kv = fresh_cache(MLA32)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    logits, kv = prefill(
        params, MLA32, kv, jnp.asarray(prompt, jnp.int32),
        jnp.arange(len(prompt), dtype=jnp.int32), table[0],
        jnp.int32(0), jnp.int32(len(prompt)),
    )
    first = jnp.argmax(logits)[None].astype(jnp.int32)
    pos = len(prompt)
    burst, _ = decode_multi(
        params, MLA32, kv, first, jnp.asarray([pos], jnp.int32),
        table, jnp.asarray([pos], jnp.int32), 4,
    )
    single = rollout_paged(params, MLA32, prompt, 5)
    assert [int(first[0])] + [int(t) for t in burst[:, 0]] == single


def test_mla_prefill_batched_matches_single():
    params = init_params(MLA32, jax.random.PRNGKey(7))
    kv = fresh_cache(MLA32)
    prompts = [[4, 8, 15, 16], [23, 42, 7, 99, 3, 12]]
    T = 8
    toks = jnp.zeros((2, T), jnp.int32)
    tables = jnp.stack([jnp.arange(1, 17, dtype=jnp.int32),
                        jnp.arange(17, 33, dtype=jnp.int32)])
    for i, p in enumerate(prompts):
        toks = toks.at[i, :len(p)].set(jnp.asarray(p, jnp.int32))
    logits_b, _ = prefill_batched(
        params, MLA32, kv,
        toks, jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T)),
        tables, jnp.zeros((2,), jnp.int32),
        jnp.asarray([len(p) for p in prompts], jnp.int32),
    )
    for i, p in enumerate(prompts):
        kv1 = fresh_cache(MLA32)
        logits_1, _ = prefill(
            params, MLA32, kv1, jnp.asarray(p, jnp.int32),
            jnp.arange(len(p), dtype=jnp.int32), tables[i],
            jnp.int32(0), jnp.int32(len(p)),
        )
        np.testing.assert_allclose(np.asarray(logits_b[i]),
                                   np.asarray(logits_1),
                                   rtol=2e-4, atol=2e-4)


async def test_engine_serves_mla_family():
    """JaxEngine end-to-end on the MLA family via get_family dispatch:
    greedy generations equal the oracle's teacher-forced argmax."""
    eng = JaxEngine(EngineConfig(
        model_config=MLA32, block_size=4, num_blocks=128,
        max_blocks_per_seq=16, max_num_seqs=4,
        prefill_buckets=(8, 16, 32, 64), seed=7,
    ))
    assert get_family(eng.model_cfg).__name__.endswith("deepseek")
    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 20]
    req = PreprocessedRequest(
        token_ids=prompt, request_id="mla0",
        sampling=SamplingOptions(temperature=0.0, seed=0),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )
    toks = []
    async for out in eng.generate(req):
        toks.extend(out.token_ids)
    assert len(toks) == 6
    seq = list(prompt)
    for t in toks:
        logits = dense_mla_logits(eng.params, MLA32, seq)
        assert int(jnp.argmax(logits[-1])) == t, \
            f"divergence at position {len(seq)}"
        seq.append(t)
    await eng.close()


def test_deepseek_presets_resolve():
    cfg = EngineConfig(model="tiny-mla").resolve_model()
    assert isinstance(cfg, DeepseekConfig)
    r1 = EngineConfig(model="deepseek-r1").resolve_model()
    assert r1.n_experts == 256 and r1.kv_lora_rank == 512


# ---------------------------------------------------------------------------
# the latent decode kernel (ops/pallas_mla_attention.py) against the jnp
# body, under the interpreter
# ---------------------------------------------------------------------------

# (heads, R, dr): the two cells' head counts, widths cut to test size
LATENT_WIDTHS = {"moonlight": (16, 64, 16), "ling": (32, 64, 32)}


@pytest.mark.parametrize("bpc", [None, 2], ids=["chunk8", "chunk2"])
@pytest.mark.parametrize("family", sorted(LATENT_WIDTHS))
def test_latent_kernel_matches_the_jnp_body(family, bpc):
    """Lanes of unequal length in tables of 12 blocks: a full table, a
    context ending mid-block, one token, exactly one block, two idle
    lanes (kv_len 0: output 0) with another lane between and after
    them, a lane whose table is far wider than its two live blocks.
    Every padded table entry points at the garbage block, which holds
    NaN for the kernel: it moves live blocks only, so none of it may
    reach an output.  Layer 1 of two: the layer index is honoured."""
    from dynamo_tpu.ops.mla_attention import (
        _mla_decode_jnp,
        mla_decode_attention,
    )
    from dynamo_tpu.ops.pallas_mla_attention import mla_decode_pallas

    nh, R, dr = LATENT_WIDTHS[family]
    bs, mb, dv = 16, 12, 8
    lens = np.asarray([mb * bs, 5 * bs + 7, 0, 1, bs, 0, 2 * bs, 3 * bs - 1],
                      np.int32)
    B = len(lens)
    nb = 1 + B * mb
    rng = np.random.default_rng(11)
    tables = np.zeros((B, mb), np.int32)
    perm = rng.permutation(nb - 1) + 1
    for b in range(B):
        used = -(-int(lens[b]) // bs)
        tables[b, :used] = perm[b * mb:b * mb + used]
    bf = jnp.bfloat16
    qa = jnp.asarray(rng.standard_normal((B, nh, R)), bf)
    qr = jnp.asarray(rng.standard_normal((B, nh, dr)), bf)
    c = jnp.asarray(rng.standard_normal((2, 1, nb, R, bs)), bf)
    kr = jnp.asarray(rng.standard_normal((2, 1, nb, dr, bs)), bf)
    scale = 0.11
    t, n = jnp.asarray(tables), jnp.asarray(lens)
    want = np.asarray(_mla_decode_jnp(qa, qr, c, kr, 1, t, n, scale))
    dirty = lambda x: x.at[:, :, 0].set(jnp.nan)
    got = np.asarray(mla_decode_pallas(
        qa, qr, dirty(c), dirty(kr), jnp.int32(1), t, n, scale,
        blocks_per_chunk=bpc, interpret=True))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    live = lens > 0
    assert np.abs(got[~live]).max() == 0.0
    # the kernel follows the body's float32 arithmetic (unrounded
    # queries, the weights as a bf16 pair): outputs are averages of unit
    # normals, and they agree to 1e-4
    np.testing.assert_allclose(got[live], want[live], atol=1e-4)
    other = np.asarray(mla_decode_pallas(
        qa, qr, c, kr, jnp.int32(0), t, n, scale, interpret=True))
    assert np.abs(other[live] - got[live]).max() > 0.1
    # through the op: W_UV outside the kernel, as in the jnp form
    w_uv = jnp.asarray(rng.standard_normal((nh, R, dv)) / R ** 0.5, bf)
    a, b = (np.asarray(mla_decode_attention(
        qa, qr, c, kr, 1, t, n, w_uv, scale, impl=impl), np.float32)
        for impl in ("pallas_interpret", "jnp"))
    np.testing.assert_allclose(a[live], b[live], atol=0.05)
    with pytest.raises(ValueError, match="MLA decode impl"):
        mla_decode_attention(qa, qr, c, kr, 1, t, n, w_uv, scale,
                             impl="jnp_bf16")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_token_write_sets_the_cells_the_xla_writer_sets(dtype):
    """`mla_write_token` (the Pallas writer beside the latent kernel)
    against `write_token_kv(resident=True)`: first and last column of a
    block, a block boundary, an idle lane between writers; bit-equal
    pools, layer 0 untouched."""
    from dynamo_tpu.ops.mla_attention import mla_write_token
    from dynamo_tpu.ops.paged_attention import write_token_kv

    R, dr, bs, mb, B = 32, 16, 8, 6, 5
    nb = 1 + B * mb
    rng = np.random.default_rng(2)
    c = jnp.asarray(rng.standard_normal((2, 1, nb, R, bs)), dtype)
    kr = jnp.asarray(rng.standard_normal((2, 1, nb, dr, bs)), dtype)
    new_c = jnp.asarray(rng.standard_normal((B, 1, R)), dtype)
    new_kr = jnp.asarray(rng.standard_normal((B, 1, dr)), dtype)
    tables = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb),
                         jnp.int32)
    ctx = jnp.asarray([0, 7, 8, 20, 47], jnp.int32)
    valid = jnp.asarray([True, True, False, True, True])
    want = write_token_kv(c, kr, 1, new_c, new_kr, tables, ctx,
                          resident=True, valid=valid)
    got = mla_write_token(c, kr, 1, new_c, new_kr, tables, ctx,
                          valid=valid, interpret=True)
    for w, g, before in zip(want, got, (c, kr)):
        assert np.array_equal(np.asarray(w, np.float32),
                              np.asarray(g, np.float32))
        assert np.array_equal(np.asarray(g[0], np.float32),
                              np.asarray(before[0], np.float32))
        assert not np.array_equal(np.asarray(g[1], np.float32),
                                  np.asarray(before[1], np.float32))


@pytest.mark.parametrize("platform,block,dtype,heights,want", [
    ("cpu", 128, jnp.bfloat16, (512, 64), "jnp"),
    ("tpu", 16, jnp.bfloat16, (512, 64), "jnp"),
    ("tpu", 128, jnp.float32, (512, 64), "jnp"),
    ("tpu", 128, jnp.bfloat16, (512, 64), "pallas"),
    ("tpu", 256, jnp.bfloat16, (512, 64), "pallas"),
    ("tpu", 128, jnp.bfloat16, (512, 8), "jnp"),
    ("tpu", 128, jnp.bfloat16, (24, 64), "jnp"),
])
def test_auto_is_decided_from_what_the_latent_cache_shows(
        platform, block, dtype, heights, want):
    """`resolve_decode_impl` asked about both members of a latent cache:
    the kernel on a TPU with lane-aligned blocks, a bf16 cache and both
    plane heights whole sublane tiles; jnp elsewhere.  An explicit impl
    is returned as given, and both families' configs say the heights."""
    from dynamo_tpu.models.ling import LingConfig
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    assert resolve_decode_impl("auto", platform, block, heights,
                               dtype) == want
    assert resolve_decode_impl("pallas_interpret", platform, block,
                               heights, dtype) == "pallas_interpret"
    for cfg in (DeepseekConfig(kv_lora_rank=heights[0],
                               qk_rope_head_dim=heights[1]),
                LingConfig(kv_lora_rank=heights[0],
                           qk_rope_head_dim=heights[1])):
        assert cfg.attn_impl == "auto"
        assert cfg.mla_plane_heights == heights
    assert DeepseekConfig().head_dim == DeepseekConfig().mla_plane_heights


def _two_lanes_and_an_idle_one(family, cfg, params, kv, prefill_kw):
    """Lanes 0 and 2 hold prompts of 9 and 21 tokens (blocks of 4: one
    ends mid-block), lane 1 is idle -> (kv, first tokens, positions,
    tables, valid)."""
    rng = np.random.default_rng(3)
    tables = np.zeros((3, 16), np.int32)
    tables[0, :8], tables[2, :8] = 1 + np.arange(8), 9 + np.arange(8)
    first, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for lane, n in ((0, 9), (2, 21)):
        toks = np.zeros(32, np.int32)          # one bucket of 32
        toks[:n] = rng.integers(3, cfg.vocab_size, n)
        logits, kv = family.prefill(
            params, cfg, kv, jnp.asarray(toks),
            jnp.arange(32, dtype=jnp.int32), jnp.asarray(tables[lane]),
            jnp.int32(0), jnp.int32(n), **prefill_kw(lane))
        first[lane], pos[lane] = int(jnp.argmax(logits)), n
    return (kv, jnp.asarray(first), jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray([True, False, True]))


def test_decode_multi_under_the_kernel_emits_the_same_tokens():
    """deepseek.decode_multi, 6 fused steps over two lanes and an idle
    one: `pallas_interpret` (the kernel + the resident column write)
    against `jnp` (the gather + the flat scatter): equal greedy tokens
    and, on every block a lane owns, equal latents and rope keys."""
    import dataclasses

    from dynamo_tpu.models import deepseek

    params = init_params(MLA32, jax.random.PRNGKey(8))
    kv, first, pos, tables, valid = _two_lanes_and_an_idle_one(
        deepseek, MLA32, params, fresh_cache(MLA32), lambda lane: {})
    out = {}
    for impl in ("jnp", "pallas_interpret"):
        cfg = dataclasses.replace(MLA32, attn_impl=impl)
        out[impl] = decode_multi(params, cfg, kv, first, pos, tables, pos,
                                 6, valid=valid)
    (ta, kva), (tb, kvb) = out["jnp"], out["pallas_interpret"]
    live = np.asarray(valid)
    assert np.asarray(ta)[:, live].tolist() == np.asarray(tb)[:, live].tolist()
    for a, b in zip(kva, kvb):
        np.testing.assert_allclose(np.asarray(a[:, :, 1:17]),
                                   np.asarray(b[:, :, 1:17]), atol=1e-5)


def test_ling_decode_multi_under_the_kernel_keeps_state_and_tail():
    """ling.decode_multi (one period: five KDA layers, then the MLA
    layer), the same burst: equal greedy tokens, and the float32 state
    and the convolution tail BIT-equal on every lane, the idle one
    included: the kernel and the resident write touch the paged members
    only."""
    import dataclasses

    from dynamo_tpu.models import ling

    cfg = ling.LingConfig(dtype=jnp.float32, experts_held=(0, 8),
                          mla_q_block=16)
    params = ling.init_params(cfg, jax.random.PRNGKey(9))
    kv = tuple(jnp.ones(s, d) if i in (2, 3) else jnp.zeros(s, d)
               for i, (s, d) in enumerate(zip(
                   ling.kv_cache_shapes(cfg, 32, 4, lanes=3),
                   ling.kv_cache_dtypes(cfg))))
    kv, first, pos, tables, valid = _two_lanes_and_an_idle_one(
        ling, cfg, params, kv, lambda lane: {"lanes": jnp.int32(lane)})
    out = {}
    for impl in ("jnp", "pallas_interpret"):
        out[impl] = ling.decode_multi(
            params, dataclasses.replace(cfg, attn_impl=impl), kv, first,
            pos, tables, pos, 6, valid=valid)
    (ta, kva), (tb, kvb) = out["jnp"], out["pallas_interpret"]
    live = np.asarray(valid)
    assert np.asarray(ta)[:, live].tolist() == np.asarray(tb)[:, live].tolist()
    for member in (2, 3):
        assert np.array_equal(np.asarray(kva[member]),
                              np.asarray(kvb[member]))
        assert float(jnp.abs(kvb[member][:, 1] - 1).max()) == 0.0
    assert not np.array_equal(np.asarray(kvb[2]), np.asarray(kv[2]))


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_engine_counts_what_the_resolved_impl_reads(impl):
    """decode_attn_read_blocks under both families: the gathering read
    moves lanes x table width a step, the kernel each step's live
    blocks (= decode_attn_live_blocks: the share reads 100 %)."""
    from dynamo_tpu.models import ling

    eng = JaxEngine(EngineConfig(
        model_config=MLA32, block_size=4, num_blocks=128,
        max_blocks_per_seq=16, max_num_seqs=4, attn_impl=impl,
        prefill_buckets=(8, 16, 32, 64), seed=7))
    assert eng.model_cfg.attn_impl == impl
    ctx, k = np.asarray([9, 21], np.int64), 4
    eng._count_decode_attn(ctx, k)
    live = int(sum(-(-(c + 1 + j) // 4) for c in ctx for j in range(k)))
    assert eng.metrics["decode_attn_read_blocks"] == (
        live if impl == "pallas_interpret" else k * 4 * 16)
    assert eng.metrics["decode_attn_live_blocks"] == k * (3 + 6)
    cfg = ling.LingConfig(n_layers=12)      # two MLA layers
    counts = ling.decode_block_counts(cfg, ctx, k, 4, 4, 16, impl)
    assert counts["decode_attn_live_blocks"] == 2 * live
    assert counts["decode_attn_read_blocks"] == 2 * (
        live if impl == "pallas_interpret" else k * 4 * 16)


# ---------------------------------------------------------------------------
# the latent PREFILL read as one flash kernel over the pool's live blocks
# (ops/pallas_mla_attention.py `mla_prefill_pallas`), the planes write
# beside it and the rule that picks (PR 49)
# ---------------------------------------------------------------------------

# rows of (context, real tokens) in a bucket of 32 over tables of 6
# blocks of 16: a row that starts mid-block with a padded tail, a fresh
# row that fills its bucket, a row with nothing valid, one token at a
# block's first column, a context of whole blocks
PREFILL_ROWS = {
    "one_fresh": [(0, 32)],
    "one_carried_padded": [(21, 27)],
    "several": [(21, 27), (0, 32), (40, 0), (48, 1), (32, 32)],
}


def _prefill_case(nh, rows, dtype, seed=5):
    """-> (q_nope, q_rope, c, kr, pools, tables, ctx, true, w_uk, w_uv):
    random operands over a two-layer pool whose garbage block is NaN."""
    R, dr, dn, dv, bs, mb, T = 32, 8, 16, 16, 16, 6, 32
    S = len(rows)
    nb = 1 + S * mb
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    pools = (normal(2, 1, nb, R, bs).at[:, :, 0].set(jnp.nan),
             normal(2, 1, nb, dr, bs).at[:, :, 0].set(jnp.nan))
    tables = jnp.asarray(1 + rng.permutation(nb - 1).reshape(S, mb),
                         jnp.int32)
    ctx = jnp.asarray([r[0] for r in rows], jnp.int32)
    true = jnp.asarray([r[1] for r in rows], jnp.int32)
    w = lambda d: jnp.asarray(rng.standard_normal((nh, R, d)) / R ** 0.5,
                              dtype)
    return (normal(S, T, nh, dn), normal(S, T, nh, dr), normal(S, T, R),
            normal(S, T, dr), pools, tables, ctx, true, w(dn), w(dv))


def _jnp_rows(qn, qr, c, kr, pools, tables, ctx, true, w_uk, w_uv):
    from dynamo_tpu.ops.mla_attention import mla_prefill_attention

    return jax.vmap(
        lambda a, b, cb, krb, tb, cl, tl: mla_prefill_attention(
            a, b, cb, krb, *pools, 1, tb, cl, tl, w_uk, w_uv)
    )(qn, qr, c, kr, tables, ctx, true)


@pytest.mark.parametrize("tiles", [{}, {"token_block": 8, "chunk_cols": 2},
                                   {"token_block": 16, "chunk_cols": 1,
                                    "heads_a_step": 4}],
                         ids=["default", "q8_k32", "q16_k16_h4"])
@pytest.mark.parametrize("rows", sorted(PREFILL_ROWS))
@pytest.mark.parametrize("family", sorted(LATENT_WIDTHS))
def test_latent_prefill_kernel_matches_the_jnp_form(family, rows, tiles):
    """The flash kernel under the interpreter against the jnp form a
    row, at both cells' head counts: every real query agrees (float32
    operands: to rounding of the running sums), a row's padding and a
    row with nothing valid return 0, nothing of the garbage block (NaN)
    or of a block past a row's frontier reaches an output, and layer 1
    of two is the layer read."""
    from dynamo_tpu.ops.mla_attention import mla_write_rows
    from dynamo_tpu.ops.pallas_mla_attention import mla_prefill_pallas

    nh = LATENT_WIDTHS[family][0]
    qn, qr, c, kr, pools, tables, ctx, true, w_uk, w_uv = _prefill_case(
        nh, PREFILL_ROWS[rows], jnp.float32)
    pools = mla_write_rows(*pools, 1, c, kr, tables, ctx, true)
    want = np.asarray(_jnp_rows(qn, qr, c, kr, pools, tables, ctx, true,
                                w_uk, w_uv))
    got = np.asarray(mla_prefill_pallas(
        qn, qr, *pools, jnp.int32(1), tables, ctx, true, w_uk, w_uv,
        interpret=True, **tiles))
    real = np.arange(32)[None, :] < np.asarray(true)[:, None]
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real], atol=2e-5)
    assert np.abs(got[~real]).max(initial=0.0) == 0.0
    if real.any():
        other = np.asarray(mla_prefill_pallas(
            qn, qr, *(jnp.nan_to_num(p) for p in pools), jnp.int32(0),
            tables, ctx, true, w_uk, w_uv, interpret=True, **tiles))
        assert np.abs(other[real] - got[real]).max() > 0.05


def test_latent_prefill_kernel_in_bf16_follows_the_jnp_form():
    """bf16 pools, weights and queries (the serving dtypes): K, V and
    the weights of the softmax are rounded to bf16 between the kernel's
    products, as XLA's default precision rounds the jnp form's on the
    chip; against the jnp form's float32 products on the CPU the
    outputs (averages of unit normals) agree to bf16's step."""
    from dynamo_tpu.ops.mla_attention import mla_prefill_flash, mla_write_rows

    qn, qr, c, kr, pools, tables, ctx, true, w_uk, w_uv = _prefill_case(
        16, PREFILL_ROWS["several"], jnp.bfloat16)
    pools = mla_write_rows(*pools, 1, c, kr, tables, ctx, true)
    want = np.asarray(_jnp_rows(qn, qr, c, kr, pools, tables, ctx, true,
                                w_uk, w_uv), np.float32)
    got = mla_prefill_flash(qn, qr, *pools, 1, tables, ctx, true, w_uk,
                            w_uv, interpret=True)
    assert got.dtype == jnp.bfloat16
    real = np.arange(32)[None, :] < np.asarray(true)[:, None]
    np.testing.assert_allclose(np.asarray(got, np.float32)[real], want[real],
                               atol=0.03)


@pytest.mark.parametrize("n_c,tk,ctx,true,want", [
    # one tile of 8 real queries at positions 16..23 over key tiles of 8:
    # tiles 0 to 2 hold keys a query sees, tile 3 lies above the frontier
    (4, 8, 16, 8, [1, 1, 1, 0]),
    # a padded tail: runs to the farthest REAL query (position 16)
    (4, 8, 16, 1, [1, 1, 1, 0]),
    (4, 8, 15, 1, [1, 1, 0, 0]),
    # nothing valid: nothing runs
    (4, 8, 16, 0, [0, 0, 0, 0]),
    # a fresh row: the diagonal tile alone
    (4, 8, 0, 8, [1, 0, 0, 0]),
])
def test_prefill_tile_plan_skips_what_no_query_sees(n_c, tk, ctx, true,
                                                    want):
    """`prefill_tile_plan`: which pairs of one query tile of 8 tokens
    run, and a skipped step names the blocks of the last pair that ran;
    a row's second tile starts 8 tokens on."""
    from dynamo_tpu.ops.pallas_mla_attention import prefill_tile_plan

    runs, fetch = prefill_tile_plan(
        jnp.asarray([ctx], jnp.int32), jnp.asarray([true], jnp.int32), 1, 8,
        n_c, tk)
    assert np.asarray(runs).tolist() == [want]
    live = max(sum(want), 1)
    assert np.asarray(fetch).tolist() == [
        [min(j, live - 1) for j in range(n_c)]]
    runs2, _ = prefill_tile_plan(
        jnp.asarray([ctx, 0], jnp.int32), jnp.asarray([16, 12], jnp.int32),
        2, 8, n_c, tk)
    assert np.asarray(runs2).shape == (4, n_c)
    assert np.asarray(runs2)[2:].tolist() == [[1, 0, 0, 0], [1, 1, 0, 0]]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", sorted(PREFILL_ROWS))
def test_latent_rows_write_sets_the_cells_the_scatter_sets(rows, dtype):
    """`mla_write_rows` (whole planes in the resident layout, the padded
    rows laid end to end as a packed stream) against
    `write_prompt_kv_batched` (the flat column scatter): bit-equal pools
    outside the garbage block, which the scatter alone dirties with the
    padding; layer 0 untouched."""
    from dynamo_tpu.ops.mla_attention import mla_write_rows
    from dynamo_tpu.ops.paged_attention import write_prompt_kv_batched

    _, _, c, kr, pools, tables, ctx, true, _, _ = _prefill_case(
        4, PREFILL_ROWS[rows], dtype)
    pools = tuple(jnp.nan_to_num(p) for p in pools)
    want = write_prompt_kv_batched(
        *pools, 1, c[:, :, None, :], kr[:, :, None, :], tables, ctx, true)
    got = mla_write_rows(*pools, 1, c, kr, tables, ctx, true)
    for w, g, before in zip(want, got, pools):
        f32 = lambda x: np.asarray(x, np.float32)
        assert np.array_equal(f32(w)[:, :, 1:], f32(g)[:, :, 1:])
        assert np.array_equal(f32(g)[:, :, 0], f32(before)[:, :, 0])
        assert np.array_equal(f32(g[0]), f32(before[0]))
        assert not np.array_equal(f32(g[1]), f32(before[1]))


@pytest.mark.parametrize("impl,platform,block,dtype,tokens,want", [
    ("auto", "tpu", 128, jnp.bfloat16, 2048, "pallas"),
    ("auto", "tpu", 128, jnp.bfloat16, 512, "pallas"),
    ("auto", "tpu", 128, jnp.bfloat16, 256, "jnp"),       # under the floor
    ("auto", "tpu", 128, jnp.bfloat16, 32, "jnp"),
    ("auto", "cpu", 128, jnp.bfloat16, 2048, "jnp"),
    ("auto", "tpu", 16, jnp.bfloat16, 2048, "jnp"),
    ("auto", "tpu", 128, jnp.float32, 2048, "jnp"),
    # what the engine has made of "auto" by the time a program is traced
    ("pallas", "tpu", 128, jnp.bfloat16, 1024, "pallas"),
    ("pallas", "tpu", 128, jnp.bfloat16, 256, "jnp"),
    ("pallas", "cpu", 128, jnp.bfloat16, 512, "pallas"),  # a described chip
    ("pallas_interpret", "cpu", 16, jnp.float32, 8, "pallas_interpret"),
    ("jnp", "tpu", 128, jnp.bfloat16, 2048, "jnp"),       # the A/B
])
def test_prefill_impl_is_decided_from_what_the_code_sees(
        impl, platform, block, dtype, tokens, want):
    """`resolve_mla_prefill_impl`: the kernel where the decode kernel
    runs, from the 512-token bucket up; and both families ask it the
    same way (`mla_prefill_impl`)."""
    import dataclasses

    from dynamo_tpu.models.deepseek import mla_prefill_impl
    from dynamo_tpu.models.ling import LingConfig
    from dynamo_tpu.ops.mla_attention import (
        MLA_PREFILL_KERNEL_MIN_TOKENS,
        resolve_mla_prefill_impl,
    )

    assert MLA_PREFILL_KERNEL_MIN_TOKENS == 512
    assert resolve_mla_prefill_impl(impl, platform, block, (512, 64), dtype,
                                    tokens) == want
    if platform == "cpu":      # the host the test runs on
        for cfg in (DeepseekConfig(attn_impl=impl),
                    LingConfig(attn_impl=impl)):
            assert mla_prefill_impl(cfg, tokens, block, dtype) == want
        # the host's counts have no cache to show: an impl the engine
        # resolved is taken as given, an unresolved "auto" is jnp
        assert mla_prefill_impl(DeepseekConfig(attn_impl=impl), tokens) == (
            "jnp" if impl == "auto" else want)


def test_latent_prefill_runs_per_head_shard_under_tp():
    """`mla_prefill_flash` under a tp = 2 mesh: the pools replicated,
    the heads sharded through the queries and w_uk / w_uv, each shard
    the kernel on its own heads: the unsharded jnp form's outputs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.mla_attention import mla_prefill_flash, mla_write_rows
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    qn, qr, c, kr, pools, tables, ctx, true, w_uk, w_uv = _prefill_case(
        4, PREFILL_ROWS["several"], jnp.float32)
    pools = mla_write_rows(*pools, 1, c, kr, tables, ctx, true)
    want = np.asarray(_jnp_rows(qn, qr, c, kr, pools, tables, ctx, true,
                                w_uk, w_uv))
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    heads = NamedSharding(mesh, P("tp", None, None))
    with mesh:
        got = jax.jit(lambda *a: mla_prefill_flash(
            *a, mesh=mesh, interpret=True))(
            qn, qr, *pools, 1, tables, ctx, true,
            jax.device_put(w_uk, heads), jax.device_put(w_uv, heads))
    real = np.arange(32)[None, :] < np.asarray(true)[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], want[real], atol=2e-5)


@pytest.mark.parametrize("family", ["deepseek", "ling"])
def test_prefill_through_the_kernel_gives_the_jnp_logits(family):
    """prefill_batched of both families, two rows (one carried from a
    first chunk, one fresh with a padded tail): `pallas_interpret` (the
    flash kernel + the planes write) against `jnp` (the gather + the
    column scatter): equal logits and, on every block a row owns, equal
    latents and rope keys, to float32 rounding (a later layer's latents
    carry the earlier reads' rounding)."""
    import dataclasses

    from dynamo_tpu.models import deepseek, ling

    if family == "ling":
        mod = ling
        cfg = ling.LingConfig(dtype=jnp.float32, experts_held=(0, 8),
                              mla_q_block=16)
        lane_kw = {"lanes": jnp.asarray([0, 2], jnp.int32)}
        kv = tuple(jnp.zeros(s, d) for s, d in zip(
            ling.kv_cache_shapes(cfg, 32, 4, lanes=3),
            ling.kv_cache_dtypes(cfg)))
    else:
        mod, cfg, lane_kw = deepseek, dataclasses.replace(
            MLA32, dtype=jnp.float32), {}
        kv = tuple(jnp.zeros(s, jnp.float32) for s in kv_cache_shapes(
            cfg, 32, 4))
    params = mod.init_params(cfg, jax.random.PRNGKey(12))
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, (2, 32)), jnp.int32)
    tables = jnp.asarray(np.stack([1 + np.arange(12), 13 + np.arange(12)]),
                         jnp.int32)
    pos = jnp.arange(32, dtype=jnp.int32)[None, :]
    out = {}
    for impl in ("jnp", "pallas_interpret"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        zero = jnp.zeros((2,), jnp.int32)
        # chunk 1: row 0 takes 13 tokens; chunk 2: row 0 goes on from 13
        # with 19 more, row 1 starts with 30 of 32
        _, kv1 = mod.prefill_batched(
            params, c, kv, toks, pos + zero[:, None], tables, zero,
            jnp.asarray([13, 0], jnp.int32), **lane_kw)
        ctx = jnp.asarray([13, 0], jnp.int32)
        out[impl] = mod.prefill_batched(
            params, c, kv1, toks, pos + ctx[:, None], tables, ctx,
            jnp.asarray([19, 30], jnp.int32), **lane_kw)
    (la, kva), (lb, kvb) = out["jnp"], out["pallas_interpret"]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-4)
    for a, b in zip(kva[:2], kvb[:2]):
        np.testing.assert_allclose(np.asarray(a[:, :, 1:25]),
                                   np.asarray(b[:, :, 1:25]), atol=1e-4)


async def test_engine_serves_mla_through_the_prefill_kernel():
    """JaxEngine on the MLA family with `attn_impl="pallas_interpret"`:
    prompts prefilled through the flash kernel and the planes write
    (one in two chunks) give the jnp engine's greedy tokens, and both
    counters say so: MLA layers x prompt tokens, all of them in the
    kernel; under `jnp` the second stays 0."""
    import dataclasses

    cfg32 = dataclasses.replace(MLA32, dtype=jnp.float32)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(3, 256, n).tolist() for n in (11, 45)]
    got = {}
    for impl in ("jnp", "pallas_interpret"):
        eng = JaxEngine(EngineConfig(
            model_config=cfg32, block_size=4, num_blocks=128,
            max_blocks_per_seq=16, max_num_seqs=4, attn_impl=impl,
            prefill_buckets=(8, 16, 32), prefill_chunk_tokens=32, seed=7))
        assert eng.metrics["mla_prefill_tokens.prefill"] == 0
        outs = []
        for i, prompt in enumerate(prompts):
            toks = []
            async for frame in eng.generate(PreprocessedRequest(
                    token_ids=prompt, request_id=f"p{i}",
                    sampling=SamplingOptions(temperature=0.0, seed=0),
                    stop=StopConditions(max_tokens=4, ignore_eos=True))):
                toks.extend(frame.token_ids)
            outs.append(toks)
        got[impl] = outs
        n = cfg32.n_layers * sum(len(p) for p in prompts)
        assert eng.metrics["mla_prefill_tokens.prefill"] == n
        assert eng.metrics["mla_prefill_kernel_tokens.prefill"] == (
            n if impl == "pallas_interpret" else 0)
        await eng.close()
    assert got["jnp"] == got["pallas_interpret"]
