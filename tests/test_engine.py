"""JAX engine correctness: paged attention vs dense reference, prefix cache,
batching invariance, tensor-parallel invariance, cancellation."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real-JAX-engine tests: XLA compiles (seconds at tier-1's -O0) and
# device work run inside the async test bodies, so the conftest's 200ms
# event-loop slow-callback gate (DYN004's runtime twin) cannot hold
# here; mocker/frontend/router fleets keep it armed.
pytestmark = pytest.mark.allow_slow_callbacks


from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.llama import LlamaConfig, init_params, rms_norm, rope
from dynamo_tpu.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

FP32 = LlamaConfig(name="tiny32", vocab_size=256, d_model=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                   dtype=jnp.float32)


def dense_reference_logits(params, cfg, token_ids):
    """Independent full-attention forward (no paging): logits for every
    position.  Used as ground truth for the paged implementation."""
    T = len(token_ids)
    x = params["embedding"][jnp.asarray(token_ids)].astype(cfg.dtype)
    positions = jnp.arange(T)
    for layer in params["layers"]:
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q = (h @ layer["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        group = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(k, group, axis=1)  # [T, nh, hd]
        vr = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("ihd,jhd->hij", q.astype(jnp.float32),
                       kr.astype(jnp.float32)) / np.sqrt(cfg.head_dim)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hij,jhd->ihd", p, vr.astype(jnp.float32))
        x = x + o.reshape(T, -1).astype(cfg.dtype) @ layer["wo"]
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ layer["w_down"]
    x = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def engine(tp=1, **kw):
    defaults = dict(model_config=FP32, block_size=4, num_blocks=128,
                    max_blocks_per_seq=16, max_num_seqs=4, tp=tp,
                    prefill_buckets=(8, 16, 32, 64), seed=7)
    defaults.update(kw)
    return JaxEngine(EngineConfig(**defaults))


def greedy_req(tokens, n, rid, seed=0):
    return PreprocessedRequest(
        token_ids=tokens, request_id=rid,
        sampling=SamplingOptions(temperature=0.0, seed=seed),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )


async def collect(eng, req, token=None):
    toks = []
    async for out in eng.generate(req, token=token):
        toks.extend(out.token_ids)
    return toks


async def test_greedy_matches_dense_reference():
    """The paged engine's greedy generations must equal teacher-forced argmax
    under an independent dense implementation."""
    eng = engine()
    prompt = [5, 9, 13, 2, 7, 11, 3, 1, 8, 20]  # 10 tokens (crosses blocks)
    toks = await collect(eng, greedy_req(prompt, 6, "r0"))
    assert len(toks) == 6

    seq = list(prompt)
    for t in toks:
        logits = dense_reference_logits(eng.params, FP32, seq)
        expect = int(jnp.argmax(logits[-1]))
        assert expect == t, f"divergence at position {len(seq)}"
        seq.append(t)
    await eng.close()


async def test_prefix_cache_reuse_preserves_output():
    eng = engine()
    prompt = list(range(30, 50))  # 20 tokens = 5 full blocks
    a = await collect(eng, greedy_req(prompt, 5, "a"))
    hit0 = eng.metrics["cache_hit_tokens"]
    b = await collect(eng, greedy_req(prompt, 5, "b"))
    assert eng.metrics["cache_hit_tokens"] > hit0  # reused prefix blocks
    assert a == b  # identical output despite skipped prefill
    await eng.close()


async def test_batching_invariance():
    """Concurrent requests must produce the same greedy outputs as solo runs."""
    eng = engine()
    prompts = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8, 1, 8], [14, 14, 2]]
    solo = []
    for i, p in enumerate(prompts):
        solo.append(await collect(eng, greedy_req(p, 4, f"solo{i}")))
        await eng.clear_kv_blocks()
    together = await asyncio.gather(*[
        collect(eng, greedy_req(p, 4, f"batch{i}"))
        for i, p in enumerate(prompts)
    ])
    assert list(together) == solo
    await eng.close()


async def test_tensor_parallel_invariance():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    prompt = list(range(60, 75))
    e1 = engine(tp=1)
    t1 = await collect(e1, greedy_req(prompt, 5, "tp1"))
    await e1.close()
    e2 = engine(tp=2)
    t2 = await collect(e2, greedy_req(prompt, 5, "tp2"))
    await e2.close()
    assert t1 == t2


async def test_long_prompt_chunked_prefill():
    eng = engine(max_blocks_per_seq=64, num_blocks=256,
                 prefill_buckets=(8, 16))  # force chunking: prompt 40 > 16
    prompt = list(range(1, 41))
    toks = await collect(eng, greedy_req(prompt, 3, "long"))
    assert len(toks) == 3
    seq = list(prompt)
    for t in toks:
        logits = dense_reference_logits(eng.params, FP32, seq)
        assert int(jnp.argmax(logits[-1])) == t
        seq.append(t)
    await eng.close()


async def test_sampled_generation_deterministic_by_seed():
    eng = engine()
    def sreq(rid, seed):
        return PreprocessedRequest(
            token_ids=[4, 8, 15, 16, 23, 42], request_id=rid,
            sampling=SamplingOptions(temperature=0.8, top_k=20, seed=seed),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
    a = await collect(eng, sreq("s1", 123))
    b = await collect(eng, sreq("s2", 123))
    c = await collect(eng, sreq("s3", 999))
    assert a == b
    assert a != c
    await eng.close()


async def test_cancellation_frees_blocks():
    from dynamo_tpu.runtime import CancellationToken

    eng = engine()
    token = CancellationToken()
    req = greedy_req(list(range(12)), 10_000, "cancelme")
    got = []

    async def consume():
        async for out in eng.generate(req, token=token):
            got.append(out)

    task = asyncio.create_task(consume())
    await asyncio.sleep(0.5)
    token.stop()
    await asyncio.wait_for(task, timeout=10)
    assert got[-1].finish_reason == "cancelled"
    # teardown happens on the next scheduler step, which may be stuck behind
    # a multi-second XLA compile on CPU — wait generously
    for _ in range(600):
        if all(s is None for s in eng._slots) and not eng.waiting:
            break
        await asyncio.sleep(0.05)
    assert all(s is None for s in eng._slots)
    assert not eng.waiting
    await eng.close()


async def test_kv_events_emitted():
    events = []

    async def sink(stored, removed):
        events.append((list(stored), list(removed)))

    cfg = EngineConfig(model_config=FP32, block_size=4, num_blocks=16,
                       max_blocks_per_seq=8, max_num_seqs=2,
                       prefill_buckets=(8, 16, 32), seed=7)
    eng = JaxEngine(cfg, kv_event_sink=sink)
    await collect(eng, greedy_req(list(range(12)), 6, "ev1"))
    await asyncio.sleep(0.05)
    stored = [h for st, _ in events for h in st]
    # 12-token prompt = 3 full blocks; some decode blocks may complete too
    assert len(stored) >= 3
    await eng.close()


async def test_trailing_block_not_registered_before_kv_materialized():
    """A request finishing exactly at a block boundary must NOT register the
    trailing block: the final sampled token's K/V is only written on the next
    decode step, which never runs.  Registering it would let a later prompt
    prefix-match a block whose last position holds zeros (ADVICE r1, high)."""
    events = []

    def sink(stored, removed):
        events.append((list(stored), list(removed)))

    cfg = EngineConfig(model_config=FP32, block_size=4, num_blocks=16,
                       max_blocks_per_seq=8, max_num_seqs=2,
                       prefill_buckets=(8, 16, 32), seed=7)
    eng = JaxEngine(cfg, kv_event_sink=sink)
    # 7-token prompt + 1 generated = 8 tokens = 2 exact blocks.  Block 0 is
    # fully materialized by prefill; block 1 is completed by the sampled
    # token whose K/V never lands in the cache.
    await collect(eng, greedy_req(list(range(1, 8)), 1, "bd1"))
    await asyncio.sleep(0.05)
    stored = [h for st, _ in events for h in st]
    assert len(stored) == 1, f"trailing block leaked into the cache: {stored}"
    await eng.close()


async def test_chunked_prefill_interleaves_with_decode():
    """A long multi-chunk prefill must not stall active decodes: with
    prefill buckets capped at 8 tokens, a 64-token prompt takes 8 chunks,
    and the already-decoding request should keep producing tokens between
    chunks (one per scheduler step) instead of stalling for the whole
    prefill (round-1 verdict weak #4)."""
    cfg = EngineConfig(model_config=FP32, block_size=4, num_blocks=128,
                       max_blocks_per_seq=32, max_num_seqs=2,
                       prefill_buckets=(8,), max_batch_tokens=8, seed=7)
    eng = JaxEngine(cfg)

    progress = []  # (who, engine prefill_tokens so far) per token

    async def run(req, tag):
        async for out in eng.generate(req):
            for _ in out.token_ids:
                progress.append((tag, eng.metrics["prefill_tokens"]))

    short = greedy_req(list(range(1, 9)), 40, "short")
    t_short = asyncio.create_task(run(short, "short"))
    # let the short request admit and start decoding
    for _ in range(600):
        if any(p[0] == "short" for p in progress):
            break
        await asyncio.sleep(0.05)
    long = greedy_req(list(range(1, 65)), 2, "long")
    t_long = asyncio.create_task(run(long, "long"))
    await asyncio.wait_for(asyncio.gather(t_short, t_long), 120)

    # tokens the short request produced while the long prefill was mid-way
    # (prefill counter strictly between its start and end values)
    pf_end = eng.metrics["prefill_tokens"]
    mid = [p for p in progress
           if p[0] == "short" and 8 < p[1] < pf_end]
    assert len(mid) >= 4, (
        f"decode stalled during chunked prefill: {progress}"
    )
    await eng.close()


async def test_sync_sink_removed_published_before_stored():
    """One allocator mutation can evict hash H and re-register it; the wire
    must carry removed before stored so routers don't drop live blocks."""
    from dynamo_tpu.router.events import KvEventPublisher

    published = []

    class FakePlane:
        async def publish(self, subject, payload):
            published.append(payload)

    class FakeRuntime:
        event_plane = FakePlane()

    pub = KvEventPublisher(FakeRuntime(), "ns", "comp", worker_id=1)
    pub.enqueue_batch(stored=[1 << 100], removed=[2 << 100])
    pub.enqueue_batch(stored=[3 << 100])
    await pub._flush()
    assert [p["op"] for p in published] == ["removed", "stored", "stored"]
    assert [p["event_id"] for p in published] == [0, 1, 2]


async def test_fused_decode_matches_single_step():
    """decode_fused_steps must not change outputs: greedy and sampled
    streams are token-identical to the single-step path (same seed
    folding), including mid-burst EOS/length finishes."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    cfg32 = LlamaConfig(name="tiny32", vocab_size=256, d_model=64,
                        n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                        ffn_dim=128, dtype=jnp.float32)
    base = dict(model_config=cfg32, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, max_num_seqs=2,
                prefill_buckets=(8, 16), seed=11)

    async def run(fused, rid, temperature, n):
        eng = JaxEngine(EngineConfig(decode_fused_steps=fused, **base))
        req = PreprocessedRequest(
            token_ids=list(range(7, 20)), request_id=rid,
            sampling=SamplingOptions(temperature=temperature, seed=123),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
        )
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        await eng.close()
        return toks

    # greedy, n not a multiple of the burst (mid-burst length finish)
    single = await run(1, "s", 0.0, 11)
    fused = await run(8, "f", 0.0, 11)
    assert fused == single and len(fused) == 11

    # sampled: per-token rng streams must line up across burst boundaries
    single = await run(1, "s2", 0.9, 10)
    fused = await run(4, "f2", 0.9, 10)
    assert fused == single


def test_prefill_batched_matches_sequential():
    """prefill_batched (multi-sequence, one program) must write the same KV
    and produce the same last-token logits as per-sequence prefill calls."""
    from dynamo_tpu.models.llama import prefill, prefill_batched

    cfg = FP32
    params = init_params(cfg, jax.random.PRNGKey(1))
    bs, nb, mb = 4, 64, 8
    shape = (cfg.n_layers, cfg.n_kv_heads, nb, cfg.head_dim, bs)
    kv_a = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    kv_b = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))

    rng = np.random.default_rng(3)
    T = 16
    lens = [16, 11, 7]  # full, partial, short
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    # disjoint block tables (ids >= 1)
    tables = np.zeros((3, mb), np.int32)
    for i, n in enumerate(lens):
        used = -(-n // bs)
        tables[i, :used] = 1 + i * mb + np.arange(used)

    # sequential oracle
    seq_logits = []
    for i, p in enumerate(prompts):
        toks = np.zeros(T, np.int32)
        toks[: lens[i]] = p
        lg, kv_a = prefill(
            params, cfg, kv_a, jnp.asarray(toks),
            jnp.arange(T, dtype=jnp.int32), jnp.asarray(tables[i]),
            jnp.int32(0), jnp.int32(lens[i]),
        )
        seq_logits.append(np.asarray(lg))

    # batched (pad to Bp=4 with an empty row)
    btoks = np.zeros((4, T), np.int32)
    for i, p in enumerate(prompts):
        btoks[i, : lens[i]] = p
    btables = np.zeros((4, mb), np.int32)
    btables[:3] = tables
    blogits, kv_b = prefill_batched(
        params, cfg, kv_b, jnp.asarray(btoks),
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (4, T)),
        jnp.asarray(btables), jnp.zeros(4, jnp.int32),
        jnp.asarray(np.array(lens + [0], np.int32)),
    )
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(blogits[i]), seq_logits[i], rtol=2e-5, atol=2e-5
        )
    # caches identical on every block the sequences own (block 0 is
    # garbage); tolerance covers batched-vs-single matmul reassociation
    np.testing.assert_allclose(
        np.asarray(kv_b[0][:, :, 1:]), np.asarray(kv_a[0][:, :, 1:]),
        rtol=1e-3, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(kv_b[1][:, :, 1:]), np.asarray(kv_a[1][:, :, 1:]),
        rtol=1e-3, atol=1e-5,
    )


def test_prefill_packed_matches_sequential():
    """prefill_packed (one padding-free stream with segment ids) must
    write the same KV and produce the same last-token logits as
    per-sequence prefill calls — including a prefix-cache-hit TAIL
    (packing starts at ctx > 0) and a chunk boundary (one prompt split
    across two packed dispatches)."""
    from dynamo_tpu.models.llama import prefill, prefill_packed

    cfg = FP32
    params = init_params(cfg, jax.random.PRNGKey(1))
    bs, nb, mb = 4, 64, 8
    shape = (cfg.n_layers, cfg.n_kv_heads, nb, cfg.head_dim, bs)
    kv_a = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    kv_b = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))

    rng = np.random.default_rng(3)
    lens = [16, 11, 7]
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    tables = np.zeros((3, mb), np.int32)
    for i, n in enumerate(lens):
        used = -(-n // bs)
        tables[i, :used] = 1 + i * mb + np.arange(used)

    # sequential oracle (whole prompts, one per call)
    T = 16
    seq_logits = []
    for i, p in enumerate(prompts):
        toks = np.zeros(T, np.int32)
        toks[: lens[i]] = p
        lg, kv_a = prefill(
            params, cfg, kv_a, jnp.asarray(toks),
            jnp.arange(T, dtype=jnp.int32), jnp.asarray(tables[i]),
            jnp.int32(0), jnp.int32(lens[i]),
        )
        seq_logits.append(np.asarray(lg))

    def packed_call(kv, parts, S=4, Tp=32):
        """parts: [(seg_row_tokens, start_pos, table_row), ...]"""
        toks = np.zeros(Tp, np.int32)
        pos = np.zeros(Tp, np.int32)
        seg = np.zeros(Tp, np.int32)
        val = np.zeros(Tp, bool)
        btables = np.zeros((S, mb), np.int32)
        last = np.zeros(S, np.int32)
        off = 0
        for i, (chunk, start, table) in enumerate(parts):
            n = len(chunk)
            toks[off:off + n] = chunk
            pos[off:off + n] = start + np.arange(n)
            seg[off:off + n] = i
            val[off:off + n] = True
            btables[i] = table
            last[i] = off + n - 1
            off += n
        return prefill_packed(
            params, cfg, kv, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(seg), jnp.asarray(btables), jnp.asarray(last),
            jnp.asarray(val),
        )

    # dispatch 1: prompt 0's FIRST chunk (10 tokens) + prompt 2 whole
    lg1, kv_b = packed_call(kv_b, [
        (prompts[0][:10], 0, tables[0]),
        (prompts[2], 0, tables[2]),
    ])
    np.testing.assert_allclose(np.asarray(lg1[1]), seq_logits[2],
                               rtol=2e-5, atol=2e-5)
    # dispatch 2: prompt 0's TAIL (chunk boundary: starts at ctx=10, the
    # prefix-hit shape) + prompt 1 whole
    lg2, kv_b = packed_call(kv_b, [
        (prompts[0][10:], 10, tables[0]),
        (prompts[1], 0, tables[1]),
    ])
    np.testing.assert_allclose(np.asarray(lg2[0]), seq_logits[0],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg2[1]), seq_logits[1],
                               rtol=2e-5, atol=2e-5)
    # caches identical on every owned block (block 0 is garbage);
    # tolerance covers packed-vs-single matmul reassociation
    for ca, cb in zip(kv_a, kv_b):
        np.testing.assert_allclose(
            np.asarray(cb[:, :, 1:]), np.asarray(ca[:, :, 1:]),
            rtol=1e-3, atol=1e-5,
        )


async def test_packed_prefill_engine_matches_legacy():
    """The packed chunked-prefill scheduler (the default) must produce
    the same greedy tokens as the legacy padded paths for concurrent
    arrivals, multi-chunk prompts, and a prefix-cache-hit second round —
    and its FPM records must carry the prefill-phase fields the SLA
    planner consumes."""
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(1, 200, n)))
               for n in (12, 7, 19, 26)]

    async def run(packed):
        eng = engine(max_num_seqs=4, prefill_packed=packed,
                     max_batch_tokens=32, max_prefill_seqs=4)
        outs = await asyncio.gather(*[
            collect(eng, greedy_req(p, 4, f"pk{packed}-{i}"))
            for i, p in enumerate(prompts)
        ])
        # prefix-cache hit: the same prompt again packs only its TAIL
        again = await collect(eng, greedy_req(prompts[0], 4,
                                              f"pk{packed}-again"))
        hits = eng.metrics["cache_hit_tokens"]
        recs = [r for r in eng.fpm if r.get("kind") == "prefill"]
        await eng.close()
        return list(outs), again, hits, recs

    p_outs, p_again, p_hits, p_recs = await run(True)
    l_outs, l_again, l_hits, _ = await run(False)
    assert p_outs == l_outs
    assert p_again == l_again
    assert p_hits > 0 and p_hits == l_hits
    assert any(r.get("packed") for r in p_recs), \
        "packed path never engaged"
    for r in p_recs:
        assert {"gap_s", "tokens", "queue_depth"} <= set(r)


async def test_concurrent_prefill_batched_and_correct():
    """Concurrent arrivals must prefill together (round-2 verdict weak #3:
    one B=1 chunk per step serializes TTFT under queue depth) and produce
    the same tokens as each prompt served alone."""
    rng = np.random.default_rng(9)
    prompts = [list(map(int, rng.integers(1, 200, 12))) for _ in range(4)]

    # oracle: each prompt alone
    alone = []
    for i, p in enumerate(prompts):
        eng = engine(decode_fused_steps=1)
        alone.append(await collect(eng, greedy_req(p, 4, f"alone-{i}")))
        await eng.close()

    eng = engine(decode_fused_steps=1, max_batch_tokens=64,
                 max_prefill_seqs=4)
    outs = await asyncio.gather(*[
        collect(eng, greedy_req(p, 4, f"conc-{i}"))
        for i, p in enumerate(prompts)
    ])
    steps = eng.metrics["prefill_steps"]
    await eng.close()
    assert outs == alone
    # 4×12 prompt tokens fit one 64-token budget: batched prefill must not
    # take one step per sequence (allow slack for admission raciness)
    assert steps < 4, f"prefill serialized: {steps} steps for 4 arrivals"


async def test_continuation_bursts_engage_and_match_full_dispatch():
    """Steady-state decode takes the device-resident continuation path
    (zero per-burst uploads); its token streams must be identical to the
    always-full-dispatch path for greedy AND sampled requests, and the
    path must disengage cleanly around membership changes (a second
    request arriving mid-decode)."""

    async def run(force_full, rid_tag):
        # block_size > k * a few bursts, so tables don't grow every burst
        # (growth forces a full dispatch by design)
        eng = engine(decode_fused_steps=4, max_num_seqs=2, block_size=16,
                     prefill_buckets=(16, 32))
        if force_full:
            eng._is_continuation = lambda a, active, k: False
        r1 = PreprocessedRequest(
            token_ids=list(range(7, 20)), request_id=f"c1-{rid_tag}",
            sampling=SamplingOptions(temperature=0.9, seed=5),
            stop=StopConditions(max_tokens=24, ignore_eos=True),
        )
        r2 = greedy_req(list(range(40, 49)), 16, f"c2-{rid_tag}")

        async def delayed():
            await asyncio.sleep(0.25)  # arrive mid-decode of r1
            return await collect(eng, r2)

        t2 = asyncio.create_task(delayed())
        toks1 = await collect(eng, r1)
        toks2 = await t2
        bursts = eng.metrics.get("cont_bursts", 0)
        await eng.close()
        return toks1, toks2, bursts

    full1, full2, b_full = await run(True, "full")
    cont1, cont2, b_cont = await run(False, "cont")
    assert b_full == 0
    assert b_cont >= 2, "continuation path never engaged"
    assert cont1 == full1, "sampled stream diverged on continuation path"
    assert cont2 == full2, "greedy stream diverged on continuation path"


async def test_ring_attention_prefill_long_prompt_matches_chunked():
    """Long-context path: a prompt beyond the largest prefill bucket on
    an sp=2 mesh takes ONE sequence-parallel ring-attention program and
    must produce the same greedy continuation as the chunked path on an
    sp=1 engine (exactness of ops/ring_attention.py composed with the
    paged cache + sampler)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    base = dict(model_config=FP32, block_size=4, num_blocks=128,
                max_blocks_per_seq=32, max_num_seqs=2,
                prefill_buckets=(8, 16), seed=7)
    prompt = list(range(1, 41))  # 40 tokens > largest bucket (16)

    chunked = JaxEngine(EngineConfig(**base))
    expect = await collect(chunked, greedy_req(prompt, 5, "chunked"))
    await chunked.close()

    eng = JaxEngine(EngineConfig(sp=2, **base))
    toks = await collect(eng, greedy_req(prompt, 5, "ring"))
    assert eng.metrics.get("ring_prefills", 0) == 1, \
        "long prompt did not take the ring-attention path"
    assert toks == expect, "ring prefill continuation diverged"

    # short prompts stay on the (cheaper) chunked path
    toks2 = await collect(eng, greedy_req(list(range(50, 60)), 3, "short"))
    assert eng.metrics.get("ring_prefills", 0) == 1
    assert len(toks2) == 3
    await eng.close()
