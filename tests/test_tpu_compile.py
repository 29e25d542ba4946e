"""Compile the main path's kernels for a TPU v5e that is described, not
attached — the only test file that describes the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
topology description (`v5e:2x2`); what it refuses here it refuses on the
chip (block shapes Mosaic cannot tile, VMEM over the limit, programs over
HBM).  Nothing runs: a compile that passes is not a chip run —
`chip_smoke.py` is.

The topology is described inside a module-scoped fixture and nowhere at
import time (xdist workers all import this file), and every
shape/sharding is built inside fixtures or tests.  libtpu's lockfile lets
one process load it at a time — it guards a chip, and describing a
topology takes none — so the fixture lifts it for this process; without
that, cases spread over several xdist workers would fail on the lock.
The fixture skips only where no TPU compiler is installed; any other
failure to describe the topology is an error, never a silent skip.
"""

import dataclasses
import importlib.util
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models import llama

BS = 128  # lane-aligned serving block size


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-topology executable can be written to JAX's persistent
    cache but not read back without a chip (it warns and recompiles):
    keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


# (nkv, n_heads, head_dim): llama-3b and llama-8b on a single device, and
# llama-8b's per-shard shape under tp=4 (the kernels run per shard under
# shard_map there)
WIDTHS = {
    "llama-3b": (8, 24, 128),
    "llama-8b": (8, 32, 128),
    "llama-8b/tp4": (2, 8, 128),
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["decode", "packed"])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, widths, quant):
    """Both Pallas kernels, compiled (never interpret), at serving
    widths: block 128, contexts to 2048, B=8 decode rows / T=2048 packed
    tokens in 4 segments."""
    from dynamo_tpu.ops.pallas_packed_prefill import (
        packed_prefill_attention_pallas,
    )
    from dynamo_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_pallas,
    )

    nkv, nh, hd = WIDTHS[widths]
    S = _sds(one_chip)
    L, NB, MB, B, T, SEGS = 2, 64, 16, 8, 2048, 4
    cache = S((L, nkv, NB, hd, BS), jnp.int8 if quant else jnp.bfloat16)
    scale = S((L, nkv, NB, BS), jnp.float32)
    kw = dict(k_scale=scale, v_scale=scale) if quant else {}
    if kernel == "decode":
        fn = partial(paged_attention_decode_pallas, layer=1)
        lowered = jax.jit(fn).lower(
            S((B, nh, hd), jnp.bfloat16), cache, cache,
            block_tables=S((B, MB), jnp.int32),
            kv_lens=S((B,), jnp.int32), **kw)
    else:
        fn = partial(packed_prefill_attention_pallas, layer=1)
        lowered = jax.jit(fn).lower(
            S((T, nh, hd), jnp.bfloat16), cache, cache,
            block_tables=S((SEGS, MB), jnp.int32),
            seg_ids=S((T,), jnp.int32), positions=S((T,), jnp.int32),
            valid=S((T,), jnp.bool_), **kw)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_multi_step_compiles_for_v5e(one_chip):
    """One fused decode burst of the engine's own program
    (JaxEngine._decode_multi_impl) at llama-3b widths, 2 layers, with
    the Pallas decode kernel inside it."""
    from dynamo_tpu.engine.core import JaxEngine

    cfg = dataclasses.replace(llama.PRESETS["llama-3b"], n_layers=2,
                              attn_impl="pallas")
    S = _sds(one_chip)
    NB, MB, B, K = 64, 16, 8, 8
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S((cfg.n_layers, cfg.n_kv_heads, NB, cfg.head_dim, BS),
                 cfg.dtype) for _ in range(2))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, llama, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    compiled = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _assert_pool_stays_where_it_lies(hlo, L, NKV, NB, HD):
    """What a compiled program may do to a [L, NKV, NB, HD, BS] bf16
    K/V pool: take it, write blocks of it in place, hand it on.  No
    `copy` of it, no layer's slice of it, one layout from entry to exit,
    and a fusion that gives out a pool-shaped value gives out the
    in-place write (a loop fusion rooted in anything else, a bitcast
    of its parameter say, is a copy of the whole pool by another name)."""
    import re

    pool = rf"bf16\[{L},{NKV},{NB},{HD},{BS}\]"
    layer = rf"bf16\[(?:1,)?{NKV},{NB},{HD},{BS}\]"
    made = re.findall(rf"= ({pool}|{layer})\S* (\w[\w-]*)\(", hlo)
    # what may produce a pool-shaped value: the in-place write and the
    # plumbing around it; nothing may produce one layer's slice
    assert {op for _, op in made} <= {
        "parameter", "get-tuple-element", "dynamic-update-slice",
        "fusion", "while", "bitcast"}, sorted(set(made))
    assert not [s for s, _ in made if not re.fullmatch(pool, s)]
    # the pool keeps one layout from entry to exit
    layouts = set(re.findall(rf"{pool}(\{{[\d,]+)", hlo))
    assert layouts == {"{4,3,2,1,0"}, layouts
    # computation name -> {instruction name: (result type, op)}, its root
    comps, roots = {}, {}
    for head, body in re.findall(
            r"^(?:ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", hlo, re.M | re.S):
        comps[head] = {
            name: (typ, op, rest) for root, name, typ, op, rest in
            re.findall(r"^ +(ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$", body,
                       re.M)}
        roots[head] = re.search(r"^ +ROOT %(\S+) =", body, re.M).group(1)
    fusions = [(typ, re.search(r"calls=%([^\s,]+)", rest).group(1))
               for comp in comps.values()
               for typ, op, rest in comp.values()
               if op == "fusion" and re.search(pool, typ)]
    assert fusions                     # the write is there, and fused
    for _, called in fusions:
        comp = comps[called]
        typ, op, rest = comp[roots[called]]
        outs = (re.findall(r"%([^\s,)]+)", rest.split(")")[0])
                if op == "tuple" else [roots[called]])
        for out in outs:
            typ, op, _ = comp[out]
            if re.search(pool, typ):
                assert op == "dynamic-update-slice", (called, out, op)


def test_decode_multi_reads_the_pool_where_it_lies(topo, one_chip):
    """`decode_multi` at Mistral-7B widths, the `mistral-7b.chat` worker's
    16 lanes x 20 blocks over a 320-block pool (4 layers), with `auto`
    resolved as on the chip: the pool goes into the kernel whole and in
    its resident layout.  Three ways to lose that, each worth as much as
    the step's weights: a relayout `copy` of the pool (XLA's layout for
    a column scatter differs from the kernel's), a per-layer slice of
    it materialized for the custom call, a copy on the way in or out."""
    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    L, NKV, NB, HD, B, MB, K = 4, 8, 320, 128, 16, 20, 8
    impl = resolve_decode_impl("auto", topo.devices[0].platform, BS, HD,
                               jnp.bfloat16)
    assert impl == "pallas"
    cfg = llama.LlamaConfig(
        name="mistral-7b-widths", vocab_size=32768, d_model=4096,
        n_layers=L, n_heads=32, n_kv_heads=NKV, head_dim=HD,
        ffn_dim=14336, rope_theta=1e6, attn_impl=impl)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S((L, NKV, NB, HD, BS), cfg.dtype) for _ in range(2))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, llama, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    hlo = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == L
    _assert_pool_stays_where_it_lies(hlo, L, NKV, NB, HD)


@pytest.mark.parametrize("T,MB", [(2048, 48), (2048, 16), (512, 4)])
def test_prefill_packed_reads_the_pool_where_it_lies(one_chip, T, MB):
    """`prefill_packed` at Mistral-7B widths (4 layers, 320 blocks, one
    segment row), the twin of the decode test above, at the doc cell's
    later chunks, its first chunk and a chat prompt (one flash step, no
    `while`): the pool comes in, is written and is read in its resident
    layout.  The flat column scatter cost 2 + 2 x layers relayout copies
    of the whole pool a program, a {3,1,4,2,0} twin of it among the
    temporaries, and one layer's slice of it materialized in every
    flash step (10 / 10 / 4 copies, 1.81 GB at the first shape: PR 30)."""
    from dynamo_tpu.engine.core import JaxEngine

    L, NKV, HD, SEGS = 4, 8, 128, 1
    cfg = llama.LlamaConfig(
        name="mistral-7b-widths", vocab_size=32768, d_model=4096,
        n_layers=L, n_heads=32, n_kv_heads=NKV, head_dim=HD,
        ffn_dim=14336, rope_theta=1e6)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    fn = jax.jit(
        partial(JaxEngine._prefill_packed_impl, llama, cfg, None),
        donate_argnums=(1,))

    def compiled(NB):
        kv = tuple(S((L, NKV, NB, HD, BS), cfg.dtype) for _ in range(2))
        return fn.lower(
            params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
            S((SEGS, MB), i32), S((SEGS,), i32), S((T,), b1),
            S((SEGS,), i32), S((SEGS,), f32), S((SEGS,), i32),
            S((SEGS,), f32)).compile()

    NB = 320
    program = compiled(NB)
    _assert_pool_stays_where_it_lies(program.as_text(), L, NKV, NB, HD)
    # no twin of the pool among the temporaries: they do not grow with
    # it.  The write that would make one is the same at every shape and
    # the asserts above rule it out at each, so one shape pays for the
    # second compile: the doc cell's later chunks, the largest
    if (T, MB) == (2048, 48):
        temp = program.memory_analysis().temp_size_in_bytes
        grown = compiled(480).memory_analysis().temp_size_in_bytes
        assert abs(grown - temp) < 50e6, (temp, grown)


@pytest.mark.parametrize("MB", [16, 32, 50])
def test_prefill_packed_attends_in_the_kernel(topo, one_chip, MB):
    """`prefill_packed` at Mistral-7B widths (4 layers, 320 blocks, one
    segment row, 2048 tokens) over the doc cell's three table widths,
    with `auto` resolved as on the chip: the attention is the Pallas
    kernel, once a layer; no value of the scan's score block's size is
    left (`f32[2048,32,1024]`, 268 MB, out to HBM and back every step
    until PR 34, in whatever layout); the pool is still read where it
    lies; and the temporaries are under what the scan's program held
    (0.44 GB)."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl

    L, NKV, NB, HD, SEGS, T = 4, 8, 320, 128, 1, 2048
    impl = resolve_packed_impl("auto", topo.devices[0].platform, BS, HD,
                               jnp.bfloat16, T, 4)
    assert impl == "pallas"
    cfg = llama.LlamaConfig(
        name="mistral-7b-widths", vocab_size=32768, d_model=4096,
        n_layers=L, n_heads=32, n_kv_heads=NKV, head_dim=HD,
        ffn_dim=14336, rope_theta=1e6, packed_attn_impl=impl)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S((L, NKV, NB, HD, BS), cfg.dtype) for _ in range(2))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    program = jax.jit(
        partial(JaxEngine._prefill_packed_impl, llama, cfg, None),
        donate_argnums=(1,)).lower(
        params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
        S((SEGS, MB), i32), S((SEGS,), i32), S((T,), b1),
        S((SEGS,), i32), S((SEGS,), f32), S((SEGS,), i32),
        S((SEGS,), f32)).compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == L
    _assert_pool_stays_where_it_lies(hlo, L, NKV, NB, HD)
    # nothing the size of a score block: the largest float32 values left
    # are the MLP's [2048, 14336] and the head's weights
    score = T * 32 * 1024
    sized = {m for m in re.findall(r"f32\[([\d,]+)\]", hlo)
             if len(m.split(",")) >= 3
             and math.prod(int(d) for d in m.split(",")) >= score}
    assert not sized, sized
    assert program.memory_analysis().temp_size_in_bytes < 0.44e9


def _assert_experts_walk_the_visited_list(hlo, held, B, d, f):
    """What a compiled decode burst may do with an expert layer
    (models/llama.py `moe_dispatch_visited`): no value of the dense
    form's intermediate ([held, lanes, f]: every lane through every held
    expert), and the stacks are the kernel's operands where they lie:
    never copied, relaid, transposed, sliced or gathered (any of which
    would read a whole stack a step, what the form is there to avoid).
    (A custom call may give one out: `ConcatBitcast`, the compiler's
    prefetch of a stack that fits, Keye's 25 MB, into VMEM ahead of the
    burst's loop, once for all its steps.)"""
    import re

    assert f"bf16[{held},{B},{f}]" not in hlo \
        and f"bf16[{held},{f},{B}]" not in hlo
    for shape in (rf"bf16\[{held},{d},{f}\]", rf"bf16\[{held},{f},{d}\]"):
        made = re.findall(rf"= {shape}\S* ([\w-]+)\(", hlo)
        # (a bitcast moves nothing: a stack that lies with the model
        # width minor, Nemotron's, handed over transposed)
        assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                             "custom-call"}, (shape, sorted(set(made)))
        for call in re.findall(rf"= {shape}\S* custom-call\(.*", hlo):
            assert 'custom_call_target="ConcatBitcast"' in call, call[:300]


def test_hybrid_decode_and_prefill_compile_for_v5e(one_chip):
    """The window + global family (models/mimo.py) at MiMo-V2-Flash's
    widths, cut to one global layer (dense MLP) and one window layer
    (16 of 256 experts held): a fused decode burst of the engine's own
    program, and a 512-token prefill chunk.  The global layer reads
    through the Pallas decode kernel with K 192 and V 128 wide (one
    custom call), its pools keep their resident layout, the expert
    layer is one more custom call over the stacks where they lie (PR 44:
    `_assert_experts_walk_the_visited_list`), and the counters ride
    under the burst's tokens."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import mimo

    cfg = dataclasses.replace(
        mimo.PRESETS["mimo-v2-flash"], n_layers=2, layer_kinds=(0, 1),
        moe_layers=(0, 1), experts_held=(0, 16), vocab_size=8192,
        attn_impl="pallas")
    S = _sds(one_chip)
    B, MB, NB, K, T = 8, 6, 49, 4, 512
    shapes = jax.eval_shape(
        lambda: mimo.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        mimo.kv_cache_shapes(cfg, NB, BS, lanes=B),
        mimo.kv_cache_dtypes(cfg)))
    assert kv[2].shape == (1, 8, 1 + 2 * B, 192, BS)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, mimo, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(mimo.KV_COUNTERS), B)
    hlo = lowered.compile().as_text()
    # the global layer's decode kernel and the expert layer's: a decode
    # step walks the experts its lanes visited, one custom call a layer
    assert hlo.count("tpu_custom_call") == 1 + 1
    layouts = set(re.findall(rf"bf16\[1,4,{NB},(?:192|128),{BS}\]"
                             r"(\{[\d,]+)", hlo))
    assert layouts == {"{4,3,2,1,0"}, layouts
    _assert_experts_walk_the_visited_list(hlo, 16, B, 4096, 2048)
    pre = jax.jit(partial(JaxEngine._prefill_impl, mimo, cfg),
                  donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32), None,
        None, S((), i32)).compile()
    mem = program.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # a prompt-sized chunk groups its picks: three grouped matmuls at
    # MiMo's widths, no token met every held expert
    assert program.as_text().count("tpu_custom_call") == 3
    assert f"bf16[16,{T},2048]" not in program.as_text()


def test_moonlight_prefill_groups_its_picks_and_decode_does_not(one_chip):
    """Moonlight's widths (benchmark/configs), cut to the dense layer and
    one routed layer.  The 1024-token prefill program (at 2048 tokens
    the dense dispatch's intermediate has the shape of the stacks
    themselves, [64, 2048, 1408], and cannot be told from them) holds no
    value of the dense dispatch's shape ([64, 1024, 1408]: every token
    through every expert) and three grouped matmuls; the fused decode
    burst at its 16 lanes holds none of [64, 16, 1408] either and ONE
    kernel, the routed layer's walk over its visited experts (PR 44; the
    config's `auto` decode read resolves on this CPU host to jnp; the
    latent kernel's program is
    test_latent_decode_reads_the_pool_where_it_lies): the shape picks
    the form."""
    import json
    from pathlib import Path

    from benchmark.reference.deepseek import program_config
    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import deepseek

    hf = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                     / "moonlight-16b-a3b-8l.json").read_text())
    cfg = dataclasses.replace(program_config(hf, "moonlight"), n_layers=2,
                              vocab_size=8192)
    S = _sds(one_chip)
    B, MB, NB, K, T = 16, 20, 64, 4, 1024
    shapes = jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    assert params["layers"][1]["moe_w_gate"].shape == (64, 2048, 1408)
    kv = tuple(S(s, cfg.dtype)
               for s in deepseek.kv_cache_shapes(cfg, NB, BS))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    prefill = jax.jit(partial(JaxEngine._prefill_impl, deepseek, cfg),
                      donate_argnums=(1,)).lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32)
    ).compile().as_text()
    assert prefill.count("tpu_custom_call") == 3
    assert f"[64,{T},1408]" not in prefill
    decode = jax.jit(
        partial(JaxEngine._decode_multi_impl, deepseek, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9)).lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32)).compile().as_text()
    assert decode.count("tpu_custom_call") == 1
    _assert_experts_walk_the_visited_list(decode, 64, B, 2048, 1408)


def test_sparse_decode_and_prefill_compile_for_v5e(one_chip):
    """The family whose attention chooses its keys (models/keye.py) at
    Keye-VL-2.0's widths, cut to two layers (16 of 128 experts held),
    with the long-context cell's cache (1593 blocks, 8 lanes x 199):
    a fused decode burst of the engine's own program finds each lane's
    top-k threshold in VMEM and reads K and V through the Pallas decode
    kernel under a per-token bias, and a 2048-token packed prefill chunk
    over the whole table width runs the threshold search, one flash
    pass under the mask and three grouped matmuls a layer.  In
    both, the three table-paged pools (K, V, index keys) keep their
    resident layout and are never copied, and the temporaries stay
    beside 8.9 GB of weights and cache at the cell's 12 layers (they do
    not grow with depth: 0.44 GB at 2 layers, off-chip compiles, PR
    33)."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import keye

    L, NB, B, MB, K, T = 2, 1593, 8, 199, 8, 2048
    cfg = dataclasses.replace(
        keye.PRESETS["keye-vl-2.0-30b-a3b"], n_layers=L,
        experts_held=(0, 16), attn_impl="pallas")
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: keye.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(keye.kv_cache_shapes(cfg, NB, BS),
                                       keye.kv_cache_dtypes(cfg)))
    assert kv[2].shape == (L, 1, NB, 64, BS)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    pools = (rf"bf16\[{L},4,{NB},128,{BS}\]", rf"bf16\[{L},1,{NB},64,{BS}\]")

    def pools_stay(hlo):
        for pool in pools:
            assert not re.findall(rf"= {pool}\S* copy\(", hlo)
            assert set(re.findall(rf"{pool}(\{{[\d,]+)", hlo)) \
                == {"{4,3,2,1,0"}

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, keye, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(keye.KV_COUNTERS), B)
    hlo = lowered.compile().as_text()
    # a layer: the top-k threshold search, the decode kernel and the
    # experts' walk over the visited list
    assert hlo.count("tpu_custom_call") == 3 * L
    pools_stay(hlo)
    _assert_experts_walk_the_visited_list(hlo, 16, B, 2048, 768)
    pre = jax.jit(partial(JaxEngine._prefill_packed_impl, keye, cfg, None),
                  donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
        S((1, MB), i32), S((1,), i32), S((T,), b1), S((1,), i32),
        S((1,), f32), S((1,), i32), S((1,), f32)).compile()
    hlo = program.as_text()
    # a layer: the threshold search, the flash pass under the mask and
    # three grouped matmuls
    assert hlo.count("tpu_custom_call") == 5 * L
    pools_stay(hlo)
    assert program.memory_analysis().temp_size_in_bytes < 1.0e9


def _assert_state_steps_in_place(hlo, shape, n_layers):
    """What a compiled decode burst may do to a lane-addressed float32
    state member of `shape` [layers, lanes, heads, dk, dv]: take it,
    hand it to ops/pallas_lane_state.py's kernel (one custom call a
    state layer, the member aliased in place) and hand it on.  Nothing
    but the program's plumbing and those calls gives out a value of the
    member's shape or of one layer's slice over all lanes: no copy, no
    `select` / `dynamic-update-slice` fusion over the whole member (the
    jnp step's `where` and `.at[pli].set`)."""
    import re

    dims = lambda d: ",".join(map(str, d))
    member = rf"f32\[{dims(shape)}\]"
    layer = rf"f32\[(?:1,)?{dims(shape[1:])}\]"
    made, calls = set(), []
    for typ, op, rest in re.findall(
            r"^ +(?:ROOT )?%\S+ = (.*?) ([\w-]+)\((.*)$", hlo, re.M):
        assert not re.search(layer, typ), (typ, op)
        if re.search(member, typ):
            made.add(op)
            if op == "custom-call":
                calls.append(rest)
    assert made <= {"parameter", "get-tuple-element", "while", "tuple",
                    "bitcast", "custom-call"}, sorted(made)
    assert len(calls) == n_layers
    for rest in calls:      # in place: the member operand is the output
        assert "tpu_custom_call" in rest
        assert "output_to_operand_aliasing" in rest
    assert not re.search(rf"{member}\{{[^}}]*S\(1\)", hlo)

def test_recurrent_decode_and_prefill_compile_for_v5e(one_chip):
    """The delta-rule linear-attention family (models/ling.py) at
    Ling-3.0-flash's widths, cut to one period (5 KDA layers and the MLA
    layer; both dense layers, 4 expert layers with 16 of 512 experts
    held), with the wide cell's cache (64 lanes, 2881 blocks, tables of
    45) and `auto` resolved as on the chip: a fused decode burst of the
    engine's own program and a 2048-token prefill chunk.  In both, the
    float32 state (5 x 64 lanes x 32 heads x 128 x 128: 671 MB here,
    1.34 GB at the cell's 12 layers) is updated where it lies: no copy
    of its shape nor of one layer's slice over the lanes, and the
    program's temporaries stay beside 7.25 GB of weights, state and
    cache at 12 layers (1.94 GB decode, 2.25 GB prefill there; off-chip
    compiles, PR 35).  The decode burst hands the member WHOLE to the
    state kernel, one custom call a KDA layer (PR 41:
    `_assert_state_steps_in_place`); the prefill chunk runs the chunked
    rule in its kernel, one custom call a KDA layer (PR 45)."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import ling
    from dynamo_tpu.ops.paged_attention import PALLAS_IMPLS

    L, NB, B, MB, K, T = 6, 2881, 64, 45, 8, 2048
    cfg = dataclasses.replace(ling.PRESETS["ling-3.0-flash"], n_layers=L,
                              experts_held=(0, 16), attn_impl="pallas")
    assert ling.state_impl(cfg, cfg.attn_impl) in PALLAS_IMPLS
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: ling.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        ling.kv_cache_shapes(cfg, NB, BS, lanes=B),
        ling.kv_cache_dtypes(cfg)))
    assert kv[2].shape == (5, B, 32, 128, 128) and kv[2].dtype == jnp.float32
    assert kv[3].shape == (5, B, 3, 12288)
    assert kv[0].shape == (1, 1, NB, 512, BS)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_

    def state_stays(hlo):
        # (a prefill row's own 2 MB working state may move; the pool and
        # a layer's slice over all lanes may not)
        for shape in (rf"f32\[5,{B},32,128,128\]",
                      rf"f32\[{B},32,128,128\]"):
            assert not re.findall(rf"= {shape}\S* copy\(", hlo), shape
        # nor seen a page a row (PR 56: no bitcast of the tiled pool)
        assert not re.findall(rf"bf16\[{2 * NB},2048\]", hlo)

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, ling, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(ling.KV_COUNTERS), B)
    program = lowered.compile()
    hlo = program.as_text()
    state_stays(hlo)
    _assert_state_steps_in_place(hlo, kv[2].shape, 5)
    _assert_experts_walk_the_visited_list(hlo, 16, B, 2560, 768)
    # the state's kernel a KDA layer, the latent's two an MLA layer,
    # the visited experts' walk an expert layer
    assert hlo.count("tpu_custom_call") == 5 + 2 * 1 + 4
    assert program.memory_analysis().temp_size_in_bytes < 0.3e9
    pre = jax.jit(partial(JaxEngine._prefill_impl, ling, cfg),
                  donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32), None,
        None, S((), i32)).compile()
    hlo = program.as_text()
    state_stays(hlo)
    # a prompt-sized chunk groups its picks: three grouped matmuls an
    # expert layer, no token met every held expert; and the chunked rule
    # is ONE custom call a KDA layer (PR 45: `chunk_impl` of the bucket,
    # ops/pallas_chunk_state.py), so nothing of the jnp form's
    # [N, H, n, C, dk] float32 intermediates (`k_seen`: 134 MB a layer)
    # nor its [N, H, C, C] matrices is left in the program
    assert ling.chunk_impl(cfg, cfg.attn_impl, T) in PALLAS_IMPLS
    # and since PR 49 the MLA layer's prefill read is ONE more
    # (`mla_prefill_impl` of the bucket;
    # test_latent_prefill_reads_the_pool_where_it_lies)
    assert hlo.count("tpu_custom_call") == 3 * 4 + 5 + 1
    for lead in ("", "1,"):
        assert f"f32[{lead}{T // 64},32,4,64,128]" not in hlo
        assert f"f32[{lead}{T // 64},32,64,64]" not in hlo
    assert f"bf16[16,{T},768]" not in hlo
    assert program.memory_analysis().temp_size_in_bytes < 3.0e9


def _latent_case(family):
    """-> (module, config with the decode read resolved as on the chip
    left at "auto", lanes, table width, pool blocks, burst steps, MLA
    layers): Moonlight's widths (benchmark/configs) cut to the dense
    layer and two routed layers at the chat cell's 16 lanes x 20 blocks
    over 512; Ling's cut to two periods (10 KDA layers, 2 MLA) at the
    wide cell's 64 lanes x 45 blocks over 2881."""
    import json
    from pathlib import Path

    if family == "ling":
        from dynamo_tpu.models import ling

        cfg = dataclasses.replace(ling.PRESETS["ling-3.0-flash"],
                                  n_layers=12, experts_held=(0, 16),
                                  vocab_size=8192)
        return ling, cfg, 64, 45, 2881, 8, 2
    from benchmark.reference.deepseek import program_config
    from dynamo_tpu.models import deepseek

    hf = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                     / "moonlight-16b-a3b-8l.json").read_text())
    cfg = dataclasses.replace(program_config(hf, "moonlight"), n_layers=3,
                              vocab_size=8192)
    return deepseek, cfg, 16, 20, 512, 4, 3


@pytest.mark.parametrize("family", ["moonlight", "ling"])
def test_latent_decode_reads_the_pool_where_it_lies(topo, one_chip, family):
    """`decode_multi` of the two latent-attention families at their
    cells' lanes, tables and pools, `auto` resolved as on the chip: two
    custom calls an MLA layer (the token's write, the read) from ONE
    lowering each (the layer index is traced), and both pools handed
    from call to call where they lie: nothing but the program's
    plumbing gives out a value of a pool's or a layer's shape (no copy,
    slice, gather, relayout: until PR 36 the read gathered lanes x table
    width = the whole pool a layer), in one layout, and never in VMEM
    (`S(1)`: left alone the compiler keeps the rope-key pool there for
    an XLA writer's sake and moves it out and back around every kernel
    call).  The twin of test_decode_multi_reads_the_pool_where_it_lies."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    mod, cfg, B, MB, NB, K, n_mla = _latent_case(family)
    R, dr = cfg.mla_plane_heights
    impl = resolve_decode_impl(cfg.attn_impl, topo.devices[0].platform, BS,
                               cfg.mla_plane_heights, cfg.dtype)
    assert impl == "pallas"
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    lanes = {"lanes": B} if getattr(mod, "KV_LANE_ADDRESSED", False) else {}
    kv_shapes = mod.kv_cache_shapes(cfg, NB, BS, **lanes)
    dtypes = (mod.kv_cache_dtypes(cfg) if lanes
              else (cfg.dtype,) * len(kv_shapes))
    kv = tuple(S(s, d) for s, d in zip(kv_shapes, dtypes))
    assert kv[0].shape == (n_mla, 1, NB, R, BS)
    assert kv[1].shape == (n_mla, 1, NB, dr, BS)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    lowered = jax.jit(
        partial(JaxEngine._decode_multi_impl, mod, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9)).lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    # (a family with a lane-addressed state adds its step's kernel: one
    # lowering, one call a state layer, test_recurrent_decode_...)
    # (and the walk over the visited experts: one lowering too, the
    # form is jitted on the config, and one call an expert layer; PR 44,
    # _assert_experts_walk_the_visited_list)
    n_state = kv[2].shape[0] if lanes else 0
    n_moe = sum("moe_w_up" in layer for layer in shapes["layers"])
    assert lowered.as_text().count("stablehlo.custom_call @tpu_custom_call") \
        == 2 + bool(n_state) + bool(n_moe)
    program = lowered.compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == 2 * n_mla + n_state + n_moe
    for hd in (R, dr):
        pool = rf"bf16\[{n_mla},1,{NB},{hd},{BS}\]"
        layer = rf"bf16\[(?:1,)?1,{NB},{hd},{BS}\]"
        made = re.findall(rf"= ({pool}|{layer})\S* (\w[\w-]*)\(", hlo)
        assert {op for _, op in made} <= {
            "parameter", "get-tuple-element", "while", "bitcast"}, \
            sorted(set(made))
        assert set(re.findall(rf"{pool}(\{{[\d,]+)", hlo)) == {"{4,3,2,1,0"}
        assert not re.search(rf"{pool}\{{[^}}]*S\(1\)", hlo)
        # nor the gather's shapes: every lane's table, or the pool less
        # the garbage block, block-major
        for blocks in (B * MB, NB - 1, NB):
            assert not re.findall(rf"bf16\[{blocks},{hd},{BS}\]", hlo)
    assert f"f32[{B},{cfg.n_heads},{MB * BS}]" not in hlo    # table-wide scores
    # the gathered context and its float32 casts were the burst's
    # temporaries: 0.79 GB (Moonlight, 8 layers), 1.94 GB (Ling)
    assert program.memory_analysis().temp_size_in_bytes < 0.3e9


def test_latent_decode_runs_per_head_shard_under_tp(topo):
    """Moonlight's `decode_multi` for four described chips, tp = 4, the
    parameters placed by the engine's rules: heads shard through
    w_uk / w_uv and the latent pools are replicated, so the read runs per
    head shard under `shard_map` (4 of 16 heads a chip) on the whole
    pool and every shard writes its own replica: two custom calls a
    layer, the pools handed on where they lie, and no collective gives
    out a pool, a layer of one, or the heads gathered for the kernel
    (what GSPMD does to a custom call it cannot partition)."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.parallel.mesh import param_sharding_rules

    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1), ("dp", "tp", "sp"))
    mod, cfg, B, MB, NB, K, n_mla = _latent_case("moonlight")
    cfg = dataclasses.replace(cfg, attn_impl="pallas", expert_shards=4)
    R, dr = cfg.mla_plane_heights
    rules = param_sharding_rules()

    def placed(path, x):
        name = next((k.key for k in reversed(path)
                     if isinstance(getattr(k, "key", None), str)), None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, rules.get(name, P())))

    params = jax.tree_util.tree_map_with_path(placed, jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.PRNGKey(0))))
    S = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P()))
    kv = tuple(S(s, cfg.dtype) for s in mod.kv_cache_shapes(cfg, NB, BS))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    hlo = jax.jit(
        partial(JaxEngine._decode_multi_impl, mod, cfg, mesh, True, K,
                False),
        donate_argnums=(1, 5, 7, 9)).lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2 * n_mla
    assert f"bf16[{B},{cfg.n_heads // 4},{R}]" in hlo     # a shard's queries
    made = re.findall(rf"= (\w+\[(?:{n_mla},)?1,{NB},(?:{R}|{dr}),{BS}\]|"
                      rf"\w+\[{B},{cfg.n_heads},(?:{R}|{dr})\])\S* "
                      rf"(\w[\w-]*)\(", hlo)
    assert {op for _, op in made} <= {
        "parameter", "get-tuple-element", "while", "bitcast"}, \
        sorted(set(made))


def _latent_prefill_lowered(mod, cfg, S, T, MB, NB, lanes):
    """The engine's own `prefill` program of a latent family, one row of
    T tokens, lowered for the shardings `S` makes."""
    from dynamo_tpu.engine.core import JaxEngine

    shapes = jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    lane_kw = {"lanes": lanes} if lanes else {}
    kv_shapes = mod.kv_cache_shapes(cfg, NB, BS, **lane_kw)
    dtypes = (mod.kv_cache_dtypes(cfg) if lanes
              else (cfg.dtype,) * len(kv_shapes))
    kv = tuple(S(s, d) for s, d in zip(kv_shapes, dtypes))
    i32, f32 = jnp.int32, jnp.float32
    lane = (None, None, S((), i32)) if lanes else ()
    return shapes, jax.jit(
        partial(JaxEngine._prefill_impl, mod, cfg), donate_argnums=(1,)
    ).lower(params, kv, S((T,), i32), S((T,), i32), S((MB,), i32),
            S((), i32), S((), i32), S((), i32), S((), f32), S((), i32),
            S((), f32), *lane)


@pytest.mark.parametrize("family", ["moonlight", "ling"])
def test_latent_prefill_reads_the_pool_where_it_lies(one_chip, family):
    """The 2048-token `prefill` program of the two latent-attention
    families at their cells' tables and pools, `auto` resolved as on the
    chip: ONE custom call more an MLA layer (the flash read over the
    pool's live blocks, PR 49: `mla_prefill_impl` of the bucket) from
    ONE lowering (the layer index is traced), the chunk written as
    whole planes, and both pools handed from write to read to write
    where they lie: no copy of a pool, no layer's slice, one layout, and
    the only fusion that gives out a pool gives out the in-place write
    (`_assert_pool_stays_where_it_lies`; beside the kernel the flat
    column scatter had XLA relay the whole pool every layer).  Nothing
    of the jnp form is left: no gathered table, no table-wide K, V or
    scores.  The 256-token program keeps the jnp read (under the
    rule's floor) and writes whole planes all the same."""
    import re

    from dynamo_tpu.models.deepseek import mla_prefill_impl
    from dynamo_tpu.ops.paged_attention import PALLAS_IMPLS

    mod, cfg, B, MB, NB, _, n_mla = _latent_case(family)
    cfg = dataclasses.replace(cfg, attn_impl="pallas")
    R, dr = cfg.mla_plane_heights
    T = 2048
    assert mla_prefill_impl(cfg, T, BS, cfg.dtype) in PALLAS_IMPLS
    lanes = B if getattr(mod, "KV_LANE_ADDRESSED", False) else 0
    S = _sds(one_chip)
    shapes, lowered = _latent_prefill_lowered(mod, cfg, S, T, MB, NB, lanes)
    n_moe = sum("moe_w_up" in layer for layer in shapes["layers"])
    n_state = len(shapes["layers"]) - n_mla if lanes else 0
    # the read's one lowering: a private function the layers call
    text = lowered.as_text()
    assert text.count('kernel_name = "_mla_prefill_kernel"') == 1
    program = lowered.compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == n_mla + n_state + 3 * n_moe
    for hd in (R, dr):
        _assert_pool_stays_where_it_lies(hlo, n_mla, 1, NB, hd)
        # nor in VMEM (`_in_hbm`: a rope-key pool that fits XLA's share
        # of it would be moved out and back around every call)
        assert not re.search(rf"bf16\[{n_mla},1,{NB},{hd},{BS}\]\{{[^}}]*S\(1\)",
                             hlo)
        assert f"bf16[{MB},{hd},{BS}]" not in hlo       # the gathered table
        assert f"f32[{MB * BS},{hd}]" not in hlo
    nh = cfg.n_heads
    assert f"f32[{nh},{MB * BS + T},128]" not in hlo    # table-wide K / V
    assert f"f32[{T},{nh},{MB * BS + T}]" not in hlo    # and scores
    # a short bucket: the jnp read, the planes write
    assert mla_prefill_impl(cfg, 256, BS, cfg.dtype) == "jnp"
    _, short = _latent_prefill_lowered(mod, cfg, S, 256, MB, NB, lanes)
    hlo = short.compile().as_text()
    for hd in (R, dr):
        pool = rf"bf16[{n_mla},1,{NB},{hd},{BS}]"
        assert pool + "{4,3,2,1,0" in hlo
        assert pool + "{3,1,4,2,0" not in hlo           # the scatter's


def test_latent_prefill_runs_per_head_shard_under_tp(topo):
    """Moonlight's 2048-token `prefill` for four described chips,
    tp = 4, the parameters placed by the engine's rules: the flash read
    runs per head shard under `shard_map` (4 of 16 heads a chip, the
    pools replicated and whole): one custom call a layer, and no
    collective gives out a pool, a layer of one, or the heads gathered
    for the kernel."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.parallel.mesh import param_sharding_rules

    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1), ("dp", "tp", "sp"))
    mod, cfg, _, MB, NB, _, n_mla = _latent_case("moonlight")
    cfg = dataclasses.replace(cfg, attn_impl="pallas", expert_shards=4)
    R, dr = cfg.mla_plane_heights
    T, nh = 2048, cfg.n_heads
    rules = param_sharding_rules()

    def placed(path, x):
        name = next((k.key for k in reversed(path)
                     if isinstance(getattr(k, "key", None), str)), None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, rules.get(name, P())))

    params = jax.tree_util.tree_map_with_path(placed, jax.eval_shape(
        lambda: mod.init_params(cfg, jax.random.PRNGKey(0))))
    S = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P()))
    kv = tuple(S(s, cfg.dtype) for s in mod.kv_cache_shapes(cfg, NB, BS))
    i32, f32 = jnp.int32, jnp.float32
    hlo = jax.jit(
        partial(JaxEngine._prefill_impl, mod, cfg, mesh=mesh),
        donate_argnums=(1,)).lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32)
    ).compile().as_text()
    assert hlo.count("tpu_custom_call") == n_mla
    # a shard's queries and output as the kernel takes them
    assert f"bf16[{T},{nh // 4 * 128}]" in hlo
    made = re.findall(rf"= (\w+\[(?:{n_mla},)?1,{NB},(?:{R}|{dr}),{BS}\]|"
                      rf"\w+\[{T},{nh * 128}\])\S* (all-gather|all-to-all|"
                      rf"collective-permute|copy)\(", hlo)
    assert not made, sorted(set(made))


def test_latent_prefill_body_stays_small(one_chip):
    """The SIZE guard.  Mosaic's code for the flash read's body is
    embedded once a layer in every kernel-bearing prefill program and is
    read, deserialized and loaded at every start: PR 48's body (8 heads
    unrolled a loop step, a pair with and without the mask, hi + lo
    products) made the 8-layer Moonlight-shaped program of the reads
    alone 27.0 MB where the jnp form's is 8.0 MB, 14 s to compile where
    it takes 2.2, and cost `moonlight-16b.chat` 11 s of `setup_s`, which
    refused it.  Today (PR 49: 2 heads a step, 512 x 512 tiles, one
    body): 8.3-8.7 MB and 1.6 s here (2.8 s on the chip's host).  The
    serialized executable is held to 1.5 x the jnp form's, so that a
    later tuning cannot grow the body unseen (`bench_mla_prefill.py
    --tune` prints all three numbers a candidate)."""
    from jax.experimental.serialize_executable import serialize

    from dynamo_tpu.ops.mla_attention import mla_prefill_attention
    from dynamo_tpu.ops.pallas_mla_attention import mla_prefill_pallas

    S = _sds(one_chip)
    bf, i32 = jnp.bfloat16, jnp.int32
    L, T, nh, MB, NB, R, dr, dn, dv = 8, 2048, 16, 20, 512, 512, 64, 128, 128
    args = (S((T, nh, dn), bf), S((T, nh, dr), bf), S((T, R), bf),
            S((T, dr), bf), S((L, 1, NB, R, BS), bf),
            S((L, 1, NB, dr, BS), bf), S((MB,), i32), S((), i32),
            S((), i32), S((L, nh, R, dn), bf), S((L, nh, R, dv), bf))

    def reads(kernel):
        def program(qn, qr, c, kr, cc, krc, table, ctx, true, w_uk, w_uv):
            out = 0.0
            for li in range(L):
                if kernel:
                    o = mla_prefill_pallas(
                        qn[None], qr[None], cc, krc, jnp.int32(li),
                        table[None], ctx[None], true[None], w_uk[li],
                        w_uv[li])[0]
                else:
                    o = mla_prefill_attention(
                        qn, qr, c, kr, cc, krc, li, table, ctx, true,
                        w_uk[li], w_uv[li])
                out = out + o.astype(jnp.float32)
                qn = (qn + 1e-3 * o[..., :dn]).astype(bf)
            return out
        compiled = jax.jit(program).lower(*args).compile()
        return len(serialize(compiled)[0])

    jnp_bytes, kernel_bytes = reads(False), reads(True)
    assert kernel_bytes <= 1.5 * jnp_bytes, (kernel_bytes, jnp_bytes)


def test_ssm_decode_and_prefill_compile_for_v5e(topo, one_chip):
    """The state-space family (models/nemotron_h.py) at the published
    nemotron_h widths, cut to the pattern's first 13 blocks (`MEMEM*`
    and one whole period `EMEMEM*`: 6 Mamba-2, 5 expert blocks with 16
    of 128 experts held, 2 attention), with the chat cell's cache (64
    lanes, 1281 blocks, tables of 20) and `auto` resolved as on the chip:
    a fused decode burst of the engine's own program and a 2048-token
    prefill chunk.  In both, the float32 state (6 x 64 lanes x 64 heads x
    64 x 128: 805 MB here, 1.61 GB at the cell's 12 Mamba blocks) is
    updated where it lies: no copy of its shape nor of one block's slice
    over the lanes, and the decode burst hands the member WHOLE to the
    state kernel, one custom call a Mamba block (PR 41:
    `_assert_state_steps_in_place`); the K/V pool goes into the decode
    kernel whole (one custom call an attention block, 2 KV heads of 128
    under 16 query heads each); a decode step walks the experts its
    lanes visited, one custom call an expert block over the two stacks
    where they lie (PR 44: the up stack, 1856 wide, lies with the model
    width minor and the kernel takes it transposed, no copy), and the
    prompt groups its picks in TWO grouped matmuls an expert block (a
    plain expert has no gate matrix) and reads its attention blocks in
    the packed flash kernel (PR 52: the padded row as a packed stream,
    written as whole planes; the pool never copied, no float32 score
    plane of the table-wide read it replaced)."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import nemotron_h as nh
    from dynamo_tpu.ops.lane_state import resolve_state_impl
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    NB, B, MB, K, T = 1281, 64, 20, 8, 2048
    impl = resolve_decode_impl("auto", topo.devices[0].platform, BS, 128,
                               jnp.bfloat16)
    assert impl == "pallas"
    assert resolve_state_impl("auto", topo.devices[0].platform, 64, 128,
                              jnp.float32) == "pallas"
    cfg = dataclasses.replace(
        nh.PRESETS["nemotron-twotower-30b-a3b"], pattern="MEMEM*EMEMEM*",
        experts_held=(0, 16), attn_impl=impl)
    NM, NA, NE = (len(cfg.layers_of(k)) for k in "M*E")
    assert (NM, NA, NE) == (6, 2, 5)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: nh.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"][0]["w_in"].shape == (2688, 10304)
    assert "moe_w_gate" not in shapes["layers"][1]
    assert shapes["layers"][1]["moe_w_up"].shape == (16, 2688, 1856)
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        nh.kv_cache_shapes(cfg, NB, BS, lanes=B), nh.kv_cache_dtypes(cfg)))
    assert kv[0].shape == (NA, 2, NB, 128, BS)
    assert kv[2].shape == (NM, B, 64, 64, 128) and kv[2].dtype == jnp.float32
    assert kv[3].shape == (NM, B, 3, 6144)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_

    def state_stays(hlo):
        # (a prefill row's own 2 MB working state may move; the pool and
        # a block's slice over all lanes may not)
        for shape in (rf"f32\[{NM},{B},64,64,128\]",
                      rf"f32\[{B},64,64,128\]"):
            assert not re.findall(rf"= {shape}\S* copy\(", hlo), shape

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, nh, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(nh.KV_COUNTERS), B)
    program = lowered.compile()
    hlo = program.as_text()
    state_stays(hlo)
    _assert_pool_stays_where_it_lies(hlo, NA, 2, NB, 128)
    _assert_state_steps_in_place(hlo, kv[2].shape, NM)
    # the attention blocks' kernel, the Mamba blocks' and the expert
    # blocks' walk over the visited list
    assert hlo.count("tpu_custom_call") == NA + NM + NE
    _assert_experts_walk_the_visited_list(hlo, 16, B, 2688, 1856)
    assert program.memory_analysis().temp_size_in_bytes < 0.3e9
    packed = resolve_packed_impl("auto", topo.devices[0].platform, BS, 128,
                                 jnp.bfloat16, T, 16)
    assert packed == "pallas"
    pre = jax.jit(
        partial(JaxEngine._prefill_impl, nh,
                dataclasses.replace(cfg, packed_attn_impl=packed)),
        donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32), None,
        None, S((), i32)).compile()
    hlo = program.as_text()
    state_stays(hlo)
    # the attention blocks read the chunk and its context in the packed
    # flash kernel (PR 52: `auto` at 2048 tokens on the chip), one
    # custom call a block beside the experts' two; the pool is written
    # and read where it lies (beside the kernel the flat column scatter
    # cost a copy of the pool), and nothing of the table-wide read's
    # float32 score plane (`[512, 32, 2560 + 512]`, 201 MB a pass) is left
    assert hlo.count("tpu_custom_call") == 2 * NE + NA
    _assert_pool_stays_where_it_lies(hlo, NA, 2, NB, 128)
    assert "f32[512,32," not in hlo
    assert f"bf16[16,{T},1856]" not in hlo \
        and f"bf16[16,1856,{T}]" not in hlo
    # 1.124 GB here (1.289 with the table-wide read and the scatter)
    assert program.memory_analysis().temp_size_in_bytes < 1.3e9


@pytest.mark.parametrize("periods, only_small", [
    (2, False), pytest.param(4, False, marks=pytest.mark.slow), (4, True)],
    ids=["20l", "40l", "40l-32tok"])
def test_dense_ssm_decode_and_prefill_compile_for_v5e(topo, one_chip,
                                                      periods, only_small):
    """The dense Mamba-2 hybrid (models/granite_hybrid.py) at the
    PUBLISHED widths with its cell's cache (64 lanes, 2881 blocks, tables
    of 45) and `auto` resolved as on the chip: a fused decode burst of
    the engine's own program and a 2048-token prefill chunk.  Tier-1
    compiles two of the four periods (9 Mamba-2 layers to 1 attention
    each) and the whole model's 32-token prefill alone (`40l-32tok`),
    the `slow` twin the whole model: all 40 layers, 13.31 GiB of
    arguments of which the float32 state member is 4.5 GiB (36 x 64 x
    64 x 64 x 128: 4.83e9 bytes, more than 2^32) in ONE array.  In both
    programs that member is updated where it lies: no copy of its shape
    nor of one layer's slice over the lanes (a second one does not fit);
    the decode burst hands it WHOLE to the state kernel, one custom call
    a Mamba layer at a head block of all 64 heads (one group: the only
    legal block, exactly the kernel's block budget) and one an attention
    layer over the K/V pool where it lies; the prefill reads its
    attention layers in the packed flash kernel.  The convolution's tail
    is a FLAT member (mamba2.state_shapes): as [36, 64, 3, 4352] XLA's
    layout for a row's gather and scatter padded its 3-axis to 128 lanes,
    a 2.39 GB temporary, and the 40-layer prefill did not fit.  The
    weights are stacked over the periods: the prefill is a `lax.scan`
    over them (one period compiled: 17.6 s for 38 unrolled, temporaries
    0.188 GiB for 1.367), the decode burst goes over STATIC slices of the
    stacks (scanned, XLA copied a period's weights out of the stacks
    every iteration: 1.244 GiB of temporaries, weights moved three times
    a step).  At 40 layers arguments + temporaries are 13.41 GiB (decode:
    0.092 of temporaries) and 13.50 GiB (prefill: 0.188), under the
    chip's 15.75 (compiled for a described v5e, PR 57; the
    configuration's `deployment` carries them)."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import granite_hybrid as gh
    from dynamo_tpu.ops.lane_state import resolve_state_impl
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.ops.pallas_lane_state import head_block_for

    NB, B, MB, K, T = 2881, 64, 45, 8, 2048
    platform = topo.devices[0].platform
    impl = resolve_decode_impl("auto", platform, BS, 64, jnp.bfloat16)
    packed = resolve_packed_impl("auto", platform, BS, 64, jnp.bfloat16, T,
                                 4)
    assert (impl, packed) == ("pallas", "pallas")
    assert resolve_state_impl("auto", platform, 64, 128,
                              jnp.float32) == "pallas"
    assert head_block_for(64, 64, 64, 128) == 64
    big = gh.PRESETS["granite-4.0-h-micro"]
    assert big.layer_kinds == gh.PERIOD * 4
    cfg = dataclasses.replace(big, layer_kinds=gh.PERIOD * periods,
                              attn_impl=impl, packed_attn_impl=packed)
    NM, NA = 9 * periods, periods
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: gh.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        gh.kv_cache_shapes(cfg, NB, BS, lanes=B), gh.kv_cache_dtypes(cfg)))
    assert kv[0].shape == (NA, 8, NB, 64, BS)
    assert kv[2].shape == (NM, B, 64, 64, 128) and kv[2].dtype == jnp.float32
    assert kv[3].shape == (NM, B, 3 * 4352)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    GiB = float(1 << 30)

    def state_stays(hlo):
        # (a prefill row's own 2 MB working state may move; the member
        # and a layer's slice over all lanes may not)
        for shape in (rf"f32\[{NM},{B},64,64,128\]",
                      rf"f32\[{B},64,64,128\]"):
            assert not re.findall(rf"= {shape}\S* copy\(", hlo), shape

    def fits(program, temp_gib):
        m = program.memory_analysis()
        # everything the program is handed is handed back in place
        assert m.alias_size_in_bytes >= sum(
            math.prod(x.shape) * x.dtype.itemsize for x in kv)
        assert m.temp_size_in_bytes < temp_gib * GiB, m.temp_size_in_bytes
        if periods == 4:
            assert m.argument_size_in_bytes > 13.2 * GiB
            assert m.argument_size_in_bytes + m.temp_size_in_bytes \
                < 15.0 * GiB

    def prefill(T, packed):
        pre = jax.jit(
            partial(JaxEngine._prefill_impl, gh,
                    dataclasses.replace(cfg, packed_attn_impl=packed)),
            donate_argnums=(1,))
        return pre.lower(
            params, kv, S((T,), i32), S((T,), i32), S((MB,), i32),
            S((), i32), S((), i32), S((), i32), S((), f32), S((), i32),
            S((), f32), None, None, S((), i32)).compile()

    def small_prefill_holds_its_start():
        # the 32-token program: a row of ONE chunk is a scan of length 1
        # that XLA unrolls; without mamba2.mixer_prefill's `hold_start`
        # the last two Mamba layers' put waited for a reader of the
        # member behind a COPY of it (4.5 GiB: refused here, and on the
        # chip as the cell's first warm-up prompt).  That was the 40
        # layers UNROLLED; with the periods scanned, as they are now,
        # this program compiles without a copy even with the barrier
        # patched out (at 20 and at 40 layers: compiled for a described
        # v5e, PR 57), so the assertion guards the property, whatever
        # keeps it; the barrier goes with ROADMAP's follow-up, after a
        # chip run without it.  Tier-1 compiles this program at BOTH
        # depths (the 40-layer one alone takes 20 s).
        small = resolve_packed_impl("auto", platform, BS, 64,
                                    jnp.bfloat16, 32, 4)
        assert small == "xla"
        program = prefill(32, small)
        state_stays(program.as_text())
        fits(program, 0.2)

    if only_small:
        return small_prefill_holds_its_start()
    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, gh, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K, B)      # no device-side counts
    program = lowered.compile()
    hlo = program.as_text()
    state_stays(hlo)
    _assert_pool_stays_where_it_lies(hlo, NA, 8, NB, 64)
    _assert_state_steps_in_place(hlo, kv[2].shape, NM)
    assert hlo.count("tpu_custom_call") == NA + NM
    fits(program, 0.2)
    program = prefill(T, packed)
    hlo = program.as_text()
    state_stays(hlo)
    # ONE period compiled and scanned over the periods: one attention
    # layer's kernel in the loop's body, whatever the depth
    assert hlo.count("tpu_custom_call") == 1
    _assert_pool_stays_where_it_lies(hlo, NA, 8, NB, 64)
    # no padded relayout of the tail member (the 4-D member's 3-axis)
    assert not re.findall(rf"bf16\[{NM},{B},3,4352\]", hlo)
    assert not re.findall(rf"= bf16\[{NM},{B},13056\]\S* copy\(", hlo)
    fits(program, 0.3)
    small_prefill_holds_its_start()


def test_window_ring_decode_and_prefill_compile_for_v5e(topo, one_chip,
                                                        capsys):
    """The window + NoPE-global family (models/cohere2.py) at Command
    A+'s published widths (128 heads over 8 KV heads of 128, 16 of 128
    experts of 4096 held, 4 shared), cut to one period of four layers
    and a vocabulary of 32768, with the long-context cell's cache (8
    lanes, 1593 blocks, tables of 199; rings of 33 blocks a lane) and
    `auto` resolved as on the chip: a fused decode burst of the engine's
    own program and a 2048-token packed prefill chunk.  Both kinds of
    layer read through the paged pools' kernels: one custom call a layer
    in the burst (the ring handed over as a table with a lower bound)
    beside the experts' walk over the visited list (PR 44), and in the
    chunk one a layer beside the experts' three grouped matmuls; the global pool and the ring pool keep their resident
    layout and are never copied, relaid or sliced; nothing of a score
    block's size is left in float32."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import cohere2
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl
    from dynamo_tpu.ops.window_attention import resolve_window_prefill_impl

    NB, B, MB, K, T, L = 1593, 8, 199, 8, 2048, 4
    impl = resolve_decode_impl("auto", topo.devices[0].platform, BS, 128,
                               jnp.bfloat16)
    assert impl == "pallas"
    assert resolve_window_prefill_impl(
        "auto", topo.devices[0].platform, 4096, 128, jnp.bfloat16,
        T, 16) == "pallas"
    cfg = dataclasses.replace(
        cohere2.PRESETS["command-a-plus"], n_layers=L,
        layer_kinds=(1, 1, 1, 0), experts_held=(0, 16), vocab_size=32768,
        attn_impl=impl, packed_attn_impl="pallas")
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: cohere2.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"][0]["moe_w_up"].shape == (16, 4096, 4096)
    assert shapes["layers"][0]["shared"]["w_down"].shape == (16384, 4096)
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        cohere2.kv_cache_shapes(cfg, NB, BS, lanes=B),
        cohere2.kv_cache_dtypes(cfg)))
    assert kv[0].shape == (1, 8, NB, 128, BS)
    assert kv[2].shape == (3, 8, 1 + 33 * B, 128, BS)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_

    def pools_stay(hlo):
        _assert_pool_stays_where_it_lies(hlo, 1, 8, NB, 128)
        _assert_pool_stays_where_it_lies(hlo, 3, 8, 1 + 33 * B, 128)

    def report(what, program):
        mem = program.memory_analysis()
        with capsys.disabled():
            print(f"\ncohere2 {what}: arguments "
                  f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
        return mem

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, cohere2, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(cohere2.KV_COUNTERS), B)
    program = lowered.compile()
    hlo = program.as_text()
    # a layer: its read and its experts' walk over the visited list
    assert hlo.count("tpu_custom_call") == 2 * L
    pools_stay(hlo)
    _assert_experts_walk_the_visited_list(hlo, 16, B, 4096, 4096)
    mem = report("decode burst", program)
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

    pre = jax.jit(
        partial(JaxEngine._prefill_packed_impl, cohere2, cfg, None),
        donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
        S((1, MB), i32), S((1,), i32), S((T,), b1), S((1,), i32),
        S((1,), f32), S((1,), i32), S((1,), f32), None, None,
        S((1,), i32)).compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == L + 3 * L
    pools_stay(hlo)
    # a body's 4 heads of a KV head are a column block of q and of the
    # output where they lie: nothing of q's size stands beside the call
    # re-laid a run of heads at a time ([4, T, 8, 4, 128] and its like)
    q_sized = {m for m in re.findall(r"bf16\[([\d,]+)\]", hlo)
               if math.prod(int(d) for d in m.split(",")) == T * 128 * 128}
    assert q_sized and not [m for m in q_sized if "4" in m.split(",")], \
        q_sized
    assert f"bf16[16,{T},4096]" not in hlo
    # nothing the size of a score block ([2048, 128, 256] and up) but
    # the grouped dispatch's own combine of a token's 8 picks
    score = T * 128 * 256
    sized = {m for m in re.findall(r"f32\[([\d,]+)\]", hlo)
             if len(m.split(",")) >= 3
             and math.prod(int(d) for d in m.split(",")) >= score}
    assert sized <= {f"{T},8,4096"}, sized
    mem = report("2048-token prefill", program)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_the_bounds_are_one_more_operand_each(one_chip):
    """The decode kernel's `kv_lo` and the packed kernel's `lower`
    compile for v5e, and each is ONE more operand of its custom call:
    without it a caller's call is the one it was (five scalar operands
    and q, K, V in decode; four scalars, the two planes, q and 2 x 8
    block operands in prefill).  (The whole programs of Mistral, MiMo,
    Keye, Ling, Moonlight and Nemotron that hold these kernels were
    compared with the parent's as traced, kernels' bodies included:
    PERF.md section 6, PR 42.)"""
    import re

    from dynamo_tpu.ops.pallas_packed_prefill import (
        packed_prefill_attention_pallas,
    )
    from dynamo_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_pallas,
    )

    nkv, nh, hd = WIDTHS["llama-8b"]
    S = _sds(one_chip)
    L, NB, MB, B, T = 2, 64, 16, 8, 2048
    cache = S((L, nkv, NB, hd, BS), jnp.bfloat16)
    i32 = jnp.int32

    def operands(lowered):
        hlo = lowered.compile().as_text()
        call = re.search(r"custom-call\((.*?)\), custom_call_target="
                         r"\"tpu_custom_call\"", hlo).group(1)
        return call.count("%")

    decode = partial(paged_attention_decode_pallas, layer=1)
    args = (S((B, nh, hd), jnp.bfloat16), cache, cache)
    kw = dict(block_tables=S((B, MB), i32), kv_lens=S((B,), i32))
    free = operands(jax.jit(decode).lower(*args, **kw))
    assert free == 5 + 3
    assert operands(jax.jit(decode).lower(
        *args, **kw, kv_lo=S((B,), i32))) == free + 1
    packed = partial(packed_prefill_attention_pallas, layer=1)
    args = (S((T, nh, hd), jnp.bfloat16), cache, cache)
    kw = dict(block_tables=S((1, MB), i32), seg_ids=S((T,), i32),
              positions=S((T,), i32), valid=S((T,), jnp.bool_))
    free = operands(jax.jit(packed).lower(*args, **kw))
    assert free == 4 + 3 + 2 * 8
    assert operands(jax.jit(packed).lower(
        *args, **kw, lower=S((T,), i32))) == free + 1


def test_block_sparse_decode_and_prefill_compile_for_v5e(one_chip):
    """The family whose sparse layers choose BLOCKS of keys beside
    lightning linear-attention layers (models/minicpm_sala.py) at the
    published widths, cut to a sparse, two lightning and a sparse layer,
    with the long-document cell's cache (8 lanes, 3129 blocks, tables of
    391) and `auto` resolved as on the chip: a fused decode burst of the
    engine's own program and a 2048-token prefill chunk.  The decode
    burst reads the chosen pages through the paged pool's decode kernel,
    once a KV group (16 query heads a KV head: the bias is added a tile
    of heads at a time, or the compiler aborts) over the pool seen as
    [layers x nkv, 1, ...]: a bitcast, never a copy; the choice is
    `topk_mask`'s kernel; the state is stepped in place.  The prefill
    chunk runs the choice's scores as one kernel (PR 56: no [256, 32,
    3128] float32 scores through HBM), one search and one flash pass
    under the block mask a sparse layer.  Neither copies a pool or the
    state."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import minicpm_sala as sala
    from dynamo_tpu.ops.paged_attention import PALLAS_IMPLS

    kinds = (sala.SPARSE, sala.LIGHTNING, sala.LIGHTNING, sala.SPARSE)
    NB, B, MB, K, T = 3129, 8, 391, 8, 2048
    cfg = dataclasses.replace(sala.PRESETS["minicpm-sala-9b"],
                              n_layers=len(kinds), layer_kinds=kinds,
                              attn_impl="pallas")
    assert sala.state_impl(cfg, cfg.attn_impl) in PALLAS_IMPLS
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: sala.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        sala.kv_cache_shapes(cfg, NB, BS, lanes=B),
        sala.kv_cache_dtypes(cfg)))
    assert kv[0].shape == (2, 2, NB, 128, BS)
    assert kv[2].shape == (2, NB, 8, 2, 128)
    assert kv[3].shape == (2, B, 32, 128, 128) and kv[3].dtype == jnp.float32
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_

    def members_stay(hlo):
        for shape in (rf"bf16\[2,2,{NB},128,{BS}\]",
                      rf"bf16\[4,1,{NB},128,{BS}\]",
                      rf"bf16\[2,{NB},8,2,128\]",
                      rf"f32\[2,{B},32,128,128\]", rf"f32\[{B},32,128,128\]"):
            assert not re.findall(rf"= {shape}\S* copy\(", hlo), shape

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, sala, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(sala.KV_COUNTERS), B)
    program = lowered.compile()
    hlo = program.as_text()
    members_stay(hlo)
    # the state is stepped where it lies: nothing but plumbing and the
    # kernel's call gives out a value of the member's shape (no `select`
    # or `dynamic-update-slice` over it: the jnp step's `where` and
    # `.at[pli].set`).  (`_assert_state_steps_in_place` does not fit: a
    # member this small, 33 MB at two layers, is moved whole into fast
    # memory for the burst by XLA's memory-space assignment, slice by
    # slice, and back with one asynchronous copy: 151 MB at the cell's
    # nine layers is not.)
    made = set(re.findall(rf"= f32\[2,{B},32,128,128\]\S* ([\w-]+)\(", hlo))
    assert made <= {"parameter", "get-tuple-element", "while", "tuple",
                    "bitcast", "custom-call", "copy-done"}, sorted(made)
    # a sparse layer: the search and the read of each of two KV groups;
    # a lightning layer: the state's kernel
    assert hlo.count("tpu_custom_call") == 2 * (1 + 2) + 2
    assert re.search(rf"bf16\[4,1,{NB},128,{BS}\]\S* bitcast\(", hlo)
    assert program.memory_analysis().temp_size_in_bytes < 0.5e9
    pre = jax.jit(partial(JaxEngine._prefill_impl, sala, cfg),
                  donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((MB,), i32), S((), i32),
        S((), i32), S((), i32), S((), f32), S((), i32), S((), f32), None,
        None, S((), i32)).compile()
    hlo = program.as_text()
    members_stay(hlo)
    # a sparse layer: the choice's scores (ops/pallas_block_choice.py),
    # the search and the flash pass; no [256, 32, 3128] float32 scores
    assert hlo.count("tpu_custom_call") == 2 * 3
    assert not re.findall(r"f32\[256,32,3128\]", hlo)
    # the token mask a KV group is the largest thing the chunk makes
    assert program.memory_analysis().temp_size_in_bytes < 2.0e9


def test_diffusion_passes_and_prefill_compile_for_v5e(one_chip):
    """The family that generates by diffusion over blocks
    (models/sdar.py) at SDAR-30B-A3B's widths, cut to two layers (all
    128 experts held), with the reasoning cell's cache (929 blocks, 32
    lanes x 29): a burst of 8 PASSES of the engine's own program (128
    rows: 32 lanes x a block of 4) writes each lane's block as one plane,
    reads the cache through the Pallas decode kernel with a lane's four
    queries as 4 x 8 query heads a KV head, walks the visited experts
    and ranks the block by the head's float32 logits; and a 2048-token
    packed prefill chunk attends block-causally in the packed kernel
    (`upper` in the positions' place: the same one custom call a layer)
    over three grouped matmuls.  In all, the pools keep their resident
    layout and are never copied, and the temporaries stay beside 10.2 GB
    of weights and cache at the cell's 6 layers."""
    import re

    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import sdar

    L, NB, B, MB, K, T = 2, 929, 32, 29, 8, 2048
    cfg = dataclasses.replace(
        sdar.PRESETS["sdar-30b-a3b"], n_layers=L, attn_impl="pallas",
        packed_attn_impl="pallas")
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: sdar.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(sdar.kv_cache_shapes(cfg, NB, BS),
                                       sdar.kv_cache_dtypes(cfg)))
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    W = sdar.lane_state_width(cfg)
    pool = rf"bf16\[{L},4,{NB},128,{BS}\]"

    def pools_stay(hlo):
        assert not re.findall(rf"= {pool}\S* copy\(", hlo)
        assert set(re.findall(rf"{pool}(\{{[\d,]+)", hlo)) \
            == {"{4,3,2,1,0"}

    fn = jax.jit(
        partial(JaxEngine._denoise_impl, sdar, cfg, None, True, K, False),
        donate_argnums=(1,))
    lowered = fn.lower(
        params, kv, S((B, W), i32), S((B,), b1), S((B, W), i32),
        S((B,), i32), S((B, MB), i32), S((B,), i32), S((B,), i32),
        S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
        S((B,), b1), S((), i32))
    assert lowered.out_info[0].shape == (
        K * cfg.block_length + 1 + len(sdar.KV_COUNTERS), B)
    assert lowered.out_info[2].shape == (B, W)
    program = lowered.compile()
    hlo = program.as_text()
    # a layer: the attention read and the experts' walk over the visited
    # list
    assert hlo.count("tpu_custom_call") == 2 * L
    pools_stay(hlo)
    _assert_experts_walk_the_visited_list(hlo, 128, B * cfg.block_length,
                                          2048, 768)
    assert program.memory_analysis().temp_size_in_bytes < 1.0e9
    pre = jax.jit(partial(JaxEngine._prefill_packed_impl, sdar, cfg, None),
                  donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
        S((1, MB), i32), S((1,), i32), S((T,), b1), S((1,), i32),
        S((1,), f32), S((1,), i32), S((1,), f32)).compile()
    hlo = program.as_text()
    # a layer: the flash pass under the block-causal bound and three
    # grouped matmuls
    assert hlo.count("tpu_custom_call") == 4 * L
    pools_stay(hlo)
    assert program.memory_analysis().temp_size_in_bytes < 2.0e9


def test_short_conv_decode_and_prefill_compile_for_v5e(topo, one_chip,
                                                       capsys):
    """The gated short-convolution family (models/lfm2.py) at LFM2-24B-
    A2B's published widths and the long-context cell's WHOLE cut (9
    layers: 7 conv, 2 attention of 32 heads over 8 KV heads of 64; one
    dense feed-forward of 11776, eight layers of all 64 experts of 1536;
    the whole vocabulary) and cache (8 lanes, 1593 blocks, tables of 199;
    tails [7, 8, 2, 2048]), `auto` resolved as on the chip: both Pallas
    reads at 64-wide heads.  A fused decode burst of the engine's own
    program: one custom call an attention layer and one an expert layer
    (the walk over the visited list), the pool never copied; and a
    2048-token packed prefill chunk: one custom call an attention layer
    (a body of 4 heads x 64 = 256 lanes) beside the experts' three
    grouped matmuls a layer, no score block.  Arguments and temporaries
    are printed: the cell holds 11.2 GB before temporaries."""
    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.ops.packed_prefill import resolve_packed_impl
    from dynamo_tpu.ops.paged_attention import resolve_decode_impl

    NB, B, MB, K, T = 1593, 8, 199, 8, 2048
    platform = topo.devices[0].platform
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    impl = resolve_decode_impl("auto", platform, BS, 64, jnp.bfloat16)
    packed = resolve_packed_impl("auto", platform, BS, 64, jnp.bfloat16, T,
                                 big.n_heads // big.n_kv_heads)
    assert (impl, packed) == ("pallas", "pallas")
    cfg = dataclasses.replace(
        big, layer_kinds=big.layer_kinds[1:10], n_dense_layers=1,
        attn_impl=impl, packed_attn_impl=packed)
    S = _sds(one_chip)
    shapes = jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"][1]["moe_w_up"].shape == (64, 2048, 1536)
    assert shapes["layers"][0]["w_up"].shape == (2048, 11776)
    params = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype), shapes)
    kv = tuple(S(s, d) for s, d in zip(
        lfm2.kv_cache_shapes(cfg, NB, BS, lanes=B),
        lfm2.kv_cache_dtypes(cfg)))
    assert kv[0].shape == (2, 8, NB, 64, BS)
    assert kv[2].shape == (7, B, 2, 2048)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_

    def report(what, program):
        mem = program.memory_analysis()
        with capsys.disabled():
            print(f"\nlfm2 {what}: arguments "
                  f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
        return mem

    fn = jax.jit(
        partial(JaxEngine._decode_multi_impl, lfm2, cfg, None, True, K,
                False),
        donate_argnums=(1, 5, 7, 9))
    lowered = fn.lower(
        params, kv, S((B,), i32), S((B,), b1), S((B,), i32), S((B,), i32),
        S((B, MB), i32), S((B,), i32), S((B,), i32), S((B,), i32),
        S((B,), f32), S((B,), i32), S((B,), f32), S((B,), b1),
        S((), i32))
    assert lowered.out_info[0].shape == (K + len(lfm2.KV_COUNTERS), B)
    program = lowered.compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == 2 + 8
    _assert_pool_stays_where_it_lies(hlo, 2, 8, NB, 64)
    _assert_experts_walk_the_visited_list(hlo, 64, B, 2048, 1536)
    mem = report("decode burst", program)
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9

    pre = jax.jit(
        partial(JaxEngine._prefill_packed_impl, lfm2, cfg, None),
        donate_argnums=(1,))
    program = pre.lower(
        params, kv, S((T,), i32), S((T,), i32), S((T,), i32),
        S((1, MB), i32), S((1,), i32), S((T,), b1), S((1,), i32),
        S((1,), f32), S((1,), i32), S((1,), f32), None, None,
        S((1,), i32)).compile()
    hlo = program.as_text()
    assert hlo.count("tpu_custom_call") == 2 + 3 * 8
    _assert_pool_stays_where_it_lies(hlo, 2, 8, NB, 64)
    # no score block: nothing float32 of [T, 32 heads, a key tile] or more
    assert f"f32[{T},32,{BS}" not in hlo and f"f32[32,{T},{BS}" not in hlo
    mem = report("2048-token prefill", program)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
