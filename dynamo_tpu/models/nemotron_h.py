"""Mamba-2 state-space layers beside a few GQA attention layers and
plain (non-gated) routed experts held as a share, ONE mixer a block (the
`nemotron_h` architecture), functional JAX over a cache of two kinds
whose second kind is not keys; same contract as the other families.

Block i is `x += mixer_i(RMSNorm(x))` with ONE mixer, chosen by
`cfg.pattern[i]` (the published `hybrid_override_pattern`):
  * `M` Mamba-2 (ops/ssm.py): [z | xBC | dt] = h W_in; a causal depthwise
    convolution of `conv_width` with a bias over the channels of x, B
    and C together, then SiLU; dt = softplus(dt~ + dt_bias) a head,
    A = -exp(A_log) a head; the head's state S (ssm_head_dim x ssm_state,
    float32) decays by the scalar exp(dt A), is fed dt x B^T and read with
    C (B and C shared by the heads of a group) plus D x; the read is
    gated by SiLU(z) and THEN RMS-normed over each group's channels, and
    goes through W_out.
  * `*` attention: models/llama.py's `_qkv` / `_attn_out` and the paged
    GQA write and read of ops/paged_attention.py (the Pallas kernel
    where `cfg.attn_impl` resolves to it), imported; a prompt's chunk is
    read through ops/packed_prefill.py (the flash scan, or the Pallas
    kernel where `cfg.packed_attn_impl` resolves to it).  NO rotary:
    position lives in the Mamba layers (`_qkv` with no positions).
  * `E` experts: DeepSeek routing (`ds_router` at one group: sigmoid,
    choice bias, top-k, renormalised, scaled) over `n_experts` router
    outputs of which this program holds `experts_held` = (first, count),
    each `Wdown relu(x Wup)^2` (two matrices, no gate: `expert_gated`
    False, `expert_act` relu2 tell moe.py's `moe_dispatch`), plus one
    shared expert of the same form.  What the absent experts would add
    is left out; the partial result goes on to the next block.
  * `-` (a plain MLP block) is not modelled and refused by the config.
The stream between blocks is float32 and the Mamba and shared-expert
matmuls keep their float32 accumulator (bf16 operands): the published
`residual_in_fp32` false is a storage precision, the reference is
float32, and 27 roundings of the stream were a third of the program's
distance from it (PERF.md section 6, PR 40).

Cache (the family contract in models/__init__.py): five members,
(k, v, state, conv tail, counters), their layer axes indexed by KIND:
k and v are paged by the block table over the `*` blocks only; `state`
[M blocks, lanes, heads, ssm_head_dim, ssm_state] float32 and `tail`
[M blocks, lanes, conv_width - 1, conv channels] are addressed by LANE
(`KV_LANE_ADDRESSED`) and are a STATE, whose life ops/lane_state.py
keeps: zeroed where a row starts at position 0, carried between a
prompt's prefill programs, untouched by a bucket's padding (dt 0 and a
zeroed input: decay 1, no feed; the tail cut at the last real token), by
a row of no tokens and by idle decode lanes, rebuilt by replay after a
preemption.

Not carried (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hashed K/V block says nothing of the
state at its end), int8 cache, speculation, LoRA, ring and packed
prefill, KVBM offload / onboard, disagg transfer and migration of a
state, tp > 1.  The published model's SECOND tower (an adaLN denoiser
with bidirectional attention inside a block, conditioned on this one)
and its block-diffusion decoding are not run: its config.json has no key
for them; this is the autoregressive `nemotron_h` tower it declares.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.lane_state import lanes_plan, rows_target
from ..ops.packed_prefill import packed_prefill_attention, write_packed_kv
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    resolve_decode_impl,
    write_token_kv,
)
from . import mamba2
from .common import burst_scan, prefill_one_row
from .llama import _attn_out, _logits, _qkv, rms_norm
from .mamba2 import Mamba2Dims, mm as _mm
from .moe import (
    ds_router,
    moe_dispatch,
    moe_held_counts,
    moe_rows,
    relu2,
)

MAMBA, ATTN, MOE = "M", "*", "E"


@dataclass(frozen=True)
class NemotronHConfig:
    name: str = "tiny-nemotron-h"
    vocab_size: int = 256
    d_model: int = 64
    pattern: str = "MEM*EME"      # one mixer a block: M | * | E
    # Mamba-2
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_state: int = 16
    ssm_groups: int = 2           # B and C are a group's; the gated norm's
    conv_width: int = 4
    ssm_chunk: int = 8            # tokens a chunk of the chunked form
    state_dtype: Any = jnp.float32
    # attention (models/llama.py _qkv reads these)
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    qk_norm: bool = False
    rope_theta: float = 10000.0   # a carried key: no rotary is applied
    # experts (models/moe.py moe_dispatch reads these)
    moe_ffn_dim: int = 32
    shared_ffn_dim: int = 64
    n_experts: int = 16           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    expert_gated: bool = False    # Wdown act(x Wup): two matrices
    expert_act: Callable = relu2
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    # models/moe.py ds_router reads these
    moe_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"       # the GQA layers' decode read and, by
                                  # its own conditions, the state's step
    packed_attn_impl: str = "auto"  # the GQA layers' prefill read
                                    # (ops/packed_prefill.py)
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        odd = set(self.pattern) - {MAMBA, ATTN, MOE}
        if odd or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: a block is one of M (Mamba-2), "
                f"* (attention), E (experts); {sorted(odd)} is not "
                "modelled")
        if self.ssm_heads % self.ssm_groups \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("heads do not split into their groups")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing is not this family's "
                             f"(n_group {self.n_group})")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == kind)

    @property
    def kind_index(self) -> Tuple[int, ...]:
        """block -> its index among the blocks of its kind: the cache
        members' layer axis."""
        return tuple(self.pattern[:i].count(k)
                     for i, k in enumerate(self.pattern))

    @property
    def ssm(self) -> Mamba2Dims:
        """The Mamba-2 mixer's widths (models/mamba2.py)."""
        return Mamba2Dims(
            heads=self.ssm_heads, head_dim=self.ssm_head_dim,
            state=self.ssm_state, groups=self.ssm_groups,
            conv_width=self.conv_width, chunk=self.ssm_chunk,
            eps=self.rms_eps, dtype=self.dtype,
            state_dtype=self.state_dtype)

    @property
    def ssm_inner(self) -> int:
        return self.ssm.inner

    @property
    def conv_dim(self) -> int:
        """x, B and C side by side."""
        return self.ssm.conv_dim

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "packed_prefill", "kvbm", "disagg", "tp")

# the state and the tail are addressed by lane: prefill takes `lanes`
KV_LANE_ADDRESSED = True

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

_PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

PRESETS: Dict[str, NemotronHConfig] = {
    "tiny-nemotron-h": NemotronHConfig(),
    # the published shapes (nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
    # config.json, model_type nemotron_h); one chip holds a share of it
    # (benchmark/configs/)
    "nemotron-twotower-30b-a3b": NemotronHConfig(
        name="nemotron-twotower-30b-a3b", vocab_size=131072, d_model=2688,
        pattern=_PUBLISHED, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        ssm_groups=8, conv_width=4, ssm_chunk=128, n_heads=32,
        n_kv_heads=2, head_dim=128, moe_ffn_dim=1856, shared_ffn_dim=3712,
        n_experts=128, experts_per_token=6, routed_scaling_factor=2.5,
        rms_eps=1e-5, max_context=262144,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: NemotronHConfig, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(k, v, state, conv tail, counters).  The paged pools have
    `num_blocks` blocks and the attention blocks only; state and tail
    have one entry a lane and Mamba block."""
    na, nm = len(cfg.layers_of(ATTN)), len(cfg.layers_of(MAMBA))
    pool = (na, cfg.n_kv_heads, num_blocks, cfg.head_dim, block_size)
    return (pool, pool) + mamba2.state_shapes(cfg.ssm, nm, lanes) \
        + ((len(KV_COUNTERS),),)


def kv_cache_dtypes(cfg: NemotronHConfig) -> Tuple[Any, ...]:
    return (cfg.dtype, cfg.dtype, cfg.state_dtype, cfg.dtype, jnp.int32)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 5


def decode_block_counts(cfg: NemotronHConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """mamba2.decode_counts over this family's `*` and `M` blocks."""
    return mamba2.decode_counts(
        cfg.ssm, len(cfg.layers_of(ATTN)), len(cfg.layers_of(MAMBA)), ctx,
        k, block_size, lanes, table_width, attn_impl)


def state_impl(cfg: NemotronHConfig, attn_impl: str) -> str:
    """The impl of the state's decode step under `attn_impl`, by the
    state's own conditions (ops/lane_state.resolve_state_impl), asked by
    the traced step and by the host's counts alike."""
    return mamba2.state_impl(cfg.ssm, attn_impl)


def prefill_token_counts(cfg: NemotronHConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """mamba2.prefill_counts over this family's `*` blocks."""
    return mamba2.prefill_counts(cfg, len(cfg.layers_of(ATTN)), pos, chunk,
                                 bucket)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: NemotronHConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    A Mamba block's parameters are mamba2.init_mixer's."""
    dense = mamba2.dense_init(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (cfg.d_model, cfg.vocab_size))
    params = place(params)
    d = cfg.d_model
    f, held = cfg.moe_ffn_dim, cfg.held[1]
    layers = []
    for li, kind in enumerate(cfg.pattern):
        k = jax.random.split(keys[2 + li], 10)
        layer: Dict[str, Any] = {
            "norm": {"norm": jnp.ones((d,), jnp.float32)}}
        if kind == MAMBA:
            layer.update(mamba2.init_mixer(cfg.ssm, d, k, dense))
        elif kind == ATTN:
            layer.update({
                "wq": dense(k[0], (d, cfg.q_dim)),
                "wk": dense(k[1], (d, cfg.kv_dim)),
                "wv": dense(k[2], (d, cfg.kv_dim)),
                "wo": dense(k[3], (cfg.q_dim, d)),
            })
        else:
            layer.update({
                "moe_gate": dense(k[0], (d, cfg.n_experts)),
                "moe_gate_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
                "moe_w_up": dense(k[1], (held, d, f)),
                "moe_w_down": dense(k[2], (held, f, d)),
                "shared": {
                    "w_up": dense(k[3], (d, cfg.shared_ffn_dim)),
                    "w_down": dense(k[4], (cfg.shared_ffn_dim, d)),
                },
            })
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.mlp")
def _plain_mlp(layer, cfg: NemotronHConfig, x: jax.Array) -> jax.Array:
    h = cfg.expert_act(_mm(x, layer["w_up"])).astype(cfg.dtype)
    return _mm(h, layer["w_down"])


def _experts(layer, cfg: NemotronHConfig, h: jax.Array,
             valid: Optional[jax.Array]):
    """h [T, d] float32 -> (out [T, d], picks on held experts, held
    experts with a token), the two counts over valid rows.  The router
    reads the normed stream unrounded and at the highest precision (a
    [T, d] x [d, router] product: nothing beside the experts): with
    random weights the sixth and the seventh choice lie a hair apart, and
    a pick that flips against the float32 reference moves every later
    token through the state."""
    with jax.default_matmul_precision("highest"):
        top_w, top_e = ds_router(layer, cfg, h)
    x = h.astype(cfg.dtype)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid) \
        + _plain_mlp(layer["shared"], cfg, x)
    return (out,) + moe_held_counts(cfg, top_e, valid)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_batched(
    params: Dict[str, Any],
    cfg: NemotronHConfig,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]; unused: no rotary
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract),
    padded per row.  A Mamba block takes each row's state and tail from
    its lane (zeros where the row starts at position 0), runs the
    chunked scan with padding switched off (dt 0 and a zeroed input) and
    puts both back; a row of no tokens writes nothing.  An attention
    block writes the rows' K and V to the pool and reads them, and the
    cached context, back through ops/packed_prefill.py: the rows laid
    end to end are a packed stream whose segments are the rows
    (cohere2.prefill_batched, keye.prefill_batched).  The write is the
    stream's too, whole planes in the layout the pool is resident in:
    beside the kernel's read the flat column scatter has XLA copy the
    pool (tests/test_tpu_compile.py).  The scan form of
    that read runs one flash pass a segment ROW over the whole stream,
    so at Bp > 1 it computes Bp-fold; every cell pins `max_prefill_seqs`
    1, and co-batched rows (ROADMAP S10) inherit this note."""
    if lanes is None:
        raise ValueError("this family's state is addressed by lane: "
                         "prefill needs `lanes`")
    k_cache, v_cache, state, tail, counters = kv_cache
    Bp, T = token_ids.shape
    x = params["embedding"][token_ids].astype(jnp.float32)  # [Bp, T, d]
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = idx < true_lens[:, None]
    # the packed stream (tables, segment rows, positions, valid), the
    # same for every attention block; no rotary here: a position says
    # where a token's K and V go and how far its query sees
    stream = (block_tables, jnp.repeat(jnp.arange(Bp, dtype=jnp.int32), T),
              (ctx_lens[:, None] + idx).reshape(-1), valid.reshape(-1))
    fresh = ctx_lens == 0
    put = rows_target(lanes, true_lens, state.shape[1])
    picks = jnp.zeros((), jnp.int32)
    kind_index = cfg.kind_index
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.pattern[li], kind_index[li]
        # the float32 stream, normed; a mixer casts it for its matmuls
        h = rms_norm(x, layer["norm"]["norm"], cfg.rms_eps)
        if kind == MAMBA:
            y, state, tail = mamba2.mixer_prefill(
                layer, cfg.ssm, h, state, tail, pli, lanes, fresh, put,
                valid, true_lens)
            x = x + y
        elif kind == ATTN:
            q, k, v = _qkv(layer, cfg,
                           h.astype(cfg.dtype).reshape(Bp * T, -1), None)
            k_cache, v_cache = write_packed_kv(
                k_cache, v_cache, pli, k, v, *stream)
            attn = packed_prefill_attention(
                q, k_cache, v_cache, pli, *stream,
                impl=cfg.packed_attn_impl)
            x = x + _attn_out(layer, attn.reshape(Bp, T, cfg.q_dim))
        else:
            out, n_on, _ = moe_rows(partial(_experts, layer, cfg), h, valid)
            x = x + out
            picks = picks + jnp.sum(n_on)
    counters = counters.at[0].add(picks)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last].astype(cfg.dtype)
    return _logits(params, cfg, xl), (k_cache, v_cache, state, tail,
                                      counters)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: NemotronHConfig,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]; unused: no rotary
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
    state_plan=None,           # decode_multi's: `lanes_plan`, once a burst
):
    """One token a lane.  A Mamba block reads and writes the live lanes'
    state where it lies (rows ARE lanes: `lanes_step`); a lane that is
    not `valid` keeps state and tail as they were."""
    k_cache, v_cache, state, tail, counters = kv_cache
    x = params["embedding"][token_ids].astype(jnp.float32)  # [B, d]
    B = x.shape[0]
    live = jnp.ones((B,), bool) if valid is None else valid
    picks = visited = jnp.zeros((), jnp.int32)
    # llama._decode_trunk's plan for the paged members
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype)
    write_token = partial(write_token_kv, resident=impl in PALLAS_IMPLS,
                          valid=valid)
    kv_lens = jnp.where(live, ctx_lens + 1, 0)
    s_impl = state_impl(cfg, cfg.attn_impl)
    if state_plan is None:
        state_plan = lanes_plan(live, s_impl)
    kind_index = cfg.kind_index
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.pattern[li], kind_index[li]
        # the float32 stream, normed; a mixer casts it for its matmuls
        h = rms_norm(x, layer["norm"]["norm"], cfg.rms_eps)
        if kind == MAMBA:
            y, state, tail = mamba2.mixer_decode(
                layer, cfg.ssm, h, state, tail, pli, state_plan, s_impl,
                live)
            x = x + y
        elif kind == ATTN:
            q, k, v = _qkv(layer, cfg, h[:, None, :].astype(cfg.dtype), None)
            k_cache, v_cache = write_token(
                k_cache, v_cache, pli, k[:, 0], v[:, 0], block_tables,
                ctx_lens)
            attn = paged_attention_decode(
                q[:, 0], k_cache, v_cache, pli, block_tables, kv_lens,
                impl=impl, mesh=mesh)
            x = x + _attn_out(layer, attn.reshape(B, cfg.q_dim))
        else:
            out, n_on, n_seen = _experts(layer, cfg, h, valid)
            x = x + out
            picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x.astype(cfg.dtype)), (
        k_cache, v_cache, state, tail, counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: NemotronHConfig,
    kv_cache,
    token_ids: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    ctx_lens: jax.Array,
    num_steps: int,
    sample_fn=None,
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    """num_steps fused decode steps (llama.decode_multi contract)."""
    # the busy lanes are the burst's: compacted once, outside the scan
    plan = None if valid is None else lanes_plan(
        valid, state_impl(cfg, cfg.attn_impl))

    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh, state_plan=plan)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
