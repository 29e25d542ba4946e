"""The Mamba-2 mixer (ops/ssm.py's recurrence between its projections,
its convolution and its gated norm) for every family that has one:
models/nemotron_h.py (one mixer a block, B and C a group of eight heads)
and models/granite_hybrid.py (a mixer and an MLP a layer, ONE B and C for
all heads).  It knows widths (`Mamba2Dims`), a layer's parameter tree and
the two lane-addressed cache members; it imports no family.

    [z | xBC | dt~] = h W_in                       one matmul, that order
    xBC = SiLU(conv_W(xBC) + b)                    depthwise, causal
    x [H, P], B, C [G, N] = split(xBC)
    dt = softplus(dt~ + dt_bias),  A = -exp(A_log) a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D x_t
    out = W_out (w * GroupRMSNorm_G(y * SiLU(z)))  gate, THEN norm

The projections take bf16 operands and keep their float32 accumulator;
state, dt and the decays are float32 (ops/ssm.py), the tail is in the
weights' dtype.  `state` [layers of the kind, lanes, H, P, N] and `tail`
[layers of the kind, lanes, W - 1, conv_dim] (or those rows end to end:
`state_shapes`) are addressed by LANE and
their life is ops/lane_state.py's: `mixer_prefill` takes each row's entry
from its lane (zeros where the row starts at position 0), runs the
chunked scan with padding switched off (dt 0 and a zeroed input) and
puts both back where they lie; `mixer_decode` steps the busy lanes'
state in place (`lanes_step`: the kernel of ops/pallas_lane_state.py
where `state_impl` says so) and keeps the idle lanes' tail.  Neither
adds the residual: the caller does, with its own multiplier.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.lane_state import (
    lanes_keep,
    lanes_step,
    resolve_state_impl,
    rows_put,
    rows_start,
)
from ..ops.packed_prefill import resolve_packed_impl
from ..ops.paged_attention import PALLAS_IMPLS
from ..ops.pallas_lane_state import LanePlan, ssd_lanes_step
from ..ops.ssm import (
    gated_group_norm,
    ssd_chunked,
    ssd_step,
    ssm_conv,
    ssm_conv_step,
    ssm_dt,
)


@dataclass(frozen=True)
class Mamba2Dims:
    """What the mixer needs of a family's config."""
    heads: int
    head_dim: int
    state: int
    groups: int               # B and C are a group's; the gated norm's
    conv_width: int
    chunk: int                # tokens a chunk of the chunked form
    eps: float
    dtype: Any                # the weights' and the tail's
    state_dtype: Any = jnp.float32

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """x, B and C side by side."""
        return self.inner + 2 * self.groups * self.state

    @property
    def in_dim(self) -> int:
        """z | x B C | dt side by side: W_in's columns."""
        return self.inner + self.conv_dim + self.heads


def mm(a: jax.Array, w: jax.Array) -> jax.Array:
    """a @ w with the accumulator kept: operands in the weights' dtype,
    the result float32 (what the MXU sums in anyway)."""
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def state_shapes(dims: Mamba2Dims, n_layers: int, lanes: int,
                 flat_tail: bool = False) -> Tuple[tuple, tuple]:
    """(state, conv tail): one entry a lane and Mamba layer.  A lane's
    tail is [W - 1, conv_dim], or with `flat_tail` those rows end to end:
    the mixer takes either (it reshapes what it is given).  Over the
    4-D member XLA's TPU layout for a prefill row's gather and scatter
    puts the W - 1 = 3 axis minor and pads it to 128 lanes: a padded
    copy of the WHOLE member as a temporary of the program, 2.39 GB at
    36 layers x 64 lanes x 4352 channels (compiled for a described v5e,
    PR 57); the flat member has no short axis to pad."""
    tail = (dims.conv_width - 1, dims.conv_dim)
    return ((n_layers, lanes, dims.heads, dims.head_dim, dims.state),
            (n_layers, lanes) + ((math.prod(tail),) if flat_tail else tail))


def state_impl(dims: Mamba2Dims, attn_impl: str) -> str:
    """The impl of the state's decode step under `attn_impl`, by the
    state's own conditions (ops/lane_state.resolve_state_impl), asked by
    the traced step and by the host's counts alike."""
    return resolve_state_impl(attn_impl, jax.default_backend(),
                              dims.head_dim, dims.state, dims.state_dtype)


def decode_counts(dims: Mamba2Dims, n_attn: int, n_mamba: int,
                  ctx: np.ndarray, k: int, block_size: int, lanes: int,
                  table_width: int, attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens (engine/core.py _count_decode_attn), for a
    family of `n_mamba` Mamba-2 layers beside `n_attn` GQA layers.  The
    attention layers' cache blocks, summed over layers and steps: `live`
    what the mask needs, `read` what the impl that runs moves (the kernel
    each step's live blocks, the gathering read every lane's whole
    table).  And the state pool's lanes: each active lane moves one
    state a Mamba layer a step, out of `lanes` slots that a step's
    program runs over; `state_live` those lane steps over the Mamba
    layers, `state_moved` the lanes whose state the step that runs moves
    (`state_impl`: the kernel the busy ones, the jnp step every slot)."""
    live = int((-(-(ctx[:, None] + 1 + np.arange(k)[None, :])
                  // block_size)).sum())
    read = live if attn_impl in PALLAS_IMPLS else k * lanes * table_width
    return {
        "decode_attn_live_blocks": n_attn * live,
        "decode_attn_read_blocks": n_attn * read,
        "ssm_lane_steps.decode": k * len(ctx),
        "ssm_slot_steps.decode": k * lanes,
        "state_live_lane_steps.decode": n_mamba * k * len(ctx),
        "state_moved_lane_steps.decode": n_mamba * k * (
            len(ctx) if state_impl(dims, attn_impl) in PALLAS_IMPLS
            else lanes),
    }


def prefill_counts(cfg, n_attn: int, pos: int, chunk: int,
                   bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos` in a program of `bucket` rows: tokens through the
    chunked scan, the bucket's rows beyond them (what padding costs the
    scan), tokens in a program that started from a carried state, rows
    that started from zeros; the tokens the `n_attn` attention layers'
    prefill read took, and those of them whose program ran it in the
    kernel: the rule the traced read applies to its cache
    (ops/packed_prefill.resolve_packed_impl), asked from the host as
    `deepseek.mla_prefill_impl` asks its own.  The host has no cache to
    show: it asks about the engine's default pool, 128-token blocks in
    the configuration's dtype, and about one row a program (the stream
    is the bucket; `Bp` rows make a stream `Bp` buckets long).  `cfg` is
    the family's config (`packed_attn_impl`, `head_dim`, `dtype`,
    `n_heads`, `n_kv_heads`)."""
    gqa = n_attn * chunk
    kernel = resolve_packed_impl(
        cfg.packed_attn_impl, jax.default_backend(), 128, cfg.head_dim,
        cfg.dtype, bucket, cfg.n_heads // cfg.n_kv_heads) in PALLAS_IMPLS
    return {
        "ssm_tokens.prefill": chunk,
        "ssm_pad_tokens.prefill": max(bucket - chunk, 0),
        "ssm_carried_tokens.prefill": chunk if pos > 0 else 0,
        "ssm_resets": int(chunk > 0 and pos == 0),
        "gqa_prefill_tokens.prefill": gqa,
        "gqa_prefill_kernel_tokens.prefill": gqa if kernel else 0,
    }


def init_mixer(dims: Mamba2Dims, d_model: int, k: Sequence[jax.Array],
               dense) -> Dict[str, Any]:
    """A layer's mixer parameters from eight keys.  What a Mamba layer
    adds to its matrices (A_log, dt_bias, D, the convolution and its
    bias, the gated norm's weight) is random so that leaving one out
    changes the answer.  `dense(key, shape)` is the family's matrix
    draw."""
    H = dims.heads
    return {
        # z | x B C | dt side by side: one matmul
        "w_in": dense(k[0], (d_model, dims.in_dim)),
        "conv_w": (jax.random.normal(
            k[1], (dims.conv_width, dims.conv_dim), jnp.float32)
            * 0.5).astype(dims.dtype),
        "conv_b": (jax.random.normal(
            k[2], (dims.conv_dim,), jnp.float32) * 0.5
            ).astype(dims.dtype),
        # dt = softplus(. + dt_bias) around 0.01 ... 1, A in
        # -(1 ... 16): a token forgets between nothing and most
        "dt_bias": jax.random.uniform(k[3], (H,), jnp.float32, -4.0, 0.5),
        "a_log": jnp.log(jax.random.uniform(k[4], (H,), jnp.float32,
                                            1.0, 16.0)),
        "d_skip": 1.0 + 0.5 * jax.random.normal(k[5], (H,), jnp.float32),
        "gate_norm": {"norm": 1.0 + 0.1 * jax.random.normal(
            k[6], (dims.inner,), jnp.float32)},
        "w_out": dense(k[7], (dims.inner, d_model)),
    }


def dense_init(dtype):
    """The families' matrix draw: N(0, 1 / fan_in) unless `scale`."""
    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype)
    return dense


@jax.named_scope("dyn.ssm_proj")
def _ssm_in(layer, dims: Mamba2Dims, h: jax.Array):
    """h [..., d] -> (z [..., inner], x B C side by side before the
    convolution [..., conv_dim], dt~ [..., H]), float32."""
    zxd = mm(h.astype(dims.dtype), layer["w_in"])
    a, b = dims.inner, dims.inner + dims.conv_dim
    return zxd[..., :a], zxd[..., a:b], zxd[..., b:]


def _ssm_heads(dims: Mamba2Dims, conv: jax.Array):
    """The convolved channels [..., conv_dim] -> x [..., H, P], B and C
    [..., G, N]."""
    gn = dims.groups * dims.state
    x, b, c = (conv[..., :dims.inner],
               conv[..., dims.inner:dims.inner + gn],
               conv[..., dims.inner + gn:])
    lead = conv.shape[:-1]
    return (x.reshape(*lead, dims.heads, dims.head_dim),
            b.reshape(*lead, dims.groups, dims.state),
            c.reshape(*lead, dims.groups, dims.state))


def _ssm_out(layer, dims: Mamba2Dims, y: jax.Array, z: jax.Array):
    """y [..., H, P] float32 the scan's read, z [..., inner] the gate's
    projection -> [..., d]."""
    g = gated_group_norm(y.reshape(*y.shape[:-2], dims.inner), z,
                         layer["gate_norm"]["norm"], dims.groups, dims.eps)
    with jax.named_scope("dyn.ssm_proj"):
        return mm(g.astype(dims.dtype), layer["w_out"])


def mixer_prefill(layer, dims: Mamba2Dims, h: jax.Array, state: jax.Array,
                  tail: jax.Array, pli: int, lanes: jax.Array,
                  fresh: jax.Array, put: jax.Array, valid: jax.Array,
                  true_lens: jax.Array, hold_start: bool = False):
    """h [Bp, T, d] the normed float32 stream of padded rows -> (the
    mixer's output [Bp, T, d] float32, state, tail).  `lanes` [Bp] each
    row's lane, `fresh` [Bp] rows that start at position 0, `put` [Bp]
    `rows_target`'s lanes (a row of no tokens writes nothing), `valid`
    [Bp, T] the real tokens, `true_lens` [Bp].  `hold_start` makes the
    rows' start state ONE value that every reader takes (a barrier), for
    a member too large to copy: a row of one chunk is a scan of length
    1, which XLA unrolls and then fuses the slice of the member into the
    scan's readers one by one; with 36 such layers its 32- to 256-token
    programs ordered a reader after the in-place put in the last two
    layers and COPIED the member to keep it (4.5 GiB: the program did
    not fit; compiled for a described v5e and met on the chip, PR 57)."""
    z, xbc, dt = _ssm_in(layer, dims, h)
    t0 = rows_start(tail, pli, lanes, fresh).reshape(
        -1, dims.conv_width - 1, dims.conv_dim)
    s0 = rows_start(state, pli, lanes, fresh).astype(jnp.float32)
    if hold_start:
        t0, s0 = jax.lax.optimization_barrier((t0, s0))
    conv, t1 = jax.vmap(ssm_conv, in_axes=(0, 0, None, 0, None))(
        xbc, t0, layer["conv_w"], true_lens, layer["conv_b"])
    # padding: no decay (dt 0) and nothing fed (x 0)
    conv = jnp.where(valid[..., None], conv, 0.0)
    dt = jnp.where(valid[..., None], ssm_dt(dt, layer["dt_bias"]), 0.0)
    xs, b, c = _ssm_heads(dims, conv)
    y, s1 = jax.vmap(
        partial(ssd_chunked, chunk=dims.chunk),
        in_axes=(0, 0, None, 0, 0, None, 0))(
        xs, dt, -jnp.exp(layer["a_log"]), b, c, layer["d_skip"], s0)
    state = rows_put(state, pli, put, s1)
    tail = rows_put(tail, pli, put, t1.reshape(-1, *tail.shape[2:]))
    return _ssm_out(layer, dims, y, z), state, tail


def mixer_decode(layer, dims: Mamba2Dims, h: jax.Array, state: jax.Array,
                 tail: jax.Array, pli: int, plan: LanePlan, s_impl: str,
                 live: jax.Array):
    """h [lanes, d] the normed float32 stream, one token a lane (rows
    ARE lanes) -> (the mixer's output [lanes, d] float32, state, tail).
    `plan` is `lanes_plan`'s for `s_impl` (`state_impl`'s answer); a
    lane that is not `live` keeps state and tail as they were."""
    z, xbc, dt = _ssm_in(layer, dims, h)
    conv, t1 = ssm_conv_step(
        xbc, tail[pli].reshape(-1, dims.conv_width - 1, dims.conv_dim),
        layer["conv_w"], layer["conv_b"])
    xs, b, c = _ssm_heads(dims, conv)
    rule = (xs, ssm_dt(dt, layer["dt_bias"]), -jnp.exp(layer["a_log"]),
            b, c, layer["d_skip"])
    y, state = lanes_step(state, pli, plan, partial(ssd_step, *rule),
                          partial(ssd_lanes_step, *rule), s_impl)
    old = tail[pli]
    tail = tail.at[pli].set(lanes_keep(live, t1.reshape(old.shape), old))
    return _ssm_out(layer, dims, y, z), state, tail
