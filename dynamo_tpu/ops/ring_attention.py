"""Ring attention: sequence-parallel exact attention for long context.

The TPU-native answer to the reference's long-sequence context parallelism
(SURVEY.md §2.4 SP row).  The sequence axis is sharded over an "sp" mesh
axis; each device holds one Q shard and one KV shard.  The kernel runs
axis_size steps of flash-style online softmax, rotating the KV shard one
hop around the ring with `lax.ppermute` per step, so

  * memory per device is O(T / sp) — context length scales linearly with
    the ring size,
  * the rotation rides the ICI ring (neighbor exchange, the topology's
    native pattern), overlapped by XLA with the per-step attention matmuls,
  * the result is EXACT attention (online-softmax rescaling, no
    approximation), verified against the single-device reference in
    tests/test_ring_attention.py.

Design notes (vs a naive translation of GPU ring attention):
  - accumulators stay in float32 regardless of input dtype (bf16-safe);
  - causal masking is done with *global* positions derived from
    `axis_index`, so per-step masks are static-shape and jit-friendly;
  - fully-masked (future) chunks still rotate — the ppermute schedule is
    uniform across devices, which XLA requires — but their contribution is
    exp(-inf) = 0 under the masked online-softmax update, so correctness
    does not depend on skipping them.

GQA is supported: kv_heads may divide q_heads; KV shards carry only the
kv_heads, the kernel broadcasts over the head-group axis on the fly.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = -1e30  # finite -inf stand-in: keeps exp/max NaN-free


def _online_update(o, m, l, s, v):
    """One flash-attention accumulator update, grouped GQA layout.

    o [T, G, R, D] f32, m/l [T, G, R] f32, s [T, G, R, Tk] f32 scores
    (already masked), v [Tk, G, D] — G = kv heads, R = q heads per group."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows with no unmasked key yet: keep exponent base at 0 to avoid
    # exp(large) — their p and alpha both come out 0/1 harmlessly
    base = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - base[..., None])           # [T, G, R, Tk]
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(jnp.where(m <= _NEG_INF / 2, 0.0, m) - base)
    alpha = jnp.where(m <= _NEG_INF / 2, jnp.where(m_new <= _NEG_INF / 2,
                                                   1.0, 0.0), alpha)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("tgrs,sgd->tgrd", p, v.astype(jnp.float32))
    o_new = o * alpha[..., None] + pv
    return o_new, m_new, l_new


def _ring_shard(q, k, v, *, axis_name: str, causal: bool, sm_scale: float):
    """Per-device body under shard_map.  q [Tq, Hq, D]; k,v [Tk, Hkv, D]."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    tq, hq, d = q.shape
    tk, hkv = k.shape[0], k.shape[1]
    # grouped GQA layout end-to-end: [T, G=hkv, R=hq//hkv, ...]
    qg = q.reshape(tq, hkv, hq // hkv, d).astype(jnp.float32)
    q_pos = my_idx * tq + jnp.arange(tq)  # global query positions

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def attend(o, m, l, kr, vr, src):
        k_pos = src * tk + jnp.arange(tk)
        s = jnp.einsum("tgrd,sgd->tgrs", qg,
                       kr.astype(jnp.float32)) * sm_scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]      # [Tq, Tk]
            s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
        return _online_update(o, m, l, s, vr)

    def step(i, carry):
        o, m, l, kr, vr = carry
        # rotate FIRST: the i=0 (resident-shard) contribution is computed
        # outside the loop, so no dead permute after the final step
        kr = lax.ppermute(kr, axis_name, perm)
        vr = lax.ppermute(vr, axis_name, perm)
        # after i forward hops the resident shard originated at ring
        # position (my_idx - i) mod axis_size
        src = (my_idx - i) % axis_size
        o, m, l = attend(o, m, l, kr, vr, src)
        return o, m, l, kr, vr

    # constants start device-invariant; the accumulators become
    # device-varying after one update, so align the carry types
    # (varying-manual-axes tracking)
    def varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    o = varying(jnp.zeros((tq, hkv, hq // hkv, d), jnp.float32))
    m = varying(jnp.full((tq, hkv, hq // hkv), _NEG_INF, jnp.float32))
    l = varying(jnp.zeros((tq, hkv, hq // hkv), jnp.float32))
    o, m, l = attend(o, m, l, k, v, my_idx)
    o, m, l, _, _ = lax.fori_loop(1, axis_size, step, (o, m, l, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
    return (o / l[..., None]).reshape(tq, hq, d).astype(q.dtype)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
    axis_name: str = "sp", causal: bool = True,
    sm_scale: Optional[float] = None, head_axis: Optional[str] = None,
) -> jax.Array:
    """Exact attention with the sequence axis sharded over `axis_name`.

    q [B, T, Hq, D], k/v [B, T, Hkv, D]; T must divide evenly by the sp
    axis size.  When the head axis is tensor-sharded, pass its mesh axis as
    `head_axis` so each tp shard keeps only its own heads (the ring runs
    per head-shard; omitting it would all-gather heads and redo every
    head's FLOPs on every tp device).  Returns [B, T, Hq, D] sharded like
    the inputs."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    body = partial(_ring_shard, axis_name=axis_name, causal=causal,
                   sm_scale=sm_scale)
    spec = P(None, axis_name, head_axis, None)
    fn = shard_map(
        jax.vmap(body, in_axes=(0, 0, 0)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)


def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """Single-device exact attention (the oracle for ring tests).

    Same shapes/semantics as ring_attention, computed globally."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    s = jnp.einsum(
        "btgrd,bsgd->btgrs",
        q.reshape(b, t, hkv, hq // hkv, d).astype(jnp.float32),
        k.astype(jnp.float32),
    ) * sm_scale
    if causal:
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("btgrs,bsgd->btgrd", p, v.astype(jnp.float32))
    return o.reshape(b, t, hq, d).astype(q.dtype)
