"""Mamba-2 state-space layers (SSD: a selective state space whose decay
is ONE scalar a head and step): a layer whose memory of the past is a
matrix of fixed size a head, not keys a token.

A head of width P keeps `S` in R^{P x N} (float32).  With dt_t > 0 a
head and step, A < 0 a head, B_t and C_t in R^N shared by the heads of a
group:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

`ssd_step` is that recurrence for one token a lane (decode).
`ssd_chunked` is the same map over a row of T tokens from a given state
(prefill), in chunks of `chunk` tokens: with l_t the running sum of
dt A inside a chunk (inclusive; every l <= 0 and falling),

    y_t    = sum_{s<=t} exp(l_t - l_s) (C_t . B_s) dt_s x_s       intra
             + exp(l_t) S_prev C_t + D x_t                         inter
    S_next = exp(l_T) S_prev + sum_s exp(l_T - l_s) dt_s x_s B_s^T

Every exponent is a difference l_t - l_s with s <= t, so it is <= 0 and
nothing overflows; the later keys are masked BEFORE the exponential.
The intra-chunk part and each chunk's own feed are computed for all
chunks at once; only the state's decay-and-add and its read stay in the
scan that hands the state on.  A token with dt = 0 and x = 0 (a
bucket's padding) decays by 1 and feeds nothing: the state after a
padded row is the state after its last real token.

This is not ops/delta_attention.py with other numbers: the decay is a
scalar a head (so exp(l_t - l_s) is one [C, C] matrix a head, no
sub-chunks), there is no delta-rule correction (so no triangular
solve), the state is not square, B and C are grouped, and the gate comes
after the read (`gated_group_norm`).

State, dt, the cumulative log-decays, the decay matrix and every product
with the state are float32 (`Precision.HIGHEST`: the MXU's default would
round the state to bfloat16 on the way in).

`ssm_conv` / `ssm_conv_step`: the causal depthwise convolution (with a
bias) in front of x, B and C, ops/delta_attention.py's under this
layer's scope; the last `width - 1` inputs a lane live beside the state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .delta_attention import F32, HI, causal_conv, causal_conv_step

ssm_conv = jax.named_scope("dyn.ssm_conv")(causal_conv)
ssm_conv_step = jax.named_scope("dyn.ssm_conv")(causal_conv_step)


def ssm_dt(dt_raw: jax.Array, dt_bias: jax.Array) -> jax.Array:
    """[..., H] the projection -> dt = softplus(dt~ + dt_bias), float32
    (time_step_limit (0, inf): no clamp)."""
    return jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))


@jax.named_scope("dyn.ssm_scan")
def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: jax.Array, state: jax.Array,
             valid: jax.Array | None = None):
    """One token a lane.  x [B, H, P], dt [B, H] (after softplus), a [H]
    (negative), b, c [B, G, N], d_skip [H], state [B, H, P, N] float32
    -> (y [B, H, P] float32, state).  A lane that is not `valid` keeps
    its state: it decays by 1 and is fed 0.

    Both results come from ONE read of the old state: the read of the
    new state is S_t C = exp(dt A) (S_{t-1} C) + dt x (B . C), so the
    reduction over N and the elementwise update are two consumers of the
    same operand and neither waits for the other's pass over 2 MB a lane
    and layer."""
    Bn, H, P = x.shape
    G, N = b.shape[1:]
    R = H // G
    x, b, c = x.astype(F32), b.astype(F32), c.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))                       # [B, H]
    feed = dt[..., None] * x                                  # [B, H, P]
    if valid is not None:
        decay = jnp.where(valid[:, None], decay, 1.0)
        feed = jnp.where(valid[:, None, None], feed, 0.0)
    # heads of a group side by side: B and C broadcast, never repeated
    s = state.reshape(Bn, G, R, P, N)
    decay, feed = decay.reshape(Bn, G, R, 1), feed.reshape(Bn, G, R, P)
    bg, cg = b[:, :, None, None, :], c[:, :, None, None, :]
    new = decay[..., None] * s + feed[..., None] * bg
    y = decay * jnp.sum(s * cg, axis=-1) \
        + feed * jnp.sum(b * c, axis=-1)[:, :, None, None]
    y = y.reshape(Bn, H, P) + d_skip.astype(F32)[:, None] * x
    return y, new.reshape(Bn, H, P, N)


@jax.named_scope("dyn.ssm_scan")
def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d_skip: jax.Array, state: jax.Array,
                chunk: int = 128):
    """A row of T tokens from `state`.  x [T, H, P], dt [T, H] (after
    softplus; 0 on padding, where x is 0 too), a [H], b, c [T, G, N],
    d_skip [H], state [H, P, N] float32 -> (y [T, H, P] float32, state
    after the last token)."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    R = H // G                                   # heads a group
    C = chunk if T >= chunk else T
    n = -(-T // C)
    pad = n * C - T

    def chunks(v):          # [T, ...] -> [n, C, ...], zero-padded
        v = jnp.pad(v.astype(F32), ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape(n, C, *v.shape[1:])

    xc, dtc, bc, cc = map(chunks, (x, dt, b, c))
    xg = xc.reshape(n, C, G, R, P)
    la = jnp.cumsum(dtc * a.astype(F32), axis=1)              # [n, C, H]
    lg = la.reshape(n, C, G, R)
    # the decay matrix exp(l_t - l_s), s <= t (masked before the exp)
    r = jnp.arange(C)
    diff = lg[:, :, None] - lg[:, None, :]                 # [n, t, s, G, R]
    decay = jnp.exp(jnp.where((r[:, None] >= r[None, :])[None, :, :, None,
                                                          None],
                              diff, -jnp.inf))
    cb = jnp.einsum("ntgk,nsgk->ntsg", cc, bc, precision=HI)
    m = cb[..., None] * decay * dtc.reshape(n, 1, C, G, R)
    y = jnp.einsum("ntsgr,nsgrp->ntgrp", m, xg, precision=HI)
    # each chunk's own feed to the state that leaves it
    to_end = jnp.exp(lg[:, -1:] - lg) * dtc.reshape(n, C, G, R)
    feed = jnp.einsum("nsgrp,nsgk->ngrpk", to_end[..., None] * xg, bc,
                      precision=HI)                        # [n, G, R, P, N]
    total = jnp.exp(lg[:, -1])                             # [n, G, R]

    def step(s, xs):
        feed, total, cc, since = xs
        # what the chunk's tokens read of the state that entered it
        y_in = jnp.einsum("grpk,tgk->tgrp", s, cc, precision=HI) \
            * since[..., None]
        return total[..., None, None] * s + feed, y_in

    state, y_in = jax.lax.scan(
        step, state.astype(F32).reshape(G, R, P, N),
        (feed, total, cc, jnp.exp(lg)))
    y = (y + y_in + d_skip.astype(F32).reshape(G, R, 1) * xg)
    return y.reshape(n * C, H, P)[:T], state.reshape(H, P, N)


@jax.named_scope("dyn.ssm_gate")
def gated_group_norm(y: jax.Array, z: jax.Array, w: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """The read's gate and norm: y * SiLU(z) first, then an RMS norm
    over each of `groups` groups of the channels, times w.  y, z
    [..., D], w [D] -> [..., D] float32."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    gg = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True)
                            + eps)
    return gg.reshape(g.shape) * w.astype(F32)
