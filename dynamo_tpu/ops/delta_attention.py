"""Delta-rule linear attention with a decay a channel (Kimi Delta
Attention, KDA): a layer whose memory of the past is a matrix of fixed
size a head, not keys a token.

A head keeps `S` in R^{dk x dv} (float32).  A token decays it channel by
channel, corrects it by a rank-one delta rule and reads it:

    S'  = Diag(a_t) S_{t-1}                       a_t in (0, 1]^dk
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t * scale

`kda_step` is that recurrence for one token a lane (decode).
`kda_chunked` is the same map over a row of T tokens from a given state
(prefill), in chunks of `chunk` tokens: with G the cumulative log-decay
inside a chunk (inclusive), K~ = k * exp(G), u_t = beta_t (v_t - S'^T k_t)

    A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)          (s < t)
    (I + A) [U0 | W] = Diag(beta) [V | K~]      unit-triangular solve
    U    = U0 - W S_0                           S_0: the chunk's start
    O    = ((q * exp(G)) S_0 + B U) * scale     B as A with q_t, s <= t
    S_C  = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

so the solve runs over all chunks at once and only three matmuls a chunk
stay in the scan that hands the state on.

Every exponent is a DIFFERENCE of cumulative log-decays: a decay of e^-5
a token is 320 nats a chunk, and a factor exp(-G_s) would overflow
float32 at the 18th token.  A and B are matmuls all the same
(`_chunk_operands`): inside a sub-chunk of `sub` tokens the difference
factors through the decay at the sub-chunk's middle, G_t - G_s =
(G_t - Gm_i) + (Gm_i - G_s), each within +-sub/2 x 5 = 40 nats; towards
earlier sub-chunks the second factor only shrinks.  The unit-triangular
system is solved by forward substitution (rows inside a sub-chunk, then
sub-chunks), which is stable where equal keys make the powers of A
large.

State, log-decays, cumulative sums and every product with the state are
float32 (`Precision.HIGHEST`: the MXU's default would round the state
to bfloat16 on the way in).

The short convolution in front of q, k and v (`short_conv`,
`short_conv_step`) keeps the last `width - 1` inputs a lane beside the
state; `kda_gates` makes log a and beta.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------------------
# what stands in front of the rule
# ---------------------------------------------------------------------------


def conv_taps(xx: jax.Array, w: jax.Array, T: int,
              keep: jax.Array | None = None) -> jax.Array:
    """The taps of a causal depthwise convolution, the one copy of that
    arithmetic: xx [T + W - 1, C] the inputs with the W - 1 before them
    in front, w [W, C] -> sum_j w[j] * xx[j : j + T], float32; `w[-1]`
    meets the current input.  `keep` [W, T] bool, where given, says
    which taps a token may read (ops/gated_conv.py: a packed stream's
    taps stop at a row's first token); None reads them all."""
    xf, wf = xx.astype(F32), w.astype(F32)
    if keep is None:
        return sum(wf[j] * xf[j:j + T] for j in range(w.shape[0]))
    return sum(wf[j] * jnp.where(keep[j][:, None], xf[j:j + T], 0.0)
               for j in range(w.shape[0]))


def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array,
                true_len: jax.Array, bias: jax.Array | None = None):
    """Causal depthwise convolution over time, then SiLU.
    x [T, C] this chunk's inputs, tail [W - 1, C] the inputs before it
    (zeros at a sequence's start), w [W, C], bias [C] where the layer
    has one.  -> (c [T, C] float32, new tail [W - 1, C]: the last W - 1
    inputs up to the `true_len`-th, so padding behind it does not shift
    the tail)."""
    T, W = x.shape[0], w.shape[0]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=0)   # [T+W-1, C]
    c = conv_taps(xx, w, T)
    if bias is not None:
        c = c + bias.astype(F32)
    new_tail = jax.lax.dynamic_slice_in_dim(xx, true_len, W - 1, axis=0)
    return jax.nn.silu(c), new_tail.astype(tail.dtype)


def causal_conv_step(x: jax.Array, tail: jax.Array, w: jax.Array,
                     bias: jax.Array | None = None, act=jax.nn.silu):
    """One token a lane: x [B, C], tail [B, W - 1, C] -> (c [B, C]
    float32, new tail).  `act` None: the taps alone (ops/gated_conv.py,
    whose operator has no activation)."""
    xx = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    c = jnp.einsum("bwc,wc->bc", xx.astype(F32), w.astype(F32),
                   precision=HI)
    if bias is not None:
        c = c + bias.astype(F32)
    return (c if act is None else act(c)), xx[:, 1:].astype(tail.dtype)


# the same two under this family's scope (ops/ssm.py has them under its own)
short_conv = jax.named_scope("dyn.attn_conv")(causal_conv)
short_conv_step = jax.named_scope("dyn.attn_conv")(causal_conv_step)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(|x|^2 + eps) over the last axis (the public kernels'
    form: the eps keeps an all-zero row finite)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


@jax.named_scope("dyn.attn_gate")
def kda_gates(f: jax.Array, b: jax.Array, a_log: jax.Array,
              dt_bias: jax.Array, lower_bound: float):
    """f [..., H, dk] the decay's projection, b [..., H] beta's; a_log
    [H], dt_bias [H, dk] -> (log a [..., H, dk] in (lower_bound, 0),
    beta [..., H]), float32.  The bounded gate: log a = lower_bound *
    sigmoid(exp(A_log) * (f + dt_bias))."""
    rate = jnp.exp(a_log.astype(F32))[:, None]
    log_a = lower_bound * jax.nn.sigmoid(
        rate * (f.astype(F32) + dt_bias.astype(F32)))
    return log_a, jax.nn.sigmoid(b.astype(F32))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


@jax.named_scope("dyn.attn_delta")
def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
             beta: jax.Array, state: jax.Array, scale: float,
             valid: jax.Array | None = None):
    """One token a lane.  q, k, log_a [B, H, dk], v [B, H, dv], beta
    [B, H], state [B, H, dk, dv] float32 -> (o [B, H, dv], state).  A
    lane that is not `valid` keeps its state bit for bit."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    sp = jnp.exp(log_a)[..., None] * state                # S'
    # S'^T k and S'^T q in one pass over the state; the read of the NEW
    # state is then S'^T q + (k . q) u
    r_k = jnp.sum(sp * k[..., None], axis=-2)
    r_q = jnp.sum(sp * q[..., None], axis=-2)
    u = beta[..., None] * (v - r_k)
    o = (r_q + jnp.sum(k * q, -1, keepdims=True) * u) * scale
    new = sp + k[..., None] * u[..., None, :]
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, state)
    return o, new


def _chunk_operands(q, k, log_a, beta, sub: int):
    """Everything of a chunk that does not depend on the state, for
    arrays shaped [..., C, dk] (beta [..., C]): the cumulative log-decay
    G, A (strictly lower, rows times beta) and B (lower), every entry a
    matmul's.  exp(G_t - G_s) factors through the decay at the MIDDLE of
    t's sub-chunk, Gm_i: the query's factor exp(G_t - Gm_i) and, for a
    key of the same sub-chunk, exp(Gm_i - G_s) lie within
    exp(+-sub/2 x |lower bound|) (8 tokens at -5: 40 nats; float32
    holds 87); an earlier key's factor is <= 1 and may underflow to 0
    with its product; later keys are masked.  The cumulative sums run
    inside a sub-chunk and then over the sub-chunks' totals, so that
    neighbours across a boundary differ by their own decay exactly."""
    *lead, C, dk = k.shape
    n = C // sub
    split = lambda x: x.reshape(*lead, n, sub, dk)
    since = jnp.cumsum(split(log_a), axis=-2)      # from the sub-chunk's start
    total = since[..., -1, :]
    Gb = jnp.cumsum(total, axis=-2) - total        # up to the sub-chunk's start
    G = (Gb[..., None, :] + since).reshape(*lead, C, dk)
    mid = since[..., (sub - 1) // 2, :]
    rel = jnp.exp(since - mid[..., None, :])             # [.., n, sub, dk]
    seen = (Gb + mid)[..., :, None, :] - G[..., None, :, :]    # [.., n, C, dk]
    later = jnp.arange(C) // sub > jnp.arange(n)[:, None]          # [n, C]
    k_seen = k[..., None, :, :] * jnp.exp(
        jnp.where(later[..., None], 0.0, seen))
    pairs = lambda x: jnp.einsum(
        "...itc,...isc->...its", split(x) * rel, k_seen,
        precision=HI).reshape(*lead, C, C)
    r = jnp.arange(C)
    A = jnp.where(r[:, None] > r[None, :], pairs(k), 0.0) * beta[..., None]
    B = jnp.where(r[:, None] >= r[None, :], pairs(q), 0.0)
    return G, A, B


def _unit_lower_inverse(A: jax.Array, sub: int) -> jax.Array:
    """(I + A)^-1 for strictly lower A [..., C, C] by forward
    substitution, which is stable where a product of (I + A^(2^j)) is
    not (equal keys, beta 1, no decay: the powers grow as C^p / p!
    before they cancel).  A row at a time inside the diagonal blocks of
    `sub`, all blocks side by side: T[t] = e_t - sum_{s<t} A[t, s] T[s];
    then a block row at a time, T_i = (I + A_ii)^-1 (E_i - A_i,<i T_<i),
    two matmuls each.  (On a v5e, 2048 tokens x 32 heads: 1.30 ms; rows
    over the whole chunk 3.54, XLA's triangular solve 3.99; my chip
    runs, PR 35.)"""
    *lead, C, _ = A.shape
    n = C // sub
    blk = A.reshape(*lead, n, sub, n, sub)
    diag = jnp.stack([blk[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(C, dtype=A.dtype)

    def row(t, T):
        a = jax.lax.dynamic_index_in_dim(diag, t, axis=-2, keepdims=False)
        new = eye[t, :sub] - jnp.sum(a[..., :, None] * T, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(T, new, t, axis=-2)

    inv = jax.lax.fori_loop(0, sub, row, jnp.zeros_like(diag))
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    rows = []
    for i in range(n):
        rhs = jnp.broadcast_to(eye[i * sub:(i + 1) * sub], (*lead, sub, C))
        if i:
            rhs = rhs - mm(A[..., i * sub:(i + 1) * sub, :i * sub],
                           jnp.concatenate(rows, axis=-2))
        rows.append(mm(inv[..., i, :, :], rhs))
    return jnp.concatenate(rows, axis=-2)


@jax.named_scope("dyn.attn_delta")
def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
                beta: jax.Array, state: jax.Array, scale: float,
                chunk: int = 64, sub: int = 16):
    """A row of T tokens from `state`.  q, k, log_a [T, H, dk], v
    [T, H, dv], beta [T, H], state [H, dk, dv] float32 -> (o [T, H, dv]
    float32, state after the last token).  A token with beta 0 and
    log a 0 (padding) leaves the state as it was.

    This is the REFERENCE form of the chunked rule, the path off the
    chip and the path of a row under one chunk.  Where ops/lane_state.py
    `resolve_chunk_impl` says so (a TPU, a float32 state of whole tiles,
    a row of whole chunks) a prefill program runs the same arithmetic as
    ONE Pallas call a layer, ops/pallas_chunk_state.py `kda_chunk_rows`,
    which keeps a chunk's operands, everything made of them here
    (k_seen, A, B, the inverse, the solve) and the carried state in VMEM
    (PR 45); tests/test_chunk_state_kernel.py holds it to this form and
    to `kda_step`."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    C = chunk if T >= chunk else -(-T // sub) * sub
    N = -(-T // C)
    pad = N * C - T

    def chunks(x):      # [T, H, ...] -> [N, H, C, ...], zero-padded
        x = jnp.pad(x.astype(F32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(N, C, *x.shape[1:]), 1, 2)

    q, k, v, log_a, beta = map(chunks, (q, k, v, log_a, beta))
    G, A, B = _chunk_operands(q, k, log_a, beta, sub)
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(G)], -1)
    sol = jnp.matmul(_unit_lower_inverse(A, sub), rhs, precision=HI)
    U0, W = sol[..., :dv], sol[..., dv:]
    G_end = G[..., -1:, :]                               # [N, H, 1, dk]
    q_in = q * jnp.exp(G)
    k_out = k * jnp.exp(G_end - G)
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)

    def step(S, xs):
        U0, W, B, q_in, k_out, decay = xs
        U = U0 - mm(W, S)                                # [H, C, dv]
        o = mm(q_in, S) + mm(B, U)
        S = decay[..., None] * S + mm(jnp.swapaxes(k_out, -1, -2), U)
        return S, o

    state, o = jax.lax.scan(
        step, state, (U0, W, B, q_in, k_out, jnp.exp(G_end[..., 0, :])))
    o = jnp.moveaxis(o, 1, 2).reshape(N * C, H, dv)[:T]
    return o * scale, state
