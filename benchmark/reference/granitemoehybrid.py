"""Mamba-2 layers beside a few NoPE GQA attention layers, every layer a
mixer AND a gated MLP, under muP multipliers (Granite-4.0-H-Micro: the
dense member of `granitemoehybrid`, `num_local_experts` 0): plain
float32 reference.

The published model, written out (ISSUE 57, "The equations"; the
configuration file's `assumed` lists what the config does not settle).
The reading of every key is `modeling_granitemoehybrid.py` in
transformers, written down without the file at hand: where that
implementation is known to differ it wins.

    RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w
    x_0 = embedding_multiplier * E[token]
    layer i, kind layer_types[i]:
        x += residual_multiplier * mixer_i(RMSNorm(x))
        [g | u] = RMSNorm(x) W_in          2048 -> 2 x 8192, gate first
        x += residual_multiplier * (silu(g) * u) W_out
    logits = RMSNorm(x) E^T / logits_scaling        (tied head)

`mamba`, H heads of P, state N, G groups (published: 64, 64, 128, 1):
    [z | xBC~ | dt~] = h W_in                       in that order
    xBC_t = SiLU(b + sum_{j<4} w_j xBC~_{t-3+j})    a channel, zeros before
                                                    the sequence
    x_t [H, P], B_t, C_t [G, N] = split(xBC_t)      head h reads group
                                                    h div (H / G)
    dt_t = softplus(dt~_t + dt_bias),  A = -exp(A_log)   (a head; no clamp:
                                                    time_step_limit (0, inf))
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      (S in R^{P x N})
    y_t = S_t C_t + D x_t
    out = W_out (w * RMSNorm_G(y_t * SiLU(z_t)))    gate, THEN norm
as the TOKEN-BY-TOKEN recurrence, a `lax.scan` over positions: the
program's chunked form shares nothing with it.

`attention`: 32 query heads over 8 KV heads of 64, no bias, NO rotary
(`position_embedding_type` "nope"), causal softmax of
q . k * attention_multiplier (1/64: NOT 1 / sqrt(64)), o W_o.

Whole sequence at once, no cache, no kernels, no batching, no chunks,
nothing imported from the program's ops.  It reads the engine's
parameter tree (one layer a position of the repeating period, every leaf
stacked over the periods: layer by layer here, in a Python loop; bf16
weights cast to float32 where they are used);
attention scores are formed `ATTN_ROWS` queries at a time and the output
head only at the positions asked for, so that a few thousand positions
at published widths fit beside the engine.  `leave_out` lets a test drop
or bend one published detail at a time and see that the comparison
notices.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _rms, _rope

ATTN_ROWS = 256         # queries of an attention layer's scores at a time
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out or bend, one at a time
# (tests/test_granite_hybrid.py)
DETAILS = ("no_residual_multiplier", "no_embedding_multiplier",
           "attention_scale_sqrt", "no_logits_scaling", "rope",
           "untied_head", "norm_before_gate")


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's
    GraniteHybridConfig; refuses what the program refuses
    (granite_hybrid.from_hf)."""
    from dynamo_tpu.models.granite_hybrid import from_hf

    return from_hf(hf, name)


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one attention layer: q.k and
    p.v, a multiply and an add each, per query head.  (A Mamba layer has
    no pairs: its cost a token is fixed.)"""
    return 4.0 * cfg.n_heads * cfg.head_dim


def token_recurrence(x, dt, a, b, c, d_skip, S):
    """The state-space recurrence itself, a token at a time: x [T, H, P],
    dt [T, H], a [H], b, c [T, G, N], d_skip [H], S [H, P, N] -> (y
    [T, H, P], S after the last token).  Head h reads group h div (H / G)."""
    rep = x.shape[1] // b.shape[1]

    def token(S, xs):
        x, dt, b, c = xs
        b, c = jnp.repeat(b, rep, axis=0), jnp.repeat(c, rep, axis=0)
        S = jnp.exp(dt * a)[:, None, None] * S \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c) + d_skip[:, None] * x

    S, y = jax.lax.scan(token, S, (x, dt, b, c))
    return y, S


def _mamba(cfg, p, h, leave_out):
    """h [T, d] normed input -> (the mixer's output [T, d], the state
    after the last token [H, P, N])."""
    T = h.shape[0]
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    W, inner = cfg.conv_width, cfg.ssm_heads * cfg.ssm_head_dim
    zxd = h @ p["w_in"]
    z, pre, dt = (zxd[:, :inner], zxd[:, inner:inner + inner + 2 * G * N],
                  zxd[:, inner + inner + 2 * G * N:])
    padded = jnp.concatenate([jnp.zeros((W - 1, pre.shape[1]), F32), pre], 0)
    c = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + T] for j in range(W))
                    + p["conv_b"])
    x = c[:, :inner].reshape(T, H, P)
    b = c[:, inner:inner + G * N].reshape(T, G, N)
    cc = c[:, inner + G * N:].reshape(T, G, N)
    y, S = token_recurrence(
        x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]), b, cc,
        p["d_skip"], jnp.zeros((H, P, N), F32))
    y = y.reshape(T, inner)
    w = p["gate_norm"]["norm"]

    def norm(v):
        g = v.reshape(T, G, inner // G)
        return (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                  + cfg.rms_eps)).reshape(T, inner) * w

    if leave_out == "norm_before_gate":   # norm first, then the gate
        out = norm(y) * jax.nn.silu(z)
    else:
        out = norm(y * jax.nn.silu(z))
    return out @ p["w_out"], S


def _attention(cfg, p, h, leave_out):
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ p["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    if leave_out == "rope":               # a rotary the published layer lacks
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    scale = cfg.head_dim ** -0.5 if leave_out == "attention_scale_sqrt" \
        else cfg.attention_multiplier
    group = cfg.n_heads // cfg.n_kv_heads     # query head i reads kv i//group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    # ATTN_ROWS queries at a time against every key; the last block's
    # filler queries sit at position T - 1 and are cut off again
    rows = min(ATTN_ROWS, T)
    pad = -T % rows

    def block(args):
        qb, i = args                                      # [rows, H, hd]
        s = jnp.einsum("ihd,jhd->hij", qb, k) * scale
        s = jnp.where(pos[None, None, :] <= i[None, :, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v)

    split = lambda x: jnp.pad(
        x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), mode="edge"
    ).reshape((T + pad) // rows, rows, *x.shape[1:])
    o = jax.lax.map(block, (split(q), split(pos)))
    return o.reshape(T + pad, -1)[:T] @ p["wo"]


def _layer(cfg, kind, layer, x, leave_out=""):
    """-> (x after the layer's two sublayers, the Mamba state after the
    last token or None)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), layer)
    res = 1.0 if leave_out == "no_residual_multiplier" \
        else cfg.residual_multiplier
    h = _rms(x, p["norm"]["norm"], cfg.rms_eps)
    if kind == "mamba":
        y, S = _mamba(cfg, p, h, leave_out)
    else:
        y, S = _attention(cfg, p, h, leave_out), None
    x = x + res * y
    gu = _rms(x, p["mlp_norm"]["norm"], cfg.rms_eps) @ p["mlp_in"]
    g, u = gu[:, :cfg.ffn_dim], gu[:, cfg.ffn_dim:]
    return x + res * ((jax.nn.silu(g) * u) @ p["mlp_out"]), S


def _layer_params(params, cfg, li):
    """Layer `li`'s own parameters: the engine's tree holds one layer a
    position of the repeating period (`cfg.period`), each leaf stacked
    over the periods ([periods, ...])."""
    n = len(cfg.period)
    return jax.tree_util.tree_map(lambda a: a[li // n],
                                  params["layers"][li % n])


def reference_forward(params: Dict[str, Any], cfg,
                      token_ids: Sequence[int], leave_out: str = "",
                      at: Optional[Sequence[int]] = None):
    """-> (logits [len(at) or T, vocab] float32 of one full forward over
    `token_ids`, {layer: Mamba state after the last token}); one jitted
    layer at a time, the head in blocks of the vocabulary and only at
    the positions `at` (all where None)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        if leave_out != "no_embedding_multiplier":
            x = x * cfg.embedding_multiplier
        fns = {kind: jax.jit(lambda lp, x, kind=kind: _layer(
            cfg, kind, lp, x, leave_out)) for kind in set(cfg.layer_kinds)}
        states = {}
        for li, kind in enumerate(cfg.layer_kinds):
            x, S = fns[kind](_layer_params(params, cfg, li), x)
            if S is not None:
                states[li] = S
        if at is not None:
            x = x[jnp.asarray(at)]
        x = _rms(x, params["final_norm"]["norm"].astype(F32), cfg.rms_eps)
        head = params["embedding"].T
        if leave_out == "untied_head":    # a head of its own, not E
            head = (jax.random.normal(jax.random.PRNGKey(57), head.shape,
                                      F32) * 0.02).astype(head.dtype)
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        logits = jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
        if leave_out != "no_logits_scaling":
            logits = logits / cfg.logits_scaling
    return logits, states


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int],
                     leave_out: str = "") -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`."""
    return reference_forward(params, cfg, token_ids, leave_out)[0]
