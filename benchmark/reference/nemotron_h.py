"""Mamba-2 state-space blocks beside GQA attention blocks and plain
(non-gated) routed experts held as a share, one mixer a block (the
`nemotron_h` architecture): plain float32 reference.

The published block, written out (ISSUE 40 point 1; the configuration
file's `assumed` lists what the config does not settle).  Block i is of
kind `hybrid_override_pattern[i]`: x += mixer_i(RMSNorm(x)).

M, Mamba-2, H heads of P, state N, G groups:
    [z | xBC~ | dt~] = h W_in
    xBC_t = SiLU(b + sum_{j<4} w_j xBC~_{t-3+j})    on each channel,
                                                    zeros before the sequence
    x_t [H, P], B_t, C_t [G, N] = split(xBC_t);  head h reads group h div (H/G)
    dt_t = softplus(dt~_t + dt_bias),  A = -exp(A_log)         (a head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T                 (S in R^{P x N})
    y_t = S_t C_t + D x_t
    out = W_out(w * GroupRMSNorm_G(y_t * SiLU(z_t)))           (gate, then norm)
as the TOKEN-BY-TOKEN recurrence, a `lax.scan` over positions: the
program's chunked form (ops/ssm.py) shares nothing with it.

*, attention: query heads over fewer KV heads, NO rotary, causal softmax
of q . k / sqrt(head_dim).

E, experts: sigmoid scores over ALL router outputs; the choice carries
the bias; the k largest choices (ties to the lower index); weights =
chosen scores over their sum, times routed_scaling_factor; the token
visits those of its experts THAT THIS SHARE HOLDS one at a time, each
W_down relu(x W_up)^2, plus one shared expert of the same form.

Whole sequence at once, no cache, no kernels, no batching, no chunks.
It reads the engine's parameter tree (bf16 weights cast to float32 where
they are used); attention scores are formed `ATTN_ROWS` queries at a
time and the output head only at the positions asked for, so that a few
thousand positions at published widths fit beside the engine.
`leave_out` lets a test drop one published detail at a time and see that
the comparison notices.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _rms

ATTN_ROWS = 256         # queries of an attention block's scores at a time
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out, one at a time (tests/test_nemotron_h.py)
DETAILS = ("decay", "dt_bias", "conv", "conv_bias", "d_skip", "gate",
           "gate_then_norm", "group_norm", "grouped_bc", "relu2",
           "routed_scale", "shared_expert", "no_rope")


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's NemotronHConfig.
    `n_routed_experts` counts the experts HELD here; `router_experts`
    (the published count) is the router's width, `ep_rank` says which
    share this is.  Without them everything is held."""
    from dynamo_tpu.models.nemotron_h import NemotronHConfig

    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern has {len(pattern)} "
                         f"blocks, num_hidden_layers {hf['num_hidden_layers']}")
    for key, want in (("model_type", "nemotron_h"),
                      ("attention_bias", False), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", 1),
                      ("sliding_window", None), ("residual_in_fp32", False),
                      ("num_logits_to_keep", 1),
                      ("time_step_limit", [0, None])):
        if hf.get(key, want) != want:
            raise ValueError(f"{key} = {hf[key]!r} is not modelled "
                             f"(only {want!r})")
    if hf["norm_eps"] != hf["layer_norm_epsilon"]:
        raise ValueError("norm_eps and layer_norm_epsilon differ: one "
                         "epsilon is modelled")
    held = hf["n_routed_experts"]
    width = hf.get("router_experts", held)
    return NemotronHConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        pattern=pattern, ssm_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"], ssm_state=hf["ssm_state_size"],
        ssm_groups=hf["n_groups"], conv_width=hf["conv_kernel"],
        ssm_chunk=hf["chunk_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rope_theta=hf["rope_theta"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        shared_ffn_dim=hf["moe_shared_expert_intermediate_size"],
        n_experts=width, experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        norm_topk_prob=hf["norm_topk_prob"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        rms_eps=hf["layer_norm_epsilon"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one attention block: q.k and
    p.v, a multiply and an add each, per query head.  (A Mamba block has
    no pairs: its cost a token is fixed.)"""
    return 4.0 * cfg.n_heads * cfg.head_dim


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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
    """h [T, d] normed input -> (the block's output [T, d], the state
    after the last token [H, P, N])."""
    T = h.shape[0]
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    W, inner = cfg.conv_width, cfg.ssm_heads * cfg.ssm_head_dim
    zxd = h @ p["w_in"]
    z, pre, dt = (zxd[:, :inner], zxd[:, inner:inner + inner + 2 * G * N],
                  zxd[:, inner + inner + 2 * G * N:])
    if leave_out == "conv":
        c = pre + p["conv_b"]
    else:
        padded = jnp.concatenate([jnp.zeros((W - 1, pre.shape[1]), F32),
                                  pre], 0)
        c = sum(p["conv_w"][j] * padded[j:j + T] for j in range(W))
        if leave_out != "conv_bias":
            c = c + p["conv_b"]
    c = jax.nn.silu(c)
    x = c[:, :inner].reshape(T, H, P)
    b = c[:, inner:inner + G * N].reshape(T, G, N)
    cc = c[:, inner + G * N:].reshape(T, G, N)
    if leave_out == "grouped_bc":         # every head reads group 0
        b = jnp.broadcast_to(b[:, :1], b.shape)
        cc = jnp.broadcast_to(cc[:, :1], cc.shape)
    dt = jax.nn.softplus(dt if leave_out == "dt_bias"
                         else dt + p["dt_bias"])
    a = jnp.zeros((H,), F32) if leave_out == "decay" \
        else -jnp.exp(p["a_log"])
    d_skip = jnp.zeros((H,), F32) if leave_out == "d_skip" \
        else p["d_skip"]
    y, S = token_recurrence(x, dt, a, b, cc, d_skip,
                            jnp.zeros((H, P, N), F32))
    y = y.reshape(T, inner)
    w = p["gate_norm"]["norm"]
    groups = 1 if leave_out == "group_norm" else G

    def norm(v):
        g = v.reshape(T, groups, inner // groups)
        return (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                  + cfg.rms_eps)).reshape(T, inner) * w

    if leave_out == "gate":
        out = norm(y)
    elif leave_out == "gate_then_norm":   # norm first, then the gate
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
    if leave_out == "no_rope":            # a rotary the published layer lacks
        from .llama import _rope
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads     # query head i reads kv i//group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = ATTN_ROWS if T > ATTN_ROWS and T % ATTN_ROWS == 0 else T

    def block(args):
        qb, i = args                                      # [rows, H, hd]
        s = jnp.einsum("ihd,jhd->hij", qb, k) / jnp.sqrt(F32(cfg.head_dim))
        s = jnp.where(pos[None, None, :] <= i[None, :, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v)

    split = lambda x: x.reshape(T // rows, rows, *x.shape[1:])
    o = jax.lax.map(block, (split(q), split(pos)))
    return o.reshape(T, -1) @ p["wo"]


def _route(cfg, layer, h, leave_out=""):
    """(weights [T, k], expert ids [T, k]) over ALL the router's
    outputs."""
    scores = jax.nn.sigmoid(h @ layer["moe_gate"].astype(F32))
    choice = scores + layer["moe_gate_bias"].astype(F32)
    ids = jax.lax.top_k(choice, cfg.experts_per_token)[1]
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    scale = 1.0 if leave_out == "routed_scale" \
        else cfg.routed_scaling_factor
    return w * scale, ids


def _plain(x, w_up, w_down, act=_relu2):
    return act(x @ w_up.astype(F32)) @ w_down.astype(F32)


def _routed(cfg, layer, h, w, ids, act=_relu2):
    """Each token through those of its own k experts that this share
    holds, one at a time; a pick held elsewhere adds nothing."""
    first, count = cfg.held

    def one_token(args):
        x, wk, ek = args
        out = jnp.zeros_like(x)
        for j in range(ek.shape[0]):
            e = ek[j] - first
            out = out + jax.lax.cond(
                (e >= 0) & (e < count),
                lambda e=e, j=j: wk[j] * _plain(
                    x, layer["moe_w_up"][e], layer["moe_w_down"][e], act),
                lambda: jnp.zeros_like(x))
        return out

    return jax.lax.map(one_token, (h, w, ids))


def _experts(cfg, layer, p, h, leave_out):
    act = jax.nn.relu if leave_out == "relu2" else _relu2
    w, ids = _route(cfg, layer, h, leave_out)
    out = _routed(cfg, layer, h, w, ids, act)
    if leave_out != "shared_expert":
        out = out + _plain(h, p["shared"]["w_up"], p["shared"]["w_down"],
                           act)
    return out


def _block(cfg, kind, layer, x, leave_out=""):
    """-> (x after the block, the Mamba state after the last token or
    None)."""
    small = {k: v for k, v in layer.items() if not k.startswith("moe_w_")}
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), small)
    h = _rms(x, p["norm"]["norm"], cfg.rms_eps)
    if kind == "M":
        y, S = _mamba(cfg, p, h, leave_out)
        return x + y, S
    if kind == "*":
        return x + _attention(cfg, p, h, leave_out), None
    return x + _experts(cfg, layer, p, h, leave_out), None


def reference_forward(params: Dict[str, Any], cfg,
                      token_ids: Sequence[int], leave_out: str = "",
                      at: Optional[Sequence[int]] = None):
    """-> (logits [len(at) or T, vocab] float32 of one full forward over
    `token_ids`, {block: Mamba state after the last token}); one jitted
    block at a time, the head in blocks of the vocabulary and only at
    the positions `at` (all where None)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        fns = {kind: jax.jit(lambda lp, x, kind=kind: _block(
            cfg, kind, lp, x, leave_out)) for kind in "M*E"}
        states = {}
        for li, (kind, lp) in enumerate(zip(cfg.pattern, params["layers"])):
            x, S = fns[kind](lp, x)
            if S is not None:
                states[li] = S
        if at is not None:
            x = x[jnp.asarray(at)]
        x = _rms(x, params["final_norm"]["norm"].astype(F32), cfg.rms_eps)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        logits = jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
    return logits, states


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int],
                     leave_out: str = "") -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`."""
    return reference_forward(params, cfg, token_ids, leave_out)[0]
