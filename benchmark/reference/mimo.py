"""Window and global attention layers over routed experts held as a share
(the MiMo-V2-Flash architecture): plain float32 reference.

The published layer, written out (ISSUE 29 point 1; the configuration
file's `assumed` lists what the config does not settle).  For layer l,
kind = hybrid_layer_pattern[l] (0 global, 1 window):

    h = RMSNorm(x);  q = h Wq as heads x head_dim;  k = h Wk as n_kv x
    head_dim;  v = h Wv as n_kv x v_head_dim  (n_kv by kind)
    rotary embedding on the first rotary_dim dimensions of q and k
    (pairs i, i + rotary_dim / 2), base by kind; the rest passes through
    s_ij = q_i . k_j / sqrt(head_dim) for j <= i, in window layers only
    i - j < sliding_window
    global: p = softmax(s);  window: p_ij = exp(s_ij) /
    (sum_j' exp(s_ij') + exp(sink_head))
    o_i = sum_j p_ij (attention_value_scale v_j);  x += concat(o) Wo
    h2 = RMSNorm(x);  layer without a router: x += SwiGLU(h2)
    else scores = sigmoid(h2 Wg) over ALL router outputs, the k largest
    of scores + bias chosen, weights = chosen scores / their sum,
    x += sum over chosen e THAT THIS SHARE HOLDS of w_e SwiGLU_e(h2)

Whole sequence at once, no cache, no kernels, no batching; a token
visits its own experts one at a time.  It reads the engine's parameter
tree (bf16 weights cast to float32 where they are used, the output head
in blocks of the vocabulary, so that a few hundred positions at
published widths fit beside the engine); what it shares with the
program is the tree's layout and the rotary pairing.  `leave_out` lets
a test drop one published detail at a time and see that the comparison
notices.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out, one at a time (tests/test_mimo.py)
DETAILS = ("sink", "value_scale", "partial_rotary", "swa_rope_base",
           "window")


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's MimoConfig.
    `n_routed_experts` counts the experts HELD here; `router_experts`
    (the published count) is the router's width, `ep_rank` says which
    share this is.  Without them everything is held."""
    from dynamo_tpu.models.mimo import MimoConfig

    L = hf["num_hidden_layers"]
    kinds, moe = hf["hybrid_layer_pattern"], hf["moe_layer_freq"]
    if len(kinds) != L or len(moe) != L:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq need "
                         f"one entry a layer ({L})")
    if hf.get("n_shared_experts") or hf.get("rope_scaling") \
            or hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("shared experts, rope scaling and other "
                         "routing methods are not modelled")
    for k in ("num_attention_heads", "head_dim", "v_head_dim"):
        if hf.get("swa_" + k, hf[k]) != hf[k]:
            raise ValueError(f"swa_{k} differing from {k} is not modelled")
    held = hf["n_routed_experts"]
    width = hf.get("router_experts", held)
    return MimoConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=L, n_heads=hf["num_attention_heads"],
        head_dim=hf["head_dim"], v_head_dim=hf["v_head_dim"],
        n_kv_heads=hf["num_key_value_heads"],
        swa_n_kv_heads=hf["swa_num_key_value_heads"],
        layer_kinds=tuple(kinds), sliding_window=hf["sliding_window"],
        rotary_dim=int(hf["head_dim"] * hf["partial_rotary_factor"]),
        rope_theta=hf["rope_theta"], swa_rope_theta=hf["swa_rope_theta"],
        attn_value_scale=hf["attention_value_scale"],
        swa_sink=hf["add_swa_attention_sink_bias"],
        full_sink=hf["add_full_attention_sink_bias"],
        ffn_dim=hf["intermediate_size"],
        moe_ffn_dim=hf["moe_intermediate_size"], moe_layers=tuple(moe),
        n_experts=width, experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        moe_scoring=hf["scoring_func"], norm_topk_prob=hf["norm_topk_prob"],
        n_group=hf["n_group"], topk_group=hf["topk_group"],
        routed_scaling_factor=hf["routed_scaling_factor"] or 1.0,
        rms_eps=hf["layernorm_epsilon"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one layer: q.k over head_dim
    and p.v over v_head_dim, a multiply and an add each, per head."""
    return cfg.n_heads * 2.0 * (cfg.head_dim + cfg.v_head_dim)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, positions, theta, rotary_dim):
    """x [T, heads, hd]: rotate the pairs (i, i + rotary_dim / 2) of the
    first rotary_dim dimensions; the others pass through."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _route(cfg, layer, h):
    """(weights [T, k], expert ids [T, k]) over ALL the router's
    outputs: sigmoid scores, the choice carries the bias, the weights
    are the chosen scores over their sum."""
    scores = jax.nn.sigmoid(h @ layer["moe_gate"].astype(F32))
    ids = jax.lax.top_k(scores + layer["moe_gate_bias"].astype(F32),
                        cfg.experts_per_token)[1]
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, ids


def _routed(cfg, layer, h, w, ids):
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
                lambda e=e, j=j: wk[j] * _swiglu(
                    x, layer["moe_w_gate"][e], layer["moe_w_up"][e],
                    layer["moe_w_down"][e]),
                lambda: jnp.zeros_like(x))
        return out

    return jax.lax.map(one_token, (h, w, ids))


def _layer(cfg, kind, layer, x, leave_out=""):
    T = x.shape[0]
    pos = jnp.arange(T)
    window = kind == 1
    nkv = cfg.swa_n_kv_heads if window else cfg.n_kv_heads
    theta = cfg.swa_rope_theta if window and leave_out != "swa_rope_base" \
        else cfg.rope_theta
    rot = cfg.head_dim if leave_out == "partial_rotary" else cfg.rotary_dim
    h = _rms(x, layer["attn_norm"]["norm"], cfg.rms_eps)
    q = (h @ layer["wq"].astype(F32)).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"].astype(F32)).reshape(T, nkv, cfg.head_dim)
    v = (h @ layer["wv"].astype(F32)).reshape(T, nkv, cfg.v_head_dim)
    q, k = _rope(q, pos, theta, rot), _rope(k, pos, theta, rot)
    group = cfg.n_heads // nkv            # query head i reads kv i // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(F32(cfg.head_dim))
    dist = pos[:, None] - pos[None, :]                  # i - j
    seen = dist >= 0
    if window and leave_out != "window":
        seen = seen & (dist < cfg.sliding_window)
    m = jnp.max(jnp.where(seen[None], s, -jnp.inf), -1, keepdims=True)
    e = jnp.where(seen[None], jnp.exp(s - m), 0.0)
    den = e.sum(-1, keepdims=True)
    if "attn_sink" in layer and leave_out != "sink":
        den = den + jnp.exp(layer["attn_sink"].astype(F32)[:, None, None]
                            - m)
    scale = 1.0 if leave_out == "value_scale" else cfg.attn_value_scale
    o = jnp.einsum("hij,jhd->ihd", e / den, scale * v)
    x = x + o.reshape(T, -1) @ layer["wo"].astype(F32)
    h = _rms(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
    if "moe_gate" not in layer:
        return x + _swiglu(h, layer["w_gate"], layer["w_up"],
                           layer["w_down"])
    w, ids = _route(cfg, layer, h)
    return x + _routed(cfg, layer, h, w, ids)


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int],
                     leave_out: str = "") -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`,
    one jitted layer at a time, the head in blocks of the vocabulary."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        fns = {kind: jax.jit(lambda lp, x, kind=kind: _layer(
            cfg, kind, lp, x, leave_out)) for kind in (0, 1)}
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            x = fns[kind](lp, x)
        x = _rms(x, params["final_norm"]["norm"], cfg.rms_eps)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        return jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
