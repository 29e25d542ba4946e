"""GQA attention over keys that a learned indexer chooses, over routed
experts held as a share (the Keye-VL-2.0-30B-A3B language model): plain
float32 reference.

The published layer, written out (ISSUE 33 point 1; the configuration
file's `assumed` lists what the config does not settle).  Every layer:

    h = RMSNorm(x);  q = h Wq as heads x head_dim;  k = h Wk, v = h Wv
    as n_kv x head_dim;  RMSNorm over head_dim on each head of q and k;
    rotary embedding on all of head_dim (pairs i, i + head_dim / 2)
    indexer: qI = h WqI as index_heads x index_head_dim;  kI =
    LayerNorm(h WkI), ONE key a token;  rotary on both, same base;
    w = h Ww;  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), s <= t
    S_t = the topk tokens s <= t with the largest I[t, s] (ties: the
    lower index), all of them while t + 1 <= topk
    p[t, s] = softmax over s in S_t of q[t] . k[s] / sqrt(head_dim);
    o[t] = sum_s p[t, s] v[s];  x += concat(o) Wo
    h2 = RMSNorm(x);  g = softmax(h2 Wg) over ALL router outputs, the k
    largest chosen and renormalised to sum 1;
    x += sum over chosen e THAT THIS SHARE HOLDS of g_e SwiGLU_e(h2)

Whole sequence at once, no cache, no kernels, no batching; the chosen
set comes from a stable sort of each query's scores; the held experts
are visited one at a time.  Queries go through indexer and attention in
blocks of `QUERY_BLOCK` and the output head in blocks of the
vocabulary, so that 6400 positions at published widths fit beside the
engine.  It reads the engine's parameter tree (bf16 weights cast to
float32 where they are used); what it shares with the program is the
tree's layout and the rotary pairing.  `leave_out` lets a test drop one
published detail at a time and see that the comparison notices;
`taps`, if given, receives each layer's attention output before Wo and
its chosen sets.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time
QUERY_BLOCK = 256       # queries through indexer and attention at a time

# details a test may leave out, one at a time (tests/test_keye.py)
DETAILS = ("selection", "relu", "index_rotary", "index_key_norm",
           "topk_halved", "qk_norm", "router_renorm")


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's KeyeConfig.
    `num_experts` counts the experts HELD here; `router_experts` (the
    published count) is the router's width, `ep_rank` says which share
    this is.  Without them everything is held."""
    from dynamo_tpu.models.keye import KeyeConfig

    sa = hf["sa_config"]
    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers") \
            or hf.get("attention_bias") or hf.get("use_sliding_window") \
            or not hf.get("norm_topk_prob", True) \
            or sa["indexer_num_kv_heads"] != 1 \
            or hf["rope_scaling"].get("rope_type", "default") != "default":
        raise ValueError("dense layers, attention biases, a sliding "
                         "window, an unnormalised router, several index "
                         "keys a token and scaled rotary are not modelled")
    held = hf["num_experts"]
    return KeyeConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        n_experts=hf.get("router_experts", held),
        experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        rope_theta=hf["rope_theta"], rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one ATTENDED (query, key) pair costs in one layer: q.k and
    p.v over head_dim, a multiply and an add each, per head."""
    return cfg.n_heads * 4.0 * cfg.head_dim


def index_pair_flops(cfg) -> float:
    """FLOPs one SCORED (query, key) pair costs in one layer: qI.kI
    over index_head_dim, a multiply and an add, per index head."""
    return cfg.index_heads * 2.0 * cfg.index_head_dim


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the pairs (i, i + hd / 2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _chosen(scores, seen, topk):
    """scores [B, T] float32, seen [B, T] bool -> [B, T] bool: the topk
    seen entries of each row with the largest score, ties to the lower
    index (a stable sort, largest first)."""
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1,
                        stable=True)[:, :topk]
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, order].set(True) & seen


def _attention_block(cfg, leave_out, q, k, v, qi, ki, wi, pos_q):
    """One block of queries (q [B, nh, hd], qi [B, H, D], wi [B, H], at
    positions pos_q) against the whole sequence (k, v [T, nkv, hd], ki
    [T, D]) -> (o [B, nh, hd], chosen [B, T])."""
    T = k.shape[0]
    seen = jnp.arange(T)[None, :] <= pos_q[:, None]
    chosen = seen
    if leave_out != "selection":
        dots = jnp.einsum("bhd,sd->bhs", qi, ki)
        if leave_out != "relu":
            dots = jax.nn.relu(dots)
        topk = cfg.index_topk // 2 if leave_out == "topk_halved" \
            else cfg.index_topk
        chosen = _chosen(jnp.einsum("bhs,bh->bs", dots, wi), seen, topk)
    group = cfg.n_heads // cfg.n_kv_heads   # query head i reads kv i // group
    kr, vr = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhd,shd->hbs", q, kr) / jnp.sqrt(F32(cfg.head_dim))
    m = jnp.max(jnp.where(chosen[None], s, -jnp.inf), -1, keepdims=True)
    e = jnp.where(chosen[None], jnp.exp(s - m), 0.0)
    p = e / e.sum(-1, keepdims=True)
    return jnp.einsum("hbs,shd->bhd", p, vr), chosen


def _routed(cfg, layer, h, leave_out):
    """g = softmax over all router outputs, the k largest renormalised;
    the held experts one at a time, each for the tokens that chose it."""
    from dynamo_tpu.models.llama import experts_held

    g = jax.nn.softmax(h @ layer["moe_gate"].astype(F32), axis=-1)
    top, ids = jax.lax.top_k(g, cfg.experts_per_token)
    if leave_out != "router_renorm":
        top = top / top.sum(-1, keepdims=True)
    first, count = experts_held(cfg)
    out = jnp.zeros_like(h)
    for e in range(count):
        w_e = jnp.sum(jnp.where(ids == first + e, top, 0.0), axis=-1)
        out = out + w_e[:, None] * _swiglu(
            h, layer["moe_w_gate"][e], layer["moe_w_up"][e],
            layer["moe_w_down"][e])
    return out


def _project(cfg, layer, x, leave_out):
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, layer["attn_norm"]["norm"], cfg.rms_eps)
    q = (h @ layer["wq"].astype(F32)).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"].astype(F32)).reshape(T, cfg.n_kv_heads,
                                              cfg.head_dim)
    v = (h @ layer["wv"].astype(F32)).reshape(T, cfg.n_kv_heads,
                                              cfg.head_dim)
    if cfg.qk_norm and leave_out != "qk_norm":
        q = _rms(q, layer["q_norm"]["norm"], cfg.rms_eps)
        k = _rms(k, layer["k_norm"]["norm"], cfg.rms_eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    qi = (h @ layer["wq_index"].astype(F32)).reshape(
        T, cfg.index_heads, cfg.index_head_dim)
    ki = h @ layer["wk_index"].astype(F32)
    if leave_out != "index_key_norm":
        ki = _layer_norm(ki, layer["k_index_norm"]["weight"],
                         layer["k_index_norm"]["bias"], cfg.rms_eps)
    if leave_out != "index_rotary":
        qi = _rope(qi, pos, cfg.rope_theta)
        ki = _rope(ki[:, None, :], pos, cfg.rope_theta)[:, 0]
    return q, k, v, qi, ki, h @ layer["ww_index"].astype(F32)


def _finish(cfg, layer, x, o, leave_out):
    x = x + o.reshape(x.shape[0], -1) @ layer["wo"].astype(F32)
    h = _rms(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
    return x + _routed(cfg, layer, h, leave_out)


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int], leave_out: str = "",
                     taps: Optional[list] = None) -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`,
    a layer in three jitted parts (projections, attention a block of
    queries at a time, output and experts), the head in blocks of the
    vocabulary.  `taps`: a list that receives, a layer, {"attn": [T, nh,
    hd] the attention output before Wo, "chosen": [T, T] bool}."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    T = len(token_ids)
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        project = jax.jit(lambda lp, x: _project(cfg, lp, x, leave_out))
        attend = jax.jit(lambda *a: _attention_block(cfg, leave_out, *a))
        finish = jax.jit(lambda lp, x, o: _finish(cfg, lp, x, o, leave_out))
        for lp in params["layers"]:
            q, k, v, qi, ki, wi = project(lp, x)
            outs = [attend(q[i:i + QUERY_BLOCK], k, v,
                           qi[i:i + QUERY_BLOCK], ki, wi[i:i + QUERY_BLOCK],
                           jnp.arange(i, min(i + QUERY_BLOCK, T)))
                    for i in range(0, T, QUERY_BLOCK)]
            o = jnp.concatenate([a for a, _ in outs])
            if taps is not None:
                taps.append({"attn": o, "chosen": jnp.concatenate(
                    [c for _, c in outs])})
            x = finish(lp, x, o)
        x = _rms(x, params["final_norm"]["norm"], cfg.rms_eps)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        return jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
