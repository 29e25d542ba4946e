"""Window and NoPE global attention beside routed and averaged shared
experts in one parallel block (Command A+, `cohere2_moe`): plain float32
reference.

The published layer, written out (ISSUE 42; the configuration file's
`assumed` lists what the config does not settle).  For layer l, kind =
layer_types[l] ("sliding_attention" | "full_attention"):

    h = LayerNorm(x) = (x - mean) / sqrt(var + eps) * w      no bias
    q = h Wq as heads x head_dim;  k = h Wk, v = h Wv as n_kv x head_dim
    sliding: rotary on all of head_dim, interleaved pairs (2i, 2i + 1),
             base rope_theta;  s_ij for 0 <= i - j < sliding_window
    full:    no positional encoding;  s_ij for j <= i
    a = softmax(q k^T / sqrt(head_dim)) v Wo
    scores = sigmoid(h Wr) over ALL router outputs, the k largest
    chosen (ties to the lower index), weights = chosen scores / their sum
    f = sum over chosen e THAT THIS SHARE HOLDS of w_e SwiGLU_e(h)
        + (1 / n_shared) sum_j SwiGLU_shared_j(h)
    x' = x + a + f                      attention and experts share h
    logits = LayerNorm(x_L) E^T logit_scale              tied embedding

Whole sequence at once, no cache, no kernels, no batching; a token
visits its own experts one at a time; attention in blocks of queries so
that 12.8 k positions at 128 heads fit beside the engine.  It reads the
engine's parameter tree (bf16 weights cast to float32 where they are
used, the head in blocks of the vocabulary); what it shares with the
program is the tree's layout, in which the shared experts are one SwiGLU
n_shared x wide.

Departures from the published description: the share of the experts
(`experts_held`; picks of experts held elsewhere add nothing), the cut
depth and vocabulary of the configuration file; the vision tower is
left out.  `leave_out` drops one published detail at a time so that a
test or the chip script can see the comparison notice.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time
QUERY_BLOCK = 512       # queries of one attention block

# details a test may leave out, one at a time (tests/test_cohere2.py,
# benchmark/chip_logits_cohere2.py)
DETAILS = ("window", "nope", "shared_mean", "norm_mean", "interleaved")

KINDS = {"full_attention": 0, "sliding_attention": 1}


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's Cohere2Config.
    `num_experts` counts the experts HELD here; `router_experts` (the
    published count) is the router's width, `ep_rank` says which share
    this is.  Without them everything is held."""
    from dynamo_tpu.models.cohere2 import Cohere2Config

    L = hf["num_hidden_layers"]
    kinds = hf["layer_types"]
    if len(kinds) != L:
        raise ValueError(f"layer_types needs one entry a layer ({L})")
    if hf.get("first_k_dense_replace", 0) or hf.get("rope_scaling") \
            or hf.get("use_qk_norm") or hf.get("attention_bias") \
            or not hf.get("use_parallel_block", True) \
            or hf.get("shared_expert_combination_strategy",
                      "average") != "average" \
            or hf.get("hidden_act", "silu") != "silu" \
            or hf.get("expert_selection_fn", "sigmoid") != "sigmoid" \
            or hf.get("position_embedding_type",
                      "rope_gptj") != "rope_gptj" \
            or hf.get("rotary_pct", 1) != 1:
        raise ValueError("dense prefix layers, rope scaling, q/k norms, "
                         "biases, a sequential block, a summed shared "
                         "expert, other activations, selection functions "
                         "and rotary forms are not modelled")
    held = hf["num_experts"]
    width = hf.get("router_experts", held)
    return Cohere2Config(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=L, n_heads=hf["num_attention_heads"],
        head_dim=hf["head_dim"], n_kv_heads=hf["num_key_value_heads"],
        layer_kinds=tuple(KINDS[k] for k in kinds),
        sliding_window=hf["sliding_window"], rope_theta=hf["rope_theta"],
        moe_ffn_dim=hf["intermediate_size"],
        n_shared_experts=hf["num_shared_experts"], n_experts=width,
        experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        norm_topk_prob=hf["norm_topk_prob"],
        norm_eps=hf["layer_norm_eps"], logit_scale=hf["logit_scale"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one layer: q.k and p.v over
    head_dim, a multiply and an add each, per head."""
    return cfg.n_heads * 4.0 * cfg.head_dim


def _norm(x, w, eps, mean=True):
    if mean:
        x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, positions, theta, interleaved=True):
    """x [T, heads, hd]: rotate the pairs (2i, 2i + 1); `interleaved`
    False pairs i with i + hd / 2 instead (the detail left out)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if not interleaved:
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _route(cfg, layer, h):
    """(weights [T, k], expert ids [T, k]) over ALL the router's
    outputs: sigmoid scores, the k largest, over their sum."""
    scores = jax.nn.sigmoid(h @ layer["moe_gate"].astype(F32))
    w, ids = jax.lax.top_k(scores, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, ids


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


def _shared(cfg, layer, h, mean=True):
    """The n_shared experts, one at a time (columns j f .. (j + 1) f of
    the stacked matrices), averaged."""
    s, f = layer["shared"], cfg.moe_ffn_dim
    total = sum(_swiglu(h, s["w_gate"][:, j * f:(j + 1) * f],
                        s["w_up"][:, j * f:(j + 1) * f],
                        s["w_down"][j * f:(j + 1) * f])
                for j in range(cfg.n_shared_experts))
    return total / cfg.n_shared_experts if mean else total


def _attention(cfg, q, k, v, window):
    """softmax(q k^T / sqrt(hd)) v, causal, `window` > 0 bounds i - j;
    in blocks of QUERY_BLOCK queries against all keys."""
    T = q.shape[0]
    group = cfg.n_heads // cfg.n_kv_heads   # query head i reads kv i // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(T)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, min(QUERY_BLOCK, T), 0)
        s = jnp.einsum("ihd,jhd->hij", qb, k) / jnp.sqrt(F32(cfg.head_dim))
        dist = (i0 + jnp.arange(qb.shape[0]))[:, None] - kpos[None, :]
        seen = dist >= 0
        if window:
            seen = seen & (dist < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hij,jhd->ihd", p, v)

    if T <= QUERY_BLOCK:
        return block(0)
    pad = -T % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(block, jnp.arange(0, T + pad, QUERY_BLOCK))
    return out.reshape(T + pad, cfg.n_heads, cfg.head_dim)[:T]


def _layer(cfg, kind, layer, x, leave_out=""):
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _norm(x, layer["attn_norm"]["norm"], cfg.norm_eps,
              leave_out != "norm_mean")
    q = (h @ layer["wq"].astype(F32)).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"].astype(F32)).reshape(T, cfg.n_kv_heads,
                                              cfg.head_dim)
    v = (h @ layer["wv"].astype(F32)).reshape(T, cfg.n_kv_heads,
                                              cfg.head_dim)
    if kind == 1 or leave_out == "nope":
        il = leave_out != "interleaved"
        q = _rope(q, pos, cfg.rope_theta, il)
        k = _rope(k, pos, cfg.rope_theta, il)
    window = cfg.sliding_window if kind == 1 and leave_out != "window" \
        else 0
    a = _attention(cfg, q, k, v, window).reshape(T, -1) \
        @ layer["wo"].astype(F32)
    w, ids = _route(cfg, layer, h)
    f = _routed(cfg, layer, h, w, ids) \
        + _shared(cfg, layer, h, leave_out != "shared_mean")
    return x + a + f


def hidden_states(params: Dict[str, Any], cfg, token_ids: Sequence[int],
                  leave_out: str = "") -> jax.Array:
    """[T, d] float32: the last layer's output under the final norm."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        fns = {kind: jax.jit(lambda lp, x, kind=kind: _layer(
            cfg, kind, lp, x, leave_out)) for kind in (0, 1)}
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            x = fns[kind](lp, x)
        return _norm(x, params["final_norm"]["norm"], cfg.norm_eps,
                     leave_out != "norm_mean")


def head_logits(params: Dict[str, Any], cfg, x: jax.Array) -> jax.Array:
    """x [N, d] under the final norm -> [N, vocab] float32, the tied
    head in blocks of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        head = params["embedding"].T
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        return cfg.logit_scale * jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int],
                     leave_out: str = "") -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`,
    one jitted layer at a time."""
    return head_logits(params, cfg,
                       hidden_states(params, cfg, token_ids, leave_out))
