"""Dense GQA decoder (Llama/Mistral lineage): plain float32 reference.

Straightforward `jax.numpy`: whole sequence at once, no cache, no
kernels, no batching.  It reads the engine's own parameter tree layer by
layer (bf16 weights cast to float32) so both sides compute on the same
numbers; the only shared convention is the tree's layout, including the
half-split rotary pairing (x[i], x[i + hd/2]) the program's weights use.
Mixture-of-experts members of the class are not covered here.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32


def program_config(hf: Dict[str, Any], name: str):
    """The source's `config.json` keys -> the program's LlamaConfig."""
    from dynamo_tpu.models.llama import LlamaConfig

    if hf.get("sliding_window") is not None:
        raise ValueError("sliding-window attention is not modelled")
    heads = hf["num_attention_heads"]
    return LlamaConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=heads,
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        ffn_dim=hf["intermediate_size"], rope_theta=hf["rope_theta"],
        rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one layer: q.k and p.v, a
    multiply and an add each, per head."""
    return 4.0 * cfg.n_heads * cfg.head_dim


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _causal_softmax(s):
    """s [heads, T, T] -> probabilities with keys after the query masked."""
    T = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1)


def _layer(cfg, layer, x):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), layer)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, p["attn_norm"]["norm"], cfg.rms_eps)
    q = (h @ p["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"]["norm"], cfg.rms_eps)
        k = _rms(k, p["k_norm"]["norm"], cfg.rms_eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads     # query head i reads kv i//group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(F32(cfg.head_dim))
    o = jnp.einsum("hij,jhd->ihd", _causal_softmax(s), v)
    x = x + o.reshape(T, -1) @ p["wo"]
    h = _rms(x, p["mlp_norm"]["norm"], cfg.rms_eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def forward_logits(params: Dict[str, Any], cfg, token_ids: Sequence[int],
                   layer) -> jax.Array:
    """Embedding, `layer(cfg, layer_params, x)` over the stack one jitted
    layer at a time, final norm and output head, all in float32."""
    layer_fn = jax.jit(lambda lp, x: layer(cfg, lp, x))
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        for lp in params["layers"]:
            x = layer_fn(lp, x)
        x = _rms(x, params["final_norm"]["norm"].astype(F32), cfg.rms_eps)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        return x @ head.astype(F32)


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int]) -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`."""
    if getattr(cfg, "n_experts", 0) > 0:
        raise NotImplementedError("no MoE reference for this class")
    return forward_logits(params, cfg, token_ids, _layer)
