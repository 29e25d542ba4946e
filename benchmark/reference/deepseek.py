"""Latent attention (MLA) over DeepSeekMoE: plain float32 reference.

The published mathematics, written out: per-head keys and values are
MATERIALISED from the latent (no weight absorption, no cache), and a
routed token visits exactly its top-k experts in a loop (no dense
dispatch).  It reads the engine's own parameter tree layer by layer
(bf16 weights cast to float32); what it shares with the program is the
tree's layout, including the half-split rotary pairing.  After the MLA
oracle of tests/test_mla.py, which still borrows the program's
projection helpers; this one borrows nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _causal_softmax, _rms, _rope, forward_logits


def program_config(hf: Dict[str, Any], name: str):
    """The source's `config.json` keys -> the program's DeepseekConfig."""
    from dynamo_tpu.models.deepseek import DeepseekConfig

    if hf.get("moe_layer_freq", 1) != 1 or hf.get("rope_scaling"):
        raise ValueError("moe_layer_freq != 1 and rope scaling are not "
                         "modelled")
    return DeepseekConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        q_lora_rank=hf.get("q_lora_rank") or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], ffn_dim=hf["intermediate_size"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        n_experts=hf["n_routed_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        n_shared_experts=hf["n_shared_experts"],
        first_k_dense=hf["first_k_dense_replace"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        moe_scoring=hf.get("scoring_func", "softmax"),
        norm_topk_prob=hf["norm_topk_prob"], n_group=hf["n_group"],
        topk_group=hf["topk_group"], rope_theta=hf["rope_theta"],
        rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one layer with per-head keys
    and values materialised: q.k over nope+rope dims, p.v over v dims."""
    return cfg.n_heads * (2.0 * (cfg.qk_nope_head_dim
                                 + cfg.qk_rope_head_dim)
                          + 2.0 * cfg.v_head_dim)


def _mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _route(cfg, layer, h):
    """(weights [T, k], expert ids [T, k]) as DeepseekV3TopkRouter / the
    V2 gate publish them: the choice may carry a bias and a group limit,
    the weights are the raw scores of the chosen, renormalised where the
    config says so, times routed_scaling_factor."""
    logits = h @ layer["moe_gate"].astype(F32)
    scores = (jax.nn.sigmoid(logits) if cfg.moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, -1))
    choice = scores + layer["moe_gate_bias"].astype(F32) \
        if "moe_gate_bias" in layer else scores
    if cfg.n_group > 1:
        T, E = choice.shape
        g = choice.reshape(T, cfg.n_group, E // cfg.n_group)
        gscore = (jax.lax.top_k(g, 2)[0].sum(-1)
                  if cfg.moe_scoring == "sigmoid" else g.max(-1))
        kept = jax.lax.top_k(gscore, cfg.topk_group)[1]
        gmask = jnp.any(jnp.arange(cfg.n_group)[None, :, None]
                        == kept[:, None, :], -1)
        choice = jnp.where(jnp.repeat(gmask, E // cfg.n_group, 1),
                           choice, 0.0)
    ids = jax.lax.top_k(choice, cfg.experts_per_token)[1]
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, ids


def _routed(layer, h, w, ids):
    """Each token through its own k experts, one token at a time."""

    def one_token(args):
        x, wk, ek = args
        out = jnp.zeros_like(x)
        for j in range(ek.shape[0]):
            e = ek[j]
            hid = jax.nn.silu(x @ layer["moe_w_gate"][e].astype(F32)) \
                * (x @ layer["moe_w_up"][e].astype(F32))
            out = out + wk[j] * (hid @ layer["moe_w_down"][e].astype(F32))
        return out

    return jax.lax.map(one_token, (h, w, ids))


def _layer(cfg, layer, x):
    small = {k: v for k, v in layer.items() if not k.startswith("moe_w_")}
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), small)
    T = x.shape[0]
    pos = jnp.arange(T)
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    h = _rms(x, p["attn_norm"]["norm"], cfg.rms_eps)
    if cfg.q_lora_rank > 0:
        q = _rms(h @ p["wq_a"], p["q_a_norm"]["norm"], cfg.rms_eps) \
            @ p["wq_b"]
    else:
        q = h @ p["wq"]
    q = q.reshape(T, cfg.n_heads, dn + cfg.qk_rope_head_dim)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, cfg.rope_theta)
    kv = h @ p["wkv_a"]
    c = _rms(kv[:, :R], p["kv_a_norm"]["norm"], cfg.rms_eps)
    k_rope = _rope(kv[:, None, R:], pos, cfg.rope_theta)  # one, shared
    k_nope = jnp.einsum("tr,hrd->thd", c, p["w_uk"])
    v = jnp.einsum("tr,hrd->thd", c, p["w_uv"])
    s = (jnp.einsum("ihd,jhd->hij", q_nope, k_nope)
         + jnp.einsum("ihd,jd->hij", q_rope, k_rope[:, 0]))
    s = s / jnp.sqrt(F32(dn + cfg.qk_rope_head_dim))
    o = jnp.einsum("hij,jhd->ihd", _causal_softmax(s), v)
    x = x + o.reshape(T, -1) @ p["wo"]
    h = _rms(x, p["mlp_norm"]["norm"], cfg.rms_eps)
    if "moe_gate" not in layer:
        return x + _mlp(p, h)
    w, ids = _route(cfg, layer, h)
    out = _routed(layer, h, w, ids)
    if "shared" in layer:
        out = out + _mlp(p["shared"], h)
    return x + out


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int]) -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`."""
    return forward_logits(params, cfg, token_ids, _layer)
