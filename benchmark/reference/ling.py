"""Delta-rule linear attention (KDA) layers with one latent-attention
(MLA) layer a period, over group-routed experts held as a share (the
Ling-3.0-flash architecture): plain float32 reference.

The published layer, written out (ISSUE 35 point 1; the configuration
file's `assumed` lists what the config does not settle).  Layer l is MLA
where (l + 1) % layer_group_size == 0, else KDA.

KDA, a head of head_dim (dk = dv):
    h = RMSNorm(x);  q~, k~, v~ = h Wq, h Wk, h Wv
    c_t = SiLU(sum_{j<4} w_j x~_{t-3+j})   on each channel of q~ k~ v~,
                                           zeros before the sequence
    q_t = c^q_t / |c^q_t|,  k_t = c^k_t / |c^k_t|,  v_t = c^v_t
    beta_t = sigmoid(h Wb)  (a head)
    log a_t = kda_lower_bound * sigmoid(exp(A_log) * (h Wf + dt_bias))
              (a channel)
    S'  = Diag(a_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t / sqrt(dk)
    x += concat_heads(sigmoid(h Wg)_head * RMSNorm_dk(o_t)) Wo
as the TOKEN-BY-TOKEN recurrence, a `lax.scan` over positions: the
program's chunked form (ops/delta_attention.py) shares nothing with it.

MLA: per-head keys and values MATERIALISED from the latent (no weight
absorption, no cache), rotary on the 64-wide parts, causal softmax.

Experts: sigmoid scores over ALL router outputs; the choice carries the
bias; a group's score is the sum of its two largest choices, the
topk_group best groups are kept, the k largest choices inside them
chosen (ties to the lower index); weights = chosen scores over their
sum, times routed_scaling_factor; the token visits those of its experts
THAT THIS SHARE HOLDS one at a time, plus the shared SwiGLU.

Whole sequence at once, no cache, no kernels, no batching.  It reads the
engine's parameter tree (bf16 weights cast to float32 where they are
used; q, k and v lie side by side in `wqkv`); the MLA scores are formed
`ATTN_ROWS` queries at a time and the output head only at the positions
asked for, so that a few thousand positions at published widths fit
beside the engine.  `leave_out` lets a test drop one published detail at
a time and see that the comparison notices.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _rms, _rope

ATTN_ROWS = 256         # queries of an MLA layer's score block at a time
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out, one at a time (tests/test_ling.py)
DETAILS = ("delta", "decay", "channel_decay", "gate_bound", "short_conv",
           "qk_l2norm", "out_gate", "group_limit", "routed_scale",
           "mla_rope")


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's LingConfig.
    `num_experts` counts the experts HELD here; `router_experts` (the
    published count) is the router's width, `ep_rank` says which share
    this is.  Without them everything is held."""
    from dynamo_tpu.models.ling import LingConfig

    L = hf["num_hidden_layers"]
    limits = (tuple(hf.get("expert_swiglu_limit_list", ()))
              + tuple(hf.get("share_expert_swiglu_limit_list", ())))
    if len(limits) not in (0, 2 * L):
        raise ValueError("the SwiGLU limit lists need one entry a layer "
                         f"({L})")
    for key, want in (("q_lora_rank", None), ("use_nGPT", False),
                      ("scale_router_input", False), ("value_norm", False),
                      ("up_proj_norm", False), ("use_kda_lora", False),
                      ("no_kda_lora", True), ("kda_safe_gate", True),
                      ("linear_silu", True), ("use_qk_norm", True),
                      ("use_mla_nope", False), ("group_norm_size", 1),
                      ("num_kv_heads_for_linear_attn", 0),
                      ("score_function", "sigmoid"),
                      ("moe_router_enable_expert_bias", True),
                      ("gated_attention_proj_granularity_type",
                       "head_wise")):
        if hf.get(key, want) != want:
            raise ValueError(f"{key} = {hf[key]!r} is not modelled "
                             f"(only {want!r})")
    if hf["rotary_dim"] != hf["qk_rope_head_dim"]:
        raise ValueError("rotary_dim is read as the MLA layers' rope "
                         "width and must equal qk_rope_head_dim")
    held = hf["num_experts"]
    width = hf.get("router_experts", held)
    return LingConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=L, n_heads=hf["num_attention_heads"],
        head_dim=hf["head_dim"], mla_period=hf["layer_group_size"],
        conv_width=hf["short_conv_kernel_size"],
        kda_lower_bound=float(hf["kda_lower_bound"]),
        kda_chunk=hf.get("kda_chunk", 64),
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], ffn_dim=hf["intermediate_size"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        shared_ffn_dim=hf["moe_shared_expert_intermediate_size"],
        first_k_dense=hf["first_k_dense_replace"], n_experts=width,
        experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        swiglu_limits=limits, norm_topk_prob=hf["norm_topk_prob"],
        n_group=hf["n_group"], topk_group=hf["topk_group"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        rope_theta=hf["rope_theta"], rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one MLA layer with per-head
    keys and values materialised: q.k over nope + rope dims, p.v over v
    dims.  (A KDA layer has no pairs: its cost a token is fixed.)"""
    return cfg.n_heads * 2.0 * (cfg.qk_nope_head_dim
                                + cfg.qk_rope_head_dim + cfg.v_head_dim)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(F32)) * (x @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def token_recurrence(q, k, v, log_a, beta, S, scale, delta=True):
    """The rule itself, a token at a time: q, k, log_a [T, H, dk], v
    [T, H, dv], beta [T, H], S [H, dk, dv] -> (o [T, H, dv], S after the
    last token).  Without `delta` the correction by what the state
    already holds for k is left out (plain gated linear attention)."""

    def token(S, xs):
        q, k, v, log_a, beta = xs
        Sp = jnp.exp(log_a)[:, :, None] * S
        seen = jnp.einsum("hkv,hk->hv", Sp, k) if delta else 0.0
        S = Sp + beta[:, None, None] * k[:, :, None] * (v - seen)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q) * scale

    S, o = jax.lax.scan(token, S, (q, k, v, log_a, beta))
    return o, S


def _kda(cfg, p, h, leave_out):
    """h [T, d] normed input -> (the layer's output [T, d], the state
    after the last token [H, dk, dv])."""
    T = h.shape[0]
    H, dk, W = cfg.n_heads, cfg.head_dim, cfg.conv_width
    pre = h @ p["wqkv"]                              # q~ | k~ | v~
    if leave_out == "short_conv":
        c = jax.nn.silu(pre)
    else:
        padded = jnp.concatenate([jnp.zeros((W - 1, pre.shape[1]), F32),
                                  pre], 0)
        c = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + T]
                            for j in range(W)))
    q, k, v = (x.reshape(T, H, dk) for x in jnp.split(c, 3, -1))
    if leave_out != "qk_l2norm":
        unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)
                                      + 1e-6)
        q, k = unit(q), unit(k)
    beta = jax.nn.sigmoid(h @ p["wb"])                          # [T, H]
    f = (h @ p["wf"]).reshape(T, H, dk) + p["dt_bias"]
    rate = jnp.exp(p["a_log"])[:, None]
    if leave_out == "gate_bound":
        log_a = -rate * jax.nn.softplus(f)
    else:
        log_a = cfg.kda_lower_bound * jax.nn.sigmoid(rate * f)
    if leave_out == "decay":
        log_a = jnp.zeros_like(log_a)
    if leave_out == "channel_decay":
        log_a = jnp.broadcast_to(log_a.mean(-1, keepdims=True), log_a.shape)

    o, S = token_recurrence(q, k, v, log_a, beta, jnp.zeros((H, dk, dk), F32),
                            1.0 / jnp.sqrt(F32(dk)),
                            delta=leave_out != "delta")
    o = _rms(o, p["o_norm"]["norm"], cfg.rms_eps)
    if leave_out != "out_gate":
        o = o * jax.nn.sigmoid(h @ p["wg"])[:, :, None]
    return o.reshape(T, H * dk) @ p["wo"], S


def _mla(cfg, p, h, leave_out):
    T = h.shape[0]
    pos = jnp.arange(T)
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rot = (lambda x: x) if leave_out == "mla_rope" \
        else (lambda x: _rope(x, pos, cfg.rope_theta))
    q = (h @ p["wq"]).reshape(T, cfg.n_heads, dn + cfg.qk_rope_head_dim)
    q_nope, q_rope = q[..., :dn], rot(q[..., dn:])
    kv = h @ p["wkv_a"]
    c = _rms(kv[:, :R], p["kv_a_norm"]["norm"], cfg.rms_eps)
    k_rope = rot(kv[:, None, R:])[:, 0]                   # one, shared
    k_nope = jnp.einsum("tr,hrd->thd", c, p["w_uk"])
    v = jnp.einsum("tr,hrd->thd", c, p["w_uv"])
    rows = ATTN_ROWS if T > ATTN_ROWS and T % ATTN_ROWS == 0 else T

    def block(args):
        qn, qr, i = args                                  # [rows, H, .]
        s = (jnp.einsum("ihd,jhd->hij", qn, k_nope)
             + jnp.einsum("ihd,jd->hij", qr, k_rope)) \
            / jnp.sqrt(F32(dn + cfg.qk_rope_head_dim))
        s = jnp.where(pos[None, None, :] <= i[None, :, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v)

    split = lambda x: x.reshape(T // rows, rows, *x.shape[1:])
    o = jax.lax.map(block, (split(q_nope), split(q_rope), split(pos)))
    return o.reshape(T, -1) @ p["wo"]


def _route(cfg, layer, h, leave_out):
    """(weights [T, k], expert ids [T, k]) over ALL the router's
    outputs."""
    scores = jax.nn.sigmoid(h @ layer["moe_gate"].astype(F32))
    choice = scores + layer["moe_gate_bias"].astype(F32)
    if leave_out != "group_limit":
        T, E = choice.shape
        g = choice.reshape(T, cfg.n_group, E // cfg.n_group)
        kept = jax.lax.top_k(jax.lax.top_k(g, 2)[0].sum(-1),
                             cfg.topk_group)[1]           # [T, topk_group]
        in_kept = jnp.any(jnp.arange(cfg.n_group)[None, :, None]
                          == kept[:, None, :], -1)        # [T, n_group]
        choice = jnp.where(jnp.repeat(in_kept, E // cfg.n_group, 1),
                           choice, -jnp.inf)
    ids = jax.lax.top_k(choice, cfg.experts_per_token)[1]
    w = jnp.take_along_axis(scores, ids, 1)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    scale = 1.0 if leave_out == "routed_scale" \
        else cfg.routed_scaling_factor
    return w * scale, ids


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
    """-> (x after the layer, the KDA state after the last token or
    None)."""
    small = {k: v for k, v in layer.items() if not k.startswith("moe_w_")}
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), small)
    h = _rms(x, p["attn_norm"]["norm"], cfg.rms_eps)
    if kind == 0:
        y, S = _kda(cfg, p, h, leave_out)
    else:
        y, S = _mla(cfg, p, h, leave_out), None
    x = x + y
    h = _rms(x, p["mlp_norm"]["norm"], cfg.rms_eps)
    if "moe_gate" not in layer:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), S
    w, ids = _route(cfg, layer, h, leave_out)
    s = p["shared"]
    return x + _routed(cfg, layer, h, w, ids) \
        + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"]), S


def reference_forward(params: Dict[str, Any], cfg,
                      token_ids: Sequence[int], leave_out: str = "",
                      at: Optional[Sequence[int]] = None):
    """-> (logits [len(at) or T, vocab] float32 of one full forward over
    `token_ids`, {layer: KDA state after the last token}); one jitted
    layer at a time, the head in blocks of the vocabulary and only at
    the positions `at` (all where None)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        fns = {kind: jax.jit(lambda lp, x, kind=kind: _layer(
            cfg, kind, lp, x, leave_out)) for kind in (0, 1)}
        states = {}
        for li, (kind, lp) in enumerate(zip(cfg.layer_kinds,
                                            params["layers"])):
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
