"""Gated short-convolution layers beside a few GQA attention layers, a
dense feed-forward in the leading layers and routed experts after them
(LFM2-24B-A2B, `lfm2_moe`): plain float32 reference.

The published layer, written out (ISSUE 55 point 1; the configuration
file's `assumed` lists what the config does not settle).  The reading of
every key is the `lfm2_moe` implementation in transformers
(`modeling_lfm2_moe.py`), written down without the file at hand: where
that implementation is known to differ it wins.

    RMSNorm(x) = x / sqrt(mean(x^2) + norm_eps) * w
    layer i, kind layer_types[i]:
        x += op_i(RMSNorm_op(x));  x += ffn_i(RMSNorm_ffn(x))

`conv` (Lfm2MoeShortConv, conv_L_cache 3, conv_bias false):
    [B, C, u] = h W_in         2048 -> 3 x 2048, split in that order
    g = B * u
    c[t] = sum_{j<3} w[j] * g[t - 2 + j]     a channel: depthwise,
           causal, g before the sequence's start is 0, w[2] meets the
           current token (the program stores the taps [3, d], the
           published module [d, 1, 3]: the same numbers transposed)
    y = (C * c) W_out          NO activation anywhere
`full_attention`: q, k, v = h Wq, h Wk, h Wv as 32 / 8 / 8 heads of 64;
    q and k RMS-normed a head over 64 (q_layernorm, k_layernorm, eps
    norm_eps), then rotary (rotate-half: pairs i, i + 32; all 64 dims;
    base rope_theta; rope_type default) at the absolute position; causal
    softmax of q.k / sqrt(64); o W_out.  No bias.
ffn, i < num_dense_layers: W2 (silu(W1 h) * (W3 h)) at intermediate_size.
    Else experts: s = sigmoid(h W_r) over all router outputs, float32;
    the CHOICE is the num_experts_per_tok largest of s + expert_bias
    (use_expert_bias), ties to the lower index; weights = s at the
    chosen (WITHOUT the bias) / (their sum + 1e-6) (norm_topk_prob)
    x routed_scaling_factor; sum over the chosen e THAT THIS PROGRAM
    HOLDS of w_e W2,e (silu(W1,e h) * (W3,e h)).  No shared expert.
Final RMSNorm (the source's `embedding_norm`); the head is the
embedding, transposed.

Departures from the published code, each where it acts:
  * the published cache keeps conv_L_cache = 3 columns of g a layer, of
    which the oldest is never read again; the program keeps 2 rows.
    Nothing here: the reference keeps no cache.
  * the program's router (models/moe.py `ds_router`) guards the
    renormalising division with 1e-20 where the published block, and
    this reference, add 1e-6: four sigmoid scores sum to about 2, so a
    weight differs by under 1e-6 relative.

Whole sequence at once, no cache, no kernels, no batching, no chunks;
the convolution is a loop over the taps on the whole sequence, the
experts are visited token by token.  It reads the engine's parameter
tree (bf16 weights cast to float32 where they are used); attention
scores are formed `ATTN_ROWS` queries at a time and the output head only
at the positions asked for, so that some thousands of positions at
published widths fit beside the engine.  `leave_out` lets a test drop or
bend one published detail at a time and see that the comparison notices;
`compute_dtype` runs the same arithmetic in a lower precision (the
control that a limit must refuse).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _rms, _rope

ATTN_ROWS = 256         # queries of an attention layer's scores at a time
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out or bend, one at a time (tests/test_lfm2.py)
DETAILS = ("no_conv_gate_in", "no_conv_gate_out", "conv_silu",
           "taps_reversed", "no_qk_norm", "no_expert_bias", "no_renorm",
           "bias_in_weights")

_KINDS = {"conv": "conv", "full_attention": "attn"}


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's Lfm2Config."""
    from dynamo_tpu.models.lfm2 import Lfm2Config

    kinds = hf["layer_types"]
    if len(kinds) != hf["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(kinds)} layers, "
                         f"num_hidden_layers {hf['num_hidden_layers']}")
    odd = sorted(set(kinds) - set(_KINDS))
    if odd:
        raise ValueError(f"layer types {odd} are not modelled (only "
                         f"{sorted(_KINDS)})")
    rope = hf["rope_parameters"]
    for key, got, want in (
            ("model_type", hf.get("model_type", "lfm2_moe"), "lfm2_moe"),
            ("conv_bias", hf.get("conv_bias", False), False),
            ("rope_type", rope.get("rope_type", "default"), "default"),
            ("norm_topk_prob", hf["norm_topk_prob"], True),
            ("use_expert_bias", hf["use_expert_bias"], True),
            ("tie_word_embeddings", hf.get("tie_word_embeddings", True),
             True)):
        if got != want:
            raise ValueError(f"{key} = {got!r} is not modelled (only "
                             f"{want!r})")
    heads = hf["num_attention_heads"]
    return Lfm2Config(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        layer_kinds=tuple(_KINDS[k] for k in kinds),
        conv_width=hf["conv_L_cache"], n_heads=heads,
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        rope_theta=float(rope["rope_theta"]),
        n_dense_layers=hf["num_dense_layers"],
        ffn_dim=hf["intermediate_size"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        n_experts=hf["num_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        rms_eps=hf["norm_eps"], max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one attention layer: q.k and
    p.v, a multiply and an add each, per query head.  (A conv layer has
    no pairs: its cost a token is fixed.)"""
    return 4.0 * cfg.n_heads * cfg.head_dim


def _conv(cfg, p, h, leave_out):
    T, d, W = h.shape[0], cfg.d_model, cfg.conv_width
    bcu = h @ p["w_in"]
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    g = u if leave_out == "no_conv_gate_in" else b * u
    w = p["conv_w"][::-1] if leave_out == "taps_reversed" else p["conv_w"]
    padded = jnp.concatenate([jnp.zeros((W - 1, d), g.dtype), g], 0)
    conv = sum(w[j] * padded[j:j + T] for j in range(W))
    if leave_out == "conv_silu":          # the repo's other convolutions
        conv = jax.nn.silu(conv)
    y = conv if leave_out == "no_conv_gate_out" else c * conv
    return y @ p["w_out"]


def _attention(cfg, p, h, leave_out):
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ p["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    if leave_out != "no_qk_norm":
        q = _rms(q, p["q_norm"]["norm"], cfg.rms_eps)
        k = _rms(k, p["k_norm"]["norm"], cfg.rms_eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads     # query head i reads kv i//group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    # ATTN_ROWS queries at a time against every key; the last block's
    # filler queries sit at position T - 1 and are cut off again
    rows = min(ATTN_ROWS, T)
    pad = -T % rows
    scale = jnp.sqrt(jnp.asarray(cfg.head_dim, h.dtype))

    def block(args):
        qb, i = args                                      # [rows, H, hd]
        s = jnp.einsum("ihd,jhd->hij", qb, k) / scale
        s = jnp.where(pos[None, None, :] <= i[None, :, None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, -1), v)

    split = lambda x: jnp.pad(
        x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), mode="edge"
    ).reshape((T + pad) // rows, rows, *x.shape[1:])
    o = jax.lax.map(block, (split(q), split(pos)))
    o = o.reshape(T + pad, -1)[:T]
    return o @ p["wo"]


def _route(cfg, p, h, leave_out="", forced=None):
    """(weights [T, k], expert ids [T, k]) over ALL the router's outputs,
    float32 whatever the rest computes in.  `forced` [T, k], where
    given, is the choice (another computation's); the weights are still
    this one's scores there."""
    s = jax.nn.sigmoid(h.astype(F32) @ p["moe_gate"].astype(F32))
    bias = p["moe_gate_bias"].astype(F32)
    choice = s if leave_out == "no_expert_bias" else s + bias
    ids = jax.lax.top_k(choice, cfg.experts_per_token)[1] \
        if forced is None else forced
    w = jnp.take_along_axis(
        choice if leave_out == "bias_in_weights" else s, ids, 1)
    if leave_out != "no_renorm":
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return w * cfg.routed_scaling_factor, ids


def _swiglu(x, w_gate, w_up, w_down):
    dt = x.dtype
    return (jax.nn.silu(x @ w_gate.astype(dt)) * (x @ w_up.astype(dt))) \
        @ w_down.astype(dt)


def _routed(cfg, layer, h, w, ids):
    """Each token through those of its own k experts that this program
    holds, one at a time; a pick held elsewhere adds nothing."""
    first, count = cfg.held

    def one_token(args):
        x, wk, ek = args
        out = jnp.zeros_like(x)
        for j in range(ek.shape[0]):
            e = ek[j] - first
            out = out + jax.lax.cond(
                (e >= 0) & (e < count),
                lambda e=e, j=j: wk[j].astype(x.dtype) * _swiglu(
                    x, layer["moe_w_gate"][e], layer["moe_w_up"][e],
                    layer["moe_w_down"][e]),
                lambda: jnp.zeros_like(x))
        return out

    return jax.lax.map(one_token, (h, w, ids))


def _layer(cfg, kind, layer, x, leave_out="", dt=F32, forced=None):
    """-> (x after the layer, the experts chosen [T, k]; [T, 0] for a
    dense layer)."""
    small = {k: v for k, v in layer.items() if not k.startswith("moe_w_")}
    p = jax.tree_util.tree_map(lambda a: a.astype(dt), small)
    h = _rms(x, p["op_norm"]["norm"], cfg.rms_eps)
    x = x + (_conv if kind == "conv" else _attention)(cfg, p, h, leave_out)
    h = _rms(x, p["ffn_norm"]["norm"], cfg.rms_eps)
    if "moe_gate" not in layer:
        return (x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]),
                jnp.zeros((x.shape[0], 0), jnp.int32))
    w, ids = _route(cfg, p, h, leave_out, forced)
    return x + _routed(cfg, layer, h, w, ids), ids


def reference_forward(params: Dict[str, Any], cfg,
                      token_ids: Sequence[int], leave_out: str = "",
                      at: Optional[Sequence[int]] = None,
                      compute_dtype=F32, picks=None,
                      return_picks: bool = False):
    """-> logits [len(at) or T, vocab] float32 of one full forward over
    `token_ids`; one jitted layer at a time, the head in blocks of the
    vocabulary and only at the positions `at` (all where None).
    `compute_dtype` other than float32 is the CONTROL: the same
    arithmetic in the precision below the one stated.  `picks`, a list
    a layer of the experts to route by ([T, k]; ignored at a dense
    layer), takes the choice from another computation;
    `return_picks` -> (logits, the list of this forward's own)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    dt = compute_dtype
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(dt)
        # one jit a kind: a dense layer's tree and an expert layer's
        # are two traces of it
        fns = {kind: jax.jit(lambda lp, x, forced, kind=kind: _layer(
            cfg, kind, lp, x, leave_out, dt, forced))
            for kind in ("conv", "attn")}
        for li, (kind, lp) in enumerate(zip(cfg.layer_kinds,
                                            params["layers"])):
            x, ids = fns[kind](lp, x, None if picks is None or
                               "moe_gate" not in lp else picks[li])
            chosen.append(ids)
        if at is not None:
            x = x[jnp.asarray(at)]
        x = _rms(x, params["final_norm"]["norm"].astype(dt), cfg.rms_eps)
        head = params["embedding"].T
        block = jax.jit(lambda x, w: (x @ w.astype(dt)).astype(F32))
        logits = jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
    return (logits, chosen) if return_picks else logits


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int],
                     leave_out: str = "") -> jax.Array:
    """[T, vocab] float32 logits of one full forward over `token_ids`."""
    return reference_forward(params, cfg, token_ids, leave_out)
