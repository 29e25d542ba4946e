"""Block-selecting sparse attention beside lightning linear-attention
layers under muP scaling (the MiniCPM-SALA architecture): plain float32
reference.

The published layers, written out (ISSUE 47; the configuration file's
`assumed` lists what the config does not settle).  d = hidden_size,
layer l is sparse where mixer_types[l] is `minicpm4`, lightning where it
is `lightning-attn`.

    x0 = E[token] * scale_emb
    every sublayer:  x <- x + f(RMSNorm(x)) * scale_depth / sqrt(depth)
    logits = RMSNorm(x_L) / (hidden_size / dim_model_base) @ W_head
    mlp:   (silu(h Wg) * (h Wu)) Wd

    sparse, h = RMSNorm(x):
      q = h Wq [nh x hd], k = h Wk, v = h Wv [nkv x hd]; per-head RMSNorm
      on q and k; no rotary.  A KV head: c_i = mean(k[s i : s i + K]),
      visible to query t once s i + K - 1 <= t.  t + 1 <= dense_len:
      query t attends every s <= t.  Otherwise, a KV group (G heads):
        a_h[t, i] = softmax_i(q[t, h] . c_i / sqrt(hd)) over visible i
        A[t, i]   = sum over the group's heads
        P[t, j]   = max of A[t, i] over the windows that touch block j
        chosen    = the first init_blocks blocks, the window / block
                    blocks ending with the query's own, and the other
                    blocks j <= t // block with the largest P until topk
                    in all, ties to the lower index
      query t attends s <= t with s // block chosen.
      o = o * sigmoid(h Wgate);  y = o Wo

    lightning, h = RMSNorm(x):
      q, k, v = h Wq, h Wk, h Wv [nh x hd each]; per-head RMSNorm on q
      and k; rotary on q and k (all dims, rotate-half)
      S_t = lambda_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t / sqrt(hd)
      o = RMSNorm_head(o) * sigmoid(h Wgate);  y = o Wo

Whole sequence at once, no cache, no kernels, no batching: the lightning
layers are the TOKEN-BY-TOKEN recurrence (a `lax.scan` over positions;
the program's chunked form, ops/ssm.py, shares nothing with it), the
sparse layers are the equations above with the queries `ATTN_ROWS` at a
time, the choice by a stable sort (the program bisects on bit patterns,
ops/sparse_attention.py `topk_mask`).  It reads the engine's parameter
tree (bf16 weights cast to float32 where they are used) one jitted layer
at a time and forms the output head only at the positions asked for, so
that 12 k positions at the published widths fit beside the engine.

`leave_out` lets a test drop one detail at a time and see that the
comparison notices.  `taps`, if given, receives a dict a layer: "kind",
"mix" (the mixer's read [T, nh, hd], before norm and gate) and, for a
sparse layer, "chosen" [T, nkv, blocks] bool.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .llama import F32, _rms, _rope

ATTN_ROWS = 128         # queries of a sparse layer's score block at a time
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out, one at a time (tests/test_minicpm_sala.py)
DETAILS = ("selection", "forced_blocks", "head_sum", "max_pool",
           "dense_len", "compressed_window", "decay", "lightning_rope",
           "output_gate", "output_norm", "qk_norm", "scale_emb",
           "scale_depth", "logit_scale")

_SWITCHES = (("attention_bias", False), ("attn_use_rope", False),
             ("hidden_act", "silu"), ("lightning_use_rope", True),
             ("lightning_scale", "1/sqrt(d)"), ("qk_norm", True),
             ("use_output_gate", True), ("use_output_norm", True),
             ("attn_use_output_gate", True))


def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's SalaConfig.  The
    block sizes are not in the published config.json: the file holds
    them under `assumed.sparse_config` (a top-level `sparse_config`, as
    the file's `rehearse` group gives, wins).  The residual scale's
    depth is the PUBLISHED one: `reduced.num_hidden_layers.source` where
    the file cut the layers."""
    from dynamo_tpu.models.minicpm_sala import KIND_OF, SalaConfig

    for key, want in _SWITCHES:
        if hf.get(key, want) != want:
            raise ValueError(f"{key} = {hf[key]!r} is not modelled "
                             f"(only {want!r})")
    heads, hd = hf["num_attention_heads"], hf["head_dim"]
    if (hf.get("lightning_nh", heads), hf.get("lightning_nkv", heads),
            hf.get("lightning_head_dim", hd)) != (heads, heads, hd):
        raise ValueError("a lightning layer has as many q, k and v heads "
                         "as the sparse layers have query heads, of the "
                         "same width")
    sc = hf.get("sparse_config") or hf["assumed"]["sparse_config"]
    cut = hf.get("reduced", {}).get("num_hidden_layers", {})
    return SalaConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        layer_kinds=tuple(KIND_OF[m] for m in hf["mixer_types"]),
        n_heads=heads, n_kv_heads=hf["num_key_value_heads"], head_dim=hd,
        ffn_dim=hf["intermediate_size"],
        kernel_size=sc["kernel_size"], kernel_stride=sc["kernel_stride"],
        sparse_block=sc["block_size"], init_blocks=sc["init_blocks"],
        window_size=sc["window_size"], topk=sc["topk"],
        dense_len=sc["dense_len"], scale_emb=float(hf["scale_emb"]),
        scale_depth=float(hf["scale_depth"]),
        dim_model_base=hf["dim_model_base"],
        residual_depth=int(cut.get("source", hf["num_hidden_layers"])),
        lightning_chunk=hf.get("lightning_chunk", 128),
        rope_theta=hf["rope_theta"], rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_context=hf["max_position_embeddings"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one (query, key) pair costs in one sparse layer: q.k and
    p.v, a multiply and an add each, per head."""
    return 4.0 * cfg.n_heads * cfg.head_dim


def score_pair_flops(cfg) -> float:
    """FLOPs one (query, compressed key) pair costs in one sparse layer:
    q.c per head."""
    return 2.0 * cfg.n_heads * cfg.head_dim


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def token_recurrence(q, k, v, log_decay, S, scale):
    """The lightning rule, a token at a time: q, k, v [T, H, hd],
    log_decay [H], S [H, dk, dv] -> (o [T, H, dv], S after the last
    token)."""
    lam = jnp.exp(log_decay)[:, None, None]

    def token(S, xs):
        q, k, v = xs
        S = lam * S + k[:, :, None] * v[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q) * scale

    S, o = jax.lax.scan(token, S, (q, k, v))
    return o, S


def _heads(cfg, p, h, leave_out, n_kv):
    T = h.shape[0]
    q = (h @ p["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(T, n_kv, cfg.head_dim)
    v = (h @ p["wv"]).reshape(T, n_kv, cfg.head_dim)
    if leave_out != "qk_norm":
        q = _rms(q, p["q_norm"]["norm"], cfg.rms_eps)
        k = _rms(k, p["k_norm"]["norm"], cfg.rms_eps)
    return q, k, v


def _lightning(cfg, p, log_decay, h, leave_out):
    T = h.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim
    q, k, v = _heads(cfg, p, h, leave_out, H)
    if leave_out != "lightning_rope":
        pos = jnp.arange(T)
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    if leave_out == "decay":
        log_decay = jnp.zeros_like(log_decay)
    o, _ = token_recurrence(q, k, v, log_decay, jnp.zeros((H, hd, hd), F32),
                            1.0 / math.sqrt(hd))
    return o


def _softmax_where(s, keep):
    """softmax over the entries `keep` marks; zeros where it marks
    none."""
    e = jnp.where(keep, jnp.exp(s - jnp.max(
        jnp.where(keep, s, -jnp.inf), -1, keepdims=True)), 0.0)
    e = jnp.where(keep, e, 0.0)
    return e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)


def _choose(cfg, leave_out, a, t, K, NB):
    """a [rows, nkv, G, NW] the softmax over the visible windows (0
    elsewhere), t [rows] -> [rows, nkv, NB] bool."""
    s, B = cfg.kernel_stride, cfg.sparse_block
    NW = a.shape[-1]
    # DETAIL head_sum: the group's heads are summed (head 0 alone without)
    A = a[:, :, 0] if leave_out == "head_sum" else a.sum(axis=2)
    # the windows that touch block j: s i + K > B j and s i < B (j + 1)
    i = jnp.arange(NW)[None, :]
    j = jnp.arange(NB)[:, None]
    touch = (s * i + K > B * j) & (s * i < B * (j + 1))       # [NB, NW]
    if leave_out == "max_pool":
        P = jnp.einsum("rkw,jw->rkj", A, touch.astype(F32)) \
            / jnp.maximum(touch.sum(-1), 1)
    else:
        P = jnp.max(jnp.where(touch[None, None], A[:, :, None, :], 0.0), -1)
    own = (t // B)[:, None]
    jj = jnp.arange(NB)[None, :]
    causal = jj <= own
    forced = (jj < cfg.init_blocks) | (jj > own - cfg.window_size // B)
    if leave_out == "forced_blocks":
        forced = jnp.zeros_like(forced)
    score = jnp.where(forced[:, None, :], jnp.inf, P)
    score = jnp.where(causal[:, None, :], score, -jnp.inf)
    # a stable sort, largest first: ties go to the lower index
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < cfg.topk) & causal[:, None, :]


def _sparse(cfg, p, h, leave_out):
    """-> (o [T, nh, hd], chosen [T, nkv, NB])."""
    T = h.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = nh // nkv
    s, B = cfg.kernel_stride, cfg.sparse_block
    # DETAIL compressed_window: a key averages `kernel_size` tokens
    # (one stride's, without)
    K = s if leave_out == "compressed_window" else cfg.kernel_size
    q, k, v = _heads(cfg, p, h, leave_out, nkv)
    NW = max((T - K) // s + 1, 1)
    starts = s * jnp.arange(NW)
    win = jnp.clip(starts[:, None] + jnp.arange(K)[None, :], 0, T - 1)
    c = k[win].mean(axis=1)                               # [NW, nkv, hd]
    NB = (T - 1) // B + 1
    pos = jnp.arange(T)
    dense_len = 0 if leave_out == "dense_len" else cfg.dense_len
    # the queries in whole blocks of `rows`: the last block is padded
    # with copies of the last query and cut off again
    rows = min(ATTN_ROWS, T)
    pad = -T % rows
    scale = 1.0 / math.sqrt(hd)

    def block(args):
        qb, t = args                                   # [rows, nh, hd]
        qg = qb.reshape(rows, nkv, G, hd)
        sc = jnp.einsum("rkgh,wkh->rkgw", qg, c) * scale
        seen = (starts[None, :] + K - 1 <= t[:, None])[:, None, None, :]
        a = _softmax_where(sc, seen)
        chosen = _choose(cfg, leave_out, a, t, K, NB)
        every = (jnp.arange(NB)[None, :] <= (t // B)[:, None])[:, None, :]
        if leave_out == "selection":
            chosen = jnp.broadcast_to(every, chosen.shape)
        chosen = jnp.where((t + 1 <= dense_len)[:, None, None], every,
                           chosen)
        keep = jnp.take_along_axis(
            chosen, jnp.broadcast_to((pos // B)[None, None, :],
                                     (rows, nkv, T)), axis=-1) \
            & (pos[None, None, :] <= t[:, None, None])
        sa = jnp.einsum("rkgh,skh->rkgs", qg, k) * scale
        pa = _softmax_where(sa, keep[:, :, None, :])
        return jnp.einsum("rkgs,skh->rkgh", pa, v).reshape(rows, nh, hd), \
            chosen

    def split(x):
        x = jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)])
        return x.reshape((T + pad) // rows, rows, *x.shape[1:])

    o, chosen = jax.lax.map(block, (split(q), split(pos)))
    return o.reshape(T + pad, nh, hd)[:T], \
        chosen.reshape(T + pad, nkv, NB)[:T]


def _layer(cfg, kind, layer, log_decay, x, leave_out=""):
    """-> (x after the layer, the mixer's read, the chosen sets or
    None)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), layer)
    res = 1.0 if leave_out == "scale_depth" else cfg.residual_scale
    h = _rms(x, p["attn_norm"]["norm"], cfg.rms_eps)
    if kind == 0:
        mix, chosen = _lightning(cfg, p, log_decay, h, leave_out), None
        o = mix if leave_out == "output_norm" \
            else _rms(mix, p["o_norm"]["norm"], cfg.rms_eps)
    else:
        mix, chosen = _sparse(cfg, p, h, leave_out)
        o = mix
    o = o.reshape(x.shape[0], -1)
    if leave_out != "output_gate":
        o = o * jax.nn.sigmoid(h @ p["w_ogate"])
    x = x + (o @ p["wo"]) * res
    h = _rms(x, p["mlp_norm"]["norm"], cfg.rms_eps)
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]) * res, \
        mix, chosen


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int], leave_out: str = "",
                     taps: Optional[list] = None,
                     at: Optional[Sequence[int]] = None) -> jax.Array:
    """[len(at) or T, vocab] float32 logits of one full forward over
    `token_ids`; one jitted layer at a time, the head in blocks of the
    vocabulary and only at the positions `at` (all where None)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][jnp.asarray(token_ids)].astype(F32)
        if leave_out != "scale_emb":
            x = x * cfg.scale_emb
        fns = {kind: jax.jit(lambda lp, ld, x, kind=kind: _layer(
            cfg, kind, lp, ld, x, leave_out)) for kind in (0, 1)}
        n_light = 0
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            ld = params["log_decay"][n_light].astype(F32)
            n_light += kind == 0
            x, mix, chosen = fns[kind](lp, ld, x)
            if taps is not None:
                tap = {"kind": kind, "mix": mix}
                if chosen is not None:
                    tap["chosen"] = chosen
                taps.append(tap)
        if at is not None:
            x = x[jnp.asarray(at)]
        x = _rms(x, params["final_norm"]["norm"].astype(F32), cfg.rms_eps)
        if leave_out != "logit_scale":
            x = x / (cfg.d_model / cfg.dim_model_base)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        block = jax.jit(lambda x, w: x @ w.astype(F32))
        logits = jnp.concatenate(
            [block(x, head[:, i:i + HEAD_BLOCK])
             for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)
    return logits
