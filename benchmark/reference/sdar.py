"""Qwen3-MoE-style decoder that generates by DIFFUSION OVER BLOCKS
(SDAR-30B-A3B-Chat, `sdar_moe`): plain float32 reference of the
forward, the pass and the generation rule.

The published layer, written out (ISSUE 51 point 1; the configuration
file's `assumed` lists what the config does not settle).  Layer l of
`num_hidden_layers` (all alike), x the stream, B = `block_length`:

    h = RMSNorm(x);  q = h Wq as heads x head_dim;  k = h Wk, v = h Wv
    as n_kv x head_dim;  RMSNorm over head_dim on each head of q and k
    (weights q_norm, k_norm);  rotary embedding on all of head_dim
    (pairs i, i + head_dim / 2, base rope_theta) at the token's
    absolute position
    o[t] = softmax_s(q[t] . k[s] / sqrt(head_dim)) v[s] over
    s <= B * (t div B) + B - 1: every position of every earlier block
    and ALL of its own (block-causal), nothing beyond what exists
    x += concat(o) Wo;  h2 = RMSNorm(x)
    g = softmax(h2 Wr) over ALL router outputs, the k largest chosen
    and renormalised to sum 1 (norm_topk_prob)
    x += sum over chosen e THAT THIS PROGRAM HOLDS of
         g_e W_down,e (silu(h2 W_gate,e) * (h2 W_up,e))
    no shared expert, no dense layer (mlp_only_layers []).
    Final RMSNorm, untied head.  NO SHIFT: the logits at position i are
    the distribution of token i; a masked position carries
    mask_token_id's embedding.

Generation (the published `block_diffusion_generate`, one sequence):
the prompt's first P0 = B * (P div B) tokens stand clean; block n is
positions [nB, nB + B); the first generated block holds the prompt's
last P mod B tokens, the rest MASK.  A DENOISE pass runs the sequence
with the block's masked positions as MASK; for each still-masked
position x0 = the sampled token, c = its probability; n_s = B div steps
(+ 1 in the first B mod steps passes); the positions transferred are
those with c > threshold if there are at least n_s of them, else the n_s
most confident (ties to the lower position); a transferred position is
final.  When a block holds no mask the next block begins (the program's
COMMIT pass, which rewrites the clean block's K/V, has no counterpart
here: nothing is cached).

Departures from the published code, each noted where it acts:
  * confidence of a greedy request = softmax(logits)[argmax] in float32
    over the UNFILTERED logits (the published sampler reaches greedy
    through top_k = 1, after which every probability is 1 and every
    position passes at once);
  * a mask is a FLAG, not an id: a prompt token or a sampled token equal
    to mask_token_id is a token (the published loop tests equality);
  * only masked positions are ever transferred (the published top-k over
    -inf confidences would rewrite a clean position where n_s exceeds
    the masks left, which the default steps = B never reaches).

`forward` is ONE full forward over a sequence with flags, no cache, no
kernels, no batching; `generate` calls it once a pass over everything
so far.  `reference_logits` answers benchmark/lib/correct.py ("row r
predicts token r + 1") for a model whose logits at a position depend on
which neighbours were still masked: row r holds the logits at position
r + 1 AT THE PASS IN WHICH THE RULE UNMASKS IT, by a teacher-forced
replay of the rule over the tokens given, every block.  Block-causality
makes a clean block's keys and values independent of everything after
it, so the replay computes them ONCE (one full forward over the tokens
given) and then runs every block's passes side by side against them:
pass j of all blocks is one batch of rows whose queries see the clean
keys before their block and their own block's keys of this pass.  That
is the same arithmetic as a full forward a block and pass (tier-1 holds
the two against each other), in 1 + B forwards instead of B x blocks.
The held experts are visited one at a time, weights cast to float32
where they are used, the head in blocks of the vocabulary, so that it
fits beside the engine.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 16384      # vocabulary columns of the output head at a time

# details a test may leave out, one at a time (tests/test_sdar.py)
DETAILS = ("block_causal", "qk_norm", "router_renorm")

def program_config(hf: Dict[str, Any], name: str):
    """The configuration file's keys -> the program's SdarConfig.
    `num_experts` counts the experts HELD here; `router_experts` (the
    published count) is the router's width, `ep_rank` says which share
    this is.  Without them everything is held."""
    from dynamo_tpu.models.sdar import SdarConfig

    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers") \
            or hf.get("attention_bias") or hf.get("use_sliding_window") \
            or not hf.get("norm_topk_prob", True) \
            or (hf.get("rope_scaling") or {}).get(
                "rope_type", "default") != "default":
        raise ValueError("dense layers, attention biases, a sliding "
                         "window, an unnormalised router and scaled "
                         "rotary are not modelled")
    held = hf["num_experts"]
    a = hf["assumed"]
    return SdarConfig(
        name=name, vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        moe_ffn_dim=hf["moe_intermediate_size"],
        n_experts=hf.get("router_experts", held),
        experts_per_token=hf["num_experts_per_tok"],
        experts_held=(hf.get("ep_rank", 0) * held, held),
        rope_theta=hf["rope_theta"], rms_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        max_context=hf["max_position_embeddings"],
        block_length=a["block_length"],
        denoising_steps=a["denoising_steps"], remasking=a["remasking"],
        confidence_threshold=a["confidence_threshold"],
        mask_token_id=a["mask_token_id"],
    )


def attn_pair_flops(cfg) -> float:
    """FLOPs one ATTENDED (query, key) pair costs in one layer: q.k and
    p.v over head_dim, a multiply and an add each, per head."""
    return cfg.n_heads * 4.0 * cfg.head_dim


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, positions, theta):
    """x [T, heads, hd]: rotate the pairs (i, i + hd / 2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _routed(cfg, layer, h, leave_out):
    """g = softmax over all router outputs, the k largest renormalised;
    the held experts one at a time, each for the tokens that chose it."""
    from dynamo_tpu.models.moe import experts_held

    g = jax.nn.softmax(h @ layer["moe_gate"].astype(F32), axis=-1)
    top, ids = jax.lax.top_k(g, cfg.experts_per_token)
    if leave_out != "router_renorm":
        top = top / top.sum(-1, keepdims=True)
    first, count = experts_held(cfg)

    def one(e, out):
        w_e = jnp.sum(jnp.where(ids == first + e, top, 0.0), axis=-1)
        hid = jax.nn.silu(h @ layer["moe_w_gate"][e].astype(F32)) \
            * (h @ layer["moe_w_up"][e].astype(F32))
        return out + w_e[:, None] * (hid @ layer["moe_w_down"][e]
                                     .astype(F32))

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(h))


def _layer(cfg, layer, x, pos, see_own, clean, leave_out):
    """One layer over rows x [T, d] at positions pos.  A query sees the
    rows' own keys where `see_own` [T, T] says so and, where `clean` =
    (k [Tc, nkv, hd], v, see [T, Tc]) is given, those too.  Returns
    (x, k, v of the rows)."""
    T = x.shape[0]
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
    keys, vals, see = k, v, see_own
    if clean is not None:
        keys = jnp.concatenate([clean[0], k])
        vals = jnp.concatenate([clean[1], v])
        see = jnp.concatenate([clean[2], see_own], axis=1)
    group = cfg.n_heads // cfg.n_kv_heads   # query head i reads kv i // group
    kr, vr = jnp.repeat(keys, group, axis=1), jnp.repeat(vals, group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, kr) / jnp.sqrt(F32(cfg.head_dim))
    p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, vr)
    x = x + o.reshape(T, -1) @ layer["wo"].astype(F32)
    h = _rms(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
    return x + _routed(cfg, layer, h, leave_out), k, v


def _block_causal(cfg, pos_q, pos_k, leave_out=""):
    """[Tq, Tk] bool: key s <= the last position of query t's block
    (`block_causal` left out: s <= t, plain causal)."""
    B = cfg.block_length
    end = pos_q if leave_out == "block_causal" else pos_q // B * B + B - 1
    return pos_k[None, :] <= end[:, None]


def _head(params, cfg, x):
    x = _rms(x, params["final_norm"]["norm"], cfg.rms_eps)
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])
    block = jax.jit(lambda x, w: x @ w.astype(F32))
    return jnp.concatenate(
        [block(x, head[:, i:i + HEAD_BLOCK])
         for i in range(0, head.shape[1], HEAD_BLOCK)], axis=1)


def _embed(params, cfg, tokens, masked):
    ids = jnp.where(jnp.asarray(masked, bool), cfg.mask_token_id,
                    jnp.asarray(tokens, jnp.int32))
    return params["embedding"][ids].astype(F32)


def forward(params: Dict[str, Any], cfg, token_ids: Sequence[int],
            masked: Optional[Sequence[bool]] = None,
            rows: Optional[slice] = None, leave_out: str = "",
            keep_kv: bool = False, cast=None):
    """ONE full forward over `token_ids` (`masked` positions carry the
    mask token's embedding) under the block-causal mask, a jitted layer
    at a time.  -> float32 logits of `rows` (all where absent)
    [n, vocab]; with `keep_kv` also every layer's (k, v).  `cast`, if
    given, is applied to each part of the tree as it is used (a layer,
    then the rest): a control may round the weights there without a
    second copy of the whole tree (benchmark/chip_logits_sdar.py)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    T = len(token_ids)
    masked = np.zeros(T, bool) if masked is None else np.asarray(masked)
    pos = jnp.arange(T)
    cast = cast or (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        rest = cast({k: v for k, v in params.items() if k != "layers"})
        x = _embed(rest, cfg, token_ids, masked)
        see = _block_causal(cfg, pos, pos, leave_out)
        layer = jax.jit(lambda lp, x: _layer(cfg, lp, x, pos, see, None,
                                             leave_out))
        kvs = []
        for lp in params["layers"]:
            x, k, v = layer(cast(lp), x)
            if keep_kv:
                kvs.append((k, v))
        logits = _head(rest, cfg, x if rows is None else x[rows])
    return (logits, kvs) if keep_kv else logits


def n_transfer(cfg, step: int) -> int:
    """Positions a denoise pass must transfer at least: B div steps, one
    more in the first B mod steps passes."""
    B, S = cfg.block_length, cfg.denoising_steps
    return max(B // S + (step < B % S), 1)


def choose(cfg, conf: np.ndarray, masked: np.ndarray, step: int,
           threshold: Optional[float] = None) -> np.ndarray:
    """The transfer rule for one block: conf [B] each position's
    confidence, masked [B] bool -> [B] bool, the positions that take
    their token in this pass.  Only masked positions are candidates."""
    thr = cfg.confidence_threshold if threshold is None else threshold
    n_s = n_transfer(cfg, step)
    high = masked & (conf > thr)
    if high.sum() >= n_s:
        return high
    order = np.argsort(np.where(masked, -conf, np.inf), kind="stable")
    take = np.zeros_like(masked)
    take[order[:min(n_s, int(masked.sum()))]] = True
    return take & masked


def _confidence(logits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Greedy: (argmax, softmax(logits)[argmax]) in float32 over the
    unfiltered logits (departure, module docstring)."""
    z = logits.astype(np.float32)
    m = z.max(-1)
    lse = m + np.log(np.exp(z - m[..., None]).sum(-1))
    return z.argmax(-1), np.exp(m - lse)


def generate(params: Dict[str, Any], cfg, prompt: Sequence[int],
             max_tokens: int, threshold: Optional[float] = None,
             stop: Sequence[int] = (), stats: Optional[dict] = None,
             forced: Optional[Sequence[int]] = None,
             taps: Optional[list] = None) -> List[int]:
    """The generation rule, greedy, one sequence, nothing cached: every
    pass is `forward` over everything so far (padded to one length with
    positions of LATER blocks, which no query of the current block sees).
    -> the generated tokens, truncated at `max_tokens` and after a token
    of `stop`.  `stats` receives denoise passes, blocks and the positions
    that passed by the threshold.  `forced`: another generator's tokens,
    taken in place of the reference's own where a position's turn comes
    (the order stays the reference's); `taps` then receives, a generated
    position, (its index among the generated, its logits at the pass that
    unmasked it): what the other generator's token is held against."""
    B = cfg.block_length
    thr = cfg.confidence_threshold if threshold is None else threshold
    seq = list(int(t) for t in prompt)
    P = len(seq)
    T = -(-(P + max_tokens) // B) * B
    out: List[int] = []
    st = {"denoise_passes": 0, "blocks": 0, "threshold_transfers": 0}
    while len(out) < max_tokens:
        start = len(seq) // B * B
        tail = len(seq) - start
        toks = np.zeros(T, np.int64)
        toks[:len(seq)] = seq
        flags = np.zeros(T, bool)
        flags[start + tail:start + B] = True
        step = 0
        while flags[start:start + B].any():
            logits = np.asarray(forward(params, cfg, toks, flags,
                                        rows=slice(start, start + B)))
            x0, conf = _confidence(logits)
            m = flags[start:start + B]
            take = choose(cfg, conf, m, step, thr)
            if (m & (conf > thr)).sum() >= n_transfer(cfg, step):
                st["threshold_transfers"] += int(take.sum())
            if forced is not None:
                at = start + np.arange(B) - P
                x0 = np.where(at < len(forced),
                              np.asarray(forced)[np.minimum(
                                  at, len(forced) - 1)], x0)
            if taps is not None:
                taps.extend((start + j - P, logits[j])
                            for j in np.nonzero(take)[0])
            toks[start:start + B][take] = x0[take]
            flags[start:start + B][take] = False
            step += 1
            st["denoise_passes"] += 1
        st["blocks"] += 1
        for t in toks[start + tail:start + B]:
            seq.append(int(t))
            out.append(int(t))
            if len(out) >= max_tokens or int(t) in stop:
                break
        else:
            continue
        break
    if stats is not None:
        stats.update(st)
    return out


def reference_logits(params: Dict[str, Any], cfg,
                     token_ids: Sequence[int], leave_out: str = "",
                     threshold: Optional[float] = None) -> jax.Array:
    """[T, vocab] float32: row r = the logits at position r + 1 at the
    pass in which the rule unmasks it, by a teacher-forced replay of the
    rule over `token_ids`, block by block (module docstring).  A
    position is filled with the GIVEN token when its turn comes; the
    order is the reference's own, from its own confidences; a position
    of the last block that lies beyond the tokens given is ranked like
    any other and, when its turn comes, filled with the reference's own
    argmax.  Row 0's predecessor, position 0's own logits, is not
    returned (nothing predicts token 0)."""
    if leave_out and leave_out not in DETAILS:
        raise ValueError(f"unknown detail {leave_out!r}; have {DETAILS}")
    B = cfg.block_length
    n = len(token_ids)
    nb = -(-(n + 1) // B)
    R = nb * B
    given = np.zeros(R, np.int64)
    given[:n] = np.asarray(token_ids)
    # the clean blocks' keys and values, once
    _, clean = forward(params, cfg, list(token_ids), rows=slice(0, 0),
                       leave_out=leave_out, keep_kv=True)
    pos = jnp.arange(R)
    see_own = (pos[:, None] // B) == (pos[None, :] // B)
    if leave_out == "block_causal":
        see_own = see_own & (pos[None, :] <= pos[:, None])
    see_clean = jnp.arange(n)[None, :] < (pos // B * B)[:, None]
    toks = np.zeros(R, np.int64)
    flags = np.ones(R, bool)
    out = np.zeros((R, cfg.vocab_size), np.float32)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda lp, x, ck, cv: _layer(
            cfg, lp, x, pos, see_own, (ck, cv, see_clean), leave_out)[0])
        step = 0
        while flags.any():
            x = _embed(params, cfg, toks, flags)
            for lp, (ck, cv) in zip(params["layers"], clean):
                x = layer(lp, x, ck, cv)
            logits = np.asarray(_head(params, cfg, x))
            x0, conf = _confidence(logits)
            for b in range(nb):
                at = slice(b * B, b * B + B)
                if not flags[at].any():
                    continue
                take = choose(cfg, conf[at], flags[at], step, threshold)
                idx = np.nonzero(take)[0] + b * B
                out[idx] = logits[idx]
                toks[idx] = np.where(idx < n, given[idx], x0[idx])
                flags[idx] = False
            step += 1
    # row r <- position r + 1
    return jnp.asarray(out[1:n + 1])
