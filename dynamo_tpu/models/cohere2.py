"""Window + NoPE-global attention beside routed and shared experts in ONE
parallel block (the Command A+ / `cohere2_moe` architecture), functional
JAX over a cache of two kinds, same contract as the other families.

Per layer, by `cfg.layer_kinds[l]` (0 global, 1 window):

    h  = LayerNorm(x)            ONE norm a layer, mean subtracted, no
                                 bias, float32
    q, k, v = h Wq, h Wk, h Wv   n_heads over n_kv_heads of head_dim
    window layer: rotary on all of head_dim, INTERLEAVED pairs
                  (2i, 2i + 1), base `rope_theta`; query i sees keys j
                  with 0 <= i - j < sliding_window
    global layer: no positional encoding at all; causal
    a  = softmax(q k^T / sqrt(head_dim)) v Wo
    f  = sum over the token's k experts THAT THIS SHARE HOLDS of
         w_e SwiGLU_e(h)  +  (1 / n_shared) sum_j Shared_j(h)
         (sigmoid scores, the k largest, weights over their sum:
         models/moe.py `ds_router` at one group and no bias; the
         shared experts are stored as one SwiGLU n_shared x wide and
         their sum is divided by n_shared: an average)
    x' = x + a + f               attention and experts read the SAME h

    logits = LayerNorm(x_L) E^T x logit_scale     tied embedding

A module of its own because every program's layer body differs from
models/mimo.py's (one norm, both branches from one `h`, one add) while
every part it shares is imported: the routing, the expert dispatches and
their counts (models/moe.py: `ds_router`, `moe_dispatch`,
`moe_held_counts`), `_mlp`, `pool_index`, the paged and packed reads and
writes and the ring's addressing (ops/window_attention.py `ring_blocks`, `ring_table`).
`_logits` is this module's own: the final norm subtracts its mean.

Cache (models/__init__.py): (k_global, v_global, k_window, v_window,
counters).  The window pools are rings addressed by lane and position,
`ceil(window / block) + 1` blocks a lane (33 at 4096 over 128), so
`KV_LANE_ADDRESSED`; every program takes the lane of each row.  K and V
are equally wide, so both kinds of layer read through the kernels the
paged pools have (ops/window_attention.py's docstring: the ring as a
block table of period W, a lower bound beside each length):

    decode   global  paged_attention_decode over the block table
             window  the same over the ring's table, `kv_lo`
    prefill  global  write_packed_kv, then packed_prefill_attention
             window  window_prefill_flash (band over [ring's tail ||
                     chunk]), then write_packed_kv over the ring's table

Not carried (`UNSUPPORTED`): prefix reuse (a hit needs the rings at the
boundary), int8 cache, speculation, LoRA, ring prefill, KVBM offload /
onboard and disagg transfer of a two-kind cache, tp > 1.  Packed prefill
IS carried (`prefill_packed`; `prefill_batched` is the same forward over
padded rows).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.packed_prefill import packed_prefill_attention, write_packed_kv
from ..ops.paged_attention import (
    PALLAS_IMPLS,
    paged_attention_decode,
    resolve_decode_impl,
    write_token_kv,
)
from ..ops.window_attention import (
    resolve_window_prefill_impl,
    ring_blocks,
    ring_decode_table,
    ring_pool_blocks,
    ring_table,
    window_prefill_flash,
)
from .common import burst_scan, pool_index, prefill_one_row
from .llama import _mlp
from .moe import ds_router, moe_dispatch, moe_held_counts

GLOBAL, WINDOW = 0, 1


@dataclass(frozen=True)
class Cohere2Config:
    name: str = "tiny-cohere2"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 16
    n_kv_heads: int = 2
    layer_kinds: Tuple[int, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL)
    sliding_window: int = 16
    rope_theta: float = 5e4
    moe_ffn_dim: int = 32         # one expert's width, routed or shared
    n_shared_experts: int = 4     # averaged, added to the routed sum
    n_experts: int = 16           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    # models/moe.py ds_router reads these
    moe_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    tie_embeddings: bool = True
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"         # of both kinds' decode read
    packed_attn_impl: str = "auto"  # of both kinds' prefill read
    eos_token_ids: Tuple[int, ...] = (2,)
    qk_norm: bool = False         # unused; uniform surface

    def __post_init__(self):
        if len(self.layer_kinds) != self.n_layers:
            raise ValueError("layer_kinds needs one entry a layer "
                             f"({self.n_layers})")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head_dim")
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "kvbm", "disagg", "tp")

# the window pools are addressed by lane: prefill programs take `lanes`
KV_LANE_ADDRESSED = True

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode")

PRESETS: Dict[str, Cohere2Config] = {
    "tiny-cohere2": Cohere2Config(),
    # the published shapes (CohereLabs/command-a-plus-05-2026
    # config.json); one chip holds a share of it (benchmark/configs/)
    "command-a-plus": Cohere2Config(
        name="command-a-plus", vocab_size=262144, d_model=4096,
        n_layers=32, n_heads=128, head_dim=128, n_kv_heads=8,
        layer_kinds=(WINDOW, WINDOW, WINDOW, GLOBAL) * 8,
        sliding_window=4096, rope_theta=5e4, moe_ffn_dim=4096,
        n_shared_experts=4, n_experts=128, experts_per_token=8,
        max_context=131072,
    ),
}


# ---------------------------------------------------------------------------
# cache spec (consumed by the engine's _init_kv_cache via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: Cohere2Config, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(k_global, v_global, k_window, v_window, counters).  The global
    pools have `num_blocks` blocks; the window pools a ring a lane."""
    ng, nw = len(cfg.layers_of(GLOBAL)), len(cfg.layers_of(WINDOW))
    rb = ring_pool_blocks(lanes, cfg.sliding_window, block_size)
    plane = (cfg.head_dim, block_size)
    return ((ng, cfg.n_kv_heads, num_blocks) + plane,) * 2 \
        + ((nw, cfg.n_kv_heads, rb) + plane,) * 2 + ((len(KV_COUNTERS),),)


def kv_cache_dtypes(cfg: Cohere2Config) -> Tuple[Any, ...]:
    return (cfg.dtype,) * 4 + (jnp.int32,)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 5


def decode_block_counts(cfg: Cohere2Config, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens, in cache blocks summed over layers and
    steps (engine/core.py _count_decode_attn): `live` what the masks
    need, `read` what the resolved impl moves for BOTH kinds (the kernel
    its live blocks, of the table or of the ring; the jnp gather every
    lane's table width, W for a ring).  And what the rings hold for
    these lanes against what a uniform cache would."""
    ng, nw = len(cfg.layers_of(GLOBAL)), len(cfg.layers_of(WINDOW))
    W = ring_blocks(cfg.sliding_window, block_size)
    pos = ctx[:, None] + np.arange(k)[None, :]          # current token's
    full = -(-(pos + 1) // block_size)                  # blocks, uniform
    lo = np.maximum(pos - cfg.sliding_window + 1, 0)
    win = pos // block_size - lo // block_size + 1      # blocks the mask needs
    n_full, n_win = int(full.sum()), int(win.sum())
    kernel = attn_impl in PALLAS_IMPLS
    return {
        "decode_attn_live_blocks": ng * n_full + nw * n_win,
        "decode_attn_read_blocks":
            ng * (n_full if kernel else k * lanes * table_width)
            + nw * (n_win if kernel else k * lanes * W),
        "kv_window_block_steps": int(np.minimum(full, W).sum()),
        "kv_uniform_block_steps": n_full,
    }


def prefill_token_counts(cfg: Cohere2Config, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos` in a program of `bucket` tokens: (query, key) pairs
    ONE window layer and ONE global layer attend, and the tokens whose
    program's window read ran in the kernel (the rule the traced code
    applies: ops/window_attention.resolve_window_prefill_impl)."""
    seen = pos + 1 + np.arange(chunk, dtype=np.int64)
    kernel = bucket > 0 and resolve_window_prefill_impl(
        cfg.packed_attn_impl, jax.default_backend(), cfg.sliding_window,
        cfg.head_dim, cfg.dtype, bucket,
        cfg.n_heads // cfg.n_kv_heads) in PALLAS_IMPLS
    return {
        "attn_pairs_window.prefill":
            int(np.minimum(seen, cfg.sliding_window).sum()),
        "attn_pairs_global.prefill": int(seen.sum()),
        "prefill_window_kernel_tokens": chunk if kernel else 0,
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: Cohere2Config, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    Only the held experts' stacks are built; the router keeps
    `n_experts` outputs.  The norms' weights are random around 1 so that
    leaving one out changes the answer."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

    def norm(key):
        return {"norm": 1.0 + 0.1 * jax.random.normal(
            key, (cfg.d_model,), jnp.float32)}

    if not cfg.tie_embeddings:
        raise ValueError("the family's output head is its embedding")
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: Dict[str, Any] = place({
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": norm(keys[1]),
    })
    d, f, fs = cfg.d_model, cfg.moe_ffn_dim, \
        cfg.moe_ffn_dim * cfg.n_shared_experts
    held = cfg.held[1]
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 12)
        layers.append(place({
            "attn_norm": norm(k[0]),
            "wq": dense(k[1], (d, cfg.n_heads * cfg.head_dim)),
            "wk": dense(k[2], (d, cfg.n_kv_heads * cfg.head_dim)),
            "wv": dense(k[3], (d, cfg.n_kv_heads * cfg.head_dim)),
            "wo": dense(k[4], (cfg.n_heads * cfg.head_dim, d)),
            "moe_gate": dense(k[5], (d, cfg.n_experts)),
            # a stack's fan-in is its second axis, not the experts held
            "moe_w_gate": dense(k[6], (held, d, f),
                                scale=1.0 / math.sqrt(d)),
            "moe_w_up": dense(k[7], (held, d, f),
                              scale=1.0 / math.sqrt(d)),
            "moe_w_down": dense(k[8], (held, f, d),
                                scale=1.0 / math.sqrt(f)),
            # the n_shared experts side by side: one SwiGLU, and the sum
            # of their outputs is one matmul with the stacked w_down
            "shared": {"w_gate": dense(k[9], (d, fs)),
                       "w_up": dense(k[10], (d, fs)),
                       "w_down": dense(k[11], (fs, d),
                                       scale=1.0 / math.sqrt(f))},
        }))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Cohere's LayerNorm: mean subtracted, no bias; float32 in and out
    (the router reads it unrounded; the matmuls round it themselves)."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * w


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    """Rotary embedding on the pairs (2i, 2i + 1) of the last axis
    (`rope_gptj`; llama.rope pairs i with i + hd / 2).  x [..., T, heads,
    hd], positions [..., T]."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[..., :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@jax.named_scope("dyn.attn_qkv")
def _qkv(layer, cfg: Cohere2Config, kind: int, h: jax.Array,
         positions: jax.Array):
    """h [T, d] -> q [T, nh, hd], k / v [T, nkv, hd]; rotary on window
    layers only."""
    T = h.shape[0]
    q = (h @ layer["wq"]).reshape(T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]).reshape(T, cfg.n_kv_heads, cfg.head_dim)
    if kind == WINDOW:
        q = rope_interleaved(q, positions, cfg.rope_theta)
        k = rope_interleaved(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("dyn.attn_out")
def _attn_out(layer, attn: jax.Array) -> jax.Array:
    """-> [T, d] float32: the accumulator goes to the stream unrounded."""
    return jnp.dot(attn.reshape(attn.shape[0], -1), layer["wo"],
                   preferred_element_type=jnp.float32)


@jax.named_scope("dyn.moe_shared")
def _shared(layer, cfg: Cohere2Config, h: jax.Array) -> jax.Array:
    out = _mlp(layer["shared"], h)
    return out * jnp.asarray(1.0 / cfg.n_shared_experts, out.dtype)


def _experts(layer, cfg: Cohere2Config, hf: jax.Array, h: jax.Array,
             valid: Optional[jax.Array]):
    """The normed stream, float32 `hf` for the router and `h` in the
    weights' dtype for the experts, [T, d] -> (routed + averaged shared
    [T, d] float32, picks on held experts, held experts with a token),
    the counts over valid rows.  The router reads the stream unrounded:
    with random weights the eighth and ninth of 128 scores lie close,
    and a rounded input flips picks against the float32 reference
    (PERF.md section 7t)."""
    top_w, top_e = ds_router(layer, cfg, hf)
    out = moe_dispatch(layer, cfg, h, top_w, top_e, valid)
    return (out.astype(jnp.float32)
            + _shared(layer, cfg, h).astype(jnp.float32),) \
        + moe_held_counts(cfg, top_e, valid)


@jax.named_scope("dyn.lm_head")
def _logits(params, cfg: Cohere2Config, x: jax.Array) -> jax.Array:
    x = layer_norm(x, params["final_norm"]["norm"], cfg.norm_eps)
    return jnp.dot(x.astype(cfg.dtype), params["embedding"].T,
                   preferred_element_type=jnp.float32) * cfg.logit_scale


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def _forward_packed(params, cfg: Cohere2Config, kv_cache, token_ids,
                    positions, seg_ids, block_tables, valid, lanes):
    """A packed stream through every layer (ops/packed_prefill.py's
    contract: a segment row is one run of the stream at consecutive
    positions).  A window layer reads [its ring's tail || the chunk]
    BEFORE the chunk is written (the chunk may overwrite cells its first
    queries see) and then writes the chunk's last `window` positions; a
    global layer writes the chunk and then reads context and chunk from
    the pool together.  -> (x [T, d], cache)."""
    if lanes is None:
        raise ValueError("this family's window pools are addressed by "
                         "lane: prefill needs `lanes`")
    kg, vg, kw, vw, counters = kv_cache
    bs = kw.shape[4]
    W = ring_blocks(cfg.sliding_window, bs)
    rings = ring_table(lanes, W, block_tables.shape[1])
    # a token is kept in its ring if a later chunk or decode can read it
    rows = jnp.arange(lanes.shape[0], dtype=jnp.int32)
    own = valid[None, :] & (seg_ids[None, :] == rows[:, None])
    end = jnp.max(jnp.where(own, positions[None, :], -1), axis=1)
    kept = valid & (positions > end[seg_ids] - cfg.sliding_window)
    # the stream between layers is float32 (section 7t, as above)
    x = params["embedding"][token_ids].astype(jnp.float32)    # [T, d]
    pool_li = pool_index(cfg)
    picks = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        hf = layer_norm(x, layer["attn_norm"]["norm"], cfg.norm_eps)
        h = hf.astype(cfg.dtype)
        q, k, v = _qkv(layer, cfg, kind, h, positions)
        if kind == WINDOW:
            attn = window_prefill_flash(
                q, k, v, kw, vw, pli, lanes, seg_ids, positions, valid,
                cfg.sliding_window, impl=cfg.packed_attn_impl)
            # the read above comes before the write below; without the
            # order said, XLA's TPU compiler keeps a copy of both rings
            # for the read of two layers in three (compiled for a
            # described v5e: tests/test_tpu_compile.py)
            attn, kw, vw = jax.lax.optimization_barrier((attn, kw, vw))
            kw, vw = write_packed_kv(kw, vw, pli, k, v, rings, seg_ids,
                                     positions, kept)
        else:
            kg, vg = write_packed_kv(kg, vg, pli, k, v, block_tables,
                                     seg_ids, positions, valid)
            with jax.named_scope("dyn.attn_global"):
                attn = packed_prefill_attention(
                    q, kg, vg, pli, block_tables, seg_ids, positions,
                    valid, impl=cfg.packed_attn_impl)
        out, n_on, _ = _experts(layer, cfg, hf, h, valid)
        x = x + (_attn_out(layer, attn) + out)
        picks = picks + n_on
    return x, (kg, vg, kw, vw, counters.at[0].add(picks))


def prefill_packed(
    params: Dict[str, Any],
    cfg: Cohere2Config,
    kv_cache,
    token_ids: jax.Array,      # [T] int32 packed stream (tail padded)
    positions: jax.Array,      # [T] int32 absolute position per token
    seg_ids: jax.Array,        # [T] int32 segment row per token
    block_tables: jax.Array,   # [S, mb] int32 per-segment block tables
    last_idx: jax.Array,       # [S] packed index of each row's last token
    valid: jax.Array,          # [T] bool: False on the padded tail
    mesh=None,
    lanes: jax.Array = None,   # [S] the scheduler's lane of each row
):
    """Packed multi-sequence chunked prefill (llama.prefill_packed's
    contract, and `lanes`).  -> (logits [S, vocab], cache)."""
    x, kv_cache = _forward_packed(params, cfg, kv_cache, token_ids,
                                  positions, seg_ids, block_tables, valid,
                                  lanes)
    return _logits(params, cfg, x[last_idx]), kv_cache


def prefill_batched(
    params: Dict[str, Any],
    cfg: Cohere2Config,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
):
    """Multi-sequence chunked prefill, padded per row
    (llama.prefill_batched's contract): the rows laid end to end are a
    packed stream whose padding lies between the runs."""
    Bp, T = token_ids.shape
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    seg = jnp.broadcast_to(jnp.arange(Bp, dtype=jnp.int32)[:, None],
                           (Bp, T))
    x, kv_cache = _forward_packed(
        params, cfg, kv_cache, token_ids.reshape(-1),
        (ctx_lens[:, None] + idx).reshape(-1), seg.reshape(-1),
        block_tables, (idx < true_lens[:, None]).reshape(-1), lanes)
    last = jnp.arange(Bp) * T + jnp.maximum(true_lens - 1, 0)
    return _logits(params, cfg, x[last]), kv_cache


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: Cohere2Config,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    kg, vg, kw, vw, counters = kv_cache
    B = token_ids.shape[0]
    bs = kg.shape[4]
    x = params["embedding"][token_ids].astype(jnp.float32)  # [B, d]
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(), bs,
                               cfg.head_dim, kg.dtype)
    resident = impl in PALLAS_IMPLS
    kv_lens = ctx_lens + 1
    if valid is not None:
        kv_lens = jnp.where(valid, kv_lens, 0)
    W = ring_blocks(cfg.sliding_window, bs)
    rings = ring_table(jnp.arange(B, dtype=jnp.int32), W,
                       block_tables.shape[1])
    if valid is not None:
        # an idle lane may be mid-prefill and must keep its ring: its
        # write goes to the garbage block
        rings = jnp.where(valid[:, None], rings, 0)
    w_table, w_lens, w_lo = ring_decode_table(
        ctx_lens, valid, cfg.sliding_window, bs)
    pool_li = pool_index(cfg)
    picks = visited = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        hf = layer_norm(x, layer["attn_norm"]["norm"], cfg.norm_eps)
        h = hf.astype(cfg.dtype)
        q, k, v = _qkv(layer, cfg, kind, h, positions)
        if kind == WINDOW:
            kw, vw = write_token_kv(kw, vw, pli, k, v, rings, ctx_lens,
                                    resident=resident, valid=valid)
            with jax.named_scope("dyn.attn_window"):
                attn = paged_attention_decode(
                    q, kw, vw, pli, w_table, w_lens, impl=impl, mesh=mesh,
                    kv_lo=w_lo)
        else:
            kg, vg = write_token_kv(kg, vg, pli, k, v, block_tables,
                                    ctx_lens, resident=resident,
                                    valid=valid)
            with jax.named_scope("dyn.attn_global"):
                attn = paged_attention_decode(
                    q, kg, vg, pli, block_tables, kv_lens, impl=impl,
                    mesh=mesh)
        out, n_on, n_seen = _experts(layer, cfg, hf, h, valid)
        x = x + (_attn_out(layer, attn) + out)
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return _logits(params, cfg, x), (kg, vg, kw, vw, counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: Cohere2Config,
    kv_cache,
    token_ids: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    ctx_lens: jax.Array,
    num_steps: int,
    sample_fn=None,
    valid: Optional[jax.Array] = None,
    mesh=None,
):
    """num_steps fused decode steps (llama.decode_multi contract)."""
    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
