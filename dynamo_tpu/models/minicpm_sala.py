"""Block-selecting sparse attention beside lightning linear-attention
layers under muP scaling (the MiniCPM-SALA architecture, `minicpm_sala`),
functional JAX over a cache of TWO kinds; same contract as the other
families.

    x0 = E[token] * scale_emb
    every sublayer:  x <- x + f(RMSNorm(x)) * scale_depth / sqrt(depth)
                     (`residual_depth`: the PUBLISHED depth, whatever
                     share of the layers this program holds)
    logits = RMSNorm(x_L) / (d_model / dim_model_base) @ W_head
    mlp: SwiGLU

Per layer l, by `cfg.layer_kinds[l]` (any list: the published
`mixer_types` is no period):
  * SPARSE (`minicpm4`; ops/block_sparse_attention.py): q as `n_heads`
    heads, k and v as `n_kv_heads` (2 under 32), per-head RMSNorm on q
    and k, NO rotary.  A KV head keeps one mean-pooled compressed key a
    `stride` tokens; a query past `dense_len` scores them, sums the
    softmax over its KV group's heads, max-pools to blocks of `block`
    keys and attends the `topk` best blocks (the first and the local
    ones forced), one set a KV GROUP; at or under `dense_len` it attends
    everything.  The switch is by the query's POSITION (the published
    code switches by the length of the forward call, which chunked
    prefill would make depend on the chunking).  The read is gated
    channel by channel, sigmoid(h Wgate), before Wo.
  * LIGHTNING (`lightning-attn`): q, k, v as `n_heads` heads each,
    per-head RMSNorm on q and k, rotary on both (every dimension,
    rotate-half); S_t = lambda_h S_{t-1} + k_t^T v_t a head in float32,
    o_t = q_t S_t / sqrt(hd); the read is RMS-normed a head, gated by
    sigmoid(h Wgate) and goes through Wo.  lambda_h is a CONSTANT a
    (layer, head): the leaf `log_decay [lightning layers, heads]` beside
    the weights (`init_params` fills it with the Lightning Attention
    convention, log lambda_h = -2^(-8 (h + 1) / heads); a checkpoint's
    own table slots in).  That recurrence is Mamba-2's SSD with a
    constant log-decay (x = v, B = k, C = q / sqrt(hd), dt = 1, one
    group a head), so the layers call ops/ssm.py `ssd_chunked` (prefill)
    and `ssd_step` / ops/pallas_lane_state.py `ssd_lanes_step` (decode):
    no second copy of the rule.  Padding is dt = 0 and v = 0.

Cache (the family contract in models/__init__.py): five members,
(k, v, compressed keys, state, counters).  K, V [sparse layers, nkv,
blocks, hd, block_size] and the compressed keys [sparse layers, blocks,
block_size / stride, nkv, hd] are paged by the sequence's ONE block
table; every program that writes a token's K writes the compressed key
its arrival completes, so a replay after a preemption rebuilds all
three.  `state` [lightning layers, lanes, heads, hd, hd] float32 is
addressed by LANE (`KV_LANE_ADDRESSED`) and is a STATE whose life
ops/lane_state.py keeps (zeroed, carried, untouched, rebuilt: models/
ling.py's words).  `counters`: what only the device knows of the sparse
layers' reads (the pages a decode step moved, the pairs a prefill pass
computed), a layer's mean.

Not carried yet (`UNSUPPORTED`; the engine falls back or refuses, never
answers wrongly): prefix reuse (a hashed block says nothing of the state
at its end: needs snapshots), int8 cache, speculation, LoRA, ring and
packed prefill, KVBM offload / onboard, disagg transfer and migration of
a state, tp > 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.block_sparse_attention import (
    BlockSizes,
    choice_impl,
    compress_chunk,
    compress_token,
    sparse_decode_attention,
    sparse_prefill_attention,
)
from ..ops.lane_state import (
    lanes_plan,
    lanes_step,
    resolve_state_impl,
    rows_put,
    rows_start,
    rows_target,
)
from ..ops.paged_attention import PALLAS_IMPLS, resolve_decode_impl
from ..ops.pallas_lane_state import ssd_lanes_step
from ..ops.sparse_attention import write_packed_members, write_token_members
from ..ops.ssm import ssd_chunked, ssd_step
from .common import burst_scan, pool_index, prefill_one_row
from .llama import _mlp, _qkv, rms_norm, rope

LIGHTNING, SPARSE = 0, 1
KIND_OF = {"lightning-attn": LIGHTNING, "minicpm4": SPARSE}

# the published mixer_types (openbmb/MiniCPM-SALA config.json)
PUBLISHED_KINDS = tuple(int(c) for c in "10000000010000001100001000000111")


@dataclass(frozen=True)
class SalaConfig:
    name: str = "tiny-sala"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 4
    layer_kinds: Tuple[int, ...] = (SPARSE, LIGHTNING, LIGHTNING, SPARSE)
    n_heads: int = 4              # both kinds' query heads
    n_kv_heads: int = 2           # the sparse layers'
    head_dim: int = 16
    ffn_dim: int = 128
    # the sparse layers' block sizes (MiniCPM4's sparse_config)
    kernel_size: int = 4
    kernel_stride: int = 2
    sparse_block: int = 8
    init_blocks: int = 1
    window_size: int = 16
    topk: int = 4
    dense_len: int = 32
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 16
    residual_depth: int = 4       # the PUBLISHED depth
    lightning_chunk: int = 8      # tokens a chunk of the chunked rule
    state_dtype: Any = jnp.float32
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    qk_norm: bool = True
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    # the sparse layers' decode read and prefill pass (paged_attention.
    # resolve_decode_impl) and, by its own conditions, the state's step
    attn_impl: str = "auto"
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        if len(self.layer_kinds) != self.n_layers \
                or set(self.layer_kinds) - {LIGHTNING, SPARSE}:
            raise ValueError(f"layer_kinds {self.layer_kinds} for "
                             f"{self.n_layers} layers")

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def sizes(self) -> BlockSizes:
        return BlockSizes(self.kernel_size, self.kernel_stride,
                          self.sparse_block, self.init_blocks,
                          self.window_size, self.topk, self.dense_len)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.residual_depth)


# what the engine must not promise for this family (engine/core.py
# _family_gaps falls back with a warning or refuses the configuration)
UNSUPPORTED = ("prefix_caching", "kv_int8", "speculation", "lora",
               "ring_prefill", "packed_prefill", "kvbm", "disagg", "tp")

# the state is addressed by lane: prefill takes `lanes`
KV_LANE_ADDRESSED = True

# the cache tuple's last member: what only the device knows, one int32
# each, a sparse layer's mean
KV_COUNTERS = ("sala_read_tokens.decode", "sala_pairs_computed.prefill")

PRESETS: Dict[str, SalaConfig] = {
    "tiny-sala": SalaConfig(),
    # the published shapes (openbmb/MiniCPM-SALA config.json; the block
    # sizes are MiniCPM4's sparse_config); one chip holds a run of its
    # layers (benchmark/configs/)
    "minicpm-sala-9b": SalaConfig(
        name="minicpm-sala-9b", vocab_size=73448, d_model=4096,
        n_layers=32, layer_kinds=PUBLISHED_KINDS, n_heads=32, n_kv_heads=2,
        head_dim=128, ffn_dim=16384, kernel_size=32, kernel_stride=16,
        sparse_block=64, init_blocks=1, window_size=2048, topk=64,
        dense_len=8192, scale_emb=12.0, scale_depth=1.4, dim_model_base=256,
        residual_depth=32, lightning_chunk=128, max_context=524288,
    ),
}


# ---------------------------------------------------------------------------
# cache spec and host-side counts (consumed by the engine via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: SalaConfig, num_blocks: int, block_size: int,
                    lanes: int = 1) -> Tuple[tuple, ...]:
    """(k, v, compressed keys, state, counters).  The first three have
    `num_blocks` blocks and the sparse layers only; the state has one
    entry a lane and lightning layer."""
    cfg.sizes.check(block_size)
    ns, nl = len(cfg.layers_of(SPARSE)), len(cfg.layers_of(LIGHTNING))
    kv = (ns, cfg.n_kv_heads, num_blocks, cfg.head_dim, block_size)
    return (kv, kv,
            (ns, num_blocks, block_size // cfg.kernel_stride,
             cfg.n_kv_heads, cfg.head_dim),
            (nl, lanes, cfg.n_heads, cfg.head_dim, cfg.head_dim),
            (len(KV_COUNTERS),))


def kv_cache_dtypes(cfg: SalaConfig) -> Tuple[Any, ...]:
    return (cfg.dtype,) * 3 + (cfg.state_dtype, jnp.int32)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 5


def _attended(cfg: SalaConfig, t: np.ndarray):
    """For queries at positions t: (causal blocks, blocks kept, keys
    attended, compressed keys the choice must score), by the equations
    alone: at or under `dense_len` everything and no scoring."""
    s = cfg.sizes
    blocks = t // s.block + 1
    sparse = (t + 1 > s.dense_len) & (blocks > s.topk)
    kept = np.where(sparse, s.topk, blocks)
    # the query's own block is the last kept and is full up to t only
    used = np.where(sparse, s.topk * s.block - (s.block - 1 - t % s.block),
                    t + 1)
    seen = np.maximum((t - (s.kernel - 1)) // s.stride + 1, 0)
    return blocks, kept, used, np.where(t + 1 > s.dense_len, seen, 0)


def state_impl(cfg: SalaConfig, attn_impl: str) -> str:
    """The impl of the state's decode step under `attn_impl`, by the
    state's own conditions (ops/lane_state.resolve_state_impl), asked by
    the traced step and by the host's counts alike."""
    return resolve_state_impl(attn_impl, jax.default_backend(),
                              cfg.head_dim, cfg.head_dim, cfg.state_dtype)


def decode_block_counts(cfg: SalaConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a decode burst of `k` steps over active
    lanes holding `ctx` tokens, ONE sparse layer, summed over steps and
    lanes (engine/core.py _count_decode_attn): the blocks the context
    holds and those the choice keeps, the tokens attention uses and the
    compressed keys it must score.  (What the read MOVED is the
    device's count, `sala_read_tokens.decode`: it depends on which
    blocks share a page.)  And the state pool's lanes, under the names
    models/ling.py feeds."""
    nl = len(cfg.layers_of(LIGHTNING))
    t = ctx[:, None] + np.arange(k, dtype=np.int64)[None, :]
    blocks, kept, used, seen = _attended(cfg, t)
    return {
        "sala_ctx_blocks.decode": int(blocks.sum()),
        "sala_kept_blocks.decode": int(kept.sum()),
        "sala_used_tokens.decode": int(used.sum()),
        "sala_scored_keys.decode": int(seen.sum()),
        "recurrent_lane_steps.decode": k * len(ctx),
        "recurrent_slot_steps.decode": k * lanes,
        "state_live_lane_steps.decode": nl * k * len(ctx),
        "state_moved_lane_steps.decode": nl * k * (
            len(ctx) if state_impl(cfg, attn_impl) in PALLAS_IMPLS
            else lanes),
    }


def prefill_token_counts(cfg: SalaConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side counts for `chunk` prompt tokens prefilled from
    position `pos`, ONE sparse layer: the (query, key) pairs the
    equations attend and the (query, compressed key) pairs they score,
    the queries on either side of `dense_len`, with those past it whose
    scores ops/pallas_block_choice.py's kernel made (`choice_impl` of
    the program's `bucket` rows; 0 where the jnp form runs); and the
    tokens through the lightning layers' chunked rule under the names
    models/ling.py feeds (no kernel form: `ssd_chunked` is the one
    form)."""
    nl = len(cfg.layers_of(LIGHTNING))
    t = pos + np.arange(chunk, dtype=np.int64)
    _, _, used, seen = _attended(cfg, t)
    n_dense = int((t + 1 <= cfg.dense_len).sum())
    kernel = choice_impl(cfg.attn_impl, bucket) in PALLAS_IMPLS
    return {
        "sala_pairs_attended.prefill": int(used.sum()),
        "sala_pairs_scored.prefill": int(seen.sum()),
        "sala_dense_queries.prefill": n_dense,
        "sala_sparse_queries.prefill": chunk - n_dense,
        "sala_choice_kernel_queries.prefill":
            chunk - n_dense if kernel else 0,
        "recurrent_tokens.prefill": chunk,
        "recurrent_carried_tokens.prefill": chunk if pos > 0 else 0,
        "recurrent_resets": int(chunk > 0 and pos == 0),
        "state_chunk_tokens.prefill": nl * chunk,
        "state_chunk_kernel_tokens.prefill": 0,
    }


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def default_log_decay(n_layers: int, heads: int) -> jax.Array:
    """log lambda_h = -2^(-8 (h + 1) / heads), every layer alike (the
    Lightning Attention convention)."""
    slope = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1)
                    / heads)
    return jnp.broadcast_to(-slope, (n_layers, heads))


def init_params(cfg: SalaConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    The norms' weights are random around 1 so that leaving one out
    changes the answer."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

    def near_one(key, n):
        return 1.0 + 0.25 * jax.random.normal(key, (n,), jnp.float32)

    keys = jax.random.split(key, cfg.n_layers + 3)
    d, hd = cfg.d_model, cfg.head_dim
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, d), scale=0.02),
        "final_norm": {"norm": jnp.ones((d,), jnp.float32)},
        "log_decay": default_log_decay(len(cfg.layers_of(LIGHTNING)),
                                       cfg.n_heads),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (d, cfg.vocab_size))
    params = place(params)
    layers = []
    for li, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(keys[2 + li], 12)
        kv_dim = cfg.kv_dim if kind == SPARSE else cfg.q_dim
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "wq": dense(k[0], (d, cfg.q_dim)),
            "wk": dense(k[1], (d, kv_dim)),
            "wv": dense(k[2], (d, kv_dim)),
            "w_ogate": dense(k[3], (d, cfg.q_dim)),
            "wo": dense(k[4], (cfg.q_dim, d)),
            "q_norm": {"norm": near_one(k[5], hd)},
            "k_norm": {"norm": near_one(k[6], hd)},
            "w_gate": dense(k[7], (d, cfg.ffn_dim)),
            "w_up": dense(k[8], (d, cfg.ffn_dim)),
            "w_down": dense(k[9], (cfg.ffn_dim, d)),
        }
        if kind == LIGHTNING:
            layer["o_norm"] = {"norm": near_one(k[10], hd)}
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _embed(params, cfg: SalaConfig, token_ids):
    x = params["embedding"][token_ids].astype(jnp.float32) * cfg.scale_emb
    return x.astype(cfg.dtype)


@jax.named_scope("dyn.lm_head")
def _logits(params, cfg: SalaConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"]["norm"], cfg.rms_eps)
    x = (x.astype(jnp.float32)
         / (cfg.d_model / cfg.dim_model_base)).astype(x.dtype)
    head = params["embedding"].T if cfg.tie_embeddings \
        else params["lm_head"]
    return (x @ head).astype(jnp.float32)


@jax.named_scope("dyn.attn_qkv")
def _lightning_qkv(layer, cfg: SalaConfig, h: jax.Array,
                   positions: jax.Array):
    """h [..., seq, d] -> q, k (normed a head, rotated) and v, each
    [..., seq, H, hd]."""
    heads = lambda z: z.reshape(*z.shape[:-1], cfg.n_heads, cfg.head_dim)
    q, k, v = (heads(h @ layer[w]) for w in ("wq", "wk", "wv"))
    q = rms_norm(q, layer["q_norm"]["norm"], cfg.rms_eps)
    k = rms_norm(k, layer["k_norm"]["norm"], cfg.rms_eps)
    return (rope(q, positions, cfg.rope_theta),
            rope(k, positions, cfg.rope_theta), v)


@jax.named_scope("dyn.attn_out")
def _gated_out(layer, cfg: SalaConfig, o: jax.Array, h: jax.Array):
    """o [..., H, hd] a mixer's read, h [..., d] the sublayer's input ->
    [..., d]: a lightning layer's read is RMS-normed a head first; both
    kinds gate channel by channel."""
    if "o_norm" in layer:
        o = rms_norm(o, layer["o_norm"]["norm"], cfg.rms_eps)
    o = o.reshape(*o.shape[:-2], cfg.q_dim).astype(jnp.float32) \
        * jax.nn.sigmoid((h @ layer["w_ogate"]).astype(jnp.float32))
    return o.astype(cfg.dtype) @ layer["wo"]


def _rule(cfg: SalaConfig, log_decay, q, k, v, live):
    """The lightning recurrence as ops/ssm.py's SSD operands: x = v,
    B = k, C = q / sqrt(hd), dt = 1 on a live token and 0 elsewhere
    (where v is 0 too), one group a head, no skip."""
    dt = jnp.broadcast_to(live[..., None], v.shape[:-1]).astype(jnp.float32)
    return (jnp.where(live[..., None, None], v, 0), dt, log_decay, k,
            q.astype(jnp.float32) / math.sqrt(cfg.head_dim),
            jnp.zeros((cfg.n_heads,), jnp.float32))


def _decode_impl(cfg: SalaConfig, k_cache) -> str:
    return resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               k_cache.shape[4], k_cache.shape[3],
                               k_cache.dtype)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill_batched(
    params: Dict[str, Any],
    cfg: SalaConfig,
    kv_cache,
    token_ids: jax.Array,      # [Bp, T_pad]
    positions: jax.Array,      # [Bp, T_pad]
    block_tables: jax.Array,   # [Bp, max_blocks]
    ctx_lens: jax.Array,       # [Bp]
    true_lens: jax.Array,      # [Bp]
    lanes: jax.Array = None,   # [Bp] the scheduler's lane of each row
    taps: Optional[list] = None,
):
    """Multi-sequence chunked prefill (llama.prefill_batched contract),
    padded per row.  `taps`, where given, receives each layer's mixer
    read [Bp, T, heads, hd] (benchmark/chip_logits_sala.py holds them to
    the reference's).  A sparse layer writes the chunk's K and V (whole
    planes in the resident layout) and the compressed keys they
    complete, then reads through the block table; a lightning layer
    takes each row's state from its lane (zeros where the row starts at
    position 0), runs the chunked rule with padding switched off and
    puts it back; a row of no tokens writes nothing."""
    if lanes is None:
        raise ValueError("this family's state is addressed by lane: "
                         "prefill needs `lanes`")
    k_c, v_c, ck, state, counters = kv_cache
    Bp, T = token_ids.shape
    x = _embed(params, cfg, token_ids)                    # [Bp, T, d]
    valid = jnp.arange(T)[None, :] < true_lens[:, None]
    seg_ids = jnp.repeat(jnp.arange(Bp, dtype=jnp.int32), T)
    fresh = ctx_lens == 0
    put = rows_target(lanes, true_lens, state.shape[1])
    pool_li = pool_index(cfg)
    impl = _decode_impl(cfg, k_c)
    res = cfg.residual_scale
    pairs = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        if kind == SPARSE:
            q, k, v = _qkv(layer, cfg, h, None)
            k_c, v_c = write_packed_members(
                (k_c, v_c), pli,
                (k.reshape(Bp * T, *k.shape[2:]),
                 v.reshape(Bp * T, *v.shape[2:])),
                block_tables, seg_ids, positions.reshape(-1),
                valid.reshape(-1))
            ck = compress_chunk(ck, k_c, pli, k, block_tables, ctx_lens,
                                true_lens, cfg.sizes)
            o, n = sparse_prefill_attention(
                q, k_c, v_c, ck, pli, block_tables, ctx_lens, true_lens,
                cfg.sizes, impl)
            pairs = pairs + n
        else:
            q, k, v = _lightning_qkv(layer, cfg, h, positions)
            s0 = rows_start(state, pli, lanes, fresh).astype(jnp.float32)
            with jax.named_scope("dyn.state_chunk"):
                o, s1 = jax.vmap(
                    partial(ssd_chunked, chunk=cfg.lightning_chunk),
                    in_axes=(0, 0, None, 0, 0, None, 0))(
                    *_rule(cfg, params["log_decay"][pli], q, k, v, valid),
                    s0)
            state = rows_put(state, pli, put, s1)
        if taps is not None:
            taps.append(o)
        x = x + (_gated_out(layer, cfg, o, h) * res).astype(cfg.dtype)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + (_mlp(layer, h) * res).astype(cfg.dtype)
    ns = max(len(cfg.layers_of(SPARSE)), 1)
    counters = counters.at[1].add(pairs // ns)
    last = jnp.maximum(true_lens - 1, 0)
    xl = x[jnp.arange(Bp), last]
    return _logits(params, cfg, xl), (k_c, v_c, ck, state, counters)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode(
    params: Dict[str, Any],
    cfg: SalaConfig,
    kv_cache,
    token_ids: jax.Array,      # [B]; row b is lane b
    positions: jax.Array,      # [B]
    block_tables: jax.Array,   # [B, max_blocks]
    ctx_lens: jax.Array,       # [B]
    valid: Optional[jax.Array] = None,
    mesh=None,
    state_plan=None,           # decode_multi's: `lanes_plan`, once a burst
):
    """One token a lane.  A sparse layer writes the token's K and V and
    the compressed key it completes, chooses, and reads the pages that
    hold a chosen block; a lightning layer reads and writes the live
    lanes' state where it lies (rows ARE lanes: `lanes_step`); a lane
    that is not `valid` keeps its state."""
    k_c, v_c, ck, state, counters = kv_cache
    x = _embed(params, cfg, token_ids)                    # [B, d]
    B = x.shape[0]
    live = jnp.ones((B,), bool) if valid is None else valid
    kv_lens = jnp.where(live, ctx_lens + 1, 0)
    pool_li = pool_index(cfg)
    impl = _decode_impl(cfg, k_c)
    s_impl = state_impl(cfg, cfg.attn_impl)
    if state_plan is None:
        state_plan = lanes_plan(live, s_impl)
    res = cfg.residual_scale
    read = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        kind, pli = cfg.layer_kinds[li], pool_li[li]
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        if kind == SPARSE:
            q, k, v = _qkv(layer, cfg, h[:, None, :], None)
            k_c, v_c = write_token_members(
                (k_c, v_c), pli, (k[:, 0], v[:, 0]), block_tables,
                ctx_lens, valid)
            ck = compress_token(ck, k_c, pli, block_tables, ctx_lens,
                                valid, cfg.sizes)
            o, n = sparse_decode_attention(
                q[:, 0], k_c, v_c, ck, pli, block_tables, kv_lens,
                cfg.sizes, impl)
            read = read + n
        else:
            q, k, v = _lightning_qkv(layer, cfg, h[:, None, :],
                                     positions[:, None])
            rule = _rule(cfg, params["log_decay"][pli], q[:, 0], k[:, 0],
                         v[:, 0], jnp.ones((B,), bool))
            with jax.named_scope("dyn.state_step"):
                o, state = lanes_step(state, pli, state_plan,
                                      partial(ssd_step, *rule),
                                      partial(ssd_lanes_step, *rule),
                                      s_impl)
        x = x + (_gated_out(layer, cfg, o, h) * res).astype(cfg.dtype)
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        x = x + (_mlp(layer, h) * res).astype(cfg.dtype)
    ns = max(len(cfg.layers_of(SPARSE)), 1)
    counters = counters.at[0].add(read // ns)
    return _logits(params, cfg, x), (k_c, v_c, ck, state, counters)


def decode_multi(
    params: Dict[str, Any],
    cfg: SalaConfig,
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
    # the busy lanes are the burst's: compacted once, outside the scan
    plan = None if valid is None else lanes_plan(
        valid, state_impl(cfg, cfg.attn_impl))

    def step(kv, tokens, pos, cls):
        return decode(params, cfg, kv, tokens, pos, block_tables, cls,
                      valid=valid, mesh=mesh, state_plan=plan)

    return burst_scan(step, kv_cache, token_ids, positions, ctx_lens,
                      num_steps, sample_fn)
