"""Qwen3-MoE-style decoder that GENERATES BY DIFFUSION OVER BLOCKS (the
SDAR-30B-A3B-Chat language model, `sdar_moe`): a block of
`block_length` positions starts as masks and is filled over several
PASSES, each pass a forward over the whole block under block-causal
attention.  Functional JAX, same contract as the other family modules;
the layer is models/llama.py's and models/moe.py's, imported, not
copied.

Per layer (all alike): q, k, v as `_qkv` makes them (per-head RMSNorm
on q and k, rotary on every dimension at the token's absolute
position); attention of query t over the keys s <= B * (t div B) + B - 1
(every earlier block and ALL of its own: block-causal); FFN: softmax
router over `n_experts`, the top `experts_per_token` renormalised,
SwiGLU experts of which this program holds `experts_held`.  No shift:
the logits at position i are the distribution of token i, and a masked
position carries `mask_token_id`'s embedding.

Generation.  The prompt's first B * (P div B) tokens are prefilled once
under the mask above; the prompt's last P mod B tokens enter the first
generated block unmasked.  A DENOISE pass runs a lane's block (masked
positions as the mask token) against the cache of every earlier block
and its own K/V; every still-masked position gets a token and its
confidence, and the rule transfers the positions over
`confidence_threshold` if there are at least n_s of them, else the n_s
most confident (n_s = B div steps, one more in the first B mod steps
passes; ties to the lower position).  A pass over a block with no mask
left is its COMMIT pass: the K/V it writes are the ones that stay, and
the lane moves to the next block.  Every pass writes the block's K/V in
place, so "keep K/V from the clean pass only" needs no second path.

What the engine learns from this module beyond the usual contract
(models/__init__.py): `GEN_BLOCK(cfg)`, the block length, and
`denoise_multi`, which stands where `decode_multi` stands for the other
families.  A lane's state between passes is (block tokens, mask flags,
block start, step index), `lane_state_width(cfg)` int32 columns a lane.

Cache: llama's (k, v) pools and a vector of device-side counts
(`KV_COUNTERS`).

Not carried yet (`UNSUPPORTED`; the engine refuses, never answers
wrongly): int8 cache, speculation, LoRA, ring prefill, KVBM, disagg,
tp > 1, and guided decoding, penalties and logprobs (each needs one
distribution a token: engine/core.py refuses them at admission).
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
)
from .common import prefill_one_row
from .llama import _attn_out, _logits, _qkv, rms_norm
from .moe import (
    experts_held,
    moe_dispatch,
    moe_held_counts,
    softmax_router,
)

@dataclass(frozen=True)
class SdarConfig:
    name: str = "tiny-sdar"
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    moe_ffn_dim: int = 32
    n_experts: int = 16           # the ROUTER's width
    experts_per_token: int = 4
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    expert_shards: int = 1        # moe.py: set by the engine from the mesh
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    qk_norm: bool = True
    tie_embeddings: bool = False
    max_context: int = 8192
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"          # the pass's read of the cache
    packed_attn_impl: str = "auto"   # the prefill's
    eos_token_ids: Tuple[int, ...] = (2,)
    # generation by diffusion: the MODEL's, not the engine's
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 255

    def __post_init__(self):
        first, count = experts_held(self)
        if not (0 <= first and count > 0
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")
        if self.remasking != "low_confidence_dynamic":
            raise ValueError(f"remasking {self.remasking!r}: only "
                             "low_confidence_dynamic is modelled")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError("denoising_steps outside [1, block_length]")

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


UNSUPPORTED = ("kv_int8", "speculation", "lora", "ring_prefill", "kvbm",
               "disagg", "tp", "guided", "penalties", "logprobs")

# the cache tuple's last member: device-side counts, one int32 each
KV_COUNTERS = ("moe_picks_held.prefill", "moe_picks_held.decode",
               "moe_experts_visited.decode", "diff_threshold_transfers")

PRESETS: Dict[str, SdarConfig] = {
    "tiny-sdar": SdarConfig(),
    # the published shapes (JetLM/SDAR-30B-A3B-Chat config.json); one
    # chip holds some of its layers (benchmark/configs/)
    "sdar-30b-a3b": SdarConfig(
        name="sdar-30b-a3b", vocab_size=151936, d_model=2048, n_layers=48,
        n_heads=32, n_kv_heads=4, head_dim=128, moe_ffn_dim=768,
        n_experts=128, experts_per_token=8, max_context=32768,
        mask_token_id=151669, eos_token_ids=(151643,),
    ),
}


def GEN_BLOCK(cfg: SdarConfig) -> int:
    """The contract's name for a family that generates by blocks: the
    block length.  The engine then prefills B * (P div B) tokens in
    chunks that end on a multiple of B, takes no token from a prefill,
    and runs `denoise_multi` where it runs `decode_multi`."""
    return cfg.block_length


def lane_state_width(cfg: SdarConfig) -> int:
    """int32 columns of a lane's state between passes: the block's
    tokens, its mask flags, its first position, its step index."""
    return 2 * cfg.block_length + 2


def pack_lane_state(blk, msk, pos, stp):
    return jnp.concatenate([blk.astype(jnp.int32), msk.astype(jnp.int32),
                            pos[:, None].astype(jnp.int32),
                            stp[:, None].astype(jnp.int32)], axis=1)


def unpack_lane_state(cfg: SdarConfig, state):
    """-> (block tokens [L, B], mask flags [L, B], block start [L],
    step [L])."""
    B = cfg.block_length
    return (state[:, :B], state[:, B:2 * B] != 0, state[:, 2 * B],
            state[:, 2 * B + 1])


def new_lane_state(cfg: SdarConfig, start: int, known) -> np.ndarray:
    """The state of a lane that joins the passes, on the host: its block
    starts at `start` (everything before is in the cache) and holds the
    `known` tokens (the prompt's last P mod B after a prefill) unmasked;
    the rest are masks, the step 0."""
    B = cfg.block_length
    state = np.zeros(lane_state_width(cfg), np.int32)
    state[:len(known)] = known
    state[B + len(known):2 * B] = 1
    state[2 * B] = start
    return state


# ---------------------------------------------------------------------------
# cache spec and host-side counts (consumed by the engine via get_family)
# ---------------------------------------------------------------------------


def kv_cache_shapes(cfg: SdarConfig, num_blocks: int,
                    block_size: int) -> Tuple[tuple, ...]:
    """(k, v, counters): llama's pools and the counts."""
    if block_size % cfg.block_length:
        raise ValueError(f"block_size {block_size} is not a multiple of "
                         f"the diffusion block {cfg.block_length}")
    kv = (cfg.n_layers, cfg.n_kv_heads, num_blocks, cfg.head_dim,
          block_size)
    return kv, kv, (len(KV_COUNTERS),)


def kv_cache_dtypes(cfg: SdarConfig) -> Tuple[Any, ...]:
    return (cfg.dtype,) * 2 + (jnp.int32,)


def kv_cache_specs() -> Tuple[P, ...]:
    """tp > 1 is not carried: everything replicated."""
    return (P(),) * 3


def decode_block_counts(cfg: SdarConfig, ctx: np.ndarray, k: int,
                        block_size: int, lanes: int, table_width: int,
                        attn_impl: str) -> Dict[str, int]:
    """Host-side counts for a burst of `k` PASSES over active lanes
    whose block starts at `ctx`, in cache blocks a layer summed over
    passes and lanes (engine/core.py _count_decode_attn): a pass reads
    the lane's context to its block's end, ctx + B tokens, once for the
    block's B queries.  How far a lane advances inside the burst is
    data; the count takes the burst's first position."""
    live = int(np.sum(-(-(ctx + cfg.block_length) // block_size))) * k
    read = live if attn_impl in PALLAS_IMPLS else k * lanes * table_width
    return {"decode_attn_live_blocks": cfg.n_layers * live,
            "decode_attn_read_blocks": cfg.n_layers * read}


def prefill_token_counts(cfg: SdarConfig, pos: int, chunk: int,
                         bucket: int = 0) -> Dict[str, int]:
    """Host-side count for `chunk` prompt tokens prefilled from position
    `pos`, in (query, key) pairs a layer under the block-causal mask: a
    token sees everything to its block's end.  (`bucket`, a padded
    program's rows, is the contract's: pairs do not pad.)"""
    B = cfg.block_length
    at = pos + np.arange(chunk, dtype=np.int64)
    return {"diff_pairs.prefill": int((at // B * B + B).sum())}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: SdarConfig, key: jax.Array,
                place=lambda tree: tree) -> Dict[str, Any]:
    """Random-init parameter pytree; `place` as in llama.init_params.
    The q/k norms' weights are random around 1 so that leaving one out
    changes the answer."""

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            cfg.dtype)

    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=0.02),
        "final_norm": {"norm": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], (cfg.d_model, cfg.vocab_size))
    params = place(params)
    d, f = cfg.d_model, cfg.moe_ffn_dim
    held = experts_held(cfg)[1]
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 9)
        layer: Dict[str, Any] = {
            "attn_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "mlp_norm": {"norm": jnp.ones((d,), jnp.float32)},
            "wq": dense(k[0], (d, cfg.q_dim)),
            "wk": dense(k[1], (d, cfg.kv_dim)),
            "wv": dense(k[2], (d, cfg.kv_dim)),
            "wo": dense(k[3], (cfg.q_dim, d)),
            "moe_gate": dense(k[4], (d, cfg.n_experts)),
            "moe_w_gate": dense(k[5], (held, d, f),
                                scale=1.0 / math.sqrt(d)),
            "moe_w_up": dense(k[6], (held, d, f),
                              scale=1.0 / math.sqrt(d)),
            "moe_w_down": dense(k[7], (held, f, d),
                                scale=1.0 / math.sqrt(f)),
        }
        if cfg.qk_norm:
            kq, kk = jax.random.split(k[8])
            layer["q_norm"] = {"norm": 1.0 + 0.25 * jax.random.normal(
                kq, (cfg.head_dim,), jnp.float32)}
            layer["k_norm"] = {"norm": 1.0 + 0.25 * jax.random.normal(
                kk, (cfg.head_dim,), jnp.float32)}
        layers.append(place(layer))
    params["layers"] = layers
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _ffn(layer, cfg: SdarConfig, x: jax.Array, valid):
    """x [T, d] -> (out [T, d], picks on held experts, held experts with
    a token), the two counts over valid rows."""
    top_w, top_e = softmax_router(layer, cfg, x)
    out = moe_dispatch(layer, cfg, x, top_w, top_e, valid)
    return (out,) + moe_held_counts(cfg, top_e, valid)


def block_upper(cfg: SdarConfig, positions, seg_ids, valid, rows: int):
    """The frontier of each token of a packed stream: its diffusion
    block's last position, capped at the last position its row holds
    in this stream (nothing beyond what exists)."""
    B = cfg.block_length
    last = jax.ops.segment_max(jnp.where(valid, positions, -1), seg_ids,
                               num_segments=rows)
    return jnp.minimum(positions // B * B + B - 1,
                       jnp.maximum(last[seg_ids], positions))


def _packed_forward(params, cfg: SdarConfig, kv_cache, token_ids,
                    positions, seg_ids, block_tables, valid, mesh=None):
    """llama._packed_forward under the block-causal bound -> (hidden
    [T, d], cache, picks on held experts summed over layers).  The
    chunk's K/V are written first; attention then
    reads everything through the block table, so a chunk has to end
    where a diffusion block ends (engine/prefill.py holds it to that)."""
    k_c, v_c, counters = kv_cache
    T = token_ids.shape[0]
    upper = block_upper(cfg, positions, seg_ids, valid,
                        block_tables.shape[0])
    x = params["embedding"][token_ids].astype(cfg.dtype)
    picks = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions)
        k_c, v_c = write_packed_kv(k_c, v_c, li, k, v, block_tables,
                                   seg_ids, positions, valid)
        attn = packed_prefill_attention(
            q, k_c, v_c, li, block_tables, seg_ids, positions, valid,
            impl=cfg.packed_attn_impl, mesh=mesh, upper=upper)
        x = x + _attn_out(layer, attn.reshape(T, cfg.q_dim))
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, _ = _ffn(layer, cfg, h, valid)
        x = x + out
        picks = picks + n_on
    return x, (k_c, v_c, counters), picks


# ---------------------------------------------------------------------------
# prefill: one body, the packed stream's
# ---------------------------------------------------------------------------


def prefill_packed(params, cfg: SdarConfig, kv_cache, token_ids, positions,
                   seg_ids, block_tables, last_idx, valid, mesh=None):
    """llama.prefill_packed's contract under the block-causal mask.
    -> (logits [S, vocab] at each segment's last packed token, cache);
    the engine takes no token from them (`GEN_BLOCK`)."""
    x, (k_c, v_c, counters), picks = _packed_forward(
        params, cfg, kv_cache, token_ids, positions, seg_ids,
        block_tables, valid, mesh=mesh)
    return (_logits(params, cfg, x[last_idx]),
            (k_c, v_c, counters.at[0].add(picks)))


def prefill_batched(params, cfg: SdarConfig, kv_cache, token_ids, positions,
                    block_tables, ctx_lens, true_lens):
    """llama.prefill_batched's contract ([Bp, T_pad] padded rows), laid
    end to end as a packed stream whose segments are the rows
    (models/keye.py's way)."""
    Bp, T = token_ids.shape
    valid = (jnp.arange(T)[None, :] < true_lens[:, None]).reshape(-1)
    seg_ids = jnp.repeat(jnp.arange(Bp, dtype=jnp.int32), T)
    last = jnp.arange(Bp) * T + jnp.maximum(true_lens - 1, 0)
    return prefill_packed(params, cfg, kv_cache, token_ids.reshape(-1),
                          positions.reshape(-1), seg_ids, block_tables,
                          last, valid)


# one sequence's chunk (llama.prefill contract): a batch of one
prefill = prefill_one_row(prefill_batched)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _pass_hidden(params, cfg: SdarConfig, kv_cache, tokens, pos,
                 block_tables, valid, mesh=None):
    """One pass of every lane's block: tokens [L, B] (masked positions
    already the mask token), pos [L] the block's first position, valid
    [L] -> (hidden [L * B, d] rows lane-major, cache with the blocks'
    K/V written in place and the two expert counts added).  The read is the paged
    decode read with a lane's B queries as B x group query heads a KV
    head against ONE kv length: they share a frontier, and that IS
    attention both ways inside the block.  (The packed stream's read
    under `upper`, which the prefill uses, computes the same numbers
    and was 2 to 2.6 times slower a pass on a v5e: 24.4-31.8 ms against
    12.0 at 32 lanes, PERF.md section 6, PR 51: not kept.)"""
    k_c, v_c, counters = kv_cache
    L, B = tokens.shape
    T = L * B
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = (pos[:, None] + jnp.arange(B, dtype=jnp.int32)).reshape(T)
    seg_ids = jnp.repeat(jnp.arange(L, dtype=jnp.int32), B)
    rows = jnp.repeat(valid, B)
    impl = resolve_decode_impl(cfg.attn_impl, jax.default_backend(),
                               k_c.shape[4], k_c.shape[3], k_c.dtype)
    # an idle lane claims no context: it reads nothing
    kv_lens = jnp.where(valid, pos + B, 0)
    x = params["embedding"][tokens.reshape(T)].astype(cfg.dtype)
    picks = visited = jnp.zeros((), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"]["norm"], cfg.rms_eps)
        q, k, v = _qkv(layer, cfg, h, positions)
        # a diffusion block lies inside one cache block: one plane a lane
        k_c, v_c = write_packed_kv(k_c, v_c, li, k, v, block_tables,
                                   seg_ids, positions, rows)
        # the block's B queries as B x group heads of their KV head
        qb = q.reshape(L, B, nkv, nh // nkv, hd).transpose(
            0, 2, 1, 3, 4).reshape(L, B * nh, hd)
        attn = paged_attention_decode(
            qb, k_c, v_c, li, block_tables, kv_lens, impl=impl, mesh=mesh)
        attn = attn.reshape(L, nkv, B, nh // nkv, hd).transpose(
            0, 2, 1, 3, 4)
        x = x + _attn_out(layer, attn.reshape(T, cfg.q_dim))
        h = rms_norm(x, layer["mlp_norm"]["norm"], cfg.rms_eps)
        out, n_on, n_seen = _ffn(layer, cfg, h, rows)
        x = x + out
        picks, visited = picks + n_on, visited + n_seen
    counters = counters.at[1].add(picks).at[2].add(visited)
    return x, (k_c, v_c, counters)


def denoise(params, cfg: SdarConfig, kv_cache, blk, msk, pos, block_tables,
            valid: Optional[jax.Array] = None, mesh=None):
    """One pass for L lanes: blk [L, B] the blocks' tokens, msk [L, B]
    True where a position is still masked, pos [L] each block's first
    position (everything before it is in the cache).  Writes each
    block's K/V at its positions and returns (logits [L, B, vocab]
    float32, cache): `decode`'s place in the contract."""
    L, B = blk.shape
    if valid is None:
        valid = jnp.ones((L,), bool)
    tokens = jnp.where(msk, jnp.int32(cfg.mask_token_id), blk)
    x, kv_cache = _pass_hidden(params, cfg, kv_cache, tokens, pos,
                               block_tables, valid, mesh=mesh)
    return _logits(params, cfg, x).reshape(L, B, -1), kv_cache


def greedy_with_confidence(logits: jax.Array):
    """logits [..., vocab] float32 -> (argmax, softmax(logits)[argmax]):
    a greedy request's token and its confidence, over the unfiltered
    logits (the published sampler reaches greedy through top_k = 1,
    after which every probability is 1)."""
    top = jnp.max(logits, axis=-1)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.exp(top - lse))


@jax.named_scope("dyn.diff_transfer")
def transfer(cfg: SdarConfig, blk, msk, pos, stp, x0, conf, valid):
    """The rule, a pass: which masked positions take their token, which
    lanes commit.  blk, msk, x0, conf [L, B]; pos, stp, valid [L] ->
    (blk, msk, pos, stp after the pass; out [L, B] the tokens of the
    blocks that lost their last mask in this pass, -1 elsewhere; the
    number of positions that passed by the threshold)."""
    B, S = cfg.block_length, cfg.denoising_steps
    masked = jnp.any(msk, axis=1)
    commit = valid & ~masked
    noising = valid & masked
    n_s = jnp.maximum(B // S + (stp < B % S), 1)[:, None]
    c = jnp.where(msk, conf, -jnp.inf)
    high = msk & (conf > cfg.confidence_threshold)
    enough = jnp.sum(high, axis=1, keepdims=True) >= n_s
    # rank among the block's positions by confidence, ties to the lower
    at = jnp.arange(B)
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (at[None, None, :]
                                            < at[None, :, None]))
    rank = jnp.sum(ahead, axis=2)
    take = jnp.where(enough, high, msk & (rank < n_s)) & noising[:, None]
    by_threshold = jnp.sum(high & enough & noising[:, None],
                           dtype=jnp.int32)
    blk = jnp.where(take, x0, blk)
    msk = msk & ~take
    done = noising & ~jnp.any(msk, axis=1)
    out = jnp.where(done[:, None], blk, -1)
    # a commit pass leaves its lane at the next block, all masks
    blk = jnp.where(commit[:, None], 0, blk)
    msk = msk | commit[:, None]
    pos = jnp.where(commit, pos + B, pos)
    stp = jnp.where(commit, 0, jnp.where(noising, stp + 1, stp))
    return blk, msk, pos, stp, out, by_threshold


def denoise_multi(params, cfg: SdarConfig, kv_cache, state, block_tables,
                  num_passes: int, sample_fn=None,
                  valid: Optional[jax.Array] = None, mesh=None):
    """`num_passes` fused passes in ONE compiled program (lax.scan):
    `decode_multi`'s place in the contract.  state [L, LANE_STATE] int32
    (`pack_lane_state`); a lane's progress through the burst is DATA
    (how many positions passed the threshold), so block tables must
    cover B * (num_passes div 2 + 1) positions from each lane's block
    start.  `sample_fn(logits [L, B, vocab], positions [L, B], stp [L])
    -> (tokens [L, B], confidence [L, B])`, greedy where absent.
    Returns (out [num_passes, L, B]: the tokens of the blocks that lost
    their last mask, a lane and pass, -1 elsewhere; the state after the
    burst; cache)."""
    L = state.shape[0]
    B = cfg.block_length
    if valid is None:
        valid = jnp.ones((L,), bool)
    if sample_fn is None:
        def sample_fn(logits, positions, stp):
            return greedy_with_confidence(logits)

    def body(carry, _):
        kv, blk, msk, pos, stp = carry
        logits, kv = denoise(params, cfg, kv, blk, msk, pos, block_tables,
                             valid=valid, mesh=mesh)
        x0, conf = sample_fn(logits, pos[:, None] + jnp.arange(B), stp)
        blk, msk, pos, stp, out, n_thr = transfer(
            cfg, blk, msk, pos, stp, x0.astype(jnp.int32), conf, valid)
        kv = kv[:-1] + (kv[-1].at[3].add(n_thr),)
        return (kv, blk, msk, pos, stp), out

    (kv_cache, *lane), outs = jax.lax.scan(
        body, (kv_cache,) + unpack_lane_state(cfg, state), None,
        length=num_passes)
    return outs, pack_lane_state(*lane), kv_cache
