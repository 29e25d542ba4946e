"""Model families, uniform functional contract per family module:

    init_params(cfg, key)            parameter pytree
    prefill / prefill_batched        chunked prompt over the paged cache
    decode / decode_multi            batched token steps
    kv_cache_shapes(cfg, nb, bs)     cache shapes, one a member
    kv_cache_specs()                 PartitionSpecs, one a member
    PRESETS                          name -> config

What families share lives in no family's module and imports none:
moe.py is the expert layer (the routers, the one dropless dispatch and
the rule that picks its form), mamba2.py the Mamba-2 mixer between its
projections and the two lane-addressed members it keeps, common.py the
decode burst's scan, the one-row prefill and a layer's index inside its
kind's cache members.

The engine binds a family once via get_family(cfg) and never branches on
architecture again — Llama/Qwen/Mixtral (llama.py, GQA cache), the
DeepSeek MLA family (deepseek.py, latent cache), the window + global
hybrid over a share of the experts (mimo.py), the GQA decoder whose
attention reads the keys a learned indexer chooses (keye.py), the
delta-rule linear-attention hybrid with one latent-attention layer a
period (ling.py), the Mamba-2 state-space hybrid with a few GQA
layers and plain experts, one mixer a block (nemotron_h.py), the
window + NoPE-global decoder whose attention and experts (routed, and
shared ones averaged) are ONE parallel block under one LayerNorm
(cohere2.py; a window of many blocks: its rings are read by the paged
pools' kernels) and the muP-scaled decoder whose sparse layers choose
BLOCKS of keys from mean-pooled compressed keys, one set a KV group,
beside lightning linear-attention layers with a constant decay a head
(minicpm_sala.py), the Qwen3-MoE-style decoder that generates by
diffusion over blocks of a few positions under block-causal attention
(sdar.py), the decoder of gated short-convolution layers beside a few
GQA layers, whose lane state is a convolution's tail alone (lfm2.py) and
the dense Mamba-2 hybrid whose every layer is a mixer AND a gated MLP
under muP multipliers, nine Mamba-2 layers to one NoPE GQA layer
(granite_hybrid.py; its mixer is mamba2.py's, the one nemotron_h.py
calls) serve through identical plumbing.

The cache is a tuple the family owns: the engine allocates one array a
shape, hands the tuple to every program and takes it back.  A family
with ONE kind of layer has two members, (k-like, v-like), each
[L, heads, blocks, width, block_size] and paged by the sequence's block
table.  What a family may add, each read by the engine through the name
given (never through the family's type):

    more table-paged         members beyond (k-like, v-like), in the
    members                  same [L, heads, blocks, width, block_size]
                             form and paged by the SAME block table
                             (keye.py: member 2, one index key a token
                             and layer; minicpm_sala.py: member 2,
                             one mean-pooled key a stride of tokens,
                             [L, blocks, slots, heads, width]: a page
                             holds the windows that END in it).  The
                             family's own programs write them: every program that writes a
                             token's K and V writes the others beside
                             them (ops/sparse_attention.py
                             `write_token_members` for a decode token,
                             `write_packed_members` for a prefill
                             chunk), so a block that prefix caching
                             reuses, or a replay after a preemption
                             recomputes, holds all its members.  The
                             engine only allocates, donates and takes
                             back; what reads the tuple as (k, v) or
                             (k, v, k_scale, v_scale) by its length
                             (KVBM tiers, disagg transfer, int8) is
                             refused for such a family (`UNSUPPORTED`).
    kv_cache_dtypes(cfg)     a dtype a member, where not all cfg.dtype
    KV_LANE_ADDRESSED        True: some members are addressed by LANE
                             (the scheduler's slot) and position, not by
                             the block table: state of bounded size a
                             sequence, such as a window layer's ring.
                             kv_cache_shapes then takes `lanes=`, prefill
                             and prefill_batched (and prefill_packed,
                             where the family has one: cohere2.py,
                             lfm2.py) take
                             `lanes=` (the lane of each row); decode
                             rows ARE lanes.  Which
                             layers use which member is the family's own
                             (mimo.py: members 0-1 the global layers'
                             paged pools, 2-3 the window layers' rings).
                             A RING forgets by itself: a position
                             overwrites the one a window before it.  A
                             lane-addressed member may also be a STATE
                             that nothing overwrites by position
                             (ling.py, nemotron_h.py and
                             granite_hybrid.py: members 2-3,
                             a float32 matrix a head and the short
                             convolution's tail, a lane and layer;
                             granite_hybrid.py's member 2 at the
                             published widths and 64 lanes is 4.89 GB
                             in ONE array, the largest member the
                             engine holds, stepped and put where it
                             lies and never copied; its tail is a
                             lane's rows end to end, and it has no
                             counters member: nothing is held as a
                             share;
                             minicpm_sala.py: member 3, the matrix
                             alone; lfm2.py: member 2, the short
                             convolution's tail ALONE, two rows of the
                             layer's width in the weights' dtype and
                             no float32 state;
                             `kv_cache_dtypes` says which member is
                             which).  The family's programs then keep
                             its life (ops/lane_state.py, the one copy
                             of it), since the
                             engine never clears a lane: a row whose
                             first position is 0 starts from ZEROS
                             whatever the lane held; chunk n + 1 of a
                             prompt starts from what chunk n LEFT; a
                             bucket's padding, a row of no tokens and
                             the idle lanes of a decode burst leave it
                             UNTOUCHED; a preempted sequence REBUILDS it
                             by replay from position 0.  A block that
                             prefix caching would reuse says nothing of
                             the state at its end, so such a family
                             lists `prefix_caching` in `UNSUPPORTED`.
    KV_COUNTERS              names of device-side counts: the tuple's
                             LAST member is an int32 vector the programs
                             add to; a decode burst carries it home under
                             its tokens and the engine adds what it grew
                             by to `metrics` under these names.
    decode_block_counts(..)  host-side counts of a decode burst for a
                             family whose layers differ in what they
                             read (mimo.py: decode_attn_* and kv_*
                             blocks) or whose attention chooses its keys
                             (keye.py: sparse_* tokens; minicpm_sala.py:
                             sala_* blocks and tokens); an empty burst
                             names the counters.
    prefill_token_counts(..) the same for a prefill chunk from the
                             host's positions and the rows its program
                             padded it to (keye.py: pairs scored and
                             kept; nemotron_h.py: what padding costs
                             the scan; cohere2.py: pairs a window and
                             a global layer attend, and whether the
                             window read ran in the kernel).
    kv_cache_scale_shapes /  int8 cache; prefill_packed, prefill_ring,
    _specs, prefill_packed,  spec_verify_packed, decode_hidden ...: a
    ...                      family without one falls back or refuses.
    GEN_BLOCK(cfg)           the family GENERATES BY BLOCKS of that many
                             positions (sdar.py: diffusion over a
                             block's masks), not one token a lane and
                             step.  The engine then prefills
                             B * (P div B) prompt tokens, in chunks that
                             end on a multiple of B, and takes NO token
                             from a prefill; the prompt's last P mod B
                             tokens enter the first block unmasked.  In
                             `decode_multi`'s place stands
                             `denoise_multi(params, cfg, kv, state,
                             tables, passes, sample_fn, valid, mesh)`:
                             a burst's unit is a PASS over every busy
                             lane's block, a lane's state between
                             passes (`lane_state_width(cfg)` int32
                             columns: tokens, mask flags, block start,
                             step; the family's own layout, built for
                             a lane that joins by `new_lane_state` and
                             read by `unpack_lane_state`) stays on the
                             device from burst to burst, how far a lane advances is data
                             (tables must cover B * (k div 2 + 1)
                             positions), and a burst sends home the
                             blocks that lost their last mask (-1
                             elsewhere) and each lane's block start.
                             A block is emitted as one frame; only
                             committed blocks reach prefix caching and
                             a preemption's replay.  What needs one
                             distribution a token (guided decoding,
                             penalties, logprobs, speculation) is
                             refused.
    UNSUPPORTED              what the engine must not promise for the
                             family (engine/core.py `_family_gaps`)."""

from . import (
    cohere2,
    deepseek,
    granite_hybrid,
    keye,
    lfm2,
    ling,
    llama,
    mimo,
    minicpm_sala,
    nemotron_h,
    sdar,
)
from .cohere2 import Cohere2Config
from .deepseek import DeepseekConfig
from .granite_hybrid import GraniteHybridConfig
from .keye import KeyeConfig
from .lfm2 import Lfm2Config
from .ling import LingConfig
from .llama import LlamaConfig, init_params
from .mimo import MimoConfig
from .minicpm_sala import SalaConfig
from .nemotron_h import NemotronHConfig
from .sdar import SdarConfig

PRESETS = {**llama.PRESETS, **deepseek.PRESETS, **mimo.PRESETS,
           **keye.PRESETS, **ling.PRESETS, **nemotron_h.PRESETS,
           **cohere2.PRESETS, **minicpm_sala.PRESETS, **sdar.PRESETS,
           **lfm2.PRESETS, **granite_hybrid.PRESETS}


def get_family(cfg):
    """Model-family module for a config instance."""
    if isinstance(cfg, DeepseekConfig):
        return deepseek
    if isinstance(cfg, MimoConfig):
        return mimo
    if isinstance(cfg, KeyeConfig):
        return keye
    if isinstance(cfg, LingConfig):
        return ling
    if isinstance(cfg, NemotronHConfig):
        return nemotron_h
    if isinstance(cfg, Cohere2Config):
        return cohere2
    if isinstance(cfg, SalaConfig):
        return minicpm_sala
    if isinstance(cfg, SdarConfig):
        return sdar
    if isinstance(cfg, Lfm2Config):
        return lfm2
    if isinstance(cfg, GraniteHybridConfig):
        return granite_hybrid
    if isinstance(cfg, LlamaConfig):
        return llama
    raise TypeError(f"unknown model config type: {type(cfg).__name__}")


__all__ = [
    "Cohere2Config",
    "DeepseekConfig",
    "GraniteHybridConfig",
    "KeyeConfig",
    "Lfm2Config",
    "LingConfig",
    "LlamaConfig",
    "MimoConfig",
    "NemotronHConfig",
    "PRESETS",
    "SalaConfig",
    "SdarConfig",
    "get_family",
    "init_params",
]
