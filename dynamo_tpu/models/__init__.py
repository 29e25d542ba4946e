"""Model families, uniform functional contract per family module:

    init_params(cfg, key)            parameter pytree
    prefill / prefill_batched        chunked prompt over the paged cache
    decode / decode_multi            batched token steps
    kv_cache_shapes(cfg, nb, bs)     cache shapes, one a member
    kv_cache_specs()                 PartitionSpecs, one a member
    PRESETS                          name -> config

The engine binds a family once via get_family(cfg) and never branches on
architecture again — Llama/Qwen/Mixtral (llama.py, GQA cache), the
DeepSeek MLA family (deepseek.py, latent cache) and the window + global
hybrid over a share of the experts (mimo.py) serve through identical
plumbing.

The cache is a tuple the family owns: the engine allocates one array a
shape, hands the tuple to every program and takes it back.  A family
with ONE kind of layer has two members, (k-like, v-like), each
[L, heads, blocks, width, block_size] and paged by the sequence's block
table.  What a family may add, each read by the engine through the name
given (never through the family's type):

    kv_cache_dtypes(cfg)     a dtype a member, where not all cfg.dtype
    KV_LANE_ADDRESSED        True: some members are addressed by LANE
                             (the scheduler's slot) and position, not by
                             the block table: state of bounded size a
                             sequence, such as a window layer's ring.
                             kv_cache_shapes then takes `lanes=`, prefill
                             and prefill_batched take `lanes=` (the lane
                             of each row); decode rows ARE lanes.  Which
                             layers use which member is the family's own
                             (mimo.py: members 0-1 the global layers'
                             paged pools, 2-3 the window layers' rings).
    KV_COUNTERS              names of device-side counts: the tuple's
                             LAST member is an int32 vector the programs
                             add to; a decode burst carries it home under
                             its tokens and the engine adds what it grew
                             by to `metrics` under these names.
    decode_block_counts(..)  host-side block counts of a decode burst for
                             a family whose layers differ in what they
                             read (decode_attn_* and kv_* counters).
    kv_cache_scale_shapes /  int8 cache; prefill_packed, prefill_ring,
    _specs, prefill_packed,  spec_verify_packed, decode_hidden ...: a
    ...                      family without one falls back or refuses.
    UNSUPPORTED              what the engine must not promise for the
                             family (engine/core.py `_family_gaps`)."""

from . import deepseek, llama, mimo
from .deepseek import DeepseekConfig
from .llama import LlamaConfig, init_params
from .mimo import MimoConfig

PRESETS = {**llama.PRESETS, **deepseek.PRESETS, **mimo.PRESETS}


def get_family(cfg):
    """Model-family module for a config instance."""
    if isinstance(cfg, DeepseekConfig):
        return deepseek
    if isinstance(cfg, MimoConfig):
        return mimo
    if isinstance(cfg, LlamaConfig):
        return llama
    raise TypeError(f"unknown model config type: {type(cfg).__name__}")


__all__ = [
    "DeepseekConfig",
    "LlamaConfig",
    "MimoConfig",
    "PRESETS",
    "get_family",
    "init_params",
]
