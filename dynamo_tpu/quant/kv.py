"""Symmetric int8 KV-cache quantization primitives.

Decode is memory-bandwidth-bound (the raw loop ran at 0.76 of the HBM
roofline on an earlier set-up; not measured on today's code), so halving the bytes the attention read streams per token
is the single biggest remaining lever on served throughput — and the
same halving doubles the KV blocks a fixed HBM budget holds (bigger
continuous batch, fewer preemptions, more prefix-cache residency).
Per-block-scale KV quantization is established practice (KIVI, Liu et
al. 2024; INT8/FP8 KV in vLLM's paged attention); this module is the
TPU-native expression over the head-major transposed paged cache.

Granularity: one fp32 scale per (layer, kv_head, block, position) —
i.e. per written TOKEN per head, stored as sibling arrays to the paged
cache shaped [L, nkv, num_blocks, block_size] (models/*.py
kv_cache_scale_shapes; sharded with the same tp split as the cache,
parallel/mesh.py kv_scale_spec).  The position axis is deliberate:
paged writes are incremental (decode appends one token into a partial
block), so a scale per (layer, head, block) alone would force a
read-modify-write requantization of the whole live block on every
append — write amplification of block_size× on the scatter AND
compounding int8→int8 requantization error as the block fills.  With a
scale per position every write site stays a pure scatter (the exact
index math the bf16 path uses, plus one [T, nkv] scale scatter), and
quantization error is bounded per token at absmax/254.  The overhead is
4 bytes per head_dim int8 elements: bytes/token ratio vs bf16 is
(head_dim + 4) / (2 * head_dim) — 1.94× blocks at head_dim 128, 1.88×
at 64, comfortably above the 1.8× capacity target.

Dequantization happens at the attention read.  On the jnp/XLA paths
(ops/paged_attention.py `_gather_ctx`) the int8 block gather is what
streams from HBM, the scale gather adds ~3% traffic, and the upcast
feeds the existing fp32 / bf16 MXU paths unchanged.  On the Pallas
paths (`impl="pallas"`, ops/pallas_paged_attention.py decode +
ops/pallas_packed_prefill.py packed prefill) the kernels DMA int8
blocks plus their fp32 scale rows into VMEM and fuse the dequantizing
multiply into the chunk consume (bf16 MXU operands on the serving
path, fp32 softmax/accumulate) — the bandwidth win happens inside the
fast attention path rather than routing around it.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

INT8_MAX = 127.0
# scales below this quantize to an all-zero block row; dividing by the
# floor instead of the true (tiny) scale cannot overflow: |x| <= 127*EPS
_EPS = 1e-30


def quantize_tokens(x) -> Tuple["jax.Array", "jax.Array"]:
    """Per-token symmetric int8 quantization over the last axis.

    x [..., hd] -> (q int8 [..., hd], scale fp32 [...]) with
    scale = absmax / 127 and q = round(x / scale) clipped to ±127, so
    |dequantize(q, scale) - x| <= scale / 2 == absmax / 254 elementwise.
    """
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / INT8_MAX
    q = jnp.round(xf / jnp.maximum(scale, _EPS)[..., None])
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize(q, scale, dtype=None):
    """Inverse of quantize_tokens: q [..., S, hd] * scale [..., S]."""
    import jax.numpy as jnp

    out = q.astype(jnp.float32) * scale[..., None]
    return out if dtype is None else out.astype(dtype)


# ---------------------------------------------------------------------------
# cache-tuple convention
# ---------------------------------------------------------------------------
# A paged KV cache is a tuple: (k, v) for full-precision caches, or
# (k, v, k_scale, v_scale) when int8-quantized.  The tuple rides through
# jit/donation/scan as one pytree, so the engine and the model families
# never branch on dtype outside these two helpers.


def is_quantized(kv_cache) -> bool:
    return len(kv_cache) == 4


def unpack_kv(kv_cache):
    """(k, v, k_scale | None, v_scale | None) from either tuple arity."""
    if len(kv_cache) == 4:
        return kv_cache
    k, v = kv_cache
    return k, v, None, None


# ---------------------------------------------------------------------------
# capacity math (host-side, numpy only — the mocker and planner import this
# without touching jax)
# ---------------------------------------------------------------------------


def kv_cache_bytes_per_block(family, model_cfg, block_size: int,
                             kv_cache_dtype: str) -> int:
    """HBM bytes ONE physical block costs across all layers (k + v and,
    for int8, both fp32 scale planes), derived from the family's own
    cache shapes so MLA's asymmetric latent/rope-key pair is priced
    correctly too."""
    k_shape, v_shape = family.kv_cache_shapes(model_cfg, 1, block_size)
    data_elems = math.prod(k_shape) + math.prod(v_shape)
    if kv_cache_dtype == "int8":
        ks_shape, vs_shape = family.kv_cache_scale_shapes(
            model_cfg, 1, block_size)
        return data_elems + 4 * (math.prod(ks_shape) + math.prod(vs_shape))
    return data_elems * np.dtype(model_cfg.dtype).itemsize


def blocks_for_hbm_budget(family, model_cfg, block_size: int,
                          kv_cache_dtype: str, hbm_bytes: int) -> int:
    """Physical blocks a byte budget holds (floor 2: block 0 is the
    garbage block, so fewer than 2 cannot serve a single sequence)."""
    per = kv_cache_bytes_per_block(family, model_cfg, block_size,
                                   kv_cache_dtype)
    return max(2, int(hbm_bytes) // max(1, per))
