"""The least work the algorithm needs, from shapes: bytes a decode step
must read and FLOPs a prefilled token must spend.  Kept with the
benchmark so that no PR that claims a gain can change the count.  These
are floors: what the program really moves or multiplies (padding, dense
expert dispatch, table-wide gathers) is more, and shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _leaves(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _leaves(v, path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaves(v, path + (i,))
    else:
        yield path, tree


def weight_bytes_per_step(params) -> float:
    """Every weight is read once a step, whatever the batch: all experts
    count (a batch of a few lanes times k already touches most of them).
    The embedding table is a lookup of a few rows and is left out."""
    return float(sum(np.prod(a.shape) * a.dtype.itemsize
                     for p, a in _leaves(params) if p[0] != "embedding"))


def kv_bytes_per_token(kv_shapes, block_size: int, itemsize: int) -> float:
    """Cache bytes one token of context holds over all layers, from the
    family's own cache shapes for ONE block."""
    return float(sum(np.prod(s) for s in kv_shapes)) / block_size * itemsize


def decode_step_bytes(weight_bytes: float, kv_per_token: float,
                      live_context_tokens: float) -> float:
    return weight_bytes + kv_per_token * live_context_tokens


def matmul_flops_per_token(params, experts_per_token: int) -> float:
    """2 x the parameters a prefilled token multiplies with: every
    matrix of every layer, a routed expert's only for the k experts the
    token visits.  Embedding (lookup) and output head (computed for the
    last position of a row only) are left out; norms and the router's
    bias are vectors and do not count."""
    total = 0.0
    for p, a in _leaves(params["layers"]):
        if a.ndim < 2:
            continue
        n = float(np.prod(a.shape))
        if str(p[-1]).startswith("moe_w_"):
            n *= experts_per_token / a.shape[0]
        total += n
    return 2.0 * total


def causal_attention_flops(prompt_len: int, pair_flops: float,
                           n_layers: int) -> float:
    """Every token attends to itself and all before it."""
    return pair_flops * n_layers * prompt_len * (prompt_len + 1) / 2.0


def describe(params, cfg, family, block_size: int,
             pair_flops: float) -> Dict[str, Any]:
    """The configuration's constants, computed once after the engine is
    built; readers combine them with counts from the window."""
    shapes = family.kv_cache_shapes(cfg, 1, block_size)
    return {
        "weight_bytes": weight_bytes_per_step(params),
        "kv_bytes_per_token": kv_bytes_per_token(
            shapes, block_size, np.dtype(cfg.dtype).itemsize),
        "matmul_flops_per_token": matmul_flops_per_token(
            params, getattr(cfg, "experts_per_token", 0)),
        "attn_pair_flops": pair_flops,
        "n_layers": cfg.n_layers,
    }
