"""The least work the algorithm needs, from shapes: bytes a decode step
must read and FLOPs a prefilled token must spend.  Kept with the
benchmark so that no PR that claims a gain can change the count.  These
are floors: what the program really moves or multiplies (padding, dense
expert dispatch, table-wide gathers) is more, and shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

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


def _is_expert_stack(path) -> bool:
    """A routed expert layer's stacked matrices: `moe_w_*`, experts on
    the first axis (the shared experts and the router are other keys)."""
    return str(path[-1]).startswith("moe_w_")


def weight_bytes_per_step(params) -> float:
    """Every weight, every held expert with it, once: what a decode step
    reads where each expert has a token.  The embedding table is a lookup
    of a few rows and is left out."""
    return float(sum(np.prod(a.shape) * a.dtype.itemsize
                     for p, a in _leaves(params) if p[0] != "embedding"))


def weight_parts(params) -> Tuple[float, float]:
    """-> (dense_weight_bytes, expert_bytes).  The first is what a decode
    step reads whatever its lanes picked: every weight outside the
    embedding (a lookup) and outside the expert stacks.  The second is ONE
    held expert's matrices in ONE layer, what a visited (step, layer,
    expert) adds to the step's need; 0 without expert stacks.  The
    layers' experts have to be alike: the program counts visits, not
    which layer they fell in."""
    dense, by_layer = 0.0, {}
    for p, a in _leaves(params):
        if _is_expert_stack(p):
            by_layer[p[:-1]] = by_layer.get(p[:-1], 0.0) + float(
                np.prod(a.shape[1:]) * a.dtype.itemsize)
        elif p[0] != "embedding":
            dense += float(np.prod(a.shape) * a.dtype.itemsize)
    sizes = set(by_layer.values())
    if len(sizes) > 1:
        raise ValueError(f"expert layers of unequal size: {sorted(sizes)}")
    return dense, (sizes.pop() if sizes else 0.0)


def step_weight_bytes(steps: float, experts_visited: float, *,
                      dense_weight_bytes: float, expert_bytes: float
                      ) -> float:
    """Weight bytes `steps` decode steps had to read: every weight outside
    the embedding and the routed experts once a step, and an expert's
    matrices for each (step, layer, held expert) that a token visited.
    The one weight term of every decode floor (this file's, moe_floors,
    sparse_floors, recurrent_floors, ssm_floors)."""
    return steps * dense_weight_bytes + experts_visited * expert_bytes


def kv_bytes_per_token(kv_shapes, block_size: int, itemsize: int) -> float:
    """Cache bytes one token of context holds over all layers, from the
    family's own cache shapes for ONE block.  A vector among the members
    is the family's device-side counts (`KV_COUNTERS`, the tuple's last
    member), not cache a token holds, and is left out."""
    return float(sum(np.prod(s) for s in kv_shapes if len(s) > 1)
                 ) / block_size * itemsize


def decode_bytes(steps: float, experts_visited: float,
                 live_context_tokens: float, *, dense_weight_bytes: float,
                 expert_bytes: float, kv_bytes_per_token: float) -> float:
    """Bytes `steps` decode steps had to read under one uniform cache:
    the weights (`step_weight_bytes`) and, each step, the cache of the
    live context (`live_context_tokens`: the mean over the stretch of the
    tokens held by the requests that were decoding)."""
    return (step_weight_bytes(steps, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + steps * kv_bytes_per_token * live_context_tokens)


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
        if _is_expert_stack(p):
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
    dense, expert = weight_parts(params)
    return {
        # weight_bytes = dense_weight_bytes + expert_bytes x every held
        # expert of every layer: printed, no reader divides by it
        "weight_bytes": weight_bytes_per_step(params),
        "dense_weight_bytes": dense,
        "expert_bytes": expert,
        "kv_bytes_per_token": kv_bytes_per_token(
            shapes, block_size, np.dtype(cfg.dtype).itemsize),
        "matmul_flops_per_token": matmul_flops_per_token(
            params, getattr(cfg, "experts_per_token", 0)),
        "attn_pair_flops": pair_flops,
        "n_layers": cfg.n_layers,
    }
