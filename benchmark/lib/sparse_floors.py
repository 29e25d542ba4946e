"""Floors for a configuration whose attention CHOOSES its keys (a
learned indexer scores every earlier token, attention reads the `topk`
best) over routed experts held as a share: the least bytes a decode
step must read and the least FLOPs a prefill must spend.
`lib/roofline.py` and `lib/moe_floors.py` count such a configuration
wrongly (every causal pair attended, a whole context of K and V read a
step, no index keys), so it has floors of its own; the constants come
from the metric files' `args`, and benchmark/tests recompute them from
the configuration file's keys.

Floors: what the program really moves or multiplies (K and V of the
whole context under a mask, every held expert for every lane under
dense dispatch, every pair of a chunk x context rectangle) is more, and
shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

from .roofline import step_weight_bytes


def decode_bytes(steps: float, experts_visited: float, ctx_tokens: float,
                 selected_tokens: float, *, dense_weight_bytes: float,
                 expert_bytes: float, layers: int, index_key_bytes: float,
                 kv_token_bytes: float) -> float:
    """Bytes `steps` decode steps had to read: every weight outside the
    embedding (a lookup) and the experts once a step; an expert's three
    matrices for each (step, layer, held expert) that a token visited;
    and in every layer one index key for each token the indexer must
    score and K and V of each token it keeps (token counts are summed
    over steps and lanes, one layer)."""
    return (step_weight_bytes(steps, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + layers * (ctx_tokens * index_key_bytes
                        + selected_tokens * kv_token_bytes))


def prefill_flops(tokens: float, held_picks: float, pairs_scored: float,
                  pairs_attended: float, *, dense_flops_per_token: float,
                  pick_flops: float, layers: int, index_pair_flops: float,
                  attn_pair_flops: float) -> float:
    """FLOPs the prefilled tokens needed: every matrix outside the
    experts (and outside embedding and output head) for each token, an
    expert's three matrices for each pick that fell on a held expert,
    and in every layer the indexer's dot for each (query, key) pair it
    scores and q.k and p.v for each pair that is kept (pair counts are
    of one layer)."""
    return (tokens * dense_flops_per_token + held_picks * pick_flops
            + layers * (pairs_scored * index_pair_flops
                        + pairs_attended * attn_pair_flops))


def constants(hf: Dict[str, Any], itemsize: int = 2) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d, nh, nkv = (hf["hidden_size"], hf["num_attention_heads"],
                  hf["num_key_value_heads"])
    hd, sa = hf["head_dim"], hf["sa_config"]
    H, D = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers = hf["num_hidden_layers"]
    attention = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    indexer = d * H * D + d * D + d * H
    dense = layers * (attention + indexer + d * hf["router_experts"])
    expert = 3 * d * hf["moe_intermediate_size"]
    return {
        "dense_weight_bytes": float((dense + d * hf["vocab_size"])
                                    * itemsize),
        "expert_bytes": float(expert * itemsize),
        "layers": layers,
        "index_key_bytes": float(D * itemsize),
        "kv_token_bytes": float(2 * nkv * hd * itemsize),
        "dense_flops_per_token": 2.0 * dense,
        "pick_flops": 2.0 * expert,
        "index_pair_flops": 2.0 * H * D,
        "attn_pair_flops": 4.0 * nh * hd,
    }
