"""Floors for a configuration that holds a SHARE of its routed experts
and has attention layers of two kinds (window and global): the least
bytes a decode step must read and the least FLOPs a prefill must spend.
`lib/roofline.py` counts such a configuration wrongly three ways (k / held
of the held experts' FLOPs for every token, full causal pairs in window
layers, one uniform cache), so it has floors of its own; the constants
come from the metric files' `args`, and benchmark/tests recompute them
from the configuration file's keys.

Floors: what the program really moves or multiplies (every held expert
for every token under dense dispatch, both ring blocks of a lane) is
more, and shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

from .roofline import step_weight_bytes


def decode_bytes(steps: float, experts_visited: float,
                 global_block_steps: float, window_block_steps: float, *,
                 dense_weight_bytes: float, expert_bytes: float,
                 global_layers: int, window_layers: int,
                 global_block_bytes: float, window_block_bytes: float
                 ) -> float:
    """Bytes `steps` decode steps had to read: every weight outside the
    embedding (a lookup) and the experts once a step; an expert's three
    matrices for each (step, layer, held expert) that a token visited;
    the cache blocks the masks need, a kind's blocks a layer of that
    kind (block counts are summed over steps and lanes, one layer)."""
    return (step_weight_bytes(steps, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + global_block_steps * global_layers * global_block_bytes
            + window_block_steps * window_layers * window_block_bytes)


def causal_pairs(prompt_len: int) -> float:
    """(query, key) pairs of a global layer: all keys up to the query."""
    return prompt_len * (prompt_len + 1) / 2.0


def window_pairs(prompt_len: int, window: int) -> float:
    """Pairs of a window layer: query i sees min(i + 1, window) keys."""
    ramp = min(prompt_len, window)
    return ramp * (ramp + 1) / 2.0 + max(prompt_len - window, 0) * window


def prompt_attention_flops(prompt_len: int, *, pair_flops: float,
                           global_layers: int, window_layers: int,
                           window: int) -> float:
    return pair_flops * (global_layers * causal_pairs(prompt_len)
                         + window_layers * window_pairs(prompt_len, window))


def prefill_flops(tokens: float, held_picks: float, attention_flops: float,
                  *, dense_flops_per_token: float, pick_flops: float
                  ) -> float:
    """FLOPs the prefilled tokens needed: every matrix outside the
    experts (and outside embedding and output head) for each token, an
    expert's three matrices for each pick that fell on a held expert,
    and the attention pairs."""
    return (tokens * dense_flops_per_token + held_picks * pick_flops
            + attention_flops)


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2
              ) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d, nh = hf["hidden_size"], hf["num_attention_heads"]
    hd, hdv = hf["head_dim"], hf["v_head_dim"]
    kinds = hf["hybrid_layer_pattern"]
    n_window = sum(kinds)
    n_global = len(kinds) - n_window
    kv = {0: hf["num_key_value_heads"], 1: hf["swa_num_key_value_heads"]}

    def attn(nkv):
        return d * nh * hd + d * nkv * (hd + hdv) + nh * hdv * d

    n_moe = sum(hf["moe_layer_freq"])
    dense = (n_global * attn(kv[0]) + n_window * attn(kv[1])
             + (len(kinds) - n_moe) * 3 * d * hf["intermediate_size"]
             + n_moe * d * hf["router_experts"])
    expert = 3 * d * hf["moe_intermediate_size"]
    head = d * hf["vocab_size"]
    return {
        "dense_weight_bytes": float((dense + head) * itemsize),
        "expert_bytes": float(expert * itemsize),
        "global_layers": n_global, "window_layers": n_window,
        "global_block_bytes": float(kv[0] * (hd + hdv) * block_size
                                    * itemsize),
        "window_block_bytes": float(kv[1] * (hd + hdv) * block_size
                                    * itemsize),
        "dense_flops_per_token": 2.0 * dense,
        "pick_flops": 2.0 * expert,
        "pair_flops": nh * 2.0 * (hd + hdv),
        "window": hf["sliding_window"],
    }
