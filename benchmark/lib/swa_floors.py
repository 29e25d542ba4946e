"""The constants of the window + global, share-of-experts floors
(benchmark/lib/moe_floors.py `decode_bytes`, `prefill_flops`; read by
benchmark/readers/moe_roofline.py) for a `cohere2_moe` configuration
file's keys.  `moe_floors.constants` reads MiMo's key names (a dense
first layer, K and V of unequal width, two KV head counts); this family
has one norm, routed and shared experts in every layer, a tied head and
one KV head count.  benchmark/tests/test_swa_floors.py holds the metric
files' `args` to this; benchmark/README-swa.md derives each line.
"""

from __future__ import annotations

from typing import Any, Dict

KINDS = ("full_attention", "sliding_attention")


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2,
              norm_itemsize: int = 4) -> Dict[str, float]:
    d, nh, nkv = (hf["hidden_size"], hf["num_attention_heads"],
                  hf["num_key_value_heads"])
    hd, f = hf["head_dim"], hf["intermediate_size"]
    kinds = hf["layer_types"]
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types needs one known kind a layer")
    layers = len(kinds)
    n_window = sum(k == "sliding_attention" for k in kinds)
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    shared = 3 * d * f * hf["num_shared_experts"]
    router = d * hf.get("router_experts", hf["num_experts"])
    dense = layers * (attn + shared + router)
    expert = 3 * d * f
    head = d * hf["vocab_size"]          # tied: read once, as the head
    norms = (layers + 1) * d             # float32 vectors
    block = nkv * 2 * hd * block_size * itemsize
    return {
        "dense_weight_bytes": float((dense + head) * itemsize
                                    + norms * norm_itemsize),
        "expert_bytes": float(expert * itemsize),
        "global_layers": layers - n_window, "window_layers": n_window,
        "global_block_bytes": float(block),
        "window_block_bytes": float(block),
        "dense_flops_per_token": 2.0 * dense,
        "pick_flops": 2.0 * expert,
        "pair_flops": nh * 4.0 * hd,
        "window": hf["sliding_window"],
    }
