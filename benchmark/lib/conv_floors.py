"""The constants of the floors of a gated short-convolution + GQA +
routed-experts configuration (`lfm2_moe`), from its file's keys, for
benchmark/lib/moe_floors.py `decode_bytes` / `prefill_flops` as
benchmark/readers/moe_roofline.py reads them: the attention layers are
its "global" layers (every key up to the query, a cache a token), there
is no window layer, and a conv layer is a fixed cost a token that lies
in `dense_*` with the other matrices.  What those floors leave out for
this family is the tails a busy lane moves a decode step (2 rows of
`hidden_size` bf16 in and out a conv layer: 8 KB x 2 x 7 x 8 lanes =
0.9 MB a step against 5 GB of weights and keys, under 0.02 %).
benchmark/tests/test_conv_floors.py holds the metric files' `args` to
this; benchmark/README-conv.md derives each line.
"""

from __future__ import annotations

from typing import Any, Dict

KINDS = ("conv", "full_attention")


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2,
              vector_itemsize: int = 4) -> Dict[str, float]:
    d, nh, nkv = (hf["hidden_size"], hf["num_attention_heads"],
                  hf["num_key_value_heads"])
    hd = hf.get("head_dim") or d // nh
    kinds = hf["layer_types"]
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types needs one known kind a layer")
    layers = len(kinds)
    n_attn = sum(k == "full_attention" for k in kinds)
    n_conv = layers - n_attn
    n_dense = hf["num_dense_layers"]
    n_moe = layers - n_dense
    conv = d * 3 * d + d * d + hf["conv_L_cache"] * d   # in, out, taps
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    dense = (n_conv * conv + n_attn * attn
             + n_dense * 3 * d * hf["intermediate_size"]
             + n_moe * d * hf["num_experts"])           # the routers
    expert = 3 * d * hf["moe_intermediate_size"]
    head = d * hf["vocab_size"]          # tied: read once, as the head
    # float32 vectors: two norms a layer and the final one, q and k
    # norms an attention layer, the choice bias an expert layer
    vectors = ((2 * layers + 1) * d + n_attn * 2 * hd
               + n_moe * hf["num_experts"])
    return {
        "dense_weight_bytes": float((dense + head) * itemsize
                                    + vectors * vector_itemsize),
        "expert_bytes": float(expert * itemsize),
        "global_layers": n_attn, "window_layers": 0,
        "global_block_bytes": float(nkv * 2 * hd * block_size * itemsize),
        "window_block_bytes": 0.0,
        "dense_flops_per_token": 2.0 * dense,
        "pick_flops": 2.0 * expert,
        "pair_flops": nh * 4.0 * hd,
        "window": 0,
    }
