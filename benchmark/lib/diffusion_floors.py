"""Floors for a configuration that GENERATES BY DIFFUSION OVER BLOCKS
under block-causal attention, every routed expert held: the least bytes
a PASS must read and the least FLOPs a prefill must spend
(benchmark/README-diffusion.md has the formulas).  `lib/roofline.py`
counts such a configuration wrongly two ways (a step there is one token
a lane; every causal pair, not every pair to the block's end), so it has
floors of its own; the constants come from the metric files' `args`, and
benchmark/tests recompute them from the configuration file's keys.

Floors: what the program really moves or multiplies (a pass re-reads
every weight for one token a lane under the rule's floor, the commit
pass included) is more, and shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

from .roofline import step_weight_bytes


def pass_bytes(passes: float, experts_visited: float,
               live_block_reads: float, *, dense_weight_bytes: float,
               expert_bytes: float, block_bytes: float) -> float:
    """Bytes `passes` passes had to read: every weight outside the
    embedding (a lookup) and the experts once a pass, the output head
    included (every pass ranks a block's positions by it); an expert's
    three matrices for each (pass, layer, held expert) that a row
    visited; and the cache blocks that hold a lane's context to its
    block's end, once for the block's queries (`live_block_reads`:
    blocks summed over layers, lanes and passes)."""
    return (step_weight_bytes(passes, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + live_block_reads * block_bytes)


def block_causal_pairs(prompt_len: int, block: int) -> float:
    """(query, key) pairs of one layer over the prefilled part of a
    prompt, block * (prompt_len div block) tokens: a token sees
    everything to its block's end."""
    n = prompt_len // block
    return block * block * n * (n + 1) / 2.0


def prefill_flops(tokens: float, held_picks: float, pairs: float, *,
                  dense_flops_per_token: float, pick_flops: float,
                  layers: int, attn_pair_flops: float) -> float:
    """FLOPs the prefilled tokens needed: every matrix outside the
    experts (and outside embedding and output head) for each token, an
    expert's three matrices for each pick that fell on a held expert,
    and in every layer q.k and p.v for each block-causal pair (the pair
    count is of one layer)."""
    return (tokens * dense_flops_per_token + held_picks * pick_flops
            + layers * pairs * attn_pair_flops)


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2
              ) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d, nh, nkv = (hf["hidden_size"], hf["num_attention_heads"],
                  hf["num_key_value_heads"])
    hd, layers = hf["head_dim"], hf["num_hidden_layers"]
    attention = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    dense = layers * (attention
                      + d * hf.get("router_experts", hf["num_experts"]))
    expert = 3 * d * hf["moe_intermediate_size"]
    return {
        "dense_weight_bytes": float((dense + d * hf["vocab_size"])
                                    * itemsize),
        "expert_bytes": float(expert * itemsize),
        "block_bytes": float(2 * nkv * hd * block_size * itemsize),
        "dense_flops_per_token": 2.0 * dense,
        "pick_flops": 2.0 * expert,
        "layers": layers,
        "attn_pair_flops": 4.0 * nh * hd,
        "block_length": hf["assumed"]["block_length"],
    }
