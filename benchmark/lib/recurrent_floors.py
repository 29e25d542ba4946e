"""Floors for a configuration whose layers are mostly a delta-rule
linear attention (a float32 STATE of fixed size a lane and layer, no
keys a token) with one latent-attention (MLA) layer a period, over
routed experts held as a share: the least bytes a decode step must move
and the least FLOPs a prefill must spend.  `lib/roofline.py`,
`lib/moe_floors.py` and `lib/sparse_floors.py` know no state bytes and
no chunk-rule FLOPs, so it has floors of its own; the constants come
from the metric files' `args`, and benchmark/tests recompute them from
the configuration file's keys.  Derivation: benchmark/README-recurrent.md.

Floors: what the program really moves or multiplies (the state read
twice a step by an unfused rule, every lane's whole block table under
the gathering MLA read, every held expert for every lane under dense
dispatch, the rule's float32 matmuls in several bf16 passes) is more,
and shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

from .moe_floors import causal_pairs  # noqa: F401  (an MLA layer's pairs)
from .roofline import step_weight_bytes


def live_latent_tokens(live_blocks: float, lane_steps: float,
                       mla_layers: int, block_size: int) -> float:
    """Latent tokens the MLA layers' masks need, ONE layer, summed over
    steps and lanes, from the program's count in BLOCKS (summed over the
    MLA layers): a lane-step of b = ceil((ctx + 1) / block) blocks holds
    at least (b - 1) x block + 1 tokens."""
    blocks = live_blocks / max(mla_layers, 1)
    return max(blocks - lane_steps, 0.0) * block_size + lane_steps


def decode_bytes(steps: float, experts_visited: float, lane_steps: float,
                 latent_tokens: float, *, dense_weight_bytes: float,
                 expert_bytes: float, lane_step_bytes: float,
                 latent_token_bytes: float) -> float:
    """Bytes `steps` decode steps had to move: every weight outside the
    embedding (a lookup) and the routed experts once a step; an expert's
    three matrices for each (step, layer, held expert) that a token
    visited; for each active lane and step the state of every KDA layer
    read and written once and the convolution's tail with it; and the
    MLA layers' latent and rope key of each live token (`latent_tokens`
    is of one layer, `latent_token_bytes` of all of them)."""
    return (step_weight_bytes(steps, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + lane_steps * lane_step_bytes
            + latent_tokens * latent_token_bytes)


def chunk_rule_flops(heads: int, dk: int, dv: int, chunk: int) -> float:
    """FLOPs one token needs in one KDA layer under the chunkwise rule
    at `chunk` tokens a chunk, a multiply and an add each, counting only
    the pairs the triangles need (README-recurrent.md): A and B over the
    earlier tokens of the chunk, the forward substitution of the
    unit-triangular solve for [V | K~], the three products with the
    state and B U."""
    a = (chunk - 1) / 2.0 * 2 * dk            # k_t . k_s, s < t
    b = (chunk + 1) / 2.0 * 2 * dk            # q_t . k_s, s <= t
    solve = (chunk - 1) / 2.0 * 2 * (dv + dk)
    state = 3 * 2 * dk * dv                   # W S, Q~ S, K^T U
    bu = (chunk + 1) / 2.0 * 2 * dv
    return heads * (a + b + solve + state + bu)


def prefill_flops(tokens: float, held_picks: float, mla_pairs: float, *,
                  dense_flops_per_token: float, pick_flops: float,
                  kda_layers: int, rule_flops_per_token: float,
                  mla_layers: int, attn_pair_flops: float) -> float:
    """FLOPs the prefilled tokens needed: every matrix outside the
    routed experts (and outside embedding and output head) for each
    token, an expert's three matrices for each pick that fell on a held
    expert, the chunk rule in every KDA layer, and q.k and p.v for each
    causal pair in every MLA layer (`mla_pairs` is of one layer)."""
    return (tokens * (dense_flops_per_token
                      + kda_layers * rule_flops_per_token)
            + held_picks * pick_flops
            + mla_layers * mla_pairs * attn_pair_flops)


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2,
              state_itemsize: int = 4, chunk: int = 64
              ) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d, H, hd = (hf["hidden_size"], hf["num_attention_heads"],
                hf["head_dim"])
    L, period = hf["num_hidden_layers"], hf["layer_group_size"]
    mla = sum(1 for i in range(L) if (i + 1) % period == 0)
    kda = L - mla
    n_dense = min(hf["first_k_dense_replace"], L)
    W = hf["short_conv_kernel_size"]
    R, dr = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    dn, dv = hf["qk_nope_head_dim"], hf["v_head_dim"]
    # q, k, v, the decay's projection, the output; beta and the gate
    kda_mats = 5 * d * H * hd + 2 * d * H
    kda_conv = W * 3 * H * hd
    mla_mats = d * H * (dn + dr) + d * (R + dr) + H * R * (dn + dv) \
        + H * dv * d
    ffn = (n_dense * 3 * d * hf["intermediate_size"]
           + (L - n_dense) * (d * hf["router_experts"] + 3 * d
                              * hf["moe_shared_expert_intermediate_size"]))
    mats = kda * kda_mats + mla * mla_mats + ffn
    expert = 3 * d * hf["moe_intermediate_size"]
    return {
        "dense_weight_bytes": float((mats + kda * kda_conv
                                     + d * hf["vocab_size"]) * itemsize),
        "expert_bytes": float(expert * itemsize),
        # state read and written, tail read and written, every KDA layer
        "lane_step_bytes": float(kda * 2 * (
            H * hd * hd * state_itemsize + (W - 1) * 3 * H * hd * itemsize)),
        "latent_token_bytes": float(mla * (R + dr) * itemsize),
        "mla_layers": mla, "kda_layers": kda, "block_size": block_size,
        "dense_flops_per_token": 2.0 * mats,
        "pick_flops": 2.0 * expert,
        "rule_flops_per_token": chunk_rule_flops(H, hd, hd, chunk),
        "attn_pair_flops": H * 2.0 * (dn + dr + dv),
    }
