"""Floors for a configuration whose blocks are Mamba-2 state-space
mixers (a float32 STATE of fixed size a lane and block, no keys a
token), a few GQA attention blocks and plain (two-matrix) routed experts
held as a share, ONE mixer a block: the least bytes a decode step must
move and the least FLOPs a prefill must spend.  `lib/roofline.py`,
`lib/moe_floors.py`, `lib/sparse_floors.py` and `lib/recurrent_floors.py`
know no SSM state bytes and count three matrices an expert, so it has
floors of its own; the constants come from the metric files' `args`, and
benchmark/tests recompute them from the configuration file's keys.
Derivation: benchmark/README-ssm.md.

Floors: what the program really moves or multiplies (the state read
twice a step by an unfused step, every held expert for every lane under
dense dispatch, the scan's float32 matmuls in several bf16 passes and
over the whole square of a chunk, a bucket's padded rows) is more, and
shows as a low share.
"""

from __future__ import annotations

from typing import Any, Dict

from .moe_floors import causal_pairs  # noqa: F401  (an attention block's pairs)
from .recurrent_floors import live_latent_tokens as live_tokens  # noqa: F401
from .roofline import step_weight_bytes


def decode_bytes(steps: float, experts_visited: float, lane_steps: float,
                 kv_tokens: float, *, dense_weight_bytes: float,
                 expert_bytes: float, lane_step_bytes: float,
                 kv_token_bytes: float) -> float:
    """Bytes `steps` decode steps had to move: every weight outside the
    embedding (a lookup) and the routed experts once a step; an expert's
    two matrices for each (step, block, held expert) that a token
    visited; for each active lane and step the state of every Mamba
    block read and written once and the convolution's tail with it; and
    the attention blocks' K and V of each live token (`kv_tokens` is of
    one block, `kv_token_bytes` of all of them)."""
    return (step_weight_bytes(steps, experts_visited,
                              dense_weight_bytes=dense_weight_bytes,
                              expert_bytes=expert_bytes)
            + lane_steps * lane_step_bytes
            + kv_tokens * kv_token_bytes)


def scan_flops(heads: int, head_dim: int, state: int, groups: int,
               chunk: int) -> float:
    """FLOPs one token needs in one Mamba block's scan, a multiply and
    an add each, whichever of the two forms needs fewer (README-ssm.md):
    the recurrence (decay the state, feed it, read it: 5 P N a head) or
    the chunked form at `chunk` tokens a chunk counting only the pairs
    its triangle needs (C . B over the earlier tokens of the chunk a
    group, their weighted sum of x a head, the chunk's feed to the state
    and the read of the state that entered it)."""
    recurrence = heads * 5.0 * head_dim * state
    pairs = (chunk + 1) / 2.0
    chunked = (groups * pairs * 2 * state            # C_t . B_s, s <= t
               + heads * pairs * 2 * head_dim        # sum_s m_ts x_s
               + heads * 2 * 2.0 * head_dim * state)  # feed, read
    return min(recurrence, chunked)


def prefill_flops(tokens: float, held_picks: float, attn_pairs: float, *,
                  dense_flops_per_token: float, pick_flops: float,
                  ssm_layers: int, scan_flops_per_token: float,
                  attn_layers: int, attn_pair_flops: float) -> float:
    """FLOPs the prefilled tokens needed: every matrix outside the
    routed experts (and outside embedding and output head) for each
    token, an expert's two matrices for each pick that fell on a held
    expert, the scan in every Mamba block, and q.k and p.v for each
    causal pair in every attention block (`attn_pairs` is of one
    block)."""
    return (tokens * (dense_flops_per_token
                      + ssm_layers * scan_flops_per_token)
            + held_picks * pick_flops
            + attn_layers * attn_pairs * attn_pair_flops)


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2,
              state_itemsize: int = 4) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d = hf["hidden_size"]
    pattern = hf["hybrid_override_pattern"]
    n_m, n_a, n_e = (pattern.count(k) for k in "M*E")
    H, P, N, G = (hf["mamba_num_heads"], hf["mamba_head_dim"],
                  hf["ssm_state_size"], hf["n_groups"])
    W, inner = hf["conv_kernel"], H * P
    conv_dim = inner + 2 * G * N
    nh, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    # in_proj (z, x B C, dt) and out_proj; q, k, v, o; router and shared
    m_mats = d * (inner + conv_dim + H) + inner * d
    m_conv = (W + 1) * conv_dim                    # taps and the bias
    a_mats = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    e_mats = d * hf["router_experts"] \
        + 2 * d * hf["moe_shared_expert_intermediate_size"]
    mats = n_m * m_mats + n_a * a_mats + n_e * e_mats
    expert = 2 * d * hf["moe_intermediate_size"]
    return {
        "dense_weight_bytes": float((mats + n_m * m_conv
                                     + d * hf["vocab_size"]) * itemsize),
        "expert_bytes": float(expert * itemsize),
        # state read and written, tail read and written, every Mamba block
        "lane_step_bytes": float(n_m * 2 * (
            H * P * N * state_itemsize + (W - 1) * conv_dim * itemsize)),
        "kv_token_bytes": float(n_a * 2 * nkv * hd * itemsize),
        "attn_layers": n_a, "ssm_layers": n_m, "block_size": block_size,
        "dense_flops_per_token": 2.0 * mats,
        "pick_flops": 2.0 * expert,
        "scan_flops_per_token": scan_flops(H, P, N, G, hf["chunk_size"]),
        "attn_pair_flops": 4.0 * nh * hd,
    }
