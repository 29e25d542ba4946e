"""The constants of the floors for a DENSE configuration of Mamba-2
state-space layers beside a few GQA attention layers, every layer a
mixer and a gated MLP, no experts (`granitemoehybrid` with
`num_local_experts` 0).  The floors themselves are
`lib/ssm_floors.py`'s (`decode_bytes`, `prefill_flops`, `scan_flops`)
with their expert terms at zero: a decode step must move every weight
outside the embedding once, each busy lane's state and tail once in and
once out a Mamba layer, and the attention layers' K and V of each live
token; a prefilled token must spend every matrix of every layer (three
an MLP, four a mixer of either kind), the scan in every Mamba layer and
q.k and p.v a causal pair in every attention layer.  Derivation:
benchmark/README-ssm-dense.md; benchmark/tests recompute the metric
files' `args` from the configuration file's keys through this."""

from __future__ import annotations

from typing import Any, Dict

from .ssm_floors import scan_flops


def constants(hf: Dict[str, Any], block_size: int, itemsize: int = 2,
              state_itemsize: int = 4) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys."""
    d, f = hf["hidden_size"], hf["shared_intermediate_size"]
    kinds = hf["layer_types"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    H, P, N, G = (hf["mamba_n_heads"], hf["mamba_d_head"],
                  hf["mamba_d_state"], hf["mamba_n_groups"])
    W, inner = hf["mamba_d_conv"], H * P
    conv_dim = inner + 2 * G * N
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    # in_proj (z, x B C, dt) and out_proj; q, k, v, o; the MLP's input
    # (gate and up side by side) and output: three matrices of d x f
    m_mats = d * (inner + conv_dim + H) + inner * d
    a_mats = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    mlp = 3 * d * f
    mats = n_m * m_mats + n_a * a_mats + len(kinds) * mlp
    # the convolution's taps and bias, A_log, D, dt_bias, the gated
    # norm; two norms a layer and the final one
    m_vecs = (W + 1) * conv_dim + 3 * H + inner
    vecs = n_m * m_vecs + (2 * len(kinds) + 1) * d
    # the tied head: the embedding read once more, as a matrix
    head = d * hf["vocab_size"]
    return {
        # vectors are float32 in the program's tree, the taps and the
        # bias in the weights' dtype
        "dense_weight_bytes": float(
            (mats + head + n_m * (W + 1) * conv_dim) * itemsize
            + (vecs - n_m * (W + 1) * conv_dim) * 4),
        # state read and written, tail read and written, every Mamba layer
        "lane_step_bytes": float(n_m * 2 * (
            H * P * N * state_itemsize + (W - 1) * conv_dim * itemsize)),
        "kv_token_bytes": float(n_a * 2 * nkv * hd * itemsize),
        "attn_layers": n_a, "ssm_layers": n_m, "block_size": block_size,
        "dense_flops_per_token": 2.0 * mats,
        "scan_flops_per_token": scan_flops(H, P, N, G,
                                           hf["mamba_chunk_size"]),
        "attn_pair_flops": 4.0 * nh * hd,
    }
