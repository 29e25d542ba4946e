"""Floors for a configuration whose sparse layers attend the BLOCKS of
keys a query chooses from mean-pooled compressed keys (K, V and the
compressed keys paged by one block table) beside lightning
linear-attention layers (a float32 STATE of fixed size a lane and
layer), dense MLPs: the least bytes a decode step must move and the
least FLOPs a prefill must spend.  `lib/roofline.py` and the other
families' floors count such a configuration wrongly (every causal pair
attended, a whole context of K and V read a step, no compressed keys,
no state), so it has floors of its own; the constants come from the
metric files' `args`, and benchmark/tests recompute them from the
configuration file's keys.  Derivation: benchmark/README-sala.md.

Floors count what the MATHEMATICS uses: the tokens attention attends
(not the pages the read moves: a page half chosen is read whole), the
pairs the block mask keeps (not the tiles the pass computes), the state
once in and once out.  What the program really moves or multiplies is
more, and shows as a low share; no read can pass 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def decode_bytes(steps: float, lane_steps: float, used_tokens: float,
                 scored_keys: float, *, dense_weight_bytes: float,
                 lane_step_bytes: float, sparse_layers: int,
                 kv_token_bytes: float, ck_bytes: float) -> float:
    """Bytes `steps` decode steps had to move: every weight outside the
    embedding (a lookup) once a step; for each active lane and step the
    state of every lightning layer read and written once; and in every
    sparse layer K and V of each token attention uses and one compressed
    key for each one the choice must score (token and key counts are
    summed over steps and lanes, ONE layer)."""
    return (steps * dense_weight_bytes + lane_steps * lane_step_bytes
            + sparse_layers * (used_tokens * kv_token_bytes
                               + scored_keys * ck_bytes))


def chunk_rule_flops(heads: int, dk: int, dv: int, chunk: int) -> float:
    """FLOPs one token needs in one lightning layer under the chunkwise
    rule at `chunk` tokens a chunk, a multiply and an add each, counting
    only the pairs the triangle needs: q_t . k_s and its weight on v_s
    over the tokens of the chunk up to t, the read of the carried state
    and the token's own outer product into it."""
    intra = (chunk + 1) / 2.0 * 2 * (dk + dv)
    state = 2 * 2 * dk * dv
    return heads * (intra + state)


def prefill_flops(tokens: float, pairs_attended: float,
                  pairs_scored: float, *, dense_flops_per_token: float,
                  lightning_layers: int, rule_flops_per_token: float,
                  sparse_layers: int, attn_pair_flops: float,
                  score_pair_flops: float) -> float:
    """FLOPs the prefilled tokens needed: every matrix of every layer
    (outside embedding and output head) and the chunk rule in every
    lightning layer for each token, and in every sparse layer q.k and
    p.v for each (query, key) pair the block mask keeps and q.c for each
    (query, compressed key) pair the choice scores (pair counts are of
    ONE layer)."""
    return (tokens * (dense_flops_per_token
                      + lightning_layers * rule_flops_per_token)
            + sparse_layers * (pairs_attended * attn_pair_flops
                               + pairs_scored * score_pair_flops))


def constants(hf: Dict[str, Any], itemsize: int = 2,
              state_itemsize: int = 4) -> Dict[str, float]:
    """The metric files' `args`, from a configuration file's keys
    (benchmark/tests holds the files to this)."""
    d, H, nkv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                     hf["num_key_value_heads"], hf["head_dim"])
    sparse = sum(m == "minicpm4" for m in hf["mixer_types"])
    light = len(hf["mixer_types"]) - sparse
    mlp = 3 * d * hf["intermediate_size"]
    # q, k, v, the gate, the output
    light_mats = 5 * d * H * hd + mlp
    sparse_mats = d * H * hd + 2 * d * nkv * hd + 2 * d * H * hd + mlp
    mats = light * light_mats + sparse * sparse_mats
    return {
        "dense_weight_bytes": float((mats + d * hf["vocab_size"])
                                    * itemsize),
        # the state read and written, every lightning layer
        "lane_step_bytes": float(light * 2 * H * hd * hd * state_itemsize),
        "sparse_layers": sparse, "lightning_layers": light,
        "kv_token_bytes": float(2 * nkv * hd * itemsize),
        "ck_bytes": float(nkv * hd * itemsize),
        "dense_flops_per_token": 2.0 * mats,
        "rule_flops_per_token": chunk_rule_flops(
            H, hd, hd, hf.get("lightning_chunk", 128)),
        "attn_pair_flops": 4.0 * H * hd,
        "score_pair_flops": 2.0 * H * hd,
    }
