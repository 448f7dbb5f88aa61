"""Operations and bytes of what a `deepseek_v32` configuration adds to a
step: the indexer's scoring, the sparse attention in the form that runs
(absorbed: one shared latent row a token for all heads), and the held
experts. From the published keys of the configuration file alone; every
function is the LEAST a step has to do, which a roofline share is held
against. Stdlib only.

One token-layer the indexer scores: its key, `index_head_dim` values, is
read once (2 B each) and meets `index_n_heads` query heads: 2 x heads x
dim operations. One token-layer attention selects: its latent row,
`kv_lora_rank + qk_rope_head_dim` values, is read once and meets every
attention head twice: scores over the whole row, the weighted sum over
the latent part. One held expert that is hit: its three matrices once.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

BYTES = 2          # bf16 values in both planes and the weights


def latent_row_values(model: Dict[str, Any]) -> int:
    """Values of a token's latent row as published: 512 + 64."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def indexer_score_cost(model: Dict[str, Any], token_layers: float
                       ) -> Tuple[float, float]:
    """(operations, bytes) of scoring ``token_layers`` live tokens, each
    in one layer, for one query."""
    ops = 2.0 * model["index_n_heads"] * model["index_head_dim"]
    return token_layers * ops, token_layers * model["index_head_dim"] * BYTES


def sparse_attention_cost(model: Dict[str, Any], token_layers: float
                          ) -> Tuple[float, float]:
    """(operations, bytes) of attending ``token_layers`` SELECTED tokens,
    each in one layer, for one query, in the absorbed form."""
    row = latent_row_values(model)
    ops = 2.0 * model["num_attention_heads"] * (row + model["kv_lora_rank"])
    return token_layers * ops, token_layers * row * BYTES


def expert_params(model: Dict[str, Any]) -> int:
    """Parameters of ONE routed expert: 3 x hidden x moe_intermediate."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_experts_cost(model: Dict[str, Any], experts_hit: float,
                      assignments_landed: float) -> Tuple[float, float]:
    """(operations, bytes) of the held experts of expert-layer runs in
    which ``experts_hit`` (summed over the runs) had an assignment and
    ``assignments_landed`` token-expert pairs were computed."""
    p = expert_params(model)
    return 2.0 * p * assignments_landed, float(p) * BYTES * experts_hit


def least_s(cost: Tuple[float, float], peak: Dict[str, float]) -> float:
    """The roofline: the longer of the compute time and the HBM time."""
    ops, nbytes = cost
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def share_parameters(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of this configuration as cut: a layer's attention
    and indexer, a dense layer, an expert layer with its held experts,
    the vocabulary slice, and all of it."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    n, r, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    rq, rc = model["q_lora_rank"], model["kv_lora_rank"]
    attn = d * rq + rq * H * (n + r) + d * (rc + r) + rc * H * (n + v) \
        + H * v * d
    indexer = rq * model["index_n_heads"] * model["index_head_dim"] \
        + d * model["index_head_dim"] + d * model["index_n_heads"]
    held = model.get("held_experts") or [0, model["n_routed_experts"]]
    expert = expert_params(model)
    moe = attn + indexer + (held[1] - held[0]) * expert \
        + model["n_shared_experts"] * expert + d * model["n_routed_experts"]
    dense = attn + indexer + 3 * d * model["intermediate_size"]
    vocab = 2 * model["vocab_size"] * d
    k = model["first_k_dense_replace"]
    return {"attention": attn, "indexer": indexer, "expert": expert,
            "dense_layer": dense, "expert_layer": moe, "vocabulary": vocab,
            "total": k * dense + (model["num_hidden_layers"] - k) * moe
            + vocab}
