"""Operations and bytes of a sparse (mixture-of-experts) decoder from its
configuration's sizes. `costs.matmul_params` assumes one dense
feed-forward a layer; here a layer has `num_experts` of them, of width
`intermediate_size` each, of which a token uses `num_experts_per_tok`,
and a router. Stdlib only, Hugging Face key names."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness import costs


def expert_params(model: Dict[str, Any]) -> int:
    """Parameters of ONE expert: gate, up and down, 3 x d x f."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Bytes of one expert's weights: what a step reads of it, once, if
    at least one token is routed to it."""
    return expert_params(model) * dtype_bytes


def expert_flops_per_assignment(model: Dict[str, Any]) -> int:
    """FLOPs of one token through one expert: 2 per parameter."""
    return 2 * expert_params(model)


def experts_flops(model: Dict[str, Any], tokens: float) -> float:
    """FLOPs the expert layers need for `tokens` tokens, all layers: each
    token goes through `num_experts_per_tok` experts a layer."""
    return float(tokens) * model["num_experts_per_tok"] \
        * expert_flops_per_assignment(model) * model["num_hidden_layers"]


def layer_expert_params(model: Dict[str, Any]) -> int:
    """All experts of one layer."""
    return model["num_experts"] * expert_params(model)


def _attention_params(model: Dict[str, Any]) -> int:
    d, hd = model["hidden_size"], costs.head_dim(model)
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def _norm_params(model: Dict[str, Any]) -> int:
    """attn_norm, mlp_norm, and OLMoE's q_norm and k_norm (over the whole
    projections)."""
    hd = costs.head_dim(model)
    return 2 * model["hidden_size"] + hd * (
        model["num_attention_heads"] + model["num_key_value_heads"])


def total_params(model: Dict[str, Any]) -> int:
    """Every parameter held: embedding, head, final norm, and per layer
    attention, the router, all experts and the norms."""
    d = model["hidden_size"]
    layer = _attention_params(model) + d * model["num_experts"] \
        + layer_expert_params(model) + _norm_params(model)
    return 2 * model["vocab_size"] * d + d \
        + model["num_hidden_layers"] * layer


def active_matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for ONE token:
    projections, router, its `num_experts_per_tok` experts, the head."""
    d = model["hidden_size"]
    layer = _attention_params(model) + d * model["num_experts"] \
        + model["num_experts_per_tok"] * expert_params(model)
    return model["num_hidden_layers"] * layer + d * model["vocab_size"]


def weight_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return total_params(model) * dtype_bytes


def decode_experts_least_s(model: Dict[str, Any], experts_hit_per_layer: float,
                           decode_tokens: float, hbm_bytes_per_s: float
                           ) -> float:
    """Least time to read, for each of `decode_tokens` decode steps and in
    each layer, the weights of the experts that were hit."""
    return experts_hit_per_layer * model["num_hidden_layers"] \
        * decode_tokens * expert_bytes(model) / hbm_bytes_per_s
