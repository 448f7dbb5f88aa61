"""Operations and bytes of a hybrid decoder (state-space, window, full and
shared-cache layers in one stack: `model_type` phi4flash) from its
configuration's sizes. `costs.matmul_params` assumes every layer is an
attention layer with one feed-forward; here a layer's mixer is one of
five kinds, only some layers cache keys and values, and nine carry
recurrent state. Stdlib only, the published key names plus `assumed`.

With ``half = num_hidden_layers / 2`` the stack is half/2 x [state-space,
window attention], [state-space, full attention], (half/2 - 1) x [gated
memory unit, cross-attention]; every layer has a gated feed-forward.
"""

from __future__ import annotations

import math
from typing import Any, Dict


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    a = model["assumed"]
    d = model["hidden_size"]
    return dict(d=d, f=model["intermediate_size"],
                H=model["num_attention_heads"],
                KV=model["num_key_value_heads"], hd=a["head_dim"],
                di=a["mamba_expand"] * d, N=a["mamba_d_state"],
                dc=a["mamba_d_conv"],
                R=a.get("mamba_dt_rank") or math.ceil(d / 16),
                L=model["num_hidden_layers"], V=model["vocab_size"],
                W=model["sliding_window"])


def layer_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind the stack has."""
    n = model["num_hidden_layers"] // 4
    return {"ssm": n + 1, "window": n, "full": 1, "gmu": n - 1,
            "cross": n - 1}


def ffn_params(model) -> int:
    D = dims(model)
    return 3 * D["d"] * D["f"]


def ssm_params(model) -> int:
    """in_proj, x_proj, dt_proj (+ bias), out_proj, conv (+ bias), A, D."""
    D = dims(model)
    d, di, N, R, dc = D["d"], D["di"], D["N"], D["R"], D["dc"]
    return 2 * d * di + di * (R + 2 * N) + R * di + di + di * d \
        + di * (dc + 1) + di * N + di


def _lambda_params(model) -> int:
    return 6 * dims(model)["hd"]        # four vectors and the sub-norm


def attention_params(model) -> int:
    """A layer with its own keys and values: Wqkv and out_proj with bias."""
    D = dims(model)
    wide = (D["H"] + 2 * D["KV"]) * D["hd"]
    return D["d"] * wide + wide + D["H"] * D["hd"] * D["d"] + D["d"] \
        + _lambda_params(model)


def cross_params(model) -> int:
    """Query and output projections only, with bias."""
    D = dims(model)
    return 2 * D["d"] * D["H"] * D["hd"] + D["H"] * D["hd"] + D["d"] \
        + _lambda_params(model)


def gmu_params(model) -> int:
    D = dims(model)
    return 2 * D["d"] * D["di"]


def total_params(model: Dict[str, Any]) -> int:
    """Every parameter held; the tied embedding once."""
    D, n = dims(model), layer_counts(model)
    norms = 4 * D["d"]                   # two LayerNorms a layer, w and b
    return D["V"] * D["d"] + 2 * D["d"] \
        + D["L"] * (ffn_params(model) + norms) \
        + n["ssm"] * ssm_params(model) \
        + (n["window"] + n["full"]) * attention_params(model) \
        + n["gmu"] * gmu_params(model) + n["cross"] * cross_params(model)


def weight_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return total_params(model) * dtype_bytes


def kv_token_layer_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Keys and values of ONE token in ONE caching layer: what the paged
    kernel reads of a token for one layer that attends it."""
    D = dims(model)
    return 2 * D["KV"] * D["hd"] * dtype_bytes


def caching_layers(model: Dict[str, Any]) -> int:
    n = layer_counts(model)
    return n["window"] + n["full"]


def full_cache_readers(model: Dict[str, Any]) -> int:
    """Layers that read the full-attention layer's cache: itself and
    every cross-attention layer."""
    return 1 + layer_counts(model)["cross"]


def ssm_state_bytes(model: Dict[str, Any]) -> int:
    """One row's float32 scan state in one state-space layer."""
    D = dims(model)
    return D["di"] * D["N"] * 4


def conv_state_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    D = dims(model)
    return D["di"] * (D["dc"] - 1) * dtype_bytes


def recurrent_bytes_per_row(model: Dict[str, Any]) -> int:
    """All recurrent state one engine slot holds."""
    return layer_counts(model)["ssm"] * (
        ssm_state_bytes(model) + conv_state_bytes(model))


def scan_least_s(model: Dict[str, Any], row_steps: float,
                 hbm_bytes_per_s: float) -> float:
    """Least time for the recurrence of `row_steps` (live rows x decode
    tokens): every state-space layer reads and writes the row's scan
    state once. The conv state is not in it: the conv is `ssm_proj`'s."""
    return row_steps * layer_counts(model)["ssm"] * 2 \
        * ssm_state_bytes(model) / hbm_bytes_per_s


def attention_least_s(model: Dict[str, Any], token_layers: float,
                      hbm_bytes_per_s: float) -> float:
    """Least time to read `token_layers` token-layers of keys and values
    (the engine's `kv_walk_tokens_*` counters count them: a token once
    for each layer that reads it)."""
    return token_layers * kv_token_layer_bytes(model) / hbm_bytes_per_s


def prefill_skip_share(model: Dict[str, Any]) -> float:
    """Share of a long prompt's token-layers the cross-decoder skip saves:
    the layers after the full-attention layer over all layers."""
    L = model["num_hidden_layers"]
    return (L - (L // 2 + 2)) / L
