"""Operations and bytes from shapes, and the table of peaks.

The yardstick: every utilisation or roofline share the benchmark prints
divides by a number computed here, from the configuration's sizes, never
by one the program reports. Stdlib only.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       "add it to benchmark/harness/peaks.json with its "
                       "source")
    return table[device_kind]


def head_dim(model: Dict[str, Any]) -> int:
    return model.get("head_dim") or \
        model["hidden_size"] // model["num_attention_heads"]


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: projections, MLP and the output head. The input embedding is a
    table lookup and the norm scales are elementwise: neither counts."""
    d, f = model["hidden_size"], model["intermediate_size"]
    hd = head_dim(model)
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    return model["num_hidden_layers"] * (attn + 3 * d * f) \
        + d * model["vocab_size"]


def total_params(model: Dict[str, Any]) -> int:
    d = model["hidden_size"]
    return matmul_params(model) + model["vocab_size"] * d \
        + (2 * model["num_hidden_layers"] + 1) * d


def attention_flops_fwd(model: Dict[str, Any], seq_len: int,
                        causal: bool = True) -> float:
    """FLOPs of attention's two matmuls (q k^T and p v) for ONE sequence,
    forward, all layers: 4 * S^2 * H * head_dim, halved under a causal
    mask because the kernel skips blocks above the diagonal."""
    full = 4.0 * seq_len * seq_len * model["num_attention_heads"] \
        * head_dim(model) * model["num_hidden_layers"]
    return full * (0.5 if causal else 1.0)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs a training step needs per token: forward + backward =
    3 x forward; forward = 2 per matmul parameter + causal attention.
    Recomputation (remat) is not counted: it is work the step chose."""
    return 6.0 * matmul_params(model) \
        + 3.0 * attention_flops_fwd(model, seq_len) / seq_len


def flash_flops(model: Dict[str, Any], seq_len: int, n_seqs: int
                ) -> Dict[str, float]:
    """FLOPs the flash kernels need for n_seqs sequences through all
    layers: forward 2 matmuls; backward 5 (the dq kernel recomputes s and
    makes dp, dq; the dk/dv kernel recomputes s and makes dp, dv, dk -
    counted as the algorithm's minimum of 5, not the 7 two kernels run)."""
    fwd = attention_flops_fwd(model, seq_len) * n_seqs
    return {"fwd": fwd, "bwd": 2.5 * fwd}


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return 2 * model["num_hidden_layers"] * model["num_key_value_heads"] \
        * head_dim(model) * dtype_bytes


def paged_attention_bytes(model: Dict[str, Any], live_tokens: float,
                          dtype_bytes: int = 2) -> float:
    """Bytes one decode step's attention must read: the keys and values
    of every live token, once, in all layers. Queries and outputs are
    thousands of times smaller and are left out."""
    return live_tokens * kv_bytes_per_token(model, dtype_bytes)


def weight_bytes(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return total_params(model) * dtype_bytes
