"""Operations and bytes of what a `dots3_note` configuration adds to a
step beside `costs_mla`'s (its full layers ARE those: the indexer's
scoring, the selected attention, the held experts, read from the same
keys): the WINDOW layers' latent attention, and the parameter arithmetic
of the cut. From the published keys of the configuration file alone; every
function is the LEAST a step has to do, which a roofline share is held
against. Stdlib only.

One slot-layer a window layer reads for a query: its latent row,
`swa_kv_lora_rank + swa_qk_rope_head_dim` values in the lanes the chip's
tiling stores them in (1,088 -> 1,152 lanes, 2,304 B), is read once and
meets every window head twice: scores over the whole row, the weighted sum
over the latent part.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark.harness.costs_mla import BYTES, expert_params, least_s  # noqa: F401

LANE = 128


def window_layers(model: Dict[str, Any]) -> int:
    return sum(k == "sliding_attention" for k in model["layer_types"])


def window_row_values(model: Dict[str, Any]) -> int:
    """Values of a token's window-layer latent row as published: 1,024 +
    64."""
    return model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"]


def window_row_bytes(model: Dict[str, Any]) -> int:
    """... as stored: whole lane tiles of bf16 (1,152 lanes, 2,304 B)."""
    return -(-window_row_values(model) // LANE) * LANE * BYTES


def swa_attention_cost(model: Dict[str, Any], slots: float
                       ) -> Tuple[float, float]:
    """(operations, bytes) of the window layers attending ``slots`` slots
    (summed over queries: min(row length, window) each), every window
    layer of the stack, in the absorbed form."""
    ops = 2.0 * model["swa_num_attention_heads"] \
        * (window_row_values(model) + model["swa_kv_lora_rank"])
    n = slots * window_layers(model)
    return n * ops, n * window_row_bytes(model)


def _attention_params(d, H, n, r, v, rq, rc) -> int:
    return d * rq + rq * H * (n + r) + d * (rc + r) + rc * H * (n + v) \
        + H * v * d + d * H            # ... and the head-wise gate


def share_parameters(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of this configuration as cut (norm weights and the
    router's bias left out, as the issue's table leaves them): a full and
    a window layer's attention, the indexer, a dense FFN, an expert layer's
    FFN with its held experts, the vocabulary slice, and all of it."""
    d = model["hidden_size"]
    full = _attention_params(
        d, model["num_attention_heads"], model["qk_nope_head_dim"],
        model["qk_rope_head_dim"], model["v_head_dim"],
        model["q_lora_rank"], model["kv_lora_rank"])
    indexer = model["q_lora_rank"] * model["index_n_heads"] \
        * model["index_head_dim"] + d * model["index_head_dim"] \
        + d * model["index_n_heads"]
    window = _attention_params(
        d, model["swa_num_attention_heads"], model["swa_qk_nope_head_dim"],
        model["swa_qk_rope_head_dim"], model["swa_v_head_dim"],
        model["swa_q_lora_rank"], model["swa_kv_lora_rank"])
    held = model.get("held_experts") or [0, model["n_routed_experts"]]
    expert = expert_params(model)
    moe = d * model["n_routed_experts"] \
        + (model["n_shared_experts"] + held[1] - held[0]) * expert
    dense = 3 * d * model["intermediate_size"]
    vocab = 2 * model["vocab_size"] * d
    k = model["first_k_dense_replace"]
    total = vocab
    for i, kind in enumerate(model["layer_types"]):
        total += (full + indexer if kind == "full_attention" else window) \
            + (dense if i < k else moe)
    return {"full_attention": full + indexer, "window_attention": window,
            "expert": expert, "dense_ffn": dense, "expert_ffn": moe,
            "vocabulary": vocab, "total": total}
