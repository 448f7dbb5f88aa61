"""Operations and bytes of a gated-delta-rule decoder (Gated DeltaNet
layers beside gated attention, an expert layer with held experts in every
layer: `model_type` qwen3_next) from its configuration's sizes. Stdlib
only, the published key names.

With ``n = full_attention_interval`` the stack is ``num_hidden_layers / n``
periods of ``n - 1`` delta layers and one attention layer; every layer has
an expert layer of which this chip holds `held_experts`.

The counts are the work the PUBLISHED equations need, whatever implements
it: a share of a roofline computed from them cannot pass 100 % unless the
time leaves work out.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# an expert's parameters, the held experts' operations and bytes and the
# roofline are the latent family's: the same published keys
from benchmark.harness.costs_mla import (   # noqa: F401
    expert_params, held_experts_cost, least_s)

BYTES = 2            # bf16
CHUNK = 64           # the published chunk of the delta rule's chunk form


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        d=model["hidden_size"], L=model["num_hidden_layers"],
        n=model["full_attention_interval"], V=model["vocab_size"],
        H=model["num_attention_heads"], KV=model["num_key_value_heads"],
        hd=model["head_dim"], Hk=model["linear_num_key_heads"],
        Hv=model["linear_num_value_heads"],
        dk=model["linear_key_head_dim"], dv=model["linear_value_head_dim"],
        dc=model["linear_conv_kernel_dim"], E=model["num_experts"],
        k=model["num_experts_per_tok"], f=model["moe_intermediate_size"],
        fs=model["shared_expert_intermediate_size"])


def layer_counts(model: Dict[str, Any]) -> Dict[str, int]:
    D = dims(model)
    periods = D["L"] // D["n"]
    return {"delta": periods * (D["n"] - 1), "attn": periods}


def share_parameters(model: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of this configuration as cut: a delta layer's and
    an attention layer's mixer, a layer's router + shared expert + held
    experts, the vocabulary slice, and all of it (norm weights left out:
    under 0.01 %)."""
    D = dims(model)
    d = D["d"]
    kd, vd = D["Hk"] * D["dk"], D["Hv"] * D["dv"]
    delta = d * (2 * kd + 2 * vd) + d * 2 * D["Hv"] \
        + D["dc"] * (2 * kd + vd) + vd * d
    attn = d * D["H"] * 2 * D["hd"] + 2 * d * D["KV"] * D["hd"] \
        + D["H"] * D["hd"] * d
    held = model.get("held_experts") or [0, D["E"]]
    moe = d * D["E"] + 3 * d * D["fs"] + d \
        + (held[1] - held[0]) * expert_params(model)
    n = layer_counts(model)
    vocab = 2 * D["V"] * d
    return {"delta_mixer": delta, "attention_mixer": attn,
            "expert": expert_params(model), "expert_layer": moe,
            "delta_layer": delta + moe, "attention_layer": attn + moe,
            "vocabulary": vocab,
            "total": n["delta"] * (delta + moe) + n["attn"] * (attn + moe)
            + vocab}


def kv_token_layer_bytes(model: Dict[str, Any]) -> int:
    """Keys and values of ONE token in ONE attention layer."""
    D = dims(model)
    return 2 * D["KV"] * D["hd"] * BYTES


def kv_token_bytes(model: Dict[str, Any]) -> int:
    """What a token stores: K and V of the attention layers alone."""
    return layer_counts(model)["attn"] * kv_token_layer_bytes(model)


def state_bytes(model: Dict[str, Any]) -> int:
    """One row's float32 matrix state in ONE delta layer."""
    D = dims(model)
    return D["Hv"] * D["dk"] * D["dv"] * 4


def conv_state_bytes(model: Dict[str, Any]) -> int:
    D = dims(model)
    return (D["dc"] - 1) * (2 * D["Hk"] * D["dk"] + D["Hv"] * D["dv"]) \
        * BYTES


def recurrent_bytes_per_row(model: Dict[str, Any]) -> int:
    """All recurrent state one engine slot holds."""
    return layer_counts(model)["delta"] * (
        state_bytes(model) + conv_state_bytes(model))


def step_least_s(model: Dict[str, Any], row_steps: float,
                 hbm_bytes_per_s: float) -> float:
    """Least time for the one-token update of `row_steps` (live rows x
    decode tokens): every delta layer reads and writes the row's matrix
    state once. Memory-bound: 6 operations a state element."""
    return row_steps * layer_counts(model)["delta"] * 2 \
        * state_bytes(model) / hbm_bytes_per_s


def chunk_cost(model: Dict[str, Any], tokens: float, dispatch_tokens: int
               ) -> Tuple[float, float]:
    """(operations, bytes) of the delta rule's chunk form over ``tokens``
    prompt tokens in every delta layer. A token, a value head, in a chunk
    of C: the products that only need a triangle (k k^T, q k^T, both C x dk
    a token, and the intra-chunk sum C x dv) count half, the triangular
    solve's two right-hand sides (C x dv and C x dk) half as well, the
    three products with the state (dk x dv each) whole. Bytes: a token's
    q, k, v in and o out once, and a row's state read and written once a
    DISPATCH of ``dispatch_tokens`` (the engine's prefill chunk: a kernel
    could keep it on the chip between the rule's chunks)."""
    D = dims(model)
    dk, dv, C = D["dk"], D["dv"], CHUNK
    per_head = 2.0 * (0.5 * (2 * C * dk + C * dv) + 0.5 * (C * dv + C * dk)
                      + 3 * dk * dv)
    layers = layer_counts(model)["delta"]
    io = (2 * D["Hk"] * dk + 2 * D["Hv"] * dv) * BYTES
    state = 2.0 * state_bytes(model) / dispatch_tokens
    return tokens * layers * D["Hv"] * per_head, \
        tokens * layers * (io + state)


def attention_least_s(model: Dict[str, Any], token_layers: float,
                      hbm_bytes_per_s: float) -> float:
    """Least time to read `token_layers` token-layers of keys and values
    (`kv_walk_tokens_full_total` counts them: a token once for each
    attention layer that reads it)."""
    return token_layers * kv_token_layer_bytes(model) / hbm_bytes_per_s
