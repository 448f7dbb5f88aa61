"""A configuration file's published keys (Hugging Face names) as the
program's `LlamaConfig`."""

from __future__ import annotations

from typing import Any, Dict


def llama_config(model: Dict[str, Any], max_len: int, *,
                 activation_dtype: str, param_dtype: str, **extra):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=dt[activation_dtype], param_dtype=dt[param_dtype], **extra)
