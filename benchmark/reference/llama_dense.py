"""Plain float32 reference for the decoder both configurations share.

Independent of ``ray_tpu.models``: its own RMSNorm, RoPE, grouped-query
attention and SwiGLU, in straightforward ``jax.numpy``. No KV cache, no
kernel, no chunking of the prompt, no bf16: activations are float32 and
every matmul runs under ``jax.default_matmul_precision("highest")`` (on a
TPU a float32 matmul is otherwise computed in bf16 passes). It reads the
program's parameter tree (stacked layers, see LAYOUT) and upcasts one
layer at a time inside a scan, so a 7 GiB bf16 model needs one layer of
float32 weights beside it, not a second copy.

The published description (Mistral-7B-v0.3 / InternLM2 modelling code on
Hugging Face), and where this departs from it:

- RMSNorm: x * rsqrt(mean(x^2) + eps) * weight, in float32.       as published
- RoPE: "rotate_half" convention, pairs (i, i + head_dim/2),
  inv_freq = theta^(-2i/head_dim), positions from 0.               as published
- Attention: softmax(q k^T / sqrt(head_dim) + causal mask) v with
  n_heads query heads sharing n_kv_heads key/value heads in groups. as published
- MLP: down(silu(gate(x)) * up(x)).                                as published
- Untied output head, no bias anywhere.                            as published
- DEPARTURE (layout only): InternLM2 stores q, k, v fused in one
  ``wqkv`` matrix, interleaved per KV group; here, as in the program,
  they are three matrices. Same mathematics, other storage.
- DEPARTURE: Mistral's ``sliding_window`` is null in v0.3, so none is
  applied; a config that sets one is refused.

LAYOUT (``params``): tok_embed [V, d]; layers.{wq [L, d, H, k], wk, wv
[L, d, KV, k], wo [L, H, k, d], w_gate, w_up [L, d, f], w_down [L, f, d],
attn_norm, mlp_norm [L, d]}; final_norm [d]; lm_head [d, V].
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [B, S, H, k] -> rotated, positions 0..S-1."""
    k = x.shape[-1]
    half = k // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / k)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, w, model: Dict[str, Any]):
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    B, S, _ = h.shape
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", x, w["wq"]), theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", x, w["wk"]), theta)
    v = jnp.einsum("bsd,dhk->bshk", x, w["wv"])
    hd = q.shape[-1]
    q = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqcgk,bpck->bcgqp", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcgqp,bpck->bqcgk", p, v).reshape(B, S, H, hd)
    h = h + jnp.einsum("bshk,hkd->bsd", o, w["wo"])
    x = _rmsnorm(h, w["mlp_norm"], eps)
    y = jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])
    return h + y @ w["w_down"]


def hidden(params, tokens, model: Dict[str, Any]):
    """tokens [B, S] -> final-norm hidden states [B, S, d], float32."""
    if model.get("sliding_window"):
        raise ValueError("the reference applies no sliding window")
    with jax.default_matmul_precision("highest"):
        h = params["tok_embed"][tokens].astype(F32)
        h, _ = jax.lax.scan(lambda c, w: (_layer(c, w, model), None),
                            h, params["layers"])
        return _rmsnorm(h, params["final_norm"], model["rms_norm_eps"])


def logits(params, tokens, model: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V], float32."""
    h = hidden(params, tokens, model)
    with jax.default_matmul_precision("highest"):
        return h @ params["lm_head"].astype(F32)


def below_best(params, seq, model: Dict[str, Any]):
    """For every position t of seq [S] but the last: how far the logit of
    the token that follows, seq[t + 1], sits below the reference's best
    logit at t, given seq[:t + 1] (teacher forced). [S - 1], >= 0."""
    lg = logits(params, seq[None, :-1], model)[0]
    chosen = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
    return lg.max(axis=-1) - chosen


def margins(params, seq, n_prompt: int, model: Dict[str, Any]):
    """`below_best` of the generated tokens alone: seq = prompt (n_prompt
    tokens) + generated tokens; returns [S - n_prompt]."""
    return below_best(params, seq, model)[n_prompt - 1:]


def loss(params, tokens, model: Dict[str, Any], chunk: int = 1024):
    """Mean next-token cross-entropy of tokens [B, S + 1], float32. The
    vocabulary projection runs `chunk` positions at a time so [B, S, V]
    float32 logits never exist at once."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    h = hidden(params, inputs, model)
    B, S, d = h.shape
    chunk = chunk if S % chunk == 0 else S
    head = params["lm_head"].astype(F32)

    def nll(args):
        hc, tc = args
        with jax.default_matmul_precision("highest"):
            lp = jax.nn.log_softmax(hc @ head, axis=-1)
        return -jnp.take_along_axis(lp, tc[..., None], axis=-1)[..., 0]

    hc = h.reshape(B, S // chunk, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, S // chunk, chunk).transpose(1, 0, 2)
    return jnp.mean(jax.lax.map(nll, (hc, tc)))
