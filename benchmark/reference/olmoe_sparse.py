"""Plain float32 reference for OLMoE's decoder: MHA with a q/k norm, and a
sparse feed-forward of 64 experts, 8 a token, no shared expert.

Independent of ``ray_tpu.models``: its own RMSNorm, RoPE, attention,
router and expert sum, in straightforward ``jax.numpy``. No KV cache, no
kernel, no sorting or grouping of tokens, no chunking of the prompt, no
bf16: activations are float32 and every matmul runs under
``jax.default_matmul_precision("highest")``. It reads the program's
parameter tree (stacked layers, see LAYOUT) and upcasts one layer, and
inside it one expert, at a time, so a bf16 model needs one expert of
float32 weights beside it, not a second copy.

The layer, as `modeling_olmoe.py` (Hugging Face transformers) has it:

    a = attn_norm(h);  q = q_norm(Wq a);  k = k_norm(Wk a);  v = Wv a
    h = h + Wo . causal_softmax(rope(q) rope(k)^T / sqrt(head_dim)) v
    u = mlp_norm(h);  p = softmax_f32(Wr u) over ALL experts;  T = top_k(p)
    h = h + sum_{e in T} p_e . W2_e (silu(W1_e u) * W3_e u)

and where this file follows or departs from it:

- RMSNorm: x * rsqrt(mean(x^2) + eps) * weight, in float32.       as published
- q_norm / k_norm: ONE RMSNorm over the whole projection (all heads
  of a token together: 2048 wide for q, kv_heads * head_dim for k),
  applied to the projection's output BEFORE it is split into heads
  and before RoPE. Not a per-head norm.                            as published
- RoPE: "rotate_half", pairs (i, i + head_dim/2),
  inv_freq = theta^(-2i/head_dim), positions from 0.               as published
- Attention: softmax(q k^T / sqrt(head_dim) + causal mask) v; the
  config is MHA (16 = 16 heads), groups are kept general.          as published
- Router: softmax over all experts, then the top k; the weights
  are those probabilities AS THEY ARE when `norm_topk_prob` is
  false (OLMoE: they sum to less than 1), renormalised over the
  chosen k only when it is true (Mixtral).                         as published
- Experts: down(silu(gate(x)) * up(x)), summed with the weights.   as published
- Untied output head, no bias anywhere (`attention_bias` false).   as published
- DEPARTURE (precision): the published code computes router logits in
  the model's dtype and only the softmax in float32; here the router
  matmul is float32 too, like everything else.
- DEPARTURE (ties): torch.topk leaves the choice among equal
  probabilities open; here the lower expert index wins, written out as a
  rank (`_chosen`) so that no library's top-k decides it.
- DEPARTURE (form): the published code loops over experts and
  index-adds the tokens each one received; here every expert is applied
  to every position and multiplied by its weight or by zero. Same sum.
- DEPARTURE (layout only): per-expert `gate_proj/up_proj/down_proj`
  modules are three stacks [E, d, f], [E, d, f], [E, f, d] here, as in
  the program.
- LEFT OUT: the load-balancing auxiliary loss (`router_aux_loss_coef`,
  `output_router_logits`) is training's.
- REFUSED: `clip_qkv` other than null, `attention_bias` true,
  `rope_scaling` other than null, a sliding window.

Besides logits it returns, for every layer and position, the GAP between
the 8th and the 9th router probability (`k`-th and `k+1`-th): routing is
discontinuous, and where the gap is smaller than the error a lower
precision puts on a router probability, that precision may choose another
8th expert. The comparison that decides `correct` reads it.

LAYOUT (``params``): tok_embed [V, d]; layers.{wq [L, d, H, k], wk, wv
[L, d, KV, k], wo [L, H, k, d], q_norm [L, H*k], k_norm [L, KV*k],
w_router [L, d, E], we_gate, we_up [L, E, d, f], we_down [L, E, f, d],
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


def _chosen(p, k: int):
    """p [..., E] -> bool [..., E]: the k largest, the lower index first
    among equals. Expert e's rank is the number of experts that beat it."""
    idx = jnp.arange(p.shape[-1])
    beats = (p[..., None, :] > p[..., :, None]) | (
        (p[..., None, :] == p[..., :, None]) & (idx[None, :] < idx[:, None]))
    return beats.sum(-1) < k


def _check(model: Dict[str, Any]) -> None:
    for key in ("clip_qkv", "rope_scaling", "sliding_window"):
        if model.get(key):
            raise ValueError(f"the reference applies no {key}")
    if model.get("attention_bias"):
        raise ValueError("the reference has no attention bias")


def _layer(h, w, model: Dict[str, Any]):
    """h [B, S, d] -> (h, gap [B, S])."""
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    top_k = model["num_experts_per_tok"]
    B, S, _ = h.shape
    experts = {n: w[n] for n in ("we_gate", "we_up", "we_down")}
    w = {n: a.astype(F32) for n, a in w.items() if n not in experts}
    x = _rmsnorm(h, w["attn_norm"], eps)
    wq, wk, wv = w["wq"], w["wk"], w["wv"]
    hd = wq.shape[-1]
    q = jnp.einsum("bsd,dn->bsn", x, wq.reshape(wq.shape[0], -1))
    k = jnp.einsum("bsd,dn->bsn", x, wk.reshape(wk.shape[0], -1))
    q = _rmsnorm(q, w["q_norm"], eps).reshape(B, S, H, hd)
    k = _rmsnorm(k, w["k_norm"], eps).reshape(B, S, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    q = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqcgk,bpck->bcgqp", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bcgqp,bpck->bqcgk", jax.nn.softmax(s, axis=-1), v)
    h = h + jnp.einsum("bshk,hkd->bsd", o.reshape(B, S, H, hd), w["wo"])

    u = _rmsnorm(h, w["mlp_norm"], eps)
    p = jax.nn.softmax(u @ w["w_router"], axis=-1)            # [B, S, E]
    chosen = _chosen(p, top_k)
    weight = jnp.where(chosen, p, 0.0)
    if model.get("norm_topk_prob"):
        weight = weight / weight.sum(-1, keepdims=True)
    ranked = jnp.sort(p, axis=-1)[..., ::-1]
    gap = ranked[..., top_k - 1] - ranked[..., top_k]

    def one_expert(acc, ew):
        e, we = ew
        w1, w3, w2 = (we[n].astype(F32)
                      for n in ("we_gate", "we_up", "we_down"))
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        return acc + y * weight[..., e][..., None], None

    n_exp = p.shape[-1]
    moe, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (jnp.arange(n_exp), experts))
    return h + moe, gap


def hidden(params, tokens, model: Dict[str, Any]):
    """tokens [B, S] -> (final-norm hidden states [B, S, d] float32,
    router gaps [L, B, S])."""
    _check(model)
    with jax.default_matmul_precision("highest"):
        h = params["tok_embed"][tokens].astype(F32)
        h, gaps = jax.lax.scan(lambda c, w: _layer(c, w, model),
                               h, params["layers"])
        return _rmsnorm(h, params["final_norm"], model["rms_norm_eps"]), gaps


def logits_and_gaps(params, tokens, model: Dict[str, Any]):
    """tokens [B, S] -> (logits [B, S, V] float32, gaps [L, B, S])."""
    h, gaps = hidden(params, tokens, model)
    with jax.default_matmul_precision("highest"):
        return h @ params["lm_head"].astype(F32), gaps


def logits(params, tokens, model: Dict[str, Any]):
    return logits_and_gaps(params, tokens, model)[0]


def below_best_and_gaps(params, seq, model: Dict[str, Any]):
    """For every position t of seq [S] but the last: how far the logit of
    the token that follows, seq[t + 1], sits below the reference's best
    logit at t, given seq[:t + 1] (teacher forced), [S - 1] >= 0; and the
    smallest router gap over the layers at t, [S - 1]."""
    lg, gaps = logits_and_gaps(params, seq[None, :-1], model)
    lg = lg[0]
    chosen = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
    return lg.max(axis=-1) - chosen, gaps[:, 0].min(axis=0)


def below_best(params, seq, model: Dict[str, Any]):
    return below_best_and_gaps(params, seq, model)[0]


def margins(params, seq, n_prompt: int, model: Dict[str, Any]):
    """`below_best` of the generated tokens alone: seq = prompt (n_prompt
    tokens) + generated tokens; returns [S - n_prompt]."""
    return below_best(params, seq, model)[n_prompt - 1:]
