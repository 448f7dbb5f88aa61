"""Plain float32 reference for Qwen3-Next's decoder: Gated DeltaNet layers
(a matrix-valued recurrent state a head) beside gated softmax attention,
3 : 1, an expert layer with a gated shared expert in every layer.

Independent of ``ray_tpu.models`` and ``ray_tpu.ops``: its own norms,
conv, delta rule, rotary, attention, router and expert sum, in
straightforward ``jax.numpy``. No cache, no kernel, no batching, no
chunking, no bf16: activations are float32 and every matmul runs under
``jax.default_matmul_precision("highest")``. The delta rule is the
RECURRENCE itself, one token at a time under a scan (not the chunkwise
form the program runs over a prompt); attention scores are full rows, a
block of queries at a time, under the causal mask. It reads the program's
parameter tree (LAYOUT) and upcasts one layer, and inside it one expert,
at a time, so a 10 k-token request fits beside the bf16 weights.

``h`` the residual stream, ``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``
the ZERO-CENTRED RMSNorm, layer ``i``:

    h = h + Mixer_i(N1_i(h));  h = h + F_i(N2_i(h));  logits = W_head N_f(h)

``Mixer_i`` is gated attention where ``(i + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise. Each line below is marked "as
published" (config.json and transformers' `modeling_qwen3_next.py`, both
written from memory: no network) or `assumed`:

- Gated DeltaNet. ``[q, k, v, z] = a W_qkvz`` (2 key_dim + 2 value_dim),
  ``[b, alpha] = a W_ba`` (2 x value heads).                       as published
  The published `in_proj_qkvz` interleaves q, k, v, z a key head; here
  they are contiguous ``[q | k | v | z]`` and ``[b | alpha]``: a
  permutation of the weight's columns, which changes no value.     assumed
- ``[q | k | v]`` through a causal depthwise conv over time, width
  linear_conv_kernel_dim, no bias, then SiLU.                      as published
- q and k L2-normalised a head, ``x * rsqrt(sum(x^2) + 1e-6)``; q scaled
  by ``linear_key_head_dim ** -0.5``; key head ``j`` serves value heads
  ``j * r .. (j + 1) * r - 1``, ``r = value heads / key heads``
  (`repeat_interleave`).                                           as published
- ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(alpha + dt_bias)``,
  float32, a value head.                                           as published
- A head's state ``S`` [dk, dv], zero before the first token; a token:
  ``S <- exp(g) S; d = beta (v - S^T k); S <- S + k d^T; o = S^T q``.
                                                                   as published
- Output ``W_o (RMSNorm(o) * w * silu(z))``, the RMSNorm over a head's dv
  with an ordinary weight ``w`` (not zero-centred), eps rms_norm_eps.
                                                                   as published
- Gated attention. ``q_proj`` gives, a head, ``[query | gate]`` (2 x
  head_dim); zero-centred RMSNorm over head_dim on each head of q and k;
  rotary on the FIRST ``partial_rotary_factor * head_dim`` dims of a head,
  half-split pairs ``(i, i + r / 2)``, ``inv_freq = theta ^ (-2 i / r)``;
  causal softmax, scale ``head_dim ** -0.5``, KV head ``j`` serving
  query heads ``j * G .. (j + 1) * G - 1``; output ``W_o (attn *
  sigmoid(gate))``. No bias anywhere.                              as published
- F_i: router logits ``W_r u`` float32 over ALL num_experts, softmax,
  the num_experts_per_tok largest, divided by their sum
  (norm_topk_prob); ``F(u) = sum_e w_e E_e(u) + sigmoid(u . w_sg)
  E_shared(u)``, every E a gated SiLU FFN.                         as published
- Ties in a top-k: the lower index wins (`jax.lax.top_k`).
- THE CHIP'S SHARE (`held`): the router and its k a token are over ALL
  num_experts; of the chosen experts only those in ``held = (lo, hi)`` are
  computed and summed (the parameter stacks then hold experts lo..hi-1
  alone), the shared expert is added once unless ``shared`` is False, and
  that partial result goes on to the next layer. ``held`` None: the whole
  layer. The vocabulary's slice is whatever rows `tok_embed` / `lm_head`
  hold. What the absent experts would add is left out here as in the
  program: no code stands in for the exchange.
- LEFT OUT: the multi-token-prediction module the checkpoint carries (an
  extra layer that drafts; the catalog's `config` has no key for it and
  the model serves without it).

LAYOUT (``params``): tok_embed [V, d]; final_norm [d]; lm_head [d, V];
``period``: {``delta`` with leading axes [P, n - 1] of {norm [d]; w_qkvz
[d, 2 kd + 2 vd]; w_ba [d, 2 Hv]; conv_w [kernel, 2 kd + vd]; a_log,
dt_bias [Hv]; o_norm [dv]; w_out [vd, d]}, ``attn`` with leading axis [P]
of {norm [d]; wq [d, H * 2 * D]; wk, wv [d, KV * D]; q_norm, k_norm [D];
wo [H * D, d]}, ``moe`` with leading axes [P, n] of {norm [d]; w_router
[d, E]; we_gate, we_up [Eh, d, f]; we_down [Eh, f, d]; ws_gate, ws_up
[d, fs]; ws_down [fs, d]; w_sgate [d]}}, P periods of n =
full_attention_interval layers, the attention layer last.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8192        # vocabulary rows a step of `head_margin`
QUERY_BLOCK = 512        # queries whose score rows are alive at once

_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def _norm1p(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """q, k [T, H, dk], v [T, H, dv], g, beta [T, H] -> o [T, H, dv]: the
    recurrence from a zero state, a token at a time."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    return jax.lax.scan(token, jnp.zeros((H, dk, dv), F32),
                        (q, k, v, g, beta))[1]


def delta_mixer(a, w, model):
    """Gated DeltaNet over the normed inputs a [T, d] -> [T, d]."""
    T = a.shape[0]
    Hk, Hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    kd, vd = Hk * dk, Hv * dv
    width = int(model["linear_conv_kernel_dim"])
    qkvz = a @ w["w_qkvz"]
    x, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    ba = a @ w["w_ba"]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])
    past = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x])
    x = jax.nn.silu(sum(past[j:j + T] * w["conv_w"][j]
                        for j in range(width)))
    q = _unit(x[:, :kd].reshape(T, Hk, dk)) * dk ** -0.5
    k = _unit(x[:, kd:2 * kd].reshape(T, Hk, dk))
    v = x[:, 2 * kd:].reshape(T, Hv, dv)
    q = jnp.repeat(q, Hv // Hk, axis=1)
    k = jnp.repeat(k, Hv // Hk, axis=1)
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + float(model["rms_norm_eps"])) * w["o_norm"]
    return (o * jax.nn.silu(z.reshape(T, Hv, dv))).reshape(T, vd) \
        @ w["w_out"]


def _rope(x, model):
    """Rotary over the first partial_rotary_factor of each head of
    x [T, H, D], half-split pairs."""
    T, _, D = x.shape
    r = int(D * float(model["partial_rotary_factor"]))
    half = r // 2
    inv = float(model["rope_theta"]) ** (
        -jnp.arange(half, dtype=F32) * 2.0 / r)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def gated_attention(a, w, model):
    """Gated softmax attention over the normed inputs a [T, d] -> [T, d],
    a block of queries at a time."""
    T = a.shape[0]
    H, KV, D = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    eps = float(model["rms_norm_eps"])
    qg = (a @ w["wq"]).reshape(T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (a @ w["wk"]).reshape(T, KV, D)
    v = (a @ w["wv"]).reshape(T, KV, D)
    q = _rope(_norm1p(q, w["q_norm"], eps), model)
    k = _rope(_norm1p(k, w["k_norm"], eps), model)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    block = min(QUERY_BLOCK, T)
    n = -(-T // block)
    qp = jnp.pad(q, ((0, n * block - T), (0, 0), (0, 0)))

    def rows(_, i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * block, block)
        s = jnp.einsum("thd,shd->hts", qb, k) * D ** -0.5
        t = i * block + jnp.arange(block)
        s = jnp.where(jnp.arange(T)[None, None, :] <= t[None, :, None],
                      s, -jnp.inf)
        return None, jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

    o = jax.lax.scan(rows, None, jnp.arange(n))[1].reshape(
        n * block, H, D)[:T]
    return (o * jax.nn.sigmoid(gate)).reshape(T, H * D) @ w["wo"]


def route(u, w, model):
    """u [T, d] -> (weights [T, k], expert ids [T, k]) over ALL experts."""
    probs = jax.nn.softmax(u @ w["w_router"], axis=-1)
    wts, idx = jax.lax.top_k(probs, int(model["num_experts_per_tok"]))
    if model["norm_topk_prob"]:
        wts = wts / wts.sum(-1, keepdims=True)
    return wts, idx


def _gated(u, g, up, down):
    return (jax.nn.silu(u @ g) * (u @ up)) @ down


def expert_layer(u, w, model, held: Optional[Tuple[int, int]] = None,
                 shared: bool = True):
    """F(u) [T, d] of an expert layer, or this share's part of it: the
    stacks ``we_*`` hold experts ``held[0] .. held[1] - 1``, in whatever
    precision they came; one is upcast at a time."""
    wts, idx = route(u, w, model)
    lo = 0 if held is None else held[0]

    def one(out, x):
        g, up, down, e = x
        we = jnp.where(idx == e, wts, 0.0).sum(-1)             # [T]
        return out + we[:, None] * _gated(
            u, g.astype(F32), up.astype(F32), down.astype(F32)), None

    n = w["we_gate"].shape[0]
    out = jax.lax.scan(one, jnp.zeros_like(u),
                       (w["we_gate"], w["we_up"], w["we_down"],
                        lo + jnp.arange(n)))[0]
    if shared:
        out = out + jax.nn.sigmoid(u @ w["w_sgate"])[:, None] * _gated(
            u, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out


def hidden(params, seq, model: Dict[str, Any],
           held: Optional[Tuple[int, int]] = None, shared: bool = True):
    """seq [T] -> final-normed hidden states [T, d]. One jitted program a
    KIND of layer, called layer after layer: a layer's float32 weights are
    alive while it runs and no longer."""
    eps = float(model["rms_norm_eps"])

    def layer(h, w, wm, mixer):
        with jax.default_matmul_precision("highest"):
            w = _f32(w)
            small = _f32({k: v for k, v in wm.items()
                          if k not in _EXPERT_KEYS})
            h = h + mixer(_norm1p(h, w["norm"], eps), w, model)
            u = _norm1p(h, small["norm"], eps)
            return h + expert_layer(
                u, {**small, **{k: wm[k] for k in _EXPERT_KEYS}}, model,
                held, shared)

    steps = {"delta": jax.jit(lambda h, w, wm: layer(h, w, wm, delta_mixer)),
             "attn": jax.jit(lambda h, w, wm: layer(h, w, wm,
                                                    gated_attention))}
    period = params["period"]
    P, nd = period["delta"]["norm"].shape[:2]
    at = jax.tree_util.tree_map
    h = params["tok_embed"][seq].astype(F32)
    for p in range(P):
        for j in range(nd):
            h = steps["delta"](h, at(lambda x: x[p, j], period["delta"]),
                               at(lambda x: x[p, j], period["moe"]))
        h = steps["attn"](h, at(lambda x: x[p], period["attn"]),
                          at(lambda x: x[p, nd], period["moe"]))
    return _norm1p(h, params["final_norm"].astype(F32), eps)


def logits(params, tokens, model: Dict[str, Any],
           held: Optional[Tuple[int, int]] = None, shared: bool = True):
    """tokens [B, S] -> logits [B, S, V] float32 (small vocabularies)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32)
        return jnp.stack([hidden(params, seq, model, held, shared) @ head
                          for seq in tokens])


def below_best(params, seq, model: Dict[str, Any],
               held: Optional[Tuple[int, int]] = None):
    """For every position t of seq [S] but the last: how far the logit of
    the token that follows sits below the reference's best logit at t,
    given seq[:t + 1] (teacher forced), [S - 1] >= 0."""
    return head_margin(params, hidden(params, seq[:-1], model, held),
                       seq[1:])


def head_margin(params, h, nxt):
    """The head a block of the vocabulary at a time."""
    head = params["lm_head"]                                   # [d, V]
    V = head.shape[1]
    block = min(HEAD_BLOCK, V)
    with jax.default_matmul_precision("highest"):
        chosen = jnp.einsum("sd,ds->s", h, head[:, nxt].astype(F32))

        def one(best, i):
            cols = jax.lax.dynamic_slice_in_dim(
                head, jnp.minimum(i * block, V - block), block, axis=1)
            return jnp.maximum(best, (h @ cols.astype(F32)).max(-1)), None

        best, _ = jax.lax.scan(one, chosen, jnp.arange(-(-V // block)))
    return best - chosen
