"""Plain float32 reference for Phi-4-mini-flash-reasoning's decoder: a
stack of state-space (Mamba-1), sliding-window attention, full attention,
gated memory unit and cross-attention layers, with differential attention
in every attention layer.

Independent of ``ray_tpu.models``: its own LayerNorm, causal conv,
selective scan, masks and softmaxes in straightforward ``jax.numpy``. No
KV cache, no block table, no kernel, no chunking of the prompt, no
skipping of layers (every layer runs for every position), no bf16:
activations are float32 and every matmul runs under
``jax.default_matmul_precision("highest")``. The state-space layers are a
sequential `lax.scan` over time, one token a step; attention builds the
full ``[S, S]`` mask, one head pair at a time. It reads the program's
parameter tree (LAYOUT) and upcasts one period of layers at a time, and
the tied head one block of the vocabulary at a time, so a 3.85 B bf16
model (15.4 GB in float32) needs no second copy.

Layer ``i`` of ``L`` (``half = L / 2``), ``h`` the residual stream:

    h = h + mixer_i(LN1_i(h));   h = h + W2_i (silu(Wg_i u) * Wu_i u),  u = LN2_i(h)
    logits = LN_f(h) E^T                                (E the embedding)

and `mixer_i` by position, with what is from the published `config.json`
("as published") and what is the family's convention, written from memory
of `configuration_phi4flash.py` / `modeling_phi4flash.py` and stated under
`assumed` in the configuration file ("assumed"):

- LayerNorm with weight and bias, eps `layer_norm_eps`, not RMSNorm.  assumed
  (the eps key is as published)
- gated SiLU feed-forward, no bias (`mlp_bias` false, `hidden_act`).  as published
- tied embedding, no head bias (`tie_word_embeddings`,
  `lm_head_bias` false).                                              as published
- no positional encoding in any layer (the published config has no
  rotary key at all; the state-space layers carry position).          as published
- a state-space layer every `mb_per_layer` = 2 layers.                as published
- i even, i <= half: Mamba-1. `[x, z] = W_in a`; `x = silu(conv1d_causal(x;
  k = d_conv) + b)`; `[dt, B, C] = W_x x`; `dt = softplus(W_dt dt + b_dt)`;
  `A = -exp(A_log)`; per channel c: `s_t = exp(dt_t A_c) s_{t-1} + dt_t B_t
  x_t`, `y_t = C_t . s_t + D_c x_t`; output `W_out (y * silu(z))`.
  d_state 16, d_conv 4, expand 2, dt_rank ceil(hidden / 16), conv bias
  yes, projection biases no.                                          assumed
- layer `half` also hands `y`, BEFORE the gate, on as the memory `m`
  of this token.                                                      assumed
- i odd, i < half: attention inside a window of `sliding_window` keys
  INCLUDING the token itself (`t - window < s <= t`); i = half + 1: full
  causal attention. `[q, k, v] = W_qkv a + b`, `out_proj` with bias.
  (`sliding_window` 512 is as published; which layers it binds, the
  window's exact edge and the biases are assumed.)                    assumed
- differential attention in every attention layer, pairs of ADJACENT
  heads: q pair j (heads 2j, 2j+1) goes with kv pair j // (H / KV)
  (heads 2p, 2p+1): `A1 = softmax(q_2j k_2p^T / sqrt(hd) + mask)`,
  `A2 = softmax(q_2j+1 k_2p+1^T / sqrt(hd) + mask)`, `V = [v_2p |
  v_2p+1]`, `o_j = (1 - l0_i) RMSNorm_2hd((A1 - l_i A2) V)`, `l0_i = 0.8 -
  0.6 exp(-0.3 i)`, `l_i = exp(lq1 . lk1) - exp(lq2 . lk2) + l0_i`; the
  sub-norm has a weight and eps 1e-5. head_dim = hidden / heads.       assumed
- i even, i >= half + 2: gated memory unit `W_out2 (m * silu(W_in2 a))`,
  `m` the memory of THIS token from layer `half`.                     assumed
- i odd, i >= half + 3: cross-attention: `q = W_q a + b` only; keys and
  values are layer `half + 1`'s; the same differential form with this
  layer's own lambdas, sub-norm and `W_o`; causal over all tokens.    assumed
- DEPARTURE (layout only): `A_log` is stored `[d_state, d_inner]`, the
  program's layout (the published module has `[d_inner, d_state]`), and
  the state here is `[d_inner, d_state]`; `Wqkv`'s output is read as
  q heads, then k heads, then v heads, head-major.
- DEPARTURE (precision): the published code runs in the checkpoint's
  dtype with a float32 scan and softmax; here everything is float32.
- LEFT OUT: dropout (`embd_pdrop`, `resid_pdrop` are 0), the cache
  classes, the prefill-time skipping of the cross-decoder (a serving
  optimisation with the same logits: here every layer sees every
  position).

LAYOUT (``params``, P = half / 2): tok_embed [V, d]; final_norm {w, b};
"self" (stacked [P, ...]) and "mid" (no leading axis), a period of
[state-space, attention]: m_norm, m_mlp_norm, a_norm, a_mlp_norm {w, b};
mamba {w_in [d, 2 di], conv_w [dc, di], conv_b [di], w_x [di, R + 2 N],
w_dt [R, di], b_dt [di], a_log [N, di], d [di], w_out [di, d]}; m_mlp,
a_mlp {w_gate, w_up [d, f], w_down [f, d]}; attn {wqkv [d, (H + 2 KV) hd],
bqkv, wo [H hd, d], bo, lq1, lk1, lq2, lk2 [hd], subln [2 hd]}. "cross"
(stacked [P - 1, ...]), a period of [memory unit, cross-attention]:
g_norm, g_mlp_norm, c_norm, c_mlp_norm; gmu {w_in [d, di], w_out [di, d]};
g_mlp, c_mlp; attn {wq [d, H hd], bq, wo, bo, lq1, lk1, lq2, lk2, subln}.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
SUBLN_EPS = 1e-5
HEAD_BLOCK = 16384        # vocabulary rows upcast at a time


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _layernorm(x, p, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


def _ffn(h, p_norm, p, eps):
    u = _layernorm(h, p_norm, eps)
    return h + (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]


def _dims(model: Dict[str, Any]):
    a = model["assumed"]
    d = model["hidden_size"]
    rank = a.get("mamba_dt_rank") or math.ceil(d / 16)
    if model["num_attention_heads"] * a["head_dim"] != d:
        raise ValueError("head_dim is not hidden_size / heads")
    return dict(d=d, H=model["num_attention_heads"],
                KV=model["num_key_value_heads"], hd=a["head_dim"],
                di=a["mamba_expand"] * d, N=a["mamba_d_state"],
                dc=a["mamba_d_conv"], R=rank,
                W=model["sliding_window"], eps=model["layer_norm_eps"],
                L=model["num_hidden_layers"])


def _check(model: Dict[str, Any]) -> None:
    if model.get("mb_per_layer") != 2:
        raise ValueError("the reference has a state-space layer every "
                         "second layer (mb_per_layer 2)")
    if model.get("mlp_bias") or model.get("lm_head_bias"):
        raise ValueError("the reference has no mlp or head bias")
    if not model.get("tie_word_embeddings"):
        raise ValueError("the reference ties the head to the embedding")
    if model["num_hidden_layers"] % 4:
        raise ValueError("the layer pattern needs a multiple of 4 layers")


def _mamba(a, p, D):
    """a [S, d] -> (mixer output [S, d], y [S, di] before the gate).
    One token a step, the state [di, N] float32 from zero."""
    S = a.shape[0]
    di, N, R, dc = D["di"], D["N"], D["R"], D["dc"]
    xz = a @ p["w_in"]
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((dc - 1, di), F32), x], axis=0)
    x = jax.nn.silu(p["conv_b"] + sum(
        padded[k:k + S] * p["conv_w"][k] for k in range(dc)))
    dbc = x @ p["w_x"]
    dt = jax.nn.softplus(dbc[:, :R] @ p["w_dt"] + p["b_dt"])      # [S, di]
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(p["a_log"]).T                                    # [di, N]

    def step(s, inp):
        dt_t, x_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, N), F32), (dt, x, Bm, Cm))
    y = y + p["d"] * x
    return (y * jax.nn.silu(z)) @ p["w_out"], y


def _lambda0(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


def _diff_attention(q, k, v, p, layer, window, D):
    """q [S, H, hd], k, v [S, KV, hd] -> [S, H * hd]: differential
    attention of stack layer ``layer``; ``window`` None is causal over
    everything."""
    S, H, hd = q.shape
    KV = k.shape[1]
    per = H // KV                       # query pairs a kv pair
    t = jnp.arange(S)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask = mask & (t[None, :] > t[:, None] - window)
    l0 = _lambda0(layer)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + l0

    def pair(j):
        kp = j // per
        q1, q2 = q[:, 2 * j], q[:, 2 * j + 1]
        k1, k2 = k[:, 2 * kp], k[:, 2 * kp + 1]
        vv = jnp.concatenate([v[:, 2 * kp], v[:, 2 * kp + 1]], axis=-1)
        a1 = jax.nn.softmax(jnp.where(mask, q1 @ k1.T * hd ** -0.5,
                                      -jnp.inf), axis=-1)
        a2 = jax.nn.softmax(jnp.where(mask, q2 @ k2.T * hd ** -0.5,
                                      -jnp.inf), axis=-1)
        o = (a1 - lam * a2) @ vv                                  # [S, 2hd]
        o = o * jax.lax.rsqrt((o * o).mean(axis=-1, keepdims=True)
                              + SUBLN_EPS) * p["subln"]
        return o * (1.0 - l0)

    o = jax.lax.map(pair, jnp.arange(H // 2))                # [H/2, S, 2hd]
    return jnp.swapaxes(o, 0, 1).reshape(S, H * hd)


def _self_period(h, w, layer, window, D):
    """Stack layers ``layer`` (state-space) and ``layer + 1`` (attention
    with its own keys and values). Returns (h, y of the state-space layer,
    k, v of the attention layer)."""
    w = _f32(w)
    eps, H, KV, hd = D["eps"], D["H"], D["KV"], D["hd"]
    out, y = _mamba(_layernorm(h, w["m_norm"], eps), w["mamba"], D)
    h = _ffn(h + out, w["m_mlp_norm"], w["m_mlp"], eps)
    a = _layernorm(h, w["a_norm"], eps)
    p = w["attn"]
    qkv = a @ p["wqkv"] + p["bqkv"]
    S = a.shape[0]
    q = qkv[:, :H * hd].reshape(S, H, hd)
    k = qkv[:, H * hd:(H + KV) * hd].reshape(S, KV, hd)
    v = qkv[:, (H + KV) * hd:].reshape(S, KV, hd)
    o = _diff_attention(q, k, v, p, layer + 1, window, D)
    h = h + o @ p["wo"] + p["bo"]
    return _ffn(h, w["a_mlp_norm"], w["a_mlp"], eps), y, k, v


def _cross_period(h, w, layer, mem, k, v, D):
    """Stack layers ``layer`` (gated memory unit) and ``layer + 1``
    (cross-attention over the full-attention layer's keys and values)."""
    w = _f32(w)
    eps, H, hd = D["eps"], D["H"], D["hd"]
    a = _layernorm(h, w["g_norm"], eps)
    h = h + (mem * jax.nn.silu(a @ w["gmu"]["w_in"])) @ w["gmu"]["w_out"]
    h = _ffn(h, w["g_mlp_norm"], w["g_mlp"], eps)
    a = _layernorm(h, w["c_norm"], eps)
    p = w["attn"]
    q = (a @ p["wq"] + p["bq"]).reshape(a.shape[0], H, hd)
    o = _diff_attention(q, k, v, p, layer + 1, None, D)
    h = h + o @ p["wo"] + p["bo"]
    return _ffn(h, w["c_mlp_norm"], w["c_mlp"], eps)


def hidden(params, seq, model: Dict[str, Any]):
    """seq [S] -> final-norm hidden states [S, d] float32."""
    _check(model)
    D = _dims(model)
    half = D["L"] // 2
    with jax.default_matmul_precision("highest"):
        h = params["tok_embed"][seq].astype(F32)

        def self_body(h, xs):
            w, i = xs
            h, *_ = _self_period(h, w, 2 * i, D["W"], D)
            return h, None

        h, _ = jax.lax.scan(self_body, h,
                            (params["self"], jnp.arange(half // 2)))
        h, mem, k, v = _self_period(h, params["mid"], half, None, D)

        def cross_body(h, xs):
            w, i = xs
            return _cross_period(h, w, half + 2 + 2 * i, mem, k, v, D), None

        h, _ = jax.lax.scan(cross_body, h,
                            (params["cross"], jnp.arange(half // 2 - 1)))
        return _layernorm(h, _f32(params["final_norm"]), D["eps"])


def logits(params, tokens, model: Dict[str, Any]):
    """tokens [B, S] -> logits [B, S, V] float32 (small vocabularies: the
    whole head at once)."""
    with jax.default_matmul_precision("highest"):
        emb = params["tok_embed"].astype(F32)
        return jnp.stack([hidden(params, seq, model) @ emb.T
                          for seq in tokens])


def below_best(params, seq, model: Dict[str, Any]):
    """For every position t of seq [S] but the last: how far the logit of
    the token that follows, seq[t + 1], sits below the reference's best
    logit at t, given seq[:t + 1] (teacher forced), [S - 1] >= 0. The
    head is applied a block of the vocabulary at a time."""
    h = hidden(params, seq[:-1], model)                      # [S - 1, d]
    emb = params["tok_embed"]
    V = emb.shape[0]
    block = min(HEAD_BLOCK, V)
    with jax.default_matmul_precision("highest"):
        chosen = jnp.einsum("sd,sd->s", h, emb[seq[1:]].astype(F32))

        def one(best, i):
            # the last block is moved back to end at V: rows seen twice
            # change no maximum
            rows = jax.lax.dynamic_slice_in_dim(
                emb, jnp.minimum(i * block, V - block), block)
            return jnp.maximum(best, (h @ rows.astype(F32).T).max(-1)), None

        # from `chosen` up: the two sums round apart by an ulp, and a
        # token that IS the best reads 0, not -1e-8
        best, _ = jax.lax.scan(one, chosen, jnp.arange(-(-V // block)))
    return best - chosen


def margins(params, seq, n_prompt: int, model: Dict[str, Any]):
    """`below_best` of the generated tokens alone: seq = prompt (n_prompt
    tokens) + generated tokens; returns [S - n_prompt]."""
    return below_best(params, seq, model)[n_prompt - 1:]
