"""Plain float32 reference for DeepSeek-V3.2-Exp's decoder: latent
attention (MLA) whose keys are SELECTED per query by a learned indexer,
leading dense layers, then expert layers with a grouped sigmoid router and
a shared expert.

Independent of ``ray_tpu.models``: its own norms, rotary tables, indexer,
attention, router and expert sum, in straightforward ``jax.numpy``. No
cache, no kernel, no batching, no chunking, no bf16: activations are
float32 and every matmul runs under
``jax.default_matmul_precision("highest")``. The attention is in the
EXPANDED form (keys and values of every head rebuilt from the latent), the
indexer's scores are one full ``[T, T]`` matrix and the selection is a mask
made from ``top_k``. It reads the program's parameter tree (LAYOUT) and
upcasts one layer, and inside it one expert, at a time.

``h`` the residual stream, ``N`` an RMSNorm with a weight, layer ``i``:

    h = h + Attn_i(N1_i(h));   h = h + F_i(N2_i(h));   logits = W_head N_f(h)

each line below marked "as published" (config.json and the published
inference code of DeepSeek-V3 / V3.2-Exp; the parts V3 shares were checked
against transformers' `modeling_deepseek_v3.py`) or `assumed` (written from
memory, no network):

- RMSNorm: x * rsqrt(mean(x^2) + eps) * weight, float32.          as published
- Latent attention. a = N1(h). c_q = N_q(W_qa a) (q_lora_rank).
  q = W_qb c_q, heads of [q_nope | q_rope]. [c_kv | k_r] = W_kva a;
  c = N_kv(c_kv); k_rope = R(k_r), ONE for all heads; q_rope =
  R(q_rope). What a token leaves behind is [c | k_rope].
  k_nope_h = W_kb^h c, v_h = W_vb^h c; score_h(t, s) = (q_nope_h . k_nope_h +
  q_rope_h . k_rope) * scale; softmax over the allowed s; output
  W_o [o_0 .. o_H-1].                                              as published
- scale = (qk_nope + qk_rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim *
  ln(factor) + 1 (yarn), as `deepseek_v3` computes it.             as published
- R: rotary with yarn-blended inverse frequencies over
  qk_rope_head_dim: inv_freq = interpolation * (1 - g) +
  extrapolation * g, g = 1 - clip((i - low) / (high - low), 0, 1), low
  and high the (floored / ceiled) correction dimensions of beta_fast
  and beta_slow rotations at original_max_position_embeddings; the
  cos/sin factor mscale / mscale_all_dim = 1.                      as published
- R's layout in the attention: on INTERLEAVED pairs (2i, 2i + 1)
  (`rope_interleave` true as in `deepseek_v3`).                    assumed
- Indexer (its own in every layer). q^I = W_iq c_q, index_n_heads
  heads of index_head_dim; k^I = LN(W_ik a), a LayerNorm with weight
  and bias, eps 1e-6; w = W_iw a * index_n_heads^-0.5 *
  index_head_dim^-0.5; I(t, s) = sum_j w(t, j) * relu(q^I(t, j) .
  k^I(s)) for s <= t; S_t = the index_topk largest I(t, s) over
  s <= t, all of them where t < index_topk. Attention's mask is
  causal AND s in S_t.                                             as published
- The indexer's rotary: on the FIRST qk_rope_head_dim of the
  index_head_dim, half-split pairs (i, i + rope/2), not interleaved. assumed
- DEPARTURE (precision of the indexer): published inference rotates q^I
  and k^I by a Hadamard matrix and quantises them to FP8 with a scale.
  The rotation is orthogonal and changes no score; here nothing is
  quantised (float32; the program: bf16, float32 accumulation).     assumed
- Ties in a top-k: the lower index wins (`jax.lax.top_k`).
- F_i, i < first_k_dense_replace: W_d (silu(W_g u) * W_u u).       as published
- F_i otherwise: sc = sigmoid(W_r u) float32. Choice scores sc + b (b
  the selection bias, a parameter). n_group groups of consecutive
  experts; a group's score = the sum of its two largest choice
  scores; keep the topk_group best groups, the others' choice scores
  become 0 (as `modeling_deepseek_v3.py` masks them); among the
  experts the num_experts_per_tok largest choice scores. Weights =
  sc (WITHOUT b) at the chosen, divided by their sum (+ 1e-20) when
  norm_topk_prob, times routed_scaling_factor. F(u) = sum_e w_e
  E_e(u) + E_shared(u), every E a gated silu FFN
  moe_intermediate_size wide (the shared one n_shared_experts times
  that).                                                           as published
- THE CHIP'S SHARE (`held`): the router, its groups and its k a token are
  over ALL n_routed_experts; of the chosen experts only those in
  ``held = (lo, hi)`` are computed and summed (the parameter stacks then
  hold experts lo..hi-1 alone), the shared expert is added once unless
  ``shared`` is False, and that partial result goes on to the next layer.
  ``held`` None: the whole layer. The vocabulary's slice is whatever rows
  `tok_embed` / `lm_head` hold.
- LEFT OUT: the multi-token-prediction module (num_nextn_predict_layers):
  an extra layer that drafts; the model serves without it.
- No bias in any projection (`attention_bias` false).             as published

LAYOUT (``params``): tok_embed [V, d]; final_norm [d]; lm_head [d, V];
``dense`` and ``moe``: the leading dense layers and the expert layers,
each a stack with a leading layer axis of {attn_norm, mlp_norm [d];
wq_a [d, rq]; q_norm [rq]; wq_b [rq, H * (n + r)]; wkv_a [d, rc + r];
kv_norm [rc]; wk_b [rc, H * n]; wv_b [rc, H * v] (the published
kv_b_proj, split into its key and value halves: layout only);
wo [H * v, d]; wi_q [rq, IH * ID];
wi_k [d, ID]; ik_norm_w, ik_norm_b [ID]; wi_w [d, IH]} and, dense,
{w_gate, w_up [d, f]; w_down [f, d]}, or, moe, {w_router [d, E];
router_bias [E]; we_gate, we_up [Eh, d, fe]; we_down [Eh, fe, d];
ws_gate, ws_up [d, fs]; ws_down [fs, d]}.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8192        # vocabulary rows a step of `below_best`


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), tree)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layernorm(x, w, b, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model: Dict[str, Any]) -> jnp.ndarray:
    """[qk_rope_head_dim / 2] inverse frequencies, yarn-blended."""
    dim = model["qk_rope_head_dim"]
    base = float(model["rope_theta"])
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    rs = model.get("rope_scaling")
    if not rs:
        return 1.0 / pos_freqs
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    g = 1 - ramp
    return (1.0 / (factor * pos_freqs)) * (1 - g) + (1.0 / pos_freqs) * g


def softmax_scale(model: Dict[str, Any]) -> float:
    s = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        s = s * m * m
    return s


def _rope_interleaved(x, ang):
    """x [..., r] rotated on pairs (2i, 2i + 1); ang [T, r / 2] broadcast
    against x's leading axes by the caller."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1) \
        .reshape(x.shape)


def _rope_halves(x, ang):
    """x [..., r] rotated on pairs (i, i + r / 2)."""
    h = x.shape[-1] // 2
    x0, x1 = x[..., :h], x[..., h:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


Q_BLOCK = 256           # queries a step of the attention at long contexts


def _by_query_blocks(fn, T: int, *per_query):
    """``fn(t0, *blocks)`` over blocks of `Q_BLOCK` queries (one call when
    T fits a block), results concatenated on the query axis: nothing
    [T, heads, T] is ever whole in memory."""
    if T <= Q_BLOCK:
        return fn(0, *per_query)
    n = -(-T // Q_BLOCK)
    pad = n * Q_BLOCK - T
    blocks = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
              .reshape(n, Q_BLOCK, *x.shape[1:]) for x in per_query]
    out = jax.lax.map(lambda xs: fn(xs[0], *xs[1:]),
                      (jnp.arange(n) * Q_BLOCK, *blocks))
    return jax.tree_util.tree_map(
        lambda y: y.reshape(n * Q_BLOCK, *y.shape[2:])[:T], out)


def indexer_inputs(a, c_q, w, model, ang):
    """(q^I [T, IH, ID], k^I [T, ID], w [T, IH]), rotated and scaled."""
    IH, ID, r = (model["index_n_heads"], model["index_head_dim"],
                 model["qk_rope_head_dim"])
    T = a.shape[0]
    q = (c_q @ w["wi_q"]).reshape(T, IH, ID)
    k = _layernorm(a @ w["wi_k"], w["ik_norm_w"], w["ik_norm_b"])
    q = jnp.concatenate([_rope_halves(q[..., :r], ang[:, None, :]),
                         q[..., r:]], axis=-1)
    k = jnp.concatenate([_rope_halves(k[..., :r], ang), k[..., r:]],
                        axis=-1)
    wt = (a @ w["wi_w"]) * IH ** -0.5 * ID ** -0.5
    return q, k, wt


def indexer_scores(t0, q, k, wt):
    """I [queries, T] float32 for the queries t0 .. t0 + len(q) - 1 (-inf
    where s > t)."""
    dots = jax.nn.relu(jnp.einsum("tjd,sd->tjs", q, k))
    scores = jnp.einsum("tj,tjs->ts", wt, dots)
    t = t0 + jnp.arange(q.shape[0])
    causal = jnp.arange(k.shape[0])[None, :] <= t[:, None]
    return jnp.where(causal, scores, -jnp.inf)


def selection(scores, topk: int):
    """[queries, T] bool: s in S_t, from `top_k` (never more than `topk`
    true a row, never one above the diagonal)."""
    Q, T = scores.shape
    vals, idx = jax.lax.top_k(scores, min(topk, T))
    return jnp.zeros((Q, T), bool).at[jnp.arange(Q)[:, None], idx].set(
        vals > -jnp.inf)


def _attention(a, w, model, ang, attend_all: bool = False):
    H = model["num_attention_heads"]
    n, r, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    rc, eps = model["kv_lora_rank"], float(model["rms_norm_eps"])
    T = a.shape[0]
    c_q = _rmsnorm(a @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(T, H, n + r)
    q_nope, q_rope = q[..., :n], _rope_interleaved(q[..., n:],
                                                   ang[:, None, :])
    kv = a @ w["wkv_a"]
    c = _rmsnorm(kv[:, :rc], w["kv_norm"], eps)
    k_rope = _rope_interleaved(kv[:, rc:], ang)                # [T, r]
    k_nope = (c @ w["wk_b"]).reshape(T, H, n)                  # expanded
    val = (c @ w["wv_b"]).reshape(T, H, v)
    qi, ki, wt = indexer_inputs(a, c_q, w, model, ang)
    scale = softmax_scale(model)

    def block(t0, q_nope, q_rope, qi, wt):
        mask = selection(indexer_scores(t0, qi, ki, wt),
                         T if attend_all else model["index_topk"])
        scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
                  + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, val).reshape(-1, H * v), mask

    o, mask = _by_query_blocks(block, T, q_nope, q_rope, qi, wt)
    return o @ w["wo"], mask


def route(u, w, model):
    """(weights [T, k], expert ids [T, k]) over ALL routed experts."""
    E, k = model["n_routed_experts"], model["num_experts_per_tok"]
    G, KG = model["n_group"], model["topk_group"]
    sc = jax.nn.sigmoid(u @ w["w_router"])                     # [T, E]
    choice = sc + w["router_bias"]
    T = u.shape[0]
    grp = choice.reshape(T, G, E // G)
    gscore = jax.lax.top_k(grp, 2)[0].sum(-1)                  # [T, G]
    keep = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], jax.lax.top_k(gscore, KG)[1]].set(True)
    choice = jnp.where(jnp.repeat(keep, E // G, axis=1), choice, 0.0)
    idx = jax.lax.top_k(choice, k)[1]
    wts = jnp.take_along_axis(sc, idx, axis=1)
    if model["norm_topk_prob"]:
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    return wts * float(model["routed_scaling_factor"]), idx


def _gated(u, g, up, down):
    return (jax.nn.silu(u @ g) * (u @ up)) @ down


def expert_layer(u, w, model, held: Optional[Tuple[int, int]] = None,
                 shared: bool = True):
    """F(u) [T, d] of an expert layer, or this share's part of it: the
    stacks ``we_*`` hold experts ``held[0] .. held[1] - 1``."""
    wts, idx = route(u, w, model)
    lo, hi = held if held is not None else (0, model["n_routed_experts"])
    out = jnp.zeros_like(u)
    for e in range(lo, hi):
        we = jnp.where(idx == e, wts, 0.0).sum(-1)             # [T]
        out = out + we[:, None] * _gated(
            u, w["we_gate"][e - lo].astype(F32),
            w["we_up"][e - lo].astype(F32),
            w["we_down"][e - lo].astype(F32))
    if shared:
        out = out + _gated(u, w["ws_gate"].astype(F32),
                           w["ws_up"].astype(F32), w["ws_down"].astype(F32))
    return out


_EXPERT_KEYS = ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down")


def hidden(params, seq, model: Dict[str, Any],
           held: Optional[Tuple[int, int]] = None, want_selection=False,
           attend_all: bool = False):
    """seq [T] -> final-normed hidden states [T, d] (and, asked, the
    selection masks [layers, T, T]). One jitted program a KIND of layer,
    called layer after layer: a layer's float32 weights are alive while it
    runs and no longer, so 4.6 B parameters need not fit twice."""
    eps = float(model["rms_norm_eps"])
    T = seq.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * yarn_inv_freq(model)[None, :]

    def layer(h, w, moe: bool):
        with jax.default_matmul_precision("highest"):
            small = _f32({k: v for k, v in w.items()
                          if k not in _EXPERT_KEYS})
            a = _rmsnorm(h, small["attn_norm"], eps)
            o, mask = _attention(a, small, model, ang, attend_all)
            h = h + o
            u = _rmsnorm(h, small["mlp_norm"], eps)
            if moe:
                f = expert_layer(u, {**small, **{k: w[k] for k in
                                                 _EXPERT_KEYS}},
                                 model, held)
            else:
                f = _gated(u, small["w_gate"], small["w_up"],
                           small["w_down"])
            return h + f, mask

    steps = {"dense": jax.jit(lambda h, w: layer(h, w, False)),
             "moe": jax.jit(lambda h, w: layer(h, w, True))}
    masks = []
    h = params["tok_embed"][seq].astype(F32)
    for name in ("dense", "moe"):
        stack = params.get(name)
        if stack is None:
            continue
        for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
            h, mask = steps[name](
                h, jax.tree_util.tree_map(lambda x: x[i], stack))
            if want_selection:
                masks.append(mask)
    h = _rmsnorm(h, params["final_norm"].astype(F32), eps)
    if want_selection:
        return h, jnp.stack(masks)
    return h


def logits(params, tokens, model: Dict[str, Any],
           held: Optional[Tuple[int, int]] = None):
    """tokens [B, S] -> logits [B, S, V] float32 (small vocabularies)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32)
        return jnp.stack([hidden(params, seq, model, held) @ head
                          for seq in tokens])


def below_best(params, seq, model: Dict[str, Any],
               held: Optional[Tuple[int, int]] = None):
    """For every position t of seq [S] but the last: how far the logit of
    the token that follows sits below the reference's best logit at t,
    given seq[:t + 1] (teacher forced), [S - 1] >= 0. The head is applied
    a block of the vocabulary at a time."""
    h = hidden(params, seq[:-1], model, held)
    return head_margin(params, h, seq[1:])


def head_margin(params, h, nxt):
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
