"""Plain float32 reference for the language model of dots3-note-prev
(`model_type` dots3_note): latent attention of TWO geometries in one
stack, full layers whose keys a learned indexer SELECTS beside window
layers that attend the last `sliding_window_size` slots with their own
head count, ranks, rotary base and scale; a head-wise output gate and
rescaled low-rank latents in both; one leading dense layer, then expert
layers with an ungrouped sigmoid router and a shared expert.

Independent of ``ray_tpu.models``: straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``, EXPANDED keys and
values (rebuilt for every head from the latent), the indexer's whole score
matrix and the window as masks a block of queries at a time, no cache, no
kernel, no chunking, one layer's float32 weights at a time and inside it
one expert. What it shares with the DeepSeek-V3.2-Exp reference beside it
(norms, the two rotary layouts, the indexer and its selection, the gated
FFN, the head's margin) it imports from there: reference code, not the
program's.

``h`` the residual stream, ``N`` an RMSNorm with a weight (eps 1e-5),
layer ``i`` of kind ``layer_types[i]``:

    h = h + Attn_i(N1_i(h));   h = h + F_i(N2_i(h));   logits = W_head N_f(h)

- Full layer (H 128, nope 128, rope 64, v 128, ranks 1024 / 512, theta
  8e7, plain rotary: `rope_scaling` null): a = N1(h); c_q = s_q N_q(W_qa
  a); q_h = W_qb,h c_q = [q_nope | R(q_rope)]; [c | k_r] = W_kva a, c =
  s_kv N_kv(c), k_rope = R(k_r), one for all heads; k_h = [W_kb,h c |
  k_rope], v_h = W_vb,h c; the indexer chooses S_t, the `index_topk`
  slots s <= t with the largest I(t, s) (all of them under `index_topk`);
  o_h = softmax_{s in S_t}(q_h . k_h * (nope + rope)^-0.5) v_h;
  o_h <- g_h o_h; Attn = W_o [o_0 .. o_H-1].       config.json + `assumed`
- Window layer (H 64, nope 192, rope 64, v 128, `swa_*` ranks 1024 / 1024,
  theta 5e4): the same with its own widths and weights, NO indexer, the
  keys s with 0 <= t - s < sliding_window_size, scale (192 + 64)^-0.5,
  its own gate.                                     config.json + `assumed`
- F_0: W_d (silu(W_g u) * W_u u), 13,824 wide. F_i, i >= 1: sc =
  sigmoid(W_r u); choice sc + b; the num_experts_per_tok largest choices
  over ALL experts; weights sc (without b) at the chosen, divided by their
  sum (+ 1e-20) (`norm_topk_prob`), times routed_scaling_factor 1; plus
  one shared expert.                                         as published
- THE CHIP'S SHARE (`held`), the vocabulary slice, no bias in any
  projection, ties in a top-k: as `deepseek_v32_sparse` says them.

The five `assumed` points of the configuration file, each ONE function
here (and one in ``ray_tpu/models/mla.py``):

(1) `lora_rescale`: `apply_mla_qkv_lora_rescale` multiplies the NORMED
    latents, s_q = sqrt(hidden / q_lora_rank), s_kv = sqrt(hidden /
    kv_lora_rank), as LongCat-Flash's `mla_scale_q_lora` /
    `mla_scale_kv_lora`; the indexer reads the scaled c_q.
(2) `head_gate`: `attention_gate_type` headwise is g = sigmoid(W_g a) in
    R^H from the layer's normed input, on the heads' outputs ahead of W_o.
(3) `window_mask`: `sliding_window_size` 513 counts the query's own slot.
(4) the attention's rotary on interleaved pairs; the indexer's on the
    first `qk_rope_head_dim` of its head, half-split pairs; its key a
    LayerNorm with weight and bias, eps 1e-6; nothing quantised
    (`deepseek_v32_sparse._rope_interleaved`, `indexer_inputs`).
(5) `route`: no `n_group` / `topk_group` in the config: one group, the
    top k over all experts.

LEFT OUT: the vision and audio towers and the multi-token-prediction head
of the published model (the configuration is the language model's).

LAYOUT (``params``): tok_embed [V, d]; final_norm [d]; lm_head [d, V];
``dense``: the leading dense layers, a stack with a leading layer axis;
``moe``: ``{"full": stack, "window": stack}``, the expert layers of each
kind in stack order (the layer's kind is `layer_types[i]`). A full layer
holds `deepseek_v32_sparse`'s attention and indexer names plus
w_attn_gate [d, H]; a window layer {attn_norm, mlp_norm, wq_a, q_norm,
wq_b, wkv_a, kv_norm, wk_b, wv_b, wo, w_attn_gate} in its own widths;
the FFN names are `deepseek_v32_sparse`'s.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v32_sparse import (   # noqa: F401
    _EXPERT_KEYS, _by_query_blocks, _f32, _gated, _rmsnorm,
    _rope_interleaved, head_margin, indexer_inputs, indexer_scores,
    selection)

F32 = jnp.float32


def lora_rescale(model: Dict[str, Any], rank: int) -> float:
    """`assumed` (1)."""
    if not model["apply_mla_qkv_lora_rescale"]:
        return 1.0
    return math.sqrt(model["hidden_size"] / rank)


def head_gate(a, w_gate):
    """`assumed` (2): [T, H]."""
    return jax.nn.sigmoid(a @ w_gate)


def window_mask(t0, n_queries: int, T: int, window: int):
    """`assumed` (3): [queries, T] bool, 0 <= t - s < window."""
    t = (t0 + jnp.arange(n_queries))[:, None]
    s = jnp.arange(T)[None, :]
    return (s <= t) & (t - s < window)


def geometry(model: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """The widths of a kind of layer under the full layer's key names."""
    if kind == "full_attention":
        return dict(model, window=None)
    out = dict(model, window=model["sliding_window_size"])
    for key in ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rope_theta"):
        out[key] = model["swa_" + key]
    return out


def _attention(a, w, geo, forced=None):
    H = geo["num_attention_heads"]
    n, r, v = geo["qk_nope_head_dim"], geo["qk_rope_head_dim"], \
        geo["v_head_dim"]
    rc, eps = geo["kv_lora_rank"], float(geo["rms_norm_eps"])
    T = a.shape[0]
    inv_freq = 1.0 / float(geo["rope_theta"]) ** (
        jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    c_q = _rmsnorm(a @ w["wq_a"], w["q_norm"], eps) \
        * lora_rescale(geo, geo["q_lora_rank"])
    q = (c_q @ w["wq_b"]).reshape(T, H, n + r)
    q_nope, q_rope = q[..., :n], _rope_interleaved(q[..., n:],
                                                   ang[:, None, :])
    kv = a @ w["wkv_a"]
    c = _rmsnorm(kv[:, :rc], w["kv_norm"], eps) * lora_rescale(geo, rc)
    k_rope = _rope_interleaved(kv[:, rc:], ang)                # [T, r]
    k_nope = (c @ w["wk_b"]).reshape(T, H, n)                  # expanded
    val = (c @ w["wv_b"]).reshape(T, H, v)
    scale = (n + r) ** -0.5
    if geo["window"] is None:
        qi, ki, wt = indexer_inputs(a, c_q, w, geo, ang)
    else:       # no indexer: what the blocks carry in its place
        qi = wt = jnp.zeros((T, 1), F32)

    given = forced if forced is not None else jnp.zeros((T, 1), bool)

    def block(t0, q_nope, q_rope, qi, wt, given):
        if forced is not None:      # the caller's selection, not its own
            mask = given
        elif geo["window"] is None:
            mask = selection(indexer_scores(t0, qi, ki, wt),
                             geo["index_topk"])
        else:
            mask = window_mask(t0, q_nope.shape[0], T, geo["window"])
        scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
                  + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, val), mask

    o, mask = _by_query_blocks(block, T, q_nope, q_rope, qi, wt, given)
    o = o * head_gate(a, w["w_attn_gate"])[:, :, None]
    return o.reshape(T, H * v) @ w["wo"], mask


def route(u, w, model):
    """`assumed` (5): (weights [T, k], expert ids [T, k]) over ALL routed
    experts, one group."""
    sc = jax.nn.sigmoid(u @ w["w_router"])                     # [T, E]
    idx = jax.lax.top_k(sc + w["router_bias"],
                        model["num_experts_per_tok"])[1]
    wts = jnp.take_along_axis(sc, idx, axis=1)
    if model["norm_topk_prob"]:
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    return wts * float(model["routed_scaling_factor"]), idx


def expert_layer(u, w, model, held: Optional[Tuple[int, int]] = None,
                 shared: bool = True):
    """F(u) [T, d] of an expert layer, or this share's part of it: the
    stacks ``we_*`` hold experts ``held[0] .. held[1] - 1``."""
    wts, idx = route(u, w, model)
    lo, hi = held if held is not None else (0, model["n_routed_experts"])

    def one(out, xs):       # one expert's float32 weights at a time
        e, gate, up, down = xs
        we = jnp.where(idx == e, wts, 0.0).sum(-1)             # [T]
        return out + we[:, None] * _gated(
            u, gate.astype(F32), up.astype(F32), down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(lo, hi), w["we_gate"], w["we_up"], w["we_down"]))
    if shared:
        out = out + _gated(u, w["ws_gate"].astype(F32),
                           w["ws_up"].astype(F32), w["ws_down"].astype(F32))
    return out


def layers_of(params, model: Dict[str, Any]):
    """[(kind, is an expert layer, the layer's weights)] in stack order,
    read from LAYOUT by `layer_types` and `first_k_dense_replace`."""
    at = {"full_attention": 0, "sliding_attention": 0}
    names = {"full_attention": "full", "sliding_attention": "window"}
    out = []
    for i, kind in enumerate(model["layer_types"]):
        if i < model["first_k_dense_replace"]:
            stack, j = params["dense"], i
        else:
            stack, j = params["moe"][names[kind]], at[kind]
            at[kind] += 1
        out.append((kind, i >= model["first_k_dense_replace"],
                    jax.tree_util.tree_map(lambda x: x[j], stack)))
    return out


def hidden(params, seq, model: Dict[str, Any],
           held: Optional[Tuple[int, int]] = None, want_selection=False,
           selection_given=None):
    """seq [T] -> final-normed hidden states [T, d] (and, asked, the FULL
    layers' selection masks [full layers, T, T]). One jitted program a
    kind of layer, called layer after layer: a layer's float32 weights are
    alive while it runs and no longer. ``selection_given`` [full layers,
    T, T] bool: the full layers attend THESE slots (a program's own
    choice) in place of their indexers' top-k; everything else is as
    published. What tells a fault in the attention from the few slots a
    bf16 indexer ranks otherwise than this float32 one."""
    eps = float(model["rms_norm_eps"])

    def layer(h, w, forced, kind: str, moe: bool):
        with jax.default_matmul_precision("highest"):
            small = _f32({k: v for k, v in w.items()
                          if k not in _EXPERT_KEYS})
            a = _rmsnorm(h, small["attn_norm"], eps)
            o, mask = _attention(a, small, geometry(model, kind), forced)
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

    steps = {}
    masks = []
    given = iter(() if selection_given is None else selection_given)
    h = params["tok_embed"][seq].astype(F32)
    for kind, moe, w in layers_of(params, model):
        if (kind, moe) not in steps:
            steps[kind, moe] = jax.jit(
                lambda h, w, forced, kind=kind, moe=moe:
                layer(h, w, forced, kind, moe))
        forced = next(given, None) if kind == "full_attention" else None
        h, mask = steps[kind, moe](h, w, forced)
        if want_selection and kind == "full_attention":
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
