"""Gated-delta-rule decoder LM: linear-attention layers whose recurrent
state is a MATRIX a head, beside gated softmax attention, an expert layer
in every layer (the Qwen3-Next shape).

Like `hybrid` and `mla`, no layer here is the Llama layer, so nothing goes
through `generate._layer_body`; what the family shares with the others is
everything around the layers: the serving engine's programs
(`engine._prefill_rows_paged`, `engine._decode_multi_paged`), its block
pool and table, the paged kernel, `moe.moe_ffn_dropless` with its held
range, sampling, the ring. The engine hands `layers_paged` what
`engine._layers_paged` gets, plus the recurrent state the config declares
(`state_planes`), and takes back hidden states.

The stack, read from the config alone (`GdnConfig.layer_plan`): ONE
segment of ``n_layers / full_attention_interval`` periods of

    [delta, delta, delta, gated attention]

(layer ``i`` is full attention where ``(i + 1) % interval == 0``), every
layer ``h = h + Mixer(N(h)); h = h + MoE(N(h))`` with ``N(x) = x / rms(x)
* (1 + w)``, the zero-centred RMSNorm. The period is one `lax.scan` body,
its delta layers a scan inside it: 48 layers trace one delta layer, one
attention layer and the expert layer twice.

Gated DeltaNet (`delta_mixer`). ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x
W_ba``; ``[q, k, v]`` go through a causal depthwise conv (width
`conv_kernel`, no bias) and SiLU; ``q`` and ``k`` are L2-normalised a
head, ``q`` scaled by ``dk ** -0.5``, a key head serving ``value_heads /
key_heads`` consecutive value heads; ``beta = sigmoid(b)``, ``g = -exp(A_log)
* softplus(a + dt_bias)`` in float32 a value head. The recurrence is
`ops.gated_delta`'s: its chunkwise form over a prefill chunk, its one-token
update for a decode token (on the chip one kernel pass in place on the
state plane: `state_step_kernel`). Output ``W_o (RMSNorm_head(o) * w *
silu(z))``. A row keeps, a delta layer, the state ``[value_heads, dk,
dv]`` float32 and the conv's last ``conv_kernel - 1`` inputs.

Gated attention (`_attention`). ``q_proj`` gives a head's query AND its
output gate; q and k get a zero-centred RMSNorm a head, rotary on the
first ``partial_rotary_factor`` of a head's dims (half-split pairs), K/V go
behind the row's ordinary table and are read by the paged kernel, and the
heads' output is scaled by ``sigmoid(gate)`` before ``W_o``.

Expert layer: `moe.moe_ffn_dropless` with the softmax router over all
`n_experts`, top-k renormalised, the `held_experts` range, and a shared
expert whose output is scaled by ``sigmoid(x . w_sgate)``.

The published checkpoint also carries a multi-token-prediction module;
none is built here (the model serves without it).
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.block_pool import CachePlane, StatePlane, kv_planes
from ray_tpu.models.hybrid import (STATE_REFUSALS, LayerKind, Segment,
                                   _starts_fresh)
from ray_tpu.models.moe import EXPERT_STACKS, moe_ffn_dropless
from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.gated_delta import (delta_chunks, delta_step,
                                     delta_step_heads, delta_step_plane,
                                     l2norm, live_rows)

Params = Dict[str, Any]

DELTA = LayerKind("delta", state="delta")
GATED_ATTN = LayerKind("gated_attn", writes="full", reads="full")


@dataclasses.dataclass(frozen=True)
class GdnConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    key_heads: int = 16                # linear_num_key_heads
    value_heads: int = 32              # linear_num_value_heads
    key_head_dim: int = 128            # linear_key_head_dim
    value_head_dim: int = 128          # linear_value_head_dim
    conv_kernel: int = 4               # linear_conv_kernel_dim
    n_experts: int = 512               # routed, over the whole deployment
    top_k: int = 10
    expert_dim: int = 512              # moe_intermediate_size
    shared_expert_dim: int = 512       # shared_expert_intermediate_size
    norm_topk_prob: bool = True
    # (lo, hi): the routed experts THIS program holds and computes; the
    # router is over all `n_experts` whatever this says. None: all.
    held_experts: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    router = "softmax"                 # `moe.moe_ffn_dropless` reads these
    shared_expert_gate = True

    def __post_init__(self):
        if self.full_attention_interval < 2 \
                or self.n_layers % self.full_attention_interval:
            raise ValueError("GdnConfig: n_layers must be whole periods of "
                             "full_attention_interval >= 2 layers")
        if self.n_heads % self.n_kv_heads \
                or self.value_heads % self.key_heads:
            raise ValueError("GdnConfig: KV heads must divide the query "
                             "heads, key heads the value heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("GdnConfig: partial_rotary_factor must leave "
                             "an even, non-empty rotary width")
        if self.held_experts is not None:
            lo, hi = self.held_experts
            if not 0 <= lo < hi <= self.n_experts:
                raise ValueError("GdnConfig: held_experts (lo, hi) must be "
                                 "a non-empty range of the routed experts")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.full_attention_interval

    @property
    def delta_per_period(self) -> int:
        return self.full_attention_interval - 1

    @property
    def n_delta_layers(self) -> int:
        return self.n_periods * self.delta_per_period

    @property
    def n_attn_layers(self) -> int:
        return self.n_periods

    @property
    def full_cache_readers(self) -> int:
        """Layers that read the K/V pool: every attention layer its own."""
        return self.n_attn_layers

    @property
    def key_dim(self) -> int:
        return self.key_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.value_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def n_shared_experts(self) -> int:
        return 1 if self.shared_expert_dim else 0

    @property
    def n_held(self) -> int:
        lo, hi = self.held_experts or (0, self.n_experts)
        return hi - lo

    def layer_plan(self) -> Tuple[Segment, ...]:
        """The stack as segments of periods, in `hybrid.Segment`'s terms:
        the ONE description the scans, the pool, the state and the
        counters are built from."""
        return (Segment("period", (DELTA,) * self.delta_per_period
                        + (GATED_ATTN,), self.n_periods, 0),)

    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        return tuple(k for seg in self.layer_plan()
                     for _ in range(seg.periods) for k in seg.kinds)

    def cache_planes(self) -> Tuple[CachePlane, ...]:
        """What a token stores: K and V of the attention layers alone,
        behind the row's table."""
        return kv_planes("full", self.n_attn_layers, self.n_kv_heads,
                         self.head_dim, jnp.dtype(self.dtype))

    def state_planes(self) -> Tuple[StatePlane, ...]:
        """What a ROW keeps whatever its length: a delta layer's matrix
        state a value head, float32, and its conv's last inputs."""
        return (StatePlane("delta", self.n_delta_layers,
                           (self.value_heads, self.key_head_dim,
                            self.value_head_dim), jnp.dtype(jnp.float32)),
                StatePlane("conv", self.n_delta_layers,
                           (self.conv_kernel - 1, self.conv_dim),
                           jnp.dtype(self.dtype)))

    def prefill_layers(self) -> int:
        return self.n_layers

    def refusals(self) -> Dict[str, str]:
        """What would need the recurrent state moved, shared, rolled back
        or split (`block_pool.ServedConfig`)."""
        no, why = "a GdnConfig cannot be served with ", STATE_REFUSALS
        return {
            "prefix_cache": no + why["prefix_cache"] + " (ROADMAP M4)",
            "preempt_swap": no + why["preempt_swap"]
            + " (pass preempt='recompute'; ROADMAP M4)",
            "draft": no + why["draft"] + "; the model's own drafting head "
            "is not built (ROADMAP M7)",
            "kv_quant": no + "kv_quant=: the quantized write's scales are "
            "sized from the dense family's layers, and the recurrent state "
            "has no quantized form",
            "lora": no + why["lora"],
            "tp": no + "tp=/mesh=: the delta-rule weights, the recurrent "
            "state and the held experts have no sharding rule and no "
            "exchange (ROADMAP M2)",
            "handoff": no + why["handoff"] + " (ROADMAP M4)"}

    def stack(self):
        return sys.modules[__name__]

    def num_params(self) -> int:
        """Parameters HELD here (held experts, this vocabulary)."""
        d, f, fs = self.dim, self.expert_dim, self.shared_expert_dim
        H, KV, hd = self.n_heads, self.n_kv_heads, self.head_dim
        delta = d + d * (self.conv_dim + self.value_dim) \
            + d * 2 * self.value_heads + self.conv_kernel * self.conv_dim \
            + 2 * self.value_heads + self.value_head_dim + self.value_dim * d
        attn = d + d * (2 * H + 2 * KV) * hd + 2 * hd + H * hd * d
        moe = d + d * self.n_experts + self.n_held * 3 * d * f \
            + 3 * d * fs + d
        return (2 * self.vocab_size * d + d + self.n_layers * moe
                + self.n_delta_layers * delta + self.n_attn_layers * attn)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# What `gdn_init` draws otherwise than the published start (its docstring):
# the tokens over which a delta head's state decays to 1/e, log-uniform
# between these, and the mean of the q and k norms' zero-centred weights.
_HEAD_MEMORY = (16.0, 16384.0)
_ATTN_NORM_MEAN = 0.6


def _decay_gates(key_a, key_tau, shape) -> Params:
    """``a_log`` as published (``log(u)``, ``u`` uniform in (0, 16)) and
    the `dt_bias` under which ``exp(a_log) * softplus(dt_bias) = 1 / tau``,
    ``tau`` log-uniform over `_HEAD_MEMORY`; both float32."""
    a = jax.random.uniform(key_a, shape, jnp.float32, 1e-3, 16.0)
    lo, hi = (jnp.log(t) for t in _HEAD_MEMORY)
    tau = jnp.exp(jax.random.uniform(key_tau, shape, jnp.float32, lo, hi))
    # softplus^-1(x) = log(expm1(x)); x is at most 1 / (1e-3 * 16)
    return {"a_log": jnp.log(a),
            "dt_bias": jnp.log(jnp.expm1(1.0 / (a * tau)))}


def gdn_init(key: jax.Array, cfg: GdnConfig) -> Params:
    """Random weights that leave every mechanism of the stack something
    to do, as a trained checkpoint's do. Matrices normal with std ``fan_in
    ** -0.5``, the embedding std 0.02; the zero-centred norm weights
    normal std 0.1 (a checkpoint's start at 0; not zero here, so that a
    program that reads ``w`` for ``1 + w`` moves logits), the head norm of
    a delta layer 1; the conv uniform in ``+- kernel ** -0.5``; the shared
    expert's gate vector std ``dim ** -0.5``.

    Two draws are NOT the published start, which is a start for training
    and under which most of the stack is idle (`_HEAD_MEMORY`,
    `_ATTN_NORM_MEAN`): ``A_log = log(u)``, ``u`` uniform in (0, 16) as
    published, but `dt_bias` such that a head's state decays by ``1 / tau``
    a token at ``a = 0``, ``tau`` log-uniform over `_HEAD_MEMORY` tokens
    (published: 1, under which nine heads in ten forget inside three
    tokens, and a state lost, stale or not handed on changes nothing a
    token later); and the q and k norms' weights around `_ATTN_NORM_MEAN`,
    so that a query's scores over its keys have a spread near 2.5 and it
    attends a dozen of 8,000 keys (at 0 the scores' spread is 1, attention
    is a mean over thousands of random values, next to nothing, and a
    wrong gate or rotary moves no logit).

    The expert stacks hold the `held_experts` alone. Jit it with `cfg`
    static to build a real-size model on the device in one program."""
    d, f, fs = cfg.dim, cfg.expert_dim, cfg.shared_expert_dim
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hv, dc = cfg.value_heads, cfg.conv_kernel
    pdt = cfg.param_dtype
    keys = iter(jax.random.split(key, 64))
    P, nd = cfg.n_periods, cfg.delta_per_period

    def mat(lead, n_in, n_out, std=None):
        std = n_in ** -0.5 if std is None else std
        return (jax.random.normal(next(keys), (*lead, n_in, n_out),
                                  jnp.float32) * std).astype(pdt)

    def vec(lead, width, std):
        return (jax.random.normal(next(keys), (*lead, width), jnp.float32)
                * std).astype(pdt)

    def delta(lead):
        return {
            "norm": vec(lead, d, 0.1),
            "w_qkvz": mat(lead, d, cfg.conv_dim + cfg.value_dim),
            "w_ba": mat(lead, d, 2 * Hv),
            "conv_w": jax.random.uniform(
                next(keys), (*lead, dc, cfg.conv_dim), jnp.float32,
                -dc ** -0.5, dc ** -0.5).astype(pdt),
            # float32 whatever the weights are: the recurrence is
            **_decay_gates(next(keys), next(keys), (*lead, Hv)),
            "o_norm": jnp.ones((*lead, cfg.value_head_dim), pdt),
            "w_out": mat(lead, cfg.value_dim, d),
        }

    def attn(lead):
        return {
            "norm": vec(lead, d, 0.1),
            "wq": mat(lead, d, H * 2 * hd),
            "wk": mat(lead, d, KV * hd), "wv": mat(lead, d, KV * hd),
            "q_norm": _ATTN_NORM_MEAN + vec(lead, hd, 0.1),
            "k_norm": _ATTN_NORM_MEAN + vec(lead, hd, 0.1),
            "wo": mat(lead, H * hd, d),
        }

    def moe(lead):
        eh = cfg.n_held
        out = {"norm": vec(lead, d, 0.1),
               "w_router": mat(lead, d, cfg.n_experts),
               "we_gate": mat((*lead, eh), d, f),
               "we_up": mat((*lead, eh), d, f),
               "we_down": mat((*lead, eh), f, d)}
        if fs:
            out.update(ws_gate=mat(lead, d, fs), ws_up=mat(lead, d, fs),
                       ws_down=mat(lead, fs, d),
                       w_sgate=vec(lead, d, d ** -0.5))
        return out

    return {
        "tok_embed": mat((), cfg.vocab_size, d, std=0.02),
        "period": {"delta": delta((P, nd)), "attn": attn((P,)),
                   "moe": moe((P, nd + 1))},
        "final_norm": vec((), d, 0.1),
        "lm_head": mat((), d, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# Layer math
# ---------------------------------------------------------------------------

def _rmsnorm1p(x, w, eps: float):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``, float32."""
    with jax.named_scope(sn.NORM):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _log_decay(a, p):
    """``g = -exp(A_log) * softplus(a + dt_bias)`` [B, S, Hv] float32:
    what a head's state decays by at a token, in logs."""
    return -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))


def _write_strength(b):
    """``beta = sigmoid(b)`` [B, S, Hv] float32."""
    return jax.nn.sigmoid(b.astype(jnp.float32))


def _unit_keys(q, k, scale: float):
    """q and k L2-normalised a head, q scaled."""
    return l2norm(q) * scale, l2norm(k)


def _gate_heads(o, gate):
    """The attention heads' output under its gate: ``o * sigmoid(gate)``."""
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def _rotary_dims(cfg: GdnConfig) -> int:
    """Dims of a head that rotate (the first of it)."""
    return cfg.rotary_dim


def _rope(x, positions, cfg: GdnConfig):
    """Rotary on the first `_rotary_dims` of each head, half-split pairs
    ``(i, i + r/2)``; the rest of the head passes. x [B, S, H, D]."""
    r = _rotary_dims(cfg)
    half = r // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / r)
    ang = positions.astype(jnp.float32)[..., None] * inv       # [B, S, half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2, rest = x32[..., :half], x32[..., half:r], x32[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(x.dtype)


def state_step_kernel(cfg: GdnConfig) -> bool:
    """Whether a decode token's one-token update goes through the kernel
    that works in place on the state plane (`ops.gated_delta.
    delta_step_plane`): on the chip, at head shapes that are whole float32
    tiles and a block of heads that fits the default scoped VMEM. Anywhere
    else `delta_step` on the layer's slice, the plain form. Said from the
    platform and the config: the engine counts such decode blocks."""
    return jax.default_backend() == "tpu" and delta_step_heads(
        cfg.value_heads, cfg.key_head_dim, cfg.value_head_dim) is not None


def delta_mixer(a, p, s0, conv0, live, cfg: GdnConfig, *, in_plane=None):
    """A Gated DeltaNet mixer over a chunk. ``a`` [B, S, d] the normed
    input; ``s0`` [B, Hv, dk, dv] float32 and ``conv0`` [B, dc-1, conv_dim]
    the state the chunk starts from; ``live`` [B, S] bool, a PREFIX of each
    row (bucket filler, frozen and dead rows are not live): only live
    positions advance the state. Returns (mixer output [B, S, d], s1,
    conv1). S == 1 (a decode token) is `delta_step`, a chunk is
    `delta_chunks`. With ``in_plane`` = (layer, `live_rows` of the token),
    S == 1, ``s0`` is the whole state plane [layers, B, Hv, dk, dv] and so
    is s1: `delta_step_plane` updates the layer's live rows where they
    lie."""
    dt_ = cfg.dtype
    B, S, _ = a.shape
    Hk, Hv, dk, dv = (cfg.key_heads, cfg.value_heads, cfg.key_head_dim,
                      cfg.value_head_dim)
    dc, cd = cfg.conv_kernel, cfg.conv_dim
    with jax.named_scope(sn.GDN_PROJ):
        qkvz = jnp.einsum("bsd,de->bse", a, p["w_qkvz"].astype(dt_))
        x, z = qkvz[..., :cd], qkvz[..., cd:]
        ba = jnp.einsum("bsd,de->bse", a, p["w_ba"].astype(dt_),
                        preferred_element_type=jnp.float32)
        beta = _write_strength(ba[..., :Hv])
        g = _log_decay(ba[..., Hv:], p)
    with jax.named_scope(sn.GDN_CONV):
        padded = jnp.concatenate([conv0.astype(dt_), x], axis=1)
        w = p["conv_w"].astype(dt_)
        x = jax.nn.silu(sum(padded[:, j:j + S] * w[j] for j in range(dc)))
        # the last dc-1 inputs the row has really seen
        idx = live.sum(axis=1, dtype=jnp.int32)[:, None] \
            + jnp.arange(dc - 1, dtype=jnp.int32)[None, :]
        conv1 = jnp.take_along_axis(padded, idx[:, :, None], axis=1) \
            .astype(conv0.dtype)
    with jax.named_scope(sn.GDN_PROJ):
        q = x[..., :cfg.key_dim].reshape(B, S, Hk, dk)
        k = x[..., cfg.key_dim:2 * cfg.key_dim].reshape(B, S, Hk, dk)
        v = x[..., 2 * cfg.key_dim:].reshape(B, S, Hv, dv) \
            .astype(jnp.float32)
        q, k = _unit_keys(q, k, dk ** -0.5)
        # a key head serves Hv / Hk consecutive value heads
        q = jnp.repeat(q, Hv // Hk, axis=2)
        k = jnp.repeat(k, Hv // Hk, axis=2)
    if S == 1:
        step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live[:, 0])
        if in_plane is None:
            o, s1 = delta_step(s0, *step)
        else:
            o, s1 = delta_step_plane(s0, in_plane[0], *step,
                                     walk=in_plane[1])
        o = o[:, None]
    else:
        o, s1 = delta_chunks(s0, q, k, v, g, beta, live, dt_)
    with jax.named_scope(sn.GDN_PROJ):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        o = o * p["o_norm"].astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32)).reshape(B, S, Hv, dv)
        out = jnp.einsum("bse,ed->bsd", o.astype(dt_).reshape(B, S, Hv * dv),
                         p["w_out"].astype(dt_))
    return out, s1, conv1


def lm_head(params: Params, h, cfg: GdnConfig):
    """Final norm and the untied head: [B, S, d] -> f32 [B, S, vocab]."""
    h = _rmsnorm1p(h, params["final_norm"], cfg.norm_eps)
    with jax.named_scope(sn.LM_HEAD):
        return jnp.einsum("bsd,dv->bsv", h,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The stack against the engine's pool and state
# ---------------------------------------------------------------------------

def layers_paged(params: Params, toks, pool_k, pool_v, bt, starts,
                 cfg: GdnConfig, *, state, bt_w=None, live, rows=None,
                 n_valid=None, last_idx=None, final: bool = True,
                 moe_live=None):
    """This family's stack against the pool and the recurrent state, as
    `block_pool.ServedConfig.stack` describes it:

      pool_k/v  the K/V pool [n_attn_layers, NB, T, KV*D], through ``bt``
      state     {"delta", "conv"} (`GdnConfig.state_planes`), a slot a row

    No window plane and every layer for every chunk: ``bt_w`` and
    ``final`` are ignored. The expert-layer counts are [n_layers, 3 or 4]."""
    B, S = toks.shape
    T = pool_k.shape[2]
    span = bt.shape[1] * T
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    nd = cfg.delta_per_period
    slots = starts[:, None] + jnp.arange(S)[None, :]
    # a chunk's bucket filler queries nothing: its result is never read
    real = None if n_valid is None else \
        jnp.arange(S)[None, :] < n_valid[:, None]
    q_slots = slots if real is None else jnp.where(real, slots, -1)
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(dt)[toks]
    bidx = jnp.arange(B)[:, None]
    fresh = _starts_fresh(starts)

    # The state's reads and writes carry the scope of the work they feed:
    # they are most of the bytes the one-token update moves.
    rule = sn.GDN_STEP if S == 1 else sn.GDN_CHUNK

    # A prefill group reads and writes its rows' slots alone: a gather of
    # whole rows out of ``[layers * slots, ...]`` and one in-place update
    # a row (a padding row repeats a real one and writes the same again),
    # never a layer's ``[slots, ...]`` of it.
    n_slots = state["delta"].shape[1]
    # A decode token on the chip hands the mixer the matrix-state plane
    # itself, with the layer's index and the token's walk over the live
    # rows: the kernel reads and writes a live row's state where it lies.
    in_place = rows is None and S == 1 and state_step_kernel(cfg)
    if in_place:
        with jax.named_scope(rule):
            walk = live_rows(live[:, 0])

    def rows_of(x, pi, zero):
        flat = x.reshape(-1, *x.shape[2:])
        got = flat[pi * n_slots + rows]
        return jnp.where(fresh.reshape(-1, *(1,) * (got.ndim - 1)), zero,
                         got)

    def rows_into(x, pi, new):
        def one(i, x):
            return jax.lax.dynamic_update_slice(
                x, new[i][None, None].astype(x.dtype),
                (pi, rows[i]) + (0,) * (x.ndim - 2))
        return jax.lax.fori_loop(0, B, one, x)

    def read_state(sd, sc, pi):
        with jax.named_scope(rule):
            if in_place:
                s0 = sd
            elif rows is None:
                s0 = sd[pi]
            else:
                s0 = rows_of(sd, pi, 0.0)
        with jax.named_scope(sn.GDN_CONV):
            c0 = sc[pi] if rows is None else rows_of(
                sc, pi, jnp.zeros((), sc.dtype))
        return s0, c0

    def write_state(sd, sc, pi, s1, c1):
        with jax.named_scope(rule):
            if in_place:
                sd = s1
            elif rows is None:
                sd = sd.at[pi].set(s1)
            else:
                sd = rows_into(sd, pi, s1)
        with jax.named_scope(sn.GDN_CONV):
            sc = sc.at[pi].set(c1) if rows is None else rows_into(sc, pi, c1)
        return sd, sc

    period = params["period"]
    experts = {n: period["moe"][n].reshape(-1, *period["moe"][n].shape[3:])
               for n in EXPERT_STACKS}
    moe_small = {n: v for n, v in period["moe"].items()
                 if n not in EXPERT_STACKS}

    def expert_layer(h, p, li):
        x = _rmsnorm1p(h, p["norm"], cfg.norm_eps)
        # the expert stacks of ALL layers go in whole, with this layer's
        # index: nothing of a layer's size is sliced out of them
        # a chunk group's padding rows repeat another row: `moe_live`
        # counts them once, but their K/V and state land on their twin's,
        # so they are READ
        out, st = moe_ffn_dropless(x, {**p, **experts}, cfg, live=moe_live,
                                   expert_stack_layer=li, read=real)
        return h + out, st

    def delta_body(carry, xs):
        h, sd, sc = carry
        p, pm, pi, li = xs
        a = _rmsnorm1p(h, p["norm"], cfg.norm_eps)
        out, s1, c1 = delta_mixer(
            a, p, *read_state(sd, sc, pi), live, cfg,
            in_plane=(pi, walk) if in_place else None)
        sd, sc = write_state(sd, sc, pi, s1, c1)
        h, st = expert_layer(h + out, pm, li)
        return (h, sd, sc), st

    def attention(h, p, ai, pk, pv):
        a = _rmsnorm1p(h, p["norm"], cfg.norm_eps)
        with jax.named_scope(sn.ATTN_QKV):
            qg = jnp.einsum("bsd,de->bse", a, p["wq"].astype(dt)) \
                .reshape(B, S, H, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
            k = jnp.einsum("bsd,de->bse", a, p["wk"].astype(dt)) \
                .reshape(B, S, KV, hd)
            v = jnp.einsum("bsd,de->bse", a, p["wv"].astype(dt))
            q = _rope(_rmsnorm1p(q, p["q_norm"], cfg.norm_eps), slots, cfg)
            k = _rope(_rmsnorm1p(k, p["k_norm"], cfg.norm_eps), slots, cfg)
        with jax.named_scope(sn.KV_WRITE):
            blk, off = bt[bidx, slots // T], slots % T
            pk = pk.at[ai, blk, off].set(
                k.reshape(B, S, -1).astype(pk.dtype))
            pv = pv.at[ai, blk, off].set(v.astype(pv.dtype))
        with jax.named_scope(sn.PAGED_ATTENTION):
            o = paged_attention(q, pk, pv, bt, q_slots, layer=ai,
                                kv_valid_len=span, sm_scale=hd ** -0.5)
        with jax.named_scope(sn.ATTN_GATE):
            o = _gate_heads(o, gate).reshape(B, S, H * hd)
        with jax.named_scope(sn.ATTN_OUT):
            h = h + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(dt))
        return h, pk, pv

    def period_body(carry, xs):
        h, pk, pv, sd, sc = carry
        p_delta, p_attn, p_moe, k = xs
        first = k * (nd + 1)
        (h, sd, sc), st = jax.lax.scan(
            delta_body, (h, sd, sc),
            (p_delta, jax.tree_util.tree_map(lambda x: x[:nd], p_moe),
             k * nd + jnp.arange(nd), first + jnp.arange(nd)))
        h, pk, pv = attention(h, p_attn, k, pk, pv)
        h, st_a = expert_layer(
            h, jax.tree_util.tree_map(lambda x: x[nd], p_moe), first + nd)
        if moe_live is not None:
            st = jnp.concatenate([st, st_a[None]])
        return (h, pk, pv, sd, sc), st

    (h, pool_k, pool_v, sd, sc), stats = jax.lax.scan(
        period_body, (h, pool_k, pool_v, state["delta"], state["conv"]),
        (period["delta"], period["attn"], moe_small,
         jnp.arange(cfg.n_periods)))
    if moe_live is not None:
        stats = stats.reshape(cfg.n_layers, -1)
    if last_idx is not None:
        h = h[jnp.arange(B), last_idx][:, None]
    return h, pool_k, pool_v, stats, {"delta": sd, "conv": sc}


def init_cache(cfg: GdnConfig, batch_size: int, max_len: int):
    """`generate.init_cache` for this family: there is none to make."""
    raise ValueError(
        "a GdnConfig has no solo generation path: its stack runs "
        "over the engine's pool, table and state slots; serve it "
        "through DecodeEngine")
