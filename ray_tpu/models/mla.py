"""Latent-attention decoder LM with a learned sparse-attention indexer and
held experts beside a shared one (the DeepSeek-V3.2-Exp shape), and, where
the config names window layers, latent attention of a SECOND geometry
beside it in one stack (the dots3-note shape: selected full layers beside
window layers with their own head count, ranks, rotary base and scale).

No layer of this family is the Llama layer, so like `hybrid` it brings its
own stack (`layers_paged`) and shares everything around it: the serving
engine's programs, step loop, ring and scheduler, its block pool and
tables, chunked prefill in place, sampling, and the expert layer of
`moe.moe_ffn_dropless` (told which experts it holds).

The stack is read from the config alone (`MlaConfig.layer_plan`):

    segment "dense":  n_dense_layers of [latent attention, dense FFN]
    segment "moe":    the rest, of      [latent attention, expert layer]

each ``h = h + Attn(N(h)); h = h + F(N(h))``, each segment one `lax.scan`
over its layers with the pools in the carry. A config with `layer_types`
mixes two kinds of attention: the expert layers are then periods of unlike
kinds (F S S S), a period's runs of one kind scanned inside the scan over
periods, and a layer's parameters lie with its kind's
(``params["moe"]["full" | "window"]``, stack order inside a kind).

A WINDOW layer (`SWA`) is the same latent attention with its own widths
and no indexer: a query at t attends the slots ``0 <= t - s <
sliding_window``. Its latent rows live in a plane of their own behind the
engine's WINDOW table (`bt_w`), of which a row keeps the blocks that cover
its last `sliding_window` slots: the layer reads the few pages that cover
its queries' windows (`_window_pages`) with the window as the mask the
kernels already take. Every plane holds the layers of ITS kind only and is
indexed by a layer's place among them.

What a token stores (`MlaConfig.cache_planes`), for every layer, through
ONE block table a row:

    latent plane  [L, NB, T, 640]  ``[c 512 | k_rope 64 | 0 x 64]``: the
                  normed latent and the ONE rotary key all heads share.
                  576 values; the chip's tiled layout pads a 576-lane row
                  to 640 lanes whatever is asked for, so the plane says
                  640 and the pool's bytes are priced at that (1,280 B a
                  token a layer, 11 % padding). There is NO value plane:
                  values are the first 512 lanes of the same row.
    index plane   [L, NB, T, 128]  the indexer's key ``k^I`` (256 B).

Attention is in the ABSORBED form: ``q'_h = q_nope_h W_kb^h`` (512 wide)
scores against ``c`` directly, the output is ``W_vb^h (sum_s p_s c_s)``,
so the 128 heads read one shared row a token and nothing is expanded.

Selection, per query t of a row: (1) score every live slot s <= t from
the index plane, ``I = sum_j w_j relu(q^I_j . k^I_s)`` in float32; (2) the
EXACT top `index_topk` of them; (3) read those slots' latent rows; (4)
attend them. A context at or under `index_topk` attends all of it (the
selection then picks every live slot). The selection is a MASK
(`select_mask`: the k-th largest score by counting, no sort, and
`lax.top_k`'s own members), a block of queries at a time; on the chip the
same mask from ONE kernel that walks the pages a block of queries can see
and none for a block with no choice to make, `select_rows`), and the row's
keys are attended densely under it: a chunk's queries would each gather
their own 2,048 rows (1.3 GB a 512-token chunk a layer), and a decode
token's gather and sort were half its time. `attend_chunk` lays the row's
pages side by side (`ops/sparse_latent_attention.py`: the kernel on the
chip, its plain form elsewhere); `attend_token`, a decode token on the
chip, reads the pages where they lie.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.block_pool import CachePlane
from ray_tpu.models.hybrid import STATE_REFUSALS, LayerKind, Segment
from ray_tpu.models.llama import _rmsnorm
from ray_tpu.models.moe import EXPERT_STACKS, moe_ffn_dropless
from ray_tpu.ops import scope_names as sn

Params = Dict[str, Any]

# a full layer's mixer: latent attention over the slots its indexer chose
MLA = LayerKind("mla", writes="latent", reads="latent")
# a window layer's: latent attention of its own geometry over the last
# `sliding_window` slots, through the window table
SWA = LayerKind("mla", writes="window", reads="window")
_KIND_NAMES = {"full": MLA, "window": SWA}


class _Geometry(NamedTuple):
    """One layer kind's attention as the shared code reads it."""

    heads: int
    nope: int
    rope: int
    v: int
    q_rank: int
    kv_rank: int
    lanes: int             # a latent row as stored
    inv_freq: Any          # [rope / 2] float32
    sm_scale: float
    scope_gate: str

# Queries are scored and selected this many a row at a time: a block's
# indexer scores [rows, block, heads, max_len] float32 are the program's
# largest temporary.
_QUERY_BLOCK = 16
_LANE = 128
# std of the SEEDED selection bias (`mla_init`; a checkpoint brings its
# own). The published bias is trained until the experts' loads are even; a
# seeded one unbalances them instead: at std 0.1 the share of assignments
# that landed on 16 held experts of 256 swung 4.0-7.5 % between seeds where
# 6.25 is even, and a cell's throughput with it (PERF.md PR 33); at 0.003
# it is 6.29 +- 0.1. Not zero: a program that drops the bias moves logits.
_ROUTER_BIAS_STD = 0.003


@dataclasses.dataclass(frozen=True)
class MlaConfig:
    vocab_size: int = 129280
    dim: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3            # first_k_dense_replace
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432               # a leading dense layer's FFN
    expert_dim: int = 2048             # one expert's (moe_intermediate_size)
    n_experts: int = 256               # routed, over the whole deployment
    n_shared_experts: int = 1
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # (lo, hi): the routed experts THIS program holds and computes; the
    # router is over all `n_experts` whatever this says. None: all.
    held_experts: Optional[Tuple[int, int]] = None
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # yarn: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim); None: plain rotary
    rope_scaling: Optional[Tuple[float, int, float, float, float, float]] = \
        (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    max_seq_len: int = 163840
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # A second kind of layer. `layer_types`: "full" | "window", one a
    # layer; None: every layer full, and nothing below is read. A window
    # layer attends the last `sliding_window` slots (the query's own
    # counted) with the ``swa_*`` widths and keeps no indexer.
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    swa_n_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    # one sigmoid scalar a head from the layer's normed input, on the
    # heads' outputs ahead of the output projection (every layer's kind)
    attn_gate: bool = False
    # the normed query and key-value latents times sqrt(dim / their rank)
    lora_rescale: bool = False

    router = "sigmoid_grouped"         # `moe.moe_ffn_dropless` reads it

    def __post_init__(self):
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("MlaConfig: n_dense_layers must leave at "
                             "least one expert layer")
        if self.n_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError("MlaConfig: n_group must divide n_experts and "
                             "topk_group cannot exceed it")
        if self.n_experts // self.n_group < 2:
            raise ValueError("MlaConfig: a group's score is the sum of its "
                             "two largest: groups of at least 2 experts")
        if self.held_experts is not None:
            lo, hi = self.held_experts
            if not 0 <= lo < hi <= self.n_experts:
                raise ValueError("MlaConfig: held_experts (lo, hi) must be "
                                 "a non-empty range of the routed experts")
        if self.qk_rope_head_dim % 2 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("MlaConfig: the rotary width must be even and "
                             "fit the indexer's head")
        if self.layer_types is not None:
            kinds = self.layer_types
            if len(kinds) != self.n_layers \
                    or any(k not in _KIND_NAMES for k in kinds):
                raise ValueError("MlaConfig: layer_types names 'full' or "
                                 "'window' once a layer")
            if "window" in kinds[:self.n_dense_layers]:
                raise ValueError("MlaConfig: the leading dense layers are "
                                 "full layers")
            if "window" in kinds and (
                    self.sliding_window < 1 or self.swa_n_heads < 1
                    or self.swa_qk_rope_head_dim % 2):
                raise ValueError("MlaConfig: window layers need "
                                 "sliding_window, the swa_* widths and an "
                                 "even rotary width")

    # -- what `moe_ffn_dropless` and the engine read -----------------------

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_held(self) -> int:
        lo, hi = self.held_experts or (0, self.n_experts)
        return hi - lo

    @property
    def latent_lanes(self) -> int:
        """Lanes of a full layer's latent row as stored: kv_lora_rank +
        rotary, up to the lane tile (576 -> 640)."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // _LANE) * _LANE

    @property
    def sm_scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling and self.rope_scaling[5]:
            m = 0.1 * self.rope_scaling[5] * math.log(self.rope_scaling[0]) \
                + 1.0 if self.rope_scaling[0] > 1 else 1.0
            s *= m * m
        return s

    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        """One `LayerKind` a layer, in stack order."""
        if self.layer_types is None:
            return (MLA,) * self.n_layers
        return tuple(_KIND_NAMES[k] for k in self.layer_types)

    @property
    def n_window_layers(self) -> int:
        return self.layer_kinds().count(SWA)

    @property
    def n_select_layers(self) -> int:
        """Layers with an indexer and a selection: the full ones."""
        return self.n_layers - self.n_window_layers

    def geometry(self, kind: LayerKind) -> _Geometry:
        """The widths, rotary and scale of ``kind``'s attention."""
        if kind is MLA:
            return _Geometry(
                self.n_heads, self.qk_nope_head_dim, self.qk_rope_head_dim,
                self.v_head_dim, self.q_lora_rank, self.kv_lora_rank,
                self.latent_lanes, yarn_inv_freq(self), self.sm_scale,
                sn.ATTN_GATE)
        n, r, rc = (self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim,
                    self.swa_kv_lora_rank)
        return _Geometry(
            self.swa_n_heads, n, r, self.swa_v_head_dim,
            self.swa_q_lora_rank, rc, -(-(rc + r) // _LANE) * _LANE,
            _inv_freq(r, self.swa_rope_theta, None), (n + r) ** -0.5,
            sn.SWA_GATE)

    def layer_plan(self) -> Tuple[Segment, ...]:
        """The stack as segments, in `hybrid.Segment`'s terms: the ONE
        description the scans, the pools and the counters are built from.
        The expert layers are whole periods of the shortest pattern their
        kinds repeat (F S S S), then what is left of a period."""
        kinds, nd = self.layer_kinds(), self.n_dense_layers
        segs = []
        if nd:
            segs.append(Segment("dense", (MLA,), nd, 0))
        moe = kinds[nd:]
        for p in range(1, len(moe) + 1):
            q = len(moe) // p
            if moe[:q * p] == moe[:p] * q \
                    and moe[q * p:] == moe[:len(moe) % p]:
                break
        segs.append(Segment("moe", moe[:p], q, nd))
        if len(moe) % p:
            segs.append(Segment("moe_tail", moe[q * p:], 1, nd + q * p))
        return tuple(segs)

    def cache_planes(self) -> Tuple[CachePlane, ...]:
        """What a token stores: a latent row and an indexer key in every
        FULL layer, behind one table, no value plane; and a latent row of
        their own width in every window layer, behind the window table."""
        dt, nf = jnp.dtype(self.dtype), self.n_select_layers
        planes = (CachePlane("latent", "full", nf, self.latent_lanes, dt),
                  CachePlane("index", "full", nf, self.index_head_dim, dt))
        if self.n_window_layers:
            planes += (CachePlane("wlatent", "window", self.n_window_layers,
                                  self.geometry(SWA).lanes, dt),)
        return planes

    def state_planes(self):
        return ()

    def prefill_layers(self) -> int:
        return self.n_layers

    def refusals(self) -> Dict[str, str]:
        """What would need the two planes shared, quantized, moved or
        split (`block_pool.ServedConfig`)."""
        no = "an MlaConfig cannot be served with "
        return {
            "prefix_cache": no + "prefix_cache=True: the trie's copy-on-"
            "write and eviction know K and V planes of one width, not a "
            "latent and an index plane (ROADMAP M3)",
            "kv_quant": no + "kv_quant=: the latent and index planes have "
            "no quantized write, and a quantized indexer key changes what "
            "is selected (ROADMAP M3)",
            "preempt_swap": no + "preempt='swap': the swap ledger gathers "
            "and scatters K and V planes of one width (pass "
            "preempt='recompute'; ROADMAP M3)",
            "tp": no + "tp=/mesh=: the latent is ONE head's cache and the "
            "held experts have no exchange: neither has a sharding rule "
            "(ROADMAP M2)",
            "lora": no + STATE_REFUSALS["lora"],
            "draft": no + "draft_params=/draft_cfg=: the verify window has "
            "no selection per drafted token, and the model's own drafting "
            "head is not built (ROADMAP M7)",
            "handoff": no + "{}: a hand-off carries K and V planes of one "
            "width, not a latent and an index plane (ROADMAP M3)"}

    def stack(self):
        return sys.modules[__name__]

    @staticmethod
    def deepseek_v32_exp(**kw) -> "MlaConfig":
        """deepseek-ai/DeepSeek-V3.2-Exp, every width, all 61 layers."""
        return MlaConfig(**kw)

    @staticmethod
    def nano_mla(**kw) -> "MlaConfig":
        defaults = dict(vocab_size=256, dim=64, n_layers=3, n_dense_layers=1,
                        n_heads=4, q_lora_rank=32, kv_lora_rank=32,
                        qk_nope_head_dim=16, qk_rope_head_dim=8,
                        v_head_dim=16, ffn_dim=128, expert_dim=32,
                        n_experts=16, top_k=4, n_group=4, topk_group=2,
                        index_n_heads=4, index_head_dim=16, index_topk=16,
                        rope_scaling=(40.0, 32, 32.0, 1.0, 1.0, 1.0),
                        max_seq_len=256, dtype=jnp.float32,
                        param_dtype=jnp.float32)
        defaults.update(kw)
        return MlaConfig(**defaults)

    def _attn_params(self, kind: LayerKind = MLA) -> int:
        d, g = self.dim, self.geometry(kind)
        n = (d * g.q_rank + g.q_rank + g.q_rank * g.heads * (g.nope + g.rope)
             + d * (g.kv_rank + g.rope) + g.kv_rank
             + g.kv_rank * g.heads * (g.nope + g.v) + g.heads * g.v * d
             + 2 * d + d * g.heads * self.attn_gate)
        if kind is MLA:
            IH, ID = self.index_n_heads, self.index_head_dim
            n += g.q_rank * IH * ID + d * ID + 2 * ID + d * IH
        return n

    def num_params(self) -> int:
        """Parameters HELD here (held experts, this vocabulary)."""
        d = self.dim
        shared = 3 * d * self.expert_dim * self.n_shared_experts
        moe = self.n_held * 3 * d * self.expert_dim + shared \
            + d * self.n_experts + self.n_experts
        return (2 * self.vocab_size * d + d
                + sum(self._attn_params(k) for k in self.layer_kinds())
                + self.n_dense_layers * 3 * d * self.ffn_dim
                + self.n_moe_layers * moe)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def mla_init(key: jax.Array, cfg: MlaConfig) -> Params:
    """Random weights. Matrices normal with std ``fan_in ** -0.5``, the
    embedding std 0.02, RMSNorm weights 1; the indexer key's LayerNorm
    bias normal std 0.02 and the router's selection bias normal std
    `_ROUTER_BIAS_STD` (not zero: a program that drops either moves
    logits). The expert
    stacks hold the `held_experts` alone. Jit it with `cfg` static to
    build a real-size model on the device in one program."""
    d = cfg.dim
    IH, ID = cfg.index_n_heads, cfg.index_head_dim
    pdt = cfg.param_dtype
    # (a stack of two kinds draws for a stack a kind, and a tail's)
    keys = iter(jax.random.split(key, 64 if cfg.layer_types is None else 128))

    def mat(lead, n_in, n_out, std=None):
        std = n_in ** -0.5 if std is None else std
        return (jax.random.normal(next(keys), (*lead, n_in, n_out),
                                  jnp.float32) * std).astype(pdt)

    def vec(lead, width, std):
        return (jax.random.normal(next(keys), (*lead, width), jnp.float32)
                * std).astype(pdt)

    def ones(lead, width):
        return jnp.ones((*lead, width), pdt)

    def attn(lead, kind=MLA):
        g = cfg.geometry(kind)
        H, n, r, v, rq, rc = g[:6]
        out = {
            "attn_norm": ones(lead, d), "mlp_norm": ones(lead, d),
            "wq_a": mat(lead, d, rq), "q_norm": ones(lead, rq),
            "wq_b": mat(lead, rq, H * (n + r)),
            "wkv_a": mat(lead, d, rc + r), "kv_norm": ones(lead, rc),
            "wk_b": mat(lead, rc, H * n), "wv_b": mat(lead, rc, H * v),
            "wo": mat(lead, H * v, d),
        }
        if kind is MLA:
            out.update({
                "wi_q": mat(lead, rq, IH * ID), "wi_k": mat(lead, d, ID),
                "ik_norm_w": ones(lead, ID),
                "ik_norm_b": vec(lead, ID, 0.02),
                "wi_w": mat(lead, d, IH)})
        if cfg.attn_gate:
            out["w_attn_gate"] = mat(lead, d, H)
        return out

    def dense(lead):
        f = cfg.ffn_dim
        return {**attn(lead), "w_gate": mat(lead, d, f),
                "w_up": mat(lead, d, f), "w_down": mat(lead, f, d)}

    def moe(lead, kind=MLA):
        f, eh = cfg.expert_dim, cfg.n_held
        fs = f * cfg.n_shared_experts
        out = {**attn(lead, kind), "w_router": mat(lead, d, cfg.n_experts),
               "router_bias": vec(lead, cfg.n_experts, _ROUTER_BIAS_STD)
               .astype(jnp.float32),
               "we_gate": mat((*lead, eh), d, f),
               "we_up": mat((*lead, eh), d, f),
               "we_down": mat((*lead, eh), f, d)}
        if fs:
            out.update(ws_gate=mat(lead, d, fs), ws_up=mat(lead, d, fs),
                       ws_down=mat(lead, fs, d))
        return out

    def segment(seg):
        """An expert segment's layers: one stack where they are of one
        kind, else a stack a kind, in stack order inside it."""
        if cfg.layer_types is None:
            return moe((seg.periods,))
        return {name: moe((seg.periods * seg.kinds.count(kind),), kind)
                for name, kind in _KIND_NAMES.items() if kind in seg.kinds}

    plan = {seg.name: seg for seg in cfg.layer_plan()}
    params = {
        "tok_embed": mat((), cfg.vocab_size, d, std=0.02),
        "moe": segment(plan["moe"]),
        "final_norm": ones((), d),
        "lm_head": mat((), d, cfg.vocab_size),
    }
    if cfg.n_dense_layers:
        params["dense"] = dense((cfg.n_dense_layers,))
    if "moe_tail" in plan:
        params["moe_tail"] = segment(plan["moe_tail"])
    return params


# ---------------------------------------------------------------------------
# Rotary
# ---------------------------------------------------------------------------

def yarn_inv_freq(cfg: MlaConfig) -> np.ndarray:
    """The full layers' `_inv_freq`."""
    return _inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling)


def _inv_freq(dim: int, base: float, rope_scaling) -> np.ndarray:
    """[dim / 2] float32 inverse frequencies: plain rotary's, blended
    with their `factor`-times slower copies by yarn's ramp between the
    correction dimensions of `beta_fast` and `beta_slow` rotations at the
    original context length (as `deepseek_v3` computes them)."""
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rope_scaling:
        return (1.0 / pos).astype(np.float32)
    factor, orig, beta_fast, beta_slow = rope_scaling[:4]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return ((1.0 / (factor * pos)) * (1 - extra)
            + (1.0 / pos) * extra).astype(np.float32)


def _rope_pairs(x, cos, sin, interleaved: bool):
    """Rotate the last axis of ``x`` [..., r] by the angles behind
    ``cos``/``sin`` [..., r / 2]: on pairs (2i, 2i + 1) when `interleaved`
    (the attention's queries and key), else on (i, i + r / 2) (the
    indexer's). The result is laid out halves-first either way: a query
    and the key it meets are rotated alike, and their product does not
    see the order."""
    if interleaved:
        pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
        x0, x1 = pairs[..., 0], pairs[..., 1]
    else:
        half = x.shape[-1] // 2
        x0, x1 = x[..., :half], x[..., half:]
    x0, x1 = x0.astype(jnp.float32), x1.astype(jnp.float32)
    return jnp.concatenate([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Selection inside paged attention
# ---------------------------------------------------------------------------

def indexer_scores(qi, wt, q_slots, pool_i, bt, li, cfg: MlaConfig):
    """Step (1): ``I`` [B, Sq, span] float32 of every slot of each query's
    row, -inf where the query may not see it (s > t, filler)."""
    B = q_slots.shape[0]
    span = bt.shape[1] * pool_i.shape[2]
    with jax.named_scope(sn.INDEXER_SCORE):
        keys = pool_i[li, bt].reshape(B, span, cfg.index_head_dim)
        dots = jax.nn.relu(jnp.einsum("bqjd,bsd->bqjs", qi, keys,
                                      preferred_element_type=jnp.float32))
        scores = jnp.einsum("bqj,bqjs->bqs", wt, dots)
        seen = jnp.arange(span)[None, None, :] <= q_slots[:, :, None]
        return jnp.where(seen, scores, -jnp.inf)


def kth_largest(scores, k: int):
    """The k-th largest value of each row of ``scores`` [..., n] float32,
    EXACTLY, without sorting: the float's bits as an order-preserving
    unsigned key, and the largest key that at least k entries reach,
    built a bit at a time from the top (32 counts over the row). A row
    with fewer than k entries above -inf gives -inf."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(bits >> 31 == 0, bits | jnp.uint32(1 << 31), ~bits)

    def step(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (key >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, step,
                          jnp.zeros(scores.shape[:-1], jnp.uint32))
    back = jnp.where(t >> 31 == 1, t & jnp.uint32((1 << 31) - 1), ~t)
    kth = jax.lax.bitcast_convert_type(back, jnp.float32)
    return jnp.where(t == 0, -jnp.inf, kth)


def select_mask(qi, wt, q_slots, pool_i, bt, li, cfg: MlaConfig):
    """Steps (1) and (2) as a MASK, for queries [B, Sq]: the additive bias
    [B, Sq, span] float32 that is 0 on the slots a query chose and -1e30
    elsewhere. The mask is the top-k's own members: every slot above the
    k-th value (`kth_largest`: a mask needs no order, so nothing is
    sorted), and of the slots AT it the lowest ones, as many as
    `lax.top_k` would take."""
    scores = indexer_scores(qi, wt, q_slots, pool_i, bt, li, cfg)
    k = min(cfg.index_topk, scores.shape[-1])
    with jax.named_scope(sn.INDEXER_TOPK):
        kth = kth_largest(scores, k)[..., None]
        above = scores > kth
        at = (scores == kth) & (scores > -jnp.inf)
        room = k - above.sum(-1, keepdims=True)
        take = above | (at & (jnp.cumsum(at, axis=-1) <= room))
        return jnp.where(take, 0.0, -1e30).astype(jnp.float32)


def select_rows(qi, wt, q_slots, pool_i, bt, li, cfg: MlaConfig):
    """`select_mask` on the chip, for all the queries [B, S] of a call at
    once (`ops.indexer_select`): a block of queries walks the pages of
    its row that hold a slot it can see, where they lie, and none while
    its last slot is below `index_topk`; nothing of the table's width is
    gathered and no score leaves VMEM. The scores and the counts are one
    kernel, under the scope of the scores."""
    from ray_tpu.ops.indexer_select import indexer_select

    with jax.named_scope(sn.INDEXER_SCORE):
        return indexer_select(qi, wt, q_slots, pool_i, bt, li,
                                   topk=cfg.index_topk, interpret=False)


def attend_token(q_full, bias, q_slots, pool_c, bt, li, cfg: MlaConfig):
    """Steps (3) and (4) for ONE query a row (a decode token) on the chip:
    the row's pages read where they lie under the selection's mask
    (`ops.sparse_latent_attention.sparse_latent_decode`): no gather of the
    chosen rows. [B, 1, H, lanes] -> [B, 1, H, kv_lora_rank]."""
    from ray_tpu.ops import sparse_latent_attention as sla

    with jax.named_scope(sn.SPARSE_ATTENTION):
        o = sla.sparse_latent_decode(
            q_full[:, 0], pool_c, bt, bias[:, 0], q_slots[:, 0], li,
            rc=cfg.kv_lora_rank, sm_scale=cfg.sm_scale, interpret=False)
    return o[:, None]


def attend_chunk(q_full, bias, q_slots, pool_c, bt, li, cfg: MlaConfig):
    """Steps (3) and (4) for a chunk: the row's latent pages side by side,
    attended densely under the selection's mask
    (`ops.sparse_latent_attention`: the kernel on the chip, its plain
    form elsewhere). [B, S, H, lanes] -> [B, S, H, kv_lora_rank]."""
    from ray_tpu.ops import sparse_latent_attention as sla

    B, S = q_slots.shape
    with jax.named_scope(sn.LATENT_GATHER):
        lat = pool_c[li, bt].reshape(B, -1, pool_c.shape[3])
    with jax.named_scope(sn.SPARSE_ATTENTION):
        q = jnp.swapaxes(q_full, 1, 2)                  # [B, H, S, lanes]
        if jax.default_backend() == "tpu" and S % 8 == 0 \
                and lat.shape[1] % 128 == 0:
            o = sla.sparse_latent_attention(
                q, lat, bias, q_slots, rc=cfg.kv_lora_rank,
                sm_scale=cfg.sm_scale)
        else:
            o = sla.sparse_latent_attention_reference(
                q, lat, bias, rc=cfg.kv_lora_rank, sm_scale=cfg.sm_scale)
        return jnp.swapaxes(o, 1, 2)


def _by_query_blocks(fn, Sq: int, *per_query):
    """``fn`` over blocks of `_QUERY_BLOCK` queries (axis 1 of every
    argument), results concatenated; one call when the queries fit one."""
    if Sq <= _QUERY_BLOCK:
        return fn(*per_query)
    n = -(-Sq // _QUERY_BLOCK)
    pad = n * _QUERY_BLOCK - Sq

    def split(x, fill):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=fill)
        x = x.reshape(x.shape[0], n, _QUERY_BLOCK, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    # the last argument is the slots: padding asks nothing (-1)
    blocks = [split(x, 0) for x in per_query[:-1]] \
        + [split(per_query[-1], -1)]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))

    def join(y):
        y = jnp.moveaxis(y, 0, 1)
        return y.reshape(y.shape[0], n * _QUERY_BLOCK, *y.shape[3:])[:, :Sq]

    return jax.tree_util.tree_map(join, out)


# ---------------------------------------------------------------------------
# The stack against the engine's pools
# ---------------------------------------------------------------------------

def _latent_proj(a, p, slots, g: _Geometry, cfg: MlaConfig):
    """A layer's low-rank projections in ``g``'s widths, for rows [B, S]
    at ``slots``: (the query latent ``c_q``, the absorbed queries laid out
    like a latent row [B, S, H, lanes], the latent rows to store
    [B, S, lanes], cos, sin). Call it under the kind's projection scope."""
    dt = cfg.dtype
    B, S, _ = a.shape
    H, n, r, rc = g.heads, g.nope, g.rope, g.kv_rank
    ang = jnp.maximum(slots, 0).astype(jnp.float32)[..., None] \
        * g.inv_freq                                      # [B, S, r / 2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    c_q = _rmsnorm(jnp.einsum("bsd,de->bse", a, p["wq_a"].astype(dt)),
                   p["q_norm"], cfg.norm_eps)
    if cfg.lora_rescale:
        c_q = c_q * lora_rescale(cfg.dim, g.q_rank)
    q = jnp.einsum("bse,ef->bsf", c_q, p["wq_b"].astype(dt)) \
        .reshape(B, S, H, n + r)
    q_rope = _rope_pairs(q[..., n:], cos[:, :, None], sin[:, :, None],
                         True)
    kv = jnp.einsum("bsd,de->bse", a, p["wkv_a"].astype(dt))
    c = _rmsnorm(kv[..., :rc], p["kv_norm"], cfg.norm_eps)
    if cfg.lora_rescale:
        c = c * lora_rescale(cfg.dim, rc)
    k_rope = _rope_pairs(kv[..., rc:], cos, sin, True)
    # absorbed: the key map goes to the query's side
    q_abs = jnp.einsum("bshn,chn->bshc", q[..., :n],
                       p["wk_b"].astype(dt).reshape(rc, H, n))
    fill = g.lanes - rc - r
    q_full = jnp.concatenate(
        [q_abs, q_rope] + ([jnp.zeros((B, S, H, fill), dt)]
                           if fill else []), axis=-1)
    latent = jnp.concatenate(
        [c, k_rope] + ([jnp.zeros((B, S, fill), dt)] if fill else []),
        axis=-1)
    return c_q, q_full, latent, cos, sin


def lora_rescale(dim: int, rank: int) -> float:
    """What a normed low-rank latent is multiplied by where the config
    says `lora_rescale`: ``sqrt(dim / rank)`` (`apply_mla_qkv_lora_rescale`
    as LongCat-Flash's `mla_scale_q_lora` / `mla_scale_kv_lora`)."""
    return math.sqrt(dim / rank)


def head_gate(a, w_gate, dt, scope: str = sn.ATTN_GATE):
    """The head-wise output gate: ``sigmoid(a W_g)`` [B, S, H], one
    scalar a head from the layer's normed input (`attention_gate_type`
    headwise), which the heads' outputs are multiplied by ahead of the
    output projection."""
    with jax.named_scope(scope):
        return jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", a, w_gate.astype(dt),
            preferred_element_type=jnp.float32)).astype(dt)


def _attn_out(h, a, o_lat, p, g: _Geometry, cfg: MlaConfig):
    """``h + W_o [g_h W_vb^h o_lat_h]``: the value map, the gate where the
    config has one, the output projection and the residual."""
    dt = cfg.dtype
    B, S, _ = h.shape
    with jax.named_scope(sn.ATTN_OUT):
        o = jnp.einsum("bshc,chv->bshv", o_lat,
                       p["wv_b"].astype(dt).reshape(g.kv_rank, g.heads, g.v))
        if cfg.attn_gate:
            o = o * head_gate(a, p["w_attn_gate"], dt, g.scope_gate)[..., None]
        return h + jnp.einsum("bse,ed->bsd", o.reshape(B, S, g.heads * g.v),
                              p["wo"].astype(dt))


def _attention(h, p, li, pool_c, pool_i, bt, slots, q_slots, cfg: MlaConfig,
               want_selection: bool = False):
    """A FULL layer's ``h + Attn(N(h))`` for rows [B, S] at ``slots``:
    writes the chunk's latent rows and indexer keys into layer ``li`` of
    the two planes (its place among the full layers), then selects and
    attends through the table. Returns (h, the planes and, asked, which
    slots each query chose [B, S, span] bool)."""
    dt = cfg.dtype
    B, S, _ = h.shape
    g = cfg.geometry(MLA)
    r = g.rope
    IH, ID = cfg.index_n_heads, cfg.index_head_dim
    T = pool_c.shape[2]
    f32 = jnp.float32
    a = _rmsnorm(h, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope(sn.MLA_PROJ):
        c_q, q_full, latent, cos, sin = _latent_proj(a, p, slots, g, cfg)
        # the indexer's inputs
        qi = jnp.einsum("bse,ef->bsf", c_q, p["wi_q"].astype(dt)) \
            .reshape(B, S, IH, ID)
        qi = jnp.concatenate(
            [_rope_pairs(qi[..., :r], cos[:, :, None], sin[:, :, None],
                         False), qi[..., r:]], axis=-1)
        ki = jnp.einsum("bsd,de->bse", a, p["wi_k"].astype(dt)).astype(f32)
        mu = ki.mean(-1, keepdims=True)
        kc = ki - mu
        ki = (kc * jax.lax.rsqrt((kc * kc).mean(-1, keepdims=True) + 1e-6)
              * p["ik_norm_w"].astype(f32)
              + p["ik_norm_b"].astype(f32)).astype(dt)
        ki = jnp.concatenate(
            [_rope_pairs(ki[..., :r], cos, sin, False), ki[..., r:]],
            axis=-1)
        wt = jnp.einsum("bsd,dj->bsj", a, p["wi_w"].astype(dt),
                        preferred_element_type=f32) \
            * np.float32(IH ** -0.5 * ID ** -0.5)
    with jax.named_scope(sn.KV_WRITE):
        blk = bt[jnp.arange(B)[:, None], slots // T]
        off = slots % T
        pool_c = pool_c.at[li, blk, off].set(latent.astype(pool_c.dtype))
        pool_i = pool_i.at[li, blk, off].set(ki.astype(pool_i.dtype))

    # the selection as a mask: on the chip a kernel that walks the row's
    # live index pages (`select_rows`), elsewhere the lax form a block of
    # queries at a time; then a decode token on the chip walks its row's
    # pages under it, and everything else attends the row's keys densely
    # under it
    from ray_tpu.ops.indexer_select import query_tile

    on_chip = T % _LANE == 0 and jax.default_backend() == "tpu"
    if on_chip and ID % _LANE == 0 and query_tile(S) is not None:
        bias = select_rows(qi, wt, q_slots, pool_i, bt, li, cfg)
    else:
        def mask(qi, wt, q_slots):
            return select_mask(qi, wt, q_slots, pool_i, bt, li, cfg)

        bias = _by_query_blocks(mask, S, qi, wt, q_slots)
    if S == 1 and on_chip:
        o_lat = attend_token(q_full, bias, q_slots, pool_c, bt, li, cfg)
    else:
        o_lat = attend_chunk(q_full, bias, q_slots, pool_c, bt, li, cfg)
    h = _attn_out(h, a, o_lat, p, g, cfg)
    return h, pool_c, pool_i, (bias == 0 if want_selection else None)


def _window_pages(bt_w, slots, q_slots, T: int, window: int):
    """What a window layer reads for rows [B, S] at ``slots``: the FEW
    pages of the window table that cover the windows of the chunk's
    queries, as a table of their own. Returns (``pages`` [B, P] block ids,
    ``bias`` [B, S, P * T] f32 that is 0 where ``0 <= t - s < window`` and
    -1e30 elsewhere, the queries' slots counted from the first page
    [B, S], -1 for filler). A row shorter than the window and a window
    that starts mid-block are masked inside the first page; entries past
    the table read the null block and are masked."""
    B, S = slots.shape
    MB = bt_w.shape[1]
    n_pages = min(MB, (S + window - 3 + T) // T + 1)
    first = jnp.maximum(slots[:, 0] - (window - 1), 0) // T       # [B]
    idx = first[:, None] + jnp.arange(n_pages)[None, :]           # [B, P]
    pages = jnp.where(
        idx < MB, jnp.take_along_axis(bt_w, jnp.minimum(idx, MB - 1), 1), 0)
    s = (idx[:, :, None] * T + jnp.arange(T)).reshape(B, 1, n_pages * T)
    t = q_slots[:, :, None]
    bias = jnp.where((s <= t) & (t - s < window), 0.0, -1e30) \
        .astype(jnp.float32)
    local = jnp.where(q_slots >= 0, q_slots - first[:, None] * T, -1)
    return pages, bias, local


def _attention_window(h, p, wi, pool_w, bt_w, slots, q_slots,
                      cfg: MlaConfig):
    """A WINDOW layer's ``h + Attn(N(h))``: its own widths, rotary base
    and scale, no indexer; writes the chunk's latent rows into layer
    ``wi`` of the window plane (its place among the window layers) through
    the window table and attends the slots ``0 <= t - s < sliding_window``
    in the pages that cover them, through the kernels the full layers use
    with the window as their mask. Returns (h, the plane)."""
    from ray_tpu.ops import sparse_latent_attention as sla

    B, S, _ = h.shape
    g = cfg.geometry(SWA)
    T = pool_w.shape[2]
    a = _rmsnorm(h, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope(sn.SWA_PROJ):
        _, q_full, latent, _, _ = _latent_proj(a, p, slots, g, cfg)
    with jax.named_scope(sn.SWA_WRITE):
        blk = bt_w[jnp.arange(B)[:, None], slots // T]
        pool_w = pool_w.at[wi, blk, slots % T].set(
            latent.astype(pool_w.dtype))
    with jax.named_scope(sn.SWA_ATTENTION):
        pages, bias, local = _window_pages(bt_w, slots, q_slots, T,
                                           cfg.sliding_window)
        on_chip = jax.default_backend() == "tpu" and T % _LANE == 0
        if S == 1 and on_chip:
            o_lat = sla.sparse_latent_decode(
                q_full[:, 0], pool_w, pages, bias[:, 0], local[:, 0], wi,
                rc=g.kv_rank, sm_scale=g.sm_scale, interpret=False)[:, None]
        else:
            # a page at a time: as ONE gather XLA splits a 1,152-lane row
            # at 640 lanes and copies the WHOLE pool twice to do it (7.6 %
            # of the cell's device time, PERF.md PR 52)
            lat = jax.lax.map(
                lambda page: jax.lax.dynamic_slice(
                    pool_w, (wi, page, 0, 0), (1, 1, T, g.lanes))[0, 0],
                pages.reshape(-1)).reshape(B, -1, g.lanes)
            q = jnp.swapaxes(q_full, 1, 2)              # [B, H, S, lanes]
            # (the kernel's key tile has to be whole lanes: 19 pages, a
            # window eight times this one, would give it 608)
            if on_chip and S % 8 == 0 \
                    and sla._tile(lat.shape[1], 1024) % _LANE == 0:
                o = sla.sparse_latent_attention(
                    q, lat, bias, local, rc=g.kv_rank, sm_scale=g.sm_scale)
            else:
                o = sla.sparse_latent_attention_reference(
                    q, lat, bias, rc=g.kv_rank, sm_scale=g.sm_scale)
            o_lat = jnp.swapaxes(o, 1, 2)
    return _attn_out(h, a, o_lat, p, g, cfg), pool_w


def _kind_runs(kinds):
    """A period's layers as runs of one kind: [(kind, its name, the run's
    first place among the period's layers of that kind, length, how many
    layers of that kind the period has)]."""
    runs, seen = [], {}
    for kind in kinds:
        name = "window" if kind is SWA else "full"
        if runs and runs[-1][0] is kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, name, seen.get(name, 0), 1])
        seen[name] = seen.get(name, 0) + 1
    return [(*r, seen[r[1]]) for r in runs]


def layers_paged(params: Params, toks, pool_c, pool_i, bt, starts,
                 cfg: MlaConfig, *, state=None, bt_w=None, live=None,
                 rows=None, n_valid=None, last_idx=None, final: bool = True,
                 moe_live=None, want_selection: bool = False):
    """This family's stack against its planes (latent ``pool_c`` and index
    ``pool_i`` of the full layers through ``bt``; the window layers'
    ``state["wlatent"]`` through ``bt_w`` where the config has any), as
    `block_pool.ServedConfig.stack` describes it. No recurrent state,
    every layer for every chunk: ``live``, ``rows`` and ``final`` are
    ignored. The expert-layer counts are [n_moe_layers, 4]; the fifth
    result is the state as handed (None without window layers) or, with
    ``want_selection`` (the benchmark's `select_overlap`), which slots
    each query chose in every FULL layer [n_select_layers, B, S, span]
    bool."""
    B, S = toks.shape
    dt = cfg.dtype
    slots = starts[:, None] + jnp.arange(S)[None, :]
    # a chunk's bucket filler queries nothing: its result is never read; a
    # chunk group's padding rows repeat another row, are counted once by
    # `moe_live` and land on their twin's slots, so they ARE read
    real = None if n_valid is None else \
        jnp.arange(S)[None, :] < n_valid[:, None]
    q_slots = slots if real is None else jnp.where(real, slots, -1)
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(dt)[toks]

    def attention(carry, p, li, kind):
        h, pc, pi, pw = carry
        if kind is SWA:
            h, pw = _attention_window(h, p, li, pw, bt_w, slots, q_slots,
                                      cfg)
            return (h, pc, pi, pw), None
        h, pc, pi, sel = _attention(h, p, li, pc, pi, bt, slots, q_slots,
                                    cfg, want_selection)
        return (h, pc, pi, pw), sel

    def dense_body(carry, xs):
        p, li = xs
        (h, *pools), sel = attention(carry, p, li, MLA)
        x = _rmsnorm(h, p["mlp_norm"], cfg.norm_eps)
        with jax.named_scope(sn.MLP):
            gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
            up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
            h = h + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                               p["w_down"].astype(dt))
        return (h, *pools), (None, sel if want_selection else None)

    def moe_body(carry, xs, kind, experts, first):
        # ``li``: the layer's place among its kind (its plane's layer);
        # ``first``: that of the stack's first layer
        p, li = xs
        (h, *pools), sel = attention(carry, p, li, kind)
        x = _rmsnorm(h, p["mlp_norm"], cfg.norm_eps)
        # the expert stacks of ALL the stack's layers go in whole, with
        # this layer's index: nothing of a layer's size is sliced out
        out, st = moe_ffn_dropless(x, {**p, **experts}, cfg, live=moe_live,
                                   expert_stack_layer=li - first,
                                   read=real)
        return (h + out, *pools), (st, sel if want_selection else None)

    def split(stack):
        """(a stack's layers without their expert stacks, those flat)."""
        return ({n: v for n, v in stack.items() if n not in EXPERT_STACKS},
                {n: stack[n].reshape(-1, *stack[n].shape[2:])
                 for n in EXPERT_STACKS})

    def period_body(carry, xs, seg, stacks, first):
        # one period of unlike kinds: its runs of one kind, each a scan
        per, k = xs
        ys = []
        for kind, name, at, n, count in _kind_runs(seg.kinds):
            carry, y = jax.lax.scan(
                functools.partial(moe_body, kind=kind, experts=stacks[name],
                                  first=first[name]),
                carry,
                (jax.tree_util.tree_map(lambda x: x[at:at + n], per[name]),
                 first[name] + k * count + at + jnp.arange(n)))
            ys.append(y)
        return carry, ys

    def in_order(runs):
        # [periods, run, ...] a run -> [the segment's layers, ...]
        runs = [y for y in runs if y is not None]
        return jnp.concatenate(runs, axis=1).reshape(
            -1, *runs[0].shape[2:]) if runs else None

    one_kind = cfg.layer_types is None     # one stack an expert segment
    plan = cfg.layer_plan()
    splits = {seg.name: split(params[seg.name]) if one_kind else
              {name: split(stack)
               for name, stack in params[seg.name].items()}
              for seg in plan if seg.name != "dense"}
    pool_w = None if state is None else state["wlatent"]
    carry = (h, pool_c, pool_i, pool_w)
    stats, chosen = [], []
    for seg in plan:
        if seg.name == "dense":
            carry, (st, sel) = jax.lax.scan(
                dense_body, carry,
                (params["dense"], seg.first_layer + jnp.arange(seg.periods)))
        elif one_kind:
            layers, experts = splits[seg.name]
            carry, (st, sel) = jax.lax.scan(
                functools.partial(moe_body, kind=MLA, experts=experts,
                                  first=cfg.n_dense_layers),
                carry, (layers, seg.first_layer + jnp.arange(seg.periods)))
        else:
            before = cfg.layer_kinds()[:seg.first_layer]
            first = {"full": before.count(MLA), "window": before.count(SWA)}
            pairs = splits[seg.name]
            per = {name: jax.tree_util.tree_map(
                lambda x: x.reshape(seg.periods, -1, *x.shape[1:]), layers)
                for name, (layers, _) in pairs.items()}
            carry, ys = jax.lax.scan(
                functools.partial(
                    period_body, seg=seg, first=first,
                    stacks={name: e for name, (_, e) in pairs.items()}),
                carry, (per, jnp.arange(seg.periods)))
            st = in_order([st for st, _ in ys])
            sel = in_order([sel for _, sel in ys])
        stats.append(st)
        chosen.append(sel)
    h, pool_c, pool_i, pool_w = carry
    if last_idx is not None:
        h = h[jnp.arange(B), last_idx][:, None]
    if want_selection:
        state = jnp.concatenate(chosen)
    elif state is not None:
        state = {**state, "wlatent": pool_w}
    stats = [st for st in stats if st is not None]
    return h, pool_c, pool_i, (
        None if not stats else stats[0] if len(stats) == 1
        else jnp.concatenate(stats)), state


def lm_head(params: Params, h, cfg: MlaConfig):
    """Final RMSNorm and the untied head: [B, S, d] -> f32 [B, S, vocab]."""
    h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
    with jax.named_scope(sn.LM_HEAD):
        return jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Solo generation: the same stack over a private pool
# ---------------------------------------------------------------------------

_SOLO_BLOCK = 32


def init_cache(cfg: MlaConfig, batch_size: int, max_len: int):
    """What `generate.init_cache` is for the other families: the engine's
    planes with a trivial table (row b owns blocks ``1 + b * MB ..
    (b + 1) * MB`` for good, in the window plane too: nothing is released
    behind a solo row's window)."""
    T = _SOLO_BLOCK
    mb = -(-max_len // T)
    nb = 1 + batch_size * mb
    cache = {name: jnp.zeros((pl.layers, nb, T, pl.lanes), pl.dtype)
             for name, pl in zip("ciw", cfg.cache_planes())}
    cache["bt"] = 1 + jnp.arange(batch_size * mb,
                                 dtype=jnp.int32).reshape(batch_size, mb)
    return cache


def forward_cached(params: Params, tokens, cache, start, cfg: MlaConfig,
                   slot_live=None):
    """`generate.forward_cached` for this family: run a chunk [B, S] at
    slot ``start`` of every row. Returns (logits of each row's LAST
    position [B, 1, vocab] f32, cache)."""
    if slot_live is not None:
        raise ValueError(
            "an MlaConfig cannot generate from left-padded prompts "
            "(prompt_live=): its rotary positions are its cache slots "
            "and the indexer scores every slot below a query; batch "
            "prompts of one length, or use the engine")
    B, S = tokens.shape
    h, c, i, _, state = layers_paged(
        params, tokens, cache["c"], cache["i"], cache["bt"],
        jnp.full((B,), start, jnp.int32), cfg,
        state={"wlatent": cache["w"]} if "w" in cache else None,
        bt_w=cache["bt"], last_idx=jnp.full((B,), S - 1, jnp.int32))
    out = {"c": c, "i": i, "bt": cache["bt"]}
    if state is not None:
        out["w"] = state["wlatent"]
    return lm_head(params, h, cfg), out
