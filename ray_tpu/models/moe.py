"""Mixture-of-Experts decoder LM (Mixtral / OLMoE style), TPU-first.

Expert parallelism is a capability the reference lacks entirely
(SURVEY.md §2.4: "Expert parallel (EP/MoE) — absent"); this module is the
new-framework original. Two expert layers live here, one per job:

- SERVING, `moe_ffn_dropless`: what `generate._layer_body` calls for an
  `MoeConfig`, so every cached path (solo `generate`, the dense and the
  paged `DecodeEngine`, fleet replicas) serves a sparse model through the
  programs the dense family uses. Token-choice top-k with NO capacity: no
  assignment is ever dropped, whatever the routing (all tokens on one
  expert included), because a served token that loses an expert is a
  wrong answer. Shapes are static in (rows, chunk), nothing syncs with
  the host, and it is the body of the layer `lax.scan` and of the fused
  decode horizon. Three regimes, chosen from the shapes it is traced
  with: a few tokens (a decode step, most of whose rows may be dead
  slots) multiply every row by every expert some LIVE row chose and read
  no other expert's weights (`ops.hit_experts`: what is read follows the
  number hit, which the program counts each step; a layer that HOLDS a
  share of the experts reads the held ones so); a chunk multiplies
  every row by every expert, since its tokens hit them all and the extra
  FLOPs hide under that read; many tokens (a prefill group) sort the
  assignments by expert and multiply them as ragged groups
  (`jax.lax.ragged_dot`), so the work follows the `tokens x top_k`
  assignments.
- TRAINING, `_moe_ffn` under `moe_forward`: GShard/Switch-style static
  capacity, dispatch/combine as one-hot einsums; assignments over an
  expert's capacity ARE dropped there (the aux load-balancing loss keeps
  the rate low). The expert dimension is a logical axis ("expert") mapped
  to the `ep` mesh axis: dispatch einsums become XLA all-to-alls over
  ICI, expert FFN weights shard E-way with zero code changes.
- Everything else (attention, RoPE, rmsnorm, scanned layers, remat)
  reuses the Llama building blocks. `qk_norm` (OLMoE) adds an RMSNorm
  over the whole q and k projections, before the split into heads and
  before RoPE; the cached paths apply it in `generate._layer_body`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import (LlamaConfig, _attention_call,
                                  _layer_checkpoint, _layer_shapes,
                                  _rmsnorm, _rope)
from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.held_grouped_ffn import (ROW_TILE, held_grouped_ffn,
                                          held_grouped_tiles, visit_schedule)
from ray_tpu.ops.hit_experts import hit_experts_ffn, hit_experts_tile
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_to_mesh

Params = Dict[str, Any]

# an expert layer's three weight stacks, [experts, ...] each
EXPERT_STACKS = ("we_gate", "we_up", "we_down")


@dataclasses.dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25       # training dispatch only
    router_aux_coef: float = 0.01
    # `ffn_dim` is the width of ONE expert. Combine weights are the
    # router's softmax probabilities of the chosen experts, renormalised
    # over the chosen k when `norm_topk_prob` (Mixtral) and left as they
    # are when not (OLMoE).
    norm_topk_prob: bool = True
    # RMSNorm over the whole q / k projection ahead of RoPE (OLMoE).
    qk_norm: bool = False
    # The expert layer's other knobs, read by `moe_ffn_dropless` from any
    # config that has them (`mla.MlaConfig` sets them all). `held_experts`
    # (lo, hi): the router still scores all `n_experts`, the stacks
    # ``we_*`` hold experts lo..hi-1 alone and only the assignments that
    # land on those are computed (one chip's share of an expert-parallel
    # layer, without its exchange); None: every expert is here.
    # `n_shared_experts`: a dense gated FFN beside the routed ones
    # (``ws_*``), added once. `router`: "softmax" over all experts, or
    # "sigmoid_grouped" (DeepSeek-V3: sigmoid scores, a selection bias
    # ``router_bias`` that chooses but does not weigh, `topk_group` of
    # `n_group` groups kept, weights times `routed_scaling_factor`).
    held_experts: Optional[Tuple[int, int]] = None
    n_shared_experts: int = 0
    router: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.remat_policy != "full":
            raise ValueError(
                "MoeConfig supports remat_policy='full' only: "
                "_moe_decoder_layer carries no checkpoint_name tags, so a "
                "named policy would save nothing it names")

    def refusals(self) -> Dict[str, str]:
        """The engine options a sparse model cannot be served with
        (`block_pool.ServedConfig`); `DecodeEngine` itself refuses LoRA
        targets that name the dense feed-forward and a dense draft."""
        return {"tp": "tp=/mesh= cannot serve an MoeConfig: the serving "
                      "sharding rules split the dense 'mlp' width, and the "
                      "expert stacks have no rule yet"}

    @staticmethod
    def mixtral_8x7b(**kw) -> "MoeConfig":
        return MoeConfig(vocab_size=32000, dim=4096, n_layers=32,
                         n_heads=32, n_kv_heads=8, ffn_dim=14336,
                         n_experts=8, top_k=2, **kw)

    @staticmethod
    def olmoe_1b_7b(**kw) -> "MoeConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct: 64 experts of width 1024,
        8 a token, un-renormalised weights, q/k norm, MHA."""
        defaults = dict(vocab_size=50304, dim=2048, n_layers=16,
                        n_heads=16, n_kv_heads=16, ffn_dim=1024,
                        n_experts=64, top_k=8, norm_topk_prob=False,
                        qk_norm=True, rope_theta=10000.0,
                        max_seq_len=4096)
        defaults.update(kw)
        return MoeConfig(**defaults)

    @staticmethod
    def nano_moe(**kw) -> "MoeConfig":
        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, n_experts=4, top_k=2,
                        max_seq_len=128)
        defaults.update(kw)
        return MoeConfig(**defaults)

    def _params_with(self, experts: int) -> int:
        d, f = self.dim, self.ffn_dim
        attn = d * self.n_heads * self.head_dim * 2 + \
            d * self.n_kv_heads * self.head_dim * 2
        norms = 2 * d
        if self.qk_norm:
            norms += (self.n_heads + self.n_kv_heads) * self.head_dim
        moe = experts * 3 * d * f + d * self.n_experts  # experts + router
        return (self.vocab_size * d * 2 + d +
                self.n_layers * (attn + moe + norms))

    def num_params(self) -> int:
        return self._params_with(self.n_experts)

    def active_params(self) -> int:
        """Params touched per token (top-k experts only) — the MFU basis."""
        return self._params_with(self.top_k)


def _moe_layer_shapes(cfg: MoeConfig) -> Dict[str, Any]:
    """Llama attention shapes + expert-stacked FFN + router."""
    d, f, e = cfg.dim, cfg.ffn_dim, cfg.n_experts
    shapes = {k: v for k, v in _layer_shapes(cfg).items()
              if not k.startswith("w_")}  # drop dense FFN
    if cfg.qk_norm:
        shapes.update({
            "q_norm": ((cfg.n_heads * cfg.head_dim,), (None,), None),
            "k_norm": ((cfg.n_kv_heads * cfg.head_dim,), (None,), None),
        })
    shapes.update({
        "w_router": ((d, e), ("embed", None), d),
        "we_gate": ((e, d, f), ("expert", "embed", "mlp"), d),
        "we_up": ((e, d, f), ("expert", "embed", "mlp"), d),
        "we_down": ((e, f, d), ("expert", "mlp", "embed"), f),
    })
    return shapes


def moe_init(rng: jax.Array, cfg: MoeConfig) -> Params:
    """Stacked-layer param tree (`[L, E, d, f]` expert stacks; the router
    initialised like every other matrix). Jit it with `cfg` static to
    build a real-size model on the device in one program."""
    shapes = _moe_layer_shapes(cfg)
    keys = jax.random.split(rng, len(shapes) + 3)
    layers = {}
    for i, (name, (shape, _, fan_in)) in enumerate(shapes.items()):
        if fan_in is None:
            layers[name] = jnp.ones((cfg.n_layers,) + shape,
                                    cfg.param_dtype)
        else:
            layers[name] = (jax.random.normal(
                keys[i], (cfg.n_layers,) + shape) * fan_in ** -0.5
                ).astype(cfg.param_dtype)
    return {
        "tok_embed": (jax.random.normal(
            keys[-3], (cfg.vocab_size, cfg.dim)) * 0.02
            ).astype(cfg.param_dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), cfg.param_dtype),
        "lm_head": (jax.random.normal(
            keys[-1], (cfg.dim, cfg.vocab_size)) * cfg.dim ** -0.5
            ).astype(cfg.param_dtype),
    }


def moe_logical_specs(cfg: MoeConfig) -> Params:
    layer_specs = {name: ("layers",) + logical
                   for name, (_, logical, _f) in
                   _moe_layer_shapes(cfg).items()}
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": layer_specs,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def moe_param_specs(cfg: MoeConfig,
                    rules: Optional[LogicalAxisRules] = None) -> Params:
    return jax.tree_util.tree_map(
        lambda logical: logical_to_mesh(logical, rules),
        moe_logical_specs(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def _route_topk(gates: jax.Array, k: int, renorm: bool = True
                ) -> Tuple[jax.Array, jax.Array]:
    """gates [G,E] -> (weights [G,k], expert_idx [G,k]); weights
    renormalized over the chosen k when `renorm`."""
    weights, idx = jax.lax.top_k(gates, k)
    if renorm:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-9)
    return weights, idx


def qk_norm(q: jax.Array, k: jax.Array, layer: Params, cfg: MoeConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """OLMoE's q/k norm: RMSNorm over the WHOLE projection (all heads of
    a token together), so it comes before the split into heads means
    anything and before RoPE. q [B,S,H,D], k [B,S,KV,D] -> same."""
    def norm(x, scale):
        flat = x.reshape(*x.shape[:2], -1)
        return _rmsnorm(flat, scale, cfg.norm_eps).reshape(x.shape)

    return norm(q, layer["q_norm"]), norm(k, layer["k_norm"])


# Up to this many tokens, every row is multiplied by every expert. The
# weights of all experts are read from HBM either way once a handful of
# tokens spread over them, and computing E rows a token instead of top_k
# hides under that read while tokens < peak FLOP/s over peak bytes/s
# (about 240 on a v5e, whatever d and f are: bytes and FLOPs of an expert
# both scale with d * f). Past that it costs compute, but still less than
# the sort, the gathers and `ragged_dot`'s small groups up to about 700
# tokens. Measured on a v5e at OLMoE's widths (PR 26, PERF.md section 6),
# ms a layer, all-experts / sorted: 64 tokens 1.22 / 2.60, 256 1.25 /
# 2.75, 512 2.30 / 3.03; sorted alone: 2048 4.57, 4096 7.83. The decode
# program alone, 32 slots of which 4 / 8 / 16 / 32 live (PR 34), ms a
# token, this form / the one below: 17.46 / 9.39 (26.7 experts hit), 17.81
# / 12.72 (42.0), 18.43 / 16.20 (56.4), 19.75 / 18.75 (62.7).
DENSE_EXPERTS_MAX_TOKENS = 512
# Up to this many tokens the all-experts form of a whole layer reads only
# the experts a row that is READ chose (`ops.hit_experts`): every hit
# expert still multiplies every row, which costs the MXU no more while a
# weight tile it is handed serves all the rows in one pass (128 of them).
# What is read follows the number hit, counted on the device each step;
# with every expert hit the kernel still streams no slower than XLA's
# einsum. Measured on a v5e at OLMoE's widths (PR 34, PERF.md section 6),
# ms for 12 layers, all-experts einsum / hit-only: 32 rows of which 8 live
# 13.82 / 8.63 (42 experts hit), 32 live 13.82 / 12.80 (63.3); 64 rows,
# all live 13.49 / 12.96; 128 rows 13.53 / 13.00 (64 hit). A layer that
# HOLDS a share reads its held experts through the same kernel
# (`held_hit_kernel`), where it ran a `cond` an expert (`_held_hit`)
# across which nothing is fetched ahead. The decode program alone on a
# v5e, rows of 4,096 tokens (PR 46, `tools/decode_alone.py`, PERF.md
# section 6), ms a token, `cond` form / kernel: Qwen3-Next (128 held of
# 512, 6.3 MB an expert, one step an expert) 16 live rows 12.53 / 9.95
# (33.7 experts hit a layer), 32 15.51 / 11.88 (58.5), 64 19.38 / 14.56
# (88.3); DeepSeek-V3.2 (16 held of 256, 88 MB an expert, 16 steps of 128
# values of f) 12 rows 12.81 / 12.59 (5.25 and 4.75 hit), 24 rows 15.41 /
# 15.05 (8.88 and 8.12: the tokens differ, and 0.76 experts of 88 MB in 4
# layers are 0.33 ms): no slower where 88 MB an expert hid the `cond`, so
# one form serves both and the tile follows from the widths.
HIT_EXPERTS_MAX_TOKENS = 128
# A layer that HOLDS a share of the experts computes, all-experts, E_held
# rows a token where top_k * E_held / E land (32 times the work at 16 of
# 256, 8 a token): there the all-experts form stops at the tokens whose
# extra FLOPs still hide under the read of the held weights.
DENSE_HELD_MAX_TOKENS = 256
# The sorted form of a layer that holds a share works through the
# assignments that landed in windows of this many times their expected
# number (rounded up to `_HELD_ROWS_ALIGN`): one window nearly always,
# more for ANY routing, all assignments on held experts included. The
# slack costs the window's gather and scatter-add, not matmuls, where the
# grouped kernel serves (`ops.held_grouped_ffn`: a row tile past what
# landed is never visited; Qwen3-Next's 4 x 512 chunk gathers 10,240 rows
# and multiplies 167 tiles of 128 for the 5,120 that land). Measured on a
# v5e at Qwen3-Next's widths, 128 held of 512, the stacks of 8 layers (PR
# 45, PERF.md section 6), ms a layer-call, three `ragged_dot` / the
# kernel: 5,120 landed 2.82 / 1.33, 1,280 landed 2.64 / 1.16, 10,240
# landed 3.05 / 1.55 (the 805 MB of a layer take 0.98); the prefill
# program alone, 1 / 2 / 4 rows of 512: 30.5 / 40.5 / 61.1 -> 18.8 / 29.0
# / 48.8 ms.
HELD_ROWS_SLACK = 2.0
_HELD_ROWS_ALIGN = 256


def route_sigmoid_grouped(logits: jax.Array, bias: jax.Array, cfg
                          ) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's router (`topk_method` noaux_tc): f32 logits [G, E] ->
    (weights [G, k], expert ids [G, k]). Sigmoid scores; ``bias`` is added
    to CHOOSE only; a group's score is the sum of its two largest choice
    scores, the `topk_group` best groups stay and the others' choice
    scores become 0; the weights are the chosen experts' scores (without
    the bias), renormalised when `norm_topk_prob`, times
    `routed_scaling_factor`."""
    g, e = logits.shape
    ng, per = cfg.n_group, e // cfg.n_group
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if ng > 1:
        gscore = jax.lax.top_k(choice.reshape(g, ng, per), 2)[0].sum(-1)
        kept = jax.lax.top_k(gscore, cfg.topk_group)[1]          # [G, kg]
        keep = jnp.any(kept[:, :, None] == jnp.arange(ng)[None, None, :],
                       axis=1)                                   # [G, ng]
        choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, 0.0)
    idx = jax.lax.top_k(choice, cfg.top_k)[1]
    weights = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, idx


def _gated_ffn(x, w_gate, w_up, w_down, dt):
    gate = jnp.einsum("gd,df->gf", x, w_gate.astype(dt))
    up = jnp.einsum("gd,df->gf", x, w_up.astype(dt))
    return jnp.einsum("gf,fd->gd", jax.nn.silu(gate) * up,
                      w_down.astype(dt))


def _shared_gate(x, w, dt):
    """``sigmoid(x . w)`` [G, 1]: what scales a GATED shared expert's
    output (a config with `shared_expert_gate`)."""
    return jax.nn.sigmoid(jnp.einsum(
        "gd,d->g", x, w.astype(dt),
        preferred_element_type=jnp.float32))[:, None].astype(dt)


def _held_hit(xf, combine, w1, w3, w2, first, eh: int, dt):
    """The all-experts form of a layer that HOLDS a share, in plain XLA,
    for the tokens the kernel of `ops.hit_experts` does not take
    (`held_hit_kernel`: a chunk of 129 to `DENSE_HELD_MAX_TOKENS` tokens,
    or rows and widths whose step does not fit the scoped VMEM): an
    expert's three matrices are read only if some row chose it, a `cond`
    an expert, across which nothing is fetched ahead. ``combine`` [G,
    E_held] holds each row's weight for each held expert (0: not chosen);
    the experts are ``first .. first + eh - 1`` of the stacks ``w1``/``w3``
    [*, d, f], ``w2`` [*, f, d]."""
    g, d = xf.shape

    def one(j, out):
        w = jax.lax.dynamic_index_in_dim(combine, j, axis=1,
                                         keepdims=True)      # [G, 1]

        def run(out):
            e = first + j
            gate = jnp.einsum("gd,df->gf", xf, w1[e])
            up = jnp.einsum("gd,df->gf", xf, w3[e])
            act = jax.nn.silu(gate) * up * w.astype(dt)
            return out + jnp.einsum("gf,fd->gd", act, w2[e],
                                    preferred_element_type=jnp.float32)

        return jax.lax.cond(jnp.any(w != 0.0), run, lambda out: out, out)

    return jax.lax.fori_loop(0, eh, one,
                             jnp.zeros((g, d), jnp.float32)).astype(dt)


def hit_experts_only(cfg, tokens: int) -> bool:
    """Whether `moe_ffn_dropless` takes, for this many tokens, the form
    that reads only the experts a live row chose (and so wants the stacks
    of all layers: ``expert_stack_layer``)."""
    return cfg.held_experts is None and tokens <= HIT_EXPERTS_MAX_TOKENS


def _expert_width(cfg) -> int:
    return cfg.expert_dim if hasattr(cfg, "expert_dim") else cfg.ffn_dim


def held_hit_kernel(cfg, tokens: int) -> bool:
    """Whether `moe_ffn_dropless` takes, for this many tokens, the kernel
    of `ops.hit_experts` over a HELD range (the held families' stacks are
    those of all layers always, so this is not `hit_experts_only`'s to
    say): at most `HIT_EXPERTS_MAX_TOKENS` rows, and experts of widths at
    which `hit_experts_tile` finds a step that fits the default scoped
    VMEM beside the rows. Said from the config: the engine counts such
    decode blocks."""
    return getattr(cfg, "held_experts", None) is not None \
        and tokens <= HIT_EXPERTS_MAX_TOKENS and hit_experts_tile(
            tokens, cfg.dim, _expert_width(cfg), cfg.dtype) is not None


def _compact_hit(combine):
    """``combine`` [G, E] -> (the columns of the experts some row chose,
    moved to the front in expert order, as rows [E, G]; their ids [E]
    int32; how many, int32). Rows and ids past the count are 0. A one-hot
    select and a sum: no sort, no scatter."""
    e = combine.shape[1]
    chosen = jnp.any(combine != 0.0, axis=0)                     # [E]
    place = jnp.cumsum(chosen, dtype=jnp.int32) - 1
    # [E entries, E experts]: entry i is expert e
    at = chosen[None, :] & (place[None, :] == jnp.arange(e)[:, None])
    ids = jnp.sum(jnp.where(at, jnp.arange(e, dtype=jnp.int32)[None, :], 0),
                  axis=1)
    cw = jnp.sum(jnp.where(at[:, None, :], combine[None], 0.0), axis=2)
    return cw, ids, chosen.sum(dtype=jnp.int32)


def held_grouped_prefill(cfg, tokens: int) -> bool:
    """Whether `moe_ffn_dropless` takes, for this many tokens, the sorted
    form over a held range through the grouped kernel of
    `ops.held_grouped_ffn` (what `_held_sorted` decides from its operands'
    shapes, said from the config: the engine counts such programs)."""
    return getattr(cfg, "held_experts", None) is not None \
        and tokens > DENSE_HELD_MAX_TOKENS and held_grouped_tiles(
            cfg.dim, _expert_width(cfg), cfg.dtype) is not None


def _held_sorted(xf, weights, idx, w1, w3, w2, lo: int, e: int, dt,
                 first=0, eh: Optional[int] = None):
    """The sorted form over the assignments that land on the held experts
    ``lo .. lo + E_held - 1`` alone: the rows of ``idx`` [G, k] that do
    are sorted by expert to the front and multiplied as ragged groups, a
    window of ``c`` rows at a time (`HELD_ROWS_SLACK` times their
    expected number) until all that landed are done, each row's result
    weighted and added to its token. One window nearly always; as many as
    it takes for ANY routing. The held experts are ``first .. first + eh
    - 1`` of the stacks (all of them by default): the stacks of SEVERAL
    layers go in whole, so no layer's weights are sliced out and copied
    (a sixth of a prefill program's time, PERF.md PR 33).

    The matmuls are `ops.held_grouped_ffn`, one kernel a window whose
    grid visits the (group, 128-row tile) pairs that hold rows and reads
    each such group's matrices once: the other layers' groups, a held
    group nobody chose and the window's rows past what landed cost
    nothing. Where a visit's working set does not fit the default scoped
    VMEM (`held_grouped_tiles`: whole rows of d 7,168 beside three weight
    tiles) they stay XLA's `ragged_dot` with the other layers' group
    sizes 0: an empty group costs it 0.05 us, a group that holds rows a
    masked row tile of some hundreds of rows whatever it holds (7.3 us
    at Qwen3-Next's widths where its 2 MiB take 2.6 to read, near the
    bytes at DeepSeek's 29 MB a matrix: PERF.md PR 45). Returns ([G, d]
    in ``dt``, the rows the matmuls computed, a traced int32)."""
    g, d = xf.shape
    k = idx.shape[1]
    eh = w1.shape[0] if eh is None else eh
    n = g * k
    c = min(n, -(-int(HELD_ROWS_SLACK * n * eh / e) // _HELD_ROWS_ALIGN)
            * _HELD_ROWS_ALIGN)
    grouped = held_grouped_tiles(d, w1.shape[2], dt) is not None
    with jax.named_scope(sn.MOE_DISPATCH):
        local = idx.reshape(n) - lo
        key = jnp.where((local >= 0) & (local < eh), local, eh)
        # landed assignments first, by expert; a window may run past n
        order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                        (0, c))
        counts = jnp.bincount(key, length=eh + 1)[:eh].astype(jnp.int32)
        ends = jnp.cumsum(counts).astype(jnp.int32)
        starts = ends - counts
        n_landed = ends[-1]
        wflat = weights.reshape(n)

    def window(i, carry):
        out, visits = carry if grouped else (carry, None)
        base = i * c
        with jax.named_scope(sn.MOE_DISPATCH):
            rows = jax.lax.dynamic_slice(order, (base,), (c,))
            ok = base + jnp.arange(c, dtype=jnp.int32) < n_landed
            tok = rows // k
            xs = xf[tok]                                        # [c, d]
            upto = jnp.clip(ends, base, base + c)
            begin = jnp.clip(starts, base, base + c)
            sizes = upto - begin
            if grouped:
                sched = visit_schedule(begin - base, sizes, c)
            elif w1.shape[0] != eh:   # this layer's groups among all
                sizes = jax.lax.dynamic_update_slice(
                    jnp.zeros((w1.shape[0],), jnp.int32), sizes, (first,))
        with jax.named_scope(sn.MOE_EXPERTS):
            if grouped:
                ys = held_grouped_ffn(xs, sched, first, w1, w3, w2)
            else:
                gate = jax.lax.ragged_dot(xs, w1, sizes)
                up = jax.lax.ragged_dot(xs, w3, sizes)
                ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w2, sizes)
        with jax.named_scope(sn.MOE_DISPATCH):
            # rows past what landed belong to no group: whatever the
            # matmuls left there is not added
            ys = jnp.where(ok[:, None], ys.astype(jnp.float32)
                           * wflat[rows][:, None], 0.0)
            out = out.at[tok].add(ys)
        return (out, visits + sched.n) if grouped else out

    n_win = (n_landed + (c - 1)) // c
    zero = jnp.zeros((g, d), jnp.float32)
    if grouped:     # the kernel multiplied its visits' row tiles
        out, visits = jax.lax.fori_loop(0, n_win, window,
                                        (zero, np.int32(0)))
        return out.astype(dt), visits * np.int32(ROW_TILE)
    out = jax.lax.fori_loop(0, n_win, window, zero)
    return out.astype(dt), n_win * np.int32(c)


def moe_ffn_dropless(x: jax.Array, layer: Params, cfg: MoeConfig,
                     live: Optional[jax.Array] = None,
                     expert_stack_layer=None,
                     read: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The serving expert layer: x [B,S,d] (already normed) -> (sum over
    each token's top-k experts of p_e * down_e(silu(gate_e x) * up_e x)
    as [B,S,d], counters). No capacity: every assignment is computed
    for ANY routing. Rows are independent of one another, so a dead
    slot's or a padded position's row changes no live row's output.

    ``cfg`` is an `MoeConfig` or any config with its expert-layer fields
    (`mla.MlaConfig`). With `held_experts` the sum runs over the chosen
    experts that are HELD here and leaves the others' part out; with
    `n_shared_experts` the shared FFN's output is added once, scaled by
    `_shared_gate` where the config has `shared_expert_gate`.

    ``live`` [B,S] bool marks the rows that are real tokens; given, the
    second result is int32 [3]: (live assignments = live tokens * top_k,
    token-expert rows the expert matmuls computed, experts with at least
    one live assignment), and with `held_experts` a fourth: the live
    assignments that landed on a held expert. None -> no counters are
    traced at all.

    ``expert_stack_layer`` (a layer that holds a share, or one at the few
    tokens of `hit_experts_only`): the stacks ``we_*`` are then those of
    ALL layers, ``[L * E_held, ...]``, and this is the index of the layer
    whose experts to use (traced: a layer scan's). None: the stacks are
    this layer's.

    ``read`` [B,S] bool, where it differs from ``live``: the rows whose
    output anyone reads (a prefill group's padding rows repeat another
    row, are counted once and READ twice). At the few tokens of
    `hit_experts_only` or `held_hit_kernel` a row nobody reads chooses
    nothing: its expert output is zero, and an expert only such rows
    chose is not read."""
    dt = cfg.dtype
    b, s, d = x.shape
    g, e, k = b * s, cfg.n_experts, cfg.top_k
    held = cfg.held_experts
    xf = x.reshape(g, d)
    with jax.named_scope(sn.MOE_ROUTER):
        logits = jnp.einsum("gd,de->ge", xf, layer["w_router"].astype(dt),
                            preferred_element_type=jnp.float32)
        if cfg.router == "sigmoid_grouped":
            weights, idx = route_sigmoid_grouped(
                logits, layer["router_bias"], cfg)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            weights, idx = _route_topk(probs, k, cfg.norm_topk_prob)  # [G,k]
    w1, w3, w2 = (layer[n].astype(dt) for n in EXPERT_STACKS)
    lo, eh = (0, e) if held is None else (held[0], held[1] - held[0])
    dense = g <= (DENSE_EXPERTS_MAX_TOKENS if held is None
                  else DENSE_HELD_MAX_TOKENS)
    hit_only = hit_experts_only(cfg, g) or held_hit_kernel(cfg, g)
    rows = np.int32(g * (eh if dense else k))
    first = 0 if expert_stack_layer is None else expert_stack_layer * eh
    if dense:
        with jax.named_scope(sn.MOE_DISPATCH):
            # [G,E_held] combine matrix: p_e where token g chose held
            # expert e, else 0 (an id outside the held range is no row
            # of the one-hot)
            combine = jnp.sum(
                jax.nn.one_hot(idx if held is None else idx - lo, eh,
                               dtype=jnp.float32)
                * weights[..., None], axis=1)
            if hit_only:
                read = live if read is None else read
                if read is not None:   # a dead row chooses nothing
                    combine = jnp.where(read.reshape(g, 1), combine, 0.0)
                cw, ids, n_hit = _compact_hit(combine)
        with jax.named_scope(sn.MOE_EXPERTS):
            if hit_only:
                out = hit_experts_ffn(
                    xf, cw, first + ids, n_hit, w1, w3, w2,
                    tf=hit_experts_tile(g, d, w1.shape[2], dt)).astype(dt)
                rows = n_hit * np.int32(g)
            elif held is not None:
                out = _held_hit(xf, combine, w1, w3, w2, first, eh, dt)
                rows = jnp.any(combine != 0.0, axis=0).sum(
                    dtype=jnp.int32) * np.int32(g)
            else:
                gate = jnp.einsum("gd,edf->gef", xf, w1)
                up = jnp.einsum("gd,edf->gef", xf, w3)
                act = jax.nn.silu(gate) * up
                # the combine folds into the down projection: one
                # contraction over (expert, f), accumulated in float32
                out = jnp.einsum(
                    "gef,efd->gd", act * combine[..., None].astype(dt), w2,
                    preferred_element_type=jnp.float32).astype(dt)
    elif held is None:
        with jax.named_scope(sn.MOE_DISPATCH):
            flat = idx.reshape(g * k)
            order = jnp.argsort(flat, stable=True)   # by expert
            inverse = jnp.argsort(order)
            group_sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
            xs = xf[order // k]                      # [G*k, d]
        with jax.named_scope(sn.MOE_EXPERTS):
            gate = jax.lax.ragged_dot(xs, w1, group_sizes)
            up = jax.lax.ragged_dot(xs, w3, group_sizes)
            ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w2,
                                    group_sizes)
        with jax.named_scope(sn.MOE_DISPATCH):
            out = jnp.einsum(
                "gkd,gk->gd", ys[inverse].reshape(g, k, d),
                weights.astype(dt),
                preferred_element_type=jnp.float32).astype(dt)
    else:
        out, rows = _held_sorted(xf, weights, idx, w1, w3, w2, lo, e, dt,
                                 first, eh)
    if cfg.n_shared_experts:
        with jax.named_scope(sn.MOE_SHARED):
            shared = _gated_ffn(xf, layer["ws_gate"], layer["ws_up"],
                                layer["ws_down"], dt)
            if getattr(cfg, "shared_expert_gate", False):
                shared = _shared_gate(xf, layer["w_sgate"], dt) * shared
            out = out + shared
    stats = None
    if live is not None:
        with jax.named_scope(sn.MOE_ROUTER):
            lv = live.reshape(g)
            if held is None:
                hit = jnp.zeros((e,), bool).at[idx.reshape(-1)].max(
                    jnp.repeat(lv, k))
                here = []
            else:
                landed = (idx >= lo) & (idx < lo + eh) & lv[:, None]
                hit = jnp.zeros((eh,), bool).at[
                    jnp.clip(idx - lo, 0, eh - 1).reshape(-1)].max(
                    landed.reshape(-1))
                here = [landed.sum(dtype=jnp.int32)]
            stats = jnp.stack([
                lv.sum(dtype=jnp.int32) * k,
                # a numpy scalar: `jnp.int32(...)` would put one on the
                # device and the trace read it back (PERF.md PR 30)
                rows,
                hit.sum(dtype=jnp.int32)] + here)
    return out.reshape(b, s, d), stats


def _moe_ffn(x: jax.Array, layer: Params,
             cfg: MoeConfig) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,d] -> (out [B,S,d], aux_loss scalar). Static-capacity
    token-choice top-k dispatch."""
    dt = cfg.dtype
    b, s, d = x.shape
    g = b * s
    e, k = cfg.n_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * g * k / e))

    xf = x.reshape(g, d)
    router_logits = jnp.einsum(
        "gd,de->ge", xf.astype(jnp.float32),
        layer["w_router"].astype(jnp.float32))
    gates = jax.nn.softmax(router_logits, axis=-1)          # [G,E]
    weights, expert_idx = _route_topk(gates, k, cfg.norm_topk_prob)

    # Position of each (token, choice) within its expert's capacity.
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [G,k,E]
    flat = onehot.reshape(g * k, e)
    # Order: token-major, choice-minor — earlier tokens win capacity.
    position = jnp.cumsum(flat, axis=0) - 1                  # [G*k,E]
    position = (position * flat).sum(-1).reshape(g, k)       # [G,k]
    in_capacity = position < capacity

    # Combine weights [G,k] -> combine tensor [G,E,C] (one-hot einsum).
    keep = weights * in_capacity.astype(weights.dtype)
    pos_onehot = jax.nn.one_hot(position, capacity,
                                dtype=dt)                    # [G,k,C]
    exp_onehot = jax.nn.one_hot(expert_idx, e, dtype=dt)     # [G,k,E]
    combine = jnp.einsum("gk,gke,gkc->gec",
                         keep.astype(dt), exp_onehot, pos_onehot)
    dispatch = (combine > 0).astype(dt)                      # [G,E,C]

    # Expert compute: [E,C,d] batched matmuls (MXU-shaped, ep-sharded).
    expert_in = jnp.einsum("gec,gd->ecd", dispatch, xf.astype(dt))
    gate = jnp.einsum("ecd,edf->ecf", expert_in,
                      layer["we_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", expert_in,
                    layer["we_up"].astype(dt))
    expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                            layer["we_down"].astype(dt))
    out = jnp.einsum("gec,ecd->gd", combine, expert_out)

    # Load-balancing aux loss (Switch/GShard): E * sum_e f_e * p_e.
    me = gates.mean(0)                                       # [E]
    ce = exp_onehot.sum(1).mean(0)                           # [E] frac routed
    aux = e * jnp.sum(me * ce) / k

    return out.reshape(b, s, d), aux.astype(jnp.float32)


def _moe_decoder_layer(carry, layer: Params, positions: jax.Array,
                       cfg: MoeConfig):
    h, aux_sum = carry
    dt = cfg.dtype
    x = _rmsnorm(h, layer["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    kk = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
    if cfg.qk_norm:
        q, kk = qk_norm(q, kk, layer, cfg)
    q = _rope(q, positions, cfg.rope_theta)
    kk = _rope(kk, positions, cfg.rope_theta)
    o = _attention_call(q, kk, v, cfg)
    h = h + jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))

    x = _rmsnorm(h, layer["mlp_norm"], cfg.norm_eps)
    moe_out, aux = _moe_ffn(x, layer, cfg)
    return (h + moe_out, aux_sum + aux)


def moe_forward(params: Params, tokens: jax.Array, cfg: MoeConfig,
                positions: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] -> (logits [B,S,V] f32, mean aux loss)."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape)
    h = params["tok_embed"].astype(cfg.dtype)[tokens]

    layer_fn = functools.partial(_moe_decoder_layer, positions=positions,
                                 cfg=cfg)
    if cfg.remat:
        layer_fn = _layer_checkpoint(layer_fn, cfg.remat_policy)

    def scan_body(carry, layer):
        return layer_fn(carry, layer), None

    (h, aux_sum), _ = jax.lax.scan(
        scan_body, (h, jnp.zeros((), jnp.float32)), params["layers"])
    h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h,
                        params["lm_head"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, aux_sum / cfg.n_layers


def moe_loss(params: Params, batch: Dict[str, jax.Array],
             cfg: MoeConfig) -> jax.Array:
    """Next-token CE + router aux loss."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, aux = moe_forward(params, inputs, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + cfg.router_aux_coef * aux


def moe_flops_per_token(cfg: MoeConfig, seq_len: int) -> float:
    """Training FLOPs/token on ACTIVE params (top-k experts)."""
    attn = 12 * cfg.n_layers * cfg.dim * seq_len
    return 6.0 * cfg.active_params() + attn * 0.5
