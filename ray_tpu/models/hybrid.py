"""Hybrid decoder LM: state-space, window-attention, full-attention and
shared-cache layers in ONE stack (the SambaY / Phi-4-mini-flash shape).

No layer of this family is the Llama layer, so nothing here goes through
`generate._layer_body`; what it shares with the other families is
everything around the layers: the serving engine's programs
(`engine._prefill_rows_paged`, `engine._decode_multi_paged`), its block
pools and tables, the paged kernel, sampling, the ring. The engine hands
`layers_paged` what `engine._layers_paged` gets, plus this family's own
device state, and takes back hidden states.

The stack, read from the config alone (`HybridConfig.layer_plan`), with
``half = n_layers // 2``:

    segment "self":  half/2 periods of [state-space, window attention]
    segment "mid":   1 period of        [state-space, full attention]
    segment "cross": half/2 - 1 periods [gated memory unit, cross-attention]

every layer ``h = h + mixer(LN(h)); h = h + FFN(LN(h))``. A layer is
described by its `LayerKind` (mixer, cache, state) and each segment is one
`lax.scan` over its periods (the middle one, a single period, is inlined),
so 32 layers trace three layer bodies.

Caches by kind. The window layers write K/V into the WINDOW pool
``[half/2, NBw, T, KV*D]`` through the row's window table, and a row holds
only the blocks that intersect the last `sliding_window` slots (the engine
frees what lies behind). The full-attention layer writes the FULL pool
``[1, NB, T, KV*D]`` through the row's ordinary table; the cross-attention
layers own no cache, write nothing and read that pool. The state-space
layers keep ``ssm`` ``[half/2 + 1, slots, d_state, d_inner]`` float32 and
``conv`` ``[half/2 + 1, slots, d_conv - 1, d_inner]`` per engine slot (the
state axis ahead of the channel axis: d_inner fills the lanes, where
``[.., d_inner, 16]`` would pad every 16 to 128).

Differential attention through the one paged kernel. A query pair
``(2j, 2j+1)`` attends KV pair ``j // (H/KV)``: head ``2j`` scores against
key head ``2p``, head ``2j+1`` against ``2p+1``, and both read the two
value heads of the pair as one value 2*D wide. The pools store 20 heads of
64 head-major, so a PAIR is 128 contiguous lanes of a key row and of a
value row. Queries go to the kernel padded to the pair's width, an even
head as ``[q, 0]`` and an odd head as ``[0, q]``: the kernel then sees
``KV/2`` KV heads of ``2*D`` with ``2*H/KV`` query heads each, the score of
``[q, 0]`` against ``[k_2p | k_2p+1]`` IS ``q . k_2p``, and its output is
``A (v_2p | v_2p+1)``, 2*D wide. Subtraction, norm and scale are XLA's
(`diff_combine`). The zero half doubles the score matmul's operations and
moves no byte more.

Prefill skips half the stack: only the segments "self" and "mid" see every
prompt token; a chunk that is not a prompt's last stops there, and the
last one runs "cross" and the head for its last real position alone.
"""

from __future__ import annotations

import dataclasses
import math
import sys
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.block_pool import StatePlane, kv_planes, \
    zero_state_planes
from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.attention import paged_attention

Params = Dict[str, Any]


class LayerKind(NamedTuple):
    """What a layer is made of: its mixer, the cache it WRITES (None: it
    writes none), the cache it reads, and the recurrent state it owns."""

    mixer: str                     # "ssm" | "attn" | "gmu" | "cross" | "mla"
    writes: Optional[str] = None   # "window" | "full" | "latent"
    reads: Optional[str] = None    # "window" | "full" | "latent"
    state: Optional[str] = None    # "ssm"


SSM = LayerKind("ssm", state="ssm")
WINDOW_ATTN = LayerKind("attn", writes="window", reads="window")
FULL_ATTN = LayerKind("attn", writes="full", reads="full")
GMU = LayerKind("gmu")
CROSS_ATTN = LayerKind("cross", reads="full")


# What recurrent state rules out (`ServedConfig.refusals`), said once:
# `gdn.py` adds its ROADMAP ids, and "lora" is `mla.py`'s sentence too.
STATE_REFUSALS = {
    "prefix_cache": "prefix_cache=True: a prefix hit needs a snapshot of "
                    "the recurrent state at the block boundary it resumes "
                    "from, and none is kept",
    "preempt_swap": "preempt='swap': the swap ledger carries K/V blocks "
                    "only, not a row's recurrent state",
    "draft": "draft_params=/draft_cfg=: a rejected draft token has already "
             "advanced the recurrent state, and there is no roll-back of it",
    "lora": "lora=: the adapter targets name the dense family's projections",
    "handoff": "{}: a hand-off carries K/V blocks only, not a row's "
               "recurrent state",
}


class Segment(NamedTuple):
    """``periods`` repeats of the layers ``kinds``, whose parameters are
    stacked ``[periods, ...]`` under ``params[name]`` (no leading axis
    where ``periods == 1``); ``first_layer`` is the stack index of the
    segment's first layer."""

    name: str
    kinds: Tuple[LayerKind, ...]
    periods: int
    first_layer: int


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn_dim: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None      # None: ceil(dim / 16)
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("HybridConfig: mb_per_layer must be 2 (a "
                             "state-space layer every second layer)")
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("HybridConfig: n_layers must be a multiple "
                             "of 4, at least 8")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads \
                or self.n_kv_heads % 2:
            raise ValueError(
                "HybridConfig: heads must divide dim, KV heads the heads, "
                "and KV heads come in pairs (differential attention)")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def rank(self) -> int:
        return self.dt_rank or math.ceil(self.dim / 16)

    @property
    def half(self) -> int:
        return self.n_layers // 2

    @property
    def n_window_layers(self) -> int:
        return self.half // 2

    @property
    def n_ssm_layers(self) -> int:
        return self.half // 2 + 1

    @property
    def full_cache_readers(self) -> int:
        """Layers that read the full-attention layer's cache: itself and
        every cross-attention layer."""
        return self.half // 2

    def layer_plan(self) -> Tuple[Segment, ...]:
        """The stack as segments of periods: the ONE description the
        programs' scans, the engine's pools and the counters are built
        from."""
        n = self.half // 2
        return (Segment("self", (SSM, WINDOW_ATTN), n, 0),
                Segment("mid", (SSM, FULL_ATTN), 1, self.half),
                Segment("cross", (GMU, CROSS_ATTN), n - 1, self.half + 2))

    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        """One `LayerKind` a layer, in stack order."""
        return tuple(k for seg in self.layer_plan()
                     for _ in range(seg.periods) for k in seg.kinds)

    def cache_planes(self):
        """What a token stores (`block_pool.CachePlane`): K and V of the
        ONE full-attention layer behind the row's table (the
        cross-attention layers read it and store nothing), and K and V of
        the window layers behind the window table (``wk`` and ``wv`` of
        the state `layers_paged` is handed)."""
        dt = jnp.dtype(self.dtype)
        return kv_planes("full", 1, self.n_kv_heads, self.head_dim, dt) \
            + kv_planes("window", self.n_window_layers, self.n_kv_heads,
                        self.head_dim, dt, prefix="w")

    def state_planes(self):
        """What a ROW keeps whatever its length (`block_pool.StatePlane`):
        a state-space layer's scan state, float32, and its conv's last
        inputs."""
        return (StatePlane("ssm", self.n_ssm_layers,
                           (self.d_state, self.d_inner),
                           jnp.dtype(jnp.float32)),
                StatePlane("conv", self.n_ssm_layers,
                           (self.d_conv - 1, self.d_inner),
                           jnp.dtype(self.dtype)))

    def prefill_layers(self) -> int:
        """Layers that see every prompt token (the rest run for the one
        position whose logits are wanted)."""
        return self.half + 2

    def refusals(self) -> Dict[str, str]:
        """What would need the recurrent state or the window pool moved,
        shared or split (`block_pool.ServedConfig`)."""
        no, why = "a HybridConfig cannot be served with ", STATE_REFUSALS
        return {
            "prefix_cache": no + why["prefix_cache"],
            "preempt_swap": no + why["preempt_swap"] + " or its window "
            "blocks (pass preempt='recompute')",
            "draft": no + why["draft"],
            "kv_quant": no + "kv_quant=: the window pool and the pair "
            "layout differential attention reads have no quantized write",
            "lora": no + why["lora"],
            "tp": no + "tp=/mesh=: the state-space and memory-unit weights "
            "and the recurrent state have no sharding rule",
            "handoff": no + why["handoff"] + " or its window blocks"}

    def stack(self):
        return sys.modules[__name__]

    @staticmethod
    def phi4_mini_flash(**kw) -> "HybridConfig":
        """microsoft/Phi-4-mini-flash-reasoning, every width."""
        return HybridConfig(**kw)

    @staticmethod
    def nano_hybrid(**kw) -> "HybridConfig":
        defaults = dict(vocab_size=256, dim=64, n_layers=8, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, sliding_window=16,
                        d_state=4, max_seq_len=256, dtype=jnp.float32,
                        param_dtype=jnp.float32)
        defaults.update(kw)
        return HybridConfig(**defaults)

    def num_params(self) -> int:
        d, f, di = self.dim, self.ffn_dim, self.d_inner
        hd, H, KV = self.head_dim, self.n_heads, self.n_kv_heads
        ffn = 3 * d * f + 4 * d                      # + its two norms
        ssm = 2 * d * di + di * (self.rank + 2 * self.d_state) \
            + self.rank * di + di + di * d \
            + di * (self.d_conv + 1 + self.d_state + 1)
        lam = 4 * hd + 2 * hd
        attn = d * (H + 2 * KV) * hd + (H + 2 * KV) * hd + H * hd * d + d \
            + lam
        cross = d * H * hd + H * hd + H * hd * d + d + lam
        gmu = 2 * d * di
        n = self.half // 2
        return (self.vocab_size * d + 2 * d
                + self.n_layers * ffn + (n + 1) * (ssm + attn)
                + (n - 1) * (gmu + cross))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def hybrid_init(key: jax.Array, cfg: HybridConfig) -> Params:
    """Random weights. Matrices normal with std ``fan_in ** -0.5``, the
    embedding (which is also the head, transposed) std 0.02, LayerNorm
    weights 1; the biases a checkpoint has (LayerNorm, `Wqkv`, `out_proj`,
    the conv) normal std 0.02 so that a lost bias moves logits. The
    state-space layers as Mamba publishes them: ``A_log = log(1..N)``,
    ``D = 1``, the `dt` bias the inverse softplus of a log-uniform draw
    in [1e-3, 1e-1], `dt_proj` uniform in ``+- rank ** -0.5``, so state
    lives for hundreds of tokens. The four lambda vectors of an attention
    layer are normal std 0.1, its sub-norm weight 1."""
    d, f, di = cfg.dim, cfg.ffn_dim, cfg.d_inner
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    N, R, dc = cfg.d_state, cfg.rank, cfg.d_conv
    pdt = cfg.param_dtype
    keys = iter(jax.random.split(key, 256))

    def mat(lead, n_in, n_out, std=None):
        std = n_in ** -0.5 if std is None else std
        return (jax.random.normal(next(keys), (*lead, n_in, n_out),
                                  jnp.float32) * std).astype(pdt)

    def vec(lead, n, std=0.02):
        return (jax.random.normal(next(keys), (*lead, n), jnp.float32)
                * std).astype(pdt)

    def norm(lead):
        return {"w": jnp.ones((*lead, d), pdt), "b": vec(lead, d)}

    def ffn(lead):
        return {"w_gate": mat(lead, d, f), "w_up": mat(lead, d, f),
                "w_down": mat(lead, f, d)}

    def lambdas(lead):
        return {"lq1": vec(lead, hd, 0.1), "lk1": vec(lead, hd, 0.1),
                "lq2": vec(lead, hd, 0.1), "lk2": vec(lead, hd, 0.1),
                "subln": jnp.ones((*lead, 2 * hd), pdt)}

    def mamba(lead):
        dt = jnp.exp(jax.random.uniform(
            next(keys), (*lead, di), jnp.float32,
            math.log(1e-3), math.log(1e-1)))
        a_log = jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
            (*lead, N, di))
        return {
            "w_in": mat(lead, d, 2 * di),
            "conv_w": jax.random.uniform(
                next(keys), (*lead, dc, di), jnp.float32, -dc ** -0.5,
                dc ** -0.5).astype(pdt),
            "conv_b": vec(lead, di),
            "w_x": mat(lead, di, R + 2 * N),
            "w_dt": jax.random.uniform(
                next(keys), (*lead, R, di), jnp.float32, -R ** -0.5,
                R ** -0.5).astype(pdt),
            # float32 whatever the weights are: the recurrence is
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": a_log + 0.0,
            "d": jnp.ones((*lead, di), jnp.float32),
            "w_out": mat(lead, di, d),
        }

    def self_period(lead):
        return {
            "m_norm": norm(lead), "mamba": mamba(lead),
            "m_mlp_norm": norm(lead), "m_mlp": ffn(lead),
            "a_norm": norm(lead),
            "attn": {"wqkv": mat(lead, d, (H + 2 * KV) * hd),
                     "bqkv": vec(lead, (H + 2 * KV) * hd),
                     "wo": mat(lead, H * hd, d), "bo": vec(lead, d),
                     **lambdas(lead)},
            "a_mlp_norm": norm(lead), "a_mlp": ffn(lead),
        }

    def cross_period(lead):
        return {
            "g_norm": norm(lead),
            "gmu": {"w_in": mat(lead, d, di), "w_out": mat(lead, di, d)},
            "g_mlp_norm": norm(lead), "g_mlp": ffn(lead),
            "c_norm": norm(lead),
            "attn": {"wq": mat(lead, d, H * hd), "bq": vec(lead, H * hd),
                     "wo": mat(lead, H * hd, d), "bo": vec(lead, d),
                     **lambdas(lead)},
            "c_mlp_norm": norm(lead), "c_mlp": ffn(lead),
        }

    n = cfg.half // 2
    return {
        "tok_embed": mat((), cfg.vocab_size, d, std=0.02),
        "self": self_period((n,)),
        "mid": self_period(()),
        "cross": cross_period((n - 1,)),
        "final_norm": norm(()),
    }


# ---------------------------------------------------------------------------
# Layer math
# ---------------------------------------------------------------------------

def _layernorm(x, p, eps: float):
    with jax.named_scope(sn.NORM):
        x32 = x.astype(jnp.float32)
        mu = x32.mean(axis=-1, keepdims=True)
        xc = x32 - mu
        var = (xc * xc).mean(axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
        return (y * p["w"].astype(jnp.float32)
                + p["b"].astype(jnp.float32)).astype(x.dtype)


def _ffn(h, p_norm, p, cfg: HybridConfig):
    dt = cfg.dtype
    x = _layernorm(h, p_norm, cfg.norm_eps)
    with jax.named_scope(sn.MLP):
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
        return h + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                              p["w_down"].astype(dt))


def _handed_on(y, z):
    """What a state-space layer hands the memory units of the
    cross-decoder: its scan's result ``y`` BEFORE the gate ``z``."""
    del z
    return y


def _starts_fresh(starts):
    """[B] bool: the rows of a prefill group that begin from ZERO
    recurrent state whatever their slot holds: a chunk at slot 0 is an
    admission (or a recompute), every other continues its row."""
    return starts == 0


def ssm_mixer(a, p, ssm0, conv0, live, cfg: HybridConfig):
    """Mamba-1 over a chunk. ``a`` [B, S, d] the normed input; ``ssm0``
    [B, N, di] float32 and ``conv0`` [B, dc-1, di] the state the chunk
    starts from; ``live`` [B, S] bool, a PREFIX of each row (bucket
    filler, frozen and dead rows are not live): only live positions
    advance the state. Returns (mixer output [B, S, d], what the layer
    hands on as memory [B, S, di] (`_handed_on`), ssm1, conv1).

    S == 1 (a decode token) is one update; a chunk is a `lax.scan` over
    its positions, the state float32 in both."""
    dt_ = cfg.dtype
    B, S, _ = a.shape
    di, N, R, dc = cfg.d_inner, cfg.d_state, cfg.rank, cfg.d_conv
    f32 = jnp.float32
    with jax.named_scope(sn.SSM_PROJ):
        xz = jnp.einsum("bsd,de->bse", a, p["w_in"].astype(dt_))
        x, z = xz[..., :di], xz[..., di:]
        padded = jnp.concatenate([conv0.astype(dt_), x], axis=1)
        w = p["conv_w"].astype(dt_)
        xc = p["conv_b"].astype(dt_) + sum(
            padded[:, k:k + S] * w[k] for k in range(dc))
        x = jax.nn.silu(xc)
        # the last dc-1 inputs the row has really seen
        idx = live.sum(axis=1, dtype=jnp.int32)[:, None] \
            + jnp.arange(dc - 1, dtype=jnp.int32)[None, :]
        conv1 = jnp.take_along_axis(padded, idx[:, :, None], axis=1) \
            .astype(conv0.dtype)
    with jax.named_scope(sn.SSM_SCAN):
        dbc = jnp.einsum("bse,er->bsr", x, p["w_x"].astype(dt_))
        dt_r, Bm, Cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
        dt = jax.nn.softplus(
            jnp.einsum("bsr,re->bse", dt_r, p["w_dt"].astype(dt_))
            .astype(f32) + p["b_dt"].astype(f32))            # [B, S, di]
        A = -jnp.exp(p["a_log"].astype(f32))                 # [N, di]
        xf, Bf, Cf = x.astype(f32), Bm.astype(f32), Cm.astype(f32)

        def update(s, dt_t, x_t, b_t, c_t, live_t):
            s1 = jnp.exp(dt_t[:, None, :] * A[None]) * s \
                + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
            y_t = jnp.einsum("bn,bnd->bd", c_t, s1)
            return jnp.where(live_t[:, None, None], s1, s), y_t

        if S == 1:
            ssm1, y = update(ssm0, dt[:, 0], xf[:, 0], Bf[:, 0], Cf[:, 0],
                             live[:, 0])
            y = y[:, None]
        else:
            def step(s, inp):
                return update(s, *inp)

            ssm1, y = jax.lax.scan(
                step, ssm0,
                tuple(jnp.swapaxes(v, 0, 1)
                      for v in (dt, xf, Bf, Cf, live)))
            y = jnp.swapaxes(y, 0, 1)
        y = (y + p["d"].astype(f32) * xf).astype(dt_)
    with jax.named_scope(sn.SSM_PROJ):
        out = jnp.einsum("bse,ed->bsd", y * jax.nn.silu(z),
                         p["w_out"].astype(dt_))
    return out, _handed_on(y, z), ssm1, conv1


def _pad_pairs(q):
    """[B, S, H, D] -> [B, S, H, 2*D]: an even head as ``[q, 0]``, an odd
    head as ``[0, q]``, so that against a key PAIR's 2*D lanes each
    scores its own key head (module docstring)."""
    B, S, H, D = q.shape
    qe = q.reshape(B, S, H // 2, 2, D)
    z = jnp.zeros((B, S, H // 2, D), q.dtype)
    even = jnp.concatenate([qe[:, :, :, 0], z], axis=-1)
    odd = jnp.concatenate([z, qe[:, :, :, 1]], axis=-1)
    return jnp.stack([even, odd], axis=3).reshape(B, S, H, 2 * D)


def lambda_init(layer):
    """``l0`` of stack layer ``layer`` (traced or not)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_combine(o, p, layer, cfg: HybridConfig):
    """The two softmaxes of each query pair, as the kernel returned them
    ``[B, S, H, 2*D]``, into the pair's output: ``(1 - l0) *
    RMSNorm((A1 - l A2) V)`` -> [B, S, H*D]."""
    B, S, H, W = o.shape
    f32 = jnp.float32
    with jax.named_scope(sn.DIFF_COMBINE):
        l0 = lambda_init(layer)
        lam = jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32))) \
            - jnp.exp(jnp.sum(p["lq2"].astype(f32)
                              * p["lk2"].astype(f32))) + l0
        o = o.astype(f32).reshape(B, S, H // 2, 2, W)
        d = o[:, :, :, 0] - lam * o[:, :, :, 1]
        d = d * jax.lax.rsqrt((d * d).mean(axis=-1, keepdims=True)
                              + cfg.norm_eps)
        d = d * p["subln"].astype(f32) * (1.0 - l0)
        return d.astype(cfg.dtype).reshape(B, S, H // 2 * W)


def _attn_out(h, o, p, cfg: HybridConfig):
    with jax.named_scope(sn.ATTN_OUT):
        return h + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(cfg.dtype)) \
            + p["bo"].astype(cfg.dtype)


def lm_head(params: Params, h, cfg: HybridConfig):
    """Final LayerNorm and the tied head: [B, S, d] -> f32 [B, S, vocab]."""
    h = _layernorm(h, params["final_norm"], cfg.norm_eps)
    with jax.named_scope(sn.LM_HEAD):
        return jnp.einsum("bsd,vd->bsv", h,
                          params["tok_embed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The stack against the engine's pools
# ---------------------------------------------------------------------------

def layers_paged(params: Params, toks, pool_k, pool_v, bt, starts,
                 cfg: HybridConfig, *, state, bt_w, live, rows=None,
                 n_valid=None, last_idx=None, final: bool = True,
                 moe_live=None):
    """This family's stack against the pools, as
    `block_pool.ServedConfig.stack` describes it:

      pool_k/v  the FULL pool [1, NB, T, KV*D], through ``bt``
      state     {"wk", "wv", "ssm", "conv"} (the window `cache_planes` and
                the `state_planes`), ``wk``/``wv`` through ``bt_w``

    No expert layers: ``moe_live`` is ignored and the counts are None."""
    B, S = toks.shape
    T = pool_k.shape[2]
    span = bt.shape[1] * T
    hd, KV, W = cfg.head_dim, cfg.n_kv_heads, cfg.sliding_window
    dt = cfg.dtype
    plan = {seg.name: seg for seg in cfg.layer_plan()}
    slots = starts[:, None] + jnp.arange(S)[None, :]
    q_slots = slots if n_valid is None else jnp.where(
        jnp.arange(S)[None, :] < n_valid[:, None], slots, -1)
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(dt)[toks]
    bidx = jnp.arange(B)[:, None]
    fresh = _starts_fresh(starts)[:, None, None]

    # The state's reads and writes carry the scope of the work they feed
    # (the scan's state `ssm_scan`, the conv's `ssm_proj`): they are most
    # of the bytes those scopes move.
    def read_state(st, pi):
        with jax.named_scope(sn.SSM_SCAN):
            ssm = st["ssm"][pi]
            if rows is not None:
                ssm = jnp.where(fresh, 0.0, ssm[rows])
        with jax.named_scope(sn.SSM_PROJ):
            conv = st["conv"][pi]
            if rows is not None:
                conv = jnp.where(fresh, jnp.zeros((), conv.dtype),
                                 conv[rows])
        return ssm, conv

    def write_state(st, pi, ssm1, conv1):
        at = (pi,) if rows is None else (pi, rows)
        with jax.named_scope(sn.SSM_SCAN):
            ssm = st["ssm"].at[at].set(ssm1)
        with jax.named_scope(sn.SSM_PROJ):
            conv = st["conv"].at[at].set(conv1)
        return dict(st, ssm=ssm, conv=conv)

    def write_kv(pool, table, li, x):
        with jax.named_scope(sn.KV_WRITE):
            blk = table[bidx, slots // T]
            return pool.at[li, blk, slots % T].set(
                x.reshape(B, S, -1).astype(pool.dtype))

    def attend(q, pk, pv, table, li, qs, window):
        with jax.named_scope(sn.PAGED_ATTENTION):
            return paged_attention(_pad_pairs(q), pk, pv, table, qs,
                                   layer=li, kv_valid_len=span,
                                   sm_scale=hd ** -0.5, window=window)

    def self_period(h, p, pools, pi, li, layer, kind):
        """[state-space, attention with its own cache]: stack layers
        ``layer`` and ``layer + 1``. ``pools`` = (k, v, table) of the
        cache the attention layer writes; ``pi`` the recurrent state's
        index, ``li`` the cache's layer."""
        st, (pk, pv, table) = pools
        a = _layernorm(h, p["m_norm"], cfg.norm_eps)
        out, y, ssm1, conv1 = ssm_mixer(a, p["mamba"], *read_state(st, pi),
                                        live, cfg)
        st = write_state(st, pi, ssm1, conv1)
        h = _ffn(h + out, p["m_mlp_norm"], p["m_mlp"], cfg)
        a = _layernorm(h, p["a_norm"], cfg.norm_eps)
        ap = p["attn"]
        with jax.named_scope(sn.ATTN_QKV):
            qkv = jnp.einsum("bsd,de->bse", a, ap["wqkv"].astype(dt)) \
                + ap["bqkv"].astype(dt)
            nq = cfg.n_heads * hd
            q = qkv[..., :nq].reshape(B, S, cfg.n_heads, hd)
            k, v = qkv[..., nq:nq + KV * hd], qkv[..., nq + KV * hd:]
        pk, pv = write_kv(pk, table, li, k), write_kv(pv, table, li, v)
        o = attend(q, pk, pv, table, li, q_slots,
                   W if kind.writes == "window" else None)
        h = _attn_out(h, diff_combine(o, ap, layer + 1, cfg), ap, cfg)
        h = _ffn(h, p["a_mlp_norm"], p["a_mlp"], cfg)
        return h, (st, (pk, pv, table)), y

    seg = plan["self"]

    def self_body(carry, xs):
        h, st, wk, wv = carry
        p, k = xs
        h, (st, (wk, wv, _)), _ = self_period(
            h, p, (st, (wk, wv, bt_w)), k, k, seg.first_layer + 2 * k,
            seg.kinds[1])
        return (h, st, wk, wv), None

    st = {"ssm": state["ssm"], "conv": state["conv"]}
    (h, st, wk, wv), _ = jax.lax.scan(
        self_body, (h, st, state["wk"], state["wv"]),
        (params["self"], jnp.arange(seg.periods)))
    seg = plan["mid"]
    h, (st, (pool_k, pool_v, _)), mem = self_period(
        h, params["mid"], (st, (pool_k, pool_v, bt)),
        plan["self"].periods, 0, seg.first_layer, seg.kinds[1])
    state = {"wk": wk, "wv": wv, **st}
    if not final:
        return None, pool_k, pool_v, None, state
    if last_idx is not None:
        # the cross-decoder and the head see ONE position a row
        at = (jnp.arange(B), last_idx)
        h, mem = h[at][:, None], mem[at][:, None]
        q_last = q_slots[at][:, None]
    else:
        q_last = q_slots
    Sq = h.shape[1]
    seg = plan["cross"]

    def cross_body(h, xs):
        p, k = xs
        layer = seg.first_layer + 2 * k
        a = _layernorm(h, p["g_norm"], cfg.norm_eps)
        with jax.named_scope(sn.GMU):
            g = jnp.einsum("bsd,de->bse", a, p["gmu"]["w_in"].astype(dt))
            h = h + jnp.einsum("bse,ed->bsd", mem * jax.nn.silu(g),
                               p["gmu"]["w_out"].astype(dt))
        h = _ffn(h, p["g_mlp_norm"], p["g_mlp"], cfg)
        a = _layernorm(h, p["c_norm"], cfg.norm_eps)
        ap = p["attn"]
        with jax.named_scope(sn.ATTN_QKV):
            q = (jnp.einsum("bsd,de->bse", a, ap["wq"].astype(dt))
                 + ap["bq"].astype(dt)).reshape(B, Sq, cfg.n_heads, hd)
        o = attend(q, pool_k, pool_v, bt, 0, q_last, None)
        h = _attn_out(h, diff_combine(o, ap, layer + 1, cfg), ap, cfg)
        return _ffn(h, p["c_mlp_norm"], p["c_mlp"], cfg), None

    h, _ = jax.lax.scan(cross_body, h,
                        (params["cross"], jnp.arange(seg.periods)))
    return h, pool_k, pool_v, None, state


# ---------------------------------------------------------------------------
# Solo generation: the same stack over a private pool
# ---------------------------------------------------------------------------

_SOLO_BLOCK = 32


def init_cache(cfg: HybridConfig, batch_size: int, max_len: int):
    """What `generate.init_cache` is for the other families: the state solo
    `generate` carries for ``batch_size`` rows of up to ``max_len`` tokens.
    It is the engine's state with a trivial table: row b owns the blocks
    ``1 + b * MB .. (b + 1) * MB`` of both pools for good (nothing is
    freed behind the window here; the window mask does the rest)."""
    T = _SOLO_BLOCK
    mb = -(-max_len // T)
    nb = 1 + batch_size * mb
    return {**{pl.name: jnp.zeros((pl.layers, nb, T, pl.lanes), pl.dtype)
               for pl in cfg.cache_planes()},
            "bt": 1 + jnp.arange(batch_size * mb,
                                 dtype=jnp.int32).reshape(batch_size, mb),
            **zero_state_planes(cfg.state_planes(), batch_size)}


def forward_cached(params: Params, tokens, cache, start, cfg: HybridConfig,
                   slot_live=None):
    """`generate.forward_cached` for this family: run a chunk [B, S] at
    slot ``start`` of every row. Returns (logits of each row's LAST
    position [B, 1, vocab] f32, cache): the layers after the
    full-attention layer and the head see that one position, as in the
    engine's prefill. It has no position ids and cannot skip a pad."""
    if slot_live is not None:
        raise ValueError(
            "a HybridConfig cannot generate from left-padded prompts "
            "(prompt_live=): a state-space layer consumes every token "
            "it is fed; batch prompts of one length, or use the engine")
    B, S = tokens.shape
    h, k, v, _, state = layers_paged(
        params, tokens, cache["k"], cache["v"], cache["bt"],
        jnp.full((B,), start, jnp.int32), cfg,
        state={n: cache[n] for n in ("wk", "wv", "ssm", "conv")},
        bt_w=cache["bt"], live=jnp.ones((B, S), bool),
        last_idx=jnp.full((B,), S - 1, jnp.int32) if S > 1 else None)
    return lm_head(params, h, cfg), {"k": k, "v": v, "bt": cache["bt"],
                                     **state}
