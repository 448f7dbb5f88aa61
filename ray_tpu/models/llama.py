"""Llama-family decoder LM, TPU-first.

Design choices (vs a torch translation):
- Pure functional: params are a pytree dict; a parallel tree of logical
  axis names drives GSPMD sharding (ray_tpu.parallel.sharding rules map
  them onto the dp/fsdp/tp/sp mesh).
- All layers are stacked and iterated with `lax.scan` ("scanned layers"),
  so compile time is O(1) in depth and XLA pipelines the weight
  all-gathers of layer i+1 under the compute of layer i.
- bf16 activations / f32 master params by default; matmuls hit the MXU.
- Attention via ray_tpu.ops (Pallas flash attention on TPU; ring
  attention over the `sp` axis for long context).
- `jax.checkpoint` (remat) per layer to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import attention
from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.sharding import logical_to_mesh, LogicalAxisRules

Params = Dict[str, Any]

# checkpoint_name tags available to remat_policy="save:...". Each marks
# one dot output in _decoder_layer; saving it exempts that matmul (and
# everything downstream of it that is also saved) from the backward-pass
# recompute. ffn_gate+ffn_up are the FLOPs-heaviest (2/3 of the MLP);
# qkv covers the three attention input projections.
REMAT_SAVE_NAMES = frozenset(
    {"qkv", "attn_out", "wo_out", "ffn_gate", "ffn_up", "ffn_down"})


def _parse_save_names(policy: str) -> list:
    """'save:a+b' -> ['a', 'b']; raises on empty or unknown names."""
    names = [n for n in policy[len("save:"):].split("+") if n]
    bad = [n for n in names if n not in REMAT_SAVE_NAMES]
    if not names or bad:
        raise ValueError(
            f"remat_policy {policy!r}: "
            + (f"unknown names {bad}" if bad else "no names given")
            + f" (valid: {sorted(REMAT_SAVE_NAMES)})")
    return names


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # activation dtype
    param_dtype: Any = jnp.float32     # master weights
    remat: bool = True
    # Per-layer checkpoint policy: "full" recomputes the layer from its
    # input (min HBM), "save_dots" keeps matmul outputs (recompute only
    # cheap elementwise — more HBM, fewer recomputed FLOPs), or
    # "save:<name>+<name>+..." keeps only the NAMED dot outputs
    # (checkpoint_name tags in _decoder_layer) — the HBM/recompute
    # frontier in between. Valid names: REMAT_SAVE_NAMES.
    # One exception under EVERY policy, "full" included: where the flash
    # kernel runs, its output and row logsumexp are kept
    # (_layer_checkpoint), so the backward pass never re-runs the
    # forward kernel. Cost: one activation-dtype [B, S, H*D] array a
    # layer (+ a [B, H, S] f32 statistic), i.e. "full" saves two carries
    # a layer instead of one: L x B x S x H*D x 2 bytes a chip more
    # (InternLM2-1.8B, 4 sequences of 4,096 a chip: +1.52 GiB; a 7B at
    # one sequence of 4,096 a chip: +1 GiB). There is no switch: no
    # other byte buys as much recompute (64 MiB a layer for 6 % of that
    # step; the FFN's activations would need 512 MiB for 7.7 %), and the
    # recompute it removes grows with S as the array does. A trainer at
    # its memory limit gives the bytes back through batch or S.
    remat_policy: str = "full"
    attn_impl: str = "auto"            # auto|flash|reference|ring
    ring_axis: str = "sp"
    # Flash-kernel tile sizes (None = kernel default). Chip-dependent:
    # larger tiles amortize the per-block softmax rescale; sweep with
    # tools/remat_sweep.py-style timing before changing.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # Cross-entropy sequence chunking: compute the vocab projection +
    # softmax loss loss_chunk tokens at a time (lax.map + remat) instead
    # of materializing the full [B, S, vocab] f32 logits. At S=2048 this
    # is MFU-neutral (measured; XLA handles the 2 GiB fine) — its purpose
    # is long-context training, where S=32k logits (e.g. B4xS32k x 32k
    # vocab = 16 GiB f32) cannot exist. None = unchunked. Ignored when
    # S % loss_chunk != 0.
    loss_chunk: Optional[int] = None

    def __post_init__(self):
        # validated here, not in dispatch: every attention path (flash,
        # ring, ulysses) receives these
        for nm in ("flash_block_q", "flash_block_k"):
            b = getattr(self, nm)
            if b is not None and b <= 0:
                raise ValueError(f"{nm} must be positive, got {b}")
        if self.remat_policy in ("full", "save_dots"):
            return
        if self.remat_policy.startswith("save:"):
            _parse_save_names(self.remat_policy)
            return
        raise ValueError(
            f"unknown remat_policy {self.remat_policy!r} "
            "(expected 'full', 'save_dots', or 'save:<names>')")

    def cache_planes(self):
        """What a token stores (`block_pool.CachePlane`): keys and values
        of every KV head in every layer."""
        from ray_tpu.models.block_pool import kv_planes

        return kv_planes("full", self.n_layers, self.n_kv_heads,
                         self.head_dim, jnp.dtype(self.dtype))

    # `block_pool.ServedConfig`'s other questions: no recurrent state, no
    # option refused, `generate._layer_body`'s layers, all for every token.
    def state_planes(self):
        return ()

    def refusals(self) -> Dict[str, str]:
        return {}

    def stack(self):
        return None

    def prefill_layers(self) -> int:
        return self.n_layers

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # ---- presets ----
    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                           ffn_dim=13824, **kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, dim=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, ffn_dim=14336,
                           rope_theta=500000.0, max_seq_len=8192, **kw)

    @staticmethod
    def nano(**kw) -> "LlamaConfig":
        """Tiny config for tests / dryruns (runs on the CPU mesh)."""
        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                        dtype=jnp.float32, remat=False)
        defaults.update(kw)
        return LlamaConfig(**defaults)

    def num_params(self) -> int:
        d, v, f, L = self.dim, self.vocab_size, self.ffn_dim, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
            + self.n_heads * hd * d
        mlp = 3 * d * f
        return v * d + L * (attn + mlp + 2 * d) + d + d * v


# ---------------------------------------------------------------------------
# Parameter init + sharding specs
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: LlamaConfig) -> Dict[str, Any]:
    """name -> (shape, logical axes, fan_in of the contraction)."""
    d, hd = cfg.dim, cfg.head_dim
    return {
        "wq": ((d, cfg.n_heads, hd), ("embed", "heads", "head_dim"), d),
        "wk": ((d, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim"), d),
        "wv": ((d, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim"), d),
        "wo": ((cfg.n_heads, hd, d), ("heads", "head_dim", "embed"),
               cfg.n_heads * hd),
        "w_gate": ((d, cfg.ffn_dim), ("embed", "mlp"), d),
        "w_up": ((d, cfg.ffn_dim), ("embed", "mlp"), d),
        "w_down": ((cfg.ffn_dim, d), ("mlp", "embed"), cfg.ffn_dim),
        "attn_norm": ((d,), ("embed",), None),
        "mlp_norm": ((d,), ("embed",), None),
    }


def llama_init(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Stacked-layer param tree: every per-layer leaf has leading [n_layers]."""
    shapes = _layer_shapes(cfg)
    keys = jax.random.split(rng, len(shapes) + 3)

    layers = {}
    for i, (name, (shape, _, fan_in)) in enumerate(shapes.items()):
        if fan_in is None:  # norm scales
            layers[name] = jnp.ones((cfg.n_layers,) + shape, cfg.param_dtype)
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


def llama_logical_specs(cfg: LlamaConfig) -> Params:
    """Tree of logical-axis tuples matching llama_init's tree."""
    layer_specs = {name: ("layers",) + logical
                   for name, (_, logical, _f) in _layer_shapes(cfg).items()}
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": layer_specs,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def llama_param_specs(cfg: LlamaConfig,
                      rules: Optional[LogicalAxisRules] = None) -> Params:
    """Tree of PartitionSpecs for the param tree under the given rules."""
    return jax.tree_util.tree_map(
        lambda logical: logical_to_mesh(logical, rules),
        llama_logical_specs(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    with jax.named_scope(sn.NORM):
        x32 = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (x32 * rms).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def _attention_call(q, k, v, cfg: LlamaConfig):
    """q,k,v: [B, S, H, D] -> [B, S, H, D]."""
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    blocks = {k_: v_ for k_, v_ in (("block_q", cfg.flash_block_q),
                                    ("block_k", cfg.flash_block_k))
              if v_ is not None}
    if cfg.attn_impl == "ring":
        out = ring_attention(qT, kT, vT, axis_name=cfg.ring_axis,
                             causal=True, **blocks)
    elif cfg.attn_impl == "ulysses":
        from ray_tpu.ops.ulysses import ulysses_attention

        out = ulysses_attention(qT, kT, vT, axis_name=cfg.ring_axis,
                                causal=True, **blocks)
    else:
        out = attention(qT, kT, vT, causal=True, impl=cfg.attn_impl,
                        **blocks)
    return out.transpose(0, 2, 1, 3)


def _decoder_layer(h: jax.Array, layer: Params, positions: jax.Array,
                   cfg: LlamaConfig) -> jax.Array:
    dt = cfg.dtype
    name = jax.ad_checkpoint.checkpoint_name
    x = _rmsnorm(h, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope(sn.ATTN_QKV):
        q = name(jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt)),
                 "qkv")
        k = name(jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt)),
                 "qkv")
        v = name(jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt)),
                 "qkv")
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    with jax.named_scope(sn.ATTENTION):
        o = name(_attention_call(q, k, v, cfg), "attn_out")
    with jax.named_scope(sn.ATTN_OUT):
        h = h + name(jnp.einsum("bshk,hkd->bsd", o,
                                layer["wo"].astype(dt)), "wo_out")

    x = _rmsnorm(h, layer["mlp_norm"], cfg.norm_eps)
    with jax.named_scope(sn.MLP):
        gate = name(jnp.einsum("bsd,df->bsf", x,
                               layer["w_gate"].astype(dt)), "ffn_gate")
        up = name(jnp.einsum("bsd,df->bsf", x, layer["w_up"].astype(dt)),
                  "ffn_up")
        h = h + name(jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                                layer["w_down"].astype(dt)), "ffn_down")
    return h


def _layer_checkpoint(layer_fn, remat_policy: str):
    """`jax.checkpoint` of one decoder layer under `remat_policy`
    (validated in LlamaConfig.__post_init__). Every policy also keeps
    the flash kernel's output and row statistics
    (`FLASH_RESIDUAL_NAMES`), so the backward pass recomputes what XLA
    computes and never re-runs the attention kernel. Where no kernel
    runs (`attn_impl="reference"`) no such name exists and each policy
    saves what it names and nothing more."""
    policies = jax.checkpoint_policies
    names = list(FLASH_RESIDUAL_NAMES)
    if remat_policy.startswith("save:"):
        names += _parse_save_names(remat_policy)
    keep = policies.save_only_these_names(*names)
    if remat_policy == "save_dots":
        keep = policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, keep)
    return jax.checkpoint(layer_fn, policy=keep)


def llama_hidden(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                 positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 -> final-norm hidden states [B, S, dim]
    (activation dtype) — the backbone without the vocab projection."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape)
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(cfg.dtype)[tokens]

    layer_fn = functools.partial(_decoder_layer, positions=positions, cfg=cfg)
    if cfg.remat:
        layer_fn = _layer_checkpoint(layer_fn, cfg.remat_policy)

    def scan_body(h, layer):
        return layer_fn(h, layer), None

    h, _ = jax.lax.scan(scan_body, h, params["layers"])
    return _rmsnorm(h, params["final_norm"], cfg.norm_eps)


def llama_forward(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                  positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (float32)."""
    h = llama_hidden(params, tokens, cfg, positions)
    with jax.named_scope(sn.LM_HEAD):
        logits = jnp.einsum("bsd,dv->bsv", h,
                            params["lm_head"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    return logits


def _nll(h: jax.Array, targets: jax.Array, lm_head: jax.Array,
         cfg: LlamaConfig) -> jax.Array:
    """[.., S, d] hidden + [.., S] targets -> [.., S] token nll (f32)."""
    with jax.named_scope(sn.LM_HEAD):
        logits = jnp.einsum("...sd,dv->...sv", h,
                            lm_head.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    with jax.named_scope(sn.LOSS):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0]


def llama_loss(params: Params, batch: Dict[str, jax.Array],
               cfg: LlamaConfig) -> jax.Array:
    """Next-token cross-entropy. batch: {'tokens': [B,S]} or
    {'inputs': [B,S], 'targets': [B,S]} (optional 'mask').

    With cfg.loss_chunk set (and dividing S), the vocab projection +
    softmax run loss_chunk tokens at a time under lax.map + remat: the
    [B, S, vocab] f32 logits are never materialized and the backward
    recomputes one chunk's projection instead of saving softmax
    residuals for the whole sequence — identical loss/grads (tested),
    lower HBM traffic."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        mask = None
    h = llama_hidden(params, inputs, cfg)
    B, S = targets.shape
    chunk = cfg.loss_chunk
    if chunk and S % chunk == 0 and S > chunk:
        n = S // chunk
        h_c = h.reshape(B, n, chunk, cfg.dim).transpose(1, 0, 2, 3)
        t_c = targets.reshape(B, n, chunk).transpose(1, 0, 2)
        nll = jax.lax.map(
            jax.checkpoint(lambda ht: _nll(ht[0], ht[1],
                                           params["lm_head"], cfg)),
            (h_c, t_c))                      # [n, B, chunk]
        nll = nll.transpose(1, 0, 2).reshape(B, S)
    else:
        nll = _nll(h, targets, params["lm_head"], cfg)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def llama_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (fwd+bwd): 6*N + attention term."""
    n = cfg.num_params()
    attn = 12 * cfg.n_layers * cfg.dim * seq_len  # causal: *0.5 of full
    return 6.0 * n + attn * 0.5
