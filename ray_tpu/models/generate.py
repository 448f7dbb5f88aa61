"""Autoregressive generation with a KV cache, TPU-first.

The reference ships no generation loop (models are torch user code);
serving an LM is the flagship deployment though, so the decode path is
first-class here. XLA-friendly by construction: ONE jitted program for
prefill and one for the whole decode loop (`lax.scan` over steps), all
shapes static (cache is preallocated at `max_len`, live length carried
as a traced scalar), GQA K/V heads repeated at attention time only.

Consistency contract (tested): prefill+cached-decode logits equal the
full uncached `llama_forward` on the concatenated sequence.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig, _rmsnorm, _rope
from ray_tpu.models.moe import (EXPERT_STACKS, MoeConfig, hit_experts_only,
                                moe_ffn_dropless, qk_norm)
from ray_tpu.ops import scope_names as sn

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]  # {"k","v": [L, B, max_len, kv_heads, hd]}


def init_cache(cfg: LlamaConfig, batch_size: int,
               max_len: Optional[int] = None,
               sharding=None) -> Cache:
    """Zero KV cache ``[L, B, max_len, KV, D]``. ``sharding`` (an
    optional `jax.sharding.Sharding`) commits both arrays to a device
    mesh — the tensor-parallel engine shards the KV-head axis so each
    chip holds only its heads' cache.

    A family that brings its own stack (`block_pool.ServedConfig.stack`)
    keeps another state, its stack's own `init_cache`: pools behind a
    trivial block table, and what recurrent state it has."""
    max_len = max_len or cfg.max_seq_len
    own = cfg.stack()
    if own is not None:
        return own.init_cache(cfg, batch_size, max_len)
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads,
             cfg.head_dim)
    cache = {"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
    if sharding is not None:
        cache = {k: jax.device_put(v, sharding) for k, v in cache.items()}
    return cache


def _cached_attention(q, k_cache, v_cache, q_slots, kv_valid_len,
                      cfg: LlamaConfig, slot_live=None):
    """q: [B, S, H, D]; caches [B, max_len, KV, D]. Attends q (written
    at cache slots q_slots [B, S]) over cache slots < kv_valid_len,
    causally (slot index <= query slot). ``slot_live`` [B, max_len]
    (optional) additionally masks dead slots — left-pad positions in a
    ragged batch."""
    B, S, H, D = q.shape
    max_len = k_cache.shape[1]
    rep = H // k_cache.shape[2]
    with jax.named_scope(sn.KV_GATHER):
        k = jnp.repeat(k_cache, rep, axis=2)  # [B, max_len, H, D]
        v = jnp.repeat(v_cache, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (D ** -0.5)
    slots = jnp.arange(max_len)
    mask = (slots[None, None, None, :] <= q_slots[:, None, :, None]) \
        & (slots[None, None, None, :] < kv_valid_len)
    if slot_live is not None:
        mask = mask & slot_live[:, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _lora_delta(x, ab, slots, dt):
    """Per-row gathered low-rank delta (S-LoRA): x [B, S, n_in] through
    row-selected adapter factors a [A, n_in, r] / b [A, r, n_out]
    (b pre-scaled by alpha/rank at pool registration) -> [B, S, n_out].
    Slot 0 holds the all-zero null adapter, so base-only rows compute
    an exactly-zero delta inside the same fused program."""
    a = ab["a"][slots].astype(dt)                 # [B, n_in, r]
    b = ab["b"][slots].astype(dt)                 # [B, r, n_out]
    return jnp.einsum("bsr,bro->bso",
                      jnp.einsum("bsi,bir->bsr", x, a), b)


def _expert_stacks(layers: Params, cfg: LlamaConfig, tokens: int):
    """What a layer scan over ``layers`` should slice a layer at a time,
    and what it should not: (the scan's layers, the expert stacks of ALL
    layers as ``[L * E, ...]`` or None). For the few tokens at which an
    `MoeConfig`'s expert layer reads only the experts that were hit
    (`moe.hit_experts_only`) the stacks stay whole beside the scan and
    `_layer_body` is told the layer's index (``experts``): a kernel's
    operand sliced out of the scan's would be COPIED, 805 MB a layer at
    OLMoE's widths. Any other config or size: ``layers`` as they are."""
    if not (isinstance(cfg, MoeConfig) and hit_experts_only(cfg, tokens)):
        return layers, None
    stacks = {n: layers[n].reshape(-1, *layers[n].shape[2:])
              for n in EXPERT_STACKS}
    return ({n: v for n, v in layers.items() if n not in stacks}, stacks)


def _layer_body(h, layer, k_cache, v_cache, positions, write_kv,
                q_slots, kv_valid_len, cfg: LlamaConfig,
                slot_live=None, attend=None, lora=None,
                lora_slots=None, moe_live=None, experts=None,
                moe_read=None):
    """The decoder-layer math shared by ALL cached decode paths —
    generate.py's contiguous-chunk writes, engine.py's per-row
    scatter writes, and the paged engine's block-pool writes: rmsnorm
    → q/k/v projections → RoPE → cache write → causal cached attention
    → attn residual → gated MLP residual.

    The ONLY things that differ between the paths are how this chunk's
    K/V land in storage and how attention reads them back, so exactly
    those are injected: ``write_kv(k_cache, v_cache, k, v) ->
    (k_cache, v_cache)`` always, and optionally ``attend(q, k, v,
    k_cache, v_cache) -> o`` when the storage is not a dense
    [B, max_len] cache row (the paged engine passes
    `ops.attention.paged_attention` over its block pool — which stays op-for-op lockstep with
    `_cached_attention`, so token identity across paths holds). Every
    other op is shared by construction (a norm tweak or attention
    change here reaches every engine automatically).

    Multi-LoRA: ``lora`` (optional) is ONE layer's slice of the
    adapter-pool stacks ({name: {"a": [A, n_in, r], "b": [A, r,
    n_out]}}) and ``lora_slots`` [B] maps each row to its adapter
    slot; every projection named in the stacks gains a per-row
    `_lora_delta` on top of the shared base matmul. Both are pytree
    leaves of the enclosing jit — lora=None paths trace a program
    byte-identical to before this feature existed.

    The model seam: the two places where a family differs are read from
    ``cfg`` HERE and nowhere else. An `MoeConfig` with `qk_norm` norms
    the whole q and k projections ahead of RoPE, and any `MoeConfig`
    replaces the gated MLP by `moe.moe_ffn_dropless`; a `LlamaConfig`
    traces exactly what it always did. The KV side is the same for
    both. ``moe_live`` [B, S] bool (engine programs) marks the rows
    that are real tokens and asks the expert layer for its counters,
    the fourth result (None for a dense model or without the mask).
    ``experts`` (`_expert_stacks`): all layers' expert stacks and this
    layer's index, where the scan's ``layer`` came without them;
    ``moe_read`` [B, S] bool: the rows anyone reads, where those are not
    ``moe_live`` (`moe.moe_ffn_dropless`)."""
    dt = cfg.dtype
    sparse = isinstance(cfg, MoeConfig)
    x = _rmsnorm(h, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope(sn.ATTN_QKV):
        q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
        if lora is not None:
            if "wq" in lora:
                q = q + _lora_delta(x, lora["wq"], lora_slots,
                                    dt).reshape(q.shape)
            if "wk" in lora:
                k = k + _lora_delta(x, lora["wk"], lora_slots,
                                    dt).reshape(k.shape)
            if "wv" in lora:
                v = v + _lora_delta(x, lora["wv"], lora_slots,
                                    dt).reshape(v.shape)
        if sparse and cfg.qk_norm:
            q, k = qk_norm(q, k, layer, cfg)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    with jax.named_scope(sn.KV_WRITE):
        k_cache, v_cache = write_kv(k_cache, v_cache, k, v)
    if attend is not None:
        with jax.named_scope(sn.PAGED_ATTENTION):
            o = attend(q, k, v, k_cache, v_cache)
    else:
        with jax.named_scope(sn.CACHED_ATTENTION):
            o = _cached_attention(q, k_cache, v_cache, q_slots,
                                  kv_valid_len, cfg, slot_live=slot_live)
    with jax.named_scope(sn.ATTN_OUT):
        attn_out = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
        if lora is not None and "wo" in lora:
            o_flat = o.reshape(o.shape[0], o.shape[1], -1)
            attn_out = attn_out + _lora_delta(o_flat, lora["wo"],
                                              lora_slots, dt)
        h = h + attn_out
    x = _rmsnorm(h, layer["mlp_norm"], cfg.norm_eps)
    if sparse:
        stacks, li = ({}, None) if experts is None else experts
        moe_out, moe_stats = moe_ffn_dropless(
            x, {**layer, **stacks}, cfg, moe_live, expert_stack_layer=li,
            read=moe_read)
        return h + moe_out, k_cache, v_cache, moe_stats
    with jax.named_scope(sn.MLP):
        gate = jnp.einsum("bsd,df->bsf", x, layer["w_gate"].astype(dt))
        up = jnp.einsum("bsd,df->bsf", x, layer["w_up"].astype(dt))
        if lora is not None:
            if "w_gate" in lora:
                gate = gate + _lora_delta(x, lora["w_gate"], lora_slots,
                                          dt)
            if "w_up" in lora:
                up = up + _lora_delta(x, lora["w_up"], lora_slots, dt)
        act = jax.nn.silu(gate) * up
        mlp_out = jnp.einsum("bsf,fd->bsd", act,
                             layer["w_down"].astype(dt))
        if lora is not None and "w_down" in lora:
            mlp_out = mlp_out + _lora_delta(act, lora["w_down"],
                                            lora_slots, dt)
        h = h + mlp_out
    return h, k_cache, v_cache, None


def _cached_layer(h, layer, k_cache, v_cache, positions, slot_ids,
                  start, kv_valid_len, cfg: LlamaConfig,
                  slot_live=None, experts=None):
    """One decoder layer over a chunk [B, S, d] whose K/V are WRITTEN
    into the cache at slots [start, start+S); ``positions`` are the
    ROPE position ids (per-row, pad-adjusted in ragged batches) while
    ``slot_ids`` are the cache slot indices the chunk occupies.
    Returns (h, k_cache, v_cache)."""

    def write_kv(k_cache, v_cache, k, v):
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, start, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, start, 0, 0))
        return k_cache, v_cache

    return _layer_body(h, layer, k_cache, v_cache, positions, write_kv,
                       slot_ids, kv_valid_len, cfg,
                       slot_live=slot_live, experts=experts)[:3]


def _layer_xs(layers: Params, cache: Cache, stacks):
    """A dense cache's layer scan's ``xs``: a layer's weights and cache
    rows, and the layer's index where the expert stacks stay whole."""
    xs = (layers, cache["k"], cache["v"])
    if stacks is not None:
        xs += (jnp.arange(cache["k"].shape[0]),)
    return xs


def lm_head(params: Params, h: jax.Array, cfg: LlamaConfig):
    """Final norm and vocab projection, [B, S, d] -> f32 [B, S, vocab], of
    the families whose layers are `_layer_body`'s."""
    h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
    with jax.named_scope(sn.LM_HEAD):
        return jnp.einsum("bsd,dv->bsv", h,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def forward_cached(params: Params, tokens: jax.Array, cache: Cache,
                   start, cfg: LlamaConfig, *,
                   positions: Optional[jax.Array] = None,
                   slot_live: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Cache]:
    """Run a token chunk [B, S] at cache offset `start` (traced scalar
    ok), writing its K/V into the cache. Returns
    (logits [B, S, vocab] f32, updated cache). Prefill is one call with
    the whole prompt; decode is S=1 calls. ``positions`` overrides the
    RoPE position ids (ragged batches: left-pad rows start their real
    tokens at position 0); ``slot_live`` [B, max_len] masks dead (pad)
    cache slots out of every attention.

    A family that brings its own stack runs its own `forward_cached`:
    it takes no position ids, refuses ``slot_live`` (it cannot skip a
    pad), and its logits are the chunk's LAST position's alone,
    [B, 1, vocab]."""
    own = cfg.stack()
    if own is not None:
        return own.forward_cached(params, tokens, cache, start, cfg,
                                  slot_live=slot_live)
    B, S = tokens.shape
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(cfg.dtype)[tokens]
    slot_ids = start + jnp.broadcast_to(jnp.arange(S), (B, S))
    if positions is None:
        positions = slot_ids
    kv_valid_len = start + S

    layers, stacks = _expert_stacks(params["layers"], cfg, B * S)

    def body(carry, xs):
        h = carry
        layer, k_c, v_c = xs[:3]
        h, k_c, v_c = _cached_layer(
            h, layer, k_c, v_c, positions, slot_ids, start, kv_valid_len,
            cfg, slot_live=slot_live,
            experts=None if stacks is None else (stacks, xs[3]))
        return h, (k_c, v_c)

    h, (k_new, v_new) = jax.lax.scan(
        body, h, _layer_xs(layers, cache, stacks))
    return lm_head(params, h, cfg), {"k": k_new, "v": v_new}


def forward_cached_rows(params: Params, tokens: jax.Array, cache: Cache,
                        starts: jax.Array, cfg: LlamaConfig
                        ) -> Tuple[jax.Array, Cache]:
    """Run a token chunk [B, S] with a PER-ROW cache offset: row b's
    tokens land at cache slots ``starts[b] + i`` (scatter writes) and
    attend that row's whole prefix ``[0, starts[b] + i]``. Returns
    (logits [B, S, vocab] f32, updated cache).

    `forward_cached` runs a chunk at ONE shared offset; solo
    speculative decoding (models/speculative.py) feeds rows whose
    frontiers differ — accepted lengths diverge per row — so each chunk
    must continue from its own row's frontier in the same batched
    program. (The serving engine's programs do the same against the
    block pool instead of a dense cache: `engine._layers_paged`.) Rows'
    slots below ``starts[b]`` must already hold valid K/V; slots at or
    beyond the chunk are excluded by the causal ``slot <= q_slot`` mask,
    so stale K/V is never attended. RoPE positions equal cache slots.

    Write-before-attend: the whole chunk's K/V is scattered into the
    cache BEFORE the chunk attends, so re-running a chunk over slots
    whose previous contents are stale simply overwrites them. The
    speculative paths (this one and the engine's) lean on this as their
    no-rollback cache discipline — a rejected draft window's K/V is
    left in place and the next round's verify chunk lands exactly on
    top of it, the causal mask hiding whatever lies beyond the chunk."""
    B, S = tokens.shape
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(cfg.dtype)[tokens]
    slot_ids = starts[:, None] + jnp.arange(S)[None, :]      # [B, S]
    bidx = jnp.arange(B)

    def write_kv(k_cache, v_cache, k, v):
        k_cache = k_cache.at[bidx[:, None], slot_ids].set(
            k.astype(k_cache.dtype))
        v_cache = v_cache.at[bidx[:, None], slot_ids].set(
            v.astype(v_cache.dtype))
        return k_cache, v_cache

    layers, stacks = _expert_stacks(params["layers"], cfg, B * S)

    def body(h, xs):
        layer, k_c, v_c = xs[:3]
        h, k_c, v_c, _ = _layer_body(
            h, layer, k_c, v_c, slot_ids, write_kv, slot_ids,
            k_c.shape[1], cfg,
            experts=None if stacks is None else (stacks, xs[3]))
        return h, (k_c, v_c)

    h, (k_new, v_new) = jax.lax.scan(
        body, h, _layer_xs(layers, cache, stacks))
    return lm_head(params, h, cfg), {"k": k_new, "v": v_new}


def filter_logits(logits: jax.Array, top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jax.Array:
    """Mask logits outside the top-k / nucleus (top-p) candidate set to
    the dtype's min (so `jax.random.categorical` never samples them).

    [..., vocab] -> same shape. Both knobs are STATIC (one XLA program
    per (k, p) pair — serving reuses a handful of compiles); when both
    are given, top-k applies first, then top-p over the survivors (the
    usual composition). top_p=1.0 / top_k>=vocab are no-ops."""
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, logits.dtype)
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_k < logits.shape[-1]:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_p < 1.0:
            idx = jnp.argsort(logits, axis=-1)[..., ::-1]
            sort = jnp.take_along_axis(logits, idx, axis=-1)
            probs = jax.nn.softmax(sort.astype(jnp.float32), axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep tokens whose PRECEDING cumulative mass is still below
            # top_p; the argmax always survives (its preceding mass is 0)
            keep = (cum - probs) < top_p
            # scatter the keep-mask back through the argsort rather than
            # thresholding on the logit VALUE: a token tying the smallest
            # kept logit must not ride into the nucleus and inflate it
            inv = jnp.argsort(idx, axis=-1)
            keep = jnp.take_along_axis(keep, inv, axis=-1)
            logits = jnp.where(keep, logits, neg)
    return logits


@functools.partial(jax.jit, static_argnames=("top_k", "top_p"))
def _sample_token(logits: jax.Array, key: jax.Array, temperature,
                  top_k: Optional[int], top_p: Optional[float]) -> jax.Array:
    """[B, vocab] logits -> [B] sampled int32 (temperature + filters).
    Jitted (static knobs) so the streaming path's per-token sampling is
    one fused program, not op-by-op dispatches of sort/softmax/cumsum;
    inside `generate`'s already-jitted scan it simply inlines."""
    scaled = logits / jnp.maximum(temperature, 1e-6)
    scaled = filter_logits(scaled, top_k, top_p)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def step_rng_key(rng: jax.Array, step) -> jax.Array:
    """The ONE per-step sampling-key schedule: ``fold_in(rng, step)``.

    Deliberately independent of max_new_tokens, of the batch size, and
    of how many steps are fused into one program — the key for a row's
    i-th sampled token depends only on (rng, i). That invariance is
    what lets the continuous-batching engine fuse H decode iterations
    into one program (engine.py `_decode_multi_paged`) and still reproduce a
    request's solo `generate` samples token-for-token: each request
    carries its own rng stream, folded with its own token index, no
    matter which batch companions or horizon boundaries it crosses."""
    return jax.random.fold_in(rng, step)


def sample_rows(logits: jax.Array, row_keys: jax.Array,
                tok_idx: jax.Array, *, greedy: bool, temperature,
                top_k: Optional[int], top_p: Optional[float]) -> jax.Array:
    """Per-ROW sampling inside an already-jitted decode program.

    logits [B, vocab] f32; row_keys [B, 2] uint32 (one rng stream per
    row); tok_idx [B] int32 (tokens that row has sampled so far). Row b
    draws with ``step_rng_key(row_keys[b], tok_idx[b])`` and its own
    categorical — bit-identical to a solo B=1 `generate` seeded with
    that row's rng (counter-mode bits make the [1, vocab] and [vocab]
    draws equal), so batched engine sampling can honor the per-request
    token-identity contract. Greedy ignores keys (argmax)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.vmap(step_rng_key)(row_keys, tok_idx)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    scaled = filter_logits(scaled, top_k, top_p)
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def _check_sampling_knobs(greedy: bool, top_k, top_p) -> None:
    """greedy=True (the default) argmaxes — refuse to silently drop
    explicitly-requested sampling filters."""
    if greedy and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require greedy=False (greedy decoding ignores "
            "sampling filters)")


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "greedy",
                                    "top_k", "top_p"))
def generate(params: Params, prompt: jax.Array, cfg: LlamaConfig, *,
             max_new_tokens: int = 32, temperature: float = 1.0,
             greedy: bool = True, eos_id: Optional[int] = None,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             prompt_live: Optional[jax.Array] = None,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """prompt [B, P] int32 -> [B, P + max_new_tokens] int32.

    One compiled program: prefill writes the prompt's K/V, then a
    `lax.scan` emits max_new_tokens steps (static trip count — XLA
    unrolls nothing, reuses one step computation). With eos_id set,
    finished rows keep emitting eos (scan trip count stays static; the
    caller trims). Sampling (greedy=False) draws from the
    temperature-scaled distribution restricted by `filter_logits`'s
    static top_k / top_p knobs; token i's key is
    ``step_rng_key(rng, i)`` (see its docstring — the schedule is the
    cross-path sampling contract shared with the serving engine).

    Ragged batches: LEFT-pad prompts to a common length and pass
    ``prompt_live`` [B, P] (True = real token). Pad slots are masked
    out of every attention, RoPE positions start at 0 on each row's
    first real token, and every row's last real token lands on slot
    P-1 — so the uniform decode loop serves rows of different prompt
    lengths in one program (see ``pad_prompts``)."""
    B, P = prompt.shape
    max_len = P + max_new_tokens
    if max_len > cfg.max_seq_len:
        raise ValueError(f"{max_len} exceeds max_seq_len "
                         f"{cfg.max_seq_len}")
    _check_sampling_knobs(greedy, top_k, top_p)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, max_len)

    if prompt_live is not None:
        live = prompt_live.astype(bool)
        positions = jnp.maximum(
            jnp.cumsum(live.astype(jnp.int32), axis=1) - 1, 0)
        slot_live = jnp.concatenate(
            [live, jnp.ones((B, max_new_tokens), bool)], axis=1)
        n_real = live.sum(axis=1).astype(jnp.int32)          # [B]
    else:
        positions = None
        slot_live = None
        n_real = jnp.full((B,), P, jnp.int32)

    logits, cache = forward_cached(params, prompt, cache, 0, cfg,
                                   positions=positions,
                                   slot_live=slot_live)
    last = logits[:, -1]

    def sample(logits_row, i):
        if greedy:
            return jnp.argmax(logits_row, axis=-1).astype(jnp.int32)
        return _sample_token(logits_row, step_rng_key(rng, i),
                             temperature, top_k, top_p)

    def step(carry, i):
        cache, last_logits, slot, pos_ids, done = carry
        with jax.named_scope(sn.SAMPLE):
            tok = sample(last_logits, i)
            if eos_id is not None:
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
        logits, cache = forward_cached(
            params, tok[:, None], cache, slot, cfg,
            positions=pos_ids[:, None], slot_live=slot_live)
        return (cache, logits[:, 0], slot + 1, pos_ids + 1, done), tok

    done0 = jnp.zeros((B,), bool)
    (_, _, _, _, _), toks = jax.lax.scan(
        step, (cache, last, P, n_real, done0),
        jnp.arange(max_new_tokens))
    return jnp.concatenate([prompt, toks.T], axis=1)


# Donated cache: each step consumes the previous cache exactly once —
# without donation every step would COPY the whole [L,B,max_len,KV,D]
# cache across the jit boundary (multi-GB per token at real configs).
@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _prefill_jit(params, prompt, cache, cfg, positions=None,
                 slot_live=None):
    return forward_cached(params, prompt, cache, 0, cfg,
                          positions=positions, slot_live=slot_live)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _decode_step_jit(params, tok, cache, slot, pos_ids, cfg,
                     slot_live=None):
    return forward_cached(params, tok[:, None], cache, slot, cfg,
                          positions=pos_ids[:, None],
                          slot_live=slot_live)


def generate_stream(params, prompt, cfg: LlamaConfig, *,
                    max_new_tokens: int = 32,
                    eos_id: Optional[int] = None,
                    temperature: float = 1.0, greedy: bool = True,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    prompt_live: Optional[jax.Array] = None,
                    rng: Optional[jax.Array] = None):
    """Decode as a PYTHON GENERATOR yielding one [B] token
    array per step — the token-streaming serving path (each step is
    one cached jitted program with a donated KV cache; `generate`'s
    scanned loop is the lower-latency batch path when streaming isn't
    needed). Stops early when every row has emitted eos. Ragged
    batches: LEFT-pad and pass ``prompt_live`` exactly as with
    `generate`. Sampling (greedy=False, temperature/top_k/top_p) uses
    `generate`'s exact per-step key schedule, so a streamed run with
    the same rng yields token-identical output to the batch path.

    Validation runs EAGERLY (this is a plain function returning the
    generator): bad knobs fail at the call site, not mid-stream at the
    first next()."""
    B, P = prompt.shape
    max_len = P + max_new_tokens
    if max_len > cfg.max_seq_len:
        raise ValueError(f"{max_len} exceeds max_seq_len "
                         f"{cfg.max_seq_len}")
    _check_sampling_knobs(greedy, top_k, top_p)
    return _stream_inner(params, prompt, cfg, max_new_tokens, eos_id,
                         temperature, greedy, top_k, top_p,
                         prompt_live, rng)


def _stream_inner(params, prompt, cfg, max_new_tokens, eos_id,
                  temperature, greedy, top_k, top_p, prompt_live, rng):
    import numpy as np

    B, P = prompt.shape
    max_len = P + max_new_tokens
    cache = init_cache(cfg, B, max_len)
    if prompt_live is not None:
        live = prompt_live.astype(bool)
        positions = jnp.maximum(
            jnp.cumsum(live.astype(jnp.int32), axis=1) - 1, 0)
        slot_live = jnp.concatenate(
            [live, jnp.ones((B, max_new_tokens), bool)], axis=1)
        pos = live.sum(axis=1).astype(jnp.int32)
    else:
        positions = None
        slot_live = None
        pos = jnp.full((B,), P, jnp.int32)
    logits, cache = _prefill_jit(params, prompt, cache, cfg,
                                 positions=positions,
                                 slot_live=slot_live)
    last = logits[:, -1]
    done = np.zeros((B,), bool)
    if not greedy:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
    for step in range(max_new_tokens):
        if greedy:
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            tok = _sample_token(last, step_rng_key(rng, step),
                                temperature, top_k, top_p)
        if eos_id is not None:
            tok = jnp.where(jnp.asarray(done), eos_id, tok)
        tok_np = np.asarray(tok)  # graftlint: disable=host-sync -- solo streaming yields one host token per step by contract; the engine path amortises via _device_get
        yield tok_np
        if eos_id is not None:
            done = done | (tok_np == eos_id)
            if done.all():
                return
        if step + 1 < max_new_tokens:
            logits, cache = _decode_step_jit(
                params, tok, cache, P + step, pos + step, cfg,
                slot_live=slot_live)
            last = logits[:, 0]


def pad_prompts(prompts, pad_id: int = 0, *, bucket_len: bool = False,
                pad_batch_to: Optional[int] = None):
    """Left-pad a ragged list of token lists to a dense [B, P] array +
    the matching ``prompt_live`` mask for `generate`.

    Empty prompts are rejected: a fully-dead row has no last real
    token to sample from (its attention would be all-masked garbage) —
    prepend a BOS token instead.

    Serving knobs (jit-cache hygiene — every distinct (B, P) pair is a
    separate XLA compile): ``bucket_len=True`` rounds P up to the next
    power of two, and ``pad_batch_to=N`` appends single-token filler
    rows up to batch N (the CALLER slices its outputs back to the real
    row count) — together a handful of compiles cover all traffic."""
    import numpy as np

    if not prompts:
        raise ValueError("pad_prompts needs at least one prompt")
    if any(len(p) == 0 for p in prompts):
        raise ValueError(
            "empty prompt: generation needs at least one real token "
            "per row (prepend a BOS token)")
    n_rows = len(prompts)
    rows = list(prompts)
    if pad_batch_to is not None and n_rows < pad_batch_to:
        rows += [[pad_id]] * (pad_batch_to - n_rows)
    P = max(len(p) for p in rows)
    if bucket_len:
        P = 1 << (P - 1).bit_length()
    out = np.full((len(rows), P), pad_id, np.int32)
    live = np.zeros((len(rows), P), bool)
    for i, p in enumerate(rows):
        out[i, P - len(p):] = np.asarray(p, np.int32)
        live[i, P - len(p):] = True
    return out, live
