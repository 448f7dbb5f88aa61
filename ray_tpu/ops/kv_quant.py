"""Low-bit paged-KV quantization: per-block, per-kv-head scales.

The paged engine stores its KV pool as ``[L, NB, T, KV*D]`` blocks (a
token's KV heads merged head-major into one lane axis); to
double the concurrent requests per HBM byte the pool can instead hold
int8 (qmax 127) or fp8-e4m3 (qmax 448) values plus a parallel f32 scale
slab shaped ``[L, NB, KV]`` — one scale per block per kv head, indexed
by the SAME physical block ids as the pages so the refcounted BlockPool
ledger covers both with no extra alloc/free sites.

Quantization is symmetric absmax: ``s = amax / qmax`` over a block's
valid slots (``s = 1.0`` for all-zero blocks so dequant stays exact and
finite), ``q = round_or_cast(clip(x / s, -qmax, qmax))``, dequant
``x' = q.astype(f32) * s``.  Two properties the engine leans on:

* **Requantization is byte-stable.** Re-quantizing a dequantized block
  with a freshly recomputed scale reproduces the identical bytes: the
  recomputed ``amax' = max|q|*s`` differs from ``amax`` only by float
  rounding, so ``s'/s = 1 ± O(2^-23)`` and ``round(q * s/s')`` (int8) /
  nearest-fp8 rounding (e4m3, whose relative spacing is ≥ 2^-3) lands
  back on ``q`` exactly.  This is what makes a swap-out / swap-in or a
  copy-on-write of a block lossless, and what lets `paged_quant_write`
  take a half-filled frontier block up again — provided the
  dequantized values stay float32 end to end (a bf16 round-trip would
  break it). (No program re-writes a block it did not touch: prefill
  writes the blocks its chunk falls in and none below its start.)
* **Stale slots are zeroed at every write.** A block's scale is an
  absmax over ALL its slots, so garbage left by a previous tenant (or a
  rejected speculative window) would silently coarsen the valid tokens'
  quantization.  Every write site therefore zeroes slots at/beyond the
  row's written frontier before recomputing the scale.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "KVQuantSpec",
    "KV_QUANT_MODES",
    "resolve_kv_quant",
    "block_scale",
    "quantize",
    "dequantize",
    "paged_quant_write",
]

KV_QUANT_MODES = ("int8", "fp8_e4m3")


@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Hashable description of one quantized-KV mode (safe to pass as a
    jit static argument: all fields are plain python scalars)."""

    name: str         # "int8" | "fp8_e4m3"
    dtype_name: str   # numpy dtype name of the stored pool values
    qmax: float       # largest representable magnitude pre-scale
    itemsize: int = 1  # bytes per stored value

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def is_int(self) -> bool:
        return self.name == "int8"


_SPECS = {
    "int8": KVQuantSpec("int8", "int8", 127.0, 1),
    "fp8_e4m3": KVQuantSpec("fp8_e4m3", "float8_e4m3fn", 448.0, 1),
}


def resolve_kv_quant(name: Optional[str]) -> Optional[KVQuantSpec]:
    """Map an engine-level ``kv_quant`` knob to a spec (None -> None)."""
    if name is None:
        return None
    spec = _SPECS.get(name)
    if spec is None:
        raise ValueError(
            f"kv_quant must be one of {KV_QUANT_MODES} or None, got "
            f"{name!r}")
    return spec


def block_scale(amax: jax.Array, qspec: KVQuantSpec) -> jax.Array:
    """amax -> scale with the all-zero guard (scale 1.0 so dequant of a
    zero block is exactly zero and never divides by zero)."""
    return jnp.where(amax > 0, amax / qspec.qmax, 1.0).astype(jnp.float32)


def quantize(x: jax.Array, scale: jax.Array,
             qspec: KVQuantSpec) -> jax.Array:
    """``x`` f32 -> stored dtype; ``scale`` must broadcast against x."""
    y = jnp.clip(x.astype(jnp.float32) / scale, -qspec.qmax, qspec.qmax)
    if qspec.is_int:
        y = jnp.round(y)
    return y.astype(qspec.dtype)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Stored dtype -> f32.  Keep the result f32 (see module docstring:
    a bf16 round-trip breaks requantization byte-stability)."""
    return q.astype(jnp.float32) * scale


def paged_quant_write(pool: jax.Array, scales: jax.Array, layer,
                      bt: jax.Array, start: jax.Array, vals: jax.Array,
                      qspec: KVQuantSpec,
                      n_valid: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Read-modify-write ``vals`` [B, S, KV, D] into layer ``layer`` of
    the quantized ``pool`` [L, NB, T, KV*D] at contiguous cache slots
    ``start[b] + s`` routed through block table ``bt`` [B, MB],
    recomputing the per-block per-kv-head ``scales`` [L, NB, KV] of
    every touched block. Only the touched blocks are gathered and
    scattered back, ``pool[layer, block]``: no layer is sliced out.

    This is every write site of a quantized pool: S == 1 for plain
    decode, the draft/verify window for speculation, a chunk's bucket
    for prefill. The window can straddle block boundaries, so the write
    goes block by block over the (at most ``(S + T - 2)//T + 1``) window
    blocks; each step RMWs ONE block per row — gather + dequant, scatter
    this window's tokens that land in that block (offset T +
    ``mode="drop"`` masks the rest), zero every slot at/beyond the
    written frontier (stale garbage from a previous tenant or a
    rejected speculative window must not leak into the absmax),
    requantize with the fresh scale, scatter back. ``n_valid`` [B]
    (prefill's; default S) is how many of a row's S tokens are real:
    bucket filler behind them is not written and the frontier is
    ``start + n_valid``. A window of more than two blocks runs as a
    `fori_loop` (a prefill chunk's 17 would be traced and lowered 17
    times in each of the engine's ~35 prefill programs).

    Rows whose window block index runs off the table (retired rows, or
    frontiers at max_len) resolve to physical block 0 — the reserved
    null block, never attended — exactly like the unquantized write
    path's masked scatter.
    """
    B, S, KV, D = vals.shape
    T = pool.shape[2]
    MB = bt.shape[1]
    vals = vals.astype(jnp.float32)
    bidx = jnp.arange(B)
    nbw = (S + T - 2) // T + 1            # max blocks a window can touch
    off0 = start % T                      # [B] offset in first block
    n_valid = S if n_valid is None else n_valid[:, None]
    # token s sits at window position off0 + s (filler: nowhere)
    pos = jnp.where(jnp.arange(S)[None, :] < n_valid,
                    off0[:, None] + jnp.arange(S)[None, :], nbw * T)
    frontier = start[:, None] + n_valid                       # [B, 1]

    def one_block(w, carry):
        pool, scales = carry
        lb = start // T + w               # [B] logical block index
        blk = jnp.where(lb < MB, bt[bidx, jnp.minimum(lb, MB - 1)], 0)
        cur = dequantize(pool[layer, blk].reshape(B, T, KV, D),
                         scales[layer, blk][:, None, :, None])
        # a token lands in this step's block iff pos // T == w. Offset
        # T is OOB and dropped.
        offs = jnp.where(pos // T == w, pos % T, T)
        cur = cur.at[bidx[:, None], offs].set(vals, mode="drop")
        # zero stale slots at/beyond the written frontier
        slot = (lb * T)[:, None] + jnp.arange(T)[None, :]     # [B, T]
        cur = jnp.where((slot < frontier)[:, :, None, None], cur, 0.0)
        amax = jnp.max(jnp.abs(cur), axis=(1, 3))             # [B, KV]
        s_new = block_scale(amax, qspec)
        pool = pool.at[layer, blk].set(quantize(
            cur, s_new[:, None, :, None], qspec).reshape(B, T, KV * D))
        return pool, scales.at[layer, blk].set(s_new)

    if nbw > 2:
        return jax.lax.fori_loop(0, nbw, one_block, (pool, scales))
    for w in range(nbw):
        pool, scales = one_block(w, (pool, scales))
    return pool, scales
