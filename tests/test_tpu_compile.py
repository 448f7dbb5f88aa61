"""The chip's compiler, asked without the chip.

The TPU compiler is installed beside JAX and compiles for a device that
is described, not attached (`topologies.get_topology_desc`). Interpret
mode hides what Mosaic refuses — a block layout, an unaligned slice, too
much VMEM — so every kernel `impl="auto"` can select on a TPU is compiled
here, `interpret=False`, at the shapes `chip_smoke.py` runs: Llama-3-8B
attention widths for the paged decode kernel, the 551M flagship's
`[8, 12, 2048, 128]` at 1024x1024 tiles for flash forward and backward.
Nothing runs, so these say nothing about values (the interpret-mode
sweeps do) or times (only a chip run does).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_attention_kernel import paged_attention_kernel

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"cannot describe v5e:2x2 here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip: the next one warns
    and compiles again. Keep these out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_with_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _paged_kernel_compiles(v5e, B, S, T, MB, NB, quant, H=32,
                           KV=8) -> bool:
    D = 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    args = [arg((B, S, H, D), jnp.bfloat16),
            arg((NB, T, KV, D), quant or jnp.bfloat16),
            arg((NB, T, KV, D), quant or jnp.bfloat16),
            arg((B, MB), jnp.int32), arg((B, S), jnp.int32)]
    if quant is not None:
        args += [arg((NB, KV), jnp.float32)] * 2

    def fn(q, k, v, bt, slots, k_scale=None, v_scale=None):
        return paged_attention_kernel(
            q, k, v, bt, slots, kv_valid_len=MB * T, k_scale=k_scale,
            v_scale=v_scale, interpret=False)

    return _compiles_with_kernel(fn, *args)


@pytest.mark.parametrize("quant", [None, jnp.int8], ids=["bf16", "int8"])
@pytest.mark.parametrize("block_tokens", [16, 128])
def test_paged_kernel_compiles_at_llama3_8b_widths(v5e, block_tokens,
                                                   quant):
    MB = 2048 // block_tokens
    assert _paged_kernel_compiles(v5e, 8, 1, block_tokens, MB, 8 * MB + 1,
                                  quant)


@pytest.mark.parametrize("quant", [None, jnp.int8, jnp.float8_e4m3fn],
                         ids=["bf16", "int8", "fp8"])
@pytest.mark.parametrize("slots", [1, 4], ids=["decode", "spec4"])
def test_paged_kernel_compiles_at_benchmark_shape(v5e, slots, quant):
    """`benchmark/configs/mistral-7b-v0.3-serve.json` as the engine runs
    it: 32 rows, 128 table entries of 32 tokens, a pool of 1,878 blocks,
    8 KV heads of 128; and the speculative verify's 4 query slots."""
    assert _paged_kernel_compiles(v5e, 32, slots, 32, 128, 1878, quant)


def test_paged_kernel_compiles_at_olmoe_shape(v5e):
    """`benchmark/configs/olmoe-1b-7b-serve.json` as the engine runs it:
    MHA, 16 query and 16 KV heads of 128 (one query head a group, where
    Mistral has four), 32 rows, 64 table entries of 32 tokens."""
    assert _paged_kernel_compiles(v5e, 32, 1, 32, 64, 615, None, H=16,
                                  KV=16)


def test_expert_layer_compiles_at_olmoe_widths(v5e):
    """Both regimes of `moe.moe_ffn_dropless` at the published widths: 32
    decode rows (every expert for every row) and a prefill chunk of
    4 x 512 tokens (sorted, `ragged_dot`)."""
    from ray_tpu.models import MoeConfig, moe

    cfg = MoeConfig.olmoe_1b_7b(n_layers=1, dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    d, f, e = cfg.dim, cfg.ffn_dim, cfg.n_experts
    layer = {"w_router": arg((d, e)), "we_gate": arg((e, d, f)),
             "we_up": arg((e, d, f)), "we_down": arg((e, f, d))}
    for rows, chunk in ((32, 1), (4, 512)):
        text = jax.jit(lambda x, layer, live: moe.moe_ffn_dropless(
            x, layer, cfg, live)).lower(
            arg((rows, chunk, d)), layer,
            arg((rows, chunk), jnp.bool_)).compile().as_text()
        assert ("ragged" in text) == (rows * chunk
                                      > moe.DENSE_EXPERTS_MAX_TOKENS)


def _flash_args(v5e, b, h, hkv, s, d):
    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, s, d), jnp.bfloat16,
                                    sharding=v5e)

    return arg(h), arg(hkv), arg(hkv)


def test_flash_fwd_compiles_at_flagship_shape(v5e):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=128 ** -0.5,
                               block_q=1024, block_k=1024,
                               interpret=False)

    assert _compiles_with_kernel(fn, *_flash_args(v5e, 8, 12, 12, 2048,
                                                  128))


def test_flash_bwd_compiles_at_flagship_shape(v5e):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=128 ** -0.5,
                               block_q=1024, block_k=1024,
                               interpret=False).astype(jnp.float32).sum()

    assert _compiles_with_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                                 *_flash_args(v5e, 8, 12, 12, 2048, 128))


def test_flash_fwd_compiles_under_gqa(v5e):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=128 ** -0.5,
                               interpret=False)

    assert _compiles_with_kernel(fn, *_flash_args(v5e, 1, 32, 8, 2048,
                                                  128))
