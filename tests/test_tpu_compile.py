"""The chip's compiler, asked without the chip.

The TPU compiler is installed beside JAX and compiles for a device that
is described, not attached (`topologies.get_topology_desc`). Interpret
mode hides what Mosaic refuses — a block layout, an unaligned slice, too
much VMEM — so every kernel `impl="auto"` can select on a TPU is compiled
here, `interpret=False`, at the shapes `chip_smoke.py` runs: Llama-3-8B
attention widths for the paged decode kernel, the 551M flagship's
`[8, 12, 2048, 128]` at 1024x1024 tiles for flash forward and backward,
and the train cell's `[4, 16, 4096, 128]` / 8 KV heads at the default
512x512 tiles, whose calls step through the causal triangle (36 pairs of
64, PR 50).
The fused decode PROGRAM is compiled whole as well, at the benchmark's
shapes, and held to what PR 27 bought: the KV pool is one buffer updated
in place, so the program holds nothing else of the pool's or a layer's
size and its workspace is a fraction of the pool. So is the prefill
program, held to what PR 30 bought: no dense per-row view of the table,
a workspace that does not grow with `max_len`. And the train cell's step
over the four described chips, held to what PR 32 bought: three kernels
a layer, the flash kernel's residuals stacked once and in place.
Nothing runs, so these say nothing about values (the interpret-mode
sweeps do) or times (only a chip run does).
"""

import functools
import math
import os
import re
from unittest import mock

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
def v5e_2x2():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"cannot describe v5e:2x2 here: {e}")


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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
                           KV=8, D=128) -> bool:
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    L = 3   # the pool as the engine stores it; the layer is an operand
    args = [arg((B, S, H, D), jnp.bfloat16),
            arg((L, NB, T, KV * D), quant or jnp.bfloat16),
            arg((L, NB, T, KV * D), quant or jnp.bfloat16),
            arg((B, MB), jnp.int32), arg((B, S), jnp.int32),
            arg((), jnp.int32)]
    if quant is not None:
        args += [arg((L, NB, KV), jnp.float32)] * 2

    def fn(q, k, v, bt, slots, layer, k_scale=None, v_scale=None):
        return paged_attention_kernel(
            q, k, v, bt, slots, layer=layer, kv_valid_len=MB * T,
            k_scale=k_scale, v_scale=v_scale, interpret=False)

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


def test_paged_kernel_compiles_at_qwen3next_shape(v5e):
    """`benchmark/configs/qwen3-next-80b-a3b-serve.json` as the engine
    runs its gated-attention layers: 16 query heads over 2 KV heads of
    256 (a head is two lane tiles of the stacked operand), 64 rows, 132
    table entries of 256 tokens, two pages a step."""
    assert _paged_kernel_compiles(v5e, 64, 1, 256, 132, 4097, None, H=16,
                                  KV=2, D=256)


def test_paged_kernel_compiles_with_a_window_at_the_pair_layout(v5e):
    """`benchmark/configs/phi-4-mini-flash-serve.json`: key heads of 64
    read as PAIRS of 128 lanes (queries zero-padded to the pair, 40 query
    heads over 10 pairs), value pairs 128 wide, a walk that starts at the
    row's first live page of the 8-layer window pool: a decode token of 64
    rows and a 4 x 512 chunk (tiles of 64 queries)."""
    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def fn(q, k, v, bt, slots, layer):
        return paged_attention_kernel(
            q, k, v, bt, slots, layer=layer, kv_valid_len=160 * 32,
            sm_scale=0.125, window=512, interpret=False)

    pool = arg((8, 1353, 32, 1280), jnp.bfloat16)
    for rows, slots in ((64, 1), (4, 512)):
        assert _compiles_with_kernel(
            fn, arg((rows, slots, 40, 128), jnp.bfloat16), pool, pool,
            arg((rows, 160)), arg((rows, slots)), arg(()))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


# rows, query slots, Q heads, KV heads, head size, block tokens, table
# entries, pool blocks, window
_CELL_CALLS = {
    "mistral_decode": (32, 1, 32, 8, 128, 32, 128, 1878, None),
    "mistral_4x512": (4, 512, 32, 8, 128, 32, 128, 1878, None),
    "olmoe_decode": (32, 1, 16, 16, 128, 32, 64, 615, None),
    "olmoe_4x512": (4, 512, 16, 16, 128, 32, 64, 615, None),
    "phi4flash_decode": (64, 1, 40, 10, 128, 32, 160, 1353, 512),
    "phi4flash_4x512": (4, 512, 40, 10, 128, 32, 160, 1353, 512),
    "qwen3next_decode": (64, 1, 16, 2, 256, 256, 132, 4097, None),
}


@pytest.mark.parametrize("case", list(_CELL_CALLS))
def test_paged_kernel_call_orders_its_rows_and_asks_for_what_it_did(case):
    """The kernel's call at the serving cells' shapes, as traced: the
    rows of a call are one pipeline, so the grid axis is "arbitrary";
    what carries it from row to row is two words of SMEM; the VMEM
    scratch is two slots of a step's keys each for K and V beside the
    softmax trio, a head's at a prefill tile and, at a decode row, the
    stacked one: all heads' rows by all heads' lanes (128 KiB at
    Mistral's and OLMoE's shapes, 200 KiB at phi4flash's, 32 KiB at
    qwen3next's; the per-head trio's accumulators were 16 / 8 / 20 / 16),
    the block-diagonal query of that shape and the rows' slots. A decode
    row's call still asks for no `vmem_limit_bytes` (what a kernel may
    take, XLA cannot give the program around it: PERF.md PR 30) and a
    chunk's tiles for the 32 MiB they asked for before."""
    from ray_tpu.ops.paged_attention_kernel import walk_shape

    B, S, H, KV, D, T, MB, NB, window = _CELL_CALLS[case]
    sd = jax.ShapeDtypeStruct

    def fn(q, k, v, bt, slots, layer):
        return paged_attention_kernel(
            q, k, v, bt, slots, layer=layer, kv_valid_len=MB * T,
            window=window, interpret=False)

    pool = sd((3, NB, T, KV * D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(fn)(
        sd((B, S, H, D), jnp.bfloat16), pool, pool, sd((B, MB), jnp.int32),
        sd((B, S), jnp.int32), sd((), jnp.int32))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert params.dimension_semantics == ("arbitrary",)
    pps, tq, stacked = walk_shape(S, H, KV, D, T, MB, 2)
    assert stacked == (S == 1)      # a decode row; a chunk's tiles loop
    keys = pps * T          # 512, or what fits 1 MiB a slot
    assert keys == {(8, 128): 512, (16, 128): 256, (10, 128): 384,
                    (2, 256): 512}[KV, D]
    tiles = -(-S // tq)
    grid = call.params["grid_mapping"].grid
    assert grid == (-(-B * tiles // 8) * 8,)
    rows = tq * (H // KV)
    scratch = [str(a) for a in call.params["grid_mapping"].scratch_avals]
    trio = [f"Ref<vmem>{{float32[{KV},{rows},{D}]}}",
            f"Ref<vmem>{{float32[{KV},{rows},1]}}",
            f"Ref<vmem>{{float32[{KV},{rows},1]}}"]
    if stacked:
        trio = [f"Ref<vmem>{{float32[{KV * rows},{KV * D}]}}",
                f"Ref<vmem>{{float32[{KV * rows},1]}}",
                f"Ref<vmem>{{float32[{KV * rows},1]}}",
                f"Ref<vmem>{{bfloat16[{KV * rows},{KV * D}]}}",
                f"Ref<vmem>{{int32[{KV * rows},1]}}"]
    assert scratch == [
        f"Ref<vmem>{{bfloat16[2,{keys},{KV * D}]}}"] * 2 + [
        "Ref<semaphore_mem>{dma_sem[2,2]}"] + trio + ["Ref<smem>{int32[2]}"]
    assert params.vmem_limit_bytes == (None if S == 1 else 32 << 20)


# -- the fused decode program, whole ------------------------------------------

def _param_shapes(v5e, cfg):
    """The model's parameters as shapes on the described chip."""
    from ray_tpu.models import MoeConfig, llama_init, moe_init

    init = moe_init if isinstance(cfg, MoeConfig) else llama_init
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg)))


def _decode_program(v5e, cfg, n_blocks, max_blocks, quant=None, rows=32,
                    block_tokens=32, horizon=8):
    """`_decode_multi_paged` compiled for the described chip at an
    engine's shapes, with the kernel selected: `impl="auto"` asks
    `jax.default_backend()`, which answers "cpu" here, so the TEST makes
    it answer "tpu" while the program is traced (the program has no
    switch for it). Returns the compiled program and the pool's shape."""
    from ray_tpu.models import MoeConfig, engine
    from ray_tpu.ops.kv_quant import resolve_kv_quant

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = _param_shapes(v5e, cfg)
    qspec = resolve_kv_quant(quant)
    shape = (cfg.n_layers, n_blocks, block_tokens,
             cfg.n_kv_heads * cfg.head_dim)
    pool = arg(shape, jnp.bfloat16 if qspec is None else qspec.dtype)
    scale = None if qspec is None else arg(
        (cfg.n_layers, n_blocks, cfg.n_kv_heads), jnp.float32)
    lane = arg((rows,), jnp.int32)
    flag = arg((rows,), jnp.bool_)
    moe_ctr = arg((4,), jnp.int32) if isinstance(cfg, MoeConfig) else None
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = engine._decode_multi_paged.lower(
            params, pool, pool, arg((rows, max_blocks), jnp.int32),
            arg((rows, cfg.vocab_size), jnp.float32), lane, flag, lane,
            lane, arg((rows, 2), jnp.uint32), flag, 1.0, cfg, horizon,
            True, None, None, None, scale_k=scale, scale_v=scale,
            qspec=qspec, moe_ctr=moe_ctr)
    return lowered.compile(), shape


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", re.M)
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")


def _pool_sized_instructions(text, pool_shape):
    """(opcode, dims) of every instruction of the optimized HLO that
    yields, alone or in a tuple, an array with the pool's block axis
    among its dimensions and at least a tenth of one layer-of-pool's
    elements: the pool, a layer of it, or a lane slice of either
    (`pool_shape[1]` is chosen so that nothing else has it). Parameters
    and the tuples and loops that merely pass the pool on are left out."""
    least = math.prod(pool_shape[1:]) // 10
    found = []
    for types, op in _HLO_LINE.findall(text):
        if op in _PLUMBING:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", types):
            sizes = [int(d) for d in dims.split(",")]
            if pool_shape[1] in sizes and math.prod(sizes) >= least:
                found.append((op, dims))
    return found


def _mistral(n_layers):
    from ray_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=32768, dim=4096, n_layers=n_layers,
                       n_heads=32, n_kv_heads=8, ffn_dim=14336,
                       rope_theta=1e6, max_seq_len=4096,
                       dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def _olmoe(n_layers):
    from ray_tpu.models import MoeConfig

    return MoeConfig.olmoe_1b_7b(n_layers=n_layers, dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("case", ["mistral", "olmoe", "mistral_int8"])
def test_decode_program_moves_nothing_of_the_pools_size(v5e, case):
    """The benchmark's two engines (`mistral-7b-v0.3-serve`: 12 layers,
    1,878 blocks, 128 table entries; `olmoe-1b-7b-serve`: 12 layers, 615
    blocks, 64 entries) and an int8 pool: in the optimized HLO the only
    instructions of the pool's or a layer-of-pool's size are the two
    in-place `kv_write` scatter fusions (K and V) — no copy, slice,
    reshape or restack (before PR 27: two copies of the whole pool a
    token and six layer-sized ops a layer, 4.23 GiB of workspace) — and
    the program keeps ONE Pallas kernel of attention."""
    cfg, nb, mb, quant = {
        "mistral": (_mistral(12), 1878, 128, None),
        "olmoe": (_olmoe(12), 615, 64, None),
        "mistral_int8": (_mistral(12), 1878, 128, "int8"),
    }[case]
    compiled, shape = _decode_program(v5e, cfg, nb, mb, quant)
    text = compiled.as_text()
    moved = _pool_sized_instructions(text, shape)
    dims = ",".join(map(str, shape))
    assert sorted(moved) == [("fusion", dims)] * 2 + [("scatter", dims)] * 2
    fusions = [ln for ln in text.splitlines()
               if re.search(r"= \w+\[%s\]\S* fusion\(" % dims, ln)]
    assert len(fusions) == 2 and all("kv_write" in ln for ln in fusions)
    # ... beside, for OLMoE, the expert layer's (`ops/hit_experts.py`)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == (2 if case == "olmoe" else 1)
    # a decode row's kernel asks for no VMEM of its own: what it may
    # take, XLA cannot give the program, which then reads a stacked
    # weight it kept in VMEM from HBM again (+1.4 % a token, PR 30)
    assert all('"scoped_memory_configs":[]' in ln for ln in calls)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (1 << 30), f"{temp / 2**30:.2f} GiB of workspace"
    # PERF.md section 4's figures, to the MiB (0.38 GiB; OLMoE's decode
    # program holds nothing beside its arguments)
    if quant is None:
        assert temp >> 20 == {"mistral": 384, "olmoe": 0}[case]


def test_olmoe_decode_program_reads_experts_where_they_lie(v5e):
    """The `olmoe-chat-short` cell's decode program (12 layers, 615
    blocks, 32 slots) with the expert layer that reads only the experts a
    live row chose (PR 34): the kernel is handed ALL layers' expert stacks
    and the layer's index, so the optimized HLO makes nothing of a layer's
    64 experts (a `copy` or `dynamic-slice` of one, which a kernel operand
    sliced out of the layer scan's would be: 0.75 GiB written and read
    again a layer) and nothing of the stacks' size, and its workspace
    stays what the all-experts program's was: next to nothing (peak
    12.44 GB = the arguments, both as compiled here). A prefill chunk of
    256 tokens keeps the all-experts einsum: no second kernel there."""
    from ray_tpu.models import moe
    from ray_tpu.ops import scope_names as sn

    cfg = _olmoe(12)
    compiled, _ = _decode_program(v5e, cfg, 615, 64)
    text = compiled.as_text()
    assert moe.hit_experts_only(cfg, 32)
    assert sum(sn.HIT_EXPERTS_KERNEL in ln for ln in text.splitlines()
               if "tpu_custom_call" in ln) == 1
    e, d, f = cfg.n_experts, cfg.dim, cfg.ffn_dim
    stacks = {f"{n},{d},{f}" for n in (e, e * cfg.n_layers)} \
        | {f"{n},{f},{d}" for n in (e, e * cfg.n_layers)} \
        | {f"{cfg.n_layers},{e},{d},{f}", f"{cfg.n_layers},{e},{f},{d}"}
    made = [(op, dims) for types, op in _HLO_LINE.findall(text)
            if op not in _PLUMBING
            for dims in re.findall(r"\w+\[([\d,]+)\]", types)
            if dims in stacks]
    assert not made, made
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (64 << 20), f"{temp / 2**20:.1f} MiB of workspace"
    assert not moe.hit_experts_only(cfg, 256)
    chunk, _ = _prefill_program(v5e, cfg, 615, 64, 1, 256)
    assert chunk.as_text().count("tpu_custom_call") == 1


def test_decode_program_fits_16_layers_beside_a_5_gib_pool(v5e):
    """16 Mistral layers (7.0 GiB) with a 5 GiB pool (2,561 blocks): the
    chip's compiler refused it at 17.6 of 15.75 GiB while the program
    held a second pool (PR 23). It compiles, and arguments + workspace
    stay under the chip's memory."""
    compiled, _ = _decode_program(v5e, _mistral(16), 2561, 128)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < (1 << 30)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.5 * 2**30


def _prefill_program(v5e, cfg, n_blocks, max_blocks, rows, chunk,
                     slots=32, block_tokens=32):
    """`_prefill_rows_paged` compiled for the described chip for a group
    of ``rows`` x ``chunk`` tokens, with the kernel selected as in
    `_decode_program`. Returns the compiled program and the pool's
    shape."""
    from ray_tpu.models import MoeConfig, engine

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    shape = (cfg.n_layers, n_blocks, block_tokens,
             cfg.n_kv_heads * cfg.head_dim)
    pool = arg(shape, jnp.bfloat16)
    moe_ctr = arg((4,)) if isinstance(cfg, MoeConfig) else None
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = engine._prefill_rows_paged.lower(
            _param_shapes(v5e, cfg), arg((rows, chunk)), pool, pool,
            arg((slots, cfg.vocab_size), jnp.float32),
            arg((rows, max_blocks)), arg((rows,)), arg((rows,)),
            arg((rows,)), cfg, moe_ctr=moe_ctr)
    return lowered.compile(), shape


def _row_scatters(text, pool_shape):
    """The in-place writes of a chunk's K/V: the compiler flattens the
    pool to ``[L*NB*T, KV*D]`` and scatters token rows into it."""
    flat = "%d,%d" % (math.prod(pool_shape[:3]), pool_shape[3])
    return [ln for ln in text.splitlines()
            if re.search(r"= \w+\[%s\]\S* scatter\(" % flat, ln)]


@pytest.mark.parametrize("case", ["mistral_4x512", "mistral_8x512",
                                  "olmoe_4x512"])
def test_prefill_program_builds_no_view_of_the_table(v5e, case):
    """The benchmark's two engines' largest prefill groups. Until PR 30
    the program gathered every table entry of every admission row into
    a dense view ``[layers, rows, max_len, KV, D]``, attended all of it
    and wrote it back whole: 3.45 GiB of workspace at 4 x 512 and 6.97
    at 8 x 512 for Mistral, whatever the pool's size. Now a chunk's K/V
    is scattered into the pool in place (two row scatters, K and V),
    attention goes through the block table in ONE Pallas kernel, and
    `lm_head` sees one position a row: no array of the view's shape, of
    a layer of it, or of ``[rows, chunk, vocab]`` is left, nothing else
    of the pool's size, and the workspace is under 1 GiB (0.06, 0.11 and
    0.32 GiB as compiled here)."""
    cfg, nb, mb, n = {
        "mistral_4x512": (_mistral(12), 1878, 128, 4),
        "mistral_8x512": (_mistral(12), 1878, 128, 8),
        "olmoe_4x512": (_olmoe(12), 615, 64, 4),
    }[case]
    compiled, shape = _prefill_program(v5e, cfg, nb, mb, n, 512)
    text = compiled.as_text()
    kv, d, span = cfg.n_kv_heads, cfg.head_dim, mb * 32
    for view in ((n, span, kv, d), (n, span, kv * d), (n, mb, 32, kv * d),
                 (n, 512, cfg.vocab_size)):
        dims = ",".join(map(str, view))
        assert not re.search(r"\[(\d+,)?%s\]" % dims, text), dims
    assert _pool_sized_instructions(text, shape) == []
    assert len(_row_scatters(text, shape)) == 2
    assert "mini-gather" not in text
    # the expert layer brings its own kernels (`ragged_dot`)
    assert (text.count("tpu_custom_call") == 1) == (case != "olmoe_4x512")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (1 << 30), f"{temp / 2**30:.2f} GiB of workspace"
    if n == 4:      # PERF.md section 4's figures, to the MiB
        assert temp >> 20 == {"mistral_4x512": 57, "olmoe_4x512": 330}[case]


def test_prefill_program_fits_16_layers_beside_a_5_gib_pool(v5e):
    """What ROADMAP R0 needs to re-size the Mistral cells: 16 layers
    (7.0 GiB) and a 5 GiB pool (2,561 blocks) with a FULL prefill group
    of 8 rows x 512 tokens, beside the decode program
    (`test_decode_program_fits_16_layers_beside_a_5_gib_pool`). The view
    alone was 6.97 GiB there."""
    compiled, _ = _prefill_program(v5e, _mistral(16), 2561, 128, 8, 512)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < (1 << 30)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.5 * 2**30


@pytest.mark.parametrize("program", ["prefill", "cow", "swap_out"])
def test_other_holders_of_the_pool_copy_none_of_it(v5e, program):
    """The programs beside the decode that touch pages of the pool, at
    the Mistral cell's shape: the prefill of one row x 512 tokens, the
    copy-on-write of four blocks, the swap-out of sixteen. The last two
    get their pages through `engine._gather_pages`; as a gather of whole
    pages (1,024 lanes) the chip's compiler sliced the POOL into
    512-lane halves first (`mini-gather-slice`), a copy of the pool per
    call. What is left of the pool's size are the in-place scatters back
    into it; prefill gathers nothing and scatters token rows."""
    from ray_tpu.models import engine

    shape = (12, 1878, 32, 1024)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    pool = arg(shape, jnp.bfloat16)
    if program == "prefill":
        compiled, _ = _prefill_program(v5e, _mistral(12), 1878, 128, 1, 512)
        text = compiled.as_text()
        assert "mini-gather" not in text
        # one row: the two scatters keep the pool's own shape
        dims = ",".join(map(str, shape))
        assert sorted(_pool_sized_instructions(text, shape)) \
            == [("fusion", dims)] * 2 + [("scatter", dims)] * 2
        assert _row_scatters(text, shape) == []
        return
    if program == "cow":
        lowered = engine._cow_blocks.lower(pool, pool, arg((4,)), arg((4,)))
    else:
        lowered = engine._swap_out_gather.lower(pool, pool, arg((16,)))
    text = lowered.compile().as_text()
    assert "mini-gather" not in text
    dims = ",".join(map(str, shape))
    writes = 0 if program == "swap_out" else 2
    assert sorted(_pool_sized_instructions(text, shape)) \
        == [("fusion", dims)] * writes + [("scatter", dims)] * writes


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


def _flash_pair_counts(kernel):
    """`flash_pairs_*_total` of `kernel` in this process's registry."""
    from ray_tpu.util import metrics

    return {row["name"]: row["value"] for row in metrics.snapshots()
            if row["name"].startswith("flash_pairs_")
            and row["tags"].get("kernel") == kernel}


@pytest.mark.parametrize("program", ["fwd", "bwd"])
def test_flash_compiles_at_the_train_cell_shape(v5e, program):
    """`internlm2-train-fsdp4`: a chip's `q [4,16,4096,128]`, 8 KV heads,
    the default 512 x 512 tiles. A call steps through the causal triangle:
    36 pairs a (batch, head), 8 of them masked, where the rectangle is
    64."""
    from ray_tpu.ops import scope_names as sn

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=128 ** -0.5,
                               interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    kernels = {"fwd": [sn.FLASH_FWD],
               "bwd": [sn.FLASH_FWD, sn.FLASH_BWD_DQ, sn.FLASH_BWD_DKV]}
    before = {kern: _flash_pair_counts(kern) for kern in kernels[program]}
    fn = fwd if program == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    assert _compiles_with_kernel(fn, *_flash_args(v5e, 4, 16, 8, 4096, 128))
    for kern in kernels[program]:
        now = _flash_pair_counts(kern)
        got = {what: (now[f"flash_pairs_{what}_total"]
                      - before[kern].get(f"flash_pairs_{what}_total", 0))
               / (4 * 16) for what in ("live", "masked", "rectangle")}
        assert got == {"live": 36, "masked": 8, "rectangle": 64}, kern


def test_flash_fwd_compiles_under_gqa(v5e):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=128 ** -0.5,
                               interpret=False)

    assert _compiles_with_kernel(fn, *_flash_args(v5e, 1, 32, 8, 2048,
                                                  128))


# -- the hybrid cell's programs ------------------------------------------------

def _hybrid_program(v5e, program):
    """The `phi4flash-reason` cell's engine as `DecodeEngine` builds it
    (64 slots, 160 table entries of 32 tokens, a full-layer pool of 8,193
    blocks, a window pool of 1,353, recurrent state for every slot):
    `_decode_multi_paged` at horizon 8 or `_prefill_rows_paged` for 4 x 512
    tokens, compiled for the described chip with the kernel selected."""
    from ray_tpu.models import HybridConfig, engine, hybrid_init
    from ray_tpu.models.block_pool import zero_state_planes

    cfg = HybridConfig.phi4_mini_flash(max_seq_len=5120)
    B, T, MB = 64, 32, 160

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def shapes(fn):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype),
                            jax.eval_shape(fn))

    params = shapes(lambda: hybrid_init(jax.random.PRNGKey(0), cfg))
    hyb = shapes(lambda: {
        **{pl.name: jnp.zeros((pl.layers, 1353, T, pl.lanes), pl.dtype)
           for pl in cfg.cache_planes() if pl.table == "window"},
        **zero_state_planes(cfg.state_planes(), B)})
    pool = arg((1, 8193, T, 1280), jnp.bfloat16)
    logits = arg((B, cfg.vocab_size), jnp.float32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if program == "decode":
            lane, flag = arg((B,)), arg((B,), jnp.bool_)
            lowered = engine._decode_multi_paged.lower(
                params, pool, pool, arg((B, MB)), logits, lane, flag, lane,
                lane, arg((B, 2), jnp.uint32), flag, 1.0, cfg, 8, True,
                None, None, None, hyb=hyb, bt_w=arg((B, MB)))
        else:
            lowered = engine._prefill_rows_paged.lower(
                params, arg((4, 512)), pool, pool, logits, arg((4, MB)),
                arg((4,)), arg((4,)), arg((4,)), cfg, hyb=hyb,
                bt_w=arg((4, MB)), final=program == "prefill_last")
    return lowered.compile()


@pytest.mark.parametrize("program", ["decode", "prefill_last",
                                     "prefill_not_last"])
def test_hybrid_cell_programs_fit_the_chip(v5e, program):
    """All 32 layers of Phi-4-mini-flash-reasoning in bf16 (7.18 GiB)
    beside both pools and the state: 10.32 GiB of arguments, and no
    program adds a GiB of workspace (0.13 decode, 0.21 / 0.24 prefill as
    compiled here), within the chip's 15.75. Three kernel calls a program
    (window, full, cross layers), one body; a chunk that is not a prompt's
    last stops before the cross-decoder: one call, and the cross-decoder's
    2.7 GiB of weights are not even arguments."""
    compiled = _hybrid_program(v5e, program)
    m = compiled.memory_analysis()
    last = program != "prefill_not_last"
    assert m.temp_size_in_bytes < (1 << 30)
    if last:        # PERF.md section 4's figures, to the MiB
        assert m.temp_size_in_bytes >> 20 == {"decode": 135,
                                              "prefill_last": 212}[program]
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 11 * 2**30
    assert (m.argument_size_in_bytes > 10 * 2**30) == last
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (3 if last else 1)
    # the pools and the state are updated in place: donated and aliased
    assert m.alias_size_in_bytes > 3 * 2**30


# -- the latent-attention cell's programs ------------------------------------------

def _mla_program(v5e, program):
    """The `dsv32-longdoc` cell's engine as `DecodeEngine` builds it (24
    slots, 72 table entries of 256 tokens, a 3 GiB pool of a latent and an
    index plane for 5 layers, experts [0, 16) of 256 held, 1/8 of the
    vocabulary): `_decode_multi_paged` at the cell's horizon of 2 or
    `_prefill_rows_paged` for 4 x 512 tokens, compiled for the described
    chip with the kernels selected."""
    from ray_tpu.models import MlaConfig, engine, mla_init

    cfg = MlaConfig(vocab_size=16160, n_layers=5, n_dense_layers=1,
                    held_experts=(0, 16), max_seq_len=18432)
    B, T, MB = 24, 256, 72
    latent, index = cfg.cache_planes()
    nb = 1 + (3 << 30) // (latent.block_bytes(T) + index.block_bytes(T))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda: mla_init(jax.random.PRNGKey(0), cfg)))
    pc = arg((5, nb, T, latent.lanes), jnp.bfloat16)
    pi = arg((5, nb, T, index.lanes), jnp.bfloat16)
    logits = arg((B, cfg.vocab_size), jnp.float32)
    ctr = arg((5,))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if program == "decode":
            lane, flag = arg((B,)), arg((B,), jnp.bool_)
            lowered = engine._decode_multi_paged.lower(
                params, pc, pi, arg((B, MB)), logits, lane, flag, lane,
                lane, arg((B, 2), jnp.uint32), flag, 1.0, cfg, 2, True,
                None, None, None, moe_ctr=ctr)
        else:
            lowered = engine._prefill_rows_paged.lower(
                params, arg((4, 512)), pc, pi, logits, arg((4, MB)),
                arg((4,)), arg((4,)), arg((4,)), cfg, moe_ctr=ctr)
    return lowered.compile()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mla_cell_programs_fit_the_chip(v5e, program):
    """One chip's share of DeepSeek-V3.2-Exp in bf16 (4.64 B parameters,
    8.63 GiB) beside a 3 GiB pool of two planes: 11.64 GiB of arguments,
    and no program adds 2 GiB of workspace (0.66 decode, 1.54 the 4 x 512
    prefill as compiled here), within the chip's 15.75. Each program holds
    its form of the attention's kernel, one call a segment, behind the
    selection's, which walks a row's live index pages; both planes are
    updated in place."""
    compiled = _mla_program(v5e, program)
    m = compiled.memory_analysis()
    assert 11 * 2**30 < m.argument_size_in_bytes < 12 * 2**30
    assert m.temp_size_in_bytes < 2 * 2**30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2**30
    assert m.alias_size_in_bytes > 3 * 2**30
    # the selection's kernel: a token's pages read where they lie, a
    # chunk's keys under its mask
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # a chunk's held experts stay XLA's at 7,168 x 2,048 an expert
    # (`held_grouped_tiles`): no program of this cell holds the grouped
    # kernel; the decode program's 24 rows read theirs through the
    # hit-experts kernel at an f-tile of 128 (`moe.held_hit_kernel`)
    from ray_tpu.ops.scope_names import (HELD_GROUPED_KERNEL,
                                         HIT_EXPERTS_KERNEL)
    assert HELD_GROUPED_KERNEL not in text
    assert (HIT_EXPERTS_KERNEL in text) == (program == "decode")
    _selection_walks_the_rows_pages(text, 24 if program == "decode" else 4,
                                    72, 256)


def _selection_walks_the_rows_pages(text, rows, max_blocks, block_tokens):
    """What PR 53 bought: the program holds the selection's kernel, and
    no gather of its rows' whole index tables ``[rows, span, 128]`` is
    left in it (under either of the shapes XLA gave it)."""
    from ray_tpu.ops.scope_names import INDEXER_SELECT_KERNEL

    assert INDEXER_SELECT_KERNEL in text
    span = max_blocks * block_tokens
    assert not re.search(
        rf"bf16\[{rows},(?:{span}|{max_blocks},{block_tokens}),128[\],]",
        text)


# -- the two-geometry latent cell's programs -------------------------------------

def _dots3_config():
    """dots3-note-prev as `dots3-mixed-ctx` cuts it: layers F F S S S,
    experts [0, 32) of 256 held, 1/8 of the vocabulary."""
    from ray_tpu.models import MlaConfig

    return MlaConfig(
        vocab_size=19008, dim=5120, n_layers=5, n_dense_layers=1,
        n_heads=128, q_lora_rank=1024, kv_lora_rank=512, ffn_dim=13824,
        expert_dim=1536, n_group=1, topk_group=1, routed_scaling_factor=1.0,
        held_experts=(0, 32), norm_eps=1e-5, rope_theta=8e7,
        rope_scaling=None, max_seq_len=33792,
        layer_types=("full", "full", "window", "window", "window"),
        sliding_window=513, swa_n_heads=64, swa_q_lora_rank=1024,
        swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=5e4,
        attn_gate=True, lora_rescale=True)


@functools.lru_cache(maxsize=None)
def _dots3_program(v5e, program):
    """The `dots3-mixed-ctx` cell's engine as `DecodeEngine` builds it (64
    slots, 132 table entries of 256 tokens, a 2.25 GiB pool of a latent
    and an index plane for the 2 full layers, a window pool of 409 blocks
    of a 1,152-lane latent plane for the 3 window layers):
    `_decode_multi_paged` at the cell's horizon of 8 or
    `_prefill_rows_paged` for 4 x 512 tokens, compiled for the described
    chip with the kernels selected."""
    from ray_tpu.models import engine, mla_init

    cfg = _dots3_config()
    B, T, MB = 64, 256, 132
    latent, index, wlatent = cfg.cache_planes()
    nb = 1 + (9 << 28) // (latent.block_bytes(T) + index.block_bytes(T))
    nb_w = 1 + B * 6 + 8 * 3

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda: mla_init(jax.random.PRNGKey(0), cfg)))
    pc = arg((latent.layers, nb, T, latent.lanes), jnp.bfloat16)
    pi = arg((index.layers, nb, T, index.lanes), jnp.bfloat16)
    hyb = {"wlatent": arg((wlatent.layers, nb_w, T, wlatent.lanes),
                          jnp.bfloat16)}
    logits = arg((B, cfg.vocab_size), jnp.float32)
    ctr = arg((5,))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if program == "decode":
            lane, flag = arg((B,)), arg((B,), jnp.bool_)
            lowered = engine._decode_multi_paged.lower(
                params, pc, pi, arg((B, MB)), logits, lane, flag, lane,
                lane, arg((B, 2), jnp.uint32), flag, 1.0, cfg, 8, True,
                None, None, None, moe_ctr=ctr, hyb=hyb, bt_w=arg((B, MB)))
        else:
            lowered = engine._prefill_rows_paged.lower(
                params, arg((4, 512)), pc, pi, logits, arg((4, MB)),
                arg((4,)), arg((4,)), arg((4,)), cfg, moe_ctr=ctr, hyb=hyb,
                bt_w=arg((4, MB)))
    return lowered.compile()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dots3_cell_programs_fit_the_chip(v5e, program):
    """One chip's share of dots3-note-prev in bf16 (4.09 B parameters,
    7.61 GiB) beside a 2.25 GiB pool of the full layers' two planes and a
    0.67 GiB window pool of the window layers' one: 10.54 GiB of arguments
    (`engine_notes` of benchmark/configs/dots3-note-prev-serve.json), and
    no program adds 3 GiB of workspace. The window layers call the full
    layers' two kernels at their own geometry (64 heads, 1,152 lanes,
    latent 1,024): Mosaic takes both; all three planes are updated in
    place."""
    compiled = _dots3_program(v5e, program)
    m = compiled.memory_analysis()
    assert 10.3 * 2**30 < m.argument_size_in_bytes < 10.8 * 2**30
    assert m.temp_size_in_bytes < 3 * 2**30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2**30
    assert m.alias_size_in_bytes > 2.9 * 2**30
    from ray_tpu.ops.scope_names import (SPARSE_LATENT_DECODE_KERNEL,
                                         SPARSE_LATENT_KERNEL)
    text = compiled.as_text()
    want = SPARSE_LATENT_DECODE_KERNEL if program == "decode" \
        else SPARSE_LATENT_KERNEL
    assert text.count(want) >= 2       # a full and a window call at least
    _selection_walks_the_rows_pages(text, 64 if program == "decode" else 4,
                                    132, 256)


# rows, heads, lanes, latent, table entries, pool blocks, pages a step
_LATENT_DECODE = {
    "dsv32_full_24x128x640": (24, 128, 640, 512, 72, 8192, 8),
    "dots3_full_64x128x640": (64, 128, 640, 512, 132, 6144, 8),
    "dots3_window_64x64x1152": (64, 64, 1152, 1024, 4, 409, 4),
}


@pytest.mark.parametrize("case", list(_LATENT_DECODE))
def test_latent_decode_kernel_compiles_at_its_callers_shapes(v5e, case):
    """A decode token's latent attention alone at its three callers'
    shapes (`mla.attend_token` of both latent cells, a window layer of
    dots3's), pages of 256 slots: rows of a call one pipeline over the
    plane where it lies, `pages_per_step` pages a step of the double
    buffer, inside the default scoped VMEM (no `vmem_limit_bytes`:
    PERF.md PR 30) and with no workspace of the plane's or the table's
    size outside the kernel."""
    from ray_tpu.ops import sparse_latent_attention as sla

    B, H, W, rc, MB, NB, pps = _LATENT_DECODE[case]
    T = 256
    assert sla.pages_per_step(T, W, 2, MB) == pps

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def fn(q, pool, bt, bias, slots, layer):
        return sla.sparse_latent_decode(q, pool, bt, bias, slots, layer,
                                        rc=rc, sm_scale=0.1, interpret=False)

    compiled = jax.jit(fn).lower(
        arg((B, H, W), jnp.bfloat16), arg((3, NB, T, W), jnp.bfloat16),
        arg((B, MB)), arg((B, MB * T), jnp.float32), arg((B,)),
        arg(())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the delta-rule cell's programs ----------------------------------------------

@functools.lru_cache(maxsize=None)
def _gdn_program(v5e, program):
    """The `qwen3next-longctx` cell's engine as `DecodeEngine` builds it
    (64 slots, 132 table entries of 256 tokens, a 4 GiB K/V pool of the 2
    attention layers, a matrix and a conv state for 6 delta layers a slot,
    experts [0, 128) of 512 held, 1/4 of the vocabulary):
    `_decode_multi_paged` at the cell's horizon of 8 or
    `_prefill_rows_paged` for 4 x 512 tokens, compiled for the described
    chip with the paged kernel selected."""
    from ray_tpu.models import GdnConfig, engine, gdn_init
    from ray_tpu.models.block_pool import zero_state_planes

    cfg = GdnConfig(vocab_size=37984, n_layers=8, held_experts=(0, 128),
                    max_seq_len=33792)
    B, T, MB = 64, 256, 132
    k, v = cfg.cache_planes()
    nb = 1 + (4 << 30) // (k.block_bytes(T) + v.block_bytes(T))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def shapes(fn):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype),
                            jax.eval_shape(fn))

    params = shapes(lambda: gdn_init(jax.random.PRNGKey(0), cfg))
    state = shapes(lambda: zero_state_planes(cfg.state_planes(), B))
    pool = arg((k.layers, nb, T, k.lanes), jnp.bfloat16)
    logits = arg((B, cfg.vocab_size), jnp.float32)
    ctr = arg((5,))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if program == "decode":
            lane, flag = arg((B,)), arg((B,), jnp.bool_)
            lowered = engine._decode_multi_paged.lower(
                params, pool, pool, arg((B, MB)), logits, lane, flag, lane,
                lane, arg((B, 2), jnp.uint32), flag, 1.0, cfg, 8, True,
                None, None, None, moe_ctr=ctr, hyb=state)
        else:
            lowered = engine._prefill_rows_paged.lower(
                params, arg((4, 512)), pool, pool, logits, arg((4, MB)),
                arg((4,)), arg((4,)), arg((4,)), cfg, moe_ctr=ctr,
                hyb=state)
    return lowered.compile()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gdn_cell_programs_fit_the_chip(v5e, program):
    """One chip's share of Qwen3-Next-80B-A3B in bf16 (two periods, 128 of
    512 experts a layer, a quarter of the vocabulary: 3.67 B parameters,
    6.83 GiB) beside a 4 GiB K/V pool and 0.77 GiB of recurrent state: the
    bytes `engine_notes` of the cell's configuration states. The paged
    kernel compiles at 2 KV heads of 256 with 8 query heads each; the pool
    and the state are updated in place."""
    compiled = _gdn_program(v5e, program)
    m = compiled.memory_analysis()
    assert 11.5 * 2**30 < m.argument_size_in_bytes < 12 * 2**30
    assert m.temp_size_in_bytes < 2 * 2**30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2**30
    # donated and aliased: pool 4 GiB, state 0.77, logits
    assert m.alias_size_in_bytes > 4.7 * 2**30
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    # the held experts' matmuls: the grouped kernel in a 4 x 512 chunk
    # (one call a layer body: the delta period's and the attention
    # layer's), the hit-experts kernel over the held range in the decode
    # program's 64 rows (`moe.held_hit_kernel`: one step an expert at
    # 2,048 x 512), and neither holds the other's
    from ray_tpu.ops.scope_names import (HELD_GROUPED_KERNEL,
                                         HIT_EXPERTS_KERNEL)
    assert (HELD_GROUPED_KERNEL in text) == (program == "prefill")
    assert (HIT_EXPERTS_KERNEL in text) == (program == "decode")
    assert "ragged-dot" not in text and "ragged_dot" not in text


def test_gdn_decode_program_updates_the_state_plane_in_place(v5e):
    """The decode program's delta layers take the one-token kernel
    (`ops.gated_delta.delta_step_plane`, one call in the delta layer's
    scan body) on the 0.77 GiB state plane WHERE IT LIES, through the
    carries of the horizon's, the periods' and the delta layers' scans:
    the call's first result aliases its plane operand, nothing else in
    the program produces a value of the plane's shape (no copy ahead of
    the call), the temporaries stay under the plane's size, and no op
    under `gdn_step` slices a layer's ``[64, 32, 128, 128]`` out of the
    plane or puts one back. The prefill program keeps the chunk form and
    `delta_step` on its rows: no such kernel."""
    from ray_tpu.ops.scope_names import DELTA_STEP_KERNEL, GDN_STEP

    compiled = _gdn_program(v5e, "decode")
    text = compiled.as_text()
    plane, layer = "f32[6,64,32,128,128]", "f32[64,32,128,128]"
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and f"%{DELTA_STEP_KERNEL}" in ln]
    assert len(calls) == 1
    call = calls[0]
    assert plane in call.split(" custom-call(")[0]
    # result 0 is operand 4: after the layer, the live rows, their count
    # and the heads' scalars, all four prefetched into SMEM
    assert "output_to_operand_aliasing={{0}: (4, {})}" in call
    assert f"/{GDN_STEP}/" in call
    makers = [ln for ln in text.splitlines()
              if re.match(rf"\s*(ROOT )?%\S+ = {re.escape(plane)}", ln)
              and " get-tuple-element(" not in ln
              and " parameter(" not in ln]
    assert makers == []
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 6 * 64 * 32 * 128 * 128 * 4 // 2
    for ln in text.splitlines():
        if f"/{GDN_STEP}/" in ln and re.search(
                r"dynamic-(update-)?slice\(", ln):
            assert layer not in ln and plane not in ln, ln[:200]
    assert DELTA_STEP_KERNEL not in _gdn_program(v5e, "prefill").as_text()


@pytest.mark.parametrize("hb", [8, 16, 32])
def test_delta_step_kernel_compiles_at_the_cells_shapes(v5e, hb):
    """The one-token kernel alone on the cell's plane (6 layers, 64 slots,
    32 heads of 128 x 128 float32), at a whole row a step (what
    `delta_step_heads` names: 8 MiB of state blocks) and at 16 and 8
    heads, inside the default scoped VMEM; a donated plane comes back as
    the call's own buffer. Four heads a step is no whole tile of the
    per-head rows and is refused."""
    from ray_tpu.ops import gated_delta as gd

    L, B, H, dk, dv = 6, 64, 32, 128, 128
    assert gd.delta_step_heads(H, dk, dv) == 32

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    args = (arg((L, B, H, dk, dv)), arg((), jnp.int32), arg((B, H, dk)),
            arg((B, H, dk)), arg((B, H, dv)), arg((B, H)), arg((B, H)),
            arg((B,), jnp.bool_))

    def fn(hb, *a):
        return gd.delta_step_plane(*a, interpret=False, hb=hb)

    compiled = jax.jit(functools.partial(fn, hb), donate_argnums=0) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit" not in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == L * B * H * dk * dv * 4
    assert m.temp_size_in_bytes < 4 << 20
    if hb == 8:
        with pytest.raises(Exception):
            jax.jit(functools.partial(fn, 4)).lower(*args).compile()


# -- the held experts' grouped kernel ---------------------------------------------

# window rows, d, f, held groups, groups in the stacks
_HELD_PREFILL = {
    "qwen3next_4x512": (10240, 2048, 512, 128, 1024),
    "qwen3next_1x512": (2560, 2048, 512, 128, 1024),
    "dsv32_4x512": (2048, 7168, 2048, 16, 64),
}


@pytest.mark.parametrize("case", list(_HELD_PREFILL))
def test_held_grouped_kernel_at_the_held_cells_prefill_shapes(v5e, case):
    """Qwen3-Next's experts (2,048 x 512) go through the grouped kernel
    inside the default scoped VMEM (no `vmem_limit_bytes`: PERF.md PR 30).
    DeepSeek-V3.2's (7,168 x 2,048) do not fit there, whole rows of d
    beside three weight tiles: `held_grouped_tiles` says so from the
    widths, `_held_sorted` keeps `ragged_dot`, and Mosaic refuses the
    narrowest tile when it is forced."""
    from ray_tpu.ops import held_grouped_ffn as hg

    c, d, f, eh, n = _HELD_PREFILL[case]
    bf = jnp.bfloat16

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    args = (arg((c, d), bf), arg((eh,)), arg((eh,)), arg(()),
            arg((n, d, f), bf), arg((n, d, f), bf), arg((n, f, d), bf))

    def fn(xs, starts, sizes, first, w1, w3, w2, tf=None):
        return hg.held_grouped_ffn(
            xs, hg.visit_schedule(starts, sizes, c), first, w1, w3, w2,
            interpret=False, tf=tf)

    tiles = hg.held_grouped_tiles(d, f, bf)
    if case.startswith("qwen3next"):
        assert tiles is not None
        compiled = jax.jit(fn).lower(*args).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "vmem_limit" not in text
    else:
        assert tiles is None
        with pytest.raises(Exception, match="(?i)vmem"):
            jax.jit(functools.partial(fn, tf=128)).lower(*args).compile()


# -- the hit-experts kernel over a held range ----------------------------------------

# rows, d, f, groups in the stacks, the f-tile `hit_experts_tile` names
_HELD_DECODE = {
    "qwen3next_decode_64": (64, 2048, 512, 1024, 512),
    "qwen3next_chunk_128": (128, 2048, 512, 1024, 512),
    "dsv32_decode_24": (24, 7168, 2048, 64, 128),
    "dsv32_decode_64": (64, 7168, 2048, 64, 128),
    "dsv32_chunk_128": (128, 7168, 2048, 64, None),
}


@pytest.mark.parametrize("case", list(_HELD_DECODE))
def test_hit_experts_kernel_at_the_held_cells_decode_shapes(v5e, case):
    """The decode step's held experts at the two held cells' shapes:
    Qwen3-Next's (2,048 x 512) take one step an expert, DeepSeek-V3.2's
    (7,168 x 2,048) sixteen of 128 values of f, both inside the default
    scoped VMEM (no `vmem_limit_bytes`: PERF.md PR 30) with the tile
    `hit_experts_tile` names from the rows and the widths; where it names
    none (128 rows of d 7,168 and their float32 result beside three weight
    blocks) Mosaic refuses the narrowest tile, and the next wider one than
    it names where it names one."""
    from ray_tpu.ops import hit_experts as he

    g, d, f, n, want = _HELD_DECODE[case]
    bf = jnp.bfloat16
    eh = n // 8 if d == 2048 else n // 4

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    args = (arg((g, d), bf), arg((eh, g), jnp.float32), arg((eh,)), arg(()),
            arg((n, d, f), bf), arg((n, d, f), bf), arg((n, f, d), bf))

    def lowered(tf):
        return jax.jit(functools.partial(
            he.hit_experts_ffn, interpret=False, tf=tf)).lower(*args)

    tf = he.hit_experts_tile(g, d, f, bf)
    assert tf == want
    if tf is not None:
        text = lowered(tf).compile().as_text()
        assert "tpu_custom_call" in text and "vmem_limit" not in text
    if tf != f:     # a wider step than the one it names does not fit
        with pytest.raises(Exception, match="(?i)vmem"):
            lowered(128 if tf is None else 2 * tf).compile()


# -- the train cell's step -------------------------------------------------------

def _train_cell_step(topo):
    """`internlm2-train-fsdp4`'s step as `benchmark/harness/drivers/
    train_step.py` builds it (published InternLM2-1.8B widths, 24 layers,
    `{"fsdp": 4}`, batch 16 x 4,097, AdamW, `remat_policy="full"`,
    `loss_chunk` 1024, bf16 activations over f32 weights), compiled whole
    for the four described chips with the flash kernel selected."""
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.models import (LlamaConfig, llama_init, llama_loss,
                                llama_param_specs)
    from ray_tpu.models.training import (batch_sharding_fn,
                                         make_sharded_train_step)
    from ray_tpu.parallel import create_mesh

    cfg = LlamaConfig(vocab_size=92544, dim=2048, n_layers=24, n_heads=16,
                      n_kv_heads=8, ffn_dim=8192, max_seq_len=4096,
                      rope_theta=1e6, dtype=jnp.bfloat16,
                      param_dtype=jnp.float32, remat=True,
                      remat_policy="full", attn_impl="flash",
                      loss_chunk=1024)
    mesh = create_mesh({"fsdp": 4}, topo.devices)
    specs = llama_param_specs(cfg)
    shapes = jax.eval_shape(lambda: llama_init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, specs)
    # the optimizer state sharded like the parameters it mirrors, as the
    # benchmark's driver places it
    by_shape = {(a.shape, a.dtype): a.sharding
                for a in jax.tree.leaves(params)}
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=by_shape.get(
                (a.shape, a.dtype), NamedSharding(mesh, PartitionSpec()))),
        jax.eval_shape(opt.init, shapes))
    tokens = np.zeros((16, 4097), np.int32)
    batch = {"tokens": jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=batch_sharding_fn(mesh, ("batch", None))(tokens))}
    _, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg), opt, mesh, specs)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = step_fn.lower(params, opt_state, batch)
    return lowered.compile()


def _makers(text, type_):
    """Opcodes of the instructions whose result is one array of exactly
    `type_`, the tuples and loops that pass it on left out."""
    return sorted(op for types, op in _HLO_LINE.findall(text)
                  if op not in _PLUMBING
                  and re.fullmatch(re.escape(type_) + r"(\{\S*)?", types))


def test_train_cell_step_keeps_the_flash_residuals_once(v5e_2x2):
    """A rematerialised layer never re-runs the attention kernel: the
    step holds THREE Pallas calls (forward, backward dq, backward dk/dv;
    four while the backward pass re-ran the forward, before PR 32). What
    is kept instead is, a chip, one `bf16[24, 4, 16, 4096, 128]` stack of
    kernel outputs (1.50 GiB) and one compact `f32[24, 4, 16, 4096]` stack
    of row statistics (24 MiB): each allocated once and written by one
    in-place `dynamic-update-slice` fusion, no copy, no transposed twin,
    and no stack of the kernel's own `[..., 4096, 1]` layout (which a
    tiled layout pads 128-fold).

    Memory, two accountings. What the chip's loader reserves is the
    arguments plus ONE heap, `peak_memory_in_bytes`: 12.55 GiB of the
    chip's 15.75 (11.03 before PR 32: the difference is the two stacks),
    which the chip confirmed (a step still ran beside 3,200 MiB of
    ballast a chip and not beside 3,328; PERF.md section 6, PR 32).
    `argument_size + temp_size` reads 15.72 GiB (13.03 before): it counts
    every scan-stacked residual twice, once a loop, so it overstates
    what is reserved by 3.2 GiB here; it is held under the chip's memory
    all the same, because it is the figure earlier notes quote."""
    compiled = _train_cell_step(v5e_2x2)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    in_place = ["custom-call", "dynamic-update-slice", "fusion"]
    assert _makers(text, "bf16[24,4,16,4096,128]") == in_place
    assert _makers(text, "f32[24,4,16,4096]") == in_place
    assert "[24,4,16,4096,1]" not in text
    m = compiled.memory_analysis()
    gib = float(1 << 30)
    assert m.peak_memory_in_bytes / gib < 12.6
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) / gib < 15.75
