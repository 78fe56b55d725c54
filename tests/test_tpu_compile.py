"""The main path's kernels compile for the chip — checked without one.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`/opt/skills/guides/on-chip-measurement` §2):
what Mosaic refuses (a slice not aligned to the tiling, too much SMEM or
VMEM) it refuses here, at no chip time. Interpret mode
(tests/kernels/) cannot see any of that, which is how a kernel that had
never compiled was the default for every prompt token.

Each kernel test lowers one kernel at Llama-2-7B widths (32 heads of 128,
hidden 4096, inter 11008) for a described v5e device and asserts that
`tpu_custom_call` is in the compiled text — a seam that resolved to a
reference cannot pass as a kernel. The MoE tests hold the compiled expert
FFN (the dense form: plain XLA; the grouped form: two Pallas kernels behind
`ops/grouped_ffn.py`) to the stored layout of the expert stacks: no copy of
a stack in any program. A compile that passes is a compile, never a chip
run.

This file lives at the top of tests/ on purpose: tests/kernels/conftest.py
wraps every test there in interpret mode, under which nothing reaches
Mosaic. The topology is described inside a fixture (never at import: the
driver's xdist workers all import this file, and only one process may
load libtpu), and the compiles run in this process with the persistent
compilation cache off around them (a described-device entry cannot be
read back without a chip, and warns).
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-2-7B attention and pool shapes, as served.
HEADS, HEAD_DIM, HIDDEN, INTER = 32, 128, 4096, 11008
BLOCKS, BLOCK_SIZE = 1600, 16
POOL_DTYPES = {"bf16": jnp.bfloat16, "fp8_e5m2": jnp.float8_e5m2}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_for_chip(one_chip):
    """compile(fn, *(shape, dtype)) -> the compiled program, for the
    described chip, with the persistent compilation cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_program(fn, *specs, donate=()):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

    yield compile_program
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(compiled_for_chip):
    """compile(fn, *(shape, dtype)) -> compiled text."""
    return lambda fn, *specs, donate=(): compiled_for_chip(
        fn, *specs, donate=donate).as_text()


def _pool(dtype, hkv=HEADS):
    return ((BLOCKS, hkv, BLOCK_SIZE, HEAD_DIM), dtype)


# (rows, table width, query heads, KV heads): Llama-2-7B at bs=96, and
# the decode programs of the benchmark's two per-head cells (128 rows x
# 64-block tables: Mistral-7B's 8 KV heads x 4, DeepSeek-MoE's 16 x 1).
DECODE_SHAPES = {"llama7b-96x32": (96, 32, HEADS, HEADS),
                 "mistral7b-128x64": (128, 64, 32, 8),
                 "dsmoe16b-128x64": (128, 64, 16, 16)}


@pytest.mark.parametrize("shape", sorted(DECODE_SHAPES))
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_paged_attention_compiles(compile_for_chip, pool, shape):
    """The decode kernel of decode_fused/decode_cont."""
    from intellillm_tpu.ops.pallas.paged_attention import paged_attention
    (b, w, hq, hkv), dtype = DECODE_SHAPES[shape], POOL_DTYPES[pool]

    def fn(q, k_cache, v_cache, tables, ctx):
        return paged_attention(q, k_cache, v_cache, tables, ctx,
                               HEAD_DIM ** -0.5, return_lse=True)

    text = compile_for_chip(
        fn, ((b, 1, hq, HEAD_DIM), jnp.bfloat16), _pool(dtype, hkv),
        _pool(dtype, hkv), ((b, w), jnp.int32), ((b, ), jnp.int32))
    assert "tpu_custom_call" in text


# (rows, table width, query heads, KV heads). (2048, 256) is the default
# token budget with max_model_len 4096 tables; 512 x 64 is the cells'
# largest `mixed` step.
RAGGED_SHAPES = {"llama7b-256x32": (256, 32, HEADS, HEADS),
                 "llama7b-2048x256": (2048, 256, HEADS, HEADS),
                 "mistral7b-512x64": (512, 64, 32, 8),
                 "dsmoe16b-512x64": (512, 64, 16, 16)}


@pytest.mark.parametrize("shape", sorted(RAGGED_SHAPES))
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_ragged_paged_attention_compiles(compile_for_chip, pool, shape):
    """What the mixed seam selects on a TPU: the fused cache-write +
    attend kernel. (2048, 256) takes more SMEM than one launch may, so
    the seam must have split it."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    (rows, width, hq, hkv), dtype = RAGGED_SHAPES[shape], POOL_DTYPES[pool]

    def fn(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx):
        return ragged_paged_attention(q, k_new, v_new, k_cache, v_cache,
                                      slots, tables, ctx, HEAD_DIM ** -0.5)

    text = compile_for_chip(
        fn, ((rows, 1, hq, HEAD_DIM), jnp.bfloat16),
        ((rows, hkv, HEAD_DIM), dtype), ((rows, hkv, HEAD_DIM), dtype),
        _pool(dtype, hkv), _pool(dtype, hkv), ((rows, ), jnp.int32),
        ((rows, width), jnp.int32), ((rows, ), jnp.int32), donate=(3, 4))
    assert "tpu_custom_call" in text


def _kernel_dots(fn, *specs):
    """Dots in the text of the Pallas kernels `fn` launches (every copy a
    branch or a loop body holds counts), and the launches."""
    jaxpr = jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(shape, dtype)
                                 for shape, dtype in specs))
    text = str(jaxpr)
    return text.count("dot_general"), text.count("pallas_call")


@pytest.mark.parametrize("shape", ["mistral7b-128x64", "dsmoe16b-128x64",
                                   "llama7b-96x32"])
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_paged_kernels_hold_one_update_a_piece_size(pool, shape):
    """What PR 27 paid for in set-up time: `_switch` instantiates the
    piece's update once a tail size, plus the full group. Each copy is two
    dots (QK and PV, batched over the head block), whatever the heads: a
    per-head unroll would multiply the kernel's text, and with it every
    program's lowering, by the heads of a block."""
    from intellillm_tpu.ops.pallas import paged_attention as pa
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    (b, w, hq, hkv), dtype = DECODE_SHAPES[shape], POOL_DTYPES[pool]
    hp = pa._largest_divisor(hkv, pa._default_hp(jnp.zeros((), dtype)))
    ppg = pa._pages_per_group(
        w, hp * BLOCK_SIZE * HEAD_DIM * jnp.dtype(dtype).itemsize)
    row_updates = ppg // pa._sub_pages(ppg) + 1

    def decode(q, k_cache, v_cache, tables, ctx):
        return pa.paged_attention(q, k_cache, v_cache, tables, ctx,
                                  HEAD_DIM ** -0.5, return_lse=True)

    dots, launches = _kernel_dots(
        decode, ((b, 1, hq, HEAD_DIM), jnp.bfloat16), _pool(dtype, hkv),
        _pool(dtype, hkv), ((b, w), jnp.int32), ((b, ), jnp.int32))
    assert launches == 1 and dots == 2 * row_updates

    rows = 512

    def ragged(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx, starts):
        return ragged_paged_attention(q, k_new, v_new, k_cache, v_cache,
                                      slots, tables, ctx, HEAD_DIM ** -0.5,
                                      run_starts=starts)

    dots, launches = _kernel_dots(
        ragged, ((rows, 1, hq, HEAD_DIM), jnp.bfloat16),
        ((rows, hkv, HEAD_DIM), dtype), ((rows, hkv, HEAD_DIM), dtype),
        _pool(dtype, hkv), _pool(dtype, hkv), ((rows, ), jnp.int32),
        ((rows, w), jnp.int32), ((rows, ), jnp.int32), ((rows, ), jnp.int32))
    # A tile of many: its in-tile part, and an update a piece size of its
    # own coarser sub-groups (a quarter of a group), two dots each.
    tile_updates = ppg // max(ppg // 4, 1) + 1
    assert launches == 1
    assert dots == 2 * row_updates + 2 * (1 + tile_updates)


# smallthinker-21ba3b-l8's launches under its 4,096-token window: the
# cell's 512-row chunk and its 16-row decode step, both at the widest table
# (8,192 tokens of 16-token blocks), 28 query heads over 4 KV heads.
@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("rows", [16, 512])
def test_window_kernels_compile(compile_for_chip, rows, windowed):
    """Both paged kernels with a row's first visible position (`lo`): the
    walk starts at `lo`'s page and the programs are named apart."""
    from intellillm_tpu.ops.pallas.paged_attention import paged_attention
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    width, hq, hkv, dtype = 512, 28, 4, jnp.bfloat16
    lo = (((rows, ), jnp.int32), ) if windowed else ()

    def ragged(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx, *lo):
        return ragged_paged_attention(q, k_new, v_new, k_cache, v_cache,
                                      slots, tables, ctx, HEAD_DIM ** -0.5,
                                      None, *lo)

    def paged(q, k_cache, v_cache, tables, ctx, *lo):
        return paged_attention(q, k_cache, v_cache, tables, ctx,
                               HEAD_DIM ** -0.5, return_lse=True,
                               lo=lo[0] if lo else None)

    text = compile_for_chip(
        ragged, ((rows, 1, hq, HEAD_DIM), jnp.bfloat16),
        ((rows, hkv, HEAD_DIM), dtype), ((rows, hkv, HEAD_DIM), dtype),
        _pool(dtype, hkv), _pool(dtype, hkv), ((rows, ), jnp.int32),
        ((rows, width), jnp.int32), ((rows, ), jnp.int32), *lo,
        donate=(3, 4))
    assert "tpu_custom_call" in text
    assert ("_ragged_call_window" in text) == windowed
    # The decode kernel takes a whole batch's tables into SMEM in one
    # launch (1 MiB on the chip): 128 rows x 512 blocks fit, 512 do not.
    rows = min(rows, 128)
    lo = (((rows, ), jnp.int32), ) if windowed else ()
    text = compile_for_chip(
        paged, ((rows, 1, hq, HEAD_DIM), jnp.bfloat16), _pool(dtype, hkv),
        _pool(dtype, hkv), ((rows, width), jnp.int32),
        ((rows, ), jnp.int32), *lo)
    assert "tpu_custom_call" in text
    assert ("_paged_attention_call_window" in text) == windowed


# The `mixed` chunk step of the cells that run the ragged kernel, with the
# rows' runs handed in: (table width, query heads, KV heads, pool, window).
# (The A.X-K1 cell's prompt steps run the latent kernel.)
TILED_CELLS = {
    "mistral7b": (64, 32, 8, jnp.float8_e5m2, False),
    "dsmoe16b": (64, 16, 16, jnp.float8_e5m2, False),
    "lfm2moe-heads-of-64-stored-128": (64, 32, 8, jnp.bfloat16, False),
    "smallthinker-full": (512, 28, 4, jnp.bfloat16, False),
    "smallthinker-window": (512, 28, 4, jnp.bfloat16, True),
}


@pytest.mark.parametrize("cell", sorted(TILED_CELLS))
def test_tiled_ragged_kernel_compiles_at_the_cells_shapes(compile_for_chip,
                                                          cell):
    """512 rows with `run_starts`: one program whatever the runs are (the
    split into tiles is data), named as the attention shares read it."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    width, hq, hkv, dtype, windowed = TILED_CELLS[cell]
    rows = 512
    lo = (((rows, ), jnp.int32), ) if windowed else ()

    def fn(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx, starts,
           *lo):
        return ragged_paged_attention(
            q, k_new, v_new, k_cache, v_cache, slots, tables, ctx,
            HEAD_DIM ** -0.5, None, *lo, run_starts=starts)

    text = compile_for_chip(
        fn, ((rows, 1, hq, HEAD_DIM), jnp.bfloat16),
        ((rows, hkv, HEAD_DIM), dtype), ((rows, hkv, HEAD_DIM), dtype),
        _pool(dtype, hkv), _pool(dtype, hkv), ((rows, ), jnp.int32),
        ((rows, width), jnp.int32), ((rows, ), jnp.int32),
        ((rows, ), jnp.int32), *lo, donate=(3, 4))
    assert "tpu_custom_call" in text
    assert ("_ragged_call_window" in text) == windowed
    assert re.search(r"_ragged_call(?!_window)\b", text) or windowed


def test_mixed_seam_selects_the_kernel(compile_for_chip):
    """The seam itself (ops/ragged_attention), told it is on a TPU,
    resolves to the Pallas kernel and not to the reference."""
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.ops.ragged_attention import ragged_fused_attention
    rows, width, dtype = 256, 32, jnp.float8_e5m2

    def fn(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx):
        return ragged_fused_attention(q, k_new, v_new, k_cache, v_cache,
                                      slots, tables, ctx, HEAD_DIM ** -0.5)

    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        text = compile_for_chip(
            fn, ((rows, 1, HEADS, HEAD_DIM), jnp.bfloat16),
            ((rows, HEADS, HEAD_DIM), jnp.bfloat16),
            ((rows, HEADS, HEAD_DIM), jnp.bfloat16), _pool(dtype),
            _pool(dtype), ((rows, ), jnp.int32), ((rows, width), jnp.int32),
            ((rows, ), jnp.int32), donate=(3, 4))
    finally:
        set_use_pallas(None)
    assert "tpu_custom_call" in text


def test_staged_decode_selects_the_kernel(compile_for_chip):
    """`layers/attention.py`'s K-step decode substep with Pallas forced on
    (what a TPU resolves to): the pool part is the paged kernel, reached
    through its seam."""
    from intellillm_tpu.layers.attention import (AttentionMetadata,
                                                 PagedAttention)
    from intellillm_tpu.ops.dispatch import set_use_pallas
    attn = PagedAttention(HEADS, HEAD_DIM, HEAD_DIM ** -0.5)
    rows, width, chunk, dtype = 128, 64, 8, jnp.float8_e5m2

    def fn(q, k, v, k_pool, v_pool, k_stage, v_stage, tables, ctx, idx):
        meta = AttentionMetadata(context_lens=ctx, block_tables=tables,
                                 staged=True, stage_index=idx)
        return attn(q, k, v, (k_pool, v_pool, k_stage, v_stage), meta)[0]

    qkv = ((rows, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    stage = ((rows, chunk, HEADS, HEAD_DIM), jnp.bfloat16)
    set_use_pallas(True)
    try:
        text = compile_for_chip(
            fn, qkv, qkv, qkv, _pool(dtype), _pool(dtype), stage, stage,
            ((rows, width), jnp.int32), ((rows, ), jnp.int32),
            ((), jnp.int32))
    finally:
        set_use_pallas(None)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dout", [HIDDEN, INTER])
def test_bgmv_compiles(compile_for_chip, dout):
    """Batched-LoRA delta, rank 16, 4 adapter slots, 4096 -> dout."""
    from intellillm_tpu.ops.pallas.bgmv import bgmv
    rows, slots, rank = 256, 4, 16
    text = compile_for_chip(
        bgmv, ((rows, 1, HIDDEN), jnp.bfloat16),
        ((slots, HIDDEN, rank), jnp.bfloat16),
        ((slots, rank, dout), jnp.bfloat16), ((rows, ), jnp.int32))
    assert "tpu_custom_call" in text


def test_quant_matmul_int4_compiles(compile_for_chip):
    """AWQ/GPTQ int4 dequant-matmul, group 128, 4096 -> 11008."""
    from intellillm_tpu.ops.pallas.quant_matmul import quant_matmul_int4
    rows, groups = 256, HIDDEN // 128

    def fn(x, q4, s4, z4):
        return quant_matmul_int4(x, {"q4": q4, "s4": s4, "z4": z4})

    text = compile_for_chip(
        fn, ((rows, HIDDEN), jnp.bfloat16),
        ((HIDDEN // 2, INTER), jnp.uint8), ((groups, INTER), jnp.float32),
        ((groups, INTER), jnp.float32))
    assert "tpu_custom_call" in text


def test_quant_matmul_int4_lut_compiles(compile_for_chip):
    """SqueezeLLM per-channel 16-entry LUT variant, 4096 -> 11008."""
    from intellillm_tpu.ops.pallas.quant_matmul import quant_matmul_int4_lut

    def fn(x, q4lut, lut):
        return quant_matmul_int4_lut(x, {"q4lut": q4lut, "lut": lut})

    text = compile_for_chip(
        fn, ((256, HIDDEN), jnp.bfloat16),
        ((HIDDEN // 2, INTER), jnp.uint8), ((16, INTER), jnp.float32))
    assert "tpu_custom_call" in text


# --- MoE expert FFN: no copy of an expert stack in any program -----------

# (experts held, hidden, expert inner, top-k) as published.
DSMOE16B = (64, 2048, 1408, 6)
MIXTRAL_8X7B = (8, 4096, 14336, 2)
AXK1_SHARE = (12, 7168, 2048, 8)          # 12 of the router's 192
LFM2_8B_A1B = (32, 2048, 1792, 4)
SMALLTHINKER = (64, 2560, 768, 6)


def _moe_specs(rows, widths, n_router=None):
    """x, router, and the three stacks in their stored layout [N, I, D]."""
    n, d, i, _ = widths
    stack = ((n, i, d), jnp.bfloat16)
    return (((rows, d), jnp.bfloat16), ((d, n_router or n), jnp.float32),
            stack, stack, stack)


def _stack_sized_copies(text, widths):
    """Lines of the compiled text that copy an operand with as many
    elements as one expert stack (N*D*I), in whatever layout."""
    n, d, i, _ = widths
    found = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) copy(-start)?\(", line)
        if m and any(math.prod(map(int, dims.split(","))) == n * d * i
                     for dims in re.findall(r"\[([\d,]+)\]", m.group(1))):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("rows,loop", [(128, 0), (128, 8), (256, 0),
                                       (512, 0)],
                         ids=["decode-128", "decode-128-in-loop-of-8",
                              "prefill-256", "dense-512-two-chunks"])
def test_moe_dense_copies_no_expert_stack(compiled_for_chip, rows, loop):
    """The dense form at DeepSeek-MoE-16B widths (`moe_ffn` takes it up to
    256 rows; 512 rows in two chunks is what it was before the grouped
    form took them). Alone at 128 rows XLA never copied; inside a loop
    (the K=8 loop of decode_fused/decode_cont, the chunk `while` of two
    chunks) its layout assignment wants w1/w3 with D minor-most and,
    handed [N, D, I], copied both 369 MB stacks in front of the loop
    (0.74 GB of temporaries a layer)."""
    from intellillm_tpu.layers.moe import Routing, moe_ffn, moe_ffn_dense
    top_k = DSMOE16B[3]
    form = moe_ffn if rows <= 256 else moe_ffn_dense

    def ffn(x, gate_w, w1, w2, w3):
        return form(x, gate_w, w1, w2, w3, top_k,
                    routing=Routing(renormalize=False))

    def looped(x, *weights):
        return jax.lax.fori_loop(0, loop, lambda _, h: ffn(h, *weights), x)

    compiled = compiled_for_chip(looped if loop else ffn,
                                 *_moe_specs(rows, DSMOE16B))
    assert _stack_sized_copies(compiled.as_text(), DSMOE16B) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


# name -> (widths, router width, rows, Routing settings, most bytes of
# temporaries): the three MoE cells' 512-row `mixed` programs and a
# 2048-row Mixtral-8x7B prefill. The temporaries are the sorted rows
# [T*K, D], the activation [T*K, I] and the float32 output [T*K, D]; for
# the share T*K is all 4,096 choices, of which 1 in 16 is held here.
GROUPED_PROGRAMS = {
    "dsmoe16b-512": (DSMOE16B, 64, 512, dict(renormalize=False), 0.06e9),
    "axk1-share-512": (AXK1_SHARE, 192, 512,
                       dict(scoring="sigmoid", n_group=8, topk_group=4,
                            scale=2.5), 0.3e9),
    "lfm2moe-512": (LFM2_8B_A1B, 32, 512,
                    dict(scoring="sigmoid", sum_eps=1e-6), 0.06e9),
    "mixtral8x7b-2048": (MIXTRAL_8X7B, 8, 2048, {}, 0.25e9),
}


@pytest.mark.parametrize("program", sorted(GROUPED_PROGRAMS))
def test_moe_grouped_copies_no_expert_stack(compiled_for_chip, force_pallas,
                                            program):
    """`moe_ffn` over 256 rows takes the grouped form: its two kernels
    compile for the chip at the cells' widths (a 7168-wide hidden, a
    1408-wide inner: 11 lane tiles), read the stacks where they are stored
    (no copy of one) and leave the temporaries stated above."""
    from intellillm_tpu.layers.moe import Routing, moe_ffn
    widths, n_router, rows, settings, most = GROUPED_PROGRAMS[program]
    force_pallas(True)

    def ffn(x, gate_w, w1, w2, w3):
        return moe_ffn(x, gate_w, w1, w2, w3, widths[3],
                       routing=Routing(**settings))

    compiled = compiled_for_chip(ffn, *_moe_specs(rows, widths, n_router))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _stack_sized_copies(text, widths) == []
    assert compiled.memory_analysis().temp_size_in_bytes < most


# name -> (widths, router width, rows, Routing settings, activation,
# substeps of a K-loop around the layer or 0): steps of so few rows that
# the rule takes the grouped form for the experts nobody chose: the
# SmallThinker cell's 16-row decode step inside its K=8 loop, a lone
# request's row, and the 16- and 32-row `mixed` steps of two other cells.
FEW_ROW_PROGRAMS = {
    "smallthinker-16-in-loop-of-8": (SMALLTHINKER, 64, 16, {}, "relu", 8),
    "smallthinker-1-in-loop-of-8": (SMALLTHINKER, 64, 1, {}, "relu", 8),
    "dsmoe16b-16": (DSMOE16B, 64, 16, dict(renormalize=False), "silu", 0),
    "axk1-share-32": (AXK1_SHARE, 192, 32,
                      dict(scoring="sigmoid", n_group=8, topk_group=4,
                           scale=2.5), "silu", 0),
}


@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "masked"])
@pytest.mark.parametrize("program", sorted(FEW_ROW_PROGRAMS))
def test_moe_few_rows_take_the_grouped_kernels(compiled_for_chip,
                                               force_pallas, program, masked):
    """`moe_ffn` at a step of few rows lowers to the two grouped kernels
    for the described chip, inside a `lax.scan` as `decode_fused` /
    `decode_cont` run it (the number of visits is a value of the loop's
    body, not of the program), with no copy of an expert stack for the
    life of the program and temporaries of one 128-row tile. `masked`:
    with the step's live rows as one more operand (`moe_ffn(live=)`, the
    runner's `context_lens > 0`): the same two kernels and nothing more
    of an expert stack's size."""
    from intellillm_tpu.layers.moe import Routing, grouped_rows, moe_ffn
    widths, n_router, rows, settings, activation, loop = FEW_ROW_PROGRAMS[
        program]
    assert grouped_rows(rows, widths[3], n_router)
    force_pallas(True)

    def ffn(x, gate_w, w1, w2, w3, context_lens=None):
        return moe_ffn(x, gate_w, w1, w2, w3, widths[3],
                       routing=Routing(**settings), activation=activation,
                       count_visited=True,
                       live=context_lens > 0 if masked else None)

    def looped(x, *operands):
        def substep(h, _):
            out, visited = ffn(h, *operands)
            return (h + out).astype(h.dtype), visited
        return jax.lax.scan(substep, x, None, length=loop)

    specs = _moe_specs(rows, widths, n_router)
    if masked:
        specs += (((rows, ), jnp.int32), )
    compiled = compiled_for_chip(looped if loop else ffn, *specs)
    text = compiled.as_text()
    assert "_grouped_gate_up_call" in text and "_grouped_down_call" in text
    assert text.count("tpu_custom_call") == 2
    assert _stack_sized_copies(text, widths) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9


@pytest.mark.parametrize("rows", [128, 256])
@pytest.mark.parametrize("widths,n_router,settings", [
    (w, r, s) for w, r, _, s, _ in GROUPED_PROGRAMS.values()],
    ids=[name.rsplit("-", 1)[0] for name in GROUPED_PROGRAMS])
def test_moe_up_to_256_rows_is_the_dense_program(rows, widths, n_router,
                                                 settings):
    """A decode step (128 rows) and a `mixed` step of up to 256 rows lower
    to the text `moe_ffn_dense` lowers to, operation for operation: the
    grouped form adds nothing to the programs it does not take
    (`moe_ffn_dense` itself is the parent's, untouched)."""
    from intellillm_tpu.layers.moe import Routing, moe_ffn, moe_ffn_dense
    routing = Routing(**settings)
    args = [jax.ShapeDtypeStruct(shape, dtype)
            for shape, dtype in _moe_specs(rows, widths, n_router)]
    texts = [jax.jit(lambda *a, form=form: form(
        *a, widths[3], routing=routing)).lower(*args).as_text()
        for form in (moe_ffn, moe_ffn_dense)]
    assert texts[0] == texts[1]
    assert "ragged_dot" not in texts[0] and "custom_call" not in texts[0]


# A.X-K1's latent attention, as the cell `axk1-ep16-decode-closed` runs it:
# 64 heads over one stored vector of 640 lanes (512 + 64 + pad), the value
# its first 512; a 9,000-block bf16 pool; max_model_len 4096 tables.
# Xing4.0 (`xing4-ep8-decode-closed`) launches the same kernels at 32 heads.
MLA_HEADS, MLA_DK, MLA_DV, MLA_BLOCKS = 64, 640, 512, 9000
MLA_SCALE = 192 ** -0.5 * 1.3466 ** 2


def _latent_pool(dtype=jnp.bfloat16):
    return ((MLA_BLOCKS, 1, BLOCK_SIZE, MLA_DK), dtype)


@pytest.mark.parametrize("heads", [MLA_HEADS, 32])
def test_mla_paged_attention_compiles(compile_for_chip, heads):
    """The pool walk of decode_fused/decode_cont at 128 rows x 64-block
    tables, at both latent cells' head counts."""
    from intellillm_tpu.ops.pallas.mla_attention import mla_paged_attention
    b, w = 128, 64

    def fn(q, pool, tables, ctx):
        return mla_paged_attention(q, pool, tables, ctx, MLA_SCALE, MLA_DV)

    text = compile_for_chip(
        fn, ((b, heads, MLA_DK), jnp.bfloat16), _latent_pool(),
        ((b, w), jnp.int32), ((b, ), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,width,heads", [
    (256, 32, MLA_HEADS), (4096, 256, MLA_HEADS),
    # `decode-closed`'s `mixed` launches under 64-block tables
    (512, 64, MLA_HEADS), (128, 64, MLA_HEADS),
    (512, 64, 32), (256, 64, 32), (128, 64, 32)])
def test_mla_ragged_paged_attention_compiles(compile_for_chip, rows, width,
                                             heads):
    """The mixed step's fused latent-cache write + walk. (4096, 256) is
    the warm-up shape: more SMEM than one launch may take, so the wrapper
    must have split it."""
    from intellillm_tpu.ops.pallas.mla_attention import (
        mla_ragged_paged_attention)

    def fn(q, new, pool, slots, tables, ctx):
        return mla_ragged_paged_attention(q, new, pool, slots, tables, ctx,
                                          MLA_SCALE, MLA_DV)

    text = compile_for_chip(
        fn, ((rows, heads, MLA_DK), jnp.bfloat16),
        ((rows, MLA_DK), jnp.bfloat16), _latent_pool(),
        ((rows, ), jnp.int32), ((rows, width), jnp.int32),
        ((rows, ), jnp.int32), donate=(2, ))
    assert "tpu_custom_call" in text
    # The pool is updated in place: no copy of it in the program.
    pool_elems = MLA_BLOCKS * BLOCK_SIZE * MLA_DK
    assert not [
        line for line in text.splitlines()
        if re.search(r"= (.*?) copy(-start)?\(", line) and any(
            math.prod(map(int, dims.split(","))) == pool_elems
            for dims in re.findall(r"\[([\d,]+)\]", line.split("=")[1]))]


@pytest.mark.parametrize("kernel", ["ragged", "paged"])
def test_mla_kernels_compile_for_a_float32_pool_at_highest_precision(
        compile_for_chip, kernel):
    """A `--dtype float32` run under `jax.default_matmul_precision(
    "highest")` (the reference checks' sharp version) at 32 heads and
    512-block tables: the float32 walk buffers, query block and the
    products' passes take 25 MB of VMEM, over the compiler's default scope
    (on the chip: `Scoped allocation with size 25.37M and limit 24.00M`,
    my chip run c1, PR 54), so the wrappers ask for a wider one for a pool
    wider than 2 bytes (`_vmem_limit`) and for no other."""
    from intellillm_tpu.ops.pallas import mla_attention as mla
    assert mla._vmem_limit(jnp.bfloat16) is None
    assert mla._vmem_limit(jnp.float32) == 64 << 20
    f32 = jnp.float32
    rows, w = (256, 512) if kernel == "ragged" else (16, 512)

    def ragged(q, new, pool, slots, tables, ctx):
        return mla.mla_ragged_paged_attention(q, new, pool, slots, tables,
                                              ctx, MLA_SCALE, MLA_DV)

    def paged(q, pool, tables, ctx):
        return mla.mla_paged_attention(q, pool, tables, ctx, MLA_SCALE,
                                       MLA_DV)

    with jax.default_matmul_precision("highest"):
        if kernel == "ragged":
            text = compile_for_chip(
                ragged, ((rows, 32, MLA_DK), f32), ((rows, MLA_DK), f32),
                _latent_pool(f32), ((rows, ), jnp.int32),
                ((rows, w), jnp.int32), ((rows, ), jnp.int32), donate=(2, ))
        else:
            text = compile_for_chip(
                paged, ((rows, 32, MLA_DK), f32), _latent_pool(f32),
                ((rows, w), jnp.int32), ((rows, ), jnp.int32))
    assert "tpu_custom_call" in text


def test_mla_seams_select_the_kernels(compile_for_chip):
    """`layers/mla_attention.py` through both seams with Pallas forced on
    (what a TPU resolves to): each program holds a kernel."""
    from intellillm_tpu.layers.attention import AttentionMetadata
    from intellillm_tpu.layers.mla_attention import LatentAttention
    from intellillm_tpu.ops import dispatch
    attn = LatentAttention(MLA_HEADS, MLA_DV, 64, MLA_SCALE)
    b, w, c = 128, 64, 8

    def mixed(q, new, pool, slots, tables, ctx):
        meta = AttentionMetadata(slot_mapping=slots, context_lens=ctx,
                                 block_tables=tables)
        return attn(q, new, (pool, ), meta)

    def staged(q, new, pool, stage, tables, ctx, k):
        meta = AttentionMetadata(context_lens=ctx, block_tables=tables,
                                 staged=True, stage_index=k)
        return attn(q, new, (pool, stage), meta)

    dispatch.set_use_pallas(True)
    try:
        q = ((b, 1, MLA_HEADS, 576), jnp.bfloat16)
        new = ((b, 1, 576), jnp.bfloat16)
        text = compile_for_chip(
            mixed, q, new, _latent_pool(), ((b, 1), jnp.int32),
            ((b, w), jnp.int32), ((b, ), jnp.int32), donate=(2, ))
        assert "tpu_custom_call" in text
        text = compile_for_chip(
            staged, q, new, _latent_pool(),
            ((b, c, 1, MLA_DK), jnp.bfloat16), ((b, w), jnp.int32),
            ((b, ), jnp.int32), ((), jnp.int32))
        assert "tpu_custom_call" in text
    finally:
        dispatch.set_use_pallas(None)


# --- the continuation program of the benchmark's three cells ---------------

# configuration file -> layers kept here (a dense and, where the model has
# them, a MoE layer: every kind of layer at its published width).
CELL_CONFIGS = {"mistral-7b-v0.2": 2, "deepseek-moe-16b-l5": 2,
                "ax-k1-ep16-l7": 2}
CELL_ROWS, CELL_TABLE_WIDTH, CELL_K, CELL_BLOCKS = 128, 64, 8, 9000


def _cell_runner(one_chip, tmp_path, config, layers):
    """A `ModelRunner` of the cell configuration `config` cut to `layers`
    layers (the cell's dtype, quantization and pool dtype, published
    widths; 512-token steps, 128 seats), its parameters and caches
    shapes on the described chip: (runner, model, params, kv, hf).
    `set_use_pallas(True)` is the caller's, around this and its
    lowerings."""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.utils import STR_DTYPE_TO_JNP
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs", config + ".json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    hf = hf_config_of(cell, rehearsal=False)
    hf["num_hidden_layers"] = layers
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"],
        quantization=serve["quantization"])
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    runner = ModelRunner(
        model, params, model_config,
        SchedulerConfig(max_num_batched_tokens=512,
                        max_num_seqs=CELL_ROWS,
                        max_model_len=serve["max_model_len"],
                        enable_chunked_prefill=True),
        CacheConfig(cache_dtype=serve["kv_cache_dtype"],
                    num_device_blocks_override=CELL_BLOCKS,
                    swap_space_gib=0.01),
        ParallelConfig())
    pools, heads, width = model_config.get_cache_row_shape()
    pool_dtype = jnp.dtype(STR_DTYPE_TO_JNP[
        serve["dtype"] if serve["kv_cache_dtype"] == "auto"
        else serve["kv_cache_dtype"]])
    kv = [tuple(jax.ShapeDtypeStruct((CELL_BLOCKS, heads, 16, width),
                                     pool_dtype, sharding=one_chip)
                for _ in range(pools)) for _ in range(layers)]
    return runner, model, params, kv, hf


@pytest.mark.parametrize("config", sorted(CELL_CONFIGS))
def test_decode_cont_takes_joiners_at_no_more_temporaries(
        compiled_for_chip, one_chip, tmp_path, config):
    """`decode_cont` as the cells dispatch it (128 rows x 64-block tables,
    K=8, the cell's dtype, quantization and pool dtype, published widths)
    compiles for the described v5e with the joiners' token and mask
    columns, holds both paged kernels, and needs no more temporaries
    than the program it replaces (input tokens sliced from the previous
    step's packed output alone)."""
    from intellillm_tpu.ops.dispatch import set_use_pallas

    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner, model, params, kv, _ = _cell_runner(
            one_chip, tmp_path, config, CELL_CONFIGS[config])
        b, w, k = CELL_ROWS, CELL_TABLE_WIDTH, CELL_K

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        # _pack's layout at logprob_k = 1, with an expert-parallel
        # share's two counter columns and the two of the expert visits.
        share = (2 * bool(getattr(model, "moe_choices_per_token", 0))
                 + 2 * getattr(model, "counts_expert_visits", False))
        packed = sds(jnp.int32, b, 4 * k + share)
        i32, f32 = jnp.int32, jnp.float32
        rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b), sds(f32, b),
                sds(i32, b), sds(f32, b), sds(f32, b), sds(jnp.uint32, b),
                sds(f32, b), sds(f32, b), sds(f32, b), None, None)
        flags = dict(prev_t1=k, num_steps=k, logprob_k=1, do_topk=False,
                     do_topp=False, do_minp=False, do_penalties=False,
                     do_random=False)

        def sliced_only(params, kv_caches, prev_packed, *rest, prev_t1,
                        **flags):
            return runner._decode_fn(
                params, kv_caches, prev_packed[:, prev_t1 - 1:prev_t1],
                *rest, **flags)

        # `compiled_for_chip` is asked for its side: the persistent
        # compilation cache is off while these two compile.
        assert not jax.config.jax_enable_compilation_cache
        joined = runner._jit_decode_cont.lower(
            params, kv, packed, sds(i32, b, 1), sds(jnp.bool_, b, 1),
            *rest, **flags).compile()
        before = jax.jit(
            sliced_only, static_argnames=tuple(flags),
            donate_argnames=("kv_caches", )).lower(
                params, kv, packed, *rest, **flags).compile()
    finally:
        set_use_pallas(None)
    assert joined.as_text().count("tpu_custom_call") >= 2
    temp = joined.memory_analysis().temp_size_in_bytes
    assert temp <= 1.01 * before.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("config", ["deepseek-moe-16b-l5",
                                    "mistral-7b-v0.2"])
def test_mixed_head_runs_on_the_emitting_rows(compiled_for_chip, one_chip,
                                              tmp_path, config):
    """`mixed` at 512 rows x 64-block tables with 16 emitting slots (what
    a prompt step of the cells is: `_decode_fn_single` `emit_rows`), the
    cell's dtype, quantization and pool dtype, one layer at the published
    widths, compiled for the described v5e: one head of `[16, vocab]`
    under a conditional and no float32 value of `[512, vocab]`; without
    `emit_rows` the logits of all 512 rows are there, as they were."""
    from intellillm_tpu.ops.dispatch import set_use_pallas

    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner, _, _, kv, hf = _cell_runner(one_chip, tmp_path, config, 1)
        assert not jax.config.jax_enable_compilation_cache
        texts = {slots: runner.lower_mixed_step(
            kv, 512, CELL_TABLE_WIDTH, do_random=False,
            emit_slots=slots).compile().as_text() for slots in (16, None)}
    finally:
        set_use_pallas(None)
    vocab = hf["vocab_size"]
    every_row = re.compile(r"\b\w+\[512,%d\]" % vocab)
    few_rows = re.compile(r"\b(f32|bf16)\[16,%d\]" % vocab)
    assert every_row.search(texts[None])
    assert not every_row.search(texts[16])
    assert few_rows.search(texts[16]) and "conditional(" in texts[16]
    assert "conditional(" not in texts[None]
    assert all("tpu_custom_call" in text for text in texts.values())


def test_joined_continuation_is_the_plain_one_s_executable(
        tiny_llama_dir, example_prompts):
    """The runner's bucket key of a continuation that takes joiners equals
    a plain one's, and jit holds one `decode_cont` executable for both."""
    from intellillm_tpu import LLM, SamplingParams
    from intellillm_tpu.obs import get_compile_tracker

    tracker = get_compile_tracker()
    tracker.reset_for_testing()
    llm = LLM(model=tiny_llama_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01,
              num_decode_steps=8)
    engine = llm.llm_engine
    runner = engine.worker.model_runner
    joins = []
    dispatch = runner.execute_decode_cont

    def recording(cont, tables, first_token_rows, *args, **kwargs):
        joins.append(sum(int((rows >= 0).sum())
                         for rows in first_token_rows.values()))
        return dispatch(cont, tables, first_token_rows, *args, **kwargs)

    runner.execute_decode_cont = recording
    sp = SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True)
    pending = list(enumerate(example_prompts))
    while engine.has_unfinished_requests() or engine.has_inflight() \
            or pending:
        if pending and (len(pending) == len(example_prompts)
                        or engine._newest_decode_inflight() is not None):
            i, prompt = pending.pop(0)
            engine.add_request(str(i), prompt, sp)
        engine.step_pipelined()
    assert 0 in joins and max(joins) >= 1     # plain and joined, both ran
    snap = tracker.snapshot()
    assert snap["compiles"]["decode_cont"] == 1
    assert runner._jit_decode_cont._cache_size() == 1


@pytest.mark.parametrize("rows", [16, 32, 64, 128, 256, 512])
def test_join_gather_compiles_at_each_mixed_bucket(compiled_for_chip, rows):
    """The two programs that hand a continuation of 128 rows the first
    tokens of a prompt step in flight compile for the described v5e at
    every `mixed` bucket of the cells (the step's packed output at
    logprob_k = 1 with an expert-parallel share's counter columns): the
    step's first samples come out at the one length whatever its rows,
    the gather over them returns the `join_tokens` operand `decode_cont`
    takes, and both hold next to nothing."""
    import functools

    from intellillm_tpu.worker.model_runner import ModelRunner

    i32 = jnp.int32
    length = 512
    first = compiled_for_chip(
        functools.partial(ModelRunner._first_tokens_fn, length=length),
        ((rows, 4 + 4), i32))
    assert f"s32[{length}]" in first.as_text()
    gather = compiled_for_chip(
        ModelRunner._join_gather_fn, ((CELL_ROWS, 1), i32),
        ((length, ), i32), ((CELL_ROWS, ), i32))
    assert f"s32[{CELL_ROWS},1]" in gather.as_text()
    for compiled in (first, gather):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 64 * 1024


# --- a hybrid stack: state layers, 64-wide heads stored at 128 lanes --------


def _row_loops(text, shape):
    """The `while` bodies of a compiled program that hold, themselves or
    in a fusion they call, a `dynamic-update-slice` whose result is
    `shape` (as `bf16[128,73728]`): a scatter that XLA expanded into a
    loop of one row a turn. (A K-step scan replaces its carried stage
    whole and holds none.)"""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    dus = re.compile(r" = " + re.escape(shape) + r"\S* dynamic-update-slice\(")

    def holds(comp):
        return any(dus.search(line) or any(
            holds(c) for c in re.findall(r"calls=%([\w.\-]+)", line))
            for line in comps.get(comp, ()))

    bodies = {b for lines in comps.values() for line in lines
              if " while(" in line
              for b in re.findall(r"body=%([\w.\-]+)", line)}
    return sorted(b for b in bodies if holds(b))


@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_state_model_programs_compile(compiled_for_chip, one_chip, tmp_path,
                                      program):
    """`lfm2-8b-a1b-l14`'s programs as its cell dispatches them (`mixed`
    at 512 rows, `decode_cont` at 128 rows x 64-block tables, K=8, bf16,
    published widths; depth cut to the two dense conv layers + one period)
    compile for the described v5e with the state slots' argument, and hold
    the attention kernel (a 64-wide head through the 128-lane pool) and
    the short-convolution kernel; no loop of a row a turn writes the conv
    state's pool."""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs",
                           "lfm2-8b-a1b-l14.json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    hf = hf_config_of(cell, rehearsal=False)
    hf["num_hidden_layers"] = layers = 6
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"])
    kinds = model_config.get_layer_cache_kinds()
    assert kinds == ["state", "state", "kv", "state", "state", "state"]
    assert model_config.get_cache_row_shape() == (2, 8, 128)
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    cache_config = CacheConfig(num_device_blocks_override=CELL_BLOCKS,
                               swap_space_gib=0.01)
    cache_config.num_state_slots = CELL_ROWS
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner = ModelRunner(
            model, params, model_config,
            SchedulerConfig(max_num_batched_tokens=512,
                            max_num_seqs=CELL_ROWS,
                            max_model_len=serve["max_model_len"],
                            enable_chunked_prefill=True),
            cache_config, ParallelConfig())

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
        kv = [(sds(bf16, CELL_ROWS, 2 * 2048), ) if kind == "state" else
              (sds(bf16, CELL_BLOCKS, 8, 16, 128), ) * 2 for kind in kinds]
        assert not jax.config.jax_enable_compilation_cache
        if program == "mixed":
            compiled = runner.lower_mixed_step(
                kv, 512, CELL_TABLE_WIDTH, do_random=False).compile()
        else:
            b, w, k = CELL_ROWS, CELL_TABLE_WIDTH, CELL_K
            rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b),
                    sds(f32, b), sds(i32, b), sds(f32, b), sds(f32, b),
                    sds(jnp.uint32, b), sds(f32, b), sds(f32, b),
                    sds(f32, b), None, None)
            compiled = runner._jit_decode_cont.lower(
                params, kv, sds(i32, b, 4 * k), sds(i32, b, 1),
                sds(jnp.bool_, b, 1), *rest, state_slots=sds(i32, b),
                prev_t1=k, num_steps=k, logprob_k=1, do_topk=False,
                do_topp=False, do_minp=False, do_penalties=False,
                do_random=False).compile()
    finally:
        set_use_pallas(None)
    text = compiled.as_text()
    assert "_short_conv_call" in text
    assert text.count("tpu_custom_call") >= 2
    assert _row_loops(text, "bf16[128,4096]") == []
    # One layer of each kind plus the head stay far inside the chip.
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024**3


# --- a matrix state a head: the delta rule on the pool, in place ------------

def _matrix_state_program(one_chip, tmp_path, program, config_name,
                          hf_patch, kinds, state_shapes, rows=CELL_ROWS,
                          table_width=CELL_TABLE_WIDTH, blocks=CELL_BLOCKS):
    """`config_name`'s `mixed` (512 rows) or `decode_cont` (`rows` rows x
    `table_width`-block tables, K=8: 128 x 64 unless the cell's are others)
    program as its cell dispatches it, bf16 at the published widths with
    `hf_patch` laid over the config (the depth cut), compiled for the
    described v5e."""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.worker.cache_engine import CacheEngine
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs", config_name + ".json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    hf = dict(hf_config_of(cell, rehearsal=False), **hf_patch)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"])
    assert model_config.get_layer_cache_kinds() == kinds
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    cache_config = CacheConfig(num_device_blocks_override=blocks,
                               swap_space_gib=0.01)
    cache_config.num_state_slots = rows
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner = ModelRunner(
            model, params, model_config,
            SchedulerConfig(max_num_batched_tokens=512,
                            max_num_seqs=rows,
                            max_model_len=serve["max_model_len"],
                            enable_chunked_prefill=True),
            cache_config, ParallelConfig())

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
        state = tuple(
            sds(jnp.dtype(dtype),
                *CacheEngine.state_pool_shape(shape, rows))
            for shape, dtype in model_config.get_state_arrays())
        assert [s.shape for s in state] == state_shapes
        pools, kv_heads, width = model_config.get_cache_row_shape()
        kv = [state if kind == "state" else
              (sds(bf16, blocks, kv_heads, 16, width), ) * pools
              for kind in kinds]
        assert not jax.config.jax_enable_compilation_cache
        if program == "mixed":
            return runner.lower_mixed_step(
                kv, 512, table_width, do_random=False).compile()
        b, w, k = rows, table_width, CELL_K
        rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b),
                sds(f32, b), sds(i32, b), sds(f32, b), sds(f32, b),
                sds(jnp.uint32, b), sds(f32, b), sds(f32, b),
                sds(f32, b), None, None)
        return runner._jit_decode_cont.lower(
            params, kv, sds(i32, b, 4 * k), sds(i32, b, 1),
            sds(jnp.bool_, b, 1), *rest, state_slots=sds(i32, b),
            prev_t1=k, num_steps=k, logprob_k=1, do_topk=False,
            do_topp=False, do_minp=False, do_penalties=False,
            do_random=False).compile()
    finally:
        set_use_pallas(None)


@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_matrix_state_model_programs_compile(compiled_for_chip, one_chip,
                                             tmp_path, program):
    """`solar-open2-ep8-l4`'s programs as its cell dispatches them (`mixed`
    at 512 rows, `decode_cont` at 128 rows x 64-block tables, K=8, bf16,
    published widths; depth cut to the GQA layer and one KDA layer) compile
    for the described v5e, hold the delta rule's kernel of that program
    (`_kda_chunk_call` / `_kda_step_call`) and the filter's, and carry the
    541 MB matrix pool through the K substeps without a copy of it: the
    program's temporaries stay under the pool's own size. The taps pool's
    rows are written by slot in one pass (`ops/state_rows.py`): no loop of
    a row a turn (13 us each on the chip, 128-512 turns a layer)."""
    compiled = _matrix_state_program(
        one_chip, tmp_path, program, "solar-open2-ep8-l4",
        {"num_hidden_layers": 2}, ["kv", "state"],
        [(128, 73728), (129, 64, 128, 128)])
    kernel = "_kda_chunk_call" if program == "mixed" else "_kda_step_call"
    text = compiled.as_text()
    assert kernel in text and "_conv_silu_call" in text
    assert text.count("tpu_custom_call") >= 3
    assert _row_loops(text, "bf16[128,73728]") == []
    pool_bytes = 129 * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_matrix_state_beside_latent_pool_programs_compile(
        compiled_for_chip, one_chip, tmp_path, program):
    """`ling-3.0-flash-ep8-l12`'s programs as its cell dispatches them
    (`mixed` at 512 rows, `decode_cont` at 16 rows, both at 512-block
    tables of an 8,192-token model length, K=8, bf16, published widths, 64
    of 512 experts held; depth cut to a KDA layer over the dense MLP and a
    latent layer over the experts, by the model's own rule at
    `layer_group_size` 2) compile for the described v5e with the kernels
    on: the delta rule's kernel of that program at 32 heads, the filter's,
    the latent kernel of that program over a pool of 640-lane rows, and
    the grouped expert kernels (16 rows of 8 choices leave most of 512
    experts unchosen: the decode program takes the grouped form too). The
    decode program carries the 36 MB matrix pool through the K substeps
    without a copy of it: its temporaries stay under one state pool's
    size beside the staged logits."""
    compiled = _matrix_state_program(
        one_chip, tmp_path, program, "ling-3.0-flash-ep8-l12",
        {"num_hidden_layers": 2, "layer_group_size": 2,
         "first_k_dense_replace": 1,
         "expert_swiglu_limit_list": [0, 0],
         "share_expert_swiglu_limit_list": [0, 0]},
        ["state", "kv"], [(16, 36864), (17, 32, 128, 128)],
        rows=16, table_width=512, blocks=16000)
    text = compiled.as_text()
    for kernel in (("_kda_chunk_call", "_ragged_call_mla")
                   if program == "mixed"
                   else ("_kda_step_call", "_paged_attention_call_mla")):
        assert kernel in text, kernel
    assert "_conv_silu_call" in text
    assert "_grouped_gate_up_call" in text and "_grouped_down_call" in text
    assert _row_loops(text, "bf16[16,36864]") == []
    pool_bytes = 17 * 32 * 128 * 128 * 4
    staged_logits = 16 * 19648 * 4 * CELL_K
    temps = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode_cont":
        assert temps < pool_bytes + staged_logits, temps
    else:
        assert temps < 2 * 1024**3, temps


@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_state_space_model_programs_compile(compiled_for_chip, one_chip,
                                            tmp_path, program):
    """`granite-4.0-h-small-ep4-l10`'s programs as its cell dispatches
    them (as above; depth cut to two Mamba-2 layers and the attention
    layer) compile for the described v5e, hold the scan's kernel of that
    program (`_ssd_chunk_call` / `_ssd_step_call`) and the filter's, and
    carry the 541 MB matrix pools through the K substeps without a copy of
    one: the program's temporaries stay under one pool's size. The taps
    pool is `[128, 25,344]`, wider than the 24,576 columns at which XLA's
    scatter became a loop of a row a turn: its rows are written by slot in
    one pass (`ops/state_rows.py`)."""
    compiled = _matrix_state_program(
        one_chip, tmp_path, program, "granite-4.0-h-small-ep4-l10",
        {"num_hidden_layers": 3,
         "layer_types": ["mamba", "attention", "mamba"]},
        ["state", "kv", "state"], [(128, 25344), (129, 64, 128, 128)])
    kernel = "_ssd_chunk_call" if program == "mixed" else "_ssd_step_call"
    text = compiled.as_text()
    assert kernel in text and "_conv_silu_call" in text
    assert text.count("tpu_custom_call") >= 4
    assert _row_loops(text, "bf16[128,25344]") == []
    pool_bytes = 129 * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.skipif(not os.environ.get("FALCON_H1_COMPILE"),
                    reason="35 s a program: run by hand with "
                    "FALCON_H1_COMPILE=1 (tier-1 has no time for it)")
@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_parallel_hybrid_model_programs_compile(compiled_for_chip, one_chip,
                                                tmp_path, program):
    """`falcon-h1-34b-l6`'s programs as its cell dispatches them (as above;
    depth cut to two layers, each with BOTH caches; the whole 261,120-row
    head) compile for the described v5e, hold the scan's kernel of that
    program at two groups of B and C over 256 state channels beside the
    paged attention's, and carry the 541 MB matrix pools through the K
    substeps without a copy of one."""
    compiled = _matrix_state_program(
        one_chip, tmp_path, program, "falcon-h1-34b-l6",
        {"num_hidden_layers": 2}, ["kv", "state"] * 2,
        [(128, 15360), (129, 32, 256, 128)])
    kernel = "_ssd_chunk_call" if program == "mixed" else "_ssd_step_call"
    attn = ("_ragged" if program == "mixed" else "_paged_attention_call")
    text = compiled.as_text()
    assert kernel in text and "_conv_silu_call" in text and attn in text
    pool_bytes = 129 * 32 * 256 * 128 * 4
    memory = compiled.memory_analysis()
    print(program, "temporaries", memory.temp_size_in_bytes / 1e9, "GB")
    assert memory.temp_size_in_bytes < 2 * pool_bytes


# --- window and full layers: two cache groups a step ------------------------

@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_window_group_model_programs_compile(compiled_for_chip, one_chip,
                                             tmp_path, program):
    """`smallthinker-21ba3b-l8`'s programs as its cell dispatches them
    (`mixed` at 512 rows, `decode_cont` at 16 rows, both at 512-block
    tables of an 8,192-token model length, K=8, bf16, published widths;
    depth cut to one period: a full layer and three window layers) compile
    for the described v5e with the window group's tables, and hold the
    windowed and the unwindowed attention kernels side by side."""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs",
                           "smallthinker-21ba3b-l8.json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    hf = hf_config_of(cell, rehearsal=False)
    hf["num_hidden_layers"] = 4
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"])
    kinds = model_config.get_layer_cache_kinds()
    assert kinds == ["kv", "kv_window", "kv_window", "kv_window"]
    assert model_config.get_window_group_size() == 4096
    assert model_config.get_cache_row_shape() == (2, 4, 128)
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    blocks, rows, width, k = 16000, 16, 512, CELL_K
    cache_config = CacheConfig(
        num_device_blocks_override=blocks, swap_space_gib=0.01,
        window_group_size=model_config.get_window_group_size())
    cache_config.window_ahead_tokens = 512
    cache_config.num_device_blocks = blocks
    cache_config.size_window_group(model_config)
    assert cache_config.num_window_device_blocks == 9000
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner = ModelRunner(
            model, params, model_config,
            SchedulerConfig(max_num_batched_tokens=512, max_num_seqs=rows,
                            max_model_len=serve["max_model_len"],
                            enable_chunked_prefill=True),
            cache_config, ParallelConfig())

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
        kv = [(sds(bf16, 9000 if kind == "kv_window" else blocks, 4, 16,
                   128), ) * 2 for kind in kinds]
        assert not jax.config.jax_enable_compilation_cache
        if program == "mixed":
            compiled = runner.lower_mixed_step(
                kv, 512, width, do_random=False).compile()
        else:
            b, w = rows, width
            rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b),
                    sds(f32, b), sds(i32, b), sds(f32, b), sds(f32, b),
                    sds(jnp.uint32, b), sds(f32, b), sds(f32, b),
                    sds(f32, b), None, None)
            compiled = runner._jit_decode_cont.lower(
                params, kv, sds(i32, b, 4 * k), sds(i32, b, 1),
                sds(jnp.bool_, b, 1), *rest, window_tables=sds(i32, b, w),
                prev_t1=k, num_steps=k, logprob_k=1, do_topk=False,
                do_topp=False, do_minp=False, do_penalties=False,
                do_random=False).compile()
    finally:
        set_use_pallas(None)
    text = compiled.as_text()
    kernel, windowed = (("_ragged_call", "_ragged_call_window")
                        if program == "mixed" else
                        ("_paged_attention_call",
                         "_paged_attention_call_window"))
    assert windowed in text and re.search(kernel + r"(?!_window)\b", text)
    # 512 chunk rows take the grouped (ReLU) expert kernels, and so do 16
    # decode rows (top-6 of 64: a fifth of the experts chosen by no row),
    # inside the K-loop, with no copy of a [64, 768, 2560] stack.
    assert "_grouped_gate_up_call" in text and "_grouped_down_call" in text
    assert _stack_sized_copies(text, SMALLTHINKER) == []
    # One period plus the whole vocabulary's head stay inside the chip.
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024**3


# --- scans, a window group and one shared full pool in one model -------------

@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_shared_pool_hybrid_model_programs_compile(compiled_for_chip,
                                                   one_chip, tmp_path,
                                                   program):
    """`phi-4-mini-flash-reasoning`'s programs as its cell dispatches them
    (`mixed` at 512 rows, `decode_cont` at 64 rows, both at 256-block
    tables of a 4,096-token model length, K=8, bf16, published widths and
    the whole 200,064-row head; depth cut to 8 layers, which the model's own
    rule lays out as scan, window, scan, window, scan (gives `m`), full,
    GMU, cross) compile for the described v5e with the kernels on: the
    Mamba-1 scan's kernel of that program, the filter's, the windowed and
    the unwindowed attention kernels side by side at a stored head of 128
    (two 64-wide heads: no padding), the full pool read by two layers. The
    decode program carries the three 21 MB matrix pools through the K
    substeps without a copy of one: its temporaries stay under one pool's
    size beside the staged logits."""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.worker.cache_engine import CacheEngine
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    hf = dict(hf_config_of(cell, rehearsal=False), num_hidden_layers=8)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"])
    kinds = model_config.get_layer_cache_kinds()
    assert kinds == ["state", "kv_window", "state", "kv_window", "state",
                     "kv"]
    assert model_config.get_layer_cache_entries() == [0, 1, 2, 3, 4, 5,
                                                      None, 5]
    assert model_config.get_cache_walkers() == (2, 2, 1)
    assert model_config.get_window_group_size() == 512
    assert model_config.get_cache_row_shape() == (2, 10, 128)
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    blocks, rows, width, k = 16000, 64, 256, CELL_K
    cache_config = CacheConfig(
        num_device_blocks_override=blocks, swap_space_gib=0.01,
        window_group_size=model_config.get_window_group_size())
    cache_config.window_ahead_tokens = 512
    cache_config.num_device_blocks = blocks
    cache_config.size_window_group(model_config)
    assert cache_config.num_window_device_blocks == 4000
    cache_config.num_state_slots = rows
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner = ModelRunner(
            model, params, model_config,
            SchedulerConfig(max_num_batched_tokens=512, max_num_seqs=rows,
                            max_model_len=serve["max_model_len"],
                            enable_chunked_prefill=True),
            cache_config, ParallelConfig())

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
        state = tuple(
            sds(jnp.dtype(dtype), *CacheEngine.state_pool_shape(shape, rows))
            for shape, dtype in model_config.get_state_arrays())
        assert [s.shape for s in state] == [(64, 15360), (65, 40, 16, 128)]
        kv = [state if kind == "state" else
              (sds(bf16, 4000 if kind == "kv_window" else blocks, 10, 16,
                   128), ) * 2 for kind in kinds]
        assert not jax.config.jax_enable_compilation_cache
        if program == "mixed":
            compiled = runner.lower_mixed_step(
                kv, 512, width, do_random=False).compile()
        else:
            b, w = rows, width
            rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b),
                    sds(f32, b), sds(i32, b), sds(f32, b), sds(f32, b),
                    sds(jnp.uint32, b), sds(f32, b), sds(f32, b),
                    sds(f32, b), None, None)
            compiled = runner._jit_decode_cont.lower(
                params, kv, sds(i32, b, 4 * k), sds(i32, b, 1),
                sds(jnp.bool_, b, 1), *rest, state_slots=sds(i32, b),
                window_tables=sds(i32, b, w), prev_t1=k, num_steps=k,
                logprob_k=1, do_topk=False, do_topp=False, do_minp=False,
                do_penalties=False, do_random=False).compile()
    finally:
        set_use_pallas(None)
    text = compiled.as_text()
    scan, kernel, windowed = (
        ("_sscan_chunk_call", "_ragged_call", "_ragged_call_window")
        if program == "mixed" else
        ("_sscan_step_call", "_paged_attention_call",
         "_paged_attention_call_window"))
    assert scan in text and "_conv_silu_call" in text
    assert windowed in text and re.search(kernel + r"(?!_window)\b", text)
    assert _row_loops(text, "bf16[64,15360]") == []
    memory = compiled.memory_analysis()
    print(program, "temporaries", memory.temp_size_in_bytes / 1e6, "MB")
    if program == "decode_cont":
        # No copy of a matrix pool [65, 40, 16, 128] (21 MB: three of them
        # would be the temporaries' size again), and the temporaries
        # under one pool and one substep's float32 logits.
        assert _stack_sized_copies(text, (65, 640, 128, None)) == []
        pool_bytes = 65 * 40 * 16 * 128 * 4
        logits = rows * 200064 * 4
        assert memory.temp_size_in_bytes < pool_bytes + logits


# --- latent attention under a multi-stream residual --------------------------

XING_BLOCKS = 4500


@pytest.mark.parametrize("program", ["mixed", "decode_cont"])
def test_multi_stream_latent_model_programs_compile(compiled_for_chip,
                                                    one_chip, tmp_path,
                                                    program):
    """`xing4.0-29b-a4b-ep8`'s programs as its cell dispatches them
    (`mixed` at 512 rows, `decode_cont` at 128 rows x 64-block tables, K=8,
    bf16, published widths; depth cut to 2 dense + 4 routed layers: whether
    all 40 fit is the chip's to say) compile for the described v5e and
    hold the latent kernel of that program at 32 heads (`_ragged_call_mla`
    / `_paged_attention_call_mla`: A.X-K1 launches them at 64; the kernels
    take all H heads of a row in one block, so no block-shape rule changed
    and `test_mla_*` above still compile the 64-head launches) beside the
    two kernels of the stream mixes (`_mhc_pre_call`, `_mhc_post_call`,
    since PR 47: the Sinkhorn sweeps are a loop INSIDE the first, so the
    program's only `while`s are the scan over the routed layers, ONE
    whatever their number, and the K-substep scan around it in the decode
    program). The streams are flat, `[rows, 14336]` (`[rows, 1, 14336]`
    where the model holds them), float32 from the embedding to the head's
    sum (one kernel a mix in a program's text to trace and lower, whichever
    sublayer or layer calls it), a shape no other tensor of the
    configuration has, which the benchmark's `mhc_dev_share` reads the
    kernels by (its `,14336]`); the `[rows, 1, 4, 3584]` view is left at
    the head's sum alone. The scan copies neither the one latent pool
    (every layer a view of it) nor an expert stack (the grouped kernels and
    the dense dots read the stacked arrays in place): the program's
    temporaries stay under a third of the pool and no operation copies a
    layer's stack. (`XING_LAYERS=40 XING_HLO_DIR=<dir>` compiles the full
    depth and writes the text and the memory analysis there: 35 s a
    program, PERF.md section 6.)"""
    import json

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from intellillm_tpu.worker.model_runner import ModelRunner

    from chipbench import CHIP_DIR      # puts the harness on sys.path
    from harness.server import hf_config_of

    with open(os.path.join(CHIP_DIR, "configs",
                           "xing4.0-29b-a4b-ep8.json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    layers = int(os.environ.get("XING_LAYERS", 6))
    hf = dict(hf_config_of(cell, rehearsal=False), num_hidden_layers=layers)
    assert (hf["hidden_size"], hf["num_attention_heads"], hf["hc_mult"],
            hf["first_k_dense_replace"]) == (3584, 32, 4, 2)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    model_config = ModelConfig(
        model=str(tmp_path), dtype=serve["dtype"], load_format="dummy",
        max_model_len=serve["max_model_len"])
    model = get_model_class(model_config.hf_config.architectures)(
        model_config)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_random_params(0)))
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        runner = ModelRunner(
            model, params, model_config,
            SchedulerConfig(max_num_batched_tokens=512,
                            max_num_seqs=CELL_ROWS,
                            max_model_len=serve["max_model_len"],
                            enable_chunked_prefill=True),
            CacheConfig(num_device_blocks_override=XING_BLOCKS,
                        swap_space_gib=0.01), ParallelConfig())

        def sds(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        i32, f32 = jnp.int32, jnp.float32
        assert model_config.get_cache_row_shape() == (1, layers, 640)
        kv = [(sds(jnp.bfloat16, XING_BLOCKS, layers, 16, 640), )]
        assert not jax.config.jax_enable_compilation_cache
        if program == "mixed":
            compiled = runner.lower_mixed_step(
                kv, 512, CELL_TABLE_WIDTH, do_random=False).compile()
        else:
            b, w, k = CELL_ROWS, CELL_TABLE_WIDTH, CELL_K
            rest = (sds(i32, b, 1), sds(i32, b, w), sds(i32, b),
                    sds(f32, b), sds(i32, b), sds(f32, b), sds(f32, b),
                    sds(jnp.uint32, b), sds(f32, b), sds(f32, b),
                    sds(f32, b), None, None)
            # _pack's layout at logprob_k = 1: the share's two columns,
            # the visits' two, the mixes' one.
            compiled = runner._jit_decode_cont.lower(
                params, kv, sds(i32, b, 4 * k + 5), sds(i32, b, 1),
                sds(jnp.bool_, b, 1), *rest, prev_t1=k, num_steps=k,
                logprob_k=1, do_topk=False, do_topp=False, do_minp=False,
                do_penalties=False, do_random=False).compile()
    finally:
        set_use_pallas(None)
    text = compiled.as_text()
    if os.environ.get("XING_HLO_DIR"):
        print("MEMORY", program, compiled.memory_analysis())
        with open(os.path.join(os.environ["XING_HLO_DIR"],
                               f"xing4_{program}.hlo"), "w") as f:
            f.write(text)
    kernel = ("_ragged_call_mla" if program == "mixed"
              else "_paged_attention_call_mla")
    assert kernel in text and "tpu_custom_call" in text
    rows = 512 if program == "mixed" else CELL_ROWS
    # Two sublayers' kernels in each dense layer and in the scanned layer,
    # each reading the flat float32 streams.
    for name in ("_mhc_pre_call", "_mhc_post_call"):
        calls = re.findall(rf"%{name}[.\d]* = .*custom-call\(.*", text)
        assert len(calls) == 2 * 3, (name, len(calls))
        assert all(f"f32[{rows},14336]" + "{1,0}" in call.split(
            "operand_layout_constraints")[1] for call in calls), name
        assert not any(f"bf16[{rows},14336]" in call for call in calls), name
    # The scan over the routed layers, and the K-substep scan around it in
    # the decode program; no sweep loop and no other.
    whiles = len(re.findall(r" while\(", text))
    assert whiles == 1 + (program == "decode_cont"), whiles
    # No copy of the streams is made, in either shape or dtype.
    assert not re.search(
        rf"= (f32|bf16)\[{rows},(1,)?14336\]\S* copy\(", text)
    assert not re.search(rf"= f32\[{rows},(1,)?4,3584\]\S* copy\(", text)
    pool_bytes = XING_BLOCKS * layers * 16 * 640 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes // 3, (temp, pool_bytes)
    assert not re.search(r"= bf16\[8,1024,3584\]\S* copy\(", text)


@pytest.mark.parametrize("layers", [1, 40])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("rows", [16, 128, 512])
def test_stream_mix_kernels_compile(compile_for_chip, rows, dtype, layers):
    """The two kernels of a hyper-connection sublayer alone, through their
    seam, at Xing4.0's widths (4 streams of 3,584): the decode programs'
    128 rows, the largest `mixed` bucket and the smallest (a part of one
    128-row tile: the pipeline pads it, the seam does not step down), in
    the cell's bfloat16 and the sharp reference check's float32, the
    parameters a stack of one layer and the model's stack of forty with
    the layer's place (the kernel reads `Phi` out of the stack: no copy of
    a `[24, 14336]` slice is made for it)."""
    from intellillm_tpu.layers.hyper_connections import HyperConnections
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops import hyper_connections as seam
    from intellillm_tpu.ops.dispatch import set_use_pallas
    n, c = 4, 3584
    hc = HyperConnections(n=n, sweeps=20, eps=1e-6, clamp_min=-30.0,
                          clamp_max=30.0, rms_eps=1e-6)
    names = ("phi", "alpha", "b_pre", "b_post", "b_res")
    lead = (layers, )

    def sublayer(x, y, layer, *leaves):
        u, coef, err = seam.mhc_pre(x, dict(zip(names, leaves)), hc, layer)
        return u, err, seam.mhc_post(x, y, coef, hc)

    paths = lambda: dict(                                       # noqa: E731
        get_compile_tracker().snapshot()["kernel_dispatch"])
    before = paths()
    set_use_pallas(True)        # jax.default_backend() is "cpu" here
    try:
        # The sharp check runs under JAX_DEFAULT_MATMUL_PRECISION=highest,
        # which reaches a kernel's own dots too.
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            text = compile_for_chip(
                sublayer, ((rows, 1, n * c), dtype), ((rows, 1, c), dtype),
                ((), jnp.int32),
                *[(lead + shape, jnp.float32)
                  for shape in hc.param_shapes(c).values()])
    finally:
        set_use_pallas(None)
    after = paths()
    for label in ("mhc_pre", "mhc_post"):
        assert after.get("pallas:" + label, 0) == before.get(
            "pallas:" + label, 0) + 1
        assert after.get("reference:" + label, 0) == before.get(
            "reference:" + label, 0)
        assert f"_{label}_call" in text
    assert text.count("tpu_custom_call") >= 2
    assert not re.search(r"= f32\[(1,)?24,14336\]\S* (copy|fusion)\(", text)
