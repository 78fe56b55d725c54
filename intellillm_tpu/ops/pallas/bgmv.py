"""Batched-gather-matrix-vector (BGMV) Pallas kernel for multi-LoRA.

Punica-style replacement for `lora/layers.lora_delta`: the jnp path
gathers per-row adapter matrices (`a_stack[row_slots]` — a materialized
[B, Din, R] + [B, R, Dout] copy in HBM every step) before two einsums.
This kernel instead keeps the WHOLE adapter stacks resident in VMEM via
constant-index-map BlockSpecs and picks each row's adapter with a
dynamic leading-axis VMEM index (`a_ref[slot]`) — no gather, no HBM
copy, no per-slot DMA.

Why whole-stack VMEM residency instead of per-row HBM slab DMAs: the
shrink matrix's minor dimension is the rank (R ~ 8..64), far below the
128-lane alignment Mosaic DMA windows need, so slicing [Din, R] slabs
out of HBM per row is either unsupported or pathologically padded. The
stacks are small — S slots x (Din x R + R x Dout) is a few MB for
typical ranks — so `bgmv_supported` gates on a VMEM budget and the
caller falls back to the jnp gather-einsum path beyond it.

Numerics replicate the reference exactly in structure: f32 shrink dot,
downcast of the intermediate to the activation dtype (the reference's
`h.astype(x.dtype)` between the einsums), f32 expand dot, downcast out.
Slot 0 is the pinned all-zero adapter, so no-LoRA rows get an exact
+0.0 delta — same guarantee as the gather path, bit-for-bit.

Selection: `lora/layers.lora_delta` gates on
`use_kernel("bgmv", bgmv_supported(...))`; see docs/kernels.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Leave headroom under the ~16 MB/core VMEM for the row blocks, scratch
# and compiler spills: both stacks together may use at most this much.
_VMEM_STACK_BUDGET_BYTES = 12 * 1024 * 1024


def bgmv_supported(x: jnp.ndarray, a_stack: jnp.ndarray,
                   b_stack: jnp.ndarray) -> bool:
    """Static gate for the Pallas path: 128-aligned model dims (Mosaic
    lane alignment) and both adapter stacks fitting the VMEM budget."""
    din, dout = a_stack.shape[-2], b_stack.shape[-1]
    if din % 128 != 0 or dout % 128 != 0:
        return False
    stack_bytes = (a_stack.size * a_stack.dtype.itemsize +
                   b_stack.size * b_stack.dtype.itemsize)
    return stack_bytes <= _VMEM_STACK_BUDGET_BYTES


def _bgmv_kernel(
    # scalar prefetch (SMEM)
    row_slots_ref,      # [B] i32 adapter slot per row (0 = no adapter)
    # inputs
    x_ref,              # [RB, L, Din]
    a_ref,              # [S, Din, R] — whole stack, VMEM resident
    b_ref,              # [S, R, Dout]
    # outputs
    o_ref,              # [RB, L, Dout]
    *,
    rows_per_block: int,
    x_dtype,
):
    rb0 = pl.program_id(0) * rows_per_block
    for i in range(rows_per_block):
        slot = row_slots_ref[rb0 + i]
        a = a_ref[slot].astype(jnp.float32)              # [Din, R]
        b = b_ref[slot].astype(jnp.float32)              # [R, Dout]
        x = x_ref[i].astype(jnp.float32)                 # [L, Din]
        h = jax.lax.dot_general(
            x, a, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)          # [L, R]
        # Match the reference's intermediate downcast between the dots.
        h = h.astype(x_dtype).astype(jnp.float32)
        o = jax.lax.dot_general(
            h, b, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)          # [L, Dout]
        o_ref[i] = o.astype(o_ref.dtype)


@jax.jit
def _bgmv_call(x, a_stack, b_stack, row_slots):
    bsz, seq, din = x.shape
    s, _, rank = a_stack.shape
    dout = b_stack.shape[-1]
    # 8-row grid blocks amortize grid overhead when the batch allows;
    # ragged batches fall back to one row per step.
    rb = 8 if bsz % 8 == 0 else 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz // rb, ),
        in_specs=[
            pl.BlockSpec((rb, seq, din), lambda r, *_: (r, 0, 0)),
            # Constant index maps: the stacks are one block, loaded into
            # VMEM once and reused by every grid step.
            pl.BlockSpec((s, din, rank), lambda r, *_: (0, 0, 0)),
            pl.BlockSpec((s, rank, dout), lambda r, *_: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, seq, dout), lambda r, *_: (r, 0, 0)),
    )
    kernel = functools.partial(_bgmv_kernel, rows_per_block=rb,
                               x_dtype=x.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, seq, dout), x.dtype),
    )(row_slots.astype(jnp.int32), x, a_stack, b_stack)


def bgmv(
    x: jnp.ndarray,          # [B, L, Din]
    a_stack: jnp.ndarray,    # [S, Din, R] (slot 0 all-zero)
    b_stack: jnp.ndarray,    # [S, R, Dout]
    row_slots: jnp.ndarray,  # [B] i32
) -> jnp.ndarray:
    """Per-row adapter delta: out[i] = (x[i] @ a[slot_i]) @ b[slot_i],
    returned in x.dtype. Callers must check `bgmv_supported` first."""
    return _bgmv_call(x, a_stack, b_stack, row_slots)
