"""Seam of the grouped expert FFN (label `grouped_ffn`).

`layers/moe.py` calls this and nothing below it: rows already sorted by
expert go through gate, up, activation and down of the expert that owns
them. The seam asks `ops/dispatch.use_kernel` once and runs the Pallas
kernels (`ops/pallas/grouped_ffn.py`; `_grouped_gate_up_call` and
`_grouped_down_call` in the device trace) or the jnp oracle below
(`jax.lax.ragged_dot`), the same arithmetic: products in the rows' dtype,
float32 accumulation, the activation (`activation`: "silu" | "relu" of the
gate, static) rounded once to the rows' dtype, the result left in float32.

Rows past the last group are nobody's. The oracle gives them zeros, the
kernels leave them as the device found them: a caller reads neither.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from intellillm_tpu.ops.dispatch import use_kernel
from intellillm_tpu.ops.pallas.grouped_ffn import (grouped_ffn,
                                                   grouped_ffn_supported)

# Rows a tile of the kernels' walk: one MXU pass of rows on a v5e. With 48
# to 64 rows an expert (the cells' 512-row steps) a wider tile multiplies
# more rows that are not the visiting expert's.
ROW_TILE = 128

# [M, K] x [N, I, K] -> [M, I]: the stacks' own layout, contracted on
# their last axis.
_ON_LAST_AXES = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1, ), (2, )), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def gate_activation(name: str):
    """The expert gate's activation by name ("silu" | "relu")."""
    try:
        return {"silu": jax.nn.silu, "relu": jax.nn.relu}[name]
    except KeyError:
        raise ValueError(f"unknown expert activation {name!r}") from None


def grouped_expert_ffn(xs: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray,
                       w3: jnp.ndarray, group_sizes: jnp.ndarray,
                       tile: int = ROW_TILE,
                       activation: str = "silu") -> jnp.ndarray:
    """[M, D] float32 of `xs` [M, D] (M a multiple of `tile`), the stacks
    [N, I, D] and `group_sizes` [N]: expert e owns the rows from the sum of
    the sizes before it."""
    if use_kernel("grouped_ffn", grouped_ffn_supported(xs, w1, tile)):
        return grouped_ffn(xs, w1, w2, w3, group_sizes, tile, activation)
    return grouped_expert_ffn_reference(xs, w1, w2, w3, group_sizes,
                                        activation)


def grouped_expert_ffn_reference(xs, w1, w2, w3, group_sizes,
                                 activation: str = "silu"):
    f32 = jnp.float32
    group_sizes = group_sizes.astype(jnp.int32)
    h1 = jax.lax.ragged_dot_general(xs, w1, group_sizes, _ON_LAST_AXES,
                                    preferred_element_type=f32)
    h3 = jax.lax.ragged_dot_general(xs, w3, group_sizes, _ON_LAST_AXES,
                                    preferred_element_type=f32)
    h = (gate_activation(activation)(h1) * h3).astype(xs.dtype)
    return jax.lax.ragged_dot(h, w2, group_sizes, preferred_element_type=f32)
