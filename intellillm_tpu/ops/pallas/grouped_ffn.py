"""Grouped expert FFN over rows sorted by expert, two kernels.

The rows `xs` [M, D] are the token-choices of a step ordered by expert:
expert e owns rows `[offsets[e], offsets[e+1])`, `group_sizes` [N] says how
many, and rows past the last group belong to nobody (their output is left
as the device found it: the caller reads none of them). The stacks are in
their stored layout, all three `[N, I, D]` (layers/moe.py).

    h   = silu(xs . w1[e]^T) * (xs . w3[e]^T)      [M, I]  `_grouped_gate_up_call`
    out = h . w2[e]                                [M, D]  `_grouped_down_call`

with bf16 (or float32) operands, float32 accumulation, `h` rounded once to
the rows' dtype and `out` left in float32: the dense path's arithmetic.

After `jax.experimental.pallas.ops.tpu.megablox.gmm` (the grid walk, the
visit metadata and the masked store are its): rows are cut into tiles of
`tile`, and the grid's second axis walks *visits*, (row tile, expert)
pairs in row order. A tile is visited once by each expert that has a row
in it, so M/tile + N - 1 visits at most and no padding block an expert.
What differs from `gmm`: the contraction is never tiled (a visit's dot
covers the whole of D or I, so there is no accumulator and no k axis); gate
and up share one kernel, one read of the rows and one store; and the
weight window is as wide as `_WEIGHT_WINDOW_BYTES` allows. The weight
window's block index is (expert, column tile): consecutive visits of one
expert leave it unchanged and Pallas skips the copy, so an expert's
weights are read once a column tile however many row tiles it spans.
The column tile is the grid's outer axis for that reason; the price is one
more read of the rows a column tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Double-buffered weight windows of one kernel may take this much of the
# chip's 128 MiB of VMEM; the column tile is the widest whole-lane divisor
# of the output width that fits.
_WEIGHT_WINDOW_BYTES = 32 << 20
_VMEM_LIMIT_BYTES = 96 << 20


def grouped_ffn_supported(xs: jnp.ndarray, w1: jnp.ndarray,
                          tile: int) -> bool:
    """Whole 128-lane tiles of hidden and inner width, whole sublane tiles
    of rows a row tile (16 for a 2-byte dtype, 8 for float32)."""
    m, d = xs.shape
    _, i, _ = w1.shape
    rows = 8 if xs.dtype.itemsize >= 4 else 16
    return (d % 128 == 0 and i % 128 == 0 and tile % rows == 0
            and m % tile == 0 and xs.dtype == w1.dtype
            and xs.dtype in (jnp.bfloat16, jnp.float32))


def visit_metadata(group_sizes: jnp.ndarray, m: int, tile: int):
    """((offsets [N+1], expert of visit [V], row tile of visit [V]), number
    of visits): the walk of `gmm`'s `make_group_metadata`, for groups that
    start at row 0, follow each other and may end before `m`. V = M/tile +
    N - 1 is the static bound; entries past the number of visits repeat
    the last expert and are not run."""
    n = group_sizes.shape[0]
    tiles_m = m // tile
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # Tiles an expert touches: from the tile its first row is in to the
    # tile its last row is in; none where it has no row.
    touched = jnp.where(group_sizes == 0, 0,
                        (ends + tile - 1) // tile - starts // tile)
    bound = tiles_m + n - 1
    expert_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), touched,
                           total_repeat_length=bound)
    # A tile is visited once by whoever owns its first row (or, past the
    # last group, by nobody: those visits are never reached), and once more
    # by every expert that starts inside it.
    starts_inside = (starts % tile != 0) & (group_sizes > 0)
    extra = jnp.zeros(tiles_m, jnp.int32).at[
        jnp.where(starts_inside, starts // tile, tiles_m)].add(
            1, mode="drop")
    tile_of = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                         total_repeat_length=bound)
    return (offsets.astype(jnp.int32), expert_of, tile_of), touched.sum()


def _own_rows(meta, tile: int, width: int):
    """[tile, width] mask: rows of this visit's tile that belong to this
    visit's expert."""
    offsets, expert_of, tile_of = meta
    visit = pl.program_id(1)
    e = expert_of[visit]
    row = (jax.lax.broadcasted_iota(jnp.int32, (tile, width), 0)
           + tile_of[visit] * tile)
    return (row >= offsets[e]) & (row < offsets[e + 1])


_ON_LAST_AXES = (((1, ), (1, )), ((), ()))      # a . b^T


def _gate_up_kernel(offsets, expert_of, tile_of, x_ref, w1_ref, w3_ref,
                    h_ref, *, activation: str = "silu"):
    x = x_ref[...]
    h1 = jax.lax.dot_general(x, w1_ref[...], _ON_LAST_AXES,
                             preferred_element_type=jnp.float32)
    h3 = jax.lax.dot_general(x, w3_ref[...], _ON_LAST_AXES,
                             preferred_element_type=jnp.float32)
    if activation == "silu":
        gate = h1 * jax.nn.sigmoid(h1)
    elif activation == "relu":
        gate = jnp.maximum(h1, 0.0)
    else:
        raise ValueError(f"unknown expert activation {activation!r}")
    h = (gate * h3).astype(h_ref.dtype)
    own = _own_rows((offsets, expert_of, tile_of), *h_ref.shape)
    # Another expert's visit of this tile wrote the other rows: keep them.
    h_ref[...] = jnp.where(own, h, h_ref[...])


def _down_kernel(offsets, expert_of, tile_of, h_ref, w2_ref, out_ref):
    out = jnp.dot(h_ref[...], w2_ref[...],
                  preferred_element_type=jnp.float32)
    own = _own_rows((offsets, expert_of, tile_of), *out_ref.shape)
    out_ref[...] = jnp.where(own, out, out_ref[...])


def _column_tile(width: int, contraction: int, stacks: int,
                 itemsize: int) -> int:
    """Widest divisor of `width` in whole 128-lane tiles whose
    double-buffered weight windows fit `_WEIGHT_WINDOW_BYTES`."""
    lanes = width // 128
    for parts in range(1, lanes + 1):
        if lanes % parts:
            continue
        tc = width // parts
        if 2 * stacks * tc * contraction * itemsize <= _WEIGHT_WINDOW_BYTES:
            return tc
    return 128


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# Index maps of a visit `v` at column tile `c` (the three scalar-prefetch
# arrays follow the grid indices): its row tile whole or at that column,
# its expert's weights.
def _rows(c, v, offsets, expert_of, tile_of):
    return tile_of[v], 0


def _rows_at_column(c, v, offsets, expert_of, tile_of):
    return tile_of[v], c


@functools.partial(jax.jit, static_argnames=("tile", "activation"))
def _grouped_gate_up_call(meta, visits, xs, w1, w3, *, tile,
                          activation="silu"):
    m, d = xs.shape
    n, i, _ = w1.shape
    ti = _column_tile(i, d, 2, w1.dtype.itemsize)
    weight = pl.BlockSpec((None, ti, d),
                          lambda c, v, off, e, t: (e[v], c, 0))
    return pl.pallas_call(
        (_gate_up_kernel if activation == "silu" else
         functools.partial(_gate_up_kernel, activation=activation)),
        out_shape=jax.ShapeDtypeStruct((m, i), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tile, d), _rows), weight, weight],
            out_specs=pl.BlockSpec((tile, ti), _rows_at_column),
            grid=(i // ti, visits)),
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=4 * m * d * i,
            transcendentals=m * i if activation == "silu" else 0,
            bytes_accessed=(2 * n * i * d + m * d * (i // ti) + m * i)
            * xs.dtype.itemsize),
    )(*meta, xs, w1, w3)


@functools.partial(jax.jit, static_argnames=("tile", ))
def _grouped_down_call(meta, visits, h, w2, *, tile):
    m, i = h.shape
    n, _, d = w2.shape
    td = _column_tile(d, i, 1, w2.dtype.itemsize)
    return pl.pallas_call(
        _down_kernel,
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tile, i), _rows),
                      pl.BlockSpec((None, i, td),
                                   lambda c, v, off, e, t: (e[v], 0, c))],
            out_specs=pl.BlockSpec((tile, td), _rows_at_column),
            grid=(d // td, visits)),
        compiler_params=_COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * d * i, transcendentals=0,
            bytes_accessed=(n * i * d + m * i * (d // td))
            * h.dtype.itemsize + m * d * 4),
    )(*meta, h, w2)


def grouped_ffn(xs, w1, w2, w3, group_sizes, tile: int,
                activation: str = "silu"):
    """[M, D] float32: the expert FFN of rows sorted by expert. Callers
    check `grouped_ffn_supported` first."""
    meta, visits = visit_metadata(group_sizes.astype(jnp.int32),
                                  xs.shape[0], tile)
    h = _grouped_gate_up_call(meta, visits, xs, w1, w3, tile=tile,
                              activation=activation)
    return _grouped_down_call(meta, visits, h, w2, tile=tile)
