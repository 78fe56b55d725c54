"""Bodies of the short convolutions, one pass each: the gate-filter-gate
body of a gated short convolution, and (at the end of the file) a plain
4-tap filter, with or without a bias, then SiLU.

Per row t and channel e, with `[B | C | x]` the mixer's in-projection and
`p1`, `p2` the gated inputs one and two positions before t in the row's
own sequence (resolved by the caller: `layers/short_conv.py`):

    g = B * x
    y = C * (w[0] * p2 + w[1] * p1 + w[2] * g)

Elementwise, so the grid tiles rows and channels freely; the three thirds
of the in-projection are three windows onto one array (no slicing copy).
Arithmetic is float32 inside, `g` and `y` are rounded once to the
activations' dtype. The wrapper is jitted under the name the device trace
shows the kernel by (`_short_conv_call`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROW_TILES = (256, 128, 64, 32, 16, 8)
_LANE_TILES = (512, 256, 128)


def short_conv_supported(bcx: jnp.ndarray) -> bool:
    """Whole sublane tiles of rows (16 for a 2-byte dtype, 8 for float32)
    and whole 128-lane tiles of channels."""
    t, e3 = bcx.shape
    rows = 8 if bcx.dtype.itemsize >= 4 else 16
    return t % rows == 0 and (e3 // 3) % 128 == 0


def _short_conv_kernel(b_ref, c_ref, x_ref, p1_ref, p2_ref, w_ref,
                       y_ref, g_ref):
    f32 = jnp.float32
    g = b_ref[...].astype(f32) * x_ref[...].astype(f32)
    # The state holds g as stored: filter what a later step will read.
    g_out = g.astype(g_ref.dtype)
    g_ref[...] = g_out
    w = w_ref[...]
    conv = (w[0:1, :] * p2_ref[...].astype(f32)
            + w[1:2, :] * p1_ref[...].astype(f32)
            + w[2:3, :] * g_out.astype(f32))
    y_ref[...] = (c_ref[...].astype(f32) * conv).astype(y_ref.dtype)


@jax.jit
def _short_conv_call(bcx, p1, p2, w):
    t, e3 = bcx.shape
    e = e3 // 3
    tr = next(r for r in _ROW_TILES if t % r == 0)
    tc = next(c for c in _LANE_TILES if e % c == 0)
    third = e // tc
    # The filter as whole float32 sublane tiles: 3 taps in 8 rows.
    w8 = jnp.zeros((8, e), jnp.float32).at[:w.shape[0]].set(
        w.astype(jnp.float32))

    def window(k):
        return pl.BlockSpec((tr, tc), lambda i, j: (i, j + k * third))

    row = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    return pl.pallas_call(
        _short_conv_kernel,
        grid=(t // tr, third),
        in_specs=[window(0), window(1), window(2), row, row,
                  pl.BlockSpec((8, tc), lambda i, j: (0, j))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((t, e), bcx.dtype),
                   jax.ShapeDtypeStruct((t, e), bcx.dtype)],
    )(bcx, bcx, bcx, p1, p2, w8)


def short_conv(bcx, p1, p2, w):
    """(y [T, E], g [T, E]) of `bcx` [T, 3E], `p1` / `p2` [T, E] and the
    filter `w` [3, E] (tap 0 the oldest). Callers check
    `short_conv_supported` first."""
    return _short_conv_call(bcx, p1, p2, w)


def conv_silu_supported(x: jnp.ndarray, prev: jnp.ndarray) -> bool:
    """Whole sublane tiles of rows, whole lane tiles of channels, three
    earlier taps."""
    t, e = x.shape
    rows = 8 if x.dtype.itemsize >= 4 else 16
    return t % rows == 0 and e % 128 == 0 and prev.shape[0] == 3


def _conv_silu_kernel(x_ref, p_ref, w_ref, y_ref):
    f32 = jnp.float32
    w = w_ref[...]
    conv = w[3:4, :] * x_ref[...].astype(f32)
    for j in range(3):
        conv += w[j:j + 1, :] * p_ref[j].astype(f32)
    y_ref[...] = (conv * jax.nn.sigmoid(conv)).astype(y_ref.dtype)


def _conv_bias_silu_kernel(x_ref, p_ref, w_ref, y_ref):
    """The same filter with a bias a channel inside the SiLU: row 4 of
    the filter's tile."""
    f32 = jnp.float32
    w = w_ref[...]
    conv = w[4:5, :] + w[3:4, :] * x_ref[...].astype(f32)
    for j in range(3):
        conv += w[j:j + 1, :] * p_ref[j].astype(f32)
    y_ref[...] = (conv * jax.nn.sigmoid(conv)).astype(y_ref.dtype)


@jax.jit
def _conv_silu_call(x, prev, w, bias=None):
    t, e = x.shape
    tr = next(r for r in _ROW_TILES if t % r == 0)
    tc = next(c for c in _LANE_TILES if e % c == 0)
    w8 = jnp.zeros((8, e), jnp.float32).at[:w.shape[0]].set(
        w.astype(jnp.float32))
    if bias is not None:
        w8 = w8.at[4].set(bias.astype(jnp.float32))
    row = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    return pl.pallas_call(
        _conv_silu_kernel if bias is None else _conv_bias_silu_kernel,
        grid=(t // tr, e // tc),
        in_specs=[row, pl.BlockSpec((3, tr, tc), lambda i, j: (0, i, j)),
                  pl.BlockSpec((8, tc), lambda i, j: (0, j))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((t, e), x.dtype),
    )(x, prev, w8)


def conv_silu(x, prev, w, bias=None):
    """y [T, E] = silu of the 4-tap filter `w` [4, E] (tap 0 the oldest)
    over `prev` [3, T, E] (oldest first) and `x` [T, E], plus `bias` [E]
    inside the SiLU where there is one. Callers check
    `conv_silu_supported` first."""
    return _conv_silu_call(x, prev, w, bias)
