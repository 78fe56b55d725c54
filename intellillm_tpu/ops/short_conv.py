"""Seam of the short convolutions' bodies (labels `short_conv`,
`short_conv_silu`).

Two bodies of one family, each a depthwise causal filter over a row's own
past, resolved by the caller: LFM2's gate x 3-tap x gate
(`short_conv_body`), and the plain 4-tap filter then SiLU
(`conv_silu_body`) that a delta-rule layer's q, k and v pass and, with a
bias a channel inside the SiLU, a state-space layer's x, B and C.

`layers/short_conv.py` calls this and nothing below it; it asks
`ops/dispatch.use_kernel` once and runs the Pallas kernel
(`ops/pallas/short_conv.py`, `_short_conv_call` in the device trace) or
the jnp oracle below, the same arithmetic: float32 inside, `g` rounded to
the activations' dtype before the filter reads it (a later step reads it
from the state at that precision).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from intellillm_tpu.ops.dispatch import use_kernel
from intellillm_tpu.ops.pallas.short_conv import (conv_silu,
                                                  conv_silu_supported,
                                                  short_conv,
                                                  short_conv_supported)


def gated_input(bcx: jnp.ndarray) -> jnp.ndarray:
    """g = B * x of an in-projection `[B | C | x]`, as the state stores
    it."""
    e = bcx.shape[-1] // 3
    f32 = jnp.float32
    return (bcx[..., :e].astype(f32) * bcx[..., 2 * e:].astype(f32)).astype(
        bcx.dtype)


def short_conv_body(bcx: jnp.ndarray, p1: jnp.ndarray, p2: jnp.ndarray,
                    w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y, g) [T, E] of the in-projection `bcx` [T, 3E], the gated inputs
    one (`p1`) and two (`p2`) positions back, and the filter `w` [3, E]
    (tap 0 the oldest)."""
    if use_kernel("short_conv", short_conv_supported(bcx)):
        return short_conv(bcx, p1, p2, w)
    return short_conv_body_reference(bcx, p1, p2, w)


def short_conv_body_reference(bcx, p1, p2, w):
    e = bcx.shape[-1] // 3
    f32 = jnp.float32
    g = gated_input(bcx)
    w = w.astype(f32)
    conv = (w[0] * p2.astype(f32) + w[1] * p1.astype(f32)
            + w[2] * g.astype(f32))
    y = (bcx[..., e:2 * e].astype(f32) * conv).astype(bcx.dtype)
    return y, g


def conv_silu_body(x: jnp.ndarray, prev: jnp.ndarray, w: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """y [T, E] = silu(sum_j w[j] * tap_j + bias) of a row's input `x`
    [T, E], its inputs at the `taps - 1` positions before it `prev`
    [taps - 1, T, E] (oldest first, 0 before the sequence's start), the
    filter `w` [taps, E] (tap 0 the oldest) and, where the filter has
    one, its bias [E]."""
    if use_kernel("short_conv_silu", conv_silu_supported(x, prev)):
        return conv_silu(x, prev, w, bias)
    return conv_silu_body_reference(x, prev, w, bias)


def conv_silu_body_reference(x, prev, w, bias=None):
    f32 = jnp.float32
    w = w.astype(f32)
    taps = jnp.concatenate([prev.astype(f32), x.astype(f32)[None]], axis=0)
    conv = (w[:, None, :] * taps).sum(0)
    if bias is not None:
        conv = conv + bias.astype(f32)
    return (conv * jax.nn.sigmoid(conv)).astype(x.dtype)
