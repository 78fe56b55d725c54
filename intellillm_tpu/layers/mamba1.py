"""Mamba-1: a selective state-space scan whose decay is a matrix over
(channel, state) pairs and whose step `dt` comes from a low-rank
projection (the mixer of a "state" layer of Phi-4-mini-flash).

With `u` the layer's normed input, `inner` channels, `N` state channels,
`R` the rank of `dt`'s projection:

    [x | z] = u W_in                                  (inner | inner)
    x = silu(depthwise causal 4-tap filter of x + b_conv)
    [r | B | C] = x W_x                               (R | N | N)
    dt = softplus(r W_dt + b_dt) ;  A = -exp(A_log)   [N, inner]
    S_t = exp(dt_t A) * S_{t-1} + B_t (dt_t x_t)^T    [N, inner]
    y_t = S_t^T C_t + D x_t
    out = (y * silu(z)) W_out

The function returns `y` beside `out`: the scan's output with the skip
and BEFORE the gate is what the model's gated memory units read.

What a sequence keeps between steps, one slot each in the layer's two
pools (`ModelConfig.get_state_arrays`): the last three rows of `x` before
the filter (its taps, oldest first, in the model's dtype) and the scan's
float32 matrix `[N, inner]` (`ops/linear_attention.py` `sscan_*`). As in
`layers/mamba2.py` a step never zeroes a slot: a row at a position under
3 takes 0 for what lies before the sequence's start, and the scan starts
from zero at position 0, whatever the slot holds. The two cases (`mixed`:
`sscan_chunk` over the runs; a K-step decode program, staged:
`sscan_step` on the pool by slot) are that file's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from intellillm_tpu.layers.linear_attention import (earlier_taps, later_taps,
                                                    store_taps)
from intellillm_tpu.ops.linear_attention import sscan_chunk, sscan_step
from intellillm_tpu.ops.short_conv import conv_silu_body


def mamba1_mixer(h, lp, cache, attn_metadata, positions, *, d_state: int,
                 dt_rank: int):
    """`h` [B, 1, E] -> (out [B, 1, E], y [B, inner] float32, cache).
    `cache` is (taps pool, matrix pool) or, staged, (taps stage, matrix
    pool)."""
    b, l, e = h.shape
    assert l == 1, "a step's rows are one token each"
    f32 = jnp.float32
    proj = h.reshape(b, e) @ lp["in_proj"]                    # [B, 2 inner]
    inner = proj.shape[1] // 2
    pre, z = proj[:, :inner], proj[:, inner:]
    pos = positions.reshape(b)
    taps, matrices = cache
    prev, last = earlier_taps(pre, taps, attn_metadata, pos)
    x = conv_silu_body(pre, prev, lp["conv_w"], lp["conv_b"])
    low = x @ lp["x_proj"]                                    # [B, R + 2 N]
    b_t = low[:, dt_rank:dt_rank + d_state]
    c_t = low[:, dt_rank + d_state:]
    dt = jax.nn.softplus((low[:, :dt_rank] @ lp["dt_proj"]).astype(f32)
                         + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["A_log"].astype(f32))                     # [N, inner]
    new_taps = later_taps(prev, pre, taps.dtype)
    if attn_metadata.staged:
        y, matrices = sscan_step(x, dt, a, b_t, c_t, matrices,
                                 attn_metadata.state_slots)
    else:
        y, matrices = sscan_chunk(x, dt, a, b_t, c_t, matrices,
                                  attn_metadata.state_slots, pos)
    cache = (store_taps(taps, new_taps, attn_metadata, last), matrices)
    y = y + lp["D"].astype(f32) * x.astype(f32)
    gated = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
    return (gated @ lp["out_proj"]).reshape(b, 1, e), y, cache
