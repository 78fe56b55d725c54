"""Mamba-2: a selective state-space scan with a scalar decay a head (the
mixer of a "state" layer of a state-space hybrid).

With `x` the layer's normed input, `H` heads of `P` channels (`inner = H
P`), `N` state channels, `G` groups of B and C (head h reads group
`g = h // (H / G)`; Granite 4.0-H has one, Falcon-H1 two) and, where the
model has one, a vector `mup` of one scale for each of the in-projection's
five segments `[z | x_s | B | C | dt]` (`segment_scales`):

    [z | xBC | dt] = (x W_in) * mup               (inner | inner + 2GN | H)
    xBC = silu(depthwise causal 4-tap filter of xBC + b_conv)
    x_s | B | C = xBC                             (inner | G N | G N)
    dt = softplus(dt + dt_bias) ;  a = -exp(A_log)            one a head
    S_t = exp(dt_t a) S_{t-1} + B_t[g] (dt_t x_s,t)^T         [N, P] a head
    y_t = S_t^T C_t[g] + D x_s,t
    out = [RMSNorm_group(y * silu(z)) * w_norm] W_out

(the gate first, then a mean square over each group's `inner / G` channels
on its own: with one group, over all of them).

What a sequence keeps between steps, one slot each in the layer's two
pools (`ModelConfig.get_state_arrays`): the last three rows of `xBC`
before the filter (its taps, oldest first, in the model's dtype) and the
scan's float32 matrix `[N, inner]` (`ops/linear_attention.py`). As in
`layers/linear_attention.py` a step never zeroes a slot: a row at a
position under 3 takes 0 for what lies before the sequence's start, and
the scan starts from zero at position 0, whatever the slot holds. The two
cases (`mixed`: `ssd_chunk` over the runs; a K-step decode program,
staged: `ssd_step` on the pool by slot) are that file's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.layers.linear_attention import (earlier_taps, later_taps,
                                                    store_taps)
from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.ops.linear_attention import ssd_chunk, ssd_step
from intellillm_tpu.ops.short_conv import conv_silu_body


def segment_vector(scales, inner: int, groups: int, d_state: int,
                   num_heads: int) -> np.ndarray:
    """One scale for each output of the in-projection, from one for each
    of its segments `[z | x_s | B | C | dt]`."""
    widths = (inner, inner, groups * d_state, groups * d_state, num_heads)
    return np.repeat(np.asarray(scales, np.float32), widths)


def mamba2_mixer(h, lp, cache, attn_metadata, positions, *, num_heads: int,
                 head_dim: int, d_state: int, eps: float, groups: int = 1,
                 segment_scales=None):
    """`h` [B, 1, E] -> ([B, 1, E], cache). `cache` is (taps pool, matrix
    pool) or, staged, (taps stage, matrix pool). `segment_scales`: five
    numbers (`segment_vector`), or None for a model without them."""
    b, l, e = h.shape
    assert l == 1, "a step's rows are one token each"
    f32 = jnp.float32
    inner = num_heads * head_dim
    bc = groups * d_state
    filtered = inner + 2 * bc
    proj = h.reshape(b, e) @ lp["in_proj"]        # [B, 2 inner + 2 G N + H]
    if segment_scales is not None:
        proj = (proj.astype(f32) * segment_vector(
            segment_scales, inner, groups, d_state, num_heads)
                ).astype(proj.dtype)
    z = proj[:, :inner]
    pre = proj[:, inner:inner + filtered]
    pos = positions.reshape(b)
    slots = attn_metadata.state_slots
    taps, matrices = cache
    prev, last = earlier_taps(pre, taps, attn_metadata, pos)
    xbc = conv_silu_body(pre, prev, lp["conv_w"], lp["conv_b"])
    x = xbc[:, :inner].reshape(b, num_heads, head_dim)
    b_t = xbc[:, inner:inner + bc].reshape(b, groups, d_state)
    c_t = xbc[:, inner + bc:].reshape(b, groups, d_state)
    dt = jax.nn.softplus(proj[:, inner + filtered:].astype(f32)
                         + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["A_log"].astype(f32))
    new_taps = later_taps(prev, pre, taps.dtype)
    if attn_metadata.staged:
        y, matrices = ssd_step(x, dt, a, b_t, c_t, matrices, slots)
    else:
        y, matrices = ssd_chunk(x, dt, a, b_t, c_t, matrices, slots, pos)
    cache = (store_taps(taps, new_taps, attn_metadata, last), matrices)
    y = y + lp["D"].astype(f32)[None, :, None] * x.astype(f32)
    y = y.reshape(b, inner) * jax.nn.silu(z.astype(f32))
    y = rms_norm(y.reshape(b, groups, inner // groups),
                 lp["gate_norm"].astype(f32).reshape(groups, -1), eps)
    y = y.reshape(b, inner).astype(h.dtype)
    return (y @ lp["out_proj"]).reshape(b, 1, e), cache
