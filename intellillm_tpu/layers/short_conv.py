"""Gated short convolution with per-sequence state (the mixer of a "conv"
layer in a hybrid stack).

    [B | C | x] = u W_in            (hidden -> 3 x hidden)
    g_t = B_t * x_t
    c_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t     (depthwise, causal,
                                                   g before the start = 0)
    y_t = (C_t * c_t) W_out

What a sequence keeps between steps is its last two `g` vectors: one slot
of the layer's state pool `[slots, 2 * hidden]` (`worker/cache_engine.py`),
the older in the first half. A step never zeroes a slot: a row at position 0 or 1
takes 0 for what lies before the sequence's start whatever the slot
holds, so allocation, recompute-preemption and a slot's reuse need no
device work.

Two cases, as `layers/attention.py` has them:

- a `mixed` step (`attn_metadata.staged` false): every row is one token
  with its position and the slot of its sequence
  (`attn_metadata.state_slots`; a pad row's lies past the pool). A
  prompt's chunk is consecutive rows of one slot at consecutive
  positions: `g_{t-1}`, `g_{t-2}` are the rows before it of the same
  sequence where the step holds them, else the slot's, else 0, so nothing
  crosses a boundary between sequences. The last row of each sequence
  writes the slot.
- a K-step decode program (staged): the layer's cache is (stage,),
  the stage `[B, 2 * hidden]` the rows' own states, which the runner
  gathered from the pool before the first substep, carries through the
  substeps and scatters back after the last (`worker/model_runner.py`).
"""
from __future__ import annotations

import jax.numpy as jnp

from intellillm_tpu.ops.linear_attention import run_structure
from intellillm_tpu.ops.short_conv import gated_input, short_conv_body
from intellillm_tpu.ops.state_rows import gather_rows, scatter_rows


def _prev_rows(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Row r holds row r - n of `x` (zeros above the first)."""
    return jnp.pad(x, ((n, 0), ) + ((0, 0), ) * (x.ndim - 1))[:x.shape[0]]


def short_conv_mixer(h, w_in, conv_w, w_out, cache, attn_metadata,
                     positions):
    """`h` [B, 1, E] -> ([B, 1, E], cache). `cache` is (state pool,) or,
    staged, (stage,)."""
    b, l, e = h.shape
    assert l == 1, "a step's rows are one token each"
    bcx = (h @ w_in).reshape(b, 3 * e)
    pos = positions.reshape(b)
    if attn_metadata.staged:
        (stage, ) = cache
        p2, p1 = stage[:, :e], stage[:, e:]
    else:
        (pool, ) = cache
        slots = attn_metadata.state_slots
        # Row r-1 / r-2 is the token before / two before this row's, of
        # the same sequence.
        same1, last = run_structure(slots, pos)
        same2 = same1 & _prev_rows(same1, 1)
        g_rows = gated_input(bcx)
        held = gather_rows(pool, slots)                        # [B, 2E]
        older, newer = held[:, :e], held[:, e:]
        p1 = jnp.where(same1[:, None], _prev_rows(g_rows, 1), newer)
        p2 = jnp.where(same2[:, None], _prev_rows(g_rows, 2),
                       jnp.where(same1[:, None], newer, older))
    # Before the sequence's start there is nothing, whatever a slot holds.
    p1 = jnp.where((pos >= 1)[:, None], p1, 0).astype(bcx.dtype)
    p2 = jnp.where((pos >= 2)[:, None], p2, 0).astype(bcx.dtype)
    y, g = short_conv_body(bcx, p1, p2, conv_w)
    new = jnp.concatenate([p1, g], axis=1).astype(cache[0].dtype)  # [B, 2E]
    if attn_metadata.staged:
        cache = (new, )
    else:
        # Only a sequence's last row in the step writes its slot; the
        # others, and pad rows, point past the pool and are dropped.
        dst = jnp.where(last, slots, pool.shape[0])
        cache = (scatter_rows(pool, dst, new), )
    return (y @ w_out).reshape(b, 1, e), cache
