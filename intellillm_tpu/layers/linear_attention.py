"""Kimi Delta Attention: a gated delta rule whose decay is a vector over the
key channels (the mixer of a "state" layer of a linear-attention hybrid).

Per head of `d` key and value channels, `x` the layer's normed input:

    [q~ | k~ | v~] = x W_qkv                     (hidden -> 3 x H x d)
    q, k, v = silu(depthwise causal 4-tap filter of q~, k~, v~)
    q = q / ||q|| * d^-1/2 ;  k = k / ||k||      (per head, eps 1e-6)
    g = -exp(A_log) * softplus(W_f2 (W_f1 x) + dt_bias)     (= log a <= 0)
    b = 2 * sigmoid(x W_b)       (the 2: negative eigenvalues allowed)
    o = delta rule over (q, k, v, g, b)          (ops/linear_attention.py)
    y = [RMSNorm_head(o) * w_norm * sigmoid(W_g2 (W_g1 x))] W_o

What a family may change, each before the seam (`ops/linear_attention.py`
takes `g` and `b` as they come): the decay bounded below (`lower_bound`:
`g = lower_bound * sigmoid(exp(A_log) * (. + dt_bias))`, in (lower_bound,
0), in place of the softplus form); the decay's and the output gate's
projections at full rank (a layer that holds `f` / `g` and no `f_a`, `f_b`
/ `g_a`, `g_b` pair); the write strength without the 2 (`beta_scale` 1).

What a sequence keeps between steps, one slot each in the layer's two
pools (`ModelConfig.get_state_arrays`): the last three rows of
`[q~ | k~ | v~]` (the filters' taps, oldest first, in the model's dtype)
and the delta rule's matrix a head, float32. As in `layers/short_conv.py`
a step never zeroes a slot: a row at a position under 3 takes 0 for what
lies before the sequence's start, and the delta rule starts from zero at
position 0, whatever the slot holds.

Two cases, as `layers/short_conv.py` has them:

- a `mixed` step: every row is one token with its position and the slot
  of its sequence. The taps of a row are the rows before it of the same
  run where the step holds them, else the slot's, else 0; the delta rule
  runs in its chunk form over the runs (`kda_chunk`). A run's last row
  writes both pools.
- a K-step decode program (staged): the cache is (taps stage, matrix
  pool): the taps `[B, 3 * width]` gathered by the runner before the first
  substep and scattered back after the last, the matrix pool itself,
  which `kda_step` updates in place by slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.layers.short_conv import _prev_rows
from intellillm_tpu.ops.linear_attention import (kda_chunk, kda_step,
                                                 run_structure)
from intellillm_tpu.ops.short_conv import conv_silu_body
from intellillm_tpu.ops.state_rows import gather_rows, scatter_rows

_NORM_EPS = 1e-6


def _l2_normalise(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + _NORM_EPS)


def _gate_projection(x, lp, name: str):
    """`x` through the layer's low-rank pair `<name>_a`, `<name>_b`, or
    through its one full-rank matrix `<name>` where it holds that."""
    if name in lp:
        return x @ lp[name]
    return (x @ lp[name + "_a"]) @ lp[name + "_b"]


def kda_gates(x, lp, num_heads: int, head_dim: int, lower_bound=None,
              beta_scale: float = 2.0):
    """(g [B, H, d] float32 = the log of the decay, b [B, H] float32) of
    the layer's input `x` [B, E]. `lower_bound` None: `g = -exp(A_log) *
    softplus(.)`; a number (< 0): `g = lower_bound * sigmoid(exp(A_log) *
    .)`."""
    f32 = jnp.float32
    raw = _gate_projection(x, lp, "f").astype(f32) + lp["dt_bias"].astype(f32)
    if lower_bound is None:
        g = (-jnp.exp(lp["A_log"].astype(f32))[None, :, None]
             * jax.nn.softplus(raw).reshape(-1, num_heads, head_dim))
    else:
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(lp["A_log"].astype(f32))[None, :, None]
            * raw.reshape(-1, num_heads, head_dim))
    beta = jax.nn.sigmoid((x @ lp["b_proj"]).astype(f32))
    if beta_scale != 1.0:
        beta = beta_scale * beta
    return g, beta


def earlier_taps(pre, taps, attn_metadata, pos):
    """(prev [3, B, W], last): a 4-tap filter's three earlier inputs of
    every row of `pre` [B, W] (oldest first, 0 before the sequence's
    start), and which rows end their run (None staged). `taps` is the
    layer's taps pool `[slots, 3 * W]` or, staged, its stage `[B, 3 * W]`.
    In a `mixed` step a row's tap n back is the run's own row n back where
    the step holds it, else what the slot held when the run began."""
    b, width = pre.shape
    slots = attn_metadata.state_slots
    last = None
    if attn_metadata.staged:
        held = taps
    else:
        continues, last = run_structure(slots, pos)
        held = gather_rows(taps, slots)                       # [B, 3 * W]
        # `back` rows of a row's own run lie above it in the step (3 and
        # more count as 3).
        back = jnp.zeros((b, ), jnp.int32)
        run = continues
        for n in range(1, 4):
            back = back + run.astype(jnp.int32)
            run = run & _prev_rows(continues, n)
    held = held.reshape(b, 3, width)
    prev = []
    for n in (3, 2, 1):                       # oldest first
        if attn_metadata.staged:
            tap = held[:, 3 - n]
        else:
            # The run's own row n back where the step holds it, else what
            # the slot held when the run began.
            from_slot = jnp.take_along_axis(
                held, jnp.clip(3 - n + back, 0, 2)[:, None, None],
                axis=1)[:, 0]
            tap = jnp.where((back >= n)[:, None], _prev_rows(pre, n),
                            from_slot)
        # Before the sequence's start there is nothing.
        prev.append(jnp.where((pos >= n)[:, None], tap, 0).astype(pre.dtype))
    return jnp.stack(prev), last                              # [3, B, W]


def later_taps(prev, pre, dtype):
    """[B, 3 * W]: what the row after this one finds as its taps."""
    return jnp.concatenate([prev[1], prev[2], pre], axis=1).astype(dtype)


def store_taps(taps, new_taps, attn_metadata, last):
    """The layer's taps after the step: staged, the new stage; in a
    `mixed` step the pool, where only a run's last row writes its slot
    (the others, and pad rows, point past the pool and are dropped)."""
    if attn_metadata.staged:
        return new_taps
    dst = jnp.where(last, attn_metadata.state_slots, taps.shape[0])
    return scatter_rows(taps, dst, new_taps)


def kda_mixer(h, lp, cache, attn_metadata, positions, *, num_heads: int,
              head_dim: int, eps: float, lower_bound=None,
              beta_scale: float = 2.0):
    """`h` [B, 1, E] -> ([B, 1, E], cache). `cache` is (taps pool, matrix
    pool) or, staged, (taps stage, matrix pool). `lower_bound`,
    `beta_scale`: `kda_gates`'s."""
    b, l, e = h.shape
    assert l == 1, "a step's rows are one token each"
    x = h.reshape(b, e)
    width = 3 * num_heads * head_dim
    pre = x @ lp["qkv"]                                       # [B, 3HD]
    pos = positions.reshape(b)
    slots = attn_metadata.state_slots
    taps, matrices = cache
    prev, last = earlier_taps(pre, taps, attn_metadata, pos)
    qkv = conv_silu_body(pre, prev, lp["conv_w"])
    q, k, v = (qkv[:, i * width // 3:(i + 1) * width // 3].reshape(
        b, num_heads, head_dim) for i in range(3))
    q = _l2_normalise(q) * head_dim ** -0.5
    k = _l2_normalise(k)
    g, beta = kda_gates(x, lp, num_heads, head_dim, lower_bound, beta_scale)
    new_taps = later_taps(prev, pre, taps.dtype)
    if attn_metadata.staged:
        o, matrices = kda_step(q, k, v, g, beta, matrices, slots)
    else:
        o, matrices = kda_chunk(q, k, v, g, beta, matrices, slots, pos)
    cache = (store_taps(taps, new_taps, attn_metadata, last), matrices)
    gate = jax.nn.sigmoid(_gate_projection(x, lp, "g").astype(jnp.float32))
    o = rms_norm(o, lp["o_norm"].astype(jnp.float32), eps)
    y = (o.reshape(b, width // 3) * gate).astype(h.dtype)
    return (y @ lp["o"]).reshape(b, 1, e), cache
