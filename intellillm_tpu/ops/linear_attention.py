"""Seam of the recurrences that keep a float32 matrix a sequence in a state
pool: the gated delta rule with a per-channel decay (Kimi Delta Attention;
labels `kda_chunk`, `kda_step`), the selective state-space scan with a
scalar decay a head and no delta term (Mamba-2; labels `ssd_chunk`,
`ssd_step`) and, at the end of the file, the selective scan whose decay is
a matrix over (channel, state) pairs (Mamba-1; labels `sscan_chunk`,
`sscan_step`). The three bodies share the contract of the pool, the slots
and the runs (`run_structure`).

The delta rule.

Per head, with `S` a float32 matrix `[d_k, d_v]` (rows = key channels),
`a_t = exp(g_t)` in (0, 1)^{d_k} and `b_t` in (0, 2):

    S'  = diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

`layers/linear_attention.py` calls the two functions below and nothing
under them; each asks `ops/dispatch.use_kernel` once and runs its Pallas
kernel (`ops/pallas/kda.py`: `_kda_chunk_call`, `_kda_step_call` in the
device trace) or the jnp oracle beside it, which is the recurrence as
written, a row at a time.

The state lives in the layer's pool `[slots + 1, H, d_k, d_v]` float32
(`worker/cache_engine.py`): a row's `slot` names its sequence's matrix,
the last row of the pool is where pad rows point (`slot == slots`), and
both functions return the pool updated in place (the caller donates it).

- `kda_chunk` (a `mixed` step): rows of several sequences. A run of
  consecutive rows of one slot at consecutive positions continues one
  state: the run's first row starts from the slot's matrix, or from zero
  at position 0 whatever the slot holds, and the run's last row writes the
  slot. Nothing crosses a boundary between runs.
- `kda_step` (a substep of a K-step decode program): every row is a run
  of one token on its own slot.

Arguments: `q`, `k`, `v` `[T, H, d]` (q, k normalised by the caller),
`g` `[T, H, d_k]` float32 = log a_t <= 0, `beta` `[T, H]` float32.
Outputs are float32 `[T, H, d_v]`.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from intellillm_tpu.ops.dispatch import use_kernel
from intellillm_tpu.ops.pallas import kda as _kernels
from intellillm_tpu.ops.pallas import selective_scan as _sscan
from intellillm_tpu.ops.pallas import ssd as _ssd


def delta_rule_update(state, q, k, v, g, beta):
    """One token of the recurrence for a batch of heads: `state`
    [..., d_k, d_v] float32, `q`, `k`, `g` [..., d_k], `v` [..., d_v],
    `beta` [...]. Returns (o [..., d_v], new state), float32."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decayed = jnp.exp(g.astype(f32))[..., :, None] * state
    u = v - jnp.einsum("...k,...kv->...v", k, decayed,
                       precision=jax.lax.Precision.HIGHEST)
    new = decayed + (beta.astype(f32)[..., None, None] * k[..., :, None]
                     * u[..., None, :])
    o = jnp.einsum("...k,...kv->...v", q, new,
                   precision=jax.lax.Precision.HIGHEST)
    return o, new


def run_structure(slots: jnp.ndarray, positions: jnp.ndarray):
    """(continues [T] bool, last [T] bool) of a `mixed` step's rows: a row
    continues the row before it when that is the token before it of the
    same sequence; a run's last row is one that the next row does not
    continue."""
    t = slots.shape[0]
    prev_slot = jnp.pad(slots, (1, 0))[:t]
    prev_pos = jnp.pad(positions, (1, 0))[:t]
    continues = ((slots == prev_slot) & (positions == prev_pos + 1)
                 & (jnp.arange(t) >= 1))
    last = ~jnp.pad(continues[1:], (0, 1))
    return continues, last


def kda_chunk(q, k, v, g, beta, pool, slots, positions
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(o [T, H, d_v] float32, pool) of a `mixed` step's rows."""
    if use_kernel("kda_chunk",
                  _kernels.kda_supported(q, pool, _kernels.CHUNK)):
        continues, _ = run_structure(slots, positions)
        return _kernels.kda_chunk(q, k, v, g, beta, pool, slots, positions,
                                  continues)
    return kda_chunk_reference(q, k, v, g, beta, pool, slots, positions)


def kda_step(q, k, v, g, beta, pool, slots
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(o [B, H, d_v] float32, pool) of one decode substep; a pad row's
    slot is the pool's last row."""
    if use_kernel("kda_step", _kernels.kda_supported(q, pool)):
        return _kernels.kda_step(q, k, v, g, beta, pool, slots)
    return kda_step_reference(q, k, v, g, beta, pool, slots)


def kda_step_reference(q, k, v, g, beta, pool, slots):
    o, new = delta_rule_update(pool[slots], q, k, v, g, beta)
    return o, pool.at[slots].set(new)


def kda_chunk_reference(q, k, v, g, beta, pool, slots, positions):
    """The recurrence a row at a time, the current run's state carried
    beside the pool."""
    continues, last = run_structure(slots, positions)
    pad = pool.shape[0] - 1

    def row(carry, x):
        pool, cur = carry
        q_t, k_t, v_t, g_t, b_t, slot, pos, cont, is_last = x
        start = jnp.where(pos == 0, jnp.zeros_like(cur), pool[slot])
        o, new = delta_rule_update(jnp.where(cont, cur, start), q_t, k_t,
                                   v_t, g_t, b_t)
        pool = pool.at[jnp.where(is_last, slot, pad)].set(new)
        return (pool, new), o

    (pool, _), o = jax.lax.scan(
        row, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (q, k, v, g, beta, slots, positions, continues, last))
    return o, pool


# --- the selective state-space scan ------------------------------------------
#
# Per head h of `P` channels, with `H_t` a float32 matrix `[N, P]` (rows =
# state channels), `dt_t` > 0 and `a` < 0 one number a head, `B_t[g]`,
# `C_t[g]` in R^N shared by the heads of group g (`G` groups; Mamba-2's
# map: head h reads group `h // (H / G)`, a group's heads contiguous):
#
#     H_t = exp(dt_t a) H_{t-1} + B_t[g] (dt_t x_t)^T
#     y_t = H_t^T C_t[g]
#
# (the skip `D x_t` is the layer's). Nothing in it couples two channels of
# `x`, so a sequence's state is one matrix `[N, heads x P]` whose columns
# decay by their head's factor; the pool holds it with the columns cut
# into lane tiles, `[slots + 1, tiles, N, lanes]` (`config.ssd_state_shape`),
# and lane tile t reads group `t // (tiles / G)` (a group's channels are
# whole tiles).
# The contract is `kda_chunk`'s / `kda_step`'s: a run of consecutive rows
# of one slot continues one state, starts from zero at position 0 whatever
# the slot holds, its last row writes the slot, nothing crosses runs, pad
# rows point at the pool's last row.
#
# Arguments: `x` [T, H, P], `dt` [T, H] float32, `a` [H] float32, `b`, `c`
# [T, G, N] (or [T, N]: one group). Outputs are float32 `[T, H, P]`.


def ssd_update(state, x, dt, a, b, c):
    """One token of the scan: `state` [..., tiles, N, lanes] float32, `x`
    [..., H, P], `dt` [..., H], `a` [H], `b`, `c` [..., G, N] (or
    [..., N]: one group). Returns (y [..., H, P], new state), float32."""
    f32 = jnp.float32
    p = x.shape[-1]
    lead, (tiles, _, lanes) = state.shape[:-3], state.shape[-3:]
    if b.ndim == dt.ndim:
        b, c = b[..., None, :], c[..., None, :]
    dt = dt.astype(f32)
    keep = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=-1)
    dtx = dt[..., None] * x.astype(f32)
    by_tile = lead + (tiles, 1, lanes)
    # [..., tiles, N, 1]: a group's column for each of its tiles in turn.
    b_c, c_c = (jnp.repeat(v.astype(f32), tiles // b.shape[-2],
                           axis=-2)[..., None] for v in (b, c))
    new = keep.reshape(by_tile) * state + b_c * dtx.reshape(by_tile)
    y = (c_c * new).sum(-2)
    return y.reshape(x.shape), new


def ssd_chunk(x, dt, a, b, c, pool, slots, positions
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y [T, H, P] float32, pool) of a `mixed` step's rows."""
    if use_kernel("ssd_chunk", _ssd.ssd_supported(x, b, pool)):
        continues, last = run_structure(slots, positions)
        return _ssd.ssd_chunk(x, dt, a, b, c, pool, slots, positions,
                              continues, last)
    return ssd_chunk_reference(x, dt, a, b, c, pool, slots, positions)


def ssd_step(x, dt, a, b, c, pool, slots
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y [B, H, P] float32, pool) of one decode substep; a pad row's
    slot is the pool's last row."""
    if use_kernel("ssd_step", _ssd.ssd_supported(x, b, pool)):
        return _ssd.ssd_step(x, dt, a, b, c, pool, slots)
    return ssd_step_reference(x, dt, a, b, c, pool, slots)


def ssd_step_reference(x, dt, a, b, c, pool, slots):
    y, new = ssd_update(pool[slots], x, dt, a, b, c)
    return y, pool.at[slots].set(new)


def ssd_chunk_reference(x, dt, a, b, c, pool, slots, positions):
    """The recurrence a row at a time, the current run's state carried
    beside the pool."""
    continues, last = run_structure(slots, positions)
    pad = pool.shape[0] - 1

    def row(carry, r):
        pool, cur = carry
        x_t, dt_t, b_t, c_t, slot, pos, cont, is_last = r
        start = jnp.where(pos == 0, jnp.zeros_like(cur), pool[slot])
        y, new = ssd_update(jnp.where(cont, cur, start), x_t, dt_t, a, b_t,
                            c_t)
        pool = pool.at[jnp.where(is_last, slot, pad)].set(new)
        return (pool, new), y

    (pool, _), y = jax.lax.scan(
        row, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (x, dt, b, c, slots, positions, continues, last))
    return y, pool


# --- the selective scan with a decay a (channel, state) pair ------------------
#
# Mamba-1: no heads. With `S_t` a float32 matrix `[N, inner]` (rows = state
# channels), `dt_t` > 0 a channel, `A` `[N, inner]` < 0 learned, `B_t`, `C_t`
# in R^N one each a token:
#
#     S_t = exp(dt_t[c] A[n, c]) S_{t-1} + B_t (dt_t x_t)^T
#     y_t = S_t^T C_t
#
# (the skip `D x_t` is the layer's). The decay is a matrix as large as the
# state, so no scalar a head can stand for it and neither `ssd_*` kernel
# computes it. The pool holds the state with its channels cut into lane
# tiles, `[slots + 1, tiles, N, 128]`; the contract of slots, runs and the
# pad row is the two bodies' above.
#
# Arguments: `x`, `dt` [T, inner] (`dt` float32), `a` [N, inner] float32,
# `b`, `c` [T, N]. Outputs are float32 `[T, inner]`.


def sscan_update(state, x, dt, a, b, c):
    """One token of the scan: `state` [..., tiles, N, lanes] float32, `x`,
    `dt` [..., inner], `a` [N, inner], `b`, `c` [..., N]. Returns (y
    [..., inner], new state), float32."""
    f32 = jnp.float32
    lead, (tiles, n, lanes) = state.shape[:-3], state.shape[-3:]
    dt = dt.astype(f32)
    by_tile = lead + (tiles, 1, lanes)
    a_t = a.astype(f32).reshape(n, tiles, lanes).swapaxes(0, 1)
    b_c, c_c = (v.astype(f32)[..., None, :, None] for v in (b, c))
    new = (jnp.exp(dt.reshape(by_tile) * a_t) * state
           + b_c * (dt * x.astype(f32)).reshape(by_tile))
    return (c_c * new).sum(-2).reshape(x.shape), new


def sscan_chunk(x, dt, a, b, c, pool, slots, positions
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y [T, inner] float32, pool) of a `mixed` step's rows."""
    if use_kernel("sscan_chunk", _sscan.sscan_supported(x, pool)):
        continues, last = run_structure(slots, positions)
        return _sscan.sscan_chunk(x, dt, a, b, c, pool, slots, positions,
                                  continues, last)
    return sscan_chunk_reference(x, dt, a, b, c, pool, slots, positions)


def sscan_step(x, dt, a, b, c, pool, slots
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(y [B, inner] float32, pool) of one decode substep; a pad row's
    slot is the pool's last row."""
    if use_kernel("sscan_step", _sscan.sscan_supported(x, pool)):
        return _sscan.sscan_step(x, dt, a, b, c, pool, slots)
    return sscan_step_reference(x, dt, a, b, c, pool, slots)


def sscan_step_reference(x, dt, a, b, c, pool, slots):
    y, new = sscan_update(pool[slots], x, dt, a, b, c)
    return y, pool.at[slots].set(new)


def sscan_chunk_reference(x, dt, a, b, c, pool, slots, positions):
    """The recurrence a row at a time, the current run's state carried
    beside the pool."""
    continues, last = run_structure(slots, positions)
    pad = pool.shape[0] - 1

    def row(carry, r):
        pool, cur = carry
        x_t, dt_t, b_t, c_t, slot, pos, cont, is_last = r
        start = jnp.where(pos == 0, jnp.zeros_like(cur), pool[slot])
        y, new = sscan_update(jnp.where(cont, cur, start), x_t, dt_t, a,
                              b_t, c_t)
        pool = pool.at[jnp.where(is_last, slot, pad)].set(new)
        return (pool, new), y

    (pool, _), y = jax.lax.scan(
        row, (pool, jnp.zeros(pool.shape[1:], pool.dtype)),
        (x, dt, b, c, slots, positions, continues, last))
    return y, pool
