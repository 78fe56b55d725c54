"""The selective state-space scan (Mamba-2: a scalar decay a head, no delta
term, B and C shared by the heads of a group) on the state pool, in place:
a step form and a chunk form.

A sequence's state is one float32 matrix `[N, inner]` (`N` state channels
on the sublanes, the layer's `inner = heads x P` channels along the lanes,
cut into `tiles` lane tiles of 128: `config.ssd_state_shape`). With
`keep_t[c] = exp(dt_t a)` of channel c's head and `u_t = dt_t x_t`:

    S_t = keep_t (columns) * S_{t-1} + B_t u_t^T
    y_t = S_t^T C_t

Everything that is one number a channel (`keep`, `u`, `y`) is a row along
the lanes, as the activations hold it; B and C are columns along the
sublanes, a pair a group, `[G N, 2]` a row: lane tile j reads the `N`
sublanes of its group, `tile_groups(tiles, G)[j]` (a static map; with
one group the same for every tile). The contraction with
C is a sublane sum (VPU adds and one 8-row reduce a tile). With the state a head as `[P, N]`
the same contraction would be a lane reduce a vreg, 1,024 of them a row.
Nothing here runs on the MXU: a float32 product costs it three to six
passes, more than the copy of the state that bounds the step form.

Both kernels read and write the layer's pool `[slots + 1, tiles, N, 128]`
through `input_output_aliases`; the pool's last row is where pad rows point
(`ops/linear_attention.py`). Both wrappers are jitted under the names the
device trace shows the kernels by (`_ssd_step_call`, `_ssd_chunk_call`).

`_ssd_step_call` (a decode substep, one token a row). Grid (rows,): a
row's whole state (4 MiB at 8,192 channels of 128) is one block, indexed by
the row's slot (scalar prefetch), so the pipeline copies the next row's
state in and the last row's out while this row's is updated. What bounds
it: the copy, 2 x 4 MiB a row at the HBM rate.

`_ssd_chunk_call` (a `mixed` step, runs of rows). Grid (blocks of `ROWS`
rows,), sequential. The rows are walked one at a time with the step's own
arithmetic, the current run's state in VMEM: a run's first row reads its
slot (or zeroes the state at position 0), its last row writes it, by
explicit copies, so a run of r rows moves its state once and not r times,
and a row's result is bit for bit what the step form gives from the same
state. Why not the block decomposition of state-space duality (`C B^T`
once, a decay triangle a head, two state products a chunk): at float32
(HIGHEST) its 64-row chunks cost the MXU more cycles a row than the VPU
walk does (docs/kernels.md has the count), and half a `mixed` step's rows
are decode rows, runs of one. What bounds it: the VPU walk (about six
operations a state vreg a row) and, for runs of a row or two, the two
copies of the state, which are not overlapped with it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 64
_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20
_F32 = jnp.float32
# A row's flags in the chunk form.
_START, _ZERO, _LAST = 1, 2, 4


def ssd_supported(x: jnp.ndarray, b: jnp.ndarray, pool: jnp.ndarray) -> bool:
    """A float32 pool of whole 128-lane tiles that hold the layer's
    channels, whole tiles a group of B and C, and whole sublane tiles of
    state channels and of rows."""
    t, h, p = x.shape
    tiles, n, lanes = pool.shape[1:]
    groups = _groups(b)
    return (pool.dtype == _F32 and lanes == _LANES and n % 8 == 0
            and tiles * lanes == h * p and b.shape[-1] == n and t % 8 == 0
            and tiles % groups == 0)


def _by_channel(x, dt, a):
    """(keep, u) [T, inner] float32: each channel's decay and `dt x`."""
    t, h, p = x.shape
    dt = dt.astype(_F32)
    keep = jnp.repeat(jnp.exp(dt * a.astype(_F32)), p, axis=-1)
    u = (dt[..., None] * x.astype(_F32)).reshape(t, h * p)
    return keep, u


def _groups(b) -> int:
    """`b` (or `c`) is [T, G, N], or [T, N] for one group."""
    return b.shape[1] if b.ndim == 3 else 1


def _columns(b, c):
    """([T, G N, 2], G): B and C as columns along the sublanes, group after
    group."""
    groups = _groups(b)
    cols = jnp.stack([b.astype(_F32), c.astype(_F32)], axis=-1)
    return cols.reshape(b.shape[0], groups * b.shape[-1], 2), groups


def tile_groups(tiles: int, groups: int):
    """The group of B and C each lane tile reads: a group's heads are
    contiguous, so its channels are `tiles / groups` tiles in a row."""
    return tuple(t // (tiles // groups) for t in range(tiles))


def _tile_columns(cols_ref, row, tiles: int, groups: int):
    """Per lane tile, (B column, C column) [N, 1] of the tile's group in
    row `row` of `cols_ref` [rows, G N, 2]: one pair a group, loaded once."""
    n = cols_ref.shape[1] // groups
    pairs = [(cols_ref[row, g * n:(g + 1) * n, 0:1],
              cols_ref[row, g * n:(g + 1) * n, 1:2]) for g in range(groups)]
    return [pairs[g] for g in tile_groups(tiles, groups)]


def _update(keep, u, b_c, c_c, state):
    """One tile: `keep`, `u` [1, 128], `b_c`, `c_c` [N, 1], `state`
    [N, 128] -> (y [1, 128], new state)."""
    new = keep * state + b_c * u
    return jnp.sum(c_c * new, axis=0, keepdims=True), new


# --- the step form -----------------------------------------------------------

def _ssd_step_kernel(slots_ref, keep_ref, u_ref, cols_ref, s_ref, y_ref,
                     s_out_ref, *, tiles: int, groups: int):
    del slots_ref
    for j, (b_c, c_c) in enumerate(_tile_columns(cols_ref, 0, tiles,
                                                 groups)):
        y, new = _update(keep_ref[0, j:j + 1, :], u_ref[0, j:j + 1, :], b_c,
                         c_c, s_ref[0, j])
        s_out_ref[0, j] = new
        y_ref[0, j:j + 1, :] = y


@jax.jit
def _ssd_step_call(x, dt, a, b, c, pool, slots):
    rows, h, p = x.shape
    tiles, n, lanes = pool.shape[1:]
    keep, u = (v.reshape(rows, tiles, lanes) for v in _by_channel(x, dt, a))
    cols, groups = _columns(b, c)
    state = pl.BlockSpec((1, tiles, n, lanes), lambda i, s: (s[i], 0, 0, 0))
    row = pl.BlockSpec((1, tiles, lanes), lambda i, s: (i, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_ssd_step_kernel, tiles=tiles, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, ),
            in_specs=[row, row,
                      pl.BlockSpec((1, groups * n, 2),
                                   lambda i, s: (i, 0, 0)),
                      state],
            out_specs=(row, state)),
        out_shape=(jax.ShapeDtypeStruct((rows, tiles, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the scalar-prefetch argument.
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(slots.astype(jnp.int32), keep, u, cols, pool)
    return y.reshape(rows, h, p), pool


def ssd_step(x, dt, a, b, c, pool, slots):
    """(y [B, H, P] float32, pool). Callers check `ssd_supported`."""
    return _ssd_step_call(x, dt, a, b, c, pool, slots)


# --- the chunk form ----------------------------------------------------------

def _ssd_chunk_kernel(slots_ref, flags_ref, keep_ref, u_ref, cols_ref,
                      pool_in, y_ref, pool_out, z_ref, sem, *, tiles: int,
                      groups: int, pad: int, rows: int):
    row0 = pl.program_id(0) * rows
    y_ref[...] = jnp.zeros_like(y_ref)

    def row(t, carry):
        slot, flags = slots_ref[row0 + t], flags_ref[row0 + t]

        @pl.when(slot < pad)
        def _live():
            @pl.when((flags & (_START | _ZERO)) == (_START | _ZERO))
            def _from_zero():
                z_ref[...] = jnp.zeros_like(z_ref)

            @pl.when((flags & (_START | _ZERO)) == _START)
            def _from_slot():
                read = pltpu.make_async_copy(pool_in.at[slot], z_ref, sem)
                read.start()
                read.wait()

            for j, (b_c, c_c) in enumerate(_tile_columns(cols_ref, t, tiles,
                                                         groups)):
                y, new = _update(keep_ref[t, j:j + 1, :],
                                 u_ref[t, j:j + 1, :], b_c, c_c, z_ref[j])
                z_ref[j] = new
                y_ref[t, j:j + 1, :] = y

            @pl.when((flags & _LAST) != 0)
            def _to_slot():
                write = pltpu.make_async_copy(z_ref, pool_out.at[slot], sem)
                write.start()
                write.wait()

        return carry

    lax.fori_loop(0, rows, row, 0)


@jax.jit
def _ssd_chunk_call(x, dt, a, b, c, pool, slots, positions, continues, last):
    t, h, p = x.shape
    tiles, n, lanes = pool.shape[1:]
    pad = pool.shape[0] - 1
    # Whole sublane tiles of rows a block, as many as divide the step.
    rows = next(r for r in range(min(t, ROWS), 0, -8) if t % r == 0)
    i32 = jnp.int32
    keep, u = (v.reshape(t, tiles, lanes) for v in _by_channel(x, dt, a))
    cols, groups = _columns(b, c)
    flags = ((~continues).astype(i32) * _START
             + (positions == 0).astype(i32) * _ZERO
             + last.astype(i32) * _LAST)
    # A row's channels as [tiles, lanes]: the walk indexes a row on the
    # leading axis (Mosaic loads no single sublane at a dynamic offset).
    block = pl.BlockSpec((rows, tiles, lanes), lambda i, *_: (i, 0, 0))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    y, pool = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, tiles=tiles, groups=groups,
                          pad=pad, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t // rows, ),
            in_specs=[block, block,
                      pl.BlockSpec((rows, groups * n, 2),
                                   lambda i, *_: (i, 0, 0)),
                      any_space],
            out_specs=(block, any_space),
            scratch_shapes=[pltpu.VMEM((tiles, n, lanes), _F32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=(jax.ShapeDtypeStruct((t, tiles, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the 2 scalar-prefetch arguments.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            has_side_effects=True, vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(slots.astype(i32), flags, keep, u, cols, pool)
    return y.reshape(t, h, p), pool


def ssd_chunk(x, dt, a, b, c, pool, slots, positions, continues, last):
    """(y [T, H, P] float32, pool) of a `mixed` step's rows; `continues`
    / `last` [T] as `ops/linear_attention.py` `run_structure` gives them.
    Callers check `ssd_supported`."""
    return _ssd_chunk_call(x, dt, a, b, c, pool, slots, positions,
                           continues, last)
