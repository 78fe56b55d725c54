"""The selective state-space scan of Mamba-1 (a decay a (channel, state)
pair, `dt` a channel, one B and one C a token) on the state pool, in
place: a step form and a chunk form.

A sequence's state is one float32 matrix `[N, inner]` (`N` = 16 state
channels on the sublanes, the layer's `inner` channels along the lanes,
cut into `tiles` lane tiles of 128: the pool is `[slots + 1, tiles, N,
128]`). With `A` `[N, inner]` < 0, `dt_t`, `x_t` rows of `inner`:

    S_t = exp(dt_t (cols) * A) * S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t

Neither kernel of `ops/pallas/ssd.py` computes this: there the decay is
one number a head, a row along the lanes made outside the kernel; here
it is a matrix as large as the state, which is made where it is used
(`exp` on the EUP, of a product of a row and `A`'s tile) and never
stored. `dt` and `u = dt x` are rows along the lanes, as the activations
hold them; B and C are columns along the sublanes, `[N, 2]` a row. The
contraction with C is a sublane sum (two vregs a tile).

Both kernels read and write the layer's pool through
`input_output_aliases`; the pool's last row is where pad rows point
(`ops/linear_attention.py`). Both wrappers are jitted under the names
the device trace shows the kernels by (`_sscan_step_call`,
`_sscan_chunk_call`).

`_sscan_step_call` (a decode substep, one token a row). Grid (rows,): a
row's whole state (320 KiB at 5,120 channels) is one block, indexed by the
row's slot (scalar prefetch), so the pipeline copies the next row's state
in and the last row's out while this row's is updated. What bounds it:
the copy, 2 x 320 KiB a row, and at this size the grid's step (~0.35 us)
beside it.

`_sscan_chunk_call` (a `mixed` step, runs of rows). Grid (blocks of `ROWS`
rows,), sequential; the rows walked one at a time with the step's own
arithmetic, the current run's state in VMEM (320 KiB): a run's first row
reads its slot (or zeroes the state at position 0), its last row writes
it, by explicit copies, so a run of r rows moves its state once, and a
row's result is bit for bit what the step form gives from the same state.
What bounds it: the VPU / EUP walk (one `exp` and about seven operations
a state vreg a row).
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
_F32 = jnp.float32
# A row's flags in the chunk form.
_START, _ZERO, _LAST = 1, 2, 4


def sscan_supported(x: jnp.ndarray, pool: jnp.ndarray) -> bool:
    """A float32 pool of whole 128-lane tiles that hold the layer's
    channels, whole sublane tiles of state channels and of rows."""
    t, inner = x.shape
    tiles, n, lanes = pool.shape[1:]
    return (pool.dtype == _F32 and lanes == _LANES and n % 8 == 0
            and tiles * lanes == inner and t % 8 == 0)


def _rows(x, dt, tiles: int):
    """(dt, u = dt x) `[T, tiles, 128]` float32."""
    dt = dt.astype(_F32)
    u = dt * x.astype(_F32)
    return (v.reshape(v.shape[0], tiles, _LANES) for v in (dt, u))


def _columns(b, c):
    """`[T, N, 2]`: B and C as columns along the sublanes."""
    return jnp.stack([b.astype(_F32), c.astype(_F32)], axis=-1)


def _by_tile(a, tiles: int):
    """`A` `[N, inner]` as the pool holds a state: `[tiles, N, 128]`."""
    n = a.shape[0]
    return a.astype(_F32).reshape(n, tiles, _LANES).swapaxes(0, 1)


def _update(dt, u, a, b_c, c_c, state):
    """One tile: `dt`, `u` [1, 128], `a`, `state` [N, 128], `b_c`, `c_c`
    [N, 1] -> (y [1, 128], new state)."""
    new = jnp.exp(dt * a) * state + b_c * u
    return jnp.sum(c_c * new, axis=0, keepdims=True), new


# --- the step form -----------------------------------------------------------

def _sscan_step_kernel(slots_ref, dt_ref, u_ref, cols_ref, a_ref, s_ref,
                       y_ref, s_out_ref, *, tiles: int):
    del slots_ref
    b_c, c_c = cols_ref[0, :, 0:1], cols_ref[0, :, 1:2]
    for j in range(tiles):
        y, new = _update(dt_ref[0, j:j + 1, :], u_ref[0, j:j + 1, :],
                         a_ref[j], b_c, c_c, s_ref[0, j])
        s_out_ref[0, j] = new
        y_ref[0, j:j + 1, :] = y


@jax.jit
def _sscan_step_call(x, dt, a, b, c, pool, slots):
    rows, inner = x.shape
    tiles, n, lanes = pool.shape[1:]
    dt, u = _rows(x, dt, tiles)
    state = pl.BlockSpec((1, tiles, n, lanes), lambda i, s: (s[i], 0, 0, 0))
    row = pl.BlockSpec((1, tiles, lanes), lambda i, s: (i, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_sscan_step_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, ),
            in_specs=[row, row,
                      pl.BlockSpec((1, n, 2), lambda i, s: (i, 0, 0)),
                      pl.BlockSpec((tiles, n, lanes),
                                   lambda i, s: (0, 0, 0)),
                      state],
            out_specs=(row, state)),
        out_shape=(jax.ShapeDtypeStruct((rows, tiles, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the scalar-prefetch argument.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", )),
    )(slots.astype(jnp.int32), dt, u, _columns(b, c), _by_tile(a, tiles),
      pool)
    return y.reshape(rows, inner), pool


def sscan_step(x, dt, a, b, c, pool, slots):
    """(y [B, inner] float32, pool). Callers check `sscan_supported`."""
    return _sscan_step_call(x, dt, a, b, c, pool, slots)


# --- the chunk form ----------------------------------------------------------

def _sscan_chunk_kernel(slots_ref, flags_ref, dt_ref, u_ref, cols_ref, a_ref,
                        pool_in, y_ref, pool_out, z_ref, sem, *, tiles: int,
                        pad: int, rows: int):
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

            b_c, c_c = cols_ref[t, :, 0:1], cols_ref[t, :, 1:2]
            for j in range(tiles):
                y, new = _update(dt_ref[t, j:j + 1, :], u_ref[t, j:j + 1, :],
                                 a_ref[j], b_c, c_c, z_ref[j])
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
def _sscan_chunk_call(x, dt, a, b, c, pool, slots, positions, continues,
                      last):
    t, inner = x.shape
    tiles, n, lanes = pool.shape[1:]
    pad = pool.shape[0] - 1
    # Whole sublane tiles of rows a block, as many as divide the step.
    rows = next(r for r in range(min(t, ROWS), 0, -8) if t % r == 0)
    i32 = jnp.int32
    dt, u = _rows(x, dt, tiles)
    flags = ((~continues).astype(i32) * _START
             + (positions == 0).astype(i32) * _ZERO
             + last.astype(i32) * _LAST)
    # A row's channels as [tiles, lanes]: the walk indexes a row on the
    # leading axis (Mosaic loads no single sublane at a dynamic offset).
    block = pl.BlockSpec((rows, tiles, lanes), lambda i, *_: (i, 0, 0))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    y, pool = pl.pallas_call(
        functools.partial(_sscan_chunk_kernel, tiles=tiles, pad=pad,
                          rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t // rows, ),
            in_specs=[block, block,
                      pl.BlockSpec((rows, n, 2), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec((tiles, n, lanes),
                                   lambda i, *_: (0, 0, 0)),
                      any_space],
            out_specs=(block, any_space),
            scratch_shapes=[pltpu.VMEM((tiles, n, lanes), _F32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=(jax.ShapeDtypeStruct((t, tiles, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the 2 scalar-prefetch arguments.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ), has_side_effects=True),
    )(slots.astype(i32), flags, dt, u, _columns(b, c), _by_tile(a, tiles),
      pool)
    return y.reshape(t, inner), pool


def sscan_chunk(x, dt, a, b, c, pool, slots, positions, continues, last):
    """(y [T, inner] float32, pool) of a `mixed` step's rows; `continues`
    / `last` [T] as `ops/linear_attention.py` `run_structure` gives them.
    Callers check `sscan_supported`."""
    return _sscan_chunk_call(x, dt, a, b, c, pool, slots, positions,
                             continues, last)
