"""The gated delta rule with a per-channel decay (Kimi Delta Attention) on
the state pool, in place: a step form and a chunk form.

Per head, `S` float32 `[d_k, d_v]` (key channels on sublanes, value
channels on lanes), `a_t = exp(g_t)`:

    S'  = diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Both kernels read and write the layer's pool `[slots + 1, H, d_k, d_v]`
through `input_output_aliases`; the pool's last row is where pad rows point
(`ops/linear_attention.py`). Both wrappers are jitted under the names the
device trace shows the kernels by (`_kda_step_call`, `_kda_chunk_call`).

`_kda_step_call` (a decode substep, one token a row). Grid (rows,): a
row's whole state (`[H, d_k, d_v]`, 4 MiB at 64 heads of 128) is one
block, indexed by the row's slot (scalar prefetch), so the pipeline copies
the next row's state in and the last row's out while this row's is
updated. The update needs `k`, `q`, `a` and `b k` along the SUBLANES of a
head's tile (they scale and contract its key channels), so the wrapper
hands them transposed, `[rows, 4, d_k, H]`: a head's vector is one lane of
the block, broadcast along lanes where it is used. The two contractions
over key channels are sublane sums (VPU adds and one 8-row reduce), not
MXU products: float32 on the MXU costs three to six passes of a 128 x 128
weight load a head, more than the state's own copy. What bounds it: the
copy, 2 x 4 MiB a row at the HBM rate.

`_kda_chunk_call` (a `mixed` step, runs of rows). The rows are cut into
aligned chunks of `CHUNK` rows; the grid is (head groups, chunks), chunks
innermost and sequential, the current run's state carried across chunks in
VMEM. A chunk may hold rows of several runs ("segments"): the kernel loops
over the segments a chunk touches (scalar-prefetched: first and last row,
slot, whether it starts at position 0), masks the rows of the others, and
for each one computes the chunk in the WY form. With `G` the inclusive
cumulative log decay over the segment's rows in the chunk, `k+ = k e^G`,
`k- = k e^-G`, `Z0` the state the segment enters the chunk with:

    u_t = b_t v_t - (b_t k+_t) Z0 - sum_{i<t} (b_t k+_t . k-_i) u_i
    O   = (q e^G) Z0 + tril(q+ k-^T) U
    S   = diag(e^G_last) Z0 + (k e^(G_last - G))^T U

`u` is solved row by row (forward substitution: a product form of the
inverse cancels catastrophically where keys repeat with b near 2), on the
VPU for every head of the group at once. `e^-G` alone would overflow where
a channel decays fast (0.02 a token is e^-62 over a chunk), so the two
factors of a pair (t, i) are taken relative to the chunk's mid decay
`r = (G_first + G_last) / 2`: `e^(G_t - r)` and `e^(r - G_i)` stay inside
float32 while the chunk's whole decay is above e^-170 a channel, which the
wrapper guarantees by flooring `g` at `LOG_DECAY_FLOOR` a token (a channel
that keeps under e^-11 of itself a token). That is why a chunk is 16 rows
and not 64: four times the rows need a second level of sub-chunks. A
segment's state is read from its slot where it starts in the chunk (or
zeroed at position 0) and written where it ends, by explicit copies. What
bounds it: the float32 MXU passes of five 128-deep products a head and
chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16
LOG_DECAY_FLOOR = -11.0
_HEAD_GROUP = 16
_VMEM_LIMIT_BYTES = 64 << 20
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def kda_supported(q: jnp.ndarray, pool: jnp.ndarray, rows: int = 1) -> bool:
    """Whole 128-lane tiles of key and value channels, a float32 pool,
    and (the chunk form) whole chunks of rows."""
    t, _, d = q.shape
    return (d % 128 == 0 and pool.shape[-1] % 128 == 0
            and pool.shape[-2] == d and pool.dtype == _F32
            and t % rows == 0)


# --- the step form -----------------------------------------------------------

def _kda_step_kernel(slots_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref, *,
                     heads: int):
    del slots_ref
    for h in range(heads):
        k_c, q_c, a_c, kb_c = (cols_ref[0, i, :, h:h + 1] for i in range(4))
        decayed = a_c * s_ref[0, h]
        u = v_ref[0, h:h + 1, :] - jnp.sum(k_c * decayed, axis=0,
                                           keepdims=True)
        new = decayed + kb_c * u
        s_out_ref[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(q_c * new, axis=0, keepdims=True)


@jax.jit
def _kda_step_call(q, k, v, g, beta, pool, slots):
    b, h, d = q.shape
    dv = pool.shape[-1]
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    cols = jnp.stack([k, q, jnp.exp(g.astype(_F32)),
                      beta.astype(_F32)[..., None] * k], axis=1)
    cols = jnp.swapaxes(cols, 2, 3)                        # [B, 4, d, H]
    state = pl.BlockSpec((1, h, d, dv), lambda i, s: (s[i], 0, 0, 0))
    rows = pl.BlockSpec((1, h, dv), lambda i, s: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, ),
            in_specs=[pl.BlockSpec((1, 4, d, h), lambda i, s: (i, 0, 0, 0)),
                      rows, state],
            out_specs=(rows, state)),
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the scalar-prefetch argument.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(slots.astype(jnp.int32), cols, v, pool)


def kda_step(q, k, v, g, beta, pool, slots):
    """(o [B, H, d_v] float32, pool). Callers check `kda_supported`."""
    return _kda_step_call(q, k, v, g, beta, pool, slots)


# --- the chunk form ----------------------------------------------------------

def _nt(a, b):
    """a [M, K] . b [N, K]^T."""
    return lax.dot_general(a, b, (((1, ), (1, )), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _tn(a, b):
    """a [K, M]^T . b [K, N]."""
    return lax.dot_general(a, b, (((0, ), (0, )), ((), ())),
                           precision=_HIGHEST, preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=_F32)


def _kda_chunk_kernel(seg_start, seg_end, seg_slot, seg_zero, blk_first,
                      blk_last, q_ref, k_ref, kb_ref, vb_ref, g_ref, pool_in,
                      o_ref, pool_out, z_ref, sem, *, group: int, pad: int):
    gi, bi = pl.program_id(0), pl.program_id(1)
    c = CHUNK
    row0 = bi * c
    o_ref[...] = jnp.zeros_like(o_ref)
    rows = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    r_i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    c_i = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower_incl, upper_strict = r_i >= c_i, r_i < c_i
    first_row = (lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0).astype(_F32)
    heads = pl.ds(gi * group, group)

    def segment(s, carry):
        lo, hi, slot = seg_start[s], seg_end[s], seg_slot[s]

        @pl.when(slot < pad)
        def _run():
            @pl.when((lo >= row0) & (seg_zero[s] != 0))
            def _from_zero():
                z_ref[...] = jnp.zeros_like(z_ref)

            @pl.when((lo >= row0) & (seg_zero[s] == 0))
            def _from_slot():
                read = pltpu.make_async_copy(pool_in.at[slot, heads], z_ref,
                                             sem)
                read.start()
                read.wait()

            m = ((rows + row0 >= lo) & (rows + row0 < hi)).astype(_F32)

            def head(j, carry):
                z0 = z_ref[j]
                big_g = _nn(lower_incl.astype(_F32), g_ref[j] * m)  # [C, dk]
                g_last = big_g[c - 1:c]
                mid = 0.5 * (big_g[0:1] + g_last)
                e_abs = jnp.exp(big_g)
                e_rel = jnp.exp(big_g - mid)
                k = k_ref[j]
                kb, vb, q = kb_ref[j] * m, vb_ref[j] * m, q_ref[j]
                k_minus = k * jnp.exp(mid - big_g)
                # a_t[i, t] = k-_i . (b k+)_t for i < t. The pairs the
                # masks drop are the ones whose factor is a growth, not a
                # decay, and may have overflowed: selected away, never
                # multiplied by zero.
                a_t = jnp.where(upper_strict, _nt(k_minus, kb * e_rel), 0.0)
                p = jnp.where(lower_incl, _nt(q * e_rel, k_minus), 0.0)
                both = _nn(jnp.concatenate([kb * e_abs, q * e_abs], axis=0),
                           z0)                                  # [2C, dv]
                rhs = vb - both[:c]
                u = jnp.zeros_like(rhs)
                for t in range(c):
                    u_t = rhs[t:t + 1] - jnp.sum(a_t[:, t:t + 1] * u, axis=0,
                                                 keepdims=True)
                    u = jnp.where(rows == t, u_t, u)
                o_ref[j] += (both[c:] + _nn(p, u)) * m
                # diag(e^G_last) along the state's sublanes: the row as
                # the one non-zero row of an [8, dk] operand, contracted
                # against ones.
                keep = _tn(first_row * jnp.exp(g_last),
                           jnp.ones((8, z0.shape[1]), _F32))
                z_ref[j] = keep * z0 + _tn(k * jnp.exp(g_last - big_g), u)
                return carry

            lax.fori_loop(0, group, head, 0)

            @pl.when(hi <= row0 + c)
            def _to_slot():
                write = pltpu.make_async_copy(z_ref, pool_out.at[slot, heads],
                                              sem)
                write.start()
                write.wait()

        return carry

    lax.fori_loop(blk_first[bi], blk_last[bi] + 1, segment, 0)


@jax.jit
def _kda_chunk_call(q, k, v, g, beta, pool, slots, positions, continues):
    t, h, d = q.shape
    dv = pool.shape[-1]
    pad = pool.shape[0] - 1
    group = next(n for n in range(min(h, _HEAD_GROUP), 0, -1) if h % n == 0)
    i32 = jnp.int32
    # Segments: runs of rows, in row order; entries past the last repeat
    # the step's end and a pad slot.
    starts = ~continues
    seg_of_row = jnp.cumsum(starts.astype(i32)) - 1
    seg_start = jnp.nonzero(starts, size=t, fill_value=t)[0].astype(i32)
    seg_end = jnp.concatenate([seg_start[1:], jnp.full((1, ), t, i32)])
    first = jnp.minimum(seg_start, t - 1)
    seg_slot = jnp.where(seg_start < t, slots.astype(i32)[first], pad)
    seg_zero = (positions[first] == 0).astype(i32)

    def by_head(x):
        return jnp.swapaxes(x.astype(_F32), 0, 1)              # [H, T, d]

    b = beta.astype(_F32)[..., None]
    k = k.astype(_F32)
    block = pl.BlockSpec((group, CHUNK, d), lambda gi, bi, *_: (gi, bi, 0))
    block_v = pl.BlockSpec((group, CHUNK, dv), lambda gi, bi, *_: (gi, bi, 0))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    o, pool = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, group=group, pad=pad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(h // group, t // CHUNK),
            in_specs=[block, block, block, block_v, block, any_space],
            out_specs=(block_v, any_space),
            scratch_shapes=[pltpu.VMEM((group, d, dv), _F32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=(jax.ShapeDtypeStruct((h, t, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # Operand indices count the 6 scalar-prefetch arguments.
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True, vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(seg_start, seg_end, seg_slot, seg_zero, seg_of_row[::CHUNK],
      seg_of_row[CHUNK - 1::CHUNK], by_head(q), by_head(k), by_head(b * k),
      by_head(b * v.astype(_F32)),
      by_head(jnp.maximum(g.astype(_F32), LOG_DECAY_FLOOR)), pool)
    return jnp.swapaxes(o, 0, 1), pool


def kda_chunk(q, k, v, g, beta, pool, slots, positions, continues):
    """(o [T, H, d_v] float32, pool) of a `mixed` step's rows;
    `continues` [T]: a row is the token after the row before it, of the
    same sequence. Callers check `kda_supported(q, pool, CHUNK)`."""
    return _kda_chunk_call(q, k, v, g, beta, pool, slots, positions,
                           continues)
