"""Pallas TPU kernels for latent (MLA) attention over the latent pool.

With the key/value up-projection absorbed into the query and the output
(`layers/mla_attention.py`), attention over a latent cache is multi-query
attention with ONE shared key a token: every one of the H query heads
scores against the same stored vector `[c_kv | k_rope | 0-pad]` (width
`Dk`, 640 for 512 + 64), and the value is the first `Dv` lanes of that
same vector (`c_kv`, 512). So there is one pool, no V pool and no head
axis, and a grid step is one row: all H heads against one stream of pages.

Two kernels, siblings of `paged_attention.py` (decode) and
`ragged_paged_attention.py` (the mixed step's fused write + walk), whose
page walk (double-buffered multi-page DMA groups, the last group
prefetching the next row's first), online softmax and slab
read-modify-write they keep:

- `mla_paged_attention`: the K-step decode programs' pool part. Returns
  the attended latent `[B, H, Dv]` and the per-head logsumexp, which
  `layers/mla_attention.py` merges with the staged tokens' part.
- `mla_ragged_paged_attention`: the `mixed` program. Writes each row's new
  latent vector into its slot (slab read-modify-write, as the ragged
  kernel), walks the prior context, and merges the row's own token from
  VMEM.

What differs from the per-head kernels: one DMA stream instead of two (the
value is a lane slice of the key already in VMEM: a token's 1,280 bytes
are read once, not twice), no head blocks (one latent serves every
head), the QK^T dot in the cache's own dtype when the
query has it (bf16 x bf16 accumulated in f32 is exact in the products),
and the scale applied to the f32 scores rather than to the query.

The jitted wrappers are named `_ragged_call_mla` / `_paged_attention_call_mla`
on purpose: a device trace names a kernel's operation after its wrapper, and
the benchmark's `attn_dev_share` counts `^%_(ragged|paged_attention)_call`, so
these count as attention there (a latent-attention model runs no other).

Numerics: f32 online softmax and f32 PV regardless of cache dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from intellillm_tpu.ops.pallas.paged_attention import _largest_divisor
from intellillm_tpu.ops.pallas.ragged_paged_attention import (
    _SMEM_TABLE_BYTES)

_NEG_INF = -1e30


def walk_extent(ctx, block_size: int, w: int, xp=np):
    """The latent walk's rule, for `_walk` (`xp=jnp`) and the host's
    counter: whole groups of `_largest_divisor(w, 16)` pages whatever the
    row holds, one at least. These are the fixed groups that
    `paged_attention.walk_extent` replaced in the per-head kernels; at
    1,280 B a token they cost this model little (PERF.md section 7).
    Returns (pages moved, tokens multiplied)."""
    group = _largest_divisor(w, 16) * block_size
    groups = xp.maximum((ctx + (group - 1)) // group, 1)
    return groups * (group // block_size), groups * group


def _group_copies(pool_ref, buf_ref, sem, tables_ref, b, g, buf, *,
                  pages_per_group, w_max):
    """The async copies moving page-group g of row b into VMEM buffer
    `buf` (identical descriptor lists at start and wait time)."""
    copies = []
    for j in range(pages_per_group):
        idx = jnp.minimum(g * pages_per_group + j, w_max - 1)
        page = tables_ref[b * w_max + idx]
        copies.append(pltpu.make_async_copy(
            pool_ref.at[page], buf_ref.at[buf, j], sem.at[buf]))
    return copies


def _walk(pool_ref, tables_ref, buf_idx_ref, init_ref, q_ref, kbuf, ksem,
          m_scr, l_scr, acc_scr, *, b, limit, batch_size, pages_per_group,
          block_size, scale, w_max, v_width):
    """Online softmax of row b's H heads over pool positions `< limit`,
    into the scratch accumulators. Returns the query block as loaded."""
    bk = pages_per_group * block_size
    num_groups = lax.div(walk_extent(limit, block_size, w_max, xp=jnp)[0],
                         pages_per_group)

    copies = functools.partial(_group_copies, pool_ref, kbuf, ksem,
                               tables_ref, pages_per_group=pages_per_group,
                               w_max=w_max)

    @pl.when(init_ref[0] == 1)
    def _first():
        for c in copies(b, 0, 0):
            c.start()
    init_ref[0] = 0
    start_buf = buf_idx_ref[0]
    has_next = b + 1 < batch_size

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                      # [H, Dk]
    h = q.shape[0]
    col_tok = lax.broadcasted_iota(jnp.int32, (h, bk), 1)

    def body(g, carry):
        buf = lax.rem(start_buf + g, 2)
        nxt = lax.rem(buf + 1, 2)

        @pl.when(g + 1 < num_groups)
        def _prefetch_own():
            for c in copies(b, g + 1, nxt):
                c.start()

        @pl.when((g + 1 == num_groups) & has_next)
        def _prefetch_successor():
            # In the ragged kernel this is issued before the successor's
            # own cache write: safe only because the successor's own
            # token is masked from its walk (`limit` = ctx - 1 there).
            for c in copies(b + 1, 0, nxt):
                c.start()

        for c in copies(b, g, buf):
            c.wait()

        k = kbuf[buf].reshape(bk, kbuf.shape[-1])        # [P*BS, Dk]
        if k.dtype == q.dtype:
            s = lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32)
        else:
            s = lax.dot_general(q.astype(jnp.float32),
                                k.astype(jnp.float32),
                                (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                    # [H, P*BS]
        mask = g * bk + col_tok < limit

        m_prev = m_scr[:, 0][:, None]
        m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=1,
                        keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Mask AFTER the exp (a fully-invalid group has m_new == s).
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_scr[:, 0][:, None] * alpha + jnp.sum(p, axis=1,
                                                       keepdims=True)
        # The value is the key's first Dv lanes: no second stream.
        v = k[:, :v_width].astype(jnp.float32)
        pv = lax.dot_general(p, v, (((1, ), (0, )), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    lax.fori_loop(0, num_groups, body, 0, unroll=False)
    buf_idx_ref[0] = lax.rem(start_buf + num_groups, 2)
    return q


def _decode_kernel(context_lens_ref, tables_ref, buf_idx_ref, init_ref,
                   q_ref, pool_ref, o_ref, lse_ref, kbuf, ksem, m_scr,
                   l_scr, acc_scr, **static):
    b = pl.program_id(0)
    _walk(pool_ref, tables_ref, buf_idx_ref, init_ref, q_ref, kbuf, ksem,
          m_scr, l_scr, acc_scr, b=b, limit=context_lens_ref[b], **static)
    l = l_scr[:, 0][:, None]
    m = m_scr[:, 0][:, None]
    o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _ragged_kernel(context_lens_ref, tables_ref, slots_ref, buf_idx_ref,
                   init_ref, q_ref, new_ref, pool_ref, o_ref, pool_out_ref,
                   kbuf, ksem, wbuf, wsem, m_scr, l_scr, acc_scr, *,
                   block_size, scale, v_width, **static):
    del pool_out_ref                      # in-place alias of pool_ref
    b = pl.program_id(0)
    ctx = context_lens_ref[b]
    slot = slots_ref[b]

    # 1. Write this row's latent vector before anything downstream can
    #    read it: read-modify-write of the slot's [1, BS, Dk] page slab
    #    (Mosaic refuses a one-row slice of a tiled memref). Serial within
    #    the sequential grid, so the next row of the same sequence reads
    #    this row's token. Pad rows (slot < 0) skip it.
    @pl.when(slot >= 0)
    def _write():
        page = lax.div(slot, block_size)
        off = lax.rem(slot, block_size)
        rd = pltpu.make_async_copy(pool_ref.at[page], wbuf, wsem)
        rd.start()
        rd.wait()
        is_row = lax.broadcasted_iota(jnp.int32, wbuf.shape, 1) == off
        # Select in f32: exact for bf16 / fp8, and avoids packed-dtype
        # selects Mosaic does not lower on v5e.
        new = new_ref[0].astype(jnp.float32)[:, None, :]
        wbuf[...] = jnp.where(is_row, new,
                              wbuf[...].astype(jnp.float32)
                              ).astype(wbuf.dtype)
        wr = pltpu.make_async_copy(wbuf, pool_ref.at[page], wsem)
        wr.start()
        wr.wait()

    # 2. The prior context only (pos < ctx - 1) from the pool ...
    q = _walk(pool_ref, tables_ref, buf_idx_ref, init_ref, q_ref, kbuf,
              ksem, m_scr, l_scr, acc_scr, b=b, limit=ctx - 1,
              block_size=block_size, scale=scale, v_width=v_width, **static)

    # 3. ... and the row's own token (pos = ctx - 1) from VMEM, already in
    #    the cache dtype, so it reads as a reference read of the
    #    just-written cache line would.
    k_self = new_ref[0].astype(jnp.float32)              # [1, Dk]
    s_self = jnp.sum(q.astype(jnp.float32) * k_self, axis=1,
                     keepdims=True) * scale              # [H, 1]
    valid = ctx > 0
    s_self = jnp.where(valid, s_self, _NEG_INF)
    m_prev = m_scr[:, 0][:, None]
    m_new = jnp.maximum(m_prev, s_self)
    alpha = jnp.exp(m_prev - m_new)
    p_self = jnp.where(valid, jnp.exp(s_self - m_new), 0.0)
    l = l_scr[:, 0][:, None] * alpha + p_self
    acc = acc_scr[...] * alpha + p_self * k_self[:, :v_width]
    o = acc / jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _scratch(ppg, bs, dk, h, v_width, dtype):
    return [
        pltpu.VMEM((2, ppg, 1, bs, dk), dtype),
        pltpu.SemaphoreType.DMA((2, )),
    ], [
        pltpu.VMEM((h, 128), jnp.float32),
        pltpu.VMEM((h, 128), jnp.float32),
        pltpu.VMEM((h, v_width), jnp.float32),
    ]


@functools.partial(jax.jit, static_argnames=("scale_static", "v_width"))
def _paged_attention_call_mla(q, pool, block_tables, context_lens, *,
                    scale_static: float, v_width: int):
    b, _, h, dk = q.shape
    _, _, bs, _ = pool.shape
    w = block_tables.shape[1]
    ppg = _largest_divisor(w, 16)
    bufs, accs = _scratch(ppg, bs, dk, h, v_width, pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, ),
        in_specs=[
            pl.BlockSpec((1, 1, h, dk), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, h, v_width), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, 1, h, 128), lambda b_, *_: (b_, 0, 0, 0)),
        ),
        scratch_shapes=bufs + accs,
    )
    kernel = functools.partial(
        _decode_kernel, batch_size=b, pages_per_group=ppg, block_size=bs,
        scale=scale_static, w_max=w, v_width=v_width)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, 1, h, v_width), q.dtype),
            jax.ShapeDtypeStruct((b, 1, h, 128), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", )),
    )(context_lens, block_tables.reshape(-1), jnp.zeros((1, ), jnp.int32),
      jnp.ones((1, ), jnp.int32), q, pool)
    return out[:, 0], lse[:, 0, :, 0]


def mla_paged_attention(
    q: jnp.ndarray,             # [B, H, Dk] absorbed query, zero-padded
    pool: jnp.ndarray,          # [NB, 1, BS, Dk] latent pool
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32 tokens in the pool (0 = pad row)
    scale: float,
    v_width: int,
):
    """Decode-phase latent attention. Returns (out [B, H, v_width],
    logsumexp [B, H])."""
    return _paged_attention_call_mla(q[:, None], pool, block_tables.astype(jnp.int32),
                           context_lens.astype(jnp.int32),
                           scale_static=float(scale), v_width=v_width)


@functools.partial(jax.jit, static_argnames=("scale_static", "v_width"))
def _ragged_call_mla(q, new, pool, slot_mapping, block_tables,
                     context_lens, *, scale_static: float, v_width: int):
    b, _, h, dk = q.shape
    _, _, bs, _ = pool.shape
    w = block_tables.shape[1]
    ppg = _largest_divisor(w, 16)
    bufs, accs = _scratch(ppg, bs, dk, h, v_width, pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, ),
        in_specs=[
            pl.BlockSpec((1, 1, h, dk), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, h, v_width), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=bufs + [
            pltpu.VMEM((1, bs, dk), pool.dtype),
            pltpu.SemaphoreType.DMA,
        ] + accs,
    )
    kernel = functools.partial(
        _ragged_kernel, batch_size=b, pages_per_group=ppg, block_size=bs,
        scale=scale_static, w_max=w, v_width=v_width)
    out, pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, 1, h, v_width), q.dtype),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ),
        # Operand indices COUNT the 5 scalar-prefetch args: the pool is
        # operand 7, aliased onto output 1 for the in-place update.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ), has_side_effects=True),
    )(context_lens, block_tables.reshape(-1), slot_mapping,
      jnp.zeros((1, ), jnp.int32), jnp.ones((1, ), jnp.int32), q, new, pool)
    return out[:, 0], pool


def mla_ragged_paged_attention(
    q: jnp.ndarray,             # [B, H, Dk] absorbed query, zero-padded
    new: jnp.ndarray,           # [B, Dk] new latent rows — MUST be cache dtype
    pool: jnp.ndarray,          # [NB, 1, BS, Dk]
    slot_mapping: jnp.ndarray,  # [B] i32 flat physical slots, -1 = pad
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32, counting the new token
    scale: float,
    v_width: int,
):
    """Fused latent-cache write + causal attention over the flat mixed
    batch. Returns (out [B, H, v_width], pool) with the pool updated in
    place (donated/aliased)."""
    if new.dtype != pool.dtype:
        raise ValueError(
            "mla_ragged_paged_attention requires the new rows pre-cast to "
            f"the cache dtype (got {new.dtype} vs {pool.dtype}): the "
            "self-token must see post-cast values and DMAs cannot cast")
    b = q.shape[0]
    # Pad rows arrive as -1 or as the scatter path's out-of-bounds
    # sentinel; the kernel knows one spelling and never DMAs past the pool.
    slots = slot_mapping.astype(jnp.int32)
    num_slots = pool.shape[0] * pool.shape[2]
    slots = jnp.where((slots >= 0) & (slots < num_slots), slots, -1)
    # Block tables ride in SMEM: launch over row chunks that fit (as the
    # ragged kernel does); the pool is aliased through, so chunk i's
    # writes precede chunk i+1's reads.
    w = block_tables.shape[1]
    rows = _largest_divisor(b, max(_SMEM_TABLE_BYTES // (4 * w), 1))
    ctx = context_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    outs = []
    for r0 in range(0, b, rows):
        rs = slice(r0, r0 + rows)
        out, pool = _ragged_call_mla(
            q[rs, None], new[rs, None], pool, slots[rs], tables[rs],
            ctx[rs], scale_static=float(scale), v_width=v_width)
        outs.append(out)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out, pool
