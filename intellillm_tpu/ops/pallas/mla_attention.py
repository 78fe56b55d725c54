"""Pallas TPU kernels for latent (MLA) attention over the latent pool.

With the key/value up-projection absorbed into the query and the output
(`layers/mla_attention.py`), attention over a latent cache is multi-query
attention with ONE shared key a token: every one of the H query heads
scores against the same stored vector `[c_kv | k_rope | 0-pad]` (width
`Dk`, 640 for 512 + 64), and the value is the first `Dv` lanes of that
same vector (`c_kv`, 512). So there is one pool, no V pool and no head
axis: all H heads of a row against one stream of pages.

Two kernels, siblings of `paged_attention.py` (decode) and
`ragged_paged_attention.py` (the mixed step's fused write + walk):

- `mla_paged_attention`: the K-step decode programs' pool part. Returns
  the attended latent `[B, H, Dv]` and the per-head logsumexp, which
  `layers/mla_attention.py` merges with the staged tokens' part.
- `mla_ragged_paged_attention`: the `mixed` program. Writes each row's new
  latent vector into its slot (slab read-modify-write, as the ragged
  kernel; the read is skipped where the buffer holds the page because the
  row before wrote to it too, a chunk's next token), walks the prior
  context, and merges the row's own token from VMEM.

The page walk is the per-head kernels': `paged_attention._page_walker`
over ONE pool with a head block of 1 (`held_back` 0 in decode, 1 in
`mixed`), under the one rule `walk_extent` that the host's counter reads
too. A row moves the ceil(ctx / block_size) pages it holds, none for a pad
row; its full groups are static pieces, its tail's copies go out and are
waited for a sub-group a loop turn, and the tail is multiplied as ONE
piece, the smallest of a few sizes that holds it (`_switch`); a walk's
last group starts the next row's first, so the DMA pipeline never drains.
A grid step owns `_ROWS_PER_STEP` rows, walked one after the other, in
both kernels: rows run in batch order within a step as across the
sequential grid, so the `mixed` kernel's slab write still precedes every
later row's read of the same sequence. Until PR 46 the walk was a copy of
the fixed 16-page groups the per-head kernels gave up at PR 27 (walked /
context 1.35 in the serving cells).

What the chip said while this was rebuilt (v5e, 128 rows x 32 and 64
heads, `decode-closed` contexts of 16-976 tokens under 64-block tables;
`benchmarks/kernels/benchmark_paged_walk.py --latent-heads`, PERF.md
section 6, PR 46): a piece costs ~0.45 us whatever it holds plus its
bytes at about the HBM rate; the dots are nearly free (multiplying pages a
row does not hold costs ~0.2 ns a key, and a bf16 PV dot measured no
faster than the f32 one), so 64 heads cost what 32 do; the exact walk at
16 pages a group was SLOWER than the fixed groups (its tail loops, the
`_switch` tree and four copies of the update's body cost more than the
bytes and keys it saves), and 8 rows a grid step are worth 2 %. What pays
is fewer pieces: a group is up to 64 pages (`_GROUP_PAGES`), so a row of
the serving cells is one piece, and the tail's sizes are few. The value
buffer starts out zeroed (`_zero_pages`): a page that is not moved leaves
in VMEM what was there, the value is the key's own lanes, and 0 x NaN is
NaN in the PV dot.

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from intellillm_tpu.ops.pallas.paged_attention import (_ROWS_PER_STEP,
                                                       _largest_divisor,
                                                       _page_walker,
                                                       _zero_pages)
from intellillm_tpu.ops.pallas.ragged_paged_attention import (
    _SMEM_TABLE_BYTES)

_NEG_INF = -1e30
# Pages of one DMA group, a divisor of the table's width: a row is best ONE
# piece (every piece pays the QK -> softmax -> PV chain's latency and its
# conditionals once, ~0.45 us on v5e, whatever it holds), and at 20 KiB a
# page 64 pages are 2.6 MB of double buffer and 2 MB of f32 values.
_GROUP_PAGES = 64
# The sizes (pages) a row's tail is multiplied at, below a whole group:
# the smallest that holds it. Few, because every size is a copy of the
# update's body and a level of `_switch`, and multiplying pages the row
# does not hold costs little (~0.2 ns a key). The decode kernel's rows hold
# their whole contexts (16-61 pages in the serving cells); most of the
# `mixed` kernel's are prefill-chunk rows that hold a few.
_DECODE_PIECE_PAGES = (16, 32)
_MIXED_PIECE_PAGES = (4, 16)


def _row_walk(walk, start_first_group, state_ref, q, kbuf, m_scr, l_scr,
              acc_scr, *, b, limit, batch_size, pages_per_group, piece_pages,
              block_size, scale, v_width):
    """Online softmax of row b's H heads (`q` [H, Dk]) over pool positions
    `< limit`, into the scratch accumulators: `walk` (`_page_walker`)
    moves the pages the row holds and hands each piece to `update`."""
    ppg, bs = pages_per_group, block_size
    h = q.shape[0]

    def start_successor(buf):
        # In the ragged kernel this is issued before the successor's own
        # cache write: safe only because the successor's own token is
        # held back from its walk (`limit` = ctx - 1 there) and merged
        # from VMEM.
        @pl.when(b + 1 < batch_size)
        def _():
            start_first_group(b + 1, 0, buf)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(g, buf, npg: int):
        """Folds the first `npg` pages of group g, in buffer `buf`, into
        the accumulators: the pages waited for, and up to the piece's
        size what an earlier walk left there (finite, and masked)."""
        keys = npg * bs
        k = kbuf[buf, pl.ds(0, npg)].reshape(keys, kbuf.shape[-1])
        if k.dtype == q.dtype:
            s = lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32)
        else:
            s = lax.dot_general(q.astype(jnp.float32),
                                k.astype(jnp.float32),
                                (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                    # [H, keys]
        mask = (g * (ppg * bs)
                + lax.broadcasted_iota(jnp.int32, (h, keys), 1)) < limit

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

    # The tail's piece sizes: those given below a whole group, then it.
    state_ref[0] = walk(
        b, 0, state_ref[0], start_successor, update,
        tuple(n for n in piece_pages if n < ppg) + (ppg, ))


def _walker(context_lens_ref, tables_ref, pool_ref, kbuf, ksem, state_ref, *,
            held_back, batch_size, pages_per_group, block_size, w_max):
    """`_page_walker` over the one latent pool (a head block of 1), and
    what the launch's first grid step lays out once: a buffer that is
    finite (the value is the key's own lanes, and a page the walk does
    not move leaves in VMEM what was there: 0 x NaN is NaN in the PV
    dot), and the first row's first group on its way."""
    _, start_first_group, walk = _page_walker(
        context_lens_ref, tables_ref, ((pool_ref, kbuf, ksem), ),
        held_back=held_back, batch_size=batch_size, heads_per_block=1,
        pages_per_group=pages_per_group, block_size=block_size, w_max=w_max)

    @pl.when(pl.program_id(0) == 0)
    def _first():
        _zero_pages(kbuf)
        state_ref[0] = 0
        state_ref[1] = -1       # the write buffer holds no page yet
        start_first_group(0, 0, 0)

    return start_first_group, walk


def _decode_kernel(context_lens_ref, tables_ref, q_ref, pool_ref, o_ref,
                   lse_ref, kbuf, ksem, state_ref, m_scr, l_scr, acc_scr, *,
                   batch_size, pages_per_group, block_size, w_max, **static):
    rows_per_step = q_ref.shape[0]
    row0 = pl.program_id(0) * rows_per_step
    start_first_group, walk = _walker(
        context_lens_ref, tables_ref, pool_ref, kbuf, ksem, state_ref,
        held_back=0, batch_size=batch_size, pages_per_group=pages_per_group,
        block_size=block_size, w_max=w_max)

    def one_row(t, carry):
        b = row0 + t
        _row_walk(walk, start_first_group, state_ref, q_ref[t, 0], kbuf,
                  m_scr, l_scr, acc_scr, b=b, limit=context_lens_ref[b],
                  batch_size=batch_size, pages_per_group=pages_per_group,
                  piece_pages=_DECODE_PIECE_PAGES, block_size=block_size,
                  **static)
        l = l_scr[:, 0][:, None]
        m = m_scr[:, 0][:, None]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[t, 0] = o.astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                        _NEG_INF)
        lse_ref[t, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])
        return carry

    # The rows of a grid step share the step's fixed cost.
    lax.fori_loop(0, rows_per_step, one_row, 0)


def _ragged_kernel(context_lens_ref, tables_ref, slots_ref, q_ref, new_ref,
                   pool_ref, o_ref, pool_out_ref, kbuf, ksem, wbuf, wsem,
                   state_ref, m_scr, l_scr, acc_scr, *, batch_size,
                   pages_per_group, block_size, scale, w_max, v_width):
    del pool_out_ref                      # in-place alias of pool_ref
    rows_per_step = q_ref.shape[0]
    row0 = pl.program_id(0) * rows_per_step
    start_first_group, walk = _walker(
        context_lens_ref, tables_ref, pool_ref, kbuf, ksem, state_ref,
        held_back=1, batch_size=batch_size, pages_per_group=pages_per_group,
        block_size=block_size, w_max=w_max)

    def one_row(t, carry):
        b = row0 + t
        ctx = context_lens_ref[b]
        slot = slots_ref[b]

        # 1. Write this row's latent vector before anything downstream
        #    can read it: read-modify-write of the slot's [1, BS, Dk] page
        #    slab (Mosaic refuses a one-row slice of a tiled memref).
        #    Rows run one after the other, within a grid step and across
        #    the sequential grid, so the next row of the same sequence
        #    reads this row's token. Pad rows (slot < 0) skip it.
        @pl.when(slot >= 0)
        def _write():
            page = lax.div(slot, block_size)
            off = lax.rem(slot, block_size)

            # Unless the buffer holds the page as the pool has it: the
            # last write went to it too (a chunk's next token).
            @pl.when(state_ref[1] != page)
            def _fetch():
                rd = pltpu.make_async_copy(pool_ref.at[page], wbuf, wsem)
                rd.start()
                rd.wait()
            state_ref[1] = page
            is_row = lax.broadcasted_iota(jnp.int32, wbuf.shape, 1) == off
            # Select in f32: exact for bf16 / fp8, and avoids packed-dtype
            # selects Mosaic does not lower on v5e.
            new = new_ref[t].astype(jnp.float32)[:, None, :]
            wbuf[...] = jnp.where(is_row, new,
                                  wbuf[...].astype(jnp.float32)
                                  ).astype(wbuf.dtype)
            wr = pltpu.make_async_copy(wbuf, pool_ref.at[page], wsem)
            wr.start()
            wr.wait()

        # 2. The prior context only (pos < ctx - 1) from the pool ...
        q = q_ref[t, 0]                                  # [H, Dk]
        _row_walk(walk, start_first_group, state_ref, q, kbuf, m_scr,
                  l_scr, acc_scr, b=b, limit=ctx - 1, batch_size=batch_size,
                  pages_per_group=pages_per_group,
                  piece_pages=_MIXED_PIECE_PAGES, block_size=block_size,
                  scale=scale, v_width=v_width)

        # 3. ... and the row's own token (pos = ctx - 1) from VMEM,
        #    already in the cache dtype, so it reads as a reference read
        #    of the just-written cache line would.
        k_self = new_ref[t].astype(jnp.float32)          # [1, Dk]
        s_self = jnp.sum(q.astype(jnp.float32) * k_self, axis=1,
                         keepdims=True) * scale          # [H, 1]
        valid = ctx > 0
        s_self = jnp.where(valid, s_self, _NEG_INF)
        m_prev = m_scr[:, 0][:, None]
        m_new = jnp.maximum(m_prev, s_self)
        alpha = jnp.exp(m_prev - m_new)
        p_self = jnp.where(valid, jnp.exp(s_self - m_new), 0.0)
        l = l_scr[:, 0][:, None] * alpha + p_self
        acc = acc_scr[...] * alpha + p_self * k_self[:, :v_width]
        o = acc / jnp.where(l == 0.0, 1.0, l)
        o_ref[t, 0] = o.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, rows_per_step, one_row, 0)


def _vmem_limit(dtype):
    """A float32 pool (a `--dtype float32` run: the reference checks'
    sharp version) doubles the walk's buffers and the query block, and
    under `jax.default_matmul_precision("highest")` its products take
    several passes with their temporaries: 25 MB at 32 heads, over the
    compiler's default scope. The 2-byte pools of the serving cells keep
    that default (None: their programs are what they were)."""
    return (64 << 20) if jnp.dtype(dtype).itemsize > 2 else None


def _launch_shapes(b, h, dk, bs, w, v_width, dtype):
    """(rows a grid step, pages a group, the walk's scratch, the
    accumulators) of a launch, all from its operands' shapes."""
    ppg = _largest_divisor(w, _GROUP_PAGES)
    return _largest_divisor(b, _ROWS_PER_STEP), ppg, [
        pltpu.VMEM((2, ppg, 1, bs, dk), dtype),
        pltpu.SemaphoreType.DMA((2, )),
    ], [
        # The buffer the next walk's group 0 is in; the page the ragged
        # kernel's write buffer holds.
        pltpu.SMEM((2, ), jnp.int32),
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
    t, ppg, bufs, accs = _launch_shapes(b, h, dk, bs, w, v_width, pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // t, ),
        in_specs=[
            pl.BlockSpec((t, 1, h, dk), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((t, 1, h, v_width), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((t, 1, h, 128), lambda b_, *_: (b_, 0, 0, 0)),
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
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=_vmem_limit(pool.dtype)),
    )(context_lens, block_tables.reshape(-1), q, pool)
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
    t, ppg, bufs, accs = _launch_shapes(b, h, dk, bs, w, v_width, pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b // t, ),
        in_specs=[
            pl.BlockSpec((t, 1, h, dk), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((t, 1, dk), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((t, 1, h, v_width), lambda b_, *_: (b_, 0, 0, 0)),
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
        # Operand indices COUNT the 3 scalar-prefetch args: the pool is
        # operand 5, aliased onto output 1 for the in-place update.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ), has_side_effects=True,
            vmem_limit_bytes=_vmem_limit(pool.dtype)),
    )(context_lens, block_tables.reshape(-1), slot_mapping, q, new, pool)
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
