"""Ragged fused cache-write + paged attention for the mixed dispatch.

One Pallas kernel serves the whole flat `(token_budget,)` mixed batch:
decode rows (one new token, long paged context) and prefill-chunk rows
(one token of an in-flight chunk, `context_lens = position + 1`) differ
only in their per-row metadata. The grid is (span of T rows, kv-head
block), T from the launch's shapes (`tile_rows`), and the kernel's unit
of work is a **tile**: a whole span whose rows are consecutive tokens of
one sequence (`whole_spans` of `run_starts`, which the runner hands the
seam: nothing else in the metadata says which rows continue the row
before them), in a launch as high as a full tile (`tiles_many`), is a
tile of many; every row of any other span is a tile of one. A tile

1. writes its rows' new K/V (this head block's slice) into the pool at
   their slots — the fused replacement for the separate
   `ops/kv_cache.reshape_and_cache` scatter pass. The pool's pages are
   `(block_size, D)`-tiled and Mosaic refuses a one-row slice of a tiled
   memref, so a page goes out as its whole `[HP, BS, D]` slab
   (`_page_slab_copies`): built from the tile's rows alone where the tile
   covers the page, a read-modify-write only at a tile's unaligned first
   and last page (and for a single row);
2. walks the paged context BELOW its first row once, with the
   double-buffered multi-page DMA groups of `ops/pallas/paged_attention.py`
   (`_page_walker`: the pages moved follow the first row's context,
   `walk_extent`, from the page of its `lo` under a window), each group
   multiplied against the whole tile, a KV head at a time:
   `[G x T, D] x [D, keys]`, f32 accumulators, online softmax a row, each
   row's own `lo` and ALiBi position in the mask and the bias;
3. adds the in-tile causal part from the VMEM input blocks (the tile's
   own keys, already in the cache dtype).

A tile of one row (a decode row, a pad row, a run's ragged ends, a run
shorter than a span, any row of a step of fewer rows than a full tile)
is the row-at-a-time walk the kernel had before tiles: one batched dot
for all HP heads, each over its own keys (`_row_update`), the self token
merged from VMEM. A step of 128 single rows and a step of one 512-row
run are the two ends of the same grid.

Why only whole spans tile. A row of a tile sums its keys in two pieces
(the walked context, the tile's own rows) whose boundary and lanes follow
where the span starts, a row alone in one: the float32 sums differ in
their last bits, so a row's output would follow where the step put its
run. A deep bf16 model turns any last-bit difference into the noise of
its arithmetic (PERF.md section 6, PR 36: Mistral-7B read 0.03-0.21 nats
apart at the first token after one flipped last bit in 2,048 attention
outputs a layer, as it did with every run tiled), and the benchmark's
probes hold a short prompt alone and among others to less. So rows whose
run fills no span keep the one order of sums a row alone has.

Write-before-read ordering across tiles relies on the sequential grid
(`dimension_semantics=("arbitrary", "arbitrary")`) and on the order of
the loop inside a grid step: the tiles of one sequence land in batch
order, so tile i+1's context walk sees tile i's K/V because tile i's
write-back completed (start, then wait) before tile i's own walk began
(and tile i+1's read-modify-write of a shared page starts from what tile
i wrote).

The one hazard is the cross-tile prefetch: the last page group of each
walk prefetches the NEXT walk's group 0 — *before* that tile's own cache
write. The kernel therefore never reads a tile's own tokens back from
HBM: the HBM walk is held to the positions below the tile's first row
(`held_back=1` of the first row's context) and the tile's own scores and
values come straight from the VMEM K/V input blocks. (The in-flight
prefetch may still copy the raced bytes, where the prior context shares
the first row's page; they are masked out of the math. The slab
write-back rewrites the page's other rows with the bytes they already
hold, so a prefetch racing it reads the same values either way. A page
the tile covers whole lies wholly at or above its first row: no walk of
that tile moves it.)

Numerics contract: callers pass `k_new`/`v_new` already cast to the
cache dtype — the reference path reads the cache *after* the write, so
a tile's own tokens must be seen post-cast (e.g. fp8-quantized), and
DMAs cannot cast. K, V, the query and the probabilities enter both dots
in f32, in a tile of many as in `_row_update`. The caches are
updated in place via `input_output_aliases` (indices count the
scalar-prefetch operands).

Selection: `ops/ragged_attention.ragged_fused_attention` asks
`use_kernel("ragged", head_size % 128 == 0)`; everything
else takes the jnp reference composition (reshape_and_cache then
decode_attention_reference), which is the golden-pinned oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from intellillm_tpu.ops.pallas.paged_attention import (_default_hp,
                                                       _heads_per_row_block,
                                                       _largest_divisor,
                                                       _padded_g,
                                                       _page_walker,
                                                       _pages_per_group,
                                                       _row_update,
                                                       _stage_rows,
                                                       _sub_pages,
                                                       _zero_pages)

_NEG_INF = -1e30
# SMEM the flattened block tables of one launch may take (of 1 MiB on
# v5e, shared with the other scalar-prefetch operands).
_SMEM_TABLE_BYTES = 512 * 1024
# Score rows (query tokens x query heads of one KV head) a tile's dots
# may have: what the f32 scores, probabilities and accumulators of one
# head leave of VMEM beside the page groups.
_TILE_SCORE_ROWS = 1024
# Of v5e's 128 MiB: the tile's f32 scratch and scores pass the 16 MiB a
# kernel is given unasked.
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
_DOT_NT = (((1, ), (1, )), ((), ()))
_DOT_NN = (((1, ), (0, )), ((), ()))


def _whole_sublanes(rows: int, cap: int) -> int:
    """The largest divisor of `rows` (a multiple of 8) that is a multiple
    of 8 (whole f32 sublane tiles) and at most `cap` (at least 8)."""
    return 8 * _largest_divisor(rows // 8, max(cap // 8, 1))


def _tile_height(queries_per_kv: int) -> int:
    """The most rows a tile may have: 128, or fewer where G query heads
    a KV head would pass `_TILE_SCORE_ROWS` score rows."""
    return min(128, _TILE_SCORE_ROWS // queries_per_kv)


def tile_rows(rows: int, queries_per_kv: int) -> int:
    """Rows of one span, from the shapes of a launch of `rows` rows: the
    consecutive batch rows one grid step owns, and the rows of one run
    that walk their paged context together where they fill it."""
    return _whole_sublanes(rows, _tile_height(queries_per_kv))


def tiles_many(rows: int, queries_per_kv: int) -> bool:
    """Whether a launch of `rows` rows has tiles of many at all: its
    spans are as high as the shapes allow, not cut short by the launch
    (a launch twice as long would cut the same spans). In a step of
    fewer rows every row walks alone, so a run of fewer rows than a full
    tile is never a tile of many, whatever step it rides in."""
    return (tile_rows(2 * rows, queries_per_kv)
            == tile_rows(rows, queries_per_kv))


def launch_rows(rows: int, width: int) -> int:
    """Rows of one launch: the block tables ride in SMEM (scalar
    prefetch), 1 MiB on v5e, and a 2048-row budget with 128-block tables
    does not fit, nor do 512 rows of 512-block tables."""
    return _whole_sublanes(rows, _SMEM_TABLE_BYTES // (4 * width))


def whole_spans(run_starts, live, rows_per_tile: int, xp=jnp):
    """Which spans of `rows_per_tile` rows are one tile of many (numpy
    for the host's counter, jnp for the launch): every row after the
    span's first continues the row before it (none starts a run, none is
    a pad row). Every row of another span is a tile of one: a run's
    ragged ends, and a run shorter than a span, walk a row at a time, and
    what such a row computes does not follow where the step put its run
    (the module docstring says why)."""
    prev_live = xp.concatenate([xp.zeros(1, bool), live[:-1]])
    follows = (run_starts == 0) & live & prev_live
    return follows.reshape(-1, rows_per_tile)[:, 1:].all(axis=1)


def _page_slab_copies(k_hbm_ref, v_hbm_ref, kw_buf, vw_buf, kw_sem, vw_sem,
                      page, h0, *, heads_per_block, to_hbm: bool):
    """The two DMAs moving this head block's [HP, BS, D] slab of `page`
    between the pool and the VMEM write buffers. Whole trailing
    (BS, D) tiles only: Mosaic refuses a one-row slice of a tiled
    memref ("Slice shape along dimension 0 must be aligned to tiling"),
    so a token-granular write is a read-modify-write of the slab."""
    k_hbm = k_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)]
    v_hbm = v_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)]
    if to_hbm:
        return (pltpu.make_async_copy(kw_buf, k_hbm, kw_sem),
                pltpu.make_async_copy(vw_buf, v_hbm, vw_sem))
    return (pltpu.make_async_copy(k_hbm, kw_buf, kw_sem),
            pltpu.make_async_copy(v_hbm, vw_buf, vw_sem))


def _ragged_kernel(*refs, batch_size: int, num_head_blocks: int,
                   heads_per_block: int, num_groups_g: int,
                   pages_per_group: int, block_size: int, tile: int,
                   tiled: bool, scale: float, w_max: int, windowed: bool,
                   alibi: bool):
    (   # scalar prefetch (SMEM)
        context_lens_ref,   # [B] i32 (include the row's own new token);
                            # windowed [2B]: then the rows' `lo`
        tables_ref,         # [B * W] i32 (flattened)
        slots_ref,          # [B] i32 flat physical slots, -1 = pad row
        spans_ref,          # [B / T + 1] i32: is the span one tile of
                            # many; last, one past the last live row
        # inputs, a span of T rows x this head block
        q_ref,              # [T, HP*G, D] a row's query heads, flat
        qt_ref,             # [HP, G, T, D] the same, rows innermost
        slopes_ref,         # [HP, G, 128] f32 ALiBi slopes, col 0 (0 = none)
        k_new_ref,          # [T, HP, D] the rows' new K, cache dtype
        v_new_ref,
        kt_new_ref,         # [HP, T, D] the same, rows innermost
        vt_new_ref,
        *rest) = refs
    lo_col_ref = None       # [1, G*T, 1] i32 `lo` of score row g*T + t
    if windowed:
        lo_col_ref, *rest = rest
    (   k_hbm_ref,          # [NB, Hkv, BS, D] (HBM resident, aliased output)
        v_hbm_ref,
        # outputs
        o_ref,              # [T, HP*G, D] a span of tiles of one
        ot_ref,             # [HP, G, T, D] a span that is a tile of many
        _k_out_ref,         # aliased views of k_hbm_ref / v_hbm_ref
        _v_out_ref,
        # scratch
        k_buf,              # [2, P, HP, BS, D] VMEM double buffer
        v_buf,
        k_sem,              # read-DMA semaphores [2]
        v_sem,
        kw_buf,             # [HP, BS, D] VMEM write-slab buffers
        vw_buf,
        kw_sem,             # write-DMA semaphores (scalar)
        vw_sem,
        state_ref,          # [3] i32 SMEM: the buffer the next walk's
                            # group 0 is in; the (page, head block)
                            # kw/vw_buf hold
        q_scr,              # [HP / R, Gb, D] f32: a tile of one, by
        slope_scr,          # [HP / R, Gb, 128]      score row
        m_scr,              # [HP / R, Gb, 128] f32
        l_scr,
        acc_scr,            # [HP / R, Gb, D] f32
        mt_scr,             # [HP, G * T, 128] f32: a tile of many
        lt_scr,
        acct_scr,           # [HP, G * T, D] f32
    ) = rest
    span = pl.program_id(0)
    hb = pl.program_id(1)
    hp, g_sz, ppg, bs, t_sz = (heads_per_block, num_groups_g,
                               pages_per_group, block_size, tile)
    gt = g_sz * t_sz
    d = k_buf.shape[-1]
    first_page, start_first_group, walk = _page_walker(
        context_lens_ref, tables_ref,
        ((k_hbm_ref, k_buf, k_sem), (v_hbm_ref, v_buf, v_sem)),
        held_back=1, batch_size=batch_size, heads_per_block=hp,
        pages_per_group=ppg, block_size=bs, w_max=w_max, windowed=windowed)
    n_live = spans_ref[pl.num_programs(0)]
    row0 = span * t_sz                   # the span's first row
    row_end = jnp.minimum(row0 + t_sz, n_live)

    # The first grid step starts the first tile's group 0 (afterwards
    # every walk's group 0 was prefetched by the walk before it) and lays
    # out, once, what every step reads back.
    @pl.when((span == 0) & (hb == 0))
    def _first():
        _zero_pages(v_buf)
        q_scr[...] = jnp.zeros_like(q_scr)
        slope_scr[...] = jnp.zeros_like(slope_scr)
        state_ref[0] = 0
        state_ref[1] = -1            # the write buffers hold no slab yet
        state_ref[2] = -1

        @pl.when(n_live > 0)
        def _():
            start_first_group(0, 0, 0)

    def successor_after(next_row):
        """`start_successor` of a tile's walk: the next walk is the next
        tile of this span (`next_row`, after a tile of one; None after a
        tile of many), else this span again for the next head block, else
        the next span. Issued before that tile's own cache write: safe
        only because the walk is held to the tile's prior context."""
        def start_successor(buf):
            next_hb = hb + 1 < num_head_blocks
            row = jnp.where(next_hb, row0, row0 + t_sz)
            head = jnp.where(next_hb, hb + 1, 0)
            exists = next_hb | (row0 + t_sz < n_live)
            if next_row is not None:
                here = next_row < row_end
                row = jnp.where(here, next_row, row)
                head = jnp.where(here, hb, head)
                exists = here | exists

            @pl.when(exists)
            def _():
                start_first_group(row, head, buf)
        return start_successor

    def write_rows(r0, t0, n):
        """1. The tile's K/V into the pool, before anything downstream
        can read it: page by page, the rows of the page replaced in the
        VMEM slab [HP, BS, D] and the slab written back. A page the tile
        covers whole is built from the rows alone; one it covers in part
        (a tile's unaligned first and last page, a single row's) is
        fetched first, unless the buffers already hold it as the pool
        has it because the last write went to it too. Serial within the
        (sequential) grid step, so the next tile of the same sequence
        reads this tile's tokens. A pad row (slot < 0) writes nothing."""
        slot0 = slots_ref[r0]

        @pl.when(slot0 >= 0)
        def _write():
            off0 = lax.rem(slot0, bs)

            def one_page(j, carry):
                i_lo = jnp.maximum(j * bs - off0, 0)
                i_hi = jnp.minimum((j + 1) * bs - off0, n)
                page = lax.div(slots_ref[r0 + i_lo], bs)
                slab = functools.partial(
                    _page_slab_copies, k_hbm_ref, v_hbm_ref, kw_buf, vw_buf,
                    kw_sem, vw_sem, page, hb * hp, heads_per_block=hp)

                @pl.when((i_hi - i_lo < bs)
                         & ((state_ref[1] != page) | (state_ref[2] != hb)))
                def _fetch():
                    for c in slab(to_hbm=False):
                        c.start()
                    for c in slab(to_hbm=False):
                        c.wait()
                state_ref[1] = page
                state_ref[2] = hb
                slab_row = lax.broadcasted_iota(jnp.int32, kw_buf.shape, 1)

                def one_row(i, carry):
                    is_row = slab_row == off0 + i - j * bs
                    for new_ref, buf in ((k_new_ref, kw_buf),
                                         (v_new_ref, vw_buf)):
                        # Select in f32: exact for bf16 and fp8 values,
                        # and avoids packed-dtype selects Mosaic does not
                        # lower on v5e.
                        new = new_ref[t0 + i].astype(jnp.float32)[:, None, :]
                        buf[...] = jnp.where(
                            is_row, new, buf[...].astype(jnp.float32)
                        ).astype(buf.dtype)
                    return carry
                lax.fori_loop(i_lo, i_hi, one_row, 0)
                for c in slab(to_hbm=True):
                    c.start()
                for c in slab(to_hbm=True):
                    c.wait()
                return carry
            lax.fori_loop(0, lax.div(off0 + n + bs - 1, bs), one_page, 0)

    def tile_of_one(r, carry):
        t0 = r - row0
        ctx = context_lens_ref[r]
        limit = ctx - 1              # the row's prior context
        lo0 = context_lens_ref[batch_size + r] if windowed else None
        first_token = first_page(r) * bs if windowed else 0
        write_rows(r, t0, 1)
        # 2. Walk the prior context (the row's own token is held back
        #    and merged from VMEM below).
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        blocks = acc_scr.shape[0]
        rows = hp * g_sz // blocks           # score rows a block uses
        by_row = lambda x: x.reshape(blocks, rows, x.shape[-1])
        q = q_ref[t0].astype(jnp.float32)                    # [HP*G, D]
        q_scr[:, :rows, :] = by_row(q)
        _stage_rows(slope_scr, slopes_ref[...])
        update = _row_update(
            limit.astype(jnp.float32), limit, lo0, k_buf, v_buf, q_scr,
            slope_scr, m_scr, l_scr, acc_scr, first_token=first_token,
            scale=scale, num_groups_g=g_sz, pages_per_group=ppg,
            block_size=bs)
        state_ref[0] = walk(r, hb, state_ref[0], successor_after(r + 1),
                            update, _sub_pages(ppg))

        # 3. Merge the self token (pos = ctx - 1) from the VMEM input
        #    block. k_new/v_new are already in the cache dtype, so the
        #    f32 upcast here matches a reference read of the
        #    just-written cache line. ALiBi bias is slope * (pos -
        #    query_pos) = 0 for the self token.
        k_self = jnp.broadcast_to(
            k_new_ref[t0].astype(jnp.float32)[:, None, :],
            (hp, g_sz, d)).reshape(hp * g_sz, d)
        v_self = jnp.broadcast_to(
            v_new_ref[t0].astype(jnp.float32)[:, None, :],
            (hp, g_sz, d)).reshape(hp * g_sz, d)
        s_self = by_row(jnp.sum(q * k_self, axis=1, keepdims=True) * scale)
        valid = ctx > 0
        s_self = jnp.where(valid, s_self, _NEG_INF)      # [HP/R, R*G, 1]

        m_prev = m_scr[:, :rows, :1]
        m_new = jnp.maximum(m_prev, s_self)
        alpha = jnp.exp(m_prev - m_new)
        p_self = jnp.where(valid, jnp.exp(s_self - m_new), 0.0)
        l = l_scr[:, :rows, :1] * alpha + p_self
        acc = acc_scr[:, :rows, :] * alpha + p_self * by_row(v_self)
        o_ref[t0] = (acc / jnp.where(l == 0.0, 1.0, l)).reshape(
            hp * g_sz, d).astype(o_ref.dtype)
        return carry

    def tile_of_many():
        # The span's T rows are consecutive tokens of one sequence. Score
        # row g*T + t is query head g of span row t.
        limit = context_lens_ref[row0] - 1   # the tile's prior context
        first_token = first_page(row0) * bs if windowed else 0
        lo_col = lo_col_ref[0] if windowed else None          # [G*T, 1]
        row_t = lax.rem(lax.broadcasted_iota(jnp.int32, (gt, 1), 0), t_sz)
        write_rows(row0, 0, t_sz)

        def q_of(h):
            return (qt_ref[h].astype(jnp.float32) * scale).reshape(gt, d)

        def bias(h, key_minus_query_pos):
            slope = jnp.broadcast_to(
                slopes_ref[h][:, None, :], (g_sz, t_sz, 128)
            ).reshape(gt, 128)[:, :1]
            return slope * key_minus_query_pos.astype(jnp.float32)

        def fold(h, s, mask, v, first: bool):
            """The online-softmax update of head h's accumulators with
            scores `s` [G*T, keys] of the keys whose values are `v`
            [keys, D] f32."""
            m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=1,
                            keepdims=True)
            if first:
                m_new = m_cur
            else:
                m_prev = mt_scr[h][:, :1]
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
            # Mask AFTER the exp, as `_row_update` does.
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot_general(p, v, _DOT_NN,
                                      preferred_element_type=jnp.float32)
            if not first:
                l_new = lt_scr[h][:, :1] * alpha + l_new
                acc = acct_scr[h] * alpha + acc
            acct_scr[h] = acc
            mt_scr[h] = jnp.broadcast_to(m_new, (gt, 128))
            lt_scr[h] = jnp.broadcast_to(l_new, (gt, 128))

        # 3. The in-tile causal part, from the VMEM input blocks (the
        #    kernel never reads back from HBM what the same grid step
        #    wrote). It comes first: it starts the accumulators.
        col_t = lax.broadcasted_iota(jnp.int32, (1, t_sz), 1)
        causal = col_t <= row_t                               # [G*T, T]
        if windowed:
            causal = causal & (col_t + limit >= lo_col)

        def in_tile_part(h, carry):
            s = jax.lax.dot_general(
                q_of(h), kt_new_ref[h].astype(jnp.float32), _DOT_NT,
                preferred_element_type=jnp.float32)          # [G*T, T]
            if alibi:
                s = s + bias(h, col_t - row_t)
            fold(h, s, causal, vt_new_ref[h].astype(jnp.float32),
                 first=True)
            return carry
        lax.fori_loop(0, hp, in_tile_part, 0)

        # 2. Walk the prior context once, each group against the whole
        #    tile, a KV head at a time.
        def update(g, buf, npg: int):
            keys = npg * bs
            key_pos = (first_token + g * (ppg * bs)
                       + lax.broadcasted_iota(jnp.int32, (1, keys), 1))
            mask = key_pos < limit                            # [1, keys]
            if windowed:
                mask = mask & (key_pos >= lo_col)             # [G*T, keys]
            else:
                mask = jnp.broadcast_to(mask, (gt, keys))

            def one_head(h, carry):
                k = k_buf[buf, pl.ds(0, npg), h].astype(
                    jnp.float32).reshape(keys, d)
                v = v_buf[buf, pl.ds(0, npg), h].astype(
                    jnp.float32).reshape(keys, d)
                s = jax.lax.dot_general(
                    q_of(h), k, _DOT_NT,
                    preferred_element_type=jnp.float32)      # [G*T, keys]
                if alibi:
                    s = s + bias(h, key_pos - (row_t + limit))
                fold(h, s, mask, v, first=False)
                return carry
            lax.fori_loop(0, hp, one_head, 0)

        state_ref[0] = walk(row0, hb, state_ref[0], successor_after(None),
                            update, max(ppg // 4, 1))

        def store(h, carry):
            l = lt_scr[h][:, :1]
            ot_ref[h] = (acct_scr[h] / jnp.where(l == 0.0, 1.0, l)).reshape(
                g_sz, t_sz, d).astype(ot_ref.dtype)
            return carry
        lax.fori_loop(0, hp, store, 0)

    def tiles_of_one():
        lax.fori_loop(row0, row_end, tile_of_one, 0)

    if tiled:
        lax.cond(spans_ref[span] != 0, tile_of_many, tiles_of_one)
    else:               # a launch of fewer rows than a full tile
        tiles_of_one()


def _ragged_launch(q, slopes, k_new, v_new, k_cache, v_cache, slot_mapping,
                   block_tables, context_lens, run_starts, *,
                   scale_static: float, windowed: bool, alibi: bool):
    """One launch over `q` [B, Hkv, G, D]. `context_lens` [B], or
    windowed [2B]: the contexts, then the rows' first visible positions
    (`_page_walker` says how it walks then). Returns (out [B, Hkv, G, D],
    the pools)."""
    b, hkv, g, d = q.shape
    nb, _, bs, _ = k_cache.shape
    w = block_tables.shape[1]
    hp = _largest_divisor(hkv, _default_hp(k_cache))
    ppg = _pages_per_group(w, hp * bs * d * k_cache.dtype.itemsize)
    blocks = hp // _heads_per_row_block(hp, g)
    gb = _padded_g(g)
    t = tile_rows(b, g)
    spans, gt = b // t, g * t
    live = context_lens[:b] > 0
    tiled = tiles_many(b, g)
    whole = (whole_spans(run_starts, live, t) if tiled
             else jnp.zeros((spans, ), bool))
    n_live = jnp.max(jnp.where(live, jnp.arange(1, b + 1), 0))
    # A block's two minor dimensions are whole tiles of its dtype, or the
    # query rides in f32 (G = 7, a span of 8 rows).
    rows_dtype = q.dtype if (hp * g) % 16 == 0 else jnp.float32
    tile_dtype = q.dtype if t % 16 == 0 else jnp.float32

    by_row = lambda p_, h_, *_: (p_, h_, 0)
    by_head = lambda p_, h_, *_: (h_, p_, 0)
    in_specs = [
        pl.BlockSpec((t, hp * g, d), by_row),
        pl.BlockSpec((hp, g, t, d), lambda p_, h_, *_: (h_, 0, p_, 0)),
        pl.BlockSpec((hp, g, 128), lambda p_, h_, *_: (h_, 0, 0)),
        pl.BlockSpec((t, hp, d), by_row),
        pl.BlockSpec((t, hp, d), by_row),
        pl.BlockSpec((hp, t, d), by_head),
        pl.BlockSpec((hp, t, d), by_head),
    ]
    operands = [
        q.reshape(b, hkv * g, d).astype(rows_dtype),
        q.transpose(1, 2, 0, 3).astype(tile_dtype),
        jnp.broadcast_to(slopes[:, :, None], (hkv, g, 128)),
        k_new, v_new, k_new.swapaxes(0, 1), v_new.swapaxes(0, 1),
    ]
    if windowed:
        in_specs.append(pl.BlockSpec((1, gt, 1),
                                     lambda p_, h_, *_: (p_, 0, 0)))
        operands.append(jnp.tile(context_lens[b:].reshape(spans, 1, t),
                                 (1, g, 1)).reshape(spans, gt, 1))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    num_scalar_prefetch = 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(spans, hkv // hp),
        in_specs=in_specs + [any_space, any_space],
        out_specs=(
            pl.BlockSpec((t, hp * g, d), by_row),
            pl.BlockSpec((hp, g, t, d), lambda p_, h_, *_: (h_, 0, p_, 0)),
            any_space, any_space,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppg, hp, bs, d), k_cache.dtype),
            pltpu.VMEM((2, ppg, hp, bs, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.VMEM((hp, bs, d), k_cache.dtype),
            pltpu.VMEM((hp, bs, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((3, ), jnp.int32),
            pltpu.VMEM((blocks, gb, d), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, d), jnp.float32),
            pltpu.VMEM((hp, gt, 128), jnp.float32),
            pltpu.VMEM((hp, gt, 128), jnp.float32),
            pltpu.VMEM((hp, gt, d), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _ragged_kernel,
        batch_size=b,
        num_head_blocks=hkv // hp,
        heads_per_block=hp,
        num_groups_g=g,
        pages_per_group=ppg,
        block_size=bs,
        tile=t,
        tiled=tiled,
        scale=scale_static,
        w_max=w,
        windowed=windowed,
        alibi=alibi,
    )
    # The pools are the last two inputs, aliased onto the last two
    # outputs for the in-place update (operand indices COUNT the
    # scalar-prefetch args).
    pool_at = num_scalar_prefetch + len(operands)
    out_rows, out_tiles, k_cache, v_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv * g, d), rows_dtype),
            jax.ShapeDtypeStruct((hkv, g, b, d), tile_dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ),
        input_output_aliases={pool_at: 2, pool_at + 1: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(
        context_lens,
        block_tables.reshape(-1),
        slot_mapping,
        jnp.concatenate([whole, n_live[None]]).astype(jnp.int32),
        *operands,
        k_cache,
        v_cache,
    )
    # A row's output is where its tile put it; a pad row's is zero.
    out = jnp.where(
        jnp.repeat(whole, t)[:, None, None, None],
        out_tiles.transpose(2, 0, 1, 3).astype(q.dtype),
        jnp.where(live[:, None, None, None],
                  out_rows.reshape(b, hkv, g, d).astype(q.dtype), 0))
    return out, k_cache, v_cache


@functools.partial(jax.jit, static_argnames=("scale_static", "alibi"))
def _ragged_call(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                 slot_mapping, block_tables, context_lens, run_starts, *,
                 scale_static: float, alibi: bool):
    return _ragged_launch(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                          slot_mapping, block_tables, context_lens,
                          run_starts, scale_static=scale_static,
                          windowed=False, alibi=alibi)


@functools.partial(jax.jit, static_argnames=("scale_static", "alibi"))
def _ragged_call_window(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                        slot_mapping, block_tables, context_lens, run_starts,
                        lo, *, scale_static: float, alibi: bool):
    """The same launch under a sliding window (`lo` [B]: each row's first
    visible position); a name of its own so that a trace tells the
    windowed calls apart."""
    return _ragged_launch(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                          slot_mapping, block_tables,
                          jnp.concatenate([context_lens, lo]), run_starts,
                          scale_static=scale_static, windowed=True,
                          alibi=alibi)


def ragged_paged_attention(
    q: jnp.ndarray,             # [B, 1, Hq, D] flat mixed batch
    k_new: jnp.ndarray,         # [B, Hkv, D] — MUST be cache dtype
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,       # [NB, Hkv, BS, D]
    v_cache: jnp.ndarray,
    slot_mapping: jnp.ndarray,  # [B] i32 flat physical slots, -1 = pad
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32, counting the new token
    scale: float,
    alibi_slopes=None,
    lo=None,
    run_starts=None,
):
    """Fused cache-write + causal paged attention over the flat mixed
    batch. Returns (out [B, 1, Hq, D], k_cache, v_cache) with the caches
    updated in place (donated/aliased). `lo` [B] i32 (a sliding window):
    a row attends to positions `lo <= pos < context_lens` only and walks
    no page below `lo`'s. `run_starts` [B] i32: 0 for a row that
    continues the row before it (the same sequence and block table, the
    next position), anything else for a row that does not; None says
    that no row does, and every row is then a tile of one."""
    b, one, hq, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    if k_new.dtype != k_cache.dtype or v_new.dtype != v_cache.dtype:
        raise ValueError(
            "ragged_paged_attention requires k_new/v_new pre-cast to the "
            f"cache dtype (got {k_new.dtype}/{v_new.dtype} vs "
            f"{k_cache.dtype}) — the self-token must see post-cast "
            "values and DMAs cannot cast")
    q_grouped = q.reshape(b, hkv, g, d)
    alibi = alibi_slopes is not None
    if alibi:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g)
    else:
        slopes = jnp.zeros((hkv, g), jnp.float32)
    # Pad rows arrive as -1 or as the scatter path's out-of-bounds
    # sentinel (num_blocks * block_size, model_runner._decode_fn_single);
    # the kernel knows one spelling, and must never DMA past the pool.
    slots = slot_mapping.astype(jnp.int32)
    num_slots = k_cache.shape[0] * k_cache.shape[2]
    slots = jnp.where((slots >= 0) & (slots < num_slots), slots, -1)
    ctx = context_lens.astype(jnp.int32)
    starts = (jnp.ones((b, ), jnp.int32) if run_starts is None
              else run_starts.astype(jnp.int32))
    rows_in = [q_grouped, k_new, v_new, slots, block_tables, ctx, starts]
    if lo is not None:
        rows_in.append(lo.astype(jnp.int32))
    # Whole sublane tiles of rows: pad rows make up a batch of fewer.
    pad = -b % 8
    if pad:
        fill = [0, 0, 0, -1, 0, 0, 1, 0]
        rows_in = [jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1),
                           constant_values=c)
                   for x, c in zip(rows_in, fill)]
    # Launch the grid over row chunks whose tables fit SMEM; the pool is
    # aliased through, so chunk i's writes precede chunk i+1's reads
    # exactly as tiles do inside one launch.
    rows = launch_rows(b + pad, block_tables.shape[1])
    call = _ragged_call if lo is None else _ragged_call_window
    outs = []
    for r0 in range(0, b + pad, rows):
        out, k_cache, v_cache = call(
            rows_in[0][r0:r0 + rows], slopes,
            *(x[r0:r0 + rows] for x in rows_in[1:3]), k_cache, v_cache,
            *(x[r0:r0 + rows] for x in rows_in[3:]),
            scale_static=float(scale), alibi=alibi)
        outs.append(out)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out[:b].reshape(b, 1, hq, d), k_cache, v_cache
