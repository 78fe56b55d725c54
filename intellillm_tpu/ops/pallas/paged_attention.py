"""Pallas TPU paged-attention decode kernel (head-block-vectorized).

Role parity: reference `csrc/attention/attention_kernels.cu` (951 LoC —
`paged_attention_v1/v2` block-table gather + online softmax, V2 adds
cross-partition reduction). One kernel covers both roles: the per-sequence
KV walk is streamed through VMEM in multi-page groups with online-softmax
accumulators, so no separate V2 reduction pass is needed.

Architecture (evolved against device-time traces; this module is the
consolidated survivor of the v3/v4 pair — v4 won on real TPU and the v3
twin was deleted, see the selection history below):
- v1 gridded (batch, kv_head, page): one 4 KiB DMA per grid step → 16k
  grid steps/layer, ~5 ms/layer of DMA latency (>90% of decode time).
- v2 gridded (batch, kv_head) with an inline page walk and double-buffered
  multi-page DMA groups: ~0.65 ms/layer — still 4x off the HBM roofline
  because each page DMA is one head = 4 KiB.
- v3 additionally blocks over kv heads: each grid step owns
  (sequence, HP kv heads) and every page DMA moves a contiguous
  [HP, block_size, head_size] slab (32 KiB at HP=8/bf16/D=128). The last
  page group prefetches the NEXT grid step's first group so the DMA
  pipeline never drains across grid steps.
- v4 (this kernel) vectorizes the per-group math across the whole head
  block: ONE batched dot computes all HP heads' scores ([HP, G, P·BS]
  instead of HP unrolled [G, P·BS] matmuls) and the online-softmax
  update runs on [HP·G, P·BS] tiles. For MHA (G=1) this turns ~30 VPU
  ops on <1x128> vectors per head into single ops on full 8x128+ tiles —
  the v3 profile showed op-issue overhead, not DMA bandwidth, dominating
  at 40 GB/s effective KV read. Validated on real TPU v5e at +15%
  end-to-end decode throughput over v3 (935.8 vs 810.6 tok/s/chip,
  llama2-7b int8/fp8-KV bs=32); v3 and v4 agreed to 2e-6 on identical
  inputs before the v3 twin was removed.
- The paged pools stay in HBM (`memory_space=ANY`); the kernel issues
  explicit `pltpu.make_async_copy`s against `k_hbm.at[page].at[head
  slice]` — the block table (scalar-prefetched to SMEM) is read at
  copy-issue time, which is the CUDA kernel's `block_table` gather loop.
- GQA: queries are laid out [B, Hkv, G, D]; a grid step computes all G
  query heads of its HP kv heads — no KV duplication (the reference
  expands KV heads instead, `attention.py:106-120`).
- ALiBi is native: per-head slopes ride along in VMEM and bias the scores
  by (key_pos - query_pos) before the online softmax, matching
  `decode_attention_reference`.
- Besides the attended output, the kernel emits the per-head logsumexp so
  fused multi-step decode can merge pool-part and stage-part attention.

- The walk follows the row, not the table (`walk_extent`, the one rule
  both kernels and the host's counter share): a row moves the
  ceil(ctx / block_size) pages it holds and multiplies them up to the
  next sub-group of 4 pages. Until PR 27 every group moved and
  multiplied all 16 pages whatever the context (a pad row one whole
  group): at the serving cells' 16-976-token contexts under 64-block
  tables that was 1.37x the tokens a decode row attends to and 2.4x a
  prefill-chunk row's. What the chip said while this was built (v5e,
  128 rows x 8 KV heads x 4, fp8; `benchmarks/kernels/
  benchmark_paged_walk.py`): the kernel is bound by its instruction
  stream, not by bytes (copies alone run at 715 GB/s and hide behind the
  dots); a conditional region costs ~27 ns taken or not, about what
  three page copies cost to issue, a loop iteration ~5 ns; and an
  update of any size pays the QK -> softmax -> PV chain's latency once.
  So: a row's full groups run as static pieces (one wait a pool for
  the whole group's bytes), its tail's copies go out in a loop (a
  conditional a page, or a run of pages for each bit of the count, both
  measured slower than moving all 16), and the tail is multiplied as
  ONE piece sized by a tree of two-way conditionals (a chain of
  sub-group updates measured 30-70 % slower than the fixed group). A
  page that is not moved leaves in VMEM what was there, so V starts out
  zeroed: 0 x NaN is NaN.

The ragged mixed-batch sibling (ops/pallas/ragged_paged_attention.py)
reuses this module's `_page_walker` (the DMA walk) and `_row_update`
(the flat-dot online softmax of one query token a row) and adds the
fused cache-write, the in-flight-token handling and the tiles of many
rows the flat mixed dispatch needs.

Numerics: f32 accumulation regardless of cache dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# Pages of a sub-group: a row's tail is multiplied up to the last
# sub-group that holds one of its pages.
_SUB_PAGES = 4
# A column of another head's, as a token position no context reaches.
_FAR = 1 << 30


def _largest_divisor(n: int, cap: int) -> int:
    for p in range(min(cap, n), 0, -1):
        if n % p == 0:
            return p
    return 1


def _pages_per_group(w: int, page_bytes: int) -> int:
    """Pages of one DMA group, a divisor of the table's width: 16 at
    most, 32 where a page (one head block's slab) is 16 KiB or less.
    Every piece of the walk pays a fixed price (the QK -> softmax -> PV
    chain's latency, its conditionals), so a short row is best one
    piece; the cap is what the f32 copies of a group leave of VMEM."""
    return _largest_divisor(w, 32 if page_bytes <= 16 * 1024 else 16)


def _sub_pages(pages_per_group: int) -> int:
    if pages_per_group % _SUB_PAGES:
        return pages_per_group
    return _SUB_PAGES


def walk_extent(ctx, block_size: int, w: int, sub_pages: int = 1, xp=np,
                lo=None):
    """THE rule of the page walk, shared by both paged kernels (`xp=jnp`,
    on SMEM scalars) and by the host's counter (numpy, vectorised): a row
    whose walk must see `ctx` tokens (its context for decode, its context
    less its own token for the ragged kernel) moves the first
    ceil(ctx / block_size) pages of its `w`-wide table, none for a pad
    row, and multiplies those pages rounded up to a whole sub-group.
    Under a sliding window the row sees no position below `lo` (its
    first visible one): the walk starts at the page that holds `lo`
    (`lo // block_size`, the edge page whole: the mask is exact inside
    it) and moves nothing below it.
    Returns (pages moved, tokens multiplied)."""
    pages = xp.clip((ctx + (block_size - 1)) // block_size, 0, w)
    if lo is not None:
        pages = xp.maximum(pages - lo // block_size, 0)
    subs = (pages + (sub_pages - 1)) // sub_pages
    return pages, subs * (sub_pages * block_size)


def _switch(index, lo: int, hi: int, branch) -> None:
    """Run `branch(i)` for the i in lo..hi that `index` equals, through a
    tree of two-way conditionals (each costs about as much as issuing a
    page's copies, so as few as decide it)."""
    if lo == hi:
        branch(lo)
        return
    mid = (lo + hi) // 2
    lax.cond(index <= mid, lambda: _switch(index, lo, mid, branch),
             lambda: _switch(index, mid + 1, hi, branch))


def _page_walker(context_lens_ref, tables_ref, k_hbm_ref, v_hbm_ref, k_buf,
                 v_buf, k_sem, v_sem, *, held_back: int, batch_size: int,
                 heads_per_block: int, pages_per_group: int,
                 block_size: int, w_max: int, windowed: bool = False):
    """The DMA side of the page walk, for one launch's refs: returns
    (`first_page`, `start_first_group`, `walk`).

    `walk(row, hb, start_buf, start_successor, update, sub)` streams the
    pages `row` holds of its context less its last `held_back` tokens
    (kv-head block `hb`) through the VMEM double buffer in groups of
    `pages_per_group` pages and calls `update(g, buf, npg)` on each piece
    (the first `npg` pages of group g, in buffer `buf`); it returns the
    buffer the next walk's group 0 is in. Each group's copies are issued
    while the group before it is multiplied, and a walk's last group
    calls `start_successor(buf)`, which issues the first group of the
    NEXT walk (`start_first_group(row, hb, buf)`), so the DMA pipeline
    never drains. `walk_extent` says how far the walk goes. The row's
    full groups come first, each moved and multiplied as one static
    piece; then its tail, the pages of its last group that it holds,
    moved page by page and multiplied in one piece up to the last
    `sub`-page sub-group that holds one. A row with nothing to walk (a
    pad row) moves and multiplies nothing. Copies are started and waited
    for by the same rule on the same row's context, so what is waited
    for is what was started.

    `windowed`: `context_lens_ref` is [2B], the rows' contexts and then
    their first visible positions `lo` (a sliding window). A row's walk
    starts at table entry `first_page(row) = lo // block_size` (its page
    0 is that entry: groups, tail and prefetch count from there);
    `update` masks the positions below `lo` in that first page. Not
    windowed, nothing here differs from what it was."""
    hp, ppg, bs = heads_per_block, pages_per_group, block_size
    pools = ((k_hbm_ref, k_buf, k_sem), (v_hbm_ref, v_buf, v_sem))

    def first_page(row):
        return lax.div(context_lens_ref[batch_size + row], bs)

    def walk_pages(row):
        return walk_extent(context_lens_ref[row] - held_back, bs, w_max,
                           xp=jnp,
                           lo=(context_lens_ref[batch_size + row]
                               if windowed else None))[0]

    def start_page(row, hb_, g, j, buf):
        entry = row * w_max + g * ppg + j
        if windowed:
            entry = entry + first_page(row)
        page = tables_ref[entry]
        # Chained single-axis dynamic slices: Mosaic supports dynamic
        # indexing one (leading) axis at a time.
        for hbm, vmem, sem in pools:
            pltpu.make_async_copy(
                hbm.at[page].at[pl.ds(hb_ * hp, hp)], vmem.at[buf, j],
                sem.at[buf]).start()

    def start_full(row, hb_, g, buf):
        for j in range(ppg):
            start_page(row, hb_, g, j, buf)

    def start_tail(row, hb_, g, n, buf):
        def one(j, carry):
            start_page(row, hb_, g, j, buf)
            return carry
        lax.fori_loop(0, n, one, 0)

    # A DMA semaphore counts bytes, so a wait names only how many: the
    # buffer itself stands in for the source.
    def wait_full(buf):
        for _, vmem, sem in pools:
            pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                  sem.at[buf]).wait()

    def wait_tail(n, buf):
        def one(j, carry):
            for _, vmem, sem in pools:
                pltpu.make_async_copy(vmem.at[buf, j], vmem.at[buf, j],
                                      sem.at[buf]).wait()
            return carry
        lax.fori_loop(0, n, one, 0)

    def start_first_group(row, hb_, buf):
        n = jnp.minimum(walk_pages(row), ppg)
        lax.cond(n == ppg, lambda: start_full(row, hb_, 0, buf),
                 lambda: start_tail(row, hb_, 0, n, buf))

    def walk(row, hb, start_buf, start_successor, update, sub):
        pages = walk_pages(row)
        full_groups = lax.div(pages, ppg)
        tail = pages - full_groups * ppg

        def full_group(g, carry):
            buf = lax.rem(start_buf + g, 2)
            nxt = 1 - buf

            @pl.when(g + 1 < full_groups)
            def _next_full_group():
                start_full(row, hb, g + 1, nxt)

            @pl.when(g + 1 == full_groups)
            def _after_the_full_groups():
                lax.cond(tail > 0,
                         lambda: start_tail(row, hb, full_groups, tail, nxt),
                         lambda: start_successor(nxt))

            wait_full(buf)
            update(g, buf, ppg)
            return carry

        lax.fori_loop(0, full_groups, full_group, 0)
        tail_buf = lax.rem(start_buf + full_groups, 2)

        def tail_group():
            start_successor(1 - tail_buf)
            wait_tail(tail, tail_buf)
            # One piece whatever its size: a chain of smaller updates
            # would pay the QK -> softmax -> PV latency once a link.
            _switch(lax.div(tail + sub - 1, sub), 1, ppg // sub,
                    lambda t: update(full_groups, tail_buf, t * sub))

        def no_tail():
            @pl.when(full_groups == 0)  # nothing held: nothing in flight
            def _():
                start_successor(tail_buf)

        lax.cond(tail > 0, tail_group, no_tail)
        return lax.rem(start_buf + full_groups
                       + (tail > 0).astype(jnp.int32), 2)

    return first_page, start_first_group, walk


def _position_code(code_scr, *, heads_per_block: int, num_groups_g: int,
                   block_size: int) -> None:
    """Lay out the position code of the flat score layout, once a launch.
    The KV buffer flattens page-major: flat column c = (page*HP + head)*BS
    + tok, so head(c) = (c // BS) % HP and the token's index within the
    group is page(c)*BS + tok(c). Columns of other heads than the row's
    are junk: coded out of reach."""
    hp, bs = heads_per_block, block_size
    rows_i = lax.broadcasted_iota(jnp.int32, code_scr.shape, 0)
    cols_i = lax.broadcasted_iota(jnp.int32, code_scr.shape, 1)
    col_head = lax.rem(lax.div(cols_i, bs), hp)
    col_tok = lax.div(cols_i, hp * bs) * bs + lax.rem(cols_i, bs)
    code_scr[...] = jnp.where(lax.div(rows_i, num_groups_g) == col_head,
                              col_tok, _FAR)


def _zero_pages(v_buf) -> None:
    """A page the walk does not move leaves in VMEM what was there: the
    mask zeroes its probabilities, but 0 x NaN in the PV dot is NaN, so V
    starts out finite and from then on holds only pages that some row
    owns. (K needs nothing: a masked score is selected away, never
    multiplied.)"""
    zero = jnp.zeros(v_buf.shape[2:], jnp.float32).astype(v_buf.dtype)
    for i in range(v_buf.shape[0]):
        for j in range(v_buf.shape[1]):
            v_buf[i, j] = zero


def _row_update(q_flat, slope, query_pos_f, limit, lo, k_buf, v_buf, m_scr,
                l_scr, acc_scr, code_scr, *, first_token, heads_per_block:
                int, pages_per_group: int, block_size: int):
    """`update(g, buf, npg)` of one query token a row: folds the first
    `npg` pages of group g, held in buffer `buf`, into the row's
    accumulators. `q_flat` [HP*G, D] f32 (scaled), `slope` [HP*G, 1];
    the row sees positions `lo <= pos < limit` (`lo` None: from 0) and
    its walk's page 0 starts at position `first_token`."""
    hp, ppg, bs = heads_per_block, pages_per_group, block_size
    rows = q_flat.shape[0]

    def update(g, buf, npg: int):
        ncols = npg * hp * bs
        # Token position of each FLAT column within the full sequence
        # (out of reach for a column of another head's).
        token_pos = g * (ppg * bs) + code_scr[:, :ncols]  # [HP*G, NC]
        if lo is not None:
            token_pos = token_pos + first_token
            mask = (token_pos < limit) & (token_pos >= lo)
        else:
            mask = token_pos < limit

        # ONE flat dot for all HP heads: [HP*G, D] x [NPG*HP*BS, D]^T. The
        # cross-head scores are junk (masked out); the extra FLOPs are
        # ~2 MXU tiles — far cheaper than HP separate small dots or a
        # (Mosaic-hostile) batched dot.
        d = k_buf.shape[-1]
        k = k_buf[buf, :npg].reshape(ncols, d).astype(jnp.float32)
        v = v_buf[buf, :npg].reshape(ncols, d).astype(jnp.float32)
        s = jax.lax.dot_general(
            q_flat, k, (((1, ), (1, )), ((), ())),
            preferred_element_type=jnp.float32)          # [HP*G, NC]
        # ALiBi: score += slope * (key_pos - query_pos).
        s = s + slope * (token_pos.astype(jnp.float32) - query_pos_f)

        m_prev = m_scr[:, 0][:, None]                    # [HP*G, 1]
        m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=1,
                        keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Mask AFTER the exp: with nothing valid m_new == s == -inf-ish
        # and exp(0) would otherwise contribute 1s; the mask also zeroes
        # the cross-head columns so pv below stays block-diagonal.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # [HP*G, NC]

        l_new = l_scr[:, 0][:, None] * alpha + jnp.sum(p, axis=1,
                                                       keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)          # [HP*G, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, (rows, 128))
        l_scr[...] = jnp.broadcast_to(l_new, (rows, 128))

    return update


def _walk(context_lens_ref, tables_ref, buf_idx_ref, init_ref, q_ref,
          slopes_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem, v_sem,
          m_scr, l_scr, acc_scr, code_scr, *, batch_size: int,
          num_head_blocks: int, heads_per_block: int, num_groups_g: int,
          pages_per_group: int, block_size: int, scale: float, w_max: int,
          windowed: bool = False):
    """Online softmax of this grid step's (row, kv-head block) over the
    row's paged context, into the scratch accumulators (`_page_walker`
    moves the pages, `_row_update` multiplies them). A step's last group
    issues the first group of the NEXT grid step."""
    b = pl.program_id(0)
    hb = pl.program_id(1)
    hp, g_sz, ppg, bs = (heads_per_block, num_groups_g, pages_per_group,
                         block_size)
    first_page, start_first_group, walk = _page_walker(
        context_lens_ref, tables_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf,
        k_sem, v_sem, held_back=0, batch_size=batch_size,
        heads_per_block=hp, pages_per_group=ppg, block_size=bs,
        w_max=w_max, windowed=windowed)

    # Very first grid step starts its own group 0; afterwards every step's
    # group 0 was prefetched by its predecessor. It also lays out, once,
    # what every step reads back: the position code of the flat score
    # layout, and a V buffer that is finite.
    @pl.when(init_ref[0] == 1)
    def _first():
        _position_code(code_scr, heads_per_block=hp, num_groups_g=g_sz,
                       block_size=bs)
        _zero_pages(v_buf)
        start_first_group(b, hb, 0)
    init_ref[0] = 0

    # Successor grid point (head-block fastest, then batch).
    wrap = hb + 1 == num_head_blocks
    nhb = jnp.where(wrap, 0, hb + 1)
    nb = jnp.where(wrap, b + 1, b)

    def start_successor(buf):
        @pl.when(nb < batch_size)
        def _():
            start_first_group(nb, nhb, buf)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q_flat = (q_ref[0].astype(jnp.float32) *
              scale).reshape(hp * g_sz, -1)              # [HP*G, D]
    update = _row_update(
        q_flat, slopes_ref[:, :, 0].reshape(hp * g_sz, 1),
        (context_lens_ref[b] - 1).astype(jnp.float32), context_lens_ref[b],
        context_lens_ref[batch_size + b] if windowed else None,
        k_buf, v_buf, m_scr, l_scr, acc_scr, code_scr,
        first_token=first_page(b) * bs if windowed else 0,
        heads_per_block=hp, pages_per_group=ppg, block_size=bs)
    buf_idx_ref[0] = walk(b, hb, buf_idx_ref[0], start_successor, update,
                          _sub_pages(ppg))


def _decode_kernel(
    # scalar prefetch (SMEM)
    context_lens_ref,   # [B] i32
    tables_ref,         # [B * W] i32 (flattened)
    buf_idx_ref,        # [1] i32 — VMEM buffer holding the next step's group 0
    init_ref,           # [1] i32 — 1 until the first grid step has run
    # inputs
    q_ref,              # [1, HP, G, D]
    slopes_ref,         # [HP, G, 128] f32 ALiBi slopes, col 0 (0 = none)
    k_hbm_ref,          # [NB, Hkv, BS, D] (HBM resident)
    v_hbm_ref,
    # outputs
    o_ref,              # [1, HP, G, D]
    lse_ref,            # [1, HP, G, 128] f32 logsumexp (col 0)
    # scratch
    k_buf,              # [2, P, HP, BS, D] VMEM double buffer
    v_buf,
    k_sem,              # DMA semaphores [2]
    v_sem,
    m_scr,              # [HP * G, 128] f32 running max
    l_scr,              # [HP * G, 128] f32 running denominator
    acc_scr,            # [HP * G, D] f32 running numerator
    code_scr,           # [HP * G, P * HP * BS] i32 position code
    *,
    heads_per_block: int,
    num_groups_g: int,
    **walk_params,
):
    hp, g_sz = heads_per_block, num_groups_g
    _walk(context_lens_ref, tables_ref, buf_idx_ref, init_ref, q_ref,
          slopes_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem, v_sem,
          m_scr, l_scr, acc_scr, code_scr, heads_per_block=hp,
          num_groups_g=g_sz, **walk_params)

    l = l_scr[:, 0][:, None]                             # [HP*G, 1]
    m = m_scr[:, 0][:, None]
    o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)       # [HP*G, D]
    o_ref[0] = o.reshape(hp, g_sz, -1).astype(o_ref.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)
    lse_ref[0] = jnp.broadcast_to(
        lse.reshape(hp, g_sz, 1), lse_ref[0].shape)


def _default_hp(k_cache) -> int:
    """Head-block size: each page DMA moves [HP, BS, D] — bigger HP means
    fewer, larger DMAs and fewer grid steps (the KV walk is DMA-issue-
    bound, not bandwidth-bound). Measured on v5e, llama-7b end-to-end:
    bf16 KV: hp cap 8 -> 1487, 16 -> 1603, 32 -> 1551 tok/s/chip (32
    pays a quadratically growing junk-column score dot); fp8 KV:
    16 -> 1811, 32 -> 1836 (half-size pages tip the balance toward
    fewer, larger DMAs). So: 16, and 32 for 1-byte caches."""
    return 32 if k_cache.dtype.itemsize == 1 else 16


def _paged_attention_launch(q_grouped, slopes, k_cache, v_cache,
                            block_tables, context_lens, *,
                            scale_static: float, windowed: bool):
    """`context_lens` [B], or windowed [2B]: the contexts, then the rows'
    first visible positions."""
    b, hkv, g, d = q_grouped.shape
    nb, _, bs, _ = k_cache.shape
    w = block_tables.shape[1]
    hp = _largest_divisor(hkv, _default_hp(k_cache))
    ppg = _pages_per_group(w, hp * bs * d * k_cache.dtype.itemsize)

    # <8 sublanes in the q block: hint a f32 <1x128> layout (a bf16 <8x128>
    # memref would be mis-tiled for tiny G).
    q_kernel_dtype = q_grouped.dtype if g % 8 == 0 else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv // hp),
        in_specs=[
            pl.BlockSpec((1, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((hp, g, 128), lambda b_, h_, *_: (h_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, hp, g, 128), lambda b_, h_, *_: (b_, h_, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppg, hp, bs, d), k_cache.dtype),
            pltpu.VMEM((2, ppg, hp, bs, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, d), jnp.float32),
            pltpu.VMEM((hp * g, ppg * hp * bs), jnp.int32),
        ],
    )

    kernel = functools.partial(
        _decode_kernel,
        batch_size=b,
        num_head_blocks=hkv // hp,
        heads_per_block=hp,
        num_groups_g=g,
        pages_per_group=ppg,
        block_size=bs,
        scale=scale_static,
        w_max=w,
        **({"windowed": True} if windowed else {}),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q_grouped.dtype),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(
        context_lens,
        block_tables.reshape(-1),
        jnp.zeros((1, ), jnp.int32),
        jnp.ones((1, ), jnp.int32),
        q_grouped.astype(q_kernel_dtype),
        jnp.broadcast_to(slopes[:, :, None], (hkv, g, 128)),
        k_cache,
        v_cache,
    )
    return out.astype(q_grouped.dtype), lse[..., 0]


@functools.partial(
    jax.jit, static_argnames=("scale_static", ))
def _paged_attention_call(q_grouped, slopes, k_cache, v_cache, block_tables,
                          context_lens, *, scale_static: float):
    return _paged_attention_launch(q_grouped, slopes, k_cache, v_cache,
                                   block_tables, context_lens,
                                   scale_static=scale_static, windowed=False)


@functools.partial(
    jax.jit, static_argnames=("scale_static", ))
def _paged_attention_call_window(q_grouped, slopes, k_cache, v_cache,
                                 block_tables, context_lens, lo, *,
                                 scale_static: float):
    """The same launch under a sliding window (`lo` [B]: each row's first
    visible position); a name of its own so that a trace tells the
    windowed calls apart."""
    return _paged_attention_launch(
        q_grouped, slopes, k_cache, v_cache, block_tables,
        jnp.concatenate([context_lens, lo]), scale_static=scale_static,
        windowed=True)


def paged_attention(
    q: jnp.ndarray,             # [B, 1, Hq, D]
    k_cache: jnp.ndarray,       # [NB, Hkv, BS, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32
    scale: float,
    alibi_slopes: Optional[jnp.ndarray] = None,
    return_lse: bool = False,
    lo: Optional[jnp.ndarray] = None,
):
    """Decode-phase paged attention. Returns [B, 1, Hq, D] (and, with
    return_lse, the per-head logsumexp [B, Hq] for attention merging).
    `lo` [B] i32 (a sliding window): a row attends to positions
    `lo <= pos < context_lens` only, and walks no page below `lo`'s.
    The head size must be whole 128-lane tiles (Mosaic DMA windows are
    128-aligned in the minor dimension): the seam
    (`ops/ragged_attention.py` `paged_pool_attention`) sends other head
    sizes to the jnp reference."""
    b, one, hq, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    q_grouped = q.reshape(b, hkv, g, d)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g)
    else:
        slopes = jnp.zeros((hkv, g), jnp.float32)
    if lo is None:
        out, lse = _paged_attention_call(q_grouped, slopes, k_cache, v_cache,
                                         block_tables, context_lens,
                                         scale_static=float(scale))
    else:
        out, lse = _paged_attention_call_window(
            q_grouped, slopes, k_cache, v_cache, block_tables,
            context_lens.astype(jnp.int32), lo.astype(jnp.int32),
            scale_static=float(scale))
    out = out.reshape(b, 1, hq, d)
    if return_lse:
        return out, lse.reshape(b, hq)
    return out

