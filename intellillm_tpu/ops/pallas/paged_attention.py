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
- v4 vectorized the per-group math across the whole head block as ONE
  flat float32 dot ([HP*G, D] x [P*HP*BS, D]^T) with the online softmax
  on [HP*G, P*HP*BS] tiles: full vector tiles where v3's HP unrolled
  [G, P*BS] pieces used a sliver of each (+15 % end to end over v3 on
  v5e, llama2-7b int8 / fp8-KV bs=32; the v3 twin was then removed).
  Its price: of every score tile one column in HP is the row's own
  head's (the others were coded out of reach and masked): the position
  code, the mask, the exp, the sums and the PV dot's streamed operand
  all ran over HP times the columns a row attends to.
- v5 (this kernel, PR 42) keeps the single instruction stream and drops
  the junk columns: ONE *batched* dot a piece for all HP heads, each head
  over its own keys ([HP, Gb, D] x [HP, P*BS, D]^T; a piece's pages
  [P, HP, BS, D] are re-indexed head-major in registers: whole (BS, D)
  tiles change places, nothing moves within one), and the softmax chain
  on the rows' own columns only. The score rows keep the flat form's
  full tiles: where G query heads a KV head are fewer than a sublane
  tile, 8 // G heads share one 8-row block (`_heads_per_row_block`):
  the block's rows are every one of its heads' dot operand, and each row
  of the result is taken from the head it belongs to (a select a result
  register), so the accumulators are the flat [HP*G] rows again, eight
  to a tile. No per-head loop and no unroll in the source: a per-head
  unroll of 8 measured 2.7x the parent's lowering time a program.
  A grid step owns `_ROWS_PER_STEP` rows, walked one after the other:
  they share the step's fixed cost (its blocks copied in and out).
  The operands stay float32 whatever the pool: on v5e a bfloat16 operand
  measured SLOWER (PERF.md section 6, PR 42): an fp8 value reaches
  float32 in one unpack a register, bfloat16 costs that, a pack and a
  relayout (the pool's 16-row slabs are two (8, 128) memory tiles, half a
  bfloat16 register each), and the MXU's float32 passes were not what
  bound the flat form.
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
  measured slower than moving all 16; since PR 42 a sub-group of pages
  a loop turn, and a wait names a sub-group's bytes), and the tail is
  multiplied as
  ONE piece sized by a tree of two-way conditionals (a chain of
  sub-group updates measured 30-70 % slower than the fixed group). A
  page that is not moved leaves in VMEM what was there, so V starts out
  zeroed: 0 x NaN is NaN.

The ragged mixed-batch sibling (ops/pallas/ragged_paged_attention.py)
reuses this module's `_page_walker` (the DMA walk) and `_row_update`
(the online softmax of one query token a row) and adds the
fused cache-write, the in-flight-token handling and the tiles of many
rows the flat mixed dispatch needs.

Numerics: f32 accumulation, running max, denominator and logsumexp
regardless of cache dtype; per-row arithmetic never depends on the
other rows of a launch.
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
# Rows of one grid step of the decode kernel: they share the step's fixed
# cost (its blocks' copies in and out, ~0.35 us).
_ROWS_PER_STEP = 8


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


def _page_walker(context_lens_ref, tables_ref, pools, *, held_back: int,
                 batch_size: int, heads_per_block: int, pages_per_group: int,
                 block_size: int, w_max: int, windowed: bool = False):
    """The DMA side of the page walk, for one launch's refs: returns
    (`first_page`, `start_first_group`, `walk`). `pools` are the pools
    the walk moves, each as (the pool in HBM `[NB, heads, BS, D]`, its
    VMEM double buffer `[2, P, HP, BS, D]`, its DMA semaphores `[2]`):
    K and V for the per-head kernels, the one latent pool (a head block
    of 1) for `mla_attention.py`.

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
    moved and waited for a sub-group of pages a loop turn (then a page a
    turn) and multiplied in one piece up to the last `sub`-page
    sub-group that holds one (`sub` a tuple of ascending page counts
    that ends in `pages_per_group`: the smallest of these pieces that
    holds the tail). A row with nothing to walk (a
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
    chunk = _sub_pages(ppg)

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
        """The first `n` pages of a group: a sub-group a loop turn, then
        the pages left over."""
        def one(j, carry):
            start_page(row, hb_, g, j, buf)
            return carry

        def some(c, carry):
            for i in range(chunk):
                start_page(row, hb_, g, c * chunk + i, buf)
            return carry
        whole = lax.div(n, chunk)
        lax.fori_loop(0, whole, some, 0)
        lax.fori_loop(whole * chunk, n, one, 0)

    # A DMA semaphore counts bytes, so a wait names only how many: the
    # buffer itself stands in for the source.
    def wait_full(buf):
        for _, vmem, sem in pools:
            pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                  sem.at[buf]).wait()

    def wait_tail(n, buf):
        """For `n` pages' bytes: a sub-group's at a time (a wait costs
        ~5 ns whatever it names), then a page's."""
        def wait_pages(first, count: int):
            for _, vmem, sem in pools:
                pages = vmem.at[buf, pl.ds(first, count)]
                pltpu.make_async_copy(pages, pages, sem.at[buf]).wait()

        def one(j, carry):
            wait_pages(j, 1)
            return carry

        def some(c, carry):
            wait_pages(c * chunk, chunk)
            return carry
        whole = lax.div(n, chunk)
        lax.fori_loop(0, whole, some, 0)
        lax.fori_loop(whole * chunk, n, one, 0)

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
            if isinstance(sub, int):
                _switch(lax.div(tail + sub - 1, sub), 1, ppg // sub,
                        lambda t: update(full_groups, tail_buf, t * sub))
            else:
                # The piece sizes given: the smallest that holds the tail.
                _switch(sum((tail > n).astype(jnp.int32) for n in sub[:-1]),
                        0, len(sub) - 1,
                        lambda i: update(full_groups, tail_buf, sub[i]))

        def no_tail():
            @pl.when(full_groups == 0)  # nothing held: nothing in flight
            def _():
                start_successor(tail_buf)

        lax.cond(tail > 0, tail_group, no_tail)
        return lax.rem(start_buf + full_groups
                       + (tail > 0).astype(jnp.int32), 2)

    return first_page, start_first_group, walk


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


def _padded_g(num_groups_g: int) -> int:
    """Query heads a KV head, up to whole float32 sublane tiles."""
    return -(-num_groups_g // 8) * 8


def _heads_per_row_block(heads_per_block: int, num_groups_g: int) -> int:
    """KV heads whose query heads share one block of score rows: as many
    as fill a sublane tile (8 // G of G = 1, 2, 4: then the blocks are the
    flat [HP * G] rows, eight to a tile), one where a head's G rows take
    a tile or more to themselves."""
    if 8 % num_groups_g:
        return 1
    return _largest_divisor(heads_per_block, 8 // num_groups_g)


def _stage_rows(dst_scr, x) -> None:
    """A row's per-query-head values `x` [HP, G, n] into the score rows'
    layout `dst_scr` [HP / R, Gb, n] f32: query head g of the block's
    j-th KV head in row j * G + g (rows past R * G stay the zeros
    `_first` laid out)."""
    hp, g, n = x.shape
    nb = dst_scr.shape[0]
    x = x.astype(jnp.float32)
    if nb != hp:
        x = x.reshape(hp * g, n).reshape(nb, hp * g // nb, n)
    dst_scr[:, :x.shape[1], :] = x


def _row_update(query_pos_f, limit, lo, k_buf, v_buf, q_scr, slope_scr,
                m_scr, l_scr, acc_scr, *, first_token, scale: float,
                num_groups_g: int, pages_per_group: int, block_size: int):
    """`update(g, buf, npg)` of one query token a row: folds the first
    `npg` pages of group g, held in buffer `buf`, into the row's
    accumulators, every KV head of the block over its own keys.
    `q_scr` and `slope_scr` hold the row's query and ALiBi slopes in the
    score rows' layout (`_stage_rows`); the row sees positions
    `lo <= pos < limit` (`lo` None: from 0) and its walk's page 0 starts
    at position `first_token`."""
    ppg, bs = pages_per_group, block_size
    hp, d = k_buf.shape[2], k_buf.shape[-1]
    nb, gb, _ = acc_scr.shape
    r = hp // nb
    # Which of a block's R heads a score row belongs to.
    row_head = lax.broadcasted_iota(jnp.int32, (1, gb, 1), 1) // num_groups_g

    def by_head(rows):
        """A block's rows [HP / R, Gb, n] as the dot's operand of each of
        its heads [HP, Gb, n]."""
        return rows if r == 1 else jnp.repeat(rows, r, axis=0)

    def own_rows(x):
        """Of the dot's result [HP, Gb, n], each row from the head it
        belongs to: [HP / R, Gb, n]."""
        if r == 1:
            return x
        x = x.reshape(nb, r, gb, x.shape[-1])
        out = x[:, 0]
        for j in range(1, r):
            out = jnp.where(row_head == j, x[:, j], out)
        return out

    def update(g, buf, npg: int):
        keys = npg * bs
        # Token position of each of a head's keys within the sequence.
        token_pos = (first_token + g * (ppg * bs)
                     + lax.broadcasted_iota(jnp.int32, (1, 1, keys), 2))
        mask = token_pos < limit                           # [1, 1, keys]
        if lo is not None:
            mask = mask & (token_pos >= lo)

        def heads_keys(pool_buf):
            """The piece's pages [NPG, HP, BS, D] as each head's own keys
            [HP, NPG * BS, D] f32: whole (BS, D) tiles change places,
            nothing moves within one."""
            x = pool_buf[buf, pl.ds(0, npg)].astype(jnp.float32)
            return jnp.swapaxes(x, 0, 1).reshape(hp, keys, d)

        # ONE batched dot for all HP heads, each over its own keys.
        s = own_rows(jax.lax.dot_general(
            by_head(q_scr[...]), heads_keys(k_buf),
            (((2, ), (2, )), ((0, ), (0, ))),
            preferred_element_type=jnp.float32))       # [HP/R, Gb, keys]
        # ALiBi: score += slope * (key_pos - query_pos).
        s = s * scale + slope_scr[:, :, :1] * (
            token_pos.astype(jnp.float32) - query_pos_f)

        m_prev = m_scr[:, :, :1]                           # [HP/R, Gb, 1]
        m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Mask AFTER the exp: with nothing valid m_new == s == -inf-ish
        # and exp(0) would otherwise contribute 1s.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # [HP/R, Gb, keys]
        l_new = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = own_rows(jax.lax.dot_general(
            by_head(p), heads_keys(v_buf), (((2, ), (1, )), ((0, ), (0, ))),
            preferred_element_type=jnp.float32))          # [HP/R, Gb, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    return update


def _walk(context_lens_ref, tables_ref, buf_idx_ref, init_ref, q_ref,
          slopes_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem, v_sem,
          q_scr, slope_scr, m_scr, l_scr, acc_scr, *, t, batch_size: int,
          num_head_blocks: int, pages_per_group: int, block_size: int,
          scale: float, w_max: int, windowed: bool = False):
    """Online softmax of row `t` of this grid step's rows (and its kv-head
    block) over the row's paged context, into the scratch accumulators
    (`_page_walker` moves the pages, `_row_update` multiplies them). A
    walk's last group issues the first group of the NEXT walk."""
    rows_per_step = q_ref.shape[0]
    row0 = pl.program_id(0) * rows_per_step
    b = row0 + t
    hb = pl.program_id(1)
    hp, ppg, bs = k_buf.shape[2], pages_per_group, block_size
    first_page, start_first_group, walk = _page_walker(
        context_lens_ref, tables_ref,
        ((k_hbm_ref, k_buf, k_sem), (v_hbm_ref, v_buf, v_sem)),
        held_back=0, batch_size=batch_size, heads_per_block=hp,
        pages_per_group=ppg, block_size=bs, w_max=w_max, windowed=windowed)

    # Very first grid step starts its own group 0; afterwards every step's
    # group 0 was prefetched by its predecessor. It also lays out, once,
    # what every step reads back: a V buffer that is finite, and zeros in
    # the score rows no query head takes.
    @pl.when(init_ref[0] == 1)
    def _first():
        _zero_pages(v_buf)
        q_scr[...] = jnp.zeros_like(q_scr)
        slope_scr[...] = jnp.zeros_like(slope_scr)
        start_first_group(b, hb, 0)
    init_ref[0] = 0

    # The next walk: the step's next row, else the step's rows again for
    # the next head block, else the next step's first row.
    in_step = t + 1 < rows_per_step
    wrap = hb + 1 == num_head_blocks
    nhb = jnp.where(in_step, hb, jnp.where(wrap, 0, hb + 1))
    nb = jnp.where(in_step, b + 1,
                   jnp.where(wrap, row0 + rows_per_step, row0))

    def start_successor(buf):
        @pl.when(nb < batch_size)
        def _():
            start_first_group(nb, nhb, buf)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    _stage_rows(q_scr, q_ref[t])
    _stage_rows(slope_scr, slopes_ref[...])

    update = _row_update(
        (context_lens_ref[b] - 1).astype(jnp.float32), context_lens_ref[b],
        context_lens_ref[batch_size + b] if windowed else None,
        k_buf, v_buf, q_scr, slope_scr, m_scr, l_scr, acc_scr,
        first_token=first_page(b) * bs if windowed else 0, scale=scale,
        num_groups_g=q_ref.shape[2], pages_per_group=ppg, block_size=bs)
    buf_idx_ref[0] = walk(b, hb, buf_idx_ref[0], start_successor, update,
                          _sub_pages(ppg))


def _decode_kernel(
    # scalar prefetch (SMEM)
    context_lens_ref,   # [B] i32
    tables_ref,         # [B * W] i32 (flattened)
    buf_idx_ref,        # [1] i32 — VMEM buffer holding the next step's group 0
    init_ref,           # [1] i32 — 1 until the first grid step has run
    # inputs
    q_ref,              # [T, HP, G, D] the step's T rows
    slopes_ref,         # [HP, G, 128] f32 ALiBi slopes, col 0 (0 = none)
    k_hbm_ref,          # [NB, Hkv, BS, D] (HBM resident)
    v_hbm_ref,
    # outputs
    o_ref,              # [T, HP, G, D]
    lse_ref,            # [T, HP, G, 128] f32 logsumexp (col 0)
    # scratch
    k_buf,              # [2, P, HP, BS, D] VMEM double buffer
    v_buf,
    k_sem,              # DMA semaphores [2]
    v_sem,
    q_scr,              # [HP / R, Gb, D] f32 the query, by score row
    slope_scr,          # [HP / R, Gb, 128] f32 the slopes, by score row
    m_scr,              # [HP / R, Gb, 128] f32 running max
    l_scr,              # [HP / R, Gb, 128] f32 running denominator
    acc_scr,            # [HP / R, Gb, D] f32 running numerator
    **walk_params,
):
    rows_per_step, hp, g_sz, d = q_ref.shape
    rows = hp * g_sz // acc_scr.shape[0]     # score rows a block uses

    def one_row(t, carry):
        _walk(context_lens_ref, tables_ref, buf_idx_ref, init_ref, q_ref,
              slopes_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem, v_sem,
              q_scr, slope_scr, m_scr, l_scr, acc_scr, t=t, **walk_params)
        l = l_scr[:, :rows, :1]                          # [HP/R, R*G, 1]
        m = m_scr[:, :rows, :1]
        o = acc_scr[:, :rows, :] / jnp.where(l == 0.0, 1.0, l)
        lse = jnp.broadcast_to(
            jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)),
                      _NEG_INF), (acc_scr.shape[0], rows, 128))
        if rows != g_sz:    # R heads a block: the flat rows, then by head
            o = o.reshape(hp * g_sz, d)
            lse = lse.reshape(hp * g_sz, 128)
        o_ref[t] = o.reshape(hp, g_sz, d).astype(o_ref.dtype)
        lse_ref[t] = lse.reshape(hp, g_sz, 128)
        return carry

    # The rows of a grid step share the step's fixed cost.
    lax.fori_loop(0, rows_per_step, one_row, 0)


def _default_hp(k_cache) -> int:
    """Head-block size: each page DMA moves [HP, BS, D] — bigger HP means
    fewer, larger DMAs and fewer grid steps (the KV walk is DMA-issue-
    bound, not bandwidth-bound). Measured on v5e, llama-7b end-to-end:
    bf16 KV: hp cap 8 -> 1487, 16 -> 1603, 32 -> 1551 tok/s/chip; fp8
    KV: 16 -> 1811, 32 -> 1836 (half-size pages tip the balance toward
    fewer, larger DMAs). So: 16, and 32 for 1-byte caches. Those runs
    were of the flat form, whose score tile grew quadratically in HP by
    its junk columns; since PR 42 a head multiplies its own keys only,
    so that ground for stopping at 16 is gone and the caps have not
    been measured again (PERF.md section 7)."""
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
    blocks = hp // _heads_per_row_block(hp, g)
    gb = _padded_g(g)
    t = _largest_divisor(b, _ROWS_PER_STEP)

    # <8 sublanes in the q block: hint a f32 <1x128> layout (a bf16 <8x128>
    # memref would be mis-tiled for tiny G).
    q_kernel_dtype = q_grouped.dtype if g % 8 == 0 else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b // t, hkv // hp),
        in_specs=[
            pl.BlockSpec((t, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((hp, g, 128), lambda b_, h_, *_: (h_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((t, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((t, hp, g, 128), lambda b_, h_, *_: (b_, h_, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppg, hp, bs, d), k_cache.dtype),
            pltpu.VMEM((2, ppg, hp, bs, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.VMEM((blocks, gb, d), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, 128), jnp.float32),
            pltpu.VMEM((blocks, gb, d), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _decode_kernel,
        batch_size=b,
        num_head_blocks=hkv // hp,
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

