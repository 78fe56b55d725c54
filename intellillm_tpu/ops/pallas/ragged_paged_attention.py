"""Ragged fused cache-write + paged attention for the mixed dispatch.

One Pallas kernel serves the whole flat `(token_budget,)` mixed batch:
decode rows (one new token, long paged context) and prefill-chunk rows
(one token of an in-flight chunk, `context_lens = position + 1`) differ
only in their per-row metadata, so a single grid over (row, kv-head
block) handles both. Per grid step the kernel

1. writes the row's new K/V (this head block's slice) into its pool block
   at `slot_mapping[row]` — the fused replacement for the separate
   `ops/kv_cache.reshape_and_cache` scatter pass. The pool's pages are
   `(block_size, D)`-tiled and Mosaic refuses a one-row slice of a tiled
   memref, so the write is a read-modify-write of the slot's
   `[HP, BS, D]` slab (see `_page_slab_copies`), and
2. walks the row's paged prior context with the same double-buffered
   multi-page DMA groups and flat-dot online softmax as
   `ops/pallas/paged_attention.py` (whose `_group_copies` walk it
   reuses).

Write-before-read ordering across rows relies on the sequential grid
(`dimension_semantics=("arbitrary", "arbitrary")`): chunk rows of the
same sequence land in batch order, so row i+1's context walk sees row
i's K/V because row i's write-back completed inside row i's grid step
(and row i+1's slab read-modify-write starts from what row i wrote).

The one hazard is the cross-step prefetch: the last page group of each
step prefetches the NEXT step's group 0 — *before* that step's own
cache write. The kernel therefore never reads a row's own token back
from HBM: the HBM walk is masked to `pos < ctx - 1` and the self-token
score/value come straight from the VMEM K/V input block, merged into
the online-softmax accumulators after the walk. (The in-flight prefetch
may still copy the raced bytes; they are masked out of the math. The
slab write-back rewrites the page's other rows with the bytes they
already hold, so a prefetch racing it reads the same values either way.)

Numerics contract: callers pass `k_new`/`v_new` already cast to the
cache dtype — the reference path reads the cache *after* the write, so
the self-token must see post-cast (e.g. fp8-quantized) values, and DMAs
cannot cast. The caches are updated in place via `input_output_aliases`
(indices count the scalar-prefetch operands).

Selection: `ops/ragged_attention.ragged_fused_attention` gates on
`use_pallas_kernel("ragged")` and `head_size % 128 == 0`; everything
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
                                                       _group_copies,
                                                       _largest_divisor)

_NEG_INF = -1e30
# SMEM the flattened block tables of one launch may take (of 1 MiB on
# v5e, shared with the other scalar-prefetch operands).
_SMEM_TABLE_BYTES = 512 * 1024


def _page_slab_copies(k_hbm_ref, v_hbm_ref, kw_buf, vw_buf, kw_sem, vw_sem,
                      page, h0, *, heads_per_block, to_hbm: bool):
    """The two DMAs moving this head block's [HP, BS, D] slab of `page`
    between the pool and the VMEM write buffers. Whole trailing
    (BS, D) tiles only: Mosaic refuses a one-row slice of a tiled
    memref ("Slice shape along dimension 0 must be aligned to tiling"),
    so the token-granular write is a read-modify-write of the slab."""
    k_hbm = k_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)]
    v_hbm = v_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)]
    if to_hbm:
        return (pltpu.make_async_copy(kw_buf, k_hbm, kw_sem),
                pltpu.make_async_copy(vw_buf, v_hbm, vw_sem))
    return (pltpu.make_async_copy(k_hbm, kw_buf, kw_sem),
            pltpu.make_async_copy(v_hbm, vw_buf, vw_sem))


def _ragged_kernel(
    # scalar prefetch (SMEM)
    context_lens_ref,   # [B] i32 (include the row's own new token)
    tables_ref,         # [B * W] i32 (flattened)
    slots_ref,          # [B] i32 flat physical slots, -1 = pad row
    buf_idx_ref,        # [1] i32
    init_ref,           # [1] i32
    # inputs
    q_ref,              # [1, HP, G, D]
    slopes_ref,         # [HP, G, 128] f32 ALiBi slopes, col 0 (0 = none)
    k_new_ref,          # [1, HP, D] — this row's new K, cache dtype
    v_new_ref,
    k_hbm_ref,          # [NB, Hkv, BS, D] (HBM resident, aliased output)
    v_hbm_ref,
    # outputs
    o_ref,              # [1, HP, G, D]
    k_out_ref,          # aliased views of k_hbm_ref / v_hbm_ref
    v_out_ref,
    # scratch
    k_buf,              # [2, P, HP, BS, D] VMEM double buffer
    v_buf,
    k_sem,              # read-DMA semaphores [2]
    v_sem,
    kw_buf,             # [HP, BS, D] VMEM write-slab buffers
    vw_buf,
    kw_sem,             # write-DMA semaphores (scalar)
    vw_sem,
    m_scr,              # [HP * G, 128] f32
    l_scr,
    acc_scr,            # [HP * G, D] f32
    *,
    batch_size: int,
    num_head_blocks: int,
    heads_per_block: int,
    num_groups_g: int,
    pages_per_group: int,
    block_size: int,
    scale: float,
    w_max: int,
):
    del k_out_ref, v_out_ref  # in-place aliases of the HBM inputs
    b = pl.program_id(0)
    hb = pl.program_id(1)
    ctx = context_lens_ref[b]
    slot = slots_ref[b]
    bk = pages_per_group * block_size
    hp, g_sz = heads_per_block, num_groups_g
    # The HBM walk covers the prior context only (pos < ctx - 1); the
    # row's own token is merged from VMEM after the walk.
    num_groups = jnp.maximum(lax.div((ctx - 1) + bk - 1, bk), 1)

    # 1. Write this row's K/V before anything downstream can read it:
    #    fetch the slot's [HP, BS, D] page slab, replace row `off` in
    #    VMEM, write the slab back. Serial within the (sequential) grid
    #    step, so the next row of the same sequence reads this row's
    #    token. Pad rows (slot < 0) skip it entirely.
    @pl.when(slot >= 0)
    def _write():
        page = lax.div(slot, block_size)
        off = lax.rem(slot, block_size)
        slab = functools.partial(
            _page_slab_copies, k_hbm_ref, v_hbm_ref, kw_buf, vw_buf,
            kw_sem, vw_sem, page, hb * hp, heads_per_block=hp)
        for c in slab(to_hbm=False):
            c.start()
        for c in slab(to_hbm=False):
            c.wait()
        is_row = lax.broadcasted_iota(jnp.int32, kw_buf.shape, 1) == off
        for new_ref, buf in ((k_new_ref, kw_buf), (v_new_ref, vw_buf)):
            # Select in f32: exact for bf16 and fp8 values, and avoids
            # packed-dtype selects Mosaic does not lower on v5e.
            new = new_ref[0].astype(jnp.float32)[:, None, :]
            buf[...] = jnp.where(is_row, new,
                                 buf[...].astype(jnp.float32)
                                 ).astype(buf.dtype)
        for c in slab(to_hbm=True):
            c.start()
        for c in slab(to_hbm=True):
            c.wait()

    def copies(b_, hb_, g_, buf_):
        return _group_copies(k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem,
                             v_sem, tables_ref, b_, hb_, g_, buf_,
                             heads_per_block=hp,
                             pages_per_group=pages_per_group, w_max=w_max)

    @pl.when(init_ref[0] == 1)
    def _first():
        for c in copies(b, hb, 0, 0):
            c.start()
    init_ref[0] = 0
    start_buf = buf_idx_ref[0]

    wrap = hb + 1 == num_head_blocks
    nhb = jnp.where(wrap, 0, hb + 1)
    nb = jnp.where(wrap, b + 1, b)
    has_next = nb < batch_size

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q_flat = (q_ref[0].astype(jnp.float32) *
              scale).reshape(hp * g_sz, -1)              # [HP*G, D]
    ncols = pages_per_group * hp * block_size
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (hp * g_sz, ncols), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (hp * g_sz, ncols), 1)
    col_head = lax.rem(lax.div(cols_i, block_size), hp)
    block_mask = lax.div(rows_i, g_sz) == col_head
    col_tok = (lax.div(cols_i, hp * block_size) * block_size +
               lax.rem(cols_i, block_size))

    def body(g, carry):
        buf = lax.rem(start_buf + g, 2)
        nxt = lax.rem(buf + 1, 2)

        @pl.when(g + 1 < num_groups)
        def _prefetch_own():
            for c in copies(b, hb, g + 1, nxt):
                c.start()

        @pl.when((g + 1 == num_groups) & has_next)
        def _prefetch_successor():
            # Issued before the successor's own cache write — safe only
            # because the successor's self-token is masked from its walk.
            for c in copies(nb, nhb, 0, nxt):
                c.start()

        for c in copies(b, hb, g, buf):
            c.wait()

        token_pos = g * bk + col_tok                     # [HP*G, NC]
        mask = block_mask & (token_pos < ctx - 1)
        pos_f = token_pos.astype(jnp.float32)
        ctx_f = (ctx - 1).astype(jnp.float32)

        k = k_buf[buf].reshape(-1, k_buf.shape[-1]).astype(jnp.float32)
        v = v_buf[buf].reshape(-1, v_buf.shape[-1]).astype(jnp.float32)
        s = jax.lax.dot_general(
            q_flat, k, (((1, ), (1, )), ((), ())),
            preferred_element_type=jnp.float32)          # [HP*G, HP*PBS]
        slope = slopes_ref[:, :, 0].reshape(hp * g_sz, 1)
        s = s + slope * (pos_f - ctx_f)

        m_prev = m_scr[:, 0][:, None]
        m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=1,
                        keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)

        l_new = l_scr[:, 0][:, None] * alpha + jnp.sum(p, axis=1,
                                                       keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, (hp * g_sz, 128))
        l_scr[...] = jnp.broadcast_to(l_new, (hp * g_sz, 128))
        return carry

    lax.fori_loop(0, num_groups, body, 0, unroll=False)
    buf_idx_ref[0] = lax.rem(start_buf + num_groups, 2)

    # 2. Merge the self token (pos = ctx - 1) from the VMEM input block.
    #    k_new/v_new are already in the cache dtype, so the f32 upcast
    #    here matches a reference read of the just-written cache line.
    #    ALiBi bias is slope * (pos - query_pos) = 0 for the self token.
    k_self = jnp.broadcast_to(
        k_new_ref[0].astype(jnp.float32)[:, None, :],
        (hp, g_sz, k_new_ref.shape[-1])).reshape(hp * g_sz, -1)
    v_self = jnp.broadcast_to(
        v_new_ref[0].astype(jnp.float32)[:, None, :],
        (hp, g_sz, v_new_ref.shape[-1])).reshape(hp * g_sz, -1)
    s_self = jnp.sum(q_flat * k_self, axis=1, keepdims=True)
    valid = ctx > 0
    s_self = jnp.where(valid, s_self, _NEG_INF)          # [HP*G, 1]

    m_prev = m_scr[:, 0][:, None]
    m_new = jnp.maximum(m_prev, s_self)
    alpha = jnp.exp(m_prev - m_new)
    p_self = jnp.where(valid, jnp.exp(s_self - m_new), 0.0)
    l = l_scr[:, 0][:, None] * alpha + p_self
    acc = acc_scr[...] * alpha + p_self * v_self

    o = acc / jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = o.reshape(hp, g_sz, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale_static", ))
def _ragged_call(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                 slot_mapping, block_tables, context_lens, *,
                 scale_static: float):
    b, hkv, g, d = q_grouped.shape
    nb, _, bs, _ = k_cache.shape
    w = block_tables.shape[1]
    ppg = _largest_divisor(w, 16)
    hp = _largest_divisor(hkv, _default_hp(k_cache))
    q_kernel_dtype = q_grouped.dtype if g % 8 == 0 else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hkv // hp),
        in_specs=[
            pl.BlockSpec((1, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((hp, g, 128), lambda b_, h_, *_: (h_, 0, 0)),
            pl.BlockSpec((1, hp, d), lambda b_, h_, *_: (b_, h_, 0)),
            pl.BlockSpec((1, hp, d), lambda b_, h_, *_: (b_, h_, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, hp, g, d), lambda b_, h_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
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
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, d), jnp.float32),
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
        scale=scale_static,
        w_max=w,
    )
    out, k_cache, v_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q_grouped.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ),
        # Operand indices COUNT the 5 scalar-prefetch args: the caches
        # are operands 9/10, aliased onto outputs 1/2 for the in-place
        # pool update.
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
    )(
        context_lens,
        block_tables.reshape(-1),
        slot_mapping,
        jnp.zeros((1, ), jnp.int32),
        jnp.ones((1, ), jnp.int32),
        q_grouped.astype(q_kernel_dtype),
        jnp.broadcast_to(slopes[:, :, None], (hkv, g, 128)),
        k_new,
        v_new,
        k_cache,
        v_cache,
    )
    return out.astype(q_grouped.dtype), k_cache, v_cache


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
):
    """Fused cache-write + causal paged attention over the flat mixed
    batch. Returns (out [B, 1, Hq, D], k_cache, v_cache) with the caches
    updated in place (donated/aliased)."""
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
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g)
    else:
        slopes = jnp.zeros((hkv, g), jnp.float32)
    # Pad rows arrive as -1 or as the scatter path's out-of-bounds
    # sentinel (num_blocks * block_size, model_runner._decode_fn_single);
    # the kernel knows one spelling, and must never DMA past the pool.
    slots = slot_mapping.astype(jnp.int32)
    num_slots = k_cache.shape[0] * k_cache.shape[2]
    slots = jnp.where((slots >= 0) & (slots < num_slots), slots, -1)
    # The block tables ride in SMEM (scalar prefetch), 1 MiB on v5e: a
    # 2048-row budget with 128-block tables (max_model_len 2048) does
    # not fit. Launch the grid over row chunks that do; the pool is
    # aliased through, so chunk i's writes precede chunk i+1's reads
    # exactly as rows do inside one launch.
    w = block_tables.shape[1]
    rows = _largest_divisor(b, max(_SMEM_TABLE_BYTES // (4 * w), 1))
    ctx = context_lens.astype(jnp.int32)
    outs = []
    for r0 in range(0, b, rows):
        rs = slice(r0, r0 + rows)
        out, k_cache, v_cache = _ragged_call(
            q_grouped[rs], slopes, k_new[rs], v_new[rs], k_cache, v_cache,
            slots[rs], block_tables[rs], ctx[rs],
            scale_static=float(scale))
        outs.append(out)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out.reshape(b, 1, hq, d), k_cache, v_cache
