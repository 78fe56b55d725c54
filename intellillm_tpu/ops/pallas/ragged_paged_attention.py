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
   `ops/pallas/paged_attention.py` (whose `_walk` it calls: the pages
   moved and multiplied follow the row's context, `walk_extent`).

Write-before-read ordering across rows relies on the sequential grid
(`dimension_semantics=("arbitrary", "arbitrary")`): chunk rows of the
same sequence land in batch order, so row i+1's context walk sees row
i's K/V because row i's write-back completed inside row i's grid step
(and row i+1's slab read-modify-write starts from what row i wrote).

The one hazard is the cross-step prefetch: the last page group of each
step prefetches the NEXT step's group 0 — *before* that step's own
cache write. The kernel therefore never reads a row's own token back
from HBM: the HBM walk is held to `pos < ctx - 1` (it moves the pages
of that prior context only) and the self-token score/value come straight
from the VMEM K/V input block, merged into the online-softmax
accumulators after the walk. (The in-flight prefetch may still copy the
raced bytes, where the prior context shares the token's page; they are
masked out of the math. The slab write-back rewrites the page's other
rows with the bytes they already hold, so a prefetch racing it reads
the same values either way.)

Numerics contract: callers pass `k_new`/`v_new` already cast to the
cache dtype — the reference path reads the cache *after* the write, so
the self-token must see post-cast (e.g. fp8-quantized) values, and DMAs
cannot cast. The caches are updated in place via `input_output_aliases`
(indices count the scalar-prefetch operands).

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
                                                       _largest_divisor,
                                                       _pages_per_group,
                                                       _walk)

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
    slab_ref,           # [2] i32 SMEM: the (page, head block) kw/vw_buf hold
    m_scr,              # [HP * G, 128] f32
    l_scr,
    acc_scr,            # [HP * G, D] f32
    code_scr,           # [HP * G, P * HP * BS] i32 position code
    *,
    heads_per_block: int,
    num_groups_g: int,
    block_size: int,
    **walk_params,
):
    del k_out_ref, v_out_ref  # in-place aliases of the HBM inputs
    b = pl.program_id(0)
    hb = pl.program_id(1)
    ctx = context_lens_ref[b]
    slot = slots_ref[b]
    hp, g_sz = heads_per_block, num_groups_g

    # 1. Write this row's K/V before anything downstream can read it:
    #    fetch the slot's [HP, BS, D] page slab, replace row `off` in
    #    VMEM, write the slab back. Serial within the (sequential) grid
    #    step, so the next row of the same sequence reads this row's
    #    token. The fetch is skipped where the buffers already hold that
    #    slab as the pool has it, because the last write went to it too
    #    (a prefill chunk's rows fill one page 16 times over): one DMA
    #    round trip a row instead of two. Pad rows (slot < 0) skip the
    #    write entirely.
    @pl.when(init_ref[0] == 1)       # first grid step: they hold nothing
    def _no_slab_yet():
        slab_ref[0] = -1
        slab_ref[1] = -1

    @pl.when(slot >= 0)
    def _write():
        page = lax.div(slot, block_size)
        off = lax.rem(slot, block_size)
        slab = functools.partial(
            _page_slab_copies, k_hbm_ref, v_hbm_ref, kw_buf, vw_buf,
            kw_sem, vw_sem, page, hb * hp, heads_per_block=hp)

        @pl.when((slab_ref[0] != page) | (slab_ref[1] != hb))
        def _fetch():
            for c in slab(to_hbm=False):
                c.start()
            for c in slab(to_hbm=False):
                c.wait()
        slab_ref[0] = page
        slab_ref[1] = hb
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

    # 2. Walk the prior context only (the row's last token is held back
    #    and merged from VMEM below).
    q_flat = _walk(context_lens_ref, tables_ref, buf_idx_ref, init_ref,
                   q_ref, slopes_ref, k_hbm_ref, v_hbm_ref, k_buf, v_buf,
                   k_sem, v_sem, m_scr, l_scr, acc_scr, code_scr,
                   held_back=1,
                   heads_per_block=hp, num_groups_g=g_sz,
                   block_size=block_size, **walk_params)

    # 3. Merge the self token (pos = ctx - 1) from the VMEM input block.
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


def _ragged_launch(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                   slot_mapping, block_tables, context_lens, *,
                   scale_static: float, windowed: bool):
    """`context_lens` [B], or windowed [2B]: the contexts, then the rows'
    first visible positions (`_walk` says how it walks then)."""
    b, hkv, g, d = q_grouped.shape
    nb, _, bs, _ = k_cache.shape
    w = block_tables.shape[1]
    hp = _largest_divisor(hkv, _default_hp(k_cache))
    ppg = _pages_per_group(w, hp * bs * d * k_cache.dtype.itemsize)
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
            pltpu.SMEM((2, ), jnp.int32),
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, 128), jnp.float32),
            pltpu.VMEM((hp * g, d), jnp.float32),
            pltpu.VMEM((hp * g, ppg * hp * bs), jnp.int32),
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
        **({"windowed": True} if windowed else {}),
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


@functools.partial(jax.jit, static_argnames=("scale_static", ))
def _ragged_call(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                 slot_mapping, block_tables, context_lens, *,
                 scale_static: float):
    return _ragged_launch(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                          slot_mapping, block_tables, context_lens,
                          scale_static=scale_static, windowed=False)


@functools.partial(jax.jit, static_argnames=("scale_static", ))
def _ragged_call_window(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                        slot_mapping, block_tables, context_lens, lo, *,
                        scale_static: float):
    """The same launch under a sliding window (`lo` [B]: each row's first
    visible position); a name of its own so that a trace tells the
    windowed calls apart."""
    return _ragged_launch(q_grouped, slopes, k_new, v_new, k_cache, v_cache,
                          slot_mapping, block_tables,
                          jnp.concatenate([context_lens, lo]),
                          scale_static=scale_static, windowed=True)


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
):
    """Fused cache-write + causal paged attention over the flat mixed
    batch. Returns (out [B, 1, Hq, D], k_cache, v_cache) with the caches
    updated in place (donated/aliased). `lo` [B] i32 (a sliding window):
    a row attends to positions `lo <= pos < context_lens` only and walks
    no page below `lo`'s."""
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
        if lo is None:
            out, k_cache, v_cache = _ragged_call(
                q_grouped[rs], slopes, k_new[rs], v_new[rs], k_cache,
                v_cache, slots[rs], block_tables[rs], ctx[rs],
                scale_static=float(scale))
        else:
            out, k_cache, v_cache = _ragged_call_window(
                q_grouped[rs], slopes, k_new[rs], v_new[rs], k_cache,
                v_cache, slots[rs], block_tables[rs], ctx[rs],
                lo.astype(jnp.int32)[rs], scale_static=float(scale))
        outs.append(out)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out.reshape(b, 1, hq, d), k_cache, v_cache
