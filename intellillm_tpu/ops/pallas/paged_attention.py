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

The ragged mixed-batch sibling (ops/pallas/ragged_paged_attention.py)
reuses this module's `_group_copies` DMA walk and adds the fused
cache-write + in-flight-token handling the flat mixed dispatch needs.

Numerics: f32 accumulation regardless of cache dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _group_copies(k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem, v_sem,
                  tables_ref, b, hb, g, buf, *, heads_per_block,
                  pages_per_group, w_max):
    """The async copies moving page-group g of sequence b / kv-head block
    hb into VMEM buffer `buf`. Identical descriptor lists are built at
    start and wait time (a DMA is identified by its (src, dst, sem))."""
    copies = []
    h0 = hb * heads_per_block
    for j in range(pages_per_group):
        idx = jnp.minimum(g * pages_per_group + j, w_max - 1)
        page = tables_ref[b * w_max + idx]
        # Chained single-axis dynamic slices: Mosaic supports dynamic
        # indexing one (leading) axis at a time.
        copies.append(pltpu.make_async_copy(
            k_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)],
            k_buf.at[buf, j], k_sem.at[buf]))
        copies.append(pltpu.make_async_copy(
            v_hbm_ref.at[page].at[pl.ds(h0, heads_per_block)],
            v_buf.at[buf, j], v_sem.at[buf]))
    return copies


def _largest_divisor(n: int, cap: int) -> int:
    for p in range(min(cap, n), 0, -1):
        if n % p == 0:
            return p
    return 1


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
    b = pl.program_id(0)
    hb = pl.program_id(1)
    ctx = context_lens_ref[b]
    bk = pages_per_group * block_size
    num_groups = jnp.maximum(lax.div(ctx + bk - 1, bk), 1)
    hp, g_sz = heads_per_block, num_groups_g

    def copies(b_, hb_, g_, buf_):
        return _group_copies(k_hbm_ref, v_hbm_ref, k_buf, v_buf, k_sem,
                             v_sem, tables_ref, b_, hb_, g_, buf_,
                             heads_per_block=hp,
                             pages_per_group=pages_per_group, w_max=w_max)

    # Very first grid step starts its own group 0; afterwards every step's
    # group 0 was prefetched by its predecessor.
    @pl.when(init_ref[0] == 1)
    def _first():
        for c in copies(b, hb, 0, 0):
            c.start()
    init_ref[0] = 0
    start_buf = buf_idx_ref[0]

    # Successor grid point (head-block fastest, then batch).
    wrap = hb + 1 == num_head_blocks
    nhb = jnp.where(wrap, 0, hb + 1)
    nb = jnp.where(wrap, b + 1, b)
    has_next = nb < batch_size

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q_flat = (q_ref[0].astype(jnp.float32) *
              scale).reshape(hp * g_sz, -1)              # [HP*G, D]
    # Static masks for the flat [HP*G, P*HP*BS] score layout. The KV
    # buffer flattens page-major: flat column c = (page*HP + head)*BS +
    # tok, so head(c) = (c // BS) % HP and the in-sequence token index is
    # page(c)*BS + tok(c).
    ncols = pages_per_group * hp * block_size
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (hp * g_sz, ncols), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (hp * g_sz, ncols), 1)
    col_head = lax.rem(lax.div(cols_i, block_size), hp)
    block_mask = lax.div(rows_i, g_sz) == col_head
    col_tok = (lax.div(cols_i, hp * block_size) * block_size +
               lax.rem(cols_i, block_size))              # [HP*G, NC]

    def body(g, carry):
        buf = lax.rem(start_buf + g, 2)
        nxt = lax.rem(buf + 1, 2)

        @pl.when(g + 1 < num_groups)
        def _prefetch_own():
            for c in copies(b, hb, g + 1, nxt):
                c.start()

        @pl.when((g + 1 == num_groups) & has_next)
        def _prefetch_successor():
            for c in copies(nb, nhb, 0, nxt):
                c.start()

        for c in copies(b, hb, g, buf):
            c.wait()

        # Token position of each FLAT column within the full sequence.
        token_pos = g * bk + col_tok                     # [HP*G, NC]
        mask = block_mask & (token_pos < ctx)
        pos_f = token_pos.astype(jnp.float32)
        ctx_f = (ctx - 1).astype(jnp.float32)

        # ONE flat dot for all HP heads: [HP*G, D] x [P*HP*BS, D]^T. The
        # cross-head scores are junk (masked by block_mask below); the
        # extra FLOPs are ~2 MXU tiles — far cheaper than HP separate
        # small dots or a (Mosaic-hostile) batched dot.
        k = k_buf[buf].reshape(-1, k_buf.shape[-1]).astype(jnp.float32)
        v = v_buf[buf].reshape(-1, v_buf.shape[-1]).astype(jnp.float32)
        s = jax.lax.dot_general(
            q_flat, k, (((1, ), (1, )), ((), ())),
            preferred_element_type=jnp.float32)          # [HP*G, HP*PBS]
        # ALiBi: score += slope * (key_pos - query_pos).
        slope = slopes_ref[:, :, 0].reshape(hp * g_sz, 1)
        s = s + slope * (pos_f - ctx_f)

        m_prev = m_scr[:, 0][:, None]                    # [HP*G, 1]
        m_cur = jnp.max(jnp.where(mask, s, _NEG_INF), axis=1,
                        keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Mask AFTER the exp: with a fully-invalid group m_new == s ==
        # -inf-ish and exp(0) would otherwise contribute 1s; the mask also
        # zeroes the cross-head columns so pv below stays block-diagonal.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # [HP*G, HP*PBS]

        l_new = l_scr[:, 0][:, None] * alpha + jnp.sum(p, axis=1,
                                                       keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)          # [HP*G, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, (hp * g_sz, 128))
        l_scr[...] = jnp.broadcast_to(l_new, (hp * g_sz, 128))
        return carry

    lax.fori_loop(0, num_groups, body, 0, unroll=False)
    buf_idx_ref[0] = lax.rem(start_buf + num_groups, 2)

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
    fewer, larger DMAs). Default 16, 32 for 1-byte caches;
    INTELLILLM_PAGED_HP overrides for experiments."""
    import os
    default = 32 if k_cache.dtype.itemsize == 1 else 16
    return int(os.environ.get("INTELLILLM_PAGED_HP", default))


@functools.partial(
    jax.jit, static_argnames=("scale_static", ))
def _paged_attention_call(q_grouped, slopes, k_cache, v_cache, block_tables,
                          context_lens, *, scale_static: float):
    b, hkv, g, d = q_grouped.shape
    nb, _, bs, _ = k_cache.shape
    w = block_tables.shape[1]
    ppg = _largest_divisor(w, 16)
    hp = _largest_divisor(hkv, _default_hp(k_cache))

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


def paged_attention(
    q: jnp.ndarray,             # [B, 1, Hq, D]
    k_cache: jnp.ndarray,       # [NB, Hkv, BS, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32
    scale: float,
    alibi_slopes: Optional[jnp.ndarray] = None,
    return_lse: bool = False,
):
    """Decode-phase paged attention. Returns [B, 1, Hq, D] (and, with
    return_lse, the per-head logsumexp [B, Hq] for attention merging)."""
    import os

    from intellillm_tpu.utils import parse_env_flag
    raw = os.environ.get("INTELLILLM_PAGED_V4")
    if raw is not None and raw.strip():
        # The v3 twin this flag used to select was folded away; warn once
        # per call site so stale launch configs surface instead of
        # silently running a kernel the operator thinks they disabled.
        import warnings
        if parse_env_flag(raw) is False:
            warnings.warn(
                "INTELLILLM_PAGED_V4=0 no longer selects a v3 kernel — "
                "the v3/v4 pair was consolidated into one paged-attention "
                "kernel. Use INTELLILLM_USE_PALLAS=0 for the jnp "
                "reference path.")
    b, one, hq, d = q.shape
    if d % 128 != 0:
        # Mosaic DMA windows must be 128-aligned in the minor dimension, so
        # head sizes like 64/80 cannot be sliced out of the HBM pool; use
        # the jnp gather reference (these are the small-model head sizes).
        from intellillm_tpu.ops.attention import decode_attention_reference
        return decode_attention_reference(q, k_cache, v_cache, block_tables,
                                          context_lens, scale, alibi_slopes,
                                          return_lse=return_lse)
    hkv = k_cache.shape[1]
    g = hq // hkv
    q_grouped = q.reshape(b, hkv, g, d)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(hkv, g)
    else:
        slopes = jnp.zeros((hkv, g), jnp.float32)
    out, lse = _paged_attention_call(q_grouped, slopes, k_cache, v_cache,
                                     block_tables, context_lens,
                                     scale_static=float(scale))
    out = out.reshape(b, 1, hq, d)
    if return_lse:
        return out, lse.reshape(b, hq)
    return out


# Import-compat alias for callers of the pre-consolidation twin module's
# entry point (the kernels are one and the same now).
paged_attention_v4 = paged_attention
