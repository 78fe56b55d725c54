"""Seams of per-head attention over the paged KV pool.

`layers/attention.py` calls these and nothing below them. Each asks
`ops/dispatch.use_kernel` once, with the one thing the kernels need of
the shapes (a head size of whole 128-lane tiles: Mosaic DMA windows
cannot slice 64 or 80 lanes out of the HBM pool), and runs its Pallas
kernel or its jnp oracle; the choice is trace-time, so the jit bucket
keys never change.

- `ragged_fused_attention` (label `ragged`): every row of a `mixed` step.
  The oracle composes the `reshape_and_cache` scatter and the
  `decode_attention_reference` gather, the pair the kernel fuses; the
  kernel is pinned against that composition. `run_starts` tells the
  kernel which rows continue the row before them (a prompt chunk's): a
  run's rows walk their paged context once a tile (`ragged_walkers`
  says which rows those walks are, for the runner's counters).
- `paged_pool_attention` (label `paged`): the pool part of a K-step
  decode substep, with the logsumexp the layer merges the staged tokens'
  part by.

`walk_extent` is the page walk's rule of both kernels, here for the
runner's walked-tokens counter.

A sliding window is a static argument of both seams (`window`, tokens):
a row whose query stands at position p attends to positions
`p - window < j <= p`, so its first visible position is
`window_lo(context + ahead, window)`, which the kernels walk from
(`ops/pallas/paged_attention.py` `_page_walker`; in the device trace the
windowed launches are `_ragged_call_window` and
`_paged_attention_call_window`). The block table is indexed by logical
block as ever; entries below the window's first page may be stale
(`core/block_manager.py`) and are never read by the kernels. With
`window=None` the programs are what they were.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.dispatch import shard_over_heads, use_kernel
from intellillm_tpu.ops.kv_cache import reshape_and_cache
from intellillm_tpu.ops.pallas.paged_attention import (  # noqa: F401
    paged_attention, walk_extent)
from intellillm_tpu.ops.pallas.ragged_paged_attention import (
    launch_rows, ragged_paged_attention, tile_rows, tiles_many, whole_spans)

Arrays3 = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]


def window_lo(query_pos_plus_one, window: int):
    """First position a query at `query_pos_plus_one - 1` sees under a
    window of `window` tokens (works on numpy and jnp alike)."""
    return (query_pos_plus_one - window).clip(0)


def ragged_walkers(run_starts, live, table_width: int,
                   queries_per_kv: int):
    """(which rows of a `mixed` step walk their paged context, which
    rows are a tile alone), numpy, for the host's counters: the kernel
    walks once for a whole span of rows of one run (`whole_spans`: the
    span's first row walks, the others ride along) and once a row
    everywhere else. `walk_extent` of a walker's context is what its
    tile moves."""
    n = run_starts.shape[0]
    pad = -n % 8                # the launch makes up whole sublane tiles
    rows = launch_rows(n + pad, table_width)
    t = tile_rows(rows, queries_per_kv)
    alone = np.ones(n, bool)
    if tiles_many(rows, queries_per_kv):
        alone = ~np.repeat(whole_spans(
            np.pad(run_starts, (0, pad), constant_values=1),
            np.pad(live, (0, pad)), t, xp=np), t)[:n]
    return alone | (np.arange(n) % t == 0), alone


def ragged_fused_attention(
    q: jnp.ndarray,             # [B, 1, Hq, D] flat mixed batch
    k_new: jnp.ndarray,         # [B, Hkv, D] new K per row (model dtype)
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,       # [NB, Hkv, BS, D]
    v_cache: jnp.ndarray,
    slot_mapping: jnp.ndarray,  # [B] i32 flat physical slots, -1 = pad
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32, counting the new token
    scale: float,
    alibi_slopes: Optional[jnp.ndarray] = None,
    mesh=None,
    window: Optional[int] = None,
    run_starts: Optional[jnp.ndarray] = None,
) -> Arrays3:
    """Write each row's K/V into the paged pool and causally attend over
    it (decode rows and prefill-chunk rows alike — chunk rows just carry
    `context_lens = position + 1`). Returns (out, k_cache, v_cache).
    `mesh` (multi-chip runs) shards the kernel over kv heads. `window`:
    a row attends to its last `window` tokens, its own included.
    `run_starts` [B] i32: 0 for a row that continues the row before it
    (the same sequence and table, the next position), else 1; None: no
    row is known to."""
    lo = None if window is None else window_lo(context_lens, window)
    if use_kernel("ragged", q.shape[-1] % 128 == 0):
        def kernel(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx,
                   starts, slopes, *lo):
            return ragged_paged_attention(q, k_new, v_new, k_cache, v_cache,
                                          slots, tables, ctx, scale, slopes,
                                          *lo, run_starts=starts)

        # The kernel's DMAs cannot cast, and its self-token read must
        # match a reference read of the just-written cache line — cast
        # to the cache dtype (e.g. fp8 KV quantization) outside.
        return shard_over_heads(
            kernel, mesh, k_cache.shape[1],
            in_head_dims=(2, 1, 1, 1, 1, None, None, None, None,
                          None if alibi_slopes is None else 0)
            + (() if lo is None else (None, )),
            out_head_dims=(2, 1, 1))(
                q, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
                k_cache, v_cache, slot_mapping, block_tables, context_lens,
                run_starts, alibi_slopes, *(() if lo is None else (lo, )))
    return ragged_fused_attention_reference(
        q, k_new, v_new, k_cache, v_cache, slot_mapping, block_tables,
        context_lens, scale, alibi_slopes, lo=lo)


def ragged_fused_attention_reference(
    q, k_new, v_new, k_cache, v_cache, slot_mapping, block_tables,
    context_lens, scale, alibi_slopes=None, lo=None) -> Arrays3:
    """The incumbent composition, verbatim: scatter pass then paged
    gather-attention. Bit-equal to the pre-fusion hot path."""
    k_cache, v_cache = reshape_and_cache(k_new, v_new, k_cache, v_cache,
                                         slot_mapping)
    out = decode_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale, alibi_slopes,
                                     lo=lo)
    return out, k_cache, v_cache


def paged_pool_attention(
    q: jnp.ndarray,             # [B, 1, Hq, D]
    k_cache: jnp.ndarray,       # [NB, Hkv, BS, D], read-only
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32 tokens in the pool
    scale: float,
    alibi_slopes: Optional[jnp.ndarray] = None,
    mesh=None,
    window: Optional[int] = None,
    query_ahead=0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attention of one query token a row over what the pool holds of its
    sequence. Returns (out [B, 1, Hq, D], logsumexp [B, Hq]). `mesh`
    (multi-chip runs) shards the kernel over kv heads. `window`: the
    query, which stands `query_ahead` positions past the pool's last
    token (a staged decode's substep k: k + 1), attends to its last
    `window` tokens; those of them the pool holds are this call's."""
    lo = (None if window is None
          else window_lo(context_lens + query_ahead, window))
    if use_kernel("paged", q.shape[-1] % 128 == 0):
        def kernel(q, k_cache, v_cache, tables, ctx, slopes, lo=None):
            return paged_attention(q, k_cache, v_cache, tables, ctx, scale,
                                   slopes, return_lse=True, lo=lo)

        return shard_over_heads(
            kernel, mesh, k_cache.shape[1],
            in_head_dims=(2, 1, 1, None, None,
                          None if alibi_slopes is None else 0)
            + (() if lo is None else (None, )),
            out_head_dims=(2, 1))(
                q, k_cache, v_cache, block_tables, context_lens,
                alibi_slopes, *(() if lo is None else (lo, )))
    return decode_attention_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale, alibi_slopes,
                                      return_lse=True, lo=lo)
