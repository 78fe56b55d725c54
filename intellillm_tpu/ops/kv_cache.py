"""KV-cache block-pool ops.

Role parity: reference `csrc/cache_kernels.cu` — `reshape_and_cache` (:221,
scatter of new K/V into the paged pool), `copy_blocks` (:88, CoW block
copies), `swap_blocks` (:14, HBM↔host moves). On TPU these are functional
jnp scatters/gathers on the pool arrays: under jit with buffer donation XLA
performs them in place; swaps are `jax.device_put/device_get` transfers.

Cache layout (per layer):
    k_cache, v_cache: [num_blocks, num_kv_heads, block_size, head_size]

The kv-head axis sits ahead of (block_size, head_size) so that a Pallas
block of one (physical block, head) pair is a [block_size, head_size] tile
— (16, 128) for bf16 at head_size 128, exactly the minimum bf16 tile — and
so the pool shards over the mesh "model" axis on dim 1.

Padding: PAD_SLOT_ID (-1) rows must NOT scatter (negative indices wrap in
XLA scatter semantics — they'd silently corrupt the last pool block); they
are remapped to an out-of-bounds sentinel which mode="drop" discards.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

PAD_SLOT_ID = -1


def scatter_rows(
    rows: jnp.ndarray,     # [num_tokens, H, D]
    pool: jnp.ndarray,     # [num_blocks, H, block_size, D]
    slot_mapping: jnp.ndarray,  # [num_tokens] i32; slot = block*block_size+off
) -> jnp.ndarray:
    """Scatter new rows into one paged pool at their assigned slots (a K
    pool, a V pool, or a latent pool with H = 1)."""
    num_blocks, num_heads, block_size, _ = pool.shape
    # Negative (padding) slots → OOB sentinel, dropped by the scatter.
    safe_slots = jnp.where(slot_mapping < 0, num_blocks * block_size,
                           slot_mapping)
    block_idx = safe_slots // block_size           # [T]
    off_idx = safe_slots % block_size              # [T]
    head_idx = jnp.arange(num_heads, dtype=slot_mapping.dtype)
    return pool.at[block_idx[:, None], head_idx[None, :],
                   off_idx[:, None]].set(rows.astype(pool.dtype),
                                         mode="drop")


def reshape_and_cache(
    key: jnp.ndarray,      # [num_tokens, num_kv_heads, head_size]
    value: jnp.ndarray,    # [num_tokens, num_kv_heads, head_size]
    k_cache: jnp.ndarray,  # [num_blocks, H, block_size, D]
    v_cache: jnp.ndarray,
    slot_mapping: jnp.ndarray,  # [num_tokens] i32; slot = block*block_size+off
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new K/V rows into the paged pool at their assigned slots."""
    return (scatter_rows(key, k_cache, slot_mapping),
            scatter_rows(value, v_cache, slot_mapping))


def commit_staged_chunk(
    stages: Tuple[jnp.ndarray, ...],   # each [B, C, Hkv, D]
    pools: Tuple[jnp.ndarray, ...],    # each [NB, Hkv, BS, D]
    start_pos: jnp.ndarray,     # [B] i32: pool position of stage slot 0
    n_valid: jnp.ndarray,       # [B] i32: staged tokens to commit (0=pad)
    block_tables: jnp.ndarray,  # [B, W] i32
) -> Tuple[jnp.ndarray, ...]:
    """Commit a fused-decode staging chunk into the pool, page-granular.
    `stages`/`pools` are a layer's (K, V) pair or its one latent pool.

    Role parity: reference `csrc/cache_kernels.cu:221` (reshape_and_cache),
    specialized to the chunk commit where each sequence writes C
    *contiguous* positions. The generic `reshape_and_cache` scatter
    expands to one ~256 B row write per (token, kv-head) — at B=64, C=16,
    Hkv=32 that is 32k latency-bound row DMAs ≈ 2.2 ms per (layer, K/V)
    on v5e, ~70% of the chunked fused decode step. Contiguity bounds the
    pages a chunk touches to C/BS+1 per sequence, so this path instead
    gathers those whole pages, merges the staged tokens in registers (a
    one-hot einsum computes the dynamic position shift exactly — one f32
    product per element), and scatters full [Hkv, BS, D] pages back:
    row-DMA count drops ~250x and every byte moved is a full-page burst.

    Safety: every page is written at most once — pad rows, overflow
    columns past the table width, and pages beyond the last valid token
    redirect to the out-of-bounds sentinel and are dropped (`mode="drop"`),
    and block ownership (copy-on-write gives running sequences exclusive
    tail pages) rules out cross-sequence duplicates.
    """
    b, c, hkv, d = stages[0].shape
    nb, _, bs, _ = pools[0].shape
    w = block_tables.shape[1]
    npages = (c + bs - 1) // bs + 1

    j0 = start_pos // bs
    cols = j0[:, None] + jnp.arange(npages, dtype=jnp.int32)[None, :]
    # A page is live iff the sequence is real, the column is inside the
    # table, and the page overlaps [start, start + n_valid).
    last_page = (start_pos + jnp.maximum(n_valid, 1) - 1) // bs
    live = ((n_valid[:, None] > 0) & (cols < w) &
            (cols <= last_page[:, None]))                    # [B, P]
    page_ids = jnp.take_along_axis(block_tables,
                                   jnp.clip(cols, 0, w - 1), axis=1)
    gather_ids = jnp.where(live, jnp.clip(page_ids, 0, nb - 1), 0)

    page_start = cols * bs
    shift = start_pos[:, None] - page_start                  # [B, P]
    o = jnp.arange(bs, dtype=jnp.int32)[None, None, :]
    t = o - shift[:, :, None]                                # [B, P, BS]
    mask = (t >= 0) & (t < n_valid[:, None, None]) & live[:, :, None]
    onehot = ((t[..., None] == jnp.arange(c, dtype=jnp.int32)) &
              mask[..., None]).astype(jnp.float32)           # [B, P, BS, C]

    def merge(stage, pool):
        cur = pool[gather_ids]                               # [B,P,H,BS,D]
        sel = jnp.einsum("bpoc,bchd->bphod", onehot,
                         stage.astype(jnp.float32))
        merged = jnp.where(mask[:, :, None, :, None],
                           sel.astype(pool.dtype), cur)
        scatter_ids = jnp.where(live, page_ids, nb)          # OOB → drop
        return pool.at[scatter_ids].set(merged, mode="drop")

    return tuple(merge(stage, pool) for stage, pool in zip(stages, pools))


def commit_staged_chunk_by_layer(
    stages: Tuple[jnp.ndarray, ...],   # ([B, C, layers, D], )
    pools: Tuple[jnp.ndarray, ...],    # ([NB, layers, BS, D], )
    start_pos: jnp.ndarray,
    n_valid: jnp.ndarray,
    block_tables: jnp.ndarray,
    group: int = 8,
) -> Tuple[jnp.ndarray, ...]:
    """`commit_staged_chunk` for a latent pool that holds every layer of
    the model where a per-head pool has its heads
    (`ModelConfig.get_packed_latent_layers`). A "page" of all 40 layers
    is 819 KB, and XLA's gather of such rows cuts the WHOLE pool in two
    along its lanes first (2.2 + 1.5 GB of temporaries, compiled for a
    described v5e); so the layers go as rows instead: the pool viewed as
    `[NB * layers, 1, BS, D]` (the same bytes), layer `l`'s block `b` at
    `b * layers + l`, `group` layers a call (each call's temporaries are
    its own pages: 128 rows x 8 layers x 2 pages x 20 KB x (2 + 4 + 2 B
    a value) = 170 MB, not 840)."""
    (stage, ), (pool, ) = stages, pools
    b, c, layers, d = stage.shape
    nb, _, bs, _ = pool.shape
    flat = pool.reshape(nb * layers, 1, bs, d)
    for first in range(0, layers, group):
        g = min(group, layers - first)
        of = jnp.arange(first, first + g, dtype=jnp.int32)
        rows = stage[:, :, first:first + g].transpose(2, 0, 1, 3).reshape(
            g * b, c, 1, d)
        tables = (block_tables[None] * layers + of[:, None, None]).reshape(
            g * b, -1)
        (flat, ) = commit_staged_chunk(
            (rows, ), (flat, ), jnp.tile(start_pos, g), jnp.tile(n_valid, g),
            tables)
    return (flat.reshape(pool.shape), )


def gather_kv_for_attention(
    cache: jnp.ndarray,          # [NB, H, BS, D]
    block_tables: jnp.ndarray,   # [B, W] i32
) -> jnp.ndarray:
    """Gather per-sequence context: returns [B, W*BS, H, D] (token-major)."""
    b, w = block_tables.shape
    nb, h, bs, d = cache.shape
    g = cache[block_tables]              # [B, W, H, BS, D]
    g = jnp.swapaxes(g, 2, 3)            # [B, W, BS, H, D]
    return g.reshape(b, w * bs, h, d)


def _pad_indices(idx: List[int], sentinel: int) -> "np.ndarray":
    """Pad an index list to the next power of two with an out-of-bounds
    sentinel so jit compiles a bounded set of shapes and extra rows drop."""
    import numpy as np

    n = max(len(idx), 1)
    padded_n = 1 << (n - 1).bit_length()
    out = np.full(padded_n, sentinel, np.int32)
    out[:len(idx)] = idx
    return out


@functools.partial(jax.jit, donate_argnums=(0, ))
def _copy_blocks_jit(kv_caches, src_idx, dst_idx):
    # A layer's cache is (K, V) or the 1-tuple (latent pool,).
    return [tuple(pool.at[dst_idx].set(pool[src_idx], mode="drop")
                  for pool in pools) for pools in kv_caches]


def copy_blocks(
    kv_caches: List[Tuple[jnp.ndarray, ...]],
    src_to_dsts: Dict[int, List[int]],
) -> List[Tuple[jnp.ndarray, ...]]:
    """Copy-on-write block copies, applied to every layer's pool.

    Runs as one donated jit call so XLA updates the pools in place (an
    eager .at[].set would rewrite every pool array in full each step)."""
    if not src_to_dsts:
        return kv_caches
    srcs: List[int] = []
    dsts: List[int] = []
    for src, dst_list in src_to_dsts.items():
        for dst in dst_list:
            srcs.append(src)
            dsts.append(dst)
    num_blocks = kv_caches[0][0].shape[0]
    src_idx = jnp.asarray(_pad_indices(srcs, 0))  # clamped gather rows are
    dst_idx = jnp.asarray(_pad_indices(dsts, num_blocks))  # dropped on write
    return _copy_blocks_jit(kv_caches, src_idx, dst_idx)


@jax.jit
def _gather_blocks(cache: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return cache[idx]


@functools.partial(jax.jit, donate_argnums=(0, ))
def _scatter_blocks_jit(cache, rows, dst_idx):
    return cache.at[dst_idx].set(rows, mode="drop")


def swap_blocks(
    src_cache: jnp.ndarray,
    dst_cache,
    src_to_dst: Dict[int, int],
    direction: str,
):
    """Move whole blocks between the HBM pool and the host swap pool.

    direction="out": src is the device pool (jnp), dst a host numpy pool.
    direction="in":  src is the host numpy pool, dst the device pool
    (donated → in-place scatter).
    Returns the updated destination pool.
    """
    import numpy as np

    srcs = list(src_to_dst.keys())
    dsts = list(src_to_dst.values())
    if direction == "out":
        idx = _pad_indices(srcs, 0)
        gathered = np.asarray(_gather_blocks(src_cache, jnp.asarray(idx)))
        dst_cache[np.asarray(dsts)] = gathered[:len(dsts)]
        return dst_cache
    elif direction == "in":
        num_blocks = dst_cache.shape[0]
        idx = _pad_indices(srcs, 0)          # host gather: any valid row
        rows = jnp.asarray(np.ascontiguousarray(src_cache[idx]))
        dst_idx = jnp.asarray(_pad_indices(dsts, num_blocks))
        return _scatter_blocks_jit(dst_cache, rows, dst_idx)
    raise ValueError(f"Unknown swap direction: {direction}")
