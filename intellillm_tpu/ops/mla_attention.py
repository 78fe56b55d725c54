"""Seams for latent (MLA) attention over the latent pool.

The absorbed form (`layers/mla_attention.py`) makes attention over a
latent cache multi-query attention with one shared key a token: H query
heads of width `Dk` against the stored vector `[c_kv | k_rope | 0-pad]`,
the value being that vector's first `Dv` lanes. Two seams, selected at
trace time (`use_kernel("mla", ...)`, dispatch labels `pallas:mla` /
`reference:mla`):

- `mla_fused_attention`: the `mixed` program — write every row's new
  latent vector into the pool, then attend causally over it (decode rows
  and prefill-chunk rows alike).
- `mla_pool_attention`: the K-step decode programs' pool part, with the
  logsumexp the layer needs to merge the staged tokens' part.

The jnp references compose the per-head references (`ops/attention.py`)
with the pool given as both K and V and one kv head: they are the oracle
the Pallas kernels (`ops/pallas/mla_attention.py`) are held to. Those
kernels' page walk is the per-head kernels' (`ops/ragged_attention.py`
`walk_extent`, which the runner's walked-tokens counter reads for every
cache kind).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.dispatch import use_kernel
from intellillm_tpu.ops.kv_cache import scatter_rows
from intellillm_tpu.ops.pallas.mla_attention import (
    mla_paged_attention, mla_ragged_paged_attention)


def _use_kernel(pool: jnp.ndarray, v_width: int) -> bool:
    """Mosaic DMA windows are whole 128-lane tiles: the stored width
    always is (`ModelConfig.get_latent_cache_width`); a `kv_lora_rank`
    that is not serves from the reference, under its label."""
    return use_kernel("mla", pool.shape[-1] % 128 == 0
                      and v_width % 128 == 0)


def mla_fused_attention(
    q: jnp.ndarray,             # [B, H, Dk] absorbed query, zero-padded
    new: jnp.ndarray,           # [B, Dk] new latent rows (model dtype)
    pool: jnp.ndarray,          # [NB, 1, BS, Dk]
    slot_mapping: jnp.ndarray,  # [B] i32 flat physical slots, -1 = pad
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32, counting the new token
    scale: float,
    v_width: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (attended latent [B, H, v_width], pool)."""
    if _use_kernel(pool, v_width):
        return mla_ragged_paged_attention(
            q, new.astype(pool.dtype), pool, slot_mapping, block_tables,
            context_lens, scale, v_width)
    return mla_fused_attention_reference(q, new, pool, slot_mapping,
                                         block_tables, context_lens, scale,
                                         v_width)


def mla_fused_attention_reference(q, new, pool, slot_mapping, block_tables,
                                  context_lens, scale, v_width):
    pool = scatter_rows(new[:, None, :], pool, slot_mapping)
    out = decode_attention_reference(q[:, None], pool, pool, block_tables,
                                     context_lens, scale)
    return out[:, 0, :, :v_width], pool


def mla_pool_attention(
    q: jnp.ndarray,             # [B, H, Dk]
    pool: jnp.ndarray,          # [NB, 1, BS, Dk]
    block_tables: jnp.ndarray,  # [B, W] i32
    context_lens: jnp.ndarray,  # [B] i32 tokens in the pool
    scale: float,
    v_width: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (attended latent [B, H, v_width], logsumexp [B, H])."""
    if _use_kernel(pool, v_width):
        return mla_paged_attention(q, pool, block_tables, context_lens,
                                   scale, v_width)
    return mla_pool_attention_reference(q, pool, block_tables,
                                        context_lens, scale, v_width)


def mla_pool_attention_reference(q, pool, block_tables, context_lens, scale,
                                 v_width):
    out, lse = decode_attention_reference(q[:, None], pool, pool,
                                          block_tables, context_lens, scale,
                                          return_lse=True)
    return out[:, 0, :, :v_width], lse
