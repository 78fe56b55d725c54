"""Fused cache-write + attend seam for the mixed token-budget dispatch.

`layers/attention.py` calls `ragged_fused_attention` for every
non-prompt (mixed/decode) step. The selection is trace-time
(`use_pallas_kernel("ragged")`), so the jit bucket keys never change
and the single `mixed` executable is preserved; on CPU — and on TPU for
head sizes that fail the 128-lane DMA alignment — the reference path
composes exactly the same primitives in exactly the same order as the
pre-fusion incumbent (`reshape_and_cache` scatter, then
`decode_attention_reference` gather), so greedy outputs are
bit-identical by construction. That composition is the golden oracle
the Pallas kernel is pinned against.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.dispatch import (shard_over_heads,
                                         use_pallas_kernel)
from intellillm_tpu.ops.kv_cache import reshape_and_cache

Arrays3 = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]


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
) -> Arrays3:
    """Write each row's K/V into the paged pool and causally attend over
    it (decode rows and prefill-chunk rows alike — chunk rows just carry
    `context_lens = position + 1`). Returns (out, k_cache, v_cache).
    `mesh` (multi-chip runs) shards the kernel over kv heads."""
    d = q.shape[-1]
    if use_pallas_kernel("ragged") and d % 128 == 0:
        from intellillm_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention)

        def kernel(q, k_new, v_new, k_cache, v_cache, slots, tables, ctx,
                   slopes):
            return ragged_paged_attention(q, k_new, v_new, k_cache, v_cache,
                                          slots, tables, ctx, scale, slopes)

        # The kernel's DMAs cannot cast, and its self-token read must
        # match a reference read of the just-written cache line — cast
        # to the cache dtype (e.g. fp8 KV quantization) outside.
        return shard_over_heads(
            kernel, mesh, k_cache.shape[1],
            in_head_dims=(2, 1, 1, 1, 1, None, None, None,
                          None if alibi_slopes is None else 0),
            out_head_dims=(2, 1, 1))(
                q, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
                k_cache, v_cache, slot_mapping, block_tables, context_lens,
                alibi_slopes)
    return ragged_fused_attention_reference(
        q, k_new, v_new, k_cache, v_cache, slot_mapping, block_tables,
        context_lens, scale, alibi_slopes)


def ragged_fused_attention_reference(
    q, k_new, v_new, k_cache, v_cache, slot_mapping, block_tables,
    context_lens, scale, alibi_slopes=None) -> Arrays3:
    """The incumbent composition, verbatim: scatter pass then paged
    gather-attention. Bit-equal to the pre-fusion hot path."""
    k_cache, v_cache = reshape_and_cache(k_new, v_new, k_cache, v_cache,
                                         slot_mapping)
    out = decode_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale, alibi_slopes)
    return out, k_cache, v_cache
