"""PagedAttention layer: cache write + phase dispatch.

Role parity: reference `vllm/model_executor/layers/attention.py`
(PagedAttention :22): writes new KV into the paged pool
(`cache_ops.reshape_and_cache`, :94-102), then prompt-phase attention
(xformers / Triton prefix kernel, :151-178) or decode-phase paged attention
(CUDA V1/V2 kernels, :230-302). MQA/GQA, ALiBi (:196-227), sliding window
(:131-133) supported.

TPU redesign: one functional layer; `is_prompt` is a static (trace-time)
flag so prefill and decode are separate XLA programs. The non-prompt
(mixed/decode) path goes through the fused cache-write + attend seam
(ops/ragged_attention.py): one Pallas kernel on TPU writes each row's K/V
into the pool inside the grid and attends over it, replacing the separate
reshape_and_cache scatter; the jnp reference composes the same scatter +
gather pair elsewhere. Prompt phases keep the explicit scatter followed by
the prefill kernels.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from intellillm_tpu.logger import init_logger
from intellillm_tpu.ops.attention import (context_attention_reference,
                                          decode_attention_reference,
                                          prefill_attention_reference)
from intellillm_tpu.ops.kv_cache import reshape_and_cache
from intellillm_tpu.ops.ragged_attention import ragged_fused_attention

logger = init_logger(__name__)

_SUPPORTED_HEAD_SIZES = (64, 80, 96, 112, 128, 256)

KVCache = Tuple[jnp.ndarray, jnp.ndarray]


@struct.dataclass
class AttentionMetadata:
    """Per-step batch metadata handed into the jitted step function.

    Shapes are bucket-padded by the ModelRunner so jit sees a bounded shape
    set. Equivalent of the reference's InputMetadata
    (`vllm/model_executor/input_metadata.py`).
    """
    # Static: selects the prefill vs decode program.
    is_prompt: bool = struct.field(pytree_node=False)
    # [B, L] (prefill) or [B, 1] (decode); flat slot = block*bs + offset,
    # PAD_SLOT_ID (-1) for padding.
    slot_mapping: jnp.ndarray = None
    # [B] total valid context length per sequence (incl. current tokens).
    context_lens: jnp.ndarray = None
    # [B, max_blocks_per_seq] physical block ids (decode / prefix-prefill).
    block_tables: Optional[jnp.ndarray] = None
    # [B] cached-prefix length per seq (prefix-cached prefill only).
    prefix_lens: Optional[jnp.ndarray] = None
    # Static: whether this prefill reuses cached prefix blocks.
    use_prefix: bool = struct.field(pytree_node=False, default=False)
    # Multi-step (fused) decode: tokens produced inside the fused loop live
    # in per-layer staging buffers, not the pool. `staged` switches the
    # layer to pool(read-only) + stage(read/write) attention; stage_index
    # is the current substep (traced scalar).
    staged: bool = struct.field(pytree_node=False, default=False)
    stage_index: Optional[jnp.ndarray] = None
    # Sequence-parallel prefill: (mesh, axis_name) — static; when set, the
    # prompt attention runs as ring attention with the sequence dim
    # sharded over that mesh axis (ops/ring_attention.py). The runner only
    # sets this for single-prompt, no-prefix, no-ALiBi, no-sliding-window
    # prefills past the configured length threshold.
    sp: Optional[tuple] = struct.field(pytree_node=False, default=None)
    # Static: the runner's device mesh, so the Pallas seams can run per
    # kv-head shard when it spans chips (ops/dispatch.shard_over_heads).
    mesh: Optional[object] = struct.field(pytree_node=False, default=None)


class PagedAttention:
    """Attention over the paged KV pool. Stateless; weights live in the
    caller's param tree."""

    def __init__(
        self,
        num_heads: int,
        head_size: int,
        scale: float,
        num_kv_heads: Optional[int] = None,
        sliding_window: Optional[int] = None,
        alibi_slopes=None,
    ) -> None:
        self.num_heads = num_heads
        self.head_size = head_size
        self.scale = scale
        self.num_kv_heads = num_kv_heads or num_heads
        self.sliding_window = sliding_window
        self.alibi_slopes = (jnp.asarray(alibi_slopes, jnp.float32)
                             if alibi_slopes is not None else None)
        assert self.num_heads % self.num_kv_heads == 0

    def __call__(
        self,
        query: jnp.ndarray,   # [B, L, Hq, D]
        key: jnp.ndarray,     # [B, L, Hkv, D]
        value: jnp.ndarray,   # [B, L, Hkv, D]
        kv_cache,             # KVCache, or (kp, vp, k_stage, v_stage) staged
        attn_metadata: AttentionMetadata,
    ):
        if attn_metadata.staged:
            return self._staged_decode(query, key, value, kv_cache,
                                       attn_metadata)
        b, l, hq, d = query.shape
        k_cache, v_cache = kv_cache

        flat_k = key.reshape(b * l, self.num_kv_heads, d)
        flat_v = value.reshape(b * l, self.num_kv_heads, d)
        slots = attn_metadata.slot_mapping.reshape(-1)

        if attn_metadata.is_prompt:
            # Prompt phase keeps the separate scatter pass: prompt kernels
            # read K/V from the live activations (and the pool for prefix
            # reuse), so there is nothing to fuse the write into.
            k_cache, v_cache = reshape_and_cache(flat_k, flat_v, k_cache,
                                                 v_cache, slots)
            if attn_metadata.use_prefix:
                new_lens = attn_metadata.context_lens - attn_metadata.prefix_lens
                out = context_attention_reference(
                    query, key, value, k_cache, v_cache,
                    attn_metadata.block_tables, attn_metadata.prefix_lens,
                    new_lens, self.scale, self.alibi_slopes,
                    self.sliding_window)
            elif attn_metadata.sp is not None:
                # Sequence-parallel prefill over the mesh seq axis.
                # Default: ring attention (ppermute K/V rotation, online
                # softmax, O(L/N) peak activations — scales to any
                # length). INTELLILLM_SP_MODE=ulysses switches to the
                # all-to-all layout (2 a2a hops + one dense attention per
                # head shard — fewer collectives while the full-sequence
                # KV still fits a chip and kv heads divide the axis).
                mesh, axis = attn_metadata.sp
                mode = os.environ.get("INTELLILLM_SP_MODE", "ring").lower()
                hkv = key.shape[2]
                if mode == "ulysses" and hkv % mesh.shape[axis] == 0:
                    from intellillm_tpu.ops.ulysses_attention import (
                        ulysses_attention)
                    out = ulysses_attention(query, key, value, mesh, axis,
                                            scale=self.scale, causal=True)
                else:
                    if mode == "ulysses":
                        logger.warning(
                            "INTELLILLM_SP_MODE=ulysses needs kv heads "
                            "(%d) divisible by the seq axis (%d); using "
                            "ring attention.", hkv, mesh.shape[axis])
                    from intellillm_tpu.ops.ring_attention import (
                        ring_attention)
                    out = ring_attention(query, key, value, mesh, axis,
                                         scale=self.scale, causal=True,
                                         head_axis="model")
            else:
                out = _prefill_dispatch(query, key, value,
                                        attn_metadata.context_lens,
                                        self.scale, self.sliding_window,
                                        self.alibi_slopes)
        else:
            # This branch also serves CHUNKED-CONTEXT PREFILL (mixed
            # steps, worker/model_runner._execute_mixed): each prefill
            # chunk arrives as flat rows with per-token context_lens =
            # position + 1. The fused seam writes every row's K/V into
            # the pool BEFORE its read (in-kernel on TPU, a separate
            # reshape_and_cache pass on the reference path), so a chunk-k
            # query at position p attends to chunks 0..k-1 (already paged
            # in from earlier steps) plus the in-flight chunk's rows <= p
            # — exact causal attention per sequence, one block table per
            # row, no separate chunked kernel needed.
            out, k_cache, v_cache = ragged_fused_attention(
                query, flat_k, flat_v, k_cache, v_cache, slots,
                attn_metadata.block_tables, attn_metadata.context_lens,
                self.scale, self.alibi_slopes, mesh=attn_metadata.mesh)
        return out, (k_cache, v_cache)

    def _staged_decode(self, query, key, value, kv_cache, attn_metadata):
        """Fused multi-step decode: pool is read-only; the substep's K/V go
        into the staging buffer at stage_index, attention merges the pool
        part (paged kernel, with logsumexp) and the stage part."""
        from intellillm_tpu.ops.attention import (merge_attention_parts,
                                                  staged_decode_attention)

        k_pool, v_pool, k_stage, v_stage = kv_cache
        k_idx = attn_metadata.stage_index

        # Write this substep's K/V ([B, 1, Hkv, D]) into stage slot k
        # (in-place dynamic-update-slice).
        k_stage = jax.lax.dynamic_update_slice_in_dim(
            k_stage, key.astype(k_stage.dtype), k_idx, axis=1)
        v_stage = jax.lax.dynamic_update_slice_in_dim(
            v_stage, value.astype(v_stage.dtype), k_idx, axis=1)

        out_pool, lse_pool = _decode_dispatch(
            query, k_pool, v_pool, attn_metadata.block_tables,
            attn_metadata.context_lens, self.scale, self.alibi_slopes,
            return_lse=True, mesh=attn_metadata.mesh)
        out_stage, lse_stage = staged_decode_attention(
            query, k_stage, v_stage, k_idx, self.scale)
        out = merge_attention_parts(out_pool, lse_pool, out_stage, lse_stage)
        return out, (k_pool, v_pool, k_stage, v_stage)


def model_uses_alibi(model) -> bool:
    """True if any PagedAttention layer in the model applies ALiBi.

    Derived from the layers themselves (not a per-model flag) so a new
    ALiBi model family cannot silently miss the fused multi-step decode
    clamp: the engine forces K=1 for ALiBi models because the staged scan
    holds context_lens constant across substeps."""
    seen = set()

    def walk(obj, depth) -> bool:
        if id(obj) in seen or depth > 4:
            return False
        seen.add(id(obj))
        if isinstance(obj, PagedAttention):
            return obj.alibi_slopes is not None
        if isinstance(obj, (list, tuple)):
            return any(walk(v, depth + 1) for v in obj)
        d = getattr(obj, "__dict__", None)
        if not isinstance(d, dict):
            return False
        return any(walk(v, depth + 1) for v in d.values())

    return walk(model, 0)


def _prefill_dispatch(query, key, value, context_lens, scale, sliding_window,
                      alibi_slopes):
    """Choose the prefill kernel: Pallas blockwise-causal flash attention
    on TPU (O(L) HBM traffic), padded-dense jnp reference elsewhere."""
    from intellillm_tpu.ops import dispatch
    if dispatch.use_pallas():
        from intellillm_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention(query, key, value, context_lens, scale,
                               sliding_window, alibi_slopes)
    return prefill_attention_reference(query, key, value, context_lens,
                                       scale, sliding_window, alibi_slopes)


def _decode_dispatch(q, k_cache, v_cache, block_tables, context_lens, scale,
                     alibi_slopes, return_lse: bool = False, mesh=None):
    """Choose the decode kernel: Pallas paged attention on TPU, jnp gather
    reference elsewhere (CPU tests / interpreters). `mesh` (multi-chip
    runs) shards the kernel over kv heads."""
    from intellillm_tpu.ops import dispatch
    if dispatch.use_pallas():
        from intellillm_tpu.ops.pallas.paged_attention import paged_attention

        def kernel(q, k_cache, v_cache, tables, ctx, slopes):
            # The kernel computes the logsumexp either way.
            return paged_attention(q, k_cache, v_cache, tables, ctx, scale,
                                   slopes, return_lse=True)

        out, lse = dispatch.shard_over_heads(
            kernel, mesh, k_cache.shape[1],
            in_head_dims=(2, 1, 1, None, None,
                          None if alibi_slopes is None else 0),
            out_head_dims=(2, 1))(
                q, k_cache, v_cache, block_tables, context_lens,
                alibi_slopes)
        return (out, lse) if return_lse else out
    return decode_attention_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale, alibi_slopes,
                                      return_lse=return_lse)
