"""Latent (MLA) attention layer: absorbed projections over the latent pool.

Multi-head latent attention caches, for each token and layer, one vector
`[c_kv | k_rope]` (`kv_lora_rank` + `qk_rope_head_dim` values) instead of
per-head keys and values. The published (expanded) form up-projects the
whole context in every step: `[k_nope | v]_h = c_kv W_kvb,h`. This layer
runs the ABSORBED form, equal in exact arithmetic, in which the
up-projection moves onto the query and the output:

    q~_h = q_nope,h W_UK,h^T                  (kv_lora_rank wide)
    s_h  = (q~_h . c_kv + q_rope,h . k_rope) * scale
    o^_h = sum_t softmax(s_h)_t c_kv,t        (kv_lora_rank wide)
    o_h  = o^_h W_UV,h

so a step reads each cached vector once and nothing of the context is
up-projected. The caller (the model) makes `q_lat = [q~ | q_rope]` and
applies `W_UV`; this layer owns the cache write, the two program shapes
(the `mixed` step and the staged K-step decode) and the seams
(`ops/mla_attention.py`).

Stored width: the cached vector and the query are zero-padded to whole
128-lane tiles (576 -> 640); zero lanes add nothing to a score and the
value is the first `kv_lora_rank` lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from intellillm_tpu.layers.attention import AttentionMetadata
from intellillm_tpu.ops.attention import (merge_attention_parts,
                                          staged_decode_attention)
from intellillm_tpu.ops.mla_attention import (mla_fused_attention,
                                              mla_pool_attention)


class LatentAttention:
    """Stateless; weights live in the caller's param tree."""

    def __init__(self, num_heads: int, kv_lora_rank: int, rope_dim: int,
                 scale: float) -> None:
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.rope_dim = rope_dim
        self.scale = scale
        self.width = kv_lora_rank + rope_dim
        self.stored_width = -(-self.width // 128) * 128
        # Never ALiBi (layers/attention.py model_uses_alibi looks for it).
        self.alibi_slopes = None

    def _pad(self, x: jnp.ndarray) -> jnp.ndarray:
        pad = self.stored_width - x.shape[-1]
        if pad == 0:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def __call__(
        self,
        q_lat: jnp.ndarray,    # [B, L, H, width]  [q~ | q_rope]
        kv_new: jnp.ndarray,   # [B, L, width]     [c_kv | k_rope]
        kv_cache,              # (pool,), or (pool, stage) when staged
        attn_metadata: AttentionMetadata,
    ):
        """Returns (o^ [B, L, H, kv_lora_rank], kv_cache)."""
        mesh = attn_metadata.mesh
        if mesh is not None and mesh.devices.size > 1:
            raise NotImplementedError(
                "latent attention has no head axis to shard the pool "
                "over; tensor parallelism is not supported for it yet")
        b, l, h, _ = q_lat.shape
        q = self._pad(q_lat).reshape(b * l, h, self.stored_width)
        new = self._pad(kv_new).reshape(b * l, self.stored_width)
        with jax.named_scope("mla_attention"):
            if attn_metadata.staged:
                out, kv_cache = self._staged(q, new, kv_cache,
                                             attn_metadata)
            else:
                (pool, ) = kv_cache
                out, pool = mla_fused_attention(
                    q, new, pool, attn_metadata.slot_mapping.reshape(-1),
                    attn_metadata.block_tables, attn_metadata.context_lens,
                    self.scale, self.kv_lora_rank)
                kv_cache = (pool, )
        return out.reshape(b, l, h, self.kv_lora_rank), kv_cache

    def _staged(self, q, new, kv_cache, attn_metadata):
        """Fused multi-step decode: the pool is read-only; the substep's
        vector goes into the staging buffer at stage_index, and the pool
        part (kernel, with logsumexp) and the stage part are merged."""
        pool, stage = kv_cache                 # stage [B, C, 1, Dk]
        k_idx = attn_metadata.stage_index
        stage = jax.lax.dynamic_update_slice_in_dim(
            stage, new[:, None, None, :].astype(stage.dtype), k_idx, axis=1)
        out_pool, lse_pool = mla_pool_attention(
            q, pool, attn_metadata.block_tables,
            attn_metadata.context_lens, self.scale, self.kv_lora_rank)
        out_stage, lse_stage = staged_decode_attention(
            q[:, None], stage, stage, k_idx, self.scale)
        out = merge_attention_parts(
            out_pool[:, None], lse_pool,
            out_stage[..., :self.kv_lora_rank], lse_stage)
        return out[:, 0], (pool, stage)
