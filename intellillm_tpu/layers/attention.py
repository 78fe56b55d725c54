"""PagedAttention layer: the two cases a serving step has.

Role parity: reference `vllm/model_executor/layers/attention.py`
(PagedAttention :22), which writes new KV into the paged pool
(`cache_ops.reshape_and_cache`, :94-102) and then runs a prompt-phase or
a decode-phase kernel. MQA/GQA and ALiBi (:196-227) supported.

TPU redesign: one functional layer with no phase. Every row of a `mixed`
step (a decode token or one token of a prompt's chunk) goes through the
fused cache-write + attend seam (`ops/ragged_attention.py`
`ragged_fused_attention`); the K-step decode programs keep the pool
read-only and merge its part (`paged_pool_attention`, same module) with
the staged tokens' part. Which kernel serves a seam is the seam's
business: this module names none.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from intellillm_tpu.ops.attention import (merge_attention_parts,
                                          staged_decode_attention)
from intellillm_tpu.ops.ragged_attention import (paged_pool_attention,
                                                 ragged_fused_attention)

KVCache = Tuple[jnp.ndarray, jnp.ndarray]


@struct.dataclass
class AttentionMetadata:
    """Per-step batch metadata handed into the jitted step function.

    Shapes are bucket-padded by the ModelRunner so jit sees a bounded shape
    set. Equivalent of the reference's InputMetadata
    (`vllm/model_executor/input_metadata.py`).
    """
    # [B, 1] flat slot = block*bs + offset of each row's new token, a slot
    # past the pool for a pad row; None when staged.
    slot_mapping: Optional[jnp.ndarray] = None
    # [B] valid context length per row (incl. the row's own token; staged:
    # tokens in the pool).
    context_lens: jnp.ndarray = None
    # [B, max_blocks_per_seq] physical block ids.
    block_tables: Optional[jnp.ndarray] = None
    # Multi-step (fused) decode: tokens produced inside the fused loop live
    # in per-layer staging buffers, not the pool. `staged` switches the
    # layer to pool(read-only) + stage(read/write) attention; stage_index
    # is the current substep (traced scalar).
    staged: bool = struct.field(pytree_node=False, default=False)
    stage_index: Optional[jnp.ndarray] = None
    # Static: the runner's device mesh, so the Pallas seams can run per
    # kv-head shard when it spans chips (ops/dispatch.shard_over_heads).
    mesh: Optional[object] = struct.field(pytree_node=False, default=None)
    # [B] the state slot whose sequence each row continues (models with
    # "state" layers, layers/short_conv.py); a slot past the pool for a
    # pad row, as slot_mapping does. None for every other model.
    state_slots: Optional[jnp.ndarray] = None
    # The window group's table and slots (a model with "kv_window" layers
    # beside "kv" ones, `ModelConfig.get_layer_cache_kinds`): a layer
    # with a sliding window reads these instead of `block_tables` /
    # `slot_mapping`. None for every other model, one whose every layer
    # has the window included (its one group is the windowed one).
    window_block_tables: Optional[jnp.ndarray] = None
    window_slot_mapping: Optional[jnp.ndarray] = None
    # [B] i32, a `mixed` step: 0 for a row that continues the row before
    # it (the next position of the same sequence: a prompt chunk's rows
    # after its first), 1 for every other row. The ragged seam's kernel
    # walks a run's paged context once a tile of its rows. None: no row
    # is known to continue another.
    run_starts: Optional[jnp.ndarray] = None
    # [B] bool: which rows of the step are a sequence's (the runner's own
    # test of a pad row; staged, `context_lens` counts the pool's tokens
    # and is 0 for a live row whose first token is still in the stage).
    # The routed experts compute nothing for the others
    # (layers/moe.py `moe_ffn`). None: every row is live.
    live_rows: Optional[jnp.ndarray] = None


class PagedAttention:
    """Attention over the paged KV pool. Stateless; weights live in the
    caller's param tree."""

    def __init__(
        self,
        num_heads: int,
        head_size: int,
        scale: float,
        num_kv_heads: Optional[int] = None,
        alibi_slopes=None,
        sliding_window: Optional[int] = None,
    ) -> None:
        """`sliding_window`: a query attends to its last `sliding_window`
        tokens, its own included (None: to its whole context)."""
        self.sliding_window = sliding_window
        self.num_heads = num_heads
        self.head_size = head_size
        self.scale = scale
        self.num_kv_heads = num_kv_heads or num_heads
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
        # A pool whose rows are wider than the head (the head padded to
        # whole 128-lane tiles, `ModelConfig.get_cache_row_shape`): q, k
        # and v take the pool's width with zeros, which add nothing to a
        # score and come back as zeros cut off the output, so the seams
        # and their kernels see a head of whole tiles.
        head = query.shape[-1]
        pad = kv_cache[0].shape[-1] - head
        if pad:
            query, key, value = (
                jnp.pad(x, ((0, 0), ) * (x.ndim - 1) + ((0, pad), ))
                for x in (query, key, value))
        out, kv_cache = self._attend(query, key, value, kv_cache,
                                     attn_metadata)
        return (out[..., :head] if pad else out), kv_cache

    def _attend(self, query, key, value, kv_cache, attn_metadata):
        if attn_metadata.staged:
            return self._staged_decode(query, key, value, kv_cache,
                                       attn_metadata)
        # Each row is one token: a decode row, or one position of a
        # prompt's chunk carrying context_lens = position + 1. The seam
        # writes every row's K/V into the pool before any row reads it, so
        # a chunk's query at position p attends to the chunks already
        # paged in plus the in-flight rows <= p: exact causal attention
        # per sequence, one block table per row.
        b, l, _, d = query.shape
        k_cache, v_cache = kv_cache
        tables, slots = self._group_tables(attn_metadata)
        out, k_cache, v_cache = ragged_fused_attention(
            query, key.reshape(b * l, self.num_kv_heads, d),
            value.reshape(b * l, self.num_kv_heads, d), k_cache, v_cache,
            slots.reshape(-1), tables, attn_metadata.context_lens,
            self.scale, self.alibi_slopes, mesh=attn_metadata.mesh,
            run_starts=attn_metadata.run_starts, **self._window_kwargs())
        return out, (k_cache, v_cache)

    def _group_tables(self, attn_metadata):
        """(block tables, slot mapping) of this layer's cache group."""
        if (self.sliding_window is not None
                and attn_metadata.window_block_tables is not None):
            return (attn_metadata.window_block_tables,
                    attn_metadata.window_slot_mapping)
        return attn_metadata.block_tables, attn_metadata.slot_mapping

    def _window_kwargs(self, **more):
        """The seams' window arguments; none for a layer without one, so
        that its calls are what they were."""
        if self.sliding_window is None:
            return {}
        return dict(window=self.sliding_window, **more)

    def _staged_decode(self, query, key, value, kv_cache, attn_metadata):
        """Fused multi-step decode: pool is read-only; the substep's K/V go
        into the staging buffer at stage_index, attention merges the pool
        part (paged kernel, with logsumexp) and the stage part."""
        k_pool, v_pool, k_stage, v_stage = kv_cache
        k_idx = attn_metadata.stage_index

        # Write this substep's K/V ([B, 1, Hkv, D]) into stage slot k
        # (in-place dynamic-update-slice).
        k_stage = jax.lax.dynamic_update_slice_in_dim(
            k_stage, key.astype(k_stage.dtype), k_idx, axis=1)
        v_stage = jax.lax.dynamic_update_slice_in_dim(
            v_stage, value.astype(v_stage.dtype), k_idx, axis=1)

        # Under a window the substep's query stands k + 1 positions past
        # the pool's last token, so the window's lower edge moves a token
        # a substep; the stage's tokens (at most a staging chunk, which
        # the runner holds to the window) are all inside it.
        out_pool, lse_pool = paged_pool_attention(
            query, k_pool, v_pool, self._group_tables(attn_metadata)[0],
            attn_metadata.context_lens, self.scale, self.alibi_slopes,
            mesh=attn_metadata.mesh,
            **self._window_kwargs(query_ahead=k_idx + 1))
        out_stage, lse_stage = staged_decode_attention(
            query, k_stage, v_stage, k_idx, self.scale)
        out = merge_attention_parts(out_pool, lse_pool, out_stage, lse_stage)
        return out, (k_pool, v_pool, k_stage, v_stage)


class DifferentialAttention:
    """Differential attention (arXiv:2410.05258) around the seams above,
    which it leaves as they are.

    Heads come in pairs: query heads `2i, 2i + 1` (`q1_i`, `q2_i`) and K/V
    heads `2j, 2j + 1` (`k1_j, k2_j`, `v1_j, v2_j`), `j = i // (query
    pairs a K/V pair)`, each `d` wide:

        a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2]
        a2 = softmax(q2 k2^T / sqrt(d)) [v1 | v2]               (2 d wide)
        out = (1 - lambda_init) RMSNorm_{2d}(a1 - lambda a2) * w

    The fold: K/V heads `2j, 2j + 1` side by side ARE one stored head of
    `2 d` (`[k1 | k2]`, `[v1 | v2]`: a plain reshape, and the pool's row
    `ModelConfig.get_cache_row_shape` gives), `q1` enters as `[q1 | 0]` and
    `q2` as `[0 | q2]`, and plain grouped-query attention of all the query
    heads over the stored heads at scale `d ** -0.5` returns `a1` on the
    even heads and `a2` on the odd ones: the zeros drop the other half's
    products from a score, and the values are both halves as they are
    wanted. At `d` = 64 the stored head is 128 lanes: the kernels run
    without padding.

    A layer that reads another layer's pool (a cross layer) calls this
    with that layer's `key`, `value` and cache: the seams write the rows
    again, with the values they hold, and attend."""

    def __init__(self, num_heads: int, head_size: int, num_kv_heads: int,
                 sliding_window: Optional[int] = None,
                 eps: float = 1e-5) -> None:
        assert num_heads % 2 == 0 and num_kv_heads % 2 == 0
        self.head_size = head_size
        self.eps = eps
        self.inner = PagedAttention(num_heads, 2 * head_size,
                                    head_size**-0.5, num_kv_heads // 2,
                                    sliding_window=sliding_window)

    def __call__(self, query, key, value, kv_cache, attn_metadata, *,
                 lam, lambda_init: float, subln):
        """`query` [B, L, Hq, d], `key`, `value` [B, L, Hkv, d] -> ([B, L,
        Hq d], cache). `lam` a float32 scalar, `subln` [2 d]."""
        b, l, hq, d = query.shape
        pairs = query.reshape(b, l, hq // 2, 2, d)
        zeros = jnp.zeros_like(pairs[..., 0, :])
        folded_q = jnp.stack(
            [jnp.concatenate([pairs[..., 0, :], zeros], -1),
             jnp.concatenate([zeros, pairs[..., 1, :]], -1)],
            axis=-2).reshape(b, l, hq, 2 * d)
        stored = key.shape[2] // 2
        out, kv_cache = self.inner(
            folded_q, key.reshape(b, l, stored, 2 * d),
            value.reshape(b, l, stored, 2 * d), kv_cache, attn_metadata)
        out = out.reshape(b, l, hq // 2, 2, 2 * d).astype(jnp.float32)
        diff = out[..., 0, :] - lam * out[..., 1, :]
        diff = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True) + self.eps)
        diff = diff * subln.astype(jnp.float32) * (1.0 - lambda_init)
        return diff.reshape(b, l, hq * d).astype(query.dtype), kv_cache


def _any_attention_layer(model, said) -> bool:
    """Whether `said(layer)` holds of any PagedAttention in the model."""
    seen = set()

    def walk(obj, depth) -> bool:
        if id(obj) in seen or depth > 4:
            return False
        seen.add(id(obj))
        if isinstance(obj, PagedAttention):
            return said(obj)
        if isinstance(obj, (list, tuple)):
            return any(walk(v, depth + 1) for v in obj)
        d = getattr(obj, "__dict__", None)
        if not isinstance(d, dict):
            return False
        return any(walk(v, depth + 1) for v in d.values())

    return walk(model, 0)


def model_uses_alibi(model) -> bool:
    """True if any PagedAttention layer in the model applies ALiBi.

    Derived from the layers themselves (not a per-model flag) so a new
    ALiBi model family cannot silently miss the fused multi-step decode
    clamp: the engine forces K=1 for ALiBi models because the staged scan
    holds context_lens constant across substeps."""
    return _any_attention_layer(
        model, lambda layer: layer.alibi_slopes is not None)


def model_attention_windows(model) -> bool:
    """True if any PagedAttention layer in the model has a sliding window:
    a config that gives one is served only by a model that builds its
    layers with it (the windowed cache group gives back the blocks such
    a layer no longer reads)."""
    return _any_attention_layer(
        model, lambda layer: layer.sliding_window is not None)
