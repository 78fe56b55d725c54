"""Attention ops over the paged KV pool — reference (pure jnp) impls.

Role parity:
- decode: reference `ops.paged_attention_v1/v2` CUDA kernels
  (`csrc/attention/attention_kernels.cu`). Here: block-table gather +
  masked attention (correct everywhere, used for tests/CPU), the oracle
  of the Pallas kernels behind the seams of `ops/ragged_attention.py` and
  `ops/mla_attention.py`; the staged part and the merge of the K-step
  decode.
- `prefill_attention_reference`: dense causal attention over a padded
  prompt batch (the reference's xformers call,
  `vllm/model_executor/layers/attention.py:151-161`). No serving path
  runs it: a prompt is served as chunk rows through the fused seam. It
  is the independent answer the tests hold that path to.

GQA/MQA is handled by reshaping queries to [.., kv_heads, group, ..] rather
than materializing repeated KV heads (reference expands heads instead,
attention.py:106-120 — wasteful on HBM bandwidth).
ALiBi biases (attention.py:196-227) are supported throughout; a sliding
window reaches the paged references as each row's first visible position
(`lo`), and a stale table entry below it is gathered and masked, never a
mask inside attention, and only the dense oracle takes one.
"""
from __future__ import annotations

from typing import Optional

import functools

import jax
import jax.numpy as jnp

_NEG_INF = float("-inf")


def _grouped_query_reshape(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """[..., num_q_heads, D] -> [..., num_kv_heads, group_size, D]."""
    *lead, num_q_heads, d = q.shape
    assert num_q_heads % num_kv_heads == 0, (num_q_heads, num_kv_heads)
    group = num_q_heads // num_kv_heads
    return q.reshape(*lead, num_kv_heads, group, d)


@functools.partial(jax.jit, static_argnames=("scale", "sliding_window"))
def prefill_attention_reference(
    q: jnp.ndarray,            # [B, L, Hq, D]
    k: jnp.ndarray,            # [B, L, Hkv, D]
    v: jnp.ndarray,            # [B, L, Hkv, D]
    context_lens: jnp.ndarray,  # [B] int32 — actual (unpadded) lengths
    scale: float,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jnp.ndarray] = None,  # [Hq]
) -> jnp.ndarray:
    """Causal self-attention over padded prompt batches.

    Returns [B, L, Hq, D]. Query rows past context_lens attend to the
    valid keys (cheap, finite garbage — ignored downstream); keys past
    context_lens are masked out everywhere.
    """
    b, l, hq, d = q.shape
    hkv = k.shape[2]
    qg = _grouped_query_reshape(q, hkv)  # [B, L, Hkv, G, D]

    # scores[b, h, g, i, j] = q_i · k_j
    scores = jnp.einsum("blhgd,bmhd->bhglm", qg * scale, k,
                        preferred_element_type=jnp.float32)

    pos_q = jnp.arange(l)[:, None]   # i
    pos_k = jnp.arange(l)[None, :]   # j
    mask = pos_k <= pos_q            # causal
    if sliding_window is not None:
        mask &= pos_k > (pos_q - sliding_window)
    # mask out padded keys
    valid_k = pos_k < context_lens[:, None, None, None, None]
    full_mask = mask[None, None, None, :, :] & valid_k

    if alibi_slopes is not None:
        # bias = -slope * (i - j), per query head
        dist = (pos_q - pos_k).astype(jnp.float32)  # [L, L]
        bias = -alibi_slopes.reshape(hkv, hq // hkv, 1, 1) * dist[None, None]
        scores = scores + bias[None]

    scores = jnp.where(full_mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # Fully-masked (padded) query rows softmax to NaN; zero them.
    probs = jnp.where(full_mask.any(axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhglm,bmhd->blhgd", probs, v.astype(probs.dtype))
    return out.reshape(b, l, hq, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "return_lse"))
def decode_attention_reference(
    q: jnp.ndarray,             # [B, 1, Hq, D]
    k_cache: jnp.ndarray,       # [num_blocks, Hkv, block_size, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks_per_seq] int32
    context_lens: jnp.ndarray,  # [B] int32 (length including current token)
    scale: float,
    alibi_slopes: Optional[jnp.ndarray] = None,
    return_lse: bool = False,
    lo: Optional[jnp.ndarray] = None,
):
    """Single-token decode attention via block-table gather. `lo` [B]
    (a sliding window): positions below it are not attended to.

    Correct-everywhere baseline for the Pallas paged-attention kernel; used
    directly on CPU (tests) and as the numerics oracle in kernel tests.
    With return_lse, also returns logsumexp [B, Hq] for attention merging.
    """
    from intellillm_tpu.ops.kv_cache import gather_kv_for_attention

    b = q.shape[0]
    hq, d = q.shape[2], q.shape[3]
    nb, hkv, bs, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    max_ctx = max_blocks * bs

    k = gather_kv_for_attention(k_cache, block_tables)
    v = gather_kv_for_attention(v_cache, block_tables)

    qg = _grouped_query_reshape(q[:, 0], hkv)  # [B, Hkv, G, D]
    scores = jnp.einsum("bhgd,bmhd->bhgm", qg * scale, k,
                        preferred_element_type=jnp.float32)

    pos = jnp.arange(max_ctx)[None, :]
    valid = pos < context_lens[:, None]        # [B, max_ctx]
    if lo is not None:
        valid &= pos >= lo[:, None]

    if alibi_slopes is not None:
        slopes = alibi_slopes.reshape(hkv, hq // hkv)
        dist = (context_lens[:, None] - 1 - pos).astype(jnp.float32)
        scores = scores - slopes[None, :, :, None] * dist[:, None, None, :]

    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    any_valid = valid.any(axis=-1)[:, None, None, None]
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bhgm,bmhd->bhgd", probs, v.astype(probs.dtype))
    out = out.reshape(b, 1, hq, d).astype(q.dtype)
    if not return_lse:
        return out
    lse = jax.scipy.special.logsumexp(scores, axis=-1)      # [B, Hkv, G]
    lse = jnp.where(any_valid[..., 0], lse, _NEG_INF)
    return out, lse.reshape(b, hq)


def staged_decode_attention(
    q: jnp.ndarray,          # [B, 1, Hq, D]
    k_stage: jnp.ndarray,    # [B, S, Hkv, D] — staged tokens (pos n-1..n-1+S)
    v_stage: jnp.ndarray,
    stage_index,             # scalar: current substep k; slots 0..k valid
    scale: float,
):
    """Attention over the in-flight staged tokens of a fused decode batch.

    Returns (out [B, 1, Hq, D], lse [B, Hq]); combine with the pool part
    via merge_attention_parts. Used by multi-step decode, where tokens
    produced inside the fused loop live in a small staging buffer instead
    of the paged pool (keeps the pool loop-invariant so XLA doesn't
    double-buffer it through the scan).
    """
    b, s, hkv, d = k_stage.shape
    hq = q.shape[2]
    qg = _grouped_query_reshape(q[:, 0], hkv)  # [B, Hkv, G, D]
    scores = jnp.einsum("bhgd,bshd->bhgs", qg * scale,
                        k_stage.astype(qg.dtype),
                        preferred_element_type=jnp.float32)
    valid = jnp.arange(s)[None, :] <= stage_index       # [1, S]
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bshd->bhgd", p / l, v_stage.astype(p.dtype))
    lse = (m + jnp.log(l))[..., 0]                      # [B, Hkv, G]
    return (out.reshape(b, 1, hq, d).astype(q.dtype),
            lse.reshape(b, hq))


def merge_attention_parts(
    out_a: jnp.ndarray,   # [B, 1, Hq, D]
    lse_a: jnp.ndarray,   # [B, Hq]
    out_b: jnp.ndarray,
    lse_b: jnp.ndarray,
) -> jnp.ndarray:
    """Numerically-stable combination of two partial softmax-attention
    results over disjoint key sets (the role of the reference V2 kernel's
    cross-partition reduction, `attention_kernels.cu:462-501`)."""
    # Clamp to a finite floor: an empty part may carry -inf, and
    # (-inf) - (-inf) would poison pad rows with NaNs.
    lse_a = jnp.maximum(lse_a, -1e30)
    lse_b = jnp.maximum(lse_b, -1e30)
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    denom = jnp.maximum(wa + wb, 1e-30)
    wa = (wa / denom)[:, None, :, None]
    wb = (wb / denom)[:, None, :, None]
    return (out_a.astype(jnp.float32) * wa +
            out_b.astype(jnp.float32) * wb).astype(out_a.dtype)
