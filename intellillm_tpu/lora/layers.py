"""Batched multi-LoRA application — the TPU bgmv equivalent.

Role parity: reference punica kernels (`csrc/punica/bgmv/bgmv_impl.cuh`,
`vllm/lora/punica.py:17-40` bgmv/add_lora) and the per-layer LoRA wrappers
(`vllm/lora/layers.py:32-101` _apply_lora*). Two paths behind one seam:

- Pallas BGMV kernel (ops/pallas/bgmv.py) on TPU: the adapter stacks stay
  VMEM-resident and each row's adapter is picked by a dynamic VMEM index
  — no gathered [B, Din, R] copy in HBM per step. Taken where
  `use_kernel("bgmv", bgmv_supported(...))` (128-aligned dims, VMEM
  budget) says so.
- jnp reference elsewhere: the per-row adapter slab is gathered from the
  stacked tensors and contracted with two einsums — XLA maps the
  [B, Din, R] x [B, R, Dout] chain onto the MXU directly.

Rows with slot 0 hit the reserved all-zero adapter on either path, so
padding rows and no-LoRA rows get an exact +0.0 delta.
"""
from __future__ import annotations

import jax.numpy as jnp

from intellillm_tpu.ops.dispatch import use_kernel


def lora_delta(
    x: jnp.ndarray,          # [B, L, Din] layer input
    a_stack: jnp.ndarray,    # [S, Din, R] adapter A, slot 0 = zeros
    b_stack: jnp.ndarray,    # [S, R, Dout] adapter B (pre-scaled), slot 0 = 0
    row_slots: jnp.ndarray,  # [B] int32 adapter slot per batch row
) -> jnp.ndarray:
    """y_delta[b] = (x[b] @ A[slot[b]]) @ B[slot[b]].

    B is pre-scaled by lora_alpha/r at activation time, so the delta adds
    directly onto the base projection output.
    """
    from intellillm_tpu.ops.pallas.bgmv import bgmv, bgmv_supported
    if use_kernel("bgmv", bgmv_supported(x, a_stack, b_stack)):
        return bgmv(x, a_stack, b_stack, row_slots)
    a_sel = a_stack[row_slots]                     # [B, Din, R]
    b_sel = b_stack[row_slots]                     # [B, R, Dout]
    h = jnp.einsum("bld,bdr->blr", x, a_sel,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("blr,bro->blo", h, b_sel,
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def lora_embed(
    input_ids: jnp.ndarray,   # [B, L] int32, ids may reach vocab+extra
    base_embed: jnp.ndarray,  # [>=vocab, E] base table (may be TP-padded)
    vocab_size: int,
    vocab_state: dict,        # manager vocab_stacks (embed_a/b, extra_embed)
    row_slots: jnp.ndarray,   # [B]
) -> jnp.ndarray:
    """Embedding with adapter vocab support (reference
    `vllm/lora/layers.py:147` VocabParallelEmbeddingWithLoRA): ids beyond
    the base vocab read the adapter's extra-token rows, and the
    PEFT-Embedding LoRA delta B·A[id] adds on top for all ids."""
    h = base_embed[jnp.minimum(input_ids, base_embed.shape[0] - 1)]
    is_extra = input_ids >= vocab_size
    ex = vocab_state["extra_embed"][row_slots]          # [B, X, E]
    idx = jnp.clip(input_ids - vocab_size, 0, ex.shape[1] - 1)
    h_ex = jnp.take_along_axis(ex, idx[..., None], axis=1)
    h = jnp.where(is_extra[..., None], h_ex, h)
    # Per-token A row (embedding semantics) x per-row B.
    a_rows = vocab_state["embed_a"][
        row_slots[:, None], jnp.minimum(input_ids,
                                        vocab_state["embed_a"].shape[1] - 1)]
    delta = jnp.einsum("blr,bre->ble", a_rows,
                       vocab_state["embed_b"][row_slots],
                       preferred_element_type=jnp.float32)
    return h + delta.astype(h.dtype)


def lora_logits(
    hidden: jnp.ndarray,      # [B, ..., E]
    base_logits: jnp.ndarray,  # [B, ..., >=vocab] (may be TP-padded)
    vocab_size: int,
    vocab_state: dict,        # head_a/b, extra_head, extra_counts
    row_slots: jnp.ndarray,   # [B]
) -> jnp.ndarray:
    """Logits with adapter vocab support (reference
    `vllm/lora/layers.py:783` SamplerWithLoRA): base-vocab delta via the
    lm_head A/B pair plus extra-token columns from the adapter's output
    embeddings. Returns EXACTLY vocab+extra columns — padding columns are
    dropped and invalid extra slots are -inf, so no downstream mask is
    needed."""
    ha = vocab_state["head_a"][row_slots]               # [B, E, R]
    hb = vocab_state["head_b"][row_slots]               # [B, R, V]
    t = jnp.einsum("b...e,ber->b...r", hidden, ha,
                   preferred_element_type=jnp.float32).astype(hidden.dtype)
    delta = jnp.einsum("b...r,brv->b...v", t, hb,
                       preferred_element_type=jnp.float32)
    base = (base_logits[..., :vocab_size]
            + delta.astype(base_logits.dtype))

    xh = vocab_state["extra_head"][row_slots]           # [B, E, X]
    ex = jnp.einsum("b...e,bex->b...x", hidden, xh,
                    preferred_element_type=jnp.float32
                    ).astype(base_logits.dtype)
    # Mask extra slots the row's adapter doesn't define (including all of
    # them for slot-0 / no-adapter rows).
    counts = vocab_state["extra_counts"][row_slots]     # [B]
    pos = jnp.arange(ex.shape[-1])
    counts_b = counts.reshape((-1, ) + (1, ) * (ex.ndim - 1))
    ex = jnp.where(pos >= counts_b, -1e30, ex)
    return jnp.concatenate([base, ex], axis=-1)
