"""Mixture-of-Experts feed-forward.

Role parity: reference `vllm/model_executor/layers/fused_moe.py` (Triton
grouped-GEMM over experts + CUDA `moe_align_block_size`,
`csrc/moe_align_block_size_kernels.cu`). TPU redesign, two paths:

- `moe_ffn_grouped` — sort-based ragged grouped matmul with STATIC shapes
  (the XLA-friendly equivalent of `moe_align_block_size` + grouped GEMM):
  flatten the (token, k) assignments, stable-sort by expert, pad each
  expert's group up to a block multiple, then scan over fixed-size token
  blocks, each of which gathers exactly one expert's weights. Per-token
  FLOPs are proportional to top_k (plus at most one padding block per
  expert), not num_experts. No token dropping: the padded buffer is sized
  T*K + N*block, an upper bound on the sum of per-expert padded groups.
- `moe_ffn_dense` — every expert over every token, combined by routing
  weights. For tiny decode batches (T*K << N*block) this is the faster
  path: the step is bound by reading all expert weights from HBM either
  way, and dense avoids the sort/scatter entirely.

`moe_ffn` dispatches between them by comparing each path's FLOP model
(dense: N*T products; grouped: T*K plus up to one padding block/expert).

Stored layout of the expert stacks: all three are `[N, I, D]` (experts,
inner, hidden), so `w1` (gate) and `w3` (up) are the checkpoint's own
`[out, in]` matrices stacked, and their matmuls contract on the last axis.
That is the layout XLA's TPU layout assignment wants for them whenever the
expert einsums sit inside a loop (the chunk `while` of a prefill step, the
K-loop of `decode_fused`): handed `[N, D, I]` it copied both stacks, in
every `mixed` step and for the life of the decode programs
(tests/test_tpu_compile.py `test_moe_dense_copies_no_expert_stack` holds
the compiled text to no such copy).

Routing matches HF Mixtral: softmax over ALL experts → top-k → renormalize
the selected weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from intellillm_tpu.utils import cdiv

# Rows of one bf16 sublane tile: a chunk of the dense path is padded to a
# multiple of it and by no more.
_ROW_TILE = 16


def _route(x: jnp.ndarray, gate_w: jnp.ndarray, top_k: int,
           renormalize: bool = True):
    router_logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    weights = jax.nn.softmax(router_logits, axis=-1)          # [T, N]
    topw, topi = jax.lax.top_k(weights, top_k)                # [T, K]
    if renormalize:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi


def random_gate_up_stack(key, n: int, d: int, i: int, dtype) -> jnp.ndarray:
    """A random w1 or w3 for `load_format="dummy"`: drawn as [N, D, I], so
    that a seed keeps giving the model it gave when that was the stored
    layout, and returned as the stored [N, I, D]. Waits for the result:
    the host otherwise runs layers ahead of the device and the draws'
    float32 and untransposed intermediates (five times the stack) pile up
    beside the weights."""
    w = (jax.random.normal(key, (n, d, i), jnp.float32) * 0.02).astype(dtype)
    return jax.block_until_ready(w.swapaxes(1, 2))


def dense_chunking(t: int, chunk_size: int) -> tuple[int, int]:
    """(number of chunks, rows a chunk) the dense path cuts `t` rows into:
    as few chunks as `chunk_size` allows, of equal size, each rounded up
    to the sublane tile — so a 128-row decode step is one chunk of 128 and
    not 256 rows of which half are zeros. The pad is under one tile a
    chunk."""
    most = max(chunk_size // _ROW_TILE, 1) * _ROW_TILE
    num_chunks = cdiv(t, most)
    chunk = cdiv(cdiv(t, num_chunks), _ROW_TILE) * _ROW_TILE
    return num_chunks, chunk


def moe_ffn_dense(
    x: jnp.ndarray,        # [T, D]
    gate_w: jnp.ndarray,   # [D, N] router
    w1: jnp.ndarray,       # [N, I, D]  (gate proj per expert)
    w2: jnp.ndarray,       # [N, I, D]  (down proj per expert)
    w3: jnp.ndarray,       # [N, I, D]  (up proj per expert)
    top_k: int,
    chunk_size: int = 256,
    renormalize: bool = True,
) -> jnp.ndarray:
    """`chunk_size` is the most rows a chunk may hold (it bounds the
    [N, C, I] activations); the rows present decide the rest."""
    t, d = x.shape
    n = w1.shape[0]

    with jax.named_scope("moe_router"):
        topw, topi = _route(x, gate_w, top_k, renormalize)
    onehot = jax.nn.one_hot(topi, n, dtype=jnp.float32)       # [T, K, N]
    combine = (topw[..., None] * onehot).sum(axis=1)          # [T, N]

    num_chunks, chunk = dense_chunking(t, chunk_size)
    pad_t = num_chunks * chunk
    x_pad = jnp.pad(x, ((0, pad_t - t), (0, 0)))
    comb_pad = jnp.pad(combine, ((0, pad_t - t), (0, 0)))
    x_chunks = x_pad.reshape(num_chunks, chunk, d)
    c_chunks = comb_pad.reshape(num_chunks, chunk, n)

    def chunk_fn(carry, inp):
        xc, cc = inp
        h1 = jnp.einsum("td,nid->nti", xc, w1,
                        preferred_element_type=jnp.float32)
        h3 = jnp.einsum("td,nid->nti", xc, w3,
                        preferred_element_type=jnp.float32)
        h = jax.nn.silu(h1) * h3
        out = jnp.einsum("nti,nid->ntd", h.astype(x.dtype), w2,
                         preferred_element_type=jnp.float32)   # [N, C, D]
        mixed = jnp.einsum("ntd,tn->td", out, cc)
        return carry, mixed.astype(x.dtype)

    # named_scope: metadata only, so that the trace's device operations
    # carry their layer (obs/kernels.py, docs/observability.md).
    with jax.named_scope("moe_experts"):
        _, outs = jax.lax.scan(chunk_fn, None, (x_chunks, c_chunks))
    return outs.reshape(pad_t, d)[:t]


def moe_ffn_grouped(
    x: jnp.ndarray,        # [T, D]
    gate_w: jnp.ndarray,   # [D, N] router
    w1: jnp.ndarray,       # [N, I, D]
    w2: jnp.ndarray,       # [N, I, D]
    w3: jnp.ndarray,       # [N, I, D]
    top_k: int,
    block: int = 512,
    renormalize: bool = True,
) -> jnp.ndarray:
    t, d = x.shape
    n = w1.shape[0]
    tk = t * top_k

    with jax.named_scope("moe_router"):
        topw, topi = _route(x, gate_w, top_k, renormalize)

    flat_e = topi.reshape(-1)                                  # [T*K]
    flat_w = topw.reshape(-1)                                  # [T*K]
    sort_idx = jnp.argsort(flat_e, stable=True)                # [T*K]
    sorted_e = flat_e[sort_idx]
    token_idx = sort_idx // top_k                              # source token

    counts = jnp.bincount(flat_e, length=n).astype(jnp.int32)
    padded = cdiv(counts, block) * block                       # [N]
    pad_cum = jnp.cumsum(padded)
    starts = pad_cum - padded                                  # [N] slot base
    grp_cum = jnp.cumsum(counts)
    grp_start = grp_cum - counts                               # [N] in sorted
    pos_in_grp = jnp.arange(tk) - grp_start[sorted_e]
    slot = starts[sorted_e] + pos_in_grp                       # [T*K] dest

    # Static upper bound on sum of padded group sizes (block multiple).
    s = (cdiv(tk, block) + n) * block
    nb = s // block
    xb = jnp.zeros((s, d), x.dtype).at[slot].set(x[token_idx])

    # Expert owning each block; blocks past the last padded group get a
    # clipped id and compute on zeros (their output is never gathered).
    blk_off = jnp.arange(nb) * block
    blk_expert = jnp.clip(jnp.searchsorted(pad_cum, blk_off, side="right"),
                          0, n - 1)

    def body(carry, inp):
        xc, e = inp                                            # [B, D], []
        w1e = jax.lax.dynamic_index_in_dim(w1, e, 0, keepdims=False)
        w3e = jax.lax.dynamic_index_in_dim(w3, e, 0, keepdims=False)
        w2e = jax.lax.dynamic_index_in_dim(w2, e, 0, keepdims=False)
        h1 = jnp.einsum("bd,id->bi", xc, w1e,
                        preferred_element_type=jnp.float32)
        h3 = jnp.einsum("bd,id->bi", xc, w3e,
                        preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h1) * h3).astype(x.dtype)
        return carry, jnp.dot(h, w2e, preferred_element_type=jnp.float32)

    with jax.named_scope("moe_experts"):
        _, out_blocks = jax.lax.scan(body, None,
                                     (xb.reshape(nb, block, d), blk_expert))
    out = out_blocks.reshape(s, d)                             # f32

    contrib = out[slot] * flat_w[sort_idx][:, None]            # [T*K, D]
    y = jnp.zeros((t, d), jnp.float32).at[token_idx].add(contrib)
    return y.astype(x.dtype)


def moe_ffn(
    x: jnp.ndarray,
    gate_w: jnp.ndarray,
    w1: jnp.ndarray,
    w2: jnp.ndarray,
    w3: jnp.ndarray,
    top_k: int,
    block: int = 512,
    renormalize: bool = True,
) -> jnp.ndarray:
    # All three stacks are [N, I, D] (module docstring): w1/w3 contract on
    # their last axis, so no loop around this call makes XLA re-lay them.
    # QuantMixtral (reference mixtral_quant.py): expert stacks arrive as
    # packed int4 — HBM holds only the packed bytes, [N, in/2, out] as the
    # checkpoint packs them; the dense stack is a transient dequant feeding
    # the expert einsums (XLA fuses the affine into the dot producers where
    # profitable), which for w1/w3 ([N, D, I] unpacked) is handed over
    # with its last two axes swapped.
    from intellillm_tpu.layers.quantization import (dequant_int4_stack,
                                                    is_quantized)
    if is_quantized(w1):
        w1 = dequant_int4_stack(w1, x.dtype).swapaxes(1, 2)
    if is_quantized(w2):
        w2 = dequant_int4_stack(w2, x.dtype)
    if is_quantized(w3):
        w3 = dequant_int4_stack(w3, x.dtype).swapaxes(1, 2)
    t = x.shape[0]
    n = w1.shape[0]
    # Dense runs n*t token-expert rows; grouped runs the routed rows
    # plus up to one padding block per expert: t*top_k + n*block worst
    # case. Switch at row parity. Fetch-synced v5e device timing (n=8,
    # top_k=2, e=2048, inter=4096, block=512) showed grouped at or ahead
    # of dense from a few hundred tokens (4.6 vs 16.0 ms at t=256,
    # 6.6 vs 8.9 ms at t=2048, 8.1 vs 13.9 ms at t=4096) and tied within
    # noise below, so the old 2x-FLOP-win margin (crossover ~2k tokens)
    # left prefill-sized batches on the slow dense path. The n*block
    # padding term must stay in the inequality: many-expert models
    # (DeepSeek n=64) pay n padding blocks on the grouped path, which
    # dominates at small t.
    if t * top_k + n * block <= n * t:
        return moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=block,
                               renormalize=renormalize)
    return moe_ffn_dense(x, gate_w, w1, w2, w3, top_k,
                         renormalize=renormalize)
