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

Routing is a parameter (`Routing`): the default matches HF Mixtral
(softmax over ALL experts → top-k → renormalize the selected weights);
sigmoid scores, group-limited selection, a scaling factor and an
expert-parallel share (a stack that holds fewer experts than the router
has outputs) are the other settings.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from intellillm_tpu.utils import cdiv

# Rows of one bf16 sublane tile: a chunk of the dense path is padded to a
# multiple of it and by no more.
_ROW_TILE = 16


@dataclasses.dataclass(frozen=True)
class Routing:
    """How the router's scores become (weights, experts), and which of the
    router's experts the stack handed to `moe_ffn` holds.

    - `scoring`: "softmax" over all experts (Mixtral, DeepSeek-MoE) or
      "sigmoid" of each expert's logit alone.
    - `n_group` / `topk_group`: group-limited selection. The experts are
      `n_group` equal consecutive groups, a group's score is its largest
      expert score, only experts of the best `topk_group` groups may be
      chosen. 1 / 1 = no limit.
    - `renormalize`: chosen weights divided by their sum (floored at
      1e-9), or by their sum plus `sum_eps` where a family adds an epsilon
      to it; `scale`: then multiplied by it (`routed_scaling_factor`).
    - `select_bias`: the layer passes a per-expert bias to `_route`; it is
      added to the scores for the CHOICE of experts (after the group
      limit's own scores, which use it too) and is no part of their
      weights.
    - `held_start`: expert-parallel share. The stack's N experts are the
      router's outputs `[held_start, held_start + N)`. Routing, the choice
      of top-k and the normalisation are over ALL of the router's outputs;
      only held experts are computed, and what the absent ones would add
      is left out (it is another chip's part of the sum).
    """
    scoring: str = "softmax"
    renormalize: bool = True
    n_group: int = 1
    topk_group: int = 1
    scale: float = 1.0
    held_start: int = 0
    select_bias: bool = False
    sum_eps: float = 0.0


_SOFTMAX_TOP_K = Routing()


def _route(x: jnp.ndarray, gate_w: jnp.ndarray, top_k: int,
           routing: Routing = _SOFTMAX_TOP_K, select_bias=None):
    """(weights [T, K] f32, experts [T, K] i32) over the router's whole
    width. `select_bias` [N] is the selection bias of a routing that has
    one (`routing.select_bias`)."""
    router_logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    if routing.scoring == "softmax":
        weights = jax.nn.softmax(router_logits, axis=-1)      # [T, N]
    elif routing.scoring == "sigmoid":
        weights = jax.nn.sigmoid(router_logits)
    else:
        raise ValueError(f"unknown router scoring {routing.scoring!r}")
    choose_from = weights
    if routing.select_bias:
        choose_from = weights + select_bias.astype(jnp.float32)
    if routing.n_group > 1:
        t, n = weights.shape
        per = n // routing.n_group
        group_score = choose_from.reshape(t, routing.n_group, per).max(-1)
        _, best = jax.lax.top_k(group_score, routing.topk_group)
        keep = jnp.zeros((t, routing.n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        # Scores are positive (sigmoid, softmax): -1 never wins a top-k
        # against an expert of a kept group.
        choose_from = jnp.where(jnp.repeat(keep, per, axis=1), choose_from,
                                -jnp.inf if routing.select_bias else -1.0)
    _, topi = jax.lax.top_k(choose_from, top_k)               # [T, K]
    topw = jnp.take_along_axis(weights, topi, axis=1)
    if routing.renormalize:
        total = topw.sum(-1, keepdims=True)
        topw = topw / (total + routing.sum_eps if routing.sum_eps
                       else jnp.maximum(total, 1e-9))
    if routing.scale != 1.0:
        topw = topw * routing.scale
    return topw, topi


def held_assignments(topi: jnp.ndarray, n_held: int,
                     held_start: int) -> jnp.ndarray:
    """[T] i32: how many of each token's chosen experts this stack holds
    (the numerator of intellillm_moe_assignments_total{held="here"})."""
    local = topi - held_start
    return ((local >= 0) & (local < n_held)).sum(-1).astype(jnp.int32)


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
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
) -> jnp.ndarray:
    """`chunk_size` is the most rows a chunk may hold (it bounds the
    [N, C, I] activations); the rows present decide the rest. `routed` is
    a (weights, experts) pair the caller already has from `_route`."""
    t, d = x.shape
    n = w1.shape[0]

    if routed is None:
        with jax.named_scope("moe_router"):
            routed = _route(x, gate_w, top_k, routing)
    topw, topi = routed
    if routing.held_start:
        # one_hot gives a zero row to an index outside [0, N): a choice
        # of an expert held elsewhere adds nothing here.
        topi = topi - routing.held_start
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
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
) -> jnp.ndarray:
    t, d = x.shape
    n = w1.shape[0]
    tk = t * top_k

    if routed is None:
        with jax.named_scope("moe_router"):
            routed = _route(x, gate_w, top_k, routing)
    topw, topi = routed

    # A choice of an expert this stack does not hold (a share: the router
    # is wider than the stack) goes to a group of its own past the last
    # held expert, index n, which sorts last, which no block computes and
    # whose weight is 0. A stack that holds every expert has no such row.
    local = topi.reshape(-1) - routing.held_start              # [T*K]
    here = (local >= 0) & (local < n)
    flat_e = jnp.where(here, local, n)
    flat_w = jnp.where(here, topw.reshape(-1), 0.0)
    sort_idx = jnp.argsort(flat_e, stable=True)                # [T*K]
    sorted_e = flat_e[sort_idx]
    token_idx = sort_idx // top_k                              # source token

    counts = jnp.bincount(flat_e, length=n).astype(jnp.int32)  # held only
    padded = cdiv(counts, block) * block                       # [N]
    pad_cum = jnp.cumsum(padded)
    starts = pad_cum - padded                                  # [N] slot base
    grp_cum = jnp.cumsum(counts)
    grp_start = grp_cum - counts                               # [N] in sorted
    # Static upper bound on sum of padded group sizes (block multiple).
    s = (cdiv(tk, block) + n) * block
    nb = s // block
    # Absent rows get a slot past the buffer: the scatter drops them, the
    # gather below clamps, and their weight is 0.
    safe_e = jnp.minimum(sorted_e, n - 1)
    pos_in_grp = jnp.arange(tk) - grp_start[safe_e]
    slot = jnp.where(sorted_e < n, starts[safe_e] + pos_in_grp, s)
    xb = jnp.zeros((s, d), x.dtype).at[slot].set(x[token_idx], mode="drop")

    # Expert owning each block; blocks past the last padded group get a
    # clipped id and compute on zeros (their output is never gathered).
    blk_expert = jnp.clip(
        jnp.searchsorted(pad_cum, jnp.arange(nb) * block, side="right"),
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

    contrib = (out[jnp.minimum(slot, s - 1)]
               * flat_w[sort_idx][:, None])                    # [T*K, D]
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
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
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
    # dominates at small t. The routed rows are those routed HERE: a
    # stack that holds n of the router's n_router experts is sent
    # t*top_k*n/n_router of the choices in expectation (all of them when
    # it holds every expert, as Mixtral and DeepSeek-MoE do).
    n_router = gate_w.shape[-1]
    if t * top_k * n + n * block * n_router <= n * t * n_router:
        return moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=block,
                               routing=routing, routed=routed)
    return moe_ffn_dense(x, gate_w, w1, w2, w3, top_k, routing=routing,
                         routed=routed)
