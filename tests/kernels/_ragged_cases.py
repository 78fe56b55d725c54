"""What the ragged kernel's test files share: the mixed batch and the run
of kernel and oracle side by side (tests/kernels/test_ragged_paged_attention.py,
test_ragged_walk.py, test_ragged_grid.py, test_ragged_grid_window.py), and
the grid's case and check, which the grid's two files share."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.ragged_attention import (
    ragged_fused_attention_reference)

requires_tpu = pytest.mark.kernel


def _mixed_batch(rng, *, hq, hkv, d, nb=64, bs=16, w=8,
                 ctx_lens=(1, 17, 63, 30, 31, 32, 0), dtype=np.float32,
                 chunk_run=True):
    """A mixed batch: decode rows plus a chunk run (three consecutive
    rows of ONE sequence at positions 29/30/31 — rows 3..5 share a block
    table, each must see its predecessors' just-written K/V) plus a pad
    row (ctx 0, slot -1)."""
    b = len(ctx_lens)
    k_cache = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(dtype))
    v_cache = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(dtype))
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))

    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    if chunk_run:
        # Rows 3..5 are the chunk run: one sequence, one table. Only
        # meaningful for ascending positions: the kernel writes row by
        # row, the oracle all rows first, and they agree exactly when
        # no row overwrites a position an earlier row attends to — which
        # is what the scheduler emits.
        tables[4] = tables[3]
        tables[5] = tables[3]
    slots = []
    for i, c in enumerate(ctx_lens):
        if c == 0:
            slots.append(-1)
            continue
        blk = int(tables[i, (c - 1) // bs])
        slots.append(blk * bs + (c - 1) % bs)
    return (q, k_new, v_new, k_cache, v_cache,
            jnp.asarray(np.asarray(slots, np.int32)), jnp.asarray(tables),
            jnp.asarray(np.asarray(ctx_lens, np.int32)))


def _run_both(args, scale, alibi_slopes=None, cache_cast=None):
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    q, k_new, v_new, k_cache, v_cache, slots, tables, ctx = args
    if cache_cast is not None:
        k_cache = k_cache.astype(cache_cast)
        v_cache = v_cache.astype(cache_cast)
    out_k, kc_k, vc_k = ragged_paged_attention(
        q, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
        k_cache, v_cache, slots, tables, ctx, scale, alibi_slopes)
    # Interpret mode runs the kernel through host callbacks that issue
    # jax operations of their own: dispatching the oracle while they run
    # can deadlock the CPU client.
    jax.block_until_ready((out_k, kc_k, vc_k))
    out_r, kc_r, vc_r = ragged_fused_attention_reference(
        q, k_new, v_new, k_cache, v_cache, slots, tables, ctx, scale,
        alibi_slopes)
    return (out_k, kc_k, vc_k), (out_r, kc_r, vc_r)


# --- the grid (test_ragged_grid.py, test_ragged_grid_window.py) ------------

GRID_POOLS = {"f32": jnp.float32, "bf16": jnp.bfloat16,
              "fp8_e5m2": jnp.float8_e5m2}
GRID_HEADS = {"8x4": (8, 4), "16x1": (16, 1), "4x7": (4, 7), "4x1": (4, 1)}
GRID_WINDOW = 100


def _grid_case(pool, heads, use_alibi, windowed, q_dtype):
    """Decode rows (a tail of three pages, one that crosses the 16-page
    group, the whole 32-block table), three chunk rows of one sequence
    and a pad row, every K/V value a value of the pool's dtype."""
    hkv, g = GRID_HEADS[heads]
    rng = np.random.default_rng(31)
    dtype = GRID_POOLS[pool]
    args = list(_mixed_batch(rng, hq=hkv * g, hkv=hkv, d=128, nb=8 * 32,
                             w=32, ctx_lens=(1, 37, 300, 30, 31, 32, 512,
                                             0)))
    q, k_new, v_new, k_cache, v_cache = args[:5]
    args[:5] = (q.astype(q_dtype), (k_new * 0.5).astype(dtype),
                (v_new * 0.5).astype(dtype), (k_cache * 0.5).astype(dtype),
                (v_cache * 0.5).astype(dtype))
    kwargs = {}
    if use_alibi:
        kwargs["alibi_slopes"] = jnp.asarray(
            rng.random(hkv * g).astype(np.float32) * 0.1)
    if windowed:
        kwargs["lo"] = jnp.maximum(args[7] - GRID_WINDOW, 0)
    return args, kwargs


def check_parity_grid(pool, heads, use_alibi, windowed):
    """The kernel against the oracle on `_grid_case`: the output of live
    rows within the pool dtype's tolerance, the pools byte for byte."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    q_dtype = jnp.float32 if pool == "f32" else jnp.bfloat16
    args, kwargs = _grid_case(pool, heads, use_alibi, windowed, q_dtype)
    out_k, kc_k, vc_k = jax.block_until_ready(ragged_paged_attention(
        *args, 128 ** -0.5, **kwargs))
    out_r, kc_r, vc_r = ragged_fused_attention_reference(
        *args, 128 ** -0.5, **kwargs)
    tol = 2e-3 if pool == "f32" else 2e-2
    live = np.asarray(args[7]) > 0
    np.testing.assert_allclose(np.asarray(out_k, np.float32)[live],
                               np.asarray(out_r, np.float32)[live],
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(kc_k.astype(jnp.float32)),
                                  np.asarray(kc_r.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(vc_k.astype(jnp.float32)),
                                  np.asarray(vc_r.astype(jnp.float32)))
