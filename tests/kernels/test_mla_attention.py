"""Latent (MLA) attention kernels vs their jnp reference compositions
(`ops/mla_attention.py`): the mixed step's fused latent-cache write + walk
and the decode programs' pool walk. On CPU the kernels run under Pallas TPU
interpret mode (tests/kernels/conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.mla_attention import (mla_fused_attention_reference,
                                              mla_pool_attention_reference)

requires_tpu = pytest.mark.kernel

# Stored width 256 = kv_lora_rank 128 + rope 64 + 64 zero lanes; the value
# is the first 128 lanes.
DK, DV, H = 256, 128, 8


def _batch(rng, *, nb=64, bs=16, w=8, ctx_lens=(1, 17, 63, 30, 31, 32, 0),
           dtype=np.float32):
    """Decode rows, a chunk run (rows 3..5: one sequence at positions
    29/30/31, each must see its predecessors' just-written vectors) and a
    pad row (ctx 0, slot -1)."""
    b = len(ctx_lens)

    def latent(shape):
        x = rng.normal(size=shape).astype(np.float32)
        x[..., 192:] = 0.0             # the zero pad lanes
        return x

    pool = jnp.asarray(latent((nb, 1, bs, DK))).astype(dtype)
    q = jnp.asarray(latent((b, H, DK))).astype(dtype)
    new = jnp.asarray(latent((b, DK))).astype(dtype)
    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    tables[4] = tables[3]
    tables[5] = tables[3]
    slots = []
    for i, c in enumerate(ctx_lens):
        if c == 0:
            slots.append(-1)
            continue
        slots.append(int(tables[i, (c - 1) // bs]) * bs + (c - 1) % bs)
    return (q, new, pool, jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(tables), jnp.asarray(np.asarray(ctx_lens, np.int32)))


@requires_tpu
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-3),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_ragged_matches_reference(dtype, tol):
    from intellillm_tpu.ops.pallas.mla_attention import (
        mla_ragged_paged_attention)
    q, new, pool, slots, tables, ctx = _batch(np.random.default_rng(0),
                                              dtype=dtype)
    scale = 0.13
    out_k, pool_k = mla_ragged_paged_attention(q, new, pool, slots, tables,
                                               ctx, scale, DV)
    out_r, pool_r = mla_fused_attention_reference(q, new, pool, slots,
                                                  tables, ctx, scale, DV)
    assert out_k.shape == (q.shape[0], H, DV)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    # The in-grid write leaves the pool byte-identical to the scatter.
    np.testing.assert_array_equal(np.asarray(pool_k, np.float32),
                                  np.asarray(pool_r, np.float32))
    # The pad row's output is zeros, not NaN.
    assert np.all(np.asarray(out_k[-1], np.float32) == 0.0)


@requires_tpu
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-3),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_paged_matches_reference(dtype, tol):
    from intellillm_tpu.ops.pallas.mla_attention import mla_paged_attention
    q, _, pool, _, tables, ctx = _batch(
        np.random.default_rng(1), ctx_lens=(5, 128, 16, 77, 0, 1),
        dtype=dtype)
    scale = 0.13
    out_k, lse_k = mla_paged_attention(q, pool, tables, ctx, scale, DV)
    out_r, lse_r = mla_pool_attention_reference(q, pool, tables, ctx, scale,
                                                DV)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(lse_k)[live],
                               np.asarray(lse_r)[live], rtol=tol, atol=tol)
    # An empty row's logsumexp must lose every merge.
    assert np.all(np.asarray(lse_k)[~live] <= -1e29)


@requires_tpu
def test_mla_ragged_row_chunks_see_earlier_chunks(monkeypatch):
    """A launch split over row chunks (SMEM budget for the block tables):
    the pool is aliased through, so a later chunk reads what an earlier
    one wrote."""
    from intellillm_tpu.ops.pallas import mla_attention as mod
    monkeypatch.setattr(mod, "_SMEM_TABLE_BYTES", 4 * 8 * 4)   # 4 rows
    args = _batch(np.random.default_rng(2),
                  ctx_lens=(1, 17, 63, 30, 31, 32, 9, 0))
    out_k, pool_k = mod.mla_ragged_paged_attention(*args, 0.2, DV)
    out_r, pool_r = mla_fused_attention_reference(*args, 0.2, DV)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(np.asarray(pool_k), np.asarray(pool_r))
