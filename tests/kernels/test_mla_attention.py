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


# --- the walk's edges, at the cells' stored width --------------------------

# The cells' own vector (640 lanes, the value its first 512). The test
# holds a group to 16 pages, so that its 32-block tables (512 tokens) are
# two groups as a 128-block table is on the chip: full groups, then a tail.
EDGE_DK, EDGE_DV, EDGE_H, EDGE_W, EDGE_BS = 640, 512, 8, 32, 16
# Both sides of a page, a sub-group (64 tokens) and a group (256); a full
# table; a pad row between live rows and as the last row of a grid step of
# 8 rows. The ragged kernel's walk sees a context less its own token.
EDGE_CTX = (1, 15, 0, 16, 17, 63, 64, 0,
            65, 255, 256, 257, 512, 0, 300, 129)
# (first row, rows, first context) of chunk runs: consecutive tokens of one
# sequence, each of which must see its predecessors' just-written vectors:
# across a grid-step boundary (rows 6..9), and across a launch boundary
# (rows 14..17: the test holds a launch to 16 rows of tables).
EDGE_RUNS = ((6, 4, 62), (14, 4, 254))


def _edge_batch(rng, ctx_lens, dtype, runs=()):
    """Rows over distinct pages; every pool block that no row holds, and
    every table entry past a row's pages, is NaN."""
    ctx = np.asarray(ctx_lens, np.int32)
    for r0, n, c0 in runs:
        ctx[r0:r0 + n] = c0 + np.arange(n)
    b = len(ctx)
    nb = 1 + b * EDGE_W
    pool = rng.normal(size=(nb, 1, EDGE_BS, EDGE_DK)).astype(np.float32)
    tables = (1 + np.arange(b * EDGE_W, dtype=np.int32)).reshape(b, EDGE_W)
    for r0, n, _ in runs:
        tables[r0:r0 + n] = tables[r0]
    held = -(-ctx // EDGE_BS)
    is_held = np.zeros(nb, bool)
    for r in range(b):
        is_held[tables[r, :held[r]]] = True
        tables[r, held[r]:] = 0             # block 0 is never held
    pool[~is_held] = np.nan
    slots = np.where(
        ctx > 0, tables[np.arange(b), np.maximum(ctx - 1, 0) // EDGE_BS]
        * EDGE_BS + np.maximum(ctx - 1, 0) % EDGE_BS, -1).astype(np.int32)
    q = rng.normal(size=(b, EDGE_H, EDGE_DK)).astype(np.float32)
    new = rng.normal(size=(b, EDGE_DK)).astype(np.float32)
    return (jnp.asarray(q).astype(dtype), jnp.asarray(new).astype(dtype),
            jnp.asarray(pool).astype(dtype), jnp.asarray(slots),
            jnp.asarray(tables), jnp.asarray(ctx))


def _finite(pool):
    return jnp.where(jnp.isnan(pool), 0, pool)


@requires_tpu
@pytest.mark.parametrize("kernel", ["paged", "ragged"])
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-3),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_mla_walk_edges_match_reference(kernel, dtype, tol, monkeypatch):
    """The walk moves the pages a row holds and multiplies no other: a
    page the row does not hold is NaN here and must not reach the
    result (the reference reads the same pool with its NaNs zeroed)."""
    from intellillm_tpu.ops.pallas import mla_attention as mod
    monkeypatch.setattr(mod, "_GROUP_PAGES", 16)
    scale = 0.07
    if kernel == "paged":
        q, _, pool, _, tables, ctx = _edge_batch(
            np.random.default_rng(3), EDGE_CTX, dtype)
        out_k, lse_k = mod.mla_paged_attention(q, pool, tables, ctx, scale,
                                               EDGE_DV)
        out_r, lse_r = mla_pool_attention_reference(
            q, _finite(pool), tables, ctx, scale, EDGE_DV)
        live = np.asarray(ctx) > 0
        np.testing.assert_allclose(np.asarray(lse_k)[live],
                                   np.asarray(lse_r)[live], rtol=tol,
                                   atol=tol)
        assert np.all(np.asarray(lse_k)[~live] <= -1e29)
    else:
        monkeypatch.setattr(mod, "_SMEM_TABLE_BYTES", 4 * EDGE_W * 16)
        # Twice the rows: two launches of 16, so the second run straddles
        # a launch boundary.
        ctx_lens = EDGE_CTX + EDGE_CTX[::-1]
        q, new, pool, slots, tables, ctx = _edge_batch(
            np.random.default_rng(4), ctx_lens, dtype, EDGE_RUNS)
        out_k, pool_k = mod.mla_ragged_paged_attention(
            q, new, pool, slots, tables, ctx, scale, EDGE_DV)
        out_r, pool_r = mla_fused_attention_reference(
            q, new, _finite(pool), slots, tables, ctx, scale, EDGE_DV)
        np.testing.assert_array_equal(
            np.asarray(_finite(pool_k), np.float32),
            np.asarray(pool_r, np.float32))
        live = np.asarray(ctx) > 0
    out_k = np.asarray(out_k, np.float32)
    assert np.isfinite(out_k).all()
    np.testing.assert_allclose(out_k, np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    # A pad row's output is zeros.
    assert np.all(out_k[~live] == 0.0)


def test_latent_walk_extent_against_a_count_over_the_traffic():
    """The twin of `test_walk_extent_against_a_count_over_the_traffic` for
    the latent kernels: ONE rule serves both families (the kernels take it
    through `_page_walker`, the host's counter through the runner's
    import), and over `decode-closed`'s contexts under 64-block tables it
    moves within 5 % of what decode rows attend to, where the fixed
    16-page groups the latent kernels kept until PR 46 moved 1.35x."""
    import json
    import os
    import sys
    chip = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                        "chip")
    sys.path.insert(0, chip)
    from harness.loadgen import length_pairs

    from intellillm_tpu.ops.pallas import mla_attention, paged_attention
    from intellillm_tpu.worker import model_runner
    assert mla_attention._page_walker is paged_attention._page_walker
    assert model_runner.walk_extent is paged_attention.walk_extent
    with open(os.path.join(chip, "traffic", "decode-closed.json")) as f:
        traffic = json.load(f)
    pairs = length_pairs(traffic, traffic["block"])
    bs, w = 16, 64
    decode = np.asarray([p + j for p, o in pairs for j in range(o)])
    prefill = np.asarray([c - 1 for p, _ in pairs for c in range(1, p + 1)])
    for walk, bound, old_ratio in ((decode, 1.05, 1.3), (prefill, 1.10, 2.2)):
        pages, _ = paged_attention.walk_extent(walk, bs, w)
        counted = (np.arange(w)[None, :] * bs < walk[:, None]).sum(axis=1)
        np.testing.assert_array_equal(pages, counted)
        attended = (walk + 1).sum()
        assert pages.sum() * bs / attended <= bound
        fixed_groups = np.maximum(-(-walk // 256), 1) * 256
        assert fixed_groups.sum() / attended >= old_ratio
    # Every context of the traffic is one group of a 64-block table, so one
    # piece a row; the piece sizes end in the whole group.
    assert mla_attention._largest_divisor(w, mla_attention._GROUP_PAGES) == w
