"""Page-granular chunk commit vs the generic row-scatter reshape_and_cache.

Reference pattern: `tests/kernels/test_cache.py` (reshape_and_cache vs a
torch loop). The page gather→merge→scatter must produce bit-identical
pools to the row scatter for contiguous chunk commits, including
page-straddling starts, pad rows, and overshoot truncation.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.kv_cache import commit_staged_chunk, reshape_and_cache


def _reference(k_stage, v_stage, k_pool, v_pool, start, n_valid,
               block_tables, bs):
    b, c, hkv, d = k_stage.shape
    nb = k_pool.shape[0]
    slots = []
    for i in range(b):
        for t in range(c):
            if t < n_valid[i]:
                pos = start[i] + t
                blk = int(block_tables[i, pos // bs])
                slots.append(blk * bs + pos % bs)
            else:
                slots.append(nb * bs)  # OOB -> dropped
    slots = jnp.asarray(np.asarray(slots, np.int32))
    return reshape_and_cache(k_stage.reshape(b * c, hkv, d),
                             v_stage.reshape(b * c, hkv, d),
                             k_pool, v_pool, slots)


@pytest.mark.parametrize("start_offsets", [[0, 3, 15, 9]])
@pytest.mark.parametrize("chunk", [8, 16, 32])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_commit_staged_chunk_matches_row_scatter(start_offsets, chunk,
                                                 dtype):
    rng = np.random.default_rng(0)
    b, hkv, d, bs, nb, w = 4, 4, 32, 16, 64, 16
    k_pool = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), dtype=dtype)
    v_pool = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)), dtype=dtype)
    k_stage = jnp.asarray(rng.normal(size=(b, chunk, hkv, d)), dtype=dtype)
    v_stage = jnp.asarray(rng.normal(size=(b, chunk, hkv, d)), dtype=dtype)
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    # Row 3 is a pad row (n_valid 0); row 2 truncates (overshoot).
    start = jnp.asarray(
        np.asarray([o + 32 * i for i, o in enumerate(start_offsets)],
                   np.int32))
    n_valid = jnp.asarray(np.asarray([chunk, chunk, chunk // 2, 0],
                                     np.int32))

    got_k, got_v = commit_staged_chunk((k_stage, v_stage), (k_pool, v_pool),
                                       start, n_valid, tables)
    ref_k, ref_v = _reference(k_stage, v_stage, k_pool, v_pool,
                              np.asarray(start), np.asarray(n_valid),
                              np.asarray(tables), bs)
    np.testing.assert_array_equal(np.asarray(got_k, np.float32),
                                  np.asarray(ref_k, np.float32))
    np.testing.assert_array_equal(np.asarray(got_v, np.float32),
                                  np.asarray(ref_v, np.float32))


def test_commit_last_table_column_no_duplicate_write():
    """start in the LAST table column: the straddle candidate column is
    out of the table and must be dropped, not clipped onto the same page
    (a clipped duplicate would scatter the page twice with unspecified
    order)."""
    rng = np.random.default_rng(1)
    b, c, hkv, d, bs, nb, w = 1, 16, 2, 32, 16, 8, 4
    k_pool = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    v_pool = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    k_stage = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    v_stage = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    tables = jnp.asarray(np.asarray([[3, 5, 1, 7]], np.int32))
    start = jnp.asarray(np.asarray([48], np.int32))     # last column, o=0
    n_valid = jnp.asarray(np.asarray([c], np.int32))

    got_k, _ = commit_staged_chunk((k_stage, v_stage), (k_pool, v_pool),
                                   start, n_valid, tables)
    got_k = np.asarray(got_k)
    for t in range(c):
        np.testing.assert_array_equal(got_k[7, :, t, :],
                                      np.asarray(k_stage)[0, t, :, :])
    # Nothing else was touched.
    untouched = [p for p in range(nb) if p != 7]
    assert np.all(got_k[untouched] == 0)


def test_commit_page_straddle_two_pages():
    """start%BS + C > BS forces writes across both candidate pages."""
    rng = np.random.default_rng(2)
    b, c, hkv, d, bs, nb, w = 2, 16, 2, 32, 16, 16, 4
    k_pool = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    v_pool = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    k_stage = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    v_stage = jnp.asarray(rng.normal(size=(b, c, hkv, d)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    start = jnp.asarray(np.asarray([8, 24], np.int32))
    n_valid = jnp.asarray(np.asarray([c, c], np.int32))

    got_k, _ = commit_staged_chunk((k_stage, v_stage), (k_pool, v_pool),
                                   start, n_valid, tables)
    got_k = np.asarray(got_k)
    tables_np = np.asarray(tables)
    for i in range(b):
        s = int(start[i])
        for t in range(c):
            pos = s + t
            blk = tables_np[i, pos // bs]
            np.testing.assert_array_equal(
                got_k[blk, :, pos % bs, :],
                np.asarray(k_stage)[i, t, :, :])


@pytest.mark.parametrize("layers,group", [(5, 2), (8, 8), (3, 8)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_commit_by_layer_matches_the_commit_with_layers_as_heads(
        layers, group, dtype):
    """A latent pool that holds every layer where a per-head pool has its
    heads (`ModelConfig.get_packed_latent_layers`): committing the stage
    with the layers as rows of the pool viewed `[NB * layers, 1, BS, D]`,
    `group` layers a call, leaves the pool bit for bit as the page commit
    with the layers as heads does (which the row scatter above is held
    to), pad rows, truncation and page-straddling starts included."""
    from intellillm_tpu.ops.kv_cache import commit_staged_chunk_by_layer
    rng = np.random.default_rng(1)
    b, d, bs, nb, w, chunk = 4, 128, 16, 24, 6, 8
    pool = jnp.asarray(rng.normal(size=(nb, layers, bs, d)), dtype=dtype)
    stage = jnp.asarray(rng.normal(size=(b, chunk, layers, d)), dtype=dtype)
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    start = jnp.asarray(np.asarray([0, 19, 47, 9], np.int32))
    n_valid = jnp.asarray(np.asarray([chunk, chunk, chunk // 2, 0], np.int32))
    (want, ) = commit_staged_chunk((stage, ), (pool, ), start, n_valid,
                                   tables)
    (got, ) = commit_staged_chunk_by_layer((stage, ), (pool, ), start,
                                           n_valid, tables, group=group)
    assert got.shape == pool.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert not np.array_equal(np.asarray(got, np.float32),
                              np.asarray(pool, np.float32))
