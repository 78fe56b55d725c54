"""The ragged kernel's walk over a row's pages, at the edges of its rule
(split from test_ragged_paged_attention.py, which says how these run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.ragged_attention import (
    ragged_fused_attention_reference)
from tests.kernels._ragged_cases import _run_both, requires_tpu

WALK_BS, WALK_W = 16, 32            # two 16-page groups of 256 tokens
# context_lens count the row's own token, which the walk holds back: the
# walk sees ctx - 1, so every edge of the decode kernel's list appears
# here one higher as well.
WALK_EDGES = (0, 1, WALK_BS - 1, WALK_BS, WALK_BS + 1, WALK_BS + 2, 255,
              256, 257, 258, WALK_BS * WALK_W)
WALK_POOLS = {"bf16": jnp.bfloat16, "fp8_e5m2": jnp.float8_e5m2}


def _edge_batch(rng, ctx, hq, hkv, dtype, poison=None):
    """Rows (each a sequence of its own) alternating every edge context
    with `ctx`, so that the cross-step prefetch meets `ctx` as
    predecessor and as successor of each. With `poison`, every page that
    is not among a row's first ceil(ctx / bs) table entries holds it,
    and each table's tail points at such pages."""
    bs, w, d = WALK_BS, WALK_W, 128
    ctxs = np.asarray([c for edge in WALK_EDGES for c in (edge, ctx)],
                      np.int32)
    b = len(ctxs)
    nb = b * w + 8
    k = rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
    v = rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    if poison is not None:
        owned = np.zeros(nb, bool)
        for row, c in enumerate(ctxs):
            owned[tables[row, :-(-int(c) // bs)]] = True
        k[~owned] = poison
        v[~owned] = poison
    last = np.maximum(ctxs - 1, 0)
    slots = np.where(ctxs > 0,
                     tables[np.arange(b), last // bs] * bs + last % bs, -1)
    q = rng.normal(size=(b, 1, hq, d)).astype(np.float32)
    k_new = rng.normal(size=(b, hkv, d)).astype(np.float32)
    v_new = rng.normal(size=(b, hkv, d)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(k).astype(dtype), jnp.asarray(v).astype(dtype),
            jnp.asarray(slots.astype(np.int32)), jnp.asarray(tables),
            jnp.asarray(ctxs))


def _assert_live_rows_match(out_k, out_r, ctxs):
    out_k, live = np.asarray(out_k, np.float32), np.asarray(ctxs) > 0
    assert np.isfinite(out_k).all()
    tol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(out_k[live],
                               np.asarray(out_r, np.float32)[live],
                               rtol=tol, atol=tol)
    assert (out_k[~live] == 0).all()


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2)])
@pytest.mark.parametrize("pool", sorted(WALK_POOLS))
@pytest.mark.parametrize("ctx", WALK_EDGES)
def test_ragged_walk_edges(ctx, pool, hq, hkv):
    """Output and pool at the edges of the walk's rule: a pad row, the
    first token, a page (of prior context) less one / exactly / plus
    one, a group less one / exactly / plus one, the whole table."""
    rng = np.random.default_rng(11)
    args = _edge_batch(rng, ctx, hq, hkv, WALK_POOLS[pool])
    (out_k, kc_k, vc_k), (out_r, kc_r, vc_r) = _run_both(args, 128**-0.5)
    _assert_live_rows_match(out_k, out_r, args[-1])
    np.testing.assert_array_equal(np.asarray(kc_k.astype(jnp.float32)),
                                  np.asarray(kc_r.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(vc_k.astype(jnp.float32)),
                                  np.asarray(vc_r.astype(jnp.float32)))


@requires_tpu
@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("pool", sorted(WALK_POOLS))
def test_ragged_moves_no_page_a_row_does_not_hold(pool, poison):
    """Every page outside a row's first ceil(ctx / bs) table entries is
    NaN or Inf, the tables' tails point at them, and the output is
    finite and right: the walk moved none of them (a moved page's V
    reaches the PV dot, where 0 x NaN is NaN, whatever the mask says)."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(12)
    q, k_new, v_new, k_cache, v_cache, slots, tables, ctxs = _edge_batch(
        rng, 40, 8, 2, WALK_POOLS[pool], poison=poison)
    out_k, _, _ = ragged_paged_attention(
        q, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
        k_cache, v_cache, slots, tables, ctxs, 128**-0.5)
    jax.block_until_ready(out_k)    # as in _run_both

    def finite(cache):
        c = cache.astype(jnp.float32)
        return jnp.where(jnp.isfinite(c), c, 0.0).astype(cache.dtype)

    out_r, _, _ = ragged_fused_attention_reference(
        q, k_new, v_new, finite(k_cache), finite(v_cache), slots, tables,
        ctxs, 128**-0.5)
    _assert_live_rows_match(out_k, out_r, ctxs)


@requires_tpu
@pytest.mark.parametrize("pool", sorted(WALK_POOLS))
def test_ragged_slab_fetch_is_skipped_only_for_the_page_just_written(pool):
    """The write keeps the last page slab in VMEM and fetches a row's
    slab only when it is another: rows of two sequences interleaved
    (A, B, A, A, B, pad, B: fetch, fetch, fetch, kept, fetch, -, kept)
    leave the pool as the scatter pass does and attend to each other's
    tokens."""
    rng = np.random.default_rng(13)
    bs, w, d, hq, hkv, nb = 16, 8, 128, 8, 2, 32
    seq = [0, 1, 0, 0, 1, -1, 1]
    ctxs = [21, 37, 22, 23, 38, 0, 39]
    seq_tables = rng.permutation(nb)[:2 * w].reshape(2, w).astype(np.int32)
    tables = np.stack([seq_tables[max(s, 0)] for s in seq])
    slots = np.asarray(
        [-1 if s < 0 else int(tables[i, (c - 1) // bs]) * bs + (c - 1) % bs
         for i, (s, c) in enumerate(zip(seq, ctxs))], np.int32)
    b = len(seq)
    dtype = WALK_POOLS[pool]
    args = (jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
                        ).astype(dtype),
            jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
                        ).astype(dtype),
            jnp.asarray(slots), jnp.asarray(tables),
            jnp.asarray(np.asarray(ctxs, np.int32)))
    (out_k, kc_k, vc_k), (out_r, kc_r, vc_r) = _run_both(args, d**-0.5)
    _assert_live_rows_match(out_k, out_r, args[-1])
    np.testing.assert_array_equal(np.asarray(kc_k.astype(jnp.float32)),
                                  np.asarray(kc_r.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(vc_k.astype(jnp.float32)),
                                  np.asarray(vc_r.astype(jnp.float32)))
