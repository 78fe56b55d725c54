"""Ragged fused cache-write + attend kernel vs the incumbent composition
(reshape_and_cache then decode_attention_reference) — the golden oracle
the mixed path is pinned against. On TPU the Mosaic kernel compiles
natively; on CPU it runs under Pallas TPU interpret mode
(tests/kernels/conftest.py). The walk's edges are in test_ragged_walk.py,
the grid of pool dtype x heads x ALiBi x window in test_ragged_grid.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.ragged_attention import (
    ragged_fused_attention_reference)
from tests.kernels._ragged_cases import (_mixed_batch, _run_both,
                                         requires_tpu)


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
def test_ragged_matches_incumbent_composition(hq, hkv):
    rng = np.random.default_rng(0)
    d = 128
    args = _mixed_batch(rng, hq=hq, hkv=hkv, d=d)
    (out_k, kc_k, vc_k), (out_r, kc_r, vc_r) = _run_both(args, d**-0.5)
    tol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)
    # The in-grid write must leave the pool byte-identical to the
    # separate scatter pass (same dtype, no arithmetic on the way in).
    np.testing.assert_array_equal(np.asarray(kc_k), np.asarray(kc_r))
    np.testing.assert_array_equal(np.asarray(vc_k), np.asarray(vc_r))


@requires_tpu
def test_ragged_chunk_rows_see_in_flight_writes():
    """The chunk-run rows (3..5) attend to positions written by the rows
    just before them in the SAME kernel launch — the write-before-read
    ordering the sequential grid guarantees. A kernel that read stale
    pages for its predecessor's token would diverge from the oracle
    exactly on rows 4 and 5."""
    rng = np.random.default_rng(2)
    d, hq, hkv = 128, 4, 2
    args = _mixed_batch(rng, hq=hq, hkv=hkv, d=d)
    (out_k, _, _), (out_r, _, _) = _run_both(args, d**-0.5)
    tol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(np.asarray(out_k)[4:6],
                               np.asarray(out_r)[4:6],
                               rtol=tol, atol=tol)


@requires_tpu
def test_ragged_alibi_matches_incumbent():
    from intellillm_tpu.layers.alibi import get_alibi_slopes
    rng = np.random.default_rng(3)
    d, hq, hkv = 128, 8, 2
    slopes = jnp.asarray(get_alibi_slopes(hq), jnp.float32)
    args = _mixed_batch(rng, hq=hq, hkv=hkv, d=d)
    (out_k, _, _), (out_r, _, _) = _run_both(args, d**-0.5,
                                             alibi_slopes=slopes)
    tol = 2e-2 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)


@requires_tpu
def test_ragged_bf16_cache_self_token_uses_cast_values():
    """With a bf16 pool the self-token must contribute its POST-cast
    value (the reference reads the cache after the write); a kernel that
    attended over the f32 pre-cast k_new/v_new would drift on exactly
    the ctx=1 row, where the self token is the whole softmax."""
    rng = np.random.default_rng(4)
    d, hq, hkv = 128, 4, 2
    args = _mixed_batch(rng, hq=hq, hkv=hkv, d=d,
                        ctx_lens=(1, 1, 5, 40, 1, 2, 0), chunk_run=False)
    (out_k, kc_k, vc_k), (out_r, kc_r, vc_r) = _run_both(
        args, d**-0.5, cache_cast=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(
        np.asarray(kc_k.astype(jnp.float32)),
        np.asarray(kc_r.astype(jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(vc_k.astype(jnp.float32)),
        np.asarray(vc_r.astype(jnp.float32)))


@requires_tpu
def test_ragged_rejects_uncast_kv():
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(5)
    d, hq, hkv = 128, 4, 2
    q, k_new, v_new, k_cache, v_cache, slots, tables, ctx = _mixed_batch(
        rng, hq=hq, hkv=hkv, d=d)
    with pytest.raises(ValueError, match="pre-cast"):
        ragged_paged_attention(q, k_new, v_new,
                               k_cache.astype(jnp.bfloat16),
                               v_cache.astype(jnp.bfloat16), slots,
                               tables, ctx, d**-0.5)


# --- a whole prompt as chunk rows against dense causal attention ----------
#
# Everything above holds the kernel to the scatter + gather composition.
# This holds the *seam* (kernel and composition alike) to an answer that
# shares nothing with either: dense causal attention over the prompt.

PROMPT_LEN, PROMPT_BS, PROMPT_W = 45, 16, 8
# How the prompt reaches the seam: the chunks (position ranges), a call
# each, and how many leading tokens are in the pool before the first.
PROMPT_ROUTES = {
    "one_chunk": (0, [(0, 45)]),
    "two_chunks_split_in_a_block": (0, [(0, 21), (21, 45)]),
    "three_chunks": (0, [(0, 16), (16, 37), (37, 45)]),
    "cached_prefix_of_two_blocks": (32, [(32, 45)]),
}
# The seam's two paths: the composition at a head size no kernel serves,
# the Pallas kernel (interpret mode here) at one it does.
PROMPT_PATHS = {"oracle_d64": (64, None), "kernel_d128": (128, True)}


@requires_tpu
@pytest.mark.parametrize("path", sorted(PROMPT_PATHS))
@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2)])
@pytest.mark.parametrize("route", sorted(PROMPT_ROUTES))
def test_prompt_as_chunk_rows_equals_dense_causal_attention(
        route, hq, hkv, alibi, path, force_pallas):
    from intellillm_tpu.layers.alibi import get_alibi_slopes
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.attention import prefill_attention_reference
    from intellillm_tpu.ops.ragged_attention import ragged_fused_attention
    d, force = PROMPT_PATHS[path]
    cached, chunks = PROMPT_ROUTES[route]
    n, bs, w, nb = PROMPT_LEN, PROMPT_BS, PROMPT_W, 64
    rng = np.random.default_rng(11)
    q = rng.normal(size=(n, hq, d)).astype(np.float32)
    k = rng.normal(size=(n, hkv, d)).astype(np.float32)
    v = rng.normal(size=(n, hkv, d)).astype(np.float32)
    slopes = (jnp.asarray(get_alibi_slopes(hq), jnp.float32)
              if alibi else None)
    scale = d**-0.5

    # A pool full of another tenant's values, so a read past what the
    # prompt wrote shows; the prompt's table, a decode row of another
    # sequence and a pad row ride along in every call.
    k_pool = rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
    v_pool = rng.normal(size=(nb, hkv, bs, d)).astype(np.float32)
    tables = rng.permutation(nb)[:2 * w].reshape(2, w).astype(np.int32)
    table, other = tables
    pos = np.arange(n)
    slot_of = table[pos // bs] * bs + pos % bs
    for p in range(cached):           # the prefix another request cached
        k_pool[table[p // bs], :, p % bs] = k[p]
        v_pool[table[p // bs], :, p % bs] = v[p]
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)

    force_pallas(force)
    label = ("pallas" if force else "reference") + ":ragged"
    before = get_compile_tracker().snapshot()["kernel_dispatch"].get(label, 0)
    out = np.zeros((n, hq, d), np.float32)
    other_ctx = 20
    for lo, hi in chunks:
        rows = hi - lo
        extra = rng.normal(size=(2, hq, d)).astype(np.float32)
        extra_kv = rng.normal(size=(2, 2, hkv, d)).astype(np.float32)
        other_slot = other[(other_ctx - 1) // bs] * bs + (other_ctx - 1) % bs
        got, k_pool, v_pool = ragged_fused_attention(
            jnp.asarray(np.concatenate([q[lo:hi], extra]))[:, None],
            jnp.asarray(np.concatenate([k[lo:hi], extra_kv[0]])),
            jnp.asarray(np.concatenate([v[lo:hi], extra_kv[1]])),
            k_pool, v_pool,
            jnp.asarray(np.concatenate([slot_of[lo:hi], [other_slot, -1]]),
                        jnp.int32),
            jnp.asarray(np.concatenate(
                [np.tile(table, (rows, 1)), other[None],
                 np.zeros((1, w), np.int32)])),
            jnp.asarray(np.concatenate([pos[lo:hi] + 1, [other_ctx, 0]]),
                        jnp.int32),
            scale, slopes)
        # Interpret mode: let the kernel's callbacks finish before the
        # next dispatch (see _run_both).
        jax.block_until_ready((got, k_pool, v_pool))
        out[lo:hi] = np.asarray(got)[:rows, 0]
        other_ctx += 1
    after = get_compile_tracker().snapshot()["kernel_dispatch"].get(label, 0)
    assert after - before == len(chunks), "the seam took the other path"

    dense = prefill_attention_reference(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        jnp.asarray([n], jnp.int32), scale, None, slopes)
    first = chunks[0][0]
    tol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(out[first:], np.asarray(dense)[0, first:],
                               rtol=tol, atol=tol)
    # The prompt's K/V are in its pages, position by position.
    np.testing.assert_array_equal(
        np.asarray(k_pool)[table[pos // bs], :, pos % bs], k)
    np.testing.assert_array_equal(
        np.asarray(v_pool)[table[pos // bs], :, pos % bs], v)


@requires_tpu
@pytest.mark.parametrize("pool", [None, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (28, 4)])
def test_ragged_chunk_run_as_one_tile(hq, hkv, pool):
    """The mixed batch with its chunk run (rows 3..5) handed in as a run:
    `run_starts` says rows 4 and 5 continue the row before them, the
    kernel writes and walks them as one tile between tiles of one, and
    output and pool equal the oracle's. (tests/kernels/test_ragged_tiles.py
    has the runs that cross tiles and pages.)"""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(6)
    d = 128
    q, k_new, v_new, k, v, slots, tables, ctx = _mixed_batch(
        rng, hq=hq, hkv=hkv, d=d)
    if pool is not None:
        k, v = k.astype(pool), v.astype(pool)
    starts = jnp.asarray([1, 1, 1, 1, 0, 0, 1], jnp.int32)
    got = jax.block_until_ready(ragged_paged_attention(
        q, k_new.astype(k.dtype), v_new.astype(v.dtype), k, v, slots,
        tables, ctx, d**-0.5, run_starts=starts))
    want = ragged_fused_attention_reference(
        q, k_new, v_new, k, v, slots, tables, ctx, d**-0.5)
    tol = 2e-2 if pool is not None else 2e-3
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32),
                               rtol=tol, atol=tol)
    for pool_got, pool_want in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(
            np.asarray(pool_got.astype(jnp.float32)),
            np.asarray(pool_want.astype(jnp.float32)))
