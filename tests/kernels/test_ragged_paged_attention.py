"""Ragged fused cache-write + attend kernel vs the incumbent composition
(reshape_and_cache then decode_attention_reference) — the golden oracle
the mixed path is pinned against. On TPU the Mosaic kernel compiles
natively; on CPU it runs under Pallas TPU interpret mode
(tests/kernels/conftest.py)."""
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


# --- the walk's edges ------------------------------------------------------

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


# --- the piece's arithmetic of a row that walks alone ----------------------
# (`_row_update`, shared with the decode kernel): pool dtype x heads x ALiBi
# x window, every row a tile of one.

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


@requires_tpu
@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("use_alibi", [False, True], ids=["rope", "alibi"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", sorted(GRID_POOLS))
def test_ragged_parity_grid(pool, heads, use_alibi, windowed):
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


@requires_tpu
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", ["bf16", "fp8_e5m2"])
def test_ragged_narrow_pool_loses_nothing_of_the_float32_dot_form(pool,
                                                                 heads):
    """The same values in a float32 pool are the float32-dot form on the
    same inputs: a row that walks alone reads within 2e-5 of it from the
    narrow pool, whatever the dots' operands are made of."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    args, kwargs = _grid_case(pool, heads, True, True, jnp.float32)
    out = jax.block_until_ready(ragged_paged_attention(
        *args, 128 ** -0.5, **kwargs))[0]
    wide = [a.astype(jnp.float32) if a.dtype == GRID_POOLS[pool] else a
            for a in args]
    out_wide = jax.block_until_ready(ragged_paged_attention(
        *wide, 128 ** -0.5, **kwargs))[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_wide),
                               rtol=0, atol=2e-5)
