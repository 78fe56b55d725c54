"""The two paged attention kernels under a sliding window against the jnp
reference: a row attends to positions `lo <= pos < ctx` only and its walk
starts at the page that holds `lo` (`walk_extent` with `lo`), so a stale
table entry below it is never read. On CPU the kernels run under Pallas
TPU interpret mode (tests/kernels/conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.pallas.paged_attention import (paged_attention,
                                                       walk_extent)
from intellillm_tpu.ops.ragged_attention import (
    paged_pool_attention, ragged_fused_attention,
    ragged_fused_attention_reference, window_lo)

requires_tpu = pytest.mark.kernel

BS, W, D = 16, 32, 128            # two 16-page groups of 256 tokens
WINDOW = 100                      # not a multiple of the page: an edge page
# Contexts below, at and beyond the window; edges of pages and of groups.
CTXS = (0, 1, 17, 99, 100, 101, 116, 117, 255, 256, 257, 356, 357, 400,
        BS * W)


def _tol():
    return 5e-3 if jax.default_backend() == "tpu" else 2e-3


def _pool(rng, b, hkv, poison_below=None):
    """Pools and tables of `b` rows owning distinct pages. With
    `poison_below` [b] (page indices), a row's table entries below that
    index point at pages of NaN: what a walk that starts too early
    moves."""
    nb = b * W + 8
    k = rng.normal(size=(nb, hkv, BS, D)).astype(np.float32)
    v = rng.normal(size=(nb, hkv, BS, D)).astype(np.float32)
    tables = rng.permutation(nb - 8)[:b * W].reshape(b, W).astype(np.int32)
    if poison_below is not None:
        k[nb - 8:] = np.nan
        v[nb - 8:] = np.nan
        for row, first in enumerate(poison_below):
            tables[row, :first] = nb - 1 - (row % 8)
    return jnp.asarray(k), jnp.asarray(v), tables


def _finite(cache):
    return jnp.where(jnp.isfinite(cache), cache, 0.0)


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)])
@pytest.mark.parametrize("ahead", [0, 1, 9])
def test_paged_window_matches_reference_and_skips_dead_pages(hq, hkv, ahead):
    """The pool part of a staged decode substep: the query stands `ahead`
    positions past the pool's last token. Pages wholly below the window
    are NaN in the pool the kernel reads, and the output is finite and
    right: the walk moved none of them."""
    rng = np.random.default_rng(5)
    ctx = np.asarray(CTXS, np.int32)
    lo = np.clip(ctx + ahead - WINDOW, 0, None)
    k, v, tables = _pool(rng, len(ctx), hkv, poison_below=lo // BS)
    q = jnp.asarray(rng.normal(size=(len(ctx), 1, hq, D)).astype(np.float32))
    out, lse = jax.block_until_ready(paged_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(ctx), D**-0.5,
        return_lse=True, lo=jnp.asarray(lo)))
    ref, ref_lse = decode_attention_reference(
        q, _finite(k), _finite(v), jnp.asarray(tables), jnp.asarray(ctx),
        D**-0.5, return_lse=True, lo=jnp.asarray(lo))
    # A row whose window holds nothing of the pool (ctx 0) attends to
    # nothing here: zeros, and a logsumexp that vanishes in the merge.
    live = (ctx - lo) > 0
    out, lse = np.asarray(out), np.asarray(lse)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=_tol(), atol=_tol())
    np.testing.assert_allclose(lse[live], np.asarray(ref_lse)[live],
                               rtol=_tol(), atol=_tol())
    assert (out[~live] == 0).all() and (lse[~live] <= -1e29).all()


@requires_tpu
def test_window_none_is_the_unwindowed_kernel():
    """A window wider than every context changes nothing, and `lo=None`
    runs the launch it always ran (its jitted wrapper's name)."""
    rng = np.random.default_rng(6)
    ctx = np.asarray(CTXS, np.int32)
    k, v, tables = _pool(rng, len(ctx), 2)
    q = jnp.asarray(rng.normal(size=(len(ctx), 1, 8, D)).astype(np.float32))
    args = (q, k, v, jnp.asarray(tables), jnp.asarray(ctx), D**-0.5)
    plain = jax.block_until_ready(paged_attention(*args))
    wide = jax.block_until_ready(paged_attention(
        *args, lo=jnp.zeros(len(ctx), jnp.int32)))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(wide),
                               rtol=1e-6, atol=1e-6)
    text = jax.jit(lambda *a: paged_pool_attention(*a, D**-0.5)).lower(
        *args[:5]).as_text()
    assert "_paged_attention_call_window" not in text


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)])
def test_ragged_window_matches_reference(hq, hkv):
    """A `mixed` step's rows under a window: decode rows on both sides of
    the window's edge, a chunk run of one sequence across it (each row
    sees its predecessors' just-written K/V), a pad row. The pool comes
    out byte-identical to the scatter pass."""
    rng = np.random.default_rng(7)
    ctx = np.asarray([1, 17, 99, 100, 101, 102, 103, 300, 0, 512],
                     np.int32)
    b = len(ctx)
    lo = np.clip(ctx - WINDOW, 0, None)
    k, v, tables = _pool(rng, b, hkv, poison_below=lo // BS)
    # Rows 3..6 are the chunk run (positions 99..102 of one sequence).
    for r in (4, 5, 6):
        tables[r] = tables[3]
    slots = np.asarray(
        [-1 if c == 0 else int(tables[i, (c - 1) // BS]) * BS + (c - 1) % BS
         for i, c in enumerate(ctx)], np.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, D)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, D)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, D)).astype(np.float32))
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    got = jax.block_until_ready(ragged_paged_attention(
        q, k_new, v_new, k, v, jnp.asarray(slots), jnp.asarray(tables),
        jnp.asarray(ctx), D**-0.5, lo=jnp.asarray(lo)))
    want = ragged_fused_attention_reference(
        q, k_new, v_new, _finite(k), _finite(v), jnp.asarray(slots),
        jnp.asarray(tables), jnp.asarray(ctx), D**-0.5,
        lo=jnp.asarray(lo))
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=_tol(), atol=_tol())
    for pool_got, pool_want in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(_finite(pool_got)),
                                      np.asarray(pool_want))


def test_seams_take_a_static_window():
    """The seams compute `lo` from the context: `window_lo`. (On the CPU
    backend the seams run their jnp oracle.)"""
    rng = np.random.default_rng(8)
    ctx = jnp.asarray(np.asarray([5, 40, 41, 90], np.int32))
    k, v, tables = _pool(rng, 4, 2)
    q = jnp.asarray(rng.normal(size=(4, 1, 4, D)).astype(np.float32))
    out, _ = paged_pool_attention(q, k, v, jnp.asarray(tables), ctx,
                                  D**-0.5, window=40, query_ahead=3)
    want, _ = decode_attention_reference(
        q, k, v, jnp.asarray(tables), ctx, D**-0.5, return_lse=True,
        lo=jnp.asarray([0, 3, 4, 53]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert window_lo(np.asarray([5, 40, 41]), 40).tolist() == [0, 0, 1]
    slots = jnp.asarray([-1, -1, -1, -1], jnp.int32)
    new = jnp.zeros((4, 2, D), jnp.float32)
    out, _, _ = ragged_fused_attention(q, new, new, k, v, slots,
                                       jnp.asarray(tables), ctx, D**-0.5,
                                       window=40)
    want = decode_attention_reference(
        q, k, v, jnp.asarray(tables), ctx, D**-0.5,
        lo=jnp.asarray([0, 0, 1, 50]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [64, 100, 4096])
def test_walk_extent_with_a_window(window):
    """Against a count by pages: the walk moves the pages that hold a
    position in [lo, seen), the edge page whole and nothing below it;
    over long contexts that is the window's pages, not the context's."""
    bs, w = 16, 512
    ends = np.arange(1, 8192, 37)                 # query position + 1
    seen = ends - 1                               # a `mixed` row's walk
    lo = np.clip(ends - window, 0, None)
    pages, multiplied = walk_extent(seen, bs, w, sub_pages=4, lo=lo)
    page_lo = np.arange(w)[None, :] * bs
    counted = ((page_lo < seen[:, None])
               & (page_lo + bs > lo[:, None])).sum(axis=1)
    np.testing.assert_array_equal(pages, counted)
    assert (pages * bs < window + 2 * bs).all()
    assert (multiplied >= pages * bs).all()
    full_pages, _ = walk_extent(seen, bs, w, sub_pages=4)
    beyond = ends > window + bs
    assert (pages[beyond] < full_pages[beyond]).all()
    np.testing.assert_array_equal(pages[lo == 0], full_pages[lo == 0])
    # a pad row moves nothing, with a window as without
    assert walk_extent(np.asarray([0, -1]), bs, w,
                       lo=np.asarray([0, 0]))[0].tolist() == [0, 0]


# --- a chunk's tile across the window's edge --------------------------------

def _chunk_rows(rng, start, n, hq, hkv, poison=True):
    """`n` rows of one sequence from position `start`, after one decode
    row of another; entries of the chunk's table below its first row's
    window point at pages of NaN."""
    ctx = np.asarray([40] + list(range(start + 1, start + n + 1)), np.int32)
    b = len(ctx)
    lo = np.clip(ctx - WINDOW, 0, None)
    below = np.concatenate([lo[:1], np.full(n, lo[1])]) // BS
    k, v, tables = _pool(rng, b, hkv,
                         poison_below=below if poison else None)
    tables[2:] = tables[1]
    slots = (tables[np.arange(b), (ctx - 1) // BS] * BS
             + (ctx - 1) % BS).astype(np.int32)
    starts = np.asarray([1, 1] + [0] * (n - 1), np.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, D)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, D)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, D)).astype(np.float32))
    return (q, k_new, v_new, k, v, jnp.asarray(slots), jnp.asarray(tables),
            jnp.asarray(ctx)), jnp.asarray(lo), jnp.asarray(starts)


# First position of a 23-row chunk (one tile of 24 rows with the decode
# row before it): every row inside the window (lo 0 throughout); rows on
# both sides of the edge, the first row whose `lo` leaves page 0 among
# them (position 115: lo 16); every row beyond the window from a page's
# middle; from a page's first token, many groups in.
WINDOW_CHUNK_STARTS = (30, 90, 104, 200, 384)


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(28, 4), (8, 2)])
@pytest.mark.parametrize("start", WINDOW_CHUNK_STARTS)
def test_ragged_window_tile_matches_reference(start, hq, hkv):
    """The rows of one tile under a window: each row's own `lo` masks the
    walk and the tile's own keys, the walk starts at the page of the
    first row's `lo` and reads no page below it (those hold NaN)."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(21)
    (q, k_new, v_new, k, v, slots, tables, ctx), lo, starts = _chunk_rows(
        rng, start, 23, hq, hkv)
    got = jax.block_until_ready(ragged_paged_attention(
        q, k_new, v_new, k, v, slots, tables, ctx, D**-0.5, lo=lo,
        run_starts=starts))
    want = ragged_fused_attention_reference(
        q, k_new, v_new, _finite(k), _finite(v), slots, tables, ctx,
        D**-0.5, lo=lo)
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=_tol(), atol=_tol())
    for pool_got, pool_want in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(_finite(pool_got)),
                                      np.asarray(pool_want))


@requires_tpu
def test_ragged_window_tiles_equal_rows_alone():
    """What `run_starts` changes is how often the context is walked, not
    what a row attends to: the same step with every row a tile of one."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(22)
    args, lo, starts = _chunk_rows(rng, 104, 23, 8, 2, poison=False)
    tiled = jax.block_until_ready(ragged_paged_attention(
        *args, D**-0.5, lo=lo, run_starts=starts))
    alone = jax.block_until_ready(ragged_paged_attention(
        *args, D**-0.5, lo=lo))
    np.testing.assert_allclose(np.asarray(tiled[0]), np.asarray(alone[0]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(tiled[1:], alone[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@requires_tpu
@pytest.mark.parametrize("window", [None, WINDOW])
def test_seam_tiles_heads_padded_to_a_lane_tile(window):
    """Heads of 64 in a pool of 128 lanes (`PagedAttention` pads q, k and
    v with zeros), a chunk's rows as tiles through the seam: equal to the
    jnp composition at the head's own width."""
    from intellillm_tpu.layers.attention import (AttentionMetadata,
                                                 PagedAttention)
    from intellillm_tpu.ops.dispatch import set_use_pallas
    rng = np.random.default_rng(23)
    hq, hkv, head = 8, 2, 64
    (q, k_new, v_new, k, v, slots, tables, ctx), _, starts = _chunk_rows(
        rng, 104, 23, hq, hkv, poison=False)
    b = q.shape[0]
    layer = PagedAttention(hq, head, head**-0.5, hkv, sliding_window=window)
    meta = AttentionMetadata(slot_mapping=slots[:, None], context_lens=ctx,
                             block_tables=tables, run_starts=starts)
    narrow = (q[..., :head].reshape(b, 1, hq, head),
              k_new[..., :head].reshape(b, 1, hkv, head),
              v_new[..., :head].reshape(b, 1, hkv, head))
    outs = {}
    for pallas in (True, False):
        set_use_pallas(pallas)
        try:
            out, pools = layer(*narrow, (k, v), meta)
            outs[pallas] = jax.block_until_ready((out, ) + tuple(pools))
        finally:
            set_use_pallas(None)
    assert outs[True][0].shape == (b, 1, hq, head)
    np.testing.assert_allclose(np.asarray(outs[True][0]),
                               np.asarray(outs[False][0]),
                               rtol=_tol(), atol=_tol())
    for got, want in zip(outs[True][1:], outs[False][1:]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@requires_tpu
@pytest.mark.parametrize("known_runs", [True, False])
@pytest.mark.parametrize("window", [None, WINDOW])
def test_seam_tiles_on_a_two_device_mesh(window, known_runs):
    """`shard_over_heads`: each device runs the tiles on its half of the
    KV heads; `run_starts` is row metadata, replicated (or None: no row
    is known to continue another, and every row walks alone)."""
    from intellillm_tpu.ops.dispatch import set_use_pallas
    from jax.sharding import Mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    rng = np.random.default_rng(24)
    (q, k_new, v_new, k, v, slots, tables, ctx), lo, starts = _chunk_rows(
        rng, 104, 23, 8, 2, poison=False)
    set_use_pallas(True)
    try:
        got = jax.block_until_ready(ragged_fused_attention(
            q, k_new, v_new, k, v, slots, tables, ctx, D**-0.5, mesh=mesh,
            window=window, run_starts=starts if known_runs else None))
    finally:
        set_use_pallas(None)
    want = ragged_fused_attention_reference(
        q, k_new, v_new, k, v, slots, tables, ctx, D**-0.5,
        lo=None if window is None else lo)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=_tol(), atol=_tol())
    for pool_got, pool_want in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(pool_got),
                                      np.asarray(pool_want))
