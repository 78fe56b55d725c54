"""The ragged kernel's tiles of many rows against the oracle.

`run_starts` says which rows of a `mixed` step continue the row before
them (a prompt chunk's); the kernel (`ops/pallas/ragged_paged_attention.py`)
then writes a run's K/V a page at a time and walks its paged context once
a tile of its rows. The oracle (reshape_and_cache then
decode_attention_reference) knows nothing of runs: every case is held to
it on the output and on the pool's bytes. The cases under a sliding
window are in test_window_attention.py. On CPU the kernel runs under
Pallas TPU interpret mode (tests/kernels/conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.ragged_attention import (
    ragged_fused_attention_reference)

requires_tpu = pytest.mark.kernel
POOLS = {"bf16": jnp.bfloat16, "fp8_e5m2": jnp.float8_e5m2}

TILE_BS, TILE_W = 16, 16
# (tokens already in the pool, rows in this step) a sequence, None a pad
# row; rows lie in list order. With `small_tiles` a tile has 16 rows.
TILE_LAYOUTS = {
    # One run over three tiles, from a position inside a page to one.
    "run_across_tiles": [(21, 44), None, None, None, None],
    # Starts and ends inside pages, inside one tile.
    "run_inside_pages": [(37, 9)] + [None] * 7,
    # A prompt's first chunk: no context to walk, only the tile itself.
    "first_chunk": [(0, 32)],
    # Runs of one between long runs; a run of one ends the batch.
    "ones_between_runs": [(5, 1), (16, 20), (80, 1), (3, 1), (48, 23),
                          (9, 1), (250, 1)],
    # Two sequences (and two single rows) inside one tile's span of rows.
    "two_runs_in_a_span": [(30, 6), (12, 1), (100, 7), (63, 1), None],
    # A pad row between live rows, and pad rows at the end.
    "pad_rows": [(17, 5), None, (40, 10), None, None, None, None, None,
                 None, None, None, None, None, None, None, None, None],
    # Whole pages only: 32 rows from a page's first token.
    "whole_pages": [(64, 32)],
}
TILE_HEADS = {"g7": (28, 4), "g1": (4, 4), "g4": (8, 2)}


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of at most 16 rows, so that a small batch has several. The
    launches are cached by shape, not by this rule: the steps that run
    under it have shapes no other test has (48 and 96 rows)."""
    from intellillm_tpu.ops.pallas import ragged_paged_attention as rpa
    monkeypatch.setattr(rpa, "_tile_height", lambda g: 16)


def _run_batch(rng, layout, *, hq, hkv, dtype=jnp.float32, bs=TILE_BS,
               w=TILE_W, d=128, rows=48):
    """The step's arrays for `layout`, each sequence with a table of its
    own over a pool of random pages, and its `run_starts`; pad rows make
    it up to `rows` rows (one program for every layout)."""
    layout = list(layout)
    layout += [None] * (rows - sum(1 if e is None else e[1] for e in layout))
    tables, ctxs, slots, starts = [], [], [], []
    nb = 8 * w + 4              # the same pool for every layout
    perm = rng.permutation(nb).astype(np.int32)
    sequences = 0
    for entry in layout:
        if entry is None:
            tables.append(np.zeros(w, np.int32))
            ctxs.append(0)
            slots.append(-1)
            starts.append(1)
            continue
        table = perm[sequences * w:(sequences + 1) * w]
        sequences += 1
        have, n = entry
        for j in range(n):
            pos = have + j
            tables.append(table)
            ctxs.append(pos + 1)
            slots.append(int(table[pos // bs]) * bs + pos % bs)
            starts.append(int(j == 0))
    b = len(ctxs)
    k = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))
    return ((q, k_new, v_new, k.astype(dtype), v.astype(dtype),
             jnp.asarray(np.asarray(slots, np.int32)),
             jnp.asarray(np.stack(tables)),
             jnp.asarray(np.asarray(ctxs, np.int32))),
            jnp.asarray(np.asarray(starts, np.int32)))


def _assert_tiled_matches(args, starts, scale, slopes=None, tol=None):
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    q, k_new, v_new, k, v, slots, tables, ctx = args
    got = jax.block_until_ready(ragged_paged_attention(
        q, k_new.astype(k.dtype), v_new.astype(v.dtype), k, v, slots,
        tables, ctx, scale, slopes, run_starts=starts))
    want = ragged_fused_attention_reference(
        q, k_new, v_new, k, v, slots, tables, ctx, scale, slopes)
    out_k, live = np.asarray(got[0], np.float32), np.asarray(ctx) > 0
    assert np.isfinite(out_k).all()
    if tol is None:
        tol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
    np.testing.assert_allclose(out_k[live],
                               np.asarray(want[0], np.float32)[live],
                               rtol=tol, atol=tol)
    assert (out_k[~live] == 0).all()
    for pool_got, pool_want in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(
            np.asarray(pool_got.astype(jnp.float32)),
            np.asarray(pool_want.astype(jnp.float32)))
    return got


@requires_tpu
@pytest.mark.parametrize("heads", sorted(TILE_HEADS))
@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
def test_ragged_tiles_match_the_oracle(small_tiles, layout, heads):
    hq, hkv = TILE_HEADS[heads]
    rng = np.random.default_rng(11)
    args, starts = _run_batch(rng, TILE_LAYOUTS[layout], hq=hq, hkv=hkv)
    _assert_tiled_matches(args, starts, 128**-0.5)


@requires_tpu
@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("layout", ["run_across_tiles",
                                    "ones_between_runs"])
def test_ragged_tiles_write_the_pool_dtype(small_tiles, layout, pool):
    """bf16 and fp8 pools: the tile's own keys are read as the pool will
    hold them (post-cast), and the pages it writes are byte-equal."""
    rng = np.random.default_rng(12)
    args, starts = _run_batch(rng, TILE_LAYOUTS[layout], hq=8, hkv=2,
                              dtype=POOLS[pool])
    _assert_tiled_matches(args, starts, 128**-0.5, tol=2e-2)


@requires_tpu
@pytest.mark.parametrize("layout", ["run_across_tiles",
                                    "two_runs_in_a_span"])
def test_ragged_tiles_alibi(small_tiles, layout):
    """ALiBi's bias takes each row's own position."""
    from intellillm_tpu.layers.alibi import get_alibi_slopes
    rng = np.random.default_rng(13)
    slopes = jnp.asarray(get_alibi_slopes(8), jnp.float32)
    args, starts = _run_batch(rng, TILE_LAYOUTS[layout], hq=8, hkv=2)
    _assert_tiled_matches(args, starts, 128**-0.5, slopes)


@requires_tpu
def test_ragged_later_chunk_reads_the_earlier_chunks_pages(small_tiles):
    """Two steps of one prompt: the second chunk's tiles walk the pages
    the first chunk's tiles wrote (and, inside each step, a later tile
    reads an earlier tile's tokens from the pool)."""
    rng = np.random.default_rng(14)
    (q, k_new, v_new, k, v, slots, tables, ctx), starts = _run_batch(
        rng, [(0, 96)], hq=8, hkv=2, rows=96)
    first = slice(0, 48)
    second = slice(48, 96)
    k, v = _assert_tiled_matches(
        (q[first], k_new[first], v_new[first], k, v, slots[first],
         tables[first], ctx[first]), starts[first], 128**-0.5)[1:]
    _assert_tiled_matches(
        (q[second], k_new[second], v_new[second], k, v, slots[second],
         tables[second], ctx[second]), starts[first], 128**-0.5)


@requires_tpu
def test_ragged_tile_of_128_rows():
    """The tile as the cells run it: 128 rows a tile, a 256-row run over
    more than one page group, and single rows in the spans' last rows."""
    rng = np.random.default_rng(15)
    args, starts = _run_batch(rng, [(300, 250), (77, 1), (500, 1),
                                    (0, 3), None],
                              hq=8, hkv=2, w=64, rows=256)
    _assert_tiled_matches(args, starts, 128**-0.5)


def test_tile_rows_follow_the_shapes():
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        launch_rows, tile_rows, tiles_many, whole_spans)
    assert tile_rows(512, 7) == tile_rows(512, 4) == tile_rows(256, 1) == 128
    assert tile_rows(512, 16) == 64 and tile_rows(16, 7) == 16
    assert tile_rows(24, 4) == 24 and tile_rows(8, 1) == 8
    assert launch_rows(512, 512) == 256 and launch_rows(512, 64) == 512
    # A launch of fewer rows than a full tile has no tile of many: a
    # run shorter than a full tile never is one, whatever its step.
    assert tiles_many(512, 7) and tiles_many(128, 4) and tiles_many(64, 16)
    assert not (tiles_many(64, 7) or tiles_many(16, 4) or tiles_many(32, 16))
    whole = whole_spans(np.asarray([1, 0, 0, 0, 1, 0, 1, 0, 0, 0, 0, 0]),
                        np.asarray([1, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1],
                                   bool), 4, xp=np)
    # Rows 0-3 are a whole span of one run: a tile of many, and so are
    # rows 8-11 (they continue row 7). Rows 4-7 are not (a pad row
    # among them): each a tile of one.
    assert whole.tolist() == [True, False, True]


@requires_tpu
def test_a_runs_output_does_not_follow_what_else_the_step_holds(small_tiles):
    """The same 40-row run alone from row 0 and from row 7 behind a
    sequence of single rows (cut by the spans at other rows, so other
    keys are the tiles' own and other keys are walked, and other rows
    walk alone): the same output up to the order of float32 sums."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(16)
    (q, k_new, v_new, k, v, slots, tables, ctx), starts = _run_batch(
        rng, [(9, 40)] + [(20 + i, 1) for i in range(7)], hq=8, hkv=2,
        dtype=jnp.bfloat16)
    k_new, v_new = k_new.astype(k.dtype), v_new.astype(v.dtype)
    order = np.r_[40:47, 0:40, 47]          # the single rows first
    outs = []
    for rows in (np.arange(48), order):
        out, _, _ = jax.block_until_ready(ragged_paged_attention(
            q[rows], k_new[rows], v_new[rows], k, v, slots[rows],
            tables[rows], ctx[rows], 128**-0.5, run_starts=starts[rows]))
        outs.append(np.asarray(out, np.float32)[np.argsort(rows)])
    np.testing.assert_allclose(outs[0][:40], outs[1][:40], rtol=1e-5,
                               atol=1e-5)
