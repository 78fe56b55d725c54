"""Pallas paged-attention kernel vs the jnp reference over a parameter
grid (reference pattern: `tests/kernels/test_attention.py` sweeps dtypes ×
head configs × block sizes against `ref_single_query_cached_kv_attention`).

On TPU the Mosaic kernel compiles natively; on CPU it runs under
Pallas TPU interpret mode (tests/kernels/conftest.py), so the grid is
exercised everywhere.

One kernel, one grid of tests: the old v3/v4 twin modules were
consolidated — the head-block-vectorized (v4) kernel is the only
implementation, so the former per-variant fixtures and the v3/v4
cross-consistency check are gone with the twin.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.pallas.paged_attention import paged_attention

# On CPU the kernels run in TPU interpret mode (see conftest.py);
# the marker is kept as documentation of the native target.
requires_tpu = pytest.mark.kernel


def make_cache(rng, nb, hkv, bs, d, dtype):
    k = rng.normal(size=(nb, hkv, bs, d)).astype(dtype)
    v = rng.normal(size=(nb, hkv, bs, d)).astype(dtype)
    return jnp.asarray(k), jnp.asarray(v)


def _oracle_tol(use_alibi: bool) -> float:
    # Real-TPU ALiBi runs land up to ~9e-3 off the f32 jnp oracle (online
    # vs full softmax rounding under large negative biases). CPU interpret
    # mode keeps a tight bound so kernel-logic regressions fail loudly.
    if jax.default_backend() == "tpu":
        return 2e-2 if use_alibi else 5e-3
    return 2e-3


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("w", [8, 16])    # w=16 exercises ppg=16 groups
@pytest.mark.parametrize("use_alibi", [False, True])
def test_paged_attention_matches_reference(hq, hkv, w, use_alibi):
    """The consolidated kernel vs the jnp oracle over head configs ×
    table widths × ALiBi, including the logsumexp output."""
    rng = np.random.default_rng(0)
    b, d, bs = 4, 128, 16
    nb = b * w + 8
    k_cache, v_cache = make_cache(rng, nb, hkv, bs, d, np.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    ctx = jnp.asarray(np.asarray([1, 17, 63, w * bs], np.int32))
    slopes = (jnp.asarray(rng.random(hq).astype(np.float32))
              if use_alibi else None)

    out, lse = paged_attention(q, k_cache, v_cache, tables, ctx,
                               d**-0.5, slopes, return_lse=True)
    ref, ref_lse = decode_attention_reference(q, k_cache, v_cache, tables,
                                              ctx, d**-0.5, slopes,
                                              return_lse=True)
    tol = _oracle_tol(use_alibi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=tol, atol=tol)


@requires_tpu
def test_paged_attention_lse_matches_reference():
    rng = np.random.default_rng(1)
    b, hq, hkv, d, nb, bs, w = 2, 4, 2, 128, 32, 16, 4
    k_cache, v_cache = make_cache(rng, nb, hkv, bs, d, np.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    block_tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    context_lens = jnp.asarray(np.asarray([5, 40], np.int32))
    scale = d**-0.5

    out_k, lse_k = paged_attention(q, k_cache, v_cache, block_tables,
                                   context_lens, scale, return_lse=True)
    out_r, lse_r = decode_attention_reference(q, k_cache, v_cache,
                                              block_tables, context_lens,
                                              scale, return_lse=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_r),
                               rtol=2e-2, atol=2e-2)


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2)])
def test_paged_attention_alibi_matches_reference(hq, hkv):
    """ALiBi bias is computed natively inside the kernel; previously this
    configuration fell back to the jnp gather path."""
    from intellillm_tpu.layers.alibi import get_alibi_slopes

    rng = np.random.default_rng(3)
    b, d, nb, bs, w = 4, 128, 64, 16, 8
    k_cache, v_cache = make_cache(rng, nb, hkv, bs, d, np.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    context_lens = jnp.asarray(np.asarray([1, 17, 63, 128], np.int32))
    slopes = jnp.asarray(get_alibi_slopes(hq), jnp.float32)
    scale = d**-0.5

    out_k = paged_attention(q, k_cache, v_cache, jnp.asarray(tables),
                            context_lens, scale, alibi_slopes=slopes)
    out_r = decode_attention_reference(q, k_cache, v_cache,
                                       jnp.asarray(tables), context_lens,
                                       scale, alibi_slopes=slopes)
    tol = _oracle_tol(use_alibi=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)


@requires_tpu
def test_paged_attention_bf16_cache_wide_table():
    """bf16 KV with a 32-wide block table (llama-7b decode shape at
    max_model_len=512): ppg hits its 16-page cap, giving the largest
    VMEM double-buffer the kernel ever allocates for 2-byte caches —
    validated on real v5e (the f32 grid above is 2x larger still)."""
    rng = np.random.default_rng(7)
    b, d, bs, hq, hkv, w = 4, 128, 16, 32, 32, 32
    nb = b * w + 8
    k_cache, v_cache = make_cache(rng, nb, hkv, bs, d, np.float32)
    k_cache = k_cache.astype(jnp.bfloat16)
    v_cache = v_cache.astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    ctx = jnp.asarray(np.asarray([1, 100, 300, w * bs], np.int32))

    out = paged_attention(q, k_cache, v_cache, tables, ctx, d**-0.5)
    ref = decode_attention_reference(q, k_cache, v_cache, tables, ctx,
                                     d**-0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# --- the walk's edges ------------------------------------------------------

WALK_BS, WALK_W = 16, 32            # two 16-page groups of 256 tokens
WALK_EDGES = (0, 1, WALK_BS - 1, WALK_BS, WALK_BS + 1, 255, 256, 257,
              WALK_BS * WALK_W)
WALK_POOLS = {"bf16": jnp.bfloat16, "fp8_e5m2": jnp.float8_e5m2}


def _edge_batch(rng, ctx, hkv, dtype, poison=None):
    """Rows alternating every edge context with `ctx`, so that the
    cross-step prefetch meets `ctx` as predecessor and as successor of
    each. Every row owns distinct pages; with `poison`, every page that
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
    return (jnp.asarray(k).astype(dtype), jnp.asarray(v).astype(dtype),
            jnp.asarray(tables), jnp.asarray(ctxs))


def _finite_pool(cache):
    """What the reference may read: the same pool with the poison out."""
    c = cache.astype(jnp.float32)
    return jnp.where(jnp.isfinite(c), c, 0.0).astype(cache.dtype)


def _assert_rows_match(out, lse, ref, ref_lse, ctxs):
    """Live rows equal the oracle; a pad row attends to nothing: zeros,
    and a logsumexp that vanishes in any merge."""
    out, lse, live = np.asarray(out), np.asarray(lse), np.asarray(ctxs) > 0
    assert np.isfinite(out).all()
    tol = _oracle_tol(use_alibi=False)
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(lse[live], np.asarray(ref_lse)[live],
                               rtol=tol, atol=tol)
    assert (out[~live] == 0).all() and (lse[~live] <= -1e29).all()


@requires_tpu
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2)])
@pytest.mark.parametrize("pool", sorted(WALK_POOLS))
@pytest.mark.parametrize("ctx", WALK_EDGES)
def test_paged_attention_walk_edges(ctx, pool, hq, hkv):
    """Output and logsumexp at the edges of the walk's rule: a pad row,
    one token, a page less one / exactly / plus one, a group less one /
    exactly / plus one, the whole table."""
    rng = np.random.default_rng(11)
    k_cache, v_cache, tables, ctxs = _edge_batch(rng, ctx, hkv,
                                                 WALK_POOLS[pool])
    q = jnp.asarray(rng.normal(size=(len(ctxs), 1, hq, 128)
                               ).astype(np.float32))
    out, lse = jax.block_until_ready(paged_attention(
        q, k_cache, v_cache, tables, ctxs, 128**-0.5, return_lse=True))
    ref, ref_lse = decode_attention_reference(
        q, k_cache, v_cache, tables, ctxs, 128**-0.5, return_lse=True)
    _assert_rows_match(out, lse, ref, ref_lse, ctxs)


@requires_tpu
@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("pool", sorted(WALK_POOLS))
def test_paged_attention_moves_no_page_a_row_does_not_hold(pool, poison):
    """Every page outside a row's first ceil(ctx / bs) table entries is
    NaN or Inf, the tables' tails point at them, and the output is
    finite and right: the walk moved none of them (a moved page's V
    reaches the PV dot, where 0 x NaN is NaN, whatever the mask says)."""
    rng = np.random.default_rng(12)
    k_cache, v_cache, tables, ctxs = _edge_batch(
        rng, 40, 2, WALK_POOLS[pool], poison=poison)
    q = jnp.asarray(rng.normal(size=(len(ctxs), 1, 8, 128)
                               ).astype(np.float32))
    out, lse = jax.block_until_ready(paged_attention(
        q, k_cache, v_cache, tables, ctxs, 128**-0.5, return_lse=True))
    ref, ref_lse = decode_attention_reference(
        q, _finite_pool(k_cache), _finite_pool(v_cache), tables, ctxs,
        128**-0.5, return_lse=True)
    _assert_rows_match(out, lse, ref, ref_lse, ctxs)


def test_walk_extent_against_a_count_over_the_traffic():
    """The rule, against a count by pages, over every context the
    benchmark's `decode-closed` traffic sends through the two kernels
    (64-block tables): what the walk moves is within 5 % of what decode
    rows attend to and within 10 % for prefill-chunk rows, where the
    fixed 16-page groups it replaced moved 1.35x and 2.4x."""
    import json
    import os
    import sys
    chip = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                        "chip")
    sys.path.insert(0, chip)
    from harness.loadgen import length_pairs

    from intellillm_tpu.ops.pallas.paged_attention import walk_extent
    with open(os.path.join(chip, "traffic", "decode-closed.json")) as f:
        traffic = json.load(f)
    pairs = length_pairs(traffic, traffic["block"])
    bs, w = 16, 64
    # a decode row attends to p + j + 1 tokens, all but its newest in the
    # pool; prefill row c of a prompt to c, all but its own in the pool
    decode = np.asarray([p + j for p, o in pairs for j in range(o)])
    prefill = np.asarray([c - 1 for p, _ in pairs for c in range(1, p + 1)])
    for walk, bound, old_ratio in ((decode, 1.05, 1.3), (prefill, 1.10, 2.2)):
        pages, multiplied = walk_extent(walk, bs, w, sub_pages=4)
        counted = (np.arange(w)[None, :] * bs < walk[:, None]).sum(axis=1)
        np.testing.assert_array_equal(pages, counted)
        assert (multiplied >= pages * bs).all()
        assert (multiplied < pages * bs + 4 * bs).all()
        attended = (walk + 1).sum()
        assert pages.sum() * bs / attended <= bound
        fixed_groups = np.maximum(-(-walk // 256), 1) * 256
        assert fixed_groups.sum() / attended >= old_ratio
    # a pad row moves nothing; a context past the table is held to it
    assert walk_extent(np.asarray([0, -1, 5000]), bs, w)[0].tolist() == [
        0, 0, w]


# --- the piece's arithmetic: pool dtype x heads x ALiBi x window -----------

GRID_POOLS = {"f32": jnp.float32, "bf16": jnp.bfloat16,
              "fp8_e5m2": jnp.float8_e5m2}
# KV heads x query heads a KV head: Mistral / LFM2, DeepSeek-MoE,
# SmallThinker, and a tensor-parallel shard of few heads.
GRID_HEADS = {"8x4": (8, 4), "16x1": (16, 1), "4x7": (4, 7), "4x1": (4, 1)}
GRID_WINDOW = 100           # not a whole page: the mask cuts inside one


def _grid_case(pool, heads, use_alibi, windowed, q_dtype, seed=21):
    """Four rows under 32-block tables: one token, a tail of three pages,
    a tail that ends a sub-group short of the group, the whole table."""
    hkv, g = GRID_HEADS[heads]
    rng = np.random.default_rng(seed)
    b, d, bs, w = 4, 128, 16, 32
    nb = b * w + 8
    k_cache, v_cache = make_cache(rng, nb, hkv, bs, d, np.float32)
    k_cache = (k_cache * 0.5).astype(GRID_POOLS[pool])
    v_cache = (v_cache * 0.5).astype(GRID_POOLS[pool])
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)).astype(np.float32)
                    ).astype(q_dtype)
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    ctx = np.asarray([1, 37, 300, w * bs], np.int32)
    kwargs = {}
    if use_alibi:
        kwargs["alibi_slopes"] = jnp.asarray(
            rng.random(hkv * g).astype(np.float32) * 0.1)
    if windowed:
        kwargs["lo"] = jnp.asarray(np.maximum(ctx - GRID_WINDOW, 0))
    return (q, k_cache, v_cache, tables, jnp.asarray(ctx), d ** -0.5), kwargs


@requires_tpu
@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("use_alibi", [False, True], ids=["rope", "alibi"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", sorted(GRID_POOLS))
def test_paged_attention_parity_grid(pool, heads, use_alibi, windowed):
    """Every pool dtype x every head shape the cells serve (8 // G heads
    share a block of score rows where G is 1, 2 or 4; G = 7 pads a row),
    against the float32 oracle: the output as the serving path asks it (a
    bfloat16 query for the narrow pools) and the float32 logsumexp."""
    q_dtype = jnp.float32 if pool == "f32" else jnp.bfloat16
    args, kwargs = _grid_case(pool, heads, use_alibi, windowed, q_dtype)
    out, lse = jax.block_until_ready(
        paged_attention(*args, return_lse=True, **kwargs))
    ref, ref_lse = decode_attention_reference(*args, return_lse=True,
                                              **kwargs)
    tol = _oracle_tol(use_alibi) if pool == "f32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    tol = _oracle_tol(use_alibi)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=tol, atol=tol)


@requires_tpu
@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", ["bf16", "fp8_e5m2"])
def test_narrow_pool_loses_nothing_of_the_float32_dot_form(pool, heads,
                                                           q_dtype):
    """A bfloat16 / fp8 pool value is exact in float32: the same values in
    a float32 pool are the float32-dot form on the same inputs, and the
    narrow pool's output and logsumexp stay within 2e-5 of it (under
    anything a bfloat16 logit can see) whatever the dots' operands are
    made of."""
    args, kwargs = _grid_case(pool, heads, True, True, q_dtype)
    q, k_cache, v_cache = args[:3]
    out, lse = jax.block_until_ready(paged_attention(
        *args, return_lse=True, **kwargs))
    wide, wide_lse = jax.block_until_ready(paged_attention(
        q.astype(jnp.float32), k_cache.astype(jnp.float32),
        v_cache.astype(jnp.float32), *args[3:], return_lse=True, **kwargs))
    # The output's dtype is the query's: a bfloat16 output is held to its
    # own rounding, the float32 logsumexp to 2e-5 either way.
    out_tol = 2e-5 if q_dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(wide), rtol=0, atol=out_tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(wide_lse),
                               rtol=0, atol=2e-5)


@requires_tpu
@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="the chip's oracle runs bfloat16 passes")
@pytest.mark.parametrize("plain", [True, False], ids=["plain",
                                                      "alibi-window"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
def test_float32_pool_stands_where_the_flat_form_stood(heads, plain):
    """A float32 pool keeps float32 dots. The flat form read at most
    1.5e-7 (output) and 4.8e-7 (logsumexp) from the float32 oracle on
    these inputs (PR 41's tree, interpret mode); a head's sums now run
    over its own columns only, which reorders them and changes nothing a
    float32 can hold: the same limits, with float32 rounding's room."""
    args, kwargs = _grid_case("f32", heads, not plain, not plain,
                              jnp.float32)
    out, lse = jax.block_until_ready(
        paged_attention(*args, return_lse=True, **kwargs))
    ref, ref_lse = decode_attention_reference(*args, return_lse=True,
                                              **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=5e-7)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=0, atol=2e-6)
