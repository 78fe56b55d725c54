"""`ops/state_rows.py` against what it replaced: `gather_rows` /
`scatter_rows` give bit for bit what `pool.at[slots].get(mode="fill",
fill_value=0)` / `pool.at[slots].set(rows, mode="drop")` give, at the two
state models' widths, in both dtypes a 2-d state array can have, with pad
rows, NaN / inf / -0.0 in the pool and in the rows, and (read only)
repeated slots."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.state_rows import gather_rows, scatter_rows

SLOTS = 128
WIDTHS = [4096, 73728]          # LFM2's conv state, a delta-rule layer's taps
DTYPES = ["bfloat16", "float32"]
# (rows, width): a decode stage's 16 / 128 rows; 512 rows of a `mixed`
# step (more rows than slots) at the narrow pool.
SHAPES = [(r, w) for w in WIDTHS for r in (16, 128)] + [(512, 4096)]


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def draw(rng, shape, dtype):
    """Normal values with every special value a float has among them."""
    a = rng.normal(size=shape).astype(np.float32)
    for value in (np.nan, np.inf, -np.inf, -0.0, 0.0):
        a[rng.random(shape) < 0.01] = value
    return jnp.asarray(a, jnp.dtype(dtype))


def slot_pattern(rng, pattern, rows):
    """Slots a row, pad = SLOTS. Distinct but for `repeated`."""
    live = min(rows, SLOTS)
    slots = np.full(rows, SLOTS, np.int32)
    where = rng.permutation(rows)[:live]
    if pattern == "permutation":        # of all slots, where rows allow
        slots[where] = rng.permutation(SLOTS)[:live]
    elif pattern == "some_pad":
        keep = where[:max(1, live // 2)]
        slots[keep] = rng.permutation(SLOTS)[:len(keep)]
    elif pattern == "identity":
        slots[:live] = np.arange(live)
    elif pattern == "repeated":
        slots[:] = rng.integers(0, SLOTS // 4, size=rows)
        slots[rng.random(rows) < 0.2] = SLOTS
    else:
        assert pattern == "all_pad"
    return jnp.asarray(slots)


@pytest.mark.parametrize("pattern", ["permutation", "some_pad", "identity",
                                     "all_pad", "repeated"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rows,width", SHAPES)
def test_gather_rows_is_at_get_bit_for_bit(rows, width, dtype, pattern):
    rng = np.random.default_rng(rows + width)
    pool = draw(rng, (SLOTS, width), dtype)
    slots = slot_pattern(rng, pattern, rows)
    got = jax.jit(gather_rows)(pool, slots)
    want = pool.at[slots].get(mode="fill", fill_value=0)
    assert got.dtype == pool.dtype and got.shape == (rows, width)
    np.testing.assert_array_equal(bits(got), bits(want))
    pad = np.asarray(slots) == SLOTS
    assert not bits(got)[pad].any()                 # +0.0 in every bit


@pytest.mark.parametrize("pattern", ["permutation", "some_pad", "identity",
                                     "all_pad"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rows,width", SHAPES)
def test_scatter_rows_is_at_set_bit_for_bit(rows, width, dtype, pattern):
    rng = np.random.default_rng(rows + width + 1)
    pool = draw(rng, (SLOTS, width), dtype)
    new = draw(rng, (rows, width), dtype)
    slots = slot_pattern(rng, pattern, rows)
    want = pool.at[slots].set(new, mode="drop")
    got = jax.jit(scatter_rows)(pool, slots, new)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    np.testing.assert_array_equal(bits(got), bits(want))
    # A slot no row writes keeps its bytes, NaN and inf among them.
    unwritten = np.setdiff1d(np.arange(SLOTS), np.asarray(slots))
    np.testing.assert_array_equal(bits(got)[unwritten],
                                  bits(pool)[unwritten])
    if len(unwritten):
        assert np.isnan(np.asarray(pool, np.float32)[unwritten]).any()


def test_scatter_rows_casts_the_rows_to_the_pool():
    """As `.at[].set` does: a float32 row lands in a bfloat16 pool as
    bfloat16."""
    pool = jnp.zeros((4, 8), jnp.bfloat16)
    new = jnp.full((2, 8), 1.00390625, jnp.float32)
    got = scatter_rows(pool, jnp.asarray([2, 4], jnp.int32), new)
    want = pool.at[jnp.asarray([2, 4])].set(new, mode="drop")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(bits(got), bits(want))


def test_benchmark_rehearses_and_the_product_is_not_exact():
    """`benchmarks/kernels/benchmark_state_rows.py --tiny` runs its control
    flow on the CPU and prints no time; every form but the selection
    product gives the `.at[]` forms' bytes with NaN, inf and -0.0 in the
    pool, and the product (why it was not kept) does not."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "kernels",
                                      "benchmark_state_rows.py"), "--tiny"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    table = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    assert {(t["way"], t["form"]) for t in table} >= {
        ("scatter", "kept"), ("scatter", "at_set"), ("scatter", "onehot"),
        ("gather", "kept"), ("gather", "onehot")}
    for t in table:
        assert t["us_a_call"] is None and t["exact"], t
        assert t["exact_with_specials"] == (t["form"] != "onehot"), t
