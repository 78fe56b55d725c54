"""The Mamba-1 selective scan behind the state seam (`ops/linear_attention.py`
`sscan_update` / `sscan_step_reference` / `sscan_chunk_reference`, and the
Pallas kernels of `ops/pallas/selective_scan.py` in interpret mode): a decay a
(channel, state) pair, `dt` a channel, one B and one C a token.

What they are held to is the plain recurrence in numpy float64 with the state
as published, `[inner, N]`, written out here, so that the oracles' tiling and
the kernels' are both checked against the rule and not against each other.
One static configuration a kernel (tier-1 has little time left): the chunk
form on a `mixed` step of 64 rows that holds runs of 1, 3, 16 and 40 rows,
the step form on a decode substep of 8.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops import linear_attention as la
from intellillm_tpu.ops.pallas import selective_scan as ss

INNER, N, SLOTS = 256, 16, 5
TILES = INNER // 128
# float32 against float64 over runs of up to 40 tokens of O(1) terms summed
# over 16 state channels.
TOL = 2e-5


def draw(t, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, INNER))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=(t, INNER)))
    a = -rng.uniform(1.0, 16.0, size=(INNER, N))
    b, c = rng.normal(size=(2, t, N))
    pool = rng.normal(size=(SLOTS + 1, INNER, N))
    return x, dt, a, b, c, pool


def as_pool(published):
    """[S, inner, N] -> the kernels' [S, tiles, N, 128]."""
    s = published.shape[0]
    return np.moveaxis(
        np.moveaxis(published, 1, 2).reshape(s, N, TILES, 128), 2, 1)


def recurrence(x, dt, a, b, c, pool, slots, positions):
    """float64, a row at a time; pad rows (slot == SLOTS) give zeros and
    touch nothing."""
    pool = pool.copy()
    y = np.zeros_like(x)
    cur = None
    for t in range(len(slots)):
        s, p = int(slots[t]), int(positions[t])
        if s == SLOTS:
            cur = None
            continue
        continues = (t > 0 and slots[t - 1] == s
                     and positions[t - 1] == p - 1 and cur is not None)
        if not continues:
            cur = np.zeros_like(pool[s]) if p == 0 else pool[s].copy()
        cur = (np.exp(dt[t][:, None] * a) * cur
               + np.outer(dt[t] * x[t], b[t]))
        y[t] = cur @ c[t]
        pool[s] = cur
    return y, pool


def on_device(x, dt, a, b, c, pool):
    f32 = jnp.float32
    return (jnp.asarray(x, f32), jnp.asarray(dt, f32),
            jnp.asarray(a.T, f32),                  # [N, inner]
            jnp.asarray(b, f32), jnp.asarray(c, f32),
            jnp.asarray(as_pool(pool), f32))


# A `mixed` step of 64 rows: a run of 40 from position 0 (a prompt's first
# chunk), a run of 16 that continues its slot, a run of 3, a decode row
# (a run of 1), pad rows.
SLOTS_64 = np.array([0] * 40 + [1] * 16 + [2] * 3 + [3] + [SLOTS] * 4)
POS_64 = np.concatenate([np.arange(40), 50 + np.arange(16),
                         7 + np.arange(3), [90], np.zeros(4, int)])


def check(y, new, want_y, want_pool, slots):
    live = slots < SLOTS
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(new)[:SLOTS],
                               as_pool(want_pool)[:SLOTS], atol=TOL,
                               rtol=TOL)


@pytest.mark.parametrize("form", ["oracle", "pallas"])
def test_chunk_form_follows_the_recurrence(tpu_interpret, form):
    x, dt, a, b, c, pool = draw(64, seed=1)
    want_y, want_pool = recurrence(x, dt, a, b, c, pool, SLOTS_64, POS_64)
    args = on_device(x, dt, a, b, c, pool)
    sl, ps = jnp.asarray(SLOTS_64), jnp.asarray(POS_64)
    assert ss.sscan_supported(args[0], args[5])
    if form == "pallas":
        cont, last = la.run_structure(sl, ps)
        y, new = ss.sscan_chunk(*args, sl, ps, cont, last)
    else:
        y, new = la.sscan_chunk_reference(*args, sl, ps)
    check(y, new, want_y, want_pool, SLOTS_64)
    # slot 4, which no row names, keeps its bytes
    np.testing.assert_array_equal(np.asarray(new)[4], np.asarray(args[5])[4])
    # One decay a channel (its mean over the state channels: what a scalar
    # decay a head would hold) is another result.
    flat = np.repeat(a.mean(1, keepdims=True), N, axis=1)
    wrong_y, _ = recurrence(x, dt, flat, b, c, pool, SLOTS_64, POS_64)
    live = SLOTS_64 < SLOTS
    assert np.abs(np.asarray(y)[live] - wrong_y[live]).max() > 0.1


@pytest.mark.parametrize("form", ["oracle", "pallas"])
def test_step_form_follows_the_recurrence(tpu_interpret, form):
    """A decode substep of 8 rows: every row its own slot, three pad rows."""
    x, dt, a, b, c, pool = draw(8, seed=2)
    slots = np.array([2, 0, 4, 1, 3, SLOTS, SLOTS, SLOTS])
    want_y, want_pool = recurrence(x, dt, a, b, c, pool, slots,
                                   np.full(8, 40))
    args = on_device(x, dt, a, b, c, pool)
    fn = ss.sscan_step if form == "pallas" else la.sscan_step_reference
    y, new = fn(*args, jnp.asarray(slots))
    check(y, new, want_y, want_pool, slots)


def test_a_chunk_rows_result_is_the_steps(tpu_interpret):
    """A run of one row through the chunk kernel and the same row through
    the step kernel: the same bits, state and output."""
    x, dt, a, b, c, pool = draw(8, seed=3)
    args = on_device(x, dt, a, b, c, pool)
    slots = np.array([2, 0, 4, 1, 3, SLOTS, SLOTS, SLOTS])
    sl, ps = jnp.asarray(slots), jnp.full(8, 40)
    cont, last = la.run_structure(sl, ps)
    y_c, new_c = ss.sscan_chunk(*args, sl, ps, cont, last)
    y_s, new_s = ss.sscan_step(*args, sl)
    live = slots < SLOTS
    np.testing.assert_array_equal(np.asarray(y_c)[live],
                                  np.asarray(y_s)[live])
    np.testing.assert_array_equal(np.asarray(new_c)[:SLOTS],
                                  np.asarray(new_s)[:SLOTS])


def test_what_the_kernels_do_not_take_goes_to_the_oracle():
    f32 = jnp.float32
    pool = jnp.zeros((3, 2, 16, 128), f32)
    assert ss.sscan_supported(jnp.zeros((8, 256), f32), pool)
    assert not ss.sscan_supported(jnp.zeros((8, 128), f32), pool)
    assert not ss.sscan_supported(jnp.zeros((6, 256), f32), pool)
    assert not ss.sscan_supported(jnp.zeros((8, 256), f32),
                                  pool.astype(jnp.bfloat16))
    assert not ss.sscan_supported(jnp.zeros((8, 128), f32),
                                  jnp.zeros((3, 2, 16, 64), f32))
