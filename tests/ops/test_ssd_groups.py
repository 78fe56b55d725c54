"""Two groups of B and C in the state-space scan (`ops/linear_attention.py`
`ssd_update` / `ssd_step_reference` / `ssd_chunk_reference`, and the Pallas
kernels of `ops/pallas/ssd.py` in interpret mode) at the widths Falcon-H1
runs: 256 state channels on the sublanes, heads of 128 channels (a head = one
lane tile), head h reading group `h // (H / G)`.

What they are held to is the plain recurrence in numpy float64 with the state
a head as published, `[H, P, N]`, and B and C picked by `h // (H / G)`
written out here, so that the oracles' `repeat` and the kernels' static map
are both checked against the rule and not against each other. With one group
the oracles are bit for bit what they were before they took groups.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.config import ssd_state_shape
from intellillm_tpu.ops import linear_attention as la
from intellillm_tpu.ops.pallas import ssd

H, P, N, G, SLOTS = 4, 128, 256, 2, 3
TILES = H * P // 128
# float32 against float64 over runs of up to 8 tokens of O(1) terms summed
# over 256 state channels.
TOL = 5e-5


def draw(t, seed, groups=G):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, H, P))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=(t, H)))
    a = -rng.uniform(1.0, 16.0, size=H)
    b, c = rng.normal(size=(2, t, groups, N)) / np.sqrt(N) * 4
    pool = rng.normal(size=(SLOTS + 1, H, P, N))
    return x, dt, a, b, c, pool


def as_pool(by_head):
    """[S, H, P, N] -> the kernels' [S, tiles, N, 128]."""
    s = by_head.shape[0]
    flat = np.moveaxis(by_head.reshape(s, H * P, N), 1, 2)      # [S, N, HP]
    return np.moveaxis(flat.reshape(s, N, TILES, 128), 2, 1)


def recurrence(x, dt, a, b, c, pool, slots, positions):
    """float64, a row and a head at a time; pad rows (slot == SLOTS) give
    zeros and touch nothing."""
    pool = pool.copy()
    y = np.zeros_like(x)
    cur = None
    groups = b.shape[1]
    for t in range(len(slots)):
        s, p = int(slots[t]), int(positions[t])
        if s == SLOTS:
            cur = None
            continue
        continues = (t > 0 and slots[t - 1] == s
                     and positions[t - 1] == p - 1 and cur is not None)
        if not continues:
            cur = np.zeros_like(pool[s]) if p == 0 else pool[s].copy()
        for h in range(H):
            g = h // (H // groups)
            cur[h] = (np.exp(dt[t, h] * a[h]) * cur[h]
                      + np.outer(dt[t, h] * x[t, h], b[t, g]))
            y[t, h] = cur[h] @ c[t, g]
        pool[s] = cur
    return y, pool


def on_device(x, dt, a, b, c, pool):
    f32 = jnp.float32
    return (jnp.asarray(x, f32), jnp.asarray(dt, f32), jnp.asarray(a, f32),
            jnp.asarray(b, f32), jnp.asarray(c, f32),
            jnp.asarray(as_pool(pool), f32))


# a `mixed` step of 16 rows: a run from position 0, a run that continues its
# slot, decode rows (runs of one), pad rows
SLOTS_16 = np.array([0] * 5 + [1] * 4 + [2, 0, 1] + [SLOTS] * 4)
POS_16 = np.array([0, 1, 2, 3, 4, 7, 8, 9, 10, 30, 5, 11, 0, 0, 0, 0])


def test_state_shape_at_the_published_widths():
    assert ssd_state_shape(4096, 256, 2) == (32, 256, 128)
    assert ssd_state_shape(H * P, N, G) == (TILES, N, 128)
    # a tiny model: a group's 64 channels are its one tile
    assert ssd_state_shape(128, 16, 2) == (2, 16, 64)
    assert ssd.tile_groups(32, 2) == (0, ) * 16 + (1, ) * 16
    assert ssd.tile_groups(64, 1) == (0, ) * 64


@pytest.mark.parametrize("form", ["oracle", "pallas"])
def test_chunk_form_reads_each_heads_group(tpu_interpret, form):
    x, dt, a, b, c, pool = draw(16, seed=1)
    want_y, want_pool = recurrence(x, dt, a, b, c, pool, SLOTS_16, POS_16)
    args = on_device(x, dt, a, b, c, pool)
    sl, ps = jnp.asarray(SLOTS_16), jnp.asarray(POS_16)
    assert ssd.ssd_supported(args[0], args[3], args[5])
    if form == "pallas":
        cont, last = la.run_structure(sl, ps)
        y, new = ssd.ssd_chunk(*args, sl, ps, cont, last)
    else:
        y, new = la.ssd_chunk_reference(*args, sl, ps)
    live = SLOTS_16 < SLOTS
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(new)[:SLOTS],
                               as_pool(want_pool)[:SLOTS], atol=TOL,
                               rtol=TOL)
    # B and C of group 0 for every head is another result
    wrong_y, _ = recurrence(x, dt, a, b[:, :1], c[:, :1], pool, SLOTS_16,
                            POS_16)
    assert np.abs(np.asarray(y)[live] - wrong_y[live]).max() > 0.1


@pytest.mark.parametrize("form", ["oracle", "pallas"])
def test_step_form_reads_each_heads_group(tpu_interpret, form):
    """A decode substep of 8 rows: every row its own slot, two pad rows."""
    x, dt, a, b, c, pool = draw(8, seed=2)
    slots = np.array([2, 0, 1, SLOTS, SLOTS, SLOTS, SLOTS, SLOTS])
    want_y, want_pool = recurrence(x, dt, a, b, c, pool, slots,
                                   np.full(8, 40))
    args = on_device(x, dt, a, b, c, pool)
    fn = ssd.ssd_step if form == "pallas" else la.ssd_step_reference
    y, new = fn(*args, jnp.asarray(slots))
    live = slots < SLOTS
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(new)[:SLOTS],
                               as_pool(want_pool)[:SLOTS], atol=TOL,
                               rtol=TOL)


def test_one_group_is_bit_for_bit_the_ungrouped_oracle():
    """`ssd_update` with b, c `[T, 1, N]`, with `[T, N]`, and the function
    as it stood before it took groups (copied here): the same bits."""
    f32 = jnp.float32

    def before(state, x, dt, a, b, c):
        p = x.shape[-1]
        lead, (tiles, _, lanes) = state.shape[:-3], state.shape[-3:]
        dt = dt.astype(f32)
        keep = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=-1)
        dtx = dt[..., None] * x.astype(f32)
        by_tile = lead + (tiles, 1, lanes)
        new = (keep.reshape(by_tile) * state
               + b.astype(f32)[..., None, :, None] * dtx.reshape(by_tile))
        y = (c.astype(f32)[..., None, :, None] * new).sum(-2)
        return y.reshape(x.shape), new

    x, dt, a, b, c, pool = draw(8, seed=3, groups=1)
    x, dt, a, b, c, pool = on_device(x, dt, a, b, c, pool)
    state = pool[jnp.asarray([0, 1, 2, 0, 1, 2, 0, 1])]
    want = jax.jit(before)(state, x, dt, a, b[:, 0], c[:, 0])
    for bb, cc in ((b, c), (b[:, 0], c[:, 0])):
        got = jax.jit(la.ssd_update)(state, x, dt, a, bb, cc)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
