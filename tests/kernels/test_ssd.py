"""The state-space scan's two forms (`ops/linear_attention.py`: the Pallas
kernels of `ops/pallas/ssd.py` in interpret mode, and the oracles beside
the seam) against the plain recurrence, a token at a time in numpy float64
with the state a head as published, `[H, P, N]`: the kernels' own layout
(`[N, inner]` in lane tiles) is theirs alone.

dt is drawn from 1e-4 to 4 and the heads' rates from 1 to 16: decays from
next to 1 down to e^-64 a token, where seeded weights would give mild ones.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.config import ssd_state_shape
from intellillm_tpu.ops import linear_attention as la
from intellillm_tpu.ops.pallas import ssd

H, P, N, SLOTS = 8, 32, 16, 6
TILES = H * P // 128

# float32 against float64: a state summed over up to 80 tokens of O(1)
# terms, each product rounded once (6e-8 relative); readings are ~2e-6.
TOL = 2e-5


def draw(t, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, H, P))
    dt = np.exp(rng.uniform(np.log(1e-4), np.log(4.0), size=(t, H)))
    a = -rng.uniform(1.0, 16.0, size=H)
    b, c = rng.normal(size=(t, N)), rng.normal(size=(t, N))
    pool = rng.normal(size=(SLOTS + 1, H, P, N))
    return x, dt, a, b, c, pool


def as_pool(by_head):
    """[S, H, P, N] -> the kernels' [S, tiles, N, 128]."""
    s = by_head.shape[0]
    flat = np.moveaxis(by_head.reshape(s, H * P, N), 1, 2)      # [S, N, HP]
    return np.moveaxis(flat.reshape(s, N, TILES, 128), 2, 1)


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
        cur = (np.exp(dt[t] * a)[:, None, None] * cur
               + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        y[t] = cur @ c[t]
        pool[s] = cur
    return y, pool


def as_device(host):
    x, dt, a, b, c, pool = host
    return [jnp.asarray(v, jnp.float32)
            for v in (x, dt, a, b, c, as_pool(pool))]


MIXED = dict(
    # two prompts (one from position 0, one continuing its slot), a decode
    # row, pad rows
    prompts=(np.array([0] * 21 + [2] * 17 + [3] + [SLOTS] * 9),
             np.array(list(range(21)) + list(range(5, 22)) + [9] + [0] * 9)),
    # every row its own sequence: a K=1 decode step through the chunk form
    singles=(np.array([4, 1, 0, 5, 3, 2] + [SLOTS] * 10),
             np.array([7, 0, 30, 2, 1, 11] + [0] * 10)),
    # one run longer than a block of rows, from the slot's own matrix
    long=(np.array([1] * 80), np.arange(3, 83)),
    # a run that starts at position 0 on a slot that holds something
    dirty=(np.array([5] * 8), np.arange(8)),
)


@pytest.mark.parametrize("form", ["pallas", "oracle"])
@pytest.mark.parametrize("case", sorted(MIXED))
def test_chunk_form_against_the_recurrence(case, form):
    slots, pos = MIXED[case]
    host = draw(len(slots), seed=len(case))
    want_y, want_pool = recurrence(*host, slots, pos)
    x, dt, a, b, c, pool = as_device(host)
    sl, ps = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    if form == "pallas":
        assert ssd.ssd_supported(x, b, pool)
        cont, last = la.run_structure(sl, ps)
        y, new = ssd.ssd_chunk(x, dt, a, b, c, pool, sl, ps, cont, last)
    else:
        y, new = la.ssd_chunk_reference(x, dt, a, b, c, pool, sl, ps)
    y, new = jax.block_until_ready((y, new))
    live = slots < SLOTS
    assert np.abs(np.asarray(y)[live] - want_y[live]).max() < TOL
    assert np.abs(np.asarray(new)[:SLOTS]
                  - as_pool(want_pool)[:SLOTS]).max() < TOL


@pytest.mark.parametrize("form", ["pallas", "oracle"])
def test_step_form_against_the_recurrence(form):
    """Every row a run of one token on its own slot; pad rows write the
    pool's pad row and no sequence's."""
    slots = np.array([4, 1, SLOTS, 0, SLOTS, 3, 2, SLOTS])
    pos = np.array([7, 3, 0, 12, 0, 1, 40, 0])
    host = draw(8, seed=3)
    want_y, want_pool = recurrence(*host, slots, pos)
    x, dt, a, b, c, pool = as_device(host)
    sl = jnp.asarray(slots, jnp.int32)
    if form == "pallas":
        assert ssd.ssd_supported(x, b, pool)
    fn = ssd.ssd_step if form == "pallas" else la.ssd_step_reference
    y, new = jax.block_until_ready(fn(x, dt, a, b, c, pool, sl))
    live = slots < SLOTS
    assert np.abs(np.asarray(y)[live] - want_y[live]).max() < TOL
    assert np.abs(np.asarray(new)[:SLOTS]
                  - as_pool(want_pool)[:SLOTS]).max() < TOL


def test_the_two_forms_give_a_row_the_same_bits():
    """A run of one row through the chunk form and through the step form,
    from the same state: the same arithmetic, so the same bits (a probe's
    row in a `mixed` step against the same row in a decode step)."""
    slots = np.array([4, 1, 0, 5, 3, 2, SLOTS, SLOTS])
    pos = np.array([7, 2, 30, 2, 1, 11, 0, 0])
    x, dt, a, b, c, pool = as_device(draw(8, seed=9))
    sl, ps = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    cont, last = la.run_structure(sl, ps)
    y_c, pool_c = ssd.ssd_chunk(x, dt, a, b, c, pool, sl, ps, cont, last)
    y_s, pool_s = ssd.ssd_step(x, dt, a, b, c, pool, sl)
    live = slots < SLOTS
    np.testing.assert_array_equal(np.asarray(y_c)[live], np.asarray(y_s)[live])
    np.testing.assert_array_equal(np.asarray(pool_c)[:SLOTS],
                                  np.asarray(pool_s)[:SLOTS])


def test_seam_records_the_path_it_took():
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.dispatch import set_use_pallas
    x, dt, a, b, c, pool = as_device(draw(16, seed=1))
    sl = jnp.asarray(np.arange(16) % SLOTS, jnp.int32)
    ps = jnp.zeros(16, jnp.int32)

    def seen():
        return dict(get_compile_tracker().snapshot()["kernel_dispatch"])

    try:
        for force, label in ((True, "pallas:"), (False, "reference:")):
            set_use_pallas(force)
            before = seen()
            jax.block_until_ready(la.ssd_chunk(x, dt, a, b, c, pool, sl, ps))
            jax.block_until_ready(la.ssd_step(x[:8], dt[:8], a, b[:8], c[:8],
                                              pool, sl[:8]))
            after = seen()
            for name in ("ssd_chunk", "ssd_step"):
                assert after.get(label + name, 0) > before.get(
                    label + name, 0)
    finally:
        set_use_pallas(None)


def test_what_the_kernels_do_not_take_goes_to_the_oracle():
    """Channels that fill no whole lane tile (a tiny preset of 64), rows
    that fill no sublane tile, a pool in another dtype."""
    f32 = jnp.float32
    x, b = jnp.zeros((16, 4, 16)), jnp.zeros((16, 16))
    assert ssd_state_shape(64, 16) == (1, 16, 64)
    assert not ssd.ssd_supported(x, b, jnp.zeros((3, 1, 16, 64), f32))
    x = jnp.zeros((20, 4, 32))
    assert ssd_state_shape(128, 16) == (1, 16, 128)
    assert not ssd.ssd_supported(x, jnp.zeros((20, 16)),
                                 jnp.zeros((3, 1, 16, 128), f32))
    x = jnp.zeros((16, 4, 32))
    assert not ssd.ssd_supported(x, b, jnp.zeros((3, 1, 16, 128),
                                                 jnp.bfloat16))
    assert ssd.ssd_supported(x, b, jnp.zeros((3, 1, 16, 128), f32))
    assert ssd_state_shape(8192, 128) == (64, 128, 128)


def test_filter_with_a_bias_against_its_oracle():
    """The 4-tap filter then SiLU with a bias a channel inside the SiLU
    (a state-space layer's x, B and C), at a channel count that is no
    multiple of 512 (8,448 = 33 x 256); without a bias the call is the one
    a delta-rule layer makes, to the bit."""
    from intellillm_tpu.ops.pallas.short_conv import (conv_silu,
                                                      conv_silu_supported)
    from intellillm_tpu.ops.short_conv import conv_silu_body_reference
    rng = np.random.default_rng(2)
    # bf16: one rounding of an O(1) output (2**-8 relative).
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)):
        x = jnp.asarray(rng.normal(size=(32, 768)), dtype)
        prev = jnp.asarray(rng.normal(size=(3, 32, 768)), dtype)
        w = jnp.asarray(rng.normal(size=(4, 768)) / 2, dtype)
        bias = jnp.asarray(rng.normal(size=768) / 2, dtype)
        assert conv_silu_supported(x, prev)
        got = jax.block_until_ready(conv_silu(x, prev, w, bias))
        want = conv_silu_body_reference(x, prev, w, bias)
        assert got.dtype == dtype
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) < tol
        plain = conv_silu_body_reference(x, prev, w)
        assert float(jnp.abs(plain.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) > 0.1
        np.testing.assert_array_equal(
            np.asarray(conv_silu(x, prev, w).astype(jnp.float32)),
            np.asarray(conv_silu(x, prev, w, None).astype(jnp.float32)))
    assert str(jax.make_jaxpr(lambda *a: conv_silu(*a))(x, prev, w)) == str(
        jax.make_jaxpr(lambda *a: conv_silu(*a, None))(x, prev, w))
