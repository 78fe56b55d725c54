"""The delta rule's two forms (`ops/linear_attention.py`: the Pallas kernels
of `ops/pallas/kda.py` in interpret mode, and the oracles beside the seam)
against the plain recurrence, a token at a time in numpy float64.

The decays are drawn down to 0.02 and up to 0.9999 a channel and the write
strength near 0 and near 2: seeded weights give mild gates and would never
show a chunk form that divides by an underflowed cumulative decay, nor a
solve that cancels where keys repeat.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops import linear_attention as la
from intellillm_tpu.ops.pallas import kda

H, D, SLOTS = 2, 128, 6


def draw(t, seed, a_lo=0.02, a_hi=0.9999, repeat_keys=False):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(t, H, D))
    q *= D ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    k = rng.normal(size=(t, H, D))
    if repeat_keys:                 # the same key row after row
        k[:] = k[:1]
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(t, H, D))
    a = rng.uniform(a_lo, a_hi, size=(t, H, D))
    a[rng.random((t, H, D)) < 0.25] = a_lo
    a[rng.random((t, H, D)) < 0.25] = a_hi
    beta = rng.choice([1e-3, 1.0, 1.999], size=(t, H))
    pool = rng.normal(size=(SLOTS + 1, H, D, D))
    return q, k, v, np.log(a), beta, pool


def recurrence(q, k, v, g, beta, pool, slots, positions):
    """float64, a row at a time; pad rows (slot == SLOTS) give zeros and
    touch nothing."""
    pool = pool.copy()
    o = np.zeros_like(v)
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
        cur = np.exp(g[t])[:, :, None] * cur
        u = v[t] - np.einsum("hk,hkv->hv", k[t], cur)
        cur = cur + beta[t][:, None, None] * k[t][:, :, None] * u[:, None, :]
        o[t] = np.einsum("hk,hkv->hv", q[t], cur)
        pool[s] = cur
    return o, pool


def as_device(xs):
    return [jnp.asarray(x, jnp.float32) for x in xs]


MIXED = dict(
    # two prompts (one from position 0, one continuing its slot at a
    # length that is no multiple of the chunk), a decode row, pad rows
    prompts=(np.array([0] * 21 + [2] * 17 + [3] + [SLOTS] * 9),
             np.array(list(range(21)) + list(range(5, 22)) + [9] + [0] * 9)),
    # every row its own sequence: a K=1 decode step through the chunk form
    singles=(np.array([4, 1, 0, 5, 3, 2] + [SLOTS] * 10),
             np.array([7, 0, 30, 2, 1, 11] + [0] * 10)),
    # one run across every chunk boundary, from the slot's own matrix
    long=(np.array([1] * 48), np.arange(3, 51)),
)


@pytest.mark.parametrize("form", ["pallas", "oracle"])
@pytest.mark.parametrize("case", sorted(MIXED))
def test_chunk_form_against_the_recurrence(case, form):
    slots, pos = MIXED[case]
    host = draw(len(slots), seed=len(case))
    want_o, want_pool = recurrence(*host, slots, pos)
    q, k, v, g, beta, pool = as_device(host)
    sl, ps = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    if form == "pallas":
        assert kda.kda_supported(q, pool, kda.CHUNK)
        cont, _ = la.run_structure(sl, ps)
        o, new = kda.kda_chunk(q, k, v, g, beta, pool, sl, ps, cont)
    else:
        o, new = la.kda_chunk_reference(q, k, v, g, beta, pool, sl, ps)
    o, new = jax.block_until_ready((o, new))
    live = slots < SLOTS
    assert np.abs(np.asarray(o)[live] - want_o[live]).max() < 2e-5
    assert np.abs(np.asarray(new)[:SLOTS] - want_pool[:SLOTS]).max() < 2e-5


def test_chunk_form_where_keys_repeat_at_full_write_strength():
    """The same key row after row with b near 2 and next to no decay: the
    triangular system's inverse has entries that alternate in sign; a
    product form of it would cancel, the substitution does not."""
    slots, pos = np.array([2] * 32), np.arange(32)
    host = list(draw(32, seed=5, a_lo=0.999, a_hi=0.9999, repeat_keys=True))
    host[4] = np.full_like(host[4], 1.999)
    want_o, want_pool = recurrence(*host, slots, pos)
    q, k, v, g, beta, pool = as_device(host)
    sl, ps = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    cont, _ = la.run_structure(sl, ps)
    o, new = jax.block_until_ready(
        kda.kda_chunk(q, k, v, g, beta, pool, sl, ps, cont))
    assert np.abs(np.asarray(o) - want_o).max() < 1e-4
    assert np.abs(np.asarray(new)[2] - want_pool[2]).max() < 1e-4


def test_chunk_form_floors_a_decay_that_float32_cannot_carry():
    """A channel that keeps under e^-11 of itself a token is floored
    there (`LOG_DECAY_FLOOR`): what is left of the state is under 2e-5 of
    it either way, and nothing overflows."""
    slots, pos = np.array([0] * 16), np.arange(16)
    host = list(draw(16, seed=8))
    host[3] = np.where(np.random.default_rng(1).random(host[3].shape) < 0.5,
                       -60.0, host[3])
    want_o, _ = recurrence(*host, slots, pos)
    q, k, v, g, beta, pool = as_device(host)
    sl, ps = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    cont, _ = la.run_structure(sl, ps)
    o, new = jax.block_until_ready(
        kda.kda_chunk(q, k, v, g, beta, pool, sl, ps, cont))
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(new)).all()
    assert np.abs(np.asarray(o) - want_o).max() < 1e-3


@pytest.mark.parametrize("form", ["pallas", "oracle"])
def test_step_form_against_the_recurrence(form):
    """Every row a run of one token on its own slot; pad rows write the
    pool's pad row and no sequence's."""
    slots = np.array([4, 1, SLOTS, 0, SLOTS])
    pos = np.array([7, 3, 0, 12, 0])
    host = draw(5, seed=3)
    want_o, want_pool = recurrence(*host, slots, pos)
    q, k, v, g, beta, pool = as_device(host)
    sl = jnp.asarray(slots, jnp.int32)
    fn = kda.kda_step if form == "pallas" else la.kda_step_reference
    o, new = jax.block_until_ready(fn(q, k, v, g, beta, pool, sl))
    live = slots < SLOTS
    assert np.abs(np.asarray(o)[live] - want_o[live]).max() < 2e-5
    assert np.abs(np.asarray(new)[:SLOTS] - want_pool[:SLOTS]).max() < 2e-5


def test_seam_records_the_path_it_took():
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.dispatch import set_use_pallas
    q, k, v, g, beta, pool = as_device(draw(16, seed=1))
    sl = jnp.asarray(np.arange(16) % SLOTS, jnp.int32)
    ps = jnp.zeros(16, jnp.int32)

    def seen():
        return dict(get_compile_tracker().snapshot()["kernel_dispatch"])

    try:
        for force, label in ((True, "pallas:"), (False, "reference:")):
            set_use_pallas(force)
            before = seen()
            jax.block_until_ready(la.kda_chunk(q, k, v, g, beta, pool, sl, ps))
            jax.block_until_ready(la.kda_step(q[:4], k[:4], v[:4], g[:4],
                                              beta[:4], pool, sl[:4]))
            after = seen()
            for name in ("kda_chunk", "kda_step"):
                assert after.get(label + name, 0) > before.get(
                    label + name, 0)
    finally:
        set_use_pallas(None)


def test_narrow_heads_take_the_oracle():
    """A head that is no whole lane tile (the tiny presets) is served by
    the oracle, and the seam says so."""
    pool = jnp.zeros((3, 2, 32, 32), jnp.float32)
    assert not kda.kda_supported(jnp.zeros((16, 2, 32)), pool, kda.CHUNK)
    assert not kda.kda_supported(jnp.zeros((20, 2, 128)),
                                 jnp.zeros((3, 2, 128, 128)), kda.CHUNK)


def test_filter_then_silu_body_against_its_oracle():
    from intellillm_tpu.ops.pallas.short_conv import (conv_silu,
                                                      conv_silu_supported)
    from intellillm_tpu.ops.short_conv import conv_silu_body_reference
    rng = np.random.default_rng(2)
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)):
        x = jnp.asarray(rng.normal(size=(32, 256)), dtype)
        prev = jnp.asarray(rng.normal(size=(3, 32, 256)), dtype)
        w = jnp.asarray(rng.normal(size=(4, 256)) / 2, dtype)
        assert conv_silu_supported(x, prev)
        got = jax.block_until_ready(conv_silu(x, prev, w))
        want = conv_silu_body_reference(x, prev, w)
        assert got.dtype == dtype
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) < tol
