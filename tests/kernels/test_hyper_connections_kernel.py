"""The stream mixes' Pallas kernels (`ops/pallas/hyper_connections.py`)
through their seam (`ops/hyper_connections.py`) against the float64 oracle
of `tests/layers/test_hyper_connections.py` and against the jnp functions
the seam keeps as its own oracle. On CPU the kernels run under Pallas TPU
interpret mode (tests/kernels/conftest.py)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers import hyper_connections as layer
from intellillm_tpu.ops import dispatch
from intellillm_tpu.ops import hyper_connections as seam
from intellillm_tpu.ops.pallas import hyper_connections as kernels

requires_tpu = pytest.mark.kernel

_spec = importlib.util.spec_from_file_location(
    "layers_test_hyper_connections", os.path.join(
        os.path.dirname(__file__), "..", "layers",
        "test_hyper_connections.py"))
published = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(published)
N, HC = published.N, published.HC
C = 128                 # a lane tile a stream


def params(rng, b_res_scale=1.0):
    p = published.params(rng, b_res_scale)
    p["phi"] = (rng.normal(size=(HC.coefficients, N * C))
                / np.sqrt(N * C)).astype(np.float32)
    return p


@pytest.fixture
def kernels_on():
    dispatch.set_use_pallas(True)
    yield
    dispatch.set_use_pallas(None)


def f32(a):
    return np.asarray(a, np.float32)


def stack_of_one(p):
    """A sublayer's parameters as the seam takes them: a stack of layers
    (here the one, at place 0)."""
    return {k: jnp.asarray(v)[None] for k, v in p.items()}


def through_the_kernels(x, p, w):
    """(u, post, comb, err, streams after F = tanh(u W)) of flat streams
    `x [T, n C]`, each a float32 NumPy array."""
    u, coef = jax.block_until_ready(
        kernels.mhc_pre(x, stack_of_one(p), HC, 0))
    y = jnp.tanh(u.astype(jnp.float32) @ jnp.asarray(w)).astype(x.dtype)
    out = jax.block_until_ready(kernels.mhc_post(x, y, coef, HC))
    post, comb = seam.unpack(coef, HC)
    return (f32(u), f32(post), f32(comb),
            f32(coef[:, kernels.err_column(HC)]), f32(out), y)


# 8: less than a sublane tile of bfloat16; 128, 512: the cell's decode and
# largest `mixed` programs; 176: a `mixed` bucket's worth of rows that is no
# multiple of either kernel's row tile.
@requires_tpu
@pytest.mark.parametrize("dtype", [jnp.bfloat16, np.float32])
@pytest.mark.parametrize("rows", [8, 128, 176, 512])
def test_kernels_match_the_float64_oracle_and_the_jnp_functions(rows, dtype):
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, N * C)).astype(np.float32)
                    ).astype(dtype)
    p = params(rng)
    w = rng.normal(size=(C, C)).astype(np.float32) / np.sqrt(C)
    assert kernels.mhc_supported(x, HC)
    u, post, comb, err, out, y = through_the_kernels(x, p, w)
    xs = f32(x).reshape(rows, N, C)
    # The float64 equations on the values the kernels were given; the
    # post-mix on the sublayer output the kernel was given.
    want_u, want_post, want_comb, _ = published.oracle(
        xs, p, HC, lambda v: np.zeros_like(v))
    want_out = (np.einsum("tij,tjc->tic", want_comb, xs.astype(np.float64))
                + want_post[:, :, None] * f32(y)[:, None].astype(np.float64)
                ).reshape(rows, N * C)
    want_err = np.abs(want_comb.sum(1) - 1.0).max(-1)
    # float32 coefficients: a few roundings through a 512-term product,
    # two transcendental functions and 40 normalisations; `u` leaves the
    # kernel in float32 whatever the streams are; the streams' own rounding
    # where they are bfloat16 (2^-9 relative).
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(post, want_post, **tol)
    np.testing.assert_allclose(comb, want_comb, **tol)
    np.testing.assert_allclose(err, want_err, atol=2e-6)
    np.testing.assert_allclose(u, want_u, **tol)
    stored = tol if dtype == np.float32 else dict(rtol=4e-3, atol=4e-3)
    np.testing.assert_allclose(out, want_out, **stored)
    # ...and the jnp functions, which compute in the same precision.
    jp = {k: jnp.asarray(v) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        ref_u, ref_post, ref_comb = layer.mhc_pre(
            x.reshape(rows, N, C), jp, HC)
    ref_out = layer.mhc_post(x.reshape(rows, N, C), y, ref_post, ref_comb)
    np.testing.assert_allclose(post, f32(ref_post), rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(comb, f32(ref_comb), rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(err, f32(layer.col_error(ref_comb)),
                               atol=2e-6)
    ulp = dict(rtol=2e-6, atol=2e-6) if dtype == np.float32 else dict(
        rtol=2 ** -7, atol=2 ** -7)     # a bfloat16 tie rounded the other way
    np.testing.assert_allclose(u, f32(ref_u), **ulp)
    np.testing.assert_allclose(out, f32(ref_out).reshape(rows, N * C), **ulp)


@requires_tpu
def test_a_stack_of_layers_is_read_at_the_layers_place(kernels_on):
    """The parameters of 3 layers stacked, layer 2 meant, by a number (a
    dense layer's call) or by a traced scalar (a scan's): the same results
    as from a stack of layer 2's alone, bit for bit, on either path of the
    seam."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(16, 1, N * C)), jnp.bfloat16)
    layers = [{k: jnp.asarray(v) for k, v in params(rng).items()}
              for _ in range(3)]
    stack = {k: jnp.stack([p[k] for p in layers]) for k in layers[0]}
    for force in (True, False):
        dispatch.set_use_pallas(force)
        one = jax.block_until_ready(
            seam.mhc_pre(x, stack_of_one(layers[2]), HC, 0))
        for place in (2, jnp.int32(2)):
            got = jax.block_until_ready(seam.mhc_pre(x, stack, HC, place))
            for a, b in zip(one, got):
                assert a.shape == b.shape
                np.testing.assert_array_equal(f32(a), f32(b))


@requires_tpu
@pytest.mark.parametrize("stream", range(N))
def test_identity_and_one_hot_mixes_are_the_plain_residual(stream):
    """`tests/layers/`'s tie to every other model file, through the
    post-mix kernel: with `Hres` = I and `Hpost` one-hot on a stream, that
    stream gains the sublayer's output and the others are untouched; and
    through the pre-mix kernel: with `Phi` = 0 and biases of +-30 `Hpre` is
    one-hot to 1e-13, so `u` is that stream."""
    rng = np.random.default_rng(3)
    rows = 48
    x = jnp.asarray(rng.normal(size=(rows, N * C)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(rows, C)).astype(np.float32))
    one_hot = jnp.zeros((rows, N)).at[:, stream].set(1.0)
    coef = seam.pack(one_hot, jnp.broadcast_to(jnp.eye(N), (rows, N, N)))
    assert float(coef[:, kernels.err_column(HC)].max()) == 0.0
    out = jax.block_until_ready(kernels.mhc_post(x, y, coef, HC))
    want = f32(x).reshape(rows, N, C).copy()
    want[:, stream] += f32(y)
    np.testing.assert_array_equal(f32(out).reshape(rows, N, C), want)
    p = {"phi": jnp.zeros((HC.coefficients, N * C)),
         "alpha": jnp.ones((3, )),
         "b_pre": jnp.full((N, ), -30.0).at[stream].set(30.0),
         "b_post": jnp.zeros((N, )), "b_res": jnp.zeros((N, N))}
    u, coef = jax.block_until_ready(
        kernels.mhc_pre(x, stack_of_one(p), HC, 0))
    np.testing.assert_allclose(
        f32(u), f32(x).reshape(rows, N, C)[:, stream], rtol=1e-6, atol=1e-6)
    # Equal logits: the uniform matrix, doubly stochastic from the start.
    np.testing.assert_allclose(f32(seam.unpack(coef, HC)[1]), 0.25,
                               rtol=1e-5)


def _wrong(which, x, p):
    """The coefficients of `x [T, n, C]` float32 computed wrong one way
    (float64 otherwise): `projection_one_pass`, the projection's operands
    rounded to bfloat16 (what a float32 dot is on a TPU without
    `Precision.HIGHEST`); `rows_then_columns`, the sweeps' two
    normalisations in the other order."""
    t = x.shape[0]
    flat = x.reshape(t, -1).astype(np.float64)
    r = 1.0 / np.sqrt((flat**2).mean(-1, keepdims=True) + HC.rms_eps)
    phi = p["phi"].astype(np.float64)
    if which == "projection_one_pass":
        bf = lambda a: f32(jnp.asarray(a, jnp.float32).astype(  # noqa: E731
            jnp.bfloat16)).astype(np.float64)
        m = bf(flat * r) @ bf(phi).T
    else:
        m = (flat * r) @ phi.T
    a = p["alpha"].astype(np.float64)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))                    # noqa: E731
    post = 2.0 * sig(a[1] * m[:, N:2 * N] + p["b_post"])
    comb = np.exp(np.clip(a[2] * m[:, 2 * N:].reshape(t, N, N) + p["b_res"],
                          HC.clamp_min, HC.clamp_max))
    first, then = (2, 1) if which == "rows_then_columns" else (1, 2)
    for _ in range(HC.sweeps):
        comb = comb / (comb.sum(first, keepdims=True) + HC.eps)
        comb = comb / (comb.sum(then, keepdims=True) + HC.eps)
    return post, comb


@requires_tpu
@pytest.mark.parametrize("which", ["projection_one_pass",
                                   "rows_then_columns"])
def test_the_tolerance_sees_a_wrong_computation(which):
    """What the first test's 1e-5 is worth: float32 streams (so that the
    projection's operands have more than bfloat16's bits), the kernels'
    coefficients inside 1e-5 of the published form and the form computed
    wrong far outside it. Logits four times the init's spread: matrices
    that 20 sweeps leave short of doubly stochastic, where the order of the
    two normalisations shows (at the init's scale most are converged, and
    either order gives the same matrix to 1e-4)."""
    rng = np.random.default_rng(11)
    rows = 128
    xs = rng.normal(size=(rows, N, C)).astype(np.float32)
    p = params(rng, b_res_scale=4.0)
    _, post, comb, _, _, _ = through_the_kernels(
        jnp.asarray(xs.reshape(rows, N * C)), p, np.zeros((C, C), np.float32))
    right_post, right_comb = _wrong(None, xs, p)
    wrong_post, wrong_comb = _wrong(which, xs, p)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(post, right_post, **tol)
    np.testing.assert_allclose(comb, right_comb, **tol)
    off = max(np.abs(post - wrong_post).max(), np.abs(comb - wrong_comb).max())
    assert off > 20 * 1e-5, off


@requires_tpu
def test_seam_takes_the_kernels_and_says_so(kernels_on):
    """Pallas forced: `intellillm_kernel_dispatch_total` gains
    `pallas:mhc_pre` and `pallas:mhc_post` and no `reference:` label, at a
    whole tile of rows and at a part of one (`[B, L, n C]` as the model
    holds the streams); a stream that is no whole lane tile is the
    oracle's, and it says so."""
    from intellillm_tpu.obs import get_compile_tracker
    rng = np.random.default_rng(7)
    p = stack_of_one(params(rng))

    def paths():
        return dict(get_compile_tracker().snapshot()["kernel_dispatch"])

    def delta(before, after, label):
        return after.get(label, 0) - before.get(label, 0)

    before = paths()
    # The model's float32 streams, bfloat16 ones, and a part of a tile.
    cases = (((128, 1), jnp.float32), ((128, 1), jnp.bfloat16),
             ((1, 24), jnp.bfloat16))
    for lead, dtype in cases:
        x = jnp.asarray(rng.normal(size=lead + (N * C, )), dtype)
        u, coef, err = seam.mhc_pre(x, p, HC, 0)
        out = jax.block_until_ready(seam.mhc_post(x, u, coef, HC))
        assert u.shape == lead + (C, ) and err.shape == lead
        assert u.dtype == coef.dtype == err.dtype == jnp.float32
        assert out.shape == x.shape and out.dtype == x.dtype
        dispatch.set_use_pallas(False)
        want_u, want_coef, want_err = seam.mhc_pre(x, p, HC, 0)
        want = seam.mhc_post(x, want_u, want_coef, HC)
        dispatch.set_use_pallas(True)
        assert want.dtype == x.dtype and want_u.dtype == jnp.float32
        np.testing.assert_allclose(f32(err), f32(want_err), atol=2e-6)
        # The oracle too hands `u` on unrounded and rounds the streams
        # once, on the way out: a bfloat16 result differs by a tie
        # rounded the other way at most.
        np.testing.assert_allclose(f32(u), f32(want_u), rtol=2e-6, atol=2e-6)
        ulp = 2e-6 if dtype == jnp.float32 else 2**-7
        np.testing.assert_allclose(f32(out), f32(want), rtol=ulp, atol=ulp)
    mid = paths()
    assert delta(before, mid, "pallas:mhc_pre") == len(cases)
    assert delta(before, mid, "pallas:mhc_post") == len(cases)
    assert delta(before, mid, "reference:mhc_pre") == len(cases)    # the
    assert delta(before, mid, "reference:mhc_post") == len(cases)   # oracle
    narrow = {**p, "phi": p["phi"][..., :N * 48]}
    x = jnp.asarray(rng.normal(size=(16, N * 48)), jnp.bfloat16)
    u, coef, _ = seam.mhc_pre(x, narrow, HC, 0)
    seam.mhc_post(x, u, coef, HC)
    after = paths()
    assert delta(mid, after, "pallas:mhc_pre") == 0
    assert delta(mid, after, "reference:mhc_pre") == 1
    assert delta(mid, after, "reference:mhc_post") == 1
