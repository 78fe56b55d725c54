"""`layers/hyper_connections.py` against a NumPy float64 oracle of the
published equations, the Sinkhorn sweeps' convergence, and the tie to the
plain residual every other model file carries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers.hyper_connections import (HyperConnections,
                                                     col_error, mhc_post,
                                                     mhc_pre)

N, C = 4, 48
HC = HyperConnections(n=N, sweeps=20, eps=1e-6, clamp_min=-30.0,
                      clamp_max=30.0, rms_eps=1e-6)


def params(rng, b_res_scale=1.0):
    """The seeded init's scale (models/xing4.py `init_random_params`):
    `phi` fan-in scaled over a unit-RMS statistic, gates in [0.5, 1.5],
    biases of scale 1."""
    return {"phi": (rng.normal(size=(2 * N + N * N, N * C))
                    / np.sqrt(N * C)).astype(np.float32),
            "alpha": rng.uniform(0.5, 1.5, 3).astype(np.float32),
            "b_pre": rng.normal(size=N).astype(np.float32),
            "b_post": rng.normal(size=N).astype(np.float32),
            "b_res": (b_res_scale * rng.normal(size=(N, N))).astype(
                np.float32)}


def oracle(x, p, hc, f):
    """float64 NumPy, the equations as published: x [T, n, C] -> (u,
    post, comb, x')."""
    x = x.astype(np.float64)
    t = x.shape[0]
    flat = x.reshape(t, -1)
    r = 1.0 / np.sqrt((flat**2).mean(-1, keepdims=True) + hc.rms_eps)
    m = (flat * r) @ p["phi"].astype(np.float64).T
    a = p["alpha"].astype(np.float64)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))                    # noqa: E731
    pre = sig(a[0] * m[:, :N] + p["b_pre"])
    post = 2.0 * sig(a[1] * m[:, N:2 * N] + p["b_post"])
    z = np.clip(a[2] * m[:, 2 * N:].reshape(t, N, N) + p["b_res"],
                hc.clamp_min, hc.clamp_max)
    comb = np.exp(z)
    for _ in range(hc.sweeps):
        comb = comb / (comb.sum(1, keepdims=True) + hc.eps)
        comb = comb / (comb.sum(2, keepdims=True) + hc.eps)
    u = np.einsum("ti,tic->tc", pre, x)
    y = f(u)
    out = np.einsum("tij,tjc->tic", comb, x) + post[:, :, None] * y[:, None]
    return u, post, comb, out


@pytest.mark.parametrize("lead", [(7, ), (3, 1)])
def test_mix_matches_the_float64_oracle(lead):
    """float32 against float64 on O(1) values: 1e-5 relative is a few
    float32 roundings through a 192-term dot, two transcendental functions
    and 40 normalisations; anything left out of the equations is 1e-2 and
    more."""
    rng = np.random.default_rng(0)
    t = int(np.prod(lead))
    x = rng.normal(size=(t, N, C)).astype(np.float32)
    p = params(rng)
    w = rng.normal(size=(C, C)).astype(np.float32) / np.sqrt(C)
    want_u, want_post, want_comb, want_out = oracle(
        x, p, HC, lambda u: np.tanh(u @ w.astype(np.float64)))
    jp = {k: jnp.asarray(v) for k, v in p.items()}
    xs = jnp.asarray(x).reshape(lead + (N, C))
    with jax.default_matmul_precision("highest"):
        u, post, comb = mhc_pre(xs, jp, HC)
        out = mhc_post(xs, jnp.tanh(u @ jnp.asarray(w)), post, comb)
    assert u.shape == lead + (C, ) and post.shape == lead + (N, )
    assert comb.shape == lead + (N, N) and out.shape == xs.shape
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(u).reshape(t, C), want_u, **tol)
    np.testing.assert_allclose(np.asarray(post).reshape(t, N), want_post,
                               **tol)
    np.testing.assert_allclose(np.asarray(comb).reshape(t, N, N), want_comb,
                               **tol)
    np.testing.assert_allclose(np.asarray(out).reshape(t, N, C), want_out,
                               **tol)


def test_streams_keep_their_dtype_and_coefficients_are_float32():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, N, C)), jnp.bfloat16)
    p = {k: jnp.asarray(v) for k, v in params(rng).items()}
    u, post, comb = mhc_pre(x, p, HC)
    assert u.dtype == jnp.bfloat16
    assert post.dtype == comb.dtype == jnp.float32
    assert mhc_post(x, u, post, comb).dtype == jnp.bfloat16


def test_twenty_sweeps_leave_a_doubly_stochastic_matrix(record_property):
    """Logits of the seeded init's scale (biases N(0, 1), gates in [0.5,
    1.5] on an N(0, 1) projection), 4,096 tokens: rows sum to 1 within
    2e-6 (the last normalisation, with `hc_eps` 1e-6 in the denominator of
    sums near 1); columns within 1e-5 for the median token (1.0e-6
    measured: `hc_eps` again) and within 1e-3 for 99 of 100 (1.3e-4).
    ISSUE 45 expected every column within 1e-4; the LARGEST over the
    tokens reads 3.7e-2, because Sinkhorn-Knopp converges linearly at a
    rate the matrix sets, and a token whose projection is a 3-sigma draw
    under a gate of 1.5 has logits spread over e^+-5, where 20 sweeps (the
    published count) leave a few percent. That is what
    `intellillm_mhc_col_error_max` reads on the chip, an extreme value
    over 1e9 mixes; it is recorded here and not limited, as is what 20
    sweeps leave at logits spread to the clamp."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4096, N, C)).astype(np.float32))
    p = {k: jnp.asarray(v) for k, v in params(rng).items()}
    _, _, comb = mhc_pre(x, p, HC)
    rows = np.abs(np.asarray(comb).sum(-1) - 1.0).max()
    cols = np.asarray(col_error(comb))
    assert rows < 2e-6, rows
    assert np.median(cols) < 1e-5 and np.quantile(cols, 0.99) < 1e-3, (
        np.median(cols), np.quantile(cols, 0.99))
    np.testing.assert_allclose(
        cols, np.abs(np.asarray(comb).sum(-2) - 1.0).max(-1), atol=1e-7)
    wide = {k: jnp.asarray(v) for k, v in params(
        np.random.default_rng(2), b_res_scale=15.0).items()}
    _, _, comb = mhc_pre(x, wide, HC)
    assert np.isfinite(np.asarray(comb)).all()
    record_property("col_error_max_at_the_clamp",
                    float(np.asarray(col_error(comb)).max()))
    record_property("col_error_max_at_the_init_scale", float(cols.max()))


@pytest.mark.parametrize("stream", range(N))
def test_identity_and_one_hot_mixes_are_the_plain_residual(stream):
    """The tie to every other model file: with `Hres` = I and `Hpre`,
    `Hpost` one-hot on one stream, that stream is `x + F(x)` and the
    others are untouched. The coefficients are handed to `mhc_post`
    directly (a sigmoid never is exactly one-hot), and `Hpre` is applied as
    `mhc_pre` applies it."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, N, C)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(C, C)).astype(np.float32) / np.sqrt(C))
    f = lambda v: jnp.tanh(v @ w)                               # noqa: E731
    one_hot = jnp.zeros((6, N)).at[:, stream].set(1.0)
    u = sum(one_hot[:, i, None] * x[:, i] for i in range(N))
    out = mhc_post(x, f(u), one_hot,
                   jnp.broadcast_to(jnp.eye(N), (6, N, N)))
    want = x.at[:, stream].add(f(x[:, stream]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    assert float(col_error(jnp.broadcast_to(jnp.eye(N), (6, N, N))).max()
                 ) == 0.0


def test_a_sublayers_bytes_and_shapes():
    """4 streams of 3,584 bf16 values: 28,672 B a row, read by the
    pre-mix, read and written by the post-mix: 86,016 B a row and
    sublayer; 24 coefficients a token."""
    hc = HyperConnections(4, 20, 1e-6, -30.0, 30.0, 1e-6)
    assert hc.mix_bytes(3584, 2) == 86016
    assert hc.coefficients == 24
    assert hc.param_shapes(3584)["phi"] == (24, 14336)
