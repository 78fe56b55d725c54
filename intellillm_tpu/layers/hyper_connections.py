"""Manifold-constrained hyper-connections: a residual path of `n` streams.

mHC (arXiv:2512.24880, section 4; Hyper-Connections, arXiv:2409.19606)
replaces the one-vector pre-norm residual stream with `n` streams
`X [..., n, C]`. A sublayer `F` reads a per-token mix of the streams and
writes to all of them:

    x     = reshape(X, [nC]) in float32 ; r = rsqrt(mean(x^2) + rms_eps)
    m     = Phi (x * r)                          Phi [2n + n^2, nC] float32
    Hpre  = sigmoid(a_pre  * m[0:n]   + b_pre)                    [n]
    Hpost = 2 * sigmoid(a_post * m[n:2n] + b_post)                [n]
    Hres  = SK(clamp(a_res * reshape(m[2n:], [n, n]) + b_res))    [n, n]
    SK(Z): M = exp(Z); `sweeps` times: M = M / (its column sums + eps),
           then M = M / (its row sums + eps)
    u     = sum_i Hpre[i] X[i]
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(u)

`mhc_pre` gives `(u, post, comb)` = `(u, Hpost, Hres)` and `mhc_post` the
new streams; the sublayer (its norm included) runs between the two. With
`Hres` = I and `Hpre`, `Hpost` one-hot on the same stream, the block is
the plain residual `x + F(x)` of every other model file.

The statistic has no learned gain (it would fold into `Phi`). The
coefficients are computed and applied in float32, the projection at
`Precision.HIGHEST` (a float32 product; on a TPU a float32 dot is otherwise
ONE bfloat16 pass, which the float32 reference check sees: its
`projection_one_pass` control); the streams are stored in the model's
dtype. The last sweep
normalises rows, so rows sum to 1 to rounding and columns nearly:
`col_error` says how nearly.

How it is laid out for the chip. A sublayer's parameters `p`:
`phi [2n + n^2, nC]` (a Linear's `[out, in]`: `[nC, 24]` would pad its 24
columns to 128 lanes, 5.3 times the bytes), `alpha [3]` (`a_pre`,
`a_post`, `a_res`), `b_pre [n]`, `b_post [n]`, `b_res [n, n]`, all
float32. The coefficients are computed with the TOKENS along the lanes
(`m [2n + n^2, T]`: a coefficient is one `[T]` row, not 1 of 16 lanes of
a `[T, n, n]` tile), every entry of the matrix its own array and the
`n x n` sums of a sweep written out as adds, not as reductions or slices:
a sweep is then elementwise and nothing else. The sweeps are the turns of
a `fori_loop`, not unrolled. Compiled for a described v5e (docs/kernels.md
has the table): unrolled like this the 20 sweeps are 30 operations a
matrix, the fewest of the forms tried (80 with `sum(axis)` or with slices
of an `[n, n, T]` array), but XLA's CPU backend then takes 16 s a matrix
and program to compile, which no test or rehearsal can pay 80 times; the
loop is a `while` of 20 turns of 3 operations each and compiles in a
tenth of a second anywhere. The two mixes are 4- and 16-term sums over
the streams (the pre-mix written out, the post-mix a broadcast product
summed over the stream axis): they move `X`'s bytes (read once by the pre-mix,
read and written by the post-mix) and multiply nothing on the MXU.

This file is the ORACLE of the seam `ops/hyper_connections.py`, which is
what `models/xing4.py` calls: on a TPU each mix is one Pallas kernel
(`ops/pallas/hyper_connections.py`, since PR 47: the streams flat `[T,
n C]`, the sweeps a loop inside the pre-mix's launch on values that stay
in VMEM, `sinkhorn` below its body as it is XLA's), and everywhere else,
in every CPU test and rehearsal, these functions. What is left open is one
launch for the post-mix of a sublayer and the pre-mix of the next (PERF.md
section 7).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class HyperConnections:
    """The path's constants, as a config names them: `hc_mult` streams,
    `hc_sinkhorn_iters` sweeps, `hc_eps` in every Sinkhorn denominator,
    the clamp on `Hres`' logits, and the statistic's epsilon."""
    n: int
    sweeps: int
    eps: float
    clamp_min: float
    clamp_max: float
    rms_eps: float

    @classmethod
    def from_config(cls, cfg) -> "HyperConnections":
        return cls(n=int(cfg.hc_mult), sweeps=int(cfg.hc_sinkhorn_iters),
                   eps=float(cfg.hc_eps),
                   clamp_min=float(cfg.mhc_h_res_clamp_min),
                   clamp_max=float(cfg.mhc_h_res_clamp_max),
                   rms_eps=float(cfg.rms_norm_eps))

    @property
    def coefficients(self) -> int:
        return 2 * self.n + self.n * self.n

    def param_shapes(self, hidden: int) -> dict:
        """One sublayer's entries of a model's `_param_shapes`."""
        n = self.n
        return {"phi": (self.coefficients, n * hidden), "alpha": (3, ),
                "b_pre": (n, ), "b_post": (n, ), "b_res": (n, n)}

    def mix_bytes(self, hidden: int, itemsize: int) -> int:
        """Bytes of streams one row moves through one sublayer: the
        pre-mix reads `X`, the post-mix reads it and writes it."""
        return 3 * self.n * hidden * itemsize


def sinkhorn(z, sweeps: int, eps: float):
    """`z[i][j]`: the `n x n` logits, each an array over the tokens ->
    the entries after `sweeps` Sinkhorn-Knopp sweeps (columns, then
    rows), as such a nest. Every entry is its own array and every sum is
    written out, so that a sweep is elementwise and nothing else; the
    sweeps are the turns of a `fori_loop`."""
    n = len(z)

    def sweep(_, m):
        col = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        m = [[m[i][j] / col[j] for j in range(n)] for i in range(n)]
        row = [sum(m[i][j] for j in range(n)) + eps for i in range(n)]
        return [[m[i][j] / row[i] for j in range(n)] for i in range(n)]

    return jax.lax.fori_loop(
        0, sweeps, sweep,
        [[jnp.exp(z[i][j]) for j in range(n)] for i in range(n)])


def mhc_pre(x: jnp.ndarray, p: dict, hc: HyperConnections
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`x [..., n, C]` streams -> (`u [..., C]` the sublayer's input in
    `x`'s dtype, `post [..., n]`, `comb [..., n, n]` float32)."""
    n, c = x.shape[-2:]
    lead = x.shape[:-2]
    f32 = jnp.float32
    with jax.named_scope("mhc_pre"):
        xf = x.astype(f32).reshape(-1, n * c)                     # [T, nC]
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                          + hc.rms_eps)
        m = jax.lax.dot_general(
            p["phi"].astype(f32), xf * r, (((1, ), (1, )), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)                           # [24, T]
        a, b_pre, b_post, b_res = (p[k].astype(f32) for k in (
            "alpha", "b_pre", "b_post", "b_res"))
        pre = [jax.nn.sigmoid(a[0] * m[i] + b_pre[i]) for i in range(n)]
        post = [2.0 * jax.nn.sigmoid(a[1] * m[n + i] + b_post[i])
                for i in range(n)]
        comb = sinkhorn(
            [[jnp.clip(a[2] * m[2 * n + i * n + j] + b_res[i, j],
                       hc.clamp_min, hc.clamp_max) for j in range(n)]
             for i in range(n)], hc.sweeps, hc.eps)
        xs = xf.reshape(-1, n, c)
        u = sum(pre[i][:, None] * xs[:, i] for i in range(n))
        return (u.astype(x.dtype).reshape(lead + (c, )),
                jnp.stack(post, axis=-1).reshape(lead + (n, )),
                jnp.stack([jnp.stack(row, axis=-1) for row in comb],
                          axis=-2).reshape(lead + (n, n)))


def mhc_post(x: jnp.ndarray, y: jnp.ndarray, post: jnp.ndarray,
             comb: jnp.ndarray) -> jnp.ndarray:
    """The streams after a sublayer whose output is `y [..., C]`:
    `X'[i] = sum_j comb[i, j] X[j] + post[i] y`, in `x`'s dtype."""
    f32 = jnp.float32
    with jax.named_scope("mhc_post"):
        xf, yf = x.astype(f32), y.astype(f32)
        mixed = (comb[..., :, :, None] * xf[..., None, :, :]).sum(axis=-2)
        return (mixed + post[..., None] * yf[..., None, :]).astype(x.dtype)


def col_error(comb: jnp.ndarray) -> jnp.ndarray:
    """`comb [..., n, n]` -> the largest |column sum - 1| of each matrix
    `[...]`: how far the sweeps, the clamp and the epsilon left it from
    doubly stochastic (rows are the last normalised, so they sum to 1 to
    rounding)."""
    n = comb.shape[-1]
    col = sum(comb[..., i, :] for i in range(n))
    return jnp.max(jnp.abs(col - 1.0), axis=-1)
