"""The two stream mixes of a manifold-constrained hyper-connection sublayer
(`layers/hyper_connections.py` has the equations), one launch each.

The streams are FLAT here: `X [T, n C]`, stream `i` the lanes `[i C,
(i + 1) C)` of a row (the bytes of `[T, n, C]`, but tiled over whole rows:
a `[.., n, C]` array tiles its `n` = 4 sublanes, a quarter of a vreg, and
XLA re-lays it between any two operations that disagree). Between the two
kernels of a sublayer the coefficients travel as ONE float32 array `coef
[T, 128]`, a token a row: columns `[0, n)` `Hpost`, `[n, n + n^2)` `Hres`
row-major, column `n + n^2` the token's largest |column sum - 1| (what
`col_error` gives), then `Hpre` (the pre-mix's own: it needs the column
form to apply it), zeros after.

`_mhc_pre_call`. Grid (tiles of 128 rows, n): a step holds ONE stream of a
tile `[128, C]`, so the next stream's copy runs under this one's work.
Every step adds its share of the statistic (float32 sum of squares) and of
the projection, and keeps the stream in VMEM; the last stream's step does
the rest on what never left VMEM: `r`, the coefficients, the clamp, `exp`,
the sweeps (`layers/hyper_connections.py` `sinkhorn` itself, each entry a
`[1, 128]` row with the tokens along the lanes: 16 independent chains a
sweep, elementwise), the column error and the `n`-term pre-mix.

The projection is a float32 product, on the MXU in ONE bfloat16 pass over
the streams: `Phi` is cut into three bfloat16 parts (`hi + mid + lo`, 24
bits of mantissa) that are stacked along the rows of one operand (3 x 24 =
72 of them, 80 with the bfloat16 tile's padding), a stream's tile `[128,
128]` is the latched operand, and the three partial results are added in
float32. A bfloat16 stream is one part, so its product is exact to
float32's accumulation; a float32 stream is cut the same way and takes
three such passes (nine products: what `Precision.HIGHEST` multiplies, and
three more). The stacked `Phi` is built in the first row tile's steps and
stays in VMEM for the others. With `Phi`'s parts streamed (80 rows a K
tile) and not the stream's 128 rows, the MXU does five pushes a K tile for
eight, and its result has the tokens along the lanes, where the
coefficients want them. `r` is one number a token, so `Phi (x r)` is
computed as `r (Phi x)`: the statistic and the projection both read a
stream as it arrives. The parameters come as a stack over layers with the
layer's place (scalar prefetch): `Phi`'s window is indexed by it, so a scan
over layers hands in its stacked `[layers, 24, n C]` and no slice of it is
made; the 27 small numbers of every layer lie in SMEM.

Tokens lie along the sublanes where the streams are (the statistic is a
`[128, 1]` column out of a lane reduce, a coefficient applied to a stream
a column broadcast along the lanes) and along the lanes where the
coefficients are computed (a `[1, 128]` row a coefficient, one vreg an
operation); two `[128, 128]` float32 transposes on the XLU stand between:
the statistic to a row, the finished coefficients to columns.

`_mhc_post_call`. Grid (tiles of rows,): `X'[i] = sum_j Hres[i, j] X[j] +
Hpost[i] y` in float32, in the reference's order of summation, a 512-lane
piece at a time: one read of the streams, of `y` and of `coef`, one write.

The kernels keep the dtype of the streams they are given; `u` leaves the
pre-mix in float32 and `y` enters the post-mix in float32 whatever that is.
`models/xing4.py` holds the streams in float32 from the embedding to the
head. XLA made nearly that of the jnp functions under its default
`xla_allow_excess_precision`: every rounding that a widening followed was
folded away, so a layer's streams were rounded to bfloat16 once, at the
scan's carry, and the norm after a pre-mix read the unrounded sum. Rounded
at every kernel's edge instead, the forty-layer bfloat16 reference check
reads a fifth further from the float32 reference and breaks its limits
(PERF.md section 6, PR 47). One dtype for all four kernels of a layer also
means a program holds TWO kernels to trace and lower, not six (bfloat16 or
float32 streams in, bfloat16 or float32 out, a dense layer's or the scan's
parameters): in the host's Python a kernel costs a program's first call
about what a tenth of the rest of the model does, warm cache or cold
(PERF.md section 6, PR 47). A float32 stream is projected exactly (three
parts, three MXU passes): both pre-mixes of a layer pay for that.

Rows that do not fill the last tile are the pipeline's padding: every
quantity is a row's own, so what a padded row holds reaches no other row,
and its results are not written back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from intellillm_tpu.layers.hyper_connections import (HyperConnections,
                                                     sinkhorn)

COEF_WIDTH = 128            # `coef`'s lanes: one vreg row a token
_PRE_ROWS = 128             # the transposes' tile
_POST_ROWS = 32
_POST_LANES = 512
_PARTS = 3                  # bfloat16 parts of a float32 value
_VMEM_LIMIT_BYTES = 64 << 20
_F32 = jnp.float32
_BF16 = jnp.bfloat16


def err_column(hc: HyperConnections) -> int:
    """`coef`'s column of the column error."""
    return hc.n + hc.n * hc.n


def mhc_supported(x: jnp.ndarray, hc: HyperConnections) -> bool:
    """Flat streams `[..., n C]` of whole 128-lane tiles in bfloat16 or
    float32, and coefficients that fit the layout above: `2n + n^2` a
    multiple of 8 (their three parts are cut on sublane tiles) and, with
    the column error, no more than `coef`'s 128 columns, as `n` = 4 and
    `n` = 2 are. Any number of rows."""
    k = hc.coefficients
    return (x.shape[-1] % hc.n == 0
            and (x.shape[-1] // hc.n) % 128 == 0
            and x.dtype in (_BF16, _F32)
            and k % 8 == 0 and k + 1 <= COEF_WIDTH)


def _bf16_parts(x):
    """`x` as bfloat16 parts that sum to it, the smallest first."""
    if x.dtype == _BF16:
        return [x]
    hi = x.astype(_BF16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return [(rest - mid.astype(_F32)).astype(_BF16), mid, hi]


def _stack_rows(hc: HyperConnections) -> int:
    """Rows of `Phi`'s stacked parts: whole bfloat16 sublane tiles."""
    return -(-_PARTS * hc.coefficients // 16) * 16


def _pre_kernel(layer_ref, scalars_ref, x_ref, phi_ref, u_ref, coef_ref,
                stack_ref, keep_ref, m_ref, ss_ref, t_ref, *,
                hc: HyperConnections):
    n, k = hc.n, hc.coefficients
    tile, s = pl.program_id(0), pl.program_id(1)
    rows, c = x_ref.shape
    layer = layer_ref[0]

    def scalar(j):      # the layer's alpha, b_pre, b_post, b_res, in a row
        return scalars_ref[layer, j]

    @pl.when(tile == 0)
    def _stack_phi():
        lo, mid, hi = _bf16_parts(phi_ref[...])
        stack_ref[s] = jnp.concatenate(
            [hi, mid, lo,
             jnp.zeros((stack_ref.shape[1] - _PARTS * k, c), _BF16)], axis=0)

    @pl.when(s == 0)
    def _start():
        m_ref[...] = jnp.zeros_like(m_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...]
    keep_ref[s] = x
    xf = x.astype(_F32)
    ss_ref[...] += jnp.sum(xf * xf, axis=-1, keepdims=True)
    for part in _bf16_parts(x):
        # `Phi`'s parts are the streamed operand (72 rows a K tile, not the
        # tile's 128) and the result has the tokens along the lanes.
        # bfloat16 operands, one pass each whatever the process's default
        # (the parts ARE the float32 product; `jax_default_matmul_precision`
        # "highest" on a bfloat16 dot is refused by Mosaic).
        m_ref[...] += lax.dot_general(
            stack_ref[s], part, (((1, ), (1, )), ((), ())),
            precision=lax.Precision.DEFAULT, preferred_element_type=_F32)

    @pl.when(s == n - 1)
    def _finish():
        # The statistic is a column (a lane reduce of the streams); the
        # coefficients want it as a row: through the XLU, broadcast.
        ss = jnp.broadcast_to(ss_ref[...], (rows, COEF_WIDTH)).T[0:1, :]
        r = lax.rsqrt(ss / (n * c) + hc.rms_eps)                 # [1, rows]
        t_ref[0:k, :] = (m_ref[0:k, :] + (m_ref[k:2 * k, :]
                                          + m_ref[2 * k:3 * k, :])) * r

        def m(j):
            return t_ref[j:j + 1, :]                             # [1, rows]

        pre = [jax.nn.sigmoid(scalar(0) * m(i) + scalar(3 + i))
               for i in range(n)]
        post = [2.0 * jax.nn.sigmoid(scalar(1) * m(n + i)
                                     + scalar(3 + n + i)) for i in range(n)]
        comb = sinkhorn(
            [[jnp.clip(scalar(2) * m(2 * n + i * n + j)
                       + scalar(3 + 2 * n + i * n + j),
                       hc.clamp_min, hc.clamp_max) for j in range(n)]
             for i in range(n)], hc.sweeps, hc.eps)
        err = functools.reduce(jnp.maximum, [
            jnp.abs(sum(comb[i][j] for i in range(n)) - 1.0)
            for j in range(n)])
        t_ref[...] = jnp.zeros_like(t_ref)
        packed = post + [e for row in comb for e in row] + [err] + pre
        for j, value in enumerate(packed):
            t_ref[j:j + 1, :] = value
        coef = t_ref[...].T                                      # [rows, 128]
        coef_ref[...] = coef
        first = err_column(hc) + 1
        u = coef[:, first:first + 1] * keep_ref[0].astype(_F32)
        for i in range(1, n):
            u += coef[:, first + i:first + i + 1] * keep_ref[i].astype(_F32)
        u_ref[...] = u.astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hc", ))
def _mhc_pre_call(x, layer, phi, alpha, b_pre, b_post, b_res, *,
                  hc: HyperConnections):
    t, nc = x.shape
    n, k = hc.n, hc.coefficients
    c = nc // n
    rows = _PRE_ROWS
    layers = phi.shape[0]
    scalars = jnp.concatenate(
        [v.astype(_F32).reshape(layers, -1)
         for v in (alpha, b_pre, b_post, b_res)], axis=1)
    return pl.pallas_call(
        functools.partial(_pre_kernel, hc=hc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(t, rows), n),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((rows, c), lambda i, s, layer: (i, s)),
                # `Phi` is read by the first row tile alone: the others
                # leave its window where it is, so nothing is fetched for
                # them.
                pl.BlockSpec((None, k, c), lambda i, s, layer: (
                    layer[0], 0, jnp.where(i == 0, s, n - 1)))],
            out_specs=[
                pl.BlockSpec((rows, c), lambda i, s, layer: (i, 0)),
                pl.BlockSpec((rows, COEF_WIDTH), lambda i, s, layer: (i, 0))],
            scratch_shapes=[
                pltpu.VMEM((n, _stack_rows(hc), c), _BF16),   # Phi's parts
                pltpu.VMEM((n, rows, c), x.dtype),      # the tile's streams
                pltpu.VMEM((_stack_rows(hc), rows), _F32),    # the projection
                pltpu.VMEM((rows, 1), _F32),            # the sum of squares
                pltpu.VMEM((COEF_WIDTH, rows), _F32)]),  # tokens on lanes
        out_shape=[jax.ShapeDtypeStruct((t, c), _F32),
                   jax.ShapeDtypeStruct((t, COEF_WIDTH), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(layer.reshape(1), scalars, x, phi.astype(_F32))


def mhc_pre(x, p: dict, hc: HyperConnections, layer):
    """Flat streams `x [T, n C]` and the mixes' parameters -> (`u [T, C]`,
    `coef [T, 128]`, both float32). `p`'s leaves carry the layers of a
    stack along a leading axis and `layer` (an int, or an int32 scalar
    traced in a scan) says whose are meant: the kernel reads that layer's
    `Phi` out of the stack where it lies (a slice of it handed in would be
    a copy of 1.4 MB a sublayer). Callers check `mhc_supported` first."""
    # (an int32 scalar whichever way the caller names the layer: a number
    # would be weakly typed, a second jaxpr and a second lowering a program)
    return _mhc_pre_call(x, jnp.asarray(layer, jnp.int32), p["phi"],
                         p["alpha"], p["b_pre"], p["b_post"], p["b_res"],
                         hc=hc)


def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n: int):
    c = y_ref.shape[-1]
    coef = coef_ref[...]

    def col(j):
        return coef[:, j:j + 1]                                  # [rows, 1]

    for c0 in range(0, c, _POST_LANES):
        width = min(_POST_LANES, c - c0)

        def piece(ref, start):
            return ref[:, start + c0:start + c0 + width].astype(_F32)

        y = piece(y_ref, 0)
        xs = [piece(x_ref, j * c) for j in range(n)]
        for i in range(n):
            acc = col(n + i * n) * xs[0]
            for j in range(1, n):
                acc += col(n + i * n + j) * xs[j]
            acc += col(i) * y
            o_ref[:, i * c + c0:i * c + c0 + width] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", ))
def _mhc_post_call(x, y, coef, *, n: int):
    t, nc = x.shape
    c = nc // n
    # Whole sublane tiles of either dtype, and no more rows than there are.
    rows = min(_POST_ROWS, -(-t // 16) * 16)

    def row(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(pl.cdiv(t, rows), ),
        in_specs=[row(nc), row(c), row(COEF_WIDTH)],
        out_specs=row(nc),
        out_shape=jax.ShapeDtypeStruct((t, nc), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", ),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(x, y, coef)


def mhc_post(x, y, coef, hc: HyperConnections):
    """The flat streams `[T, n C]` after a sublayer whose output is `y [T,
    C]`, by `coef [T, 128]` as `mhc_pre` gives it, in `x`'s dtype. `x` and
    `y` in any float dtype."""
    return _mhc_post_call(x, y, coef, n=hc.n)
