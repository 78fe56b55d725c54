"""Seam of the hyper-connection residual's two stream mixes (labels
`mhc_pre`, `mhc_post`).

`models/xing4.py` calls this and nothing below it. A sublayer is

    u, coef, err = mhc_pre(x, p, hc, layer) # before the sublayer F
    x = mhc_post(x, F(norm(u)), coef, hc)   # after it

with the streams FLAT, `x [..., n C]` (stream `i` the lanes `[i C, (i + 1)
C)`: the bytes of `[..., n, C]`), `u [..., C]` float32, `err [...]`
the token's largest |column sum - 1| of its mixing matrix, and `coef [...,
128]` float32 the coefficients as the two halves hand them over: columns
`[0, n)` `Hpost`, `[n, n + n^2)` `Hres` row-major, column `n + n^2` is
`err` (`unpack` gives the first two their shapes back; the columns after
are unspecified). Each function asks `ops/dispatch.use_kernel` once and
runs its Pallas kernel (`ops/pallas/hyper_connections.py`: `_mhc_pre_call`
/ `_mhc_post_call` in the device trace) or the jnp oracle, which is
`layers/hyper_connections.py` itself on the `[..., n, C]` view: the same
equations, float32 coefficients, a float32 projection product, the sweeps
in the published order.

On a mesh of more than one device the oracle serves (`mesh`): Mosaic kernels
are not partitioned by the compiler, and the model that has these streams
replicates them and refuses tensor parallelism anyway
(`models/xing4.py` `partition_specs`).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from intellillm_tpu.layers import hyper_connections as reference
from intellillm_tpu.layers.hyper_connections import HyperConnections
from intellillm_tpu.ops.dispatch import use_kernel
from intellillm_tpu.ops.pallas import hyper_connections as kernels
from intellillm_tpu.ops.pallas.hyper_connections import COEF_WIDTH, err_column


def _supported(x, hc: HyperConnections, mesh) -> bool:
    return ((mesh is None or mesh.devices.size == 1)
            and kernels.mhc_supported(x, hc))


def pack(post: jnp.ndarray, comb: jnp.ndarray) -> jnp.ndarray:
    """`post [..., n]`, `comb [..., n, n]` -> `coef [..., 128]`."""
    lead, n = post.shape[:-1], post.shape[-1]
    used = [post, comb.reshape(lead + (n * n, )),
            reference.col_error(comb)[..., None]]
    return jnp.concatenate(
        used + [jnp.zeros(lead + (COEF_WIDTH - n - n * n - 1, ),
                          jnp.float32)], axis=-1)


def unpack(coef: jnp.ndarray, hc: HyperConnections
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`coef [..., 128]` -> (`post [..., n]`, `comb [..., n, n]`)."""
    n = hc.n
    return coef[..., :n], coef[..., n:n + n * n].reshape(
        coef.shape[:-1] + (n, n))


def mhc_pre(x: jnp.ndarray, p: dict, hc: HyperConnections, layer,
            mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flat streams `x [..., n C]` -> (`u [..., C]`, `coef [..., 128]`,
    `err [...]`), all float32. `p`: the mixes' parameters
    (`HyperConnections.param_shapes`) of a stack of layers along a leading
    axis, of which layer `layer`'s (an int, or an int32 scalar traced in a
    scan over layers) are meant: the kernel reads them where they lie."""
    lead, c = x.shape[:-1], x.shape[-1] // hc.n
    if use_kernel("mhc_pre", _supported(x, hc, mesh)):
        u, coef = kernels.mhc_pre(x.reshape(-1, hc.n * c), p, hc, layer)
        u, coef = u.reshape(lead + (c, )), coef.reshape(lead + (COEF_WIDTH, ))
    else:
        p = {key: v[layer] for key, v in p.items()}
        # On the widened streams: `u` unrounded, as the kernel hands it on.
        u, post, comb = reference.mhc_pre(
            x.reshape(lead + (hc.n, c)).astype(jnp.float32), p, hc)
        coef = pack(post, comb)
    return u, coef, coef[..., err_column(hc)]


def mhc_post(x: jnp.ndarray, y: jnp.ndarray, coef: jnp.ndarray,
             hc: HyperConnections, mesh=None) -> jnp.ndarray:
    """The flat streams after a sublayer whose output is `y [..., C]`, in
    `x`'s dtype."""
    lead, c = x.shape[:-1], y.shape[-1]
    if use_kernel("mhc_post", _supported(x, hc, mesh)):
        return kernels.mhc_post(
            x.reshape(-1, hc.n * c), y.astype(jnp.float32).reshape(-1, c),
            coef.reshape(-1, COEF_WIDTH), hc).reshape(x.shape)
    post, comb = unpack(coef, hc)
    return reference.mhc_post(x.reshape(lead + (hc.n, c)), y, post,
                              comb).reshape(x.shape)
