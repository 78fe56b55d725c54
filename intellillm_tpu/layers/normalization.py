"""Normalization layers.

Role parity: reference `vllm/model_executor/layers/layernorm.py` (RMSNorm
:10 with fused-add CUDA ops `csrc/layernorm_kernels.cu`). On TPU, XLA fuses
the residual-add + rmsnorm chain natively; the functions mirror the fused
CUDA entry points (rms_norm / fused_add_rms_norm) for call-site parity.

All reductions run in float32 regardless of activation dtype.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp


def rms_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-6,
) -> jnp.ndarray:
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (xf * weight.astype(jnp.float32)).astype(orig_dtype)


def fused_add_rms_norm(
    x: jnp.ndarray,
    residual: Optional[jnp.ndarray],
    weight: jnp.ndarray,
    eps: float = 1e-6,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (normed(x + residual), x + residual); `residual` None (a
    first layer's: the stream is `x` alone) stands for zeros."""
    added = x if residual is None else x + residual
    return rms_norm(added, weight, eps), added


def layer_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    eps: float = 1e-5,
) -> jnp.ndarray:
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(orig_dtype)
