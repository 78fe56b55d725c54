"""Kernel dispatch policy: Pallas fast paths vs jnp reference impls.

The reference picks CUDA kernel V1 vs V2 by context length heuristics
(`attention.py:230-302`); here every kernel sits behind a *seam* (a
function in `ops/`, `lora/layers.py` or `layers/quantization.py` that
owns one Pallas kernel and its jnp oracle), and a seam asks one question:
`use_kernel(name, supported)`. The answer is the backend's (Pallas on a
TPU, the oracle elsewhere; `set_use_pallas` / INTELLILLM_USE_PALLAS turn
every seam at once, to isolate a kernel fault) AND whether the shapes at
hand are ones the kernel supports, which the seam works out from what it
is given (head size a multiple of 128, `bgmv_supported`, ...). The
label of the path *taken* goes to intellillm_kernel_dispatch_total{path}
(`pallas:<name>` / `reference:<name>`).

The choice is made while tracing, so the counter moves in lockstep with
XLA compiles and the jit bucket keys never change.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax

_FORCE: Optional[bool] = None


def set_use_pallas(force: Optional[bool]) -> None:
    """Force Pallas kernels on/off (None = auto by backend)."""
    global _FORCE
    _FORCE = force


def use_kernel(kernel: str, supported: bool = True) -> bool:
    """Whether the seam `kernel` runs its Pallas kernel on the shapes at
    hand (`supported`: the seam's own test of them). Records the path
    taken; nothing else writes that record."""
    result = _resolve_use_pallas() and bool(supported)
    from intellillm_tpu.obs import record_kernel_dispatch
    record_kernel_dispatch(("pallas:" if result else "reference:") + kernel)
    return result


def kernel_selection() -> Dict[str, object]:
    """Trace-time selection snapshot (no metrics side effects) for
    `/debug/kernels` and the warm-up stats: which path the seams would
    take, shapes permitting, if a program were traced right now. `ragged`
    and `bgmv` repeat `use_pallas`: readers of the snapshot ask for them
    by name."""
    base = _resolve_use_pallas()
    return {
        "use_pallas": base,
        "forced": _FORCE is not None,
        "backend": jax.default_backend(),
        "ragged": base,
        "bgmv": base,
    }


def shard_over_heads(fn, mesh, num_kv_heads: int, in_head_dims,
                     out_head_dims):
    """Wrap a Pallas seam for a multi-chip mesh. Mosaic kernels cannot be
    partitioned by the compiler ("wrap the call in a shard_map"), so each
    chip runs the kernel on its own kv-head shard of the "model" axis —
    the axis the pool and the attention projections already shard on.
    `in_head_dims`/`out_head_dims` give, per argument/result, the index
    of its head dimension (None = replicated: row metadata). When the
    kv heads do not divide the axis the pool is replicated
    (parallel/mesh.shard_kv_cache) and every chip runs all heads. The
    "data" axis is never named: the pool is replicated over it, so every
    data replica must apply every row's write."""
    if mesh is None or mesh.devices.size == 1:
        return fn
    from jax.sharding import PartitionSpec as P
    axis = "model" if num_kv_heads % mesh.shape["model"] == 0 else None

    def spec(head_dim):
        if head_dim is None or axis is None:
            return P()
        return P(*([None] * head_dim + [axis]))

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(d) for d in in_head_dims),
        out_specs=tuple(spec(d) for d in out_head_dims),
        check_vma=False)


def _resolve_use_pallas() -> bool:
    if _FORCE is not None:
        return _FORCE
    from intellillm_tpu.utils import parse_env_flag
    raw = os.environ.get("INTELLILLM_USE_PALLAS")
    flag = parse_env_flag(raw)
    if flag is not None:
        return flag
    if raw is not None and raw.strip():
        import warnings
        warnings.warn(
            f"INTELLILLM_USE_PALLAS={raw!r} not recognized "
            "(use 0/1/true/false/on/off/yes/no); deferring to the "
            "backend default")
    return jax.default_backend() == "tpu"
