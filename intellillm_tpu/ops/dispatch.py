"""Kernel dispatch policy: Pallas fast paths vs jnp reference impls.

The reference picks CUDA kernel V1 vs V2 by context length heuristics
(`attention.py:230-302`); here the choice is Pallas-vs-jnp by backend, with
an env/programmatic override for tests and debugging.

Two layers of selection (both trace-time, so the counter moves in
lockstep with XLA compiles and the jit bucket keys never change):

- `use_pallas()` — the backend-level gate (INTELLILLM_USE_PALLAS or
  default-on-TPU). Used by the prefill flash and decode paged kernels.
- `use_pallas_kernel(name)` — the backend gate AND a per-kernel
  INTELLILLM_PALLAS_<NAME> flag (default on), so one hot-path kernel can
  be reverted to its jnp reference without losing the others. Used by
  the ragged fused cache-write+attend kernel ("ragged") and the
  batched-LoRA BGMV kernel ("bgmv"); see docs/kernels.md. A kernel with
  no flag of its own (the latent-attention seams, "mla") follows the
  backend gate alone and still records its own label.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax

_FORCE: Optional[bool] = None

# Per-kernel opt-out flags for `use_pallas_kernel`. Every entry is a
# bounded label of intellillm_kernel_dispatch_total{path} (as
# "pallas:<name>" / "reference:<name>") — adding one here means
# documenting it in docs/kernels.md (flag-docs lint enforces this).
_KERNEL_FLAGS: Dict[str, str] = {
    "ragged": "INTELLILLM_PALLAS_RAGGED",
    "bgmv": "INTELLILLM_PALLAS_BGMV",
}


def set_use_pallas(force: Optional[bool]) -> None:
    """Force Pallas kernels on/off (None = auto by backend)."""
    global _FORCE
    _FORCE = force


def use_pallas() -> bool:
    result = _resolve_use_pallas()
    # Dispatch decisions happen at trace time, so the counter moves in
    # lockstep with XLA compiles (intellillm_kernel_dispatch_total).
    from intellillm_tpu.obs import record_kernel_dispatch
    record_kernel_dispatch("pallas" if result else "reference")
    return result


def use_pallas_kernel(kernel: str) -> bool:
    """Per-kernel selection: the backend gate AND the kernel's own
    INTELLILLM_PALLAS_* flag (unset/empty counts as enabled)."""
    result = _resolve_use_pallas() and _kernel_flag(kernel) is not False
    from intellillm_tpu.obs import record_kernel_dispatch
    record_kernel_dispatch(
        ("pallas:" if result else "reference:") + kernel)
    return result


def kernel_selection() -> Dict[str, object]:
    """Trace-time selection snapshot (no metrics side effects) for
    `/debug/kernels` and the warm-up stats: which path each kernel seam
    would take if a program were traced right now."""
    base = _resolve_use_pallas()
    sel: Dict[str, object] = {
        "use_pallas": base,
        "forced": _FORCE is not None,
        "backend": jax.default_backend(),
    }
    for kernel in _KERNEL_FLAGS:
        sel[kernel] = base and _kernel_flag(kernel) is not False
    return sel


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


def _kernel_flag(kernel: str) -> Optional[bool]:
    from intellillm_tpu.utils import parse_env_flag
    env = _KERNEL_FLAGS.get(kernel)
    if env is None:
        return None
    raw = os.environ.get(env)
    flag = parse_env_flag(raw)
    if flag is None and raw is not None and raw.strip():
        import warnings
        warnings.warn(
            f"{env}={raw!r} not recognized "
            "(use 0/1/true/false/on/off/yes/no); treating as enabled")
    return flag


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
