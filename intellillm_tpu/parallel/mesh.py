"""Device mesh + sharding: the TPU-native replacement for the reference's
entire parallelism stack.

Role parity map (SURVEY §2.6):
- Megatron-style TP layer classes (`vllm/model_executor/layers/linear.py`
  ColumnParallelLinear :130 / RowParallelLinear :444,
  `vocab_parallel_embedding.py` :39) → `PartitionSpec`s over the mesh
  "model" axis; XLA GSPMD inserts the same all-reduces
  (2 per decoder layer + 1 at sampling, SURVEY §3.3) as ICI collectives.
- NCCL process groups + `communication_op.py` wrappers + custom IPC
  all-reduce (`csrc/custom_all_reduce.cu`) → `jax.lax.psum` et al., emitted
  by the compiler. Nothing to hand-write; this module only describes WHERE
  tensors live.
- Ray actor orchestration (`engine/ray_utils.py`) → single controller: one
  process drives every chip in the mesh.

Mesh axes: ("data", "model"). TP = size of "model"; DP = size of "data"
(used by the multi-chip dry-run/training-style step; online serving scales
DP by engine replicas, same as the reference).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from intellillm_tpu.config import ParallelConfig
from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)


def build_mesh(parallel_config: ParallelConfig,
               devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    tp = parallel_config.tensor_parallel_size
    dp = parallel_config.data_parallel_size
    need = tp * dp
    if need > len(devices):
        raise ValueError(
            f"Requested tp={tp} dp={dp} but only {len(devices)} devices "
            "are visible.")
    mesh_devices = np.asarray(devices[:need]).reshape(dp, tp)
    return Mesh(mesh_devices, axis_names=("data", "model"))


def is_single_device(mesh: Mesh) -> bool:
    return mesh.devices.size == 1


def shard_params(host_params: Any, mesh: Mesh, model) -> Any:
    """Place the host param pytree onto the mesh.

    Uses the model's `partition_specs()` (a pytree of PartitionSpec
    mirroring the param tree) when tensor parallelism is active; falls back
    to replication for leaves whose dims don't divide the axis (e.g. GQA
    kv projections with fewer kv heads than tp degree — the reference
    replicates kv heads the same way, `config.py:256-264`).
    """
    if is_single_device(mesh):
        return jax.device_put(host_params)

    specs = None
    if hasattr(model, "partition_specs"):
        specs = model.partition_specs()
    if specs is None:
        logger.warning("Model has no partition_specs; replicating params.")
        return jax.device_put(host_params,
                              NamedSharding(mesh, P()))

    # Look specs up by tree path: the param tree may contain None where the
    # spec tree has a leaf (e.g. tied lm_head), so a plain tree.map would
    # see mismatched structures.
    spec_by_path = {
        jax.tree_util.keystr(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
    }
    # Vocab-sized dims may be ZERO-PADDED to a shardable multiple instead
    # of replicating (reference pads the vocab to 64*tp,
    # `vocab_parallel_embedding.py:39-111`); the model names which
    # (path, dim) pairs are safe to pad — padding is only valid where
    # extra rows/cols are inert (embedding rows never gathered; logit
    # columns masked to -inf by the runner).
    pad_eligible = {}
    if hasattr(model, "tp_pad_paths"):
        pad_eligible = model.tp_pad_paths()

    def place(path, leaf):
        key = jax.tree_util.keystr(path)
        spec = spec_by_path.get(key)
        if spec is None:
            # Quantized-weight spec dicts ({"q4": spec, ...}) cover the
            # packed representations, but the loader may legitimately
            # fall back to a DENSE array at the parent path (irregular
            # group layouts, dummy weights). The dense weight has the
            # same dims as its packed "q4"/"q" form — inherit that spec
            # instead of silently replicating a multi-GiB expert stack —
            # unless the spec dict names another under "dense" (MoE
            # w1/w3: stored [N, I, D], packed [N, D/2, I]).
            spec = (spec_by_path.get(key + "['dense']")
                    or spec_by_path.get(key + "['q4']")
                    or spec_by_path.get(key + "['q']") or P())
        fixed = []
        for dim, axis in enumerate(spec):
            if axis is None:
                fixed.append(None)
                continue
            axis_size = mesh.shape[axis]
            if leaf.shape[dim] % axis_size != 0:
                if pad_eligible.get(key) == dim:
                    pad_to = 64 * axis_size
                    target = -(-leaf.shape[dim] // pad_to) * pad_to
                    widths = [(0, 0)] * leaf.ndim
                    widths[dim] = (0, target - leaf.shape[dim])
                    leaf = np.pad(np.asarray(leaf), widths)
                    logger.info(
                        "Param %s dim %d padded %d -> %d for %s=%d.",
                        key, dim, target - widths[dim][1], target, axis,
                        axis_size)
                    fixed.append(axis)
                    continue
                logger.warning(
                    "Param %s dim %d (%d) not divisible by %s=%d; "
                    "replicating.", key, dim, leaf.shape[dim], axis,
                    axis_size)
                fixed.append(None)
            else:
                fixed.append(axis)
        return jax.device_put(leaf, NamedSharding(mesh, P(*fixed)))

    return jax.tree_util.tree_map_with_path(place, host_params)


def leaf_shard_bytes(x: Any) -> int:
    """Per-chip bytes of one (possibly sharded) array: the shard shape
    under its NamedSharding, the full shape when unsharded/host-side."""
    try:
        shape = x.sharding.shard_shape(x.shape)
    except Exception:
        shape = x.shape
    n = 1
    for s in shape:
        n *= s
    return n * x.dtype.itemsize


def param_shard_bytes(tree: Any) -> int:
    """Per-chip resident bytes of a sharded param pytree — used both by
    the worker's memory profile and the obs memory ledger."""
    return sum(leaf_shard_bytes(x) for x in jax.tree.leaves(tree))


def shard_kv_cache(mesh: Mesh,
                   num_kv_heads: Optional[int] = None
                   ) -> Optional[NamedSharding]:
    """KV pool sharding: [num_blocks, num_kv_heads, block_size, head_size]
    sharded by kv-head over "model" (the TP equivalent of the reference's
    KV-head division, `config.py:256-264`). When the kv-head count does not
    divide the axis (GQA with few kv heads), the pool replicates — same as
    the reference's kv-head replication for num_kv_heads < tp."""
    if mesh is None or is_single_device(mesh):
        return None
    tp = mesh.shape["model"]
    if num_kv_heads is not None and num_kv_heads % tp != 0:
        logger.warning(
            "KV pool: %d kv heads not divisible by tp=%d; replicating "
            "cache (reference replicates kv heads the same way).",
            num_kv_heads, tp)
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(None, "model", None, None))
