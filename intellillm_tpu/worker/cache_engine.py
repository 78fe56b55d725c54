"""KV-cache pool allocation and block-op execution.

Role parity: reference `vllm/worker/cache_engine.py` (CacheEngine :16):
allocates per-layer K/V pools on device and pinned host memory, executes
swap (:116-138) and copy (:140-144) plans, and computes the static
per-block byte size (:146-165) used to derive block counts from the memory
profile.

TPU redesign:
- Pool layout [num_blocks, num_kv_heads, block_size, head_size] (bf16 tile
  aligned; the reference's x=16/elem_size key trick is a CUDA coalescing
  detail with no TPU analogue).
- Two cache kinds. Per-head K/V: a layer's cache is the pair (K pool, V
  pool). Latent attention (`ModelConfig.get_latent_cache_width()`): a
  layer's cache is the 1-tuple (latent pool,), laid out
  [num_blocks, 1, block_size, stored_width] — no head axis to shard and no
  V. Every block op below maps over the tuple, so swap, copy and the KV
  audit's swap checksums work on both; KV export/import speaks (K, V) on
  the wire and refuses the latent kind with a message.
- Layers are described one by one (`ModelConfig.get_layer_cache_kinds`):
  a "kv" layer has the pools above; a "state" layer has one pool an
  array of `ModelConfig.get_state_arrays` (a short convolution: one; a
  delta-rule layer: its filters' taps and a float32 matrix a head; a
  state-space scan: its filter's taps and a float32 matrix in lane
  tiles), a slot a running sequence, not paged. A 2-d array is stored
  `[num_state_slots, taps * width]` (a slot's taps side by side, oldest
  first: whole lane tiles, no padded sublanes); a matrix a head is
  `[num_state_slots + 1, heads, d_k, d_v]`, the last row where the pad
  rows of a step program that updates the pool in place point
  (`ops/linear_attention.py`). `device_cache[i]` is layer i's tuple either way.
  A "kv_window" layer (a sliding window beside layers that keep the whole
  context) has the same pools with the window group's own number of
  blocks (`CacheConfig.num_window_device_blocks`), and the window group's
  block ops (`swap_in` / `swap_out` / `copy` with `window=True`) touch
  those layers alone.
  Block ops touch the "kv" layers, the state ops (`swap_state_out`,
  `swap_state_in`, `copy_state`) the "state" layers, by slot. Models
  whose layers are all "kv" have no state pool and run no state op.
  A model layer that holds BOTH (a scan beside its attention) is two
  entries of the list, "kv" then "state", and so is moved by both.
- Swaps are jax device↔host transfers (no CUDA streams/events; JAX's async
  dispatch overlaps them with compute until the arrays are consumed).
- Copies (CoW) are fused gather/scatter updates executed functionally; the
  engine re-binds the returned arrays (in-place under donation).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import CacheConfig, ModelConfig, ParallelConfig
from intellillm_tpu.logger import init_logger
from intellillm_tpu.ops.kv_cache import copy_blocks, swap_blocks
from intellillm_tpu.utils import STR_DTYPE_TO_JNP

logger = init_logger(__name__)

# (K pool, V pool), or (latent pool,).
KVCache = Tuple[jnp.ndarray, ...]


class CacheEngine:

    def __init__(
        self,
        cache_config: CacheConfig,
        model_config: ModelConfig,
        parallel_config: ParallelConfig,
        sharding=None,
    ) -> None:
        self.cache_config = cache_config
        self.model_config = model_config
        self.parallel_config = parallel_config

        # One entry a cache: "kv" (paged pools) or "state" (slots); a layer
        # with both is two entries.
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.num_layers = len(self.layer_kinds)
        self.kv_layers = [i for i, kind in enumerate(self.layer_kinds)
                          if kind == "kv"]
        self.window_layers = [i for i, kind in enumerate(self.layer_kinds)
                              if kind == "kv_window"]
        self.state_layers = [i for i, kind in enumerate(self.layer_kinds)
                             if kind == "state"]
        self.state_arrays = model_config.get_state_arrays()
        self.num_state_slots = (cache_config.num_state_slots
                                if self.state_layers else 0)
        # What a token stores in a layer (`get_cache_row_shape`): 2 pools
        # (K, V) of kv heads x head size, or the 1 latent pool of one
        # "head" of the stored latent width. The head count is the full
        # (unsharded) one: the pool is a logically global array sharded
        # over the mesh "model" axis by the head dim.
        self.num_pools, self.num_kv_heads, self.head_size = \
            model_config.get_cache_row_shape()
        # What the IKV1 wire format cannot carry, if anything.
        self.cache_kind = (
            "latent" if model_config.get_latent_cache_width()
            else "state" if self.state_layers
            else "window" if (self.window_layers
                              or model_config.get_sliding_window())
            else "kv")

        self.block_size = cache_config.block_size
        self.num_device_blocks = cache_config.num_device_blocks
        self.num_cpu_blocks = cache_config.num_cpu_blocks
        self.num_window_device_blocks = cache_config.num_window_device_blocks
        self.num_window_cpu_blocks = cache_config.num_window_cpu_blocks

        if cache_config.cache_dtype == "auto":
            self.dtype = jnp.dtype(STR_DTYPE_TO_JNP[model_config.dtype])
        else:
            self.dtype = jnp.dtype(STR_DTYPE_TO_JNP[cache_config.cache_dtype])

        self.sharding = sharding
        self.device_cache: List[KVCache] = self._allocate_device_cache()
        self.cpu_cache: List[Tuple[np.ndarray, ...]] = \
            self._allocate_cpu_cache()
        self.state_slot_bytes = self.get_state_slot_size(model_config)

        # Byte sizes for the obs swap accounting: swaps move host↔device
        # payload (unpadded logical bytes); CoW copies move on-device
        # (lane-padded physical) bytes.
        self.device_block_bytes = self.get_cache_block_size(
            self.block_size, cache_config.cache_dtype, model_config,
            parallel_config, cache_config.window_ahead_tokens)
        self.logical_block_bytes = self.get_logical_cache_block_size(
            self.block_size, cache_config.cache_dtype, model_config,
            cache_config.window_ahead_tokens)
        from intellillm_tpu.obs.device_telemetry import get_device_telemetry
        self._telemetry = get_device_telemetry()
        # KV integrity audit (obs/numerics.py): sampled blake2b
        # checksums over the host-staging paths — recorded at swap-out,
        # verified at swap-in; export/import staging is counted (the
        # wire format self-validates transit).
        from intellillm_tpu.obs.numerics import get_kv_audit
        self._kv_audit = get_kv_audit()

    def _block_shape(self, num_blocks: int) -> Tuple[int, ...]:
        # [num_blocks, kv_heads, block_size, head_size]: (block, head) pairs
        # are (block_size × head_size) tiles for the Pallas decode kernel;
        # dim 1 shards over the mesh "model" axis.
        return (num_blocks, self.num_kv_heads, self.block_size,
                self.head_size)

    def _state_shapes(self, device: bool):
        """(pool shape, dtype) of each array of a "state" layer; the host
        holds the same number of slots (and no pad row)."""
        return [(self.state_pool_shape(shape, self.num_state_slots, device),
                 jnp.dtype(STR_DTYPE_TO_JNP[dtype]))
                for shape, dtype in self.state_arrays]

    @staticmethod
    def state_pool_shape(shape, slots: int, device: bool = True):
        if ModelConfig.state_array_in_place(shape):
            return (slots + int(device), ) + tuple(shape)
        return (slots, int(np.prod(shape)))

    def _allocate_device_cache(self) -> List[KVCache]:
        caches = []
        for kind in self.layer_kinds:
            shape = self._block_shape(self.num_window_device_blocks
                                      if kind == "kv_window"
                                      else self.num_device_blocks)
            if kind == "state":
                # The state keeps its own dtypes whatever the KV pool's.
                caches.append(tuple(
                    jnp.zeros(pool, dtype=dtype)
                    for pool, dtype in self._state_shapes(device=True)))
                continue
            pools = []
            for _ in range(self.num_pools):
                pool = jnp.zeros(shape, dtype=self.dtype)
                if self.sharding is not None:
                    pool = jax.device_put(pool, self.sharding)
                pools.append(pool)
            caches.append(tuple(pools))
        return caches

    @staticmethod
    def _host_dtype(dtype) -> np.dtype:
        if dtype in (jnp.float32, jnp.float16):
            return np.dtype(dtype.name)
        # bf16 / fp8 swap pools keep the device dtype bit-for-bit via
        # ml_dtypes so swap in/out is lossless.
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, dtype.name))

    def _allocate_cpu_cache(self):
        np_dtype = self._host_dtype(self.dtype)
        return [tuple(np.zeros(pool, dtype=self._host_dtype(dtype))
                      for pool, dtype in self._state_shapes(device=False))
                if kind == "state" else
                tuple(np.zeros(self._block_shape(
                    self.num_window_cpu_blocks if kind == "kv_window"
                    else self.num_cpu_blocks), dtype=np_dtype)
                      for _ in range(self.num_pools))
                for kind in self.layer_kinds]

    # --- block-op execution ---------------------------------------------

    def _group_layers(self, window: bool) -> List[int]:
        return self.window_layers if window else self.kv_layers

    def swap_in(self, src_to_dst: Dict[int, int],
                window: bool = False) -> None:
        audit = self._kv_audit
        for i in self._group_layers(window):
            if audit.enabled:
                # Verify sampled host blocks BEFORE they re-enter the
                # device pool: a bit that flipped while the block sat
                # in host memory is caught here (counted + logged via
                # the kv_integrity_mismatch alert) instead of silently
                # corrupting every later token of the sequence.
                for src in src_to_dst:
                    if audit.should_audit(i, int(src)):
                        audit.verify("swap_in", i, int(src),
                                     *(cpu[int(src)]
                                       for cpu in self.cpu_cache[i]))
            self.device_cache[i] = tuple(
                swap_blocks(cpu, dev, src_to_dst, direction="in")
                for cpu, dev in zip(self.cpu_cache[i], self.device_cache[i]))
        self._telemetry.record_swap("in", len(src_to_dst),
                                    self.logical_block_bytes)

    def swap_out(self, src_to_dst: Dict[int, int],
                 window: bool = False) -> None:
        audit = self._kv_audit
        for i in self._group_layers(window):
            for dev, cpu in zip(self.device_cache[i], self.cpu_cache[i]):
                swap_blocks(dev, cpu, src_to_dst, direction="out")
            if audit.enabled:
                # swap_blocks(direction="out") is synchronous host
                # numpy, so the freshly written blocks are safe to hash
                # immediately. Sampling is deterministic per (layer,
                # block), so swap-in re-checks the same blocks.
                for dst in src_to_dst.values():
                    if audit.should_audit(i, int(dst)):
                        audit.record("swap_out", i, int(dst),
                                     *(cpu[int(dst)]
                                       for cpu in self.cpu_cache[i]))
        self._telemetry.record_swap("out", len(src_to_dst),
                                    self.logical_block_bytes)

    # --- KV export/import (disaggregated serving) ------------------------

    def export_blocks(
            self,
            block_numbers: List[int]) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Read device blocks into transient host arrays for a KV handoff.

        Same device→host path as swap_out, but into a payload-sized
        staging array (mapping device block i → staging slot j) instead
        of the fixed swap pool, so exports never contend with scheduler
        swap plans for CPU block numbers.
        """
        self._refuse_kv_transfer("export")
        src_to_dst = {int(b): j for j, b in enumerate(block_numbers)}
        shape = self._block_shape(len(block_numbers))
        np_dtype = self.cpu_cache[0][0].dtype if self.cpu_cache else \
            np.dtype(self.dtype.name)
        layers: List[Tuple[np.ndarray, np.ndarray]] = []
        for k_dev, v_dev in self.device_cache:
            k_out = np.zeros(shape, dtype=np_dtype)
            v_out = np.zeros(shape, dtype=np_dtype)
            swap_blocks(k_dev, k_out, src_to_dst, direction="out")
            swap_blocks(v_dev, v_out, src_to_dst, direction="out")
            layers.append((k_out, v_out))
        if self._kv_audit.enabled and block_numbers:
            # Coverage counters only: transit integrity on the handoff
            # path is the wire format's job (it self-validates).
            for i, (k_out, v_out) in enumerate(layers):
                for j in range(len(block_numbers)):
                    if self._kv_audit.should_audit(i, j):
                        self._kv_audit.record("export", i, j,
                                              k_out[j], v_out[j])
        self._telemetry.record_swap("out", len(block_numbers),
                                    self.logical_block_bytes)
        return layers

    def import_blocks(self, layers: List[Tuple[np.ndarray, np.ndarray]],
                      block_numbers: List[int]) -> None:
        """Scatter a KV handoff payload into device blocks (inverse of
        export_blocks; staging slot j → device block j's target)."""
        self._refuse_kv_transfer("import")
        if len(layers) != self.num_layers:
            raise ValueError(f"payload has {len(layers)} layers, cache has "
                             f"{self.num_layers}")
        src_to_dst = {j: int(b) for j, b in enumerate(block_numbers)}
        if self._kv_audit.enabled and block_numbers:
            for i, (k_host, v_host) in enumerate(layers):
                for j in range(len(block_numbers)):
                    if self._kv_audit.should_audit(i, j):
                        self._kv_audit.record("import", i, j,
                                              k_host[j], v_host[j])
        for i, (k_host, v_host) in enumerate(layers):
            k_dev, v_dev = self.device_cache[i]
            k_dev = swap_blocks(k_host, k_dev, src_to_dst, direction="in")
            v_dev = swap_blocks(v_host, v_dev, src_to_dst, direction="in")
            self.device_cache[i] = (k_dev, v_dev)
        self._telemetry.record_swap("in", len(block_numbers),
                                    self.logical_block_bytes)

    # What KV export/import cannot carry, by cache kind.
    _KV_TRANSFER_REFUSALS = {
        "latent": "this model caches one latent pool a layer",
        "state": "this model keeps a per-sequence recurrent state "
                 "beside its K/V pools, which a prefix of blocks does not "
                 "hold (it would need a state snapshot at the prefix's end)",
        "window": "this model's sliding-window cache group has given back "
                  "the blocks of a prefix longer than its window (and may "
                  "hold a table of its own beside the full layers')",
    }

    def _refuse_kv_transfer(self, what: str) -> None:
        why = self._KV_TRANSFER_REFUSALS.get(self.cache_kind)
        if why:
            raise NotImplementedError(
                f"KV {what} (worker/kv_transfer.py) carries per-layer K and "
                f"V slabs; {why}, which the IKV1 wire format cannot "
                f"describe. Disaggregated prefill/decode is not supported "
                f"for it yet.")

    def _copy_rows(self, layers: List[int],
                   src_to_dsts: Dict[int, List[int]]) -> None:
        """Rows (blocks, or slots) src -> dsts in every pool of `layers`."""
        copied = copy_blocks([self.device_cache[i] for i in layers],
                             src_to_dsts)
        for i, pools in zip(layers, copied):
            self.device_cache[i] = pools

    def copy(self, src_to_dsts: Dict[int, List[int]],
             window: bool = False) -> None:
        self._copy_rows(self._group_layers(window), src_to_dsts)
        self._telemetry.record_swap(
            "copy", sum(len(dsts) for dsts in src_to_dsts.values()),
            self.device_block_bytes)

    # --- per-sequence state ops (by slot) ---------------------------------

    def swap_state_out(self, src_to_dst: Dict[int, int]) -> None:
        """Device slot -> host slot, every "state" layer."""
        for i in self.state_layers:
            for dev, cpu in zip(self.device_cache[i], self.cpu_cache[i]):
                swap_blocks(dev, cpu, src_to_dst, direction="out")

    def swap_state_in(self, src_to_dst: Dict[int, int]) -> None:
        """Host slot -> device slot, every "state" layer."""
        for i in self.state_layers:
            self.device_cache[i] = tuple(
                swap_blocks(cpu, dev, src_to_dst, direction="in")
                for cpu, dev in zip(self.cpu_cache[i], self.device_cache[i]))

    def copy_state(self, src_to_dsts: Dict[int, List[int]]) -> None:
        """A fork: the parent's slot copied to each child's."""
        self._copy_rows(self.state_layers, src_to_dsts)

    def execute_state_ops(self, ops: List[Tuple[str, int, int]]) -> None:
        """The block manager's moves beside the primary group's plans, in
        the order it made them: ("swap_out" | "swap_in" | "fork", source
        slot, destination slot) of state slots, ("w_swap_out" |
        "w_swap_in" | "w_copy", source block, destination block) of the
        window group's blocks. Consecutive moves of one kind run as one
        call (a sequence's whole window table is one swap a layer, not
        one a block); a move that reads what an earlier one of the run
        wrote starts a new call."""
        kind, run, written = None, {}, set()
        for op, src, dst in ops:
            if run and (op != kind or src in written):
                self._execute_state_run(kind, run)
                run, written = {}, set()
            kind = op
            run.setdefault(src, []).append(dst)
            written.add(dst)
        if run:
            self._execute_state_run(kind, run)

    def _execute_state_run(self, op: str,
                           src_to_dsts: Dict[int, List[int]]) -> None:
        if op in ("w_copy", "fork"):
            if op == "fork":
                self.copy_state(src_to_dsts)
            else:
                self.copy(src_to_dsts, window=True)
            return
        # A swap moves a source to one place.
        src_to_dst = {src: dst for src, (dst, ) in src_to_dsts.items()}
        if op == "w_swap_out":
            self.swap_out(src_to_dst, window=True)
        elif op == "w_swap_in":
            self.swap_in(src_to_dst, window=True)
        elif op == "swap_out":
            self.swap_state_out(src_to_dst)
        elif op == "swap_in":
            self.swap_state_in(src_to_dst)
        else:
            raise ValueError(f"unknown state op {op!r}")

    # --- sizing ----------------------------------------------------------

    @staticmethod
    def get_cache_block_size(
        block_size: int,
        cache_dtype: str,
        model_config: ModelConfig,
        parallel_config: ParallelConfig,
        window_ahead: int = 0,
    ) -> int:
        """PHYSICAL bytes per block of the primary group across all layers
        (K + V, or the one latent pool), whole model. Where the model has
        a window group too, a primary block brings
        `get_window_group_share(window_ahead)` of a block in each of the
        window layers, counted here: the two groups are sized as one.

        TPU HBM arrays are tiled: the pool layout [NB, H, BS, D] pads the
        minor dim to the 128-lane width. For D=128 models physical ==
        logical — measured via XLA memory_analysis on v5e across
        fp8/int8/bf16/f32 AND block sizes 4/8/16/32 (no sublane padding:
        when the minor dim is exactly one lane tile, XLA merges the major
        dims, so BS needs no rounding). Small-head models (gpt2 D=64,
        tiny test models D=16) physically occupy up to 8x their logical
        bytes — sizing the pool by logical bytes made the memory profile
        allocate past HBM and OOM at engine init.
        """
        layer_block = CacheEngine.get_layer_block_size(
            block_size, cache_dtype, model_config)
        window_blocks = (model_config.get_num_window_layers() *
                         model_config.get_window_group_share(window_ahead))
        return (model_config.get_num_kv_layers() * layer_block
                + int(window_blocks * layer_block))

    @staticmethod
    def get_layer_block_size(block_size: int, cache_dtype: str,
                             model_config: ModelConfig) -> int:
        """PHYSICAL bytes of one block in ONE layer's pools (either
        group: the rows are alike)."""
        num_pools, num_kv_heads, head_size = \
            model_config.get_cache_row_shape()
        if cache_dtype == "auto":
            cache_dtype = model_config.dtype
        itemsize = jnp.dtype(STR_DTYPE_TO_JNP[cache_dtype]).itemsize
        lanes = -(-head_size // 128) * 128             # minor: pad to 128
        eff_block_size = block_size
        if lanes > 128:
            # Two+ lane tiles in the minor dim: XLA cannot merge the major
            # dims, so the sublane dim (BS) pads to the dtype tile —
            # account for it or the pool sizing under-estimates HBM and
            # OOMs at init (e.g. head_size 256 with block_size 8).
            sublane = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
            eff_block_size = -(-block_size // sublane) * sublane
        return num_pools * num_kv_heads * eff_block_size * lanes * itemsize

    @staticmethod
    def get_logical_cache_block_size(
        block_size: int,
        cache_dtype: str,
        model_config: ModelConfig,
        window_ahead: int = 0,
    ) -> int:
        """Unpadded bytes per block across all layers (K + V, or the
        latent pool at its stored width: the host pool holds the same
        zero-padded rows) — sizes the host (numpy) swap pool, which has no
        TPU tiling. The window group's share is counted as in
        `get_cache_block_size`."""
        num_pools, num_kv_heads, head_size = \
            model_config.get_cache_row_shape()
        num_layers = model_config.get_num_kv_layers()
        if cache_dtype == "auto":
            cache_dtype = model_config.dtype
        itemsize = jnp.dtype(STR_DTYPE_TO_JNP[cache_dtype]).itemsize
        layer_block = (num_pools * num_kv_heads * block_size * head_size *
                       itemsize)
        window_blocks = (model_config.get_num_window_layers() *
                         model_config.get_window_group_share(window_ahead))
        return num_layers * layer_block + int(window_blocks * layer_block)

    @staticmethod
    def get_state_slot_size(model_config: ModelConfig) -> int:
        """Bytes of one state slot across the "state" layers (0 for a
        model without them), on the device: each array's minor dimension
        padded to whole lanes (a 2-d array is one row a slot), a matrix's
        rows to whole sublane tiles. Every array has its own dtype: the
        cache dtype quantises K/V only."""
        total = 0
        for shape, dtype in model_config.get_state_arrays():
            itemsize = jnp.dtype(STR_DTYPE_TO_JNP[dtype]).itemsize
            if ModelConfig.state_array_in_place(shape):
                sublane = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
                rows = -(-shape[-2] // sublane) * sublane
                lead = int(np.prod(shape[:-2]))
                total += lead * rows * (-(-shape[-1] // 128) * 128) * itemsize
            else:
                total += (-(-int(np.prod(shape)) // 128) * 128) * itemsize
        return total and model_config.get_num_state_layers() * total
