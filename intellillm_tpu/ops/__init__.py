"""Device ops: the TPU-native equivalents of the reference's `csrc/` CUDA
kernels (`csrc/pybind.cpp` ops/cache_ops), implemented as jnp functions that
XLA fuses, with Pallas kernels for the ops where hand control of HBM traffic
pays (paged-attention decode, the fused ragged cache-write + attend on the
mixed path and their latent-cache twins, LoRA bgmv, int4 matmuls). A kernel
is reached through its seam, which asks `ops/dispatch.use_kernel`; nothing
outside this package imports `ops.pallas` for attention."""
from intellillm_tpu.ops.kv_cache import (copy_blocks, reshape_and_cache,
                                         swap_blocks)
from intellillm_tpu.ops.attention import (decode_attention_reference,
                                          prefill_attention_reference)
from intellillm_tpu.ops.ragged_attention import (
    paged_pool_attention, ragged_fused_attention,
    ragged_fused_attention_reference)

__all__ = [
    "copy_blocks",
    "reshape_and_cache",
    "swap_blocks",
    "decode_attention_reference",
    "prefill_attention_reference",
    "paged_pool_attention",
    "ragged_fused_attention",
    "ragged_fused_attention_reference",
]
