"""Pallas (Mosaic) TPU kernels — the equivalents of the reference's CUDA
kernels in `csrc/`:

- paged_attention.py — decode-phase paged attention (the consolidated
  head-block-vectorized kernel; the old v3/v4 twin modules are one now)
- ragged_paged_attention.py — fused cache-write + causal paged attention
  over the flat mixed batch (decode + prefill-chunk rows in one grid)
- mla_attention.py — the same two kernels over a latent (MLA) pool
- grouped_ffn.py — expert FFN over rows sorted by expert (grouped matmuls
  after megablox `gmm`: no padding block an expert)
- bgmv.py — batched-LoRA gather-matmul (Punica BGMV equivalent)
- quant_matmul.py — int4 weight-dequant matmuls

Each kernel is called by one seam (ops/ragged_attention.py,
ops/mla_attention.py, ops/short_conv.py, ops/grouped_ffn.py, lora/layers.py,
layers/quantization.py), which asks
ops/dispatch.use_kernel and keeps the kernel's jnp oracle (see
docs/kernels.md for the contract and the one override).
"""
