"""Weight quantization for TPU.

Role parity: reference `vllm/model_executor/layers/quantization/` (AWQ
awq.py:12 / GPTQ gptq.py / SqueezeLLM squeezellm.py + CUDA kernels under
`csrc/quantization/*`). TPU redesign — two device representations:

- "int8": per-output-channel symmetric int8 computed at load from any fp
  checkpoint. Mixed-precision `lax.dot_general(bf16, int8)` feeds int8
  weight tiles straight to the MXU without a dequantized HBM copy.
- int4 ({"q4","s4","z4"}): group-wise asymmetric 4-bit along the input
  dim, two nibbles per uint8 — the SAME affine scheme AWQ/GPTQ
  checkpoints store, so their tensors convert losslessly (no re-rounding)
  at load; dequant happens inside the matmul's operand fusion.

Checkpoint converters (`awq_unpack` / `gptq_to_int4` /
`squeezellm_dequantize`) replace the reference's CUDA dequant kernels
(`csrc/quantization/awq/gemm_kernels.cu`, `gptq/q_gemm.cu`,
`squeezellm/quant_cuda_kernel.cu`): AWQ and GPTQ load to int4 exactly
(GPTQ act-order becomes an input-row permutation — the exllama
`gptq_shuffle` role — never a re-rounding); SqueezeLLM's non-uniform LUT
cannot map onto an affine int4 grid, so it keeps its per-channel 16-entry
table as the exact device format `{"q4lut", "lut"}`
(`squeezellm_to_q4lut`), and only a layout the packer cannot express (an
odd input dim) is dequantized and requantized to int8.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

QuantizedWeight = Dict[str, jnp.ndarray]  # {"q": int8 [in,out], "s": f32 [out]}

# AWQ nibble order within each packed int32 (AWQ repo pack order).
_AWQ_ORDER = (0, 2, 4, 6, 1, 3, 5, 7)


def quantize_int8(w: np.ndarray) -> QuantizedWeight:
    """Per-output-channel symmetric int8 quantization of a [in, out] weight."""
    wf = np.asarray(w, dtype=np.float32)
    amax = np.max(np.abs(wf), axis=0)                  # [out]
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale[None, :]), -127, 127).astype(np.int8)
    return {"q": q, "s": scale}


def quantize_int8_jax(w: jnp.ndarray) -> QuantizedWeight:
    """Device-side variant (for dummy/random init)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and (("q" in w and "s" in w) or "q4" in w
                                    or "q4lut" in w)


# --- int4 (group-wise asymmetric, AWQ/GPTQ-compatible) -------------------


def pack_int4(q: np.ndarray, zeros: np.ndarray,
              scales: np.ndarray) -> QuantizedWeight:
    """q uint4-valued [in, out], zeros/scales [in/group, out] →
    {"q4": uint8 [in/2, out], "s4": f32, "z4": f32}. Row 2i is the low
    nibble of packed row i."""
    in_, out = q.shape
    assert in_ % 2 == 0
    q = q.astype(np.uint8)
    q4 = (q[0::2] | (q[1::2] << 4)).astype(np.uint8)
    return {"q4": q4, "s4": scales.astype(np.float32),
            "z4": zeros.astype(np.float32)}


def quantize_int4(w: np.ndarray, group_size: int = 128) -> QuantizedWeight:
    """Group-wise asymmetric int4 quantization of a fp [in, out] weight
    (for dummy weights / fp checkpoints served with an int4 method)."""
    wf = np.asarray(w, np.float32)
    in_, out = wf.shape
    if in_ % group_size != 0:
        group_size = in_
    g = in_ // group_size
    wg = wf.reshape(g, group_size, out)
    wmin = wg.min(axis=1)                               # [g, out]
    wmax = wg.max(axis=1)
    scale = np.maximum((wmax - wmin) / 15.0, 1e-8)
    zeros = np.round(-wmin / scale).clip(0, 15)
    q = np.clip(np.round(wg / scale[:, None] + zeros[:, None]), 0,
                15).astype(np.uint8)
    return pack_int4(q.reshape(in_, out), zeros, scale)


def _dequant_int4(w: QuantizedWeight, dtype) -> jnp.ndarray:
    q4 = w["q4"]
    in2, out = q4.shape
    lo = (q4 & 0xF)
    hi = (q4 >> 4)
    q = jnp.stack([lo, hi], axis=1).reshape(2 * in2, out)
    g = w["s4"].shape[0]
    qg = q.astype(jnp.float32).reshape(g, (2 * in2) // g, out)
    wf = (qg - w["z4"][:, None]) * w["s4"][:, None]
    return wf.reshape(2 * in2, out).astype(dtype)


def _dequant_int4lut(w: QuantizedWeight, dtype) -> jnp.ndarray:
    """{"q4lut","lut"} → dense [in, out]: per-channel 16-entry codebook
    gather (exact SqueezeLLM semantics)."""
    q4 = w["q4lut"]
    in2, out = q4.shape
    lo = (q4 & 0xF)
    hi = (q4 >> 4)
    q = jnp.stack([lo, hi], axis=1).reshape(2 * in2, out).astype(jnp.int32)
    return jnp.take_along_axis(
        w["lut"], q, axis=0).astype(dtype)               # lut [16, out]


def dequant_int4_stack(w: QuantizedWeight, dtype) -> jnp.ndarray:
    """Expert-stacked int4 → dense [N, in, out] (QuantMixtral: reference
    `mixtral_quant.py` runs per-expert quantized linears; here the packed
    per-expert tensors persist in HBM and dequantize on the fly for the
    grouped/dense MoE einsum). The packed format is the checkpoint's for
    all three stacks; `layers/moe.py` `moe_ffn` swaps the last two axes of
    what this returns for w1/w3, whose dense layout is [N, out, in].
    Optional "inv" [N, in] undoes per-expert GPTQ act-order row sorting."""
    q4 = w["q4"]                                     # [N, in/2, out]
    n, in2, out = q4.shape
    lo = (q4 & 0xF)
    hi = (q4 >> 4)
    q = jnp.stack([lo, hi], axis=2).reshape(n, 2 * in2, out)
    g = w["s4"].shape[1]
    qg = q.astype(jnp.float32).reshape(n, g, (2 * in2) // g, out)
    wf = (qg - w["z4"][:, :, None]) * w["s4"][:, :, None]
    wf = wf.reshape(n, 2 * in2, out)
    if "inv" in w:
        wf = jnp.take_along_axis(wf, w["inv"][:, :, None], axis=1)
    return wf.astype(dtype)


def stack_expert_int4(per_expert: list) -> Optional[QuantizedWeight]:
    """Stack per-expert pack_int4 dicts into the 3D QuantMixtral device
    format; returns None if any expert failed conversion or shapes
    disagree. Act-order perms become a stacked inverse-gather index."""
    if any(e is None for e in per_expert):
        return None
    shapes = {e["q4"].shape for e in per_expert}
    if len(shapes) != 1:
        return None
    out: QuantizedWeight = {
        "q4": np.stack([e["q4"] for e in per_expert]),
        "s4": np.stack([e["s4"] for e in per_expert]),
        "z4": np.stack([e["z4"] for e in per_expert]),
    }
    if any("perm" in e for e in per_expert):
        in_ = out["q4"].shape[1] * 2
        invs = []
        for e in per_expert:
            perm = e.get("perm")
            if perm is None:
                invs.append(np.arange(in_, dtype=np.int32))
            else:
                inv = np.empty(in_, np.int32)
                inv[perm] = np.arange(in_, dtype=np.int32)
                invs.append(inv)
        out["inv"] = np.stack(invs)
    return out


def qmatmul(x: jnp.ndarray, w: Union[jnp.ndarray, QuantizedWeight]
            ) -> jnp.ndarray:
    """x @ w for plain, int8-quantized, int4-quantized, or LUT-quantized
    (SqueezeLLM) weights.

    int8: mixed-dtype dot_general keeps the weight un-dequantized in HBM;
    the per-channel scale applies to the f32 accumulator. int4: nibble
    unpack + affine dequant fuse into the dot's operand producer, so HBM
    stores only the packed bytes + group scales/zeros. q4lut: same packed
    nibbles, dequantized through the exact per-channel codebook.
    """
    if not is_quantized(w):
        return x @ w
    if "q4lut" in w:
        from intellillm_tpu.ops.dispatch import use_kernel
        from intellillm_tpu.ops.pallas import quant_matmul as _qmm
        if use_kernel("int4_lut", _qmm.supports_lut(w)):
            return _qmm.quant_matmul_int4_lut(x, w)
        return x @ _dequant_int4lut(w, x.dtype)
    if "q4" in w:
        from intellillm_tpu.ops.dispatch import use_kernel
        from intellillm_tpu.ops.pallas import quant_matmul as _qmm
        if use_kernel("int4", _qmm.supports(w)):
            # Pallas kernel: packed bytes stream HBM→VMEM, dequant feeds
            # the MXU in-tile. It reserves ZERO temp HBM, where the XLA
            # path's buffer plan reserves ~6x the packed bytes (measured
            # 541 MB for 4096x11008), and fetch-synced v5e device timing
            # has it ~35% faster at every batch size measured (b=8..256:
            # 4.5/4.0/3.8/3.8 ms vs 6.1/6.0/6.1/6.1 ms incl. dispatch
            # overhead).
            return _qmm.quant_matmul_int4(x, w)
        if "perm" in w:
            # Act-order (GPTQ g_idx): weight rows were pre-sorted by group
            # at load; mirror the same reorder on the activation's
            # contraction dim.
            x = jnp.take(x, w["perm"], axis=-1)
        return x @ _dequant_int4(w, x.dtype)
    out = jax.lax.dot_general(
        x, w["q"],
        dimension_numbers=(((x.ndim - 1, ), (0, )), ((), ())),
        preferred_element_type=jnp.float32)
    return (out * w["s"]).astype(x.dtype)


# --- checkpoint converters ------------------------------------------------


def _unpack_int32_nibbles(packed: np.ndarray, order=None) -> np.ndarray:
    """[R, C] int32 → [R, C*8] uint8 nibbles; `order` maps nibble position
    → channel offset within each pack group of 8."""
    r, c = packed.shape
    u = packed.astype(np.uint32)
    out = np.empty((r, c * 8), np.uint8)
    for i in range(8):
        chan = order[i] if order is not None else i
        out[:, chan::8] = ((u >> (4 * i)) & 0xF).astype(np.uint8)
    return out


def _unpack_int32_nibbles_rows(packed: np.ndarray) -> np.ndarray:
    """[R, C] int32 → [R*8, C] uint8 nibbles, sequential along rows (the
    GPTQ/SqueezeLLM qweight layout)."""
    rows, c = packed.shape
    u = packed.astype(np.uint32)
    out = np.empty((rows * 8, c), np.uint8)
    for i in range(8):
        out[i::8] = ((u >> (4 * i)) & 0xF).astype(np.uint8)
    return out


def awq_unpack(qweight: np.ndarray, qzeros: np.ndarray,
               scales: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """AWQ GEMM-format tensors → (q [in, out], zeros [g, out],
    scales [g, out]); w = (q - z) * s. qweight/qzeros are int32 with 8
    nibbles in AWQ order; scales fp16 [g, out]."""
    q = _unpack_int32_nibbles(qweight, _AWQ_ORDER)       # [in, out]
    z = _unpack_int32_nibbles(qzeros, _AWQ_ORDER)        # [g, out]
    return q, z.astype(np.float32), np.asarray(scales, np.float32)


def awq_to_int4(qweight, qzeros, scales) -> QuantizedWeight:
    """Lossless AWQ → device int4 (same affine scheme)."""
    q, z, s = awq_unpack(qweight, qzeros, scales)
    return pack_int4(q, z, s)


def gptq_to_int4(qweight: np.ndarray, qzeros: np.ndarray,
                 scales: np.ndarray,
                 g_idx: np.ndarray = None) -> Union[QuantizedWeight, None]:
    """Lossless GPTQ → device int4: GPTQ stores the same group-wise 4-bit
    affine scheme as AWQ, only packed differently, so no value is ever
    re-rounded. Act-order checkpoints (non-trivial `g_idx`) get their
    input rows stably sorted by group so each group is contiguous, plus a
    "perm" entry that `qmatmul` applies to the activation — the role of
    the reference's exllama shuffle (`gptq.py:208-209`,
    `csrc/quantization/gptq/q_gemm.cu`) without changing any weight
    value. Returns None when the group structure is irregular (e.g. a
    group with more/fewer rows than group_size); the caller then falls
    back to int8 requantization.
    """
    q = _unpack_int32_nibbles_rows(qweight)              # [in, out]
    in_ = q.shape[0]
    z = (_unpack_int32_nibbles(qzeros) + 1).astype(np.float32)  # [g, out]
    s = np.asarray(scales, np.float32)                   # [g, out]
    g = s.shape[0]
    if g == 0 or in_ % g != 0 or in_ % 2 != 0:
        return None
    group = in_ // g
    perm = None
    if g_idx is not None and len(g_idx):
        g_idx = np.asarray(g_idx, np.int64)
        if not np.array_equal(g_idx, np.arange(in_) // group):
            counts = np.bincount(g_idx, minlength=g)
            if counts.shape[0] != g or not np.all(counts == group):
                return None
            perm = np.argsort(g_idx, kind="stable").astype(np.int32)
            q = q[perm]
    w = pack_int4(q, z, s)
    if perm is not None:
        w["perm"] = perm
    return w


def gptq_dequantize(qweight: np.ndarray, qzeros: np.ndarray,
                    scales: np.ndarray,
                    g_idx: np.ndarray = None,
                    bits: int = 4) -> np.ndarray:
    """GPTQ tensors → fp32 [in, out]. qweight int32 [in*bits/32, out]
    sequential nibbles along the INPUT dim; qzeros int32 [g, out*bits/32]
    sequential along out, storing z-1; g_idx [in] group per row
    (act-order)."""
    assert bits == 4, "only 4-bit GPTQ is supported"
    q = _unpack_int32_nibbles_rows(qweight)              # [in, out]
    in_ = q.shape[0]
    z = _unpack_int32_nibbles(qzeros) + 1                # [g, out]
    s = np.asarray(scales, np.float32)                   # [g, out]
    if g_idx is None or len(g_idx) == 0:
        group = in_ // s.shape[0]
        g_idx = np.arange(in_) // group
    g_idx = np.asarray(g_idx, np.int64)
    return (q.astype(np.float32) - z[g_idx].astype(np.float32)) * s[g_idx]


def squeezellm_dequantize(qweight: np.ndarray,
                          lookup_table: np.ndarray) -> np.ndarray:
    """SqueezeLLM: qweight int32 [in/8, out] sequential nibbles,
    lookup_table [out, 16] per-channel codebook → fp32 [in, out]."""
    q = _unpack_int32_nibbles_rows(qweight)              # [in, out]
    out = q.shape[1]
    lut = np.asarray(lookup_table, np.float32)           # [out, 16]
    return lut[np.arange(out)[None, :], q]               # [in, out]


def squeezellm_to_q4lut(qweight: np.ndarray,
                        lookup_table: np.ndarray):
    """SqueezeLLM checkpoint tensors → LOSSLESS device format
    {"q4lut": uint8 [in/2, out], "lut": f32 [16, out]}: the packed
    nibbles are the codebook indices verbatim (repacked 8-per-int32 →
    2-per-byte, same even/odd split as pack_int4) and the non-uniform
    per-channel table executes exactly at matmul time — matching the
    reference's in-kernel LUT dequant
    (csrc/quantization/squeezellm/quant_cuda_kernel.cu:1-225) instead of
    an int8 re-rounding. Returns None for layouts the packer can't
    express (odd input dim)."""
    q = _unpack_int32_nibbles_rows(qweight)              # [in, out]
    if q.shape[0] % 2:
        return None
    q4 = (q[0::2] | (q[1::2] << 4)).astype(np.uint8)     # [in/2, out]
    lut = np.ascontiguousarray(
        np.asarray(lookup_table, np.float32).T)          # [16, out]
    return {"q4lut": q4, "lut": lut}
