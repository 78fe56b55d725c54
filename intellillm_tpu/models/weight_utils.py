"""Checkpoint weight streaming.

Role parity: reference `vllm/model_executor/weight_utils.py`
(prepare_hf_model_weights :126, hf_model_weights_iterator :204,
default_weight_loader :280, dummy init :287): iterate HF checkpoint
shards (safetensors preferred, torch .bin fallback) yielding (name, array).
TPU redesign: tensors are materialized on host and converted to numpy /
ml_dtypes (no torch in the compute path); device placement + mesh sharding
happen when the model assembles its param tree.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Iterator, List, Optional, Tuple

import numpy as np

from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)


def _resolve_model_dir(model_name_or_path: str,
                       revision: Optional[str] = None) -> str:
    if os.path.isdir(model_name_or_path):
        return model_name_or_path
    # Fall back to the HF cache (offline-friendly; no network needed when
    # the snapshot is already local).
    try:
        from huggingface_hub import snapshot_download
        return snapshot_download(model_name_or_path, revision=revision)
    except Exception as e:
        raise ValueError(
            f"Cannot resolve model path {model_name_or_path!r}: {e}") from e


def _torch_tensor_to_numpy(t) -> np.ndarray:
    import torch

    if t.dtype == torch.bfloat16:
        import ml_dtypes
        return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


def hf_model_weights_iterator(
    model_name_or_path: str,
    load_format: str = "auto",
    revision: Optional[str] = None,
) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (param_name, numpy array) for every tensor in the checkpoint."""
    model_dir = _resolve_model_dir(model_name_or_path, revision)

    st_files: List[str] = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    bin_files: List[str] = sorted(glob.glob(os.path.join(model_dir, "*.bin")))
    # Exclude training-state files.
    bin_files = [f for f in bin_files if "training" not in os.path.basename(f)]

    use_safetensors = bool(st_files) and load_format in ("auto", "safetensors")
    if use_safetensors:
        from safetensors import safe_open
        for st_file in st_files:
            with safe_open(st_file, framework="np") as f:
                for name in f.keys():
                    try:
                        yield name, f.get_tensor(name)
                    except TypeError:
                        # numpy can't represent bf16 natively in some
                        # safetensors versions; go through torch.
                        from safetensors import torch as st_torch
                        tensors = st_torch.load_file(st_file)
                        yield name, _torch_tensor_to_numpy(tensors[name])
    elif bin_files:
        import torch
        for bin_file in bin_files:
            state = torch.load(bin_file, map_location="cpu", weights_only=True)
            for name, t in state.items():
                yield name, _torch_tensor_to_numpy(t)
            del state
    else:
        raise ValueError(
            f"No checkpoint files (*.safetensors / *.bin) found in {model_dir}")


def cast_array(arr: np.ndarray, dtype_str: str) -> "np.ndarray":
    import ml_dtypes

    target = {"bfloat16": ml_dtypes.bfloat16,
              "float32": np.float32,
              "float16": np.float16}[dtype_str]
    if arr.dtype == target:
        return arr
    return arr.astype(target)


def stack_experts(raw, prefix: str, held, names, dtype: str) -> dict:
    """The stacks "w1", "w2", "w3" [N, I, D] (layers/moe.py) of the experts
    `held` (`ExpertShare.held`) from a checkpoint's per-expert [out, in]
    matrices `{prefix}{j}.{name}.weight`, `names` their (gate, down, up):
    gate and up as they are, down transposed."""
    def stack(name, transposed=False):
        return np.stack([cast_array(w.T if transposed else w, dtype)
                         for w in (raw[f"{prefix}{j}.{name}.weight"]
                                   for j in held)])

    gate, down, up = names
    return {"w1": stack(gate), "w2": stack(down, True), "w3": stack(up)}


def load_linear(raw, prefix: str, dtype: str, quantization=None,
                fp_ok: bool = False):
    """Resolve one linear layer's weight from a checkpoint dict, handling
    fp and quantized (AWQ / GPTQ / SqueezeLLM) storage.

    Role parity: reference `layers/quantization/{awq,gptq,squeezellm}.py`
    create_weights/apply_weights pairs — here the conversion happens once
    at load: AWQ converts losslessly to the device int4 representation;
    GPTQ (incl. act-order g_idx) and SqueezeLLM dequantize to fp and
    requantize to per-channel int8; fp checkpoints follow `quantization`
    ("int8"/"awq" etc. → quantize; None → plain [in, out] cast).
    Returns either a plain array or a QuantizedWeight dict.
    """
    from intellillm_tpu.layers.quantization import (awq_to_int4,
                                                    gptq_dequantize,
                                                    gptq_to_int4,
                                                    quantize_int4,
                                                    quantize_int8,
                                                    squeezellm_dequantize)

    if prefix + ".weight" in raw:
        w = cast_array(raw[prefix + ".weight"].T, dtype)
        if quantization == "int8":
            return quantize_int8(w)
        if fp_ok:
            # AWQ/GPTQ/SqueezeLLM checkpoints intentionally keep some
            # linears (lm_head) full precision — serve them as-is.
            return w
        if quantization == "awq":
            return quantize_int4(w)
        if quantization in ("gptq", "squeezellm"):
            return quantize_int8(w)
        return w

    if prefix + ".qweight" not in raw:
        raise KeyError(f"No weight found for {prefix!r} "
                       "(.weight / .qweight missing)")
    if quantization == "awq":
        from intellillm_tpu.layers.quantization import awq_unpack
        if fp_ok:
            q, z, s = awq_unpack(raw[prefix + ".qweight"],
                                 raw[prefix + ".qzeros"],
                                 raw[prefix + ".scales"])
            g = s.shape[0]
            in_, out = q.shape
            w = ((q.astype(np.float32).reshape(g, in_ // g, out) -
                  z[:, None]) * s[:, None]).reshape(in_, out)
            return cast_array(w, dtype)
        return awq_to_int4(raw[prefix + ".qweight"],
                           raw[prefix + ".qzeros"],
                           raw[prefix + ".scales"])
    if quantization == "gptq":
        if fp_ok:
            w = gptq_dequantize(raw[prefix + ".qweight"],
                                raw[prefix + ".qzeros"],
                                raw[prefix + ".scales"],
                                raw.get(prefix + ".g_idx"))
            return cast_array(w, dtype)
        qw = gptq_to_int4(raw[prefix + ".qweight"],
                          raw[prefix + ".qzeros"],
                          raw[prefix + ".scales"],
                          raw.get(prefix + ".g_idx"))
        if qw is not None:
            return qw
        logger.warning(
            "GPTQ tensor %s has an irregular group layout; falling back "
            "to int8 requantization (lossy vs the checkpoint).", prefix)
        w = gptq_dequantize(raw[prefix + ".qweight"],
                            raw[prefix + ".qzeros"],
                            raw[prefix + ".scales"],
                            raw.get(prefix + ".g_idx"))
        return quantize_int8(w)
    if quantization == "squeezellm":
        if fp_ok:
            w = squeezellm_dequantize(raw[prefix + ".qweight"],
                                      raw[prefix + ".lookup_table"])
            return cast_array(w, dtype)
        # Lossless device format: packed codebook indices + the exact
        # per-channel [16, out] table, executed by the LUT dequant-matmul
        # (ops/pallas/quant_matmul.quant_matmul_int4_lut) — parity with
        # the reference's in-kernel LUT
        # (csrc/quantization/squeezellm/quant_cuda_kernel.cu).
        from intellillm_tpu.layers.quantization import squeezellm_to_q4lut
        qw = squeezellm_to_q4lut(raw[prefix + ".qweight"],
                                 raw[prefix + ".lookup_table"])
        if qw is not None:
            return qw
        logger.warning(
            "SqueezeLLM tensor %s has an odd input dim; falling back to "
            "int8 requantization (lossy vs the checkpoint).", prefix)
        w = squeezellm_dequantize(raw[prefix + ".qweight"],
                                  raw[prefix + ".lookup_table"])
        return quantize_int8(w)
    raise ValueError(
        f"{prefix!r} is stored quantized but quantization={quantization!r}")
