"""Rotary positional embeddings with scaling variants.

Role parity: reference `vllm/model_executor/layers/rotary_embedding.py`
(RotaryEmbedding :30, LinearScaling :151, DynamicNTKScaling :187,
YaRNScaling :268, factory get_rope :332) + the CUDA apply kernel
(`csrc/pos_encoding_kernels.cu`, neox & gptj styles). On TPU the apply is
plain jnp on a precomputed cos/sin table — XLA fuses it into the
surrounding matmuls; no custom kernel needed.

Tables are precomputed once per (head_size, max_len, base, scaling) in
float32 and gathered by position ids at call time.
"""
from __future__ import annotations

import copy
import math
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class RotaryEmbedding:
    """Rotary embedding (neox style: rotate halves; gptj: interleaved).

    Subclasses override `_compute_freqs` (and optionally `_mscale`) to
    implement the scaling variants; the table build and apply are shared.
    """

    def __init__(
        self,
        head_size: int,
        rotary_dim: int,
        max_position_embeddings: int,
        base: float,
        is_neox_style: bool = True,
    ) -> None:
        self.head_size = head_size
        self.rotary_dim = rotary_dim
        self.max_position_embeddings = max_position_embeddings
        self.base = base
        self.is_neox_style = is_neox_style

        freqs = self._compute_freqs()  # [table_len, rotary_dim // 2]
        mscale = self._mscale()
        self.cos_cache = jnp.asarray(
            (np.cos(freqs) * mscale).astype(np.float32))
        self.sin_cache = jnp.asarray(
            (np.sin(freqs) * mscale).astype(np.float32))

    def trimmed_to(self, limit) -> "RotaryEmbedding":
        """This embedding with the rows of its tables a position under
        `limit` (a model's `max_model_len`) can reach: every compiled
        program holds the tables as constants. A copy where rows go
        (`get_rope` shares instances), itself where none do."""
        if not limit or self.cos_cache.shape[0] <= limit:
            return self
        trimmed = copy.copy(self)
        trimmed.cos_cache = self.cos_cache[:limit]
        trimmed.sin_cache = self.sin_cache[:limit]
        return trimmed

    def _compute_inv_freq(self, base: float) -> np.ndarray:
        return 1.0 / (base**(np.arange(0, self.rotary_dim, 2,
                                       dtype=np.float64) / self.rotary_dim))

    def _compute_freqs(self) -> np.ndarray:
        inv_freq = self._compute_inv_freq(self.base)
        t = np.arange(self.max_position_embeddings, dtype=np.float64)
        return np.einsum("i,j->ij", t, inv_freq)

    def _mscale(self) -> float:
        return 1.0

    def __call__(
        self,
        positions: jnp.ndarray,  # [B, L] int32
        query: jnp.ndarray,      # [B, L, Hq, head_size]
        key: jnp.ndarray,        # [B, L, Hkv, head_size]
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cos = self.cos_cache[positions][:, :, None, :]  # [B, L, 1, rd/2]
        sin = self.sin_cache[positions][:, :, None, :]

        def rotate(x: jnp.ndarray) -> jnp.ndarray:
            rot = x[..., :self.rotary_dim]
            rest = x[..., self.rotary_dim:]
            if self.is_neox_style:
                x1 = rot[..., :self.rotary_dim // 2]
                x2 = rot[..., self.rotary_dim // 2:]
                o1 = x1 * cos - x2 * sin
                o2 = x2 * cos + x1 * sin
                rotated = jnp.concatenate([o1, o2], axis=-1)
            else:
                x1 = rot[..., 0::2]
                x2 = rot[..., 1::2]
                o1 = x1 * cos - x2 * sin
                o2 = x2 * cos + x1 * sin
                rotated = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
            if rest.shape[-1] == 0:
                return rotated.astype(x.dtype)
            return jnp.concatenate([rotated, rest], axis=-1).astype(x.dtype)

        return rotate(query), rotate(key)


class LinearScalingRotaryEmbedding(RotaryEmbedding):
    """Position ids divided by a constant factor (reference :151)."""

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float) -> None:
        self.scaling_factor = scaling_factor
        super().__init__(head_size, rotary_dim, max_position_embeddings, base,
                         is_neox_style)

    def _compute_freqs(self) -> np.ndarray:
        inv_freq = self._compute_inv_freq(self.base)
        max_len = int(self.max_position_embeddings * self.scaling_factor)
        t = np.arange(max_len, dtype=np.float64) / self.scaling_factor
        return np.einsum("i,j->ij", t, inv_freq)


class DynamicNTKScalingRotaryEmbedding(RotaryEmbedding):
    """NTK-aware base rescaling for extended contexts (reference :187)."""

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float) -> None:
        self.scaling_factor = scaling_factor
        super().__init__(head_size, rotary_dim, max_position_embeddings, base,
                         is_neox_style)

    def _compute_freqs(self) -> np.ndarray:
        max_len = int(self.max_position_embeddings * self.scaling_factor)
        adj_base = self.base * (
            (self.scaling_factor * max_len / self.max_position_embeddings) -
            (self.scaling_factor - 1))**(self.rotary_dim /
                                         (self.rotary_dim - 2))
        inv_freq = self._compute_inv_freq(adj_base)
        t = np.arange(max_len, dtype=np.float64)
        return np.einsum("i,j->ij", t, inv_freq)


def _yarn_find_correction_dim(num_rotations, dim, base, max_pos) -> float:
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))


def _yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    low = math.floor(_yarn_find_correction_dim(low_rot, dim, base, max_pos))
    high = math.ceil(_yarn_find_correction_dim(high_rot, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def _yarn_linear_ramp(low: float, high: float, dim: int) -> np.ndarray:
    if low == high:
        high += 0.001
    ramp = (np.arange(dim, dtype=np.float32) - low) / (high - low)
    return np.clip(ramp, 0, 1)


def _yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


class YaRNScalingRotaryEmbedding(RotaryEmbedding):
    """YaRN context extension (reference :268; arXiv 2309.00071)."""

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float,
                 extrapolation_factor: float = 1.0,
                 attn_factor: float = 1.0,
                 beta_fast: int = 32,
                 beta_slow: int = 1,
                 mscale: Optional[float] = None,
                 mscale_all_dim: Optional[float] = None) -> None:
        # `mscale` / `mscale_all_dim` (the DeepSeek-V2 family's rule, which
        # A.X-K1's config follows): cos/sin are scaled by the RATIO
        # yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
        # and the model puts yarn_mscale(factor, mscale_all_dim)**2 on the
        # whole attention score. Absent (Llama-style YaRN), cos/sin carry
        # yarn_mscale(factor) themselves.
        self.mscale = mscale
        self.mscale_all_dim = mscale_all_dim
        self.scaling_factor = scaling_factor
        self.extrapolation_factor = extrapolation_factor
        self.attn_factor = attn_factor
        self.beta_fast = beta_fast
        self.beta_slow = beta_slow
        super().__init__(head_size, rotary_dim, max_position_embeddings, base,
                         is_neox_style)

    def _mscale(self) -> float:
        if self.mscale is not None or self.mscale_all_dim is not None:
            return (_yarn_get_mscale(self.scaling_factor, self.mscale or 0.0)
                    / _yarn_get_mscale(self.scaling_factor,
                                       self.mscale_all_dim or 0.0)
                    * self.attn_factor)
        return _yarn_get_mscale(self.scaling_factor) * self.attn_factor

    def _compute_freqs(self) -> np.ndarray:
        pos_freqs = self.base**(np.arange(0, self.rotary_dim, 2,
                                          dtype=np.float64) / self.rotary_dim)
        inv_freq_extrapolation = 1.0 / pos_freqs
        inv_freq_interpolation = 1.0 / (self.scaling_factor * pos_freqs)
        low, high = _yarn_find_correction_range(
            self.beta_fast, self.beta_slow, self.rotary_dim, self.base,
            self.max_position_embeddings)
        inv_freq_mask = (1 - _yarn_linear_ramp(
            low, high, self.rotary_dim // 2)) * self.extrapolation_factor
        inv_freq = (inv_freq_interpolation * (1 - inv_freq_mask) +
                    inv_freq_extrapolation * inv_freq_mask)
        max_len = int(self.max_position_embeddings * self.scaling_factor)
        t = np.arange(max_len, dtype=np.float64)
        return np.einsum("i,j->ij", t, inv_freq)


_ROPE_CACHE: Dict[Any, RotaryEmbedding] = {}


def get_rope(
    head_size: int,
    rotary_dim: int,
    max_position: int,
    base: float,
    is_neox_style: bool = True,
    rope_scaling: Optional[Dict[str, Any]] = None,
) -> RotaryEmbedding:
    """Factory + cache (reference rotary_embedding.py:332-378)."""
    key = (head_size, rotary_dim, max_position, base, is_neox_style,
           tuple(sorted(rope_scaling.items())) if rope_scaling else None)
    if key in _ROPE_CACHE:
        return _ROPE_CACHE[key]

    if rope_scaling is None:
        rope = RotaryEmbedding(head_size, rotary_dim, max_position, base,
                               is_neox_style)
    else:
        scaling_type = rope_scaling.get("type",
                                        rope_scaling.get("rope_type"))
        factor = rope_scaling.get("factor", 1.0)
        if scaling_type == "linear":
            rope = LinearScalingRotaryEmbedding(head_size, rotary_dim,
                                                max_position, base,
                                                is_neox_style, factor)
        elif scaling_type == "dynamic":
            rope = DynamicNTKScalingRotaryEmbedding(head_size, rotary_dim,
                                                    max_position, base,
                                                    is_neox_style, factor)
        elif scaling_type == "yarn":
            original_max = rope_scaling.get(
                "original_max_position_embeddings", max_position)
            extra = {
                k: v
                for k, v in rope_scaling.items()
                if k in ("extrapolation_factor", "attn_factor", "beta_fast",
                         "beta_slow", "mscale", "mscale_all_dim")
            }
            rope = YaRNScalingRotaryEmbedding(head_size, rotary_dim,
                                              original_max, base,
                                              is_neox_style, factor, **extra)
        else:
            raise ValueError(f"Unknown RoPE scaling type {scaling_type}")
    _ROPE_CACHE[key] = rope
    return rope
