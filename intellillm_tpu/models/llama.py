"""Llama model family (Llama/Llama-2, Mistral, Yi, InternLM — any HF config
with the llama layer recipe: RMSNorm + rope + GQA + SwiGLU).

Role parity: reference `vllm/model_executor/models/llama.py` (LlamaMLP :53,
LlamaAttention :83, LlamaDecoderLayer :161, LlamaModel :223,
LlamaForCausalLM :271) and `mistral.py` (same recipe + sliding window).
TPU redesign: functional forward over an explicit param pytree; TP comes
from mesh sharding of the tree, not Megatron layer classes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.activation import get_act_fn
from intellillm_tpu.layers.attention import (AttentionMetadata, KVCache,
                                             PagedAttention)
from intellillm_tpu.layers.normalization import fused_add_rms_norm, rms_norm
from intellillm_tpu.layers.quantization import (is_quantized, qmatmul,
                                                quantize_int8_jax)
from intellillm_tpu.layers.rotary_embedding import get_rope
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                load_linear)

Params = Dict[str, Any]

# Quantization methods whose DUMMY weights use the int8 {"q","s"} device
# representation. Real checkpoints may resolve differently per tensor
# (AWQ/GPTQ → int4 {"q4","s4","z4"}, SqueezeLLM → exact-LUT
# {"q4lut","lut"}, irregular layouts → int8) — partition_specs therefore
# emits a union spec covering every representation.
_INT8_REPR_METHODS = ("int8", "gptq")


def _slice_lora(lora, layer_idx: int):
    """Per-layer view of the stacked adapter tensors ([L, S, ...] → [S, ...])."""
    if lora is None:
        return None
    return {
        "row_slots": lora["row_slots"],
        "a": {t: v[layer_idx] for t, v in lora["a"].items()},
        "b": {t: v[layer_idx] for t, v in lora["b"].items()},
    }


class LlamaForCausalLM:

    supports_lora = True
    supported_quantization = ("int8", "awq", "gptq", "squeezellm")

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = getattr(cfg, "num_key_value_heads",
                                    self.num_heads)
        self.hidden_size = cfg.hidden_size
        self.head_size = getattr(cfg, "head_dim", None) or (
            self.hidden_size // self.num_heads)
        self.rms_eps = getattr(cfg, "rms_norm_eps", 1e-6)
        self.act = get_act_fn(getattr(cfg, "hidden_act", "silu"))
        self.tie_word_embeddings = getattr(cfg, "tie_word_embeddings", False)
        self.quantization = model_config.quantization

        rope_theta = getattr(cfg, "rope_theta", 10000.0)
        rope_scaling = getattr(cfg, "rope_scaling", None)
        max_pos = getattr(cfg, "max_position_embeddings", 8192)
        self.rope = get_rope(self.head_size, self.head_size, max_pos,
                             rope_theta, is_neox_style=True,
                             rope_scaling=rope_scaling)
        self.attn = PagedAttention(
            num_heads=self.num_heads,
            head_size=self.head_size,
            scale=self.head_size**-0.5,
            num_kv_heads=self.num_kv_heads,
            # Mistral's (every layer's) window; None for the others.
            sliding_window=model_config.get_sliding_window(),
        )

    def __call__(
        self,
        params: Params,
        input_ids: jnp.ndarray,   # [B, L]
        positions: jnp.ndarray,   # [B, L]
        kv_caches: List[KVCache],
        attn_metadata: AttentionMetadata,
        lora=None,
    ) -> Tuple[jnp.ndarray, List[KVCache]]:
        if lora is not None and "vocab" in lora:
            from intellillm_tpu.lora.layers import lora_embed
            h = lora_embed(input_ids, params["embed_tokens"],
                           self.config.vocab_size, lora["vocab"],
                           lora["row_slots"])
        else:
            h = params["embed_tokens"][input_ids]
        residual = None
        new_caches: List[KVCache] = []
        for i in range(self.num_layers):
            lp = params["layers"][i]
            h, residual, cache = self._layer(lp, h, residual, kv_caches[i],
                                             attn_metadata, positions,
                                             lora=_slice_lora(lora, i))
            new_caches.append(cache)
        h, _ = fused_add_rms_norm(h, residual, params["norm"], self.rms_eps)
        return h, new_caches

    def _proj(self, h, lp, lora, target):
        """Base projection + multi-LoRA delta (reference
        `vllm/lora/layers.py:32-101` _apply_lora, bgmv role)."""
        out = qmatmul(h, lp[target])
        if lora is not None and target in lora["a"]:
            from intellillm_tpu.lora.layers import lora_delta
            out = out + lora_delta(h, lora["a"][target], lora["b"][target],
                                   lora["row_slots"])
        return out

    def tp_pad_paths(self):
        """(param path → dim) pairs that `shard_params` may zero-pad to a
        64*tp multiple when the vocab doesn't divide the TP degree
        (reference `vocab_parallel_embedding.py:39-111`). Padded embedding
        rows are never gathered (ids < vocab); padded logit columns are
        masked to -inf by the runner before sampling."""
        return {"['embed_tokens']": 0, "['lm_head']": 1,
                "['lm_head']['q']": 1, "['lm_head']['s']": 0}

    def lora_target_dims(self):
        """Target module name → (dim_in, dim_out), consumed by
        `lora.models.LoRAModelManager` to size the adapter stacks."""
        e = self.hidden_size
        hq = self.num_heads * self.head_size
        hkv = self.num_kv_heads * self.head_size
        inter = self.config.intermediate_size
        return {"q": (e, hq), "k": (e, hkv), "v": (e, hkv), "o": (hq, e),
                "gate": (e, inter), "up": (e, inter), "down": (inter, e)}

    def _layer(self, lp: Params, h, residual, kv_cache, attn_metadata,
               positions, lora=None):
        b, l, e = h.shape
        if residual is None:
            residual = h
            h = rms_norm(h, lp["input_norm"], self.rms_eps)
        else:
            h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                             self.rms_eps)
        q = self._proj(h, lp, lora, "q").reshape(b, l, self.num_heads,
                                                 self.head_size)
        k = self._proj(h, lp, lora, "k").reshape(b, l, self.num_kv_heads,
                                                 self.head_size)
        v = self._proj(h, lp, lora, "v").reshape(b, l, self.num_kv_heads,
                                                 self.head_size)
        q, k = self.rope(positions, q, k)
        attn_out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        h = self._proj(attn_out.reshape(b, l,
                                        self.num_heads * self.head_size),
                       lp, lora, "o")

        h, residual = fused_add_rms_norm(h, residual, lp["post_attn_norm"],
                                         self.rms_eps)
        gate = self._proj(h, lp, lora, "gate")
        up = self._proj(h, lp, lora, "up")
        h = self._proj(self.act(gate) * up, lp, lora, "down")
        return h, residual, kv_cache

    def compute_logits(self, params: Params, hidden: jnp.ndarray,
                       lora=None) -> jnp.ndarray:
        lm_head = params.get("lm_head")
        if lm_head is None:
            logits = hidden @ params["embed_tokens"].T
        else:
            logits = qmatmul(hidden, lm_head)
        if lora is not None and "vocab" in lora:
            from intellillm_tpu.lora.layers import lora_logits
            # Returns exactly vocab+extra columns, invalid extras -inf.
            logits = lora_logits(hidden, logits, self.config.vocab_size,
                                 lora["vocab"], lora["row_slots"])
        return logits

    # --- sharding --------------------------------------------------------

    def partition_specs(self):
        """PartitionSpec tree mirroring the param tree: the TP sharding that
        replaces the reference's Megatron column/row layer classes
        (`layers/linear.py:130,444`; vocab sharding
        `vocab_parallel_embedding.py:39`). Weights are stored [in, out]."""
        from jax.sharding import PartitionSpec as P

        def w(spec):
            """Quantized weights shard q on the same dims; per-out-channel
            tensors (int8 scale, int4 group scales/zeros, the SqueezeLLM
            codebook) shard only the out dim — group/codebook counts
            rarely divide the mesh. The spec is a UNION over every device
            representation the loader can produce (int8 {"q","s"}, int4
            {"q4","s4","z4","perm"}, LUT {"q4lut","lut"}): spec lookup is
            by tree path, so keys absent from the actual param dict are
            simply never consulted, while a per-quantization guess would
            silently replicate a mismatched repr (GPTQ loads int4 OR falls
            back to int8 depending on the checkpoint's group layout)."""
            if self.quantization is None:
                return spec
            return {"q": spec, "s": P(spec[1]),
                    "q4": spec, "s4": P(None, spec[1]),
                    "z4": P(None, spec[1]), "perm": P(),
                    "q4lut": spec, "lut": P(None, spec[1])}

        layer = {
            "input_norm": P(),
            "post_attn_norm": P(),
            "q": w(P(None, "model")),
            "k": w(P(None, "model")),
            "v": w(P(None, "model")),
            "o": w(P("model", None)),
            "gate": w(P(None, "model")),
            "up": w(P(None, "model")),
            "down": w(P("model", None)),
        }
        import copy as _copy
        # AWQ/GPTQ checkpoints keep lm_head full precision (only int8
        # quantizes it at load).
        head = (w(P(None, "model")) if self.quantization == "int8"
                else P(None, "model"))
        return {
            "embed_tokens": P("model", None),
            "norm": P(),
            "lm_head": head,
            "layers": [_copy.deepcopy(layer) for _ in range(self.num_layers)],
        }

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Random params generated on-device (dummy load format: the
        reference's weight_utils.py:287 initialize_dummy_weights — used for
        profiling and weight-free benchmarking)."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        cfg = self.config
        e = self.hidden_size
        v = cfg.vocab_size
        inter = cfg.intermediate_size
        hq = self.num_heads * self.head_size
        hkv = self.num_kv_heads * self.head_size
        key = jax.random.PRNGKey(seed)

        def rand(key, shape, scale=0.02, quantize=True):
            w = (jax.random.normal(key, shape, jnp.float32) *
                 scale).astype(dtype)
            if len(shape) != 2 or not quantize:
                return w
            if self.quantization == "squeezellm":
                # Dummy q4lut: random codebook indices + a uniform
                # per-channel table spanning the weight scale (real
                # checkpoints carry k-means centroids; dummy load only
                # needs the right shapes/dtypes for perf work).
                in_, out = shape
                kq, _ = jax.random.split(key)
                q4 = jax.random.randint(kq, (in_ // 2, out), 0, 256,
                                        jnp.int32).astype(jnp.uint8)
                lut = (jnp.arange(16, dtype=jnp.float32)[:, None] - 7.5
                       ) * (scale / 4) * jnp.ones((1, out), jnp.float32)
                return {"q4lut": q4, "lut": lut}
            if self.quantization in _INT8_REPR_METHODS:
                return quantize_int8_jax(w)
            if self.quantization == "awq":
                from intellillm_tpu.layers.quantization import quantize_int4
                qw = quantize_int4(np.asarray(w, np.float32))
                return {k: jnp.asarray(v) for k, v in qw.items()}
            return w

        keys = jax.random.split(key, self.num_layers + 3)
        layers = []
        for i in range(self.num_layers):
            lk = jax.random.split(keys[i], 7)
            layers.append({
                "input_norm": jnp.ones((e, ), dtype),
                "post_attn_norm": jnp.ones((e, ), dtype),
                "q": rand(lk[0], (e, hq)),
                "k": rand(lk[1], (e, hkv)),
                "v": rand(lk[2], (e, hkv)),
                "o": rand(lk[3], (hq, e)),
                "gate": rand(lk[4], (e, inter)),
                "up": rand(lk[5], (e, inter)),
                "down": rand(lk[6], (inter, e)),
            })
        # Embeddings stay unquantized (they're a gather, not a matmul).
        embed = (jax.random.normal(keys[-3], (v, e), jnp.float32) *
                 0.02).astype(dtype)
        return {
            "embed_tokens": embed,
            "norm": jnp.ones((e, ), dtype),
            "lm_head": rand(keys[-2], (e, v),
                            quantize=self.quantization == "int8"),
            "layers": layers,
        }

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        raw: Dict[str, np.ndarray] = {}
        for name, arr in hf_model_weights_iterator(model_name_or_path,
                                                   load_format, revision):
            if "rotary_emb.inv_freq" in name:
                continue
            raw[name] = arr
        self._postprocess_raw(raw)

        def L(prefix: str, fp_ok: bool = False):
            return load_linear(raw, prefix, self.dtype, self.quantization,
                               fp_ok=fp_ok)

        def V(key: str) -> np.ndarray:
            return cast_array(raw[key], self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "norm": V("model.norm.weight"),
            "lm_head": (L("lm_head", fp_ok=self.quantization != "int8")
                        if (("lm_head.weight" in raw
                             or "lm_head.qweight" in raw)
                            and not self.tie_word_embeddings) else None),
            "layers": [],
        }
        for i in range(self.num_layers):
            lp = f"model.layers.{i}."
            params["layers"].append({
                "input_norm": V(lp + "input_layernorm.weight"),
                "post_attn_norm": V(lp + "post_attention_layernorm.weight"),
                "q": L(lp + "self_attn.q_proj"),
                "k": L(lp + "self_attn.k_proj"),
                "v": L(lp + "self_attn.v_proj"),
                "o": L(lp + "self_attn.o_proj"),
                "gate": L(lp + "mlp.gate_proj"),
                "up": L(lp + "mlp.up_proj"),
                "down": L(lp + "mlp.down_proj"),
            })
        return params

    def _postprocess_raw(self, raw: Dict[str, np.ndarray]) -> None:
        """Hook for subclasses to normalize checkpoint tensors before the
        param tree is built (DeciLM kv-head degrouping)."""
        return None
