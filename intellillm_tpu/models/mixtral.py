"""Mixtral MoE family (mixtral-8x7b etc.).

Role parity: reference `vllm/model_executor/models/mixtral.py` (MixtralMoE
:57 routing through fused_moe :138) + `mixtral_quant.py`. Llama-style
attention (GQA + rope + RMSNorm) with a top-2 MoE feed-forward.
Expert weights stack to [num_experts, inner, hidden] for all of w1, w2, w3
(layers/moe.py says why), so expert parallelism is a mesh axis away (shard
dim 0 over "model" for EP, or the inner dim 1 for TP).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.attention import KVCache
from intellillm_tpu.layers.moe import (live_tokens, moe_ffn,
                                       random_gate_up_stack)
from intellillm_tpu.layers.normalization import fused_add_rms_norm, rms_norm
from intellillm_tpu.layers.quantization import qmatmul
from intellillm_tpu.logger import init_logger
from intellillm_tpu.models.llama import LlamaForCausalLM, Params
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator)

logger = init_logger(__name__)


class MixtralForCausalLM(LlamaForCausalLM):

    # int8 quantize-on-load, plus GPTQ/AWQ QuantMixtral checkpoints
    # (reference `mixtral_quant.py`): per-expert packed-int4 stacks
    # dequantized through the exact codes inside the MoE layer.
    supported_quantization = ("int8", "awq", "gptq")

    def __init__(self, model_config: ModelConfig) -> None:
        super().__init__(model_config)
        cfg = model_config.hf_config
        self.num_experts = cfg.num_local_experts
        self.top_k = cfg.num_experts_per_tok
        self.intermediate = cfg.intermediate_size

    def lora_target_dims(self):
        # Attention projections only: expert FFNs are not LoRA targets
        # (matching common Mixtral PEFT configs; MoE-expert LoRA would need
        # per-expert adapter stacks).
        dims = super().lora_target_dims()
        return {t: dims[t] for t in ("q", "k", "v", "o")}

    def _layer(self, lp, h, residual, kv_cache, attn_metadata, positions,
               lora=None):
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
        flat = h.reshape(b * l, e)
        moe_out = moe_ffn(flat, lp["gate_router"], lp["w1"], lp["w2"],
                          lp["w3"], self.top_k, mesh=attn_metadata.mesh,
                          live=live_tokens(attn_metadata.live_rows, l))
        return moe_out.reshape(b, l, e), residual, kv_cache

    def partition_specs(self):
        from jax.sharding import PartitionSpec as P
        specs = super().partition_specs()

        def ew(packed):
            """Expert-stacked weights: dim 0 = expert axis (EP candidate),
            the wide inner dim sharded over "model" for TP. Dense stacks
            are [N, I, D] for all three (layers/moe.py). Quantized stacks
            keep the checkpoint's packed [N, in/2, out]; `packed` is the
            spec of q4 there, the per-group tensors shard on the out dim
            only (union over reprs, same rationale as
            LlamaForCausalLM.partition_specs), and "dense" is for a stack
            that loaded dense after all (parallel/mesh.py)."""
            dense = P(None, "model", None)
            if self.quantization in ("awq", "gptq"):
                return {"q4": packed, "s4": P(None, None, packed[2]),
                        "z4": P(None, None, packed[2]), "inv": P(),
                        "dense": dense}
            return dense

        for layer in specs["layers"]:
            for k in ("gate", "up", "down"):
                layer.pop(k, None)
            layer["gate_router"] = P()
            layer["w1"] = ew(P(None, None, "model"))
            layer["w3"] = ew(P(None, None, "model"))
            layer["w2"] = ew(P(None, "model", None))
        return specs

    def init_random_params(self, seed: int = 0) -> Params:
        import jax
        params = super().init_random_params(seed)
        dtype = jnp.dtype(self.dtype)
        e, i, n = self.hidden_size, self.intermediate, self.num_experts
        key = jax.random.PRNGKey(seed + 1)

        def rand(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) *
                    0.02).astype(dtype)

        for li, layer in enumerate(params["layers"]):
            for k in ("gate", "up", "down"):
                layer.pop(k, None)
            lk = jax.random.split(jax.random.fold_in(key, li), 4)
            layer["gate_router"] = rand(lk[0], (e, n)).astype(jnp.float32)
            layer["w1"] = random_gate_up_stack(lk[1], n, e, i, dtype)
            layer["w2"] = rand(lk[2], (n, i, e))
            layer["w3"] = random_gate_up_stack(lk[3], n, e, i, dtype)
        return params

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        raw: Dict[str, np.ndarray] = {}
        for name, arr in hf_model_weights_iterator(model_name_or_path,
                                                   load_format, revision):
            if "rotary_emb.inv_freq" in name:
                continue
            raw[name] = arr

        from intellillm_tpu.layers.quantization import (awq_to_int4,
                                                        gptq_to_int4,
                                                        stack_expert_int4)
        from intellillm_tpu.models.weight_utils import load_linear

        def _expert_int4(prefix):
            """One expert linear → pack_int4 dict (or None: irregular)."""
            if self.quantization == "awq":
                return awq_to_int4(raw[prefix + ".qweight"],
                                   raw[prefix + ".qzeros"],
                                   raw[prefix + ".scales"])
            return gptq_to_int4(raw[prefix + ".qweight"],
                                raw[prefix + ".qzeros"],
                                raw[prefix + ".scales"],
                                raw.get(prefix + ".g_idx"))

        def E(moe_prefix, wname):
            """Stacked expert weights. fp checkpoints stack dense, all
            three [N, I, D] (layers/moe.py): w1 and w3 as the checkpoint
            has them ([out, in]), w2 transposed. GPTQ/AWQ QuantMixtral
            checkpoints (reference `mixtral_quant.py` — per-expert
            quantized linears) stack the packed int4 tensors [N, in/2,
            out], executed by the MoE layer's on-the-fly dequant. Irregular
            layouts fall back to dense fp (lossless, just bigger)."""
            keys = [f"{moe_prefix}experts.{j}.{wname}" for j in
                    range(self.num_experts)]
            out_in = wname != "w2"       # stored [out, in]: no transpose
            if (self.quantization in ("awq", "gptq")
                    and keys[0] + ".qweight" in raw):
                stacked = stack_expert_int4(
                    [_expert_int4(k) for k in keys])
                if stacked is not None:
                    return stacked
                logger.warning(
                    "QuantMixtral expert stack %s* has an irregular "
                    "layout; loading dequantized fp instead.", moe_prefix)
                dense = np.stack([
                    load_linear(raw, k, self.dtype, self.quantization,
                                fp_ok=True)
                    for k in keys])                       # [N, in, out]
                return dense.swapaxes(1, 2) if out_in else dense
            return np.stack(
                [cast_array(raw[k + ".weight"] if out_in
                            else raw[k + ".weight"].T, self.dtype)
                 for k in keys])

        def W(prefix):
            # Attention / head projections: same per-tensor resolution as
            # the llama loader (fp, int8-on-load, or packed AWQ/GPTQ).
            return load_linear(raw, prefix, self.dtype, self.quantization)

        def V(key):
            return cast_array(raw[key], self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "norm": V("model.norm.weight"),
            # lm_head stays fp in AWQ/GPTQ checkpoints (reference
            # mixtral_quant.py uses an unquantized ParallelLMHead).
            "lm_head": (load_linear(raw, "lm_head", self.dtype,
                                    self.quantization, fp_ok=True)
                        if ("lm_head.weight" in raw
                            or "lm_head.qweight" in raw) else None),
            "layers": [],
        }
        for i in range(self.num_layers):
            lp = f"model.layers.{i}."
            moe = lp + "block_sparse_moe."
            layer = {
                "input_norm": V(lp + "input_layernorm.weight"),
                "post_attn_norm": V(lp + "post_attention_layernorm.weight"),
                "q": W(lp + "self_attn.q_proj"),
                "k": W(lp + "self_attn.k_proj"),
                "v": W(lp + "self_attn.v_proj"),
                "o": W(lp + "self_attn.o_proj"),
                "gate_router": cast_array(raw[moe + "gate.weight"].T,
                                          "float32"),
                "w1": E(moe, "w1"),
                "w2": E(moe, "w2"),
                "w3": E(moe, "w3"),
            }
            params["layers"].append(layer)
        return params
