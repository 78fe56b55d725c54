"""Deepseek v1 MoE family (deepseek-moe-16b, deepseek-llm via llama).

Role parity: reference `vllm/model_executor/models/deepseek.py`. Llama
attention; the FFN is MoE on every layer except the first
`first_k_dense_replace` and layers where `moe_layer_freq` skips it. MoE
specifics vs Mixtral: top-k weights are NOT renormalized
(`norm_topk_prob=False`) and `n_shared_experts` always-on shared experts
(a dense SwiGLU of width n_shared·moe_intermediate_size) add to the
routed output.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.moe import (Routing, live_tokens, moe_ffn,
                                       random_gate_up_stack)
from intellillm_tpu.layers.normalization import fused_add_rms_norm, rms_norm
from intellillm_tpu.models.llama import LlamaForCausalLM, Params
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator)


class DeepseekForCausalLM(LlamaForCausalLM):

    supports_lora = False
    supported_quantization = ("int8", )

    def __init__(self, model_config: ModelConfig) -> None:
        super().__init__(model_config)
        cfg = model_config.hf_config
        self.n_routed = cfg.n_routed_experts
        self.n_shared = getattr(cfg, "n_shared_experts", 0) or 0
        self.top_k = cfg.num_experts_per_tok
        self.moe_inter = cfg.moe_intermediate_size
        self.routing = Routing(
            renormalize=bool(getattr(cfg, "norm_topk_prob", False)))
        self.first_dense = getattr(cfg, "first_k_dense_replace", 0)
        self.moe_freq = getattr(cfg, "moe_layer_freq", 1)

    def _is_moe_layer(self, i: int) -> bool:
        return i >= self.first_dense and i % self.moe_freq == 0

    def _layer(self, lp, h, residual, kv_cache, attn_metadata, positions,
               lora=None):
        if "w1" not in lp:
            return super()._layer(lp, h, residual, kv_cache, attn_metadata,
                                  positions)
        b, l, e = h.shape
        if residual is None:
            residual = h
            h = rms_norm(h, lp["input_norm"], self.rms_eps)
        else:
            h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                             self.rms_eps)
        from intellillm_tpu.layers.quantization import qmatmul
        q = qmatmul(h, lp["q"]).reshape(b, l, self.num_heads, self.head_size)
        k = qmatmul(h, lp["k"]).reshape(b, l, self.num_kv_heads,
                                        self.head_size)
        v = qmatmul(h, lp["v"]).reshape(b, l, self.num_kv_heads,
                                        self.head_size)
        q, k = self.rope(positions, q, k)
        attn_out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        h = qmatmul(attn_out.reshape(b, l, self.num_heads * self.head_size),
                    lp["o"])

        h, residual = fused_add_rms_norm(h, residual, lp["post_attn_norm"],
                                         self.rms_eps)
        flat = h.reshape(b * l, e)
        out = moe_ffn(flat, lp["gate_router"], lp["w1"], lp["w2"], lp["w3"],
                      self.top_k, routing=self.routing,
                      mesh=attn_metadata.mesh,
                      live=live_tokens(attn_metadata.live_rows, l))
        if self.n_shared:
            with jax.named_scope("moe_shared"):
                gate = flat @ lp["shared_gate"]
                up = flat @ lp["shared_up"]
                out = out + (self.act(gate) * up) @ lp["shared_down"]
        return out.reshape(b, l, e), residual, kv_cache

    def partition_specs(self):
        from jax.sharding import PartitionSpec as P
        specs = super().partition_specs()
        for i, layer in enumerate(specs["layers"]):
            if not self._is_moe_layer(i):
                continue
            for k in ("gate", "up", "down"):
                layer.pop(k, None)
            layer["gate_router"] = P()
            # All three stacks are [N, I, D] (layers/moe.py): the sharded
            # inner dim is axis 1.
            layer["w1"] = P(None, "model", None)
            layer["w3"] = P(None, "model", None)
            layer["w2"] = P(None, "model", None)
            layer["shared_gate"] = P(None, "model")
            layer["shared_up"] = P(None, "model")
            layer["shared_down"] = P("model", None)
        return specs

    def init_random_params(self, seed: int = 0) -> Params:
        import jax
        params = super().init_random_params(seed)
        dtype = jnp.dtype(self.dtype)
        e = self.hidden_size
        mi, n = self.moe_inter, self.n_routed
        key = jax.random.PRNGKey(seed + 7)

        def rand(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) *
                    0.02).astype(dtype)

        for i, layer in enumerate(params["layers"]):
            if not self._is_moe_layer(i):
                continue
            for k in ("gate", "up", "down"):
                layer.pop(k, None)
            lk = jax.random.split(jax.random.fold_in(key, i), 7)
            layer["gate_router"] = rand(lk[0], (e, n)).astype(jnp.float32)
            layer["w1"] = random_gate_up_stack(lk[1], n, e, mi, dtype)
            layer["w2"] = rand(lk[2], (n, mi, e))
            layer["w3"] = random_gate_up_stack(lk[3], n, e, mi, dtype)
            si = mi * self.n_shared
            layer["shared_gate"] = rand(lk[4], (e, si))
            layer["shared_up"] = rand(lk[5], (e, si))
            layer["shared_down"] = rand(lk[6], (si, e))
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

        from intellillm_tpu.layers.quantization import quantize_int8

        def E(key):
            # Expert/shared-expert weights stay full precision, matching
            # the fp partition specs in partition_specs above.
            return cast_array(raw[key].T, self.dtype)

        def W(key):
            w = cast_array(raw[key].T, self.dtype)
            if self.quantization == "int8":
                return quantize_int8(w)
            return w

        def V(key):
            return cast_array(raw[key], self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "norm": V("model.norm.weight"),
            "lm_head": W("lm_head.weight"),
            "layers": [],
        }
        n = self.n_routed
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            layer = {
                "input_norm": V(p + "input_layernorm.weight"),
                "post_attn_norm": V(p + "post_attention_layernorm.weight"),
                "q": W(p + "self_attn.q_proj.weight"),
                "k": W(p + "self_attn.k_proj.weight"),
                "v": W(p + "self_attn.v_proj.weight"),
                "o": W(p + "self_attn.o_proj.weight"),
            }
            if self._is_moe_layer(i):
                m = p + "mlp."
                layer["gate_router"] = cast_array(
                    raw[m + "gate.weight"].T, "float32")
                # Stacks are [N, I, D] (layers/moe.py): gate_proj and
                # up_proj as the checkpoint has them ([out, in]), down_proj
                # transposed.
                layer["w1"] = np.stack(
                    [V(f"{m}experts.{j}.gate_proj.weight")
                     for j in range(n)])
                layer["w2"] = np.stack(
                    [E(f"{m}experts.{j}.down_proj.weight")
                     for j in range(n)])
                layer["w3"] = np.stack(
                    [V(f"{m}experts.{j}.up_proj.weight")
                     for j in range(n)])
                if self.n_shared:
                    layer["shared_gate"] = E(
                        m + "shared_experts.gate_proj.weight")
                    layer["shared_up"] = E(
                        m + "shared_experts.up_proj.weight")
                    layer["shared_down"] = E(
                        m + "shared_experts.down_proj.weight")
            else:
                layer["gate"] = W(p + "mlp.gate_proj.weight")
                layer["up"] = W(p + "mlp.up_proj.weight")
                layer["down"] = W(p + "mlp.down_proj.weight")
            params["layers"].append(layer)
        return params
