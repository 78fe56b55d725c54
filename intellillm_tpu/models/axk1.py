"""A.X-K1 (`model_type` "axk1"): latent attention + sigmoid-routed experts.

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json (519 B
parameters; the DeepSeek-V2/V3 block). Per layer (pre-norm residual, RMSNorm
as Llama):

- Attention (MLA). `c_q = RMSNorm(x W_qa)`; `[q_nope | q_rope]_h = c_q W_qb`;
  `[c_kv | k_rope] = x W_kva`, `c_kv <- RMSNorm(c_kv)`; RoPE on `q_rope` and
  the one shared `k_rope` (interleaved pairs, YaRN frequencies, cos/sin at
  ratio `yarn_mscale(f, mscale) / yarn_mscale(f, mscale_all_dim)`); the
  cache holds `[c_kv | k_rope]`. Scores are scaled by
  `(d_nope + d_rope)^-1/2 * m^2`, `m = yarn_mscale(f, mscale_all_dim)`.
  The program runs the absorbed form, and the whole sublayer (projections,
  norms, RoPE split, absorption, `W_o`) lives in `layers/mla_attention.py`
  `LatentAttentionBlock`, written once for the families that run this
  block: `W_kvb` is stored split per head as `w_uk [H, d_nope, r_kv]` and
  `w_uv [H, r_kv, d_v]` (`W_kvb,h = [W_UK,h | W_UV,h]`), `q~_h = q_nope,h
  W_UK,h^T` goes to the kernel with `q_rope`, and the attended latent comes
  back through `W_UV,h` and `W_o`.
- FFN. The first `first_k_dense_replace` layers: SwiGLU of width
  `intermediate_size`. The others: `sigmoid` router over ALL experts,
  `n_group` groups of which the best `topk_group` are kept, top-k inside
  them, weights normalised and scaled by `routed_scaling_factor`, plus a
  shared expert (`layers/moe.py` `Routing`).

Expert parallelism (`ep_size`, `ep_rank` in the config): with `ep_size` > 1
`n_routed_experts` is what THIS chip holds (`layers/moe.py` `ExpertShare`
says of which router, `Routing.held_start` what the layer leaves out). The
shared expert, attention and the dense layer are whole on every chip (a
latent cache has no head axis to divide).

tp > 1 is not supported for this model yet (`partition_specs` says so).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.activation import get_act_fn
from intellillm_tpu.layers.mla_attention import LatentAttentionBlock
from intellillm_tpu.layers.moe import (DENSE_LAYER, ExpertCounts, ExpertShare,
                                       Routing, random_gate_up_stack,
                                       routed_experts)
from intellillm_tpu.layers.normalization import fused_add_rms_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)

Params = Dict[str, object]


class AXK1ForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "A.X-K1 runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.hidden_size = cfg.hidden_size
        self.rms_eps = cfg.rms_norm_eps
        self.act = get_act_fn(cfg.hidden_act)
        self.first_dense = cfg.first_k_dense_replace
        self.moe_freq = cfg.moe_layer_freq
        self.moe_inter = cfg.moe_intermediate_size
        self.n_shared = cfg.n_shared_experts or 0
        self.top_k = cfg.num_experts_per_tok
        # The share: `n_routed_experts` held here of a router ep_size x wider.
        self.n_held = cfg.n_routed_experts
        self.share = ExpertShare.from_config(
            cfg, self.n_held, self.top_k,
            sum(self._is_moe_layer(i) for i in range(self.num_layers)))
        self.share.report_through(self)
        if self.share.n_router % cfg.n_group:
            raise ValueError(f"router width {self.share.n_router} does not "
                             f"divide into n_group {cfg.n_group}")
        self.routing = Routing(
            scoring=cfg.scoring_func,
            renormalize=bool(cfg.norm_topk_prob),
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=float(cfg.routed_scaling_factor),
            held_start=self.share.held_start)

        self.mla = LatentAttentionBlock(cfg, model_config.max_model_len)

    def _is_moe_layer(self, i: int) -> bool:
        return i >= self.first_dense and i % self.moe_freq == 0

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = params["embed_tokens"][input_ids]
        residual = None
        new_caches = []
        counts = ExpertCounts(self.share)
        for i in range(self.num_layers):
            h, residual, cache = self._layer(
                params["layers"][i], h, residual, kv_caches[i],
                attn_metadata, positions, counts)
            new_caches.append(cache)
        h, _ = fused_add_rms_norm(h, residual, params["norm"], self.rms_eps)
        return h, new_caches, counts.counters()

    def _layer(self, lp, h, residual, kv_cache, attn_metadata, positions,
               counts):
        h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                         self.rms_eps)
        h, kv_cache = self.mla(lp, h, kv_cache, attn_metadata, positions)
        h, residual = fused_add_rms_norm(h, residual, lp["post_attn_norm"],
                                         self.rms_eps)
        if "w1" not in lp:
            h = (self.act(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]
            counts.add(DENSE_LAYER)
            return h, residual, kv_cache
        h, layer_counts = routed_experts(
            h, lp, self.share, self.routing, mesh=attn_metadata.mesh,
            activation=self.config.hidden_act.lower(),
            live=attn_metadata.live_rows)
        counts.add(layer_counts)
        return h, residual, kv_cache

    def compute_logits(self, params: Params, hidden, lora=None):
        return hidden @ params["lm_head"]

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "A.X-K1 runs on one chip (tensor_parallel_size 1) in this "
            "engine: its latent cache has no head axis to shard, and its "
            "experts are placed by the config's ep_size / ep_rank (one "
            "chip's share of an expert-parallel deployment), not by the "
            "mesh")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e = self.hidden_size
        layers = []
        for i in range(self.num_layers):
            layer = {"input_norm": (e, ), "post_attn_norm": (e, ),
                     **self.mla.param_shapes(e)}
            if self._is_moe_layer(i):
                layer.update(self.share.param_shapes(
                    e, self.moe_inter,
                    shared_inter=self.moe_inter * self.n_shared))
            else:
                inter = cfg.intermediate_size
                layer.update({"gate": (e, inter), "up": (e, inter),
                              "down": (inter, e)})
            layers.append(layer)
        return {"embed_tokens": (cfg.vocab_size, e), "norm": (e, ),
                "lm_head": (e, cfg.vocab_size), "layers": layers}

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`. Every array is
        waited for as it is drawn (as `random_gate_up_stack` does): the
        host otherwise runs layers ahead of the device and the draws'
        float32 intermediates pile up beside ~10 GB of weights."""
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                return jax.block_until_ready(w.astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            # Fan-in scaling keeps activations, scores and logits O(1) at
            # any width, so a comparison with the reference means
            # something. The projections that write to the residual stream
            # are a tenth of that: a layer then adds a tenth of the stream's
            # magnitude, as where training has let the stream dominate. At
            # full scale seven random layers amplified bf16's multiplication
            # noise to 0.2 nats of log-softmax against the float32 reference
            # and 0.44 between two bf16 programs (PERF.md section 6, PR 26).
            scale = (1.0 if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            if name in ("o", "down", "w2", "shared_down"):
                scale *= 0.1
            w = jax.random.normal(k, shape, jnp.float32) * scale
            if name == "gate_router":
                return jax.block_until_ready(w)       # float32 router
            return jax.block_until_ready(w.astype(dtype))

        def build(tree):
            if isinstance(tree, dict):
                return {k: (build(v) if isinstance(v, (dict, list))
                            else draw(v, k)) for k, v in tree.items()}
            return [build(v) for v in tree]

        return build(self._param_shapes())

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        """Checkpoint names as the DeepSeek-V3 family publishes them
        (`self_attn.{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
        kv_a_layernorm, kv_b_proj, o_proj}`, `mlp.gate`, `mlp.experts.N`,
        `mlp.shared_experts`). A share loads its own experts' slices."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key):
            return cast_array(raw[key], self.dtype)

        vocab = self.config.vocab_size
        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight")[:vocab],
            "norm": V("model.norm.weight"),
            "lm_head": T("lm_head.weight")[:, :vocab],
            "layers": [],
        }
        held = self.share.held
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            layer = {
                "input_norm": V(p + "input_layernorm.weight"),
                "post_attn_norm": V(p + "post_attention_layernorm.weight"),
                **self.mla.load(p + "self_attn.", T, V),
            }
            m = p + "mlp."
            if self._is_moe_layer(i):
                layer["gate_router"] = cast_array(raw[m + "gate.weight"].T,
                                                  "float32")
                layer.update(stack_experts(
                    raw, m + "experts.", held,
                    ("gate_proj", "down_proj", "up_proj"), self.dtype))
                if self.n_shared:
                    for x in ("gate", "up", "down"):
                        layer["shared_" + x] = T(
                            f"{m}shared_experts.{x}_proj.weight")
            else:
                layer["gate"] = T(m + "gate_proj.weight")
                layer["up"] = T(m + "up_proj.weight")
                layer["down"] = T(m + "down_proj.weight")
            params["layers"].append(layer)
        return params
