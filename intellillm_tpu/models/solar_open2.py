"""Solar Open 2 (`model_type` "solar_open2"): delta-rule linear-attention
layers with a gated softmax GQA layer at the head of every period,
sigmoid-routed experts + a shared expert in every layer.

Source: https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
(250 B parameters, 15 B active). One layer, `h` the residual stream,
RMSNorm (`rms_norm_eps`) throughout, pre-norm:

- `h += mixer(input_norm(h))`. A layer outside `gqa_layers`: Kimi Delta
  Attention (`layers/linear_attention.py`: per-sequence state, no K/V). A
  layer in `gqa_layers`: `q, k, v` without bias and WITHOUT position
  encoding (`use_rope` false), causal GQA at scale `head_dim ** -0.5`,
  then `W_o [attn * sigmoid(x W_gate)]` (`use_gqa_gate`, a gate an
  element).
- `h += moe(post_norm(h))`: `s = sigmoid(f32(x) W_r)` over all
  `n_routed_experts`; the `num_experts_per_tok` experts are the top-k of
  `s + bias`; their weights are `s` at the chosen (without the bias)
  divided by their sum, times `routed_scaling_factor` (`layers/moe.py`
  `Routing`); plus one shared SwiGLU expert of the same inner width.
- final RMSNorm; an untied head.

Expert parallelism (`ep_size`, `ep_rank` in the config, as
`models/axk1.py`): with `ep_size` > 1 `n_routed_experts` is what THIS chip
holds (`layers/moe.py` `ExpertShare`). The shared expert and both mixers
are whole on every chip.

A layer's cache in `kv_caches[i]` is what `ModelConfig.
get_layer_cache_kinds` says: a (K, V) pool pair for a GQA layer, the (taps,
matrices) pools of `ModelConfig.get_state_arrays` for a KDA layer.

tp > 1 is not supported for this model yet (`partition_specs` says so).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.attention import PagedAttention
from intellillm_tpu.layers.linear_attention import kda_mixer
from intellillm_tpu.layers.moe import (ExpertCounts, ExpertShare, Routing,
                                       random_gate_up_stack, routed_experts)
from intellillm_tpu.layers.normalization import fused_add_rms_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)

Params = Dict[str, object]


class SolarOpen2ForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "Solar Open 2 runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.hidden_size = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        linear = cfg.linear_attn_config
        self.kda_heads = linear["num_heads"]
        self.kda_dim = linear["head_dim"]
        if linear.get("num_kv_heads") not in (None, self.kda_heads):
            raise NotImplementedError(
                "linear_attn_config.num_kv_heads: the delta rule here has "
                "as many key and value heads as query heads")
        if linear["short_conv_kernel_size"] != 4:
            raise NotImplementedError(
                f"short_conv_kernel_size "
                f"{linear['short_conv_kernel_size']}: the filter's body "
                f"(ops/short_conv.py `conv_silu_body`) is written for 4 "
                f"taps")
        if cfg.use_rope or not cfg.kda_allow_neg_eigval \
                or cfg.kda_use_full_proj or cfg.first_k_dense_replace:
            raise NotImplementedError(
                "only the published setting is served: use_rope false, "
                "kda_allow_neg_eigval true, kda_use_full_proj false, "
                "first_k_dense_replace 0")
        self.gqa_gate = bool(cfg.use_gqa_gate)
        self.moe_inter = cfg.moe_intermediate_size
        self.n_shared = cfg.n_shared_experts or 0
        self.top_k = cfg.num_experts_per_tok
        # The share: `n_routed_experts` held here of a router ep_size x wider.
        self.share = ExpertShare.from_config(
            cfg, cfg.n_routed_experts, self.top_k, self.num_layers)
        self.share.report_through(self)
        self.routing = Routing(
            scoring="sigmoid", renormalize=bool(cfg.norm_topk_prob),
            scale=float(cfg.routed_scaling_factor), select_bias=True,
            held_start=self.share.held_start)
        self.attn = PagedAttention(self.num_heads, self.head_dim,
                                   self.head_dim**-0.5, self.num_kv_heads)

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = params["embed_tokens"][input_ids]
        residual = None
        new_caches = []
        counts = ExpertCounts(self.share)
        for i in range(self.num_layers):
            h, residual, cache = self._layer(
                params["layers"][i], self.layer_kinds[i], h, residual,
                kv_caches[i], attn_metadata, positions, counts)
            new_caches.append(cache)
        h, _ = fused_add_rms_norm(h, residual, params["norm"], self.eps)
        return h, new_caches, counts.counters()

    def _attention(self, lp, h, kv_cache, attn_metadata):
        b, l, _ = h.shape
        d = self.head_dim
        q = (h @ lp["q"]).reshape(b, l, self.num_heads, d)
        k = (h @ lp["k"]).reshape(b, l, self.num_kv_heads, d)
        v = (h @ lp["v"]).reshape(b, l, self.num_kv_heads, d)
        out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        out = out.reshape(b, l, self.num_heads * d)
        if self.gqa_gate:
            gate = jax.nn.sigmoid((h @ lp["gate"]).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate).astype(h.dtype)
        return out @ lp["o"], kv_cache

    def _layer(self, lp, kind, h, residual, cache, attn_metadata,
               positions, counts):
        h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                         self.eps)
        if kind == "state":
            with jax.named_scope("kda_mixer"):
                h, cache = kda_mixer(h, lp, cache, attn_metadata, positions,
                                     num_heads=self.kda_heads,
                                     head_dim=self.kda_dim, eps=self.eps)
        else:
            h, cache = self._attention(lp, h, cache, attn_metadata)
        h, residual = fused_add_rms_norm(h, residual, lp["post_norm"],
                                         self.eps)
        h, layer_counts = routed_experts(h, lp, self.share, self.routing,
                                         mesh=attn_metadata.mesh,
                                         live=attn_metadata.live_rows)
        counts.add(layer_counts)
        return h, residual, cache

    def compute_logits(self, params: Params, hidden, lora=None):
        # Logits leave the matmul in float32 (models/lfm2_moe.py says why).
        return jnp.matmul(hidden, params["lm_head"],
                          preferred_element_type=jnp.float32)

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "Solar Open 2 runs on one chip (tensor_parallel_size 1) in "
            "this engine: its linear-attention layers' state pools (a "
            "matrix a head) and fused q/k/v filter have no sharding rule "
            "over heads yet, and its experts are placed by the config's "
            "ep_size / ep_rank (one chip's share of an expert-parallel "
            "deployment), not by the mesh")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e, d = self.hidden_size, self.head_dim
        hk, dk = self.kda_heads, self.kda_dim
        layers = []
        for i in range(self.num_layers):
            layer = {"input_norm": (e, ), "post_norm": (e, )}
            if self.layer_kinds[i] == "state":
                layer.update({
                    "qkv": (e, 3 * hk * dk), "conv_w": (4, 3 * hk * dk),
                    "f_a": (e, dk), "f_b": (dk, hk * dk),
                    "dt_bias": (hk * dk, ), "A_log": (hk, ),
                    "b_proj": (e, hk),
                    "g_a": (e, dk), "g_b": (dk, hk * dk),
                    "o_norm": (dk, ), "o": (hk * dk, e)})
            else:
                layer.update({
                    "q": (e, self.num_heads * d),
                    "k": (e, self.num_kv_heads * d),
                    "v": (e, self.num_kv_heads * d),
                    "o": (self.num_heads * d, e)})
                if self.gqa_gate:
                    layer["gate"] = (e, self.num_heads * d)
            layer.update(self.share.param_shapes(
                e, self.moe_inter, select_bias=True,
                shared_inter=self.moe_inter * self.n_shared))
            layers.append(layer)
        return {"embed_tokens": (cfg.vocab_size, e), "norm": (e, ),
                "lm_head": (e, cfg.vocab_size), "layers": layers}

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`; every array is
        waited for as it is drawn (models/axk1.py says why)."""
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]
        f32 = jnp.float32

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, f32)
                return jax.block_until_ready(w.astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            if name == "expert_bias":
                # As large as the gaps between the best few of 320 scores
                # (a sigmoid of an O(1) logit: the top eight lie within a
                # few hundredths of each other), so that the choice by
                # score + bias and the choice by score alone differ for
                # most tokens, and small beside the scores' spread, so
                # that the choices stay spread over the router's whole
                # width as a balanced router's are: a bias as wide as the
                # scores sends every token to the same eight experts.
                return jax.block_until_ready(
                    0.03 * jax.random.normal(k, shape, f32))
            if name == "A_log":
                # exp(A_log) in U(1, 16), as the family's layers start.
                return jax.block_until_ready(jnp.log(
                    jax.random.uniform(k, shape, f32, 1.0, 16.0)))
            if name == "dt_bias":
                # softplus^-1 of a step dt, log-uniform in [1e-3, 0.1].
                dt = jnp.exp(jax.random.uniform(
                    k, shape, f32, math.log(1e-3), math.log(0.1)))
                return jax.block_until_ready(dt + jnp.log(-jnp.expm1(-dt)))
            if name == "conv_w":
                # Four taps of comparable weight: a tap left out shows.
                w = jax.random.normal(k, shape, f32) / 2.0
                return jax.block_until_ready(w.astype(dtype))
            # Fan-in scaling keeps activations O(1); what writes to the
            # residual stream adds a tenth of the stream's magnitude
            # (models/axk1.py, PERF.md section 6, PR 26).
            scale = (1.0 if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            if name in ("o", "w2", "shared_down"):
                scale *= 0.1
            if name in ("q", "k"):
                # Scores four times as wide as fan-in scaling gives: a
                # softmax over a few tokens, as trained heads have
                # (models/lfm2_moe.py).
                scale *= 2.0
            w = jax.random.normal(k, shape, f32) * scale
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
        """Checkpoint names as assumed from the family's earlier release
        and Kimi Linear's layout (the configuration file lists them under
        `assumed`): `model.layers.N.{input_layernorm,
        post_attention_layernorm}`; a GQA layer's `self_attn.{q_proj,
        k_proj, v_proj, g_proj, o_proj}`; a KDA layer's `self_attn.{q_proj,
        k_proj, v_proj, q_conv1d, k_conv1d, v_conv1d, f_a_proj, f_b_proj,
        dt_bias, A_log, b_proj, g_a_proj, g_b_proj, o_norm, o_proj}`;
        `mlp.{gate, gate.e_score_correction_bias, experts.J.{gate_proj,
        up_proj, down_proj}, shared_experts}`. A share loads its own
        experts' slices and its slice of the vocabulary."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key, dtype=None):
            return cast_array(raw[key], dtype or self.dtype)

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
            a = p + "self_attn."
            layer = {"input_norm": V(p + "input_layernorm.weight"),
                     "post_norm": V(p + "post_attention_layernorm.weight"),
                     "o": T(a + "o_proj.weight")}
            if self.layer_kinds[i] == "state":
                layer["qkv"] = np.concatenate(
                    [T(a + f"{x}_proj.weight") for x in "qkv"], axis=1)
                # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                layer["conv_w"] = np.concatenate(
                    [cast_array(raw[a + f"{x}_conv1d.weight"][:, 0, :].T,
                                self.dtype) for x in "qkv"], axis=1)
                layer.update({
                    "f_a": T(a + "f_a_proj.weight"),
                    "f_b": T(a + "f_b_proj.weight"),
                    "dt_bias": V(a + "dt_bias", "float32"),
                    "A_log": V(a + "A_log", "float32").reshape(-1),
                    "b_proj": T(a + "b_proj.weight"),
                    "g_a": T(a + "g_a_proj.weight"),
                    "g_b": T(a + "g_b_proj.weight"),
                    "o_norm": V(a + "o_norm.weight")})
            else:
                layer.update({"q": T(a + "q_proj.weight"),
                              "k": T(a + "k_proj.weight"),
                              "v": T(a + "v_proj.weight")})
                if self.gqa_gate:
                    layer["gate"] = T(a + "g_proj.weight")
            m = p + "mlp."
            layer["gate_router"] = cast_array(raw[m + "gate.weight"].T,
                                              "float32")
            layer["expert_bias"] = cast_array(
                raw[m + "gate.e_score_correction_bias"], "float32")
            layer.update(stack_experts(
                raw, m + "experts.", held,
                ("gate_proj", "down_proj", "up_proj"), self.dtype))
            if self.n_shared:
                for x in ("gate", "up", "down"):
                    layer["shared_" + x] = T(
                        f"{m}shared_experts.{x}_proj.weight")
            params["layers"].append(layer)
        return params
