"""LFM2-MoE (`model_type` "lfm2_moe"): gated short-convolution layers with
a grouped-query attention layer every few, sigmoid-routed experts chosen
by a biased score.

Source: https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
(8.34 B parameters; the dense family's code is transformers
`models/lfm2/modeling_lfm2.py`). One layer, `h` the residual stream,
RMSNorm throughout:

- `u = operator_norm(h)`. `layer_types[i]` "conv": the gated short
  convolution of `layers/short_conv.py` (per-sequence state, no K/V).
  "full_attention": `q, k, v` without bias, RMSNorm over each head of `q`
  and of `k` before RoPE (half-split pairing), causal GQA at scale
  `head_dim ** -0.5`, `out_proj`. A head (64 lanes as published) is stored
  in the pool at `kv_cache_head_width` (128: `layers/attention.py` pads).
- `h = h + mixer`; `h = h + FFN(ffn_norm(h))`. The first
  `num_dense_layers` layers: SwiGLU of `intermediate_size`. The others:
  `s = sigmoid(f32(x) W_g)`; the `num_experts_per_tok` experts are the
  top-k of `s + expert_bias`; their weights are `s` at the chosen experts
  (without the bias), divided by (their sum + 1e-6) when `norm_topk_prob`,
  times `routed_scaling_factor` (`layers/moe.py` `Routing`). No shared
  expert.
- `embedding_norm` after the last layer; the head is the embedding when
  `tie_word_embeddings` (the family's default).

A layer's cache in `kv_caches[i]` is what `ModelConfig.
get_layer_cache_kinds` says: a (K, V) pool pair for an attention layer, a
state pool for a conv layer.

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
from intellillm_tpu.layers.moe import (DENSE_LAYER, ExpertCounts, ExpertShare,
                                       Routing, random_gate_up_stack,
                                       routed_experts)
from intellillm_tpu.layers.normalization import fused_add_rms_norm, rms_norm
from intellillm_tpu.layers.rotary_embedding import get_rope
from intellillm_tpu.layers.short_conv import short_conv_mixer
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)

Params = Dict[str, object]

# The epsilon the family adds to the sum of the chosen scores.
_ROUTER_SUM_EPS = 1e-6


class Lfm2MoeForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "LFM2-MoE runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.hidden_size = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.norm_eps
        self.num_dense = cfg.num_dense_layers
        self.num_experts = cfg.num_experts
        self.top_k = cfg.num_experts_per_tok
        self.taps = cfg.conv_L_cache
        if self.taps != 3:
            raise NotImplementedError(
                f"conv_L_cache {self.taps}: the short-convolution body "
                f"(ops/short_conv.py) is written for 3 taps")
        if cfg.conv_bias:
            raise NotImplementedError("conv_bias is not supported")
        self.share = ExpertShare(
            self.num_experts, self.top_k,
            sum(self._is_moe_layer(i) for i in range(self.num_layers)))
        self.share.report_through(self)
        self.routing = Routing(
            scoring="sigmoid", renormalize=bool(cfg.norm_topk_prob),
            scale=float(cfg.routed_scaling_factor),
            select_bias=bool(cfg.use_expert_bias),
            sum_eps=_ROUTER_SUM_EPS)
        # Of the 128,000 published positions, those one can reach.
        self.rope = get_rope(
            self.head_dim, self.head_dim, cfg.max_position_embeddings,
            cfg.rope_theta, is_neox_style=True).trimmed_to(
                model_config.max_model_len)
        self.attn = PagedAttention(self.num_heads, self.head_dim,
                                   self.head_dim**-0.5, self.num_kv_heads)

    def _is_moe_layer(self, i: int) -> bool:
        return i >= self.num_dense

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
        h, _ = fused_add_rms_norm(h, residual, params["embedding_norm"],
                                  self.eps)
        return h, new_caches, counts.counters()

    def _attention(self, lp, h, kv_cache, attn_metadata, positions):
        b, l, _ = h.shape
        d = self.head_dim
        q = rms_norm((h @ lp["q"]).reshape(b, l, self.num_heads, d),
                     lp["q_norm"], self.eps)
        k = rms_norm((h @ lp["k"]).reshape(b, l, self.num_kv_heads, d),
                     lp["k_norm"], self.eps)
        v = (h @ lp["v"]).reshape(b, l, self.num_kv_heads, d)
        q, k = self.rope(positions, q, k)
        out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        return out.reshape(b, l, self.num_heads * d) @ lp["o"], kv_cache

    def _layer(self, lp, kind, h, residual, cache, attn_metadata,
               positions, counts):
        h, residual = fused_add_rms_norm(h, residual, lp["operator_norm"],
                                         self.eps)
        if kind == "state":
            h, cache = short_conv_mixer(h, lp["in_proj"], lp["conv_w"],
                                        lp["out_proj"], cache,
                                        attn_metadata, positions)
        else:
            h, cache = self._attention(lp, h, cache, attn_metadata,
                                       positions)
        h, residual = fused_add_rms_norm(h, residual, lp["ffn_norm"],
                                         self.eps)
        if "w1" not in lp:
            h = (jax.nn.silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]
            counts.add(DENSE_LAYER)
            return h, residual, cache
        h, layer_counts = routed_experts(h, lp, self.share, self.routing,
                                         mesh=attn_metadata.mesh,
                                         live=attn_metadata.live_rows)
        counts.add(layer_counts)
        return h, residual, cache

    def compute_logits(self, params: Params, hidden, lora=None):
        # Logits leave the matmul in float32 (the sampler works in float32
        # anyway): rounding them to the activations' dtype first costs
        # half a step of bf16 at the largest logit on every log-softmax
        # value.
        if "lm_head" in params:
            return jnp.matmul(hidden, params["lm_head"],
                              preferred_element_type=jnp.float32)
        # Tied: contract on the embedding's own last axis (no transposed
        # copy of a [vocab, hidden] table).
        return jnp.einsum("...e,ve->...v", hidden, params["embed_tokens"],
                          preferred_element_type=jnp.float32)

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "LFM2-MoE runs on one chip (tensor_parallel_size 1) in this "
            "engine: its conv layers' state pool and in-projection "
            "([B | C | x], gated channel by channel) have no sharding "
            "rule yet, and its experts no placement across chips")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e, d = self.hidden_size, self.head_dim
        layers = []
        for i in range(self.num_layers):
            layer = {"operator_norm": (e, ), "ffn_norm": (e, )}
            if self.layer_kinds[i] == "state":
                layer.update({"in_proj": (e, 3 * e),
                              "conv_w": (self.taps, e),
                              "out_proj": (e, e)})
            else:
                layer.update({
                    "q": (e, self.num_heads * d),
                    "k": (e, self.num_kv_heads * d),
                    "v": (e, self.num_kv_heads * d),
                    "q_norm": (d, ), "k_norm": (d, ),
                    "o": (self.num_heads * d, e)})
            if self._is_moe_layer(i):
                layer.update(self.share.param_shapes(
                    e, cfg.moe_intermediate_size))
                # After the stacks, where this family's seeded draws have
                # it.
                if cfg.use_expert_bias:
                    layer["expert_bias"] = (self.num_experts, )
            else:
                inter = cfg.intermediate_size
                layer.update({"gate": (e, inter), "up": (e, inter),
                              "down": (inter, e)})
            layers.append(layer)
        tree = {"embed_tokens": (cfg.vocab_size, e),
                "embedding_norm": (e, ), "layers": layers}
        if not cfg.tie_word_embeddings:
            tree["lm_head"] = (e, cfg.vocab_size)
        return tree

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`; every array is
        waited for as it is drawn (models/axk1.py says why)."""
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            if name == "embedding_norm" and "lm_head" not in shapes:
                # A tied head scores a token against its own embedding,
                # which the residual stream still carries: with a gain
                # near one that logit is ~ sqrt(hidden) / the stream's
                # growth (30 at these sizes against a spread of 1), the
                # model repeats its input, and bf16's step at 30 (0.125)
                # is all a comparison of log-softmax values then sees. A
                # gain of either sign (standard normal) takes the sum's
                # mean away and leaves the other logits what they were.
                w = jax.random.normal(k, shape, jnp.float32)
                return jax.block_until_ready(w.astype(dtype))
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                if name in ("q_norm", "k_norm"):
                    # Gains of two on q and on k: scores four times as
                    # wide as unit vectors give (a softmax over a few
                    # tokens, as trained heads have). With diffuse
                    # attention the mean of ~300 random value vectors is a
                    # twentieth of one, the attention layers add next to
                    # nothing to the stream, and neither a norm left out
                    # nor K/V kept at a lower precision shows at the
                    # logits (PERF.md section 6, PR 30).
                    w = 2.0 * w
                return jax.block_until_ready(w.astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            if name == "expert_bias":
                # As large as the scores' own spread (a sigmoid of an O(1)
                # logit), so that the choice by score + bias and the
                # choice by score alone differ for most tokens.
                return jax.block_until_ready(
                    0.5 * jax.random.normal(k, shape, jnp.float32))
            if name == "conv_w":
                # Three taps of comparable weight: a tap left out shows.
                w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(3)
                return jax.block_until_ready(w.astype(dtype))
            # Fan-in scaling keeps activations O(1); what writes to the
            # residual stream adds a tenth of the stream's magnitude, as
            # where training has let the stream dominate (models/axk1.py,
            # PERF.md section 6, PR 26). A tied embedding is at the fan-in
            # scale of the head's contraction, so that logits stay O(1),
            # and the stream and its writers are that much smaller (every
            # reader of the stream is an RMSNorm: only the ratio counts).
            stream = (1.0 if "lm_head" in shapes
                      else 1.0 / math.sqrt(self.hidden_size))
            if name == "embed_tokens":
                scale = stream
            else:
                scale = 1.0 / math.sqrt(shape[-2])
            if name in ("o", "out_proj", "down", "w2"):
                scale *= 0.1 * stream
            w = jax.random.normal(k, shape, jnp.float32) * scale
            if name == "gate_router":
                return jax.block_until_ready(w)       # float32 router
            return jax.block_until_ready(w.astype(dtype))

        def build(tree):
            if isinstance(tree, dict):
                return {k: (build(v) if isinstance(v, (dict, list))
                            else draw(v, k)) for k, v in tree.items()}
            return [build(v) for v in tree]

        shapes = self._param_shapes()
        return build(shapes)

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        """Checkpoint names as the family publishes them
        (`model.layers.N.{operator_norm, ffn_norm}`, `conv.{in_proj, conv,
        out_proj}`, `self_attn.{q_proj, k_proj, v_proj, out_proj,
        q_layernorm, k_layernorm}`, `feed_forward.{w1, w2, w3}` dense or
        `feed_forward.{gate, expert_bias, experts.J.{w1, w2, w3}}`,
        `model.embedding_norm`, `model.embed_tokens`)."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key):
            return cast_array(raw[key], self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "embedding_norm": V("model.embedding_norm.weight"),
            "layers": [],
        }
        if not self.config.tie_word_embeddings:
            params["lm_head"] = T("lm_head.weight")
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            layer = {"operator_norm": V(p + "operator_norm.weight"),
                     "ffn_norm": V(p + "ffn_norm.weight")}
            if self.layer_kinds[i] == "state":
                c = p + "conv."
                layer["in_proj"] = T(c + "in_proj.weight")
                # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                layer["conv_w"] = cast_array(
                    raw[c + "conv.weight"][:, 0, :].T, self.dtype)
                layer["out_proj"] = T(c + "out_proj.weight")
            else:
                a = p + "self_attn."
                layer.update({
                    "q": T(a + "q_proj.weight"),
                    "k": T(a + "k_proj.weight"),
                    "v": T(a + "v_proj.weight"),
                    "o": T(a + "out_proj.weight"),
                    "q_norm": V(a + "q_layernorm.weight"),
                    "k_norm": V(a + "k_layernorm.weight")})
            m = p + "feed_forward."
            if self._is_moe_layer(i):
                layer["gate_router"] = cast_array(raw[m + "gate.weight"].T,
                                                  "float32")
                if self.config.use_expert_bias:
                    layer["expert_bias"] = cast_array(
                        raw[m + "expert_bias"], "float32")
                layer.update(stack_experts(
                    raw, m + "experts.", self.share.held,
                    ("w1", "w2", "w3"), self.dtype))
            else:
                layer["gate"] = T(m + "w1.weight")
                layer["up"] = T(m + "w3.weight")
                layer["down"] = T(m + "w2.weight")
            params["layers"].append(layer)
        return params
