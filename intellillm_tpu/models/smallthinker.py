"""SmallThinker (`model_type` "smallthinker"): sliding-window RoPE layers
with a full-attention layer without position encoding every few, ReGLU
experts chosen by a router that reads the layer's input.

Source: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
(21 B parameters, 3 B active). One layer `l`, `x` its input, RMSNorm
throughout, no bias anywhere:

- `n1 = input_layernorm(x)`. The router reads `n1` (before attention):
  `r = f32(n1) W_r`, the `moe_num_active_primary_experts` largest of `r`
  are chosen and weighted by a softmax over the chosen (equal to a
  softmax over all renormalised over the chosen: `layers/moe.py`
  `Routing()`).
- `q, k, v` of `n1`, no q/k norm; RoPE (half-split pairing, whole head)
  on `q`, `k` where `rope_layout[l]` is 1, NO position encoding where it
  is 0; causal GQA at scale `head_dim ** -0.5`, over the last
  `sliding_window_size` tokens where `sliding_window_layout[l]` is 1, over
  the whole context where it is 0; `h = x + attn W_o`.
- `n2 = post_attention_layernorm(h)`; `out = h + sum_e w_e W_down,e
  (relu(W_gate,e n2) * (W_up,e n2))` over the chosen experts (ReLU gate,
  no shared expert).
- final `norm`, untied `lm_head`.

A window layer's K/V pool belongs to the window cache group
(`ModelConfig.get_layer_cache_kinds` "kv_window"), a full layer's to the
primary one; `layers/attention.py` picks the group's table by the layer's
`sliding_window`.

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
from intellillm_tpu.layers.moe import (ExpertCounts, ExpertShare, Routing,
                                       random_gate_up_stack, route,
                                       routed_experts)
from intellillm_tpu.layers.normalization import fused_add_rms_norm
from intellillm_tpu.layers.rotary_embedding import get_rope
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)

Params = Dict[str, object]


class SmallThinkerForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "SmallThinker runs unquantised (bf16 / float32) only")
        if not cfg.moe_primary_router_apply_softmax:
            raise NotImplementedError(
                "moe_primary_router_apply_softmax false (sigmoid scores) "
                "is not supported")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.hidden_size = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        self.num_experts = cfg.moe_num_primary_experts
        self.top_k = cfg.moe_num_active_primary_experts
        self.inter = cfg.moe_ffn_hidden_size
        self.rope_layout = [bool(r) for r in cfg.rope_layout[:self.num_layers]]
        self.share = ExpertShare(self.num_experts, self.top_k,
                                 self.num_layers)
        self.share.report_through(self)
        self.routing = Routing(renormalize=bool(cfg.norm_topk_prob))
        self.rope = get_rope(
            self.head_dim, self.head_dim, cfg.max_position_embeddings,
            cfg.rope_theta, is_neox_style=True,
            rope_scaling=cfg.rope_scaling).trimmed_to(
                model_config.max_model_len)
        window = int(cfg.sliding_window_size)
        scale = self.head_dim**-0.5
        self.attn_full = PagedAttention(self.num_heads, self.head_dim, scale,
                                        self.num_kv_heads)
        self.attn_window = PagedAttention(self.num_heads, self.head_dim,
                                          scale, self.num_kv_heads,
                                          sliding_window=window)
        self.windowed = [bool(w)
                         for w in cfg.sliding_window_layout[:self.num_layers]]

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = params["embed_tokens"][input_ids]
        residual = None
        new_caches = []
        counts = ExpertCounts(self.share)
        for i in range(self.num_layers):
            h, residual, cache = self._layer(
                params["layers"][i], i, h, residual, kv_caches[i],
                attn_metadata, positions, counts)
            new_caches.append(cache)
        h, _ = fused_add_rms_norm(h, residual, params["norm"], self.eps)
        return h, new_caches, counts.counters()

    def _layer(self, lp, i, h, residual, cache, attn_metadata, positions,
               counts):
        b, l, e = h.shape
        d = self.head_dim
        h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                         self.eps)
        # The router reads the layer's normalised input, not the experts'.
        routed = route(h, lp, self.share, self.routing)
        q = (h @ lp["q"]).reshape(b, l, self.num_heads, d)
        k = (h @ lp["k"]).reshape(b, l, self.num_kv_heads, d)
        v = (h @ lp["v"]).reshape(b, l, self.num_kv_heads, d)
        if self.rope_layout[i]:
            q, k = self.rope(positions, q, k)
        attn = self.attn_window if self.windowed[i] else self.attn_full
        out, cache = attn(q, k, v, cache, attn_metadata)
        h = out.reshape(b, l, self.num_heads * d) @ lp["o"]
        h, residual = fused_add_rms_norm(h, residual, lp["post_attn_norm"],
                                         self.eps)
        h, layer_counts = routed_experts(
            h, lp, self.share, self.routing, mesh=attn_metadata.mesh,
            routed=routed, activation="relu", live=attn_metadata.live_rows)
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
            "SmallThinker runs on one chip (tensor_parallel_size 1) in "
            "this engine: its window group's pools have no sharding yet "
            "(worker/cache_engine.py shards one group's) and its experts "
            "no placement across chips")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e, d = self.hidden_size, self.head_dim
        layer = {
            "input_norm": (e, ), "post_attn_norm": (e, ),
            "q": (e, self.num_heads * d), "k": (e, self.num_kv_heads * d),
            "v": (e, self.num_kv_heads * d), "o": (self.num_heads * d, e),
            **self.share.param_shapes(e, self.inter)}
        return {"embed_tokens": (cfg.vocab_size, e), "norm": (e, ),
                "lm_head": (e, cfg.vocab_size),
                "layers": [dict(layer) for _ in range(self.num_layers)]}

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
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                return jax.block_until_ready(w.astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            # Fan-in scaling keeps activations O(1); what writes to the
            # residual stream adds a fraction of the stream's magnitude,
            # as where training has let the stream dominate
            # (models/axk1.py, PERF.md section 6, PRs 26 / 30): eight
            # random layers then do not amplify a rounding. Two tenths,
            # not PR 26's one: a wrong gate activation or a window dropped
            # then moves the logits several times further than bf16's own
            # rounding does; at five tenths for the experts a top-6 choice
            # flipped by a rounding cost 0.2-0.4 nats (PERF.md section 6,
            # PR 33).
            scale = 1.0 / math.sqrt(shape[-2])
            if name in ("q", "k"):
                # Scores four times as wide as unit projections give (a
                # softmax over a few tokens, as trained heads have). With
                # diffuse attention over thousands of tokens the mean of
                # random value vectors is next to nothing, the attention
                # layers add nothing to the stream, and neither a window
                # dropped, a rotary misplaced nor K/V kept at a lower
                # precision shows at the logits (PR 30's lesson; there the
                # gain sat on the q / k norms this family lacks).
                scale *= 2.0
            if name in ("o", "w2"):
                scale *= 0.2
            if name == "embed_tokens":
                scale = 1.0
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
        """Checkpoint names as the family publishes them
        (`model.layers.N.{input_layernorm, post_attention_layernorm}`,
        `self_attn.{q,k,v,o}_proj`, `block_sparse_moe.primary_router`,
        `block_sparse_moe.experts.E.{gate,up,down}`, `model.norm`,
        `model.embed_tokens`, `lm_head`)."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key):
            return cast_array(raw[key], self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "norm": V("model.norm.weight"),
            "lm_head": T("lm_head.weight"),
            "layers": [],
        }
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            a, m = p + "self_attn.", p + "block_sparse_moe."
            params["layers"].append({
                "input_norm": V(p + "input_layernorm.weight"),
                "post_attn_norm": V(p + "post_attention_layernorm.weight"),
                "q": T(a + "q_proj.weight"), "k": T(a + "k_proj.weight"),
                "v": T(a + "v_proj.weight"), "o": T(a + "o_proj.weight"),
                "gate_router": cast_array(
                    raw[m + "primary_router.weight"].T, "float32"),
                **stack_experts(raw, m + "experts.", self.share.held,
                                ("gate", "down", "up"), self.dtype),
            })
        return params
