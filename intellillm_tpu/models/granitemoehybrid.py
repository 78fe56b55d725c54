"""Granite 4.0-H (`model_type` "granitemoehybrid"): Mamba-2 state-space
layers with a softmax GQA layer wherever `layer_types` says "attention",
softmax-routed experts + a shared MLP in every layer, and Granite's four
multipliers.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
(32 B parameters, 9 B active). With `h` the residual stream, RMSNorm
(`rms_norm_eps`) throughout, pre-norm, `rm` = `residual_multiplier`:

- `h = embed(ids) * embedding_multiplier`.
- `h += rm * mixer(input_norm(h))`. A "mamba" layer: the selective
  state-space scan of `layers/mamba2.py` (per-sequence state, no K/V;
  `mamba_n_groups` groups of B and C, 1 as published, are the layer's, and
  the gated norm is then a group's channels at a time). An
  "attention" layer: `q, k, v` without bias and WITHOUT position encoding
  (`position_embedding_type` "nope"), causal GQA whose softmax scale is
  `attention_multiplier` (not `head_dim ** -0.5`), `W_o`.
- `h += rm * (moe(post_norm(h)) + shared_mlp(post_norm(h)))`: a softmax
  over the `num_experts_per_tok` largest router logits weighs those experts
  (= a softmax over all, the top-k renormalised: `layers/moe.py` `Routing`
  as it stands); every expert and the shared MLP are SwiGLU, their gate and
  up projections one fused `input_linear` in a checkpoint.
- `logits = final_norm(h) @ embed^T / logits_scaling` (a tied head).

Expert parallelism (`ep_size`, `ep_rank` in the config, as
`models/axk1.py`): with `ep_size` > 1 `num_local_experts` is what THIS
chip holds (`layers/moe.py` `ExpertShare`). The shared MLP and both mixers
are whole on every chip.

A layer's cache in `kv_caches[i]` is what `ModelConfig.
get_layer_cache_kinds` says: a (K, V) pool pair for an attention layer, the
(taps, matrix) pools of `ModelConfig.get_state_arrays` for a Mamba-2 layer.

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
from intellillm_tpu.layers.mamba2 import mamba2_mixer
from intellillm_tpu.layers.moe import (ExpertCounts, ExpertShare, Routing,
                                       random_gate_up_stack, routed_experts)
from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator)

Params = Dict[str, object]


class GraniteMoeHybridForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "Granite 4.0-H runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.hidden_size = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = model_config.get_head_size()
        self.eps = cfg.rms_norm_eps
        self.ssm_heads = cfg.mamba_n_heads
        self.ssm_dim = cfg.mamba_d_head
        self.ssm_state = cfg.mamba_d_state
        self.ssm_inner = self.ssm_heads * self.ssm_dim
        if cfg.mamba_d_conv != 4:
            raise NotImplementedError(
                f"mamba_d_conv {cfg.mamba_d_conv}: the filter's body "
                f"(ops/short_conv.py `conv_silu_body`) is written for 4 "
                f"taps")
        if (self.ssm_heads % cfg.mamba_n_groups or cfg.mamba_proj_bias
                or not cfg.mamba_conv_bias or cfg.attention_bias
                or cfg.position_embedding_type not in (None, "nope")
                or not cfg.tie_word_embeddings
                or not cfg.num_local_experts):
            raise NotImplementedError(
                "only the published Granite 4.0-H setting is served: whole "
                "heads a group of B and C (mamba_n_groups 1 as published; "
                "more are the layer's, layers/mamba2.py), mamba_conv_bias "
                "true, no projection or attention bias, no position "
                "encoding, a tied head, experts in every layer")
        self.embedding_multiplier = float(cfg.embedding_multiplier)
        self.residual_multiplier = float(cfg.residual_multiplier)
        self.logits_scaling = float(cfg.logits_scaling)
        self.moe_inter = cfg.intermediate_size      # one expert's width
        self.shared_inter = cfg.shared_intermediate_size
        self.top_k = cfg.num_experts_per_tok
        # The share: `num_local_experts` held here of a router ep_size x
        # wider.
        self.share = ExpertShare.from_config(
            cfg, cfg.num_local_experts, self.top_k, self.num_layers)
        self.share.report_through(self)
        self.routing = Routing(held_start=self.share.held_start)
        self.attn = PagedAttention(self.num_heads, self.head_dim,
                                   float(cfg.attention_multiplier),
                                   self.num_kv_heads)

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = params["embed_tokens"][input_ids]
        h = (h.astype(jnp.float32) * self.embedding_multiplier).astype(
            h.dtype)
        new_caches = []
        counts = ExpertCounts(self.share)
        for i in range(self.num_layers):
            h, cache = self._layer(
                params["layers"][i], self.layer_kinds[i], h, kv_caches[i],
                attn_metadata, positions, counts)
            new_caches.append(cache)
        h = rms_norm(h, params["norm"], self.eps)
        return h, new_caches, counts.counters()

    def _attention(self, lp, h, kv_cache, attn_metadata):
        b, l, _ = h.shape
        d = self.head_dim
        q = (h @ lp["q"]).reshape(b, l, self.num_heads, d)
        k = (h @ lp["k"]).reshape(b, l, self.num_kv_heads, d)
        v = (h @ lp["v"]).reshape(b, l, self.num_kv_heads, d)
        out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        return out.reshape(b, l, self.num_heads * d) @ lp["o"], kv_cache

    def _add(self, h, update):
        """The residual write: `h + residual_multiplier * update`."""
        return (h.astype(jnp.float32) + self.residual_multiplier
                * update.astype(jnp.float32)).astype(h.dtype)

    def _layer(self, lp, kind, h, cache, attn_metadata, positions, counts):
        x = rms_norm(h, lp["input_norm"], self.eps)
        if kind == "state":
            with jax.named_scope("mamba2_mixer"):
                x, cache = mamba2_mixer(
                    x, lp, cache, attn_metadata, positions,
                    num_heads=self.ssm_heads, head_dim=self.ssm_dim,
                    d_state=self.ssm_state, eps=self.eps,
                    groups=self.config.mamba_n_groups)
        else:
            x, cache = self._attention(lp, x, cache, attn_metadata)
        h = self._add(h, x)
        out, layer_counts = routed_experts(
            rms_norm(h, lp["post_norm"], self.eps), lp, self.share,
            self.routing, mesh=attn_metadata.mesh,
            live=attn_metadata.live_rows)
        h = self._add(h, out)
        counts.add(layer_counts)
        return h, cache

    def compute_logits(self, params: Params, hidden, lora=None):
        # Tied: contract on the embedding's own last axis (no transposed
        # copy of a [vocab, hidden] table). Logits leave the matmul in
        # float32 (models/lfm2_moe.py says why).
        return jnp.einsum("...e,ve->...v", hidden, params["embed_tokens"],
                          preferred_element_type=jnp.float32) \
            / self.logits_scaling

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "Granite 4.0-H runs on one chip (tensor_parallel_size 1) in "
            "this engine: its state-space layers' state pools (one "
            "matrix over all the scan's heads) and the in-projection's "
            "three parts ([z | xBC | dt], filtered together) have no "
            "sharding rule over heads yet, and its experts are placed by "
            "the config's ep_size / ep_rank (one chip's share of an "
            "expert-parallel deployment), not by the mesh")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e, d = self.hidden_size, self.head_dim
        inner, hs = self.ssm_inner, self.ssm_heads
        bc = cfg.mamba_n_groups * self.ssm_state     # B's (and C's) width
        layers = []
        for i in range(self.num_layers):
            layer = {"input_norm": (e, ), "post_norm": (e, )}
            if self.layer_kinds[i] == "state":
                layer.update({
                    "in_proj": (e, 2 * inner + 2 * bc + hs),
                    "conv_w": (4, inner + 2 * bc),
                    "conv_b": (inner + 2 * bc, ),
                    "dt_bias": (hs, ), "A_log": (hs, ), "D": (hs, ),
                    "gate_norm": (inner, ), "out_proj": (inner, e)})
            else:
                layer.update({
                    "q": (e, self.num_heads * d),
                    "k": (e, self.num_kv_heads * d),
                    "v": (e, self.num_kv_heads * d),
                    "o": (self.num_heads * d, e)})
            layer.update(self.share.param_shapes(
                e, self.moe_inter, shared_inter=self.shared_inter))
            layers.append(layer)
        return {"embed_tokens": (cfg.vocab_size, e), "norm": (e, ),
                "layers": layers}

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`; every array is
        waited for as it is drawn (models/axk1.py says why)."""
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]
        f32 = jnp.float32
        # A tied embedding at the scale that leaves logits O(1) after the
        # head's division; the stream it starts is `embedding_multiplier`
        # times that, and what writes to the stream adds a tenth of its
        # magnitude through `residual_multiplier` (models/axk1.py,
        # PERF.md section 6, PR 26).
        embed = self.logits_scaling / math.sqrt(self.hidden_size)
        writer = (0.1 * self.embedding_multiplier * embed
                  / self.residual_multiplier)

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            if name == "norm":
                # The final norm before a tied head: a gain of either sign
                # (models/lfm2_moe.py says why).
                w = jax.random.normal(k, shape, f32)
                return jax.block_until_ready(w.astype(dtype))
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, f32)
                return jax.block_until_ready(w.astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            if name == "A_log":
                # exp(A_log) in U(1, 16), as the family's layers start.
                return jax.block_until_ready(jnp.log(
                    jax.random.uniform(k, shape, f32, 1.0, 16.0)))
            if name == "dt_bias":
                # softplus^-1 of a step dt, log-uniform in [1e-3, 0.1].
                dt = jnp.exp(jax.random.uniform(
                    k, shape, f32, math.log(1e-3), math.log(0.1)))
                return jax.block_until_ready(dt + jnp.log(-jnp.expm1(-dt)))
            if name == "D":
                return jax.block_until_ready(jnp.ones(shape, f32))
            if name == "conv_w":
                # Four taps of comparable weight: a tap left out shows.
                w = jax.random.normal(k, shape, f32) / 2.0
                return jax.block_until_ready(w.astype(dtype))
            if name == "conv_b":
                # As large as a tap's term: a bias left out shows.
                w = jax.random.normal(k, shape, f32) / 2.0
                return jax.block_until_ready(w.astype(dtype))
            # Fan-in scaling keeps activations O(1).
            scale = (embed if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            if name in ("o", "out_proj", "w2", "shared_down"):
                scale *= writer
            if name in ("q", "k"):
                # Scores four times as wide as fan-in scaling gives at a
                # softmax scale of head_dim ** -0.5: a softmax over a few
                # tokens, as trained heads have (models/lfm2_moe.py);
                # this model's scale is `attention_multiplier`.
                scale *= 2.0 * (self.attn.scale
                                * math.sqrt(self.head_dim)) ** -0.5
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
        """Checkpoint names after HF's `GraniteMoeHybrid*` modules (the
        configuration file lists them under `assumed`):
        `model.layers.N.{input_layernorm, post_attention_layernorm}`; an
        attention layer's `self_attn.{q_proj, k_proj, v_proj, o_proj}`; a
        Mamba-2 layer's `mamba.{in_proj, conv1d.weight, conv1d.bias,
        dt_bias, A_log, D, norm, out_proj}`; `block_sparse_moe.{router.
        layer, input_linear [experts, 2 x inner, hidden] (gate then up),
        output_linear [experts, hidden, inner]}`; `shared_mlp.{
        input_linear [2 x inner, hidden], output_linear}`. A share loads
        its own experts' slices and its slice of the vocabulary."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key, dtype=None):
            return cast_array(raw[key], dtype or self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight")[
                :self.config.vocab_size],
            "norm": V("model.norm.weight"),
            "layers": [],
        }
        held = slice(self.share.held.start, self.share.held.stop)
        mi, si = self.moe_inter, self.shared_inter
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            layer = {"input_norm": V(p + "input_layernorm.weight"),
                     "post_norm": V(p + "post_attention_layernorm.weight")}
            if self.layer_kinds[i] == "state":
                m = p + "mamba."
                layer.update({
                    "in_proj": T(m + "in_proj.weight"),
                    # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                    "conv_w": cast_array(
                        raw[m + "conv1d.weight"][:, 0, :].T, self.dtype),
                    "conv_b": V(m + "conv1d.bias"),
                    "dt_bias": V(m + "dt_bias", "float32"),
                    "A_log": V(m + "A_log", "float32"),
                    "D": V(m + "D", "float32"),
                    "gate_norm": V(m + "norm.weight"),
                    "out_proj": T(m + "out_proj.weight")})
            else:
                a = p + "self_attn."
                layer.update({"q": T(a + "q_proj.weight"),
                              "k": T(a + "k_proj.weight"),
                              "v": T(a + "v_proj.weight"),
                              "o": T(a + "o_proj.weight")})
            e = p + "block_sparse_moe."
            layer["gate_router"] = cast_array(
                raw[e + "router.layer.weight"].T, "float32")
            # Stacks are [N, I, D] (layers/moe.py).
            fused = raw[e + "input_linear.weight"][held]
            layer["w1"] = cast_array(fused[:, :mi], self.dtype)
            layer["w3"] = cast_array(fused[:, mi:], self.dtype)
            layer["w2"] = cast_array(
                raw[e + "output_linear.weight"][held].swapaxes(1, 2),
                self.dtype)
            s = p + "shared_mlp."
            fused = raw[s + "input_linear.weight"]
            layer["shared_gate"] = cast_array(fused[:si].T, self.dtype)
            layer["shared_up"] = cast_array(fused[si:].T, self.dtype)
            layer["shared_down"] = T(s + "output_linear.weight")
            params["layers"].append(layer)
        return params
