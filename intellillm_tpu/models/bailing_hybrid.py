"""Ling 3.0 (`model_type` "bailing_hybrid"): Kimi-Delta-Attention layers
with a latent-attention (MLA) layer at the end of every group of
`layer_group_size`, a dense SwiGLU in the first `first_k_dense_replace`
layers and sigmoid-routed experts in groups + a shared expert in the others.

Source: https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json
(~125 B parameters, ~5.5 B active). One layer, `h` the residual stream,
RMSNorm (`rms_norm_eps`) throughout, pre-norm:

- `h += mixer(input_norm(h))`. Layer `l` with `(l + 1) % layer_group_size
  == 0`: latent attention (`layers/mla_attention.py`
  `LatentAttentionBlock`: no low-rank query stage, `q_lora_rank` null;
  RoPE in interleaved pairs on the last `qk_rope_head_dim` of a query head
  and on the one shared rope key; the absorbed form over a pool of
  `[c_kv | k_rope]` rows; one sigmoid gate a head before `W_o`). Every
  other layer: Kimi Delta Attention (`layers/linear_attention.py`: a
  float32 matrix a head and the filters' taps in a slot a sequence, no
  K/V, no position encoding) with the decay bounded below
  (`kda_safe_gate`: `g = kda_lower_bound * sigmoid(exp(A_log) * (W_f x +
  dt_bias))`), full-rank `W_f` and `W_g` (`no_kda_lora`) and the write
  strength `sigmoid(W_b x)`.
- `h += ffn(post_norm(h))`. Layers under `first_k_dense_replace`: SwiGLU of
  `intermediate_size`. The others: `s = sigmoid(f32(x) W_r)` over all
  `num_experts`; for the CHOICE `s + expert_bias`: `n_group` groups of
  consecutive experts, a group's score the sum of its two largest, the best
  `topk_group` groups kept, the `num_experts_per_tok` largest of theirs
  chosen; weights `s` at the chosen divided by their sum, times
  `routed_scaling_factor` (`layers/moe.py` `Routing`); plus one shared
  SwiGLU expert, unscaled.
- final RMSNorm; an untied head.

Expert parallelism (`ep_size`, `ep_rank` in the config, as
`models/axk1.py`): with `ep_size` > 1 `num_experts` is what THIS chip
holds (`layers/moe.py` `ExpertShare`). The shared expert, the dense layers
and both mixers are whole on every chip.

A layer's cache in `kv_caches[i]` is what the config class's
`cache_description` says: the 1-tuple (latent pool,) for a latent layer,
the (taps, matrices) pools for a KDA layer.

Not served, each refused with a message: a nonzero entry of
`expert_swiglu_limit_list` / `share_expert_swiglu_limit_list` among the
layers kept (the published config does not give the clamp's form); the
multi-token-prediction layer is not loaded and speculative decoding is
refused (`speculation_refusal`); tp > 1 (`partition_specs`); LoRA;
quantisation.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.linear_attention import kda_mixer
from intellillm_tpu.layers.mla_attention import LatentAttentionBlock
from intellillm_tpu.layers.moe import (DENSE_LAYER, ExpertCounts, ExpertShare,
                                       Routing, random_gate_up_stack,
                                       routed_experts)
from intellillm_tpu.layers.normalization import fused_add_rms_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)

Params = Dict[str, object]


class BailingHybridForCausalLM:

    supports_lora = False
    supported_quantization = ()
    speculation_refusal = (
        "Speculative decoding is not supported for bailing_hybrid: a "
        "rejected draft token has already advanced the delta rule's matrix "
        "state, and the state pool keeps no earlier value to roll back to; "
        "the checkpoint's multi-token-prediction layer "
        "(num_nextn_predict_layers), which would be the drafter, is not "
        "loaded.")

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "bailing_hybrid runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.layer_kinds = model_config.get_layer_cache_kinds()
        self.hidden_size = cfg.hidden_size
        self.eps = cfg.rms_norm_eps
        self.kda_heads = cfg.num_attention_heads
        self.kda_dim = cfg.head_dim
        self._refuse_what_the_config_asks_beyond_the_published_setting(cfg)
        self.lower_bound = float(cfg.kda_lower_bound)
        self.first_dense = cfg.first_k_dense_replace
        self.moe_inter = cfg.moe_intermediate_size
        self.shared_inter = (cfg.moe_shared_expert_intermediate_size
                             * (cfg.num_shared_experts or 0))
        self.top_k = cfg.num_experts_per_tok
        # The share: `num_experts` held here of a router ep_size x wider.
        self.share = ExpertShare.from_config(
            cfg, cfg.num_experts, self.top_k,
            max(self.num_layers - self.first_dense, 0))
        self.share.report_through(self)
        if self.share.n_router % cfg.n_group:
            raise ValueError(f"router width {self.share.n_router} does not "
                             f"divide into n_group {cfg.n_group}")
        self.routing = Routing(
            scoring=cfg.score_function,
            renormalize=bool(cfg.norm_topk_prob),
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            group_score_top=2, scale=float(cfg.routed_scaling_factor),
            select_bias=bool(cfg.moe_router_enable_expert_bias),
            held_start=self.share.held_start)
        self.mla = LatentAttentionBlock(cfg, model_config.max_model_len)

    def _refuse_what_the_config_asks_beyond_the_published_setting(self, cfg):
        limits = [(name, l, v) for name in ("expert_swiglu_limit_list",
                                            "share_expert_swiglu_limit_list")
                  for l, v in enumerate(getattr(cfg, name)[:self.num_layers])
                  if v]
        if limits:
            name, layer, value = limits[0]
            raise NotImplementedError(
                f"{name}[{layer}] = {value}: a clamped SwiGLU is not served "
                f"(the published config gives the limit and not the "
                f"clamp's form); keep layers whose limit is 0")
        if cfg.short_conv_kernel_size != 4:
            raise NotImplementedError(
                f"short_conv_kernel_size {cfg.short_conv_kernel_size}: the "
                f"filter's body (ops/short_conv.py `conv_silu_body`) is "
                f"written for 4 taps")
        if (cfg.num_kv_heads_for_linear_attn
                not in (0, None, cfg.num_attention_heads)):
            raise NotImplementedError(
                "num_kv_heads_for_linear_attn: the delta rule here has as "
                "many key and value heads as query heads")
        if (not cfg.kda_safe_gate or not cfg.no_kda_lora
                or not cfg.linear_silu or cfg.group_norm_size != 1
                or cfg.use_mla_nope or cfg.q_lora_rank
                or not cfg.rope_interleave or cfg.topk_method != "noaux_tc"):
            raise NotImplementedError(
                "only the published setting is served: kda_safe_gate true, "
                "no_kda_lora true, linear_silu true, group_norm_size 1, "
                "use_mla_nope false, q_lora_rank null, rope_interleave "
                "true, topk_method noaux_tc")

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

    def _layer(self, lp, kind, h, residual, cache, attn_metadata,
               positions, counts):
        h, residual = fused_add_rms_norm(h, residual, lp["input_norm"],
                                         self.eps)
        if kind == "state":
            with jax.named_scope("kda_mixer"):
                h, cache = kda_mixer(h, lp, cache, attn_metadata, positions,
                                     num_heads=self.kda_heads,
                                     head_dim=self.kda_dim, eps=self.eps,
                                     lower_bound=self.lower_bound,
                                     beta_scale=1.0)
        else:
            h, cache = self.mla(lp, h, cache, attn_metadata, positions)
        h, residual = fused_add_rms_norm(h, residual, lp["post_norm"],
                                         self.eps)
        if "w1" not in lp:
            with jax.named_scope("dense_mlp"):
                h = (jax.nn.silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]
            counts.add(DENSE_LAYER)
            return h, residual, cache
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
            "bailing_hybrid runs on one chip (tensor_parallel_size 1) in "
            "this engine: its linear-attention layers' state pools (a "
            "matrix a head) and fused q/k/v filter have no sharding rule "
            "over heads yet, a latent pool has no head axis to divide, and "
            "its experts are placed by the config's ep_size / ep_rank (one "
            "chip's share of an expert-parallel deployment), not by the "
            "mesh")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e = self.hidden_size
        hd = self.kda_heads * self.kda_dim
        layers = []
        for i in range(self.num_layers):
            layer = {"input_norm": (e, ), "post_norm": (e, )}
            if self.layer_kinds[i] == "state":
                layer.update({
                    "qkv": (e, 3 * hd), "conv_w": (4, 3 * hd),
                    "f": (e, hd), "dt_bias": (hd, ),
                    "A_log": (self.kda_heads, ),
                    "b_proj": (e, self.kda_heads), "g": (e, hd),
                    "o_norm": (self.kda_dim, ), "o": (hd, e)})
            else:
                layer.update(self.mla.param_shapes(e))
            if i < self.first_dense:
                layer.update({"gate": (e, cfg.intermediate_size),
                              "up": (e, cfg.intermediate_size),
                              "down": (cfg.intermediate_size, e)})
            else:
                layer.update(self.share.param_shapes(
                    e, self.moe_inter,
                    select_bias=self.routing.select_bias,
                    shared_inter=self.shared_inter))
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
                # As large as the gaps between the best few of 512 scores,
                # small beside the scores' spread (models/solar_open2.py
                # says why): the choice by score + bias and by score alone
                # differ for most tokens, and the choices stay spread over
                # the router's width.
                return jax.block_until_ready(
                    0.03 * jax.random.normal(k, shape, f32))
            if name == "A_log":
                # exp(A_log) in U(1, 16), as the family's layers start.
                return jax.block_until_ready(jnp.log(
                    jax.random.uniform(k, shape, f32, 1.0, 16.0)))
            if name == "dt_bias":
                # The bounded gate's decay a step is exp(lower_bound *
                # sigmoid(exp(A_log) * (. + dt_bias))): a bias in U(-4, -1)
                # under a rate of 1 to 16 gives decays from a step that
                # forgets little (0.99) to one that forgets most.
                return jax.block_until_ready(
                    jax.random.uniform(k, shape, f32, -4.0, -1.0))
            if name == "conv_w":
                # Four taps of comparable weight: a tap left out shows.
                w = jax.random.normal(k, shape, f32) / 2.0
                return jax.block_until_ready(w.astype(dtype))
            # Fan-in scaling keeps activations O(1); what writes to the
            # residual stream adds a tenth of the stream's magnitude
            # (models/axk1.py, PERF.md section 6, PR 26).
            scale = (1.0 if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            if name in ("o", "w2", "shared_down", "down"):
                scale *= 0.1
            if name in ("q", "kv_a"):
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
        """Checkpoint names as assumed from the family's earlier releases,
        DeepSeek-V3's MLA and Kimi Linear's KDA (the configuration file
        lists them under `assumed`): `model.word_embeddings`,
        `model.norm`, `lm_head`; `model.layers.N.{input_layernorm,
        post_attention_layernorm}`; a latent layer's `attention.{q_proj,
        kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, g_proj, dense}`; a
        KDA layer's `attention.{q_proj, k_proj, v_proj, q_conv1d, k_conv1d,
        v_conv1d, f_proj, dt_bias, A_log, b_proj, g_proj, o_norm, dense}`;
        `mlp.{gate_proj, up_proj, down_proj}` (dense) or `mlp.{gate,
        gate.expert_bias, experts.J.{gate_proj, up_proj, down_proj},
        shared_experts}`. The multi-token-prediction layer (`model.layers.
        <num_hidden_layers>` and beyond) is not read. A share loads its
        own experts' slices and its slice of the vocabulary."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key, dtype=None):
            return cast_array(raw[key], dtype or self.dtype)

        vocab = self.config.vocab_size
        params: Params = {
            "embed_tokens": V("model.word_embeddings.weight")[:vocab],
            "norm": V("model.norm.weight"),
            "lm_head": T("lm_head.weight")[:, :vocab],
            "layers": [],
        }
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            a = p + "attention."
            layer = {"input_norm": V(p + "input_layernorm.weight"),
                     "post_norm": V(p + "post_attention_layernorm.weight")}
            if self.layer_kinds[i] == "state":
                layer["qkv"] = np.concatenate(
                    [T(a + f"{x}_proj.weight") for x in "qkv"], axis=1)
                # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                layer["conv_w"] = np.concatenate(
                    [cast_array(raw[a + f"{x}_conv1d.weight"][:, 0, :].T,
                                self.dtype) for x in "qkv"], axis=1)
                layer.update({
                    "f": T(a + "f_proj.weight"),
                    "dt_bias": V(a + "dt_bias", "float32"),
                    "A_log": V(a + "A_log", "float32").reshape(-1),
                    "b_proj": T(a + "b_proj.weight"),
                    "g": T(a + "g_proj.weight"),
                    "o_norm": V(a + "o_norm.weight"),
                    "o": T(a + "dense.weight")})
            else:
                # The latent block reads the DeepSeek-V3 names; this
                # family calls the output projection `dense`.
                raw[a + "o_proj.weight"] = raw[a + "dense.weight"]
                layer.update(self.mla.load(a, T, V))
            m = p + "mlp."
            if i < self.first_dense:
                for x in ("gate", "up", "down"):
                    layer[x] = T(f"{m}{x}_proj.weight")
                params["layers"].append(layer)
                continue
            layer["gate_router"] = cast_array(raw[m + "gate.weight"].T,
                                              "float32")
            if self.routing.select_bias:
                layer["expert_bias"] = cast_array(
                    raw[m + "gate.expert_bias"], "float32")
            layer.update(stack_experts(
                raw, m + "experts.", self.share.held,
                ("gate_proj", "down_proj", "up_proj"), self.dtype))
            if self.shared_inter:
                for x in ("gate", "up", "down"):
                    layer["shared_" + x] = T(
                        f"{m}shared_experts.{x}_proj.weight")
            params["layers"].append(layer)
        return params
