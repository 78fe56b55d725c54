"""Falcon-H1 (`model_type` "falcon_h1"): in EVERY layer a Mamba-2
selective state-space scan and a RoPE GQA attention side by side, both
reading one normed input, their outputs summed into the stream; then a
SwiGLU MLP; twelve scalars of the maximal-update parametrisation (muP).

Source: https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
(72 layers, 34 B parameters; no kin of the 2023 Falcon in `models/falcon.py`).
With `h` the residual stream, RMSNorm (`rms_norm_eps`) throughout:

    h_0 = embed(ids) * embedding_multiplier
    layer l:
      x = RMSNorm_in(h)
      m = Mamba2(x * ssm_in_multiplier) * ssm_out_multiplier
      a = Attn(x * attention_in_multiplier) * attention_out_multiplier
      h = h + (m + a)
      f = RMSNorm_preff(h)
      h = h + W_d(silu(W_g f * mlp_multipliers[0]) * (W_u f))
              * mlp_multipliers[1]
    logits = (RMSNorm_final(h_L) W_head) * lm_head_multiplier    (untied)

`Mamba2` is `layers/mamba2.py` with `mamba_n_groups` groups of B and C (head
h reads group `h // (H / G)`), the five `ssm_multipliers` on the five
segments `[z | x | B | C | dt]` of the in-projection's OUTPUT, and the gated
norm (gate first) over a group's channels at a time. `Attn`: `q`, `k`, `v`
without bias, `k` times `key_multiplier` BEFORE RoPE (rotate-half, theta
`rope_theta`, float32 tables), causal softmax at `head_dim ** -0.5`.

Every multiplier is applied where the equations put it, in float32 where it
meets a residual write or the logits; none is folded into a weight, so a
checkpoint's tensors stay the published ones.

A layer holds BOTH caches: `kv_caches[2 l]` is its (K, V) pool pair and
`kv_caches[2 l + 1]` the (taps, matrix) pools of `ModelConfig.
get_state_arrays` (`ModelConfig.get_layer_cache_kinds` says why two
entries).

tp > 1 is not supported for this model yet (`partition_specs` says so);
prefix reuse, speculation and KV export / import are refused at start-up or
at the request as for every model with a per-sequence state
(`engine/llm_engine.py`), LoRA and quantisation here.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.attention import PagedAttention
from intellillm_tpu.layers.mamba2 import mamba2_mixer, segment_vector
from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.layers.rotary_embedding import get_rope
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator)

Params = Dict[str, object]
_F32 = jnp.float32


def _scaled(x, by: float):
    """`x * by`, the product taken in float32."""
    return (x.astype(_F32) * by).astype(x.dtype)


class FalconH1ForCausalLM:

    supports_lora = False
    supported_quantization = ()

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "Falcon-H1 runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.hidden_size = cfg.hidden_size
        self.inter = cfg.intermediate_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = model_config.get_head_size()
        self.eps = cfg.rms_norm_eps
        self.ssm_heads = cfg.mamba_n_heads
        self.ssm_dim = cfg.mamba_d_head
        self.ssm_state = cfg.mamba_d_state
        self.ssm_groups = cfg.mamba_n_groups
        self.ssm_inner = self.ssm_heads * self.ssm_dim
        if cfg.mamba_d_conv != 4:
            raise NotImplementedError(
                f"mamba_d_conv {cfg.mamba_d_conv}: the filter's body "
                f"(ops/short_conv.py `conv_silu_body`) is written for 4 "
                f"taps")
        if (self.ssm_inner != cfg.mamba_d_ssm
                or self.ssm_heads % self.ssm_groups
                or cfg.mamba_proj_bias or not cfg.mamba_conv_bias
                or not cfg.mamba_rms_norm or cfg.mamba_norm_before_gate
                or getattr(cfg, "attention_bias", False)
                or getattr(cfg, "mlp_bias", False)
                or getattr(cfg, "projectors_bias", False)
                or getattr(cfg, "attn_layer_indices", None) is not None
                or not getattr(cfg, "mamba_use_mlp", True)
                or cfg.rope_scaling or cfg.tie_word_embeddings):
            raise NotImplementedError(
                "only the published Falcon-H1 setting is served: "
                "mamba_d_ssm = mamba_n_heads x mamba_d_head, whole heads a "
                "group, mamba_conv_bias true, the gated RMSNorm with the "
                "gate first (mamba_rms_norm true, mamba_norm_before_gate "
                "false), no projection, attention or MLP bias, attention "
                "and an MLP in every layer, no RoPE scaling, an untied "
                "head")
        self.embedding_multiplier = float(cfg.embedding_multiplier)
        self.lm_head_multiplier = float(cfg.lm_head_multiplier)
        self.ssm_in_multiplier = float(cfg.ssm_in_multiplier)
        self.ssm_out_multiplier = float(cfg.ssm_out_multiplier)
        self.ssm_multipliers = tuple(float(m) for m in cfg.ssm_multipliers)
        self.attention_in_multiplier = float(cfg.attention_in_multiplier)
        self.attention_out_multiplier = float(cfg.attention_out_multiplier)
        self.key_multiplier = float(cfg.key_multiplier)
        self.gate_multiplier, self.down_multiplier = (
            float(m) for m in cfg.mlp_multipliers)
        # Float32 tables (at theta 1e11 the slowest pairs turn by 1e-10 a
        # token), of the positions a sequence can reach and not of all
        # 262,144.
        self.rope = get_rope(
            self.head_dim, self.head_dim,
            min(cfg.max_position_embeddings, model_config.max_model_len),
            cfg.rope_theta, is_neox_style=True)
        self.attn = PagedAttention(self.num_heads, self.head_dim,
                                   self.head_dim**-0.5, self.num_kv_heads)

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = _scaled(params["embed_tokens"][input_ids],
                    self.embedding_multiplier)
        new_caches = []
        for i in range(self.num_layers):
            h, kv, state = self._layer(
                params["layers"][i], h, kv_caches[2 * i],
                kv_caches[2 * i + 1], attn_metadata, positions)
            new_caches += [kv, state]
        return rms_norm(h, params["norm"], self.eps), new_caches

    def _attention(self, lp, x, positions, kv_cache, attn_metadata):
        b, l, _ = x.shape
        d = self.head_dim
        q = (x @ lp["q"]).reshape(b, l, self.num_heads, d)
        k = _scaled(x @ lp["k"], self.key_multiplier).reshape(
            b, l, self.num_kv_heads, d)
        v = (x @ lp["v"]).reshape(b, l, self.num_kv_heads, d)
        q, k = self.rope(positions, q, k)
        out, kv_cache = self.attn(q, k, v, kv_cache, attn_metadata)
        return out.reshape(b, l, self.num_heads * d) @ lp["o"], kv_cache

    def _layer(self, lp, h, kv_cache, state_cache, attn_metadata, positions):
        x = rms_norm(h, lp["input_norm"], self.eps)
        with jax.named_scope("falcon_h1_mamba"):
            m, state_cache = mamba2_mixer(
                _scaled(x, self.ssm_in_multiplier), lp, state_cache,
                attn_metadata, positions, num_heads=self.ssm_heads,
                head_dim=self.ssm_dim, d_state=self.ssm_state, eps=self.eps,
                groups=self.ssm_groups, segment_scales=self.ssm_multipliers)
        with jax.named_scope("falcon_h1_attention"):
            a, kv_cache = self._attention(
                lp, _scaled(x, self.attention_in_multiplier), positions,
                kv_cache, attn_metadata)
        # The residual writes: the sum and both multipliers in float32.
        h = (h.astype(_F32)
             + self.ssm_out_multiplier * m.astype(_F32)
             + self.attention_out_multiplier * a.astype(_F32)).astype(h.dtype)
        with jax.named_scope("falcon_h1_mlp"):
            f = rms_norm(h, lp["pre_ff_norm"], self.eps)
            gate = _scaled(f @ lp["gate"], self.gate_multiplier)
            out = (jax.nn.silu(gate) * (f @ lp["up"])) @ lp["down"]
        h = (h.astype(_F32)
             + self.down_multiplier * out.astype(_F32)).astype(h.dtype)
        return h, kv_cache, state_cache

    def compute_logits(self, params: Params, hidden, lora=None):
        # The head as a checkpoint holds it, [vocab, hidden]: contract on
        # its last axis (no transposed copy of 2.7 GB). Logits leave the
        # matmul in float32 (models/lfm2_moe.py says why) and meet their
        # multiplier there.
        return jnp.einsum("...e,ve->...v", hidden, params["lm_head"],
                          preferred_element_type=_F32) \
            * self.lm_head_multiplier

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "Falcon-H1 runs on one chip (tensor_parallel_size 1) in this "
            "engine: its state pools (one matrix over all the scan's "
            "heads) and the in-projection's segments ([z | xBC | dt], "
            "filtered together, B and C a group of heads) have no "
            "sharding rule over heads yet")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        e, d, v = self.hidden_size, self.head_dim, self.config.vocab_size
        inner, hs = self.ssm_inner, self.ssm_heads
        bc = self.ssm_groups * self.ssm_state
        layer = {
            "input_norm": (e, ), "pre_ff_norm": (e, ),
            "in_proj": (e, 2 * inner + 2 * bc + hs),
            "conv_w": (4, inner + 2 * bc), "conv_b": (inner + 2 * bc, ),
            "dt_bias": (hs, ), "A_log": (hs, ), "D": (hs, ),
            "gate_norm": (inner, ), "out_proj": (inner, e),
            "q": (e, self.num_heads * d), "k": (e, self.num_kv_heads * d),
            "v": (e, self.num_kv_heads * d), "o": (self.num_heads * d, e),
            "gate": (e, self.inter), "up": (e, self.inter),
            "down": (self.inter, e)}
        return {"embed_tokens": (v, e), "norm": (e, ), "lm_head": (v, e),
                "layers": [dict(layer) for _ in range(self.num_layers)]}

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`; every array is
        waited for as it is drawn (models/axk1.py says why).

        The multipliers are muP's: trained weights are large where a
        multiplier is small. A fan-in draw that ignored them would leave
        the branches at 1e-2 or 1e+1 of the stream, and a branch left out
        would hide under any limit. So every matrix is drawn at the
        fan-in scale DIVIDED BY the multipliers between its input and its
        output's next nonlinearity or write, which leaves, with the
        multipliers in place:

        - the stream at O(1): `embed_tokens` at 1 / embedding_multiplier;
        - every segment of the in-projection's output at O(1) before the
          filter, the gate and the softplus: its columns at fan-in /
          (ssm_in_multiplier x that segment's multiplier);
        - attention scores four wide (a softmax over a few tokens, as
          trained heads have: models/lfm2_moe.py): `q` and `k` at 2 x
          fan-in / attention_in_multiplier, `k` also / key_multiplier;
        - the MLP's gate at O(1) before the SiLU: `gate` at fan-in /
          mlp_multipliers[0];
        - each of the three writers (`out_proj`, `o`, `down`) adding a
          fifth of the stream's magnitude: fan-in x 0.2 / its own output
          multiplier;
        - logits of O(1): `lm_head` at fan-in / lm_head_multiplier.
        """
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]
        e, inner = self.hidden_size, self.ssm_inner
        fan = 1.0 / math.sqrt(e)
        writer = 0.2
        segments = segment_vector(
            1.0 / (self.ssm_in_multiplier * np.asarray(self.ssm_multipliers)),
            inner, self.ssm_groups, self.ssm_state, self.ssm_heads)
        scales = {
            "embed_tokens": 1.0 / self.embedding_multiplier,
            "lm_head": fan / self.lm_head_multiplier,
            "in_proj": fan * segments,
            "out_proj": writer / math.sqrt(inner) / self.ssm_out_multiplier,
            "q": 2.0 * fan / self.attention_in_multiplier,
            "k": 2.0 * fan / (self.attention_in_multiplier
                              * self.key_multiplier),
            "v": fan / self.attention_in_multiplier,
            "o": (writer / math.sqrt(self.num_heads * self.head_dim)
                  / self.attention_out_multiplier),
            "gate": fan / self.gate_multiplier,
            "up": fan,
            "down": writer / math.sqrt(self.inter) / self.down_multiplier,
            # Four taps of comparable weight and a bias as large as a
            # tap's term: a tap or the bias left out shows.
            "conv_w": 0.5, "conv_b": 0.5,
        }

        def table(k, rows, scale, pieces=8):
            """A [vocab, hidden] table a piece of its rows at a time: the
            float32 draw of 261,120 x 5,120 would be 5.3 GB."""
            pieces = pieces if rows % pieces == 0 else 1
            draw = jax.jit(lambda ks: jax.lax.map(
                lambda kk: (jax.random.normal(kk, (rows // pieces, e), _F32)
                            * scale).astype(dtype), ks))
            return draw(jax.random.split(k, pieces)).reshape(rows, e)

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            if name in ("embed_tokens", "lm_head"):
                return jax.block_until_ready(
                    table(k, shape[0], scales[name]))
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                w = 1.0 + 0.1 * jax.random.normal(k, shape, _F32)
                return jax.block_until_ready(w.astype(dtype))
            if name == "A_log":
                # exp(A_log) in U(1, 16), as the family's layers start.
                return jax.block_until_ready(jnp.log(
                    jax.random.uniform(k, shape, _F32, 1.0, 16.0)))
            if name == "dt_bias":
                # softplus^-1 of a step dt, log-uniform in [1e-3, 0.1].
                dt = jnp.exp(jax.random.uniform(
                    k, shape, _F32, math.log(1e-3), math.log(0.1)))
                return jax.block_until_ready(dt + jnp.log(-jnp.expm1(-dt)))
            if name == "D":
                # Not all ones: a D read as 1 shows.
                return jax.block_until_ready(
                    1.0 + 0.3 * jax.random.normal(k, shape, _F32))
            w = jax.random.normal(k, shape, _F32) * scales[name]
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
        """Checkpoint names after HF's `FalconH1*` modules (the
        configuration file lists them under `assumed`):
        `model.layers.N.{input_layernorm, pre_ff_layernorm}`,
        `model.layers.N.mamba.{in_proj, conv1d.weight, conv1d.bias, dt_bias,
        A_log, D, norm, out_proj}`, `model.layers.N.self_attn.{q_proj,
        k_proj, v_proj, o_proj}`, `model.layers.N.feed_forward.{gate_proj,
        up_proj, down_proj}`, `model.embed_tokens`, `model.final_layernorm`,
        `lm_head`."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key, dtype=None):
            return cast_array(raw[key], dtype or self.dtype)

        rows = self.config.vocab_size
        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight")[:rows],
            "norm": V("model.final_layernorm.weight"),
            "lm_head": V("lm_head.weight")[:rows],       # [vocab, hidden]
            "layers": [],
        }
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            m, a, f = p + "mamba.", p + "self_attn.", p + "feed_forward."
            params["layers"].append({
                "input_norm": V(p + "input_layernorm.weight"),
                "pre_ff_norm": V(p + "pre_ff_layernorm.weight"),
                "in_proj": T(m + "in_proj.weight"),
                # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                "conv_w": cast_array(raw[m + "conv1d.weight"][:, 0, :].T,
                                     self.dtype),
                "conv_b": V(m + "conv1d.bias"),
                "dt_bias": V(m + "dt_bias", "float32"),
                "A_log": V(m + "A_log", "float32"),
                "D": V(m + "D", "float32"),
                "gate_norm": V(m + "norm.weight"),
                "out_proj": T(m + "out_proj.weight"),
                "q": T(a + "q_proj.weight"), "k": T(a + "k_proj.weight"),
                "v": T(a + "v_proj.weight"), "o": T(a + "o_proj.weight"),
                "gate": T(f + "gate_proj.weight"),
                "up": T(f + "up_proj.weight"),
                "down": T(f + "down_proj.weight")})
        return params
