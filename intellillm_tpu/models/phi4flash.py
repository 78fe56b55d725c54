"""Phi-4-mini-flash-reasoning (`model_type` "phi4flash"): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607) — Mamba-1 scans and 512-token
window attention in the first half, ONE full-attention layer, then gated
memory units and cross layers that read that one layer's keys and values.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
(3.85 B parameters, dense). Every layer, with `LN` a LayerNorm WITH bias
(`layer_norm_eps`) and no position encoding anywhere:

    h += mixer(LN(h)) ;  h += W2 (silu(g) * u),  [g | u] = W1 LN(h)

then a final LayerNorm and the head, which is the embedding (tied). The
mixer of layer `l` by `Phi4FlashConfig.layer_kind` (the model's own rule
over `num_hidden_layers`):

- "scan": Mamba-1 (`layers/mamba1.py`); the scan at `n / 2` also hands its
  output `m` (with the `D` skip, before the gate) to the GMUs;
- "window" / "full": differential attention (`layers/attention.py`
  `DifferentialAttention`), `W_qkv` and `W_o` with bias, over the last
  `sliding_window` tokens / the whole context;
- "gmu": `W_out (m * silu(W_in u))`, `m` the same token's row of the
  memory scan's output: no cache;
- "cross": the same attention with a query projection and `W_o` of its
  own over the FULL layer's keys and values: no cache of its own.

`lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)`,
`lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)`, four learned vectors a layer.

Two values are carried through the layer loop beside `h`, both of the
pass and never cached: `m`, and the full layer's new keys and values with
its cache entry (a cross layer hands the seams the same rows again, which
they write where they already are: `DifferentialAttention`). The cache
list has one entry a layer that HOLDS something
(`ModelConfig.get_layer_cache_kinds`: 9 "state", 8 "kv_window", 1 "kv" of
32 layers); `ModelConfig.get_layer_cache_entries` says which entry a layer
reads, a cross layer the full layer's.

tp > 1, LoRA, quantisation and speculative decoding are refused here;
prefix reuse and KV export / import by the engine, as for every model with
state slots or a window group.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.attention import DifferentialAttention
from intellillm_tpu.layers.mamba1 import mamba1_mixer
from intellillm_tpu.layers.normalization import layer_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator)
from intellillm_tpu.transformers_utils.configs.phi4flash import (CROSS, FULL,
                                                                 GMU, SCAN,
                                                                 WINDOW)

Params = Dict[str, object]
_F32 = jnp.float32
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class Phi4FlashForCausalLM:

    supports_lora = False
    supported_quantization = ()
    speculation_refusal = (
        "Speculative decoding is not supported for Phi-4-mini-flash: its "
        "scans keep one state a sequence, which a rejected draft token "
        "has already moved, and its window group has no teacher-forced "
        "program.")

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "Phi-4-mini-flash runs unquantised (bf16 / float32) only")
        self.config = cfg
        self.model_config = model_config
        self.dtype = model_config.dtype
        self.num_layers = cfg.num_hidden_layers
        self.kinds = [cfg.layer_kind(l) for l in range(self.num_layers)]
        self.entries = model_config.get_layer_cache_entries()
        self.hidden_size = cfg.hidden_size
        self.inter = cfg.intermediate_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.eps = cfg.layer_norm_eps
        self.inner = cfg.mamba_d_inner
        self.d_state = cfg.mamba_d_state
        self.dt_rank = cfg.mamba_dt_rank
        if cfg.mamba_d_conv != 4:
            raise NotImplementedError(
                f"mamba_d_conv {cfg.mamba_d_conv}: the filter's body "
                f"(ops/short_conv.py `conv_silu_body`) is written for 4 "
                f"taps")
        if (cfg.mb_per_layer != 2 or self.num_layers % 2
                or self.num_layers < 6 or cfg.mamba_proj_bias
                or not cfg.mamba_conv_bias or cfg.mlp_bias
                or not cfg.tie_word_embeddings or self.num_heads % 2
                or self.num_kv_heads % 2 or self.inner % 128):
            raise NotImplementedError(
                "only the published Phi-4-mini-flash setting is served: "
                "mb_per_layer 2 over an even number of layers (6 or more: "
                "the layout needs a GMU and a cross layer), a filter bias "
                "and no projection or MLP bias, a tied head, query and K/V "
                "heads in pairs, whole 128-lane tiles of scan channels")
        self.attn_full = DifferentialAttention(
            self.num_heads, self.head_dim, self.num_kv_heads)
        self.attn_window = DifferentialAttention(
            self.num_heads, self.head_dim, self.num_kv_heads,
            sliding_window=int(cfg.sliding_window))

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        h = params["embed_tokens"][input_ids]
        caches = list(kv_caches)
        memory = None       # m: the memory scan's output, [B, inner] f32
        shared = None       # the full layer's new (key, value)
        for l, (kind, entry) in enumerate(zip(self.kinds, self.entries)):
            lp = params["layers"][l]
            x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], self.eps)
            if kind == SCAN:
                with jax.named_scope("mamba1_mixer"):
                    out, y, caches[entry] = mamba1_mixer(
                        x, lp, caches[entry], attn_metadata, positions,
                        d_state=self.d_state, dt_rank=self.dt_rank)
                if l == self.config.memory_layer:
                    memory = y
            elif kind == GMU:
                out = self._gmu(lp, x, memory)
            else:
                out, caches[entry], shared = self._attention(
                    lp, l, kind, x, caches[entry], attn_metadata, shared)
            h = h + out
            x = layer_norm(h, lp["ln2_w"], lp["ln2_b"], self.eps)
            h = h + self._mlp(lp, x)
        h = layer_norm(h, params["norm_w"], params["norm_b"], self.eps)
        return h, caches

    def _mlp(self, lp, x):
        gu = x @ lp["fc1"]
        g, u = gu[..., :self.inter], gu[..., self.inter:]
        return (jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(
            x.dtype) @ lp["fc2"]

    def _gmu(self, lp, x, memory):
        b, l, e = x.shape
        gate = jax.nn.silu((x.reshape(b, e) @ lp["in_proj"]).astype(_F32))
        return ((memory * gate).astype(x.dtype)
                @ lp["out_proj"]).reshape(b, l, e)

    def _attention(self, lp, layer, kind, x, cache, attn_metadata, shared):
        b, l, _ = x.shape
        d, hq, hkv = self.head_dim, self.num_heads, self.num_kv_heads
        if kind == CROSS:
            q = x @ lp["q"] + lp["q_b"]
            k, v = shared
        else:
            qkv = x @ lp["qkv"] + lp["qkv_b"]
            q = qkv[..., :hq * d]
            k = qkv[..., hq * d:(hq + hkv) * d].reshape(b, l, hkv, d)
            v = qkv[..., (hq + hkv) * d:].reshape(b, l, hkv, d)
            if kind == FULL:
                shared = (k, v)
        lq1, lk1, lq2, lk2 = (lp[name].astype(_F32) for name in _LAMBDAS)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + lambda_init(layer))
        attn = self.attn_window if kind == WINDOW else self.attn_full
        out, cache = attn(q.reshape(b, l, hq, d), k, v, cache, attn_metadata,
                          lam=lam, lambda_init=lambda_init(layer),
                          subln=lp["subln"])
        return out @ lp["o"] + lp["o_b"], cache, shared

    def compute_logits(self, params: Params, hidden, lora=None):
        # Tied: contract on the embedding's own last axis (no transposed
        # copy of a [vocab, hidden] table). Logits leave the matmul in
        # float32 (models/lfm2_moe.py says why).
        return jnp.einsum("...e,ve->...v", hidden, params["embed_tokens"],
                          preferred_element_type=_F32)

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "Phi-4-mini-flash runs on one chip (tensor_parallel_size 1) in "
            "this engine: its scans' state pools (one matrix over all the "
            "scan's channels, which B and C are shared by), the GMUs' "
            "`m` (the memory scan's channels, which every later layer's "
            "shard would need) and the paired heads of differential "
            "attention (a stored head is two K/V heads) have no sharding "
            "rule yet, and its window group's pools none either")

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes)."""
        cfg = self.config
        e, d, inner = self.hidden_size, self.head_dim, self.inner
        hq, hkv = self.num_heads, self.num_kv_heads
        n, r = self.d_state, self.dt_rank
        layers = []
        for kind in self.kinds:
            layer = {"ln1_w": (e, ), "ln1_b": (e, ), "ln2_w": (e, ),
                     "ln2_b": (e, ), "fc1": (e, 2 * self.inter),
                     "fc2": (self.inter, e)}
            if kind == SCAN:
                layer.update({
                    "in_proj": (e, 2 * inner), "conv_w": (4, inner),
                    "conv_b": (inner, ), "x_proj": (inner, r + 2 * n),
                    "dt_proj": (r, inner), "dt_bias": (inner, ),
                    "A_log": (n, inner), "D": (inner, ),
                    "out_proj": (inner, e)})
            elif kind == GMU:
                layer.update({"in_proj": (e, inner), "out_proj": (inner, e)})
            else:
                if kind == CROSS:
                    layer.update({"q": (e, hq * d), "q_b": (hq * d, )})
                else:
                    layer.update({"qkv": (e, (hq + 2 * hkv) * d),
                                  "qkv_b": ((hq + 2 * hkv) * d, )})
                layer.update({"o": (hq * d, e), "o_b": (e, ),
                              "subln": (2 * d, )})
                layer.update({name: (d, ) for name in _LAMBDAS})
            layers.append(layer)
        return {"embed_tokens": (cfg.vocab_size, e), "norm_w": (e, ),
                "norm_b": (e, ), "layers": layers}

    # --- weights ---------------------------------------------------------

    def init_random_params(self, seed: int = 0) -> Params:
        """Seeded random weights for `--load-format dummy`; every array is
        waited for as it is drawn (models/axk1.py says why)."""
        dtype = jnp.dtype(self.dtype)
        key = jax.random.PRNGKey(seed)
        count = [0]
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        # A tied embedding at the scale that leaves logits O(1); what
        # writes to the stream adds a tenth of its magnitude (models/
        # axk1.py, PERF.md section 6, PR 26).
        embed = 1.0 / math.sqrt(self.hidden_size)
        writer = 0.1 * embed

        def ready(w, as_dtype=dtype):
            return jax.block_until_ready(w.astype(as_dtype))

        def draw(shape, name):
            count[0] += 1
            k = jax.random.fold_in(key, count[0])
            normal = jax.random.normal(k, shape, _F32)
            if name == "norm_w":
                # The final norm before a tied head: a gain of either
                # sign (models/lfm2_moe.py says why).
                return ready(normal)
            if name in ("ln1_w", "ln2_w", "subln"):
                # Not all ones, so that a norm's weight left out shows.
                return ready(1.0 + 0.1 * normal)
            if name.endswith("_b") and name != "conv_b":
                # A LayerNorm's or a projection's bias: a tenth of what
                # it is added to, so that one left out shows.
                return ready(0.1 * normal)
            if name == "A_log":
                # exp(A_log) in U(1, 16), as the family's layers start.
                return ready(jnp.log(jax.random.uniform(
                    k, shape, _F32, 1.0, 16.0)), _F32)
            if name == "dt_bias":
                # softplus^-1 of a step dt, log-uniform in [1e-3, 0.1].
                dt = jnp.exp(jax.random.uniform(
                    k, shape, _F32, math.log(1e-3), math.log(0.1)))
                return ready(dt + jnp.log(-jnp.expm1(-dt)), _F32)
            if name == "D":
                return ready(jnp.ones(shape, _F32), _F32)
            if name in ("conv_w", "conv_b"):
                # Four taps and a bias of comparable weight: one left out
                # shows.
                return ready(normal / 2.0)
            if name in _LAMBDAS:
                # lq . lk of a few tenths: lambda away from lambda_init.
                return ready(0.2 * normal, _F32)
            # Fan-in scaling keeps activations O(1).
            scale = (embed if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            w = normal * scale
            if name in ("o", "out_proj", "fc2"):
                w = w * writer
            if name in ("qkv", "q"):
                # Scores four times as wide as fan-in scaling gives: a
                # softmax over a few tokens, as trained heads have
                # (models/lfm2_moe.py). Queries and keys twice each; the
                # values as they are.
                cols = jnp.arange(shape[-1])
                w = w * jnp.where(cols < (hq + hkv) * d, 2.0, 1.0)
            return ready(w)

        def build(tree):
            if isinstance(tree, dict):
                return {k: (build(v) if isinstance(v, (dict, list))
                            else draw(v, k)) for k, v in tree.items()}
            return [build(v) for v in tree]

        return build(self._param_shapes())

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        """Checkpoint names as the hub's `modeling_phi4flash.py` gives
        them (the configuration file lists them under `assumed`): every
        mixer is `model.layers.N.attn.*` — an attention layer's `Wqkv`
        (a cross layer's holds the query rows alone), `out_proj`, the
        four `lambda_*` and `subln`; a scan's `in_proj`, `conv1d`,
        `x_proj`, `dt_proj`, `A_log` `[inner, N]`, `D`, `out_proj`; a
        GMU's `in_proj`, `out_proj` — beside `mlp.fc1` (gate then up),
        `mlp.fc2`, `input_layernorm`, `post_attention_layernorm`,
        `model.final_layernorm` and `model.embed_tokens`."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key, dtype=None):
            return cast_array(raw[key], dtype or self.dtype)

        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight"),
            "norm_w": V("model.final_layernorm.weight"),
            "norm_b": V("model.final_layernorm.bias"),
            "layers": [],
        }
        for i, kind in enumerate(self.kinds):
            p = f"model.layers.{i}."
            a = p + "attn."
            layer = {"ln1_w": V(p + "input_layernorm.weight"),
                     "ln1_b": V(p + "input_layernorm.bias"),
                     "ln2_w": V(p + "post_attention_layernorm.weight"),
                     "ln2_b": V(p + "post_attention_layernorm.bias"),
                     "fc1": T(p + "mlp.fc1.weight"),
                     "fc2": T(p + "mlp.fc2.weight")}
            if kind == SCAN:
                layer.update({
                    "in_proj": T(a + "in_proj.weight"),
                    # [channels, 1, taps] -> [taps, channels], tap 0 oldest.
                    "conv_w": cast_array(
                        raw[a + "conv1d.weight"][:, 0, :].T, self.dtype),
                    "conv_b": V(a + "conv1d.bias"),
                    "x_proj": T(a + "x_proj.weight"),
                    "dt_proj": T(a + "dt_proj.weight"),
                    "dt_bias": V(a + "dt_proj.bias", "float32"),
                    # [inner, N] -> [N, inner]: the state's own layout.
                    "A_log": cast_array(raw[a + "A_log"].T, "float32"),
                    "D": V(a + "D", "float32"),
                    "out_proj": T(a + "out_proj.weight")})
            elif kind == GMU:
                layer.update({"in_proj": T(a + "in_proj.weight"),
                              "out_proj": T(a + "out_proj.weight")})
            else:
                name = "q" if kind == CROSS else "qkv"
                layer.update({
                    name: T(a + "Wqkv.weight"),
                    name + "_b": V(a + "Wqkv.bias"),
                    "o": T(a + "out_proj.weight"),
                    "o_b": V(a + "out_proj.bias"),
                    "subln": V(a + "subln.weight")})
                layer.update({n: V(a + n, "float32") for n in _LAMBDAS})
            params["layers"].append(layer)
        return params
