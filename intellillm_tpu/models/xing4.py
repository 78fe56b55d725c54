"""Xing4.0 (`model_type` "xing4_0"): the DeepSeek-V3 block inside a
four-stream manifold-constrained hyper-connection residual.

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
(29 B parameters, 4 B a token). `n` = `hc_mult` streams `X [B, L, n, C]`:

    X_0[i] = embed(ids)                                for each stream
    layer:  X = HC(X, a -> MLA(RMSNorm_in(a)),   attn_hc)
            X = HC(X, f -> FFN(RMSNorm_post(f)), ffn_hc)
    logits = RMSNorm(sum_i X_L[i]) W_head

- `HC` is `layers/hyper_connections.py`'s equations through the seam
  `ops/hyper_connections.py` (`mhc_pre` / `mhc_post`: a Pallas kernel each on
  a TPU, on the streams held FLAT, `[B, L, n C]`, and in float32): every
  sublayer reads a per-token mix of the streams and writes to all of them
  through a per-token doubly stochastic matrix. There is no carried
  `(h, residual)` pair and no `fused_add_rms_norm`: the block norms act on
  the mixed `C`-wide vector.
- `MLA` is `layers/mla_attention.py` `LatentAttentionBlock`, the block
  `models/axk1.py` runs, at this model's widths.
- `FFN`: the first `first_k_dense_replace` layers SwiGLU of width
  `intermediate_size`; the others a `sigmoid` router over ALL experts,
  top-k of score + a per-expert bias (`topk_method` "noaux_tc": the bias
  chooses and is no part of the weights; `n_group` 1 = no group limit),
  weights normalised and scaled by `routed_scaling_factor`, plus a shared
  expert (`layers/moe.py` `routed_experts`).

Expert parallelism (`ep_size`, `ep_rank`: `layers/moe.py`
`ExpertShare.from_config`): the shared expert, attention, the mixes and
the dense layers are whole on every chip (a latent cache has no head axis
to divide); a share holds its experts and its rows of the vocabulary.

The layers after the dense ones are alike, so they are the turns of ONE
`lax.scan` (a 40-layer step program compiles one routed layer, not 38: forty
written out took XLA 90 s a program and the cell 21 minutes of set-up,
PERF.md section 6, PR 45). For that their parameters are stacked along a
leading axis (`params["moe_layers"]`; the expert stacks `[layers * held, I,
D]`, which `layers/moe.py` reads in place through `held_at`; the mixes'
parameters of ALL layers, the dense ones' too, in `params["attn_hc"]` /
`params["ffn_hc"]`, which the mixes' kernel reads in place by the layer's
number), and every layer's latent vectors lie in ONE pool `[blocks, layers, block_size, width]`
(`ModelConfig.get_packed_latent_layers`; `kv_caches` is the one-entry list
`[(pool,)]`, or `[(pool, stage)]` in the staged decode programs), of which
layer `l` sees its own rows (`layers/mla_attention.py` `_layer_view`).

Not built: the multi-token-prediction layer (`num_nextn_predict_layers`),
a drafter that adds nothing to the model's own logits; the engine has no
self-drafting path, so speculation is refused for this model
(`speculation_refusal`). tp > 1 is not supported (`partition_specs`).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.config import ModelConfig
from intellillm_tpu.layers.activation import get_act_fn
from intellillm_tpu.layers.hyper_connections import HyperConnections
from intellillm_tpu.layers.mla_attention import LatentAttentionBlock
from intellillm_tpu.layers.moe import (DENSE_LAYER, ExpertCounts, ExpertShare,
                                       Routing, random_gate_up_stack,
                                       routed_experts)
from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.models.weight_utils import (cast_array,
                                                hf_model_weights_iterator,
                                                stack_experts)
from intellillm_tpu.ops.hyper_connections import mhc_post, mhc_pre

Params = Dict[str, object]

_HC_NAMES = {"phi": "phi.weight", "alpha": "alpha", "b_pre": "b_pre",
             "b_post": "b_post", "b_res": "b_res"}
_MIXES = ("attn_hc", "ffn_hc")      # a layer's two, and the tree's stacks


class Xing4ForCausalLM:

    supports_lora = False
    supported_quantization = ()
    # engine/llm_engine.py raises it when speculation is asked for.
    speculation_refusal = (
        "Speculative decoding is not supported for this model: it ships a "
        "multi-token-prediction layer (num_nextn_predict_layers) that the "
        "engine neither builds nor loads, the engine has no self-drafting "
        "path (ROADMAP R5: worker/spec_decode/ needs a separate draft "
        "model), and no draft model shares its four-stream residual.")

    def __init__(self, model_config: ModelConfig) -> None:
        cfg = model_config.hf_config
        if model_config.quantization is not None:
            raise NotImplementedError(
                "Xing4.0 runs unquantised (bf16 / float32) only")
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
        self.n_held = cfg.n_routed_experts
        self.share = ExpertShare.from_config(
            cfg, self.n_held, self.top_k,
            max(self.num_layers - self.first_dense, 0))
        self.share.report_through(self)
        if cfg.n_group != 1 or cfg.topk_group != 1:
            raise NotImplementedError(
                f"Xing4.0 routes without a group limit (n_group 1, "
                f"topk_group 1); got {cfg.n_group} / {cfg.topk_group}")
        self.routing = Routing(
            scoring=cfg.scoring_func, select_bias=True,
            renormalize=bool(cfg.norm_topk_prob),
            scale=float(cfg.routed_scaling_factor),
            held_start=self.share.held_start)
        self.hc = HyperConnections.from_config(cfg)
        # worker/model_runner.py reads it for
        # intellillm_dispatch_stream_mix_bytes_total: the streams' bytes a
        # row moves through the 2 sublayers of every layer.
        self.mhc_bytes_per_row = 2 * self.num_layers * self.hc.mix_bytes(
            self.hidden_size, jnp.dtype(self.dtype).itemsize)
        self.mla = LatentAttentionBlock(cfg, model_config.max_model_len)
        if self.moe_freq != 1 or not 0 <= self.first_dense <= self.num_layers:
            raise NotImplementedError(
                "Xing4.0's routed layers are every layer after the first "
                f"first_k_dense_replace; got moe_layer_freq {self.moe_freq}")
        self.num_moe_layers = self.num_layers - self.first_dense

    # --- forward ---------------------------------------------------------

    def __call__(self, params: Params, input_ids, positions, kv_caches,
                 attn_metadata):
        (cache, ) = kv_caches       # every layer's: (pool,) | (pool, stage)
        h = params["embed_tokens"][input_ids]
        # The streams flat, [B, L, n C]: stream i the lanes [i C, (i + 1) C)
        # (ops/hyper_connections.py says why), and float32 from the
        # embedding to the head's sum: a sublayer's input and output are in
        # the model's dtype, the residual they are mixed into is not
        # rounded (`_layer`).
        x = jnp.tile(h, (1, 1, self.hc.n)).astype(jnp.float32)
        worst = jnp.zeros(h.shape[:2], jnp.float32)
        # Every layer's mixes, stacked: their kernel reads a layer's `phi`
        # out of the stack (a slice of it is a copy), dense layer or
        # scanned, so a program holds one kernel a sublayer.
        mixes = (params["attn_hc"], params["ffn_hc"])
        for i, lp in enumerate(params["dense_layers"]):
            x, cache, err, _ = self._layer(lp, mixes, x, cache,
                                           attn_metadata, positions, i)
            worst = jnp.maximum(worst, err)
        counts = ExpertCounts(self.share)
        if self.num_moe_layers:
            moe = dict(params["moe_layers"])
            stacks = tuple(moe.pop(k) for k in ("w1", "w2", "w3"))
            # Staged, the pool is read only: the scan closes over it and
            # carries the stage alone (a carried pool would be copied).
            fixed = cache[:1] if attn_metadata.staged else ()

            def turn(carry, xs):
                x, carried, worst, visited, held = carry
                lp, j = xs
                x, cache, err, (v, hl) = self._layer(
                    lp, mixes, x, fixed + carried, attn_metadata,
                    positions, self.first_dense + j, stacks + (j, ))
                if hl is not None:
                    held = held + hl
                return (x, cache[len(fixed):], jnp.maximum(worst, err),
                        visited + v, held), None

            (x, carried, worst, visited, held), _ = jax.lax.scan(
                turn, (x, cache[len(fixed):], worst, jnp.int32(0),
                       jnp.zeros(h.shape[:2], jnp.int32)),
                (moe, jnp.arange(self.num_moe_layers, dtype=jnp.int32)))
            cache = fixed + carried
            counts.add((visited, held if self.share.moe_choices_per_token
                        else None))
        x = x.reshape(h.shape[:2] + (self.hc.n, h.shape[-1]))
        h = rms_norm(x.sum(axis=-2).astype(h.dtype), params["norm"],
                     self.rms_eps)
        counters = counts.counters()
        # [B, L] f32: a token's largest |column sum - 1| over the call's
        # mixing matrices (worker/model_runner.py `_pack`).
        counters["mhc_col_error"] = worst
        return h, [cache], counters

    def _layer(self, lp, mixes, x, kv_cache, attn_metadata, positions,
               layer, stacks=None):
        """Layer `layer` (a Python int, or traced in the scan) over the
        streams. `mixes`: every layer's (attention, FFN) mixes, stacked.
        `stacks`: the routed layers' expert stacks and this layer's place
        among them (`routed_experts`), None for a dense layer. Returns
        (streams, kv_cache, a token's largest column error [B, L], the
        layer's counts for `ExpertCounts.add`)."""
        mesh = attn_metadata.mesh
        attn_hc, ffn_hc = mixes
        dtype = lp["input_norm"].dtype          # the model's
        # The streams are float32 and so is the mixed vector `u` until its
        # norm has read it (ops/pallas/hyper_connections.py says why); the
        # sublayers compute in the model's dtype.
        u, coef, err = mhc_pre(x, attn_hc, self.hc, layer, mesh)
        y, kv_cache = self.mla(
            lp, rms_norm(u, lp["input_norm"], self.rms_eps).astype(dtype),
            kv_cache, attn_metadata, positions, layer=layer)
        x = mhc_post(x, y, coef, self.hc, mesh)
        u, coef, ffn_err = mhc_pre(x, ffn_hc, self.hc, layer, mesh)
        err = jnp.maximum(err, ffn_err)
        f = rms_norm(u, lp["post_attn_norm"], self.rms_eps).astype(dtype)
        if stacks is None:
            y = (self.act(f @ lp["gate"]) * (f @ lp["up"])) @ lp["down"]
            layer_counts = DENSE_LAYER
        else:
            y, layer_counts = routed_experts(
                f, lp, self.share, self.routing, mesh=mesh,
                activation=self.config.hidden_act.lower(), stacks=stacks,
                live=attn_metadata.live_rows)
        return mhc_post(x, y, coef, self.hc, mesh), kv_cache, err, layer_counts

    def compute_logits(self, params: Params, hidden, lora=None):
        return hidden @ params["lm_head"]

    def partition_specs(self):
        """Only asked for when the mesh spans chips (parallel/mesh.py
        `shard_params`)."""
        raise NotImplementedError(
            "Xing4.0 runs on one chip (tensor_parallel_size 1) in this "
            "engine: its latent cache has no head axis to shard, and its "
            "experts are placed by the config's ep_size / ep_rank (one "
            "chip's share of an expert-parallel deployment), not by the "
            "mesh")

    def _layer_shapes(self, moe: bool):
        """One layer's entries (leaves: shapes), its two mixes among them
        (`layer_params` hands them over so; in the param tree they lie in
        the stacks over all layers, `_MIXES`)."""
        cfg = self.config
        e = self.hidden_size
        layer = {"input_norm": (e, ), "post_attn_norm": (e, ),
                 **self.mla.param_shapes(e)}
        if moe:
            layer.update(self.share.param_shapes(
                e, self.moe_inter, select_bias=True,
                shared_inter=self.moe_inter * self.n_shared))
        else:
            inter = cfg.intermediate_size
            layer.update({"gate": (e, inter), "up": (e, inter),
                          "down": (inter, e)})
        layer["attn_hc"] = self.hc.param_shapes(e)
        layer["ffn_hc"] = self.hc.param_shapes(e)
        return layer

    def _param_shapes(self):
        """The param tree's structure (leaves: shapes): the dense layers
        one dict each, the routed layers ONE dict whose leaves carry the
        layers along a leading axis (the expert stacks: layers x held
        experts along their first), and the two mixes of EVERY layer,
        dense or routed, in one stack each over all the layers."""
        cfg = self.config
        e = self.hidden_size
        n = self.num_moe_layers

        def stacked(tree, layers, name=None):
            if isinstance(tree, dict):
                return {k: stacked(v, layers, k) for k, v in tree.items()
                        if k not in _MIXES}
            if name in ("w1", "w2", "w3"):
                return (layers * tree[0], ) + tuple(tree[1:])
            return (layers, ) + tuple(tree)

        dense = {k: v for k, v in self._layer_shapes(False).items()
                 if k not in _MIXES}
        tree = {"embed_tokens": (cfg.vocab_size, e), "norm": (e, ),
                "lm_head": (e, cfg.vocab_size),
                "dense_layers": [dict(dense)
                                 for _ in range(self.first_dense)]}
        if n:
            tree["moe_layers"] = stacked(self._layer_shapes(True), n)
        for hc in _MIXES:
            tree[hc] = stacked(self.hc.param_shapes(e), self.num_layers)
        return tree

    def layer_params(self, params: Params, i: int) -> Params:
        """Layer `i`'s own entries of `params`, as `_layer_shapes` names
        them (for a caller that walks the layers one by one: the
        reference checks)."""
        mixes = {hc: {k: v[i] for k, v in params[hc].items()}
                 for hc in _MIXES}
        if i < self.first_dense:
            return {**params["dense_layers"][i], **mixes}
        j, n = i - self.first_dense, self.share.n_held

        def own(tree, name=None):
            if isinstance(tree, dict):
                return {k: own(v, k) for k, v in tree.items()}
            if name in ("w1", "w2", "w3"):
                return tree[j * n:(j + 1) * n]
            return tree[j]

        return {**own(params["moe_layers"]), **mixes}

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
            normal = jax.random.normal(k, shape, jnp.float32)
            if name in ("b_pre", "b_post", "b_res"):
                # The paper starts its gates small, where every Hres is
                # near-uniform and a wrong Sinkhorn would pass: these are
                # drawn far from there, as trained mixes are (logits of
                # scale 1: the matrices differ a token and a sublayer).
                return jax.block_until_ready(normal)
            if name == "alpha":
                return jax.block_until_ready(jax.random.uniform(
                    k, shape, jnp.float32, 0.5, 1.5))
            if name == "phi":
                # Fan-in scaled over the unit-RMS statistic: logits O(1).
                return jax.block_until_ready(normal / math.sqrt(shape[-1]))
            if name == "expert_bias":
                # As large as the scores' own spread (models/lfm2_moe.py).
                return jax.block_until_ready(0.5 * normal)
            if name.endswith("norm"):
                # Not all ones, so that a norm's weight left out shows.
                return jax.block_until_ready(
                    (1.0 + 0.1 * normal).astype(dtype))
            if name in ("w1", "w3"):
                n, i, d = shape
                return random_gate_up_stack(k, n, d, i, dtype)
            # Fan-in scaling; what writes to the streams a tenth of that
            # (models/axk1.py, PERF.md section 6, PR 26).
            scale = (1.0 if name == "embed_tokens"
                     else 1.0 / math.sqrt(shape[-2]))
            if name in ("o", "down", "w2", "shared_down"):
                scale *= 0.1
            w = normal * scale
            if name == "gate_router":
                return jax.block_until_ready(w)       # float32 router
            return jax.block_until_ready(w.astype(dtype))

        def build(tree):
            if isinstance(tree, dict):
                return {k: (build(v) if isinstance(v, (dict, list))
                            else draw(v, k)) for k, v in tree.items()}
            return [build(v) for v in tree]

        # The routed layers leaf by leaf, each layer's draw written into
        # the stacked array as it is made (donated: one stacked array and
        # one layer's piece are on the device at a time, never two
        # copies of 9 GB of stacks).
        put = jax.jit(
            lambda whole, piece, at: jax.lax.dynamic_update_slice_in_dim(
                whole, piece, at, axis=0), donate_argnums=0)

        def build_stacked(tree):
            out = {}
            for name, shape in tree.items():
                if isinstance(shape, dict):
                    out[name] = build_stacked(shape)
                    continue
                whole = None
                for j in range(self.num_moe_layers):
                    piece = draw(shape, name)
                    if name not in ("w1", "w2", "w3"):
                        piece = piece[None]
                    if whole is None:
                        whole = jnp.zeros(
                            (self.num_moe_layers * piece.shape[0], )
                            + piece.shape[1:], piece.dtype)
                    whole = put(whole, piece, j * piece.shape[0])
                out[name] = jax.block_until_ready(whole)
            return out

        e = self.hidden_size
        params = build({
            "embed_tokens": (self.config.vocab_size, e), "norm": (e, ),
            "lm_head": (e, self.config.vocab_size),
            "dense_layers": [self._layer_shapes(False)
                             for _ in range(self.first_dense)]})
        if self.num_moe_layers:
            params["moe_layers"] = build_stacked(self._layer_shapes(True))
        # Drawn with their layers (a seed's weights are what they were),
        # the mixes then go into their stacks over all the layers.
        for hc in _MIXES:
            parts = [jax.tree.map(lambda v: v[None], lp.pop(hc))
                     for lp in params["dense_layers"]]
            if self.num_moe_layers:
                parts.append(params["moe_layers"].pop(hc))
            params[hc] = jax.block_until_ready(jax.tree.map(
                lambda *v: jnp.concatenate(v), *parts))
        return params

    def load_weights(self, model_name_or_path: str,
                     load_format: str = "auto",
                     revision: Optional[str] = None) -> Params:
        """Checkpoint names ASSUMED after the DeepSeek-V3 family's
        (`LatentAttentionBlock.load`; `mlp.gate` with its
        `e_score_correction_bias`, `mlp.experts.N`, `mlp.shared_experts`),
        with `attn_hc.*` / `ffn_hc.*` (`phi.weight` [2n + n^2, nC],
        `alpha` [3], `b_pre`, `b_post`, `b_res`) for the mixes. A share
        loads its own experts' slices and its rows of the vocabulary. A
        prediction layer's tensors (`model.layers.<num_hidden_layers>.*`)
        are left in the checkpoint."""
        raw: Dict[str, np.ndarray] = dict(hf_model_weights_iterator(
            model_name_or_path, load_format, revision))

        def T(key):       # [out, in] -> [in, out]
            return cast_array(raw[key].T, self.dtype)

        def V(key):
            return cast_array(raw[key], self.dtype)

        def F(key):
            return cast_array(raw[key], "float32")

        # A share whose config names an `ep_size`-th of the checkpoint's
        # vocabulary takes its rank's rows; otherwise the first `vocab`.
        vocab = self.config.vocab_size
        have = raw["model.embed_tokens.weight"].shape[0]
        first = (self.share.ep_rank * vocab
                 if have == self.share.ep_size * vocab else 0)
        rows = slice(first, first + vocab)
        params: Params = {
            "embed_tokens": V("model.embed_tokens.weight")[rows],
            "norm": V("model.norm.weight"),
            "lm_head": T("lm_head.weight")[:, rows],
            "dense_layers": [],
        }
        moe_layers = []
        for i in range(self.num_layers):
            p = f"model.layers.{i}."
            layer = {
                "input_norm": V(p + "input_layernorm.weight"),
                "post_attn_norm": V(p + "post_attention_layernorm.weight"),
                **self.mla.load(p + "self_attn.", T, V),
            }
            m = p + "mlp."
            if i >= self.first_dense:
                layer["gate_router"] = cast_array(raw[m + "gate.weight"].T,
                                                  "float32")
                layer["expert_bias"] = F(m + "gate.e_score_correction_bias")
                layer.update(stack_experts(
                    raw, m + "experts.", self.share.held,
                    ("gate_proj", "down_proj", "up_proj"), self.dtype))
                if self.n_shared:
                    for x in ("gate", "up", "down"):
                        layer["shared_" + x] = T(
                            f"{m}shared_experts.{x}_proj.weight")
            else:
                for x in ("gate", "up", "down"):
                    layer[x] = T(f"{m}{x}_proj.weight")
            (moe_layers if i >= self.first_dense
             else params["dense_layers"]).append(layer)
        if moe_layers:
            # The layers along a leading axis; the expert stacks end to end.
            def stacked(trees, name=None):
                if isinstance(trees[0], dict):
                    return {k: stacked([t[k] for t in trees], k)
                            for k in trees[0]}
                return (np.concatenate if name in ("w1", "w2", "w3")
                        else np.stack)(trees)

            params["moe_layers"] = stacked(moe_layers)
        for hc in _MIXES:
            params[hc] = {ours: np.stack([
                F(f"model.layers.{i}.{hc}.{theirs}")
                for i in range(self.num_layers)])
                for ours, theirs in _HC_NAMES.items()}
        return params
