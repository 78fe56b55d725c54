"""Latent (MLA) attention layer: absorbed projections over the latent pool.

Multi-head latent attention caches, for each token and layer, one vector
`[c_kv | k_rope]` (`kv_lora_rank` + `qk_rope_head_dim` values) instead of
per-head keys and values. The published (expanded) form up-projects the
whole context in every step: `[k_nope | v]_h = c_kv W_kvb,h`. This layer
runs the ABSORBED form, equal in exact arithmetic, in which the
up-projection moves onto the query and the output:

    q~_h = q_nope,h W_UK,h^T                  (kv_lora_rank wide)
    s_h  = (q~_h . c_kv + q_rope,h . k_rope) * scale
    o^_h = sum_t softmax(s_h)_t c_kv,t        (kv_lora_rank wide)
    o_h  = o^_h W_UV,h

so a step reads each cached vector once and nothing of the context is
up-projected. `LatentAttentionBlock` is the whole attention sublayer of a
DeepSeek-V3-style block (the q / kv low-rank projections, their norms, the
RoPE split, the absorption through `w_uk` / `w_uv`, `W_o`), parametrised by
the widths and written once for the families that run it (`models/axk1.py`,
`models/xing4.py`: each held this block before PR 45 made it a layer's);
it makes `q_lat = [q~ | q_rope]` and applies `W_UV`. `LatentAttention`
owns the cache write, the two program shapes (the `mixed` step and the
staged K-step decode) and the seams (`ops/mla_attention.py`).

Stored width: the cached vector and the query are zero-padded to whole
128-lane tiles (576 -> 640); zero lanes add nothing to a score and the
value is the first `kv_lora_rank` lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from intellillm_tpu.layers.attention import AttentionMetadata
from intellillm_tpu.layers.normalization import rms_norm
from intellillm_tpu.layers.rotary_embedding import (_yarn_get_mscale,
                                                    get_rope)
from intellillm_tpu.ops.attention import (merge_attention_parts,
                                          staged_decode_attention)
from intellillm_tpu.ops.mla_attention import (mla_fused_attention,
                                              mla_pool_attention)


class LatentAttention:
    """Stateless; weights live in the caller's param tree."""

    def __init__(self, num_heads: int, kv_lora_rank: int, rope_dim: int,
                 scale: float) -> None:
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.rope_dim = rope_dim
        self.scale = scale
        self.width = kv_lora_rank + rope_dim
        self.stored_width = -(-self.width // 128) * 128
        # Never ALiBi (layers/attention.py model_uses_alibi looks for it).
        self.alibi_slopes = None

    def _pad(self, x: jnp.ndarray) -> jnp.ndarray:
        pad = self.stored_width - x.shape[-1]
        if pad == 0:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def __call__(
        self,
        q_lat: jnp.ndarray,    # [B, L, H, width]  [q~ | q_rope]
        kv_new: jnp.ndarray,   # [B, L, width]     [c_kv | k_rope]
        kv_cache,              # (pool,), or (pool, stage) when staged
        attn_metadata: AttentionMetadata,
        layer=None,
    ):
        """Returns (o^ [B, L, H, kv_lora_rank], kv_cache). `layer`: the
        layer's index (a traced scalar inside a scan over the layers) for
        a model whose layers share ONE pool `[NB, layers, BS, Dk]`
        (`ModelConfig.get_packed_latent_layers`); None where a layer has
        a pool of its own."""
        mesh = attn_metadata.mesh
        if mesh is not None and mesh.devices.size > 1:
            raise NotImplementedError(
                "latent attention has no head axis to shard the pool "
                "over; tensor parallelism is not supported for it yet")
        b, l, h, _ = q_lat.shape
        q = self._pad(q_lat).reshape(b * l, h, self.stored_width)
        new = self._pad(kv_new).reshape(b * l, self.stored_width)
        with jax.named_scope("mla_attention"):
            if attn_metadata.staged:
                out, kv_cache = self._staged(q, new, kv_cache,
                                             attn_metadata, layer)
            else:
                (pool, ) = kv_cache
                view, tables, slots = _layer_view(
                    pool, attn_metadata.block_tables,
                    attn_metadata.slot_mapping.reshape(-1), layer)
                out, view = mla_fused_attention(
                    q, new, view, slots, tables, attn_metadata.context_lens,
                    self.scale, self.kv_lora_rank)
                kv_cache = (view.reshape(pool.shape), )
        return out.reshape(b, l, h, self.kv_lora_rank), kv_cache

    def _staged(self, q, new, kv_cache, attn_metadata, layer=None):
        """Fused multi-step decode: the pool is read-only; the substep's
        vector goes into the staging buffer at stage_index, and the pool
        part (kernel, with logsumexp) and the stage part are merged. With
        the layers in one pool the stage is `[B, C, layers, Dk]` too."""
        pool, stage = kv_cache                 # stage [B, C, 1, Dk]
        k_idx = attn_metadata.stage_index
        new = new[:, None, None, :].astype(stage.dtype)
        if layer is None:
            own = stage = jax.lax.dynamic_update_slice_in_dim(
                stage, new, k_idx, axis=1)
        else:
            stage = jax.lax.dynamic_update_slice(
                stage, new, (0, k_idx, layer, 0))
            own = jax.lax.dynamic_slice_in_dim(stage, layer, 1, axis=2)
        view, tables, _ = _layer_view(pool, attn_metadata.block_tables,
                                      None, layer)
        out_pool, lse_pool = mla_pool_attention(
            q, view, tables, attn_metadata.context_lens, self.scale,
            self.kv_lora_rank)
        out_stage, lse_stage = staged_decode_attention(
            q[:, None], own, own, k_idx, self.scale)
        out = merge_attention_parts(
            out_pool[:, None], lse_pool,
            out_stage[..., :self.kv_lora_rank], lse_stage)
        return out[:, 0], (pool, stage)


def _layer_view(pool, block_tables, slots, layer):
    """(pool, tables, slots) as layer `layer` of a pool `[NB, layers, BS,
    Dk]` sees them: the pool as `[NB * layers, 1, BS, Dk]` (the same
    bytes), block `b` at `b * layers + layer`, a token's slot moved with
    its block; a slot outside the pool (a pad row's) stays outside.
    Unchanged where `layer` is None."""
    if layer is None:
        return pool, block_tables, slots
    nb, layers, bs, dk = pool.shape
    if slots is not None:
        slots = jnp.where(
            (slots >= 0) & (slots < nb * bs),
            (slots // bs * layers + layer) * bs + slots % bs, -1)
    return (pool.reshape(nb * layers, 1, bs, dk),
            block_tables * layers + layer, slots)


class LatentAttentionBlock:
    """The attention sublayer of a DeepSeek-V2/V3-style block over a
    normalised input `h [B, L, hidden]`:

        c_q = RMSNorm(h W_qa) ; [q_nope | q_rope]_h = c_q W_qb
        [c_kv | k_rope] = h W_kva ; c_kv <- RMSNorm(c_kv)
        RoPE on q_rope and the one shared k_rope (interleaved pairs, YaRN
        frequencies, cos / sin at ratio yarn_mscale(f, mscale) /
        yarn_mscale(f, mscale_all_dim)); the cache holds [c_kv | k_rope]
        scores scaled by (d_nope + d_rope)^-1/2 * m^2,
        m = yarn_mscale(f, mscale_all_dim)

    in the absorbed form: `W_kvb` is stored split per head as `w_uk [H,
    d_nope, r_kv]` and `w_uv [H, r_kv, d_v]` (`W_kvb,h = [W_UK,h |
    W_UV,h]`). Two things a family may state otherwise: `q_lora_rank`
    null = the query has no low-rank stage, `[q_nope | q_rope]_h = h W_q`
    (one matrix `q`, no norm); `gated_attention_proj_granularity_type`
    "head_wise" = head `h`'s outputs are multiplied by `sigmoid(h
    W_gate)_h`, one scalar a head (`attn_gate`), before `W_o`. Stateless:
    the weights are the entries `param_shapes` names in the caller's layer
    dict."""

    def __init__(self, cfg, max_model_len: int) -> None:
        """`cfg`: a config with the family's keys (`num_attention_heads`,
        `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
        `qk_rope_head_dim`, `v_head_dim`, `rms_norm_eps`, `rope_theta`,
        `rope_scaling`, `max_position_embeddings`; optionally
        `gated_attention_proj_granularity_type`)."""
        self.num_heads = cfg.num_attention_heads
        self.q_rank = cfg.q_lora_rank
        self.kv_rank = cfg.kv_lora_rank
        self.d_nope = cfg.qk_nope_head_dim
        self.d_rope = cfg.qk_rope_head_dim
        self.d_v = cfg.v_head_dim
        self.rms_eps = cfg.rms_norm_eps
        granularity = getattr(cfg, "gated_attention_proj_granularity_type",
                              None)
        if granularity not in (None, "head_wise"):
            raise NotImplementedError(
                f"gated_attention_proj_granularity_type {granularity!r}: "
                f"the latent block's output gate is one scalar a head "
                f"(\"head_wise\") or absent")
        self.head_gate = granularity == "head_wise"
        scaling = getattr(cfg, "rope_scaling", None)
        # YaRN's table has factor x original positions (131,072 rows, 34 MB
        # for cos and sin): keep the rows a position can reach.
        self.rope = get_rope(
            self.d_rope, self.d_rope, cfg.max_position_embeddings,
            cfg.rope_theta, is_neox_style=False,
            rope_scaling=scaling).trimmed_to(max_model_len)
        scale = (self.d_nope + self.d_rope)**-0.5
        if scaling and scaling.get("type", scaling.get("rope_type")) == "yarn":
            m = _yarn_get_mscale(scaling["factor"],
                                 scaling.get("mscale_all_dim", 0.0))
            scale *= m * m
        self.attn = LatentAttention(self.num_heads, self.kv_rank,
                                    self.d_rope, scale)

    def __call__(self, lp, h, kv_cache, attn_metadata, positions,
                 layer=None):
        """Returns (the sublayer's output [B, L, hidden], kv_cache).
        `layer`: as `LatentAttention.__call__`'s."""
        b, l, _ = h.shape
        hh = self.num_heads
        with jax.named_scope("mla_q_proj"):
            if self.q_rank:
                c_q = rms_norm(h @ lp["q_a"], lp["q_a_norm"], self.rms_eps)
                q = c_q @ lp["q_b"]
            else:
                q = h @ lp["q"]
            q = q.reshape(b, l, hh, self.d_nope + self.d_rope)
            q_nope, q_rope = q[..., :self.d_nope], q[..., self.d_nope:]
        with jax.named_scope("mla_kv_proj"):
            ckv = h @ lp["kv_a"]
            c_kv = rms_norm(ckv[..., :self.kv_rank], lp["kv_a_norm"],
                            self.rms_eps)
            k_rope = ckv[..., self.kv_rank:][:, :, None, :]
        q_rope, k_rope = self.rope(positions, q_rope, k_rope)
        with jax.named_scope("mla_absorb_q"):
            q_abs = jnp.einsum("blhn,hnr->blhr", q_nope, lp["w_uk"])
        q_lat = jnp.concatenate([q_abs, q_rope], axis=-1)
        kv_new = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
        o_lat, kv_cache = self.attn(q_lat, kv_new, kv_cache, attn_metadata,
                                    layer)
        with jax.named_scope("mla_absorb_o"):
            o = jnp.einsum("blhr,hrv->blhv", o_lat, lp["w_uv"])
        if self.head_gate:
            with jax.named_scope("mla_head_gate"):
                gate = jax.nn.sigmoid(
                    (h @ lp["attn_gate"]).astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate[..., None]).astype(h.dtype)
        return o.reshape(b, l, hh * self.d_v) @ lp["o"], kv_cache

    def param_shapes(self, hidden: int) -> dict:
        """The sublayer's entries of a model's `_param_shapes`, in the
        order the families' seeded draws walk them."""
        hh = self.num_heads
        d_q = hh * (self.d_nope + self.d_rope)
        shapes = ({"q_a": (hidden, self.q_rank), "q_a_norm": (self.q_rank, ),
                   "q_b": (self.q_rank, d_q)} if self.q_rank
                  else {"q": (hidden, d_q)})
        shapes.update({
            "kv_a": (hidden, self.kv_rank + self.d_rope),
            "kv_a_norm": (self.kv_rank, ),
            "w_uk": (hh, self.d_nope, self.kv_rank),
            "w_uv": (hh, self.kv_rank, self.d_v),
            "o": (hh * self.d_v, hidden),
        })
        if self.head_gate:
            shapes["attn_gate"] = (hidden, hh)
        return shapes

    def load(self, prefix: str, T, V) -> dict:
        """The sublayer's weights from a checkpoint under the DeepSeek-V3
        family's names (`<prefix>{q_a_proj, q_a_layernorm, q_b_proj,
        kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}`; `q_proj`
        where the query has no low-rank stage, `g_proj` the head-wise
        gate): `T(key)` gives a matrix transposed to `[in, out]`, `V(key)`
        an array as stored, both cast."""
        kv_b = V(prefix + "kv_b_proj.weight").reshape(
            self.num_heads, self.d_nope + self.d_v, self.kv_rank)
        q = ({"q_a": T(prefix + "q_a_proj.weight"),
              "q_a_norm": V(prefix + "q_a_layernorm.weight"),
              "q_b": T(prefix + "q_b_proj.weight")} if self.q_rank
             else {"q": T(prefix + "q_proj.weight")})
        gate = ({"attn_gate": T(prefix + "g_proj.weight")}
                if self.head_gate else {})
        return {
            **q, **gate,
            "kv_a": T(prefix + "kv_a_proj_with_mqa.weight"),
            "kv_a_norm": V(prefix + "kv_a_layernorm.weight"),
            "w_uk": np.ascontiguousarray(kv_b[:, :self.d_nope, :]),
            "w_uv": np.ascontiguousarray(
                kv_b[:, self.d_nope:, :].swapaxes(1, 2)),
            "o": T(prefix + "o_proj.weight"),
        }
