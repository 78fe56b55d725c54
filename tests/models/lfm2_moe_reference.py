"""LFM2-MoE: plain reference of the forward pass.

Source of the architecture: https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
(`model_type` "lfm2_moe": gated short-convolution layers with a grouped-query
attention layer every few, a dense SwiGLU in the first `num_dense_layers`
layers and sigmoid-routed experts chosen by score + bias in the rest). The
dense family's published code is transformers `models/lfm2/modeling_lfm2.py`;
`tests/models/test_lfm2_moe.py` holds this file's conv, attention and dense
layers to it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no state, no batching: the full causal forward pass over one token
sequence -> logits. It shares no code with `intellillm_tpu/`.

Weights: a dict keyed by the published checkpoint names, each a matrix laid
out `[out, in]` as a checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight  model.embedding_norm.weight  [lm_head.weight]
    model.layers.{i}.operator_norm.weight / ffn_norm.weight
    model.layers.{i}.conv.{in_proj, out_proj}.weight, .conv.conv.weight
        ([channels, 1, taps], tap 0 the oldest)                        (conv)
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, out_proj}.weight,
        .q_layernorm.weight, .k_layernorm.weight                  (attention)
    model.layers.{i}.feed_forward.{w1, w2, w3}.weight                 (dense)
    model.layers.{i}.feed_forward.gate.weight, .expert_bias,
        .experts.{j}.{w1, w2, w3}.weight                                (MoE)

`cfg` is the published config as a dict. What the published file does not
say, each marked ASSUMED where it is used:
  1. `head_dim` = hidden_size / num_attention_heads (the family's default).
  2. `tie_word_embeddings` true (the family's default): the head is the
     embedding unless `lm_head.weight` is given.
  3. The router scores with a sigmoid; `expert_bias` enters the choice of
     experts only; the chosen scores are divided by (their sum + 1e-6) when
     `norm_topk_prob`, then multiplied by `routed_scaling_factor`.
  4. RoPE pairs dimension i with i + head_dim / 2 (half-split), as the dense
     family's code does.
"""
import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROUTER_SUM_EPS = 1e-6


def _linear(x, w):
    """x [T, in] by a checkpoint matrix w [out, in]."""
    return x @ jnp.asarray(w, F32).T


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * jnp.asarray(w, F32)


def head_dim(cfg):
    # ASSUMED 1.
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def rope(x, positions, cfg):
    """x [T, H, D]; ASSUMED 4: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2,
                                                 dtype=np.float64) / d))
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(cfg, w, prefix, x):
    """x [T, E] -> [T, E]: gate, causal depthwise filter, gate."""
    t, e = x.shape
    bcx = _linear(x, w[prefix + "in_proj.weight"])
    b, c, xx = bcx[:, :e], bcx[:, e:2 * e], bcx[:, 2 * e:]
    g = b * xx
    taps = jnp.asarray(w[prefix + "conv.weight"], F32)[:, 0, :]   # [E, L]
    n = taps.shape[1]
    # g before the sequence's start is 0; tap k reads g[t - (L - 1) + k].
    padded = jnp.concatenate([jnp.zeros((n - 1, e), F32), g], axis=0)
    conv = sum(padded[k:k + t] * taps[:, k][None, :] for k in range(n))
    return _linear(c * conv, w[prefix + "out_proj.weight"])


def attention(cfg, w, prefix, x, positions):
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    eps = cfg["norm_eps"]
    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
    k = _linear(x, w[prefix + "k_proj.weight"]).reshape(t, hkv, d)
    v = _linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
    q = rope(rms_norm(q, w[prefix + "q_layernorm.weight"], eps), positions,
             cfg)
    k = rope(rms_norm(k, w[prefix + "k_layernorm.weight"], eps), positions,
             cfg)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return _linear(out.reshape(t, h * d), w[prefix + "out_proj.weight"])


def swiglu(x, w, prefix):
    return _linear(jax.nn.silu(_linear(x, w[prefix + "w1.weight"]))
                   * _linear(x, w[prefix + "w3.weight"]),
                   w[prefix + "w2.weight"])


def route(cfg, scores, bias):
    """scores [T, N] -> (weights [T, K], experts [T, K]). ASSUMED 3."""
    k = cfg["num_experts_per_tok"]
    choose = scores if bias is None else scores + jnp.asarray(bias, F32)
    experts = jnp.argsort(-choose, axis=-1, stable=True)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTER_SUM_EPS)
    return weights * cfg.get("routed_scaling_factor", 1.0), experts


def moe(cfg, w, prefix, x):
    scores = jax.nn.sigmoid(_linear(x, w[prefix + "gate.weight"]))
    bias = (w.get(prefix + "expert_bias")
            if cfg.get("use_expert_bias", True) else None)
    weights, experts = route(cfg, scores, bias)
    out = jnp.zeros_like(x)
    for j in range(cfg["num_experts"]):
        wj = jnp.where(experts == j, weights, 0.0).sum(-1)        # [T]
        out = out + wj[:, None] * swiglu(x, w, f"{prefix}experts.{j}.")
    return out


def embed(w, token_ids):
    return jnp.asarray(w["model.embed_tokens.weight"], F32)[
        np.asarray(token_ids)]


def layer(cfg, w, i, x, positions):
    p = f"model.layers.{i}."
    u = rms_norm(x, w[p + "operator_norm.weight"], cfg["norm_eps"])
    if cfg["layer_types"][i] == "conv":
        x = x + short_conv(cfg, w, p + "conv.", u)
    else:
        x = x + attention(cfg, w, p + "self_attn.", u, positions)
    u = rms_norm(x, w[p + "ffn_norm.weight"], cfg["norm_eps"])
    if i < cfg["num_dense_layers"]:
        return x + swiglu(u, w, p + "feed_forward.")
    return x + moe(cfg, w, p + "feed_forward.", u)


def head(cfg, w, x):
    x = rms_norm(x, w["model.embedding_norm.weight"], cfg["norm_eps"])
    # ASSUMED 2.
    return _linear(x, w.get("lm_head.weight",
                            w["model.embed_tokens.weight"]))


def forward(cfg, w, token_ids):
    """Logits [T, vocab] of one token sequence."""
    with jax.default_matmul_precision("highest"):
        positions = np.arange(len(token_ids))
        x = embed(w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x, positions)
        return head(cfg, w, x)
