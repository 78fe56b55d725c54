"""Solar Open 2: plain reference of the forward pass.

Source of the architecture: https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
(`model_type` "solar_open2": Kimi-Delta-Attention linear-attention layers,
a gated softmax GQA layer without position encoding wherever `gqa_layers`
lists one, and in every layer sigmoid-routed experts + one shared expert).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits, the delta rule as its token-by-token recurrence (a `lax.scan` over
the sequence). It shares no code with `intellillm_tpu/`.

Weights: a dict keyed by checkpoint names, each matrix laid out `[out, in]`
as a checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight  model.norm.weight  lm_head.weight
    model.layers.{i}.input_layernorm.weight / post_attention_layernorm.weight
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, g_proj, o_proj}.weight
                                                                       (GQA)
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, o_proj}.weight,
        .{q_conv1d, k_conv1d, v_conv1d}.weight ([channels, 1, 4], tap 0 the
        oldest), .{f_a_proj, f_b_proj, b_proj, g_a_proj, g_b_proj}.weight,
        .dt_bias [H * d], .A_log [H], .o_norm.weight [d]                (KDA)
    model.layers.{i}.mlp.gate.weight [router width, hidden],
        .gate.e_score_correction_bias [router width],
        .experts.{j}.{gate_proj, up_proj, down_proj}.weight (j: the router's
        numbering), .shared_experts.{gate_proj, up_proj, down_proj}.weight

`cfg` is the config as the cell runs it, as a dict. An expert-parallel share
(`ep_size` > 1): `n_routed_experts` counts the experts this chip holds, the
router's outputs `[ep_rank * n, (ep_rank + 1) * n)`; routing, the choice and
the normalisation are over the whole router (`n * ep_size` outputs) and only
held experts are summed: what the absent ones would add is another chip's
part. The vocabulary is whatever rows the embedding and the head have.

What the published config does not say, each marked ASSUMED where it is used:
  1. The router scores with a sigmoid; `e_score_correction_bias` enters the
     choice of experts only; the chosen scores are divided by their sum when
     `norm_topk_prob`, then multiplied by `routed_scaling_factor` (the
     family's earlier release follows the GLM-4.5 / DeepSeek-V3 router, with
     one group).
  2. `kda_use_full_proj` false = the decay's and the output gate's
     projections are low rank, of rank `head_dim` (Kimi Linear's layout).
  3. The GQA layer's output gate is a sigmoid an element (hidden ->
     heads x head_dim), applied to the attention output before `o_proj`;
     no norm on q or k there.
  4. The delta rule's state is float32; q and k are normalised to unit
     length a head (epsilon 1e-6 inside the root), q then scaled by
     `head_dim ** -0.5`; the output norm is an RMSNorm a head with
     `rms_norm_eps`.
  5. `kda_allow_neg_eigval` true = the write strength is 2 x sigmoid.
"""
import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
L2_EPS = 1e-6


def _linear(x, w):
    """x [T, in] by a checkpoint matrix w [out, in]."""
    return x @ jnp.asarray(w, F32).T


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * jnp.asarray(w, F32)


def causal_conv_silu(x, w):
    """x [T, C] through a depthwise causal filter w [C, 1, L] (tap 0 the
    oldest, 0 before the sequence's start), then SiLU."""
    t, c = x.shape
    taps = jnp.asarray(w, F32)[:, 0, :]                           # [C, L]
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((n - 1, c), F32), x], axis=0)
    conv = sum(padded[k:k + t] * taps[:, k][None, :] for k in range(n))
    return jax.nn.silu(conv)


def l2_normalise(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, a, b):
    """The recurrence, a token at a time. q, k, a [T, H, dk], v [T, H, dv],
    b [T, H] -> o [T, H, dv]. The state S [H, dk, dv] starts at zero.
    ASSUMED 4: float32."""
    def step(s, x):
        q_t, k_t, v_t, a_t, b_t = x
        s = a_t[:, :, None] * s
        u = v_t - jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32), (q, k, v, a, b))
    return o


def kda(cfg, w, prefix, x):
    """Kimi Delta Attention over x [T, E]."""
    t = x.shape[0]
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    q, k, v = (causal_conv_silu(_linear(x, w[f"{prefix}{n}_proj.weight"]),
                                w[f"{prefix}{n}_conv1d.weight"]).reshape(
                                    t, h, d) for n in "qkv")
    q = l2_normalise(q) * d ** -0.5                            # ASSUMED 4
    k = l2_normalise(k)
    # ASSUMED 2: low-rank decay projection.
    raw = _linear(_linear(x, w[prefix + "f_a_proj.weight"]),
                  w[prefix + "f_b_proj.weight"])
    raw = raw + jnp.asarray(w[prefix + "dt_bias"], F32)
    rate = jnp.exp(jnp.asarray(w[prefix + "A_log"], F32).reshape(-1))
    a = jnp.exp(-rate[None, :, None]
                * jax.nn.softplus(raw).reshape(t, h, d))
    # ASSUMED 5.
    b = 2.0 * jax.nn.sigmoid(_linear(x, w[prefix + "b_proj.weight"]))
    o = delta_rule(q, k, v, a, b)
    o = rms_norm(o, w[prefix + "o_norm.weight"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_linear(_linear(x, w[prefix + "g_a_proj.weight"]),
                                  w[prefix + "g_b_proj.weight"]))
    return _linear(o.reshape(t, h * d) * gate, w[prefix + "o_proj.weight"])


def attention(cfg, w, prefix, x):
    """Causal softmax GQA without position encoding, gated (ASSUMED 3)."""
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
    k = _linear(x, w[prefix + "k_proj.weight"]).reshape(t, hkv, d)
    v = _linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    out = out.reshape(t, h * d)
    if cfg.get("use_gqa_gate", True):
        out = out * jax.nn.sigmoid(_linear(x, w[prefix + "g_proj.weight"]))
    return _linear(out, w[prefix + "o_proj.weight"])


def swiglu(x, w, prefix):
    return _linear(jax.nn.silu(_linear(x, w[prefix + "gate_proj.weight"]))
                   * _linear(x, w[prefix + "up_proj.weight"]),
                   w[prefix + "down_proj.weight"])


def route(cfg, scores, bias):
    """scores [T, N] -> (weights [T, K], experts [T, K]). ASSUMED 1."""
    k = cfg["num_experts_per_tok"]
    experts = jnp.argsort(-(scores + jnp.asarray(bias, F32)), axis=-1,
                          stable=True)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * cfg.get("routed_scaling_factor", 1.0), experts


def held_experts(cfg):
    """The router's outputs whose experts this share holds."""
    n = cfg["n_routed_experts"]
    first = cfg.get("ep_rank", 0) * n if cfg.get("ep_size", 1) > 1 else 0
    return range(first, first + n)


def moe(cfg, w, prefix, x, shared=True):
    """The held experts' part of the routed sum, plus (`shared`) the
    shared expert, which every share computes whole."""
    scores = jax.nn.sigmoid(_linear(x, w[prefix + "gate.weight"]))
    weights, experts = route(cfg, scores,
                             w[prefix + "gate.e_score_correction_bias"])
    out = jnp.zeros_like(x)
    for j in held_experts(cfg):
        wj = jnp.where(experts == j, weights, 0.0).sum(-1)        # [T]
        out = out + wj[:, None] * swiglu(x, w, f"{prefix}experts.{j}.")
    if shared and cfg.get("n_shared_experts", 0):
        out = out + swiglu(x, w, prefix + "shared_experts.")
    return out


def embed(w, token_ids):
    return jnp.asarray(w["model.embed_tokens.weight"], F32)[
        np.asarray(token_ids)]


def layer(cfg, w, i, x):
    p = f"model.layers.{i}."
    u = rms_norm(x, w[p + "input_layernorm.weight"], cfg["rms_norm_eps"])
    if i in cfg["gqa_layers"]:
        x = x + attention(cfg, w, p + "self_attn.", u)
    else:
        x = x + kda(cfg, w, p + "self_attn.", u)
    u = rms_norm(x, w[p + "post_attention_layernorm.weight"],
                 cfg["rms_norm_eps"])
    return x + moe(cfg, w, p + "mlp.", u)


def head(cfg, w, x):
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return _linear(x, w["lm_head.weight"])


def forward(cfg, w, token_ids):
    """Logits [T, vocab] of one token sequence."""
    with jax.default_matmul_precision("highest"):
        x = embed(w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x)
        return head(cfg, w, x)
