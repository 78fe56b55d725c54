"""Xing4.0: plain reference of the forward pass.

Source of the architecture:
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
(`model_type` "xing4_0": the DeepSeek-V3 block — multi-head latent attention
and sigmoid-scored mixture of experts chosen by score + bias, with a shared
expert — inside a residual path of `hc_mult` streams mixed by
manifold-constrained hyper-connections, mHC, arXiv:2512.24880 section 4).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching, no absorbed projections, Sinkhorn-Knopp as a Python loop
over `[T, n, n]` matrices: the full causal forward pass in the EXPANDED
attention form over one token sequence -> logits. It shares no code with the
program it is a reference for.

    X_0[i] = embed(ids)                                 for each of the n streams
    layer:   X = HC(X, a -> MLA(RMSNorm_in(a)), attn_hc)
             X = HC(X, f -> FFN(RMSNorm_post(f)), ffn_hc)
    logits = RMSNorm(sum_i X_L[i]) W_head

Weights: a dict keyed by checkpoint names, each matrix laid out `[out, in]`
as a checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight  model.norm.weight  lm_head.weight
    model.layers.{i}.input_layernorm.weight / post_attention_layernorm.weight
    model.layers.{i}.self_attn.{q_a_proj, q_b_proj, kv_a_proj_with_mqa,
        kv_b_proj, o_proj}.weight, .q_a_layernorm.weight, .kv_a_layernorm.weight
    model.layers.{i}.mlp.{gate_proj, up_proj, down_proj}.weight       (dense)
    model.layers.{i}.mlp.gate.weight, .gate.e_score_correction_bias,
        .experts.{j}.{gate,up,down}_proj.weight,
        .shared_experts.{gate,up,down}_proj.weight                     (MoE)
    model.layers.{i}.{attn_hc, ffn_hc}.phi.weight [2n + n^2, nC], .alpha [3]
        (a_pre, a_post, a_res), .b_pre [n], .b_post [n], .b_res [n, n]

`cfg` is the published config as a dict, with `n_routed_experts` the router's
whole width. Departures from the published description (the config names the
keys, not their use), each marked DEPARTURE where it happens:
  1. The mix's statistic is an RMS over all n*C values with no learned gain
     (it would fold into Phi) and `rms_norm_eps`.
  2. `hc_eps` is added to every Sinkhorn denominator; the clamp
     [`mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`] acts on the logits before
     `exp`; a sweep normalises columns then rows, `hc_sinkhorn_iters` times.
  3. `a_pre`, `a_post`, `a_res` are scalars a sublayer, `b_pre`, `b_post`
     vectors, `b_res` a matrix; `Hpost` carries a factor 2.
  4. The embedding is copied into the n streams and the streams are summed
     before the final norm (Hyper-Connections, arXiv:2409.19606).
  5. `topk_method: "noaux_tc"`: a per-expert bias is added to the scores for
     the CHOICE of experts and is no part of their weights; `n_group` 1 /
     `topk_group` 1 = no group limit.
  6. RoPE pairs interleaved dimensions (2i, 2i+1), as the DeepSeek-V2/V3
     family's code does; `rms_norm_eps` is used for the norms on `c_q` and
     `c_kv` too.
  7. The multi-token-prediction layer (`num_nextn_predict_layers`) is a
     drafter and no part of the model's own logits: absent.
  8. An expert-parallel share (`ep_size`, `ep_rank`): routing is over all
     experts, but only the share's experts are computed; what the absent
     experts would add is left out, and that partial result goes on to the
     next layer. `ep_size=None` is the uncut model. A sliced vocabulary is
     the weights' own: the embedding and the head hold the slice's rows.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _linear(x, w):
    """x [T, in] by a checkpoint matrix w [out, in]."""
    return x @ jnp.asarray(w, F32).T


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * jnp.asarray(w, F32)


# --- the residual path -------------------------------------------------------

def sinkhorn(cfg, z):
    """z [T, n, n] logits -> M [T, n, n]: DEPARTURE 2. `M[t, i, j]` mixes
    stream j into stream i; a column's sum runs over i (axis 1), a row's
    over j (axis 2)."""
    eps = cfg["hc_eps"]
    m = jnp.exp(jnp.clip(z, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=2, keepdims=True) + eps)
    return m


def coefficients(cfg, w, prefix, x):
    """x [T, n, C] -> (Hpre [T, n], Hpost [T, n], Hres [T, n, n])."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    # DEPARTURE 1: no gain, the model's epsilon.
    r = 1.0 / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                       + cfg["rms_norm_eps"])
    m = _linear(flat * r, w[prefix + "phi.weight"])          # [T, 2n + n^2]
    a_pre, a_post, a_res = (jnp.asarray(w[prefix + "alpha"], F32)[k]
                            for k in range(3))
    # DEPARTURE 3
    h_pre = jax.nn.sigmoid(a_pre * m[:, :n]
                           + jnp.asarray(w[prefix + "b_pre"], F32))
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n]
                                  + jnp.asarray(w[prefix + "b_post"], F32))
    h_res = sinkhorn(cfg, a_res * m[:, 2 * n:].reshape(t, n, n)
                     + jnp.asarray(w[prefix + "b_res"], F32))
    return h_pre, h_post, h_res


def hyper_connection(cfg, w, prefix, x, sublayer):
    """X' = Hres X + Hpost (x) F(Hpre . X), x [T, n, C]."""
    h_pre, h_post, h_res = coefficients(cfg, w, prefix, x)
    u = jnp.einsum("ti,tic->tc", h_pre, x)
    y = sublayer(u)
    return (jnp.einsum("tij,tjc->tic", h_res, x)
            + h_post[:, :, None] * y[:, None, :])


def merge_streams(x):
    """DEPARTURE 4: the streams summed."""
    return x.sum(axis=1)


# --- attention ---------------------------------------------------------------

def yarn_mscale(factor, mscale):
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """YaRN's per-pair rotation frequencies: the original frequency where a
    pair turns more than `beta_fast` times within the original context, the
    interpolated one (/ factor) where it turns fewer than `beta_slow`
    times, a linear ramp between. A constant table: numpy, float64."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    sc = cfg.get("rope_scaling")
    freq = base**(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not sc:
        return 1.0 / freq
    orig = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) /
                   (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    return (1.0 / (sc["factor"] * freq)) * (1 - extrapolation) + (
        1.0 / freq) * extrapolation


def rope(x, positions, cfg):
    """x [T, ..., d_rope], positions [T] (concrete). DEPARTURE 6: pairs
    are (2i, 2i+1)."""
    angles = (np.asarray(positions, np.float64)[:, None] *
              yarn_inv_freq(cfg)[None, :])
    sc = cfg.get("rope_scaling")
    ratio = 1.0
    if sc:
        # cos/sin carry only the RATIO of the two mscales (1 for this
        # config); the square of mscale_all_dim's goes on the score.
        ratio = (yarn_mscale(sc["factor"], sc.get("mscale", 0)) /
                 yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)))
    cos = jnp.asarray(np.cos(angles) * ratio, F32)
    sin = jnp.asarray(np.sin(angles) * ratio, F32)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(cfg, w, prefix, x, positions):
    """Expanded multi-head latent attention over the whole sequence."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    d_n, d_r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, r_kv = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]          # DEPARTURE 6

    c_q = rms_norm(_linear(x, w[prefix + "q_a_proj.weight"]),
                   w[prefix + "q_a_layernorm.weight"], eps)
    q = _linear(c_q, w[prefix + "q_b_proj.weight"]).reshape(t, h, d_n + d_r)
    q_n, q_r = q[..., :d_n], q[..., d_n:]

    ckv = _linear(x, w[prefix + "kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(ckv[:, :r_kv], w[prefix + "kv_a_layernorm.weight"], eps)
    k_r = ckv[:, r_kv:]                       # one rope key for all heads

    q_r = rope(q_r, positions, cfg)
    k_r = rope(k_r, positions, cfg)

    kv = _linear(c_kv, w[prefix + "kv_b_proj.weight"]).reshape(
        t, h, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]

    sc = cfg.get("rope_scaling")
    m = yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)) if sc else 1.0
    sigma = (d_n + d_r)**-0.5 * m * m
    scores = (jnp.einsum("qhd,khd->hqk", q_n, k_n) +
              jnp.einsum("qhd,kd->hqk", q_r, k_r)) * sigma
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * d_v)
    return _linear(o, w[prefix + "o_proj.weight"])


# --- the feed-forward --------------------------------------------------------

def swiglu(x, w, prefix):
    return _linear(
        jax.nn.silu(_linear(x, w[prefix + "gate_proj.weight"])) *
        _linear(x, w[prefix + "up_proj.weight"]),
        w[prefix + "down_proj.weight"])


def route(cfg, scores, bias):
    """scores [T, N], bias [N] -> (weights [T, K], experts [T, K]).
    DEPARTURE 5: top-k of score + bias; the weights are the scores at the
    chosen experts, normalised, times `routed_scaling_factor`. Ties go to
    the lower index (as `jax.lax.top_k` breaks them)."""
    _, experts = jax.lax.top_k(scores + bias[None, :],
                               cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights * cfg["routed_scaling_factor"], experts


def moe(cfg, w, prefix, x, ep_size=None, ep_rank=0, shared=True):
    """y = sum_{e in chosen, held here} w_e E_e(x) + Shared(x). DEPARTURE 8:
    under a share only experts [ep_rank*n, (ep_rank+1)*n) are computed."""
    n = cfg["n_routed_experts"]
    assert cfg["scoring_func"] == "sigmoid"
    scores = jax.nn.sigmoid(_linear(x, w[prefix + "gate.weight"]))
    weights, experts = route(
        cfg, scores,
        jnp.asarray(w[prefix + "gate.e_score_correction_bias"], F32))
    if ep_size is None:
        held = range(n)
    else:
        per = n // ep_size
        held = range(ep_rank * per, (ep_rank + 1) * per)
    y = jnp.zeros_like(x)
    for e in held:
        w_e = jnp.where(experts == e, weights, 0.0).sum(axis=-1)     # [T]
        y = y + w_e[:, None] * swiglu(x, w, f"{prefix}experts.{e}.")
    if shared and cfg["n_shared_experts"]:
        y = y + swiglu(x, w, prefix + "shared_experts.")
    return y


def is_moe_layer(cfg, i):
    return (i >= cfg["first_k_dense_replace"]
            and i % cfg["moe_layer_freq"] == 0)


# --- the model ---------------------------------------------------------------

def embed(cfg, w, token_ids):
    """[T] -> X_0 [T, n, C]: DEPARTURE 4, the embedding in every stream."""
    e = jnp.asarray(w["model.embed_tokens.weight"], F32)[token_ids]
    return jnp.repeat(e[:, None, :], cfg["hc_mult"], axis=1)


def layer(cfg, w, i, x, positions, ep_size=None, ep_rank=0):
    """One block: x [T, n, C] -> [T, n, C]."""
    p = f"model.layers.{i}."
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = hyper_connection(
            cfg, w, p + "attn_hc.", x,
            lambda a: attention(
                cfg, w, p + "self_attn.",
                rms_norm(a, w[p + "input_layernorm.weight"], eps),
                positions))

        def ffn(f):
            f = rms_norm(f, w[p + "post_attention_layernorm.weight"], eps)
            if is_moe_layer(cfg, i):
                return moe(cfg, w, p + "mlp.", f, ep_size, ep_rank)
            return swiglu(f, w, p + "mlp.")

        return hyper_connection(cfg, w, p + "ffn_hc.", x, ffn)


def head(cfg, w, x):
    with jax.default_matmul_precision("highest"):
        return _linear(
            rms_norm(merge_streams(x), w["model.norm.weight"],
                     cfg["rms_norm_eps"]),
            w["lm_head.weight"])


def forward(cfg, w, token_ids, ep_size=None, ep_rank=0):
    """token_ids [T] -> logits [T, vocab], float32. (`layer` and `head`
    can be called one at a time by a caller that cannot hold every
    layer's weights at once.)"""
    positions = np.arange(len(token_ids))
    x = embed(cfg, w, jnp.asarray(token_ids))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, w, i, x, positions, ep_size, ep_rank)
    return head(cfg, w, x)
