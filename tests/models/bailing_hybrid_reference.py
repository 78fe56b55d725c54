"""Ling 3.0 (`model_type` "bailing_hybrid"): plain reference of the forward
pass.

Source of the architecture: https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json
(Kimi-Delta-Attention layers, a latent-attention (MLA) layer at the end of
every group of `layer_group_size`, a dense SwiGLU in the first
`first_k_dense_replace` layers, then 512 sigmoid-routed experts in 8 groups
+ one shared expert).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits; the delta rule as its token-by-token recurrence (a `lax.scan` over
the sequence); latent attention in the EXPANDED form (per-head `k_nope` and
`v` up-projected from the latent for the whole context; the engine runs the
absorbed form); the router written out with its groups. It shares no code
with `intellillm_tpu/`.

Weights: a dict keyed by checkpoint names, each matrix laid out `[out, in]`
as a checkpoint holds it (so `y = x @ W.T`):

    model.word_embeddings.weight  model.norm.weight  lm_head.weight
    model.layers.{i}.input_layernorm.weight / post_attention_layernorm.weight
    model.layers.{i}.attention.{q_proj, kv_a_proj_with_mqa, kv_b_proj,
        g_proj, dense}.weight, .kv_a_layernorm.weight                  (MLA)
    model.layers.{i}.attention.{q_proj, k_proj, v_proj, f_proj, b_proj,
        g_proj, dense}.weight, .{q_conv1d, k_conv1d, v_conv1d}.weight
        ([channels, 1, 4], tap 0 the oldest), .dt_bias [H * d], .A_log [H],
        .o_norm.weight [d]                                             (KDA)
    model.layers.{i}.mlp.{gate_proj, up_proj, down_proj}.weight      (dense)
    model.layers.{i}.mlp.gate.weight [router width, hidden],
        .gate.expert_bias [router width], .experts.{j}.{gate_proj, up_proj,
        down_proj}.weight (j: the router's numbering),
        .shared_experts.{gate_proj, up_proj, down_proj}.weight         (MoE)

`cfg` is the config as the cell runs it, as a dict. An expert-parallel share
(`ep_size` > 1): `num_experts` counts the experts this chip holds, the
router's outputs `[ep_rank * n, (ep_rank + 1) * n)`; routing, the choice and
the normalisation are over the whole router (`n * ep_size` outputs) and only
held experts are summed: what the absent ones would add is another chip's
part, and that partial result goes on to the next layer. The vocabulary is
whatever rows the embedding and the head have. The multi-token-prediction
layer is no part of the forward pass.

What the published config does not say, each marked ASSUMED where it is used
(the family's convention; the configuration's file lists them with where
each comes from):
  1. Layer `l` is the latent one iff `(l + 1) % layer_group_size == 0`.
  2. KDA's decay under `kda_safe_gate`: `g = kda_lower_bound *
     sigmoid(exp(A_log_h) * (W_f x + dt_bias))`, `a = exp(g)` in
     (e^lower_bound, 1); `W_f` and the output gate `W_g` are full
     projections (`no_kda_lora`).
  3. The write strength is `sigmoid(W_b x)`, without a factor 2.
  4. `use_qk_norm`: in a KDA layer q and k are normalised to unit length a
     head (epsilon 1e-6 inside the root), q then scaled by `head_dim **
     -0.5`; in a latent layer it is the latent's own RMSNorm
     (`kv_a_layernorm`), and NO norm a head on the expanded `k_nope` (the
     alternative, an RMSNorm a head on `q_nope | q_rope` and on the
     expanded key, would forbid the absorbed form).
  5. `gated_attention_proj_granularity_type` "head_wise": a latent layer's
     head `h` is multiplied by `sigmoid(W_gate x)_h`, one scalar a head,
     before the output projection. KDA's output gate is a sigmoid an
     element, after an RMSNorm a head with one weight of `head_dim`
     (`group_norm_size` 1).
  6. The delta rule's state is float32.
  7. The router (`noaux_tc`, DeepSeek-V3's): sigmoid scores; `expert_bias`
     enters the choice only; a group's score is the sum of its two largest
     `score + bias`; the chosen scores are divided by their sum
     (`norm_topk_prob`) and multiplied by `routed_scaling_factor`; the
     shared expert is added unscaled.
  8. RoPE pairs interleaved dimensions (2i, 2i+1) of the last
     `qk_rope_head_dim` of a query head and of the one shared rope key
     (`rope_interleave`), theta `rope_theta`, no scaling; scores are scaled
     by `(qk_nope_head_dim + qk_rope_head_dim) ** -0.5`.
  9. `rms_norm_eps` is used for every RMSNorm.
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


def is_latent_layer(cfg, i):
    """ASSUMED 1."""
    return (i + 1) % cfg["layer_group_size"] == 0


def is_moe_layer(cfg, i):
    return i >= cfg["first_k_dense_replace"]


# --- Kimi Delta Attention -----------------------------------------------------

def causal_conv_silu(x, w):
    """x [T, C] through a depthwise causal filter w [C, 1, L] (tap 0 the
    oldest, 0 before the sequence's start), then SiLU (`linear_silu`)."""
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
    ASSUMED 6: float32."""
    def step(s, x):
        q_t, k_t, v_t, a_t, b_t = x
        s = a_t[:, :, None] * s
        u = v_t - jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32), (q, k, v, a, b))
    return o


def log_decay(cfg, w, prefix, x):
    """g [T, H, d] in (kda_lower_bound, 0). ASSUMED 2."""
    t = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    raw = (_linear(x, w[prefix + "f_proj.weight"])
           + jnp.asarray(w[prefix + "dt_bias"], F32)).reshape(t, h, d)
    rate = jnp.exp(jnp.asarray(w[prefix + "A_log"], F32).reshape(-1))
    return cfg["kda_lower_bound"] * jax.nn.sigmoid(rate[None, :, None] * raw)


def write_strength(w, prefix, x):
    """b [T, H]. ASSUMED 3."""
    return jax.nn.sigmoid(_linear(x, w[prefix + "b_proj.weight"]))


def kda(cfg, w, prefix, x):
    """Kimi Delta Attention over x [T, E]."""
    t = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    q, k, v = (causal_conv_silu(_linear(x, w[f"{prefix}{n}_proj.weight"]),
                                w[f"{prefix}{n}_conv1d.weight"]).reshape(
                                    t, h, d) for n in "qkv")
    q = l2_normalise(q) * d ** -0.5                            # ASSUMED 4
    k = l2_normalise(k)
    a = jnp.exp(log_decay(cfg, w, prefix, x))
    o = delta_rule(q, k, v, a, write_strength(w, prefix, x))
    o = rms_norm(o, w[prefix + "o_norm.weight"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_linear(x, w[prefix + "g_proj.weight"]))
    return _linear(o.reshape(t, h * d) * gate, w[prefix + "dense.weight"])


# --- latent attention, expanded -----------------------------------------------

def rope(x, positions, cfg):
    """x [T, ..., d_rope], positions [T] (concrete). ASSUMED 8: pairs are
    (2i, 2i+1). A constant table: numpy, float64."""
    dim = cfg["qk_rope_head_dim"]
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    cos, sin = jnp.asarray(np.cos(angles), F32), jnp.asarray(np.sin(angles),
                                                             F32)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def head_gate(w, prefix, x):
    """[T, H]: one scalar a head. ASSUMED 5."""
    return jax.nn.sigmoid(_linear(x, w[prefix + "g_proj.weight"]))


def mla(cfg, w, prefix, x, positions, gated=True):
    """Expanded multi-head latent attention over the whole sequence; no
    low-rank stage and no norm on the query (`q_lora_rank` null)."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    d_n, d_r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, r_kv = cfg["v_head_dim"], cfg["kv_lora_rank"]

    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d_n + d_r)
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    ckv = _linear(x, w[prefix + "kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(ckv[:, :r_kv], w[prefix + "kv_a_layernorm.weight"],
                    cfg["rms_norm_eps"])                       # ASSUMED 4
    k_r = ckv[:, r_kv:]                       # one rope key for all heads
    q_r = rope(q_r, positions, cfg)
    k_r = rope(k_r, positions, cfg)
    kv = _linear(c_kv, w[prefix + "kv_b_proj.weight"]).reshape(
        t, h, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]

    scores = (jnp.einsum("qhd,khd->hqk", q_n, k_n) +
              jnp.einsum("qhd,kd->hqk", q_r, k_r)) * (d_n + d_r) ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    if gated:
        o = o * head_gate(w, prefix, x)[:, :, None]
    return _linear(o.reshape(t, h * d_v), w[prefix + "dense.weight"])


# --- the FFNs -----------------------------------------------------------------

def swiglu(x, w, prefix):
    return _linear(jax.nn.silu(_linear(x, w[prefix + "gate_proj.weight"]))
                   * _linear(x, w[prefix + "up_proj.weight"]),
                   w[prefix + "down_proj.weight"])


def group_scores(cfg, choice):
    """choice [T, G, per] -> [T, G]: the sum of a group's two largest.
    ASSUMED 7."""
    return jnp.sort(choice, axis=-1)[..., -2:].sum(-1)


def route(cfg, scores, bias):
    """scores [T, N] -> (weights [T, K], experts [T, K]). ASSUMED 7. Ties
    go to the lower index, groups and experts alike."""
    t, n = scores.shape
    g, k = cfg["n_group"], cfg["num_experts_per_tok"]
    choice = scores + jnp.asarray(bias, F32)
    best = jnp.argsort(-group_scores(cfg, choice.reshape(t, g, n // g)),
                       axis=-1, stable=True)[:, :cfg["topk_group"]]
    kept = jnp.any(jnp.arange(g)[None, :, None] == best[:, None, :], axis=-1)
    masked = jnp.where(jnp.repeat(kept, n // g, axis=1), choice, -jnp.inf)
    experts = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * cfg.get("routed_scaling_factor", 1.0), experts


def held_experts(cfg):
    """The router's outputs whose experts this share holds."""
    n = cfg["num_experts"]
    first = cfg.get("ep_rank", 0) * n if cfg.get("ep_size", 1) > 1 else 0
    return range(first, first + n)


def moe(cfg, w, prefix, x, shared=True):
    """The held experts' part of the routed sum, plus (`shared`) the
    shared expert, which every share computes whole."""
    scores = jax.nn.sigmoid(_linear(x, w[prefix + "gate.weight"]))
    weights, experts = route(cfg, scores, w[prefix + "gate.expert_bias"])
    out = jnp.zeros_like(x)
    for j in held_experts(cfg):
        wj = jnp.where(experts == j, weights, 0.0).sum(-1)        # [T]
        out = out + wj[:, None] * swiglu(x, w, f"{prefix}experts.{j}.")
    if shared and cfg.get("num_shared_experts", 0):
        out = out + swiglu(x, w, prefix + "shared_experts.")
    return out


# --- the model ----------------------------------------------------------------

def embed(w, token_ids):
    return jnp.asarray(w["model.word_embeddings.weight"], F32)[
        np.asarray(token_ids)]


def layer(cfg, w, i, x, positions):
    """One pre-norm residual block: x [T, E] -> [T, E]."""
    p = f"model.layers.{i}."
    eps = cfg["rms_norm_eps"]                                  # ASSUMED 9
    u = rms_norm(x, w[p + "input_layernorm.weight"], eps)
    if is_latent_layer(cfg, i):
        x = x + mla(cfg, w, p + "attention.", u, positions)
    else:
        x = x + kda(cfg, w, p + "attention.", u)
    u = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    if is_moe_layer(cfg, i):
        return x + moe(cfg, w, p + "mlp.", u)
    return x + swiglu(u, w, p + "mlp.")


def head(cfg, w, x):
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return _linear(x, w["lm_head.weight"])


def forward(cfg, w, token_ids):
    """Logits [T, vocab] of one token sequence."""
    positions = np.arange(len(token_ids))
    with jax.default_matmul_precision("highest"):
        x = embed(w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x, positions)
        return head(cfg, w, x)
