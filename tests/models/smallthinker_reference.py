"""SmallThinker: plain reference of the forward pass.

Source of the architecture: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
(`model_type` "smallthinker": grouped-query attention layers, three with a
sliding window and RoPE for every one that attends to its whole context with
no position encoding at all; 64 ReGLU experts a layer, chosen by a router
that reads the layer's normalised input, before attention).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits, each layer under an explicit [T, T] mask (built and applied a block
of `QUERY_BLOCK` queries at a time, so that the scores of a long sequence
fit a chip: the mask and the arithmetic are the same). It shares no code
with `intellillm_tpu/`.

Weights: a dict keyed by the published checkpoint names, each a matrix laid
out `[out, in]` as a checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight  model.norm.weight  lm_head.weight
    model.layers.{i}.input_layernorm.weight / post_attention_layernorm.weight
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, o_proj}.weight
    model.layers.{i}.block_sparse_moe.primary_router.weight
    model.layers.{i}.block_sparse_moe.experts.{j}.{gate, up, down}.weight

`cfg` is the published config as a dict. Departures from / additions to what
the published file says, each marked ASSUMED where it is used:
  1. The router reads `input_layernorm(x)`, the same vector attention reads
     (the catalog: "router placed before attention"); the experts read
     `post_attention_layernorm(h)`.
  2. RoPE pairs dimension i with i + head_dim / 2 (half-split) over the
     whole head, theta `rope_theta`; a layer whose `rope_layout` is 0 gets
     no position encoding at all.
  3. A layer whose `sliding_window_layout` is 1 lets query i see key j iff
     i - sliding_window_size < j <= i.
  4. The expert's gate goes through ReLU ("sparse ReGLU"); `gate_act` is an
     argument only so that a test can compute the layer WRONG.
  5. The chosen experts' weights are a softmax over the chosen logits, in
     float32 (`moe_primary_router_apply_softmax` with `norm_topk_prob`).
"""
import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def _linear(x, w):
    """x [T, in] by a checkpoint matrix w [out, in]."""
    return x @ jnp.asarray(w, F32).T


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * jnp.asarray(w, F32)


def rope(x, positions, cfg):
    """x [T, H, D]; ASSUMED 2: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2,
                                                 dtype=np.float64) / d))
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask(cfg, i, queries, t):
    """[len(queries), T] bool: which keys each query position sees in
    layer i. ASSUMED 3."""
    q = np.asarray(queries)[:, None]
    k = np.arange(t)[None, :]
    seen = k <= q
    if cfg["sliding_window_layout"][i]:
        seen &= k > q - cfg["sliding_window_size"]
    return seen


def attention(cfg, w, prefix, i, x, positions):
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
    k = _linear(x, w[prefix + "k_proj.weight"]).reshape(t, hkv, d)
    v = _linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
    if cfg["rope_layout"][i]:
        q, k = rope(q, positions, cfg), rope(k, positions, cfg)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    outs = []
    for s in range(0, t, QUERY_BLOCK):
        rows = np.arange(s, min(s + QUERY_BLOCK, t))
        scores = jnp.einsum("thd,shd->hts", q[rows], k) * d ** -0.5
        scores = jnp.where(mask(cfg, i, rows, t)[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd",
                               jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs, axis=0)
    return _linear(out.reshape(t, h * d), w[prefix + "o_proj.weight"])


def route(cfg, logits):
    """logits [T, N] -> (weights [T, K], experts [T, K]). ASSUMED 5."""
    k = cfg["moe_num_active_primary_experts"]
    experts = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(logits, experts, axis=-1)
    return jax.nn.softmax(chosen.astype(F32), axis=-1), experts


def moe(cfg, w, prefix, x, router_logits, gate_act=jax.nn.relu):
    weights, experts = route(cfg, router_logits)
    out = jnp.zeros_like(x)
    for j in range(cfg["moe_num_primary_experts"]):
        e = f"{prefix}experts.{j}."
        wj = jnp.where(experts == j, weights, 0.0).sum(-1)        # [T]
        y = _linear(gate_act(_linear(x, w[e + "gate.weight"]))    # ASSUMED 4
                    * _linear(x, w[e + "up.weight"]), w[e + "down.weight"])
        out = out + wj[:, None] * y
    return out


def embed(w, token_ids):
    return jnp.asarray(w["model.embed_tokens.weight"], F32)[
        np.asarray(token_ids)]


def layer(cfg, w, i, x, positions, gate_act=jax.nn.relu):
    p = f"model.layers.{i}."
    m = p + "block_sparse_moe."
    n1 = rms_norm(x, w[p + "input_layernorm.weight"], cfg["rms_norm_eps"])
    router_logits = _linear(n1, w[m + "primary_router.weight"])   # ASSUMED 1
    h = x + attention(cfg, w, p + "self_attn.", i, n1, positions)
    n2 = rms_norm(h, w[p + "post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    return h + moe(cfg, w, m, n2, router_logits, gate_act)


def head(cfg, w, x):
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return _linear(x, w["lm_head.weight"])


def forward(cfg, w, token_ids, gate_act=jax.nn.relu):
    """Logits [T, vocab] of one token sequence."""
    with jax.default_matmul_precision("highest"):
        positions = np.arange(len(token_ids))
        x = embed(w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x, positions, gate_act)
        return head(cfg, w, x)
