"""Granite 4.0-H (`granitemoehybrid`): plain reference of the forward pass.

Source of the architecture: https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
(`model_type` "granitemoehybrid": Mamba-2 state-space layers, a softmax GQA
layer without position encoding wherever `layer_types` says "attention",
and in every layer softmax-routed experts + a shared MLP; four multipliers).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits, the state-space scan as its token-by-token recurrence (a `lax.scan`
over the sequence, the state a head `[P, N]` as published). It shares no
code with `intellillm_tpu/`.

Weights: a dict keyed by checkpoint names (HF's `GraniteMoeHybrid*`
modules), each matrix laid out `[out, in]` as a checkpoint holds it (so
`y = x @ W.T`):

    model.embed_tokens.weight (also the head: tied)   model.norm.weight
    model.layers.{i}.input_layernorm.weight / post_attention_layernorm.weight
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, o_proj}.weight
                                                                 (attention)
    model.layers.{i}.mamba.in_proj.weight [2 inner + 2 N + H, hidden]
        (rows: z | x B C | dt), .conv1d.weight [inner + 2 N, 1, 4] (tap 0 the
        oldest), .conv1d.bias, .dt_bias [H], .A_log [H], .D [H],
        .norm.weight [inner], .out_proj.weight                       (mamba)
    model.layers.{i}.block_sparse_moe.router.layer.weight [router, hidden],
        .input_linear.weight [experts, 2 x width, hidden] (gate rows then
        up rows), .output_linear.weight [experts, hidden, width]
    model.layers.{i}.shared_mlp.input_linear.weight [2 x width, hidden],
        .output_linear.weight [hidden, width]

`cfg` is the config as the cell runs it, as a dict. An expert-parallel share
(`ep_size` > 1): `num_local_experts` counts the experts this chip holds, the
router's outputs `[ep_rank * n, (ep_rank + 1) * n)`; the fused stacks hold
every expert of the router, as a checkpoint does, or those experts only
(index 0 = the share's first); routing, the choice and the normalisation
are over the whole router (`n * ep_size` outputs) and
only held experts are summed: what the absent ones would add is another
chip's part. The vocabulary is whatever rows the embedding has.

What the published config does not say, each marked ASSUMED where it is used:
  1. `time_step_limit` (0, inf): no clamp on dt.
  2. The gated norm multiplies by silu(z) BEFORE normalising, and
     normalises over all `inner` channels (one group).
  3. The scan's state is float32.
  4. `intermediate_size` is one expert's width; the head width is
     hidden_size / num_attention_heads.
  5. `mamba_chunk_size` binds nothing here: the recurrence has no chunks.
"""
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


def causal_conv_silu(x, w, bias):
    """x [T, C] through a depthwise causal filter w [C, 1, L] (tap 0 the
    oldest, 0 before the sequence's start) plus its bias [C], then SiLU."""
    t, c = x.shape
    taps = jnp.asarray(w, F32)[:, 0, :]                           # [C, L]
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((n - 1, c), F32), x], axis=0)
    conv = sum(padded[k:k + t] * taps[:, k][None, :] for k in range(n))
    return jax.nn.silu(conv + jnp.asarray(bias, F32))


def scan(x, dt, a, b, c):
    """The recurrence, a token at a time. x [T, H, P], dt [T, H], a [H],
    b, c [T, N] -> y [T, H, P]. The state [H, P, N] starts at zero.
    ASSUMED 3: float32."""
    def step(s, r):
        x_t, dt_t, b_t, c_t = r
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t)

    h, p, n = x.shape[1], x.shape[2], b.shape[1]
    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), F32), (x, dt, b, c))
    return y


def mamba(cfg, w, prefix, x):
    """The Mamba-2 mixer over x [T, E]."""
    t = x.shape[0]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * p
    proj = _linear(x, w[prefix + "in_proj.weight"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * n],
                  proj[:, 2 * inner + 2 * n:])
    xbc = causal_conv_silu(xbc, w[prefix + "conv1d.weight"],
                           w[prefix + "conv1d.bias"])
    xs = xbc[:, :inner].reshape(t, h, p)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    # ASSUMED 1: no clamp.
    dt = jax.nn.softplus(dt + jnp.asarray(w[prefix + "dt_bias"], F32))
    a = -jnp.exp(jnp.asarray(w[prefix + "A_log"], F32))
    y = scan(xs, dt, a, b, c)
    y = y + jnp.asarray(w[prefix + "D"], F32)[None, :, None] * xs
    # ASSUMED 2.
    y = rms_norm(y.reshape(t, inner) * jax.nn.silu(z),
                 w[prefix + "norm.weight"], cfg["rms_norm_eps"])
    return _linear(y, w[prefix + "out_proj.weight"])


def attention(cfg, w, prefix, x):
    """Causal softmax GQA without position encoding; the softmax scale is
    `attention_multiplier`."""
    t = x.shape[0]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h                                # ASSUMED 4
    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
    k = _linear(x, w[prefix + "k_proj.weight"]).reshape(t, hkv, d)
    v = _linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * cfg["attention_multiplier"]
    causal = np.tril(np.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return _linear(out.reshape(t, h * d), w[prefix + "o_proj.weight"])


def swiglu(x, fused_in, out):
    """`fused_in` [2 x width, hidden] (gate rows then up rows), `out`
    [hidden, width]."""
    both = _linear(x, fused_in)
    width = both.shape[1] // 2
    return _linear(jax.nn.silu(both[:, :width]) * both[:, width:], out)


def held_experts(cfg):
    """The router's outputs whose experts this share holds."""
    n = cfg["num_local_experts"]
    first = cfg.get("ep_rank", 0) * n if cfg.get("ep_size", 1) > 1 else 0
    return range(first, first + n)


def moe(cfg, w, prefix, x):
    """The held experts' part of the routed sum: the `num_experts_per_tok`
    largest router logits, a softmax over those."""
    k = cfg["num_experts_per_tok"]
    logits = _linear(x, w[prefix + "router.layer.weight"])
    experts = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    weights = jax.nn.softmax(jnp.take_along_axis(logits, experts, axis=-1),
                             axis=-1)
    held = held_experts(cfg)
    fused_in = w[prefix + "input_linear.weight"]
    fused_out = w[prefix + "output_linear.weight"]
    # A stack of the held experts alone starts at the share's first.
    first = held[0] if fused_in.shape[0] == len(held) else 0
    out = jnp.zeros_like(x)
    for j in held:
        wj = jnp.where(experts == j, weights, 0.0).sum(-1)        # [T]
        out = out + wj[:, None] * swiglu(x, fused_in[j - first],
                                         fused_out[j - first])
    return out


def shared_mlp(w, prefix, x):
    return swiglu(x, w[prefix + "input_linear.weight"],
                  w[prefix + "output_linear.weight"])


def embed(cfg, w, token_ids):
    return (jnp.asarray(w["model.embed_tokens.weight"], F32)[
        np.asarray(token_ids)] * cfg["embedding_multiplier"])


def layer(cfg, w, i, x):
    p = f"model.layers.{i}."
    rm = cfg["residual_multiplier"]
    u = rms_norm(x, w[p + "input_layernorm.weight"], cfg["rms_norm_eps"])
    if cfg["layer_types"][i] == "mamba":
        x = x + rm * mamba(cfg, w, p + "mamba.", u)
    else:
        x = x + rm * attention(cfg, w, p + "self_attn.", u)
    u = rms_norm(x, w[p + "post_attention_layernorm.weight"],
                 cfg["rms_norm_eps"])
    return x + rm * (moe(cfg, w, p + "block_sparse_moe.", u)
                     + shared_mlp(w, p + "shared_mlp.", u))


def head(cfg, w, x):
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return _linear(x, w["model.embed_tokens.weight"]) / cfg["logits_scaling"]


def forward(cfg, w, token_ids):
    """Logits [T, vocab] of one token sequence."""
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x)
        return head(cfg, w, x)
