"""Falcon-H1 (`falcon_h1`): plain reference of the forward pass.

Source of the architecture: https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
(`model_type` "falcon_h1": in every layer a Mamba-2 state-space scan and a
RoPE GQA attention read one normed input and their outputs are summed, then
a SwiGLU MLP; twelve multipliers of the maximal-update parametrisation; an
untied head).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits, the state-space scan as its recurrence a token at a time and a head
at a time (the state a head `[P, N]` as published, B and C read from group
`h // (H / G)`). It shares no code with `intellillm_tpu/`.

Weights: a dict keyed by checkpoint names (HF's `FalconH1*` modules), each
matrix laid out `[out, in]` as a checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight   model.final_layernorm.weight   lm_head.weight
    model.layers.{i}.input_layernorm.weight / pre_ff_layernorm.weight
    model.layers.{i}.mamba.in_proj.weight [2 inner + 2 G N + H, hidden]
        (rows: z | x | B | C | dt), .conv1d.weight [inner + 2 G N, 1, 4] (tap 0
        the oldest), .conv1d.bias, .dt_bias [H], .A_log [H], .D [H],
        .norm.weight [inner], .out_proj.weight
    model.layers.{i}.self_attn.{q_proj, k_proj, v_proj, o_proj}.weight
    model.layers.{i}.feed_forward.{gate_proj, up_proj, down_proj}.weight

`cfg` is the config as the cell runs it, as a dict. The two tables
(`embed_tokens`, `lm_head`) may be arrays of any float dtype, on the host or
on the device: the embedding reads the rows it needs and the head is
computed a block of the vocabulary at a time, only for the rows asked for,
so that a 261,120-row table is never held in float32.

What the published config does not say, each marked ASSUMED where it is used:
  1. `ssm_multipliers` are in the order of the in-projection's five
     segments `[z, x, B, C, dt]` and scale its OUTPUT.
  2. `time_step_limit` (0, inf): no clamp on dt.
  3. The gated norm multiplies by silu(z) BEFORE normalising
     (`mamba_norm_before_gate` false), and normalises each group's
     `inner / G` channels on their own.
  4. The scan's state is float32.
  5. `key_multiplier` scales k BEFORE RoPE; RoPE rotates the pairs
     (i, i + head_dim / 2).
  6. `mamba_chunk_size`, `mamba_expand`, `mlp_expansion_factor` bind nothing
     here: the recurrence has no chunks and both widths are given.
"""
import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 32768


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


def head_groups(heads, groups):
    """The group of B and C each head reads: h // (H / G)."""
    return np.arange(heads) // (heads // groups)


def scan_head(x, dt, a, b, c):
    """One head's recurrence, a token at a time. x [T, P], dt [T], a a
    scalar, b, c [T, N] -> y [T, P]. The state [P, N] starts at zero.
    ASSUMED 4: float32."""
    def step(s, r):
        x_t, dt_t, b_t, c_t = r
        s = jnp.exp(dt_t * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((x.shape[1], b.shape[1]), F32),
                        (x, dt, b, c))
    return y


def scan(x, dt, a, b, c):
    """x [T, H, P], dt [T, H], a [H], b, c [T, G, N] -> y [T, H, P], a head
    at a time, head h with its group's B and C."""
    group = head_groups(x.shape[1], b.shape[1])
    per_head = (x.transpose(1, 0, 2), dt.T, a,
                b[:, group].transpose(1, 0, 2), c[:, group].transpose(1, 0, 2))
    y = jax.lax.map(lambda r: scan_head(*r), per_head)
    return y.transpose(1, 0, 2)


def gated_norm(y, z, w, groups, eps):
    """ASSUMED 3: the gate first, then each group's channels normalised on
    their own."""
    t, inner = y.shape
    y = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(t, inner) * jnp.asarray(w, F32)


def mamba(cfg, w, prefix, x):
    """The Mamba-2 mixer over x [T, E] (already times `ssm_in_multiplier`)."""
    t = x.shape[0]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g = cfg["mamba_n_groups"]
    inner, bc = h * p, g * n
    # ASSUMED 1: one multiplier a segment, on the projection's output.
    mup = np.repeat(np.asarray(cfg["ssm_multipliers"], np.float32),
                    (inner, inner, bc, bc, h))
    proj = _linear(x, w[prefix + "in_proj.weight"]) * mup
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    xbc = causal_conv_silu(xbc, w[prefix + "conv1d.weight"],
                           w[prefix + "conv1d.bias"])
    xs = xbc[:, :inner].reshape(t, h, p)
    b = xbc[:, inner:inner + bc].reshape(t, g, n)
    c = xbc[:, inner + bc:].reshape(t, g, n)
    # ASSUMED 2: no clamp.
    dt = jax.nn.softplus(dt + jnp.asarray(w[prefix + "dt_bias"], F32))
    a = -jnp.exp(jnp.asarray(w[prefix + "A_log"], F32))
    y = scan(xs, dt, a, b, c)
    y = y + jnp.asarray(w[prefix + "D"], F32)[None, :, None] * xs
    y = gated_norm(y.reshape(t, inner), z, w[prefix + "norm.weight"], g,
                   cfg["rms_norm_eps"])
    return _linear(y, w[prefix + "out_proj.weight"])


def rope(x, theta):
    """x [T, heads, d] at positions 0..T-1, pairs (i, i + d / 2)."""
    t, _, d = x.shape
    inv = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), F32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), F32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, w, prefix, x):
    """Causal softmax GQA over x [T, E] (already times
    `attention_in_multiplier`)."""
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = _linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
    # ASSUMED 5: the multiplier, then the rotation.
    k = (_linear(x, w[prefix + "k_proj.weight"])
         * cfg["key_multiplier"]).reshape(t, hkv, d)
    v = _linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return _linear(out.reshape(t, h * d), w[prefix + "o_proj.weight"])


def mlp(cfg, w, prefix, x):
    gate = _linear(x, w[prefix + "gate_proj.weight"]) * cfg[
        "mlp_multipliers"][0]
    up = _linear(x, w[prefix + "up_proj.weight"])
    return _linear(jax.nn.silu(gate) * up,
                   w[prefix + "down_proj.weight"]) * cfg["mlp_multipliers"][1]


def embed(cfg, w, token_ids):
    rows = np.asarray(w["model.embed_tokens.weight"])[np.asarray(token_ids)]
    return jnp.asarray(rows.astype(np.float32)) * cfg["embedding_multiplier"]


def layer(cfg, w, i, x):
    p = f"model.layers.{i}."
    u = rms_norm(x, w[p + "input_layernorm.weight"], cfg["rms_norm_eps"])
    m = mamba(cfg, w, p + "mamba.", u * cfg["ssm_in_multiplier"])
    a = attention(cfg, w, p + "self_attn.",
                  u * cfg["attention_in_multiplier"])
    x = x + (m * cfg["ssm_out_multiplier"]
             + a * cfg["attention_out_multiplier"])
    f = rms_norm(x, w[p + "pre_ff_layernorm.weight"], cfg["rms_norm_eps"])
    return x + mlp(cfg, w, p + "feed_forward.", f)


def head(cfg, w, x, rows=None):
    """Logits [rows, vocab] of the stream x [T, E] (every row, or `rows`
    alone), a block of the vocabulary at a time."""
    x = rms_norm(x if rows is None else x[np.asarray(rows)],
                 w["model.final_layernorm.weight"], cfg["rms_norm_eps"])
    table = w["lm_head.weight"]
    blocks = [x @ jnp.asarray(table[v:v + VOCAB_BLOCK]).astype(F32).T
              for v in range(0, table.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1) * cfg["lm_head_multiplier"]


def forward(cfg, w, token_ids):
    """Logits [T, vocab] of one token sequence."""
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, w, token_ids)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x)
        return head(cfg, w, x)
