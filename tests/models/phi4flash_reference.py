"""Phi-4-mini-flash-reasoning (`phi4flash`): plain reference of the forward
pass.

Source of the architecture: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
with the hub's `modeling_phi4flash.py` / `configuration_phi4flash.py` and the
SambaY paper (arXiv:2507.06607; YOCO arXiv:2405.05254; Differential
Transformer arXiv:2410.05258): Mamba-1 scans and sliding-window attention in
the first half, ONE full-attention layer, then gated memory units fed by one
scan's output and cross layers over that one layer's keys and values.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in lower precision). No kernels, no
cache, no batching: the full causal forward pass over one token sequence ->
logits; the scan a `lax.scan` a token over the state `[inner, N]` as
published, the window a mask, the cross layers taking the full layer's `k`,
`v` arrays, differential attention as four softmax products. It shares no
code with `intellillm_tpu/`.

Weights: a dict keyed by checkpoint names, each matrix `[out, in]` as a
checkpoint holds it (so `y = x @ W.T`):

    model.embed_tokens.weight (also the head)   model.final_layernorm.{weight, bias}
    model.layers.{i}.input_layernorm / post_attention_layernorm .{weight, bias}
    model.layers.{i}.mlp.fc1.weight [2 I, E] (rows: gate | up), .mlp.fc2.weight
    a scan:   .attn.in_proj.weight [2 inner, E] (rows: x | z), .attn.conv1d.weight
              [inner, 1, 4] (tap 0 the oldest), .attn.conv1d.bias,
              .attn.x_proj.weight [R + 2 N, inner] (rows: dt | B | C),
              .attn.dt_proj.{weight [inner, R], bias}, .attn.A_log [inner, N],
              .attn.D [inner], .attn.out_proj.weight
    attention: .attn.Wqkv.{weight [(Hq + 2 Hkv) d, E], bias} (rows: q | k | v; a
              cross layer's holds the q rows alone), .attn.out_proj.{weight,
              bias}, .attn.lambda_q1 / lambda_k1 / lambda_q2 / lambda_k2 [d],
              .attn.subln.weight [2 d]
    a GMU:    .attn.in_proj.weight [inner, E], .attn.out_proj.weight

`cfg` is the config as the cell runs it, as a dict. The embedding may be an
array of any float dtype, on the host or on the device: the head is computed
a block of the vocabulary at a time, only for the rows asked for.

Departures from the hub's code, each marked where it is used:
  1. The hub runs bfloat16 activations through flash-attention and the
     `mamba_ssm` kernels; here everything is float32 and written out.
  2. The hub's differential attention calls flash-attention four times
     (`q1 k1 v1`, `q1 k1 v2`, `q2 k2 v1`, `q2 k2 v2`) and concatenates; the
     two softmaxes over `[v1 | v2]` here are the same four products.
  3. The hub builds the layout from `mb_per_layer` and per-layer flags
     (`yoco_kv`, `yoco_cross`, `yoco_mb`); `layer_kind` is that rule over
     `num_hidden_layers` alone.
  4. No position encoding: the hub's attention has none (the scans and the
     window carry order).
  5. `lambda_init` is a function of the layer's index, `0.8 - 0.6 exp(-0.3
     l)`, and the sub-layer norm is an RMSNorm over `2 d` with eps 1e-5.
  6. The memory the GMUs read is the scan's output WITH the `D` skip and
     BEFORE its gate (`memory_of`).
  7. The published config has no key for Mamba-1's sizes: where `cfg` has
     none either they are the hub class's defaults, `mamba_d_state` 16 and
     `mamba_dt_rank` "auto" = ceil(hidden / 16) (`d_inner` and the filter's
     taps are read off the weights' shapes).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 32768
SUBLN_EPS = 1e-5


def _linear(x, w, bias=None):
    """x [T, in] by a checkpoint matrix w [out, in]."""
    y = x @ jnp.asarray(w, F32).T
    return y if bias is None else y + jnp.asarray(bias, F32)


def layer_norm(x, w, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * jnp.asarray(w, F32)
            + jnp.asarray(bias, F32))


def layer_kind(cfg, layer):
    """DEPARTURE 3: the layout as a rule over the number of layers."""
    half = cfg["num_hidden_layers"] // 2
    if layer % 2 == 0:
        return "scan" if layer <= half else "gmu"
    if layer < half:
        return "window"
    return "full" if layer == half + 1 else "cross"


def dt_rank(cfg):
    rank = cfg.get("mamba_dt_rank", "auto")
    return math.ceil(cfg["hidden_size"] / 16) if rank == "auto" else rank


def causal_conv_silu(x, w, bias):
    """x [T, C] through a depthwise causal filter w [C, 1, L] (tap 0 the
    oldest, 0 before the sequence's start) plus its bias [C], then SiLU."""
    t, c = x.shape
    taps = jnp.asarray(w, F32)[:, 0, :]                           # [C, L]
    n = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((n - 1, c), F32), x], axis=0)
    conv = sum(padded[k:k + t] * taps[:, k][None, :] for k in range(n))
    return jax.nn.silu(conv + jnp.asarray(bias, F32))


def selective_scan(x, dt, a, b, c):
    """The recurrence a token at a time. x, dt [T, inner], a [inner, N], b,
    c [T, N] -> y [T, inner]. The state [inner, N] starts at zero and is
    float32."""
    def step(s, r):
        x_t, dt_t, b_t, c_t = r
        s = (jnp.exp(dt_t[:, None] * a) * s
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32), (x, dt, b, c))
    return y


def memory_of(y, z):
    """DEPARTURE 6: what the GMUs read of the memory scan: its output
    with the skip, before the gate."""
    return y


def mamba(cfg, w, prefix, u):
    """The Mamba-1 mixer over u [T, E] -> (out [T, E], memory [T, inner])."""
    n, r = cfg.get("mamba_d_state", 16), dt_rank(cfg)     # DEPARTURE 7
    proj = _linear(u, w[prefix + "in_proj.weight"])
    inner = proj.shape[1] // 2
    x, z = proj[:, :inner], proj[:, inner:]
    x = causal_conv_silu(x, w[prefix + "conv1d.weight"],
                         w[prefix + "conv1d.bias"])
    low = _linear(x, w[prefix + "x_proj.weight"])
    b, c = low[:, r:r + n], low[:, r + n:]
    dt = jax.nn.softplus(_linear(low[:, :r], w[prefix + "dt_proj.weight"],
                                 w[prefix + "dt_proj.bias"]))
    a = -jnp.exp(jnp.asarray(w[prefix + "A_log"], F32))
    y = selective_scan(x, dt, a, b, c)
    y = y + jnp.asarray(w[prefix + "D"], F32) * x
    out = _linear(y * jax.nn.silu(z), w[prefix + "out_proj.weight"])
    return out, memory_of(y, z)


def gmu(cfg, w, prefix, u, memory):
    gate = jax.nn.silu(_linear(u, w[prefix + "in_proj.weight"]))
    return _linear(memory * gate, w[prefix + "out_proj.weight"])


def lambda_init(layer):
    """DEPARTURE 5."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def lambda_full(w, prefix, layer):
    lq1, lk1, lq2, lk2 = (jnp.asarray(w[prefix + name], F32) for name in (
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    return (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
            + lambda_init(layer))


def keys_and_values(cfg, w, prefix, u):
    """(k, v) [T, Hkv, d] of an attention layer's own input."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    wqkv, bias = w[prefix + "Wqkv.weight"], w[prefix + "Wqkv.bias"]
    t = u.shape[0]
    k = _linear(u, wqkv[h * d:(h + hkv) * d], bias[h * d:(h + hkv) * d])
    v = _linear(u, wqkv[(h + hkv) * d:], bias[(h + hkv) * d:])
    return k.reshape(t, hkv, d), v.reshape(t, hkv, d)


def cross_keys_and_values(cfg, w, full_prefix, u, shared):
    """What a cross layer attends to: the full layer's keys and values, as
    that layer computed them from ITS input. (`u` and `full_prefix` are
    what a wrong reference would compute a cross layer's own from.)"""
    return shared


def differential(q, k, v, lam, visible):
    """q [T, Hq, d], k, v [T, Hkv, d], `visible` [T, T] bool -> [T, Hq / 2,
    2 d]. DEPARTURE 2: heads in pairs; each of a pair's two softmaxes
    weighs [v1 | v2]."""
    t, h, d = q.shape
    hkv = k.shape[1]
    q1, q2 = q[:, 0::2], q[:, 1::2]                       # [T, Hq / 2, d]
    k1, k2, v1, v2 = k[:, 0::2], k[:, 1::2], v[:, 0::2], v[:, 1::2]
    group = np.arange(h // 2) // ((h // 2) // (hkv // 2))
    both = jnp.concatenate([v1, v2], axis=-1)[:, group]   # [T, Hq / 2, 2 d]

    def attend(q_h, k_h):
        scores = jnp.einsum("thd,shd->hts", q_h, k_h[:, group]) * d ** -0.5
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), both)

    return attend(q1, k1) - lam * attend(q2, k2)


def attention(cfg, w, prefix, layer, u, kv, window=None):
    """Differential attention of u [T, E] over the keys and values `kv`."""
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // h
    q = _linear(u, w[prefix + "Wqkv.weight"][:h * d],
                w[prefix + "Wqkv.bias"][:h * d]).reshape(t, h, d)
    # DEPARTURE 4: no position encoding.
    pos = np.arange(t)
    visible = pos[None, :] <= pos[:, None]
    if window is not None:
        visible &= pos[None, :] > pos[:, None] - window
    out = differential(q, *kv, lambda_full(w, prefix, layer), visible)
    out = out / jnp.sqrt(jnp.mean(out * out, axis=-1, keepdims=True)
                         + SUBLN_EPS)
    out = (out * jnp.asarray(w[prefix + "subln.weight"], F32)
           * (1.0 - lambda_init(layer)))
    return _linear(out.reshape(t, h * d), w[prefix + "out_proj.weight"],
                   w[prefix + "out_proj.bias"])


def mlp(cfg, w, prefix, x):
    inter = cfg["intermediate_size"]
    gu = _linear(x, w[prefix + "fc1.weight"])
    return _linear(jax.nn.silu(gu[:, :inter]) * gu[:, inter:],
                   w[prefix + "fc2.weight"])


def stream(x):
    """The residual stream after a write, as it is kept: float32 here. (A
    control that computes the reference in a lower precision rounds it
    here.)"""
    return x


def embed(cfg, w, token_ids):
    rows = np.asarray(w["model.embed_tokens.weight"])[np.asarray(token_ids)]
    return jnp.asarray(rows.astype(np.float32))


def layer(cfg, w, i, x, carried):
    """One layer; `carried` holds the two values of the pass: "memory"
    (the memory scan's output) and "kv" (the full layer's keys and
    values)."""
    p = f"model.layers.{i}."
    a = p + "attn."
    eps = cfg["layer_norm_eps"]
    kind = layer_kind(cfg, i)
    u = layer_norm(x, w[p + "input_layernorm.weight"],
                   w[p + "input_layernorm.bias"], eps)
    if kind == "scan":
        out, memory = mamba(cfg, w, a, u)
        if i == cfg["num_hidden_layers"] // 2:
            carried["memory"] = memory
    elif kind == "gmu":
        out = gmu(cfg, w, a, u, carried["memory"])
    elif kind == "cross":
        full = f"model.layers.{cfg['num_hidden_layers'] // 2 + 1}.attn."
        out = attention(cfg, w, a, i, u, cross_keys_and_values(
            cfg, w, full, u, carried["kv"]))
    else:
        kv = keys_and_values(cfg, w, a, u)
        if kind == "full":
            carried["kv"] = kv
        out = attention(cfg, w, a, i, u, kv, window=(
            cfg["sliding_window"] if kind == "window" else None))
    x = stream(x + out)
    f = layer_norm(x, w[p + "post_attention_layernorm.weight"],
                   w[p + "post_attention_layernorm.bias"], eps)
    return stream(x + mlp(cfg, w, p + "mlp.", f))


def head(cfg, w, x, rows=None):
    """Logits [rows, vocab] of the stream x [T, E] (every row, or `rows`
    alone), a block of the vocabulary at a time; the head is the
    embedding."""
    x = layer_norm(x if rows is None else x[np.asarray(rows)],
                   w["model.final_layernorm.weight"],
                   w["model.final_layernorm.bias"], cfg["layer_norm_eps"])
    table = w["model.embed_tokens.weight"]
    blocks = [x @ jnp.asarray(table[v:v + VOCAB_BLOCK]).astype(F32).T
              for v in range(0, table.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)


def forward(cfg, w, token_ids, rows=None):
    """Logits [T, vocab] of one token sequence (or of `rows` alone)."""
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, w, token_ids)
        carried = {}
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, w, i, x, carried)
        return head(cfg, w, x, rows)
