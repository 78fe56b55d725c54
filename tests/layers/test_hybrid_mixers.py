"""What Ling 3.0 added to the two mixers it shares with other families:
`layers/linear_attention.py` `kda_gates` (the decay bounded below, full-rank
projections, the write strength without the 2) against the formulas written
out, and `layers/mla_attention.py` `LatentAttentionBlock` without a low-rank
query stage and with a gate a head, in its ABSORBED form over a paged
latent pool, against the reference's EXPANDED attention
(`tests/models/bailing_hybrid_reference.py` `mla`)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers.attention import AttentionMetadata
from intellillm_tpu.layers.linear_attention import kda_gates
from intellillm_tpu.layers.mla_attention import LatentAttentionBlock
from tests.models import bailing_hybrid_reference as ref


def _gate_params(rng, e, h, d, full_rank):
    def m(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    lp = {"dt_bias": jnp.asarray(rng.uniform(-4, 1, h * d), jnp.float32),
          "A_log": jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32),
          "b_proj": m(e, h)}
    if full_rank:
        lp["f"] = m(e, h * d)
    else:
        lp.update(f_a=m(e, d), f_b=m(d, h * d))
    return lp


@pytest.mark.parametrize("full_rank", [True, False],
                         ids=["full_rank", "low_rank"])
def test_bounded_gate_against_the_formula(full_rank):
    """`g = lower_bound * sigmoid(exp(A_log_h) * (W_f x + dt_bias))`: in
    (lower_bound, 0) whatever the input, one rate a head; `b = sigmoid(W_b
    x)` at `beta_scale` 1."""
    rng = np.random.default_rng(0)
    e, h, d = 24, 3, 8
    lp = _gate_params(rng, e, h, d, full_rank)
    x = jnp.asarray(4.0 * rng.normal(size=(11, e)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        g, beta = kda_gates(x, lp, h, d, lower_bound=-5.0, beta_scale=1.0)
        raw = (x @ lp["f"] if full_rank else (x @ lp["f_a"]) @ lp["f_b"])
        b_want = 1 / (1 + np.exp(-np.asarray(x @ lp["b_proj"], np.float64)))
    raw = np.asarray(raw, np.float64) + np.asarray(lp["dt_bias"], np.float64)
    rate = np.exp(np.asarray(lp["A_log"], np.float64))
    want = -5.0 / (1 + np.exp(-rate[None, :, None] * raw.reshape(11, h, d)))
    assert g.shape == (11, h, d) and g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(g), want, rtol=2e-5, atol=1e-6)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.9 and float(g.max()) > -0.1   # both ends met
    np.testing.assert_allclose(np.asarray(beta), b_want, rtol=1e-5)


def test_softplus_gate_is_the_default_and_differs():
    """Without `lower_bound` the gate is Solar Open 2's `-exp(A_log) *
    softplus(.)` and the write strength `2 x sigmoid`: unbounded below."""
    rng = np.random.default_rng(1)
    e, h, d = 24, 3, 8
    lp = _gate_params(rng, e, h, d, full_rank=False)
    x = jnp.asarray(4.0 * rng.normal(size=(11, e)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        g, beta = kda_gates(x, lp, h, d)
        g5, beta1 = kda_gates(x, lp, h, d, lower_bound=-5.0, beta_scale=1.0)
        raw = ((x @ lp["f_a"]) @ lp["f_b"]) + lp["dt_bias"]
    want = (-np.exp(np.asarray(lp["A_log"]))[None, :, None]
            * np.log1p(np.exp(np.asarray(raw, np.float64))).reshape(11, h, d))
    np.testing.assert_allclose(np.asarray(g), want, rtol=2e-5, atol=1e-6)
    assert float(g.min()) < -5.0
    np.testing.assert_allclose(np.asarray(beta), 2 * np.asarray(beta1),
                               rtol=1e-6)
    assert float(jnp.abs(g - g5).max()) > 1.0


# --- the latent block: absorbed over a pool against expanded -----------------

CFG = dict(num_attention_heads=4, q_lora_rank=None, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
           rms_norm_eps=1e-6, rope_theta=6000000, rope_scaling=None,
           max_position_embeddings=256, hidden_size=64,
           gated_attention_proj_granularity_type="head_wise")


def _published(rng, cfg):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d_q = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, d_r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    return {"q_proj.weight": lin(h * d_q, e, 2.0),
            "kv_a_proj_with_mqa.weight": lin(r + d_r, e, 2.0),
            "kv_a_layernorm.weight": (1 + 0.1 * rng.normal(size=r)).astype(
                np.float32),
            "kv_b_proj.weight": lin(
                h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]), r),
            "g_proj.weight": lin(h, e),
            "o_proj.weight": lin(e, h * cfg["v_head_dim"])}


@pytest.mark.parametrize("gated", [True, False], ids=["head_gate", "no_gate"])
def test_absorbed_block_over_a_pool_matches_the_expanded_reference(gated):
    """A 40-token sequence as one `mixed` step's rows over a paged latent
    pool (blocks out of order), written and read back by the absorbed
    form: equal to the expanded attention over the whole sequence, with
    the gate a head and without it."""
    cfg = dict(CFG)
    if not gated:
        cfg["gated_attention_proj_granularity_type"] = None
    rng = np.random.default_rng(3)
    w = _published(rng, cfg)
    block = LatentAttentionBlock(types.SimpleNamespace(**cfg), 128)
    assert block.head_gate == gated and not block.q_rank
    shapes = block.param_shapes(cfg["hidden_size"])
    assert ("attn_gate" in shapes) == gated and "q" in shapes
    assert not {"q_a", "q_a_norm", "q_b"} & set(shapes)
    lp = block.load("", lambda k: jnp.asarray(w[k].T),
                    lambda k: jnp.asarray(w[k]))
    assert {k: v.shape for k, v in lp.items()} == shapes

    t, bs = 40, 16
    x = jnp.asarray(rng.normal(size=(t, cfg["hidden_size"])), jnp.float32)
    table = np.asarray([5, 2, 7], np.int32)          # the sequence's blocks
    pos = np.arange(t)
    meta = AttentionMetadata(
        slot_mapping=jnp.asarray(table[pos // bs] * bs + pos % bs)[:, None],
        context_lens=jnp.asarray(pos + 1, jnp.int32),
        block_tables=jnp.asarray(np.tile(table, (t, 1))))
    pool = jnp.asarray(rng.normal(size=(8, 1, bs, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, (new_pool, ) = block(lp, x[:, None, :], (pool, ), meta,
                                  jnp.asarray(pos)[:, None])
        ref_w = {("dense.weight" if k == "o_proj.weight" else k): v
                 for k, v in w.items()}
        want = ref.mla(cfg, ref_w, "", x, pos, gated=gated)
        other = ref.mla(cfg, ref_w, "", x, pos, gated=not gated)
    assert float(jnp.abs(out[:, 0] - want).max()) < 2e-5
    assert float(jnp.abs(out[:, 0] - other).max()) > 1e-2
    # the blocks no row was written to keep their bytes
    np.testing.assert_array_equal(np.asarray(new_pool[0]),
                                  np.asarray(pool[0]))
