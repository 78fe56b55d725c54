"""Grouped (ragged) and dense MoE vs a plain per-token reference, vs each
other, and vs the HF Mixtral block.

Reference test role: `tests/kernels/test_moe.py` (Triton fused_moe vs HF
MixtralSparseMoeBlock).

Every stack here is in the stored layout, `[N, I, D]` for w1, w2 and w3
(layers/moe.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers.moe import (Routing, dense_chunking, moe_ffn,
                                       moe_ffn_dense, moe_ffn_grouped)


def _rand_weights(key, n, d, i, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    gate_w = jax.random.normal(ks[0], (d, n), jnp.float32) * 0.1
    w1 = jax.random.normal(ks[1], (n, i, d), dtype) * 0.1
    w2 = jax.random.normal(ks[2], (n, i, d), dtype) * 0.1
    w3 = jax.random.normal(ks[3], (n, i, d), dtype) * 0.1
    return gate_w, w1, w2, w3


def _per_token_reference(x, gate_w, w1, w2, w3, top_k, renormalize):
    """One token and one selected expert at a time, float64 numpy: softmax
    over all experts, top-k (ties to the lower index, as lax.top_k), then
    down(silu(gate(x)) * up(x)) weighted by the routing weight. Shares
    nothing with either path under test."""
    x, gate_w, w1, w2, w3 = (np.asarray(a, np.float64)
                             for a in (x, gate_w, w1, w2, w3))
    out = np.zeros_like(x)
    for ti, row in enumerate(x):
        logits = row @ gate_w
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        chosen = np.argsort(-probs, kind="stable")[:top_k]
        weights = probs[chosen]
        if renormalize:
            weights = weights / weights.sum()
        for e, wt in zip(chosen, weights):
            gate = w1[e] @ row                                 # [I]
            up = w3[e] @ row
            h = gate / (1.0 + np.exp(-gate)) * up
            out[ti] += wt * (h @ w2[e])                        # [D]
    return out


@pytest.mark.parametrize("t", [1, 100, 128, 129, 384, 512])
@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["renorm", "no-renorm"])
@pytest.mark.parametrize("n,top_k", [(8, 2), (64, 6)])
def test_both_paths_match_per_token_reference(t, renormalize, n, top_k):
    """Row counts on both sides of a chunk (128 | 129), needing pad rows
    (1, 100), and cut in two chunks (384 -> 2 x 192, 512 -> 2 x 256)."""
    key = jax.random.PRNGKey(1000 * n + t)
    d, i = 16, 24
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    ref = _per_token_reference(x, gate_w, w1, w2, w3, top_k, renormalize)

    routing = Routing(renormalize=renormalize)
    dense = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k, routing=routing)
    grouped = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=16,
                              routing=routing)
    assert dense.shape == grouped.shape == (t, d)
    np.testing.assert_allclose(np.asarray(dense), ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(grouped), ref, rtol=2e-4,
                               atol=2e-5)


def test_dense_chunk_is_sized_to_the_rows_present():
    """As few chunks as chunk_size allows, equal, each padded to the
    16-row tile and no further."""
    assert dense_chunking(128, 256) == (1, 128)
    assert dense_chunking(384, 256) == (2, 192)
    assert dense_chunking(512, 256) == (2, 256)
    assert dense_chunking(1, 256) == (1, 16)
    assert dense_chunking(129, 256) == (1, 144)
    for t in range(1, 1200):
        num, rows = dense_chunking(t, 256)
        assert num == -(-t // 256) and rows <= 256 and rows % 16 == 0
        # Under one tile of pad a chunk; with one chunk, at most 15 rows.
        assert 0 <= num * rows - t < 16 * num, (t, num, rows)
    assert dense_chunking(100, 100) == (2, 64)       # never over chunk_size
    # The compiled dense path carries exactly those rows: 128 rows make
    # expert activations [N, 128, I], not [N, 256, I].
    n, d, i = 4, 16, 32
    gate_w, w1, w2, w3 = _rand_weights(jax.random.PRNGKey(0), n, d, i)
    for t, rows in ((128, 128), (384, 192)):
        text = jax.jit(moe_ffn_dense, static_argnums=5).lower(
            jnp.zeros((t, d)), gate_w, w1, w2, w3, 2).as_text()
        assert f"tensor<{n}x{rows}x{i}xf32>" in text
        assert f"tensor<{n}x256x{i}xf32>" not in text


@pytest.mark.parametrize("t", [1, 7, 64, 300])
@pytest.mark.parametrize("n,top_k", [(8, 2), (4, 1), (4, 4)])
@pytest.mark.parametrize("block", [8, 32])
def test_grouped_matches_dense(t, n, top_k, block):
    key = jax.random.PRNGKey(42)
    d, i = 16, 32
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)

    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    out = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_grouped_skewed_routing():
    """All tokens route to one expert — exercises group padding bounds."""
    n, top_k, d, i, t = 8, 2, 16, 32, 96
    key = jax.random.PRNGKey(0)
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    # Bias the router so experts 3 and 5 dominate every token.
    gate_w = gate_w.at[:, 3].add(50.0).at[:, 5].add(40.0)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d), jnp.float32)

    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    out = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_matches_hf_mixtral_block():
    """Both paths vs the HF MixtralSparseMoeBlock golden (fp32)."""
    import torch
    from transformers import MixtralConfig
    from transformers.models.mixtral.modeling_mixtral import (
        MixtralSparseMoeBlock)

    d, i, n, top_k, t = 32, 64, 4, 2, 40
    cfg = MixtralConfig(hidden_size=d, intermediate_size=i,
                        num_local_experts=n, num_experts_per_tok=top_k)
    torch.manual_seed(0)
    blk = MixtralSparseMoeBlock(cfg).eval()
    x_t = torch.randn(1, t, d)
    with torch.no_grad():
        ref = blk(x_t)[0][0].numpy()

    gate_w = jnp.asarray(blk.gate.weight.detach().numpy().T)
    # w1/w3 as the block has them ([out, in]); w2 transposed.
    w1 = jnp.stack([jnp.asarray(e.w1.weight.detach().numpy())
                    for e in blk.experts])
    w2 = jnp.stack([jnp.asarray(e.w2.weight.detach().numpy().T)
                    for e in blk.experts])
    w3 = jnp.stack([jnp.asarray(e.w3.weight.detach().numpy())
                    for e in blk.experts])
    x = jnp.asarray(x_t[0].numpy())

    out_d = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    out_g = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, block=16)
    np.testing.assert_allclose(np.asarray(out_d), ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out_g), ref, rtol=2e-4, atol=2e-4)


def test_dispatcher_picks_paths():
    """moe_ffn output is identical regardless of which path it picks."""
    n, top_k, d, i = 4, 2, 16, 32
    key = jax.random.PRNGKey(7)
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    for t in (3, 600):
        x = jax.random.normal(jax.random.fold_in(key, t), (t, d),
                              jnp.float32)
        ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
        out = moe_ffn(x, gate_w, w1, w2, w3, top_k, block=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
