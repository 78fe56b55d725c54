"""Interpret-mode parity: EVERY Pallas kernel against its jnp reference.

One suite, one shape apiece — the deep per-kernel grids live in
tests/kernels/; this file is the cheap cross-cutting safety net that a
CPU-tier CI job can run (and that skips with an explicit reason on jax
versions without `force_tpu_interpret_mode` — see conftest.py). If a
kernel gains a reference-contract change, it must show up here AND in
docs/kernels.md.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops.attention import decode_attention_reference
from intellillm_tpu.ops.ragged_attention import (
    ragged_fused_attention_reference)

TOL = dict(rtol=2e-3, atol=2e-3)


def test_paged_attention_parity(tpu_interpret):
    from intellillm_tpu.ops.pallas.paged_attention import paged_attention
    rng = np.random.default_rng(1)
    b, hq, hkv, d, nb, bs, w = 4, 8, 2, 128, 64, 16, 8
    k_cache = jnp.asarray(
        rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    v_cache = jnp.asarray(
        rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    tables = jnp.asarray(
        rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32))
    ctx = jnp.asarray(np.asarray([1, 17, 63, 128], np.int32))
    out, lse = paged_attention(q, k_cache, v_cache, tables, ctx, d**-0.5,
                               return_lse=True)
    ref, ref_lse = decode_attention_reference(q, k_cache, v_cache, tables,
                                              ctx, d**-0.5,
                                              return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               **TOL)


def test_ragged_fused_parity(tpu_interpret):
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(2)
    b, hq, hkv, d, nb, bs, w = 6, 4, 2, 128, 64, 16, 8
    k_cache = jnp.asarray(
        rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    v_cache = jnp.asarray(
        rng.normal(size=(nb, hkv, bs, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, d)).astype(np.float32))
    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    # A chunk run: rows 2..4 are one sequence at positions 29/30/31.
    tables[3] = tables[2]
    tables[4] = tables[2]
    ctx_lens = [1, 40, 30, 31, 32, 0]
    slots = []
    for i, c in enumerate(ctx_lens):
        if c == 0:
            slots.append(-1)
        else:
            blk = int(tables[i, (c - 1) // bs])
            slots.append(blk * bs + (c - 1) % bs)
    tables = jnp.asarray(tables)
    slots = jnp.asarray(np.asarray(slots, np.int32))
    ctx = jnp.asarray(np.asarray(ctx_lens, np.int32))

    out, kc, vc = ragged_paged_attention(q, k_new, v_new, k_cache,
                                         v_cache, slots, tables, ctx,
                                         d**-0.5)
    ref, kr, vr = ragged_fused_attention_reference(q, k_new, v_new,
                                                   k_cache, v_cache,
                                                   slots, tables, ctx,
                                                   d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vr))


def test_bgmv_parity(tpu_interpret):
    from intellillm_tpu.ops.pallas.bgmv import bgmv, bgmv_supported
    rng = np.random.default_rng(3)
    bsz, din, rank, dout, s = 8, 256, 16, 128, 4
    a = rng.normal(size=(s, din, rank)).astype(np.float32)
    b = rng.normal(size=(s, rank, dout)).astype(np.float32)
    a[0] = 0.0
    b[0] = 0.0
    a_stack, b_stack = jnp.asarray(a), jnp.asarray(b)
    x = jnp.asarray(rng.normal(size=(bsz, 1, din)).astype(np.float32))
    slots = jnp.asarray(np.asarray([0, 1, 2, 3, 0, 2, 1, 0], np.int32))
    assert bgmv_supported(x, a_stack, b_stack)

    out = bgmv(x, a_stack, b_stack, slots)
    a_sel, b_sel = a_stack[slots], b_stack[slots]
    h = jnp.einsum("bld,bdr->blr", x, a_sel,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    ref = jnp.einsum("blr,bro->blo", h, b_sel,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert (np.asarray(out)[np.asarray(slots) == 0] == 0.0).all()


def test_quant_matmul_parity(tpu_interpret):
    from intellillm_tpu.layers.quantization import (_dequant_int4,
                                                    quantize_int4)
    from intellillm_tpu.ops.pallas.quant_matmul import (quant_matmul_int4,
                                                        supports)
    rng = np.random.default_rng(4)
    in_, out_, gs, bsz = 256, 384, 32, 3
    w = {k: jnp.asarray(v) for k, v in quantize_int4(
        rng.standard_normal((in_, out_)).astype(np.float32), gs).items()}
    assert supports(w)
    x = jnp.asarray(rng.standard_normal((bsz, in_)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    ref = np.asarray(x @ _dequant_int4(w, x.dtype), np.float32)
    got = np.asarray(quant_matmul_int4(x, w), np.float32)
    np.testing.assert_allclose(got, ref, atol=0.15, rtol=0.02)
