"""The short convolution's Pallas body against its jnp oracle, and the
64-wide (and narrower) heads through the paged attention kernels by way of
a pool whose rows are one 128-lane tile. On CPU the kernels run under Pallas
TPU interpret mode (tests/kernels/conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.ops import dispatch
from intellillm_tpu.ops.short_conv import (short_conv_body,
                                           short_conv_body_reference)

requires_tpu = pytest.mark.kernel


@pytest.fixture
def kernels_on():
    dispatch.set_use_pallas(True)
    yield
    dispatch.set_use_pallas(None)


@requires_tpu
@pytest.mark.parametrize("rows,hidden,dtype,tol", [
    (16, 128, np.float32, 1e-6), (128, 2048, jnp.bfloat16, 0.0),
    (48, 384, jnp.bfloat16, 0.0), (512, 256, np.float32, 1e-6)])
def test_short_conv_kernel_matches_oracle(rows, hidden, dtype, tol):
    from intellillm_tpu.ops.pallas.short_conv import (short_conv,
                                                      short_conv_supported)
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(                               # noqa: E731
        rng.normal(size=s).astype(np.float32)).astype(dtype)
    bcx, p1, p2 = draw(rows, 3 * hidden), draw(rows, hidden), draw(rows,
                                                                  hidden)
    w = draw(3, hidden)
    assert short_conv_supported(bcx)
    y_k, g_k = jax.block_until_ready(short_conv(bcx, p1, p2, w))
    y_r, g_r = short_conv_body_reference(bcx, p1, p2, w)
    np.testing.assert_allclose(np.asarray(g_k, np.float32),
                               np.asarray(g_r, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol,
                               rtol=tol)


@requires_tpu
def test_seam_takes_the_kernel_or_says_reference(kernels_on):
    from intellillm_tpu.obs import get_compile_tracker
    rng = np.random.default_rng(1)
    draw = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731

    def paths():
        return dict(get_compile_tracker().snapshot()["kernel_dispatch"])

    before = paths()
    # whole tiles: the kernel
    y, g = short_conv_body(draw(16, 384), draw(16, 128), draw(16, 128),
                           draw(3, 128))
    jax.block_until_ready((y, g))
    # 12 rows / 96 channels: no whole tiles, the oracle, and it says so
    short_conv_body(draw(12, 288), draw(12, 96), draw(12, 96), draw(3, 96))
    after = paths()
    assert after.get("pallas:short_conv", 0) == before.get(
        "pallas:short_conv", 0) + 1
    assert after.get("reference:short_conv", 0) == before.get(
        "reference:short_conv", 0) + 1


def _batch(rng, head, width, *, nb=32, bs=16, w=4, hq=4, hkv=2,
           ctx_lens=(1, 17, 40, 30, 31, 32, 0)):
    """Decode rows, a chunk run (rows 3..5: one sequence at consecutive
    positions) and a pad row; pools `width` lanes wide whose lanes past
    `head` are zero."""
    b = len(ctx_lens)

    def padded(shape):
        x = np.zeros(shape[:-1] + (width, ), np.float32)
        x[..., :head] = rng.normal(size=shape[:-1] + (head, ))
        return jnp.asarray(x)

    k_pool, v_pool = padded((nb, hkv, bs, head)), padded((nb, hkv, bs, head))
    tables = rng.permutation(nb)[:b * w].reshape(b, w).astype(np.int32)
    tables[4] = tables[3]
    tables[5] = tables[3]
    slots = [(-1 if c == 0 else
              int(tables[i, (c - 1) // bs]) * bs + (c - 1) % bs)
             for i, c in enumerate(ctx_lens)]
    draw = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731
    return (draw(b, 1, hq, head), draw(b, 1, hkv, head),
            draw(b, 1, hkv, head), k_pool, v_pool,
            jnp.asarray(np.asarray(slots, np.int32)), jnp.asarray(tables),
            jnp.asarray(np.asarray(ctx_lens, np.int32)))


@requires_tpu
@pytest.mark.parametrize("head", [64, 16])
def test_narrow_heads_run_the_ragged_kernel_through_a_padded_pool(
        kernels_on, head):
    """`PagedAttention` with a pool one lane tile wide: q, k, v are padded
    to it, the `ragged` seam takes its kernel (label `pallas:ragged`), and
    the result is the oracle's at the head's own width."""
    from intellillm_tpu.layers.attention import (AttentionMetadata,
                                                 PagedAttention)
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.ragged_attention import (
        ragged_fused_attention_reference)
    q, k, v, kp, vp, slots, tables, ctx = _batch(np.random.default_rng(2),
                                                 head, 128)
    layer = PagedAttention(4, head, head ** -0.5, 2)
    meta = AttentionMetadata(slot_mapping=slots[:, None], context_lens=ctx,
                             block_tables=tables)
    count = lambda: get_compile_tracker().snapshot()[            # noqa: E731
        "kernel_dispatch"].get("pallas:ragged", 0)
    n0 = count()
    out, (kp2, vp2) = layer(q, k, v, (kp, vp), meta)
    jax.block_until_ready(out)
    assert count() == n0 + 1
    want, kr, vr = ragged_fused_attention_reference(
        q, k[:, 0], v[:, 0], kp[..., :head], vp[..., :head], slots, tables,
        ctx, head ** -0.5)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(want)[live], atol=2e-3)
    assert out.shape[-1] == head
    # the pool holds the new rows at the head's width and zeros beyond it
    np.testing.assert_allclose(np.asarray(kp2)[..., :head], np.asarray(kr),
                               atol=1e-6)
    assert not np.asarray(kp2)[..., head:].any()
    assert not np.asarray(vp2)[..., head:].any()


@requires_tpu
@pytest.mark.parametrize("head", [64, 16])
def test_narrow_heads_run_the_paged_kernel_in_the_staged_decode(
        kernels_on, head):
    """The K-step decode's layer: pool part through the `paged` kernel
    (label `pallas:paged`), stage part and merge, at a padded head."""
    from intellillm_tpu.layers.attention import (AttentionMetadata,
                                                 PagedAttention)
    from intellillm_tpu.obs import get_compile_tracker
    rng = np.random.default_rng(3)
    q, k, v, kp, vp, _, tables, ctx = _batch(
        rng, head, 128, ctx_lens=(5, 17, 40, 30, 16, 32, 0))
    b, c = q.shape[0], 4
    stage = lambda: jnp.zeros((b, c, 2, 128), jnp.float32)      # noqa: E731
    layer = PagedAttention(4, head, head ** -0.5, 2)
    outs = {}
    for name, force in (("kernel", True), ("oracle", False)):
        dispatch.set_use_pallas(force)
        meta = AttentionMetadata(context_lens=ctx, block_tables=tables,
                                 staged=True,
                                 stage_index=jnp.asarray(0, jnp.int32))
        n0 = get_compile_tracker().snapshot()["kernel_dispatch"].get(
            "pallas:paged", 0)
        out, cache = layer(q, k, v, (kp, vp, stage(), stage()), meta)
        outs[name] = np.asarray(jax.block_until_ready(out))
        if force:
            assert get_compile_tracker().snapshot()["kernel_dispatch"][
                "pallas:paged"] == n0 + 1
            assert cache[2].shape == (b, c, 2, 128)
    live = np.asarray(ctx) > 0
    assert outs["kernel"].shape[-1] == head
    np.testing.assert_allclose(outs["kernel"][live], outs["oracle"][live],
                               atol=2e-3)
