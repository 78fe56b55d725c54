"""Grouped and dense MoE vs a plain per-token reference, vs each other,
and vs the HF Mixtral block; the grouped form through its jnp oracle and
through the Pallas kernels (interpret mode here: conftest.py).

Reference test role: `tests/kernels/test_moe.py` (Triton fused_moe vs HF
MixtralSparseMoeBlock).

Every stack here is in the stored layout, `[N, I, D]` for w1, w2 and w3
(layers/moe.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers import moe
from intellillm_tpu.layers.moe import (Routing, _route, dense_chunking,
                                       moe_ffn, moe_ffn_dense,
                                       moe_ffn_grouped)


def _rand_weights(key, n, d, i, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    gate_w = jax.random.normal(ks[0], (d, n), jnp.float32) * 0.1
    w1 = jax.random.normal(ks[1], (n, i, d), dtype) * 0.1
    w2 = jax.random.normal(ks[2], (n, i, d), dtype) * 0.1
    w3 = jax.random.normal(ks[3], (n, i, d), dtype) * 0.1
    return gate_w, w1, w2, w3


def _per_token_reference(x, gate_w, w1, w2, w3, top_k, routing,
                         select_bias=None):
    """One token and one selected expert at a time, float64 numpy: the
    scores, the group limit, top-k (ties to the lower index, as
    lax.top_k), the weights' normalisation and scale, then
    down(silu(gate(x)) * up(x)) weighted by the routing weight, for the
    chosen experts the stack holds. Shares nothing with either form under
    test."""
    x, gate_w, w1, w2, w3 = (np.asarray(a, np.float64)
                             for a in (x, gate_w, w1, w2, w3))
    n = w1.shape[0]
    out = np.zeros_like(x)
    for ti, row in enumerate(x):
        logits = row @ gate_w
        if routing.scoring == "softmax":
            scores = np.exp(logits - logits.max())
            scores /= scores.sum()
        else:
            scores = 1.0 / (1.0 + np.exp(-logits))
        choose = scores.copy()
        if routing.select_bias:
            choose = choose + np.asarray(select_bias, np.float64)
        if routing.n_group > 1:
            per = len(choose) // routing.n_group
            best = np.argsort(-choose.reshape(routing.n_group, per).max(-1),
                              kind="stable")[:routing.topk_group]
            kept = np.zeros(routing.n_group, bool)
            kept[best] = True
            choose = np.where(np.repeat(kept, per), choose, -np.inf)
        chosen = np.argsort(-choose, kind="stable")[:top_k]
        weights = scores[chosen]
        if routing.renormalize:
            weights = weights / (weights.sum() + routing.sum_eps)
        weights = weights * routing.scale
        for e, wt in zip(chosen - routing.held_start, weights):
            if not 0 <= e < n:
                continue                           # held on another chip
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
    routing = Routing(renormalize=renormalize)
    ref = _per_token_reference(x, gate_w, w1, w2, w3, top_k, routing)

    dense = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k, routing=routing)
    grouped = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, tile=16,
                              routing=routing)
    assert dense.shape == grouped.shape == (t, d)
    np.testing.assert_allclose(np.asarray(dense), ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(grouped), ref, rtol=2e-4,
                               atol=2e-5)


# (held experts, router width, top-k, Routing) of the three MoE cells and of
# Mixtral; A.X-K1's share sits in the middle of its router's range.
CELL_ROUTINGS = {
    "dsmoe16b": (64, 64, 6, Routing(renormalize=False)),
    "axk1-share": (12, 192, 8, Routing(scoring="sigmoid", n_group=8,
                                       topk_group=4, scale=2.5,
                                       held_start=24)),
    "lfm2moe": (32, 32, 4, Routing(scoring="sigmoid", select_bias=True,
                                   sum_eps=1e-6)),
    "mixtral": (8, 8, 2, Routing()),
}


@pytest.mark.parametrize("t", [257, 384, 512])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("cell", sorted(CELL_ROUTINGS))
def test_grouped_form_at_the_cells_routing(cell, kernel, t, force_pallas):
    """The grouped form, through the oracle and through the kernels, at
    the routing settings the cells run, on the grouped side of the rule.
    One held expert gets every row (its group spans many row tiles), one
    gets none, the others' groups start and end inside tiles of 32 rows,
    and T*K is no whole number of tiles except at 512 (pad rows)."""
    n, n_router, top_k, routing = CELL_ROUTINGS[cell]
    d, i = 128, 128
    key = jax.random.PRNGKey(sum(map(ord, cell)) + t)
    _, w1, w2, w3 = _rand_weights(key, n, d, i)
    gate_w = jax.random.normal(key, (d, n_router), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    # A constant feature drives the logits of two held experts: `hot` is
    # every row's first choice, `cold` is nobody's.
    hot, cold = routing.held_start + 1, routing.held_start + 2
    x = x.at[:, 0].set(1.0)
    gate_w = gate_w.at[0, hot].set(30.0).at[0, cold].set(-30.0)
    bias = (jax.random.normal(jax.random.fold_in(key, 2), (n_router, ))
            * 0.05 if routing.select_bias else None)
    ref = _per_token_reference(x, gate_w, w1, w2, w3, top_k, routing, bias)

    routed = _route(x, gate_w, top_k, routing, select_bias=bias)
    chosen = np.asarray(routed[1])
    assert (chosen == hot).any(-1).all() and not (chosen == cold).any()
    force_pallas(kernel)
    out = jax.block_until_ready(moe_ffn_grouped(
        x, gate_w, w1, w2, w3, top_k, routing=routing, routed=routed,
        tile=32))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


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
@pytest.mark.parametrize("tile", [8, 32])
def test_grouped_matches_dense(t, n, top_k, tile):
    key = jax.random.PRNGKey(42)
    d, i = 16, 32
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)

    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    out = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, tile=tile)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_grouped_skewed_routing():
    """All tokens route to two experts: groups of many tiles beside
    groups of none."""
    n, top_k, d, i, t = 8, 2, 16, 32, 96
    key = jax.random.PRNGKey(0)
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    # Bias the router so experts 3 and 5 dominate every token.
    gate_w = gate_w.at[:, 3].add(50.0).at[:, 5].add(40.0)
    x = jax.random.normal(jax.random.fold_in(key, 2), (t, d), jnp.float32)

    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    out = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, tile=16)
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
    out_g = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, tile=16)
    np.testing.assert_allclose(np.asarray(out_d), ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out_g), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows,path", [(128, "dense"), (256, "dense"),
                                       (257, "grouped"), (512, "grouped")])
@pytest.mark.parametrize("n,n_router,top_k", [(8, 8, 2), (12, 192, 8),
                                              (32, 32, 4), (64, 64, 6)])
def test_dispatcher_picks_paths(monkeypatch, n, n_router, top_k, rows, path):
    """From 128 rows on every expert is chosen by some row at these
    routings: what fits one dense chunk stays dense, more goes grouped;
    and `moe_ffn` gives what the dense form gives on either side."""
    took = []
    for name in ("dense", "grouped"):
        def spy(*a, name=name, form=getattr(moe, "moe_ffn_" + name), **kw):
            took.append(name)
            return form(*a, **kw)
        monkeypatch.setattr(moe, "moe_ffn_" + name, spy)
    d, i = 16, 32
    key = jax.random.PRNGKey(7 + n)
    _, w1, w2, w3 = _rand_weights(key, n, d, i)
    gate_w = jax.random.normal(key, (d, n_router), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, rows), (rows, d),
                          jnp.float32)
    out = moe.moe_ffn(x, gate_w, w1, w2, w3, top_k)
    assert took == [path]
    assert moe.grouped_rows(rows, top_k, n_router) == (path == "grouped")
    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_grouped_form_over_a_mesh_sums_the_inner_shards():
    """On a mesh that spans chips each chip runs the grouped form on its
    share of the stacks' inner dimension and the float32 partial sums are
    added (an inner width the axis does not divide runs whole)."""
    from jax.sharding import Mesh
    n, top_k, d, t = 8, 2, 16, 300
    key = jax.random.PRNGKey(3)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4),
                ("data", "model"))
    for i in (32, 24 + 6):
        gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
        x = jax.random.normal(jax.random.fold_in(key, i), (t, d),
                              jnp.float32)
        ref = moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k, tile=16)
        out = jax.jit(lambda *a: moe_ffn_grouped(
            *a, top_k, tile=16, mesh=mesh))(x, gate_w, w1, w2, w3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


# (top-k, router width) of the configurations that run `moe_ffn`, and the
# rows from which a step of up to DENSE_ROWS is dense: below them a good
# share of the experts is chosen by no row (CHOSEN_SHARE_CUT).
RULE_CONFIGS = {"dsmoe16b": (6, 64, 32), "smallthinker": (6, 64, 32),
                "lfm2moe": (4, 32, 32), "axk1-share": (8, 192, 64),
                "mixtral": (2, 8, 16)}


@pytest.mark.parametrize("rows", [1, 4, 8, 16, 32, 64, 128, 256, 512])
@pytest.mark.parametrize("config", sorted(RULE_CONFIGS))
def test_the_rule_as_a_table(config, rows):
    """`grouped_rows` at the decode and `mixed` buckets the cells reach and
    at a lone request's rows: grouped under `dense_from` rows (16 decode
    rows of top-6 of 64 are expected to choose 0.79 of the experts, 16 of
    top-4 of 32 0.88, 32 of top-8 of 192 0.74, 32 of top-6 of 64 0.96) and
    over 256."""
    top_k, width, dense_from = RULE_CONFIGS[config]
    assert moe.grouped_rows(rows, top_k, width) == (
        rows < dense_from or rows > moe.DENSE_ROWS)
    share = moe.chosen_share(rows, top_k, width)
    assert 0.0 < share <= 1.0
    if rows <= moe.DENSE_ROWS:
        assert (share < moe.CHOSEN_SHARE_CUT) == (rows < dense_from)


# (held, router width, top-k, Routing, activation): ReGLU and SiLU
# experts of 64 top-6, a share of a group-limited router, and 32 top-4
# chosen with a selection bias.
FEW_ROW_CELLS = {
    "reglu-64x6": (64, 64, 6, Routing(), "relu"),
    "silu-64x6": (64, 64, 6, Routing(renormalize=False), "silu"),
    "share-12-of-192x8": (12, 192, 8, Routing(
        scoring="sigmoid", n_group=8, topk_group=4, scale=2.5,
        held_start=24), "silu"),
    "bias-32x4": (32, 32, 4, Routing(scoring="sigmoid", select_bias=True,
                                     sum_eps=1e-6), "silu"),
}


@pytest.mark.parametrize("t", [1, 4, 13, 16])
@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("cell", sorted(FEW_ROW_CELLS))
def test_few_rows_equal_the_dense_form(cell, kernel, t, force_pallas):
    """`moe_ffn` at a decode step's few rows (the grouped form, by the
    rule) gives what the dense form gives at the same rows, and reads the
    experts that have a row and no other: the count it hands back is the
    number of held experts some row chose, which is the kernels' number
    of visits (the sorted rows are one tile of 128)."""
    from intellillm_tpu.ops.pallas.grouped_ffn import visit_metadata
    n, n_router, top_k, routing, activation = FEW_ROW_CELLS[cell]
    d, i = 128, 128
    key = jax.random.PRNGKey(sum(map(ord, cell)) + t)
    _, w1, w2, w3 = _rand_weights(key, n, d, i)
    gate_w = jax.random.normal(key, (d, n_router), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    bias = (jax.random.normal(jax.random.fold_in(key, 2), (n_router, ))
            * 0.05 if routing.select_bias else None)
    routed = _route(x, gate_w, top_k, routing, select_bias=bias)
    ref = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k, routing=routing,
                        routed=routed, activation=activation)
    force_pallas(kernel)
    out, visited = jax.block_until_ready(moe_ffn(
        x, gate_w, w1, w2, w3, top_k, routing=routing, routed=routed,
        activation=activation, count_visited=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    local = np.asarray(routed[1]) - routing.held_start
    sizes = np.bincount(local[(local >= 0) & (local < n)], minlength=n)
    assert moe.grouped_rows(t, top_k, n_router)
    assert int(visited) == (sizes > 0).sum() < n
    (_, expert_of, _), visits = visit_metadata(
        jnp.asarray(sizes, jnp.int32), 128, 128)
    assert int(visits) == int(visited)
    # No visit goes to an expert without a row.
    assert set(np.asarray(expert_of)[:int(visits)]) == set(
        np.flatnonzero(sizes))


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_kernels_at_one_tile_of_a_decode_step(activation):
    """The two kernels at what 16 decode rows of top-6 of 64 give them:
    M = one tile of 128 sorted rows of which 96 are live, 46 experts with
    one to four rows each and 18 with none, against the jnp oracle."""
    from intellillm_tpu.ops.grouped_ffn import grouped_expert_ffn_reference
    from intellillm_tpu.ops.pallas.grouped_ffn import (grouped_ffn,
                                                       visit_metadata)
    n, d, i, m, live = 64, 128, 256, 128, 96
    rng = np.random.default_rng(5)
    sizes = np.zeros(n, np.int32)
    chosen = rng.choice(n, size=46, replace=False)
    sizes[chosen] = 1
    for e in rng.choice(chosen, size=live - 46):
        sizes[e] += 1
    assert sizes.sum() == live and (sizes == 0).sum() == 18
    assert sizes.max() <= 6
    key = jax.random.PRNGKey(11)
    _, w1, w2, w3 = _rand_weights(key, n, d, i)
    xs = jax.random.normal(jax.random.fold_in(key, 1), (m, d), jnp.float32)
    sizes = jnp.asarray(sizes)
    _, visits = visit_metadata(sizes, m, 128)
    assert int(visits) == 46
    out = jax.block_until_ready(grouped_ffn(xs, w1, w2, w3, sizes, 128,
                                            activation))
    ref = grouped_expert_ffn_reference(xs, w1, w2, w3, sizes, activation)
    np.testing.assert_allclose(np.asarray(out)[:live],
                               np.asarray(ref)[:live], rtol=2e-4, atol=2e-5)


# rows, live rows, top-k, router width, (first held expert, experts held),
# whether the stacks are every layer's (`held_at`, the layer's place
# traced): the cell's decode step (16 seats, 13 live, 64 ReGLU experts
# top-6), the same on stacks of three layers, two expert-parallel shares,
# a `mixed` step's rows, and the edges of the live count.
LIVE_CASES = {
    "decode-16x13-64x6": (16, 13, 6, 64, (0, 64), False),
    "stacked-16x13-64x6": (16, 13, 6, 64, (0, 64), True),
    "share-16x11-192x8": (16, 11, 8, 192, (24, 12), False),
    "share-stacked-16x12-64x8": (16, 12, 8, 64, (16, 16), True),
    "chunk-300x260-64x6": (300, 260, 6, 64, (0, 64), False),
    "top1-8x5-4x1": (8, 5, 1, 4, (0, 4), False),
    "one-live-16x1": (16, 1, 6, 64, (0, 64), False),
    "all-live-16x16": (16, 16, 6, 64, (0, 64), False),
    "none-live-16x0": (16, 0, 6, 64, (0, 64), False),
}


def _grouped_with_mask(x, gate_w, stacks, top_k, routing, routed, live,
                       stacked=None):
    """`moe_ffn_grouped` with its count, jitted. `stacked`: (the layer's
    place among the layers whose stacks these are, experts a layer), the
    place traced, as a `lax.scan` over the layers hands it."""
    place, n = stacked or (None, None)

    @jax.jit
    def call(place, live):
        return moe_ffn_grouped(
            x, gate_w, *stacks, top_k, routing=routing, routed=routed,
            activation="relu", count_visited=True, live=live,
            held_at=None if place is None else (place * n, n))

    return jax.block_until_ready(
        call(None if place is None else jnp.int32(place), live))


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_rows_that_are_not_live_choose_no_expert(case, kernel, force_pallas):
    """`moe_ffn_grouped(live=)`: a live row's output is the unmasked
    call's bit for bit, every other row's is exactly 0, and the experts
    visited are the held ones the live rows' choices name, counted here
    from the routing."""
    t, n_live, top_k, n_router, (held_start, n), stacked = LIVE_CASES[case]
    d, i = 128, 128
    key = jax.random.PRNGKey(sum(map(ord, case)))
    layers, place = (3, (1, n)) if stacked else (1, None)
    _, w1, w2, w3 = _rand_weights(key, layers * n, d, i)
    gate_w = jax.random.normal(key, (d, n_router), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    routing = Routing(scoring="sigmoid", held_start=held_start)
    routed = _route(x, gate_w, top_k, routing)
    live = np.zeros(t, bool)
    live[np.random.default_rng(t + n_live).choice(t, n_live, False)] = True
    force_pallas(kernel)
    args = (x, gate_w, (w1, w2, w3), top_k, routing, routed)
    want, visited_unmasked = _grouped_with_mask(*args, None, place)
    got, visited = _grouped_with_mask(*args, jnp.asarray(live), place)
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got[live], want[live])
    assert not got[~live].any()
    assert n_live == 0 or np.abs(got[live]).max() > 0

    def held_chosen(rows):
        local = np.asarray(routed[1])[rows].reshape(-1) - held_start
        return len(set(local[(local >= 0) & (local < n)].tolist()))

    assert int(visited) == held_chosen(live)
    assert int(visited_unmasked) == held_chosen(np.ones(t, bool))
    assert int(visited) <= int(visited_unmasked)
    if n_live == t:
        # all live = no mask at all
        assert np.array_equal(got, want)
        assert int(visited) == int(visited_unmasked)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_an_expert_only_pad_rows_chose_gets_no_visit(kernel, force_pallas):
    """Routing built by hand: 13 live rows choose among experts 0-9, the
    3 pad rows choose experts 60-63 and one the live rows chose too. The
    kernels' group sizes hold the live rows' choices alone, so experts
    60-63 have no row and get no visit (`visit_metadata`)."""
    from intellillm_tpu.ops.pallas.grouped_ffn import visit_metadata
    t, top_k, n, d, i = 16, 3, 64, 128, 128
    key = jax.random.PRNGKey(51)
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    rng = np.random.default_rng(51)
    live = np.ones(t, bool)
    live[[2, 9, 15]] = False
    topi = np.stack([rng.choice(10, top_k, False) if live[r] else
                     np.array([60 + r % 4, 63 - r % 3, 4]) for r in range(t)])
    assert len({*topi[~live].reshape(-1)} - {*topi[live].reshape(-1)}) >= 3
    topw = rng.uniform(0.1, 1.0, size=(t, top_k)).astype(np.float32)
    routed = (jnp.asarray(topw), jnp.asarray(topi, jnp.int32))
    force_pallas(kernel)
    args = (x, gate_w, (w1, w2, w3), top_k, Routing(), routed)
    want, visited_unmasked = _grouped_with_mask(*args, None)
    got, visited = _grouped_with_mask(*args, jnp.asarray(live))
    chosen_by_live = set(topi[live].reshape(-1).tolist())
    assert int(visited) == len(chosen_by_live) <= 10
    assert int(visited_unmasked) == len(set(topi.reshape(-1).tolist()))
    assert int(visited_unmasked) - int(visited) >= 3
    assert np.array_equal(np.asarray(got)[live], np.asarray(want)[live])
    assert not np.asarray(got)[~live].any()
    sizes = np.bincount(topi[live].reshape(-1), minlength=n)
    (_, expert_of, _), visits = visit_metadata(
        jnp.asarray(sizes, jnp.int32), 128, 128)
    assert int(visits) == int(visited)
    assert set(np.asarray(expert_of)[:int(visits)]) == chosen_by_live


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_no_mask_and_the_dense_form_are_as_they_were(form):
    """`live=None` is the call without the argument in both forms, and the
    dense form takes no notice of a mask: it reads every stack whatever
    the rows say and computes a pad row like any other."""
    t, top_k, n, d, i = (16, 6, 64, 128, 128) if form == "grouped" else (
        64, 6, 64, 128, 128)
    key = jax.random.PRNGKey(7)
    gate_w, w1, w2, w3 = _rand_weights(key, n, d, i)
    x = jax.random.normal(jax.random.fold_in(key, 1), (t, d), jnp.float32)
    assert moe.grouped_rows(t, top_k, n) == (form == "grouped")
    live = jnp.arange(t) % 5 != 0
    today, visited_today = moe_ffn(x, gate_w, w1, w2, w3, top_k,
                                   count_visited=True)
    none, visited_none = moe_ffn(x, gate_w, w1, w2, w3, top_k,
                                 count_visited=True, live=None)
    assert np.array_equal(np.asarray(none), np.asarray(today))
    assert int(visited_none) == int(visited_today)
    masked, visited = moe_ffn(x, gate_w, w1, w2, w3, top_k,
                              count_visited=True, live=live)
    if form == "dense":
        assert np.array_equal(np.asarray(masked), np.asarray(today))
        assert int(visited) == n
    else:
        keep = np.asarray(live)
        assert np.array_equal(np.asarray(masked)[keep],
                              np.asarray(today)[keep])
        assert not np.asarray(masked)[~keep].any()
        assert int(visited) <= int(visited_today) < n
