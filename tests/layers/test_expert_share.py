"""`layers/moe.py`'s expert-parallel share, the counters' accumulator and
the routed-experts block the five MoE families call: fields, shapes, the
loader's slice, and the block against the sequence written out by hand."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers.moe import (DENSE_LAYER, ExpertCounts, ExpertShare,
                                       Routing, _route, held_assignments,
                                       moe_ffn, route, routed_experts)
from intellillm_tpu.models.weight_utils import stack_experts


@pytest.mark.parametrize("ep_size,ep_rank,want", [
    (1, 0, dict(n_router=12, held_start=0, held=range(0, 12),
                experts_held=36, moe_choices_per_token=0)),
    (4, 2, dict(n_router=48, held_start=24, held=range(24, 36),
                experts_held=36, moe_choices_per_token=15)),
], ids=["whole", "rank2of4"])
def test_share_fields(ep_size, ep_rank, want):
    share = ExpertShare(n_held=12, top_k=5, n_moe_layers=3, ep_size=ep_size,
                        ep_rank=ep_rank)
    assert {k: getattr(share, k) for k in want} == want


@pytest.mark.parametrize("ep_size,ep_rank", [(4, 4), (4, -1), (1, 1)])
def test_rank_outside_the_size_is_refused(ep_size, ep_rank):
    with pytest.raises(ValueError, match="outside ep_size"):
        ExpertShare(8, 2, 1, ep_size=ep_size, ep_rank=ep_rank)


def test_share_of_a_config_and_what_the_runner_reads():
    """`from_config` is the one reader of `ep_size` / `ep_rank` (absent or
    None: the whole model); `report_through` sets the two attributes
    `worker/model_runner.py` `_finalize` looks for."""
    cfg = types.SimpleNamespace(ep_size=8, ep_rank=3)
    share = ExpertShare.from_config(cfg, n_held=4, top_k=2, n_moe_layers=5)
    assert (share.ep_size, share.ep_rank, share.held_start) == (8, 3, 12)
    for cfg in (types.SimpleNamespace(),
                types.SimpleNamespace(ep_size=None, ep_rank=None)):
        whole = ExpertShare.from_config(cfg, 4, 2, 5)
        assert (whole.ep_size, whole.ep_rank) == (1, 0)
    with pytest.raises(ValueError, match="outside ep_size"):
        ExpertShare.from_config(types.SimpleNamespace(ep_size=2, ep_rank=2),
                                4, 2, 5)
    model = types.SimpleNamespace()
    share.report_through(model)
    assert model.counts_expert_visits is True
    assert model.moe_choices_per_token == 10
    whole.report_through(model)
    assert model.moe_choices_per_token == 0


@pytest.mark.parametrize("ep_size", [1, 2], ids=["whole", "share"])
def test_counters_sum_the_layers_and_skip_nothing_of_a_dense_one(ep_size):
    share = ExpertShare(4, top_k=2, n_moe_layers=2, ep_size=ep_size)
    counts = ExpertCounts(share)
    held = [jnp.asarray([[1], [2], [0]], jnp.int32),
            jnp.asarray([[2], [0], [1]], jnp.int32)]
    counts.add(DENSE_LAYER)
    counts.add((jnp.int32(3), held[0] if ep_size > 1 else None))
    counts.add((jnp.int32(4), held[1] if ep_size > 1 else None))
    counters = counts.counters()
    assert counters["expert_visits"].dtype == jnp.int32
    assert counters["expert_visits"].tolist() == [7, 8]
    if ep_size > 1:
        assert sorted(counters) == ["expert_visits", "held"]
        assert counters["held"].tolist() == [[3], [2], [1]]
    else:
        assert sorted(counters) == ["expert_visits"]


def test_counters_of_dense_layers_alone():
    counts = ExpertCounts(ExpertShare(4, 2, n_moe_layers=0))
    counts.add(DENSE_LAYER)
    assert counts.counters()["expert_visits"].tolist() == [0, 0]


@pytest.mark.parametrize("select_bias,shared_inter,want", [
    (False, 0, ["gate_router", "w1", "w2", "w3"]),
    (True, 0, ["gate_router", "expert_bias", "w1", "w2", "w3"]),
    (False, 24, ["gate_router", "w1", "w2", "w3", "shared_gate",
                 "shared_up", "shared_down"]),
    (True, 24, ["gate_router", "expert_bias", "w1", "w2", "w3",
                "shared_gate", "shared_up", "shared_down"]),
], ids=["plain", "bias", "shared", "bias_shared"])
def test_param_shapes_in_the_order_the_seeded_draws_walk(select_bias,
                                                         shared_inter, want):
    share = ExpertShare(3, top_k=2, n_moe_layers=1, ep_size=4, ep_rank=1)
    shapes = share.param_shapes(16, 8, select_bias=select_bias,
                                shared_inter=shared_inter)
    assert list(shapes) == want
    assert shapes["gate_router"] == (16, 12)
    assert shapes["w1"] == shapes["w2"] == shapes["w3"] == (3, 8, 16)
    if select_bias:
        assert shapes["expert_bias"] == (12, )
    if shared_inter:
        assert shapes["shared_gate"] == shapes["shared_up"] == (16, 24)
        assert shapes["shared_down"] == (24, 16)


def test_the_loader_stacks_the_held_experts_alone():
    """Rank 1 of 4 takes experts [3, 6) of a checkpoint's 12: gate and up
    as published ([out, in] = [I, D]), down transposed to [I, D]."""
    rng = np.random.default_rng(0)
    share = ExpertShare(3, top_k=2, n_moe_layers=1, ep_size=4, ep_rank=1)
    raw = {}
    for j in range(share.n_router):
        raw[f"m.experts.{j}.g.weight"] = rng.normal(size=(8, 16)).astype(
            np.float32)
        raw[f"m.experts.{j}.u.weight"] = rng.normal(size=(8, 16)).astype(
            np.float32)
        raw[f"m.experts.{j}.d.weight"] = rng.normal(size=(16, 8)).astype(
            np.float32)
    stacks = stack_experts(raw, "m.experts.", share.held, ("g", "d", "u"),
                           "float32")
    assert list(stacks) == ["w1", "w2", "w3"]
    assert list(share.held) == [3, 4, 5]
    for at, j in enumerate(share.held):
        np.testing.assert_array_equal(stacks["w1"][at],
                                      raw[f"m.experts.{j}.g.weight"])
        np.testing.assert_array_equal(stacks["w2"][at],
                                      raw[f"m.experts.{j}.d.weight"].T)
        np.testing.assert_array_equal(stacks["w3"][at],
                                      raw[f"m.experts.{j}.u.weight"])
    assert stack_experts(raw, "m.experts.", share.held, ("g", "d", "u"),
                         "bfloat16")["w2"].dtype == jnp.bfloat16


# The argument sets of the five families that call the block
# (models/{axk1,solar_open2,granitemoehybrid,lfm2_moe,smallthinker}.py).
FAMILIES = {
    "axk1": dict(
        routing=dict(scoring="sigmoid", n_group=4, topk_group=2, scale=2.5),
        ep=(2, 1), shared=True, activation="silu"),
    "solar_open2": dict(
        routing=dict(scoring="sigmoid", scale=1.5, select_bias=True),
        ep=(2, 0), shared=True, activation="silu"),
    "granitemoehybrid": dict(routing={}, ep=(4, 3), shared=True,
                             activation="silu"),
    "lfm2_moe": dict(
        routing=dict(scoring="sigmoid", select_bias=True, sum_eps=1e-6),
        ep=(1, 0), shared=False, activation="silu"),
    "smallthinker": dict(routing={}, ep=(1, 0), shared=False,
                         activation="relu", routed_from_another_input=True),
}


@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "masked"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_block_is_the_sequence_written_out(family, masked):
    """Router, `moe_ffn`, the held count and the shared matmuls one after
    the other, as each family's `_layer` had them, give the block's output
    and counts bit for bit. `masked`: with one of three batch rows of two
    tokens each not live (`AttentionMetadata.live_rows`): the block hands
    `moe_ffn` the mask a token (six rows: the grouped form, which leaves
    the row's two tokens out); a share's held counts take no notice."""
    from intellillm_tpu.layers.moe import grouped_rows
    case = FAMILIES[family]
    rng = np.random.default_rng(5)
    b, l, d, inter, router, top_k = (3, 2, 32, 16, 16, 4) if masked else (
        6, 1, 32, 16, 16, 4)
    assert grouped_rows(b * l, top_k, router)
    live = jnp.asarray([True, False, True]) if masked else None
    live_kw = dict(live=jnp.repeat(live, l)) if masked else {}
    ep_size, ep_rank = case["ep"]
    share = ExpertShare(router // ep_size, top_k, n_moe_layers=1,
                        ep_size=ep_size, ep_rank=ep_rank)
    routing = Routing(held_start=share.held_start, **case["routing"])

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           / np.sqrt(shape[-1]))

    lp = share.param_shapes(d, inter, select_bias=routing.select_bias,
                            shared_inter=24 if case["shared"] else 0)
    lp = {k: draw(*shape) for k, shape in lp.items()}
    h = draw(b, l, d) * np.sqrt(d)
    flat = h.reshape(b * l, d)
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[case["activation"]]

    routed = None
    router_input = flat
    if case.get("routed_from_another_input"):
        other = draw(b, l, d) * np.sqrt(d)
        router_input = other.reshape(b * l, d)
        routed = route(other, lp, share, routing)
    got, (visited, held) = routed_experts(
        h, lp, share, routing, routed=routed, activation=case["activation"],
        live=live)

    want_routed = _route(router_input, lp["gate_router"], top_k, routing,
                         select_bias=lp.get("expert_bias"))
    want, want_visited = moe_ffn(
        flat, lp["gate_router"], lp["w1"], lp["w2"], lp["w3"], top_k,
        routing=routing, routed=want_routed, activation=case["activation"],
        count_visited=True, **live_kw)
    if masked:
        assert not np.asarray(want).reshape(b, l, d)[1].any()
        assert np.asarray(want).reshape(b, l, d)[[0, 2]].any()
    if case["shared"]:
        want = want + (act(flat @ lp["shared_gate"])
                       * (flat @ lp["shared_up"])) @ lp["shared_down"]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.reshape(b, l, d)))
    assert int(visited) == int(want_visited)
    if ep_size > 1:
        want_held = held_assignments(want_routed[1], share.n_held,
                                     share.held_start)
        assert held.shape == (b, l)
        assert held.reshape(-1).tolist() == want_held.tolist()
        # A share computes a part of the uncut sum: not all of it, not 0.
        assert 0 < int(want_held.sum()) < b * l * top_k
    else:
        assert held is None


@pytest.mark.parametrize("rows,path", [(8, "grouped"), (64, "dense")])
def test_a_layer_of_stacks_that_hold_every_layer(rows, path, monkeypatch):
    """`moe_ffn(held_at=)`: given the stacks of EVERY routed layer end to
    end (a model that walks its layers in a `lax.scan`) and the place of
    this layer's first expert, traced, both forms give what they give on
    the layer's own stacks, and count the same experts visited."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from intellillm_tpu.layers import moe
    layers, n, inter, hidden, top_k = 3, 4, 32, 64, 2
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(rows, hidden)).astype(np.float32))
    gate = jnp.asarray(rng.normal(size=(hidden, 2 * n)).astype(np.float32))
    stacks = [jnp.asarray(rng.normal(size=(layers * n, inter, hidden)).astype(
        np.float32) / 8) for _ in range(3)]
    # a share: the router is twice as wide as what is held, from expert 4 on
    routing = moe.Routing(scoring="sigmoid", renormalize=True, held_start=n)
    taken = []
    for name in ("moe_ffn_dense", "moe_ffn_grouped"):
        def spy(*a, name=name, orig=getattr(moe, name), **kw):
            taken.append(name)
            return orig(*a, **kw)
        monkeypatch.setattr(moe, name, spy)

    @jax.jit
    def at(place):
        return moe.moe_ffn(x, gate, *stacks, top_k, routing=routing,
                           count_visited=True, held_at=(place * n, n))

    for place in range(layers):
        own = [w[place * n:(place + 1) * n] for w in stacks]
        want, visited = moe.moe_ffn(x, gate, *own, top_k, routing=routing,
                                    count_visited=True)
        got, got_visited = at(jnp.int32(place))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        assert int(got_visited) == int(visited) > 0
    assert set(taken) == {"moe_ffn_" + path}
