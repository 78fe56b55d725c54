"""`layers/moe.py` `Routing`'s group-limited choice: a group's score as its
largest expert score (`group_score_top` 1: A.X-K1's, the default) and as
the sum of its two largest (2: DeepSeek-V3's `noaux_tc`, Ling 3.0's), each
against the choice written out as a loop a token."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.layers.moe import Routing, _route


def written_out(scores, bias, n_group, topk_group, top_k, score_top, scale):
    """One token at a time, in plain Python: groups of consecutive
    experts, a group's score the sum of its `score_top` largest `score +
    bias`, the best `topk_group` groups kept (ties to the lower index),
    the `top_k` largest `score + bias` among their experts chosen (ties to
    the lower index), weights the scores at the chosen (without the bias)
    over their sum, times `scale`."""
    weights, experts = [], []
    per = scores.shape[1] // n_group
    for s in np.asarray(scores, np.float64):
        c = s + np.asarray(bias, np.float64)
        group = [sum(sorted(c[g * per:(g + 1) * per])[-score_top:])
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-group[g], g))[
            :topk_group]
        allowed = [e for g in kept for e in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda e: (-c[e], e))[:top_k]
        total = sum(s[e] for e in chosen)
        experts.append(chosen)
        weights.append([s[e] / total * scale for e in chosen])
    return np.asarray(weights), np.asarray(experts)


@pytest.mark.parametrize("score_top", [1, 2], ids=["max", "top2_sum"])
@pytest.mark.parametrize("width,n_group,topk_group,top_k", [
    (512, 8, 4, 8), (16, 4, 2, 4), (192, 8, 4, 8)])
def test_group_choice_against_the_loop(width, n_group, topk_group, top_k,
                                       score_top):
    rng = np.random.default_rng(width + score_top)
    x = jnp.asarray(rng.normal(size=(40, 24)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(24, width)), jnp.float32)
    bias = jnp.asarray(0.2 * rng.normal(size=width), jnp.float32)
    routing = Routing(scoring="sigmoid", renormalize=True, n_group=n_group,
                      topk_group=topk_group, scale=2.5, select_bias=True,
                      group_score_top=score_top)
    with jax.default_matmul_precision("highest"):
        topw, topi = _route(x, gate, top_k, routing, select_bias=bias)
        scores = jax.nn.sigmoid(x @ gate)
    want_w, want_i = written_out(scores, bias, n_group, topk_group, top_k,
                                 score_top, 2.5)
    np.testing.assert_array_equal(np.asarray(topi), want_i)
    np.testing.assert_allclose(np.asarray(topw), want_w, rtol=1e-5)
    # never more groups than kept
    per = width // n_group
    assert all(len(set(row // per)) <= topk_group for row in np.asarray(topi))


def test_the_two_group_scores_choose_differently():
    """One strong expert against two good ones: the maximum keeps the
    first group, the sum of two the second."""
    scores = np.full((1, 8), 0.1, np.float32)
    scores[0, 0] = 0.9                    # group 0: one strong expert
    scores[0, 4], scores[0, 5] = 0.6, 0.6   # group 1: two good ones
    logits = np.log(scores / (1 - scores))
    x = jnp.ones((1, 1), jnp.float32)
    gate = jnp.asarray(logits)

    def chosen(score_top):
        routing = Routing(scoring="sigmoid", n_group=2, topk_group=1,
                          group_score_top=score_top)
        return sorted(np.asarray(_route(x, gate, 2, routing)[1])[0].tolist())

    assert chosen(1)[0] == 0 and chosen(1)[1] < 4
    assert chosen(2) == [4, 5]


def test_the_default_is_the_maximum_and_traces_what_it_traced():
    """`group_score_top` defaults to 1, and a routing that does not name it
    traces the reduce-max it always did: no top-k over a group."""
    assert Routing().group_score_top == 1
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    gate = jax.ShapeDtypeStruct((16, 32), jnp.float32)

    def text(routing):
        return str(jax.make_jaxpr(
            lambda x, g: _route(x, g, 4, routing))(x, gate))

    by_max = text(Routing(scoring="sigmoid", n_group=4, topk_group=2))
    by_sum = text(Routing(scoring="sigmoid", n_group=4, topk_group=2,
                          group_score_top=2))
    assert "reduce_max" in by_max and by_max.count("top_k") == 2
    assert by_sum.count("top_k") == 3
