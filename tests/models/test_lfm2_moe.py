"""LFM2-MoE (gated short-convolution layers with per-sequence state between
grouped-query attention layers, experts chosen by a biased sigmoid score)
against its plain float32 reference, `tests/models/lfm2_moe_reference.py`,
at a tiny preset that keeps every structural feature: 2 dense conv layers,
then one period (attention, conv, conv, conv) of MoE layers, 8 experts top-4
with a selection bias, 4 heads over 2 KV heads of 16 stored at 128 lanes.

The engine side is the normal path: a checkpoint under the family's tensor
names -> `load_weights` -> scheduler, block manager (state slots beside the
tables), the `mixed` program (chunked prefill) and the K=8 fused decode, the
convolution state carried across chunks and substeps. The reference is the
full causal forward pass over the whole sequence, teacher-forced on the
engine's tokens; what is compared is log-softmax values (the engine's top-5
logprobs at the prefill's last position and every decoded one), never
sampled tokens.
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import lfm2_moe_reference as ref

TINY = dict(
    model_type="lfm2_moe", architectures=["Lfm2MoeForCausalLM"],
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=6,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    conv_bias=False, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=4, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1.0, max_position_embeddings=128, norm_eps=1e-5,
    rope_theta=1000000.0, tie_word_embeddings=True, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the published names, [out, in]."""
    rng = np.random.default_rng(seed)
    e = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h

    def lin(out, inp):
        return (rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(np.float32)

    def norm(n):
        return (1 + 0.1 * rng.normal(size=n)).astype(np.float32)

    def mlp(prefix, inter):
        return {prefix + "w1.weight": lin(inter, e),
                prefix + "w3.weight": lin(inter, e),
                prefix + "w2.weight": lin(e, inter)}

    w = {"model.embed_tokens.weight":
         (rng.normal(size=(cfg["vocab_size"], e)) / np.sqrt(e)).astype(
             np.float32),
         "model.embedding_norm.weight": norm(e)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        w[p + "operator_norm.weight"] = norm(e)
        w[p + "ffn_norm.weight"] = norm(e)
        if cfg["layer_types"][i] == "conv":
            w[p + "conv.in_proj.weight"] = lin(3 * e, e)
            w[p + "conv.conv.weight"] = (
                rng.normal(size=(e, 1, cfg["conv_L_cache"])) /
                np.sqrt(3)).astype(np.float32)
            w[p + "conv.out_proj.weight"] = lin(e, e)
        else:
            a = p + "self_attn."
            w.update({a + "q_proj.weight": lin(h * d, e),
                      a + "k_proj.weight": lin(hkv * d, e),
                      a + "v_proj.weight": lin(hkv * d, e),
                      a + "out_proj.weight": lin(e, h * d),
                      a + "q_layernorm.weight": norm(d),
                      a + "k_layernorm.weight": norm(d)})
        f = p + "feed_forward."
        if i >= cfg["num_dense_layers"]:
            w[f + "gate.weight"] = lin(cfg["num_experts"], e)
            # As wide as the scores' own spread: choice and weight differ.
            w[f + "expert_bias"] = (0.5 * rng.normal(
                size=cfg["num_experts"])).astype(np.float32)
            for j in range(cfg["num_experts"]):
                w.update(mlp(f"{f}experts.{j}.",
                             cfg["moe_intermediate_size"]))
        else:
            w.update(mlp(f, cfg["intermediate_size"]))
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("lfm2moe") / "tiny")
    _, vocab = _build_word_tokenizer(d)
    cfg = dict(TINY, vocab_size=vocab)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)
    weights = published_weights(cfg)
    safetensors.numpy.save_file(weights, os.path.join(d, "model.safetensors"))
    return cfg, weights, d


def make_llm(model_dir, dtype="float32", blocks=64, **kw):
    from intellillm_tpu import LLM
    kw.setdefault("max_num_batched_tokens", 32)
    kw.setdefault("max_num_seqs", 8)
    return LLM(model=model_dir, dtype=dtype,
               num_device_blocks_override=blocks, max_model_len=128,
               max_paddings=512, swap_space=0.01,
               enable_chunked_prefill=True, **kw)


def generate(llm, prompts, params):
    engine = llm.llm_engine
    for i, (p, sp) in enumerate(zip(prompts, params)):
        engine.add_request(str(i), None, sp, prompt_token_ids=list(p))
    outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
    return [outs[str(i)] for i in range(len(prompts))]


def prompts_of(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg["vocab_size"], size=n).tolist() for n in lens]


def dlogprobs(cfg, weights, prompts, outs):
    """|engine logprob - reference log-softmax| over the top-5 of every
    position that gave a token, teacher-forced on the engine's tokens."""
    diffs = []
    for p, out in zip(prompts, outs):
        for c in out.outputs:
            lsm = np.asarray(jax.nn.log_softmax(
                ref.forward(cfg, weights, p + list(c.token_ids)), -1))
            for j, lp in enumerate(c.logprobs):
                for tok, v in lp.items():
                    v = float(getattr(v, "logprob", v))
                    diffs.append(abs(lsm[len(p) - 1 + j, tok] - v))
    return np.asarray(diffs)


def worst_dlogprob(*args, **kw):
    return float(dlogprobs(*args, **kw).max())


def greedy(n, **kw):
    from intellillm_tpu import SamplingParams
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          logprobs=5, **kw)


def slots_of(llm):
    return llm.llm_engine.scheduler.block_manager


# Float32 on both sides: what is left is the order of summation.
F32_TOL = 1e-4


def test_engine_matches_reference_float32(ckpt):
    """Chunked prefill (70- and 33-token prompts against a 32-token
    budget: the filter's taps cross the chunk boundaries through the state
    slot), several sequences and pad rows in one `mixed` step, then the
    K=8 fused decode and its continuation."""
    cfg, weights, d = ckpt
    llm = make_llm(d)
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(llm, prompts, [greedy(20)] * 3)
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and len(bm._free_state_slots) == 8


def grouped_ffn_traces():
    from intellillm_tpu.obs import get_compile_tracker
    seen = get_compile_tracker().snapshot()["kernel_dispatch"]
    return sum(v for k, v in seen.items() if k.endswith(":grouped_ffn"))


def test_a_step_over_256_rows_takes_the_grouped_experts(ckpt):
    """Three prompts of 100, 100 and 90 tokens under a 512-token budget
    make one `mixed` step of 290 rows (a 512-row program): over
    `layers/moe.py` `DENSE_ROWS`, so its expert layers run the grouped
    form, with the selection bias and the sum's epsilon, and still meet
    the float32 reference; the decode steps after it stay dense."""
    from intellillm_tpu.obs.efficiency import get_efficiency_tracker
    cfg, weights, d = ckpt
    before = grouped_ffn_traces()
    rows_before = get_efficiency_tracker().snapshot()["moe_ffn_rows"]
    llm = make_llm(d, max_num_batched_tokens=512)
    prompts = prompts_of(cfg, (100, 100, 90), seed=3)
    outs = generate(llm, prompts, [greedy(10)] * 3)
    assert grouped_ffn_traces() > before
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    rows = get_efficiency_tracker().snapshot()["moe_ffn_rows"]
    assert rows["grouped"] - rows_before["grouped"] == 512
    assert rows["dense"] > rows_before["dense"]


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights, K/V and convolution state, float32 reference on the
    same bf16-rounded weights: bf16's own distance at this size."""
    cfg, weights, d = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    outs = generate(make_llm(d, dtype="bfloat16"), prompts, [greedy(20)] * 3)
    diffs = dlogprobs(cfg, rounded, prompts, outs)
    assert diffs.mean() < 0.04 and diffs.max() < 0.75


@pytest.mark.parametrize("budget,lens,need", [
    (16, (21, 18, 35, 5, 3, 2, 1), {1, 2, 3, 5}),
    (19, (40, 23, 7), {2, 6, 7})])
def test_chunks_of_every_length_cross_the_filter(ckpt, monkeypatch, budget,
                                                 lens, need):
    """A token budget smaller than the prompts, shared by several: prompts
    are cut into chunks of many lengths (1, 2, 3 and 5 among them), so a
    chunk boundary falls at every offset of the 3-tap filter, rows of
    several sequences and pad rows share a `mixed` step, and nothing leaks
    across a boundary between sequences."""
    from intellillm_tpu.worker import model_runner as mr
    cfg, weights, d = ckpt
    sizes = set()
    orig = mr.ModelRunner._execute_mixed

    def spying(self, metas, kv_caches, defer_fetch=False):
        sizes.update(m.token_chunk_size for m in metas
                     if m.token_chunk_size is not None)
        return orig(self, metas, kv_caches, defer_fetch=defer_fetch)

    monkeypatch.setattr(mr.ModelRunner, "_execute_mixed", spying)
    llm = make_llm(d, max_num_batched_tokens=budget)
    prompts = prompts_of(cfg, lens, seed=5)
    outs = generate(llm, prompts, [greedy(6)] * len(lens))
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert need <= sizes, sizes


def test_single_step_decode_matches_reference(ckpt):
    """K=1: every decode token is a row of the `mixed` program, reading and
    writing its slot."""
    cfg, weights, d = ckpt
    llm = make_llm(d, num_decode_steps=1)
    assert llm.llm_engine.scheduler_config.num_decode_steps == 1
    prompts = prompts_of(cfg, (9, 40), seed=6)
    outs = generate(llm, prompts, [greedy(12)] * 2)
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL


def test_continuation_join_carries_the_state(ckpt):
    """Requests that arrive while a decode batch runs are prefilled behind
    it and join it by continuation (`decode_cont`): their slot holds the
    prefill's state when their first decode row reads it."""
    from intellillm_tpu.obs import get_efficiency_tracker
    cfg, weights, d = ckpt
    llm = make_llm(d)
    engine = llm.llm_engine
    assert engine.pipeline_enabled
    prompts = prompts_of(cfg, (12, 30, 17, 25), seed=7)
    joins0 = get_efficiency_tracker()._decode_joins["device"]
    done = {}

    def pump(n):
        for _ in range(n):
            for out in engine.step_pipelined():
                if out.finished:
                    done[out.request_id] = out

    for i in (0, 1):
        engine.add_request(str(i), None, greedy(40),
                           prompt_token_ids=prompts[i])
    pump(4)
    engine.add_request("2", None, greedy(30), prompt_token_ids=prompts[2])
    pump(3)
    engine.add_request("3", None, greedy(20), prompt_token_ids=prompts[3])
    while engine.has_unfinished_requests() or engine.has_inflight():
        pump(1)
    joins = get_efficiency_tracker()._decode_joins["device"]
    assert joins - joins0 >= 2, (
        "the late requests joined the continuation dispatched behind their "
        "prompt step, their first token read from it on the device")
    outs = [done[str(i)] for i in range(4)]
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and len(bm._free_state_slots) == 8


def test_recompute_preemption_rebuilds_the_state(ckpt, monkeypatch):
    """A pool too small for four sequences: the scheduler preempts by
    recompute; the slot is given back and the re-prefill rebuilds the state
    from position 0."""
    from intellillm_tpu.core import scheduler as sched_mod
    cfg, weights, d = ckpt
    hits = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["n"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=2)
    llm = make_llm(d, blocks=10)
    outs = generate(llm, prompts, [greedy(40)] * 4)
    assert hits["n"] > 0, "the pool was sized to force a preemption"
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert len(slots_of(llm)._free_state_slots) == 8


def test_fork_and_swap_move_the_state(ckpt, monkeypatch):
    """best_of=2: the children fork the prompt's state (a slot each, the
    parent's copied), and under a tight pool the groups preempt by SWAP:
    the state goes to a host slot with the blocks and comes back. Every
    sampled sequence agrees with the reference teacher-forced on it, and
    the tight run's tokens equal the roomy run's."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, weights, d = ckpt
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=40,
                             ignore_eos=True, logprobs=5)] * 4
    roomy = generate(make_llm(d), prompts, params)
    assert worst_dlogprob(cfg, weights, prompts, roomy) < F32_TOL
    moved = {"swap_state_out": 0, "swap_state_in": 0, "copy_state": 0}
    for name in moved:
        orig = getattr(ce.CacheEngine, name)

        def counting(self, mapping, name=name, orig=orig):
            moved[name] += 1
            return orig(self, mapping)

        monkeypatch.setattr(ce.CacheEngine, name, counting)
    llm = make_llm(d, blocks=14)
    tight = generate(llm, prompts, params)
    assert all(moved.values()), moved
    assert worst_dlogprob(cfg, weights, prompts, tight) < F32_TOL
    for r, t in zip(roomy, tight):
        assert (sorted(c.token_ids for c in r.outputs) ==
                sorted(c.token_ids for c in t.outputs))
    bm = slots_of(llm)
    assert not bm.state_slots and not bm.cpu_state_slots
    assert len(bm._free_state_slots) == len(bm._free_cpu_state_slots) == 8


def test_abort_gives_the_slot_back_and_admission_waits_for_one(ckpt):
    """Two slots for four requests (seats and blocks are plenty): two are
    admitted, the others wait for a slot; an abort frees one at once."""
    cfg, _, d = ckpt
    llm = make_llm(d)
    engine = llm.llm_engine
    bm = slots_of(llm)
    # shrink the slot list as if max_num_seqs had been 2
    bm._free_state_slots = bm._free_state_slots[-2:]
    prompts = prompts_of(cfg, (10, 11, 12, 13), seed=8)
    for i, p in enumerate(prompts):
        engine.add_request(str(i), None, greedy(24), prompt_token_ids=p)
    for _ in range(3):
        engine.step_pipelined()
    assert len(bm.state_slots) == 2 and not bm._free_state_slots
    assert len(engine.scheduler.waiting) == 2
    engine.abort_request("0")
    assert len(bm._free_state_slots) + len(bm.state_slots) == 2
    finished = set()
    while engine.has_unfinished_requests() or engine.has_inflight():
        finished |= {o.request_id for o in engine.step_pipelined()
                     if o.finished}
    assert finished >= {"1", "2", "3"}
    assert not bm.state_slots and len(bm._free_state_slots) == 2


def test_what_the_state_cannot_do_is_refused_with_a_reason(ckpt):
    from intellillm_tpu import LLM
    from intellillm_tpu.engine.arg_utils import EngineArgs
    from intellillm_tpu.engine.llm_engine import LLMEngine
    cfg, _, d = ckpt
    kw = dict(model=d, dtype="float32", num_device_blocks_override=32,
              max_model_len=128)
    with pytest.raises(NotImplementedError, match="one chip"):
        LLM(tensor_parallel_size=2, **kw)
    with pytest.raises(NotImplementedError, match="roll back"):
        LLMEngine.from_engine_args(EngineArgs(
            speculative_model=d, num_speculative_tokens=3, **kw))
    for role in ("prefill", "decode"):
        with pytest.raises(NotImplementedError, match="state snapshot"):
            LLMEngine.from_engine_args(EngineArgs(replica_role=role, **kw))
    llm = make_llm(d)
    engine = llm.llm_engine
    with pytest.raises(NotImplementedError, match="state snapshots"):
        engine.add_request("p", None, greedy(4),
                           prompt_token_ids=prompts_of(cfg, (40, ))[0],
                           prefix_pos=16)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.worker.cache_engine.export_blocks([0, 1])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        engine.worker.cache_engine.import_blocks([], [])


def test_config_describes_the_layers_one_by_one(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="float32", max_model_len=128)
    assert mc.get_layer_cache_kinds() == [
        "state", "state", "kv", "state", "state", "state"]
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (1, 5)
    assert mc.get_state_arrays() == (((2, 64), "float32"), )
    # a 16-wide head stored at one 128-lane tile
    assert mc.get_head_size() == 16
    assert mc.get_cache_row_shape() == (2, 2, 128)
    # blocks are sized by the layers that have a pool, slots by the others
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 2 * 1 * 2 * 16 * 128 * 4
    assert CacheEngine.get_logical_cache_block_size(
        16, "auto", mc) == 2 * 1 * 2 * 16 * 128 * 4
    assert CacheEngine.get_state_slot_size(mc) == 5 * 128 * 4


# --- the router ---------------------------------------------------------------

def test_router_with_a_selection_bias_against_a_hand_computation():
    """Four experts, top-2: the bias decides WHO is chosen, the scores alone
    decide their weights, the sum carries the family's 1e-6."""
    from intellillm_tpu.layers.moe import Routing, _route
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    bias = jnp.asarray([-1.0, 0.0, 0.6, 0.0], jnp.float32)
    routing = Routing(scoring="sigmoid", renormalize=True, select_bias=True,
                      sum_eps=1e-6, scale=1.0)
    topw, topi = _route(logits, jnp.eye(4, dtype=jnp.float32), 2, routing,
                        select_bias=bias)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    # score + bias: [-0.119, 0.731, 1.1, 0.269] -> experts 2 and 1
    assert np.asarray(topi).tolist() == [[2, 1]]
    want = np.asarray([s[2], s[1]]) / (s[2] + s[1] + 1e-6)
    np.testing.assert_allclose(np.asarray(topw)[0], want, rtol=1e-6)
    # without the bias the same scores choose 0 and 1
    plain = Routing(scoring="sigmoid", renormalize=True, sum_eps=1e-6)
    _, topi = _route(logits, jnp.eye(4, dtype=jnp.float32), 2, plain)
    assert np.asarray(topi).tolist() == [[0, 1]]
    # ... and the reference's `route` says the same of both
    w_ref, i_ref = ref.route(dict(num_experts_per_tok=2, norm_topk_prob=True),
                             jax.nn.sigmoid(logits), np.asarray(bias))
    assert np.asarray(i_ref).tolist() == [[2, 1]]
    np.testing.assert_allclose(np.asarray(w_ref)[0], want, rtol=1e-6)


def _route_before_pr30(x, gate_w, top_k, scoring, renormalize, n_group,
                       topk_group, scale):
    """`layers/moe.py` `_route` as it stood before `Routing` gained the
    selection bias and the epsilon of the sum, verbatim."""
    router_logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    if scoring == "softmax":
        weights = jax.nn.softmax(router_logits, axis=-1)
    else:
        weights = jax.nn.sigmoid(router_logits)
    choose_from = weights
    if n_group > 1:
        t, n = weights.shape
        per = n // n_group
        group_score = weights.reshape(t, n_group, per).max(-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        choose_from = jnp.where(jnp.repeat(keep, per, axis=1), weights, -1.0)
    _, topi = jax.lax.top_k(choose_from, top_k)
    topw = jnp.take_along_axis(weights, topi, axis=1)
    if renormalize:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        topw = topw * scale
    return topw, topi


@pytest.mark.parametrize("setting", [
    dict(scoring="softmax", renormalize=True, n_group=1, topk_group=1,
         scale=1.0),                                    # Mixtral
    dict(scoring="softmax", renormalize=False, n_group=1, topk_group=1,
         scale=1.0),                                    # DeepSeek-MoE
    dict(scoring="sigmoid", renormalize=True, n_group=8, topk_group=4,
         scale=2.5),                                    # A.X-K1
])
def test_existing_routings_are_bit_for_bit_what_they_were(setting):
    from intellillm_tpu.layers.moe import Routing, _route
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    gate = jnp.asarray(rng.normal(size=(48, 32)).astype(np.float32))
    topw, topi = _route(x, gate, 6, Routing(**setting))
    want_w, want_i = _route_before_pr30(x, gate, 6, **setting)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(topw), np.asarray(want_w))


# --- the mixer, layer by layer ------------------------------------------------

@pytest.mark.parametrize("cuts", [(1, ) * 11, (2, 2, 2, 2, 3), (3, 3, 5),
                                  (5, 1, 5), (11, )])
def test_mixer_in_chunks_equals_the_whole_sequence(cuts):
    """`short_conv_mixer` over one sequence cut into chunks (every offset
    of the filter across a boundary), a second sequence's rows and pad rows
    in the same steps, against the reference's whole-sequence convolution."""
    from intellillm_tpu.layers.attention import AttentionMetadata
    from intellillm_tpu.layers.short_conv import short_conv_mixer
    rng = np.random.default_rng(9)
    e, n, slots = 32, 11, 4
    w = {"in_proj.weight": rng.normal(size=(3 * e, e)).astype(np.float32),
         "conv.weight": rng.normal(size=(e, 1, 3)).astype(np.float32),
         "out_proj.weight": rng.normal(size=(e, e)).astype(np.float32)}
    xa = rng.normal(size=(n, e)).astype(np.float32)
    xb = rng.normal(size=(n, e)).astype(np.float32)
    want_a = np.asarray(ref.short_conv({}, w, "", jnp.asarray(xa)))
    want_b = np.asarray(ref.short_conv({}, w, "", jnp.asarray(xb)))
    pool = jnp.asarray(rng.normal(size=(slots, 2 * e)).astype(np.float32))
    got_a, got_b, start = [], [], 0
    for size in cuts:
        # sequence A's chunk, one row of B (decoding token by token while
        # it lasts), a pad row between them and one at the end
        rows = [(2, p, xa[p]) for p in range(start, start + size)]
        step = len(got_b)
        rows.append((slots, 0, np.zeros(e, np.float32)))
        if step < n:
            rows.append((0, step, xb[step]))
        rows.append((slots, 0, np.zeros(e, np.float32)))
        meta = AttentionMetadata(
            state_slots=jnp.asarray([r[0] for r in rows], jnp.int32))
        y, (pool, ) = short_conv_mixer(
            jnp.asarray(np.stack([r[2] for r in rows]))[:, None, :],
            jnp.asarray(w["in_proj.weight"].T),
            jnp.asarray(w["conv.weight"][:, 0, :].T),
            jnp.asarray(w["out_proj.weight"].T), (pool, ), meta,
            jnp.asarray([[r[1]] for r in rows], jnp.int32))
        y = np.asarray(y)[:, 0]
        got_a += list(y[:size])
        if step < n:
            got_b.append(y[size + 1])
        start += size
    np.testing.assert_allclose(np.stack(got_a), want_a, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.stack(got_b), want_b[:len(got_b)],
                               rtol=2e-5, atol=2e-5)


# --- the reference against the family's published code ------------------------

def test_reference_layers_match_transformers_lfm2():
    """The reference's conv, attention and dense-FFN layers, the final norm
    and the RoPE pairing against `transformers.models.lfm2.modeling_lfm2`
    (the dense family: the MoE block is not in the installed version)."""
    torch = pytest.importorskip("torch")
    lfm2 = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    types = ["conv", "full_attention", "conv", "conv"]
    hf_cfg = Lfm2Config(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, norm_eps=1e-5, rope_theta=1000000.0,
        conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
        layer_types=types)
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = lfm2.Lfm2Model(hf_cfg).eval().float()
    with torch.no_grad():
        for p in model.parameters():        # norms away from all-ones
            p.add_(0.1 * torch.randn_like(p))
    ids = torch.randint(3, 97, (1, 23))
    with torch.no_grad():
        want = model(input_ids=ids, use_cache=False).last_hidden_state[0]
    w = {"model." + k: v.detach().numpy()
         for k, v in model.state_dict().items()}
    cfg = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               norm_eps=1e-5, rope_theta=1000000.0, layer_types=types,
               num_dense_layers=4, num_hidden_layers=4)
    with jax.default_matmul_precision("highest"):
        x = ref.embed(w, ids[0].numpy())
        for i in range(4):
            x = ref.layer(cfg, w, i, x, np.arange(23))
        got = ref.rms_norm(x, w["model.embedding_norm.weight"], 1e-5)
    np.testing.assert_allclose(np.asarray(got), want.numpy(), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("case", ["pad_row_with_a_slot", "permuted_slots"])
def test_decode_stage_moves_the_state_as_the_at_forms_did(ckpt, monkeypatch,
                                                          case):
    """One K=8 fused decode step on pools of random bytes, the conv
    state's rows gathered and scattered by `ops/state_rows.py` and by the
    `.at[]` forms it replaced: the same tokens, every pool the same bytes.
    A row without a context is a pad row whatever slot it names: that
    slot keeps its bytes."""
    from tests.models import state_stage
    _, _, d = ckpt
    assert state_stage.assert_same_step(make_llm(d), monkeypatch, case) == 5
