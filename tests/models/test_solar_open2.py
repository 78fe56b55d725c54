"""Solar Open 2 (delta-rule linear-attention layers with a matrix state a
head between gated NoPE GQA layers, sigmoid-routed experts + a shared one, as
one share of an expert-parallel deployment) against its plain float32
reference, `tests/models/solar_open2_reference.py`, at a tiny preset that
keeps every structural feature: one period (GQA, KDA, KDA, KDA) twice over,
4 heads of 16 in both kinds of layer, 2 KV heads, 8 of 16 experts held at
`ep_size` 2, top-4 with a selection bias, a shared expert.

The engine side is the normal path: a checkpoint under the assumed tensor
names -> `load_weights` -> scheduler, block manager (state slots beside the
tables), the `mixed` program (chunked prefill: `kda_chunk`) and the K=8 fused
decode (`kda_step` on the pool by slot). The reference is the full causal
forward pass over the whole sequence, the delta rule a token at a time,
teacher-forced on the engine's tokens; what is compared is log-softmax
values, never sampled tokens.
"""
import json
import os

import jax
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import solar_open2_reference as ref

TINY = dict(
    model_type="solar_open2", architectures=["SolarOpen2ForCausalLM"],
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=8, gqa_layers=[0, 4], gqa_interval=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, n_routed_experts=8, ep_size=2, ep_rank=1,
    n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=1.0, first_k_dense_replace=0,
    max_position_embeddings=128, rms_norm_eps=1e-5,
    tie_word_embeddings=False, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0, experts=None) -> dict:
    """Seeded random weights under the checkpoint names, [out, in]; the
    experts `experts` of the router's numbering (default: every one)."""
    rng = np.random.default_rng(seed)
    e = cfg["hidden_size"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    lin_cfg = cfg["linear_attn_config"]
    hk, dk = lin_cfg["num_heads"], lin_cfg["head_dim"]
    router = cfg["n_routed_experts"] * cfg.get("ep_size", 1)
    inter = cfg["moe_intermediate_size"]

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def norm(n):
        return (1 + 0.1 * rng.normal(size=n)).astype(np.float32)

    # What writes to the residual stream adds a tenth of its magnitude, as
    # the engine's own seeded weights do (models/solar_open2.py).
    def mlp(prefix):
        return {prefix + "gate_proj.weight": lin(inter, e),
                prefix + "up_proj.weight": lin(inter, e),
                prefix + "down_proj.weight": lin(e, inter, 0.1)}

    w = {"model.embed_tokens.weight":
         rng.normal(size=(cfg["vocab_size"], e)).astype(np.float32),
         "model.norm.weight": norm(e),
         "lm_head.weight": lin(cfg["vocab_size"], e)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        w[p + "input_layernorm.weight"] = norm(e)
        w[p + "post_attention_layernorm.weight"] = norm(e)
        if i in cfg["gqa_layers"]:
            w.update({a + "q_proj.weight": lin(h * d, e, 2.0),
                      a + "k_proj.weight": lin(hkv * d, e, 2.0),
                      a + "v_proj.weight": lin(hkv * d, e),
                      a + "g_proj.weight": lin(h * d, e),
                      a + "o_proj.weight": lin(e, h * d, 0.1)})
        else:
            for n in "qkv":
                w[a + f"{n}_proj.weight"] = lin(hk * dk, e)
                w[a + f"{n}_conv1d.weight"] = (
                    rng.normal(size=(hk * dk, 1, 4)) / 2).astype(np.float32)
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), hk * dk))
            w.update({
                a + "f_a_proj.weight": lin(dk, e),
                a + "f_b_proj.weight": lin(hk * dk, dk),
                a + "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(
                    np.float32),
                a + "A_log": np.log(rng.uniform(1, 16, hk)).astype(
                    np.float32),
                a + "b_proj.weight": lin(hk, e),
                a + "g_a_proj.weight": lin(dk, e),
                a + "g_b_proj.weight": lin(hk * dk, dk),
                a + "o_norm.weight": norm(dk),
                a + "o_proj.weight": lin(e, hk * dk, 0.1)})
        m = p + "mlp."
        w[m + "gate.weight"] = lin(router, e)
        # As wide as the scores' own spread: choice and weight differ.
        w[m + "gate.e_score_correction_bias"] = (
            0.5 * rng.normal(size=router)).astype(np.float32)
        for j in range(router):
            mats = mlp(f"{m}experts.{j}.")     # drawn for every expert, so
            if experts is None or j in experts:      # a share's equal the
                w.update(mats)                       # whole model's
        w.update(mlp(m + "shared_experts."))
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("solar2") / "tiny")
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
    budget: the filters' taps and the delta rule's matrix cross the chunk
    boundaries through the state slot), several sequences and pad rows in
    one `mixed` step, then the K=8 fused decode and its continuation."""
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


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights, K/V and filter taps, the matrix state float32; float32
    reference on the same bf16-rounded weights: bf16's own distance at this
    size."""
    cfg, weights, d = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    llm = make_llm(d, dtype="bfloat16")
    pools = llm.llm_engine.worker.cache_engine.device_cache[1]
    assert [str(p.dtype) for p in pools] == ["bfloat16", "float32"]
    outs = generate(llm, prompts, [greedy(20)] * 3)
    diffs = dlogprobs(cfg, rounded, prompts, outs)
    assert diffs.mean() < 0.04 and diffs.max() < 0.75


@pytest.mark.parametrize("budget,lens,need", [
    (16, (21, 18, 35, 5, 3, 2, 1), {1, 2, 3, 5}),
    (19, (40, 23, 7), {2, 6, 7}),
    (70, (100, 90, 67), {30, 50, 67, 70})])
def test_chunks_of_every_length_cross_the_state(ckpt, monkeypatch, budget,
                                                lens, need):
    """A token budget smaller than the prompts, shared by several: prompts
    are cut into chunks of many lengths (none a multiple of 16 or 64), so a
    chunk boundary falls at every offset of the 4-tap filters and of the
    delta rule's chunks, rows of several sequences and pad rows share a
    `mixed` step, and nothing leaks across a boundary between
    sequences."""
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
    """K=1: every decode token is a row of the `mixed` program, a run of
    one row that reads and writes its slot through the chunk form."""
    cfg, weights, d = ckpt
    llm = make_llm(d, num_decode_steps=1)
    assert llm.llm_engine.scheduler_config.num_decode_steps == 1
    prompts = prompts_of(cfg, (9, 40), seed=6)
    outs = generate(llm, prompts, [greedy(12)] * 2)
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL


def test_continuation_join_carries_the_state(ckpt):
    """Requests that arrive while a decode batch runs are prefilled behind
    it and join it by continuation (`decode_cont`): their slot holds the
    prefill's state when their first decode row reads it, and the rows
    already decoding keep theirs in the pool between the two programs."""
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
    from zero at position 0, whatever the slot held."""
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
    """best_of=2: the children fork the prompt's state (a slot each, both
    of the parent's arrays copied), and under a tight pool the groups
    preempt by SWAP: taps and matrices go to a host slot with the blocks
    and come back. Every sampled sequence agrees with the reference
    teacher-forced on it, and the tight run's tokens equal the roomy
    run's."""
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


def test_admission_stops_at_the_slots_with_blocks_to_spare(ckpt):
    """Three slots for six requests under a pool that would hold them all:
    what admission runs out of is the slots, not the blocks."""
    cfg, _, d = ckpt
    llm = make_llm(d, blocks=64, max_num_seqs=3)
    engine = llm.llm_engine
    bm = slots_of(llm)
    assert bm.num_state_slots == 3
    prompts = prompts_of(cfg, (10, 11, 12, 13, 14, 15), seed=8)
    for i, p in enumerate(prompts):
        engine.add_request(str(i), None, greedy(24), prompt_token_ids=p)
    for _ in range(3):
        engine.step_pipelined()
    assert len(bm.state_slots) == 3 and not bm._free_state_slots
    assert len(engine.scheduler.waiting) == 3
    # every request's whole life would fit the free blocks
    assert bm.get_num_free_device_blocks() >= 3 * 3
    finished = set()
    while engine.has_unfinished_requests() or engine.has_inflight():
        finished |= {o.request_id for o in engine.step_pipelined()
                     if o.finished}
    assert finished == {str(i) for i in range(6)}
    assert not bm.state_slots and len(bm._free_state_slots) == 3


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
    with pytest.raises(NotImplementedError, match="state snapshots"):
        llm.llm_engine.add_request(
            "p", None, greedy(4),
            prompt_token_ids=prompts_of(cfg, (40, ))[0], prefix_pos=16)


# --- the share ----------------------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layer():
    """One MoE layer of the uncut model (16 experts, no share) against the
    sum of its 2 shares' parts (8 held each), the shared expert counted
    once: routing, choice and normalisation are over the whole router in
    every share, so the parts add up."""
    cfg = dict(TINY, vocab_size=32, num_hidden_layers=1, gqa_layers=[0])
    whole = dict(cfg, n_routed_experts=16, ep_size=1, ep_rank=0)
    w = published_weights(whole, seed=4)
    x = np.random.default_rng(9).normal(size=(24, cfg["hidden_size"])).astype(
        np.float32)
    prefix = "model.layers.0.mlp."
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(whole, w, prefix, x)
        parts = sum(ref.moe(dict(cfg, ep_rank=r), w, prefix, x,
                            shared=(r == 0)) for r in range(2))
        counted_twice = sum(ref.moe(dict(cfg, ep_rank=r), w, prefix, x)
                            for r in range(2))
    assert float(np.abs(uncut - parts).max()) < 1e-5
    assert float(np.abs(uncut - counted_twice).max()) > 1e-2


def test_the_engines_share_is_the_references_share(ckpt):
    """The engine (rank 1 of 2: experts [8, 16)) against the reference at
    the other rank must disagree, and at its own agree."""
    cfg, weights, d = ckpt
    prompts = prompts_of(cfg, (20, ), seed=11)
    outs = generate(make_llm(d), prompts, [greedy(4)])
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert worst_dlogprob(dict(cfg, ep_rank=0), weights, prompts,
                          outs) > 1e-2


# --- the state's description ----------------------------------------------------

def test_config_describes_the_layers_and_the_state(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    assert mc.get_layer_cache_kinds() == [
        "kv", "state", "state", "state"] * 2
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (2, 6)
    # the filters' taps in the model's dtype, a float32 matrix a head
    assert mc.get_state_arrays() == (((3, 3 * 4 * 16), "bfloat16"),
                                     ((4, 16, 16), "float32"))
    assert [mc.state_array_in_place(s) for s, _ in mc.get_state_arrays()] \
        == [False, True]
    # taps: 576 values padded to 640 lanes x 2 B; matrices: 4 heads x 16
    # rows x 128 lanes (16 padded to a lane tile) x 4 B; 6 layers
    assert CacheEngine.get_state_slot_size(mc) == 6 * (640 * 2
                                                       + 4 * 16 * 128 * 4)
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 2 * 2 * 2 * 16 * 128 * 2


def test_published_size_slot_bytes():
    """At the published widths a slot is 13.0 MB: 3 layers of 64 x 128 x 128
    float32 + 3 taps of 3 x 8192 bf16."""
    from types import SimpleNamespace
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    mc = ModelConfig.__new__(ModelConfig)
    mc.dtype = "bfloat16"
    mc.hf_config = SimpleNamespace(
        num_hidden_layers=4, gqa_layers=[0],
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 64, "num_kv_heads": None})
    assert mc.get_layer_cache_kinds() == ["kv", "state", "state", "state"]
    assert CacheEngine.get_state_slot_size(mc) == 3 * (
        64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert CacheEngine.state_pool_shape((64, 128, 128), 128) == (
        129, 64, 128, 128)
    assert CacheEngine.state_pool_shape((3, 24576), 128) == (128, 73728)
    assert CacheEngine.state_pool_shape((64, 128, 128), 128,
                                        device=False) == (128, 64, 128, 128)


@pytest.mark.parametrize("case", ["pad_row_with_a_slot", "permuted_slots"])
def test_decode_stage_moves_the_taps_as_the_at_forms_did(ckpt, monkeypatch,
                                                         case):
    """One K=8 fused decode step on pools of random bytes, the taps' rows
    gathered and scattered by `ops/state_rows.py` and by the `.at[]` forms
    it replaced: the same tokens, every pool the same bytes. A row without
    a context is a pad row whatever slot it names: that slot's taps keep
    their bytes."""
    from tests.models import state_stage
    _, _, d = ckpt
    assert state_stage.assert_same_step(make_llm(d), monkeypatch, case) == 6
