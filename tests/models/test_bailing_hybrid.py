"""Ling 3.0 (`bailing_hybrid`: five delta-rule linear-attention layers with a
float32 matrix state a head to one latent-attention layer with a latent
pool, in ONE sequence; a dense SwiGLU in the first two layers, then
sigmoid-routed experts in groups chosen by the sum of a group's two best
scores + a shared expert, as one share of an expert-parallel deployment)
against its plain float32 reference, `tests/models/bailing_hybrid_reference.py`,
at a tiny preset that keeps every structural feature: 12 layers by the rule
(2 dense; KDA x 5, MLA, twice), 4 heads of 16, latent rank 32 + rope 16, 16
experts in 4 groups of which 2 are kept, top-4, 4 held at `ep_size` 4.

The engine side is the normal path: a checkpoint under the assumed tensor
names -> `load_weights` -> scheduler, block manager (state slots beside the
latent blocks' tables), the `mixed` program (chunked prefill: `kda_chunk` and
the absorbed latent attention over the pool) and the K=8 fused decode
(`kda_step` on the pool by slot, the latent stage). The reference is the full
causal forward pass over the whole sequence, the delta rule a token at a
time and latent attention in the expanded form, teacher-forced on the
engine's tokens; what is compared is log-softmax values, never sampled
tokens.

One engine a setting for the whole module (`engines`): the tests of a
setting share its compiled programs.
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import bailing_hybrid_reference as ref

TINY = dict(
    model_type="bailing_hybrid", architectures=["BailingHybridForCausalLM"],
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_hidden_layers=12,
    layer_group_size=6, first_k_dense_replace=2, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, q_lora_rank=None, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    rope_theta=6000000, rope_scaling=None, rope_interleave=True,
    use_mla_nope=False, gated_attention_proj_granularity_type="head_wise",
    short_conv_kernel_size=4, linear_silu=True, kda_safe_gate=True,
    kda_lower_bound=-5, no_kda_lora=True, num_kv_heads_for_linear_attn=0,
    group_norm_size=1, use_qk_norm=True, num_experts=4, ep_size=4, ep_rank=1,
    num_shared_experts=1, num_experts_per_tok=4, n_group=4, topk_group=2,
    score_function="sigmoid", moe_router_enable_expert_bias=True,
    topk_method="noaux_tc", norm_topk_prob=True, routed_scaling_factor=2.5,
    num_nextn_predict_layers=1, max_position_embeddings=256,
    rms_norm_eps=1e-6, tie_word_embeddings=False, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0, experts=None) -> dict:
    """Seeded random weights under the checkpoint names, [out, in]; the
    experts `experts` of the router's numbering (default: every one)."""
    rng = np.random.default_rng(seed)
    e = cfg["hidden_size"]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    d_n, d_r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, r_kv = cfg["v_head_dim"], cfg["kv_lora_rank"]
    router = cfg["num_experts"] * cfg.get("ep_size", 1)

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def norm(n):
        return (1 + 0.1 * rng.normal(size=n)).astype(np.float32)

    # What writes to the residual stream adds a tenth of its magnitude, as
    # the engine's own seeded weights do (models/bailing_hybrid.py).
    def mlp(prefix, inter):
        return {prefix + "gate_proj.weight": lin(inter, e),
                prefix + "up_proj.weight": lin(inter, e),
                prefix + "down_proj.weight": lin(e, inter, 0.1)}

    w = {"model.word_embeddings.weight":
         rng.normal(size=(cfg["vocab_size"], e)).astype(np.float32),
         "model.norm.weight": norm(e),
         "lm_head.weight": lin(cfg["vocab_size"], e)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "attention."
        w[p + "input_layernorm.weight"] = norm(e)
        w[p + "post_attention_layernorm.weight"] = norm(e)
        if ref.is_latent_layer(cfg, i):
            w.update({a + "q_proj.weight": lin(h * (d_n + d_r), e, 2.0),
                      a + "kv_a_proj_with_mqa.weight": lin(r_kv + d_r, e,
                                                           2.0),
                      a + "kv_a_layernorm.weight": norm(r_kv),
                      a + "kv_b_proj.weight": lin(h * (d_n + d_v), r_kv),
                      a + "g_proj.weight": lin(h, e),
                      a + "dense.weight": lin(e, h * d_v, 0.1)})
        else:
            for n in "qkv":
                w[a + f"{n}_proj.weight"] = lin(h * d, e)
                w[a + f"{n}_conv1d.weight"] = (
                    rng.normal(size=(h * d, 1, 4)) / 2).astype(np.float32)
            w.update({
                a + "f_proj.weight": lin(h * d, e),
                # decays from a step that forgets little to one that
                # forgets most (models/bailing_hybrid.py)
                a + "dt_bias": rng.uniform(-4, -1, h * d).astype(np.float32),
                a + "A_log": np.log(rng.uniform(1, 16, h)).astype(
                    np.float32),
                a + "b_proj.weight": lin(h, e),
                a + "g_proj.weight": lin(h * d, e),
                a + "o_norm.weight": norm(d),
                a + "dense.weight": lin(e, h * d, 0.1)})
        m = p + "mlp."
        if not ref.is_moe_layer(cfg, i):
            w.update(mlp(m, cfg["intermediate_size"]))
            continue
        w[m + "gate.weight"] = lin(router, e)
        # As wide as the scores' own spread: choice and weight differ.
        w[m + "gate.expert_bias"] = (0.5 * rng.normal(size=router)).astype(
            np.float32)
        for j in range(router):
            mats = mlp(f"{m}experts.{j}.", cfg["moe_intermediate_size"])
            if experts is None or j in experts:   # drawn for every expert,
                w.update(mats)        # so a share's equal the whole model's
        w.update(mlp(m + "shared_experts.",
                     cfg["moe_shared_expert_intermediate_size"]))
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("ling3") / "tiny")
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


@pytest.fixture(scope="module")
def engines(ckpt):
    """name -> the module's one engine of that setting, built when first
    asked for."""
    _, _, d = ckpt
    settings = {"roomy": {}, "tight": dict(blocks=14),
                "three_slots": dict(max_num_seqs=3),
                "bf16": dict(dtype="bfloat16")}
    built = {}

    def get(name):
        if name not in built:
            built[name] = make_llm(d, **settings[name])
        return built[name]

    return get


def generate(llm, prompts, params):
    engine = llm.llm_engine
    base = generate.count = getattr(generate, "count", 0) + 1000
    for i, (p, sp) in enumerate(zip(prompts, params)):
        engine.add_request(str(base + i), None, sp,
                           prompt_token_ids=list(p))
    outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
    return [outs[str(base + i)] for i in range(len(prompts))]


def prompts_of(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg["vocab_size"], size=n).tolist() for n in lens]


# Every sequence goes to the reference padded to this length (the engine's
# `max_model_len`): the pass is causal, so what follows a position does not
# move it, and the reference's operations compile for one length, not for
# one a sequence (6 s each at 12 layers).
REF_LEN = 128


def dlogprobs(cfg, weights, prompts, outs):
    """|engine logprob - reference log-softmax| over the top-5 of every
    position that gave a token, teacher-forced on the engine's tokens."""
    diffs = []
    for p, out in zip(prompts, outs):
        for c in out.outputs:
            seq = p + list(c.token_ids)
            lsm = np.asarray(jax.nn.log_softmax(ref.forward(
                cfg, weights, seq + [0] * (REF_LEN - len(seq))), -1))
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


# Float32 on both sides: what is left is the order of summation (the delta
# rule's chunk form against the token-by-token scan, the absorbed latent
# products against the expanded ones).
F32_TOL = 1e-4
# What a reference computed wrong in one way, or an engine one precision
# lower, has to read at least: a hundred times what the order of summation
# leaves.
WRONG = 1e-2


@pytest.fixture(scope="module")
def served(ckpt, engines):
    """(prompts, outputs) of one run that every wrong reference is held
    against: a 70-token prompt in three chunks of a 32-token budget beside
    a 33-token one in two and a 9-token one (two sequences of different
    length in one `mixed` step, and pad rows), then K=8 decode."""
    cfg, _, _ = ckpt
    prompts = prompts_of(cfg, (9, 70, 33))
    return prompts, generate(engines("roomy"), prompts, [greedy(20)] * 3)


def test_engine_matches_reference_float32(ckpt, engines, served):
    """Chunked prefill (the matrix state and the filters' taps cross the
    chunk boundaries through the state slot, the latent rows of the
    earlier chunks are read back from the pool by the absorbed form), then
    the K=8 fused decode and its continuation."""
    cfg, weights, _ = ckpt
    llm = engines("roomy")
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert worst_dlogprob(cfg, weights, *served) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and len(bm._free_state_slots) == 8


def _patched(name, wrong):
    """The reference with its function `name` replaced for the pass."""
    def apply(cfg, w):
        return cfg, w, name, wrong
    return apply


def _softplus_decay(cfg, w, prefix, x):
    """Solar Open 2's gate in the bounded one's place."""
    t = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    raw = (ref._linear(x, w[prefix + "f_proj.weight"])
           + jnp.asarray(w[prefix + "dt_bias"])).reshape(t, h, d)
    rate = jnp.exp(jnp.asarray(w[prefix + "A_log"]).reshape(-1))
    return -rate[None, :, None] * jax.nn.softplus(raw)


WRONG_REFERENCES = {
    # A.X-K1's group score: a group's largest, not its two largest summed
    "group_score_max": _patched(
        "group_scores", lambda cfg, choice: choice.max(-1)),
    "softplus_gate": _patched("log_decay", _softplus_decay),
    "no_head_gate": _patched(
        "head_gate", lambda w, prefix, x: jnp.ones(
            (x.shape[0], w[prefix + "g_proj.weight"].shape[0]))),
    "write_strength_doubled": _patched(
        "write_strength", lambda w, prefix, x: 2.0 * jax.nn.sigmoid(
            ref._linear(x, w[prefix + "b_proj.weight"]))),
    "no_expert_bias": lambda cfg, w: (cfg, {
        k: (np.zeros_like(v) if k.endswith("expert_bias") else v)
        for k, v in w.items()}, None, None),
    "routed_scale_one": lambda cfg, w: (
        dict(cfg, routed_scaling_factor=1.0), w, None, None),
}


@pytest.mark.parametrize("name", sorted(WRONG_REFERENCES))
def test_each_wrong_reference_is_seen(ckpt, served, monkeypatch, name):
    """The reference computed wrong in one way reads far outside the
    tolerance against the same engine outputs."""
    cfg, weights, _ = ckpt
    cfg_w, weights_w, attr, wrong = WRONG_REFERENCES[name](cfg, weights)
    if attr:
        monkeypatch.setattr(ref, attr, wrong)
    assert worst_dlogprob(cfg_w, weights_w, *served) > WRONG, name


def test_a_lower_precision_than_stated_breaks_the_tolerance(ckpt, engines):
    """bf16 weights, latent rows and filter taps, the matrix state float32.
    Against the float32 reference on the same bf16-rounded weights the
    engine reads bf16's own distance at this size, far outside what holds
    the float32 engine."""
    cfg, weights, _ = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    llm = engines("bf16")
    caches = llm.llm_engine.worker.cache_engine.device_cache
    assert [str(p.dtype) for p in caches[0]] == ["bfloat16", "float32"]
    assert [str(p.dtype) for p in caches[5]] == ["bfloat16"]
    outs = generate(llm, prompts, [greedy(20)] * 3)
    diffs = dlogprobs(cfg, rounded, prompts, outs)
    assert diffs.max() > 10 * F32_TOL
    assert diffs.mean() < 0.05 and diffs.max() < 0.75


def test_continuation_join_carries_the_state(ckpt, engines):
    """Requests that arrive while a decode batch runs are prefilled behind
    it and join it by continuation (`decode_cont`): their slot holds the
    prefill's state and their blocks its latent rows when their first
    decode row reads them, and the rows already decoding keep theirs in
    the pools between the two programs."""
    from intellillm_tpu.obs import get_efficiency_tracker
    cfg, weights, _ = ckpt
    llm = engines("roomy")
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
        engine.add_request(f"j{i}", None, greedy(32),
                           prompt_token_ids=prompts[i])
    pump(4)
    engine.add_request("j2", None, greedy(24), prompt_token_ids=prompts[2])
    pump(3)
    engine.add_request("j3", None, greedy(16), prompt_token_ids=prompts[3])
    while engine.has_unfinished_requests() or engine.has_inflight():
        pump(1)
    joins = get_efficiency_tracker()._decode_joins["device"]
    assert joins - joins0 >= 2, (
        "the late requests joined the continuation dispatched behind their "
        "prompt step, their first token read from it on the device")
    outs = [done[f"j{i}"] for i in range(4)]
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and len(bm._free_state_slots) == 8


def test_recompute_preemption_rebuilds_state_and_latent_rows(
        ckpt, engines, monkeypatch):
    """A pool too small for four sequences: the scheduler preempts by
    recompute; the slot and the blocks are given back and the re-prefill
    rebuilds the state from zero at position 0 and the latent rows in new
    blocks, whatever either held."""
    from intellillm_tpu.core import scheduler as sched_mod
    cfg, weights, _ = ckpt
    hits = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["n"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=2)
    llm = engines("tight")
    outs = generate(llm, prompts, [greedy(40)] * 4)
    assert hits["n"] > 0, "the pool was sized to force a preemption"
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert len(slots_of(llm)._free_state_slots) == 8


def test_fork_and_swap_move_state_and_latent_blocks(ckpt, engines,
                                                    monkeypatch):
    """best_of=2: the children fork the prompt's state (a slot each, both
    of the parent's arrays copied) and share its latent blocks, and under
    a tight pool the groups preempt by SWAP: taps and matrices go to a
    host slot, the latent blocks to host blocks, and both come back. Every
    sampled sequence agrees with the reference teacher-forced on it."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, weights, _ = ckpt
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=40,
                             ignore_eos=True, logprobs=5)] * 4
    moved = {"swap_state_out": 0, "swap_state_in": 0, "copy_state": 0,
             "swap_out": 0, "swap_in": 0}
    for name in moved:
        orig = getattr(ce.CacheEngine, name)

        def counting(self, mapping, name=name, orig=orig):
            moved[name] += 1
            return orig(self, mapping)

        monkeypatch.setattr(ce.CacheEngine, name, counting)
    llm = engines("tight")
    tight = generate(llm, prompts, params)
    assert all(moved.values()), moved
    assert worst_dlogprob(cfg, weights, prompts, tight) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and not bm.cpu_state_slots
    assert len(bm._free_state_slots) == len(bm._free_cpu_state_slots) == 8


def test_admission_stops_at_the_slots_with_latent_blocks_to_spare(
        ckpt, engines):
    """Three slots for six requests under a pool of latent blocks that
    would hold them all: what admission runs out of is the slots, not the
    blocks; a sequence holds both a slot and a table of latent blocks."""
    cfg, _, _ = ckpt
    llm = engines("three_slots")
    engine = llm.llm_engine
    bm = slots_of(llm)
    assert bm.num_state_slots == 3
    prompts = prompts_of(cfg, (10, 11, 12, 13, 14, 15), seed=8)
    for i, p in enumerate(prompts):
        engine.add_request(f"a{i}", None, greedy(24), prompt_token_ids=p)
    for _ in range(3):
        engine.step_pipelined()
    assert len(bm.state_slots) == 3 and not bm._free_state_slots
    assert len(engine.scheduler.waiting) == 3
    # every admitted sequence holds latent blocks beside its slot
    assert all(bm.block_tables[s] for s in bm.state_slots)
    # every request's whole life would fit the free blocks
    assert bm.get_num_free_device_blocks() >= 3 * 3
    finished = set()
    while engine.has_unfinished_requests() or engine.has_inflight():
        finished |= {o.request_id for o in engine.step_pipelined()
                     if o.finished}
    assert finished == {f"a{i}" for i in range(6)}
    assert not bm.state_slots and len(bm._free_state_slots) == 3
    assert not bm.block_tables


def test_what_the_model_cannot_do_is_refused_with_a_reason(ckpt, engines,
                                                           tmp_path):
    from intellillm_tpu import LLM
    from intellillm_tpu.engine.arg_utils import EngineArgs
    from intellillm_tpu.engine.llm_engine import LLMEngine
    cfg, _, d = ckpt
    kw = dict(model=d, dtype="float32", num_device_blocks_override=32,
              max_model_len=128)
    with pytest.raises(NotImplementedError, match="one chip"):
        LLM(tensor_parallel_size=2, **kw)
    with pytest.raises(NotImplementedError,
                       match="roll back|multi-token-prediction"):
        LLMEngine.from_engine_args(EngineArgs(
            speculative_model=d, num_speculative_tokens=3, **kw))
    for role in ("prefill", "decode"):
        with pytest.raises(NotImplementedError, match="state snapshot"):
            LLMEngine.from_engine_args(EngineArgs(replica_role=role, **kw))
    with pytest.raises((NotImplementedError, ValueError),
                       match="(?i)quantis|quantiz"):
        LLM(quantization="awq", **kw)
    with pytest.raises((NotImplementedError, ValueError), match="(?i)lora"):
        LLM(enable_lora=True, **kw)
    with pytest.raises(NotImplementedError, match="state snapshots"):
        engines("roomy").llm_engine.add_request(
            "p", None, greedy(4),
            prompt_token_ids=prompts_of(cfg, (40, ))[0], prefix_pos=16)
    # a layer kept whose SwiGLU is clamped: the clamp's form is not given
    limits = [0] * 11 + [4]
    clamped = tmp_path / "clamped"
    clamped.mkdir()
    for name in os.listdir(d):
        if name != "config.json":
            os.symlink(os.path.join(d, name), clamped / name)
    (clamped / "config.json").write_text(json.dumps(
        dict(cfg, expert_swiglu_limit_list=limits)))
    with pytest.raises(NotImplementedError, match="clamped SwiGLU"):
        LLM(**dict(kw, model=str(clamped)))


# --- the share ----------------------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layer():
    """One MoE layer of the uncut model (16 experts, no share) against the
    sum of its 4 shares' parts (4 held each = one routing group each), the
    shared expert counted once: routing, the choice of groups and experts
    and the normalisation are over the whole router in every share, so the
    parts add up."""
    cfg = dict(TINY, vocab_size=32, num_hidden_layers=3, layer_group_size=6)
    whole = dict(cfg, num_experts=16, ep_size=1, ep_rank=0)
    w = published_weights(whole, seed=4)
    x = np.random.default_rng(9).normal(size=(24, cfg["hidden_size"])).astype(
        np.float32)
    prefix = "model.layers.2.mlp."
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(whole, w, prefix, x)
        parts = sum(ref.moe(dict(cfg, ep_rank=r), w, prefix, x,
                            shared=(r == 0)) for r in range(4))
        counted_each = sum(ref.moe(dict(cfg, ep_rank=r), w, prefix, x)
                           for r in range(4))
        # a token reaches a share only when its group is among its two
        _, experts = ref.route(
            whole, jax.nn.sigmoid(ref._linear(x, w[prefix + "gate.weight"])),
            w[prefix + "gate.expert_bias"])
    assert float(np.abs(uncut - parts).max()) < 1e-5
    assert float(np.abs(uncut - counted_each).max()) > 1e-2
    groups = np.asarray(experts) // 4
    assert all(len(set(row)) <= 2 for row in groups)


def test_the_engines_share_is_the_references_share(ckpt, engines):
    """The engine (rank 1 of 4: experts [4, 8) = routing group 1) against
    the reference at another rank must disagree, and at its own agree."""
    cfg, weights, _ = ckpt
    prompts = prompts_of(cfg, (20, ), seed=11)
    outs = generate(engines("roomy"), prompts, [greedy(4)])
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert worst_dlogprob(dict(cfg, ep_rank=0), weights, prompts,
                          outs) > WRONG


# --- the caches' description --------------------------------------------------

def test_config_describes_state_slots_beside_latent_pools(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    period = ["state"] * 5 + ["kv"]
    assert mc.get_layer_cache_kinds() == period * 2
    assert mc.get_layer_cache_entries() == list(range(12))
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (2, 10)
    assert mc.get_cache_walkers() == (2, 0, 0)
    # the "kv" entries' rows are latent rows: 32 + 16 values in 128 lanes
    assert mc.get_latent_cache_width() == 128
    assert mc.get_cache_row_shape() == (1, 1, 128)
    assert mc.get_sliding_window() is None
    # the filters' taps in the model's dtype, a float32 matrix a head
    assert mc.get_state_arrays() == (((3, 3 * 4 * 16), "bfloat16"),
                                     ((4, 16, 16), "float32"))
    # taps: 576 values padded to 640 lanes x 2 B; matrices: 4 heads x 16
    # rows x 128 lanes (16 padded to a lane tile) x 4 B; 10 layers
    assert CacheEngine.get_state_slot_size(mc) == 10 * (640 * 2
                                                        + 4 * 16 * 128 * 4)
    # a block: 2 latent layers x one pool x 16 tokens x 128 lanes x 2 B
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 2 * 16 * 128 * 2


def test_published_size_bytes():
    """At the published widths and the cell's depth a slot is 21.7 MB (10
    layers of 32 x 128 x 128 float32 + taps of 3 x 12,288 bf16) and a
    token's latent rows 2,560 B (2 layers of 640 bf16)."""
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.transformers_utils.configs import BailingHybridConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    mc = ModelConfig.__new__(ModelConfig)
    mc.dtype = "bfloat16"
    mc.hf_config = BailingHybridConfig(num_hidden_layers=12)
    assert mc.get_layer_cache_kinds() == (["state"] * 5 + ["kv"]) * 2
    assert CacheEngine.get_state_slot_size(mc) == 10 * (
        2097152 + 3 * 12288 * 2)
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 16 * 2560
    assert CacheEngine.state_pool_shape((32, 128, 128), 16) == (
        17, 32, 128, 128)
    assert CacheEngine.state_pool_shape((3, 12288), 16) == (16, 36864)
    # 42 layers: seven latent layers, 35 KDA
    mc.hf_config = BailingHybridConfig()
    kinds = mc.get_layer_cache_kinds()
    assert (kinds.count("kv"), kinds.count("state")) == (7, 35)
    assert [i for i, k in enumerate(kinds) if k == "kv"] == [
        5, 11, 17, 23, 29, 35, 41]


# --- counters -------------------------------------------------------------------

def test_walk_and_state_counters_count_stored_rows(ckpt, engines):
    """A decode row-substep moves the state layers' matrices in and out
    (10 layers x 2 x 4 heads x 16 x 16 x 4 B) and walks its context's
    latent rows at their STORED width (2 layers x 128 lanes x 4 B a token
    in float32); the router's held share is about a quarter of the choices
    (one group of four held of four groups; a token whose two groups do
    not include it sends nothing here)."""
    from prometheus_client import REGISTRY
    cfg, _, _ = ckpt

    def read():
        out = {}
        for fam in REGISTRY.collect():
            for s in fam.samples:
                if s.name in ("intellillm_dispatch_state_bytes_total",
                              "intellillm_dispatch_kv_walk_bytes_total",
                              "intellillm_dispatch_decode_row_steps_total"):
                    key = (s.name, s.labels.get("program"))
                    out[key] = out.get(key, 0) + s.value
                if s.name == "intellillm_moe_assignments_total":
                    key = ("held", s.labels["held"])
                    out[key] = out.get(key, 0) + s.value
        return out

    start = read()
    prompts = prompts_of(cfg, (24, ), seed=13)
    generate(engines("roomy"), prompts, [greedy(17)])
    end = read()
    delta = {k: end[k] - start.get(k, 0) for k in end}
    state = sum(v for (n, p), v in delta.items()
                if n == "intellillm_dispatch_state_bytes_total"
                and p and p.startswith("decode"))
    walk = sum(v for (n, p), v in delta.items()
               if n == "intellillm_dispatch_kv_walk_bytes_total"
               and p and p.startswith("decode"))
    # K=8 decode steps: 16 substeps for the 16 tokens past the prefill's
    assert state % (10 * 2 * 4 * 16 * 16 * 4) == 0 and state > 0
    substeps = state // (10 * 2 * 4 * 16 * 16 * 4)
    assert 16 <= substeps <= 24
    # contexts 24 .. 24 + substeps - 1, 2 latent layers x 128 x 4 B each
    assert walk % (2 * 128 * 4) == 0
    tokens = walk // (2 * 128 * 4)
    assert 24 * substeps <= tokens <= (24 + substeps) * substeps
    here, absent = delta[("held", "here")], delta[("held", "absent")]
    assert 0.1 < here / (here + absent) < 0.4


# --- the shared layers did not move for the other families ------------------

def _kda_gates_as_the_parent_held_them(x, lp, num_heads, head_dim):
    """`layers/linear_attention.py` `kda_gates` at commit ed2cd69 (before
    the bounded gate and the full-rank projections), verbatim."""
    f32 = jnp.float32
    raw = ((x @ lp["f_a"]) @ lp["f_b"]).astype(f32) + lp["dt_bias"].astype(f32)
    g = (-jnp.exp(lp["A_log"].astype(f32))[None, :, None]
         * jax.nn.softplus(raw).reshape(-1, num_heads, head_dim))
    beta = 2.0 * jax.nn.sigmoid((x @ lp["b_proj"]).astype(f32))
    return g, beta


def _kda_mixer_as_the_parent_held_it(h, lp, cache, attn_metadata, positions,
                                     *, num_heads, head_dim, eps):
    """`kda_mixer` at commit ed2cd69, verbatim."""
    from intellillm_tpu.layers import linear_attention as la
    from intellillm_tpu.layers.normalization import rms_norm
    b, l, e = h.shape
    x = h.reshape(b, e)
    width = 3 * num_heads * head_dim
    pre = x @ lp["qkv"]
    pos = positions.reshape(b)
    slots = attn_metadata.state_slots
    taps, matrices = cache
    prev, last = la.earlier_taps(pre, taps, attn_metadata, pos)
    qkv = la.conv_silu_body(pre, prev, lp["conv_w"])
    q, k, v = (qkv[:, i * width // 3:(i + 1) * width // 3].reshape(
        b, num_heads, head_dim) for i in range(3))
    q = la._l2_normalise(q) * head_dim ** -0.5
    k = la._l2_normalise(k)
    g, beta = _kda_gates_as_the_parent_held_them(x, lp, num_heads, head_dim)
    new_taps = la.later_taps(prev, pre, taps.dtype)
    if attn_metadata.staged:
        o, matrices = la.kda_step(q, k, v, g, beta, matrices, slots)
    else:
        o, matrices = la.kda_chunk(q, k, v, g, beta, matrices, slots, pos)
    cache = (la.store_taps(taps, new_taps, attn_metadata, last), matrices)
    gate = jax.nn.sigmoid(((x @ lp["g_a"]) @ lp["g_b"]).astype(jnp.float32))
    o = rms_norm(o, lp["o_norm"].astype(jnp.float32), eps)
    y = (o.reshape(b, width // 3) * gate).astype(h.dtype)
    return (y @ lp["o"]).reshape(b, 1, e), cache


def _latent_block_as_the_parent_held_it(mla):
    """`LatentAttentionBlock.__call__` at commit ed2cd69 (before the query
    without a low-rank stage and the head-wise gate), verbatim."""
    from intellillm_tpu.layers.normalization import rms_norm

    def attention(lp, h, kv_cache, attn_metadata, positions, layer=None):
        b, l, _ = h.shape
        hh = mla.num_heads
        with jax.named_scope("mla_q_proj"):
            c_q = rms_norm(h @ lp["q_a"], lp["q_a_norm"], mla.rms_eps)
            q = (c_q @ lp["q_b"]).reshape(b, l, hh, mla.d_nope + mla.d_rope)
            q_nope, q_rope = q[..., :mla.d_nope], q[..., mla.d_nope:]
        with jax.named_scope("mla_kv_proj"):
            ckv = h @ lp["kv_a"]
            c_kv = rms_norm(ckv[..., :mla.kv_rank], lp["kv_a_norm"],
                            mla.rms_eps)
            k_rope = ckv[..., mla.kv_rank:][:, :, None, :]
        q_rope, k_rope = mla.rope(positions, q_rope, k_rope)
        with jax.named_scope("mla_absorb_q"):
            q_abs = jnp.einsum("blhn,hnr->blhr", q_nope, lp["w_uk"])
        q_lat = jnp.concatenate([q_abs, q_rope], axis=-1)
        kv_new = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
        o_lat, kv_cache = mla.attn(q_lat, kv_new, kv_cache, attn_metadata,
                                   layer)
        with jax.named_scope("mla_absorb_o"):
            o = jnp.einsum("blhr,hrv->blhv", o_lat, lp["w_uv"])
        return o.reshape(b, l, hh * mla.d_v) @ lp["o"], kv_cache

    return attention


def _program_text(model, shapes, mc, staged):
    """The jaxpr of `model`'s call over a `mixed` step or a staged decode
    substep at a small size, the caches as its config describes them."""
    from intellillm_tpu.layers.attention import AttentionMetadata
    from intellillm_tpu.worker.cache_engine import CacheEngine
    f32, i32 = jnp.float32, jnp.int32
    rows, width, blocks, chunk, seats = 16, 4, 8, 8, 4

    def spec(tree):
        if isinstance(tree, dict):
            return {k: spec(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [spec(v) for v in tree]
        return jax.ShapeDtypeStruct(tree, f32)

    pools, heads, lanes = mc.get_cache_row_shape()
    pool = (jax.ShapeDtypeStruct((blocks, heads, 16, lanes), f32), ) * pools
    stage = (jax.ShapeDtypeStruct((rows, chunk, heads, lanes), f32), ) * pools
    state = []
    for shape, _ in mc.get_state_arrays():
        full = CacheEngine.state_pool_shape(shape, seats)
        if staged and not mc.state_array_in_place(shape):
            full = (rows, ) + full[1:]
        state.append(jax.ShapeDtypeStruct(full, f32))
    caches = [tuple(state) if kind == "state"
              else (pool + stage if staged else pool)
              for kind in mc.get_layer_cache_kinds()]
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, i32)      # noqa: E731

    def call(params, ids, positions, caches, slots, ctx, tables, k,
             state_slots):
        meta = AttentionMetadata(
            slot_mapping=None if staged else slots, context_lens=ctx,
            block_tables=tables, staged=staged,
            stage_index=k if staged else None, state_slots=state_slots)
        return model(params, ids, positions, caches, meta)

    args = (spec(shapes), ints(rows, 1), ints(rows, 1), caches,
            ints(rows, 1), ints(rows), ints(rows, width), ints(), ints(rows))
    return str(jax.make_jaxpr(call)(*args))


@pytest.mark.parametrize("staged", [False, True],
                         ids=["mixed", "decode_substep"])
@pytest.mark.parametrize("family", ["solar_open2", "axk1", "xing4"])
def test_the_other_families_programs_trace_what_the_parent_traced(
        family, staged, tmp_path, monkeypatch):
    """Solar Open 2's programs (the KDA mixer's gates), A.X-K1's and
    Xing4.0's (the latent block) are unchanged by what this family added to
    the layers they share: the model's jaxpr with the layer as it is
    equals the jaxpr with the layer as the parent commit held it (same
    operations, same order, same shapes)."""
    import importlib

    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.models import get_model_class
    tests_of = {"solar_open2": "tests.models.test_solar_open2",
                "axk1": "tests.models.test_axk1",
                "xing4": "tests.models.test_xing4"}
    tiny = dict(importlib.import_module(tests_of[family]).TINY, vocab_size=64)
    (tmp_path / "config.json").write_text(json.dumps(tiny))
    mc = ModelConfig(str(tmp_path), dtype="float32", max_model_len=128,
                     load_format="dummy")
    model = get_model_class(mc.hf_config.architectures)(mc)
    shapes = model._param_shapes()
    now = _program_text(model, shapes, mc, staged)
    if family == "solar_open2":
        from intellillm_tpu.models import solar_open2
        monkeypatch.setattr(solar_open2, "kda_mixer",
                            _kda_mixer_as_the_parent_held_it)
    else:
        model.mla = _latent_block_as_the_parent_held_it(model.mla)
    then = _program_text(model, shapes, mc, staged)
    assert "dot_general" in now and len(now) > 5000
    assert now == then
