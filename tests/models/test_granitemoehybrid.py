"""Granite 4.0-H (Mamba-2 state-space layers with a float32 matrix state
between NoPE GQA layers, softmax-routed experts + a shared MLP, four
multipliers, as one share of an expert-parallel deployment) against its
plain float32 reference, `tests/models/granitemoehybrid_reference.py`, at a
tiny preset that keeps every structural feature: one period cut to (mamba,
mamba, attention, mamba), 4 scan heads of 32 over 16 state
channels, 4 attention heads of 16 over 2 KV heads, the 4-tap filter with
its bias over x, B and C together, 8 of 16 experts held at `ep_size` 2,
top-4, a shared MLP, all four multipliers away from 1.

The engine side is the normal path: a checkpoint under HF's tensor names ->
`load_weights` -> scheduler, block manager (state slots beside the tables),
the `mixed` program (chunked prefill: `ssd_chunk`) and the K=8 fused decode
(`ssd_step` on the pool by slot). The reference is the full causal forward
pass over the whole sequence, the scan a token at a time, teacher-forced on
the engine's tokens; what is compared is log-softmax values, never sampled
tokens.
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import granitemoehybrid_reference as ref
from tests.models.test_solar_open2 import (generate, greedy, make_llm,
                                           prompts_of, slots_of)

TINY = dict(
    model_type="granitemoehybrid",
    architectures=["GraniteMoeHybridForCausalLM"],
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, attention_bias=False,
    position_embedding_type="nope",
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256,
    mamba_conv_bias=True, mamba_proj_bias=False,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=4,
    num_local_experts=8, ep_size=2, ep_rank=1, num_experts_per_tok=4,
    max_position_embeddings=128, rms_norm_eps=1e-5,
    tie_word_embeddings=True, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the checkpoint names, [out, in]; the
    fused expert stacks hold every expert of the router, as a checkpoint
    does. Scales as `init_random_params` draws them
    (models/granitemoehybrid.py)."""
    rng = np.random.default_rng(seed)
    e = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    hs, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner, filtered = hs * p, hs * p + 2 * n
    router = cfg["num_local_experts"] * cfg.get("ep_size", 1)
    width, shared = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    embed = cfg["logits_scaling"] / np.sqrt(e)
    # What writes to the residual stream adds a tenth of its magnitude.
    writer = (0.1 * cfg["embedding_multiplier"] * embed
              / cfg["residual_multiplier"])
    wide = 2.0 * (cfg["attention_multiplier"] * np.sqrt(d)) ** -0.5

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def norm(size):
        return (1 + 0.1 * rng.normal(size=size)).astype(np.float32)

    w = {"model.embed_tokens.weight": (embed * rng.normal(
        size=(cfg["vocab_size"], e))).astype(np.float32),
         # A gain of either sign before a tied head (models/lfm2_moe.py).
         "model.norm.weight": rng.normal(size=e).astype(np.float32)}
    for i in range(cfg["num_hidden_layers"]):
        q = f"model.layers.{i}."
        w[q + "input_layernorm.weight"] = norm(e)
        w[q + "post_attention_layernorm.weight"] = norm(e)
        if cfg["layer_types"][i] == "mamba":
            m = q + "mamba."
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), hs))
            w.update({
                m + "in_proj.weight": lin(2 * inner + 2 * n + hs, e),
                m + "conv1d.weight": (rng.normal(size=(filtered, 1, 4))
                                      / 2).astype(np.float32),
                m + "conv1d.bias": (rng.normal(size=filtered) / 2).astype(
                    np.float32),
                m + "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(
                    np.float32),
                m + "A_log": np.log(rng.uniform(1, 16, hs)).astype(
                    np.float32),
                # Not all ones: a D read as 1 shows.
                m + "D": (1 + 0.3 * rng.normal(size=hs)).astype(np.float32),
                m + "norm.weight": norm(inner),
                m + "out_proj.weight": lin(e, inner, writer)})
        else:
            a = q + "self_attn."
            w.update({a + "q_proj.weight": lin(h * d, e, wide),
                      a + "k_proj.weight": lin(hkv * d, e, wide),
                      a + "v_proj.weight": lin(hkv * d, e),
                      a + "o_proj.weight": lin(e, h * d, writer)})
        b = q + "block_sparse_moe."
        w[b + "router.layer.weight"] = lin(router, e)
        w[b + "input_linear.weight"] = np.stack(
            [lin(2 * width, e) for _ in range(router)])
        w[b + "output_linear.weight"] = np.stack(
            [lin(e, width, writer) for _ in range(router)])
        s = q + "shared_mlp."
        w[s + "input_linear.weight"] = lin(2 * shared, e)
        w[s + "output_linear.weight"] = lin(e, shared, writer)
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("granite4h") / "tiny")
    _, vocab = _build_word_tokenizer(d)
    cfg = dict(TINY, vocab_size=vocab)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)
    weights = published_weights(cfg)
    safetensors.numpy.save_file(weights, os.path.join(d, "model.safetensors"))
    return cfg, weights, d


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


# Float32 on both sides: what is left is the order of summation (the scan's
# state is summed over up to 100 tokens in either order of its 16 channels).
F32_TOL = 1e-4
# What a reference computed wrong in one way has to read at least: a
# hundred times what the order of summation leaves.
WRONG = 1e-2


@pytest.fixture(scope="module")
def served(ckpt):
    """(prompts, outputs) of one run that every wrong reference is held
    against: chunked prefill of three prompts, then K=8 decode."""
    cfg, _, d = ckpt
    prompts = prompts_of(cfg, (9, 70, 33))
    return prompts, generate(make_llm(d), prompts, [greedy(20)] * 3)


def test_engine_matches_reference_float32(ckpt, served):
    """Chunked prefill (70- and 33-token prompts against a 32-token
    budget: the filter's taps and the scan's matrix cross the chunk
    boundaries through the state slot), several sequences and pad rows in
    one `mixed` step, then the K=8 fused decode and its continuation."""
    cfg, weights, _ = ckpt
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert worst_dlogprob(cfg, weights, *served) < F32_TOL


def _without(key, value):
    return lambda cfg, w: (dict(cfg, **{key: value}), w)


def _zeroed(suffix):
    return lambda cfg, w: (cfg, {k: (np.zeros_like(v) if k.endswith(suffix)
                                     else v) for k, v in w.items()})


def _ones(suffix):
    return lambda cfg, w: (cfg, {k: (np.ones_like(v) if k.endswith(suffix)
                                     else v) for k, v in w.items()})


@pytest.mark.parametrize("name,wrong", [
    ("embedding_multiplier", _without("embedding_multiplier", 1.0)),
    ("residual_multiplier", _without("residual_multiplier", 1.0)),
    # the usual head_dim ** -0.5 in the multiplier's place
    ("attention_multiplier", _without("attention_multiplier", 16 ** -0.5)),
    ("logits_scaling", _without("logits_scaling", 1.0)),
    ("skip_D", _zeroed("mamba.D")),
    ("skip_D_read_as_one", _ones("mamba.D")),
    ("filter_bias", _zeroed("conv1d.bias")),
])
def test_each_omission_is_seen(ckpt, served, name, wrong):
    """The reference with one multiplier left out (or the usual softmax
    scale in the attention multiplier's place), without the skip `D x`, or
    without the filter's bias reads far outside the tolerance against the
    same engine outputs."""
    cfg, weights, _ = ckpt
    cfg_w, weights_w = wrong(cfg, weights)
    assert worst_dlogprob(cfg_w, weights_w, *served) > WRONG, name


def test_a_bfloat16_state_is_seen(ckpt, served, monkeypatch):
    """The reference with its scan's state rounded to bfloat16 after every
    token (what a state pool in the activations' dtype would hold) reads
    outside the float32 tolerance."""
    cfg, weights, _ = ckpt

    def scan_bf16(x, dt, a, b, c):
        def step(s, r):
            x_t, dt_t, b_t, c_t = r
            s = (jnp.exp(dt_t * a)[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            return s, jnp.einsum("hpn,n->hp", s, c_t)

        h, p, n = x.shape[1], x.shape[2], b.shape[1]
        _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                            (x, dt, b, c))
        return y

    monkeypatch.setattr(ref, "scan", scan_bf16)
    # 5e-4 at three scan layers over at most 90 tokens (2.8e-3 at the
    # published size on the chip): several times the tolerance.
    assert worst_dlogprob(cfg, weights, *served) > 3 * F32_TOL


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights, K/V and filter taps, the matrix state float32; float32
    reference on the same bf16-rounded weights: bf16's own distance at this
    size."""
    cfg, weights, d = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    llm = make_llm(d, dtype="bfloat16")
    pools = llm.llm_engine.worker.cache_engine.device_cache[0]
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
    are cut into chunks of many lengths, so a chunk boundary falls at every
    offset of the 4-tap filter, rows of several sequences and pad rows
    share a `mixed` step, and nothing leaks across a boundary between
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


def test_sequences_join_and_leave_and_slots_are_reused(ckpt):
    """Six requests over three slots, arriving while a decode batch runs:
    the late ones are prefilled behind it and join it by continuation
    (`decode_cont`); each takes a slot that another sequence left dirty,
    starts from zero at position 0 whatever the slot holds, and every
    sequence agrees with the reference."""
    from intellillm_tpu.obs import get_efficiency_tracker
    cfg, weights, d = ckpt
    llm = make_llm(d, max_num_seqs=3)
    engine = llm.llm_engine
    bm = slots_of(llm)
    assert engine.pipeline_enabled and bm.num_state_slots == 3
    prompts = prompts_of(cfg, (12, 30, 17, 25, 8, 21), seed=7)
    lens = (40, 12, 30, 20, 16, 9)
    joins0 = get_efficiency_tracker()._decode_joins["device"]
    done = {}

    def pump(n):
        for _ in range(n):
            for out in engine.step_pipelined():
                if out.finished:
                    done[out.request_id] = out

    for i in (0, 1):
        engine.add_request(str(i), None, greedy(lens[i]),
                           prompt_token_ids=prompts[i])
    pump(4)
    for i in (2, 3):
        engine.add_request(str(i), None, greedy(lens[i]),
                           prompt_token_ids=prompts[i])
    pump(3)
    for i in (4, 5):
        engine.add_request(str(i), None, greedy(lens[i]),
                           prompt_token_ids=prompts[i])
    while engine.has_unfinished_requests() or engine.has_inflight():
        pump(1)
    joins = get_efficiency_tracker()._decode_joins["device"]
    assert joins - joins0 >= 2, (
        "the late requests joined the continuation dispatched behind their "
        "prompt step, their first token read from it on the device")
    outs = [done[str(i)] for i in range(6)]
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert not bm.state_slots and len(bm._free_state_slots) == 3


def test_a_run_from_position_0_ignores_a_dirty_slot(ckpt):
    """Every pool filled with large random bytes before the first request:
    a prompt starts from zero at position 0 (taps and matrix alike),
    whatever its slot holds."""
    cfg, weights, d = ckpt
    llm = make_llm(d)
    cache = llm.llm_engine.worker.cache_engine
    rng = np.random.default_rng(0)
    cache.device_cache = [
        tuple(jnp.asarray(100 * rng.normal(size=p.shape), p.dtype)
              for p in pools) for pools in cache.device_cache]
    prompts = prompts_of(cfg, (9, 40, 3), seed=12)
    outs = generate(llm, prompts, [greedy(12)] * 3)
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL


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
    preempt by SWAP: taps and matrix go to a host slot with the blocks and
    come back. Every sampled sequence agrees with the reference
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
    sum of its 4 shares' parts (4 held each) plus the shared MLP counted
    once: routing, choice and normalisation are over the whole router in
    every share, so the parts add up."""
    cfg = dict(TINY, vocab_size=32, num_hidden_layers=1,
               layer_types=["attention"], num_local_experts=4, ep_size=4)
    whole = dict(cfg, num_local_experts=16, ep_size=1, ep_rank=0)
    w = published_weights(whole, seed=4)
    x = np.random.default_rng(9).normal(size=(24, cfg["hidden_size"])).astype(
        np.float32)
    moe, shared = "model.layers.0.block_sparse_moe.", \
        "model.layers.0.shared_mlp."

    def share(rank):
        return ref.moe(dict(cfg, ep_rank=rank), w, moe, x)

    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(whole, w, moe, x) + ref.shared_mlp(w, shared, x)
        once = ref.shared_mlp(w, shared, x)
        parts = sum(share(r) for r in range(4))
    assert float(np.abs(uncut - (parts + once)).max()) < 1e-5
    assert float(np.abs(uncut - (parts + 4 * once)).max()) > 1e-2
    assert float(np.abs(uncut - (parts - share(3) + once)).max()) > 1e-3


def test_the_engines_share_is_the_references_share(ckpt, served):
    """The engine (rank 1 of 2: experts [8, 16)) against the reference at
    the other rank must disagree."""
    cfg, weights, _ = ckpt
    assert worst_dlogprob(dict(cfg, ep_rank=0), weights, *served) > WRONG


def test_fused_input_linear_splits_into_gate_then_up(ckpt):
    """`load_weights` cuts a checkpoint's fused `input_linear` into the
    repo's `w1` (gate) and `w3` (up) stacks `[N, I, D]`, the shared MLP's
    likewise, and takes the share's experts."""
    cfg, weights, d = ckpt
    params = make_llm(d).llm_engine.worker.params
    lp = params["layers"][0]
    fused = weights["model.layers.0.block_sparse_moe.input_linear.weight"]
    width = cfg["intermediate_size"]
    np.testing.assert_array_equal(np.asarray(lp["w1"]), fused[8:, :width])
    np.testing.assert_array_equal(np.asarray(lp["w3"]), fused[8:, width:])
    assert lp["w2"].shape == (8, width, cfg["hidden_size"])
    assert lp["gate_router"].shape == (cfg["hidden_size"], 16)
    assert "lm_head" not in params


# --- the state's description ----------------------------------------------------

def test_config_describes_the_layers_and_the_state(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    assert mc.get_layer_cache_kinds() == ["state", "state", "kv", "state"]
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (1, 3)
    # the filter's taps over x, B and C in the model's dtype; the float32
    # matrix [state channels, inner] as one lane tile
    assert mc.get_state_arrays() == (((3, 128 + 2 * 16), "bfloat16"),
                                     ((1, 16, 128), "float32"))
    assert [mc.state_array_in_place(s) for s, _ in mc.get_state_arrays()] \
        == [False, True]
    # taps: 480 values padded to 512 lanes x 2 B; matrix: 16 rows x 128
    # lanes x 4 B; 3 layers
    assert CacheEngine.get_state_slot_size(mc) == 3 * (512 * 2
                                                       + 16 * 128 * 4)
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 1 * 2 * 2 * 16 * 128 * 2


def test_both_config_classes_read_alike(ckpt):
    """The installed `transformers`' own class and the engine's shim (for
    an installation that lacks it) describe the same layers and state."""
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.transformers_utils.configs import (
        GraniteMoeHybridConfig)
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    shim = ModelConfig.__new__(ModelConfig)
    shim.dtype = "bfloat16"
    shim.hf_config = GraniteMoeHybridConfig.from_dict(cfg)
    assert shim.get_layer_cache_kinds() == mc.get_layer_cache_kinds()
    assert shim.get_state_arrays() == mc.get_state_arrays()
    assert (shim.hf_config.ep_size, shim.hf_config.ep_rank) == (
        mc.hf_config.ep_size, mc.hf_config.ep_rank) == (2, 1)


def test_published_size_slot_bytes():
    """At the published widths a slot is 38.2 MB: 9 layers of 128 x 8192
    float32 + 9 taps of 3 x 8448 bf16."""
    from types import SimpleNamespace
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    mc = ModelConfig.__new__(ModelConfig)
    mc.dtype = "bfloat16"
    mc.hf_config = SimpleNamespace(
        num_hidden_layers=10,
        layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4)
    assert mc.get_layer_cache_kinds() == (
        ["state"] * 5 + ["kv"] + ["state"] * 4)
    assert mc.get_state_arrays() == (((3, 8448), "bfloat16"),
                                     ((64, 128, 128), "float32"))
    assert CacheEngine.get_state_slot_size(mc) == 9 * (
        128 * 8192 * 4 + 3 * 8448 * 2) == 38_204_928
    assert CacheEngine.state_pool_shape((64, 128, 128), 128) == (
        129, 64, 128, 128)
    assert CacheEngine.state_pool_shape((3, 8448), 128) == (128, 25344)


@pytest.mark.parametrize("case", ["pad_row_with_a_slot", "permuted_slots"])
def test_decode_stage_moves_the_taps_as_the_at_forms_did(ckpt, monkeypatch,
                                                         case):
    """One K=8 fused decode step on pools of random bytes, the taps' rows
    gathered and scattered by `ops/state_rows.py` and by the `.at[]` forms
    it replaced: the same tokens, every pool the same bytes."""
    from tests.models import state_stage
    _, _, d = ckpt
    assert state_stage.assert_same_step(make_llm(d), monkeypatch, case) == 3
