"""SmallThinker (sliding-window RoPE layers beside full-attention layers
without position encoding, ReGLU experts routed before attention) against
its plain float32 reference, `tests/models/smallthinker_reference.py`, at a
tiny preset that keeps every structural feature: 8 layers = two periods
(full + NoPE, then three window + RoPE), a window of 32 tokens, 8 experts
top-2, 4 heads over 2 KV heads.

The engine side is the normal path: a checkpoint under the family's tensor
names -> `load_weights` -> scheduler, block manager (a full and a window
block table a sequence), the `mixed` program (chunked prefill, chunks that
straddle the window's edge) and the K=8 fused decode with its continuation
(the window's lower edge moving a token a substep, the window group giving
blocks back as it goes). The reference is the full causal forward pass over
the whole sequence under an explicit mask, teacher-forced on the engine's
tokens; what is compared is log-softmax values (the engine's top-5 logprobs
at the prefill's last position and every decoded one), never sampled tokens.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer
from tests.models import smallthinker_reference as ref

WINDOW = 32
TINY = dict(
    model_type="smallthinker", architectures=["SmallThinkerForCausalLM"],
    hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=WINDOW, max_position_embeddings=256,
    rms_norm_eps=1e-6, rope_theta=1500000.0, rope_scaling=None,
    tie_word_embeddings=False, torch_dtype="float32")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the published names, [out, in]. q and k
    at twice the fan-in scale, so that attention is peaked enough for a
    wrong mask or a wrong rotation to reach the logits."""
    rng = np.random.default_rng(seed)
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    inter = cfg["moe_ffn_hidden_size"]

    def lin(out, inp, gain=1.0):
        return (gain * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def norm(n):
        return (1 + 0.1 * rng.normal(size=n)).astype(np.float32)

    w = {"model.embed_tokens.weight":
         rng.normal(size=(cfg["vocab_size"], e)).astype(np.float32),
         "model.norm.weight": norm(e),
         "lm_head.weight": lin(cfg["vocab_size"], e)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "block_sparse_moe."
        w.update({p + "input_layernorm.weight": norm(e),
                  p + "post_attention_layernorm.weight": norm(e),
                  a + "q_proj.weight": lin(h * d, e, 2.0),
                  a + "k_proj.weight": lin(hkv * d, e, 2.0),
                  a + "v_proj.weight": lin(hkv * d, e),
                  a + "o_proj.weight": lin(e, h * d, 0.5),
                  m + "primary_router.weight": lin(
                      cfg["moe_num_primary_experts"], e)})
        for j in range(cfg["moe_num_primary_experts"]):
            x = f"{m}experts.{j}."
            w.update({x + "gate.weight": lin(inter, e),
                      x + "up.weight": lin(inter, e),
                      x + "down.weight": lin(e, inter, 0.5)})
    return w


def write_model(d: str, cfg: dict) -> dict:
    import safetensors.numpy
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)
    weights = published_weights(cfg)
    safetensors.numpy.save_file(weights, os.path.join(d, "model.safetensors"))
    return weights


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    d = str(tmp_path_factory.mktemp("smallthinker") / "tiny")
    _, vocab = _build_word_tokenizer(d)
    cfg = dict(TINY, vocab_size=vocab)
    return cfg, write_model(d, cfg), d


def make_llm(model_dir, dtype="float32", blocks=96, **kw):
    from intellillm_tpu import LLM
    kw.setdefault("max_num_batched_tokens", 32)
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("block_size", 8)
    return LLM(model=model_dir, dtype=dtype,
               num_device_blocks_override=blocks, max_model_len=256,
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


def dlogprobs(cfg, weights, prompts, outs, **wrong):
    """|engine logprob - reference log-softmax| over the top-5 of every
    position that gave a token, teacher-forced on the engine's tokens."""
    diffs = []
    for p, out in zip(prompts, outs):
        for c in out.outputs:
            lsm = np.asarray(jax.nn.log_softmax(
                ref.forward(cfg, weights, p + list(c.token_ids), **wrong),
                -1))
            for j, lp in enumerate(c.logprobs):
                for tok, v in lp.items():
                    v = float(getattr(v, "logprob", v))
                    diffs.append(abs(lsm[len(p) - 1 + j, tok] - v))
    return np.asarray(diffs)


def greedy(n, **kw):
    from intellillm_tpu import SamplingParams
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          logprobs=5, **kw)


# Float32 on both sides: what is left is the order of summation (the
# engine's online softmax over pages and its pool + stage merge against the
# reference's one softmax a row), ~1e-6 a value at these sizes.
F32_TOL = 1e-4


@pytest.fixture(scope="module")
def served(ckpt):
    """One engine run the tests below read: prompts on both sides of the
    32-token window against a 32-token budget (the 70- and 45-token
    prompts' chunks straddle the window's edge; the 20-token one crosses
    it while decoding), several sequences and pad rows in one `mixed`
    step, then the K=8 fused decode and its continuation for 60 tokens:
    across the edge and across several blocks given back."""
    cfg, weights, d = ckpt
    llm = make_llm(d)
    prompts = prompts_of(cfg, (9, 70, 45, 20))
    outs = generate(llm, prompts, [greedy(60)] * 4)
    return cfg, weights, llm, prompts, outs


def test_engine_matches_reference_float32(served):
    cfg, weights, llm, prompts, outs = served
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert programs.get("decode_cont")
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    bm = llm.llm_engine.scheduler.block_manager
    assert bm.window_group is not None and bm.window_blocks_recycled > 0
    assert not bm.block_tables and not bm.window_group.tables
    assert (bm.window_group.device.get_num_free_blocks()
            == bm.window_group.num_device_blocks)


def all_full(cfg):
    return dict(cfg, sliding_window_layout=[0] * cfg["num_hidden_layers"])


def rotary_everywhere(cfg):
    return dict(cfg, rope_layout=[1] * cfg["num_hidden_layers"])


@pytest.mark.parametrize("wrong", ["window_dropped", "rotary_on_full_layers",
                                   "silu_for_relu"])
def test_a_wrong_reference_disagrees(served, wrong):
    """The comparison can tell: the reference computed with every layer
    full, with rotary on the NoPE layers, or with a SiLU gate is a
    thousand times further from the engine than the tolerance."""
    cfg, weights, _, prompts, outs = served
    kw = {}
    if wrong == "window_dropped":
        cfg = all_full(cfg)
    elif wrong == "rotary_on_full_layers":
        cfg = rotary_everywhere(cfg)
    else:
        kw = {"gate_act": jax.nn.silu}
    assert dlogprobs(cfg, weights, prompts[1:3], outs[1:3], **kw).max() > 0.05


def windows_of(llm):
    bm = llm.llm_engine.scheduler.block_manager
    return bm, bm.window_group


def assert_all_given_back(llm):
    bm, g = windows_of(llm)
    assert not bm.block_tables and not g.tables
    for group in bm.groups:
        assert (group.device.get_num_free_blocks()
                == group.num_device_blocks)
        assert group.cpu.get_num_free_blocks() == group.cpu.num_blocks


def test_single_step_decode_matches_reference(ckpt):
    """K=1: every decode token is a row of the `mixed` program, written
    through both groups' tables and read under the window."""
    cfg, weights, d = ckpt
    llm = make_llm(d, num_decode_steps=1)
    assert llm.llm_engine.scheduler_config.num_decode_steps == 1
    prompts = prompts_of(cfg, (9, 40), seed=6)
    outs = generate(llm, prompts, [greedy(30)] * 2)
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    assert windows_of(llm)[0].window_blocks_recycled > 0


def test_whole_prompt_prefill_matches_chunked(ckpt):
    """A budget that takes each prompt whole (one `mixed` step a prompt,
    rows on both sides of the window's edge in it) gives the logits the
    32-token chunks gave."""
    cfg, weights, d = ckpt
    llm = make_llm(d, max_num_batched_tokens=128)
    prompts = prompts_of(cfg, (70, 45, 20), seed=9)
    outs = generate(llm, prompts, [greedy(12)] * 3)
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL


def test_recompute_preemption_rebuilds_both_groups(ckpt, monkeypatch):
    """A pool too small for four sequences: the scheduler preempts by
    recompute; both groups' blocks are given back and the re-prefill
    fills both again from position 0."""
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
    llm = make_llm(d, blocks=40)
    outs = generate(llm, prompts, [greedy(60)] * 4)
    assert hits["n"] > 0, "the pool was sized to force a preemption"
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    assert_all_given_back(llm)


def test_fork_and_swap_move_both_groups(ckpt, monkeypatch):
    """best_of=2: the children share the prompt's blocks of both groups and
    copy on write; under a tight pool the groups preempt by SWAP: the
    window group's live blocks go to the host with the full group's and
    come back. Every sampled sequence agrees with the reference
    teacher-forced on it, and the tight run's tokens equal the roomy
    run's."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, weights, d = ckpt
    prompts = prompts_of(cfg, (20, 25, 30, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=60,
                             ignore_eos=True, logprobs=5)] * 4
    roomy = generate(make_llm(d, blocks=256), prompts, params)
    assert dlogprobs(cfg, weights, prompts, roomy).max() < F32_TOL
    moved = {"swap_out": 0, "swap_in": 0, "copy": 0}
    for name in moved:
        orig = getattr(ce.CacheEngine, name)

        def counting(self, mapping, window=False, name=name, orig=orig):
            moved[name] += bool(window)
            return orig(self, mapping, window=window)

        monkeypatch.setattr(ce.CacheEngine, name, counting)
    llm = make_llm(d, blocks=72)
    tight = generate(llm, prompts, params)
    assert all(moved.values()), moved     # the window group's own moves
    assert dlogprobs(cfg, weights, prompts, tight).max() < F32_TOL
    for r, t in zip(roomy, tight):
        assert (sorted(c.token_ids for c in r.outputs) ==
                sorted(c.token_ids for c in t.outputs))
    assert_all_given_back(llm)


def test_continuation_join_crosses_the_window(ckpt):
    """Requests that arrive while a decode batch runs are prefilled behind
    it (their chunks beside its decode rows in `mixed`) and join it by
    continuation with both their tables."""
    from intellillm_tpu.obs import get_efficiency_tracker
    cfg, weights, d = ckpt
    llm = make_llm(d)
    engine = llm.llm_engine
    assert engine.pipeline_enabled
    prompts = prompts_of(cfg, (40, 30, 50, 36), seed=7)
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
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    assert_all_given_back(llm)


@pytest.mark.parametrize("k,budget", [(8, 32), (1, 32), (8, 128)])
def test_a_window_on_every_layer_is_one_windowed_group(ckpt, tmp_path, k,
                                                       budget):
    """Every layer with the window (what the whole-model ring served, at
    K=1 and with chunks capped at the window): one cache group, the
    windowed one, through the same programs, chunks wider than the window
    and the K=8 decode included; what it gives is what the mask says."""
    cfg, weights, _ = ckpt
    cfg = dict(cfg, sliding_window_layout=[1] * cfg["num_hidden_layers"])
    d = str(tmp_path / "all-window")
    os.makedirs(d)
    _build_word_tokenizer(d)
    write_model(d, cfg)
    llm = make_llm(d, num_decode_steps=k, max_num_batched_tokens=budget)
    mc = llm.llm_engine.model_config
    assert mc.get_sliding_window() == WINDOW
    assert mc.get_window_group_size() is None
    assert set(mc.get_layer_cache_kinds()) == {"kv"}
    bm = llm.llm_engine.scheduler.block_manager
    assert bm.window_group is None and bm.groups[0].window == WINDOW
    prompts = prompts_of(cfg, (9, 70, 45), seed=4)
    outs = generate(llm, prompts, [greedy(40)] * 3)
    assert llm.llm_engine.scheduler_config.num_decode_steps == k
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    assert bm.window_blocks_recycled > 0
    # A sequence holds the window's blocks, what a chunk writes ahead of
    # it and the two partly used ones at the ends, however long it grows.
    assert bm.groups[0].cap == (WINDOW + budget) // 8 + 2


@pytest.mark.parametrize("what", ["tensor_parallel", "speculative",
                                  "prefill_role", "decode_role",
                                  "prefix_pos", "export", "import"])
def test_what_a_window_group_cannot_do_is_refused_with_a_reason(ckpt, what):
    from intellillm_tpu import LLM
    from intellillm_tpu.engine.arg_utils import EngineArgs
    from intellillm_tpu.engine.llm_engine import LLMEngine
    cfg, _, d = ckpt
    kw = dict(model=d, dtype="float32", num_device_blocks_override=64,
              max_model_len=128)
    if what == "tensor_parallel":
        with pytest.raises(NotImplementedError, match="one chip"):
            LLM(tensor_parallel_size=2, **kw)
    elif what == "speculative":
        with pytest.raises(NotImplementedError, match="no window"):
            LLMEngine.from_engine_args(EngineArgs(
                speculative_model=d, num_speculative_tokens=3, **kw))
    elif what in ("prefill_role", "decode_role"):
        with pytest.raises(NotImplementedError, match="IKV1 payload"):
            LLMEngine.from_engine_args(EngineArgs(
                replica_role=what.split("_")[0], **kw))
    else:
        engine = make_llm(d).llm_engine
        if what == "prefix_pos":
            with pytest.raises(NotImplementedError,
                               match="window group gives back"):
                engine.add_request(
                    "p", None, greedy(4),
                    prompt_token_ids=prompts_of(cfg, (40, ))[0],
                    prefix_pos=16)
        elif what == "export":
            with pytest.raises(NotImplementedError,
                               match="sliding-window cache group"):
                engine.worker.cache_engine.export_blocks([0, 1])
        else:
            with pytest.raises(NotImplementedError,
                               match="sliding-window cache group"):
                engine.worker.cache_engine.import_blocks([], [])


def test_config_describes_the_groups(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="float32", max_model_len=256)
    assert mc.get_layer_cache_kinds() == ["kv", "kv_window", "kv_window",
                                          "kv_window"] * 2
    assert (mc.get_num_kv_layers(), mc.get_num_window_layers(),
            mc.get_num_state_layers()) == (2, 6, 0)
    assert mc.get_window_group_size() == WINDOW
    assert mc.get_sliding_window() is None
    # a block of the window group a block of the full one: the window and
    # what a step writes ahead of it, over the model's length
    assert mc.get_window_group_share(32) == (32 + 32) / 256
    assert mc.get_window_group_share(10**6) == 1.0
    # a primary block's bytes count the window layers' share of one
    layer_block = 2 * 2 * 8 * 128 * 4      # K + V, 2 heads, 8 tokens, f32
    assert CacheEngine.get_cache_block_size(
        8, "auto", mc, ParallelConfig(), 32) == (
            2 * layer_block + int(6 * 0.25 * layer_block))
    assert CacheEngine.get_cache_block_size(
        8, "auto", mc, ParallelConfig()) == (
            2 * layer_block + int(6 * 32 / 256 * layer_block))


def test_tracing_shows_both_groups(served):
    """`/health/detail` `boot.cache`, `intellillm_cache_bytes{kind}`, the
    recycled-blocks counter and the attended-tokens counter by layer kind
    after the run above."""
    cfg, _, llm, prompts, outs = served
    engine = llm.llm_engine
    cache = engine._boot.snapshot()["cache"]
    groups = cache["groups"]
    assert groups["kv"]["layers"] == 2 and groups["kv"]["window"] is None
    assert groups["kv_window"]["layers"] == 6
    assert groups["kv_window"]["window"] == WINDOW
    cc = engine.cache_config
    assert groups["kv"]["tokens"] == cc.num_device_blocks * 8
    assert groups["kv_window"]["tokens"] == cc.num_window_device_blocks * 8
    assert cc.num_window_device_blocks == 24          # 96 x (32 + 32) / 256
    # K + V of 2 heads stored at one 128-lane tile, float32
    assert cache["bytes_per_token_per_layer"] == 2 * 2 * 128 * 4
    per_layer = cache["bytes_per_token_per_layer"] * 8
    assert cache["bytes"] == {
        "kv": 2 * per_layer * cc.num_device_blocks,
        "kv_window": 6 * per_layer * cc.num_window_device_blocks,
        "state": 0}
    pytest.importorskip("prometheus_client")
    from prometheus_client import REGISTRY

    def sample(name, **labels):
        return REGISTRY.get_sample_value(name, labels) or 0.0

    assert sample("intellillm_cache_bytes", kind="kv_window") > 0
    bm = engine.scheduler.block_manager
    assert sample("intellillm_window_blocks_total",
                  event="recycled") >= bm.window_blocks_recycled > 0
    full = sum(sample("intellillm_dispatch_attended_tokens_total",
                      program=p, layers="full")
               for p in ("mixed", "decode_fused", "decode_cont"))
    window = sum(sample("intellillm_dispatch_attended_tokens_total",
                        program=p, layers="window")
                 for p in ("mixed", "decode_fused", "decode_cont"))
    context = sum(sample("intellillm_dispatch_context_tokens_total",
                         program=p)
                  for p in ("mixed", "decode_fused", "decode_cont"))
    # A full layer's rows attend to their whole context; a window layer's
    # to less, by what the traffic's contexts pass the window.
    assert 0 < window < full <= context


def test_decode_steps_read_the_experts_their_rows_chose(tmp_path):
    """With 16 experts top-2 a 16-row decode step is expected to choose
    0.88 of them: the K=8 decode programs take the grouped form (by the
    rule, which the rows counter asks too), agree with the reference like
    the dense ones, and intellillm_moe_expert_visits_total says how many
    of 16 experts x 8 layers a substep each finished step read."""
    from intellillm_tpu.layers.moe import grouped_rows
    from intellillm_tpu.obs import get_efficiency_tracker
    assert grouped_rows(16, 2, 16) and not grouped_rows(32, 2, 16)
    _, vocab = _build_word_tokenizer(str(tmp_path))
    cfg = dict(TINY, vocab_size=vocab, moe_num_primary_experts=16)
    weights = write_model(str(tmp_path), cfg)
    tracker = get_efficiency_tracker()
    before = tracker.snapshot()
    prompts = prompts_of(cfg, (9, 40))
    outs = generate(make_llm(str(tmp_path)), prompts, [greedy(20)] * 2)
    assert dlogprobs(cfg, weights, prompts, outs).max() < F32_TOL
    after = tracker.snapshot()

    def delta(*keys):
        a, b = after, before
        for k in keys:
            a, b = a[k], b[k]
        return a - b

    per_substep = 16 * 8
    decode = {v: sum(delta("moe_expert_visits", p, v)
                     for p in ("decode_fused", "decode_cont"))
              for v in ("yes", "no")}
    # 19 tokens past the prefill's own sample: three K=8 steps.
    assert decode["yes"] + decode["no"] == 3 * 8 * per_substep
    # 2 live rows and 14 pad rows, which choose nothing: at most 4 experts
    # a layer-substep have a row.
    assert 0 < decode["yes"] <= 3 * 8 * 8 * 4
    # 32-row `mixed` steps run dense and read every expert, 16-row ones
    # are grouped like the decode steps.
    mixed = [delta("moe_expert_visits", "mixed", v) for v in ("yes", "no")]
    assert sum(mixed) % per_substep == 0 and mixed[0] > mixed[1]
    assert delta("moe_ffn_rows", "grouped") >= 3 * 8 * 16
    assert delta("moe_ffn_rows", "dense") >= 32


@pytest.fixture(scope="module")
def seats_13_of_16(tmp_path_factory):
    """13 two-token prompts in 16 seats, 32 experts top-2: one 32-row
    `mixed` step (26 live rows) and two K=8 decode steps of 16 rows with 3
    seats empty, all in the grouped form, run twice: as the engine runs
    them, and with the rows' liveness kept from `moe_ffn_grouped` (every
    row routed like a live one: the engine before it knew). A callback in
    front of `moe_ffn_grouped` records, each time a layer runs, the
    program it runs in, its rows, the live ones among them and how many
    experts those rows' choices name. {masked: (outputs, visits counted
    by the engine a program, records)}, with cfg, weights and prompts."""
    from intellillm_tpu.layers import moe
    from intellillm_tpu.layers.moe import grouped_rows
    from intellillm_tpu.obs import get_efficiency_tracker
    from intellillm_tpu.worker.model_runner import ModelRunner
    assert grouped_rows(16, 2, 32) and grouped_rows(32, 2, 32)
    d = str(tmp_path_factory.mktemp("smallthinker-seats") / "tiny")
    os.makedirs(d)
    _, vocab = _build_word_tokenizer(d)
    cfg = dict(TINY, vocab_size=vocab, moe_num_primary_experts=32)
    weights = write_model(d, cfg)
    prompts = prompts_of(cfg, (2, ) * 13)
    tracing, records, runs = {}, [], {}
    grouped = moe.moe_ffn_grouped

    def traced_as(name, program):
        step = getattr(ModelRunner, name)

        def fn(self, *args, **kwargs):
            tracing["program"] = program    # while jit traces `step`
            return step(self, *args, **kwargs)
        return fn

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ModelRunner, "_decode_fn",
                      traced_as("_decode_fn", "decode"))
        patch.setattr(ModelRunner, "_decode_fn_single",
                      traced_as("_decode_fn_single", "mixed"))
        tracker = get_efficiency_tracker()
        for masked in (True, False):

            def spy(x, *args, live=None, masked=masked, **kwargs):
                program = tracing["program"]
                assert live is not None and live.shape == x.shape[:1]

                def record(experts, live):
                    experts, live = np.asarray(experts), np.asarray(live)
                    records.append((program, len(live), int(live.sum()),
                                    len(set(experts[live].reshape(-1)
                                            .tolist()))))
                jax.debug.callback(record, kwargs["routed"][1],
                                   live if masked else jnp.ones_like(live))
                return grouped(x, *args, live=live if masked else None,
                               **kwargs)

            patch.setattr(moe, "moe_ffn_grouped", spy)
            del records[:]
            before = tracker.snapshot()["moe_expert_visits"]
            outs = generate(make_llm(d, max_num_seqs=16, blocks=512),
                            prompts, [greedy(17)] * 13)
            jax.effects_barrier()
            after = tracker.snapshot()["moe_expert_visits"]
            visits = {"mixed": after["mixed"]["yes"] - before["mixed"]["yes"],
                      "decode": sum(after[p]["yes"] - before[p]["yes"]
                                    for p in ("decode_fused", "decode_cont"))}
            runs[masked] = (outs, visits, list(records))
    return cfg, weights, prompts, runs


def test_empty_seats_leave_the_live_rows_results_alone(seats_13_of_16):
    """The 13 live rows' tokens and logprobs are, bit for bit, what the
    same batch gives with the pad rows routed like live ones, and agree
    with the reference."""
    cfg, weights, prompts, runs = seats_13_of_16
    (masked, _, _), (unmasked, _, _) = runs[True], runs[False]

    def logprobs(out):
        return [{t: float(getattr(v, "logprob", v)) for t, v in lp.items()}
                for lp in out.outputs[0].logprobs]

    for a, b in zip(masked, unmasked):
        assert a.outputs[0].token_ids == b.outputs[0].token_ids
        assert len(a.outputs[0].token_ids) == 17
        assert logprobs(a) == logprobs(b)
    assert dlogprobs(cfg, weights, prompts, masked).max() < F32_TOL


@pytest.mark.parametrize("program,rows,live", [("decode", 16, 13),
                                               ("mixed", 32, 26)])
def test_a_step_visits_the_experts_its_live_rows_chose(seats_13_of_16,
                                                       program, rows, live):
    """intellillm_moe_expert_visits_total{visited="yes"} of the K=8 decode
    steps (`_decode_fn`: 16 seats, 3 empty) and of the `mixed` step
    (`_decode_fn_single`: 32 rows, 6 of them pad) is what the live rows'
    routing alone names, layer by layer and substep by substep; routed
    like live ones the pad rows had more experts read."""
    _, _, _, runs = seats_13_of_16
    for masked, live_seen in ((True, live), (False, rows)):
        _, visits, records = runs[masked]
        mine = [r for r in records if r[0] == program]
        # 8 layers a substep; two K=8 decode steps, one `mixed` step
        assert len(mine) == 8 * (16 if program == "decode" else 1)
        assert {r[1:3] for r in mine} == {(rows, live_seen)}
        assert visits[program] == sum(r[3] for r in mine) > 0
    assert runs[True][1][program] < runs[False][1][program]
