"""Phi-4-mini-flash (Mamba-1 scans and window layers, ONE full-attention
layer whose pool the cross layers read, gated memory units, differential
attention) against its plain float32 reference,
`tests/models/phi4flash_reference.py`, at a tiny preset that keeps every
structural feature: 8 layers = scan, window, scan, window, scan (gives `m`),
full, GMU, cross by the model's own rule; a 32-token window; 8 query heads
over 4 K/V heads of 16 (pairs: a stored head of 32); 256 scan channels over
16 state channels, `dt` from rank 8; vocabulary 1,024.

The engine side is the normal path: a checkpoint under the hub's tensor names
-> `load_weights` -> scheduler, block manager (TWO block tables and a state
slot a sequence), the `mixed` program (chunked prefill whose chunks cross the
window: `sscan_chunk`, the wrapped window, the cross layer over the rows the
full layer has just written) and the K=8 fused decode past the window
(`sscan_step` on the pool by slot, the full layer's stage read by the cross
layer). The reference is the full causal forward pass over the whole
sequence, teacher-forced on the engine's tokens; what is compared is
log-softmax values, never sampled tokens.

One module-scoped engine serves every comparison (tier-1 has little time
left, ROADMAP R0).
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.models import phi4flash_reference as ref
from tests.models.test_falcon_h1 import word_tokenizer
from tests.models.test_solar_open2 import (generate, greedy, make_llm,
                                           prompts_of, slots_of)

TINY = dict(
    model_type="phi4flash", architectures=["Phi4FlashForCausalLM"],
    vocab_size=1024, hidden_size=128, intermediate_size=192,
    num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
    mb_per_layer=2, sliding_window=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank="auto", mamba_conv_bias=True,
    mamba_proj_bias=False, mlp_bias=False, layer_norm_eps=1e-5,
    max_position_embeddings=128, tie_word_embeddings=True,
    torch_dtype="float32")

LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the checkpoint names, [out, in], at the
    scales `init_random_params` draws them (models/phi4flash.py says why)."""
    rng = np.random.default_rng(seed)
    e, inter = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    inner, n = cfg["mamba_expand"] * e, cfg["mamba_d_state"]
    r = ref.dt_rank(cfg)
    writer = 0.2

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def vec(size, scale=1.0, around=0.0):
        return (around + scale * rng.normal(size=size)).astype(np.float32)

    w = {"model.embed_tokens.weight": lin(cfg["vocab_size"], e),
         "model.final_layernorm.weight": vec(e),
         "model.final_layernorm.bias": vec(e, 0.1)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "attn."
        w.update({
            p + "input_layernorm.weight": vec(e, 0.1, 1.0),
            p + "input_layernorm.bias": vec(e, 0.1),
            p + "post_attention_layernorm.weight": vec(e, 0.1, 1.0),
            p + "post_attention_layernorm.bias": vec(e, 0.1),
            p + "mlp.fc1.weight": lin(2 * inter, e),
            p + "mlp.fc2.weight": lin(e, inter, writer)})
        kind = ref.layer_kind(cfg, i)
        if kind == "scan":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), inner))
            w.update({
                a + "in_proj.weight": lin(2 * inner, e),
                a + "conv1d.weight": vec((inner, 1, 4), 0.5),
                a + "conv1d.bias": vec(inner, 0.5),
                a + "x_proj.weight": lin(r + 2 * n, inner),
                a + "dt_proj.weight": lin(inner, r),
                a + "dt_proj.bias": (dt + np.log(-np.expm1(-dt))).astype(
                    np.float32),
                a + "A_log": np.log(rng.uniform(1, 16, (inner, n))).astype(
                    np.float32),
                a + "D": vec(inner, 0.3, 1.0),
                a + "out_proj.weight": lin(e, inner, writer)})
        elif kind == "gmu":
            w.update({a + "in_proj.weight": lin(inner, e),
                      a + "out_proj.weight": lin(e, inner, writer)})
        else:
            rows = h * d if kind == "cross" else (h + 2 * hkv) * d
            qkv = lin(rows, e)
            qkv[:(h + hkv) * d] *= 2.0      # queries and keys
            w.update({
                a + "Wqkv.weight": qkv,
                a + "Wqkv.bias": vec(rows, 0.1),
                a + "out_proj.weight": lin(e, h * d, writer),
                a + "out_proj.bias": vec(e, 0.1),
                a + "subln.weight": vec(2 * d, 0.1, 1.0)})
            w.update({a + name: vec(d, 0.2) for name in LAMBDAS})
    return w


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("phi4flash") / "tiny")
    os.makedirs(d)
    cfg = dict(TINY)
    word_tokenizer(d, cfg["vocab_size"])
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)
    weights = published_weights(cfg)
    safetensors.numpy.save_file(weights, os.path.join(d, "model.safetensors"))
    return cfg, weights, d


_LSM = {}


def log_softmax_of(cfg, weights, tokens):
    """The reference's log-softmax [T, vocab] of one sequence; the right
    reference's are kept (several tests read the same sequences)."""
    plain = cfg is _LSM.get("cfg") and weights is _LSM.get("weights")
    key = tuple(tokens)
    if plain and key in _LSM:
        return _LSM[key]
    # Every sequence at one padded length (the pass is causal: what comes
    # after a position does not reach it), so that the plain reference's
    # operations are compiled for one shape.
    padded = list(tokens) + [0] * (PADDED - len(tokens))
    lsm = np.asarray(jax.nn.log_softmax(ref.forward(cfg, weights, padded),
                                        -1))[:len(tokens)]
    if plain:
        _LSM[key] = lsm
    return lsm


def dlogprobs(cfg, weights, prompts, outs):
    """|engine logprob - reference log-softmax| over the top-5 of every
    position that gave a token, teacher-forced on the engine's tokens."""
    diffs = []
    for p, out in zip(prompts, outs):
        for c in out.outputs:
            lsm = log_softmax_of(cfg, weights, list(p) + list(c.token_ids))
            for j, lp in enumerate(c.logprobs):
                for tok, v in lp.items():
                    v = float(getattr(v, "logprob", v))
                    diffs.append(abs(lsm[len(p) - 1 + j, tok] - v))
    return np.asarray(diffs)


def worst_dlogprob(*args):
    return float(dlogprobs(*args).max())


# Float32 on both sides: what is left is the order of summation (the scan's
# state summed over up to 110 tokens in either order of its 16 channels, the
# attention's two softmaxes over as many keys, their difference normalised).
F32_TOL = 1e-4
# What a reference computed wrong in one way has to read at least: a
# hundred times what the order of summation leaves.
WRONG = 1e-2
LENS, NEW, PADDED = (20, 70, 45, 22), 40, 112
# The full group's 14 blocks hold 224 tokens, the four sequences at their
# longest 317: the scheduler preempts. The window group gets (32 + 32) / 128
# of them, 7 blocks: a sequence's cap is 6.
TIGHT = 14
_COUNTED = {}         # the run's own increments of the dispatch counters


def _dispatch_counters():
    from prometheus_client import REGISTRY
    return {(name, program): REGISTRY.get_sample_value(
        f"intellillm_dispatch_{name}_total", {"program": program}) or 0.0
        for name in ("walked_tokens", "kv_walk_bytes",
                     "shared_kv_walk_bytes", "row_steps", "state_bytes")
        for program in ("mixed", "decode_fused", "decode_cont")}


@pytest.fixture(scope="module")
def served(ckpt):
    """(llm, prompts, outputs, preemptions) of the one float32 K=8 engine
    that every comparison is held against: four prompts (70 and 45 tokens
    against a 32-token budget and a 32-token window: chunked prefill that
    crosses the window, through both block groups and the slots) in a pool
    too small for the four, so the scheduler preempts by recompute and a
    re-admitted sequence's rows share `mixed` steps with the others'
    decode rows (sequences of different lengths in one step); then the K=8
    fused decode and its continuation, 40 tokens: every sequence decodes
    past the window."""
    from intellillm_tpu.core import scheduler as sched_mod
    cfg, weights, d = ckpt
    _LSM.update(cfg=cfg, weights=weights)
    hits = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["n"] += 1
        return orig(self, seq_group)

    prompts = prompts_of(cfg, LENS, seed=2)
    before = _dispatch_counters()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sched_mod.Scheduler, "_preempt_by_recompute", counting)
        llm = make_llm(d, blocks=TIGHT)
        # (the boot record is the process's: a later engine overwrites it)
        _COUNTED["cache"] = llm.llm_engine._boot.snapshot()["cache"]
        outs = generate(llm, prompts, [greedy(NEW)] * len(LENS))
    _COUNTED.update({k: v - before[k]
                     for k, v in _dispatch_counters().items()})
    return llm, prompts, outs, hits["n"]


def assert_all_given_back(llm, slots=8):
    bm = slots_of(llm)
    assert not bm.state_slots and not bm.cpu_state_slots
    assert len(bm._free_state_slots) == len(bm._free_cpu_state_slots) == slots
    for group in bm.groups:
        assert not group.tables
        assert (group.device.get_num_free_blocks()
                == group.num_device_blocks)
        assert group.cpu.get_num_free_blocks() == group.cpu.num_blocks


def test_engine_matches_reference_float32(ckpt, served):
    """Prefill in chunks that cross the window, then K=8 decode past it,
    through the full pool, the window pools AND the slots, logits at every
    position that gave a token, with a preempted and re-admitted sequence
    among them."""
    cfg, weights, _ = ckpt
    llm, prompts, outs, preemptions = served
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    assert preemptions > 0, "the pool was sized to force a preemption"
    assert slots_of(llm).window_blocks_recycled > 0
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert_all_given_back(llm)


# --- wrong references --------------------------------------------------------

def _own_keys_and_values(cfg, w, full_prefix, u, shared):
    return ref.keys_and_values(cfg, w, full_prefix, u)


PATCHES = {
    # the cross layer attends to keys and values of ITS OWN input
    "cross_own_kv": ("cross_keys_and_values", _own_keys_and_values),
    "lambda_zero": ("lambda_full", lambda w, prefix, layer: 0.0),
    "lambda_init_of_layer_0": ("lambda_init", lambda layer: 0.2),
    "memory_after_gate": ("memory_of", lambda y, z: y * jax.nn.silu(z)),
}
CONFIGS = {
    # the window one block short
    "window_16": lambda cfg: dict(cfg, sliding_window=16),
    "no_window": lambda cfg: dict(cfg, sliding_window=10**6),
}


@pytest.mark.parametrize("name", [*PATCHES, *CONFIGS])
def test_each_wrong_reference_is_seen(ckpt, served, monkeypatch, name):
    """The reference with the cross layer reading its own keys and values,
    lambda at 0 or `lambda_init` not the layer's, `m` taken after the gate,
    or the window cut short or left out reads far outside the tolerance
    against the same engine outputs (the 70-token prompt's: it crosses the
    window in its prefill)."""
    cfg, weights, _ = ckpt
    _, prompts, outs, _ = served
    if name in CONFIGS:
        cfg = CONFIGS[name](cfg)
    else:
        attr, wrong = PATCHES[name]
        monkeypatch.setattr(ref, attr, wrong)
        cfg = dict(cfg)             # not the kept readings' reference
    assert worst_dlogprob(cfg, weights, prompts[1:2], outs[1:2]) > WRONG, name


def test_a_lower_precision_is_seen(ckpt, served):
    """The reference on weights rounded to bfloat16 (the precision below the
    float32 the run states) reads outside the float32 tolerance."""
    cfg, weights, _ = ckpt
    _, prompts, outs, _ = served
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    assert worst_dlogprob(dict(cfg), rounded, prompts[1:2],
                          outs[1:2]) > 3 * F32_TOL


def test_folded_heads_are_the_four_products():
    """`DifferentialAttention` (K/V head pairs stored as one head twice as
    wide, `q1` as `[q1 | 0]`, `q2` as `[0 | q2]`, plain GQA) against the
    reference's four softmax products written out, on one sequence of 40
    rows written and read through a paged pool in one `mixed` step, with and
    without a 16-token window."""
    from intellillm_tpu.layers.attention import (AttentionMetadata,
                                                 DifferentialAttention)
    rng = np.random.default_rng(0)
    t, h, hkv, d, bs = 40, 8, 4, 16, 16
    q = rng.normal(size=(t, h, d)).astype(np.float32)
    k = rng.normal(size=(t, hkv, d)).astype(np.float32)
    v = rng.normal(size=(t, hkv, d)).astype(np.float32)
    subln = (1 + 0.1 * rng.normal(size=2 * d)).astype(np.float32)
    lam, init = 0.37, 0.55
    pos = np.arange(t)
    for window in (None, 16):
        visible = pos[None, :] <= pos[:, None]
        if window:
            visible &= pos[None, :] > pos[:, None] - window
        want = ref.differential(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), lam, visible)
        want = want / jnp.sqrt(jnp.mean(want * want, -1, keepdims=True)
                               + 1e-5) * subln * (1 - init)
        pool = (jnp.zeros((4, hkv // 2, bs, 2 * d), jnp.float32), ) * 2
        meta = AttentionMetadata(
            slot_mapping=jnp.asarray(pos + bs)[:, None],   # blocks 1, 2, 3
            context_lens=jnp.asarray(pos + 1),
            block_tables=jnp.tile(jnp.asarray([[1, 2, 3]]), (t, 1)))
        attn = DifferentialAttention(h, d, hkv, sliding_window=window)
        got, _ = attn(jnp.asarray(q)[:, None], jnp.asarray(k)[:, None],
                      jnp.asarray(v)[:, None], pool, meta, lam=lam,
                      lambda_init=init, subln=jnp.asarray(subln))
        np.testing.assert_allclose(np.asarray(got)[:, 0],
                                   np.asarray(want).reshape(t, h * d),
                                   atol=2e-5, rtol=2e-5)


# --- the other paths, on the same engine -------------------------------------

def test_fork_and_swap_move_both_groups_and_the_state(ckpt, served,
                                                      monkeypatch):
    """best_of=2 (an engine of its own: 32 blocks, a window group of 16,
    where two children's window caps fit and eight sequences' contexts do
    not): the children share the prompt's blocks of both groups and fork
    its state slot; the groups preempt by SWAP: the full group's blocks,
    the window group's live blocks and the slot go to the host and come
    back. Every sampled sequence agrees with the reference teacher-forced
    on it."""
    from intellillm_tpu import SamplingParams
    from intellillm_tpu.worker import cache_engine as ce
    cfg, weights, d = ckpt
    llm = make_llm(d, blocks=32)
    prompts = prompts_of(cfg, (20, 25, 40, 22), seed=3)
    params = [SamplingParams(temperature=0.8, best_of=2, n=2, max_tokens=30,
                             ignore_eos=True, logprobs=5)] * 4
    moved = {"swap_state_out": 0, "swap_state_in": 0, "copy_state": 0,
             "swap_out": 0, "swap_in": 0}
    for name in moved:
        orig = getattr(ce.CacheEngine, name)

        def counting(self, mapping, window=None, name=name, orig=orig):
            if window is None:
                moved[name] += 1
                return orig(self, mapping)
            moved[name] += bool(window)
            return orig(self, mapping, window=window)

        monkeypatch.setattr(ce.CacheEngine, name, counting)
    outs = generate(llm, prompts, params)
    assert all(moved.values()), moved
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert_all_given_back(llm)


def test_continuation_join_brings_both_tables_and_a_slot(ckpt, served):
    """Requests that arrive while a decode batch runs are prefilled behind
    it (their chunks beside its decode rows in `mixed`) and join it by
    continuation with both their tables and their slot."""
    from intellillm_tpu.obs import get_efficiency_tracker
    cfg, weights, _ = ckpt
    llm = served[0]
    engine = llm.llm_engine
    assert engine.pipeline_enabled
    prompts = prompts_of(cfg, (40, 20, 50), seed=7)
    joins0 = get_efficiency_tracker()._decode_joins["device"]
    done = {}

    def pump(n):
        for _ in range(n):
            for out in engine.step_pipelined():
                if out.finished:
                    done[out.request_id] = out

    engine.add_request("0", None, greedy(40), prompt_token_ids=prompts[0])
    pump(4)
    engine.add_request("1", None, greedy(30), prompt_token_ids=prompts[1])
    pump(3)
    engine.add_request("2", None, greedy(20), prompt_token_ids=prompts[2])
    while engine.has_unfinished_requests() or engine.has_inflight():
        pump(1)
    assert get_efficiency_tracker()._decode_joins["device"] - joins0 >= 2
    outs = [done[str(i)] for i in range(3)]
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    assert_all_given_back(llm)


# --- what the engine says of its caches --------------------------------------

def test_config_class_describes_the_caches(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    # scan, window, scan, window, scan (gives m), full, GMU, cross
    assert mc.hf_config.cache_description("bfloat16")["layers"] == [
        "state", "kv_window", "state", "kv_window", "state", "kv", None, 5]
    # one entry a layer that HOLDS something ...
    assert mc.get_layer_cache_kinds() == [
        "state", "kv_window", "state", "kv_window", "state", "kv"]
    # ... the GMU reads none and the cross layer the full layer's
    assert mc.get_layer_cache_entries() == [0, 1, 2, 3, 4, 5, None, 5]
    assert mc.get_cache_walkers() == (2, 2, 1)
    assert (mc.get_num_kv_layers(), mc.get_num_window_layers(),
            mc.get_num_state_layers()) == (1, 2, 3)
    assert mc.get_window_group_size() == 32
    assert mc.get_sliding_window() is None
    # K/V heads in pairs: 2 stored heads of 32
    assert mc.get_cache_row_shape() == (2, 2, 32)
    assert mc.get_state_arrays() == (((3, 256), "bfloat16"),
                                     ((2, 16, 128), "float32"))
    assert CacheEngine.get_state_slot_size(mc) == 3 * (
        768 * 2 + 2 * 16 * 128 * 4)
    # a primary block: the full layer's, and (32 + 0) / 128 of a block in
    # each of the two window layers (lanes padded to 128)
    layer_block = 2 * 2 * 16 * 128 * 2
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == layer_block + int(
            2 * 0.25 * layer_block)
    # the published layout
    from intellillm_tpu.transformers_utils.configs import Phi4FlashConfig
    layers = Phi4FlashConfig().cache_description("bfloat16")["layers"]
    assert [layers.count(k) for k in ("state", "kv_window", "kv", None, 17)
            ] == [9, 8, 1, 7, 7]
    assert layers[16] == "state" and layers[17] == "kv"
    # a family without a description reads as before
    plain = ModelConfig.__new__(ModelConfig)
    plain.dtype = "bfloat16"
    from transformers import LlamaConfig
    plain.hf_config = LlamaConfig(num_hidden_layers=3)
    assert plain.get_layer_cache_kinds() == ["kv"] * 3
    assert plain.get_layer_cache_entries() == [0, 1, 2]
    assert plain.get_cache_walkers() == (3, 0, 0)


def test_counters_and_boot_cache_say_pools_and_readers(ckpt, served):
    """`/health/detail` `boot.cache` gives the three kinds' bytes and, a
    group, the layers that hold a pool and those that read it; the walk
    counters count WALKERS, with the part walked by layers that hold no
    pool in a series of its own."""
    cache = _COUNTED["cache"]
    assert (cache["kv_layers"], cache["state_layers"]) == (1, 3)
    row = 2 * 2 * 16 * 128 * 4          # K + V, 2 stored heads, lanes padded
    assert cache["bytes"]["kv"] == TIGHT * row
    assert cache["bytes"]["kv_window"] == 7 * 2 * row
    assert cache["bytes"]["state"] == 8 * 3 * (768 * 4 + 2 * 16 * 128 * 4)
    groups = cache["groups"]
    assert (groups["kv"]["layers"], groups["kv"]["readers"]) == (1, 2)
    assert (groups["kv_window"]["layers"],
            groups["kv_window"]["readers"]) == (2, 2)
    token = 2 * 2 * 32 * 4              # K + V of a token in one layer
    for program in ("mixed", "decode_fused"):
        walked = _COUNTED["walked_tokens", program]
        # the mean over the four walkers x a token's bytes in the four
        assert walked > 0 and _COUNTED["kv_walk_bytes", program] == (
            walked * 4 * token)
        shared = _COUNTED["shared_kv_walk_bytes", program]
        # the cross layer's walks: a whole number of pages, under half of
        # all (the full layer's own are as many, the window layers' more)
        assert 0 < shared < _COUNTED["kv_walk_bytes", program] / 2
        assert shared % (16 * token) == 0
        # a row-substep: 3 scans x the matrix [16, 256] float32, in and out
        assert _COUNTED["state_bytes", program] == (
            _COUNTED["row_steps", program] * 3 * 2 * 16 * 256 * 4)


def test_what_the_family_cannot_do_is_refused_with_a_reason(ckpt, served):
    from intellillm_tpu import LLM
    cfg, _, d = ckpt
    llm, *_ = served
    with pytest.raises(NotImplementedError, match="one chip"):
        LLM(model=d, dtype="float32", num_device_blocks_override=32,
            max_model_len=128, tensor_parallel_size=2)
    with pytest.raises(NotImplementedError, match="state snapshots"):
        llm.llm_engine.add_request(
            "p", None, greedy(4),
            prompt_token_ids=prompts_of(cfg, (40, ))[0], prefix_pos=16)
    with pytest.raises(NotImplementedError, match="does not support quant"):
        LLM(model=d, dtype="float32", num_device_blocks_override=32,
            max_model_len=128, quantization="int8")
    with pytest.raises(NotImplementedError, match="KV export"):
        llm.llm_engine.worker.cache_engine.export_blocks([0])
    assert llm.llm_engine.worker.model.supports_lora is False
    assert "Speculative" in llm.llm_engine.worker.model.speculation_refusal


# --- the block manager: two groups and a slot under pressure -----------------

def test_block_manager_admits_by_both_groups_and_the_slots():
    """A manager with a full group, a window group and state slots: an
    admission needs blocks of BOTH groups and a slot, whichever runs out
    first makes it wait, and a freed sequence gives all three back."""
    from intellillm_tpu.core.block_manager import (AllocStatus,
                                                   BlockSpaceManager)
    from intellillm_tpu.sampling_params import SamplingParams
    from intellillm_tpu.sequence import (Sequence, SequenceGroup,
                                         SequenceStatus)

    def group_of(i, n):
        seq = Sequence(i, "x", list(range(n)), 16)
        return SequenceGroup(str(i), [seq], SamplingParams(), 0.0), seq

    def manager(full, window, slots):
        return BlockSpaceManager(16, full, 8, watermark=0.0,
                                 num_state_slots=slots,
                                 window_group=(32, window, 8),
                                 window_ahead=32)

    # the window group runs out first: a 100-token prompt holds 7 blocks of
    # the full group and its cap, (32 + 32) / 16 + 2 = 6, of the window's
    bm = manager(full=32, window=8, slots=4)
    (g0, s0), (g1, s1) = group_of(0, 100), group_of(1, 100)
    assert bm.can_allocate(g0) == AllocStatus.OK
    bm.allocate(g0)
    assert len(bm.block_tables[0]) == 7
    assert len(bm.get_window_block_table(s0)) == 6
    assert bm.get_state_slot(0) is not None
    assert bm.can_allocate(g1) == AllocStatus.LATER       # 2 window blocks
    # the slots run out first
    bm = manager(full=32, window=32, slots=1)
    bm.allocate(g0)
    assert bm.can_allocate(g1) == AllocStatus.OK
    assert not bm.can_allocate_state(g1)
    s0.status = SequenceStatus.FINISHED_STOPPED
    bm.free(s0)
    assert bm.can_allocate_state(g1)
    assert all(g.device.get_num_free_blocks() == g.num_device_blocks
               for g in bm.groups)
    # a sequence that could never hold its window cap is refused for good
    assert manager(32, 5, 4).can_allocate(g1) == AllocStatus.NEVER
    # decode growth asks both groups
    bm = manager(full=8, window=32, slots=2)
    bm.allocate(g1)
    s1.status = SequenceStatus.RUNNING
    assert bm.can_append_slots(g1, 8) is False            # 1 full block left
