"""Falcon-H1 (in every layer a Mamba-2 scan with TWO groups of B and C
beside a RoPE GQA attention, twelve muP multipliers, an untied head) against
its plain float32 reference, `tests/models/falcon_h1_reference.py`, at a tiny
preset that keeps every structural feature: 2 layers, 4 scan heads of 32 in
2 groups over 16 state channels, 4 query heads over 2 KV heads of 32, the
4-tap filter with its bias over x, B and C together, vocabulary 512, all
twelve multipliers away from 1.

The engine side is the normal path: a checkpoint under HF's tensor names ->
`load_weights` -> scheduler, block manager (a block table AND a state slot a
sequence), the `mixed` program (chunked prefill: `ssd_chunk` beside the
ragged attention) and the K=8 fused decode (`ssd_step` on the pool by slot
beside the staged K/V). The reference is the full causal forward pass over
the whole sequence, the scan a token and a head at a time, teacher-forced on
the engine's tokens; what is compared is log-softmax values, never sampled
tokens.

The file's engines are few and shared (module fixtures): tier-1 has little
time left (ROADMAP R0).
"""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from tests.models import falcon_h1_reference as ref
from tests.models.test_solar_open2 import (generate, greedy, make_llm,
                                           prompts_of, slots_of)

TINY = dict(
    model_type="falcon_h1", architectures=["FalconH1ForCausalLM"],
    vocab_size=512, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, attention_bias=False, attn_layer_indices=None,
    mamba_d_ssm=128, mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=128,
    mamba_conv_bias=True, mamba_proj_bias=False,
    mamba_norm_before_gate=False, mamba_rms_norm=True, mamba_use_mlp=True,
    mlp_bias=False, projectors_bias=False,
    embedding_multiplier=5.5, lm_head_multiplier=0.05,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.09,
    ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.4],
    attention_in_multiplier=0.7, attention_out_multiplier=0.04,
    key_multiplier=0.1, mlp_multipliers=[0.18, 0.02],
    rope_theta=100000000000, rope_scaling=None,
    max_position_embeddings=128, rms_norm_eps=1e-5,
    tie_word_embeddings=False, torch_dtype="float32")

MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier")


def published_weights(cfg: dict, seed: int = 0) -> dict:
    """Seeded random weights under the checkpoint names, [out, in], at the
    scales `init_random_params` draws them (models/falcon_h1.py says why:
    every matrix at fan-in divided by the multipliers that follow it)."""
    rng = np.random.default_rng(seed)
    e, inter = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    hs, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner, bc = hs * p, g * n
    writer = 0.2

    def lin(out, inp, scale=1.0):
        return (scale * rng.normal(size=(out, inp)) / np.sqrt(inp)).astype(
            np.float32)

    def norm(size):
        return (1 + 0.1 * rng.normal(size=size)).astype(np.float32)

    segments = np.repeat(
        1 / (cfg["ssm_in_multiplier"] * np.asarray(cfg["ssm_multipliers"])),
        (inner, inner, bc, bc, hs))
    w = {"model.embed_tokens.weight": (rng.normal(size=(
        cfg["vocab_size"], e)) / cfg["embedding_multiplier"]).astype(
            np.float32),
         "model.final_layernorm.weight": norm(e),
         "lm_head.weight": lin(cfg["vocab_size"], e,
                               1 / cfg["lm_head_multiplier"])}
    a_in = cfg["attention_in_multiplier"]
    for i in range(cfg["num_hidden_layers"]):
        q = f"model.layers.{i}."
        m, a, f = q + "mamba.", q + "self_attn.", q + "feed_forward."
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), hs))
        w.update({
            q + "input_layernorm.weight": norm(e),
            q + "pre_ff_layernorm.weight": norm(e),
            m + "in_proj.weight": (lin(2 * inner + 2 * bc + hs, e)
                                   * segments[:, None]).astype(np.float32),
            m + "conv1d.weight": (rng.normal(size=(inner + 2 * bc, 1, 4))
                                  / 2).astype(np.float32),
            m + "conv1d.bias": (rng.normal(size=inner + 2 * bc) / 2).astype(
                np.float32),
            m + "dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            m + "A_log": np.log(rng.uniform(1, 16, hs)).astype(np.float32),
            m + "D": (1 + 0.3 * rng.normal(size=hs)).astype(np.float32),
            m + "norm.weight": norm(inner),
            m + "out_proj.weight": lin(e, inner,
                                       writer / cfg["ssm_out_multiplier"]),
            a + "q_proj.weight": lin(h * d, e, 2 / a_in),
            a + "k_proj.weight": lin(hkv * d, e,
                                     2 / (a_in * cfg["key_multiplier"])),
            a + "v_proj.weight": lin(hkv * d, e, 1 / a_in),
            a + "o_proj.weight": lin(
                e, h * d, writer / cfg["attention_out_multiplier"]),
            f + "gate_proj.weight": lin(inter, e,
                                        1 / cfg["mlp_multipliers"][0]),
            f + "up_proj.weight": lin(inter, e),
            f + "down_proj.weight": lin(e, inter,
                                        writer / cfg["mlp_multipliers"][1])})
    return w


def word_tokenizer(save_dir: str, vocab_size: int) -> None:
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast
    vocab = {"<pad>": 0, "</s>": 1, "<unk>": 2}
    for i in range(vocab_size - 3):
        vocab[f"w{i:03d}"] = len(vocab)
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", eos_token="</s>",
        unk_token="<unk>").save_pretrained(save_dir)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """(cfg, weights, model dir) of the tiny model."""
    import safetensors.numpy
    d = str(tmp_path_factory.mktemp("falcon_h1") / "tiny")
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
# attention's softmax over as many keys).
F32_TOL = 1e-4
# What a reference computed wrong in one way has to read at least: a
# hundred times what the order of summation leaves.
WRONG = 1e-2
LENS, NEW, PADDED = (20, 70, 30, 22), 40, 112


_COUNTED = {}         # the run's own increments of the dispatch counters


def _dispatch_counters():
    from prometheus_client import REGISTRY
    return {(name, program): REGISTRY.get_sample_value(
        f"intellillm_dispatch_{name}_total", {"program": program}) or 0.0
        for name in ("walked_tokens", "kv_walk_bytes", "row_steps",
                     "state_bytes")
        for program in ("mixed", "decode_fused", "decode_cont")}


@pytest.fixture(scope="module")
def served(ckpt):
    """(llm, prompts, outputs, preemptions) of the one float32 K=8 run that
    every wrong reference is held against: four prompts (70 tokens against a
    32-token budget: chunked prefill through BOTH caches) in a pool of 10
    blocks, too small for the four, so the scheduler preempts by recompute
    and a re-admitted sequence's rows share `mixed` steps with the others'
    decode rows; then the K=8 fused decode and its continuation."""
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
        llm = make_llm(d, blocks=10)
        # (the boot record is the process's: a later engine overwrites it)
        _COUNTED["cache"] = llm.llm_engine._boot.snapshot()["cache"]
        outs = generate(llm, prompts, [greedy(NEW)] * len(LENS))
    _COUNTED.update({k: v - before[k]
                     for k, v in _dispatch_counters().items()})
    return llm, prompts, outs, hits["n"]


def test_engine_matches_reference_float32(ckpt, served):
    """Prefill then decode through the paged pool AND the slots, logits at
    every position that gave a token, with a preempted and re-admitted
    sequence among them."""
    cfg, weights, _ = ckpt
    llm, prompts, outs, preemptions = served
    from intellillm_tpu.obs import get_compile_tracker
    programs = get_compile_tracker().snapshot()["compiles"]
    assert programs.get("mixed") and programs.get("decode_fused")
    assert llm.llm_engine.scheduler_config.num_decode_steps == 8
    assert preemptions > 0, "the pool was sized to force a preemption"
    assert worst_dlogprob(cfg, weights, prompts, outs) < F32_TOL
    bm = slots_of(llm)
    assert not bm.state_slots and len(bm._free_state_slots) == 8


# --- wrong references ---------------------------------------------------------

def _with(key, value):
    return lambda cfg: dict(cfg, **{key: value})


def _entry(key, index):
    def wrong(cfg):
        values = list(cfg[key])
        values[index] = 1.0
        return dict(cfg, **{key: values})
    return wrong


def _norm_before_gate(y, z, w, groups, eps):
    t, inner = y.shape
    y = y.reshape(t, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(t, inner) * jnp.asarray(w, ref.F32) * jax.nn.silu(z)


def _one_norm_group(y, z, w, groups, eps):
    y = y * jax.nn.silu(z)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y * jnp.asarray(w, ref.F32)


def _key_multiplier_on_v(cfg, w, prefix, x):
    """The multiplier on v in k's place."""
    mult = cfg["key_multiplier"]
    moved = dict(w)
    moved[prefix + "v_proj.weight"] = w[prefix + "v_proj.weight"] * mult
    return _RIGHT_ATTENTION(dict(cfg, key_multiplier=1.0), moved, prefix, x)


def _rope_left_out(x, theta):
    return x


_RIGHT_ATTENTION = ref.attention
PATCHES = {
    # group 0's B and C for every head
    "group_map_all_zero": ("head_groups",
                           lambda heads, groups: np.zeros(heads, int)),
    # heads dealt to the groups in turn, not in contiguous halves
    "group_map_interleaved": ("head_groups",
                              lambda heads, groups: np.arange(heads) % groups),
    "norm_before_gate": ("gated_norm", _norm_before_gate),
    "one_norm_group": ("gated_norm", _one_norm_group),
    "key_multiplier_on_v": ("attention", _key_multiplier_on_v),
    "rope_left_out": ("rope", _rope_left_out),
}
CONFIGS = {
    **{name: _with(name, 1.0) for name in MULTIPLIERS},
    **{f"ssm_multipliers_{j}": _entry("ssm_multipliers", j)
       for j in range(5)},
    "mlp_multipliers_0": _entry("mlp_multipliers", 0),
    "mlp_multipliers_1": _entry("mlp_multipliers", 1),
}


@pytest.mark.parametrize("name", [*CONFIGS, *PATCHES])
def test_each_wrong_reference_is_seen(ckpt, served, monkeypatch, name):
    """The reference with one of the twelve multipliers at 1, the heads'
    group map wrong, the norm before the gate or over all channels at
    once, the key multiplier on v, or no RoPE reads far outside the
    tolerance against the same engine outputs (the shortest
    sequence's)."""
    cfg, weights, _ = ckpt
    _, prompts, outs, _ = served
    if name in CONFIGS:
        cfg = CONFIGS[name](cfg)
    else:
        attr, wrong = PATCHES[name]
        monkeypatch.setattr(ref, attr, wrong)
        cfg = dict(cfg)             # not the kept readings' reference
    assert worst_dlogprob(cfg, weights, prompts[:1], outs[:1]) > WRONG, name


def test_a_bfloat16_state_is_seen(ckpt, served, monkeypatch):
    """The reference with its scan's state rounded to bfloat16 after every
    token (what a state pool in the activations' dtype would hold) reads
    outside the float32 tolerance."""
    cfg, weights, _ = ckpt
    _, prompts, outs, _ = served

    def scan_head_bf16(x, dt, a, b, c):
        def step(s, r):
            x_t, dt_t, b_t, c_t = r
            s = jnp.exp(dt_t * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            return s, s @ c_t

        _, y = jax.lax.scan(
            step, jnp.zeros((x.shape[1], b.shape[1]), jnp.float32),
            (x, dt, b, c))
        return y

    monkeypatch.setattr(ref, "scan_head", scan_head_bf16)
    assert worst_dlogprob(dict(cfg), weights, prompts[1:2],
                          outs[1:2]) > 3 * F32_TOL


# --- the other programs -------------------------------------------------------

def test_single_step_decode_gives_the_fused_steps_tokens(ckpt, served):
    """K=1 (every decode token a row of the `mixed` program, a run of one
    row that reads and writes its slot through the chunk form and walks
    its pages through the ragged kernel) in a roomy pool against K=8 in
    the tight one: the same tokens, and the reference agrees with both."""
    cfg, weights, d = ckpt
    _, prompts, outs8, _ = served
    llm = make_llm(d, num_decode_steps=1)
    assert llm.llm_engine.scheduler_config.num_decode_steps == 1
    outs1 = generate(llm, prompts, [greedy(NEW)] * len(LENS))
    assert ([o.outputs[0].token_ids for o in outs1]
            == [o.outputs[0].token_ids for o in outs8])
    assert worst_dlogprob(cfg, weights, prompts, outs1) < F32_TOL


def test_engine_matches_reference_bfloat16(ckpt):
    """bf16 weights, K/V and filter taps, the matrix state float32; the
    float32 reference on the same bf16-rounded weights. The limits are
    bf16's own distance at this size with room for another seed:
    activations and the stream are rounded to 8 bits of mantissa after
    every product, a dozen times a layer, under logits of O(1); the
    engine reads 0.0041 on average and 0.017 at worst, where the float32
    engine reads 1e-5 on the same weights and the mildest wrong reference
    above 1e-2 at every position."""
    cfg, weights, d = ckpt
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in weights.items()}
    prompts = prompts_of(cfg, (9, 70, 33))
    llm = make_llm(d, dtype="bfloat16")
    cache = llm.llm_engine.worker.cache_engine.device_cache
    assert [str(p.dtype) for p in cache[0]] == ["bfloat16", "bfloat16"]
    assert [str(p.dtype) for p in cache[1]] == ["bfloat16", "float32"]
    outs = generate(llm, prompts, [greedy(20)] * 3)
    diffs = dlogprobs(cfg, rounded, prompts, outs)
    assert diffs.mean() < 0.015 and diffs.max() < 0.1


# --- what the engine says of the two caches ------------------------------------

def test_config_describes_both_caches_of_a_layer(ckpt):
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    cfg, _, d = ckpt
    mc = ModelConfig(d, dtype="bfloat16", max_model_len=128)
    # layer l: entry 2l its K/V pools, entry 2l + 1 its state pools
    assert mc.get_layer_cache_kinds() == ["kv", "state", "kv", "state"]
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (2, 2)
    # the filter's taps over x and both groups' B and C; the float32 matrix
    # [state channels, inner] as one 64-lane tile a group
    assert mc.get_state_arrays() == (((3, 128 + 2 * 2 * 16), "bfloat16"),
                                     ((2, 16, 64), "float32"))
    assert CacheEngine.get_state_slot_size(mc) == 2 * (
        640 * 2 + 2 * 16 * 128 * 4)
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 2 * 2 * 2 * 16 * 128 * 2
    # both config classes read alike
    from intellillm_tpu.transformers_utils.configs import FalconH1Config
    shim = ModelConfig.__new__(ModelConfig)
    shim.dtype = "bfloat16"
    shim.hf_config = FalconH1Config.from_dict(cfg)
    assert shim.get_layer_cache_kinds() == mc.get_layer_cache_kinds()
    assert shim.get_state_arrays() == mc.get_state_arrays()


def test_counters_and_boot_cache_say_both_kinds(ckpt, served):
    """`/health/detail` `boot.cache` and `intellillm_cache_bytes{kind}` give
    a K/V and a state figure; the decode programs count the K/V bytes
    their walks moved beside the state bytes."""
    cache = _COUNTED["cache"]
    assert (cache["kv_layers"], cache["state_layers"]) == (2, 2)
    # 10 blocks x 2 layers x (K + V) x 2 heads x 16 x 128 lanes x 4 B
    assert cache["bytes"]["kv"] == 10 * 2 * 2 * 2 * 16 * 128 * 4
    # 8 slots x 2 layers x (taps 640 x 4 B + 2 tiles x 16 x 128 lanes x 4 B)
    assert cache["bytes"]["state"] == 8 * 2 * (640 * 4 + 2 * 16 * 128 * 4)
    for program in ("mixed", "decode_fused"):
        walked = _COUNTED["walked_tokens", program]
        # a token: 2 layers x (K + V) x 2 heads x 32 x 4 B
        assert walked > 0 and _COUNTED["kv_walk_bytes", program] == (
            walked * 2 * 2 * 2 * 32 * 4)
        # a row-substep: 2 layers x the matrix [16, 128] float32, in and out
        assert _COUNTED["state_bytes", program] == (
            _COUNTED["row_steps", program] * 2 * 2 * 16 * 128 * 4)


def test_what_the_state_cannot_do_is_refused_with_a_reason(ckpt, served):
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
