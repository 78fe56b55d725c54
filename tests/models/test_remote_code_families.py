"""Trust-remote-code model families (baichuan, qwen-v1, chatglm,
deepseek, aquila) — tested without executing remote code.

Strategies (reference covers most of these only via trust_remote_code on
real checkpoints, which needs network):
- *Equivalence goldens*: baichuan-7B == llama with W_pack fused; qwen-v1
  == qwen2 with fused c_attn and renamed tensors. We convert a tiny
  HF-native checkpoint into the remote-code layout and require identical
  greedy tokens.
- *Prefill/decode self-consistency*: for archs with no HF-native twin
  (chatglm, deepseek, baichuan-ALiBi), generating N tokens then re-feeding
  prompt+prefix must reproduce the continuation — catches KV-cache layout,
  position, and rope bugs.
- *Config shims*: config.json with remote-code model_type parses without
  trust_remote_code.
"""
import json
import os

import numpy as np
import pytest
import torch

from tests.conftest import _build_word_tokenizer

MAX_TOKENS = 12


def _save_config(d, cfg: dict):
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)


def _save_tensors(d, tensors):
    import safetensors.numpy
    safetensors.numpy.save_file(
        {k: np.ascontiguousarray(v) for k, v in tensors.items()},
        os.path.join(d, "model.safetensors"))


def _engine_greedy(model_dir, prompts, max_tokens=MAX_TOKENS):
    from intellillm_tpu import LLM, SamplingParams
    llm = LLM(model=model_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01)
    outs = llm.generate(prompts, SamplingParams(temperature=0.0,
                                                max_tokens=max_tokens))
    return [o.outputs[0].token_ids for o in outs]


def _dummy_engine_greedy(hf_config, prompt_ids_list, max_tokens):
    """Engine with random weights from an in-memory config (no tokenizer)."""
    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.engine.llm_engine import LLMEngine
    from intellillm_tpu.sampling_params import SamplingParams

    model_config = ModelConfig.from_hf_config(hf_config, dtype="float32",
                                              max_model_len=128,
                                              load_format="dummy")
    cache_config = CacheConfig(block_size=16,
                               num_device_blocks_override=128,
                               swap_space_gib=0.01)
    scheduler_config = SchedulerConfig(max_num_batched_tokens=2048,
                                       max_num_seqs=8, max_model_len=128,
                                       max_paddings=512)
    engine = LLMEngine(model_config, cache_config, ParallelConfig(),
                       scheduler_config, log_stats=False,
                       skip_tokenizer_init=True)
    params = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                            ignore_eos=True)
    for i, ids in enumerate(prompt_ids_list):
        engine.add_request(str(i), None, params, prompt_token_ids=list(ids))
    results = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                results[out.request_id] = out.outputs[0].token_ids
    return [results[str(i)] for i in range(len(prompt_ids_list))]


def _check_self_consistency(hf_config, seed=0):
    """Continuations must be stable under prompt extension (prefill KV ==
    decode KV)."""
    rng = np.random.default_rng(seed)
    vocab = hf_config.vocab_size
    prompt = rng.integers(0, vocab, size=9).tolist()
    full = _dummy_engine_greedy(hf_config, [prompt], 8)[0]
    ext = _dummy_engine_greedy(hf_config, [prompt + full[:4]], 4)[0]
    assert ext == full[4:8], f"full={full} ext={ext}"


# --- baichuan: equivalence with llama ------------------------------------


@pytest.fixture(scope="module")
def baichuan_pair(tmp_path_factory):
    """(llama_dir, baichuan_dir) with identical math."""
    from transformers import LlamaConfig, LlamaForCausalLM

    root = tmp_path_factory.mktemp("baichuan-eq")
    llama_dir = str(root / "llama")
    _, vocab_size = _build_word_tokenizer(llama_dir)
    torch.manual_seed(0)
    config = LlamaConfig(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, pad_token_id=0, bos_token_id=1,
        eos_token_id=1, tie_word_embeddings=False,
        torch_dtype=torch.float32)
    model = LlamaForCausalLM(config).eval()
    model.save_pretrained(llama_dir, safe_serialization=True)

    bc_dir = str(root / "baichuan")
    _build_word_tokenizer(bc_dir)
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    tensors = {
        "model.embed_tokens.weight": sd["model.embed_tokens.weight"],
        "model.norm.weight": sd["model.norm.weight"],
        "lm_head.weight": sd["lm_head.weight"],
    }
    for i in range(2):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = sd[
            p + "input_layernorm.weight"]
        tensors[p + "post_attention_layernorm.weight"] = sd[
            p + "post_attention_layernorm.weight"]
        tensors[p + "self_attn.W_pack.weight"] = np.concatenate([
            sd[p + "self_attn.q_proj.weight"],
            sd[p + "self_attn.k_proj.weight"],
            sd[p + "self_attn.v_proj.weight"]], axis=0)
        tensors[p + "self_attn.o_proj.weight"] = sd[
            p + "self_attn.o_proj.weight"]
        for t in ("gate_proj", "up_proj", "down_proj"):
            tensors[p + f"mlp.{t}.weight"] = sd[p + f"mlp.{t}.weight"]
    _save_tensors(bc_dir, tensors)
    _save_config(bc_dir, {
        "model_type": "baichuan",
        "architectures": ["BaiChuanForCausalLM"],
        "vocab_size": vocab_size, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "hidden_act": "silu",
        "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
        "pad_token_id": 0, "bos_token_id": 1, "eos_token_id": 1,
        "tie_word_embeddings": False,
    })
    return llama_dir, bc_dir


def test_baichuan_matches_llama_twin(baichuan_pair, example_prompts,
                                     hf_runner):
    llama_dir, bc_dir = baichuan_pair
    hf = hf_runner(llama_dir)
    golden = hf.generate_greedy(example_prompts, MAX_TOKENS)
    ours = _engine_greedy(bc_dir, example_prompts)
    for h, o in zip(golden, ours):
        assert list(h[:len(o)]) == list(o[:len(h)]) or h == o, \
            f"hf={h} ours={o}"


def test_baichuan_alibi_self_consistent():
    """13B-style (hidden != 4096 → ALiBi) has no HF twin; check KV-cache
    consistency on the dummy engine."""
    from intellillm_tpu.transformers_utils.configs import BaichuanConfig
    cfg = BaichuanConfig(vocab_size=128, hidden_size=80,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128)
    cfg.architectures = ["BaichuanForCausalLM"]
    _check_self_consistency(cfg)


# --- qwen v1: equivalence with qwen2 -------------------------------------


@pytest.fixture(scope="module")
def qwen_pair(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    root = tmp_path_factory.mktemp("qwen-eq")
    q2_dir = str(root / "qwen2")
    _, vocab_size = _build_word_tokenizer(q2_dir)
    torch.manual_seed(0)
    config = Qwen2Config(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, pad_token_id=0, bos_token_id=1,
        eos_token_id=1, tie_word_embeddings=False,
        torch_dtype=torch.float32)
    model = Qwen2ForCausalLM(config).eval()
    model.save_pretrained(q2_dir, safe_serialization=True)

    q1_dir = str(root / "qwen1")
    _build_word_tokenizer(q1_dir)
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    tensors = {
        "transformer.wte.weight": sd["model.embed_tokens.weight"],
        "transformer.ln_f.weight": sd["model.norm.weight"],
        "lm_head.weight": sd["lm_head.weight"],
    }
    for i in range(2):
        src = f"model.layers.{i}."
        dst = f"transformer.h.{i}."
        tensors[dst + "ln_1.weight"] = sd[src + "input_layernorm.weight"]
        tensors[dst + "ln_2.weight"] = sd[
            src + "post_attention_layernorm.weight"]
        tensors[dst + "attn.c_attn.weight"] = np.concatenate([
            sd[src + "self_attn.q_proj.weight"],
            sd[src + "self_attn.k_proj.weight"],
            sd[src + "self_attn.v_proj.weight"]], axis=0)
        tensors[dst + "attn.c_attn.bias"] = np.concatenate([
            sd[src + "self_attn.q_proj.bias"],
            sd[src + "self_attn.k_proj.bias"],
            sd[src + "self_attn.v_proj.bias"]], axis=0)
        tensors[dst + "attn.c_proj.weight"] = sd[
            src + "self_attn.o_proj.weight"]
        # QWen: w2 = gate, w1 = up.
        tensors[dst + "mlp.w2.weight"] = sd[src + "mlp.gate_proj.weight"]
        tensors[dst + "mlp.w1.weight"] = sd[src + "mlp.up_proj.weight"]
        tensors[dst + "mlp.c_proj.weight"] = sd[src + "mlp.down_proj.weight"]
    _save_tensors(q1_dir, tensors)
    _save_config(q1_dir, {
        "model_type": "qwen",
        "architectures": ["QWenLMHeadModel"],
        "vocab_size": vocab_size, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        # QWen configs store DOUBLE the ffn width here.
        "intermediate_size": 256,
        "layer_norm_epsilon": 1e-6, "kv_channels": 16,
        "rotary_emb_base": 10000, "seq_length": 128,
        "max_position_embeddings": 128, "no_bias": True,
        "bos_token_id": 1, "eos_token_id": 1,
        "tie_word_embeddings": False,
    })
    return q2_dir, q1_dir


def test_qwen_v1_matches_qwen2_twin(qwen_pair, example_prompts, hf_runner):
    q2_dir, q1_dir = qwen_pair
    hf = hf_runner(q2_dir)
    golden = hf.generate_greedy(example_prompts, MAX_TOKENS)
    ours = _engine_greedy(q1_dir, example_prompts)
    for h, o in zip(golden, ours):
        assert list(h[:len(o)]) == list(o[:len(h)]) or h == o, \
            f"hf={h} ours={o}"


# --- chatglm / deepseek: self-consistency --------------------------------


def test_chatglm_self_consistent():
    from intellillm_tpu.transformers_utils.configs import ChatGLMConfig
    cfg = ChatGLMConfig(num_layers=2, padded_vocab_size=128, hidden_size=64,
                        ffn_hidden_size=96, kv_channels=16,
                        num_attention_heads=4, seq_length=128,
                        multi_query_attention=True, multi_query_group_num=2)
    cfg.architectures = ["ChatGLMModel"]
    _check_self_consistency(cfg)


def test_deepseek_self_consistent():
    from intellillm_tpu.transformers_utils.configs import DeepseekConfig
    cfg = DeepseekConfig(vocab_size=128, hidden_size=64,
                         intermediate_size=128, moe_intermediate_size=32,
                         num_hidden_layers=3, num_attention_heads=4,
                         num_key_value_heads=2, n_shared_experts=2,
                         n_routed_experts=4, num_experts_per_tok=2,
                         first_k_dense_replace=1, moe_layer_freq=1,
                         norm_topk_prob=False, max_position_embeddings=128)
    cfg.architectures = ["DeepseekForCausalLM"]
    _check_self_consistency(cfg)


def test_deepseek_moe_routing_no_renorm():
    """Un-renormalized top-k routing vs a numpy loop (deepseek semantics
    differ from Mixtral exactly here)."""
    import jax.numpy as jnp
    from intellillm_tpu.layers.moe import Routing, moe_ffn_dense

    rng = np.random.RandomState(0)
    t, d, i, n, k = 10, 8, 16, 4, 2
    x = rng.randn(t, d).astype(np.float32)
    gate_w = rng.randn(d, n).astype(np.float32)
    w1 = rng.randn(n, d, i).astype(np.float32) * 0.1
    w2 = rng.randn(n, i, d).astype(np.float32) * 0.1
    w3 = rng.randn(n, d, i).astype(np.float32) * 0.1

    # The stored stacks are [N, I, D] for all three (layers/moe.py).
    out = np.asarray(moe_ffn_dense(jnp.asarray(x), jnp.asarray(gate_w),
                                   jnp.asarray(w1.swapaxes(1, 2)),
                                   jnp.asarray(w2),
                                   jnp.asarray(w3.swapaxes(1, 2)), k,
                                   routing=Routing(renormalize=False)))

    def silu(v):
        return v / (1.0 + np.exp(-v))

    probs = np.exp(x @ gate_w)
    probs = probs / probs.sum(-1, keepdims=True)
    ref = np.zeros_like(x)
    for ti in range(t):
        top = np.argsort(-probs[ti])[:k]
        for e in top:
            h = silu(x[ti] @ w1[e]) * (x[ti] @ w3[e])
            ref[ti] += probs[ti, e] * (h @ w2[e])   # NO renormalization
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# --- decilm: variable GQA == degrouped uniform-GQA llama -----------------


@pytest.fixture(scope="module")
def decilm_pair(tmp_path_factory):
    """(llama_dir, decilm_dir): the llama twin stores layer-0 K/V already
    degrouped (2 kv heads expanded to 4 — interleaved h0,h0,h1,h1, which
    is the only ordering consistent with grouped-query head mapping; a
    tile ordering would break this equivalence), the DeciLM checkpoint
    stores the grouped original + num_key_value_heads_per_layer=[2, 4].
    Degrouping is exact, so greedy tokens must match."""
    from transformers import LlamaConfig, LlamaForCausalLM

    root = tmp_path_factory.mktemp("decilm-eq")
    llama_dir = str(root / "llama")
    _, vocab_size = _build_word_tokenizer(llama_dir)
    torch.manual_seed(0)
    config = LlamaConfig(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, pad_token_id=0, bos_token_id=1,
        eos_token_id=1, tie_word_embeddings=False,
        torch_dtype=torch.float32)
    model = LlamaForCausalLM(config).eval()
    head_size = 64 // 4
    with torch.no_grad():
        for t in ("k_proj", "v_proj"):
            w = getattr(model.model.layers[0].self_attn, t).weight
            grouped = w[:2 * head_size].clone()           # 2 kv heads
            degrouped = torch.repeat_interleave(
                grouped.reshape(2, head_size, -1), 2,
                dim=0).reshape(4 * head_size, -1)         # h0,h0,h1,h1
            w.copy_(degrouped)
    model.save_pretrained(llama_dir, safe_serialization=True)

    deci_dir = str(root / "decilm")
    _build_word_tokenizer(deci_dir)
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    tensors = dict(sd)
    for t in ("k_proj", "v_proj"):
        key = f"model.layers.0.self_attn.{t}.weight"
        tensors[key] = sd[key].reshape(
            2, 2, head_size, -1)[:, 0].reshape(2 * head_size, -1)
    _save_tensors(deci_dir, tensors)
    _save_config(deci_dir, {
        "model_type": "deci",
        "architectures": ["DeciLMForCausalLM"],
        "vocab_size": vocab_size, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads_per_layer": [2, 4],
        "hidden_act": "silu", "max_position_embeddings": 128,
        "rms_norm_eps": 1e-6, "pad_token_id": 0, "bos_token_id": 1,
        "eos_token_id": 1, "tie_word_embeddings": False,
    })
    return llama_dir, deci_dir


def test_decilm_variable_gqa_matches_degrouped_llama(decilm_pair,
                                                     example_prompts,
                                                     hf_runner):
    llama_dir, deci_dir = decilm_pair
    hf = hf_runner(llama_dir)
    golden = hf.generate_greedy(example_prompts, MAX_TOKENS)
    ours = _engine_greedy(deci_dir, example_prompts)
    for h, o in zip(golden, ours):
        assert list(h[:len(o)]) == list(o[:len(h)]) or h == o, \
            f"hf={h} ours={o}"


# --- internlm: llama + attention biases ----------------------------------


@pytest.fixture(scope="module")
def internlm_pair(tmp_path_factory):
    """(llama_dir, internlm_dir) with identical math: HF llama with
    attention_bias=True vs the same tensors under model_type=internlm
    with bias=true."""
    from transformers import LlamaConfig, LlamaForCausalLM

    root = tmp_path_factory.mktemp("internlm-eq")
    llama_dir = str(root / "llama")
    _, vocab_size = _build_word_tokenizer(llama_dir)
    torch.manual_seed(0)
    config = LlamaConfig(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, pad_token_id=0, bos_token_id=1,
        eos_token_id=1, tie_word_embeddings=False, attention_bias=True,
        torch_dtype=torch.float32)
    model = LlamaForCausalLM(config).eval()
    with torch.no_grad():
        # save_pretrained zero-initializes fresh biases; randomize so the
        # equivalence actually exercises them.
        for layer in model.model.layers:
            for t in ("q_proj", "k_proj", "v_proj", "o_proj"):
                getattr(layer.self_attn, t).bias.normal_(std=0.1)
    model.save_pretrained(llama_dir, safe_serialization=True)

    il_dir = str(root / "internlm")
    _build_word_tokenizer(il_dir)
    _save_tensors(il_dir,
                  {k: v.numpy() for k, v in model.state_dict().items()})
    _save_config(il_dir, {
        "model_type": "internlm",
        "architectures": ["InternLMForCausalLM"],
        "vocab_size": vocab_size, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "bias": True, "hidden_act": "silu",
        "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
        "pad_token_id": 0, "bos_token_id": 1, "eos_token_id": 1,
        "tie_word_embeddings": False,
    })
    return llama_dir, il_dir


def test_internlm_bias_matches_llama_twin(internlm_pair, example_prompts,
                                          hf_runner):
    llama_dir, il_dir = internlm_pair
    hf = hf_runner(llama_dir)
    golden = hf.generate_greedy(example_prompts, MAX_TOKENS)
    ours = _engine_greedy(il_dir, example_prompts)
    for h, o in zip(golden, ours):
        assert list(h[:len(o)]) == list(o[:len(h)]) or h == o, \
            f"hf={h} ours={o}"


# --- config shims --------------------------------------------------------


@pytest.mark.parametrize("model_type,extra", [
    ("baichuan", {"hidden_size": 64}),
    ("qwen", {"hidden_size": 64}),
    ("chatglm", {"hidden_size": 64}),
    ("deepseek", {"hidden_size": 64}),
    ("aquila", {"hidden_size": 64}),
    ("Yi", {"hidden_size": 64}),
    ("deci", {"hidden_size": 64,
              "num_key_value_heads_per_layer": [1, 2]}),
    ("internlm", {"hidden_size": 64, "bias": True}),
])
def test_config_shim_parses_without_remote_code(tmp_path, model_type,
                                                extra):
    from intellillm_tpu.transformers_utils.config import get_hf_config
    d = str(tmp_path / model_type)
    os.makedirs(d)
    cfg = {"model_type": model_type,
           "auto_map": {"AutoConfig": "configuration_x.XConfig"}}
    cfg.update(extra)
    _save_config(d, cfg)
    hf_config = get_hf_config(d, trust_remote_code=False)
    assert hf_config.model_type == model_type
    assert hf_config.hidden_size == 64


def test_named_scopes_are_metadata_only_in_the_mixed_program(monkeypatch):
    """`moe_router` / `moe_experts` / `moe_shared` / `lm_head` / `sampler`
    name the layers in a profiler trace; the lowered `mixed` program is
    otherwise the same, operation for operation."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.engine.llm_engine import LLMEngine
    from intellillm_tpu.transformers_utils.configs import DeepseekConfig

    cfg = DeepseekConfig(vocab_size=128, hidden_size=64,
                         intermediate_size=128, moe_intermediate_size=32,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, n_shared_experts=2,
                         n_routed_experts=4, num_experts_per_tok=2,
                         first_k_dense_replace=1, moe_layer_freq=1,
                         norm_topk_prob=False, max_position_embeddings=128)
    cfg.architectures = ["DeepseekForCausalLM"]
    model_config = ModelConfig.from_hf_config(cfg, dtype="float32",
                                              max_model_len=128,
                                              load_format="dummy")
    engine = LLMEngine(
        model_config,
        CacheConfig(block_size=16, num_device_blocks_override=32,
                    swap_space_gib=0.01),
        ParallelConfig(),
        SchedulerConfig(max_num_batched_tokens=256, max_num_seqs=8,
                        max_model_len=128, max_paddings=512),
        log_stats=False, skip_tokenizer_init=True)
    runner = engine.worker.model_runner
    cache = jax.ShapeDtypeStruct((32, 2, 16, 16), jnp.float32)
    kv_struct = [(cache, cache) for _ in range(2)]

    def lowered():
        # a new jit each time: the trace must not come from jit's cache
        runner._jit_decode_single = jax.jit(
            runner._decode_fn_single,
            static_argnames=("num_samples", "plp_k", "logprob_k", "do_topk",
                             "do_topp", "do_minp", "do_penalties",
                             "do_random", "do_numerics"),
            donate_argnames=("kv_caches", ))
        return runner.lower_mixed_step(kv_struct, 16, 16)

    with_scopes = lowered()
    named = with_scopes.as_text(debug_info=True)
    scopes = ("moe_router", "moe_experts", "moe_shared", "lm_head",
              "sampler")
    for scope in scopes:
        assert f"/{scope}/" in named, scope     # an entry of the name stack
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lowered()
    unnamed = without.as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" not in unnamed, scope
    assert with_scopes.as_text() == without.as_text()
