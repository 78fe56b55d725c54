"""The MoE expert stacks' stored layout, from checkpoint to tokens.

All three stacks are `[N, I, D]` (layers/moe.py says why): an HF
checkpoint's per-expert `gate_proj` / `up_proj` (`w1` / `w3` in Mixtral)
land at `w1[j]` / `w3[j]` as they are stored, `down_proj` (`w2`)
transposed. Held here on tiny hand-written HF-format checkpoints of both
MoE families: where each tensor lands, that greedy tokens are the ones the
`[N, D, I]` layout gave (recorded from the parent commit of PR 25), and
that tp=2 on the CPU mesh, which shards axis 1 of all three stacks, gives
the tokens of tp=1.
"""
import json
import os

import numpy as np
import pytest

from tests.conftest import _build_word_tokenizer

MAX_TOKENS = 12
HIDDEN, HEADS, KV_HEADS, LAYERS = 64, 4, 2, 3
# Greedy continuations of tests.conftest EXAMPLE_PROMPTS at the parent
# commit of PR 25 (expert stacks [N, D, I]), float32 on the CPU backend.
PARENT_TOKENS = {
    "deepseek": [
        [46, 64, 70, 103, 15, 13, 73, 48, 118, 38, 87, 32],
        [22, 111, 94, 49, 62, 106, 27, 107, 119, 89, 3, 117],
        [22, 111, 60, 109, 23, 61, 34, 82, 59, 108, 18, 116],
        [115, 42, 57, 75, 83, 111, 111, 83, 106, 100, 2, 9],
    ],
    "mixtral": [
        [93, 86, 16, 59, 119, 18, 49, 100, 60, 51, 16, 59],
        [108, 75, 98, 93, 55, 49, 100, 60, 109, 59, 119, 18],
        [105, 51, 68, 33, 61, 45, 92, 118, 96, 69, 72, 66],
        [87, 32, 58, 75, 24, 94, 89, 6, 26, 55, 49, 100],
    ],
}


def _checkpoint(arch, vocab_size):
    """(config dict, {HF tensor name: float32 array}) drawn from a fixed
    numpy seed, so that every commit reads the same model."""
    rng = np.random.RandomState(25)

    def w(out_dim, in_dim, scale=0.08):
        return (rng.randn(out_dim, in_dim) * scale).astype(np.float32)

    head = HIDDEN // HEADS
    common = {
        "vocab_size": vocab_size, "hidden_size": HIDDEN,
        "num_hidden_layers": LAYERS, "num_attention_heads": HEADS,
        "num_key_value_heads": KV_HEADS, "max_position_embeddings": 128,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "pad_token_id": 0, "bos_token_id": 1, "eos_token_id": 1,
        "torch_dtype": "float32",
    }
    tensors = {
        "model.embed_tokens.weight": w(vocab_size, HIDDEN, 1.0),
        "model.norm.weight": np.ones(HIDDEN, np.float32),
        "lm_head.weight": w(vocab_size, HIDDEN, 0.3),
    }
    for li in range(LAYERS):
        p = f"model.layers.{li}."
        tensors[p + "input_layernorm.weight"] = np.ones(HIDDEN, np.float32)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(
            HIDDEN, np.float32)
        tensors[p + "self_attn.q_proj.weight"] = w(HEADS * head, HIDDEN)
        tensors[p + "self_attn.k_proj.weight"] = w(KV_HEADS * head, HIDDEN)
        tensors[p + "self_attn.v_proj.weight"] = w(KV_HEADS * head, HIDDEN)
        tensors[p + "self_attn.o_proj.weight"] = w(HIDDEN, HEADS * head)

    if arch == "deepseek":
        experts, inner, shared = 8, 32, 2
        config = dict(common, model_type="deepseek",
                      architectures=["DeepseekForCausalLM"],
                      intermediate_size=96, moe_intermediate_size=inner,
                      n_routed_experts=experts, n_shared_experts=shared,
                      num_experts_per_tok=3, first_k_dense_replace=1,
                      moe_layer_freq=1, norm_topk_prob=False)
        names = {"w1": "gate_proj", "w2": "down_proj", "w3": "up_proj"}
        for li in range(LAYERS):
            m = f"model.layers.{li}.mlp."
            if li == 0:                              # the leading dense layer
                tensors[m + "gate_proj.weight"] = w(96, HIDDEN)
                tensors[m + "up_proj.weight"] = w(96, HIDDEN)
                tensors[m + "down_proj.weight"] = w(HIDDEN, 96)
                continue
            tensors[m + "gate.weight"] = w(experts, HIDDEN, 0.5)
            for j in range(experts):
                e = f"{m}experts.{j}."
                tensors[e + "gate_proj.weight"] = w(inner, HIDDEN, 0.2)
                tensors[e + "up_proj.weight"] = w(inner, HIDDEN, 0.2)
                tensors[e + "down_proj.weight"] = w(HIDDEN, inner, 0.2)
            s = f"{m}shared_experts."
            tensors[s + "gate_proj.weight"] = w(inner * shared, HIDDEN)
            tensors[s + "up_proj.weight"] = w(inner * shared, HIDDEN)
            tensors[s + "down_proj.weight"] = w(HIDDEN, inner * shared)
        moe_layers = {li: f"model.layers.{li}.mlp.experts."
                      for li in range(1, LAYERS)}
    else:
        experts, inner = 4, 96
        config = dict(common, model_type="mixtral",
                      architectures=["MixtralForCausalLM"],
                      intermediate_size=inner, num_local_experts=experts,
                      num_experts_per_tok=2, sliding_window=None)
        names = {"w1": "w1", "w2": "w2", "w3": "w3"}
        for li in range(LAYERS):
            m = f"model.layers.{li}.block_sparse_moe."
            tensors[m + "gate.weight"] = w(experts, HIDDEN, 0.5)
            for j in range(experts):
                e = f"{m}experts.{j}."
                tensors[e + "w1.weight"] = w(inner, HIDDEN, 0.2)
                tensors[e + "w3.weight"] = w(inner, HIDDEN, 0.2)
                tensors[e + "w2.weight"] = w(HIDDEN, inner, 0.2)
        moe_layers = {li: f"model.layers.{li}.block_sparse_moe.experts."
                      for li in range(LAYERS)}
    return config, tensors, (experts, names, moe_layers)


@pytest.fixture(scope="module", params=["deepseek", "mixtral"])
def moe_checkpoint(request, tmp_path_factory):
    """(arch, model dir, tensors as written, where the experts are)."""
    import safetensors.numpy
    arch = request.param
    d = str(tmp_path_factory.mktemp(f"moe-layout-{arch}"))
    _, vocab_size = _build_word_tokenizer(d)
    config, tensors, experts = _checkpoint(arch, vocab_size)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(config, f)
    safetensors.numpy.save_file(tensors,
                                os.path.join(d, "model.safetensors"))
    return arch, d, tensors, experts


def _greedy(model_dir, prompts, tp=1):
    from intellillm_tpu import LLM, SamplingParams
    llm = LLM(model=model_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=64,
              max_num_seqs=8, swap_space=0.01, tensor_parallel_size=tp)
    outs = llm.generate(prompts, SamplingParams(temperature=0.0,
                                                max_tokens=MAX_TOKENS,
                                                ignore_eos=True))
    return [list(o.outputs[0].token_ids) for o in outs]


@pytest.fixture(scope="module")
def single_chip_tokens(moe_checkpoint):
    from tests.conftest import EXAMPLE_PROMPTS
    return _greedy(moe_checkpoint[1], list(EXAMPLE_PROMPTS))


def test_loader_stacks_gate_and_up_untransposed(moe_checkpoint):
    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.models.model_loader import get_model
    _, d, tensors, (experts, names, moe_layers) = moe_checkpoint
    _, params = get_model(ModelConfig(model=d, dtype="float32"))
    for li, prefix in moe_layers.items():
        layer = params["layers"][li]
        for stack, hf_name in names.items():
            assert np.asarray(layer[stack]).shape[0] == experts
            for j in range(experts):
                stored = tensors[f"{prefix}{j}.{hf_name}.weight"]
                want = stored.T if stack == "w2" else stored   # [I, D]
                np.testing.assert_array_equal(
                    np.asarray(layer[stack][j]), want,
                    err_msg=f"layer {li} {stack}[{j}]")


def test_greedy_tokens_are_the_parents(moe_checkpoint, single_chip_tokens):
    assert single_chip_tokens == PARENT_TOKENS[moe_checkpoint[0]]


def test_tp2_equals_tp1(moe_checkpoint, single_chip_tokens):
    """tp=2 shards the inner dim, axis 1 of w1, w2 and w3."""
    from jax.sharding import PartitionSpec as P

    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.models import get_model_class
    from tests.conftest import EXAMPLE_PROMPTS
    arch, d, _, (_, _, moe_layers) = moe_checkpoint
    mc = ModelConfig(model=d, dtype="float32")
    specs = get_model_class(mc.hf_config.architectures)(mc).partition_specs()
    for li in moe_layers:
        for stack in ("w1", "w2", "w3"):
            assert specs["layers"][li][stack] == P(None, "model", None)
    assert _greedy(d, list(EXAMPLE_PROMPTS), tp=2) == single_chip_tokens


@pytest.mark.parametrize("tp", [1, 2])
def test_a_step_over_256_rows_equals_the_dense_form(moe_checkpoint,
                                                    monkeypatch, tp):
    """Three 100-token prompts under a 512-token budget make one step of
    300 rows, over `layers/moe.py` `DENSE_ROWS`: its expert layers run the
    grouped form (at tp=2 each chip on its half of the inner dimension,
    the partial sums added), and every logprob is the one the dense form
    gives at that many rows (the float32 reference both families have
    here), to 1e-4."""
    from intellillm_tpu import LLM, SamplingParams
    from intellillm_tpu.layers import moe
    _, d, _, _ = moe_checkpoint
    rng = np.random.default_rng(31)
    prompts = [rng.integers(3, 100, size=100).tolist() for _ in range(3)]
    forms = []
    for name in ("dense", "grouped"):
        def spy(*a, name=name, form=getattr(moe, "moe_ffn_" + name), **kw):
            forms.append(name)
            return form(*a, **kw)
        monkeypatch.setattr(moe, "moe_ffn_" + name, spy)

    def logprobs(tp):
        llm = LLM(model=d, dtype="float32", num_device_blocks_override=128,
                  max_model_len=128, max_num_seqs=8, swap_space=0.01,
                  max_num_batched_tokens=512, max_paddings=512,
                  tensor_parallel_size=tp)
        outs = llm.generate(
            prompt_token_ids=prompts,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                           ignore_eos=True, logprobs=5))
        return [{tok: float(getattr(v, "logprob", v))
                 for tok, v in step.items()}
                for o in outs for step in o.outputs[0].logprobs]

    got = logprobs(tp)
    assert "grouped" in forms
    del forms[:]
    monkeypatch.setattr(moe, "DENSE_ROWS", 1 << 30)
    want = logprobs(1)
    assert forms and set(forms) == {"dense"}
    assert len(got) == len(want) == 18
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        assert max(abs(g[t] - w[t]) for t in g) < 1e-4


def _fingerprint(stack_ndi):
    """A position-sensitive float64 sum over an [N, D, I] array."""
    a = np.asarray(stack_ndi, np.float64)
    return float((a * np.cos(np.arange(a.size)).reshape(a.shape)).sum())


# _fingerprint of w1 and w3 of the first MoE layer under
# `load_format="dummy"`, seed 0, at the parent commit of PR 25, where they
# were stored [N, D, I].
PARENT_DUMMY_FINGERPRINTS = {
    "deepseek": (-0.9732559293486069, 1.737100358714612),
    "mixtral": (-0.3211065342661219, 3.5071661820651387),
}


@pytest.mark.parametrize("arch", ["deepseek", "mixtral"])
def test_a_seed_still_gives_the_same_dummy_model(arch):
    """`init_random_params` draws w1/w3 as [N, D, I] and transposes."""
    from transformers import MixtralConfig

    from intellillm_tpu.config import ModelConfig
    from intellillm_tpu.models.model_loader import get_model
    from intellillm_tpu.transformers_utils.configs import DeepseekConfig
    config, _, (experts, _, moe_layers) = _checkpoint(arch, 128)
    for key in ("model_type", "torch_dtype"):
        config.pop(key)
    cls = DeepseekConfig if arch == "deepseek" else MixtralConfig
    mc = ModelConfig.from_hf_config(cls(**config), dtype="float32",
                                    max_model_len=128, load_format="dummy")
    _, params = get_model(mc)
    layer = params["layers"][min(moe_layers)]
    inner = config.get("moe_intermediate_size", config["intermediate_size"])
    assert layer["w1"].shape == layer["w3"].shape == (experts, inner, HIDDEN)
    got = tuple(_fingerprint(np.asarray(layer[k]).swapaxes(1, 2))
                for k in ("w1", "w3"))
    assert got == pytest.approx(PARENT_DUMMY_FINGERPRINTS[arch], rel=1e-9)
