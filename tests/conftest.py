"""Shared test fixtures.

Test strategy parity (SURVEY §4): golden comparison against HuggingFace
transformers (the reference's HfRunner/VllmRunner pattern,
`tests/conftest.py:47-219`), kernel tests vs pure-jnp references, and
CPU-mesh simulation for multi-chip logic (8 virtual devices via
--xla_force_host_platform_device_count; the reference used 2 real GPUs).

Models are built locally (tiny random-weight checkpoints + a word-level
tokenizer) so the suite runs with zero network access.
"""
import os

# The suite runs on the CPU backend with 8 virtual devices (it simulates
# multi-chip on a CPU mesh). The chip is reached with chip_smoke.py, not
# with pytest.
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest
import torch

_VOCAB_WORDS = [
    "the", "a", "an", "of", "to", "and", "in", "is", "was", "it", "for",
    "on", "are", "as", "with", "his", "they", "at", "be", "this", "have",
    "from", "or", "one", "had", "by", "word", "but", "not", "what", "all",
    "were", "we", "when", "your", "can", "said", "there", "use", "each",
    "which", "she", "do", "how", "their", "if", "will", "up", "other",
    "about", "out", "many", "then", "them", "these", "so", "some", "her",
    "would", "make", "like", "him", "into", "time", "has", "look", "two",
    "more", "write", "go", "see", "number", "no", "way", "could", "people",
    "my", "than", "first", "water", "been", "call", "who", "oil", "its",
    "now", "find", "long", "down", "day", "did", "get", "come", "made",
    "may", "part", "president", "united", "states", "capital", "france",
    "paris", "model", "token", "hello", "name", "cat", "dog", "runs",
    "fast", "slow", "big", "small", "red", "blue", "green", "house",
]


def _build_word_tokenizer(save_dir: str):
    """Word-level tokenizer built in-process (no hub access)."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    vocab = {"<pad>": 0, "</s>": 1, "<unk>": 2}
    for w in _VOCAB_WORDS:
        vocab[w] = len(vocab)
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        pad_token="<pad>",
        eos_token="</s>",
        unk_token="<unk>",
    )
    fast.save_pretrained(save_dir)
    return fast, len(vocab)


@pytest.fixture(scope="session")
def tiny_opt_dir(tmp_path_factory):
    """Tiny random OPT checkpoint + word tokenizer saved to disk."""
    from transformers import OPTConfig, OPTForCausalLM

    d = str(tmp_path_factory.mktemp("tiny-opt"))
    _, vocab_size = _build_word_tokenizer(d)
    torch.manual_seed(0)
    config = OPTConfig(
        vocab_size=vocab_size,
        hidden_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        ffn_dim=128,
        max_position_embeddings=128,
        do_layer_norm_before=True,
        pad_token_id=0,
        eos_token_id=1,
        bos_token_id=1,
        word_embed_proj_dim=64,
        torch_dtype=torch.float32,
    )
    model = OPTForCausalLM(config)
    model.eval()
    model.save_pretrained(d, safe_serialization=True)
    return d


@pytest.fixture(scope="session")
def tiny_llama_dir(tmp_path_factory):
    """Tiny random Llama (GQA) checkpoint + word tokenizer."""
    from transformers import LlamaConfig, LlamaForCausalLM

    d = str(tmp_path_factory.mktemp("tiny-llama"))
    _, vocab_size = _build_word_tokenizer(d)
    torch.manual_seed(0)
    config = LlamaConfig(
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        pad_token_id=0,
        eos_token_id=1,
        bos_token_id=1,
        tie_word_embeddings=False,
        torch_dtype=torch.float32,
    )
    model = LlamaForCausalLM(config)
    model.eval()
    model.save_pretrained(d, safe_serialization=True)
    return d


EXAMPLE_PROMPTS = [
    "hello my name is",
    "the president of the united states is",
    "the capital of france is",
    "the cat runs fast and the dog",
]


@pytest.fixture
def force_pallas():
    """`set_use_pallas`, put back to the backend's own choice afterwards.
    Forced on under the CPU backend, a seam still serves a shape its
    kernel does not support (every tiny model's head size) from its
    oracle."""
    from intellillm_tpu.ops.dispatch import set_use_pallas
    yield set_use_pallas
    set_use_pallas(None)


@pytest.fixture
def example_prompts():
    return list(EXAMPLE_PROMPTS)


class HfRunner:
    """Golden-reference generation with HF transformers (reference
    `tests/conftest.py:47-153`)."""

    def __init__(self, model_dir: str, dtype=torch.float32):
        from transformers import AutoModelForCausalLM, AutoTokenizer

        self.model = AutoModelForCausalLM.from_pretrained(
            model_dir, torch_dtype=dtype)
        self.model.eval()
        self.tokenizer = AutoTokenizer.from_pretrained(model_dir)

    def generate_greedy(self, prompts, max_tokens: int):
        outputs = []
        for prompt in prompts:
            input_ids = self.tokenizer(prompt,
                                       return_tensors="pt").input_ids
            with torch.no_grad():
                out = self.model.generate(input_ids,
                                          do_sample=False,
                                          max_new_tokens=max_tokens)
            output_ids = out[0][input_ids.shape[1]:].tolist()
            # Trim anything after (and including) EOS to match engine stop
            # semantics below.
            outputs.append(output_ids)
        return outputs

    def greedy_logits(self, prompt: str):
        input_ids = self.tokenizer(prompt, return_tensors="pt").input_ids
        with torch.no_grad():
            return self.model(input_ids).logits[0].numpy()


@pytest.fixture
def hf_runner():
    return HfRunner
