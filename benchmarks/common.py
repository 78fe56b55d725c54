"""Shared helpers for the benchmark harnesses.

Role parity: the request sampling and percentile surface of the reference
`benchmarks/benchmark_serving.py`. The TPU twist: a `dummy:7b` style spec
names a Llama-shaped model with random weights, materialized as a
checkpoint dir the servers boot with `--load-format dummy` (no checkpoint
downloads in the TPU environment; throughput is weight-value-independent).
"""
from __future__ import annotations

import json
import random
from typing import List, Optional, Tuple

import numpy as np

# (hidden, inter, layers, heads, kv_heads, vocab)
DUMMY_SIZES = {
    "7b": (4096, 11008, 32, 32, 32, 32000),
    "13b": (5120, 13824, 40, 40, 40, 32000),
    "1b": (2048, 5632, 22, 32, 4, 32000),
    "tiny": (256, 512, 2, 8, 8, 1024),
}


def dummy_hf_config(model: str):
    from transformers import LlamaConfig
    size = model.split(":", 1)[1]
    hidden, inter, layers, heads, kv_heads, vocab = DUMMY_SIZES[size]
    return LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=4096,
        tie_word_embeddings=False)


def save_dummy_checkpoint(model_spec, out_dir: str,
                          tokenizer_vocab: Optional[int] = None) -> str:
    """Materialize a `dummy:SIZE` spec (or an HF config object) as an
    on-disk checkpoint dir the servers can boot with `--load-format
    dummy`: the config.json plus a self-contained word-level tokenizer
    (no hub access; decode → encode roundtrips exactly, so client- and
    server-side token counts agree in `benchmark_serving.py`)."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    cfg = (dummy_hf_config(model_spec) if isinstance(model_spec, str)
           else model_spec)
    cfg.save_pretrained(out_dir)
    # Cover the full model vocab so detokenizing dummy-weight samples
    # (uniform over vocab_size ids) never hits an out-of-range token.
    if tokenizer_vocab is None:
        tokenizer_vocab = cfg.vocab_size
    vocab = {"<pad>": 0, "</s>": 1, "<unk>": 2}
    for i in range(tokenizer_vocab - len(vocab)):
        vocab[f"w{i:05d}"] = len(vocab)
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", eos_token="</s>",
        unk_token="<unk>").save_pretrained(out_dir)
    return out_dir


def sample_requests(
    dataset_path: Optional[str],
    num_prompts: int,
    tokenizer,
    input_len: int,
    output_len: int,
    vocab_size: int,
    seed: int = 0,
) -> List[Tuple[List[int], int]]:
    """(prompt_token_ids, output_len) pairs: from a ShareGPT-format json
    when given (the reference's sample_requests role),
    else synthetic random-token prompts."""
    rng = np.random.default_rng(seed)
    if dataset_path is None:
        return [
            (rng.integers(0, vocab_size, size=input_len).tolist(),
             output_len) for _ in range(num_prompts)
        ]

    with open(dataset_path) as f:
        dataset = json.load(f)
    # ShareGPT: take the first two turns (prompt, completion).
    dataset = [d for d in dataset if len(d.get("conversations", [])) >= 2]
    random.Random(seed).shuffle(dataset)
    requests: List[Tuple[List[int], int]] = []
    for d in dataset:
        prompt = d["conversations"][0]["value"]
        completion = d["conversations"][1]["value"]
        prompt_ids = tokenizer.encode(prompt)
        completion_len = len(tokenizer.encode(completion))
        if len(prompt_ids) < 4 or completion_len < 4:
            continue
        if len(prompt_ids) > 1024 or len(prompt_ids) + completion_len > 2048:
            continue
        requests.append((prompt_ids, completion_len))
        if len(requests) == num_prompts:
            break
    return requests


def percentiles(values: List[float], ps=(50, 90, 99)) -> dict:
    if not values:
        return {f"p{p}": float("nan") for p in ps}
    arr = np.asarray(values)
    return {f"p{p}": float(np.percentile(arr, p)) for p in ps}
