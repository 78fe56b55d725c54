"""Offline generation throughput.

Role parity: reference `benchmarks/benchmark_throughput.py` (ShareGPT or
synthetic workload, requests/s + tokens/s, optional HF baseline backend).

Usage:
    python benchmarks/benchmark_throughput.py --model dummy:7b \
        --num-prompts 64 --input-len 128 --output-len 128
    python benchmarks/benchmark_throughput.py --model /path/llama \
        --dataset /path/sharegpt.json --num-prompts 200
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import (build_llm, is_dummy,  # noqa: E402
                               sample_requests)


def run_intellillm(args, requests):
    from intellillm_tpu.sampling_params import SamplingParams

    llm = build_llm(args)
    engine = llm.llm_engine
    for i, (prompt_ids, output_len) in enumerate(requests):
        sampling_params = SamplingParams(
            n=args.n,
            temperature=0.0 if args.use_beam_search else 1.0,
            top_p=1.0,
            use_beam_search=args.use_beam_search,
            ignore_eos=True,
            max_tokens=output_len,
        )
        engine.add_request(str(i), None, sampling_params,
                           prompt_token_ids=prompt_ids)
    start = time.perf_counter()
    llm._run_engine(use_tqdm=not args.no_tqdm)
    return time.perf_counter() - start


def run_hf(args, requests):
    """HF transformers greedy loop (reference run_hf role) — baseline for
    small models on CPU/TPU-host."""
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(args.model)
    model.eval()
    start = time.perf_counter()
    for prompt_ids, output_len in requests:
        input_ids = torch.tensor([prompt_ids])
        with torch.no_grad():
            model.generate(input_ids, do_sample=False,
                           min_new_tokens=output_len,
                           max_new_tokens=output_len)
    return time.perf_counter() - start


def main(args):
    tokenizer = None
    vocab_size = 32000
    if is_dummy(args.model):
        from benchmarks.common import dummy_hf_config
        vocab_size = dummy_hf_config(args.model).vocab_size
        assert args.dataset is None, "--dataset needs a real tokenizer"
    else:
        from transformers import AutoTokenizer
        tokenizer = AutoTokenizer.from_pretrained(args.model)
        vocab_size = len(tokenizer)

    requests = sample_requests(args.dataset, args.num_prompts, tokenizer,
                               args.input_len, args.output_len, vocab_size,
                               args.seed)
    if args.backend == "intellillm":
        elapsed = run_intellillm(args, requests)
    else:
        elapsed = run_hf(args, requests)

    total_tokens = sum(len(p) + o for p, o in requests)
    out_tokens = sum(o for _, o in requests)
    print(f"Throughput: {len(requests) / elapsed:.2f} requests/s, "
          f"{total_tokens / elapsed:.1f} total tok/s, "
          f"{out_tokens / elapsed:.1f} output tok/s")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="Benchmark throughput.")
    parser.add_argument("--backend", type=str, default="intellillm",
                        choices=["intellillm", "hf"])
    parser.add_argument("--model", type=str, default="dummy:7b")
    parser.add_argument("--tokenizer", type=str, default=None)
    parser.add_argument("--dataset", type=str, default=None,
                        help="ShareGPT-format json; synthetic when absent")
    parser.add_argument("--num-prompts", type=int, default=64)
    parser.add_argument("--input-len", type=int, default=128)
    parser.add_argument("--output-len", type=int, default=128)
    parser.add_argument("--n", type=int, default=1)
    parser.add_argument("--use-beam-search", action="store_true")
    parser.add_argument("--quantization", "-q", type=str, default=None)
    parser.add_argument("--tensor-parallel-size", "-tp", type=int, default=1)
    parser.add_argument("--dtype", type=str, default="auto")
    parser.add_argument("--max-model-len", type=int, default=None)
    parser.add_argument("--max-num-seqs", type=int, default=256)
    parser.add_argument("--num-device-blocks", type=int, default=None)
    parser.add_argument("--kv-cache-dtype", type=str, default="auto")
    parser.add_argument("--enforce-eager", action="store_true")
    parser.add_argument("--trust-remote-code", action="store_true")
    parser.add_argument("--no-tqdm", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    main(parser.parse_args())
