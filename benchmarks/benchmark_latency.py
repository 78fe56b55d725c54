"""End-to-end latency of one fixed-size batch.

Role parity: reference `benchmarks/benchmark_latency.py` (same CLI
surface: --input-len/--output-len/--batch-size/--num-iters, profile
option). Runs a single `LLM.generate` over batch_size identical-length
prompts per iteration and reports the mean/percentile wall time.

Usage:
    python benchmarks/benchmark_latency.py --model dummy:7b \
        --input-len 32 --output-len 128 --batch-size 8
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import build_llm, is_dummy, percentiles  # noqa: E402


def main(args):
    from intellillm_tpu.sampling_params import SamplingParams

    llm = build_llm(args)
    engine = llm.llm_engine
    vocab = engine.model_config.get_vocab_size()
    rng = np.random.default_rng(args.seed)

    sampling_params = SamplingParams(
        n=args.n,
        temperature=0.0 if args.use_beam_search else 1.0,
        top_p=1.0,
        use_beam_search=args.use_beam_search,
        ignore_eos=True,
        max_tokens=args.output_len,
    )
    prompt_ids = [
        rng.integers(0, vocab, size=args.input_len).tolist()
        for _ in range(args.batch_size)
    ]

    def run():
        start = time.perf_counter()
        llm.generate(prompt_token_ids=prompt_ids,
                     sampling_params=sampling_params)
        return time.perf_counter() - start

    print("Warming up...")
    for _ in range(args.num_iters_warmup):
        run()

    if args.profile:
        import jax
        jax.profiler.start_trace(args.profile_result_dir)

    latencies = [run() for _ in range(args.num_iters)]

    if args.profile:
        import jax
        jax.profiler.stop_trace()
        print(f"Profile saved to {args.profile_result_dir}")

    stats = percentiles(latencies, (50, 90, 99))
    print(f"Avg latency: {np.mean(latencies):.4f} s")
    for k, v in stats.items():
        print(f"{k} latency: {v:.4f} s")
    tok_s = args.batch_size * args.output_len / np.mean(latencies)
    print(f"Throughput: {tok_s:.1f} output tok/s")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="Benchmark the latency of processing a single batch "
        "of requests till completion.")
    parser.add_argument("--model", type=str, default="dummy:7b")
    parser.add_argument("--tokenizer", type=str, default=None)
    parser.add_argument("--quantization", "-q", type=str, default=None)
    parser.add_argument("--tensor-parallel-size", "-tp", type=int, default=1)
    parser.add_argument("--input-len", type=int, default=32)
    parser.add_argument("--output-len", type=int, default=128)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--n", type=int, default=1)
    parser.add_argument("--use-beam-search", action="store_true")
    parser.add_argument("--num-iters-warmup", type=int, default=2)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--dtype", type=str, default="auto")
    parser.add_argument("--max-model-len", type=int, default=None)
    parser.add_argument("--max-num-seqs", type=int, default=256)
    parser.add_argument("--num-device-blocks", type=int, default=None)
    parser.add_argument("--kv-cache-dtype", type=str, default="auto")
    parser.add_argument("--enforce-eager", action="store_true")
    parser.add_argument("--trust-remote-code", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--profile", action="store_true",
                        help="capture a jax.profiler trace around the "
                        "timed iterations")
    parser.add_argument("--profile-result-dir", type=str,
                        default="/tmp/intellillm-latency-profile")
    main(parser.parse_args())
