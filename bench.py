"""Offline decode throughput on a Llama-2-7B-shaped model, on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}. Every record names the
device it ran on. Without a TPU this exits non-zero with one error line;
a CPU run (a rehearsal of the control flow, never a measurement) happens
only when `JAX_PLATFORMS=cpu` asks for it.

Baseline (BASELINE.json): Llama-2-7B serving on v5e-8 at >= 2000 output
tok/s aggregate == 250 output tok/s per chip. This harness measures
single-chip offline generation throughput (the role of the reference's
offline throughput benchmark) with dummy (random)
weights — throughput is weight-value-independent.

Env knobs: INTELLILLM_BENCH_SIZE=7b|1b|tiny|moe (default 7b),
           INTELLILLM_BENCH_BS (default: 96 for 7b+fp8-KV, else 32),
           INTELLILLM_BENCH_IN (128), INTELLILLM_BENCH_OUT (128),
           INTELLILLM_BENCH_K (fused decode steps, default 128),
           INTELLILLM_BENCH_KV (cache dtype, default fp8_e5m2 for 7b),
           INTELLILLM_BENCH_QUANT (default int8 for 7b),
           INTELLILLM_BENCH_BLOCKS (KV pool size override, in blocks),
           INTELLILLM_BENCH_BLOCK_SIZE (tokens per KV block, default 16),
           INTELLILLM_BENCH_MML (max_model_len, default 512 — raise for
           long-context operating points, e.g. 2048 with IN=1024).
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

BASELINE_TOK_S_PER_CHIP = 250.0


def require_device() -> dict:
    """The device this process measures on, as JAX reports it. A chip
    belongs to one process, so the question is asked here, in-process.
    No TPU is an error unless a CPU run was asked for by name."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform != "tpu" and not explicit_cpu:
        print(f"bench.py: no TPU: JAX found platform {dev.platform!r} "
              "(JAX_PLATFORMS=cpu runs a CPU rehearsal on purpose)",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(devices)}


SIZES = {
    # (hidden, inter, layers, heads, kv_heads, vocab)
    "7b": (4096, 11008, 32, 32, 32, 32000),
    "1b": (2048, 5632, 22, 32, 4, 32000),
    "tiny": (256, 512, 2, 8, 8, 1024),
    # "moe": Mixtral-architecture (8 experts, top-2) scaled to one v5e
    # chip: ~3.4B params -> 3.4 GiB int8 (the real 8x7B needs TP=8, which
    # this environment's single chip cannot host).
    "moe": (2048, 4096, 16, 32, 8, 32000),
}


def build_engine(size: str, max_num_seqs: int, max_model_len: int,
                 num_blocks: int, quantization=None, cache_dtype="auto"):
    from transformers import LlamaConfig, MixtralConfig

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig,
                                       SpeculativeConfig)
    from intellillm_tpu.engine.llm_engine import LLMEngine

    hidden, inter, layers, heads, kv_heads, vocab = SIZES[size]
    if size == "moe":
        hf_config = MixtralConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, max_position_embeddings=4096,
            num_local_experts=8, num_experts_per_tok=2,
            tie_word_embeddings=False)
    else:
        hf_config = LlamaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, max_position_embeddings=4096,
            tie_word_embeddings=False)
    model_config = ModelConfig.from_hf_config(
        hf_config, dtype="bfloat16", max_model_len=max_model_len,
        load_format="dummy", quantization=quantization)
    cache_config = CacheConfig(block_size=int(
                                   os.environ.get(
                                       "INTELLILLM_BENCH_BLOCK_SIZE",
                                       "16")),
                               num_device_blocks_override=num_blocks,
                               swap_space_gib=0.05,
                               cache_dtype=cache_dtype)
    scheduler_config = SchedulerConfig(
        max_num_batched_tokens=max(2048, max_model_len),
        max_num_seqs=max_num_seqs, max_model_len=max_model_len,
        max_paddings=4096,
        # K fused decode steps per dispatch: one device→host fetch per
        # K tokens/seq.
        num_decode_steps=int(os.environ.get("INTELLILLM_BENCH_K", "128")))
    # Speculative mode (INTELLILLM_BENCH_SPEC): a dummy draft model of
    # the named size proposes K tokens per round.
    speculative_config = None
    spec_size = os.environ.get("INTELLILLM_BENCH_SPEC", "").strip()
    if spec_size:
        dh, di, dl, dhe, dkv, dv = SIZES[spec_size]
        assert dv == vocab, "draft vocab must match target"
        draft_hf = LlamaConfig(
            vocab_size=dv, hidden_size=dh, intermediate_size=di,
            num_hidden_layers=dl, num_attention_heads=dhe,
            num_key_value_heads=dkv, max_position_embeddings=4096,
            tie_word_embeddings=False)
        draft_mc = ModelConfig.from_hf_config(
            draft_hf, dtype="bfloat16", max_model_len=max_model_len,
            load_format="dummy")
        spec_k = int(os.environ.get("INTELLILLM_BENCH_SPEC_K", "4"))
        # Optional adaptive band (INTELLILLM_BENCH_SPEC_K_MIN / _K_MAX):
        # warm the whole K-ladder and let the controller move inside it.
        speculative_config = SpeculativeConfig(
            draft_mc, spec_k,
            k_min=int(os.environ.get("INTELLILLM_BENCH_SPEC_K_MIN",
                                     spec_k)),
            k_max=int(os.environ.get("INTELLILLM_BENCH_SPEC_K_MAX",
                                     spec_k)))
    return LLMEngine(model_config, cache_config, ParallelConfig(),
                     scheduler_config,
                     speculative_config=speculative_config,
                     log_stats=False, skip_tokenizer_init=True)


def run(engine, batch_size: int, input_len: int, output_len: int,
        vocab: int):
    from intellillm_tpu.sampling_params import SamplingParams

    rng = np.random.default_rng(0)
    for i in range(batch_size):
        engine.add_request(
            request_id=f"bench-{time.monotonic_ns()}-{i}",
            prompt=None,
            sampling_params=SamplingParams(temperature=0.0,
                                           max_tokens=output_len,
                                           ignore_eos=True),
            prompt_token_ids=rng.integers(0, vocab, input_len).tolist(),
        )
    out_tokens = 0
    pipelined = engine.pipeline_enabled
    start = time.perf_counter()
    while engine.has_unfinished_requests() or engine.has_inflight():
        ros = engine.step_pipelined() if pipelined else engine.step()
        for ro in ros:
            if ro.finished:
                out_tokens += sum(len(c.token_ids) for c in ro.outputs)
    elapsed = time.perf_counter() - start
    return out_tokens, elapsed


def main():
    size = os.environ.get("INTELLILLM_BENCH_SIZE", "7b")
    # 7B bf16 weights are 13.5 GiB of the 16 GiB v5e chip — they only fit
    # with int8 weight quantization (6.7 GiB), which also frees HBM for a
    # real KV pool / batch. One 7B KV block (16 tokens) is 8 MiB.
    quant = os.environ.get("INTELLILLM_BENCH_QUANT",
                           "int8" if size in ("7b", "moe") else "none")
    quant = None if quant in ("none", "") else quant
    # fp8 KV halves cache HBM vs bf16; with it the 7B config holds a
    # 1600-block pool and a bs=96 decode batch on one 16 GiB chip.
    kv_dtype = os.environ.get("INTELLILLM_BENCH_KV",
                              "fp8_e5m2" if size == "7b" else "auto")
    # bs=96 only fits with the fp8 pool; bf16 KV keeps the bs=32/512-block
    # configuration (bs=64 there would thrash the pool with preemptions).
    bs_7b = 96 if kv_dtype.startswith("fp8") else 32
    default_bs = {"7b": bs_7b, "1b": 32, "tiny": 64, "moe": 64}[size]
    batch_size = int(os.environ.get("INTELLILLM_BENCH_BS", default_bs))
    input_len = int(os.environ.get("INTELLILLM_BENCH_IN", "128"))
    output_len = int(os.environ.get("INTELLILLM_BENCH_OUT", "128"))
    max_model_len = int(os.environ.get("INTELLILLM_BENCH_MML", "512"))
    num_blocks = {"7b": 1600 if kv_dtype.startswith("fp8") else 512,
                  "1b": 2048, "tiny": 4096, "moe": 2048}[size]
    num_blocks = int(os.environ.get("INTELLILLM_BENCH_BLOCKS", num_blocks))
    vocab = SIZES[size][5]

    device = require_device()
    engine = build_engine(size, batch_size, max_model_len, num_blocks,
                          quantization=quant, cache_dtype=kv_dtype)

    # A SIGTERM should flush the flight recorder before dying.
    from intellillm_tpu.obs.trace_export import install_black_box_handlers
    install_black_box_handlers((signal.SIGTERM,))

    # Warmup: compile prefill+decode buckets on a short run. When the
    # measured run will chain pipelined continuations (out > K), the
    # warmup must run K+2 tokens so the continuation executable compiles
    # HERE, not inside the measurement.
    k_steps = int(os.environ.get("INTELLILLM_BENCH_K", "128"))
    warm_out = (k_steps + 2 if engine.pipeline_enabled
                and output_len > k_steps else 4)
    run(engine, batch_size, input_len, warm_out, vocab)

    out_tokens, elapsed = run(engine, batch_size, input_len, output_len,
                              vocab)
    tok_s = out_tokens / elapsed
    family = "mixtral" if size == "moe" else "llama2"
    rec = {
        "metric": f"{family}-{size}-dummy offline output tok/s/chip "
                  f"(bs={batch_size}, in={input_len}, out={output_len}, "
                  f"mml={max_model_len}, greedy, "
                  f"{'int8-w' if quant else 'bf16'}, kv={kv_dtype})",
        "value": round(tok_s, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s / BASELINE_TOK_S_PER_CHIP, 3),
        **device,
    }
    # Per-kernel cost ledger (obs/kernels.py): static cost_analysis
    # FLOPs/bytes per executable.
    from intellillm_tpu.obs import get_kernel_ledger
    rec["kernels"] = get_kernel_ledger().snapshot(top=8)
    warmup = getattr(engine.worker, "warmup_stats", None)
    if warmup is not None:
        rec["warmup_compile"] = warmup
    wdiff = _wdiff_vs_baseline(rec)
    if wdiff is not None:
        rec["wdiff"] = wdiff
    print(json.dumps(rec))


def _wdiff_vs_baseline(rec: dict):
    """Sectioned diff against an explicit baseline snapshot, when the
    operator points INTELLILLM_WDIFF_BASELINE at one (a --summary-out
    file or a prior bench record): covers the kernel ledger and any
    other shared sections via obs/diff.py."""
    path = os.environ.get("INTELLILLM_WDIFF_BASELINE")
    if not path:
        return None
    from intellillm_tpu.obs.diff import diff_summaries, load_summary
    report = diff_summaries(load_summary(path), rec)
    return {"baseline": path, "verdict": report["verdict"],
            "regressed_sections": report["regressed_sections"]}


if __name__ == "__main__":
    main()
