"""Warm-up precompile smoke test.

`Worker.warm_up_model` (the CUDA-graph-capture analogue, reference
`model_runner.py:629-698`) normally runs only on TPU; here the backend
gate is bypassed so the exact warm-up call sequence — including the
fetch_indices (logits_processors) pytree variant and the fused-K program
— executes on CPU. Regressions in the warm-up argument plumbing
otherwise only surface as a swallowed best-effort warning on real
hardware.

Since the bucket-zoo deletion, default warm-up compiles the mixed
`(token_budget,)` family alone: exactly TWO executables (greedy +
sampled single-step decode at the top token bucket, narrowest width).
Everything else — the per-bucket sweep, fetch_indices pytree variant,
fused-K and pipelined-continuation programs — compiles lazily on first
use unless INTELLILLM_WARMUP_FULL=1.
"""
import jax
import pytest

from intellillm_tpu.config import (CacheConfig, ModelConfig, ParallelConfig,
                                   SchedulerConfig)
from intellillm_tpu.worker.worker import Worker


def _make_worker(num_decode_steps, max_model_len=128,
                 max_num_batched_tokens=2048, enable_chunked_prefill=False,
                 **scheduler_kwargs):
    from transformers import LlamaConfig

    # Smallest config that still exercises GQA: warm-up sweeps compile
    # dozens of executables, so per-compile cost dominates test time.
    hf = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=1, num_attention_heads=4,
                     num_key_value_heads=2,
                     max_position_embeddings=max_model_len,
                     tie_word_embeddings=False)
    model_config = ModelConfig.from_hf_config(hf, dtype="float32",
                                              max_model_len=max_model_len,
                                              load_format="dummy")
    cache_config = CacheConfig(block_size=16,
                               num_device_blocks_override=64,
                               swap_space_gib=0.01)
    cache_config.num_device_blocks = 64
    cache_config.num_cpu_blocks = 4
    scheduler_config = SchedulerConfig(
        max_num_batched_tokens=max_num_batched_tokens,
        max_num_seqs=8,
        max_model_len=max_model_len,
        max_paddings=512,
        num_decode_steps=num_decode_steps,
        enable_chunked_prefill=enable_chunked_prefill, **scheduler_kwargs)
    worker = Worker(model_config, ParallelConfig(), scheduler_config,
                    cache_config)
    worker.init_model()
    worker.load_model()
    worker.init_cache_engine(cache_config)
    return worker


@pytest.mark.parametrize("num_decode_steps", [1, 4])
def test_warm_up_default_is_two_mixed_executables(monkeypatch,
                                                  num_decode_steps):
    """Default warm-up compiles exactly the two steady-state sampler
    variants (greedy + sampled) of the mixed single-step program —
    regardless of --num-decode-steps (fused/continuation compile
    lazily). This is the <30s boot criterion's executable count."""
    worker = _make_worker(num_decode_steps)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = worker.warm_up_model()
    # None means the best-effort except path fired — in this controlled
    # environment that's a broken call sequence, not a hardware limit.
    assert n is not None, "warm-up fell back to lazy compilation"
    assert n == 2
    # Structured stats must agree with the return value (they feed the
    # boot timeline -> /health/detail -> bench warmup_compile field).
    assert worker.warmup_stats["executables"] == 2
    assert worker.warmup_stats["seconds"] > 0.0
    assert "error" not in worker.warmup_stats


@pytest.mark.parametrize("num_decode_steps", [1, 4])
def test_warm_up_count_invariant_under_kernel_flags(monkeypatch,
                                                    force_pallas,
                                                    num_decode_steps):
    """Selecting the Pallas kernels must not change the default warm-up:
    the selection picks a code path at trace time INSIDE the two mixed
    executables, so the count stays exactly 2 and no extra program
    appears. (On this tiny model head size is 16, so the attention
    seam falls back to the reference body — which is precisely the
    invariance being pinned: the selection must not leak into
    bucketing.) The stats must also carry the trace-time
    kernel_selection snapshot that /health/detail and bench read."""
    force_pallas(True)
    worker = _make_worker(num_decode_steps)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = worker.warm_up_model()
    assert n is not None, "warm-up fell back to lazy compilation"
    assert n == 2
    assert worker.warmup_stats["executables"] == 2
    sel = worker.warmup_stats["kernel_selection"]
    assert sel["ragged"] is True
    assert sel["bgmv"] is True


@pytest.mark.parametrize("asked, widths", [
    (16, {1: 16, 17: 32, 40: 64, 64: 64}),     # the default: every bucket
    (40, {1: 64, 17: 64, 40: 64, 64: 64}),     # made up to a bucket
    (4096, {1: 64, 17: 64, 40: 64, 64: 64}),   # never past the widest
])
def test_min_block_table_width_is_the_narrowest_width(monkeypatch, asked,
                                                      widths):
    """--min-block-table-width: no step's block tables are padded to less,
    so a deployment of long prompts compiles one width in place of one a
    bucket; the boot's warm-up starts from it."""
    worker = _make_worker(4, max_model_len=1024, max_num_batched_tokens=64,
                          enable_chunked_prefill=True,
                          min_block_table_width=asked)
    runner = worker.model_runner
    assert runner.max_table_width == 64
    assert {n: runner.table_width(n) for n in widths} == widths
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    warmed, jitted = set(), runner._jit_decode_single

    def spy(params, caches, tokens, positions, block_tables, *a, **kw):
        warmed.add(block_tables.shape[1])
        return jitted(params, caches, tokens, positions, block_tables,
                      *a, **kw)

    runner._jit_decode_single = spy
    assert worker.warm_up_model() == 2
    assert warmed == {widths[1]}


def test_min_block_table_width_is_checked():
    with pytest.raises(ValueError, match="min_block_table_width"):
        SchedulerConfig(max_num_seqs=8, max_model_len=128,
                        min_block_table_width=0)


def test_warm_up_skipped_on_cpu():
    worker = _make_worker(1)
    assert worker.warm_up_model() is None
    assert worker.warmup_stats == {"executables": 0, "seconds": 0.0}


def test_warm_up_full_covers_every_token_bucket(monkeypatch):
    """INTELLILLM_WARMUP_FULL=1 sweeps every token bucket up to the
    budget plus the two narrowest widths, both sampler variants, the
    fetch_indices pytree variant, and the fused(+continuation) K-step
    programs — so nothing of the mixed family is left to compile
    mid-serving."""
    worker = _make_worker(num_decode_steps=4, max_model_len=128,
                          max_num_batched_tokens=64,
                          enable_chunked_prefill=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("INTELLILLM_WARMUP_FULL", "1")
    n = worker.warm_up_model()
    assert n is not None
    from intellillm_tpu.utils import pad_to_bucket, pipeline_enabled_env
    buckets = worker.model_runner.mixed_token_buckets
    top = pad_to_bucket(64, buckets)
    batch_sizes = [bb for bb in buckets if bb <= top]
    assert len(batch_sizes) > 1   # full mode must sweep, not just top
    n_widths = len(buckets[:2])
    # Per (bucket, width, sampler-variant): single-step + fused +
    # (continuation when pipelining is enabled); plus ONE fetch_indices
    # variant (top bucket, narrowest width, greedy).
    per_combo = 3 if pipeline_enabled_env() else 2
    assert n == len(batch_sizes) * n_widths * 2 * per_combo + 1
    assert worker.warmup_stats["executables"] == n


def test_spec_worker_warmup_covers_teacher_and_draft(monkeypatch):
    """Speculative serving warm-up must compile the target's mixed pair,
    the draft model's mixed pair, and the K-ladder — for a fixed K
    (k_min == k_max) that is one teacher program plus one draft fused
    scan, six executables total in warmup_stats."""
    from transformers import LlamaConfig

    from intellillm_tpu.config import SpeculativeConfig
    from intellillm_tpu.worker.spec_decode.spec_worker import (
        SpecDecodeWorker)

    def mc(hidden, inter, layers):
        hf = LlamaConfig(vocab_size=128, hidden_size=hidden,
                         intermediate_size=inter, num_hidden_layers=layers,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=128,
                         tie_word_embeddings=False)
        return ModelConfig.from_hf_config(hf, dtype="float32",
                                          max_model_len=128,
                                          load_format="dummy")

    cache_config = CacheConfig(block_size=16,
                               num_device_blocks_override=64,
                               swap_space_gib=0.01)
    cache_config.num_device_blocks = 64
    cache_config.num_cpu_blocks = 4
    k_spec = 3
    scheduler_config = SchedulerConfig(max_num_batched_tokens=2048,
                                       max_num_seqs=8, max_model_len=128,
                                       max_paddings=512,
                                       num_decode_steps=k_spec + 1)
    spec = SpeculativeConfig(mc(32, 64, 1), k_spec)
    worker = SpecDecodeWorker(mc(32, 64, 1), ParallelConfig(),
                              scheduler_config, cache_config,
                              speculative_config=spec)
    worker.init_model()
    worker.load_model()
    worker.init_cache_engine(cache_config)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = worker.warm_up_model()
    assert n is not None, "spec warm-up fell back to lazy compilation"
    # 2 target mixed variants + 2 draft mixed variants + the K-ladder
    # (1 teacher + 1 draft fused per K rung; fixed K = one rung).
    assert n == 6
    assert worker.warmup_stats["executables"] == 6
    assert worker.warmup_stats["seconds"] > 0.0


def test_spec_worker_warmup_ladder_scales_with_band(monkeypatch):
    """An adaptive band [k_min, k_max] warms every rung: 4 generic mixed
    variants + 2 executables per K in the band, so no K transition can
    hit a cold compile mid-serving."""
    from transformers import LlamaConfig

    from intellillm_tpu.config import SpeculativeConfig
    from intellillm_tpu.worker.spec_decode.spec_worker import (
        SpecDecodeWorker)

    def mc(hidden, inter, layers):
        hf = LlamaConfig(vocab_size=128, hidden_size=hidden,
                         intermediate_size=inter, num_hidden_layers=layers,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=128,
                         tie_word_embeddings=False)
        return ModelConfig.from_hf_config(hf, dtype="float32",
                                          max_model_len=128,
                                          load_format="dummy")

    cache_config = CacheConfig(block_size=16,
                               num_device_blocks_override=64,
                               swap_space_gib=0.01)
    cache_config.num_device_blocks = 64
    cache_config.num_cpu_blocks = 4
    k_min, k_max = 2, 4
    scheduler_config = SchedulerConfig(max_num_batched_tokens=2048,
                                       max_num_seqs=8, max_model_len=128,
                                       max_paddings=512,
                                       num_decode_steps=k_max + 1)
    spec = SpeculativeConfig(mc(32, 64, 1), k_max, k_min=k_min,
                             k_max=k_max)
    worker = SpecDecodeWorker(mc(32, 64, 1), ParallelConfig(),
                              scheduler_config, cache_config,
                              speculative_config=spec)
    worker.init_model()
    worker.load_model()
    worker.init_cache_engine(cache_config)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = worker.warm_up_model()
    assert n is not None
    rungs = k_max - k_min + 1
    assert n == 4 + 2 * rungs
    assert worker.warmup_stats["executables"] == n


def test_join_gather_compiles_only_where_a_step_program_does(
        tiny_llama_dir):
    """The two small programs that hand a continuation the first tokens
    of a prompt step in flight are one executable a prompt step's packed
    shape and one a decode batch. Each makes its first call with the
    first call of the step program it follows (`mixed` ahead of its
    fetch, `decode_cont`): a prompt step of a size seen before joins a
    batch seen before without a compile, however late the pair comes."""
    from intellillm_tpu import LLM, SamplingParams
    from intellillm_tpu.obs import get_compile_tracker
    tracker = get_compile_tracker()
    tracker.reset_for_testing()
    llm = LLM(model=tiny_llama_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01,
              num_decode_steps=8)
    engine = llm.llm_engine
    runner = engine.worker.model_runner
    sp = SamplingParams(temperature=0.0, max_tokens=60, ignore_eos=True)

    def compiles():
        return dict(tracker.snapshot()["compiles"])

    # Prompt steps of 16, 32 and 64 rows; the 32-row one comes first
    # (alone, before any decode batch) and again last, as a joiner.
    lens = {0: 20, 1: 5, 2: 40, 3: 24}
    arrivals = {0: [0], 3: [1], 6: [2], 9: [3]}
    calls = 0
    while (engine.has_unfinished_requests() or engine.has_inflight()
           or calls < 10):
        for i in arrivals.get(calls, ()):
            engine.add_request(str(i), None, sp,
                               prompt_token_ids=[3 + i] * lens[i])
        before = compiles()
        engine.step_pipelined()
        after = compiles()
        if after.get("join_gather", 0) > before.get("join_gather", 0):
            assert any(after.get(p, 0) > before.get(p, 0)
                       for p in ("mixed", "decode_cont")), (before, after)
        if calls == 8:
            warmed = after.get("join_gather", 0)
        calls += 1
        assert calls < 400
    assert runner._join_batches == {16}
    # Three packed shapes and one decode batch: a sum, not a product.
    assert compiles()["join_gather"] == warmed == 3 + 1
    from intellillm_tpu.obs import get_efficiency_tracker
    assert get_efficiency_tracker().snapshot()[
        "decode_join_rows"]["device"] >= 3


@pytest.mark.parametrize("num_decode_steps,rows,cold", [
    (4, 40, 0),     # a prompt step beside the K-step decode programs
    (1, 40, 1),     # K = 1 warms the head over every row (decode steps)
    (4, 64, 0),     # mid-prompt: no row emits, the same executable
])
def test_warm_up_covers_the_form_a_prompt_step_takes(monkeypatch,
                                                     num_decode_steps, rows,
                                                     cold):
    """With the K-step decode programs a `mixed` step of the top bucket
    is a prompt step, whose head runs on its emitting rows
    (`_decode_fn_single` `emit_rows`): warm-up compiles that form, and
    the first such step of serving finds it."""
    from intellillm_tpu.sampling_params import SamplingParams
    from intellillm_tpu.sequence import SequenceData, SequenceGroupMetadata

    worker = _make_worker(num_decode_steps, max_num_batched_tokens=64,
                          enable_chunked_prefill=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert worker.warm_up_model() == 2
    program = worker.model_runner._jit_decode_single
    assert program._cache_size() == 2
    prompt = list(range(3, 3 + 100))
    data = SequenceData(prompt)
    meta = SequenceGroupMetadata(
        request_id="0", is_prompt=True, seq_data={0: data},
        sampling_params=SamplingParams(temperature=0.0, max_tokens=4),
        block_tables={0: list(range(7))},
        token_chunk_size=rows, num_computed_tokens=100 - rows
        if rows < 64 else 0)
    out = worker.execute_model([meta], {}, {}, {})
    assert len(out) == 1
    assert program._cache_size() == 2 + cold
