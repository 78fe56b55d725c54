"""Chunked-prefill end-to-end acceptance on the CPU backend.

The headline property: turning --enable-chunked-prefill on (with a
budget small enough to force real chunk splits and mixed steps) must
not change a single greedy token versus the legacy homogeneous
scheduler for the same requests. Plus the legacy-mode guard: a mixed
metadata list WITHOUT chunk metadata must be rejected loudly instead of
silently batching under the first entry's phase.
"""
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.sequence import SequenceData, SequenceGroupMetadata

PROMPTS = [
    "hello my name is",
    "the president of the united states is",
    "the capital of france is",
    "the cat runs fast and the dog",
    " ".join(["the cat runs fast and the dog"] * 5),  # 35 tokens
]


def _generate(llm, prompts, params_list):
    engine = llm.llm_engine
    for i, (p, sp) in enumerate(zip(prompts, params_list)):
        engine.add_request(str(i), p, sp)
    outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
    return [outs[str(i)] for i in range(len(prompts))]


def _llm(model_dir, **kw):
    return LLM(model=model_dir, dtype="float32",
               num_device_blocks_override=128, max_model_len=128,
               max_num_seqs=8, max_paddings=512, **kw)


def test_chunked_on_matches_legacy_greedy(tiny_opt_dir):
    """Same requests, chunked on vs off: greedy outputs must be
    identical token for token. The tiny budget (8) forces multi-step
    chunk splits AND steps that mix decode rows with prefill chunks."""
    params = [SamplingParams(temperature=0.0, max_tokens=16,
                             ignore_eos=True) for _ in PROMPTS]

    legacy = _generate(_llm(tiny_opt_dir), PROMPTS, params)

    from intellillm_tpu.core import scheduler as sched_mod
    mixed_steps = {"n": 0, "split": 0}
    orig = sched_mod.Scheduler._chunked_pass

    def spy(self, now, **kw):
        out = orig(self, now, **kw)
        mixed_steps["n"] += 1
        if any(start > 0 for start, _, _ in out.chunked_prefills.values()):
            mixed_steps["split"] += 1
        return out

    sched_mod.Scheduler._chunked_pass = spy
    try:
        chunked = _generate(
            _llm(tiny_opt_dir, enable_chunked_prefill=True,
                 max_num_batched_tokens=8), PROMPTS, params)
    finally:
        sched_mod.Scheduler._chunked_pass = orig

    assert mixed_steps["n"] > 0, "chunked engine never took the mixed path"
    assert mixed_steps["split"] > 0, (
        "budget was sized to split prompts across steps but none split")
    for i, (l, c) in enumerate(zip(legacy, chunked)):
        assert l.outputs[0].token_ids == c.outputs[0].token_ids, (
            f"prompt {i}: chunked-on diverged from legacy "
            f"({l.outputs[0].token_ids} vs {c.outputs[0].token_ids})")


def test_chunked_off_is_default_and_identical(tiny_opt_dir):
    """The flag is off by default, and passing it explicitly as False is
    output-identical to not passing it at all (legacy golden)."""
    params = [SamplingParams(temperature=0.0, max_tokens=8,
                             ignore_eos=True) for _ in PROMPTS[:3]]
    implicit = _generate(_llm(tiny_opt_dir), PROMPTS[:3], params)
    explicit = _generate(_llm(tiny_opt_dir, enable_chunked_prefill=False),
                         PROMPTS[:3], params)
    for l, c in zip(implicit, explicit):
        assert l.outputs[0].token_ids == c.outputs[0].token_ids


def test_mixed_metadata_without_chunk_info_raises(tiny_opt_dir):
    """Legacy-mode guard: a metadata list mixing prefill and decode
    entries with no token_chunk_size must raise instead of silently
    batching everything under the first entry's phase."""
    llm = _llm(tiny_opt_dir)
    runner = llm.llm_engine.worker.model_runner
    sp = SamplingParams(temperature=0.0, max_tokens=4)

    def meta(rid, seq_id, is_prompt):
        return SequenceGroupMetadata(
            request_id=rid, is_prompt=is_prompt,
            seq_data={seq_id: SequenceData([3, 4, 5])},
            sampling_params=sp, block_tables={seq_id: [0]})

    caches = llm.llm_engine.worker.cache_engine.device_cache
    with pytest.raises(ValueError, match="chunked-prefill"):
        runner.execute_model([meta("0", 0, True), meta("1", 1, False)],
                             caches)
