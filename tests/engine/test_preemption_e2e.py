"""End-to-end preemption correctness: a memory-pressured engine must
produce EXACTLY the outputs of an unpressured one.

Reference role: the recompute/swap preemption paths
(`core/scheduler.py:_preempt*`) are only scheduler-unit-tested; these
tests drive them through the full engine and assert token equality —
recompute must regenerate identical prefixes and swap must restore KV
bit-exactly.
"""
import pytest

from intellillm_tpu import LLM, SamplingParams


def _llm(model_dir, blocks, **kw):
    return LLM(model=model_dir, dtype="float32",
               num_device_blocks_override=blocks, max_model_len=128,
               max_num_seqs=8, max_paddings=512, swap_space=0.01, **kw)


def _generate(llm, prompts, params_list):
    engine = llm.llm_engine
    for i, (p, sp) in enumerate(zip(prompts, params_list)):
        engine.add_request(str(i), p, sp)
    outs = {o.request_id: o for o in llm._run_engine(use_tqdm=False)}
    return [outs[str(i)] for i in range(len(prompts))]


def test_recompute_preemption_preserves_greedy(tiny_opt_dir,
                                               example_prompts,
                                               monkeypatch):
    """Pool of 10 blocks vs 4 seqs needing ~4 blocks each at peak: the
    scheduler must preempt by recompute; outputs must equal the
    unpressured run's — and preemption must actually have happened."""
    from intellillm_tpu.core import scheduler as sched_mod

    params = [SamplingParams(temperature=0.0, max_tokens=48,
                             ignore_eos=True)
              for _ in example_prompts]

    roomy = _generate(_llm(tiny_opt_dir, 128), example_prompts, params)

    preemptions = {"n": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        preemptions["n"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    tight = _generate(_llm(tiny_opt_dir, 10), example_prompts, params)

    assert preemptions["n"] > 0, (
        "pool was sized to force recompute preemption but none happened")
    for i, (r, t) in enumerate(zip(roomy, tight)):
        assert r.outputs[0].token_ids == t.outputs[0].token_ids, (
            f"prompt {i} diverged under preemption")


def test_swap_preemption_preserves_outputs(tiny_opt_dir, example_prompts,
                                           monkeypatch):
    """best_of=2 groups preempt by SWAP (multi-seq state can't recompute);
    swapped-and-restored KV must reproduce the unpressured outputs, and
    the swap path must actually have run."""
    from intellillm_tpu.worker import cache_engine as ce

    params = [SamplingParams(temperature=0.8, best_of=2, n=2,
                             max_tokens=40, ignore_eos=True)
              for _ in example_prompts]

    roomy = _generate(_llm(tiny_opt_dir, 128), example_prompts, params)

    swaps = {"out": 0, "in": 0}
    orig_out = ce.CacheEngine.swap_out
    orig_in = ce.CacheEngine.swap_in

    def counting_out(self, mapping):
        swaps["out"] += 1
        return orig_out(self, mapping)

    def counting_in(self, mapping):
        swaps["in"] += 1
        return orig_in(self, mapping)

    monkeypatch.setattr(ce.CacheEngine, "swap_out", counting_out)
    monkeypatch.setattr(ce.CacheEngine, "swap_in", counting_in)

    tight = _generate(_llm(tiny_opt_dir, 14), example_prompts, params)

    assert swaps["out"] > 0 and swaps["in"] > 0, (
        "pool was sized to force swap preemption but none happened — "
        f"swaps={swaps}")
    for i, (r, t) in enumerate(zip(roomy, tight)):
        r_tok = sorted(c.token_ids for c in r.outputs)
        t_tok = sorted(c.token_ids for c in t.outputs)
        assert r_tok == t_tok, f"prompt {i} diverged under swap"


# --- chunked prefill: preemption of partially-prefilled sequences -------

_LONG_PROMPTS = [
    " ".join(["the cat runs fast and the dog"] * 7),      # 49 tokens
    " ".join(["the president of the united states is"] * 6),  # 42 tokens
    " ".join(["the capital of france is paris"] * 7),     # 42 tokens
    " ".join(["hello my name is"] * 10),                  # 40 tokens
]


# Both engine loops. The serial one ("0"): every step is a full schedule.
# The pipelined one ("1", the default): a long prompt's chunks run as
# prompt steps beside a decode step in flight, with no preemption and no
# swap planning of their own; a pool that runs short drains the pipeline
# to a full schedule, which preempts groups part-way through their
# prompts as the serial loop does (11 preemptions, 10 of them mid-chunk,
# on either loop at this size).
_LOOPS = pytest.mark.parametrize("pipeline", ["0", "1"])


@_LOOPS
def test_chunked_recompute_preemption_preserves_greedy(tiny_opt_dir,
                                                       monkeypatch,
                                                       pipeline):
    """Chunked prefill + tight pool: recompute preemption must hit at
    least one PARTIALLY-prefilled group (num_computed_tokens mid-prompt),
    and the re-chunked re-prefill must reproduce the unpressured chunked
    run's tokens exactly."""
    from intellillm_tpu.core import scheduler as sched_mod

    params = [SamplingParams(temperature=0.0, max_tokens=24,
                             ignore_eos=True)
              for _ in _LONG_PROMPTS]
    chunked_kw = dict(enable_chunked_prefill=True,
                      max_num_batched_tokens=16)

    roomy = _generate(_llm(tiny_opt_dir, 128, **chunked_kw),
                      _LONG_PROMPTS, params)

    hits = {"total": 0, "mid_chunk": 0}
    orig = sched_mod.Scheduler._preempt_by_recompute

    def counting(self, seq_group):
        hits["total"] += 1
        if any(not s.data.prefill_complete
               for s in seq_group.get_unfinished_seqs()):
            hits["mid_chunk"] += 1
        return orig(self, seq_group)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt_by_recompute",
                        counting)
    monkeypatch.setenv("INTELLILLM_PIPELINE", pipeline)
    tight = _generate(_llm(tiny_opt_dir, 10, **chunked_kw),
                      _LONG_PROMPTS, params)

    assert hits["mid_chunk"] > 0, (
        "pool was sized so recompute preemption hits a mid-chunk group "
        f"but none did — hits={hits}")
    for i, (r, t) in enumerate(zip(roomy, tight)):
        assert r.outputs[0].token_ids == t.outputs[0].token_ids, (
            f"prompt {i} diverged under mid-chunk recompute preemption")


@_LOOPS
def test_chunked_swap_preemption_preserves_greedy(tiny_opt_dir,
                                                  monkeypatch, pipeline):
    """Force SWAP preemption (instead of the single-seq recompute
    default) under chunked prefill: a swapped-out mid-chunk group keeps
    its num_computed_tokens, and swap-in must resume chunking exactly
    where the KV left off — outputs must match the unpressured run."""
    from intellillm_tpu.core import scheduler as sched_mod
    from intellillm_tpu.worker import cache_engine as ce

    params = [SamplingParams(temperature=0.0, max_tokens=24,
                             ignore_eos=True)
              for _ in _LONG_PROMPTS]
    chunked_kw = dict(enable_chunked_prefill=True,
                      max_num_batched_tokens=16)

    roomy = _generate(_llm(tiny_opt_dir, 128, **chunked_kw),
                      _LONG_PROMPTS, params)

    hits = {"swap_out": 0, "swap_in": 0, "mid_chunk": 0}
    orig_preempt = sched_mod.Scheduler._preempt

    def forced_swap(self, seq_group, blocks_to_swap_out,
                    preemption_mode=None):
        if any(not s.data.prefill_complete
               for s in seq_group.get_unfinished_seqs()):
            hits["mid_chunk"] += 1
        return orig_preempt(self, seq_group, blocks_to_swap_out,
                            sched_mod.PreemptionMode.SWAP)

    orig_out = ce.CacheEngine.swap_out
    orig_in = ce.CacheEngine.swap_in

    def counting_out(self, mapping):
        hits["swap_out"] += 1
        return orig_out(self, mapping)

    def counting_in(self, mapping):
        hits["swap_in"] += 1
        return orig_in(self, mapping)

    monkeypatch.setattr(sched_mod.Scheduler, "_preempt", forced_swap)
    monkeypatch.setattr(ce.CacheEngine, "swap_out", counting_out)
    monkeypatch.setattr(ce.CacheEngine, "swap_in", counting_in)

    monkeypatch.setenv("INTELLILLM_PIPELINE", pipeline)
    tight = _generate(_llm(tiny_opt_dir, 10, **chunked_kw),
                      _LONG_PROMPTS, params)

    assert hits["swap_out"] > 0 and hits["swap_in"] > 0, (
        f"pool was sized to force swap preemption but none ran — {hits}")
    assert hits["mid_chunk"] > 0, (
        "no swap preemption hit a mid-chunk group — the resume-from-"
        f"num_computed_tokens path went unexercised — {hits}")
    for i, (r, t) in enumerate(zip(roomy, tight)):
        assert r.outputs[0].token_ids == t.outputs[0].token_ids, (
            f"prompt {i} diverged under mid-chunk swap preemption")


def _generate_late(llm, early, late, params_early, params_late, after):
    """`early` requests first; `late` ones added once `after` pipelined
    steps have run, so they arrive beside a decode step in flight."""
    engine = llm.llm_engine
    assert engine.pipeline_enabled
    for i, (p, sp) in enumerate(zip(early, params_early)):
        engine.add_request(str(i), p, sp)
    outs, steps, added = {}, 0, False
    while (engine.has_unfinished_requests() or engine.has_inflight()
           or not added):
        if steps == after:
            for j, (p, sp) in enumerate(zip(late, params_late)):
                engine.add_request(str(len(early) + j), p, sp)
            added = True
        for o in engine.step_pipelined():
            if o.finished:
                outs[o.request_id] = o
        steps += 1
    return [outs[str(i)] for i in range(len(early) + len(late))]


@pytest.mark.parametrize("mode", ["recompute", "swap"])
def test_chunk_steps_beside_decode_under_a_tight_pool(tiny_opt_dir,
                                                      monkeypatch, mode):
    """The pipelined loop's own chunk path under memory pressure: prompts
    over the token budget arrive while a K-step decode is in flight, so
    their chunks run as prompt steps beside it
    (`_chunked_pass(prefill_only=True)`, which neither preempts nor plans
    a swap); the pool then runs short, the pipeline drains, and the full
    schedule preempts. The tokens must be the roomy run's."""
    from intellillm_tpu.core import scheduler as sched_mod

    early = _LONG_PROMPTS[2:]
    late = _LONG_PROMPTS[:2]
    p_early = [SamplingParams(temperature=0.0, max_tokens=40,
                              ignore_eos=True) for _ in early]
    p_late = [SamplingParams(temperature=0.0, max_tokens=24,
                             ignore_eos=True) for _ in late]
    kw = dict(enable_chunked_prefill=True, max_num_batched_tokens=16)

    roomy = _generate_late(_llm(tiny_opt_dir, 128, **kw), early, late,
                           p_early, p_late, after=8)

    seen = {"chunk_steps": 0, "preempted": 0}
    orig_pass = sched_mod.Scheduler._chunked_pass
    orig_preempt = sched_mod.Scheduler._preempt

    def counting_pass(self, now, prefill_only=False):
        out = orig_pass(self, now, prefill_only=prefill_only)
        if prefill_only and out.scheduled_seq_groups:
            seen["chunk_steps"] += 1
        return out

    def counting_preempt(self, seq_group, blocks_to_swap_out,
                         preemption_mode=None):
        seen["preempted"] += 1
        if mode == "swap":
            preemption_mode = sched_mod.PreemptionMode.SWAP
        return orig_preempt(self, seq_group, blocks_to_swap_out,
                            preemption_mode)

    monkeypatch.setattr(sched_mod.Scheduler, "_chunked_pass", counting_pass)
    monkeypatch.setattr(sched_mod.Scheduler, "_preempt", counting_preempt)
    tight = _generate_late(_llm(tiny_opt_dir, 14, **kw), early, late,
                           p_early, p_late, after=8)

    assert seen["chunk_steps"] > 0, (
        f"no chunk ran as a prompt step beside the decode batch — {seen}")
    assert seen["preempted"] > 0, (
        f"pool was sized to run short after the late prompts — {seen}")
    for i, (r, t) in enumerate(zip(roomy, tight)):
        assert r.outputs[0].token_ids == t.outputs[0].token_ids, (
            f"prompt {i} diverged ({mode}) — {seen}")
