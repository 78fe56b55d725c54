"""Pipelined stepping (LLMEngine.step_pipelined) equivalence.

The pipelined driver dispatches decode step N+1 before fetching step N
(continuation programs slice their input tokens from the previous step's
on-device output) and chains prompt admissions behind in-flight steps.
Outputs must match the serial step() loop token-for-token: greedy results
are schedule-independent, and sampling seeds are keyed per
(sequence, output-position) so pipelining cannot change random streams
either.

An admitted sequence joins the running decode batch at the next
continuation (a re-snapshot of the row set: rows the host knows to be
done give up their slot, joiners take free ones); what needs a coherent
host view still drains to a fresh schedule, counted by cause.
"""
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.engine.llm_engine import _PROMPT_STEPS_PER_DECODE_STEP
from intellillm_tpu.obs import get_efficiency_tracker


def _params(sampling, i, max_tokens):
    """Greedy, sampling seeded by the engine, or by the request."""
    if sampling == "greedy":
        return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                              ignore_eos=True)
    return SamplingParams(temperature=0.8, top_p=0.9, max_tokens=max_tokens,
                          ignore_eos=True,
                          seed=1000 + i if sampling == "request_seed"
                          else None)


SAMPLINGS = ("greedy", "engine_seed", "request_seed")


def _run_arrivals(llm, requests, observe=None, behind_decode=False):
    """One request before the first call and one more ahead of every
    later call (with `behind_decode`, of every call that finds a decode
    step in flight): admissions interleave with decode in every period."""
    engine = llm.llm_engine
    outs = []
    pending = list(requests)
    calls = 0
    engine.add_request(*pending.pop(0))
    while (engine.has_unfinished_requests() or engine.has_inflight()
           or pending):
        if pending and (not behind_decode
                        or engine._newest_decode_inflight() is not None):
            engine.add_request(*pending.pop(0))
        outs.extend(engine.step_pipelined())
        if observe is not None:
            observe(engine)
        calls += 1
        assert calls < 2000, "pipelined engine made no progress"
    return _collect(outs)


def _dispatches(program):
    counts = get_efficiency_tracker().snapshot()["programs"][program]
    return counts["idle"] + counts["busy"]


def _build(model_dir, **kw):
    args = dict(dtype="float32", num_device_blocks_override=128,
                max_model_len=128, max_num_seqs=8, max_paddings=512,
                swap_space=0.01, num_decode_steps=8)
    args.update(kw)
    return LLM(model=model_dir, **args)


def _collect(outs):
    done = {}
    for o in outs:
        if o.finished:
            done[o.request_id] = [
                (tuple(c.token_ids), c.text, c.finish_reason)
                for c in o.outputs]
    return done


def _run_serial(llm, requests):
    engine = llm.llm_engine
    for rid, prompt, params in requests:
        engine.add_request(rid, prompt, params)
    outs = []
    while engine.has_unfinished_requests():
        outs.extend(engine.step())
    return _collect(outs)


def _run_pipelined(llm, requests, stagger_after=None):
    """Drive step_pipelined; with stagger_after=n, add the remaining
    requests only after n pipelined calls (exercises prompt admission
    chained behind in-flight decode steps)."""
    engine = llm.llm_engine
    first = requests if stagger_after is None else requests[:stagger_after]
    rest = [] if stagger_after is None else requests[stagger_after:]
    for rid, prompt, params in first:
        engine.add_request(rid, prompt, params)
    outs = []
    calls = 0
    while engine.has_unfinished_requests() or engine.has_inflight():
        outs.extend(engine.step_pipelined())
        calls += 1
        if rest and calls >= 2:
            for rid, prompt, params in rest:
                engine.add_request(rid, prompt, params)
            rest = []
        assert calls < 2000, "pipelined engine made no progress"
    return _collect(outs)


def test_pipelined_matches_serial_greedy(tiny_llama_dir, example_prompts):
    reqs = [(str(i), p, SamplingParams(temperature=0.0, max_tokens=24,
                                       ignore_eos=True))
            for i, p in enumerate(example_prompts)]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    got = _run_pipelined(_build(tiny_llama_dir), reqs)
    assert got == ref
    # The pipelined run really exercised continuations (not just drains):
    # max_tokens=24 at K=8 needs >= 2 extra fused steps per sequence.
    assert all(r[0][2] == "length" for r in got.values())


@pytest.mark.parametrize("sampling", SAMPLINGS)
def test_pipelined_staggered_admission(tiny_llama_dir, example_prompts,
                                       sampling):
    """Requests added mid-decode are admitted via prefill chaining and
    join the running batch; the final outputs still match the serial
    loop."""
    reqs = [(str(i), p, _params(sampling, i, 20))
            for i, p in enumerate(example_prompts)]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    got = _run_pipelined(_build(tiny_llama_dir), reqs, stagger_after=2)
    assert got == ref


def _second_step_stop(toks, text12, kind):
    """Stop criteria that first match inside the second fused step
    (output positions 10..17 at K=8) of a greedy run whose first 32
    tokens are `toks` and whose first 12 detokenize to `text12`, or None:
    a stop token id that is new there, or the stop string that is all of
    the text up to the 12th token (so it matches when that token has
    been detokenized, not before)."""
    if kind == "string":
        return dict(stop=[text12.strip()]) if text12.strip() else None
    return next((dict(stop_token_ids=[toks[j]]) for j in range(10, 16)
                 if toks[j] not in toks[:j]), None)


@pytest.mark.parametrize("model, kind", [("tiny_opt", "string"),
                                         ("tiny_llama", "token_ids")])
def test_pipelined_stops_make_zombie_rows(request, example_prompts, model,
                                          kind):
    """A sequence hitting a stop mid-pipeline becomes a zombie row of the
    steps already dispatched: their overshoot is discarded (a stop string
    is seen only after detokenizing, and the text is cut with overshoot
    in flight), its KV pages are freed only after the last step that
    references them, and the next re-snapshot drops it. The surviving
    sequences finish with serial-identical tokens, text and reason. Runs
    on learned positions (OPT) and on rotary ones, K=8 fused."""
    model_dir = request.getfixturevalue(model + "_dir")
    free = [(str(i), p, _params("greedy", i, 32))
            for i, p in enumerate(example_prompts[:3])]
    probe = _run_serial(_build(model_dir), free)
    short = _run_serial(_build(model_dir),
                        [(r, p, _params("greedy", 0, 12)) for r, p, _ in free])
    # Stopping there ends that request in the second fused step, with
    # later steps already dispatched.
    rid, stop = next(
        (rid, stop) for rid, ((toks, _, _), ) in sorted(probe.items())
        if (stop := _second_step_stop(toks, short[rid][0][1], kind)))
    reqs = [(r, p, SamplingParams(temperature=0.0, max_tokens=32, **stop)
             if r == rid else sp) for r, p, sp in free]
    ref = _run_serial(_build(model_dir), reqs)
    assert ref[rid][0][2] == "stop" and 9 < len(ref[rid][0][0]) < 18

    llm = _build(model_dir)
    engine = llm.llm_engine
    for req in reqs:
        engine.add_request(*req)
    (zombie, ) = next(g for g in engine.scheduler.waiting
                      if g.request_id == rid).get_seqs()
    tables = engine.scheduler.block_manager.block_tables
    held_while_referenced = 0
    outs = []
    while engine.has_unfinished_requests() or engine.has_inflight():
        outs.extend(engine.step_pipelined())
        if not zombie.is_finished():
            continue
        referenced = any(zombie.seq_id in step._pipeline_seq_ids
                         for step in engine._inflight)
        # Pages stay while a dispatched step may still write them, and
        # go with the last such step.
        assert (zombie.seq_id in tables) == referenced
        held_while_referenced += referenced
        # A step dispatched after the stop was seen has no row for it.
        newest = engine._newest_decode_inflight()
        if newest is not None and held_while_referenced > 1:
            assert zombie.seq_id not in [
                sid for _, sid in newest.cont_state.rows]
    assert held_while_referenced >= 1
    assert _collect(outs) == ref


def test_pipelined_random_sampling_matches(tiny_llama_dir, example_prompts):
    """Seeded random sampling: continuation seeds advance exactly as a
    caught-up fresh dispatch would compute them."""
    reqs = [(str(i), p, SamplingParams(temperature=0.8, top_p=0.9,
                                       max_tokens=16, ignore_eos=True))
            for i, p in enumerate(example_prompts)]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    got = _run_pipelined(_build(tiny_llama_dir), reqs)
    assert got == ref


def test_pipelined_best_of_groups(tiny_llama_dir, example_prompts):
    """Multi-sequence groups (best_of>1 random): forked rows continue
    correctly (the post-prefill fresh decode resolves CoW; continuations
    only ever extend private trailing blocks)."""
    reqs = [(str(i), p, SamplingParams(temperature=0.7, best_of=2, n=2,
                                       max_tokens=12, ignore_eos=True))
            for i, p in enumerate(example_prompts[:3])]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    got = _run_pipelined(_build(tiny_llama_dir), reqs)
    assert got == ref


@pytest.mark.parametrize("joiner", [
    dict(temperature=0.0, presence_penalty=0.5),
    dict(temperature=0.0, repetition_penalty=1.3),
    dict(temperature=0.7, best_of=2, n=2),
], ids=["presence_penalty", "repetition_penalty", "best_of"])
def test_excluded_joiner_drains_to_fresh_schedule(tiny_llama_dir,
                                                  example_prompts, joiner):
    """A sequence whose sampling the fused program does not take cannot
    join by continuation: the pipeline drains (cause `params`) and a
    full schedule merges it (copy-on-write forks, or a K=1 batch)."""
    reqs = [("0", example_prompts[0], _params("greedy", 0, 48)),
            ("1", example_prompts[1], _params("greedy", 1, 48)),
            ("2", example_prompts[2],
             SamplingParams(max_tokens=12, ignore_eos=True, **joiner))]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    get_efficiency_tracker().reset_for_testing()
    got = _run_pipelined(_build(tiny_llama_dir), reqs, stagger_after=2)
    assert got == ref
    snap = get_efficiency_tracker().snapshot()
    assert snap["pipeline_drains"]["params"] >= 1
    assert snap["decode_join_rows"]["device"] == 0
    assert snap["decode_join_rows"]["fresh"] >= 3     # 2 + the joiner's rows


def test_pipelined_tight_pool_drains(tiny_llama_dir):
    """When in-place growth runs out of free blocks the pipeline drains to
    a full scheduling pass (which may preempt) instead of corrupting the
    pool; the request still completes."""
    llm = _build(tiny_llama_dir, num_device_blocks_override=12,
                 max_num_seqs=2, num_decode_steps=8)
    reqs = [("0", None, SamplingParams(temperature=0.0, max_tokens=16,
                                       ignore_eos=True))]
    engine = llm.llm_engine
    engine.add_request("0", None, reqs[0][2],
                       prompt_token_ids=[2, 3, 4, 5] * 20)  # 80 tokens
    outs = []
    calls = 0
    while engine.has_unfinished_requests() or engine.has_inflight():
        outs.extend(engine.step_pipelined())
        calls += 1
        assert calls < 200
    done = _collect(outs)
    assert len(done["0"][0][0]) >= 16


def test_pipelined_tight_pool_preempts_a_joined_batch(tiny_llama_dir):
    """Two sequences that outgrow the pool together: the continuation's
    block growth is refused, the pipeline drains (cause `memory`), the
    full schedule preempts one, and both finish with the serial loop's
    tokens."""
    kw = dict(num_device_blocks_override=12, max_num_seqs=4)
    sp = SamplingParams(temperature=0.0, max_tokens=56, ignore_eos=True)
    prompts = {"0": [2, 3, 4, 5] * 12, "1": [6, 7, 8, 9] * 12}   # 48 each

    def run(pipelined):
        llm = _build(tiny_llama_dir, **kw)
        engine = llm.llm_engine
        engine.add_request("0", None, sp, prompt_token_ids=prompts["0"])
        outs = []
        calls = 0
        while (engine.has_unfinished_requests() or engine.has_inflight()
               or calls < 2):
            outs.extend(engine.step_pipelined() if pipelined
                        else engine.step())
            calls += 1
            if calls == 2:
                engine.add_request("1", None, sp,
                                   prompt_token_ids=prompts["1"])
            assert calls < 400
        return _collect(outs)

    ref = run(False)
    get_efficiency_tracker().reset_for_testing()
    got = run(True)
    assert {k: v[0][0] for k, v in got.items()} == {
        k: v[0][0] for k, v in ref.items()}
    assert all(len(v[0][0]) == 56 for v in got.values())
    snap = get_efficiency_tracker().snapshot()
    assert snap["pipeline_drains"]["memory"] >= 1


@pytest.mark.parametrize("behind_decode", [False, True])
@pytest.mark.parametrize("sampling", SAMPLINGS)
def test_pipelined_continuous_arrivals(tiny_llama_dir, example_prompts,
                                       sampling, behind_decode):
    """High-rate pattern: a new request arrives on (almost) every call,
    so prompt admissions interleave with decode continuations and every
    admitted sequence joins the running batch. Outputs must still match
    the serial loop exactly."""
    prompts = (example_prompts * 3)[:10]
    reqs = [(str(i), p, _params(sampling, i, 16 + 3 * i))
            for i, p in enumerate(prompts)]
    ref = _run_serial(_build(tiny_llama_dir, max_num_seqs=12), reqs)
    got = _run_arrivals(_build(tiny_llama_dir, max_num_seqs=12), reqs,
                        behind_decode=behind_decode)
    assert got == ref


def test_joiners_enter_by_continuation_not_by_fresh_schedule(
        tiny_llama_dir, example_prompts, monkeypatch):
    """The structure of the loop under continuous arrivals with a roomy
    pool: ONE `decode_fused` (the first batch, from an idle engine), and
    every later sequence takes a slot of the running batch at a
    continuation. No drain but the first schedule's; the depth counts
    decode steps, a prompt step rides between them."""
    prompts = (example_prompts * 4)[:12]
    reqs = [(str(i), p, _params("greedy", i, 40))
            for i, p in enumerate(prompts)]
    ref = _run_serial(_build(tiny_llama_dir, max_num_seqs=16), reqs)
    get_efficiency_tracker().reset_for_testing()
    llm = _build(tiny_llama_dir, max_num_seqs=16)
    engine = llm.llm_engine
    depth = engine._pipeline_depth
    seen = set()          # (decode steps, other steps) in flight
    finalize = engine._finalize_one

    def observing():
        decodes = sum(s.cont_state is not None for s in engine._inflight)
        seen.add((decodes, len(engine._inflight) - decodes))
        return finalize()

    monkeypatch.setattr(engine, "_finalize_one", observing)
    got = _run_arrivals(llm, reqs, behind_decode=True)
    assert got == ref
    assert _dispatches("decode_fused") == 1
    assert _dispatches("decode_cont") >= 5
    snap = get_efficiency_tracker().snapshot()
    assert snap["decode_join_rows"] == {"device": len(reqs) - 1, "fresh": 1}
    assert snap["pipeline_drains"] == {
        "memory": 0, "params": 0, "chunked_prefill": 0, "bucket": 0,
        "idle": 1}
    # `depth` decode steps in flight with a prompt step beside them, and
    # never more than the stated number of prompt steps per decode step.
    assert max(d for d, _ in seen) == depth
    assert (depth, 1) in seen
    assert all(o <= _PROMPT_STEPS_PER_DECODE_STEP * max(d, 1)
               for d, o in seen)


def test_joiner_reuses_the_slot_of_a_row_done_by_max_tokens(
        tiny_llama_dir, example_prompts, monkeypatch):
    """A row whose max_tokens the dispatched steps cover gives up its
    slot without the host having seen its tokens; the next joiner takes
    it, the other rows keep theirs."""
    reqs = [("short", example_prompts[0], _params("greedy", 0, 9)),
            ("long", example_prompts[1], _params("greedy", 1, 64)),
            ("late", example_prompts[2], _params("greedy", 2, 24))]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    llm = _build(tiny_llama_dir)
    engine = llm.llm_engine
    snapshots = []
    orig = engine.worker.execute_decode_cont

    def recording(cont, *a, **kw):
        snapshots.append({rid: int(slot)
                          for (rid, _), slot in zip(cont.rows, cont.slots)})
        return orig(cont, *a, **kw)

    monkeypatch.setattr(engine.worker, "execute_decode_cont", recording)
    got = _run_pipelined(llm, reqs, stagger_after=2)
    assert got == ref
    # 1 prefill token + one K=8 step cover max_tokens=9: the first
    # continuation already runs without the short row.
    assert snapshots[0] == {"long": 1}
    joined = next(s for s in snapshots if "late" in s)
    assert joined == {"late": 0, "long": 1}


def test_batch_bucket_follows_the_load_both_ways(tiny_llama_dir,
                                                example_prompts,
                                                monkeypatch):
    """A continuation inherits the batch bucket in flight. More joiners
    than it has free slots drain (cause `bucket`) to a larger one; when
    the load has fallen and the rows have fitted the next smaller bucket
    for a few snapshots running, the loop drains (`bucket`) again and the
    fresh schedule re-buckets them, instead of running the peak's bucket
    until the engine idles."""
    prompts = (example_prompts * 6)[:20]
    reqs = [(str(i), p, _params("greedy", i, 96 if i < 3 else 10))
            for i, p in enumerate(prompts)]
    kw = dict(max_num_seqs=24, num_device_blocks_override=256)
    ref = _run_serial(_build(tiny_llama_dir, **kw), reqs)
    get_efficiency_tracker().reset_for_testing()
    llm = _build(tiny_llama_dir, **kw)
    engine = llm.llm_engine
    buckets = []          # (batch bucket, live rows) of every decode step
    orig = engine.worker.execute_decode_cont

    def recording(cont, *a, **kw):
        buckets.append((cont.ctx0.shape[0], len(cont.rows)))
        return orig(cont, *a, **kw)

    monkeypatch.setattr(engine.worker, "execute_decode_cont", recording)
    # 3 long requests first (bucket 16), then 17 short ones at once.
    got = _run_pipelined(llm, reqs, stagger_after=3)
    assert got == ref
    assert max(b for b, _ in buckets) == 32
    peak = max(i for i, (b, _) in enumerate(buckets) if b == 32)
    after = buckets[peak + 1:]
    assert after and all(b == 16 for b, _ in after)
    # Back at 16 at the fourth roomy snapshot, not at the end of the run.
    roomy = [n for b, n in buckets if b == 32 and n <= 16]
    assert len(roomy) == 3 and len(after) >= 2
    assert get_efficiency_tracker().snapshot()["pipeline_drains"][
        "bucket"] == 2


def test_full_seats_do_not_drain(tiny_llama_dir, example_prompts):
    """More requests than seats (`max_num_seqs`), the queue forming with
    decode in flight: a prompt that waits for a seat is not a reason to
    drain. Decode continues, a finished row frees a seat, and the next
    prefill-only pass admits the head of the queue, which joins by
    continuation."""
    prompts = (example_prompts * 3)[:10]
    # Seats free one at a time, with a decode step always in flight.
    reqs = [(str(i), p, _params("greedy", i, (96, 64, 40, 16)[i] if i < 4
                                else 16))
            for i, p in enumerate(prompts)]
    ref = _run_serial(_build(tiny_llama_dir, max_num_seqs=4), reqs)
    get_efficiency_tracker().reset_for_testing()
    got = _run_pipelined(_build(tiny_llama_dir, max_num_seqs=4), reqs,
                         stagger_after=4)
    assert got == ref
    snap = get_efficiency_tracker().snapshot()
    assert snap["pipeline_drains"] == {
        "memory": 0, "params": 0, "chunked_prefill": 0, "bucket": 0,
        "idle": 1}
    assert snap["decode_join_rows"] == {"device": 6, "fresh": 4}
    assert _dispatches("decode_fused") == 1


def test_prompt_over_the_budget_is_chunked_beside_the_decode_batch(
        tiny_llama_dir, example_prompts):
    """A prompt longer than one prefill-only step may carry runs its
    chunks as prompt steps of their own, chained behind the decode steps
    in flight: no drain, and its sequence joins the running batch by
    continuation once its final chunk is fetched."""
    kw = dict(max_num_batched_tokens=32, max_model_len=128)
    long_prompt = [2, 3, 4, 5, 6] * 16                       # 80 tokens
    sp = _params("greedy", 0, 40)

    def run(pipelined):
        engine = _build(tiny_llama_dir, **kw).llm_engine
        engine.add_request("0", example_prompts[0], sp)
        outs = []
        calls = 0
        while (engine.has_unfinished_requests() or engine.has_inflight()
               or calls < 3):
            outs.extend(engine.step_pipelined() if pipelined
                        else engine.step())
            calls += 1
            if calls == 3:
                engine.add_request("1", None, sp,
                                   prompt_token_ids=long_prompt)
            assert calls < 400
        return _collect(outs)

    ref = run(False)
    get_efficiency_tracker().reset_for_testing()
    assert run(True) == ref
    snap = get_efficiency_tracker().snapshot()
    assert snap["pipeline_drains"] == {
        "memory": 0, "params": 0, "chunked_prefill": 0, "bucket": 0,
        "idle": 1}
    assert snap["decode_join_rows"] == {"device": 1, "fresh": 1}


def test_prompt_over_the_budget_on_an_idle_pipeline_is_co_batched(
        tiny_llama_dir, example_prompts):
    """With no decode step in flight (requests that arrive together at an
    idle engine) a prompt over the budget is not chunked by prefill-only
    passes: the fresh schedule's chunked pass puts its chunks in steps
    with the other sequences' decode rows, which then move a token a step
    and do not stand still while the chunks run alone."""
    kw = dict(max_num_batched_tokens=32, max_model_len=128)
    long_prompt = [2, 3, 4, 5, 6] * 16                       # 80 tokens
    sp = _params("greedy", 0, 24)

    def run(pipelined):
        engine = _build(tiny_llama_dir, **kw).llm_engine
        engine.add_request("0", example_prompts[0], sp)
        engine.add_request("1", None, sp, prompt_token_ids=long_prompt)
        outs = []
        while engine.has_unfinished_requests() or engine.has_inflight():
            outs.extend(engine.step_pipelined() if pipelined
                        else engine.step())
        return _collect(outs)

    ref = run(False)
    get_efficiency_tracker().reset_for_testing()
    assert run(True) == ref
    snap = get_efficiency_tracker().snapshot()
    assert snap["co_batched_steps"] >= 1
    assert snap["pipeline_drains"]["chunked_prefill"] >= 1
    assert snap["decode_join_rows"]["device"] == 0


def test_no_overshoot_cont_when_budgets_exhausted(tiny_llama_dir,
                                                  example_prompts,
                                                  monkeypatch):
    """max_tokens == K (the offline-bench shape): after the one fused
    decode call covers every row's budget, the pipeline must NOT dispatch
    a continuation — it would be a 100% overshoot device call."""
    llm = _build(tiny_llama_dir, num_decode_steps=8)
    engine = llm.llm_engine
    calls = {"cont": 0}
    orig = engine.worker.execute_decode_cont

    def counting(*a, **kw):
        calls["cont"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(engine.worker, "execute_decode_cont", counting)
    for i, p in enumerate(example_prompts):
        engine.add_request(str(i), p,
                           SamplingParams(temperature=0.0, max_tokens=8,
                                          ignore_eos=True))
    outs = []
    n = 0
    while engine.has_unfinished_requests() or engine.has_inflight():
        outs.extend(engine.step_pipelined())
        n += 1
        assert n < 100
    done = _collect(outs)
    assert all(len(v[0][0]) == 8 for v in done.values())
    assert calls["cont"] == 0, (
        "pipeline dispatched a pure-overshoot continuation")


def test_pipelined_k1_falls_back(tiny_opt_dir, example_prompts):
    """K=1 batches (no continuation program) still work through the
    pipelined driver — each step drains before the next fresh schedule."""
    reqs = [(str(i), p, SamplingParams(temperature=0.0, max_tokens=8,
                                       ignore_eos=True))
            for i, p in enumerate(example_prompts[:2])]
    ref = _run_serial(_build(tiny_opt_dir, num_decode_steps=1), reqs)
    got = _run_pipelined(_build(tiny_opt_dir, num_decode_steps=1), reqs)
    assert got == ref


# --- a joiner's first token handed over on the device ----------------------
#
# A sequence whose final prompt step is still in flight joins the very next
# continuation: its first token is gathered on the device from that step's
# un-fetched output, and the host appends it when the step is fetched.


def _log_dispatches(engine, monkeypatch):
    """Every step the loop dispatches, in order: ("P", the sequences a
    prompt step samples a first token for), ("F", a fresh schedule's) and
    ("D", the rows of a continuation)."""
    log = []
    dispatch = engine._dispatch
    cont = engine.worker.execute_decode_cont

    def dispatching(metas, so):
        dispatch(metas, so)
        step = engine._inflight[-1]
        log.append(("P", [sid for _, sid in step.emit[1]])
                   if so.prompt_run else ("F", step._pipeline_seq_ids))

    def continuing(state, *a, **kw):
        log.append(("D", [sid for _, sid in state.rows]))
        return cont(state, *a, **kw)

    monkeypatch.setattr(engine, "_dispatch", dispatching)
    monkeypatch.setattr(engine.worker, "execute_decode_cont", continuing)
    return log


def _join_lags(log):
    """For every sequence that joined by continuation: how many
    continuations were dispatched between the prompt step that sampled
    its first token and the continuation that took it."""
    lags = {}
    fresh = {sid for kind, sids in log if kind == "F" for sid in sids}
    for i, (kind, sids) in enumerate(log):
        if kind != "P":
            continue
        for sid in set(sids) - fresh:
            later = [rows for k, rows in log[i + 1:] if k == "D"]
            took = next((n for n, rows in enumerate(later) if sid in rows),
                        None)
            if took is not None:
                lags[sid] = took
    return lags


def _run_closed(llm, requests, clients):
    """A closed loop: `clients` requests at first, and a client's next
    one as soon as the loop has returned its last one's end."""
    engine = llm.llm_engine
    pending = list(requests)
    for _ in range(clients):
        engine.add_request(*pending.pop(0))
    outs = []
    calls = 0
    while engine.has_unfinished_requests() or engine.has_inflight():
        new = engine.step_pipelined()
        outs.extend(new)
        for out in new:
            if out.finished and pending:
                engine.add_request(*pending.pop(0))
        calls += 1
        assert calls < 2000, "pipelined engine made no progress"
    return _collect(outs)


def test_closed_loop_joiner_takes_the_next_continuation(
        tiny_llama_dir, example_prompts, monkeypatch):
    """Eight clients in a closed loop: a freed seat's next request is
    prefilled behind the decode steps in flight and joins the FIRST
    continuation dispatched after its prompt step, not the one after
    that step's fetch."""
    prompts = (example_prompts * 6)[:24]
    reqs = [(str(i), p, _params("greedy", i, 12 + 5 * (i % 4)))
            for i, p in enumerate(prompts)]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    get_efficiency_tracker().reset_for_testing()
    llm = _build(tiny_llama_dir)
    log = _log_dispatches(llm.llm_engine, monkeypatch)
    got = _run_closed(llm, reqs, clients=8)
    assert got == ref
    lags = _join_lags(log)
    assert len(lags) == len(reqs) - 8
    assert set(lags.values()) == {0}, lags
    snap = get_efficiency_tracker().snapshot()
    assert snap["decode_join_rows"] == {"device": len(lags), "fresh": 8}
    assert snap["pipeline_drains"] == {
        "memory": 0, "params": 0, "chunked_prefill": 0, "bucket": 0,
        "idle": 1}
    # One executable of each decode program, whoever handed the tokens.
    runner = llm.llm_engine.worker.model_runner
    assert runner._jit_decode_cont._cache_size() == 1


_SERIAL = {}


@pytest.mark.parametrize("sampling", SAMPLINGS)
@pytest.mark.parametrize("arrivals", ["staggered", "continuous", "closed"])
def test_first_token_hand_over_matches_serial(
        tiny_llama_dir, example_prompts, arrivals, sampling):
    """The outputs equal the serial loop's with every joiner's first
    token handed over on the device, however the requests arrive: all
    behind the first two, one a step behind a decode step in flight, or
    four clients in a closed loop."""
    prompts = (example_prompts * 3)[:8]
    reqs = [(str(i), p, _params(sampling, i, 14 + 3 * i))
            for i, p in enumerate(prompts)]
    if sampling not in _SERIAL:
        _SERIAL[sampling] = _run_serial(
            _build(tiny_llama_dir, max_num_seqs=12), reqs)
    get_efficiency_tracker().reset_for_testing()
    llm = _build(tiny_llama_dir, max_num_seqs=12)
    if arrivals == "staggered":
        got = _run_pipelined(llm, reqs, stagger_after=2)
    elif arrivals == "continuous":
        got = _run_arrivals(llm, reqs, behind_decode=True)
    else:
        got = _run_closed(llm, reqs, clients=4)
    assert got == _SERIAL[sampling]
    rows = get_efficiency_tracker().snapshot()["decode_join_rows"]
    assert rows["device"] == len(reqs) - rows["fresh"] >= len(reqs) // 2
    runner = llm.llm_engine.worker.model_runner
    assert runner._jit_decode_cont._cache_size() == 1


@pytest.mark.parametrize("ending", ["stop_token", "max_tokens_1"])
def test_joiner_whose_first_token_is_its_last(tiny_llama_dir,
                                              example_prompts, monkeypatch,
                                              ending):
    """The host learns a joiner's first token a step after the device
    used it. Where that token is a stop the row is a zombie for the one
    continuation that took it, the request's output is that token alone
    and its pages go with that step; where `max_tokens` is 1 the end is
    known ahead and the sequence takes no row at all."""
    base = [("0", example_prompts[0], _params("greedy", 0, 48)),
            ("1", example_prompts[1], _params("greedy", 1, 48))]
    late = example_prompts[2]
    first = _run_serial(_build(tiny_llama_dir),
                        [("2", late, _params("greedy", 2, 1))])["2"][0][0][0]
    sp = (SamplingParams(temperature=0.0, max_tokens=24,
                         stop_token_ids=[first])
          if ending == "stop_token" else _params("greedy", 2, 1))
    reqs = base + [("2", late, sp)]
    ref = _run_serial(_build(tiny_llama_dir), reqs)
    assert ref["2"][0][0] == (first, )
    llm = _build(tiny_llama_dir)
    engine = llm.llm_engine
    log = _log_dispatches(engine, monkeypatch)
    got = _run_pipelined(llm, reqs, stagger_after=2)
    assert got == ref
    (late_sid, ) = [sid for kind, sids in log if kind == "P"
                    for sid in sids if sid not in log[0][1]]
    took = [rows for kind, rows in log if kind == "D" and late_sid in rows]
    assert len(took) == (1 if ending == "stop_token" else 0)
    bm = engine.scheduler.block_manager
    assert not bm.block_tables and not engine.scheduler._free_guard
    assert bm.get_num_free_device_blocks() == 128


def test_prompt_over_the_budget_joins_from_its_final_chunk(
        tiny_llama_dir, example_prompts, monkeypatch):
    """A prompt of three chunk steps beside the decode batch: only the
    final chunk's step samples a token, and the sequence joins the first
    continuation dispatched after that step, by the device."""
    kw = dict(max_num_batched_tokens=32, max_model_len=128)
    long_prompt = [2, 3, 4, 5, 6] * 16                       # 80 tokens
    sp = _params("greedy", 0, 40)
    reqs = [("0", example_prompts[0], sp)]

    def run(pipelined):
        llm = _build(tiny_llama_dir, **kw)
        engine = llm.llm_engine
        log = _log_dispatches(engine, monkeypatch) if pipelined else None
        engine.add_request(*reqs[0])
        outs = []
        calls = 0
        while (engine.has_unfinished_requests() or engine.has_inflight()
               or calls < 3):
            outs.extend(engine.step_pipelined() if pipelined
                        else engine.step())
            calls += 1
            if calls == 3:
                engine.add_request("1", None, sp,
                                   prompt_token_ids=long_prompt)
            assert calls < 400
        return _collect(outs), log

    ref, _ = run(False)
    get_efficiency_tracker().reset_for_testing()
    got, log = run(True)
    assert got == ref
    prompt_steps = [sids for kind, sids in log[1:] if kind == "P"]
    assert len(prompt_steps) == 3 and prompt_steps[:2] == [[], []]
    assert list(_join_lags(log[1:]).values()) == [0]
    rows = get_efficiency_tracker().snapshot()["decode_join_rows"]
    assert rows == {"device": 1, "fresh": 1}


def test_lora_row_joins_on_the_device(tiny_llama_dir, example_prompts,
                                      tmp_path, monkeypatch):
    """A joiner's adapter rides the same snapshot as its token: a row
    with an adapter and one without join a running batch of both on the
    device, and every output equals the serial loop's."""
    from intellillm_tpu.lora.request import LoRARequest
    from tests.lora.test_lora import make_adapter
    adapter = LoRARequest("ad", 1, make_adapter(str(tmp_path / "ad"),
                                                seed=11, rank=4, alpha=8.0))
    kw = dict(enable_lora=True, max_loras=2, max_lora_rank=8)
    loras = [adapter, None, adapter, None]
    reqs = [(str(i), p, _params("greedy", i, 24))
            for i, p in enumerate(example_prompts[:4])]

    def run(pipelined):
        llm = _build(tiny_llama_dir, **kw)
        engine = llm.llm_engine
        for req, lora in zip(reqs[:2], loras):
            engine.add_request(*req, lora_request=lora)
        outs = []
        calls = 0
        while engine.has_unfinished_requests() or engine.has_inflight():
            outs.extend(engine.step_pipelined() if pipelined
                        else engine.step())
            calls += 1
            if calls == 2:
                for req, lora in zip(reqs[2:], loras[2:]):
                    engine.add_request(*req, lora_request=lora)
            assert calls < 400
        return _collect(outs)

    ref = run(False)
    assert ref["0"] != ref["1"] or ref["2"] != ref["3"]
    get_efficiency_tracker().reset_for_testing()
    assert run(True) == ref
    rows = get_efficiency_tracker().snapshot()["decode_join_rows"]
    assert rows == {"device": 2, "fresh": 2}


@pytest.mark.parametrize("mesh", [dict(tensor_parallel_size=2),
                                  dict(data_parallel_size=2)])
def test_device_hand_over_on_a_mesh_keeps_one_continuation(
        tiny_llama_dir, example_prompts, mesh):
    """On a mesh of several devices a continuation's `join_tokens` is
    placed one way, gathered or zeros: the continuation stays one
    executable, the gather one a decode batch, and the outputs equal
    the serial loop's on one device."""
    prompts = (example_prompts * 3)[:8]
    reqs = [(str(i), p, _params("greedy", i, 14 + 3 * i))
            for i, p in enumerate(prompts)]
    if "greedy" not in _SERIAL:
        _SERIAL["greedy"] = _run_serial(
            _build(tiny_llama_dir, max_num_seqs=12), reqs)
    get_efficiency_tracker().reset_for_testing()
    llm = _build(tiny_llama_dir, max_num_seqs=12, **mesh)
    runner = llm.llm_engine.worker.model_runner
    # (The gather is a function of the class: its jit cache is shared.)
    gathers = runner._jit_join_gather._cache_size()
    got = _run_arrivals(llm, reqs, behind_decode=True)
    assert got == _SERIAL["greedy"]
    rows = get_efficiency_tracker().snapshot()["decode_join_rows"]
    assert rows["device"] >= len(reqs) // 2
    assert runner._jit_decode_cont._cache_size() == 1
    assert (runner._jit_join_gather._cache_size() - gathers
            <= len(runner._join_batches))
