"""Mixed-step (chunked prefill) telemetry attribution.

Acceptance: a chunked-prefill run must report real prefill vs decode
token counts with NO double counting — every prompt token appears under
`intellillm_tokens_total{phase=prefill,kind=real}` exactly once (across
however many chunks it was split into), every decode row exactly once
under phase=decode — plus sane fill ratios and MFU inputs, and the
mixed flat-batch program tracked under its own "mixed" label in the
XLA compile tracker.
"""
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_compile_tracker, get_efficiency_tracker

PROMPTS = [
    "hello my name is",
    "the president of the united states is",
    "the capital of france is",
    " ".join(["the cat runs fast and the dog"] * 4),  # 28 tokens
]
MAX_TOKENS = 8


@pytest.fixture
def trackers():
    eff = get_efficiency_tracker()
    comp = get_compile_tracker()
    eff.reset_for_testing()
    comp.reset_for_testing()
    yield eff, comp
    eff.reset_for_testing()
    comp.reset_for_testing()


def test_mixed_steps_attribute_tokens_exactly_once(tiny_opt_dir, trackers):
    eff, comp = trackers
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, num_decode_steps=1,
              enable_chunked_prefill=True, max_num_batched_tokens=8)
    # Drop warm-up dispatches: only the serving steps should be counted.
    # The reset also wipes the FLOPs model derived at engine init, so
    # re-derive it — the MFU assertions below need a denominator input.
    eff.reset_for_testing()
    comp.reset_for_testing()
    eff.configure_model(llm.llm_engine.model_config)

    engine = llm.llm_engine
    tok = engine.tokenizer
    prompt_lens = [len(tok.encode(p)) for p in PROMPTS]
    for i, p in enumerate(PROMPTS):
        engine.add_request(str(i), p, SamplingParams(
            temperature=0.0, max_tokens=MAX_TOKENS, ignore_eos=True))
    outs = list(llm._run_engine(use_tqdm=False))
    assert all(len(o.outputs[0].token_ids) == MAX_TOKENS for o in outs)

    snap = eff.snapshot()
    tokens = snap["tokens_total"]

    # Every prompt token prefilled exactly once across all its chunks
    # (roomy pool → no preemption → no re-prefill), despite prompts
    # being split by the 8-token budget and sharing flat batches with
    # decode rows.
    assert tokens["prefill"]["real"] == sum(prompt_lens), (
        f"prefill real tokens {tokens['prefill']['real']} != "
        f"prompt tokens {sum(prompt_lens)} — chunk tokens double- or "
        "under-counted")

    # Each generated token except the final-chunk sample comes from one
    # real decode row in exactly one step.
    expected_decode = sum(MAX_TOKENS - 1 for _ in PROMPTS)
    assert tokens["decode"]["real"] == expected_decode, (
        f"decode real tokens {tokens['decode']['real']} != "
        f"{expected_decode} — decode rows double-counted or chunk rows "
        "leaked into the decode phase")

    # Flat-batch padding is accounted (pad > 0: budget 8 pads to the
    # 16-row token bucket) and ratios stay in range.
    assert tokens["decode"]["pad"] > 0 or tokens["prefill"]["pad"] > 0
    assert snap["pad_fraction"] is not None and 0 < snap["pad_fraction"] < 1
    fills = snap["fill_ratio_avg"]
    assert 0 < fills["prefill"]["batch"] <= 1
    assert 0 < fills["decode"]["batch"] <= 1
    # MFU inputs: steps counted, FLOPs model derived.
    assert snap["steps"] > 0
    assert snap["flops_per_token"] and snap["flops_per_token"] > 0

    # The dispatch boundary counts each mixed step once (not once per
    # side), every row one token: chunk tokens + decode rows, and the
    # context each row's attention walks (its position + 1).
    mixed = snap["programs"]["mixed"]
    assert mixed["idle"] + mixed["busy"] == snap["steps"]
    assert mixed["row_steps"] == sum(prompt_lens) + expected_decode
    expected_ctx = sum(n * (n + 1) // 2 for n in prompt_lens) + sum(
        n + j for n in prompt_lens for j in range(1, MAX_TOKENS))
    assert mixed["context_tokens"] == expected_ctx
    # ... and what the kernel's page walk moves for those rows: whole
    # pages of the context less the row's own token.
    # (No chunk fills a whole span of rows at this budget, so every row is
    # a tile of one: tests/worker/test_mixed_run_starts.py holds the rule.)
    bs = engine.cache_config.block_size
    pages = lambda ctx: -(-(ctx - 1) // bs)
    assert mixed["walked_tokens"] == bs * (
        sum(pages(c) for n in prompt_lens for c in range(1, n + 1))
        + sum(pages(n + j) for n in prompt_lens
              for j in range(1, MAX_TOKENS)))
    tiles = snap["mixed_tile_rows"]
    assert tiles == {"one": mixed["row_steps"], "many": 0}
    # the serial step() fetches each step before it dispatches the next
    assert mixed["busy"] == 0 or engine.pipeline_enabled

    # The mixed flat-batch program is tracked under its own label.
    csnap = comp.snapshot()
    mixed_programs = [p for p in csnap["compiles"] if p == "mixed"]
    assert mixed_programs, (
        f"no 'mixed' program in compile tracker: {csnap['compiles']}")


def test_chunked_off_still_runs_only_mixed_family(tiny_opt_dir, trackers):
    """--disable-chunked-prefill changes ADMISSION (whole-prompt chunks),
    not execution: the compile tracker must show only the mixed program
    family — the legacy homogeneous prefill program is gone — and
    prefill tokens still attribute exactly once."""
    eff, comp = trackers
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, num_decode_steps=1,
              enable_chunked_prefill=False)
    eff.reset_for_testing()
    comp.reset_for_testing()
    engine = llm.llm_engine
    tok = engine.tokenizer
    prompt_lens = [len(tok.encode(p)) for p in PROMPTS]
    for i, p in enumerate(PROMPTS):
        engine.add_request(str(i), p, SamplingParams(
            temperature=0.0, max_tokens=MAX_TOKENS, ignore_eos=True))
    list(llm._run_engine(use_tqdm=False))

    compiles = comp.snapshot()["compiles"]
    assert "mixed" in compiles, compiles
    allowed = {"mixed", "decode_fused", "decode_cont", "decode_teacher"}
    assert set(compiles) <= allowed, (
        f"non-mixed-family program dispatched: {compiles}")
    tokens = get_efficiency_tracker().snapshot()["tokens_total"]
    assert tokens["prefill"]["real"] == sum(prompt_lens)


def test_fused_decode_programs_count_rows_steps_and_context(tiny_opt_dir,
                                                            trackers):
    """K-step decode programs: row_steps is rows x substeps (what
    `tokens_total{phase=decode,kind=real}` counts too), and a row's
    substep j walks a context one token longer than substep j - 1's."""
    eff, _ = trackers
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, num_decode_steps=4)
    eff.reset_for_testing()
    engine = llm.llm_engine
    tok = engine.tokenizer
    prompt_lens = [len(tok.encode(p)) for p in PROMPTS]
    n_out = 17
    for i, p in enumerate(PROMPTS):
        engine.add_request(str(i), p, SamplingParams(
            temperature=0.0, max_tokens=n_out, ignore_eos=True))
    outs = list(llm._run_engine(use_tqdm=False))
    assert all(len(o.outputs[0].token_ids) == n_out for o in outs)

    snap = eff.snapshot()
    programs = snap["programs"]
    decode = [programs["decode_fused"], programs["decode_cont"]]
    assert programs["decode_fused"]["row_steps"] > 0
    if engine.pipeline_enabled:
        assert programs["decode_cont"]["row_steps"] > 0
    # prompts are prefilled by `mixed`, one row a token
    assert programs["mixed"]["row_steps"] >= sum(prompt_lens)
    decode_rows = sum(d["row_steps"] for d in decode)
    assert (decode_rows + programs["mixed"]["row_steps"] - sum(prompt_lens)
            == snap["tokens_total"]["decode"]["real"])
    # the mean context of a decode row-step lies between the shortest
    # prompt and the longest finished sequence (overshoot rows included)
    mean_ctx = sum(d["context_tokens"] for d in decode) / decode_rows
    assert min(prompt_lens) < mean_ctx < max(prompt_lens) + n_out + 8
    # first fused step of the batch: contexts n+1 .. n+4 for each row,
    # so no decode program can have walked less than that
    first = sum(4 * (n + 1) + 6 for n in prompt_lens)
    assert sum(d["context_tokens"] for d in decode) >= first
    # the page walk moves whole pages of what the pool holds when the
    # K=4 program starts: the context a substep attends to, less its own
    # and the staged tokens (at most 4), rounded up to a page
    bs = engine.cache_config.block_size
    for d in decode:
        assert d["walked_tokens"] % bs == 0
        assert (d["context_tokens"] - 4 * d["row_steps"]
                <= d["walked_tokens"]
                < d["context_tokens"] + bs * d["row_steps"])


def test_joined_continuation_counts_the_joiners_rows_and_contexts(
        tiny_opt_dir, trackers):
    """A `decode_cont` dispatch reports the re-snapshot it ran: a row
    that joined at it counts with its own context (prompt + 1), a row
    whose budget the earlier steps cover no longer counts, so
    context_tokens / row_steps stays the mean context a decode
    row-substep reads."""
    eff, _ = trackers
    k = 4
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, num_decode_steps=k)
    engine = llm.llm_engine
    if not engine.pipeline_enabled:
        pytest.skip("pipelined stepping is off")
    eff.reset_for_testing()
    prompt_lens = {str(i): len(engine.tokenizer.encode(p))
                   for i, p in enumerate(PROMPTS)}
    n_out = {"0": 30, "1": 1 + k, "2": 18, "3": 22}
    runner = engine.worker.model_runner
    snapshots = []
    dispatch = runner.execute_decode_cont

    def recording(cont, *args, **kwargs):
        snapshots.append([rid for rid, _ in cont.rows])
        return dispatch(cont, *args, **kwargs)

    runner.execute_decode_cont = recording
    pending = list(enumerate(PROMPTS))
    while (engine.has_unfinished_requests() or engine.has_inflight()
           or pending):
        # "0" and "1" start together; the others arrive behind decode.
        if pending and (len(pending) > 2
                        or engine._newest_decode_inflight() is not None):
            i, prompt = pending.pop(0)
            engine.add_request(str(i), prompt, SamplingParams(
                temperature=0.0, max_tokens=n_out[str(i)], ignore_eos=True))
        engine.step_pipelined()

    snap = eff.snapshot()
    # "2" and "3" arrive behind decode: their prompt step is in flight
    # when the next continuation takes them, their token on the device.
    assert snap["decode_join_rows"] == {"device": 2, "fresh": 2}
    assert snap["programs"]["decode_fused"]["idle"] == 1
    # "1" is done after the fresh step (1 + K tokens): no continuation
    # has a row for it; the joiners appear.
    assert all("1" not in rows for rows in snapshots)
    assert any("2" in rows for rows in snapshots)
    # Every continuation's row-substeps and contexts, from the requests
    # alone: a row's j-th decode step starts at prompt + 1 + K * j.
    steps_before = {"0": 1, "1": 1, "2": 0, "3": 0}    # the fresh step
    row_steps = context = 0
    for rows in snapshots:
        for rid in rows:
            ctx = prompt_lens[rid] + 1 + k * steps_before[rid]
            row_steps += k
            context += k * ctx + k * (k - 1) // 2
            steps_before[rid] += 1
    cont = snap["programs"]["decode_cont"]
    assert (cont["row_steps"], cont["context_tokens"]) == (row_steps,
                                                           context)
    bs = engine.cache_config.block_size
    assert cont["walked_tokens"] % bs == 0
    assert (cont["context_tokens"] - k * cont["row_steps"]
            <= cont["walked_tokens"]
            < cont["context_tokens"] + bs * cont["row_steps"])
