"""The `mixed` program's head runs on the rows that emit.

`_execute_mixed` knows the rows of a step whose token is a result (a
decode row, the last row of a final chunk). Where those, made up to a
bucket of their own, are fewer than the rows the step holds, it hands
the program `emit_rows`, and the head and the sampler run on those rows
alone, or not at all where none emits. No switch selects the old form,
so the oracle is made here: the same program over the same arguments
without `emit_rows` (`_compute_logits_and_sample` over every row), run
just before each dispatch on the caches the dispatch is about to take.
"""
import jax
import numpy as np
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_efficiency_tracker
from intellillm_tpu.obs.efficiency import HEAD_ROW_KINDS

LONG = " ".join(["the cat runs fast and the dog"] * 12)      # 84 tokens
MID = " ".join(["the president of the united states is"] * 5)  # 35 tokens
SHORT = ["hello my name is", "the capital of france is",
         "the cat runs fast and the dog"]
# Positional places of `_decode_fn_single`'s arguments.
CONTEXT_LENS, FETCH_INDICES = 5, 17


def _llm(model_dir, **kw):
    kw = {"max_num_batched_tokens": 32, "num_decode_steps": 1, **kw}
    return LLM(model=model_dir, dtype="float32",
               num_device_blocks_override=128, max_model_len=128,
               max_num_seqs=8, max_paddings=512,
               enable_chunked_prefill=True, **kw)


class Recorder:
    """Every `mixed` dispatch of a runner, and beside each one that
    carried `emit_rows` what the all-rows form gives of the same
    arguments."""

    def __init__(self, llm):
        self.runner = runner = llm.llm_engine.worker.model_runner
        self.steps = []
        program = runner._jit_decode_single
        # The same function, nothing donated: the caches it reads are
        # the real call's to take afterwards.
        oracle = jax.jit(
            runner._decode_fn_single,
            static_argnames=("num_samples", "plp_k", "logprob_k",
                             "do_topk", "do_topp", "do_minp",
                             "do_penalties", "do_random", "do_numerics"))

        def recording(*args, **kwargs):
            step = {"rows": np.asarray(args[CONTEXT_LENS]).shape[0],
                    "live": int((np.asarray(args[CONTEXT_LENS]) > 0).sum()),
                    "plp_k": kwargs.get("plp_k", 0), "emit": None,
                    "t1": kwargs.get("num_samples", 1),
                    "kt": kwargs["logprob_k"]}
            if "emit_rows" in kwargs:
                step["emit"] = emit = np.asarray(kwargs["emit_rows"])
                rest = {k: v for k, v in kwargs.items() if k != "emit_rows"}
                every = list(args)
                if len(every) > FETCH_INDICES and every[
                        FETCH_INDICES] is not None:
                    # The escape's indices are of the gathered rows.
                    every[FETCH_INDICES] = emit[
                        np.asarray(every[FETCH_INDICES])]
                want = oracle(*every, **rest)
                step["want"] = [np.asarray(a) for a in want[:-1]]
                jax.block_until_ready(want)
            got = program(*args, **kwargs)
            step["got"] = [np.asarray(a) for a in got[:-1]]
            self.steps.append(step)
            return got

        runner._jit_decode_single = recording

    def gathered(self):
        return [s for s in self.steps if s["emit"] is not None]


def _sample_columns(runner, packed, t1, kt):
    """The packed output's sample columns as `_unpack` reads them, and
    the counter columns behind them."""
    width = 2 * t1 + 2 * kt
    return runner._unpack(packed, t1, 1, kt), packed[:, width:]


def _hold_to_oracle(runner, step):
    """A gathered step's outputs against the all-rows form's."""
    b, t1, kt = step["rows"], step["t1"], step["kt"]
    emit = step["emit"]
    emitting = emit[emit < b]
    assert len(emit) < b and (emit[len(emitting):] == b).all()
    got, want = step["got"][0], step["want"][0]
    assert got.shape == want.shape and got.dtype == want.dtype
    (s, lp, ids, tlp), counters = _sample_columns(runner, got, t1, kt)
    (s0, lp0, ids0, tlp0), counters0 = _sample_columns(runner, want, t1, kt)
    # The emitting rows: tokens and panel ids equal, logprobs to
    # float32's rounding (the head's matmul has another row count).
    np.testing.assert_array_equal(s[emitting], s0[emitting])
    np.testing.assert_array_equal(ids[emitting], ids0[emitting])
    np.testing.assert_allclose(lp[emitting], lp0[emitting], atol=2e-5)
    np.testing.assert_allclose(tlp[emitting], tlp0[emitting], atol=2e-5)
    # Every other row is zeros.
    others = np.setdiff1d(np.arange(b), emitting)
    width = got.shape[1] - counters.shape[1]
    assert not got[others, :width].any()
    # The per-row counters are over all the rows, as they were.
    np.testing.assert_array_equal(counters, counters0)
    # What a continuation would take as the rows' first tokens.
    first = np.asarray(runner._first_tokens_fn(got, length=b))
    first0 = np.asarray(runner._first_tokens_fn(want, length=b))
    np.testing.assert_array_equal(first[emitting], first0[emitting])
    return emitting


def _run(llm, requests):
    for i, (prompt, params) in enumerate(requests):
        llm.llm_engine.add_request(str(i), prompt, params)
    outs = list(llm._run_engine(use_tqdm=False))
    return sorted(outs, key=lambda o: int(o.request_id))


GREEDY = dict(temperature=0.0, max_tokens=5, ignore_eos=True, logprobs=2)


def test_a_final_and_a_mid_prompt_chunk(tiny_opt_dir):
    """(a) and (c): chunk steps with no emitting row run no head and
    return zeros; a final chunk's last row emits from a head of 16."""
    llm = _llm(tiny_opt_dir, num_decode_steps=4)
    rec = Recorder(llm)
    outs = _run(llm, [(LONG, SamplingParams(**GREEDY)),
                      (MID, SamplingParams(**GREEDY))])
    assert all(len(o.outputs[0].token_ids) == 5 for o in outs)
    gathered = rec.gathered()
    counts = [len(_hold_to_oracle(rec.runner, s)) for s in gathered]
    # Mid-prompt steps (nothing emits) and final ones (one or two rows).
    assert 0 in counts and max(counts) >= 1
    for step, n in zip(gathered, counts):
        if n == 0:
            width = 2 * step["t1"] + 2 * step["kt"]
            assert not step["got"][0][:, :width].any()
    # And whole requests agree with an engine whose steps are all too
    # small to gather (16 rows: `E >= b`).
    small = _llm(tiny_opt_dir, num_decode_steps=4,
                 max_num_batched_tokens=16)
    rec_small = Recorder(small)
    outs_small = _run(small, [(LONG, SamplingParams(**GREEDY)),
                              (MID, SamplingParams(**GREEDY))])
    assert not rec_small.gathered()
    for a, b in zip(outs, outs_small):
        assert a.outputs[0].token_ids == b.outputs[0].token_ids


def test_chunk_rows_beside_decode_rows(tiny_opt_dir):
    """(b): a K = 1 engine's co-batched steps: the decode rows and a
    final chunk's last row emit, a chunk's other rows do not."""
    llm = _llm(tiny_opt_dir, max_num_batched_tokens=64)
    rec = Recorder(llm)
    requests = [(p, SamplingParams(**GREEDY)) for p in SHORT]
    requests += [(LONG, SamplingParams(**GREEDY))]
    outs = _run(llm, requests)
    assert all(len(o.outputs[0].token_ids) == 5 for o in outs)
    seen = [(_hold_to_oracle(rec.runner, s), s) for s in rec.gathered()]
    # Some step held decode rows beside chunk rows: several emit, and
    # not every live row does.
    assert any(1 < len(e) < s["live"] for e, s in seen)
    # A step of decode rows alone binds as it always did.
    assert any(s["emit"] is None and s["live"] <= 16 for s in rec.steps)


def test_best_of_fan_out_on_a_final_chunk(tiny_opt_dir):
    """(d): RANDOM `best_of` > 1: the emitting row draws every stream of
    its fan-out from its own seed."""
    llm = _llm(tiny_opt_dir)
    rec = Recorder(llm)
    params = SamplingParams(temperature=0.8, n=3, best_of=3, max_tokens=4,
                            ignore_eos=True, seed=1234)
    outs = _run(llm, [(LONG, params)])
    assert len(outs[0].outputs) == 3
    emitting = [(len(_hold_to_oracle(rec.runner, s)), s["t1"])
                for s in rec.gathered()]
    # The final chunk's step draws the fan-out's bucket of streams.
    assert (1, 4) in emitting


def test_a_logits_processor_row(tiny_opt_dir):
    """(e): the escape's raw logits are the emitting row's, indexed
    among the gathered rows; the processor decides the token."""
    llm = _llm(tiny_opt_dir)
    rec = Recorder(llm)
    forced = 7

    def only(token_ids, logits):
        out = np.full_like(logits, -1e9)
        out[forced] = 0.0
        return out

    params = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True,
                            logits_processors=[only])
    outs = _run(llm, [(LONG, params), (MID, SamplingParams(**GREEDY))])
    assert list(outs[0].outputs[0].token_ids) == [forced] * 3
    fetched = 0
    for step in rec.gathered():
        emitting = _hold_to_oracle(rec.runner, step)
        if len(step["got"]) > 1:
            # (packed, fetched raw logits): the rows the oracle fetched.
            got, want = step["got"][1], step["want"][1]
            assert got.shape == want.shape and len(emitting)
            np.testing.assert_allclose(got[0], want[0], atol=2e-5)
            fetched += 1
    assert fetched


def test_penalties(tiny_opt_dir):
    """(f): the id lists the penalties scatter from go with their rows."""
    llm = _llm(tiny_opt_dir, max_num_batched_tokens=64)
    rec = Recorder(llm)
    params = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                            presence_penalty=0.7, frequency_penalty=0.3,
                            repetition_penalty=1.3, logprobs=2)
    outs = _run(llm, [(p, params) for p in SHORT] + [(LONG, params)])
    assert all(len(o.outputs[0].token_ids) == 6 for o in outs)
    counts = [len(_hold_to_oracle(rec.runner, s)) for s in rec.gathered()]
    assert max(counts) > 1


def test_prompt_logprobs_keep_the_head_over_every_row(tiny_opt_dir):
    """(g): every chunk row's distribution is a result: no step that
    carries a panel gathers, and the panel is whole and equal to that of
    an engine whose steps are too small to gather."""
    params = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True,
                            prompt_logprobs=2, logprobs=2)
    panels = []
    for budget in (32, 16):
        llm = _llm(tiny_opt_dir, max_num_batched_tokens=budget)
        rec = Recorder(llm)
        outs = _run(llm, [(LONG, params)])
        assert all(s["emit"] is None for s in rec.steps if s["plp_k"])
        assert any(s["plp_k"] and s["rows"] == budget for s in rec.steps)
        panels.append(outs[0].prompt_logprobs)
    wide, narrow = panels
    n = len(wide)
    assert n == len(narrow) > 80 and wide[0] is None
    for a, b in zip(wide[1:], narrow[1:]):
        assert a is not None and sorted(a) == sorted(b)
        for token, lp in a.items():
            assert lp == pytest.approx(b[token], abs=2e-5)


def test_head_rows_counter_over_a_prompt_of_three_chunks(tiny_opt_dir):
    """intellillm_head_rows_total{program="mixed"}: a prompt of 84
    tokens in chunks of 32: `batch` the rows of each step, `computed`
    0, 0, 16, `emitting` 0, 0, 1."""
    from prometheus_client import REGISTRY
    eff = get_efficiency_tracker()
    eff.reset_for_testing()
    llm = _llm(tiny_opt_dir, num_decode_steps=4)
    eff.reset_for_testing()

    def sample(program, kind):
        return REGISTRY.get_sample_value(
            "intellillm_head_rows_total",
            {"program": program, "kind": kind}) or 0.0

    # Every child is there from the first scrape.
    for program in ("mixed", "decode_fused", "decode_cont"):
        for kind in HEAD_ROW_KINDS:
            assert REGISTRY.get_sample_value(
                "intellillm_head_rows_total",
                {"program": program, "kind": kind}) is not None
    seen = []
    engine = llm.llm_engine
    engine.add_request("0", LONG, SamplingParams(
        temperature=0.0, max_tokens=1, ignore_eos=True))
    before = {k: sample("mixed", k) for k in HEAD_ROW_KINDS}
    while engine.has_unfinished_requests():
        engine.step()
        now = {k: sample("mixed", k) for k in HEAD_ROW_KINDS}
        seen.append({k: int(now[k] - before[k]) for k in HEAD_ROW_KINDS})
        before = now
    seen = [s for s in seen if s["batch"]]
    assert [s["batch"] for s in seen] == [32, 32, 32]
    assert [s["computed"] for s in seen] == [0, 0, 16]
    assert [s["emitting"] for s in seen] == [0, 0, 1]
    assert eff.snapshot()["head_rows"]["mixed"] == {
        "batch": 96, "computed": 16, "emitting": 1}
    eff.reset_for_testing()


def test_decode_programs_compute_every_row(tiny_opt_dir):
    eff = get_efficiency_tracker()
    eff.reset_for_testing()
    llm = _llm(tiny_opt_dir, num_decode_steps=4)
    eff.reset_for_testing()
    _run(llm, [(p, SamplingParams(**GREEDY)) for p in SHORT])
    rows = eff.snapshot()["head_rows"]
    decode = [rows[p] for p in ("decode_fused", "decode_cont")]
    assert sum(r["batch"] for r in decode) > 0
    for r in decode:
        assert r["computed"] == r["batch"] >= r["emitting"]
    eff.reset_for_testing()


def test_every_row_emitting_lowers_to_the_all_rows_program(tiny_opt_dir):
    """A step handed no `emit_rows` (`E >= b`, a `prompt_logprobs`
    panel) lowers to the text of the program written out here the way it
    stood before the head could run on fewer rows: the model, the head
    and the sampler over all the rows, `_pack`."""
    import jax.numpy as jnp

    from intellillm_tpu.layers.attention import AttentionMetadata

    llm = _llm(tiny_opt_dir)
    runner = llm.llm_engine.worker.model_runner
    kv_struct = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        llm.llm_engine.worker.cache_engine.device_cache)
    b, w = 32, 16

    def _decode_fn_single(params, kv_caches, token_ids, positions,
                          block_tables, context_lens, temperatures, top_ks,
                          top_ps, min_ps, seeds, pres_pen, freq_pen, rep_pen,
                          prompt_tokens, output_tokens, run_starts=None):
        bs = runner.block_size
        pos = positions[:, 0]
        pool = kv_caches[runner._kv0][0]
        slot = (jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                                    axis=1)[:, 0] * bs + pos % bs)
        meta = AttentionMetadata(
            slot_mapping=jnp.where(context_lens > 0, slot,
                                   pool.shape[0] * bs)[:, None],
            context_lens=context_lens, block_tables=block_tables,
            mesh=runner.mesh, state_slots=None, run_starts=run_starts)
        hidden, new_caches, counters = runner._call_model(
            params, token_ids, pos[:, None], kv_caches, meta, None)
        (sampled, lp, tk_ids, tk_lp, _, _,
         _) = runner._compute_logits_and_sample(
            params, hidden[:, 0], temperatures, top_ks, top_ps, min_ps,
            seeds, pres_pen, freq_pen, rep_pen, prompt_tokens, output_tokens,
            None, num_samples=1, logprob_k=1, do_topk=False, do_topp=False,
            do_minp=False, do_penalties=False, do_random=True)
        packed = runner._pack(sampled, lp, tk_ids[:, None, :],
                              tk_lp[:, None, :], counters=counters,
                              live=context_lens > 0)
        return packed, new_caches

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    before = jax.jit(_decode_fn_single, donate_argnames=("kv_caches", )
                     ).lower(
        runner.params, kv_struct, sds(i32, b, 1), sds(i32, b, 1),
        sds(i32, b, w), sds(i32, b), sds(f32, b), sds(i32, b), sds(f32, b),
        sds(f32, b), sds(u32, b), sds(f32, b), sds(f32, b), sds(f32, b),
        None, None, run_starts=sds(i32, b)).as_text()
    now = runner.lower_mixed_step(kv_struct, b, w).as_text()
    assert now == before
    vocab = runner.vocab_size
    assert f"tensor<{b}x{vocab}xf32>" in now
    # ... and the other form is another program: a head of 16 rows
    # under a conditional, no logits of every row.
    gathered = runner.lower_mixed_step(kv_struct, b, w,
                                       emit_slots=16).as_text()
    assert f"tensor<{b}x{vocab}xf32>" not in gathered
    assert f"tensor<16x{vocab}xf32>" in gathered
    assert "stablehlo.case" in gathered or "stablehlo.if" in gathered
