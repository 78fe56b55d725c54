"""End-to-end observability: a real CPU engine run must export per-phase
timing that adds up, count XLA compiles exactly once per jit bucket, and
leave an ordered flight-recorder trace per request.

These are the PR's acceptance tests — they drive the full stack
(LLM → LLMEngine → Scheduler → Worker → ModelRunner) rather than the
obs primitives in isolation (tests/obs/ covers those).
"""
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.engine import metrics as metrics_mod
from intellillm_tpu.engine.metrics import _PROMETHEUS
from intellillm_tpu.obs import (get_compile_tracker, get_flight_recorder,
                                get_slo_tracker, get_step_tracer)


@pytest.fixture
def fresh_obs():
    """Reset the process-global observability state around the test so
    earlier engine tests in the same process don't pollute counters."""
    get_step_tracer().reset_for_testing()
    get_compile_tracker().reset_for_testing()
    get_flight_recorder().reset_for_testing()
    get_slo_tracker().reset_for_testing()
    # (looked up now: tests/obs/test_metrics_no_prometheus.py reloads the
    # module, and the class imported before that is not the engine's)
    metrics_mod._Metrics.reset_for_testing()
    yield
    get_slo_tracker().reset_for_testing()
    metrics_mod._Metrics.reset_for_testing()


def _registry_value(name: str, label_filter=None) -> float:
    from prometheus_client import REGISTRY
    total = 0.0
    for metric in REGISTRY.collect():
        for sample in metric.samples:
            if sample.name == name and (
                    label_filter is None or
                    all(sample.labels.get(k) == v
                        for k, v in label_filter.items())):
                total += sample.value
    return total


@pytest.mark.skipif(not _PROMETHEUS, reason="needs prometheus_client")
def test_engine_run_exports_phase_breakdown(tiny_opt_dir, fresh_obs):
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01,
              disable_log_stats=False)
    engine = llm.llm_engine
    params = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    for i, prompt in enumerate(["hello my name is",
                                "the capital of france is"]):
        engine.add_request(str(i), prompt, params)
    outs = llm._run_engine(use_tqdm=False)
    assert all(len(o.outputs[0].token_ids) == 24 for o in outs)

    phase_sum = _registry_value("intellillm_step_phase_seconds_sum")
    step_sum = _registry_value("intellillm_step_time_seconds_sum")
    gap_sum = _registry_value("intellillm_step_gap_seconds_sum")
    n_steps = _registry_value("intellillm_step_time_seconds_count")
    assert n_steps > 0, "no step histogram samples exported"
    assert phase_sum > 0.0
    # Every step but the first follows one with work pending: a loop gap.
    assert _registry_value("intellillm_step_gap_seconds_count") == n_steps - 1
    # Exclusive phase accounting: the sum must cover at least 80% of the
    # loop's period (step + gap; `stats` falls in the gap) and can never
    # exceed it by more than drain jitter.
    period = step_sum + gap_sum
    assert phase_sum >= 0.8 * period, (
        f"phases cover only {phase_sum / period:.0%} of the loop period")
    assert phase_sum <= period * 1.05 + 0.005

    # The hot phases must all have fired on a prefill+decode run.
    for phase in ("schedule", "prepare_inputs", "execute", "fetch_wait",
                  "sample", "process_outputs", "detokenize", "stats"):
        assert _registry_value("intellillm_step_phase_seconds_count",
                               {"phase": phase}) > 0, f"{phase} missing"

    # The engine also keeps the last drained breakdown in-process. (The
    # last pipelined drain can be a tail finalize with no execute span,
    # so only non-emptiness is guaranteed.)
    assert engine.last_step_time > 0.0
    assert engine.last_step_phases


@pytest.mark.skipif(not _PROMETHEUS, reason="needs prometheus_client")
def test_async_engine_accounts_for_the_whole_loop_period(tiny_opt_dir,
                                                         fresh_obs):
    """Through AsyncLLMEngine the loop period (step + loop gap) is covered
    by named phases, the between-step ones included: admit and stream_out
    on the loop thread, handoff and handback across the threads."""
    import asyncio

    from intellillm_tpu.engine.arg_utils import AsyncEngineArgs
    from intellillm_tpu.engine.async_llm_engine import AsyncLLMEngine
    from intellillm_tpu.obs.tracing import PHASES

    args = AsyncEngineArgs(model=tiny_opt_dir, dtype="float32",
                           max_model_len=128,
                           num_device_blocks_override=128, max_num_seqs=8,
                           max_paddings=512, swap_space=0.01,
                           disable_log_requests=True)
    params = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)

    async def run():
        engine = AsyncLLMEngine.from_engine_args(args)

        async def one(i):
            n = 0
            async for out in engine.generate(
                    f"hello my name is number {i}", params, str(i)):
                n = len(out.outputs[0].token_ids)
            return n

        # two waves, so that requests are admitted while others decode
        first = [asyncio.ensure_future(one(i)) for i in range(4)]
        await asyncio.sleep(0.05)
        counts = await asyncio.gather(*first, *(one(i) for i in range(4, 8)))
        engine._background_loop_unshielded.cancel()
        return counts

    assert asyncio.run(run()) == [24] * 8

    by_phase = {phase: _registry_value("intellillm_step_phase_seconds_sum",
                                       {"phase": phase})
                for phase in PHASES}
    period = (_registry_value("intellillm_step_time_seconds_sum")
              + _registry_value("intellillm_step_gap_seconds_sum"))
    assert _registry_value("intellillm_step_gap_seconds_count") > 0
    covered = sum(by_phase.values())
    assert covered >= 0.95 * period, (
        f"phases cover {covered / period:.1%} of the loop period: "
        f"{by_phase}")
    assert covered <= period * 1.05 + 0.005
    for phase in ("fetch_wait", "process_outputs", "stats", "stream_out",
                  "admit", "handoff", "handback"):
        assert by_phase[phase] > 0.0, f"{phase} never recorded"


@pytest.mark.skipif(not _PROMETHEUS, reason="needs prometheus_client")
def test_engine_run_exports_the_device_timeline(tiny_opt_dir, fresh_obs):
    """A real run through the runner's dispatch sites: every program's
    device seconds and the dry seconds by phase reach /metrics and tile
    the loop's period."""
    from intellillm_tpu.obs.tracing import IDLE_PHASES

    # (the efficiency tracker's counters outlive an engine: a delta)
    dispatched_before = _registry_value("intellillm_dispatch_total")
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01,
              disable_log_stats=False)
    engine = llm.llm_engine
    # every child exists at 0 before the first step
    assert _registry_value("intellillm_device_busy_seconds_total") == 0.0
    from prometheus_client import REGISTRY
    phases = {s.labels["phase"] for m in REGISTRY.collect()
              for s in m.samples
              if s.name == "intellillm_device_idle_seconds_total"}
    assert phases == set(IDLE_PHASES)

    params = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    for i, prompt in enumerate(["hello my name is",
                                "the capital of france is"]):
        engine.add_request(str(i), prompt, params)
    llm._run_engine(use_tqdm=False)

    busy = _registry_value("intellillm_device_busy_seconds_total")
    idle = _registry_value("intellillm_device_idle_seconds_total")
    period = (_registry_value("intellillm_step_time_seconds_sum")
              + _registry_value("intellillm_step_gap_seconds_sum"))
    for program in ("mixed", "decode_fused", "decode_cont"):
        assert _registry_value("intellillm_device_busy_seconds_total",
                               {"program": program}) > 0.0, program
    # The device was dry at least until the first dispatch returned.
    assert _registry_value("intellillm_device_idle_seconds_total",
                           {"phase": "execute"}) > 0.0
    # Busy and idle tile the loop's period: every step but the last was
    # retired inside it, and no second is counted twice. (The arithmetic
    # is held exactly, on a scripted clock, in tests/obs/test_tracing.py.)
    assert 0.5 * period < busy + idle <= period * (1 + 1e-6)
    totals = get_step_tracer().device_totals()
    assert sum(totals["busy_by_program"].values()) == pytest.approx(busy)
    assert sum(totals["idle_by_phase"].values()) == pytest.approx(idle)
    # the tracer was told of every dispatch the efficiency tracker counted
    assert _registry_value("intellillm_dispatch_total") > dispatched_before
    # nothing is left behind once the engine waits for traffic
    assert not get_step_tracer()._pending


def test_compile_counters_once_per_bucket(tiny_opt_dir, fresh_obs):
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01)
    engine = llm.llm_engine
    params = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)

    engine.add_request("11", "hello my name is", params)
    llm._run_engine(use_tqdm=False)
    snap1 = get_compile_tracker().snapshot()
    # Prompts execute as chunk rows of the mixed program — there is no
    # separate "prefill" executable anymore.
    assert snap1["compiles"].get("mixed") == 1, snap1
    assert "prefill" not in snap1["compiles"], snap1
    decode_compiles1 = sum(v for k, v in snap1["compiles"].items()
                           if k.startswith("decode"))
    assert decode_compiles1 >= 1, snap1
    assert snap1["live_executables"] == sum(snap1["compiles"].values())

    # Identical second request: every bucket is warm — zero new compiles,
    # only cache hits.
    engine.add_request("12", "hello my name is", params)
    llm._run_engine(use_tqdm=False)
    snap2 = get_compile_tracker().snapshot()
    assert snap2["compiles"] == snap1["compiles"], (
        f"cache hit recompiled: {snap1['compiles']} -> {snap2['compiles']}")
    assert sum(snap2["cache_hits"].values()) > sum(
        snap1["cache_hits"].values())


def test_flight_recorder_traces_request_lifecycle(tiny_opt_dir, fresh_obs):
    llm = LLM(model=tiny_opt_dir, dtype="float32",
              num_device_blocks_override=128, max_model_len=128,
              max_num_seqs=8, max_paddings=512, swap_space=0.01)
    engine = llm.llm_engine
    params = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    engine.add_request("21", "the cat runs fast and the dog", params)
    llm._run_engine(use_tqdm=False)

    trace = get_flight_recorder().get_trace("21")
    assert trace is not None
    events = [e["event"] for e in trace]
    # Ordered lifecycle: arrival → admission → scheduling → prefill →
    # first token → finish, with monotonically nondecreasing timestamps.
    for a, b in [("arrived", "queued"), ("queued", "scheduled"),
                 ("scheduled", "prefill_start"),
                 ("prefill_start", "first_token"),
                 ("first_token", "finished")]:
        assert events.index(a) < events.index(b), events
    assert all(trace[i]["ts"] <= trace[i + 1]["ts"]
               for i in range(len(trace) - 1))
    assert trace[events.index("finished")].get("detail") == "length"
    # Finished: moved off the live table into the finished ring.
    assert "21" not in get_flight_recorder().live_request_ids()
    assert any(x["request_id"] == "21"
               for x in get_flight_recorder().recent_finished())

    # The finish fed the SLO tracker exactly once, with metrics derived
    # from this trace.
    s = get_slo_tracker().summary()
    assert s["window"] == 1
    assert s["finished_total"] == {"length": 1}
    assert s["ttft_ms"]["p50"] > 0.0
    assert s["tpot_ms"]["p50"] >= 0.0
