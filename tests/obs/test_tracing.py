"""StepTracer unit tests: span timing, exclusive nesting, drain
semantics, the loop gap, thread crossings, profiler annotations, and the
disabled fast path."""
import threading
import time

import pytest

from intellillm_tpu.obs.tracing import (PHASES, _NULL_SPAN, StepTracer,
                                        get_step_tracer)


def test_span_measures_elapsed_time():
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    with tracer.span("execute"):
        time.sleep(0.02)
    phases, total = tracer.end_step()
    assert set(phases) == {"execute"}
    assert 0.015 <= phases["execute"] <= 0.2
    assert total >= phases["execute"]


def test_nested_spans_are_exclusive():
    """A child's time must be subtracted from its parent so the phase sum
    never double-counts (and stays comparable to step wall time)."""
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    with tracer.span("schedule"):
        time.sleep(0.01)
        with tracer.span("execute"):
            time.sleep(0.02)
        time.sleep(0.01)
    phases, total = tracer.end_step()
    assert 0.015 <= phases["execute"] <= 0.2
    # Exclusive parent time is ~20ms, NOT ~40ms (child excluded).
    assert 0.015 <= phases["schedule"] <= 0.035
    assert sum(phases.values()) <= total + 1e-6


def test_same_phase_accumulates_across_spans():
    tracer = StepTracer(enabled=True)
    with tracer.span("sample"):
        time.sleep(0.005)
    with tracer.span("sample"):
        time.sleep(0.005)
    phases, _ = tracer.end_step()
    assert phases["sample"] >= 0.008


def test_end_step_drains():
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    with tracer.span("schedule"):
        pass
    phases, total = tracer.end_step()
    assert "schedule" in phases
    # Second drain: everything was consumed.
    phases2, total2 = tracer.end_step()
    assert phases2 == {}
    assert total2 == 0.0


def test_end_step_without_begin_degrades_to_phase_sum():
    tracer = StepTracer(enabled=True)
    with tracer.span("detokenize"):
        time.sleep(0.005)
    phases, total = tracer.end_step()
    assert total == sum(phases.values())


def test_disabled_tracer_is_noop():
    tracer = StepTracer(enabled=False)
    assert tracer.span("execute") is _NULL_SPAN
    tracer.begin_step()
    with tracer.span("execute"):
        time.sleep(0.002)
    assert tracer.end_step() == ({}, 0.0)


def test_known_phases_exported():
    assert PHASES == ("admit", "handoff", "schedule", "prepare_inputs",
                      "execute", "fetch_wait", "sample", "swap_copy",
                      "process_outputs", "detokenize", "stats", "handback",
                      "stream_out")


def test_global_tracer_singleton():
    t = get_step_tracer()
    assert get_step_tracer() is t
    t.reset_for_testing()
    assert t.end_step()[0] == {}


def test_fetch_wait_nested_in_sample_is_subtracted_from_it():
    """`sample` is host work only: the blocking fetch inside it is its
    own phase (the one phase in which the host waits for the device)."""
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    with tracer.span("sample"):
        with tracer.span("fetch_wait"):
            time.sleep(0.03)
        time.sleep(0.005)
    phases, total = tracer.end_step()
    assert 0.025 <= phases["fetch_wait"] <= 0.2
    assert phases["sample"] < 0.02
    assert sum(phases.values()) <= total + 1e-6


@pytest.mark.parametrize("phase", ["handoff", "handback"])
def test_crossing_begun_on_one_thread_ended_on_another_accounts_once(phase):
    tracer = StepTracer(enabled=True)
    tracer.begin_crossing()

    def other_thread():
        time.sleep(0.01)
        tracer.end_crossing(phase)
        tracer.end_crossing(phase)      # a second end adds nothing

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    phases, _ = tracer.end_step()
    assert set(phases) == {phase}
    assert 0.008 <= phases[phase] <= 0.2
    # never a stack span: nothing is left open behind it
    assert tracer._stack == []


def test_loop_gap_recorded_when_work_is_pending():
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    tracer.end_step(work_pending=True)
    assert tracer.last_gap is None          # nothing preceded the first step
    with tracer.span("stats"):
        time.sleep(0.01)
    tracer.begin_step()
    with tracer.span("schedule"):
        pass
    phases, total = tracer.end_step(work_pending=True)
    # the gap precedes the drained step and holds the between-step span
    assert tracer.last_gap >= phases["stats"] >= 0.008
    assert total < tracer.last_gap


def test_loop_gap_not_recorded_when_the_engine_was_idle():
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    tracer.end_step(work_pending=False)     # drained: now waiting for traffic
    time.sleep(0.01)
    tracer.begin_step()
    tracer.end_step(work_pending=True)
    assert tracer.last_gap is None
    tracer.begin_step()
    tracer.end_step()
    assert tracer.last_gap is not None


def test_disabled_tracer_new_sites_are_noops():
    tracer = StepTracer(enabled=False)
    for phase in ("fetch_wait", "process_outputs", "stats", "stream_out",
                  "admit"):
        assert tracer.span(phase) is _NULL_SPAN
    tracer.set_profiling(True)
    assert tracer._annotate is None          # no annotation either
    tracer.begin_step()
    tracer.begin_crossing()
    tracer.end_crossing("handoff")
    assert tracer.end_step(work_pending=True) == ({}, 0.0)
    tracer.begin_step()
    tracer.end_step(work_pending=True)
    assert tracer.last_gap is None
    assert tracer._acc == {}


def test_annotations_are_built_only_while_the_profile_flag_is_set(
        monkeypatch):
    import jax.profiler
    built = []

    class FakeAnnotation:
        def __init__(self, name, **kwargs):
            built.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            built.append(("exit", {}))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tracer = StepTracer(enabled=True)
    tracer.begin_step()
    with tracer.span("schedule"):
        pass
    assert built == []
    tracer.set_profiling(True)
    tracer.begin_step()
    with tracer.span("schedule"):
        with tracer.span("execute"):
            pass
    # the step's first annotation carries the step number, the rest none
    assert built == [("intellillm/schedule", {"step": 2}),
                     ("intellillm/execute", {}), ("exit", {}), ("exit", {})]
    # a span that began under the flag still closes its annotation after
    # the flag cleared; later spans build none
    span = tracer.span("sample")
    span.__enter__()
    tracer.set_profiling(False)
    span.__exit__(None, None, None)
    assert built[-2:] == [("intellillm/sample", {}), ("exit", {})]
    n = len(built)
    with tracer.span("detokenize"):
        pass
    assert len(built) == n
    phases, _ = tracer.end_step()
    assert {"schedule", "execute", "sample", "detokenize"} <= set(phases)
