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
    # an annotation carries its phase and nothing else
    assert built == [("intellillm/schedule", {}),
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


# --- the device timeline -----------------------------------------------------
#
# A patched clock and handles whose `is_ready()` is scripted: every time
# below is set by hand, so the expectations are exact.


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def monotonic(self):
        return self.t


class _Handle:
    def __init__(self, ready=False):
        self.ready = ready
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


@pytest.fixture
def clock(monkeypatch):
    from intellillm_tpu.obs import tracing
    c = _Clock()
    monkeypatch.setattr(tracing, "time", c)
    return c


def _span(tracer, clock, phase, t_in, t_out, inside=None, waits_on=None):
    """Enter `phase` at t_in, run `inside()`, exit at t_out."""
    clock.t = t_in
    with tracer.span(phase, waits_on=waits_on):
        if inside is not None:
            inside()
        clock.t = t_out


def _finishes(*handles):
    """For `_span(inside=)`: the handles' steps end while the span is open."""
    def finish():
        for h in handles:
            h.ready = True
    return finish


def _dispatch(tracer, clock, program, handle, t_in, t_call, t_out,
              bucket=None):
    """An `execute` span whose dispatch call returns at t_call."""
    def call():
        clock.t = t_call
        tracer.note_dispatch(program, handle, bucket)
    _span(tracer, clock, "execute", t_in, t_out, call)


def _fetch(tracer, clock, handle, t_in, t_out):
    """A `fetch_wait` on `handle`'s step, which ends while it blocks."""
    _span(tracer, clock, "fetch_wait", t_in, t_out, _finishes(handle),
          waits_on=handle)


def test_busy_of_two_queued_steps_splits_at_the_first_ones_completion(clock):
    tracer = StepTracer(enabled=True)
    a, b = _Handle(), _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "mixed", a, 101.0, 102.0, 103.0)
    _dispatch(tracer, clock, "decode_cont", b, 104.0, 105.0, 106.0)
    _fetch(tracer, clock, a, 107.0, 120.0)
    _fetch(tracer, clock, b, 121.0, 130.0)
    clock.t = 132.0
    _, total = tracer.end_step(work_pending=True)
    # a ran from its dispatch; b, queued behind it, from a's completion.
    assert tracer.last_device_busy == {"mixed": 18.0, "decode_cont": 10.0}
    # dry from begin_step to the first dispatch, and after b.
    assert tracer.last_device_idle == {"unannotated": 3.0, "execute": 1.0}
    assert (sum(tracer.last_device_busy.values())
            + sum(tracer.last_device_idle.values())) == total == 32.0


@pytest.mark.parametrize("found_in, stamp", [("fetch_wait", 120.0),
                                             ("process_outputs", 115.0)])
def test_completion_is_stamped_at_a_blocking_fetchs_exit_else_at_the_midpoint(
        clock, found_in, stamp):
    tracer = StepTracer(enabled=True)
    a = _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "decode_cont", a, 100.0, 102.0, 103.0)
    _span(tracer, clock, found_in, 110.0, 120.0, _finishes(a),
          waits_on=a if found_in == "fetch_wait" else None)
    clock.t = 120.0
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": stamp - 102.0}
    # the time from the stamp to the poll that found it passed under the
    # span that was open meanwhile
    assert tracer.last_device_idle.get(found_in, 0.0) == 120.0 - stamp


@pytest.mark.parametrize("away_until, busy, idle", [
    # the host is away for 100: the step ran its usual 10, not 50
    (212.0, 10.0, {"process_outputs": 91.0}),
    # found finished sooner than that: the completion stays between the
    # two polls that bracket it
    (114.0, 3.0, {}),
])
def test_a_completion_nobody_waited_for_is_placed_by_the_programs_usual_time(
        clock, away_until, busy, idle):
    tracer = StepTracer(enabled=True)
    a, b = _Handle(), _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "decode_cont", a, 100.0, 100.0, 100.0)
    _fetch(tracer, clock, a, 101.0, 110.0)
    _dispatch(tracer, clock, "decode_cont", b, 110.0, 111.0, 111.0)
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": 10.0}     # blocked on
    tracer.begin_step()
    # a pause of the host (a collector's) inside process_outputs, or none
    _span(tracer, clock, "process_outputs", 112.0, away_until, _finishes(b))
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": busy}
    assert tracer.last_device_idle == idle


def test_a_step_already_finished_when_the_fetch_begins_is_not_stamped_at_its_exit(
        clock):
    tracer = StepTracer(enabled=True)
    a = _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "mixed", a, 100.0, 101.0, 102.0)
    _span(tracer, clock, "schedule", 103.0, 104.0)
    a.ready = True                  # while the host is under no span
    _span(tracer, clock, "fetch_wait", 110.0, 111.0, waits_on=a)
    clock.t = 111.0
    tracer.end_step(work_pending=True)
    # between the polls at 104 and 110: the midpoint
    assert tracer.last_device_busy == {"mixed": 6.0}
    assert tracer.last_device_idle == {"execute": 1.0, "unannotated": 3.0,
                                       "fetch_wait": 1.0}


def test_idle_is_charged_to_the_innermost_open_span_and_never_twice(clock):
    tracer = StepTracer(enabled=True)
    a, b = _Handle(), _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "decode_cont", a, 100.0, 101.0, 101.0)

    def fetch():
        _fetch(tracer, clock, a, 102.0, 110.0)
    _span(tracer, clock, "sample", 101.0, 112.0, fetch)

    def detok():
        _span(tracer, clock, "detokenize", 113.0, 115.0)
    _span(tracer, clock, "process_outputs", 112.0, 118.0, detok)
    _span(tracer, clock, "prepare_inputs", 118.0, 121.0)
    _dispatch(tracer, clock, "decode_cont", b, 121.0, 123.0, 124.0)
    clock.t = 124.0
    phases, total = tracer.end_step(work_pending=True)
    idle = tracer.last_device_idle
    assert idle == {"execute": 1.0 + 2.0, "sample": 2.0,
                    "process_outputs": 1.0 + 3.0, "detokenize": 2.0,
                    "prepare_inputs": 3.0}
    assert tracer.last_device_busy == {"decode_cont": 9.0}
    # b is still running: its time is not counted yet, and the period is
    # tiled up to its dispatch
    assert sum(idle.values()) + 9.0 == 123.0 - 100.0
    # a phase's dry time is never more than the phase's own time
    assert all(idle[p] <= phases[p] for p in idle)


def test_dry_time_between_steps_goes_to_the_loop_gap_and_the_crossings(clock):
    tracer = StepTracer(enabled=True)
    a = _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "mixed", a, 100.0, 100.0, 100.0)
    _fetch(tracer, clock, a, 100.0, 104.0)
    tracer.end_step(work_pending=True)              # at 104
    assert tracer.last_device_busy == {"mixed": 4.0}
    assert tracer.last_device_idle == {}
    _span(tracer, clock, "stats", 104.0, 105.0)
    clock.t = 106.0
    tracer.begin_crossing()
    clock.t = 108.0
    tracer.end_crossing("handback")
    _span(tracer, clock, "stream_out", 109.0, 110.0)
    _span(tracer, clock, "admit", 110.0, 111.0)
    tracer.begin_crossing()
    clock.t = 114.0
    tracer.end_crossing("handoff")
    clock.t = 115.0
    tracer.begin_step()
    clock.t = 116.0
    _, total = tracer.end_step(work_pending=False)
    assert tracer.last_gap == 11.0 and total == 1.0
    assert tracer.last_device_idle == {
        "stats": 1.0, "loop_gap": 1.0 + 1.0 + 1.0, "handback": 2.0,
        "stream_out": 1.0, "admit": 1.0, "handoff": 3.0, "unannotated": 1.0}
    assert sum(tracer.last_device_idle.values()) == tracer.last_gap + total


def test_nothing_is_charged_while_the_engine_waits_for_traffic(clock):
    tracer = StepTracer(enabled=True)
    a = _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "mixed", a, 100.0, 101.0, 101.0)
    _fetch(tracer, clock, a, 101.0, 105.0)
    tracer.end_step(work_pending=False)             # at 105
    # an hour without traffic, then a request: admit, handoff, a step
    _span(tracer, clock, "admit", 3700.0, 3701.0)
    tracer.begin_crossing()
    clock.t = 3702.0
    tracer.end_crossing("handoff")
    tracer.begin_step()
    _span(tracer, clock, "schedule", 3702.0, 3704.0)
    clock.t = 3704.0
    _, total = tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {}
    # the dry time starts with the step, as the period does
    assert tracer.last_device_idle == {"schedule": 2.0} and total == 2.0


def test_a_step_that_found_nothing_to_do_starts_no_period(clock):
    tracer = StepTracer(enabled=True)
    clock.t = 100.0
    tracer.begin_step()
    _span(tracer, clock, "schedule", 100.0, 101.0)
    tracer.cancel_step()
    _span(tracer, clock, "admit", 500.0, 501.0)
    tracer.begin_step()
    clock.t = 502.0
    tracer.end_step()
    assert tracer.last_device_idle == {"schedule": 1.0, "unannotated": 1.0}


def test_a_fetch_that_waited_on_an_older_step_measures_neither(clock):
    """The fetch of `a` returns with `b` finished as well: the host was
    away past both, so neither ended at the exit."""
    tracer = StepTracer(enabled=True)
    a, b = _Handle(), _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "decode_cont", a, 100.0, 100.0, 100.0)
    _dispatch(tracer, clock, "mixed", b, 100.0, 100.0, 100.0)
    _span(tracer, clock, "fetch_wait", 100.0, 130.0, _finishes(a, b),
          waits_on=a)
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": 10.0, "mixed": 10.0}
    assert tracer.last_device_idle == {"fetch_wait": 10.0}
    assert tracer._usual == {}                  # nothing was measured


def test_a_loop_whose_fetches_never_block_learns_from_close_polls(clock):
    """Host-bound: every step is finished before its fetch begins, so no
    completion is measured. Polls a tenth of the step's time apart place
    it closely, and teach the usual time that a pause of the host is then
    held against."""
    tracer = StepTracer(enabled=True)
    clock.t = 100.0
    tracer.begin_step()
    for t0 in (100.0, 120.0):
        h = _Handle()
        _dispatch(tracer, clock, "decode_cont", h, t0, t0, t0)
        _span(tracer, clock, "process_outputs", t0 + 1.0, t0 + 9.5)
        # ends at 10 +- 0.5 between these two polls
        _span(tracer, clock, "detokenize", t0 + 9.5, t0 + 10.5, _finishes(h))
        _span(tracer, clock, "fetch_wait", t0 + 15.0, t0 + 15.0, waits_on=h)
    clock.t = 140.0
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": 20.0}
    assert tracer._usual == {("decode_cont", None): 10.0}
    # the collector stops the host for 300 with the third step queued
    tracer.begin_step()
    h = _Handle()
    _dispatch(tracer, clock, "decode_cont", h, 140.0, 140.0, 140.0)
    _span(tracer, clock, "sample", 141.0, 441.0, _finishes(h))
    _span(tracer, clock, "fetch_wait", 441.0, 441.0, waits_on=h)
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"decode_cont": 10.0}
    assert tracer.last_device_idle == {"sample": 291.0}
    assert tracer._usual == {("decode_cont", None): 10.0}   # not taught


def test_the_usual_time_is_kept_by_program_and_bucket(clock):
    tracer = StepTracer(enabled=True)
    clock.t = 100.0
    tracer.begin_step()
    t = 100.0
    for bucket, took in ((64, 10.0), (512, 60.0)):
        h = _Handle()
        _dispatch(tracer, clock, "mixed", h, t, t, t, bucket=bucket)
        _fetch(tracer, clock, h, t, t + took)
        t += took
    assert tracer._usual == {("mixed", 64): 10.0, ("mixed", 512): 60.0}
    # a 512-row step nobody waited for, found after a long absence
    h = _Handle()
    _dispatch(tracer, clock, "mixed", h, t, t, t, bucket=512)
    _span(tracer, clock, "process_outputs", t + 1.0, t + 400.0, _finishes(h))
    tracer.end_step(work_pending=True)
    assert tracer.last_device_busy == {"mixed": 10.0 + 60.0 + 60.0}


def test_device_dry_asks_the_list_and_the_totals_are_read_at_drains(clock):
    tracer = StepTracer(enabled=True)
    a = _Handle()
    assert tracer.device_dry()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "decode_fused", a, 100.0, 101.0, 101.0)
    assert not tracer.device_dry()
    a.ready = True
    clock.t = 103.0
    assert tracer.device_dry()
    # nothing is drained yet: a reader between drains sees the last one
    assert tracer.device_totals() == {
        "busy_by_program": {}, "idle_by_phase": {}}
    clock.t = 104.0
    tracer.end_step(work_pending=True)
    first = tracer.device_totals()
    assert first == {"busy_by_program": {"decode_fused": 1.0},
                     "idle_by_phase": {"execute": 1.0, "unannotated": 2.0}}
    _span(tracer, clock, "stats", 104.0, 106.0)
    assert tracer.device_totals() == first
    tracer.begin_step()
    tracer.end_step()
    second = tracer.device_totals()
    # busy + idle between two readings is the time between the drains
    assert second["idle_by_phase"]["stats"] == 106.0 - 104.0
    assert second["busy_by_program"] == first["busy_by_program"]
    first["busy_by_program"]["decode_fused"] = 0.0      # a copy
    assert tracer.device_totals() == second


def test_several_steps_found_finished_at_one_poll_are_spaced_evenly(clock):
    tracer = StepTracer(enabled=True)
    a, b = _Handle(), _Handle()
    clock.t = 100.0
    tracer.begin_step()
    _dispatch(tracer, clock, "mixed", a, 100.0, 100.0, 100.0)
    _dispatch(tracer, clock, "mixed", b, 100.0, 100.0, 100.0)
    a.ready = b.ready = True
    _span(tracer, clock, "fetch_wait", 109.0, 109.0)
    tracer.end_step(work_pending=True)
    # found at the enter poll (109), the last poll at 100: 103 and 106
    assert tracer.last_device_busy == {"mixed": 6.0}
    assert tracer.last_device_idle == {"unannotated": 3.0}


def test_disabled_tracer_keeps_the_newest_handle_only_and_stamps_nothing():
    tracer = StepTracer(enabled=False)
    assert tracer.device_dry()
    a, b = _Handle(), _Handle()
    tracer.note_dispatch("mixed", a)
    tracer.note_dispatch("decode_cont", b)
    assert [h for _, h, _ in tracer._pending] == [b]
    tracer.begin_step()
    with tracer.span("fetch_wait"):
        pass
    assert (a.asked, b.asked) == (0, 0)         # no polls at spans
    assert not tracer.device_dry()
    b.ready = True
    assert tracer.device_dry()
    assert b.asked == 2 and a.asked == 0
    tracer.end_step()
    assert tracer.last_device_busy == {} and tracer.last_device_idle == {}
    assert tracer.device_totals()["busy_by_program"] == {}
    assert tracer._usual == {}


def test_idle_phases_are_the_phases_and_the_two_uncovered_kinds():
    from intellillm_tpu.obs.tracing import IDLE_PHASES
    assert IDLE_PHASES == PHASES + ("loop_gap", "unannotated")
