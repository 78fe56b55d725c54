"""Step-phase tracing: low-overhead span timers for the whole serving loop.

The reference stack (and our port of its `engine/metrics.py`) only counts
tokens and queue depths; it cannot say WHERE the serving loop spends its
wall time. This module decomposes one loop period — an engine step plus
the time until the next one begins — into named phases, each measured
where the work happens:

    admit           new/aborted requests into the engine (async engine,
                    loop thread)
    handoff         run_in_executor call -> step entered with the KV lock
                    held: executor queueing + lock wait
    schedule        scheduler pass + metadata build (core/scheduler.py),
                    and the row re-snapshot of a decode continuation
                    (llm_engine._resnapshot)
    prepare_inputs  host batch prep + sampling tensors (model_runner)
    execute         jit dispatch of the device step (model_runner)
    fetch_wait      the blocking device->host copies of a step's results:
                    the host waits for the device. The ONLY phase in
                    which the host is not the one working
    sample          sampler post-processing of the fetched results
                    (exclusive of fetch_wait, which nests inside it)
    swap_copy       KV block swap-in/out/copy ops (worker)
    process_outputs stop checks, token appends, RequestOutput building,
                    finish hooks (llm_engine; detokenize nests inside)
    detokenize      incremental detokenization (llm_engine)
    stats           the telemetry's own cost per step: step-boundary
                    recorders, _get_stats, StatLogger.log
    handback        step returned on the executor thread -> the loop
                    task runs again: the event loop's wake-up latency
    stream_out      outputs handed to their streams, and the stream
                    coroutines' turn on the event loop (asyncio.sleep(0))

— with monotonic clocks and a shared null context manager on the
disabled path, so tracing costs two `time.monotonic()` calls per span
when on and one attribute read when off (INTELLILLM_TRACING=0).

Spans may nest: a child's time is subtracted from its enclosing span, so
the per-phase times are *exclusive* and sum to covered wall time without
double counting. The engine brackets each iteration with `begin_step()` /
`end_step()`; `end_step()` drains the accumulated phase dict plus the
step's wall time, which `StatLogger` exports as per-phase Prometheus
histograms and folds into the periodic "step breakdown" log line. Spans
recorded outside a bracket (stats, handback, stream_out, admit, handoff:
the between-step phases) carry into the next drain.

The **loop gap** is the time from one `end_step()` to the next
`begin_step()`, recorded only when the engine still had work at
`end_step(work_pending=True)`: waiting for traffic is not a gap. It is
drained with the step that follows it (`last_gap`), so step time + gap is
the loop's period and the phases above should cover all of it.

While a profiler trace runs (`set_profiling(True)`, flipped by
`LLMEngine.start_profile` / `stop_profile`) every span also enters a
`jax.profiler.TraceAnnotation("intellillm/<phase>")`, so the trace's host
plane carries the phases on the device planes' clock and
`obs/kernels.py` can charge each device idle gap to a phase. The first
annotation of each step carries `step=<n>`. `handoff` and `handback`
cross threads and have no annotation: they are the holes between the
loop thread's and the executor thread's annotations.

One process-global tracer (like the Prometheus registry): the scheduler,
worker, runner and both engines record into the current step without
threading a handle through every call signature. Single-writer by
construction: the between-step spans run on the event-loop thread and the
step on an executor thread, but the loop awaits the step, so the two
never overlap.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

# Phases in display order (the breakdown log line follows it): one loop
# period from admission to streaming out.
PHASES = ("admit", "handoff", "schedule", "prepare_inputs", "execute",
          "fetch_wait", "sample", "swap_copy", "process_outputs",
          "detokenize", "stats", "handback", "stream_out")
ANNOTATION_PREFIX = "intellillm/"


class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "_phase", "_t0", "_child", "_annotation")

    def __init__(self, tracer: "StepTracer", phase: str) -> None:
        self._tracer = tracer
        self._phase = phase

    def __enter__(self):
        t = self._tracer
        self._child = 0.0
        self._annotation = None
        if t._annotate is not None:
            # Kept on the span: the flag may flip before __exit__.
            self._annotation = t._annotate(self._phase)
            self._annotation.__enter__()
        t._stack.append(self)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        t = self._tracer
        t._stack.pop()
        # Exclusive time: subtract what nested spans already claimed.
        t._acc[self._phase] = t._acc.get(self._phase, 0.0) + dur - self._child
        if t._stack:
            t._stack[-1]._child += dur
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class StepTracer:
    """Accumulates exclusive wall time per phase for the current loop
    period. Single-writer by design (see the module docstring); readers
    take the drained snapshots, never the live dict."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self._acc: Dict[str, float] = {}
        self._stack: List[_Span] = []
        self._step_start: Optional[float] = None
        # Loop gap: clock started by end_step(work_pending=True), read by
        # the next begin_step(), drained into last_gap by its end_step().
        self._gap_from: Optional[float] = None
        self._gap: Optional[float] = None
        self.last_gap: Optional[float] = None
        self._crossing_t0: Optional[float] = None
        # Builds a span's TraceAnnotation while a profiler trace runs.
        self._annotate = None
        self.step_no = 0
        self._tag_step = False

    def span(self, phase: str):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, phase)

    def begin_step(self) -> None:
        if not self.enabled:
            return
        now = time.monotonic()
        if self._gap_from is not None:
            self._gap = now - self._gap_from
            self._gap_from = None
        self._step_start = now
        self.step_no += 1
        self._tag_step = True

    def end_step(self, work_pending: bool = False
                 ) -> Tuple[Dict[str, float], float]:
        """Drain (phase_times, step_wall_time); `last_gap` is then the
        loop gap that preceded the drained step (None if the engine was
        idle before it). Spans recorded outside a begin/end bracket carry
        into the next drain; without a bracket the wall time degrades to
        the phase sum. `work_pending`: the engine still has unfinished
        requests or steps in flight, so the time to the next begin_step()
        is a loop gap."""
        if not self.enabled:
            return {}, 0.0
        acc, self._acc = self._acc, {}
        self.last_gap, self._gap = self._gap, None
        now = time.monotonic()
        self._gap_from = now if work_pending else None
        if self._step_start is None:
            return acc, sum(acc.values())
        total = now - self._step_start
        self._step_start = None
        # A drain mid-span (not expected on the engine paths) would leak
        # the open span's time; the stack is empty at every call site.
        return acc, total

    # The two thread crossings of the async engine's loop are timed from
    # one thread to the other, so they cannot be stack spans: begun on one
    # thread, ended on the other, never nested.

    def begin_crossing(self) -> None:
        if self.enabled:
            self._crossing_t0 = time.monotonic()

    def end_crossing(self, phase: str) -> None:
        """Account the crossing begun by begin_crossing() to `phase`
        (`handoff` | `handback`), once."""
        t0 = self._crossing_t0
        if t0 is not None:
            self._crossing_t0 = None
            self._acc[phase] = (self._acc.get(phase, 0.0)
                                + time.monotonic() - t0)

    def set_profiling(self, on: bool) -> None:
        """A profiler trace starts/stops: spans enter TraceAnnotations
        while — and only while — it runs. jax is imported here, never on
        the span path."""
        if not (on and self.enabled):
            self._annotate = None
            return
        from jax.profiler import TraceAnnotation

        def annotate(phase: str):
            if self._tag_step:
                self._tag_step = False
                return TraceAnnotation(ANNOTATION_PREFIX + phase,
                                       step=self.step_no)
            return TraceAnnotation(ANNOTATION_PREFIX + phase)

        self._annotate = annotate

    def reset_for_testing(self) -> None:
        self.__init__(self.enabled)


def _enabled_from_env() -> bool:
    from intellillm_tpu.utils import parse_env_flag
    flag = parse_env_flag(os.environ.get("INTELLILLM_TRACING"))
    return True if flag is None else flag


_STEP_TRACER = StepTracer(enabled=_enabled_from_env())


def get_step_tracer() -> StepTracer:
    return _STEP_TRACER


class request_context:
    """Bind a request id to the logging layer for the duration of a
    with-block: `%(request_id)s` in a log format (see logger.py,
    INTELLILLM_LOG_REQUEST_ID=1) then correlates engine log lines with
    the flight recorder's per-request events."""

    __slots__ = ("_rid", "_token")

    def __init__(self, request_id: str) -> None:
        self._rid = request_id

    def __enter__(self):
        from intellillm_tpu.logger import request_id_ctx
        self._token = request_id_ctx.set(self._rid)
        return self

    def __exit__(self, *exc):
        from intellillm_tpu.logger import request_id_ctx
        request_id_ctx.reset(self._token)
        return False
