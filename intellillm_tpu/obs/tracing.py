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
disabled path, so tracing costs two `time.monotonic()` calls and two
`is_ready()` queries per span when on and one attribute read when off
(INTELLILLM_TRACING=0: the tracer then keeps the newest dispatch's handle
for `device_dry()` and nothing else).

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
`obs/kernels.py` can charge each device idle gap to a phase. `handoff`
and `handback` cross threads and have no annotation: they are the holes
between the loop thread's and the executor thread's annotations.

The **device timeline** puts the device's own time on the same clock,
with no profiler. The runner hands every dispatched step's output to
`note_dispatch(program, handle)`; at every instant the tracer reads its
clock anyway (a span's enter and exit, `begin_step`, `end_step`, the two
crossings) it asks the oldest unfinished handle `is_ready()`, which
never blocks. A step found finished at the exit of the `fetch_wait` that
waited on its output is stamped there: measured. One found finished at
any other poll lies between that poll and the one before, and is
*estimated*: halfway where the two polls are close (a tenth of the
step's time: exact to the millisecond), else where the usual time of its
program at its shapes puts it, held to that interval. The usual time is
a running mean of the measured and the closely bracketed steps, so a
loop whose fetches never block learns it too; it keeps a pause of the
host (a full garbage collection stops it for a third of a second) from
being counted as the step's time, and cannot see a step that ran slow
while nobody looked. XLA runs
programs in order, so a step was busy from the later of its dispatch and
the completion before it until its own completion (`device_busy`, by
program), and from the completion that leaves nothing unfinished until
the next dispatch the device is dry: that time is charged, exclusively
like the phases, to the innermost span open while it passed, to
`loop_gap` between steps, to `unannotated` under no span inside a step
(`device_idle`, by phase). Busy + idle tile the loop's period. Both are
drained by `end_step()` beside the phases; nothing is charged while the
engine waits for traffic. It sees the step programs only: a block copy
between steps is device time it calls idle under `swap_copy`.

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
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

# Phases in display order (the breakdown log line follows it): one loop
# period from admission to streaming out.
PHASES = ("admit", "handoff", "schedule", "prepare_inputs", "execute",
          "fetch_wait", "sample", "swap_copy", "process_outputs",
          "detokenize", "stats", "handback", "stream_out")
# What dry device time is charged to: a phase, or no span at all.
IDLE_PHASES = PHASES + ("loop_gap", "unannotated")
ANNOTATION_PREFIX = "intellillm/"


class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _add_into(total: Dict[str, float], part: Dict[str, float]) -> None:
    for name, v in part.items():
        total[name] = total.get(name, 0) + v


class _Span:
    __slots__ = ("_tracer", "_phase", "_waits_on", "_t0", "_child",
                 "_annotation")

    def __init__(self, tracer: "StepTracer", phase: str,
                 waits_on=None) -> None:
        self._tracer = tracer
        self._phase = phase
        self._waits_on = waits_on

    def __enter__(self):
        t = self._tracer
        self._child = 0.0
        self._annotation = None
        if t._annotate is not None:
            # Kept on the span: the flag may flip before __exit__.
            self._annotation = t._annotate(self._phase)
            self._annotation.__enter__()
        self._t0 = now = time.monotonic()
        t._tick(now)
        t._stack.append(self)
        return self

    def __exit__(self, *exc):
        now = time.monotonic()
        dur = now - self._t0
        t = self._tracer
        t._tick(now, waited=self._waits_on)
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
    period, and the device's busy and dry time beside it. Single-writer
    by design (see the module docstring); readers take the drained
    snapshots, never the live dicts."""

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
        # The device timeline. Unfinished dispatches, oldest first:
        # ((program, bucket), handle, when the dispatch call returned).
        # Disabled, the newest only.
        self._pending: Deque[Tuple[Tuple[str, Any], Any, float]] = deque(
            maxlen=None if enabled else 1)
        self._last_poll = 0.0
        self._last_done = 0.0
        # Device time of a program at one bucket of shapes, a running
        # mean of the steps a fetch waited on and of those two close
        # polls bracketed: places a completion nobody saw closely.
        self._usual: Dict[Tuple[str, Any], float] = {}
        # Since when the device is dry and the time not yet charged; None
        # while it has work, or while the engine waits for traffic.
        self._dry_from: Optional[float] = None
        self._busy: Dict[str, float] = {}
        self._idle: Dict[str, float] = {}
        self.last_device_busy: Dict[str, float] = {}
        self.last_device_idle: Dict[str, float] = {}
        # Everything drained so far.
        self._totals: Dict[str, Dict[str, float]] = {
            "busy_by_program": {}, "idle_by_phase": {}}

    def span(self, phase: str, waits_on=None):
        """`waits_on`: the dispatched step's handle this span blocks on
        (a `fetch_wait`): still unfinished when the span is entered, the
        step is known to end at the span's exit."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, phase, waits_on)

    # --- the device timeline ----------------------------------------------

    def note_dispatch(self, program: str, handle, bucket=None) -> None:
        """A step of `program` was dispatched; `handle` is an output of
        it (anything with a non-blocking `is_ready()`), `bucket` what
        tells its shapes from the program's other ones (hashable)."""
        if not self.enabled:
            self._pending.append(((program, bucket), handle, 0.0))
            return
        now = time.monotonic()
        self._tick(now)
        self._dry_from = None
        self._pending.append(((program, bucket), handle, now))

    def device_dry(self) -> bool:
        """Whether nothing dispatched earlier is still running on the
        device: a readiness query, never a wait."""
        if self.enabled:
            self._tick(time.monotonic())
            return not self._pending
        return not self._pending or self._pending[-1][1].is_ready()

    def _tick(self, now: float, waited=None,
              phase: Optional[str] = None) -> None:
        """The clock was read at `now`: retire the steps that finished
        since the last poll (`waited`: the handle the host blocked on
        until now), and charge the dry time since then to what the host
        was in (`phase`, else the innermost open span)."""
        pending = self._pending
        if not pending and self._dry_from is None:
            return      # waiting for traffic: nothing to stamp or charge
        if pending and pending[0][1].is_ready():
            ready = 1
            while ready < len(pending) and pending[ready][1].is_ready():
                ready += 1
            # A completion lies between the last poll and this one
            # (several: in order).
            for left in range(ready, 0, -1):
                key, handle, dispatched = pending.popleft()
                start = max(dispatched, self._last_done)
                lo = max(start, self._last_poll)
                usual = self._usual.get(key)
                # Measured: the host blocked on this step until now (not
                # on an older one, with this one finished meanwhile).
                measured = handle is waited and left == 1
                close = ready == 1 and now - lo <= 0.1 * (now - start)
                if measured:
                    done = now
                elif close or usual is None:
                    done = lo + (now - lo) / (left + 1)
                else:
                    # The host may have been away far longer than the
                    # step ran: a collector's pause.
                    done = min(max(start + usual, lo), now)
                if measured or close:
                    took = done - start
                    self._usual[key] = took if usual is None else (
                        usual + 0.25 * (took - usual))
                self._busy[key[0]] = (self._busy.get(key[0], 0.0)
                                      + done - start)
                self._last_done = done
            if not pending:
                self._dry_from = self._last_done
        if self._dry_from is not None and now > self._dry_from:
            if phase is None:
                phase = ("unannotated" if self._step_start is not None
                         else "loop_gap")
                if self._stack:
                    phase = self._stack[-1]._phase
            self._idle[phase] = (self._idle.get(phase, 0.0)
                                 + now - self._dry_from)
            self._dry_from = now
        self._last_poll = now

    def device_totals(self) -> Dict[str, Dict[str, float]]:
        """Cumulative device seconds by program and dry seconds by host
        phase as of the last `end_step()`. Two readings bracket whole
        steps: what they gained, busy + idle, is the time between those
        two step boundaries (to the step still running at either, which
        counts once it is retired; less any wait for traffic). Never
        steps back. For readers on other threads (the capture endpoint,
        /health/detail)."""
        return {key: dict(value) for key, value in self._totals.items()}

    # --- the step bracket --------------------------------------------------

    def begin_step(self) -> None:
        if not self.enabled:
            return
        now = time.monotonic()
        if self._gap_from is not None:
            self._gap = now - self._gap_from
            self._gap_from = None
            self._tick(now)
        else:
            # Out of a wait for traffic: the period, and with it the
            # device's dry time, starts here.
            self._last_poll = now
            if not self._pending:
                self._dry_from = now
        self._step_start = now

    def cancel_step(self) -> None:
        """The step begun found nothing to do and nothing in flight: no
        period, so the wait that follows is not the device's dry time."""
        self._step_start = None
        self._dry_from = None

    def end_step(self, work_pending: bool = False
                 ) -> Tuple[Dict[str, float], float]:
        """Drain (phase_times, step_wall_time); `last_gap` is then the
        loop gap that preceded the drained step (None if the engine was
        idle before it), `last_device_busy` / `last_device_idle` the
        device seconds by program and dry seconds by phase retired since
        the last drain. Spans recorded outside a begin/end bracket carry
        into the next drain; without a bracket the wall time degrades to
        the phase sum. `work_pending`: the engine still has unfinished
        requests or steps in flight, so the time to the next begin_step()
        is a loop gap."""
        if not self.enabled:
            return {}, 0.0
        acc, self._acc = self._acc, {}
        self.last_gap, self._gap = self._gap, None
        now = time.monotonic()
        self._tick(now)
        self.last_device_busy, self._busy = self._busy, {}
        self.last_device_idle, self._idle = self._idle, {}
        _add_into(self._totals["busy_by_program"], self.last_device_busy)
        _add_into(self._totals["idle_by_phase"], self.last_device_idle)
        if not work_pending:
            # Waiting for traffic is neither a gap nor dry time.
            self._pending.clear()
            self._dry_from = None
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
            self._crossing_t0 = now = time.monotonic()
            self._tick(now)

    def end_crossing(self, phase: str) -> None:
        """Account the crossing begun by begin_crossing() to `phase`
        (`handoff` | `handback`), once."""
        t0 = self._crossing_t0
        if t0 is not None:
            self._crossing_t0 = None
            now = time.monotonic()
            self._acc[phase] = self._acc.get(phase, 0.0) + now - t0
            self._tick(now, phase=phase)

    def set_profiling(self, on: bool) -> None:
        """A profiler trace starts/stops: spans enter TraceAnnotations
        while — and only while — it runs. jax is imported here, never on
        the span path."""
        if not (on and self.enabled):
            self._annotate = None
            return
        from jax.profiler import TraceAnnotation
        self._annotate = lambda phase: TraceAnnotation(
            ANNOTATION_PREFIX + phase)

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
