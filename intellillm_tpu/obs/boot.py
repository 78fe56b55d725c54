"""Boot-phase timing: where the seconds between the process's start and
the server answering went.

The timeline starts at the process's start as the OS gives it
(`/proc/self/stat`), not at the first line of this package that runs, so
the interpreter, the imports and the runtime's start are inside it. At
any moment one phase is open or none is: a phase opened inside another
takes its seconds out of the enclosing one, so phases never overlap and

    total_s = sum(phases_s, without the derived `weights_load`) + unphased_s

Phases, in the order a server passes through them:

    imports          process start to the end of the entrypoint's imports
                     (`mark("imports")` at the top of its `main`)
    engine_init      `from_engine_args`: the configs, the tokenizer, the
                     scheduler and the block manager: what is left of
                     the constructor outside the phases below
    runtime_init     the first `jax.devices()`: the backend's start
    init_model       the mesh and the compile cache's set-up
    load_model       building the model and filling its weights
    weights_load     init_model + load_model (derived; the old name)
    cache_init       memory profile and the cache pools
    warmup_compile   the warm-up's dispatches
    serve_start      `mark_complete()` to the HTTP server's start-up hook

`where()` names the open phase ("serving" when none is) for
obs/compile_tracker.py, which charges every trace, lowering and compile
to the phase it happened in. Exposed under the "boot" key of
`/health/detail` and as `intellillm_boot_phase_seconds{phase}`. No
threads, no env vars; the clock is `time.monotonic()`.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

try:
    from prometheus_client import Gauge
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

# `weights_load` is reported as the sum of these two and is in no sum
# itself.
_WEIGHTS_PARTS = ("init_model", "load_model")
# What `setup.pre_engine_s` adds up (with `unphased_s`): the seconds that
# stood in no phase before this timeline started at the process's start.
PRE_ENGINE_PHASES = ("imports", "runtime_init", "engine_init")


def seconds_since_process_start() -> float:
    """How long this process has existed, from the kernel's own record
    (start time in clock ticks since boot, field 22 of /proc/self/stat,
    against CLOCK_BOOTTIME). 0.0 where that cannot be read: the timeline
    then starts when it is made, as it did before."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # The command name (field 2) may hold spaces and brackets.
            fields = f.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return max(time.clock_gettime(time.CLOCK_BOOTTIME) - started, 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


class _BootMetrics:
    """The one collector of this module (process-global, built once: the
    singleton pattern of compile_tracker._CompileMetrics)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance.gauge_phase = Gauge(
                "intellillm_boot_phase_seconds",
                "Seconds of the boot spent in each phase (process start "
                "to the server answering; `unphased` is what no phase "
                "covers).", ["phase"])
        return cls._instance

    @classmethod
    def reset_for_testing(cls) -> None:
        inst = cls._instance
        if inst is not None:
            from prometheus_client import REGISTRY
            try:
                REGISTRY.unregister(inst.gauge_phase)
            except Exception:
                pass
        cls._instance = None


class BootTimeline:
    """Wall-clock durations of named boot phases for one process.
    `clock` and `age_s` (how long the process had existed when the
    timeline was made) are for tests on a scripted clock."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 age_s: Optional[float] = None) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._phases_s: Dict[str, float] = {}
        self._unphased_s = 0.0
        self._open: List[str] = []
        self._info: Dict[str, Any] = {}
        if age_s is None:
            age_s = seconds_since_process_start()
        self._started = clock() - age_s
        # Everything before this reading is charged.
        self._charged_to = self._started
        self._completed_at: Optional[float] = None
        self._serving_at: Optional[float] = None

    def _charge_locked(self, name: Optional[str] = None) -> None:
        """The seconds since the last charge go to `name`, else to the
        open phase, else to `unphased_s`. After `mark_complete` the boot
        is over and nothing more is charged."""
        if self._completed_at is not None:
            return
        now = self._clock()
        seconds = max(now - self._charged_to, 0.0)
        self._charged_to = now
        name = name or (self._open[-1] if self._open else None)
        if name is None:
            self._unphased_s += seconds
        else:
            self._phases_s[name] = self._phases_s.get(name, 0.0) + seconds

    @contextmanager
    def phase(self, name: str):
        with self._lock:
            self._charge_locked()
            self._open.append(name)
        try:
            yield
        finally:
            with self._lock:
                self._charge_locked()
                self._open.remove(name)

    def mark(self, name: str) -> None:
        """Everything since the last charge was `name` (the entrypoint's
        `imports`: nothing could open a phase before they were done)."""
        with self._lock:
            self._charge_locked(name)

    def where(self) -> str:
        """The open phase, "serving" when none is."""
        with self._lock:
            return self._open[-1] if self._open else "serving"

    def set_info(self, name: str, value: Any) -> None:
        """Attach a structured (JSON-safe) block to the snapshot — e.g.
        warm-up's compiled-executable count next to its wall time, so
        benches can machine-check boot criteria instead of grepping
        logs."""
        with self._lock:
            self._info[name] = value

    def mark_complete(self) -> None:
        with self._lock:
            if self._completed_at is None:
                self._charge_locked()
                self._completed_at = self._charged_to
        self._export()

    def mark_serving(self) -> None:
        """The HTTP server is about to answer: closes `serve_start`. A
        process that serves nothing over HTTP never calls it, and its
        `total_s` ends at `mark_complete`."""
        with self._lock:
            if self._completed_at is None or self._serving_at is not None:
                return
            self._serving_at = self._clock()
            self._phases_s["serve_start"] = max(
                self._serving_at - self._completed_at, 0.0)
        self._export()

    def _export(self) -> None:
        if not _PROMETHEUS:
            return
        snap = self.snapshot()
        gauge = _BootMetrics().gauge_phase
        for name, seconds in snap["phases_s"].items():
            gauge.labels(name).set(seconds)
        gauge.labels("unphased").set(snap["unphased_s"])

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            end = self._serving_at or self._completed_at
            phases = dict(self._phases_s)
            if any(p in phases for p in _WEIGHTS_PARTS):
                phases["weights_load"] = sum(
                    phases.get(p, 0.0) for p in _WEIGHTS_PARTS)
            snap = {
                "phases_s": {k: round(v, 3) for k, v in phases.items()},
                "unphased_s": round(self._unphased_s, 3),
                "total_s": (round(end - self._started, 3)
                            if end is not None else None),
                "complete": self._completed_at is not None,
            }
            snap.update(self._info)
            return snap

    def reset_for_testing(self) -> None:
        self.__init__(clock=self._clock)
        if _PROMETHEUS:
            _BootMetrics.reset_for_testing()


_TIMELINE: Optional[BootTimeline] = None
_TIMELINE_LOCK = threading.Lock()


def get_boot_timeline() -> BootTimeline:
    global _TIMELINE
    if _TIMELINE is None:
        with _TIMELINE_LOCK:
            if _TIMELINE is None:
                _TIMELINE = BootTimeline()
    return _TIMELINE
