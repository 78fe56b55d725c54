"""XLA compile tracking for the jit-executable cache, and the account
of what every first call cost, stage by stage.

Every (batch-bucket x length/width-bucket x static-flag) combination the
runner dispatches is a separate XLA executable (`worker/model_runner.py`
shape bucketing). A cold bucket compiles mid-serving and stalls the
engine for seconds-to-tens-of-seconds; this module makes that visible as
metric deltas instead of mystery latency spikes:

    intellillm_xla_compiles_total{program}      first call per bucket
    intellillm_xla_cache_hits_total{program}    every re-dispatch
    intellillm_xla_compile_time_seconds{program}  a first call's wall
                                                  time, without the
                                                  ledger's introspection
    intellillm_live_executables                 distinct buckets seen
    intellillm_xla_stage_seconds_total{program,stage}
    intellillm_xla_persistent_cache_total{program,result}

Tracking is host-side: the runner derives a bucket key from exactly the
quantities jit keys its dispatch cache on (padded shapes + static args),
so the compile counter increments once per new bucket and never on a
cache hit — deterministically, independent of XLA's persistent on-disk
cache (which can make a "compile" fast but not free).

**Stages.** jax reports, on the thread that traces or compiles and only
then, how long each stage of a compile took (`jax.monitoring`). The
tracker listens (`install_listeners`, once) and splits a first call's
wall time:

    trace       the outermost `jaxpr_trace_duration` (an inner jitted
                function's trace nests in it and is summed by name in
                `traced_functions` instead)
    lower       `jaxpr_to_mlir_module_duration` (Mosaic's lowering of a
                `pallas_call` is in here)
    cache_read  `cache_retrieval_time_sec`: reading and loading an
                executable from the persistent cache (reported on a hit)
    compile     `backend_compile_duration` less the cache read inside it:
                XLA's compile on a miss; on a hit, the cache key's hashing
    introspect  the wall time of the kernel ledger's second
                `lower().compile()` (obs/kernels.py `commit`); what jax
                reports during it is `introspect_detail_s`, no stage
    rest        wall less the above: argument handling, dispatch

A first call is open on the calling thread while `call` runs it; what
jax reports on a thread with none open (the weights' init programs, the
memory profile's lowering, cache helpers) is the program `untracked`,
charged to the boot phase open at that moment (obs/boot.py `where`).
`setup_record()` is `/health/detail` `boot.setup`. A dispatch of a
bucket already seen does what it did before: no listener runs (jax
reports nothing), no new lock.

`ops/dispatch.py` also records its Pallas-vs-reference kernel choice here
(intellillm_kernel_dispatch_total{path}); the choice is made at trace
time, so the counts move together with compiles.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Deque, Dict, Hashable, List, Optional, Set

from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs.boot import PRE_ENGINE_PHASES, get_boot_timeline
from intellillm_tpu.obs.heap import get_heap
from intellillm_tpu.obs.kernels import KNOWN_PROGRAMS

logger = init_logger(__name__)

try:
    from prometheus_client import Counter, Gauge, Histogram
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

_COMPILE_TIME_BUCKETS = [0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                         10.0, 30.0, 60.0, 120.0, 300.0]

STAGES = ("trace", "lower", "compile", "cache_read", "introspect", "rest")
CACHE_RESULTS = ("hit", "miss", "not_cached", "not_asked")
UNTRACKED = "untracked"
# The stage series' `program` label: the step programs, the gathers
# behind a prompt step, and what no first call was open for; any other
# program is "other".
_PROGRAM_LABELS = KNOWN_PROGRAMS + ("join_gather", UNTRACKED)
_MAX_CALLS = 128
_MAX_FUNCTIONS = 512      # names held; the record shows the 16 largest
_TOP_FUNCTIONS = 16

# jax.monitoring's names (jax/_src/dispatch.py, jax/_src/compiler.py).
# The first three open with a scalar event and close with a duration;
# `backend` encloses `compile_or_get_cached`, so a cache read is in it.
_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _CompileMetrics:
    """Prometheus collectors for compile tracking (process-global, built
    once — same singleton pattern as engine/metrics._Metrics)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init()
        return cls._instance

    def _init(self) -> None:
        self.counter_compiles = Counter(
            "intellillm_xla_compiles_total",
            "XLA executable compiles (first dispatch of a new jit bucket).",
            ["program"])
        self.counter_cache_hits = Counter(
            "intellillm_xla_cache_hits_total",
            "jit dispatches served by an already-compiled executable.",
            ["program"])
        self.histogram_compile_time = Histogram(
            "intellillm_xla_compile_time_seconds",
            "Wall time of the first dispatch of a new jit bucket: trace, "
            "lowering, compile or cache read, and dispatch; without the "
            "kernel ledger's introspection that follows it.", ["program"],
            buckets=_COMPILE_TIME_BUCKETS)
        self.gauge_live_executables = Gauge(
            "intellillm_live_executables",
            "Distinct jit buckets (live XLA executables) seen so far.")
        self.counter_kernel_dispatch = Counter(
            "intellillm_kernel_dispatch_total",
            "Kernel dispatch decisions at trace time (ops/dispatch.py).",
            ["path"])
        self.counter_stage_seconds = Counter(
            "intellillm_xla_stage_seconds_total",
            "Seconds of first calls by stage (trace, lower, compile, "
            "cache_read, introspect, rest); program `untracked` is what "
            "jax traced or compiled outside a tracked first call.",
            ["program", "stage"])
        self.counter_persistent_cache = Counter(
            "intellillm_xla_persistent_cache_total",
            "Executables a first call asked the persistent compilation "
            "cache for, by result: hit, miss (compiled and written), "
            "not_cached (compiled, not written: under the cache's "
            "minimum compile time or entry size), not_asked.",
            ["program", "result"])

    @classmethod
    def reset_for_testing(cls) -> None:
        inst = cls._instance
        if inst is not None and _PROMETHEUS:
            from prometheus_client import REGISTRY
            for collector in vars(inst).values():
                try:
                    REGISTRY.unregister(collector)
                except Exception:
                    pass
        cls._instance = None


def _rounded(d: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v, 6) for k, v in d.items()}


class _Account:
    """What jax reports on one thread while one first call is open (or,
    `program` untracked, while none is), as it arrives."""

    __slots__ = ("program", "key", "where", "start_unix", "stages", "cache",
                 "pending_read", "pending", "line")

    def __init__(self, program: str, key: Hashable, where: str) -> None:
        self.program = program
        self.key = key
        self.where = where
        self.start_unix = time.time()
        self.line: Optional[Dict[str, Any]] = None   # its entry of `calls`
        self.pending_read = 0.0
        self.pending: Set[str] = set()
        self.clear()

    def clear(self) -> None:
        self.stages = dict.fromkeys(STAGES, 0.0)
        self.cache = dict.fromkeys(CACHE_RESULTS, 0)

    def add(self, stage: str, seconds: float) -> None:
        if stage == "backend":
            # One executable is done. `backend` enclosed its cache read
            # and the cache's events, which came before this.
            stage = "compile"
            seconds = max(seconds - self.pending_read, 0.0)
            result = next((r for r in ("hit", "miss") if r in self.pending),
                          "not_cached" if self.pending else "not_asked")
            self.cache[result] += 1
            self.pending_read, self.pending = 0.0, set()
        elif stage == "cache_read":
            self.pending_read += seconds
        self.stages[stage] += seconds

    def close(self, wall_s: float) -> None:
        self.stages["rest"] = max(wall_s - sum(self.stages.values()), 0.0)


class _Sums:
    """Totals of accounts: a program's or a phase's."""

    __slots__ = ("count", "wall_s", "stages", "cache")

    def __init__(self) -> None:
        self.count = 0
        self.wall_s = 0.0
        self.stages = dict.fromkeys(STAGES, 0.0)
        self.cache = dict.fromkeys(CACHE_RESULTS, 0)

    def add(self, other, wall_s: float, count: int) -> None:
        """Take in an account's, or another total's, stages and cache."""
        self.count += count
        self.wall_s += wall_s
        for k, v in other.stages.items():
            self.stages[k] += v
        for k, v in other.cache.items():
            self.cache[k] += v

    def as_dict(self) -> Dict[str, Any]:
        return {"count": self.count, "wall_s": round(self.wall_s, 6),
                "by_stage_s": _rounded(self.stages),
                "cache": dict(self.cache)}


def _program_label(program: str) -> str:
    return program if program in _PROGRAM_LABELS else "other"


def _count_function(table: Dict[str, List[float]], name: str,
                    seconds: float) -> None:
    if name not in table and len(table) >= _MAX_FUNCTIONS:
        name = "other"
    row = table.setdefault(name, [0.0, 0])
    row[0] += seconds
    row[1] += 1


def _top_functions(table: Dict[str, List[float]]) -> List[Dict[str, Any]]:
    rows = sorted(table.items(), key=lambda kv: -kv[1][0])[:_TOP_FUNCTIONS]
    return [{"fun_name": name, "seconds": round(s, 6), "count": int(n)}
            for name, (s, n) in rows]


class CompileTracker:
    """Host-side registry of jit buckets dispatched so far.

    `call()` wraps a jit dispatch: a never-seen (program, key) counts as a
    compile and its wall time feeds the compile-time histogram; a known
    key counts as a cache hit. Thread-safe (the async engine dispatches
    from an executor thread while tests may read snapshots). A first
    call's account belongs to its thread while the call is open and goes
    under the lock once, when it closes."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self._lock = threading.Lock()
        self._keys: Dict[str, Set[Hashable]] = {}
        self._compiles: Dict[str, int] = {}
        self._hits: Dict[str, int] = {}
        self._compile_time: Dict[str, float] = {}
        self._kernel_dispatch: Dict[str, int] = {}
        self._metrics = _CompileMetrics() if _PROMETHEUS else None
        # The callbacks registered with jax.monitoring, if any.
        self._listening: tuple = ()
        self._reset_stages()

    def _reset_stages(self) -> None:
        # A thread's: `open` (the account jax's reports go to), `last`
        # (the first call that closed last, for its introspection),
        # `untracked` (the account for when none is open) and `depth`
        # (how many of jax's timed spans are open).
        self._tls = threading.local()
        self._calls: Deque[Dict[str, Any]] = deque(maxlen=_MAX_CALLS)
        self._by_program: Dict[str, _Sums] = {}
        self._by_phase: Dict[str, _Sums] = {}
        self._in_boot_s = 0.0
        self._after_boot_s = 0.0
        # fun_name -> [seconds, count]: the traces of jitted functions
        # inside a program's, and what untracked work was spent on.
        self._traced: Dict[str, List[float]] = {}
        self._untracked_functions: Dict[str, List[float]] = {}

    # --- jax.monitoring listeners ----------------------------------------

    def install_listeners(self) -> None:
        """Register with jax.monitoring, once: where an engine is built
        (before anything compiles) and at a first call."""
        if self._listening or not self.enabled:
            return
        with self._lock:
            if self._listening:
                return
            from jax import monitoring
            self._listening = (self._on_span_start, self._on_duration,
                               self._on_event)
            monitoring.register_scalar_listener(self._listening[0])
            monitoring.register_event_duration_secs_listener(
                self._listening[1])
            monitoring.register_event_listener(self._listening[2])

    def _remove_listeners(self) -> None:
        with self._lock:
            if not self._listening:
                return
            from jax import monitoring
            monitoring.unregister_scalar_listener(self._listening[0])
            monitoring.unregister_event_duration_listener(
                self._listening[1])
            monitoring.unregister_event_listener(self._listening[2])
            self._listening = ()

    def _account(self) -> _Account:
        """The open first call's account, else this thread's account of
        untracked work under the boot phase open now."""
        tls = self._tls
        acct = getattr(tls, "open", None)
        if acct is not None:
            return acct
        acct = getattr(tls, "untracked", None)
        if acct is None:
            acct = tls.untracked = _Account(UNTRACKED, None, "")
        acct.where = get_boot_timeline().where()
        return acct

    def _on_span_start(self, event: str, value, **kwargs) -> None:
        if event in _SPAN_EVENTS:
            tls = self._tls
            tls.depth = getattr(tls, "depth", 0) + 1

    def _on_event(self, event: str, **kwargs) -> None:
        what = _CACHE_EVENTS.get(event)
        if what is not None:
            self._account().pending.add(what)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        stage = _SPAN_EVENTS.get(event)
        if stage is None:
            if event != _CACHE_READ_EVENT:
                return
            stage = "cache_read"
        else:
            tls = self._tls
            tls.depth = max(getattr(tls, "depth", 0) - 1, 0)
            if tls.depth:
                # A span inside a span: its seconds are in the outer
                # one's. An inner jitted function's trace goes by name.
                if stage == "trace":
                    with self._lock:
                        _count_function(self._traced,
                                        str(kwargs.get("fun_name")),
                                        duration)
                return
        acct = self._account()
        acct.add(stage, duration)
        name = kwargs.get("fun_name")
        if acct.program == UNTRACKED and name:
            # (A cache read comes without a name, inside the `backend`
            # span that brings it.)
            self._flush_untracked(
                acct, name[4:-1] if name.startswith("jit(") else name)

    def _flush_untracked(self, acct: _Account, fun_name: str) -> None:
        seconds = sum(acct.stages.values())
        executables = sum(acct.cache.values())
        with self._lock:
            for sums in (self._by_program.setdefault(UNTRACKED, _Sums()),
                         self._by_phase.setdefault(acct.where, _Sums())):
                sums.add(acct, wall_s=seconds, count=executables)
            _count_function(self._untracked_functions, fun_name, seconds)
        self._export_stages(acct)
        acct.clear()

    def _export_stages(self, acct: _Account) -> None:
        if self._metrics is None:
            return
        label = _program_label(acct.program)
        for stage, seconds in acct.stages.items():
            if seconds:
                self._metrics.counter_stage_seconds.labels(
                    label, stage).inc(seconds)
        for result, n in acct.cache.items():
            if n:
                self._metrics.counter_persistent_cache.labels(
                    label, result).inc(n)

    # --- first calls -------------------------------------------------------

    def call(self, program: str, key: Hashable,
             fn: Callable[..., Any], /, *args, **kwargs) -> Any:
        if not self.enabled:
            return fn(*args, **kwargs)
        with self._lock:
            is_new = key not in self._keys.setdefault(program, set())
            if is_new:
                self._keys[program].add(key)
        if not is_new:
            self._record_hit(program)
            return fn(*args, **kwargs)
        self.install_listeners()
        tls = self._tls
        acct = _Account(program, key, get_boot_timeline().where())
        outer, tls.open = getattr(tls, "open", None), acct
        t0 = time.monotonic()
        try:
            out = fn(*args, **kwargs)
        except BaseException:
            # Failed first dispatch (e.g. compile OOM): forget the key so
            # a retry counts as a fresh compile, not a cache hit. Its
            # stages go with it.
            with self._lock:
                self._keys.get(program, set()).discard(key)
            raise
        finally:
            tls.open = outer
        elapsed = time.monotonic() - t0
        acct.close(elapsed)
        tls.last = acct
        self._record_compile(acct, elapsed)
        # What the miss built (the program's jaxpr, its executable, jax's
        # cache entries) lives as long as the server (obs/heap.py).
        get_heap().first_call_done()
        return out

    @contextmanager
    def introspection(self, program: str, key: Hashable):
        """Around the kernel ledger's second `lower().compile()` of the
        first call that just closed on this thread: its wall time is that
        call's stage `introspect`, and what jax reports meanwhile is no
        stage of any call (it is kept as `introspect_detail_s`). Yields a
        dict that holds `seconds` afterwards."""
        span: Dict[str, float] = {"seconds": 0.0}
        tls = self._tls
        last = getattr(tls, "last", None)
        if last is not None and (last.program, last.key) != (program, key):
            last = None
        scratch = _Account(program, key, "")
        outer, tls.open = getattr(tls, "open", None), scratch
        t0 = time.monotonic()
        try:
            yield span
        finally:
            tls.open = outer
            span["seconds"] = time.monotonic() - t0
            if self.enabled:
                self._record_introspection(program, last, span["seconds"],
                                           scratch.stages)

    def _record_compile(self, acct: _Account, elapsed: float) -> None:
        program = acct.program
        acct.line = {"program": program, "bucket": repr(acct.key)[:200],
                     "where": acct.where,
                     "start_unix": round(acct.start_unix, 3),
                     "wall_s": round(elapsed, 6),
                     "stages": _rounded(acct.stages),
                     "cache": dict(acct.cache)}
        with self._lock:
            self._compiles[program] = self._compiles.get(program, 0) + 1
            self._compile_time[program] = (
                self._compile_time.get(program, 0.0) + elapsed)
            live = sum(len(k) for k in self._keys.values())
            self._calls.append(acct.line)
            for sums in (self._by_program.setdefault(program, _Sums()),
                         self._by_phase.setdefault(acct.where, _Sums())):
                sums.add(acct, wall_s=elapsed, count=1)
            self._add_by_boot_locked(acct.where, elapsed)
        logger.debug("XLA compile: program=%s key=%s %.3fs (%d live "
                     "executables)", program, acct.key, elapsed, live)
        if self._metrics is not None:
            self._metrics.counter_compiles.labels(program).inc()
            self._metrics.histogram_compile_time.labels(program).observe(
                elapsed)
            self._metrics.gauge_live_executables.set(live)
            self._export_stages(acct)

    def _record_introspection(self, program: str, acct: Optional[_Account],
                              seconds: float,
                              detail: Dict[str, float]) -> None:
        where = acct.where if acct else get_boot_timeline().where()
        with self._lock:
            if acct is not None:
                acct.line["stages"]["introspect"] = round(seconds, 6)
                acct.line["introspect_detail_s"] = _rounded(
                    {k: v for k, v in detail.items() if v})
            for sums in (self._by_program.setdefault(program, _Sums()),
                         self._by_phase.setdefault(where, _Sums())):
                sums.stages["introspect"] += seconds
            self._add_by_boot_locked(where, seconds)
        if self._metrics is not None:
            self._metrics.counter_stage_seconds.labels(
                _program_label(program), "introspect").inc(seconds)

    def _add_by_boot_locked(self, where: str, seconds: float) -> None:
        if where == "serving":
            self._after_boot_s += seconds
        else:
            self._in_boot_s += seconds

    def _record_hit(self, program: str) -> None:
        with self._lock:
            self._hits[program] = self._hits.get(program, 0) + 1
        if self._metrics is not None:
            self._metrics.counter_cache_hits.labels(program).inc()

    def record_kernel_dispatch(self, path: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._kernel_dispatch[path] = (
                self._kernel_dispatch.get(path, 0) + 1)
        if self._metrics is not None:
            self._metrics.counter_kernel_dispatch.labels(path).inc()

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time copy for tests / bench attribution dumps."""
        with self._lock:
            return {
                "compiles": dict(self._compiles),
                "cache_hits": dict(self._hits),
                "compile_time_seconds": dict(self._compile_time),
                "live_executables": sum(
                    len(k) for k in self._keys.values()),
                "kernel_dispatch": dict(self._kernel_dispatch),
            }

    def setup_record(self) -> Dict[str, Any]:
        """`/health/detail` `boot.setup`: the boot's phases beside every
        first call's stages (docs/observability.md has the schema).
        `first_calls` counts the tracked first calls; its `by_stage_s`
        and `cache` take the untracked work in as well."""
        boot = get_boot_timeline().snapshot()
        phases = boot["phases_s"]
        with self._lock:
            tracked = _Sums()
            everything = _Sums()
            for program, sums in self._by_program.items():
                everything.add(sums, sums.wall_s, sums.count)
                if program != UNTRACKED:
                    tracked.add(sums, sums.wall_s, sums.count)
            total_s = tracked.wall_s + tracked.stages["introspect"]
            staged = sum(v for k, v in tracked.stages.items()
                         if k != "rest")
            untracked = self._by_program.get(UNTRACKED)
            first_calls = {
                "count": tracked.count,
                "wall_s": round(tracked.wall_s, 6),
                "total_s": round(total_s, 6),
                "untracked_s": round(untracked.wall_s if untracked else 0.0,
                                     6),
                "by_stage_s": _rounded(everything.stages),
                "in_boot_s": round(self._in_boot_s, 6),
                "after_boot_s": round(self._after_boot_s, 6),
                "cache": dict(everything.cache),
                "accounted_share": (round(staged / total_s, 6)
                                    if total_s > 0 else None),
            }
            return {
                "phases_s": phases,
                "unphased_s": boot["unphased_s"],
                "pre_engine_s": round(
                    sum(phases.get(p, 0.0) for p in PRE_ENGINE_PHASES)
                    + boot["unphased_s"], 3),
                "first_calls": first_calls,
                "by_program": {p: s.as_dict()
                               for p, s in self._by_program.items()},
                "by_phase_stage_s": {w: _rounded(s.stages)
                                     for w, s in self._by_phase.items()},
                "traced_functions": _top_functions(self._traced),
                "untracked_functions": _top_functions(
                    self._untracked_functions),
                "calls": [dict(c, stages=dict(c["stages"]))
                          for c in self._calls],
            }

    def reset_for_testing(self) -> None:
        self._remove_listeners()
        with self._lock:
            self._keys = {}
            self._compiles = {}
            self._hits = {}
            self._compile_time = {}
            self._kernel_dispatch = {}
            self._reset_stages()
        if self._metrics is not None:
            _CompileMetrics.reset_for_testing()
            self._metrics = _CompileMetrics() if _PROMETHEUS else None


_COMPILE_TRACKER = CompileTracker()


def get_compile_tracker() -> CompileTracker:
    return _COMPILE_TRACKER


def record_kernel_dispatch(path: str) -> None:
    _COMPILE_TRACKER.record_kernel_dispatch(path)
