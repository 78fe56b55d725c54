"""Per-kernel cost attribution: the kernel-level efficiency ledger.

The obs stack already answers *when* compute happens (step phases,
compile stalls) and *how much of it is pad* (obs/efficiency.py); this
module answers *where inside the executables it goes*, with two feeds:

**Static introspection at compile time.** The model runner's jit
dispatch hook (`worker/model_runner.py::_guarded_call`) calls
`prepare()` / `commit()` around every dispatch with the exact
(program, bucket-key) pair the CompileTracker keys its cache on. The
first dispatch of a bucket captures the call's *abstract* shapes
(ShapeDtypeStructs — taken BEFORE the dispatch, because kv_caches are
donated and invalid afterwards) and, once the dispatch succeeds, runs
`fn.lower(...).compile()` to read XLA's own `cost_analysis()` /
`memory_analysis()` — the pattern proven one-off in
`worker/worker.py::_estimate_step_temp_bytes`. Each (program, bucket)
becomes a ledger entry with FLOPs, bytes accessed, argument/output/
temp/peak HBM, compile-path wall time, a derived roofline intensity
(FLOPs per byte accessed), and a dispatch counter.

**Measured device time on demand.** `POST
/debug/profiler/capture?steps=N` (entrypoints/debug_routes.py) runs a
bounded jax.profiler trace around N engine steps, then `load_profile()`
reads the `.xplane.pb` the profiler wrote (`jax.profiler.ProfileData`)
and `reduce_profile()` folds it: device window, busy time as the UNION
of the device op line's intervals, idle, operations by SELF time (an
enclosing `while` or program is not counted on top of its body), time
by program, and every idle gap split over the `intellillm/<phase>`
annotations that obs/tracing.py writes into the host plane while the
trace runs — which host phase the device was waiting for.
`merge_profile()` stores the block next to the static feed so cost-model
FLOPs sit beside measured seconds in one `GET /debug/kernels` response.
(The arithmetic is that of benchmarks/chip/harness/trace_reduce.py,
written again here because the program may not import the benchmark; a
test holds the two to the same busy, idle and self times.)

**MFU cross-check.** `record_step()` (engine step boundary) folds the
cost-model FLOPs dispatched that step into a rolling window and exports
`intellillm_kernel_mfu_costmodel` NEXT TO efficiency.py's analytic
`intellillm_mfu` — two independent FLOPs models for the same quantity.
A persistent gap between them bounds the analytic model's known error
bars (attention score FLOPs, embeddings); see docs/observability.md.

**Degradation contract (CPU / no-TPU).** Introspection mode is
`INTELLILLM_KERNEL_INTROSPECT=auto|1|0`; under `auto` (default) the
second compile that `lower().compile()` costs is only paid on TPU —
on the CPU tier-1 backend entries are still created but every analysis
field is null (None in JSON, NaN-not-0 on gauges, same contract as
`intellillm_mfu`), and an introspection that raises or returns empty
degrades the same way: never an exception on the dispatch path.

INTELLILLM_KERNEL_LEDGER=0 disables everything (hooks become no-ops).
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)

try:
    from prometheus_client import Gauge
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

_DEFAULT_MFU_WINDOW = 64
# Bounded label cardinality: the runner dispatches exactly these
# programs (worker/model_runner.py); anything else is labeled "other"
# so a future call site cannot explode the series space.
KNOWN_PROGRAMS = ("mixed", "decode_fused", "decode_cont", "decode_teacher")

# Capture bounds for POST /debug/profiler/capture (debug_routes.py).
_DEFAULT_CAPTURE_MAX_STEPS = 64
_DEFAULT_CAPTURE_TIMEOUT_S = 30.0


class _KernelMetrics:
    """Prometheus collectors for the kernel ledger (process-global,
    built once — same singleton pattern as engine/metrics._Metrics)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init()
        return cls._instance

    def _init(self) -> None:
        self.gauge_flops = Gauge(
            "intellillm_kernel_flops",
            "cost_analysis() FLOPs of the program's most expensive "
            "executable (max over live jit buckets). NaN until a bucket "
            "of the program is introspected.", ["program"])
        self.gauge_bytes = Gauge(
            "intellillm_kernel_bytes_accessed",
            "cost_analysis() bytes accessed (HBM traffic) of the "
            "program's most expensive executable. NaN until "
            "introspected.", ["program"])
        self.gauge_hbm_peak = Gauge(
            "intellillm_kernel_hbm_peak_bytes",
            "memory_analysis() peak HBM estimate (arguments + outputs + "
            "temps + generated code) of the program's hungriest "
            "executable. NaN until introspected.", ["program"])
        self.gauge_executables = Gauge(
            "intellillm_kernel_executables",
            "Ledger entries (live jit buckets) per program.", ["program"])
        self.gauge_mfu_costmodel = Gauge(
            "intellillm_kernel_mfu_costmodel",
            "Rolling MFU from XLA cost_analysis() FLOPs (vs the analytic "
            "intellillm_mfu — two FLOPs models, one quantity). NaN when "
            "peak FLOPs or per-executable FLOPs are unknown (CPU).")

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


def _enabled_from_env() -> bool:
    from intellillm_tpu.utils import parse_env_flag
    flag = parse_env_flag(os.environ.get("INTELLILLM_KERNEL_LEDGER"))
    return True if flag is None else flag


def _introspect_mode_from_env() -> str:
    """"auto" (TPU/GPU only), "on", or "off"."""
    raw = (os.environ.get("INTELLILLM_KERNEL_INTROSPECT") or "auto")
    raw = raw.strip().lower()
    if raw in ("auto", ""):
        return "auto"
    from intellillm_tpu.utils import parse_env_flag
    flag = parse_env_flag(raw)
    if flag is None:
        logger.warning("Ignoring invalid INTELLILLM_KERNEL_INTROSPECT=%r "
                       "(want auto, 1, or 0).", raw)
        return "auto"
    return "on" if flag else "off"


def _env_positive(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        value = float(raw)
    except ValueError:
        logger.warning("Ignoring invalid %s=%r (want a number).", name, raw)
        return default
    return value if value > 0 else default


def capture_max_steps() -> int:
    """Upper bound on ?steps= for POST /debug/profiler/capture."""
    return int(_env_positive("INTELLILLM_PROFILER_CAPTURE_MAX_STEPS",
                             _DEFAULT_CAPTURE_MAX_STEPS))


def capture_timeout_s() -> float:
    """Give-up wall-clock for a capture waiting on engine steps (idle
    engines would otherwise hold the profiler open forever)."""
    return _env_positive("INTELLILLM_PROFILER_CAPTURE_TIMEOUT_S",
                         _DEFAULT_CAPTURE_TIMEOUT_S)


def _abstractify(tree):
    """ShapeDtypeStructs for the array leaves, everything else kept
    verbatim. Must run BEFORE the dispatch: kv_caches are donated, so
    the concrete buffers are deleted once the call returns."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def _parse_cost_analysis(raw) -> Dict[str, Optional[float]]:
    """jax's Compiled.cost_analysis() returns a dict on some versions
    and a per-device LIST of dicts on others (0.4.x); fold either into
    {flops, bytes_accessed, transcendentals}, None for absent keys."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else {}
    if not isinstance(raw, dict):
        return {}
    out: Dict[str, Optional[float]] = {}
    for field, key in (("flops", "flops"),
                       ("bytes_accessed", "bytes accessed"),
                       ("transcendentals", "transcendentals")):
        value = raw.get(key)
        try:
            value = float(value)
        except (TypeError, ValueError):
            value = None
        # XLA reports -1 for "unknown"; normalize to null per the
        # degradation contract (NaN-not-0, None-not-0).
        out[field] = value if value is not None and value >= 0 else None
    return out


class _Pending:
    """First-dispatch token handed from prepare() to commit()/abandon().
    Holds the abstract call signature captured pre-donation."""

    __slots__ = ("program", "key", "fn", "abstract_args", "kwargs",
                 "introspect")

    def __init__(self, program, key, fn, abstract_args, kwargs, introspect):
        self.program = program
        self.key = key
        self.fn = fn
        self.abstract_args = abstract_args
        self.kwargs = kwargs
        self.introspect = introspect


class KernelLedger:
    """Process-global per-(program, bucket) cost ledger (one engine per
    process, same as CompileTracker). The dispatch-path hooks are
    dict/set updates under one lock; introspection (a second XLA
    compile) runs only on the first dispatch of a bucket and only when
    the backend warrants it — and NEVER raises into the dispatch."""

    def __init__(self, enabled: Optional[bool] = None) -> None:
        self.enabled = (_enabled_from_env() if enabled is None else enabled)
        self.introspect_mode = _introspect_mode_from_env()
        self._lock = threading.Lock()
        self._seen: Dict[str, set] = {}
        # (program, bucket-str) -> entry dict (JSON-safe values only).
        self._entries: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._backend: Optional[str] = None
        self._device_kind: Optional[str] = None
        self._peak_flops: Optional[float] = None
        self._device_resolved = False
        # Cost-model MFU: FLOPs dispatched since the last step boundary,
        # folded into a rolling (flops, seconds) window like
        # efficiency.py's token window.
        window = _env_positive("INTELLILLM_MFU_WINDOW",
                               _DEFAULT_MFU_WINDOW)
        self._steps: deque = deque(maxlen=max(int(window), 1))
        self._pending_flops = 0.0
        self._pending_flops_known = True
        self._num_steps = 0
        self._mfu_costmodel: Optional[float] = None
        # Measured feed (merge_profile): the latest capture's op table.
        self._profile: Optional[Dict[str, Any]] = None
        self._metrics = _KernelMetrics() if _PROMETHEUS else None
        if self._metrics is not None:
            self._metrics.gauge_mfu_costmodel.set(float("nan"))

    # --- backend resolution (lazy: jax may not be initialized yet) --------

    def _resolve_device_locked(self) -> None:
        if self._device_resolved:
            return
        self._device_resolved = True
        try:
            import jax
            self._backend = jax.default_backend()
            devices = jax.local_devices()
            if devices:
                self._device_kind = (
                    getattr(devices[0], "device_kind", None)
                    or getattr(devices[0], "platform", None))
        except Exception:
            self._backend = None
        from intellillm_tpu.obs.efficiency import resolve_peak_flops
        self._peak_flops = resolve_peak_flops(self._device_kind)

    def _should_introspect_locked(self) -> bool:
        if self.introspect_mode == "off":
            return False
        if self.introspect_mode == "on":
            return True
        # auto: lower().compile() costs a second compile per bucket —
        # free-ish on TPU (persistent compile cache), pure overhead on
        # the CPU tier-1 backend, where entries stay null instead.
        self._resolve_device_locked()
        return self._backend not in (None, "cpu")

    # --- dispatch-path hooks (model_runner._guarded_call) -----------------

    def prepare(self, program: str, key, fn, args: tuple,
                kwargs: dict) -> Optional[_Pending]:
        """Called before EVERY jit dispatch. Seen bucket: count the
        dispatch, accumulate its cost-model FLOPs for the step window,
        return None. New bucket: capture the abstract signature (before
        donation invalidates the buffers) and return a pending token."""
        if not self.enabled:
            return None
        bucket = repr(key)
        with self._lock:
            seen = self._seen.setdefault(program, set())
            if key in seen:
                entry = self._entries.get((program, bucket))
                if entry is not None:
                    entry["dispatches"] += 1
                    self._account_flops_locked(entry)
                return None
            seen.add(key)
            introspect = self._should_introspect_locked()
        abstract_args = None
        if introspect:
            try:
                abstract_args = _abstractify(args)
            except Exception as e:  # never break the dispatch
                logger.warning("Kernel ledger: cannot abstract args for "
                               "%s %s (%s); entry will be null.",
                               program, bucket, e)
                introspect = False
        return _Pending(program, key, fn, abstract_args, kwargs, introspect)

    def abandon(self, pending: Optional[_Pending]) -> None:
        """First dispatch raised (compile OOM etc.): forget the key so a
        retry is introspected fresh — mirrors CompileTracker."""
        if pending is None:
            return
        with self._lock:
            self._seen.get(pending.program, set()).discard(pending.key)

    def commit(self, pending: Optional[_Pending],
               elapsed: float) -> None:
        """First dispatch succeeded: introspect the executable and write
        the ledger entry. Any introspection failure degrades to a null
        entry (the CPU contract) — this method never raises."""
        if pending is None:
            return
        entry: Dict[str, Any] = {
            "program": pending.program,
            "bucket": repr(pending.key),
            "flops": None,
            "bytes_accessed": None,
            "transcendentals": None,
            "intensity_flops_per_byte": None,
            "hbm_argument_bytes": None,
            "hbm_output_bytes": None,
            "hbm_temp_bytes": None,
            "hbm_generated_code_bytes": None,
            "hbm_peak_bytes": None,
            "compile_seconds": round(float(elapsed), 6),
            "introspect_seconds": None,
            "dispatches": 1,
            "analysis": "skipped",
        }
        if pending.introspect:
            # The first call's stage `introspect` (obs/compile_tracker.py):
            # what this `lower().compile()` behind it cost (on jax 0.9.0
            # it is served from jax's in-memory caches: 0.02-0.05 s a
            # program on the chip, PERF.md section 7).
            from intellillm_tpu.obs.compile_tracker import (
                get_compile_tracker)
            with get_compile_tracker().introspection(
                    pending.program, pending.key) as span:
                try:
                    self._introspect_into(entry, pending)
                except Exception as e:
                    entry["analysis"] = "error"
                    logger.warning(
                        "Kernel ledger: introspection failed for %s %s "
                        "(%s); entry fields stay null.", pending.program,
                        entry["bucket"], e)
            entry["introspect_seconds"] = round(span["seconds"], 6)
        with self._lock:
            self._entries[(pending.program, entry["bucket"])] = entry
            self._account_flops_locked(entry)
            aggregates = self._program_aggregates_locked()
        self._export_metrics(aggregates)

    def _introspect_into(self, entry: Dict[str, Any],
                         pending: _Pending) -> None:
        compiled = pending.fn.lower(*pending.abstract_args,
                                    **pending.kwargs).compile()
        cost = _parse_cost_analysis(compiled.cost_analysis())
        entry.update(cost)
        flops = entry.get("flops")
        byts = entry.get("bytes_accessed")
        if flops is not None and byts:
            entry["intensity_flops_per_byte"] = round(flops / byts, 3)
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        if mem is not None:
            for field, attr in (
                    ("hbm_argument_bytes", "argument_size_in_bytes"),
                    ("hbm_output_bytes", "output_size_in_bytes"),
                    ("hbm_temp_bytes", "temp_size_in_bytes"),
                    ("hbm_generated_code_bytes",
                     "generated_code_size_in_bytes")):
                value = getattr(mem, attr, None)
                entry[field] = int(value) if value is not None else None
            parts = [entry[f] for f in ("hbm_argument_bytes",
                                        "hbm_output_bytes",
                                        "hbm_temp_bytes",
                                        "hbm_generated_code_bytes")]
            if any(p is not None for p in parts):
                entry["hbm_peak_bytes"] = sum(p for p in parts
                                              if p is not None)
        entry["analysis"] = ("ok" if any(
            entry[f] is not None for f in ("flops", "bytes_accessed",
                                           "hbm_peak_bytes")) else "empty")

    # --- cost-model MFU (engine step boundary) ----------------------------

    def _account_flops_locked(self, entry: Dict[str, Any]) -> None:
        flops = entry.get("flops")
        if flops is None:
            # One un-introspected dispatch poisons the whole step: a
            # partial FLOPs sum would UNDERstate MFU, so the step reads
            # null instead (NaN-not-0 contract).
            self._pending_flops_known = False
        else:
            self._pending_flops += flops

    def record_step(self, step_time: Optional[float]) -> Optional[float]:
        """Engine step boundary: fold the cost-model FLOPs dispatched
        since the previous boundary with this step's wall time into the
        rolling cost-model MFU. Returns the rolling value (None when
        peak FLOPs or any dispatch's FLOPs are unknown)."""
        if not self.enabled:
            return None
        with self._lock:
            flops = self._pending_flops
            known = self._pending_flops_known
            self._pending_flops = 0.0
            self._pending_flops_known = True
            if step_time is None or step_time <= 0:
                return self._mfu_costmodel
            self._num_steps += 1
            if not known:
                # Drop the whole window on an unknown step rather than
                # mixing known and unknown FLOPs sums.
                self._steps.clear()
                self._mfu_costmodel = None
                mfu = None
            else:
                self._steps.append((flops, float(step_time)))
                mfu = self._rolling_mfu_locked()
                self._mfu_costmodel = mfu
        if self._metrics is not None:
            self._metrics.gauge_mfu_costmodel.set(
                mfu if mfu is not None else float("nan"))
        return mfu

    def _rolling_mfu_locked(self) -> Optional[float]:
        self._resolve_device_locked()
        if self._peak_flops is None or not self._steps:
            return None
        total_s = sum(dt for _, dt in self._steps)
        if total_s <= 0:
            return None
        total_flops = sum(f for f, _ in self._steps)
        return total_flops / (total_s * self._peak_flops)

    # --- measured feed (profiler capture) ---------------------------------

    def step_count(self) -> int:
        with self._lock:
            return self._num_steps

    def merge_profile(self, reduced: Optional[Dict[str, Any]], *,
                      steps: int, top: int = 16) -> Dict[str, Any]:
        """Store a capture's reduction (reduce_profile's result; None for
        a capture that left no readable trace) next to the static feed,
        its operation table cut to the top-K by self time and its
        (phase, program) gap table to the top 2K. Returns the stored
        block."""
        block: Dict[str, Any] = dict(reduced or {
            "window_s": 0.0, "busy_s": 0.0, "idle_s": 0.0,
            "idle_share": None, "devices": [], "ops": [], "programs": [],
            "idle_by_phase": {}, "idle_by_phase_program": [],
            "annotations": {}})
        ops = block["ops"]
        block["ops_total"] = len(ops)
        block["ops"] = ops[:max(int(top), 1)]
        block["idle_by_phase_program"] = block["idle_by_phase_program"][
            :2 * max(int(top), 1)]
        block["steps"] = int(steps)
        with self._lock:
            block["captured_at_step"] = self._num_steps
            self._profile = block
        return block

    # --- read side (endpoints / top / serve_bench / bench) ----------------

    def _program_aggregates_locked(self) -> Dict[str, Dict[str, Any]]:
        aggregates: Dict[str, Dict[str, Any]] = {}
        for (program, _), entry in self._entries.items():
            agg = aggregates.setdefault(program, {
                "executables": 0, "dispatches": 0, "flops_max": None,
                "bytes_accessed_max": None, "hbm_peak_bytes_max": None,
                "compile_seconds_total": 0.0, "analyzed": 0,
            })
            agg["executables"] += 1
            agg["dispatches"] += entry["dispatches"]
            agg["compile_seconds_total"] += entry["compile_seconds"] or 0.0
            if entry["analysis"] == "ok":
                agg["analyzed"] += 1
            for field in ("flops", "bytes_accessed", "hbm_peak_bytes"):
                value = entry.get(field)
                if value is None:
                    continue
                prev = agg[field + "_max"]
                agg[field + "_max"] = (value if prev is None
                                       else max(prev, value))
        for agg in aggregates.values():
            agg["compile_seconds_total"] = round(
                agg["compile_seconds_total"], 4)
        return aggregates

    def _export_metrics(self,
                        aggregates: Dict[str, Dict[str, Any]]) -> None:
        if self._metrics is None:
            return
        m = self._metrics
        for program, agg in aggregates.items():
            label = program if program in KNOWN_PROGRAMS else "other"
            nan = float("nan")
            m.gauge_flops.labels(label).set(
                agg["flops_max"] if agg["flops_max"] is not None else nan)
            m.gauge_bytes.labels(label).set(
                agg["bytes_accessed_max"]
                if agg["bytes_accessed_max"] is not None else nan)
            m.gauge_hbm_peak.labels(label).set(
                agg["hbm_peak_bytes_max"]
                if agg["hbm_peak_bytes_max"] is not None else nan)
            m.gauge_executables.labels(label).set(agg["executables"])

    @staticmethod
    def _entry_sort_key(entry: Dict[str, Any]):
        # Analyzed entries first, most expensive first; null entries
        # follow, hottest (most dispatched) first.
        flops = entry.get("flops")
        return (0 if flops is not None else 1,
                -(flops or 0.0), -entry["dispatches"])

    def snapshot(self, top: int = 8) -> Dict[str, Any]:
        """JSON-safe ledger for GET /debug/kernels and serve_bench
        (unknown values are None — never NaN, never 0)."""
        with self._lock:
            self._resolve_device_locked()
            entries = sorted((dict(e) for e in self._entries.values()),
                             key=self._entry_sort_key)
            mfu_cm = self._mfu_costmodel
            body = {
                "enabled": self.enabled,
                "introspection": self.introspect_mode,
                "backend": self._backend,
                "device_kind": self._device_kind,
                "peak_flops": self._peak_flops,
                "executables_total": len(entries),
                "executables": entries[:max(int(top), 0)],
                "programs": self._program_aggregates_locked(),
                "steps": self._num_steps,
                "mfu_costmodel": (round(mfu_cm, 6)
                                  if mfu_cm is not None
                                  and math.isfinite(mfu_cm) else None),
                "profile": (dict(self._profile)
                            if self._profile is not None else None),
            }
        # Cross-check: the analytic rolling MFU next to the cost-model
        # one (ISSUE: two modules must not silently disagree about the
        # FLOPs model — export both, document the gap).
        from intellillm_tpu.obs.efficiency import get_efficiency_tracker
        mfu = get_efficiency_tracker().rolling_mfu()
        body["mfu_analytic"] = (round(mfu, 6)
                                if mfu is not None and math.isfinite(mfu)
                                else None)
        # Which path each kernel seam would take if a program were traced
        # right now (docs/kernels.md) — lets a /debug/kernels before/after
        # say WHICH kernels produced the ledger it shows.
        try:
            from intellillm_tpu.ops.dispatch import kernel_selection
            body["selection"] = kernel_selection()
        except Exception:  # pragma: no cover - ops layer must not break obs
            body["selection"] = None
        return body

    def health_block(self) -> Dict[str, Any]:
        """Compact block for /health/detail (full table at
        /debug/kernels)."""
        snap = self.snapshot(top=0)
        return {
            "enabled": snap["enabled"],
            "introspection": snap["introspection"],
            "executables_total": snap["executables_total"],
            "programs": snap["programs"],
            "mfu_costmodel": snap["mfu_costmodel"],
            "mfu_analytic": snap["mfu_analytic"],
            "profiled_steps": (snap["profile"] or {}).get("steps"),
        }

    def reset_for_testing(self) -> None:
        _KernelMetrics.reset_for_testing()
        self.__init__()


# --- the profiler capture's reader ------------------------------------------
#
# doc = {"planes": [{"name", "lines": [{"name", "events":
#        [[name, start_ns, duration_ns], ...]}]}]}: device planes whole, of
# the host planes only the tracer's annotations. All planes share a clock.

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_HOST_PLANE = re.compile(r"^/host:")
_OP_LINE = re.compile(r"^XLA Ops$")
_MODULE_LINE = re.compile(r"^XLA Modules$")
# The CPU backend has no device plane: its executables' thunks run on the
# client's own threads in the host plane, between bookkeeping events.
_CPU_OP_LINE = re.compile(r"^tf_XLA\w*CpuClient")
_CPU_NOT_AN_OP = re.compile(r"::|^end: |\(wait for completion\)")
# jit names of the runner's step functions -> the programs' names.
_PROGRAM_OF_MODULE = {"jit__decode_fn_single": "mixed",
                      "jit__decode_fn": "decode_fused",
                      "jit__decode_cont_fn": "decode_cont",
                      "jit__decode_teacher_fn": "decode_teacher"}


def load_profile(logdir: str) -> Optional[Dict[str, Any]]:
    """The newest `*.xplane.pb` under `logdir` as a plain doc, or None
    when there is none or it cannot be read (the capture endpoint then
    answers an empty block, not a 500). jax's reader is imported here:
    nothing on a serving path pays for it."""
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    from intellillm_tpu.obs.tracing import ANNOTATION_PREFIX
    try:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(paths[-1])
        planes = []
        for plane in data.planes:
            device = bool(_DEVICE_PLANE.search(plane.name))
            if not (device or _HOST_PLANE.search(plane.name)):
                continue
            lines = []
            for line in plane.lines:
                keep_all = device or _CPU_OP_LINE.search(line.name)
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events
                          if keep_all
                          or ev.name.startswith(ANNOTATION_PREFIX)]
                if events:
                    lines.append({"name": line.name, "events": events})
            planes.append({"name": plane.name, "lines": lines})
    except Exception as e:
        logger.warning("Kernel ledger: unreadable profile %s (%s).",
                       paths[-1], e)
        return None
    return {"planes": planes, "file": os.path.basename(paths[-1])}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[list]) -> Dict[str, List[float]]:
    """name -> [self nanoseconds, count] on one line: an event's duration
    minus the events nested directly inside it."""
    totals: Dict[str, List[float]] = {}
    stack: List[list] = []      # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            cell = totals.setdefault(name, [0.0, 0])
            cell[0] += max(0, self_ns)
            cell[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def _innermost_segments(spans: List[Tuple[int, int, str]]
                        ) -> List[Tuple[int, int, str]]:
    """Annotation spans (start, end, phase), nested or on several threads
    -> disjoint sorted segments, each instant given to the span that began
    last among those covering it (the innermost)."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []        # (end, phase)
    cursor = 0

    def advance(upto: float) -> None:
        nonlocal cursor
        while stack:
            while stack and stack[-1][0] <= cursor:
                stack.pop()
            if not stack or cursor >= upto:
                return
            end = min(stack[-1][0], upto)
            out.append((cursor, end, stack[-1][1]))
            cursor = end

    for start, end, phase in sorted(spans, key=lambda x: (x[0], -x[1])):
        advance(start)
        cursor = start
        stack.append((end, phase))
    advance(float("inf"))
    return out


def _split_gap(g0: int, g1: int, segments: List[Tuple[int, int, str]],
               starts: List[int]) -> Dict[str, int]:
    """Nanoseconds of the idle gap [g0, g1) by host phase. An instant no
    annotation covers is a thread crossing if it lies between the
    annotations at either side of one (`handoff` after admit, `handback`
    before stream_out), else `unannotated`."""
    out: Dict[str, int] = {}

    def add(phase: str, ns: int) -> None:
        if ns > 0:
            out[phase] = out.get(phase, 0) + ns

    def hole(before: Optional[str], after: Optional[str]) -> str:
        if before == "admit":
            return "handoff"
        if after == "stream_out" and before not in (None, "stream_out"):
            return "handback"
        return "unannotated"

    k = max(bisect.bisect_right(starts, g0) - 1, 0)
    before = segments[k - 1][2] if k > 0 else None
    cursor = g0
    while k < len(segments) and segments[k][0] < g1:
        s, e, phase = segments[k]
        if e > cursor:
            add(hole(before, phase), min(s, g1) - cursor)
            lo = max(s, cursor)
            add(phase, min(e, g1) - lo)
            cursor = max(cursor, min(e, g1))
        before = phase
        k += 1
    after = segments[k][2] if k < len(segments) else None
    add(hole(before, after), g1 - cursor)
    return out


def _program_name(module_event: str) -> str:
    base = re.sub(r"\(\d+\)$", "", module_event)
    return _PROGRAM_OF_MODULE.get(base, base)


def reduce_profile(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Busy union, idle, self time by operation, time by program, and the
    idle gaps by host phase — averaged over the device planes. Raises
    ValueError when the doc holds no device operations."""
    from intellillm_tpu.obs.tracing import ANNOTATION_PREFIX
    devices = [p for p in doc["planes"] if _DEVICE_PLANE.search(p["name"])]
    op_line, cpu = _OP_LINE, False
    if not devices:
        devices = [p for p in doc["planes"] if _HOST_PLANE.search(p["name"])]
        op_line, cpu = _CPU_OP_LINE, True
    spans: List[Tuple[int, int, str]] = []
    annotations: Dict[str, int] = {}
    for plane in doc["planes"]:
        if not _HOST_PLANE.search(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(ANNOTATION_PREFIX):
                    phase = name[len(ANNOTATION_PREFIX):]
                    spans.append((start, start + dur, phase))
                    annotations[phase] = annotations.get(phase, 0) + 1
    segments = _innermost_segments(spans)
    seg_starts = [s for s, _, _ in segments]

    per_device = []
    first, last = [], []
    ops: Dict[str, List[float]] = {}
    programs: Dict[str, List[float]] = {}
    by_phase: Dict[str, float] = {}
    by_phase_program: Dict[Tuple[str, str], List[float]] = {}
    gap_inputs = []
    for plane in devices:
        op_events = [e for ln in plane["lines"] if op_line.search(ln["name"])
                     for e in ln["events"]
                     if not (cpu and (e[2] <= 0
                                      or _CPU_NOT_AN_OP.search(e[0])))]
        if not op_events:
            continue
        mod_events = [e for ln in plane["lines"]
                      if _MODULE_LINE.search(ln["name"])
                      for e in ln["events"]]
        busy = _union([(s, s + d) for _, s, d in op_events])
        per_device.append({"plane": plane["name"],
                           "busy_s": sum(e - s for s, e in busy) / 1e9,
                           "op_events": len(op_events)})
        first.append(busy[0][0])
        last.append(busy[-1][1])
        for name, (ns, n) in _self_times(op_events).items():
            cell = ops.setdefault(name, [0.0, 0])
            cell[0] += ns
            cell[1] += n
        for name, _, d in mod_events:
            cell = programs.setdefault(_program_name(name), [0.0, 0])
            cell[0] += d
            cell[1] += 1
        gap_inputs.append((busy, sorted((s, name) for name, s, _ in
                                        mod_events),
                           # of two operations that start together, the
                           # enclosing one
                           {s: name for name, s, _ in
                            sorted(op_events, key=lambda e: e[2])}))
    if not per_device:
        raise ValueError(
            "no device operations in the profile; its planes are "
            f"{[p['name'] for p in doc['planes']]}")
    n = len(per_device)
    w0, w1 = min(first), max(last)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(d["busy_s"] for d in per_device) / n
    # Idle gaps: between two busy intervals of a device, and from the
    # window's edges to a device's first and last operation (several chips
    # do not start and end together), so that they add up to window - busy.
    # A gap is also named by what ended it: the program that starts at its
    # end, else the operation (its name up to the `=`).
    for busy, mod_starts, op_starts in gap_inputs:
        edges = [(w0, w0)] + busy + [(w1, w1)]
        for (_, g0), (g1, _) in zip(edges, edges[1:]):
            if g1 <= g0:
                continue
            k = bisect.bisect_left(mod_starts, (g0, ""))
            if k < len(mod_starts) and mod_starts[k][0] <= g1:
                ended_by = _program_name(mod_starts[k][1])
            elif g1 in op_starts:
                ended_by = op_starts[g1].split(" = ")[0][:80]
            else:
                ended_by = "end of window"
            for phase, ns in _split_gap(g0, g1, segments,
                                        seg_starts).items():
                by_phase[phase] = by_phase.get(phase, 0.0) + ns
                cell = by_phase_program.setdefault((phase, ended_by),
                                                   [0.0, 0])
                cell[0] += ns
                cell[1] += 1

    def table(cells: Dict[str, List[float]]) -> List[list]:
        return sorted(([name, ns / 1e9 / n, cnt]
                       for name, (ns, cnt) in cells.items()),
                      key=lambda r: -r[1])

    idle_s = window_s - busy_s
    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_s": idle_s,
        "idle_share": idle_s / window_s if window_s else None,
        "devices": per_device,
        "ops": table(ops), "programs": table(programs),
        "idle_by_phase": {phase: ns / 1e9 / n for phase, ns in
                          sorted(by_phase.items(), key=lambda kv: -kv[1])},
        "idle_by_phase_program": sorted(
            ([phase, prog, ns / 1e9 / n, cnt] for (phase, prog), (ns, cnt)
             in by_phase_program.items()), key=lambda r: -r[2]),
        "annotations": annotations,
    }


def capture_profile(logdir: str) -> Optional[Dict[str, Any]]:
    """load_profile + reduce_profile for the capture endpoint: None when
    the capture left nothing to reduce."""
    doc = load_profile(logdir)
    if doc is None:
        return None
    try:
        return reduce_profile(doc)
    except ValueError as e:
        logger.warning("Kernel ledger: %s", e)
        return None


_LEDGER: Optional[KernelLedger] = None
_LEDGER_LOCK = threading.Lock()


def get_kernel_ledger() -> KernelLedger:
    global _LEDGER
    if _LEDGER is None:
        with _LEDGER_LOCK:
            if _LEDGER is None:
                _LEDGER = KernelLedger()
    return _LEDGER


def wait_for_steps(ledger: KernelLedger, target_steps: int,
                   timeout_s: Optional[float] = None,
                   poll_s: float = 0.05) -> int:
    """Block (call from an executor thread, never the event loop) until
    the engine has advanced `target_steps` step boundaries past the
    current count, or `timeout_s` elapsed. Returns steps observed."""
    if timeout_s is None:
        timeout_s = capture_timeout_s()
    start = ledger.step_count()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        observed = ledger.step_count() - start
        if observed >= target_steps:
            return observed
        time.sleep(poll_s)
    return ledger.step_count() - start
