"""Durable trace export: tail-sampling JSONL sink + crash "black box".

The flight recorder (obs/flight_recorder.py) is an in-memory ring — it
answers "what just happened" but vanishes with the process, which is
exactly when a post-mortem needs it. This module adds two durable
escape hatches:

- `TraceSink`: a tail-sampling JSONL exporter. When a request reaches a
  terminal flight-recorder event, obs/slo.py hands the finished trace
  here; traces that *matter* (SLO-violating, preempted, aborted or
  rerouted requests) are always kept, the healthy rest is sampled by a
  deterministic hash of the trace id (stable across processes — the
  router and every replica keep the SAME sampled requests, so a fleet
  trace can be stitched from the shards). Files rotate at a byte bound
  with a bounded backlog, so the sink can stay on for weeks.

- `flush_black_box()`: a crash-safe dump of everything the in-memory
  observability stack knows — live + recently-finished traces, watchdog
  state and stall reports, the SLO summary — written as one JSON file.
  `install_black_box_handlers()` hooks fatal signals for long-running
  servers and for bench.py.

Config (environment; documented in docs/observability.md):

    INTELLILLM_TRACE_EXPORT      enable the sink (default off). "0"
                                 short-circuits `maybe_export` on a
                                 single attribute check — nothing on
                                 the request path allocates.
    INTELLILLM_TRACE_DIR         sink directory (default
                                 /tmp/intellillm-traces)
    INTELLILLM_TRACE_SAMPLE      keep-fraction for healthy traces
                                 (default 0.05)
    INTELLILLM_TRACE_MAX_BYTES   rotate traces.jsonl past this size
                                 (default 32 MiB)
    INTELLILLM_TRACE_MAX_FILES   rotated files kept (default 4)
    INTELLILLM_BLACK_BOX_DIR     black-box dump directory (default
                                 /tmp/intellillm-blackbox)

Exported (when `prometheus_client` is installed — silently skipped
otherwise):

    intellillm_trace_exported_total{decision}  counter — decision is
        kept_slo | kept_sampled | dropped
"""
from __future__ import annotations

import hashlib
import json
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional

from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)

try:
    from prometheus_client import Counter
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

_DEFAULT_TRACE_DIR = "/tmp/intellillm-traces"
_DEFAULT_BLACK_BOX_DIR = "/tmp/intellillm-blackbox"
_DEFAULT_SAMPLE = 0.05
_DEFAULT_MAX_BYTES = 32 * 1024 * 1024
_DEFAULT_MAX_FILES = 4

# Request ids that cross trust boundaries (X-Request-Id headers) are
# constrained to this alphabet and length; anything else is rejected so
# a hostile header can't smuggle newlines into JSONL/log lines or grow
# ring-buffer keys without bound.
_ID_ALLOWED = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789._:-#")
MAX_REQUEST_ID_LEN = 128


def sanitize_request_id(raw: Optional[str]) -> Optional[str]:
    """Validate a client-supplied request/trace id: truncate to
    MAX_REQUEST_ID_LEN, reject empty values or ones with characters
    outside the safe alphabet. Returns the usable id or None (caller
    then mints its own)."""
    if raw is None:
        return None
    raw = raw.strip()[:MAX_REQUEST_ID_LEN]
    if not raw or any(c not in _ID_ALLOWED for c in raw):
        return None
    return raw


class _TraceMetrics:
    """Prometheus collectors for the trace sink (process-global, built
    once — same singleton pattern as obs/slo.py)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init()
        return cls._instance

    def _init(self) -> None:
        self.counter_exported = Counter(
            "intellillm_trace_exported_total",
            "Trace-sink decisions per finished request "
            "(kept_slo | kept_sampled | dropped).", ["decision"])

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


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("Ignoring invalid %s=%r", name, raw)
        return default


def _keep_hash(trace_id: str) -> float:
    """Deterministic [0, 1) sampling coordinate for a trace id — stable
    across processes and PYTHONHASHSEED, so every hop of a fleet keeps
    the same sampled requests."""
    digest = hashlib.blake2b(trace_id.encode("utf-8"),
                             digest_size=8).digest()
    return int.from_bytes(digest, "big") / 2 ** 64


class TraceSink:
    """Tail-sampling JSONL trace exporter with bounded rotation.

    `maybe_export` is called once per *finished* request (never per
    token); with the sink disabled it returns on one attribute check."""

    #: terminal reasons that are always kept, sampling aside
    ALWAYS_KEEP_REASONS = ("abort", "rerouted", "error")

    def __init__(self, enabled: Optional[bool] = None,
                 trace_dir: Optional[str] = None,
                 sample: Optional[float] = None,
                 max_bytes: Optional[int] = None,
                 max_files: Optional[int] = None) -> None:
        from intellillm_tpu.utils import parse_env_flag
        if enabled is None:
            flag = parse_env_flag(os.environ.get("INTELLILLM_TRACE_EXPORT"))
            enabled = bool(flag)  # default OFF: durable IO is opt-in
        self.enabled = enabled
        self.trace_dir = trace_dir or os.environ.get(
            "INTELLILLM_TRACE_DIR", _DEFAULT_TRACE_DIR)
        self.sample = (sample if sample is not None else
                       _env_float("INTELLILLM_TRACE_SAMPLE",
                                  _DEFAULT_SAMPLE))
        self.max_bytes = int(max_bytes if max_bytes is not None else
                             _env_float("INTELLILLM_TRACE_MAX_BYTES",
                                        _DEFAULT_MAX_BYTES))
        self.max_files = max(int(
            max_files if max_files is not None else
            _env_float("INTELLILLM_TRACE_MAX_FILES", _DEFAULT_MAX_FILES)), 1)
        self._lock = threading.Lock()
        self._metrics = _TraceMetrics() if _PROMETHEUS else None

    @property
    def path(self) -> str:
        return os.path.join(self.trace_dir, "traces.jsonl")

    # --- sampling decision ------------------------------------------------

    def _decide(self, trace_id: str, rec: Optional[Dict[str, Any]]
                ) -> Optional[str]:
        """Tail-sampling verdict: 'kept_slo' for traces an operator will
        ask about (SLO violation, preemption, abort/reroute/failure),
        'kept_sampled' for the hash-sampled healthy rest, None to drop."""
        rec = rec or {}
        interesting = (
            rec.get("slo_violated")
            or rec.get("preemptions")
            or rec.get("reason") in self.ALWAYS_KEEP_REASONS)
        if interesting:
            return "kept_slo"
        if _keep_hash(trace_id) < self.sample:
            return "kept_sampled"
        return None

    # --- export -----------------------------------------------------------

    def maybe_export(self, trace_id: str,
                     events: List[Dict[str, Any]],
                     rec: Optional[Dict[str, Any]] = None,
                     hop: Optional[str] = None,
                     decisions: Optional[List[Dict[str, Any]]] = None
                     ) -> Optional[str]:
        """Export one finished trace if the tail-sampling policy keeps
        it. Returns the decision ('kept_slo' | 'kept_sampled') or None
        when dropped/disabled. `decisions` carries the scheduler
        decision-log verdicts (obs/decisions.py) for the request, so
        exported dumps explain the waits they record."""
        if not self.enabled:
            return None
        decision = self._decide(trace_id, rec)
        if self._metrics is not None:
            self._metrics.counter_exported.labels(
                decision or "dropped").inc()
        if decision is None:
            return None
        line = json.dumps({
            "trace_id": trace_id,
            "ts": time.time(),
            "hop": hop,
            "decision": decision,
            "slo": rec,
            "events": events,
            **({"sched_decisions": decisions} if decisions else {}),
        }, separators=(",", ":"))
        try:
            with self._lock:
                os.makedirs(self.trace_dir, exist_ok=True)
                self._rotate_if_needed(len(line) + 1)
                with open(self.path, "a", encoding="utf-8") as f:
                    f.write(line + "\n")
        except OSError as e:  # a full disk must never fail a request
            logger.warning("trace export failed: %s", e)
            return None
        return decision

    def _rotate_if_needed(self, incoming: int) -> None:
        """Shift traces.jsonl → .1 → .2 … when the active file would
        exceed max_bytes; the oldest rotated file past max_files is
        deleted (caller holds the lock)."""
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return
        if size + incoming <= self.max_bytes:
            return
        oldest = f"{self.path}.{self.max_files - 1}"
        if os.path.exists(oldest):
            os.remove(oldest)
        for i in range(self.max_files - 2, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        if self.max_files > 1:
            os.replace(self.path, f"{self.path}.1")
        else:
            os.remove(self.path)

    def files(self) -> List[str]:
        """Active + rotated sink files that currently exist, newest
        first."""
        out = []
        for name in [self.path] + [f"{self.path}.{i}"
                                   for i in range(1, self.max_files)]:
            if os.path.exists(name):
                out.append(name)
        return out


# Built lazily so tests can flip the env and rebuild (same pattern as
# obs/slo.py's tracker singleton).
_TRACE_SINK: Optional[TraceSink] = None
_SINK_LOCK = threading.Lock()


def get_trace_sink() -> TraceSink:
    global _TRACE_SINK
    if _TRACE_SINK is None:
        with _SINK_LOCK:
            if _TRACE_SINK is None:
                _TRACE_SINK = TraceSink()
    return _TRACE_SINK


def reset_trace_sink_for_testing() -> None:
    global _TRACE_SINK
    with _SINK_LOCK:
        _TRACE_SINK = None
    _TraceMetrics.reset_for_testing()


# --- crash black box -------------------------------------------------------

def flush_black_box(reason: str,
                    extra: Optional[Dict[str, Any]] = None,
                    black_box_dir: Optional[str] = None) -> Optional[str]:
    """Dump everything the in-memory observability stack knows to one
    JSON file and return its path (None when even that fails — the
    black box must never raise out of a dying process).

    Contents: live + recently-finished flight-recorder traces, watchdog
    state and its ring of stall reports, the SLO summary, the numerics
    snapshot (sentinels + KV-integrity audit + canary ledger — a crash
    right after an anomaly is exactly when that context matters), and
    any caller-provided `extra` (bench.py passes its progress dict)."""
    dump: Dict[str, Any] = {
        "reason": str(reason)[:500],
        "ts": time.time(),
        "pid": os.getpid(),
    }
    try:  # each section independently best-effort
        from intellillm_tpu.obs.flight_recorder import get_flight_recorder
        recorder = get_flight_recorder()
        live_ids = recorder.live_request_ids()
        dump["live_traces"] = {
            rid: recorder.get_trace(rid) for rid in live_ids[:256]}
        dump["recent_finished"] = recorder.recent_finished(limit=64)
    except Exception as e:
        dump["live_traces_error"] = repr(e)
    try:
        from intellillm_tpu.obs.watchdog import get_watchdog
        watchdog = get_watchdog()
        dump["watchdog"] = watchdog.snapshot()
        dump["stall_reports"] = watchdog.reports()
    except Exception as e:
        dump["watchdog_error"] = repr(e)
    try:
        from intellillm_tpu.obs.slo import get_slo_tracker
        dump["slo"] = get_slo_tracker().summary()
    except Exception as e:
        dump["slo_error"] = repr(e)
    try:
        from intellillm_tpu.obs.numerics import numerics_debug_snapshot
        dump["numerics"] = numerics_debug_snapshot()
    except Exception as e:
        dump["numerics_error"] = repr(e)
    try:
        from intellillm_tpu.obs.numerics import get_canary_ledger
        canary = get_canary_ledger().snapshot()
        if canary.get("runs_total"):  # router process only
            dump["canary"] = canary
    except Exception as e:
        dump["canary_error"] = repr(e)
    if extra:
        dump["extra"] = extra

    out_dir = black_box_dir or os.environ.get(
        "INTELLILLM_BLACK_BOX_DIR", _DEFAULT_BLACK_BOX_DIR)
    path = os.path.join(out_dir,
                        f"blackbox-{os.getpid()}-{int(time.time())}.json")
    try:
        os.makedirs(out_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(dump, f, default=str)
        os.replace(tmp, path)  # readers never see a torn file
    except Exception as e:
        logger.warning("black-box flush failed: %s", e)
        return None
    return path


def install_black_box_handlers(signals=(signal.SIGTERM,)) -> None:
    """Chain a black-box flush in front of the existing handlers for
    `signals` — for long-running servers where a SIGTERM would otherwise
    take every in-flight trace with it. Callers that own their signal
    handling (aiohttp's run_app) should instead call flush_black_box()
    from their own shutdown path."""
    for signum in signals:
        previous = signal.getsignal(signum)

        def _handler(num, frame, _prev=previous):
            flush_black_box(f"signal {num}")
            if callable(_prev):
                _prev(num, frame)
            elif _prev == signal.SIG_DFL:
                signal.signal(num, signal.SIG_DFL)
                os.kill(os.getpid(), num)

        try:
            signal.signal(signum, _handler)
        except (ValueError, OSError):  # non-main thread / exotic signum
            logger.warning("could not install black-box handler for %s",
                           signum)
