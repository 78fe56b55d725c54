"""Numerics & output-integrity observability.

The rest of the obs stack explains *where time goes*; this module
watches whether the *numbers are right* — the three silent-corruption
channels a TPU serving fleet actually has:

- **In-graph sentinels** (opt-in: `--enable-numerics` /
  `INTELLILLM_NUMERICS`): the mixed dispatch returns a tiny per-row
  logit-statistics panel (NaN count, +Inf count, finite max-abs, top-1
  probability, entropy) as an extra device output. A row that trips a
  sentinel (any NaN, any +Inf, or max-abs past
  `INTELLILLM_NUMERICS_MAX_ABS`) is quarantined: the engine finishes
  the request with a structured abort instead of streaming the
  poisoned token, records a `numerics_anomaly` flight event, and the
  page-severity `numerics_anomaly` alert rule fires.
- **KV integrity auditing**: sampled blake2b checksums of host-staged
  KV blocks, recorded at swap-out and verified at swap-in (the
  export/import wire format already self-validates in transit —
  `worker/kv_transfer.py` — so those paths only count sampled staging
  hashes here). A verify mismatch is a caught bit-flip: counted,
  logged, and surfaced by the `kv_integrity_mismatch` alert rule.
- **Fleet divergence canaries**: the router's health poller
  periodically runs a deterministic greedy canary prompt through each
  replica and compares output digests fleet-wide; verdicts land in the
  `CanaryLedger` (read by the router's `/debug/numerics`, fleet
  alerts, and black-box dumps).

Exported (when `prometheus_client` is installed — python-side totals
keep the test surface working without it):

    intellillm_numerics_rows_checked_total           counter
    intellillm_numerics_anomalies_total{kind}        counter
    intellillm_numerics_quarantined_total            counter
    intellillm_kv_integrity_checksums_total{path}    counter
    intellillm_kv_integrity_mismatches_total{path}   counter

`kind` is `nan | inf | max_abs`; `path` is
`swap_out | swap_in | export | import`. Router-side canary families
(`intellillm_router_canary_*`) live in router/metrics.py. Being
`intellillm_*` counters the families are auto-sampled by the metrics
history, and the alert rules read this module's singletons directly
(same pattern as the watchdog/kv-transfer rules).

Testing hooks (forced corruption, used by the e2e tests and documented
in docs/observability.md): `NumericsTracker.inject_nan(request_id)`
poisons one logit row of the next dispatched step carrying that
request in-graph; a KV byte-flip is simulated by mutating the host
swap pool between swap-out and swap-in — the sampled audit catches it.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from intellillm_tpu.logger import init_logger
from intellillm_tpu.utils import parse_env_flag

logger = init_logger(__name__)

try:
    from prometheus_client import Counter
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False

ANOMALY_KINDS = ("nan", "inf", "max_abs")
KV_AUDIT_PATHS = ("swap_out", "swap_in", "export", "import")

# Columns of the [B, 5] float32 sentinel panel the mixed dispatch
# returns (worker/model_runner.py _compute_logits_and_sample).
STAT_COLUMNS = ("nan_count", "inf_count", "max_abs", "top1_prob", "entropy")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("%s=%r is not a number; using %s", name, raw, default)
        return default


class _NumericsMetrics:
    """Prometheus collectors (process-global, built once — same
    singleton pattern as obs/kv_transfer.py)."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init()
        return cls._instance

    def _init(self) -> None:
        self.counter_rows = Counter(
            "intellillm_numerics_rows_checked_total",
            "Logit rows checked by the in-graph numerics sentinels.")
        self.counter_anomalies = Counter(
            "intellillm_numerics_anomalies_total",
            "Sentinel trips by kind (nan | inf | max_abs).", ["kind"])
        self.counter_quarantined = Counter(
            "intellillm_numerics_quarantined_total",
            "Requests quarantined (structured abort) after a sentinel "
            "trip — never streamed a poisoned token.")
        self.counter_kv_checksums = Counter(
            "intellillm_kv_integrity_checksums_total",
            "Sampled blake2b checksums of host-staged KV blocks "
            "(path = swap_out | swap_in | export | import).", ["path"])
        self.counter_kv_mismatches = Counter(
            "intellillm_kv_integrity_mismatches_total",
            "KV checksum verify failures — caught host-pool corruption "
            "(path = swap_in today; transit is wire-validated).", ["path"])

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


class NumericsTracker:
    """Sentinel-side state: enablement, per-step panel observation,
    the anomaly ledger, and the quarantine hand-off to the engine.
    Thread-safe; works without prometheus."""

    def __init__(self, now_fn=time.monotonic) -> None:
        self._now = now_fn
        self._lock = threading.Lock()
        self.enabled = parse_env_flag(
            os.environ.get("INTELLILLM_NUMERICS", "")) is True
        self.max_abs_threshold = _env_float(
            "INTELLILLM_NUMERICS_MAX_ABS", 1e4)
        self.rows_checked = 0
        self.anomalies: Dict[str, int] = {k: 0 for k in ANOMALY_KINDS}
        self.quarantined_total = 0
        self._last_anomaly_ts: Optional[float] = None
        self._last_anomaly: Optional[Dict[str, Any]] = None
        self._recent: deque = deque(maxlen=32)
        # request_id -> anomaly info, pending engine pickup. Bounded:
        # a request the engine never processes (aborted race) must not
        # grow this without bound.
        self._quarantine: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._inject: set = set()
        self._last_step: Optional[Dict[str, Any]] = None
        self._metrics = _NumericsMetrics() if _PROMETHEUS else None

    # --- configuration ----------------------------------------------------

    def configure(self, enabled: Optional[bool] = None,
                  max_abs_threshold: Optional[float] = None) -> None:
        if enabled is not None:
            self.enabled = bool(enabled)
        if max_abs_threshold is not None:
            self.max_abs_threshold = float(max_abs_threshold)

    # --- testing hook -----------------------------------------------------

    def inject_nan(self, request_id: str) -> None:
        """Forced-corruption hook: the next dispatched step carrying
        `request_id` gets NaN added to that row's logits in-graph, so
        the full sentinel → quarantine → alert path is exercised end to
        end (not simulated host-side)."""
        with self._lock:
            self._inject.add(request_id)

    def inject_vector(self, rows: Sequence[Tuple[str, int]],
                      padded_n: int) -> np.ndarray:
        """[padded_n] float32 additive row vector for the dispatch:
        zeros normally, NaN at rows whose request has a pending
        injection (consumed here, exactly once)."""
        vec = np.zeros(padded_n, np.float32)
        with self._lock:
            if self._inject:
                hit = set()
                for i, (req_id, _seq_id) in enumerate(rows):
                    if req_id in self._inject:
                        vec[i] = np.nan
                        hit.add(req_id)
                self._inject -= hit
        return vec

    # --- observation (worker side, at the per-step fetch) -----------------

    def observe_step(self, stats: np.ndarray,
                     pairs: Iterable[Tuple[int, Tuple[str, int]]]) -> None:
        """Scan the fetched [B, 5] panel for the step's real rows.
        `pairs` maps panel row index -> (request_id, seq_id)."""
        now = self._now()
        checked = 0
        tripped: List[Dict[str, Any]] = []
        top1_sum = 0.0
        entropy_sum = 0.0
        for row, (req_id, seq_id) in pairs:
            nan_c = float(stats[row, 0])
            inf_c = float(stats[row, 1])
            max_abs = float(stats[row, 2])
            checked += 1
            if np.isfinite(stats[row, 3]):
                top1_sum += float(stats[row, 3])
            if np.isfinite(stats[row, 4]):
                entropy_sum += float(stats[row, 4])
            kinds = []
            if nan_c > 0 or not np.isfinite(max_abs):
                kinds.append("nan")
            if inf_c > 0:
                kinds.append("inf")
            if max_abs > self.max_abs_threshold:
                kinds.append("max_abs")
            if kinds:
                tripped.append({
                    "request_id": req_id, "seq_id": seq_id,
                    "kinds": kinds, "nan_count": nan_c, "inf_count": inf_c,
                    "max_abs": max_abs, "ts": now,
                })
        with self._lock:
            self.rows_checked += checked
            self._last_step = {
                "rows": checked,
                "mean_top1_prob": round(top1_sum / checked, 6)
                if checked else None,
                "mean_entropy": round(entropy_sum / checked, 6)
                if checked else None,
            }
            for info in tripped:
                for kind in info["kinds"]:
                    self.anomalies[kind] += 1
                self._last_anomaly_ts = now
                self._last_anomaly = info
                self._recent.append(info)
                self._quarantine[info["request_id"]] = info
                while len(self._quarantine) > 256:
                    self._quarantine.popitem(last=False)
        if self._metrics is not None:
            if checked:
                self._metrics.counter_rows.inc(checked)
            for info in tripped:
                for kind in info["kinds"]:
                    self._metrics.counter_anomalies.labels(kind).inc()
        for info in tripped:
            logger.error(
                "numerics sentinel tripped for request %s seq %s: %s "
                "(nan=%g inf=%g max_abs=%g) — quarantining",
                info["request_id"], info["seq_id"],
                ",".join(info["kinds"]), info["nan_count"],
                info["inf_count"], info["max_abs"])

    # --- quarantine hand-off (engine side) --------------------------------

    def take_quarantine(self, request_id: str) -> Optional[Dict[str, Any]]:
        """Pop and return the pending anomaly for `request_id` (None if
        clean). The engine calls this before streaming a step's token;
        a hit means: finish with a structured error instead."""
        with self._lock:
            info = self._quarantine.pop(request_id, None)
            if info is not None:
                self.quarantined_total += 1
        if info is not None and self._metrics is not None:
            self._metrics.counter_quarantined.inc()
        return info

    # --- read side --------------------------------------------------------

    def last_anomaly_age_s(self) -> Optional[float]:
        with self._lock:
            if self._last_anomaly_ts is None:
                return None
            return self._now() - self._last_anomaly_ts

    def health_block(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "rows_checked": self.rows_checked,
                "anomalies": sum(self.anomalies.values()),
                "quarantined": self.quarantined_total,
            }

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "max_abs_threshold": self.max_abs_threshold,
                "rows_checked": self.rows_checked,
                "anomalies": dict(self.anomalies),
                "quarantined": self.quarantined_total,
                "last_anomaly": dict(self._last_anomaly)
                if self._last_anomaly else None,
                "recent_anomalies": [dict(a) for a in self._recent],
                "last_step": dict(self._last_step)
                if self._last_step else None,
            }


class KVIntegrityAuditor:
    """Sampled blake2b checksums of host-staged KV blocks.

    The swap path is the verified one: `record("swap_out", ...)` hashes
    a sampled block right after the synchronous device→host copy and
    `verify("swap_in", ...)` re-hashes the same host block before it is
    scattered back to the device — any bit that flipped while the block
    sat in the host pool is caught as a mismatch instead of silently
    corrupting every later token. Export/import staging hashes are
    counted for coverage telemetry only: transit integrity on those
    paths is the wire format's job (it self-validates and raises).

    Sampling is deterministic per (layer, block) so swap-out and
    swap-in always agree on which blocks carry a digest."""

    def __init__(self, now_fn=time.monotonic) -> None:
        self._now = now_fn
        self._lock = threading.Lock()
        self.enabled = parse_env_flag(
            os.environ.get("INTELLILLM_KV_AUDIT", "")) is not False
        self.sample = min(max(_env_float(
            "INTELLILLM_KV_AUDIT_SAMPLE", 0.25), 0.0), 1.0)
        self.checksums: Dict[str, int] = {p: 0 for p in KV_AUDIT_PATHS}
        self.mismatches: Dict[str, int] = {p: 0 for p in KV_AUDIT_PATHS}
        self._digests: Dict[Tuple[int, int], str] = {}
        self._last_mismatch_ts: Optional[float] = None
        self._last_mismatch: Optional[Dict[str, Any]] = None
        self._metrics = _NumericsMetrics() if _PROMETHEUS else None

    def configure(self, enabled: Optional[bool] = None,
                  sample: Optional[float] = None) -> None:
        if enabled is not None:
            self.enabled = bool(enabled)
        if sample is not None:
            self.sample = min(max(float(sample), 0.0), 1.0)

    def should_audit(self, layer: int, block: int) -> bool:
        if not self.enabled or self.sample <= 0.0:
            return False
        if self.sample >= 1.0:
            return True
        h = hashlib.blake2b(f"{layer}:{block}".encode(),
                            digest_size=8).digest()
        return int.from_bytes(h, "big") / 2**64 < self.sample

    @staticmethod
    def _digest(k_arr: np.ndarray, v_arr: Optional[np.ndarray]) -> str:
        h = hashlib.blake2b(digest_size=16)
        h.update(np.ascontiguousarray(k_arr).view(np.uint8))
        if v_arr is not None:
            h.update(np.ascontiguousarray(v_arr).view(np.uint8))
        return h.hexdigest()

    def record(self, path: str, layer: int, block: int,
               k_arr: np.ndarray, v_arr: Optional[np.ndarray] = None
               ) -> None:
        """Hash a sampled staged block: its K and V slabs, or (`v_arr`
        None) the one slab of a latent pool. `swap_out` digests are kept
        for later `verify`; export/import only count (see class
        docstring)."""
        assert path in ("swap_out", "export", "import"), path
        digest = self._digest(k_arr, v_arr)
        with self._lock:
            self.checksums[path] += 1
            if path == "swap_out":
                self._digests[(layer, block)] = digest
        if self._metrics is not None:
            self._metrics.counter_kv_checksums.labels(path).inc()

    def verify(self, path: str, layer: int, block: int,
               k_arr: np.ndarray, v_arr: Optional[np.ndarray] = None
               ) -> Optional[bool]:
        """Re-hash a host block about to be swapped in. Returns True
        (match), False (CAUGHT corruption) or None (no digest on
        record — the block wasn't sampled at swap-out)."""
        assert path == "swap_in", path
        with self._lock:
            expect = self._digests.get((layer, block))
        if expect is None:
            return None
        digest = self._digest(k_arr, v_arr)
        ok = digest == expect
        now = self._now()
        with self._lock:
            self.checksums[path] += 1
            if not ok:
                self.mismatches[path] += 1
                self._last_mismatch_ts = now
                self._last_mismatch = {
                    "path": path, "layer": layer, "block": block,
                    "expected": expect, "actual": digest, "ts": now,
                }
        if self._metrics is not None:
            self._metrics.counter_kv_checksums.labels(path).inc()
            if not ok:
                self._metrics.counter_kv_mismatches.labels(path).inc()
        if not ok:
            logger.error(
                "KV integrity mismatch at swap-in (layer %d, host block "
                "%d): expected %s got %s — host-pool corruption caught "
                "before reuse", layer, block, expect, digest)
        return ok

    def forget(self, layer: int, block: int) -> None:
        """Drop a stale digest (the host block was overwritten by a new
        swap-out; record() already replaces — this is for explicit
        invalidation if a caller frees host blocks out of band)."""
        with self._lock:
            self._digests.pop((layer, block), None)

    # --- read side --------------------------------------------------------

    def last_mismatch_age_s(self) -> Optional[float]:
        with self._lock:
            if self._last_mismatch_ts is None:
                return None
            return self._now() - self._last_mismatch_ts

    def health_block(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "sample": self.sample,
                "checksums": sum(self.checksums.values()),
                "mismatches": sum(self.mismatches.values()),
            }

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "sample": self.sample,
                "checksums": dict(self.checksums),
                "mismatches": dict(self.mismatches),
                "tracked_digests": len(self._digests),
                "last_mismatch": dict(self._last_mismatch)
                if self._last_mismatch else None,
            }


class CanaryLedger:
    """Fleet divergence canary verdicts (router process).

    The router's health poller runs a deterministic greedy canary
    prompt through each healthy replica every N poll cycles, digests
    the outputs, and records the fleet verdict here: the majority
    digest is the reference, replicas off it are `suspect`. The ledger
    is the single read surface — router `/debug/numerics`, fleet
    alerts, and black-box dumps all consume `snapshot()`."""

    def __init__(self, now_fn=time.monotonic) -> None:
        self._now = now_fn
        self._lock = threading.Lock()
        self.runs_total = 0
        self.divergence_total: Dict[str, int] = {}
        self._last_run_ts: Optional[float] = None
        self._reference: Optional[str] = None
        self._verdicts: Dict[str, Dict[str, Any]] = {}

    def record_run(self, digests: Dict[str, Optional[str]],
                   reference: Optional[str],
                   suspects: Sequence[str]) -> None:
        now = self._now()
        with self._lock:
            self.runs_total += 1
            self._last_run_ts = now
            self._reference = reference
            self._verdicts = {
                rid: {"digest": digest,
                      "suspect": rid in suspects,
                      "ts": now}
                for rid, digest in digests.items()
            }
            for rid in suspects:
                self.divergence_total[rid] = \
                    self.divergence_total.get(rid, 0) + 1

    def suspects(self) -> List[str]:
        with self._lock:
            return sorted(r for r, v in self._verdicts.items()
                          if v["suspect"])

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "runs_total": self.runs_total,
                "last_run_age_s": round(self._now() - self._last_run_ts, 3)
                if self._last_run_ts is not None else None,
                "reference_digest": self._reference,
                "verdicts": {r: dict(v) for r, v in self._verdicts.items()},
                "divergence_total": dict(self.divergence_total),
                "suspects": sorted(r for r, v in self._verdicts.items()
                                   if v["suspect"]),
            }


def numerics_health_block() -> Dict[str, Any]:
    """The compact `/health/detail` "numerics" block: sentinel and
    KV-audit counters, cheap enough to include unconditionally."""
    return {
        "sentinels": get_numerics_tracker().health_block(),
        "kv_audit": get_kv_audit().health_block(),
    }


def numerics_debug_snapshot() -> Dict[str, Any]:
    """The full `GET /debug/numerics` body (engine processes; the
    router adds its canary fleet view on top)."""
    return {
        "sentinels": get_numerics_tracker().snapshot(),
        "kv_audit": get_kv_audit().snapshot(),
    }


_TRACKER: Optional[NumericsTracker] = None
_AUDIT: Optional[KVIntegrityAuditor] = None
_CANARY: Optional[CanaryLedger] = None
_SINGLETON_LOCK = threading.Lock()


def get_numerics_tracker() -> NumericsTracker:
    global _TRACKER
    if _TRACKER is None:
        with _SINGLETON_LOCK:
            if _TRACKER is None:
                _TRACKER = NumericsTracker()
    return _TRACKER


def get_kv_audit() -> KVIntegrityAuditor:
    global _AUDIT
    if _AUDIT is None:
        with _SINGLETON_LOCK:
            if _AUDIT is None:
                _AUDIT = KVIntegrityAuditor()
    return _AUDIT


def get_canary_ledger() -> CanaryLedger:
    global _CANARY
    if _CANARY is None:
        with _SINGLETON_LOCK:
            if _CANARY is None:
                _CANARY = CanaryLedger()
    return _CANARY


def reset_for_testing() -> None:
    global _TRACKER, _AUDIT, _CANARY
    _NumericsMetrics.reset_for_testing()
    _TRACKER = None
    _AUDIT = None
    _CANARY = None
