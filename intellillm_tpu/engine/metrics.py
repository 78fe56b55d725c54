"""Engine metrics: Prometheus export + periodic stdout log.

Role parity: reference `vllm/engine/metrics.py` (metric definitions :22-63,
Stats :67, StatLogger.log :136) — same metric names (prefix `intellillm:`
instead of `vllm:`), using `prometheus_client` instead of aioprometheus.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs.heap import GENERATIONS, get_heap

logger = init_logger(__name__)

try:
    from prometheus_client import Counter, Gauge, Histogram
    _PROMETHEUS = True
except ImportError:  # pragma: no cover
    _PROMETHEUS = False


@dataclass
class Stats:
    """Snapshot of engine state for one iteration."""
    now: float
    num_running: int
    num_swapped: int
    num_waiting: int
    device_cache_usage: float
    cpu_cache_usage: float
    num_prompt_tokens: int
    num_generation_tokens: int
    # Absolute KV-pool byte figures (0 when block sizing is unknown, e.g.
    # synthetic Stats in tests) — the log line shows used/total alongside
    # the percentages.
    device_cache_bytes_used: int = 0
    device_cache_bytes_total: int = 0
    cpu_cache_bytes_used: int = 0
    cpu_cache_bytes_total: int = 0
    time_to_first_tokens: List[float] = field(default_factory=list)
    time_per_output_tokens: List[float] = field(default_factory=list)
    time_e2e_requests: List[float] = field(default_factory=list)
    # Speculative decoding: rolling draft-token acceptance rate (None
    # when spec decoding is off) — reference RejectionSampler counters.
    spec_acceptance_rate: Optional[float] = None
    # Step-phase breakdown from obs.tracing (exclusive seconds per phase
    # for this iteration) and the iteration's wall time. Empty / 0.0 when
    # tracing is disabled.
    step_phase_times: Dict[str, float] = field(default_factory=dict)
    step_time: float = 0.0
    # Loop gap before this step: previous end_step() -> this begin_step(),
    # None when the engine had no work pending then (or tracing is off).
    step_gap: Optional[float] = None
    # The device timeline's drain (obs.tracing): seconds each program ran
    # on the device and seconds the device sat dry under each host phase,
    # retired since the previous step's drain. Empty when tracing is off.
    device_busy: Dict[str, float] = field(default_factory=dict)
    device_idle: Dict[str, float] = field(default_factory=dict)


def _fmt_bytes(n: float) -> str:
    for unit, scale in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if abs(n) >= scale:
            return f"{n / scale:.2f}{unit}"
    return f"{int(n)}B"


class _Metrics:

    _instance = None

    def __new__(cls, labelnames: List[str]):
        # Prometheus registries are process-global; build once.
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance._init(labelnames)
        return cls._instance

    def _init(self, labelnames: List[str]) -> None:
        self.gauge_scheduler_running = Gauge(
            "intellillm_num_requests_running",
            "Number of requests currently running on TPU.", labelnames)
        self.gauge_scheduler_swapped = Gauge(
            "intellillm_num_requests_swapped",
            "Number of requests swapped to CPU.", labelnames)
        self.gauge_scheduler_waiting = Gauge(
            "intellillm_num_requests_waiting",
            "Number of requests waiting to be processed.", labelnames)
        self.gauge_device_cache_usage = Gauge(
            "intellillm_hbm_cache_usage_perc",
            "HBM KV-cache usage. 1 means 100 percent usage.", labelnames)
        self.gauge_cpu_cache_usage = Gauge(
            "intellillm_cpu_cache_usage_perc",
            "CPU swap KV-cache usage. 1 means 100 percent usage.", labelnames)
        self.counter_prompt_tokens = Counter(
            "intellillm_prompt_tokens_total",
            "Number of prefill tokens processed.", labelnames)
        self.counter_generation_tokens = Counter(
            "intellillm_generation_tokens_total",
            "Number of generation tokens processed.", labelnames)
        self.histogram_time_to_first_token = Histogram(
            "intellillm_time_to_first_token_seconds",
            "Histogram of time to first token in seconds.", labelnames,
            buckets=[0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25,
                     0.5, 0.75, 1.0, 2.5, 5.0, 7.5, 10.0])
        self.histogram_time_per_output_token = Histogram(
            "intellillm_time_per_output_token_seconds",
            "Histogram of time per output token in seconds.", labelnames,
            buckets=[0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3, 0.4,
                     0.5, 0.75, 1.0, 2.5])
        self.histogram_e2e_request_latency = Histogram(
            "intellillm_e2e_request_latency_seconds",
            "Histogram of end to end request latency in seconds.", labelnames,
            buckets=[1.0, 2.5, 5.0, 10.0, 15.0, 20.0, 30.0, 40.0, 50.0, 60.0])
        self.gauge_spec_acceptance = Gauge(
            "intellillm_spec_acceptance_rate",
            "Speculative decoding draft-token acceptance rate (rolling).",
            labelnames)
        self.histogram_step_phase = Histogram(
            "intellillm_step_phase_seconds",
            "Exclusive wall time per engine-step phase (obs.tracing).",
            list(labelnames) + ["phase"],
            buckets=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5])
        self.histogram_step_time = Histogram(
            "intellillm_step_time_seconds",
            "Total wall time of one engine step.", labelnames,
            buckets=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0])
        self.histogram_step_gap = Histogram(
            "intellillm_step_gap_seconds",
            "Loop gap: wall time from one engine step's end to the next "
            "step's begin while work was pending (obs.tracing).",
            labelnames,
            buckets=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5])
        self.counter_device_busy = Counter(
            "intellillm_device_busy_seconds_total",
            "Seconds a step program ran on the device, on the host's "
            "clock with no profiler (obs.tracing device timeline).",
            list(labelnames) + ["program"])
        self.counter_device_idle = Counter(
            "intellillm_device_idle_seconds_total",
            "Seconds the device had no dispatched step left to run while "
            "the engine had work, by the host phase (or loop_gap, "
            "unannotated) that passed meanwhile (obs.tracing).",
            list(labelnames) + ["phase"])
        self.counter_gc_pause = Counter(
            "intellillm_gc_pause_seconds_total",
            "Seconds the process stood still in Python's garbage "
            "collector, by the generation collected (obs.heap).",
            list(labelnames) + ["generation"])
        self.counter_gc_collections = Counter(
            "intellillm_gc_collections_total",
            "Collections of Python's garbage collector, by the generation "
            "collected (obs.heap).",
            list(labelnames) + ["generation"])
        self.gauge_gc_frozen = Gauge(
            "intellillm_gc_frozen_objects",
            "Objects in the collector's permanent generation, which no "
            "collection walks, as of the last freeze (obs.heap).",
            labelnames)

    @classmethod
    def reset_for_testing(cls) -> None:
        """Drop the singleton and unregister its collectors so tests can
        rebuild engines (with possibly different label sets) without
        tripping prometheus duplicate-registration errors."""
        inst = cls._instance
        if inst is not None and _PROMETHEUS:
            from prometheus_client import REGISTRY
            for collector in vars(inst).values():
                try:
                    REGISTRY.unregister(collector)
                except Exception:
                    pass
        cls._instance = None


class StatLogger:
    """Aggregates per-iteration stats; logs locally every `local_interval`
    and exports to Prometheus continuously."""

    def __init__(self, local_interval: float,
                 labels: Dict[str, str]) -> None:
        self.local_interval = local_interval
        self.labels = labels
        self.last_local_log = time.monotonic()
        self.num_prompt_tokens: List[int] = []
        self.num_generation_tokens: List[int] = []
        # Last-seen cumulative real/pad token totals from the efficiency
        # tracker (obs/efficiency.py) — interval deltas drive the
        # prefill/decode tok/s split and pad% in the periodic line.
        self._eff_tokens_prev: Dict[str, Dict[str, int]] = {}
        # Interval accumulators for the "step breakdown" log line.
        self.phase_seconds: Dict[str, float] = {}
        self.step_seconds: float = 0.0
        self.gap_seconds: float = 0.0
        self.num_steps: int = 0
        self.device_busy_seconds: float = 0.0
        self.device_idle_seconds: Dict[str, float] = {}
        self.metrics = _Metrics(list(labels.keys())) if _PROMETHEUS else None
        if self.metrics is not None:
            # A window's delta must find every series at its start.
            from intellillm_tpu.obs.kernels import KNOWN_PROGRAMS
            from intellillm_tpu.obs.tracing import IDLE_PHASES
            lv = self.labels.values()
            for program in KNOWN_PROGRAMS:
                self.metrics.counter_device_busy.labels(*lv, program)
            for phase in IDLE_PHASES:
                self.metrics.counter_device_idle.labels(*lv, phase)
            for generation in GENERATIONS:
                self.metrics.counter_gc_pause.labels(*lv, generation)
                self.metrics.counter_gc_collections.labels(*lv, generation)
            self._export_heap()

    def _export_heap(self) -> None:
        """The collector's seconds and counts since the last export
        (obs/heap.py keeps them; its hook touches no collector of
        ours)."""
        m, lv = self.metrics, self.labels.values()
        heap = get_heap()
        for generation, seconds, n in heap.drain():
            m.counter_gc_pause.labels(*lv, generation).inc(seconds)
            m.counter_gc_collections.labels(*lv, generation).inc(n)
        m.gauge_gc_frozen.labels(*lv).set(heap.frozen_objects)

    def _throughput(self, tracked: List[int], now: float) -> float:
        elapsed = now - self.last_local_log
        return sum(tracked) / elapsed if elapsed > 0 else 0.0

    def log(self, stats: Stats) -> None:
        if self.metrics is not None:
            m = self.metrics
            lv = self.labels.values()
            m.gauge_scheduler_running.labels(*lv).set(stats.num_running)
            m.gauge_scheduler_swapped.labels(*lv).set(stats.num_swapped)
            m.gauge_scheduler_waiting.labels(*lv).set(stats.num_waiting)
            m.gauge_device_cache_usage.labels(*lv).set(stats.device_cache_usage)
            m.gauge_cpu_cache_usage.labels(*lv).set(stats.cpu_cache_usage)
            m.counter_prompt_tokens.labels(*lv).inc(stats.num_prompt_tokens)
            m.counter_generation_tokens.labels(*lv).inc(
                stats.num_generation_tokens)
            for t in stats.time_to_first_tokens:
                m.histogram_time_to_first_token.labels(*lv).observe(t)
            for t in stats.time_per_output_tokens:
                m.histogram_time_per_output_token.labels(*lv).observe(t)
            for t in stats.time_e2e_requests:
                m.histogram_e2e_request_latency.labels(*lv).observe(t)
            if stats.spec_acceptance_rate is not None:
                m.gauge_spec_acceptance.labels(*lv).set(
                    stats.spec_acceptance_rate)
            for phase, secs in stats.step_phase_times.items():
                m.histogram_step_phase.labels(*lv, phase).observe(secs)
            if stats.step_time > 0.0:
                m.histogram_step_time.labels(*lv).observe(stats.step_time)
            if stats.step_gap is not None:
                m.histogram_step_gap.labels(*lv).observe(stats.step_gap)
            for program, secs in stats.device_busy.items():
                m.counter_device_busy.labels(*lv, program).inc(secs)
            for phase, secs in stats.device_idle.items():
                m.counter_device_idle.labels(*lv, phase).inc(secs)
            self._export_heap()

        self.num_prompt_tokens.append(stats.num_prompt_tokens)
        self.num_generation_tokens.append(stats.num_generation_tokens)
        for phase, secs in stats.step_phase_times.items():
            self.phase_seconds[phase] = (
                self.phase_seconds.get(phase, 0.0) + secs)
        if stats.step_time > 0.0 or stats.step_phase_times:
            self.step_seconds += stats.step_time
            self.gap_seconds += stats.step_gap or 0.0
            self.num_steps += 1
        self.device_busy_seconds += sum(stats.device_busy.values())
        for phase, secs in stats.device_idle.items():
            self.device_idle_seconds[phase] = (
                self.device_idle_seconds.get(phase, 0.0) + secs)

        if stats.now - self.last_local_log > self.local_interval:
            prefill_tps, decode_tps, mfu_str, pad_str = \
                self._efficiency_interval(stats.now)

            def usage(frac: float, used: int, total: int) -> str:
                pct = "%.1f%%" % (frac * 100)
                if total <= 0:  # byte sizing unknown (synthetic Stats)
                    return pct
                return "%s (%s/%s)" % (pct, _fmt_bytes(used),
                                       _fmt_bytes(total))

            logger.info(
                "Avg prefill throughput: %.1f tok/s, Avg decode "
                "throughput: %.1f tok/s, MFU: %s, pad: %s, Running: %d "
                "reqs, Swapped: %d reqs, Pending: %d reqs, HBM KV cache "
                "usage: %s, CPU KV cache usage: %s",
                prefill_tps, decode_tps, mfu_str, pad_str,
                stats.num_running, stats.num_swapped, stats.num_waiting,
                usage(stats.device_cache_usage,
                      stats.device_cache_bytes_used,
                      stats.device_cache_bytes_total),
                usage(stats.cpu_cache_usage, stats.cpu_cache_bytes_used,
                      stats.cpu_cache_bytes_total))
            if self.num_steps > 0 and self.phase_seconds:
                from intellillm_tpu.obs.tracing import PHASES
                ordered = [p for p in PHASES if p in self.phase_seconds]
                ordered += [p for p in self.phase_seconds
                            if p not in ordered]
                covered = sum(self.phase_seconds.values())
                # The loop's period is step + gap; the between-step phases
                # (stats ... handoff) fall in the gap.
                period = self.step_seconds + self.gap_seconds
                other = max(period - covered, 0.0)
                parts = ["%s %.1fms" % (
                    p, self.phase_seconds[p] / self.num_steps * 1e3)
                    for p in ordered]
                parts.append("other %.1fms" % (other / self.num_steps * 1e3))
                idle = sum(self.device_idle_seconds.values())
                device = ""
                if period > 0.0 and (idle or self.device_busy_seconds):
                    device = "; dev busy %.1f%% idle %.1f%%" % (
                        self.device_busy_seconds / period * 100,
                        idle / period * 100)
                    if idle:
                        device += " (%s)" % max(
                            self.device_idle_seconds,
                            key=self.device_idle_seconds.get)
                logger.info("Step breakdown over %d steps (avg/step): %s; "
                            "step %.1fms + loop gap %.1fms%s",
                            self.num_steps, ", ".join(parts),
                            self.step_seconds / self.num_steps * 1e3,
                            self.gap_seconds / self.num_steps * 1e3, device)
            self._log_slo_summary()
            self.num_prompt_tokens = []
            self.num_generation_tokens = []
            self.phase_seconds = {}
            self.step_seconds = 0.0
            self.gap_seconds = 0.0
            self.num_steps = 0
            self.device_busy_seconds = 0.0
            self.device_idle_seconds = {}
            self.last_local_log = stats.now

    def _efficiency_interval(self, now: float):
        """Prefill/decode real-token tok/s, rolling MFU, and pad%% for
        the periodic line, from the efficiency tracker's cumulative
        counters (obs/efficiency.py). When the tracker recorded nothing
        this interval (disabled, or synthetic Stats in tests) the split
        falls back to the engine-side accumulators and pad%% reads
        n/a."""
        from intellillm_tpu.obs.efficiency import get_efficiency_tracker
        eff = get_efficiency_tracker()
        tok = eff.tokens_total()
        prev, self._eff_tokens_prev = self._eff_tokens_prev, tok
        elapsed = now - self.last_local_log

        def delta(phase: str, kind: str) -> int:
            return (tok.get(phase, {}).get(kind, 0)
                    - prev.get(phase, {}).get(kind, 0))

        d_prefill = delta("prefill", "real")
        d_decode = delta("decode", "real")
        d_pad = delta("prefill", "pad") + delta("decode", "pad")
        if d_prefill or d_decode or d_pad:
            prefill_tps = d_prefill / elapsed if elapsed > 0 else 0.0
            decode_tps = d_decode / elapsed if elapsed > 0 else 0.0
            pad_str = "%.1f%%" % (
                d_pad / (d_prefill + d_decode + d_pad) * 100)
        else:
            prefill_tps = self._throughput(self.num_prompt_tokens, now)
            decode_tps = self._throughput(self.num_generation_tokens, now)
            pad_str = "n/a"
        mfu = eff.rolling_mfu()
        mfu_str = "%.1f%%" % (mfu * 100) if mfu is not None else "n/a"
        return prefill_tps, decode_tps, mfu_str, pad_str

    def _log_slo_summary(self) -> None:
        """Rolling per-request percentiles + goodput (obs/slo.py), logged
        alongside the throughput line each interval."""
        from intellillm_tpu.obs.slo import get_slo_tracker
        s = get_slo_tracker().summary()
        if not s["window"]:
            return

        def fmt(d: Optional[Dict[str, float]]) -> str:
            if not d:
                return "n/a"
            return "%.0f/%.0f/%.0f" % (d["p50"], d["p90"], d["p99"])

        goodput = ("%.1f%%" % (s["goodput_ratio"] * 100)
                   if s["goodput_ratio"] is not None else "n/a")
        logger.info(
            "Request SLO over last %d finishes (p50/p90/p99 ms): "
            "queue-wait %s, TTFT %s, TPOT %s, e2e %s; goodput %s "
            "(TTFT<=%.0fms, TPOT<=%.0fms)",
            s["window"], fmt(s["queue_wait_ms"]), fmt(s["ttft_ms"]),
            fmt(s["tpot_ms"]), fmt(s["e2e_ms"]), goodput,
            s["slo_ttft_ms"], s["slo_tpot_ms"])
