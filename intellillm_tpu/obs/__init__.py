"""Observability subsystem: step-phase tracing, XLA compile tracking,
the per-request flight recorder, request SLO telemetry, the engine
stall watchdog, device/HBM telemetry, the compute-efficiency ledger,
the per-kernel cost ledger, the in-process metrics history, the alert
rule engine, the bounded workload log (capture & replay), the
numerics/output-integrity layer (in-graph sentinels, KV integrity
audit, fleet canary ledger), and the benchmark summary differ behind
`tools.wdiff`. See docs/observability.md."""
from intellillm_tpu.obs.alerts import (AlertManager, AlertRule,
                                       built_in_rules, get_alert_manager)
from intellillm_tpu.obs.boot import BootTimeline, get_boot_timeline
from intellillm_tpu.obs.compile_tracker import (CompileTracker,
                                                get_compile_tracker,
                                                record_kernel_dispatch)
from intellillm_tpu.obs.decisions import (CAUSES, DECISIONS, DecisionLog,
                                          explain_request, get_decision_log)
from intellillm_tpu.obs.device_telemetry import (DeviceTelemetry,
                                                 get_device_telemetry)
from intellillm_tpu.obs.diff import (diff_summaries, format_report,
                                     load_summary)
from intellillm_tpu.obs.efficiency import (EfficiencyTracker,
                                           get_efficiency_tracker)
from intellillm_tpu.obs.flight_recorder import (EVENTS, FlightRecorder,
                                                get_flight_recorder)
from intellillm_tpu.obs.heap import get_heap
from intellillm_tpu.obs.history import MetricsHistory, get_metrics_history
from intellillm_tpu.obs.kernels import (KernelLedger, get_kernel_ledger,
                                        load_profile, reduce_profile)
from intellillm_tpu.obs.kv_transfer import (KVTransferStats,
                                            get_kv_transfer_stats)
from intellillm_tpu.obs.numerics import (CanaryLedger, KVIntegrityAuditor,
                                         NumericsTracker, get_canary_ledger,
                                         get_kv_audit, get_numerics_tracker,
                                         numerics_debug_snapshot,
                                         numerics_health_block)
from intellillm_tpu.obs.slo import (SLOTracker, derive_request_metrics,
                                    get_slo_tracker)
from intellillm_tpu.obs.trace_export import (TraceSink, flush_black_box,
                                             get_trace_sink,
                                             install_black_box_handlers,
                                             sanitize_request_id)
from intellillm_tpu.obs.tracing import (PHASES, StepTracer, get_step_tracer,
                                        request_context)
from intellillm_tpu.obs.watchdog import EngineWatchdog, get_watchdog
from intellillm_tpu.obs.workload import (WorkloadLog, dump_iwl,
                                         get_workload_log, merge_workloads,
                                         parse_iwl)

__all__ = [
    "AlertManager",
    "AlertRule",
    "BootTimeline",
    "CAUSES",
    "CanaryLedger",
    "CompileTracker",
    "DECISIONS",
    "DecisionLog",
    "DeviceTelemetry",
    "EVENTS",
    "EfficiencyTracker",
    "EngineWatchdog",
    "FlightRecorder",
    "KVIntegrityAuditor",
    "KVTransferStats",
    "KernelLedger",
    "MetricsHistory",
    "NumericsTracker",
    "PHASES",
    "SLOTracker",
    "StepTracer",
    "TraceSink",
    "WorkloadLog",
    "built_in_rules",
    "diff_summaries",
    "dump_iwl",
    "derive_request_metrics",
    "format_report",
    "load_summary",
    "explain_request",
    "flush_black_box",
    "get_alert_manager",
    "get_boot_timeline",
    "get_canary_ledger",
    "get_compile_tracker",
    "get_decision_log",
    "get_device_telemetry",
    "get_efficiency_tracker",
    "get_flight_recorder",
    "get_heap",
    "get_kernel_ledger",
    "get_kv_audit",
    "get_kv_transfer_stats",
    "get_metrics_history",
    "get_numerics_tracker",
    "get_slo_tracker",
    "get_step_tracer",
    "get_trace_sink",
    "get_watchdog",
    "get_workload_log",
    "install_black_box_handlers",
    "merge_workloads",
    "numerics_debug_snapshot",
    "numerics_health_block",
    "parse_iwl",
    "load_profile",
    "reduce_profile",
    "record_kernel_dispatch",
    "request_context",
    "sanitize_request_id",
]
