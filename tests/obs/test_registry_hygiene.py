"""Registry-hygiene guard, now a thin wrapper over the `metric-hygiene`
lint rule (intellillm_tpu/analysis/rules/metric_hygiene.py): names must
carry the `intellillm_` prefix (one grafana namespace, no collisions
with other exporters), every module that registers collectors must
expose a `reset_for_testing` hook, and collectors live only in the
designated metrics modules. The rule also runs in the lint CI gate
(tests/analysis/test_tree_clean.py); this wrapper keeps the original
guard-the-guard assertions so the scrape itself can't rot."""
from intellillm_tpu.analysis.engine import load_project
from intellillm_tpu.analysis.rules.metric_hygiene import (
    MetricHygieneRule, prometheus_collector_calls)


def _metric_constructors():
    """(module, metric_name) for every collector constructed in-package."""
    found = []
    for mod in load_project().modules:
        for _, name in prometheus_collector_calls(mod):
            found.append((mod, name))
    return found


def _hygiene_violations():
    project = load_project()
    rule = MetricHygieneRule(project.settings)
    out = []
    for mod in project.modules:
        out.extend(rule.check(mod))
    return out


def test_constructors_are_found():
    # Guard the guard: the scrape must keep seeing the known collectors,
    # or the assertions below pass vacuously.
    names = {name for _, name in _metric_constructors()}
    assert len(names) >= 30, sorted(names)
    assert "intellillm_step_phase_seconds" in names
    assert "intellillm_device_hbm_bytes_in_use" in names
    assert "intellillm_swap_bytes_total" in names
    # Router families (PR 6) are in-package and covered by this guard.
    assert "intellillm_router_requests_total" in names
    assert "intellillm_router_routing_decisions_total" in names
    assert "intellillm_router_predicted_load_tokens" in names
    # Distributed-tracing families (PR 7).
    assert "intellillm_trace_exported_total" in names
    assert "intellillm_trace_hop_seconds" in names
    # Speculative-decoding families (PR 13).
    assert "intellillm_spec_draft_tokens_total" in names
    assert "intellillm_spec_accepted_tokens_total" in names
    assert "intellillm_spec_emitted_tokens_total" in names
    assert "intellillm_spec_current_k" in names
    assert "intellillm_spec_verify_waste_ratio" in names
    # Per-kernel cost-ledger families (PR 16).
    assert "intellillm_kernel_flops" in names
    assert "intellillm_kernel_bytes_accessed" in names
    assert "intellillm_kernel_hbm_peak_bytes" in names
    assert "intellillm_kernel_executables" in names
    assert "intellillm_kernel_mfu_costmodel" in names
    # Scheduler decision-tracing families (PR 17).
    assert "intellillm_sched_deferred_seconds_total" in names
    assert "intellillm_sched_decisions_total" in names
    # Workload-capture families (PR 18).
    assert "intellillm_workload_requests_total" in names
    assert "intellillm_workload_prompt_tokens_total" in names
    assert "intellillm_workload_output_tokens_total" in names
    # Numerics / output-integrity families (PR 19).
    assert "intellillm_numerics_rows_checked_total" in names
    assert "intellillm_numerics_anomalies_total" in names
    assert "intellillm_numerics_quarantined_total" in names
    assert "intellillm_kv_integrity_checksums_total" in names
    assert "intellillm_kv_integrity_mismatches_total" in names
    assert "intellillm_router_canary_runs_total" in names
    assert "intellillm_router_canary_divergence_total" in names
    assert "intellillm_router_canary_suspect" in names
    # Set-up accounting families (PR 50): first calls by stage, the
    # persistent cache's results, the boot's phases.
    assert "intellillm_xla_stage_seconds_total" in names
    assert "intellillm_xla_persistent_cache_total" in names
    assert "intellillm_boot_phase_seconds" in names


def test_every_metric_name_is_prefixed():
    bad = [v.format() for v in _hygiene_violations()
           if "prefix" in v.message]
    assert not bad, (
        f"metrics without the intellillm_ prefix: {bad} — all exported "
        "series share one namespace")


def test_every_metrics_module_has_reset_hook():
    missing = [v.format() for v in _hygiene_violations()
               if "reset_for_testing" in v.message]
    assert not missing, (
        f"modules registering Prometheus collectors without a "
        f"reset_for_testing hook: {missing} — tests cannot unregister "
        "their collectors between engine rebuilds")


def test_collectors_only_in_designated_modules():
    # New with the lint suite: ad-hoc families outside obs/,
    # engine/metrics.py, router/metrics.py dodge the guards above.
    strays = [v.format() for v in _hygiene_violations()
              if "outside" in v.message]
    assert not strays, strays
