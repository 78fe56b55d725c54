"""Metrics-reference doc-drift guard, now a thin wrapper over the
`docs-metrics` lint rule (intellillm_tpu/analysis/rules/doc_guards.py):
every `intellillm_*` metric name defined in the source must be
documented in docs/observability.md's metrics reference, and every
metric the doc mentions must still exist in the source. This wrapper
keeps the original guard-the-guard assertions so the scrape itself
can't rot."""
from intellillm_tpu.analysis.engine import load_project
from intellillm_tpu.analysis.rules.doc_guards import (DocsMetricsRule,
                                                      doc_metric_names,
                                                      source_metric_names)


def _docs_metrics_violations():
    project = load_project()
    return list(DocsMetricsRule(project.settings).finalize(project))


def test_sources_define_metrics():
    # Guard the guard: if the regex scrape breaks, this fails before the
    # cross-check tests vacuously pass.
    names = set(source_metric_names(load_project().settings))
    assert len(names) >= 20, names
    assert "intellillm_slo_goodput_ratio" in names
    assert "intellillm_step_phase_seconds" in names
    assert "intellillm_router_requests_total" in names
    assert "intellillm_trace_hop_seconds" in names
    assert "intellillm_trace_exported_total" in names
    # Set-up accounting (PR 50).
    assert "intellillm_xla_stage_seconds_total" in names
    assert "intellillm_xla_persistent_cache_total" in names
    assert "intellillm_boot_phase_seconds" in names


def test_every_source_metric_is_documented():
    undocumented = [v.format() for v in _docs_metrics_violations()
                    if "not documented" in v.message]
    assert not undocumented, (
        f"metrics defined in source but missing from the metrics "
        f"reference: {undocumented} — add them to docs/observability.md")


def test_every_documented_metric_exists_in_source():
    stale = [v.format() for v in _docs_metrics_violations()
             if "absent from the source" in v.message]
    assert not stale, (
        f"metrics documented but absent from the source: {stale} — "
        "remove or rename them in docs/observability.md")


def test_doc_scrape_sees_documented_metrics():
    # Guard the guard on the doc side too.
    documented = set(doc_metric_names(load_project().settings))
    assert len(documented) >= 20, sorted(documented)
    assert "intellillm_step_phase_seconds" in documented
    assert "intellillm_xla_stage_seconds_total" in documented
    assert "intellillm_xla_persistent_cache_total" in documented
    assert "intellillm_boot_phase_seconds" in documented
