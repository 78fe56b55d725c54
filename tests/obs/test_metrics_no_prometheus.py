"""StatLogger, SLOTracker, and EngineWatchdog must degrade gracefully
when prometheus_client is absent (the engine never requires it —
`serve` extra only) — plus coverage of the StatLogger interval log
lines (step breakdown + SLO percentiles/goodput)."""
import importlib
import sys

import pytest

import intellillm_tpu.engine.metrics as metrics_mod
import intellillm_tpu.obs.alerts as alerts_mod
import intellillm_tpu.obs.device_telemetry as devtel_mod
import intellillm_tpu.obs.efficiency as eff_mod
import intellillm_tpu.obs.history as history_mod
import intellillm_tpu.obs.slo as slo_mod
import intellillm_tpu.obs.watchdog as watchdog_mod


def _stats(reloaded, now):
    return reloaded.Stats(
        now=now, num_running=1, num_swapped=0, num_waiting=2,
        device_cache_usage=0.5, cpu_cache_usage=0.0,
        num_prompt_tokens=16, num_generation_tokens=4,
        time_to_first_tokens=[0.01],
        time_per_output_tokens=[0.002],
        time_e2e_requests=[0.1],
        spec_acceptance_rate=0.75,
        step_phase_times={"execute": 0.005, "schedule": 0.001},
        step_time=0.007)


def test_statlogger_interval_log_lines(monkeypatch):
    """Crossing local_interval must emit the throughput line, the step
    breakdown line, and (when the SLO window is non-empty) the rolling
    percentile/goodput line."""
    tracker = slo_mod.get_slo_tracker()
    tracker.reset_for_testing()
    tracker.configure(slo_ttft_ms=100.0, slo_tpot_ms=10.0)
    tracker.observe({"queue_wait_s": 0.02, "ttft_s": 0.05,
                     "tpot_s": 0.005, "e2e_s": 0.5,
                     "generation_tokens": 8, "preemptions": {},
                     "reason": "stop"})
    tracker.observe({"queue_wait_s": 0.04, "ttft_s": 0.5,
                     "tpot_s": 0.005, "e2e_s": 1.0,
                     "generation_tokens": 8, "preemptions": {},
                     "reason": "stop"})
    lines = []
    monkeypatch.setattr(metrics_mod.logger, "info",
                        lambda msg, *args: lines.append(msg % args))
    try:
        stat_logger = metrics_mod.StatLogger(local_interval=0.0,
                                             labels={"model_name": "m"})
        # last_local_log is initialized to time.monotonic(); pin it so the
        # synthetic stats.now deterministically crosses the interval.
        stat_logger.last_local_log = 999.0
        stat_logger.log(_stats(metrics_mod, now=1000.0))
        breakdown = [ln for ln in lines if "Step breakdown" in ln]
        assert breakdown and "execute" in breakdown[0]
        slo_lines = [ln for ln in lines if "Request SLO" in ln]
        assert slo_lines, lines
        line = slo_lines[0]
        assert "last 2 finishes" in line
        assert "queue-wait 20/40/40" in line
        assert "TTFT 50/500/500" in line
        # One of two finishes blew the 100ms TTFT SLO.
        assert "goodput 50.0%" in line
        assert "TTFT<=100ms, TPOT<=10ms" in line
    finally:
        tracker.reset_for_testing()
        if metrics_mod._PROMETHEUS:
            metrics_mod._Metrics.reset_for_testing()


def test_statlogger_slo_line_skipped_when_window_empty(monkeypatch):
    tracker = slo_mod.get_slo_tracker()
    tracker.reset_for_testing()
    lines = []
    monkeypatch.setattr(metrics_mod.logger, "info",
                        lambda msg, *args: lines.append(msg % args))
    try:
        stat_logger = metrics_mod.StatLogger(local_interval=0.0,
                                             labels={"model_name": "m"})
        stat_logger.last_local_log = 999.0
        stat_logger.log(_stats(metrics_mod, now=1000.0))
        assert [ln for ln in lines if "Avg prefill throughput" in ln]
        assert not [ln for ln in lines if "Request SLO" in ln]
    finally:
        if metrics_mod._PROMETHEUS:
            metrics_mod._Metrics.reset_for_testing()


def test_statlogger_without_prometheus(monkeypatch):
    # Unregister the real singleton's collectors BEFORE hiding the
    # package (afterwards the module can't reach the registry), then
    # make `import prometheus_client` raise ImportError and rebuild the
    # module so its _PROMETHEUS flag flips off.
    metrics_mod._Metrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(metrics_mod)
        assert reloaded._PROMETHEUS is False

        logger = reloaded.StatLogger(local_interval=0.0,
                                     labels={"model_name": "m"})
        assert logger.metrics is None
        stats = reloaded.Stats(
            now=1000.0, num_running=1, num_swapped=0, num_waiting=2,
            device_cache_usage=0.5, cpu_cache_usage=0.0,
            num_prompt_tokens=16, num_generation_tokens=4,
            time_to_first_tokens=[0.01],
            time_per_output_tokens=[0.002],
            time_e2e_requests=[0.1],
            spec_acceptance_rate=0.75,
            step_phase_times={"execute": 0.005, "schedule": 0.001},
            step_time=0.007)
        logger.log(stats)          # must not raise
        logger.log(stats)          # crosses local_interval: logs breakdown
    finally:
        monkeypatch.undo()
        restored = importlib.reload(metrics_mod)
        assert restored._PROMETHEUS is True
        restored._Metrics.reset_for_testing()


def test_slo_tracker_without_prometheus(monkeypatch):
    """Every new SLO metric path (queue-time histogram, preemption and
    finished counters, generation-tokens histogram, goodput gauge) must
    work — including the goodput math — with prometheus_client absent."""
    slo_mod._SLOMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(slo_mod)
        assert reloaded._PROMETHEUS is False

        tracker = reloaded.SLOTracker(slo_ttft_ms=100.0, slo_tpot_ms=10.0)
        assert tracker._metrics is None
        tracker.observe({"queue_wait_s": 0.02, "ttft_s": 0.100,
                         "tpot_s": 0.010, "e2e_s": 0.5,
                         "generation_tokens": 8,
                         "preemptions": {"swap": 1}, "reason": "stop"})
        tracker.observe({"queue_wait_s": 0.02, "ttft_s": 0.200,
                         "tpot_s": 0.010, "e2e_s": 0.5,
                         "generation_tokens": 8, "preemptions": {},
                         "reason": "length"})
        s = tracker.summary()
        # Boundary math intact: exactly-at-SLO is good, over is not.
        assert s["goodput_ratio"] == pytest.approx(0.5)
        assert s["window"] == 2
        assert s["finished_total"] == {"stop": 1, "length": 1}
        assert s["preemptions_total"] == {"swap": 1}
        assert s["queue_wait_ms"]["p50"] == pytest.approx(20.0)
    finally:
        monkeypatch.undo()
        restored = importlib.reload(slo_mod)
        assert restored._PROMETHEUS is True
        restored._SLOMetrics.reset_for_testing()


def test_watchdog_without_prometheus(monkeypatch):
    """A stall must still fire (report + state flip) without the
    intellillm_engine_stalls_total counter."""
    import time

    watchdog_mod._WatchdogMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(watchdog_mod)
        assert reloaded._PROMETHEUS is False

        wd = reloaded.EngineWatchdog(enabled=True, stall_s=0.02,
                                     dispatch_s=30.0)
        wd.attach(has_work=lambda: True, start_monitor=False)
        assert wd._metrics is None
        time.sleep(0.04)
        report = wd.check_now()
        assert report is not None
        assert report["reason"] == "no_step_progress"
        assert wd.state == "stalled"
    finally:
        monkeypatch.undo()
        restored = importlib.reload(watchdog_mod)
        assert restored._PROMETHEUS is True
        restored._WatchdogMetrics.reset_for_testing()


def test_device_telemetry_without_prometheus(monkeypatch):
    """Every device-telemetry path — poll, headroom, ledger, swap
    accounting, snapshot — must work with prometheus_client absent (the
    plain-dict state backs /health/detail and serve_bench)."""
    devtel_mod._DeviceMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(devtel_mod)
        assert reloaded._PROMETHEUS is False

        t = reloaded.DeviceTelemetry(enabled=True, poll_s=60.0,
                                     headroom_warn=0.05)
        assert t._metrics is None
        sample = t.poll_once()           # real CPU poll: null byte fields
        assert sample
        t.set_ledger({"params": 1000, "kv_pool": 2000}, log_table=False)
        t.record_swap("out", 2, 100)
        t.record_swap("in", 2, 100)
        t.record_swap("copy", 1, 300)
        snap = t.snapshot()
        assert snap["ledger_bytes"] == {"params": 1000, "kv_pool": 2000}
        assert snap["swap_bytes_total"] == {"in": 200, "out": 200,
                                            "copy": 300}
        assert snap["devices"]
    finally:
        monkeypatch.undo()
        restored = importlib.reload(devtel_mod)
        assert restored._PROMETHEUS is True
        restored._DeviceMetrics.reset_for_testing()


def test_statlogger_line_splits_throughput_and_adds_efficiency(
        monkeypatch):
    """The periodic line reports prefill/decode tok/s from the
    efficiency tracker's real-token counters, plus pad%% and MFU (n/a
    until a FLOPs model + peak are configured)."""
    from intellillm_tpu.obs.efficiency import get_efficiency_tracker
    slo_mod.get_slo_tracker().reset_for_testing()
    eff = get_efficiency_tracker()
    eff.reset_for_testing()
    eff.record_dispatch("prefill", 3, 4, real_tokens=30, padded_tokens=64,
                        len_real=10, len_padded=16)
    eff.record_dispatch("decode", 6, 8, real_tokens=6, padded_tokens=8,
                        width_real=3, width_padded=16)
    lines = []
    monkeypatch.setattr(metrics_mod.logger, "info",
                        lambda msg, *args: lines.append(msg % args))
    try:
        stat_logger = metrics_mod.StatLogger(local_interval=0.0,
                                             labels={"model_name": "m"})
        stat_logger.last_local_log = 999.0
        stat_logger.log(_stats(metrics_mod, now=1000.0))
        tline = [ln for ln in lines if "Avg prefill throughput" in ln]
        assert tline, lines
        line = tline[0]
        # Interval spans exactly 1 s, so the tracker's real-token deltas
        # are the rates verbatim.
        assert "Avg prefill throughput: 30.0 tok/s" in line
        assert "Avg decode throughput: 6.0 tok/s" in line
        # pad = (64-30) + (8-6) = 36 of 72 total tokens.
        assert "pad: 50.0%" in line
        assert "MFU: n/a" in line
    finally:
        eff.reset_for_testing()
        if metrics_mod._PROMETHEUS:
            metrics_mod._Metrics.reset_for_testing()


def test_statlogger_falls_back_without_tracker_data(monkeypatch):
    """Synthetic Stats with an empty efficiency tracker (disabled, or
    unit tests): the split falls back to the engine-side accumulators
    and pad%% reads n/a instead of a bogus 0."""
    from intellillm_tpu.obs.efficiency import get_efficiency_tracker
    slo_mod.get_slo_tracker().reset_for_testing()
    eff = get_efficiency_tracker()
    eff.reset_for_testing()
    lines = []
    monkeypatch.setattr(metrics_mod.logger, "info",
                        lambda msg, *args: lines.append(msg % args))
    try:
        stat_logger = metrics_mod.StatLogger(local_interval=0.0,
                                             labels={"model_name": "m"})
        stat_logger.last_local_log = 999.0
        stat_logger.log(_stats(metrics_mod, now=1000.0))
        line = [ln for ln in lines if "Avg prefill throughput" in ln][0]
        assert "Avg prefill throughput: 16.0 tok/s" in line
        assert "Avg decode throughput: 4.0 tok/s" in line
        assert "pad: n/a" in line
    finally:
        eff.reset_for_testing()
        if metrics_mod._PROMETHEUS:
            metrics_mod._Metrics.reset_for_testing()


def test_efficiency_without_prometheus(monkeypatch):
    """Every efficiency path — dispatch accounting, warm-up exclusion,
    MFU roll-up, snapshot — must work with prometheus_client absent
    (the plain-dict ledger backs /debug/efficiency and serve_bench)."""
    eff_mod._EfficiencyMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(eff_mod)
        assert reloaded._PROMETHEUS is False

        t = reloaded.EfficiencyTracker(enabled=True)
        assert t._metrics is None
        t.record_dispatch("prefill", 3, 4, real_tokens=30,
                          padded_tokens=64, len_real=10, len_padded=16)
        t.record_dispatch("decode", 6, 8, real_tokens=6, padded_tokens=8,
                          width_real=3, width_padded=16)
        with t.warmup():
            t.record_dispatch("decode", 1, 8, real_tokens=1,
                              padded_tokens=8)
        t.record_step(0.01)              # must not raise
        snap = t.snapshot()
        assert snap["tokens_total"]["prefill"] == {"real": 30, "pad": 34}
        assert snap["tokens_total"]["decode"] == {"real": 6, "pad": 2}
        assert snap["warmup_excluded_dispatches"] == 1
        assert snap["fill_ratio_avg"]["prefill"]["batch"] == \
            pytest.approx(0.75)
        assert snap["fill_ratio_avg"]["decode"]["block_width"] == \
            pytest.approx(3 / 16)
        assert snap["top_waste"]
        assert snap["mfu"] is None       # no FLOPs model / peak known
    finally:
        monkeypatch.undo()
        restored = importlib.reload(eff_mod)
        assert restored._PROMETHEUS is True
        restored._EfficiencyMetrics.reset_for_testing()


def test_history_without_prometheus(monkeypatch):
    """The history store must sample, tier, and answer window queries
    with prometheus_client absent (the registry scrape just yields
    nothing; collectors still feed the rings that back /debug/history)."""
    history_mod._HistoryMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(history_mod)
        assert reloaded._PROMETHEUS is False

        clock = {"t": 0.0}
        h = reloaded.MetricsHistory(enabled=True, interval_s=10.0,
                                    now_fn=lambda: clock["t"])
        assert h._metrics is None
        series = {}
        h.register_collector(lambda: dict(series))
        for i in range(12):
            clock["t"] = i * 10.0
            series["intellillm_test_gauge"] = float(i)
            h.sample_once()
        assert h.latest("intellillm_test_gauge") == 11.0
        assert len(h.query("intellillm_test_gauge", tier="raw")) == 12
        assert h.query("intellillm_test_gauge", tier="1m")
        assert h.avg("intellillm_test_gauge", 30.0) == pytest.approx(9.5)
        snap = h.snapshot()
        assert snap["series"] == 1
        assert snap["memory_bytes"] <= snap["memory_cap_bytes"]
    finally:
        monkeypatch.undo()
        restored = importlib.reload(history_mod)
        assert restored._PROMETHEUS is True
        restored._HistoryMetrics.reset_for_testing()


def test_alerts_without_prometheus(monkeypatch):
    """The full pending/firing/resolved cycle must run — snapshot,
    summary, page flag — without the intellillm_alerts gauge."""
    alerts_mod._AlertMetrics.reset_for_testing()
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    try:
        reloaded = importlib.reload(alerts_mod)
        assert reloaded._PROMETHEUS is False

        clock = {"t": 0.0}
        flag = {"active": True}
        rule = reloaded.AlertRule(
            "test_rule", severity="page",
            evaluate_fn=lambda h, now: (flag["active"], 1.0, "d"))
        manager = reloaded.AlertManager(enabled=True, rules=[rule],
                                        webhook_url="",
                                        now_fn=lambda: clock["t"])
        assert manager._metrics is None
        manager.evaluate_now()
        snap = manager.snapshot()
        assert snap["rules"]["test_rule"]["state"] == "firing"
        assert manager.page_firing() is True
        flag["active"] = False
        clock["t"] = 10.0
        manager.evaluate_now()
        assert manager.snapshot()["rules"]["test_rule"]["state"] \
            == "resolved"
        assert manager.summary()["page_firing"] is False
    finally:
        monkeypatch.undo()
        restored = importlib.reload(alerts_mod)
        assert restored._PROMETHEUS is True
        restored._AlertMetrics.reset_for_testing()


def test_spec_acceptance_rate_optional():
    from intellillm_tpu.engine.metrics import Stats
    stats = Stats(now=0.0, num_running=0, num_swapped=0, num_waiting=0,
                  device_cache_usage=0.0, cpu_cache_usage=0.0,
                  num_prompt_tokens=0, num_generation_tokens=0)
    assert stats.spec_acceptance_rate is None
    assert stats.step_phase_times == {}
    assert stats.step_time == 0.0


@pytest.mark.parametrize("busy, idle, field", [
    ({"decode_cont": 0.006}, {"prepare_inputs": 0.0007, "sample": 0.0003},
     "dev busy 60.0% idle 10.0% (prepare_inputs)"),
    ({"mixed": 0.01}, {}, "dev busy 100.0% idle 0.0%"),
    ({}, {}, None),             # tracing off: the line is as it was
])
def test_step_breakdown_line_gains_the_device_timeline(monkeypatch, busy,
                                                       idle, field):
    """`dev busy a% idle b% (top phase)`: the timeline's seconds over the
    interval's period (step time + loop gap), and the phase under which
    most of the dry time passed."""
    lines = []
    monkeypatch.setattr(metrics_mod.logger, "info",
                        lambda msg, *args: lines.append(msg % args))
    stat_logger = metrics_mod.StatLogger(local_interval=0.0,
                                         labels={"model_name": "m"})
    stat_logger.last_local_log = 999.0
    stats = _stats(metrics_mod, now=1000.0)
    stats.step_gap = 0.003          # period 10 ms
    stats.device_busy, stats.device_idle = busy, idle
    try:
        stat_logger.log(stats)
    finally:
        if metrics_mod._PROMETHEUS:
            metrics_mod._Metrics.reset_for_testing()
    line, = [ln for ln in lines if "Step breakdown" in ln]
    if field is None:
        assert "dev busy" not in line
        assert line.endswith("step 7.0ms + loop gap 3.0ms")
    else:
        assert line.endswith("step 7.0ms + loop gap 3.0ms; " + field)
    # the interval's accumulators start again
    assert stat_logger.device_busy_seconds == 0.0
    assert stat_logger.device_idle_seconds == {}
