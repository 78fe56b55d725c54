"""In-process smoke test of the server observability surface: /health,
/metrics (exports `intellillm_` series), and the /debug routes — via
aiohttp's TestServer, no subprocess or real engine needed."""
import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

from intellillm_tpu.engine.metrics import _Metrics, _PROMETHEUS
from intellillm_tpu.entrypoints import api_server as demo_server
from intellillm_tpu.entrypoints.openai import api_server as openai_server
from intellillm_tpu.obs import (get_alert_manager, get_flight_recorder,
                                get_metrics_history, get_slo_tracker,
                                get_watchdog)


def _seed_recorder():
    recorder = get_flight_recorder()
    recorder.reset_for_testing()
    recorder.record("smoke-1", "arrived", detail="prompt_tokens=4")
    recorder.record("smoke-1", "scheduled")
    recorder.record("smoke-1", "prefill_start", detail="tokens=4")
    recorder.record("smoke-1", "first_token")
    recorder.record("smoke-1", "finished", detail="stop")
    recorder.record("smoke-live", "arrived")
    return recorder


def _run(app, scenario):
    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await scenario(client)
        finally:
            await client.close()
    asyncio.run(go())


@pytest.mark.skipif(not _PROMETHEUS, reason="needs prometheus_client")
def test_openai_server_observability_surface():
    _Metrics.reset_for_testing()
    _Metrics(["model_name"])  # register the intellillm_ collectors
    get_slo_tracker()         # register the SLO collectors
    get_watchdog()            # register the stall counter
    _seed_recorder()
    try:
        async def scenario(client):
            resp = await client.get("/health")
            assert resp.status == 200

            resp = await client.get("/metrics")
            assert resp.status == 200
            body = await resp.text()
            assert "intellillm_" in body
            assert "intellillm_step_phase_seconds" in body
            assert "intellillm_xla_compiles_total" in body
            # SLO + watchdog collectors registered via the singletons
            # the engine constructs at init.
            assert "intellillm_request_queue_time_seconds" in body
            assert "intellillm_request_generation_tokens" in body
            assert "intellillm_request_preemptions_total" in body
            assert "intellillm_request_finished_total" in body
            assert "intellillm_slo_goodput_ratio" in body
            assert "intellillm_engine_stalls_total" in body

            # Completed request: ordered lifecycle events.
            resp = await client.get("/debug/trace",
                                    params={"request_id": "smoke-1"})
            assert resp.status == 200
            data = await resp.json()
            assert data["request_id"] == "smoke-1"
            assert [e["event"] for e in data["events"]] == [
                "arrived", "scheduled", "prefill_start", "first_token",
                "finished"]
            ts = [e["ts"] for e in data["events"]]
            assert ts == sorted(ts)

            resp = await client.get("/debug/trace",
                                    params={"request_id": "never-seen"})
            assert resp.status == 404

            resp = await client.get("/debug/trace")
            assert resp.status == 200
            data = await resp.json()
            assert data["live_request_ids"] == ["smoke-live"]
            assert [x["request_id"] for x in data["recent_finished"]] == [
                "smoke-1"]

            resp = await client.get("/debug/trace",
                                    params={"limit": "bogus"})
            assert resp.status == 400

            # Profiler admin routes are opt-in (--enable-profiling):
            # absent by default.
            resp = await client.post("/debug/profiler/start")
            assert resp.status == 404
            resp = await client.post("/debug/profiler/stop")
            assert resp.status == 404

        _run(openai_server.build_app(), scenario)
    finally:
        get_flight_recorder().reset_for_testing()
        _Metrics.reset_for_testing()


def test_openai_server_debug_routes_require_api_key():
    """--api-key must gate /debug like every non-health route."""
    async def scenario(client):
        resp = await client.get("/debug/trace")
        assert resp.status == 401
        resp = await client.get("/debug/stall")
        assert resp.status == 401
        resp = await client.get(
            "/debug/trace", headers={"Authorization": "Bearer sekrit"})
        assert resp.status == 200
        resp = await client.get("/health")
        assert resp.status == 200  # health stays open
        # /health/detail is a liveness probe too: exempt, and 503 (not
        # 401) because this test app has no engine behind it.
        resp = await client.get("/health/detail")
        assert resp.status == 503
        # The exemption is an exact match, not a prefix: /healthfoo must
        # NOT slip past auth (it 401s before routing can 404 it).
        resp = await client.get("/healthfoo")
        assert resp.status == 401

    _run(openai_server.build_app(api_key="sekrit"), scenario)


def test_health_detail_and_stall_without_engine():
    """Both servers serve the deep-health surface even before (or
    without) an engine: /health/detail reports "initializing" with 503,
    /debug/stall returns the watchdog snapshot and an empty ring."""
    wd = get_watchdog()

    async def scenario(client):
        resp = await client.get("/health/detail")
        assert resp.status == 503
        data = await resp.json()
        assert data["status"] == "initializing"
        assert data["watchdog"]["state"] == "ok"
        assert "slo" in data
        # Present even before the engine exists (poller/ledger just
        # haven't sampled yet).
        assert "device_telemetry" in data
        assert "devices" in data["device_telemetry"]

        resp = await client.get("/debug/stall")
        assert resp.status == 200
        data = await resp.json()
        assert data["watchdog"]["enabled"] is wd.enabled
        assert data["reports"] == []

    _run(openai_server.build_app(), scenario)
    _run(demo_server.build_app(), scenario)


def test_profiler_routes_registered_only_with_opt_in():
    """--enable-profiling gates the profiler admin endpoints on both
    servers (they degrade serving and write traces to a caller-chosen
    dir; the demo server has no auth at all)."""
    async def gated(client):
        # Registered, but no engine behind this test app: refuses 503
        # instead of tracing.
        resp = await client.post("/debug/profiler/start")
        assert resp.status == 503
        resp = await client.post("/debug/profiler/stop")
        assert resp.status == 503
        resp = await client.post("/debug/profiler/capture")
        assert resp.status == 503

    async def absent(client):
        resp = await client.post("/debug/profiler/start")
        assert resp.status == 404
        resp = await client.post("/debug/profiler/stop")
        assert resp.status == 404
        resp = await client.post("/debug/profiler/capture")
        assert resp.status == 404

    _run(openai_server.build_app(enable_profiling=True), gated)
    _run(demo_server.build_app(enable_profiling=True), gated)
    _run(demo_server.build_app(), absent)


def test_kernels_endpoint_and_health_block_on_both_servers():
    """/debug/kernels is always registered (read-only) on both servers
    and serves the process-global ledger; /health/detail carries the
    compact kernels block. Entries introspected elsewhere in the
    process (here: faked) are visible through every surface."""
    from types import SimpleNamespace

    from intellillm_tpu.obs import get_kernel_ledger

    ledger = get_kernel_ledger()
    ledger.reset_for_testing()
    ledger.introspect_mode = "on"
    mem = SimpleNamespace(argument_size_in_bytes=100,
                          output_size_in_bytes=20,
                          temp_size_in_bytes=30,
                          generated_code_size_in_bytes=1)
    compiled = SimpleNamespace(
        cost_analysis=lambda: [{"flops": 64.0, "bytes accessed": 32.0}],
        memory_analysis=lambda: mem)
    fn = SimpleNamespace(
        lower=lambda *a, **k: SimpleNamespace(compile=lambda: compiled))
    pending = ledger.prepare("mixed", (8, 128), fn, (), {})
    ledger.commit(pending, 0.25)
    try:
        async def scenario(client):
            resp = await client.get("/debug/kernels", params={"top": "4"})
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert data["executables_total"] == 1
            entry = data["executables"][0]
            assert entry["program"] == "mixed"
            assert entry["flops"] == 64.0
            assert entry["hbm_peak_bytes"] == 151
            assert data["programs"]["mixed"]["dispatches"] == 1
            # Cross-check fields ride along even when both are null.
            assert "mfu_costmodel" in data and "mfu_analytic" in data

            resp = await client.get("/debug/kernels",
                                    params={"top": "bogus"})
            assert resp.status == 400

            # Compact block on deep health (503: no engine behind the
            # test app, body rides along like the other obs blocks).
            resp = await client.get("/health/detail")
            data = await resp.json()
            kernels = data["kernels"]
            assert kernels["executables_total"] == 1
            assert kernels["programs"]["mixed"]["flops_max"] == 64.0
            assert "executables" not in kernels

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        ledger.reset_for_testing()


def test_profiler_capture_runs_against_a_fake_engine(monkeypatch, tmp_path):
    """Full capture-and-reduce flow without an engine: a fake engine
    "profiles" by taking a real (CPU) jax.profiler trace of two annotated
    steps into the capture's temp dir; the endpoint bounds the step wait,
    reduces the xplane, merges the block into the ledger, and 409s while
    a trace is already running."""
    import time

    import jax
    import jax.numpy as jnp
    from aiohttp import web

    from intellillm_tpu.entrypoints.debug_routes import add_debug_routes
    from intellillm_tpu.obs import get_kernel_ledger
    from intellillm_tpu.obs.tracing import StepTracer

    monkeypatch.setenv("INTELLILLM_PROFILER_CAPTURE_TIMEOUT_S", "0.2")
    ledger = get_kernel_ledger()
    ledger.reset_for_testing()
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    fn(x).block_until_ready()

    class _FakeEngine:
        def __init__(self):
            self.profiling = False

        def start_profile(self, trace_dir):
            if self.profiling:
                return None
            self.profiling = True
            tracer = StepTracer(enabled=True)
            jax.profiler.start_trace(trace_dir)
            tracer.set_profiling(True)
            for _ in range(2):
                with tracer.span("schedule"):
                    # long against a 128x128 matmul even on a loaded
                    # machine (2 ms lost to `fetch_wait` under six test
                    # workers)
                    time.sleep(0.02)
                with tracer.span("execute"):
                    y = fn(x)
                with tracer.span("fetch_wait"):
                    y.block_until_ready()
            tracer.set_profiling(False)
            jax.profiler.stop_trace()
            return trace_dir

        def stop_profile(self):
            self.profiling = False

    engine = _FakeEngine()
    app = web.Application()
    add_debug_routes(app, lambda: engine, enable_profiling=True)
    try:
        async def scenario(client):
            resp = await client.post("/debug/profiler/capture",
                                     params={"steps": "2", "top": "1"})
            assert resp.status == 200
            data = await resp.json()
            assert data["steps_requested"] == 2
            assert data["steps_observed"] == 0  # idle fake engine
            profile = data["profile"]
            assert profile["steps"] == 0
            assert profile["ops_total"] >= 1 and len(profile["ops"]) == 1
            assert profile["busy_s"] > 0
            assert profile["annotations"]["execute"] == 2
            # the idle gaps by host phase add up to the idle time, and the
            # sleep inside `schedule` is the largest of them
            idle = profile["idle_by_phase"]
            assert sum(idle.values()) == pytest.approx(profile["idle_s"])
            assert max(idle, key=idle.get) == "schedule"
            # Merged into the ledger: /debug/kernels now carries it.
            resp = await client.get("/debug/kernels")
            stored = (await resp.json())["profile"]
            assert stored["ops_total"] == profile["ops_total"]
            assert stored["idle_by_phase"] == idle

            # Concurrent capture while a trace runs: 409, engine state
            # untouched.
            engine.profiling = True
            resp = await client.post("/debug/profiler/capture")
            assert resp.status == 409
            engine.profiling = False

            resp = await client.post("/debug/profiler/capture",
                                     params={"steps": "bogus"})
            assert resp.status == 400

        _run(app, scenario)
    finally:
        ledger.reset_for_testing()


def test_profiler_capture_returns_the_programs_own_clock_beside_the_traces(
        monkeypatch):
    """Two clocks, one capture: a loop of annotated steps runs (on the
    CPU backend) through the process's tracer while the endpoint, through
    the real `LLMEngine.start_profile`, traces some of them. The `clock`
    block is the device timeline's account of those steps; its busy + dry
    seconds tile its window, which is the trace's."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    from aiohttp import web

    from intellillm_tpu.engine.llm_engine import LLMEngine
    from intellillm_tpu.entrypoints.debug_routes import add_debug_routes
    from intellillm_tpu.obs import (get_efficiency_tracker,
                                    get_kernel_ledger, get_step_tracer)

    monkeypatch.setenv("INTELLILLM_PROFILER_CAPTURE_TIMEOUT_S", "20")
    ledger = get_kernel_ledger()
    ledger.reset_for_testing()
    efficiency = get_efficiency_tracker()
    tracer = get_step_tracer()
    monkeypatch.setattr(tracer, "enabled", True)
    tracer.reset_for_testing()
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384))
    fn(x).block_until_ready()
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            tracer.begin_step()
            with tracer.span("schedule"):
                time.sleep(0.004)
            dry = tracer.device_dry()
            with tracer.span("execute"):
                y = fn(x)
                tracer.note_dispatch("mixed", y)
            efficiency.record_dispatch(
                "decode", 1, 1, real_tokens=1, padded_tokens=1,
                program="mixed", device_idle=dry)
            with tracer.span("sample"):
                with tracer.span("fetch_wait", waits_on=y):
                    y.block_until_ready()
            step_time = tracer.end_step(work_pending=True)[1]
            ledger.record_step(step_time)

    engine = object.__new__(LLMEngine)
    engine._tracer = tracer
    app = web.Application()
    engines = [engine]
    add_debug_routes(app, lambda: engines[0], enable_profiling=True)
    thread = threading.Thread(target=loop)
    thread.start()
    try:
        async def scenario(client):
            resp = await client.post("/debug/profiler/capture",
                                     params={"steps": "64"})
            assert resp.status == 200
            data = await resp.json()
            assert data["steps_observed"] >= 64
            profile, clock = data["profile"], data["clock"]
            assert profile["annotations"]["execute"] >= 64
            assert clock["dispatches"]["mixed"] >= 64
            busy = sum(clock["busy_by_program"].values())
            idle = sum(clock["idle_by_phase"].values())
            assert set(clock["busy_by_program"]) == {"mixed"} and busy > 0
            # the timeline is read at step boundaries, and busy + idle
            # is the time between them; the trace's window is the same to
            # a step at either end
            assert busy + idle == clock["window_s"]
            assert clock["window_s"] == pytest.approx(profile["window_s"],
                                                      rel=0.1)
            # both accounts name the sleep under `schedule` as where the
            # device waits
            by_clock, by_trace = clock["idle_by_phase"], profile[
                "idle_by_phase"]
            assert max(by_clock, key=by_clock.get) == "schedule"
            assert max(by_trace, key=by_trace.get) == "schedule"
            # /health/detail shows the totals since start (the blocks
            # that need no engine come before it asks for one)
            engines[0] = None
            resp = await client.get("/health/detail")
            totals = (await resp.json())["device_clock"]
            assert totals["busy_by_program"]["mixed"] >= busy
            assert totals["idle_by_phase"]["schedule"] >= by_clock[
                "schedule"]

        _run(app, scenario)
    finally:
        stop.set()
        thread.join()
        tracer.reset_for_testing()
        ledger.reset_for_testing()


@pytest.mark.skipif(not _PROMETHEUS, reason="needs prometheus_client")
def test_both_servers_serve_metrics_from_shared_handler():
    """/metrics comes from ONE handler in debug_routes — the demo server
    (which used to lack it) and the OpenAI server must both serve the
    device-telemetry series."""
    from intellillm_tpu.obs import get_device_telemetry

    get_device_telemetry().poll_once()  # ensure the collectors exist

    async def scenario(client):
        resp = await client.get("/metrics")
        assert resp.status == 200
        body = await resp.text()
        assert "intellillm_device_hbm_bytes_in_use" in body
        assert "intellillm_hbm_ledger_bytes" in body
        assert 'intellillm_swap_bytes_total{direction="in"}' in body
        assert 'intellillm_swap_bytes_total{direction="out"}' in body
        assert 'intellillm_swap_bytes_total{direction="copy"}' in body

    _run(demo_server.build_app(), scenario)
    _run(openai_server.build_app(), scenario)


def test_history_and_alerts_endpoints_on_both_servers(monkeypatch):
    """/debug/history serves the store snapshot, per-series points with
    window parsing (and 404/400 on bad input); /debug/alerts serves the
    rule table; /health/detail carries the alert summary + boot block.
    Both servers share the handlers via debug_routes."""
    history = get_metrics_history()
    manager = get_alert_manager()
    history.reset_for_testing()
    manager.reset_for_testing()
    # Isolate from gauges other tests left in the live prometheus
    # registry (a stale goodput value would trip the burn-rate rule).
    monkeypatch.setattr(history, "_scrape_registry", lambda: {})
    history.register_collector(
        lambda: {"intellillm_test_endpoint_gauge": 0.25})
    history.sample_once()
    manager.attach(history)
    manager.evaluate_now()
    try:
        async def scenario(client):
            resp = await client.get("/debug/history")
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert "intellillm_test_endpoint_gauge" in data["series"]
            assert data["memory_bytes"] <= data["memory_cap_bytes"]

            resp = await client.get(
                "/debug/history",
                params={"metric": "intellillm_test_endpoint_gauge",
                        "window": "5m"})
            assert resp.status == 200
            data = await resp.json()
            assert data["window_s"] == 300.0
            assert [p[1] for p in data["points"]] == [0.25]

            resp = await client.get(
                "/debug/history", params={"metric": "intellillm_nope"})
            assert resp.status == 404

            resp = await client.get(
                "/debug/history",
                params={"metric": "intellillm_test_endpoint_gauge",
                        "window": "soon"})
            assert resp.status == 400

            # Non-finite floats parse but are not windows: "nan" slips
            # past a bare <= 0 check and an "inf" cutoff silently
            # empties the series — both must 400, not 200-with-[].
            for bogus in ("nan", "inf", "-inf"):
                resp = await client.get(
                    "/debug/history",
                    params={"metric": "intellillm_test_endpoint_gauge",
                            "window": bogus})
                assert resp.status == 400, bogus

            resp = await client.get("/debug/alerts")
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert "slo_burn_rate" in data["rules"]
            assert data["rules"]["slo_burn_rate"]["state"] in (
                "inactive", "pending", "firing", "resolved")
            assert data["firing"] == []
            assert data["page_firing"] is False

            # No engine behind the test app: 503 "initializing", but the
            # alert summary and boot timeline ride along already.
            resp = await client.get("/health/detail")
            assert resp.status == 503
            data = await resp.json()
            assert data["alerts"]["page_firing"] is False
            assert "firing" in data["alerts"]
            assert "phases_s" in data["boot"]
            # The set-up record rides the boot block from the first poll.
            setup = data["boot"]["setup"]
            assert setup["phases_s"] == data["boot"]["phases_s"]
            assert set(setup["first_calls"]["by_stage_s"]) == {
                "trace", "lower", "compile", "cache_read", "introspect",
                "rest"}
            assert isinstance(setup["calls"], list)

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        history.reset_for_testing()
        manager.reset_for_testing()


def test_predictor_endpoint_and_health_block_on_both_servers():
    """/debug/predictor serves the calibration table; /health/detail
    carries the compact predictor block (the router polls it for the
    calibration factor) — even while the server is still initializing."""
    from intellillm_tpu.prediction import (
        get_prediction_service, reset_prediction_service_for_testing)

    class _Stub:
        def predict(self, prompt, prompt_token_ids):
            return 100

    reset_prediction_service_for_testing()
    svc = get_prediction_service().configure(_Stub())
    assert svc.predict("dbg-1", None, list(range(40))) is not None
    svc.observe_finish("dbg-1", 20)
    try:
        async def scenario(client):
            resp = await client.get("/debug/predictor")
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert data["samples_total"] == 1
            assert data["predictor"] == "_Stub"
            assert data["global_calibration_factor"] == pytest.approx(0.2)
            assert data["buckets"]["32-63"]["factor_p50"] == pytest.approx(
                0.2)
            assert data["recent"][0]["request_id"] == "dbg-1"
            assert data["recent"][0]["actual"] == 20

            # No engine behind the test app: 503 "initializing", but the
            # predictor block rides along for the router's poller.
            resp = await client.get("/health/detail")
            assert resp.status == 503
            data = await resp.json()
            assert data["predictor"]["enabled"] is True
            assert data["predictor"]["samples"] == 1
            assert data["predictor"]["calibration_factor"] == (
                pytest.approx(0.2))

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        reset_prediction_service_for_testing()


def test_spec_endpoint_and_health_block_on_both_servers():
    """/debug/spec serves the rolling spec stats (404 when no draft
    model is configured); /health/detail carries the compact spec block
    only while spec serving is active."""
    from intellillm_tpu.worker.spec_decode import metrics as spec_metrics

    spec_metrics.reset_for_testing()
    try:
        async def scenario_disabled(client):
            resp = await client.get("/debug/spec")
            assert resp.status == 404
            resp = await client.get("/health/detail")
            data = await resp.json()
            assert "spec" not in data

        _run(demo_server.build_app(), scenario_disabled)
        _run(openai_server.build_app(), scenario_disabled)

        stats = spec_metrics.get_spec_stats()
        stats.configure(k_min=2, k_max=5, k_init=4)
        stats.record_pass(drafted=8, accepted=6, emitted=8, verified=10)
        stats.record_pass(drafted=8, accepted=2, emitted=4, verified=10)

        async def scenario_enabled(client):
            resp = await client.get("/debug/spec")
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert data["k"] == 4
            assert data["k_min"] == 2 and data["k_max"] == 5
            assert data["passes"] == 2
            assert data["acceptance_rate"] == pytest.approx(0.5)
            assert data["verify_waste_ratio"] == pytest.approx(0.4)
            assert data["totals"]["draft_tokens"] == 16
            assert data["totals"]["emitted_tokens"] == 12

            resp = await client.get("/health/detail")
            data = await resp.json()
            assert data["spec"]["k"] == 4
            assert data["spec"]["acceptance_rate"] == pytest.approx(0.5)

        _run(demo_server.build_app(), scenario_enabled)
        _run(openai_server.build_app(), scenario_enabled)
    finally:
        spec_metrics.reset_for_testing()


def test_trace_event_filter_and_finished_counts():
    """/debug/trace grows `?event=` (only traces containing that event;
    unknown names 400 with the valid set) and `finished_counts` — how
    the last ring of requests terminated."""
    recorder = _seed_recorder()
    recorder.record("smoke-2", "arrived")
    recorder.record("smoke-2", "preempted", detail="mode=swap")
    recorder.record("smoke-2", "aborted")
    try:
        async def scenario(client):
            resp = await client.get("/debug/trace")
            assert resp.status == 200
            data = await resp.json()
            assert data["finished_counts"] == {"finished": 1, "aborted": 1}

            resp = await client.get("/debug/trace",
                                    params={"event": "preempted"})
            assert resp.status == 200
            data = await resp.json()
            assert [x["request_id"] for x in data["recent_finished"]] == [
                "smoke-2"]

            resp = await client.get("/debug/trace",
                                    params={"event": "finished"})
            data = await resp.json()
            assert [x["request_id"] for x in data["recent_finished"]] == [
                "smoke-1"]

            resp = await client.get("/debug/trace",
                                    params={"event": "exploded"})
            assert resp.status == 400
            assert "preempted" in (await resp.json())["error"]

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        get_flight_recorder().reset_for_testing()


def test_explain_endpoint_and_contention_block_on_both_servers():
    """/debug/explain/{id} decomposes the wait by cause on both servers;
    /health/detail carries the fleet-level `contention` block (served
    even while the app has no engine behind it)."""
    import time as time_mod

    from intellillm_tpu.obs import decisions as decisions_mod

    decisions_mod.reset_for_testing()
    recorder = _seed_recorder()
    dlog = decisions_mod.get_decision_log()
    dlog.note_queued("smoke-1")
    dlog.begin_pass()
    dlog.pass_blocked("token_budget")
    time_mod.sleep(0.02)
    dlog.end_pass(["smoke-1"])
    dlog.begin_pass()
    dlog.defer("smoke-1", "tenant_fairness")
    time_mod.sleep(0.01)
    dlog.end_pass(["smoke-1"])
    dlog.begin_pass()
    dlog.scheduled("smoke-1")
    dlog.end_pass([])
    dlog.seal("smoke-1")
    try:
        async def scenario(client):
            resp = await client.get("/debug/explain/smoke-1")
            assert resp.status == 200
            data = await resp.json()
            assert data["found"] is True
            assert data["state"] == "finished"
            by_cause = data["queue_wait"]["by_cause"]
            assert by_cause["token_budget"] > 0
            assert by_cause["tenant_fairness"] > 0
            # by_cause entries and total_s are each rounded to 6
            # decimals independently, so the sum of parts can drift
            # from the rounded total by ~1e-6 per cause.
            assert data["queue_wait"]["total_s"] == pytest.approx(
                sum(by_cause.values()), abs=1e-5)
            assert "token_budget" in data["verdict"]
            # The flight-recorder timeline and measured SLO cross-check
            # ride along (smoke-1 has a full seeded trace).
            assert [e["event"] for e in data["trace"]][-1] == "finished"
            assert "measured_s" in data["queue_wait"]
            assert "unexplained_s" in data["queue_wait"]
            kinds = [d["decision"] for d in data["decisions"]]
            assert "defer" in kinds and "scheduled" in kinds

            resp = await client.get("/debug/explain/never-seen")
            assert resp.status == 404

            # Fleet-level ledger on deep health (503: no engine).
            resp = await client.get("/health/detail")
            assert resp.status == 503
            contention = (await resp.json())["contention"]
            assert contention["enabled"] is True
            causes = contention["deferred_seconds_by_cause"]
            assert causes["token_budget"] > 0
            assert causes["tenant_fairness"] > 0
            assert "unattributed" not in causes
            assert contention["decisions"]["scheduled"] == 1

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        get_flight_recorder().reset_for_testing()
        decisions_mod.reset_for_testing()


def test_demo_server_has_debug_routes():
    _seed_recorder()
    try:
        async def scenario(client):
            resp = await client.get("/health")
            assert resp.status == 200
            resp = await client.get("/debug/trace",
                                    params={"request_id": "smoke-1"})
            assert resp.status == 200
            data = await resp.json()
            assert data["events"][-1]["event"] == "finished"

        _run(demo_server.build_app(), scenario)
    finally:
        get_flight_recorder().reset_for_testing()


def test_trace_pagination_offset():
    """/debug/trace pages its ring with ?limit= and ?offset= (newest
    first, offset skips from the newest end) on both servers."""
    recorder = get_flight_recorder()
    recorder.reset_for_testing()
    for i in range(5):
        recorder.record(f"page-{i}", "arrived")
        recorder.record(f"page-{i}", "finished")
    try:
        async def scenario(client):
            resp = await client.get("/debug/trace",
                                    params={"limit": "2"})
            data = await resp.json()
            assert [x["request_id"] for x in data["recent_finished"]] == [
                "page-4", "page-3"]

            resp = await client.get("/debug/trace",
                                    params={"limit": "2", "offset": "2"})
            data = await resp.json()
            assert [x["request_id"] for x in data["recent_finished"]] == [
                "page-2", "page-1"]

            resp = await client.get("/debug/trace",
                                    params={"offset": "99"})
            data = await resp.json()
            assert data["recent_finished"] == []

            resp = await client.get("/debug/trace",
                                    params={"offset": "-1"})
            assert resp.status == 400
            resp = await client.get("/debug/trace",
                                    params={"offset": "bogus"})
            assert resp.status == 400

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        recorder.reset_for_testing()


def test_workload_endpoint_on_both_servers():
    """/debug/workload serves the capture ring (paged JSON, newest
    first) and the full stream as an IWL1 document via ?format=iwl on
    both servers."""
    from intellillm_tpu.obs.workload import (get_workload_log, parse_iwl,
                                             reset_workload_log_for_testing)

    reset_workload_log_for_testing()
    log = get_workload_log()
    for i in range(3):
        log.record(trace_id=f"wl-{i}", arrival_ts=100.0 + i,
                   prompt_len=4, prompt_hash=f"{i:016x}",
                   sampling={"max_tokens": 8}, emitted_tokens=8,
                   reason="finished")
    try:
        async def scenario(client):
            resp = await client.get("/debug/workload")
            assert resp.status == 200
            data = await resp.json()
            assert data["enabled"] is True
            assert data["count"] == 3
            assert data["raw_prompts"] is False
            assert [r["id"] for r in data["records"]] == [
                "wl-2", "wl-1", "wl-0"]

            resp = await client.get("/debug/workload",
                                    params={"limit": "1", "offset": "1"})
            data = await resp.json()
            assert [r["id"] for r in data["records"]] == ["wl-1"]

            resp = await client.get("/debug/workload",
                                    params={"format": "iwl"})
            assert resp.status == 200
            header, recs = parse_iwl(await resp.text())
            assert header["iwl"] == 1 and header["requests"] == 3
            # IWL order is arrival order with rebased offsets.
            assert [r["id"] for r in recs] == ["wl-0", "wl-1", "wl-2"]
            assert [r["t"] for r in recs] == [0.0, 1.0, 2.0]

            resp = await client.get("/debug/workload",
                                    params={"limit": "bogus"})
            assert resp.status == 400

        _run(demo_server.build_app(), scenario)
        _run(openai_server.build_app(), scenario)
    finally:
        reset_workload_log_for_testing()
