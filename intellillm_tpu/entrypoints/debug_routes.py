"""Debug/observability HTTP routes shared by both API servers.

    GET  /metrics                       Prometheus scrape endpoint (501
                                        when prometheus_client is not
                                        installed — `serve` extra)
    GET  /debug/trace?request_id=<id>   flight-recorder events for one
                                        request (404 if unknown/evicted)
    GET  /debug/trace                   live request ids + recently
                                        finished traces (?limit=N,
                                        ?offset=N pages the ring,
                                        ?event=<name> keeps only traces
                                        containing that event) + per-
                                        terminal-event counts over the
                                        finished ring
    GET  /debug/workload                captured workload log: per-
                                        request arrival/shape/sampling/
                                        tenant/outcome records
                                        (?limit=/?offset= pages,
                                        ?format=iwl returns the IWL1
                                        JSONL replay artifact)
    GET  /debug/explain/{request_id}    per-request root-cause explain:
                                        scheduler decision events, the
                                        queue-wait / stall decomposition
                                        by cause, the measured SLO
                                        timings, and a top-line verdict
                                        (obs/decisions.py; 404 if the
                                        request was never seen)
    GET  /debug/stall                   watchdog state + ring of stall
                                        reports (thread stacks, queue
                                        depths, compile snapshot)
    GET  /debug/efficiency              cumulative compute-efficiency
                                        ledger: real/pad token totals,
                                        per-axis fill ratios, rolling
                                        MFU, and the per-bucket pad-
                                        FLOPs waste attribution
                                        (?top=N trims the waste list)
    GET  /debug/history?metric=&window= in-process metrics history:
                                        [[t, v], ...] points for one
                                        series (window like "5m"/"1h"
                                        or seconds), or the store
                                        snapshot + series list when no
                                        metric is given
    GET  /debug/alerts                  alert rule table with pending/
                                        firing/resolved states
    GET  /debug/predictor               length-predictor calibration
                                        table (per-bucket factors) +
                                        recent predicted-vs-actual
                                        samples (docs/scheduling.md)
    GET  /debug/spec                    speculative-decoding stats:
                                        current/band K, rolling
                                        acceptance rate, verify-waste
                                        ratio, lifetime token totals
                                        (404 when the engine runs
                                        without a draft model)
    GET  /debug/numerics                numerics & output-integrity
                                        snapshot: sentinel stats
                                        (rows checked, anomalies by
                                        kind, recent trips, quarantine
                                        set) + KV-integrity audit
                                        counters (obs/numerics.py)
    GET  /debug/kernels                 per-(program, bucket) kernel
                                        cost ledger: cost_analysis
                                        FLOPs / bytes / peak HBM per
                                        executable, cost-model-vs-
                                        analytic MFU cross-check, and
                                        the latest measured per-op
                                        wall-time capture (?top=N
                                        trims the tables)
    GET  /health/detail                 structured liveness: last-step
                                        age, watchdog state, queue
                                        depths, KV usage, SLO summary,
                                        boot-phase timings, alert
                                        summary; 503 while the watchdog
                                        has a stall declared (and
                                        before the engine is up);
                                        "degraded" (still 200) while a
                                        page-severity alert is firing
    POST /debug/profiler/start?dir=...  begin a jax.profiler device trace
                                        (auto-stopped after
                                        INTELLILLM_PROFILER_MAX_S; 409
                                        while one is running)
    POST /debug/profiler/stop           end it (writes the trace to disk)
    POST /debug/profiler/capture?steps=N&top=K
                                        bounded capture-and-parse: trace
                                        N engine steps into a temp dir,
                                        fold the trace events into
                                        per-op wall time, merge the
                                        top-K ops into the kernel
                                        ledger, delete the temp dir

See docs/observability.md. The profiler endpoints drive
LLMEngine.start_profile/stop_profile and are admin-only: profiling
degrades serving and writes trace files to a caller-chosen directory,
so they are registered only with `enable_profiling=True` (the servers'
--enable-profiling flag). The read-only /debug/trace route is always
registered; on the OpenAI server every /debug route additionally sits
behind the same --api-key auth as every non-health route.
"""
from __future__ import annotations

import asyncio
import math
from typing import Callable, Optional

from aiohttp import web

from intellillm_tpu.obs import (EVENTS, explain_request, get_alert_manager,
                                get_boot_timeline, get_compile_tracker,
                                get_decision_log, get_device_telemetry,
                                get_efficiency_tracker,
                                get_flight_recorder, get_heap,
                                get_kernel_ledger, get_metrics_history,
                                get_slo_tracker,
                                get_step_tracer, get_watchdog,
                                get_workload_log)
from intellillm_tpu.prediction import get_prediction_service
from intellillm_tpu.worker.spec_decode.metrics import get_spec_stats


def _parse_window(raw: Optional[str], default: float = 600.0) -> float:
    """Accept "300", "5m", "1h" (and "30s"); raise ValueError otherwise."""
    if not raw:
        return default
    raw = raw.strip().lower()
    scale = 1.0
    if raw.endswith(("s", "m", "h")):
        scale = {"s": 1.0, "m": 60.0, "h": 3600.0}[raw[-1]]
        raw = raw[:-1]
    value = float(raw) * scale
    # NaN slips past a bare `<= 0` and an infinite cutoff silently
    # empties every query — both are caller errors, not windows.
    if not math.isfinite(value) or value <= 0:
        raise ValueError("window must be positive and finite")
    return value


async def debug_history(request: web.Request) -> web.Response:
    """Shared by both API servers and the router (module-level like
    `metrics`, since the handler has no engine dependency)."""
    history = get_metrics_history()
    metric = request.query.get("metric")
    try:
        window_s = _parse_window(request.query.get("window"))
    except (ValueError, KeyError):
        return web.json_response(
            {"error": "window must look like 300, 5m, or 1h"}, status=400)
    if not metric:
        body = history.snapshot()
        body["series"] = history.series_names()
        return web.json_response(body)
    if metric not in history.series_names():
        return web.json_response(
            {"error": f"unknown series {metric!r} "
             "(see /debug/history for the list)"}, status=404)
    tier = request.query.get("tier")
    points = history.query(metric, window_s, tier=tier)
    return web.json_response({"metric": metric, "window_s": window_s,
                              "points": points})


async def debug_alerts(request: web.Request) -> web.Response:
    return web.json_response(get_alert_manager().snapshot())


async def debug_numerics(request: web.Request) -> web.Response:
    """Numerics sentinels + KV-integrity audit snapshot (module-level
    like `metrics`: both singletons are process-global). Always
    registered — with sentinels off the body still reports
    enabled=false plus the KV-audit counters, so dashboards can
    distinguish 'numerics off' from 'numerics on and clean'."""
    from intellillm_tpu.obs import numerics_debug_snapshot
    return web.json_response(numerics_debug_snapshot())


async def debug_predictor(request: web.Request) -> web.Response:
    """Calibration table + recent predicted-vs-actual samples. Module
    level like `metrics`: the prediction service is process-global, so
    the handler has no engine dependency."""
    return web.json_response(get_prediction_service().snapshot())


async def debug_spec(request: web.Request) -> web.Response:
    """Speculative-decoding stats (module-level like `metrics`: the
    stats singleton is process-global). 404 when no draft model is
    configured, so dashboards can distinguish 'spec off' from 'spec on
    but cold'."""
    stats = get_spec_stats()
    if not stats.enabled:
        return web.json_response(
            {"error": "speculative decoding is not enabled "
             "(no --speculative-model)"}, status=404)
    return web.json_response(stats.summary())


def parse_paging(request: web.Request, default_limit: int = 32
                 ) -> "tuple[int, int]":
    """?limit=/?offset= for ring-buffer listings. Raises ValueError with
    a client-facing message."""
    try:
        limit = int(request.query.get("limit", str(default_limit)))
        offset = int(request.query.get("offset", "0"))
    except ValueError:
        raise ValueError("limit and offset must be integers")
    if limit < 0 or offset < 0:
        raise ValueError("limit and offset must be non-negative")
    return limit, offset


async def debug_workload(request: web.Request) -> web.Response:
    """The workload log (obs/workload.py) for THIS process. Module-level
    like `metrics` — no engine dependency — so both API servers share
    it; the router has its own fleet-merged variant. `?format=iwl`
    returns the ring as a versioned IWL1 JSONL document ready for
    `serve_bench --scenario replay`."""
    log = get_workload_log()
    if request.query.get("format", "json") == "iwl":
        return web.Response(text=log.iwl_text(), content_type="text/plain")
    try:
        limit, offset = parse_paging(request, default_limit=128)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.json_response(log.snapshot(limit=limit, offset=offset))


async def metrics(request: web.Request) -> web.Response:
    """Prometheus scrape endpoint — ONE handler shared by both servers
    (the demo server used to lack it entirely)."""
    try:
        from prometheus_client import REGISTRY, generate_latest
    except ImportError:
        return web.Response(
            status=501,
            text="prometheus_client is not installed (serve extra)")
    return web.Response(body=generate_latest(REGISTRY),
                        content_type="text/plain")


def add_debug_routes(app: web.Application,
                     get_engine: Callable[[], Optional[object]],
                     enable_profiling: bool = False) -> None:
    """`get_engine` returns the synchronous LLMEngine (or None before
    startup) — resolved per request because both servers assign their
    engine globals after module import."""

    async def debug_trace(request: web.Request) -> web.Response:
        recorder = get_flight_recorder()
        request_id = request.query.get("request_id")
        if request_id:
            events = recorder.get_trace(request_id)
            if events is None:
                return web.json_response(
                    {"error": f"no trace for request_id={request_id} "
                     "(never seen, or evicted from the ring)"}, status=404)
            return web.json_response({"request_id": request_id,
                                      "events": events})
        try:
            limit, offset = parse_paging(request)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        event = request.query.get("event")
        if event is not None and event not in EVENTS:
            return web.json_response(
                {"error": f"unknown event {event!r} "
                 f"(one of: {', '.join(EVENTS)})"}, status=400)
        return web.json_response({
            "live_request_ids": recorder.live_request_ids(),
            "finished_counts": recorder.finished_counts(),
            "recent_finished": recorder.recent_finished(limit, event=event,
                                                        offset=offset),
        })

    async def debug_explain(request: web.Request) -> web.Response:
        """Root-cause explain for one request on this hop (the router's
        /debug/explain/{trace_id} stitches these across hops)."""
        request_id = request.match_info["request_id"]
        payload = explain_request(request_id)
        if not payload["found"]:
            return web.json_response(
                {"error": f"no trace or scheduler decisions for "
                 f"request_id={request_id} (never seen, or evicted)"},
                status=404)
        return web.json_response(payload)

    async def debug_stall(request: web.Request) -> web.Response:
        watchdog = get_watchdog()
        return web.json_response({
            "watchdog": watchdog.snapshot(),
            "reports": watchdog.reports(),
        })

    async def debug_efficiency(request: web.Request) -> web.Response:
        try:
            top_n = int(request.query.get("top", "8"))
        except ValueError:
            return web.json_response({"error": "top must be an integer"},
                                     status=400)
        return web.json_response(
            get_efficiency_tracker().snapshot(top_n=top_n))

    async def debug_kernels(request: web.Request) -> web.Response:
        try:
            top = int(request.query.get("top", "8"))
        except ValueError:
            return web.json_response({"error": "top must be an integer"},
                                     status=400)
        return web.json_response(get_kernel_ledger().snapshot(top=top))

    async def health_detail(request: web.Request) -> web.Response:
        """Deep liveness, as opposed to the LB-cheap bare-200 /health:
        503 while the watchdog has declared a stall (or before engine
        startup), 200 with the same body otherwise. A firing
        page-severity alert reports "degraded" but stays 200 — alerts
        flag trends, not hard process death, and a 503 here would have
        the LB amplify a goodput dip into an outage."""
        watchdog = get_watchdog()
        alerts = get_alert_manager()
        # Re-evaluate the rule set on deep-health reads: a stall that
        # cleared between sampler ticks must not linger as "degraded"
        # for up to one history interval (rules are plain dict math over
        # pre-aggregated windows — cheap enough for LB-cadence polling).
        alerts.evaluate_now()
        body = {
            "watchdog": watchdog.snapshot(),
            "slo": get_slo_tracker().summary(),
            "compiles": get_compile_tracker().snapshot(),
            "device_telemetry": get_device_telemetry().snapshot(),
            # Compact: the full per-bucket ledger lives at
            # /debug/efficiency.
            "efficiency": get_efficiency_tracker().snapshot(
                top_n=4, include_buckets=False),
            # Compact: the per-executable table lives at /debug/kernels.
            "kernels": get_kernel_ledger().health_block(),
            # Device seconds by program and dry seconds by host phase
            # since start, profiler off (obs/tracing.py).
            "device_clock": get_step_tracer().device_totals(),
            # The collector's seconds and counts by generation, and what
            # the freeze points took out of its sight (obs/heap.py).
            "heap": get_heap().snapshot(),
            "live_requests": len(get_flight_recorder().live_request_ids()),
            # Fleet contention ledger: deferred seconds by cause +
            # decision counts (per-request decomposition at
            # /debug/explain/{id}; intellillm-top renders this as the
            # CONTENTION panel).
            "contention": get_decision_log().summary(),
            "alerts": alerts.summary(),
            # Phases from the process's start, and under `setup` every
            # first call's stages (obs/compile_tracker.py).
            "boot": dict(get_boot_timeline().snapshot(),
                         setup=get_compile_tracker().setup_record()),
            # Compact: the per-bucket table lives at /debug/predictor.
            # The router's load estimator consumes calibration_factor
            # from here to correct its own predicted lengths.
            "predictor": get_prediction_service().health_block(),
        }
        # Output-integrity surface (obs/numerics.py): sentinel +
        # KV-audit counters. The router's canary verdict rides the
        # fleet view, not this per-replica block (full snapshot at
        # /debug/numerics).
        from intellillm_tpu.obs import numerics_health_block
        body["numerics"] = numerics_health_block()
        # Spec-decode block only when a draft model is serving; fleet
        # aggregation treats a missing key as "spec off" (full table at
        # /debug/spec).
        spec_stats = get_spec_stats()
        if spec_stats.enabled:
            body["spec"] = spec_stats.summary()
        engine = get_engine()
        if engine is None:
            body["status"] = "initializing"
            return web.json_response(body, status=503)
        scheduler = engine.scheduler
        # Disaggregated serving surface: the router's health poller
        # reads the role, serve_bench reads the transfer summary.
        body["role"] = getattr(getattr(engine, "scheduler_config", None),
                               "replica_role", "mixed")
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        body["kv_transfer"] = get_kv_transfer_stats().summary()
        body["queue_depths"] = {
            "waiting": len(scheduler.waiting),
            "running": len(scheduler.running),
            "swapped": len(scheduler.swapped),
        }
        try:
            body["kv_cache_usage"] = engine.kv_cache_usage()
        except Exception:
            body["kv_cache_usage"] = None
        # Multi-tenant surface (docs/multitenancy.md): registrations,
        # per-tenant SLO/goodput splits, and the device-resident adapter
        # set. The router's adapter-affinity override and intellillm-top's
        # TENANTS panel both read this block; emitted only when tenancy
        # is in play (registered tenants or a LoRA-enabled worker) so
        # single-tenant fleets pay nothing.
        from intellillm_tpu.tenancy import (get_tenant_registry,
                                            get_tenant_stats)
        registry = get_tenant_registry()
        lora_manager = getattr(getattr(engine, "worker", None),
                               "lora_manager", None)
        if registry.tenant_ids() or lora_manager is not None:
            tenants_block = registry.snapshot()
            tenants_block["stats"] = get_tenant_stats().summary()
            tenants_block["active_adapters"] = (
                sorted(lora_manager.list_loras())
                if lora_manager is not None else [])
            body["tenants"] = tenants_block
        stalled = watchdog.state == "stalled"
        if stalled:
            body["status"] = "stalled"
        elif alerts.page_firing():
            body["status"] = "degraded"
        else:
            body["status"] = "ok"
        return web.json_response(body, status=503 if stalled else 200)

    async def profiler_start(request: web.Request) -> web.Response:
        engine = get_engine()
        if engine is None:
            return web.json_response({"error": "engine not ready"},
                                     status=503)
        trace_dir = request.query.get("dir", "/tmp/intellillm-trace")
        started = engine.start_profile(trace_dir)
        if started is None:
            return web.json_response(
                {"error": "a trace is already running"}, status=409)
        return web.json_response({"trace_dir": started})

    async def profiler_stop(request: web.Request) -> web.Response:
        engine = get_engine()
        if engine is None:
            return web.json_response({"error": "engine not ready"},
                                     status=503)
        # stop_trace serializes the whole trace to disk — keep it off the
        # event loop so in-flight requests/streams don't stall.
        loop = asyncio.get_event_loop()
        await loop.run_in_executor(None, engine.stop_profile)
        return web.json_response({"ok": True})

    async def profiler_capture(request: web.Request) -> web.Response:
        """Bounded capture-and-reduce (obs/kernels.py): profile N engine
        steps into a temp dir, reduce the trace (device busy union and
        idle, operations by self time, idle gaps by host phase), merge
        the block into the kernel ledger, and delete the trace — no
        caller-chosen paths, no unbounded trace left
        running (the step wait is capped by
        INTELLILLM_PROFILER_CAPTURE_TIMEOUT_S on idle engines, and the
        engine's INTELLILLM_PROFILER_MAX_S watchdog backstops both)."""
        engine = get_engine()
        if engine is None:
            return web.json_response({"error": "engine not ready"},
                                     status=503)
        from intellillm_tpu.obs.kernels import (capture_max_steps,
                                                capture_profile,
                                                wait_for_steps)
        try:
            steps = int(request.query.get("steps", "8"))
            top = int(request.query.get("top", "16"))
        except ValueError:
            return web.json_response(
                {"error": "steps and top must be integers"}, status=400)
        steps = max(1, min(steps, capture_max_steps()))
        ledger = get_kernel_ledger()
        import shutil
        import tempfile
        tmpdir = tempfile.mkdtemp(prefix="intellillm-kernel-capture-")
        started = engine.start_profile(tmpdir)
        if started is None:
            shutil.rmtree(tmpdir, ignore_errors=True)
            return web.json_response(
                {"error": "a trace is already running"}, status=409)
        # Whole steps on the program's own clock (obs/tracing.py's device
        # timeline, read at step boundaries), to hold against the trace's
        # account: the two windows differ by up to a step at each end.
        def clock_now():
            programs = get_efficiency_tracker().snapshot(
                top_n=0, include_buckets=False)["programs"]
            return {**get_step_tracer().device_totals(),
                    "dispatches": {name: c["idle"] + c["busy"]
                                   for name, c in programs.items()}}
        clock_start = clock_now()
        loop = asyncio.get_event_loop()
        try:
            observed = await loop.run_in_executor(
                None, wait_for_steps, ledger, steps)
            clock_end = clock_now()
            await loop.run_in_executor(None, engine.stop_profile)
            reduced = await loop.run_in_executor(None, capture_profile,
                                                 tmpdir)
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
        block = ledger.merge_profile(reduced, steps=observed, top=top)
        clock = {key: {name: v - clock_start[key].get(name, 0)
                       for name, v in clock_end[key].items()}
                 for key in ("busy_by_program", "idle_by_phase",
                             "dispatches")}
        # Busy and idle tile the loop's period: the seconds between the
        # two step boundaries.
        clock["window_s"] = (sum(clock["busy_by_program"].values())
                             + sum(clock["idle_by_phase"].values()))
        return web.json_response({
            "steps_requested": steps,
            "steps_observed": observed,
            "profile": block,
            "clock": clock,
        })

    async def mark_serving(app: web.Application) -> None:
        # The boot's last phase (`serve_start`) ends where the server
        # starts to listen.
        get_boot_timeline().mark_serving()

    app.on_startup.append(mark_serving)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_get("/debug/workload", debug_workload)
    app.router.add_get("/debug/explain/{request_id}", debug_explain)
    app.router.add_get("/debug/stall", debug_stall)
    app.router.add_get("/debug/efficiency", debug_efficiency)
    app.router.add_get("/debug/history", debug_history)
    app.router.add_get("/debug/alerts", debug_alerts)
    app.router.add_get("/debug/predictor", debug_predictor)
    app.router.add_get("/debug/spec", debug_spec)
    app.router.add_get("/debug/numerics", debug_numerics)
    app.router.add_get("/debug/kernels", debug_kernels)
    app.router.add_get("/health/detail", health_detail)
    if enable_profiling:
        app.router.add_post("/debug/profiler/start", profiler_start)
        app.router.add_post("/debug/profiler/stop", profiler_stop)
        app.router.add_post("/debug/profiler/capture", profiler_capture)
