"""Replica abstractions + the `ReplicaManager` liveness poller.

A Replica is something the router can stream a `/generate`-shaped
request through and health-probe. Two concrete kinds:

- `InProcessReplica` wraps an `AsyncLLMEngine` in this process — the CPU
  test vehicle (and a future `data`-axis in-process fleet).
- `HTTPReplica` fronts a separate engine-server process (the demo
  `api_server`), speaking its exact wire protocol: POST `/generate`
  with `stream=true` → newline-delimited JSON chunks whose `text` field
  is CUMULATIVE (prompt + text so far). Cumulative chunks are what make
  transparent mid-stream failover possible: a restarted request on
  another replica simply resumes emitting supersets.

The `ReplicaManager` owns the fleet: attach/launch, a background
health-poll loop against each replica's `/health/detail`, per-replica
predicted-load/in-flight accounting, and the per-replica gauges.

Divergence canaries (obs/numerics.py, docs/observability.md): every
`INTELLILLM_CANARY_EVERY` poll ticks (0 = off) the manager streams one
deterministic greedy prompt through each live replica, digests the
final output, and compares digests fleet-wide. A replica that
disagrees with the strict majority is marked `suspect` — visible in
the router's `/health/detail` fleet view and fleet alerts — and, with
`INTELLILLM_CANARY_DRAIN=1`, drained from routing candidates until its
canary re-converges. No strict majority (e.g. a 1:1 split) marks
nobody: the canary detects the odd replica out, not which side is
right.
"""
from __future__ import annotations

import asyncio
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import AsyncIterator, Dict, List, Optional, Tuple

from intellillm_tpu.logger import init_logger
from intellillm_tpu.router.metrics import get_router_metrics
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.utils import random_uuid

logger = init_logger(__name__)


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("Ignoring invalid %s=%r (want an int).", name, raw)
        return default


class ReplicaFailure(Exception):
    """A replica failed while serving a request (connection drop,
    mid-stream error, non-2xx). Routable to another replica."""


class Replica:
    """Base replica: identity, health state, and load accounting."""

    def __init__(self, replica_id: str, role: str = "mixed") -> None:
        self.replica_id = replica_id
        # Disaggregated serving role (docs/routing.md "Disaggregated
        # roles"): "mixed" serves everything; "prefill" runs chunked
        # prefill and exports KV; "decode" imports KV and decodes.
        self.role = role
        self.healthy = False
        self.last_health: Optional[dict] = None
        self.last_health_ts: Optional[float] = None
        self.consecutive_failures = 0
        # Router-side load model: outstanding predicted decode tokens and
        # in-flight request count (decremented on completion OR failure).
        self.predicted_load = 0.0
        self.inflight = 0
        # Divergence-canary state (ReplicaManager.run_canary): suspect
        # means this replica's deterministic canary digest disagreed
        # with the fleet majority on the latest run.
        self.suspect = False
        self.last_canary_digest: Optional[str] = None
        self.last_canary_ts: Optional[float] = None
        # Testing hook: a non-None value short-circuits canary() so
        # fleet tests can force divergence without a model in the loop.
        self.canary_digest_override: Optional[str] = None

    @property
    def calibration_factor(self) -> float:
        """This replica's predictor calibration factor (global p50
        actual/predicted ratio) from its last /health/detail poll; 1.0
        until the replica reports one. The router scales its predicted
        lengths by this so fleet load estimates use corrected lengths."""
        predictor = (self.last_health or {}).get("predictor") or {}
        try:
            factor = float(predictor.get("calibration_factor", 1.0))
        except (TypeError, ValueError):
            return 1.0
        return factor if factor > 0 else 1.0

    async def generate(self, payload: dict,
                       predicted_len: Optional[int] = None,
                       request_id: Optional[str] = None
                       ) -> AsyncIterator[dict]:
        """Stream the request. `request_id` is the router-assigned
        distributed trace (sub-)id; None lets the replica mint one."""
        raise NotImplementedError

    async def health_detail(self) -> Tuple[int, dict]:
        """(status_code, body) of the replica's /health/detail."""
        raise NotImplementedError

    async def canary(self, prompt: str, max_tokens: int = 8
                     ) -> Optional[str]:
        """Stream the deterministic greedy canary `prompt` through this
        replica and return a digest of the final cumulative output (None
        when the stream produced nothing). Greedy + fixed prompt means
        every healthy replica serving the same weights must produce the
        same digest — any disagreement is weight corruption, numerics
        divergence, or version skew. Raises ReplicaFailure like any
        other request; the manager treats that as digest None."""
        if self.canary_digest_override is not None:
            return self.canary_digest_override
        payload = {"prompt": prompt, "temperature": 0.0,
                   "max_tokens": max_tokens}
        final: Optional[str] = None
        gen = self.generate(
            payload, request_id=f"canary-{self.replica_id}-{random_uuid()}")
        async for chunk in gen:
            texts = chunk.get("text")
            if texts:
                final = texts[0]
        if final is None:
            return None
        return hashlib.blake2b(final.encode("utf-8"),
                               digest_size=16).hexdigest()

    async def export_kv(self, prompt: str) -> bytes:
        """Export the KV prefix this replica prefilled for `prompt`
        (content-addressed wire payload, worker/kv_transfer.py)."""
        raise NotImplementedError

    async def import_kv(self, payload: bytes) -> dict:
        """Install an exported KV payload; returns {key, imported,
        num_blocks, prefix_pos} (prefix_pos in the replica's own token
        space)."""
        raise NotImplementedError

    async def fetch_trace(self, request_id: str) -> Optional[list]:
        """This replica's flight-recorder events for `request_id`, or
        None when unknown/unreachable — the stitching side of
        router/trace.py. Must not raise: a dead replica is exactly when
        the stitched view matters most."""
        return None

    async def fetch_explain(self, request_id: str) -> Optional[dict]:
        """This replica's /debug/explain payload (scheduler decision
        decomposition, obs/decisions.py) for `request_id`, or None when
        unknown/unreachable. Same must-not-raise contract as
        fetch_trace."""
        return None

    async def fetch_workload(self, limit: int = 1024) -> Optional[list]:
        """This replica's captured workload records (obs/workload.py),
        arrival-ordered, or None when unknown/unreachable — the fleet-
        merge side of the router's /debug/workload. Same must-not-raise
        contract as fetch_trace."""
        return None

    async def close(self) -> None:
        pass


class InProcessReplica(Replica):
    """Wraps an in-process `AsyncLLMEngine` (CPU tests, single-host
    fleets). `kill()` simulates a replica crash: in-flight streams raise
    `ReplicaFailure` at the next chunk and the replica goes unhealthy."""

    def __init__(self, replica_id: str, engine,
                 role: str = "mixed") -> None:
        super().__init__(replica_id, role=role)
        self.engine = engine
        self._killed = False

    def kill(self) -> None:
        self._killed = True
        self.healthy = False

    async def generate(self, payload: dict,
                       predicted_len: Optional[int] = None,
                       request_id: Optional[str] = None
                       ) -> AsyncIterator[dict]:
        if self._killed:
            raise ReplicaFailure(f"replica {self.replica_id} is down")
        payload = dict(payload)
        prompt = payload.pop("prompt")
        prefix_pos = payload.pop("prefix_pos", None)
        payload.pop("stream", None)
        sampling_params = SamplingParams(**payload)
        request_id = request_id or random_uuid()
        gen = self.engine.generate(prompt, sampling_params, request_id,
                                   prefix_pos=prefix_pos,
                                   predicted_len=predicted_len)
        async for request_output in gen:
            if self._killed:
                # Seal the trace as `rerouted` BEFORE the abort lands
                # (aborts are processed at the next engine step): the
                # request leaves no orphaned live flight-recorder entry
                # on this dead replica, and the late `aborted` hits a
                # sealed trace — so the SLO finish hook fires for the
                # retried attempt only, not this one.
                from intellillm_tpu.obs import get_flight_recorder
                get_flight_recorder().record(
                    request_id, "rerouted",
                    detail=f"replica={self.replica_id} died mid-stream")
                try:
                    await self.engine.abort(request_id)
                finally:
                    pass
                raise ReplicaFailure(
                    f"replica {self.replica_id} died mid-stream")
            yield {
                "text": [
                    request_output.prompt + output.text
                    for output in request_output.outputs
                ]
            }

    async def health_detail(self) -> Tuple[int, dict]:
        if self._killed:
            raise ReplicaFailure(f"replica {self.replica_id} is down")
        llm_engine = getattr(self.engine, "engine", None)
        if llm_engine is None:
            return 503, {"status": "initializing"}
        scheduler = llm_engine.scheduler
        body = {
            "status": "ok",
            "role": getattr(llm_engine.scheduler_config, "replica_role",
                            "mixed"),
            "queue_depths": {
                "waiting": len(scheduler.waiting),
                "running": len(scheduler.running),
                "swapped": len(scheduler.swapped),
            },
        }
        try:
            body["kv_cache_usage"] = llm_engine.kv_cache_usage()
        except Exception:
            body["kv_cache_usage"] = None
        # Same block the HTTP replicas expose via debug_routes'
        # /health/detail — the router reads calibration_factor from it.
        from intellillm_tpu.prediction import get_prediction_service
        body["predictor"] = get_prediction_service().health_block()
        return 200, body

    async def fetch_trace(self, request_id: str) -> Optional[list]:
        # The process-global recorder — the engine's hop. A killed
        # replica can still serve its sealed traces (that is the point:
        # the `rerouted` terminal must be visible in the stitched view).
        from intellillm_tpu.obs import get_flight_recorder
        return get_flight_recorder().get_trace(request_id)

    async def fetch_explain(self, request_id: str) -> Optional[dict]:
        from intellillm_tpu.obs import explain_request
        payload = explain_request(request_id)
        return payload if payload.get("found") else None

    async def fetch_workload(self, limit: int = 1024) -> Optional[list]:
        # The process-global log — in-process replicas share it, so the
        # router's merge dedups the shared records by trace id.
        from intellillm_tpu.obs import get_workload_log
        return get_workload_log().records()[-limit:]

    async def export_kv(self, prompt: str) -> bytes:
        if self._killed:
            raise ReplicaFailure(f"replica {self.replica_id} is down")
        try:
            return await self.engine.export_kv(prompt)
        except (KeyError, ValueError, RuntimeError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: kv export failed: {e}") from e

    async def import_kv(self, payload: bytes) -> dict:
        if self._killed:
            raise ReplicaFailure(f"replica {self.replica_id} is down")
        try:
            return await self.engine.import_kv(payload)
        except (ValueError, RuntimeError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: kv import failed: {e}") from e


class HTTPReplica(Replica):
    """Fronts an engine server over HTTP (demo api_server protocol).

    Optionally owns the server subprocess (launched replicas); `close()`
    then terminates it.
    """

    def __init__(self, replica_id: str, base_url: str,
                 proc: Optional[subprocess.Popen] = None,
                 request_timeout_s: float = 600.0,
                 role: str = "mixed") -> None:
        super().__init__(replica_id, role=role)
        self.base_url = base_url.rstrip("/")
        self.proc = proc
        self.request_timeout_s = request_timeout_s
        self._session = None

    def _get_session(self):
        import aiohttp
        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.request_timeout_s))
        return self._session

    async def generate(self, payload: dict,
                       predicted_len: Optional[int] = None,
                       request_id: Optional[str] = None
                       ) -> AsyncIterator[dict]:
        # predicted_len stays router-side: the demo server's SamplingParams
        # parsing rejects unknown fields.
        import aiohttp
        body = dict(payload)
        body["stream"] = True
        # Context propagation: the replica server honors X-Request-Id,
        # so its flight-recorder events land under the router's trace id.
        headers = {"X-Request-Id": request_id} if request_id else None
        try:
            async with self._get_session().post(
                    f"{self.base_url}/generate", json=body,
                    headers=headers) as resp:
                if resp.status != 200:
                    raise ReplicaFailure(
                        f"replica {self.replica_id}: /generate -> "
                        f"{resp.status}")
                async for line in resp.content:
                    line = line.strip()
                    if line:
                        yield json.loads(line)
        except (aiohttp.ClientError, asyncio.TimeoutError,
                ConnectionError, json.JSONDecodeError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: {type(e).__name__}: {e}"
            ) from e

    async def health_detail(self) -> Tuple[int, dict]:
        import aiohttp
        try:
            async with self._get_session().get(
                    f"{self.base_url}/health/detail",
                    timeout=aiohttp.ClientTimeout(total=5.0)) as resp:
                return resp.status, await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError,
                ConnectionError, json.JSONDecodeError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: {type(e).__name__}: {e}"
            ) from e

    async def fetch_trace(self, request_id: str) -> Optional[list]:
        import aiohttp
        try:
            async with self._get_session().get(
                    f"{self.base_url}/debug/trace",
                    params={"request_id": request_id},
                    timeout=aiohttp.ClientTimeout(total=5.0)) as resp:
                if resp.status != 200:
                    return None
                body = await resp.json()
                return body.get("events")
        except Exception:
            # Unreachable replica: the stitched trace reports the
            # attempt with events=None instead of failing the fetch.
            return None

    async def fetch_explain(self, request_id: str) -> Optional[dict]:
        import aiohttp
        try:
            async with self._get_session().get(
                    f"{self.base_url}/debug/explain/{request_id}",
                    timeout=aiohttp.ClientTimeout(total=5.0)) as resp:
                if resp.status != 200:
                    return None
                return await resp.json()
        except Exception:
            # Same contract as fetch_trace: a dead replica yields
            # explain=None for the attempt, never a failed stitch.
            return None

    async def fetch_workload(self, limit: int = 1024) -> Optional[list]:
        import aiohttp
        try:
            async with self._get_session().get(
                    f"{self.base_url}/debug/workload",
                    params={"limit": str(limit)},
                    timeout=aiohttp.ClientTimeout(total=5.0)) as resp:
                if resp.status != 200:
                    return None
                body = await resp.json()
                # snapshot() pages newest-first; restore arrival order.
                records = body.get("records") or []
                return sorted(records,
                              key=lambda r: (r.get("ts") or 0.0,
                                             r.get("id") or ""))
        except Exception:
            # A dead replica contributes nothing to the fleet merge
            # instead of failing it (same contract as fetch_trace).
            return None

    async def export_kv(self, prompt: str) -> bytes:
        import aiohttp
        try:
            async with self._get_session().post(
                    f"{self.base_url}/kv/export",
                    json={"prompt": prompt}) as resp:
                if resp.status != 200:
                    raise ReplicaFailure(
                        f"replica {self.replica_id}: /kv/export -> "
                        f"{resp.status}")
                return await resp.read()
        except (aiohttp.ClientError, asyncio.TimeoutError,
                ConnectionError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: {type(e).__name__}: {e}"
            ) from e

    async def import_kv(self, payload: bytes) -> dict:
        import aiohttp
        try:
            async with self._get_session().post(
                    f"{self.base_url}/kv/import", data=payload,
                    headers={"Content-Type": "application/octet-stream"}
            ) as resp:
                if resp.status != 200:
                    raise ReplicaFailure(
                        f"replica {self.replica_id}: /kv/import -> "
                        f"{resp.status}")
                return await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError,
                ConnectionError, json.JSONDecodeError) as e:
            raise ReplicaFailure(
                f"replica {self.replica_id}: {type(e).__name__}: {e}"
            ) from e

    async def close(self) -> None:
        if self._session is not None and not self._session.closed:
            await self._session.close()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                # Off-loop: Popen.wait blocks up to its full timeout,
                # which would freeze every other stream on the router's
                # event loop for the duration of a slow shutdown.
                await asyncio.to_thread(self.proc.wait, 10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def _tensor_parallel_size(engine_argv: List[str]) -> int:
    """The chips one replica spans, read off its engine flags."""
    for flag in ("--tensor-parallel-size", "-tp"):
        for i, arg in enumerate(engine_argv):
            if arg == flag and i + 1 < len(engine_argv):
                return int(engine_argv[i + 1])
            if arg.startswith(flag + "="):
                return int(arg.split("=", 1)[1])
    return 1


def launch_http_replica(replica_id: str, port: int,
                        engine_argv: List[str],
                        host: str = "127.0.0.1",
                        role: str = "mixed",
                        child_index: int = 0) -> HTTPReplica:
    """Launch a demo api_server subprocess as a replica. It inherits this
    process's environment (so JAX_PLATFORMS etc. apply) plus the checkout
    on its path and, as replica `child_index` of this host, chips of its
    own (utils.child_process_env): N replicas that each saw every chip
    would all claim chip 0."""
    cmd = [
        sys.executable, "-m", "intellillm_tpu.entrypoints.api_server",
        "--host", host, "--port", str(port),
    ] + list(engine_argv)
    if role != "mixed" and "--replica-role" not in engine_argv:
        cmd += ["--replica-role", role]
    logger.info("launching replica %s: %s", replica_id, " ".join(cmd))
    from intellillm_tpu.utils import child_process_env
    env = child_process_env(
        child_index=child_index,
        chips_per_child=_tensor_parallel_size(engine_argv))
    proc = subprocess.Popen(cmd, env=env)
    return HTTPReplica(replica_id, f"http://{host}:{port}", proc=proc,
                       role=role)


class ReplicaManager:
    """Owns the replica fleet: registration, background health polling,
    and router-side load accounting (+ per-replica gauges)."""

    def __init__(self, health_interval_s: float = 2.0,
                 unhealthy_after: int = 2,
                 canary_every: Optional[int] = None,
                 canary_prompt: Optional[str] = None,
                 canary_max_tokens: Optional[int] = None,
                 canary_drain: Optional[bool] = None) -> None:
        self.replicas: Dict[str, Replica] = {}
        self.health_interval_s = health_interval_s
        # Probes that must fail consecutively before a replica is marked
        # unhealthy (one blip shouldn't drain it). Failures during
        # serving bypass this via mark_failed().
        self.unhealthy_after = unhealthy_after
        self._poll_task: Optional[asyncio.Task] = None
        # Divergence canary (module docstring): run every N poll ticks;
        # 0 disables. Args override env so tests and the router CLI can
        # both configure it.
        self.canary_every = (canary_every if canary_every is not None
                             else _env_int("INTELLILLM_CANARY_EVERY", 0))
        self.canary_prompt = (canary_prompt if canary_prompt is not None
                              else os.environ.get(
                                  "INTELLILLM_CANARY_PROMPT",
                                  "The quick brown fox"))
        self.canary_max_tokens = (
            canary_max_tokens if canary_max_tokens is not None
            else _env_int("INTELLILLM_CANARY_MAX_TOKENS", 8))
        if canary_drain is None:
            from intellillm_tpu.utils import parse_env_flag
            canary_drain = parse_env_flag(
                os.environ.get("INTELLILLM_CANARY_DRAIN", "")) is True
        self.canary_drain = canary_drain
        self._polls_since_canary = 0

    # --- fleet membership -------------------------------------------------

    def add(self, replica: Replica, healthy: bool = False) -> None:
        assert replica.replica_id not in self.replicas, replica.replica_id
        replica.healthy = healthy
        self.replicas[replica.replica_id] = replica
        self._export_gauges(replica)

    def get(self, replica_id: str) -> Replica:
        return self.replicas[replica_id]

    def healthy_loads(self, exclude: Optional[set] = None,
                      role: Optional[str] = None) -> Dict[str, float]:
        """Routing candidates: healthy replicas (minus `exclude`) →
        outstanding predicted decode tokens. Unhealthy replicas are
        simply absent — in-flight work keeps draining, new work skips
        them (drain-on-unhealthy). `role` narrows candidates to one
        disaggregated role; None means any role."""
        exclude = exclude or set()
        return {
            rid: r.predicted_load
            for rid, r in self.replicas.items()
            if r.healthy and rid not in exclude
            and (role is None or r.role == role)
        }

    def disagg_active(self) -> bool:
        """Whether the fleet can run a disaggregated handoff right now:
        at least one healthy prefill AND one healthy decode replica."""
        roles = {r.role for r in self.replicas.values() if r.healthy}
        return "prefill" in roles and "decode" in roles

    # --- load accounting --------------------------------------------------

    def on_route(self, replica_id: str, predicted_len: int) -> None:
        r = self.replicas[replica_id]
        r.predicted_load += predicted_len
        r.inflight += 1
        m = get_router_metrics()
        if m is not None:
            m.counter_requests.labels(replica=replica_id).inc()
        self._export_gauges(r)

    def on_complete(self, replica_id: str, predicted_len: int) -> None:
        r = self.replicas[replica_id]
        r.predicted_load = max(r.predicted_load - predicted_len, 0.0)
        r.inflight = max(r.inflight - 1, 0)
        self._export_gauges(r)

    def mark_failed(self, replica_id: str) -> None:
        """Serving failure: drop the replica from candidates immediately
        (don't wait for the next poll tick)."""
        r = self.replicas[replica_id]
        r.healthy = False
        r.consecutive_failures += 1
        self._export_gauges(r)

    # --- health polling ---------------------------------------------------

    async def poll_once(self) -> None:
        for r in list(self.replicas.values()):
            try:
                status, body = await r.health_detail()
            except Exception as e:
                r.consecutive_failures += 1
                if r.consecutive_failures >= self.unhealthy_after:
                    if r.healthy:
                        logger.warning("replica %s unhealthy: %s",
                                       r.replica_id, e)
                    r.healthy = False
                self._export_gauges(r)
                continue
            r.last_health = body
            r.last_health_ts = time.monotonic()
            # Replicas self-report their role on /health/detail; trust it
            # over static config so a fleet assembled from bare URLs
            # still disaggregates correctly.
            reported_role = body.get("role")
            if reported_role in ("mixed", "prefill", "decode"):
                r.role = reported_role
            # A 503 "initializing" body is a live-but-not-ready replica;
            # "stalled" (watchdog) is unhealthy like a probe failure.
            # "degraded" (page-severity alert firing) stays HEALTHY:
            # /health/detail keeps it at 200 precisely so load balancers
            # don't eject a still-serving replica, and this poller must
            # honor the same contract — a fleet-wide alert (e.g.
            # slo_burn_rate) would otherwise degrade every replica and
            # turn a goodput dip into a router-wide 503 outage.
            ok = status == 200 and body.get("status") in ("ok", "degraded")
            # A canary-divergent replica under drain stays out of the
            # candidate set no matter what its own health says — its
            # self-report is exactly what the canary distrusts. The
            # suspect flag clears on a later converging canary run.
            if r.suspect and self.canary_drain:
                ok = False
            if ok:
                if not r.healthy:
                    logger.info("replica %s healthy", r.replica_id)
                r.healthy = True
                r.consecutive_failures = 0
            else:
                r.consecutive_failures += 1
                if r.consecutive_failures >= self.unhealthy_after:
                    r.healthy = False
            self._export_gauges(r)
        if self.canary_every > 0:
            self._polls_since_canary += 1
            if self._polls_since_canary >= self.canary_every:
                self._polls_since_canary = 0
                await self.run_canary()

    # --- divergence canary ------------------------------------------------

    async def run_canary(self) -> Dict[str, Optional[str]]:
        """One fleet-wide canary round (module docstring): same greedy
        prompt through every live replica, strict-majority digest vote,
        off-majority replicas marked suspect. Suspect-but-drained
        replicas stay in the round so a recovered replica (restart,
        reload) can re-converge and rejoin. Returns the per-replica
        digests (None = the canary itself failed, which is a health
        problem, not a divergence verdict)."""
        digests: Dict[str, Optional[str]] = {}
        for rid, r in list(self.replicas.items()):
            if not (r.healthy or r.suspect):
                continue
            try:
                digests[rid] = await r.canary(self.canary_prompt,
                                              self.canary_max_tokens)
            except Exception as e:
                logger.warning("replica %s canary failed: %s", rid, e)
                digests[rid] = None
            r.last_canary_digest = digests[rid]
            r.last_canary_ts = time.monotonic()
        counts: Dict[str, int] = {}
        for digest in digests.values():
            if digest is not None:
                counts[digest] = counts.get(digest, 0) + 1
        reference: Optional[str] = None
        suspects: List[str] = []
        if counts:
            best, best_n = max(counts.items(), key=lambda kv: kv[1])
            if best_n * 2 > sum(counts.values()):
                reference = best
                suspects = sorted(
                    rid for rid, digest in digests.items()
                    if digest is not None and digest != reference)
        for rid in digests:
            r = self.replicas.get(rid)
            if r is None:
                continue
            was_suspect = r.suspect
            r.suspect = rid in suspects
            if r.suspect and not was_suspect:
                logger.error(
                    "replica %s canary DIVERGED from fleet majority "
                    "(digest %s vs reference %s)%s", rid,
                    r.last_canary_digest, reference,
                    "; draining" if self.canary_drain else "")
                if self.canary_drain:
                    r.healthy = False
            elif was_suspect and not r.suspect:
                logger.info("replica %s canary re-converged", rid)
            self._export_gauges(r)
        from intellillm_tpu.obs import get_canary_ledger
        get_canary_ledger().record_run(digests, reference, suspects)
        m = get_router_metrics()
        if m is not None:
            m.counter_canary_runs.inc()
            for rid in suspects:
                m.counter_canary_divergence.labels(replica=rid).inc()
        return digests

    async def _poll_loop(self) -> None:
        while True:
            try:
                await self.poll_once()
            except Exception:
                logger.exception("replica health poll failed")
            await asyncio.sleep(self.health_interval_s)

    def start_polling(self) -> None:
        if self._poll_task is None or self._poll_task.done():
            self._poll_task = asyncio.get_event_loop().create_task(
                self._poll_loop())

    async def stop(self) -> None:
        if self._poll_task is not None:
            self._poll_task.cancel()
            try:
                await self._poll_task
            except (asyncio.CancelledError, Exception):
                pass
            self._poll_task = None
        for r in self.replicas.values():
            await r.close()

    # --- observability ----------------------------------------------------

    def _export_gauges(self, r: Replica) -> None:
        m = get_router_metrics()
        if m is None:
            return
        m.gauge_predicted_load.labels(replica=r.replica_id).set(
            r.predicted_load)
        m.gauge_inflight.labels(replica=r.replica_id).set(r.inflight)
        m.gauge_healthy.labels(replica=r.replica_id).set(
            1 if r.healthy else 0)
        m.gauge_canary_suspect.labels(replica=r.replica_id).set(
            1 if r.suspect else 0)
        depths = (r.last_health or {}).get("queue_depths") or {}
        for queue, depth in depths.items():
            m.gauge_queue_depth.labels(replica=r.replica_id,
                                       queue=queue).set(depth)

    def snapshot(self) -> Dict[str, dict]:
        """Per-replica state for the router's aggregated /health/detail."""
        out = {}
        for rid, r in self.replicas.items():
            out[rid] = {
                "healthy": r.healthy,
                "role": r.role,
                "suspect": r.suspect,
                "canary_digest": r.last_canary_digest,
                "canary_age_s": (
                    round(time.monotonic() - r.last_canary_ts, 3)
                    if r.last_canary_ts is not None else None),
                "predicted_load_tokens": r.predicted_load,
                "inflight": r.inflight,
                "consecutive_failures": r.consecutive_failures,
                "last_health_age_s": (
                    round(time.monotonic() - r.last_health_ts, 3)
                    if r.last_health_ts is not None else None),
                "health": r.last_health,
            }
        return out
