"""Multi-replica router HTTP front end (aiohttp).

Speaks the demo api_server's `/generate` protocol on the front side and
streams through to a chosen replica on the back side:

    GET  /health             200 when ≥1 replica is healthy, else 503
    POST /generate           routed completion; same body as api_server.
                             Honors/echoes X-Request-Id (the distributed
                             trace id, propagated to the replica)
    GET  /metrics            Prometheus scrape (intellillm_router_* +
                             any in-process replica families)
    GET  /health/detail      aggregated: router decision counters,
                             policy state, per-replica health/load
                             snapshots, trace/hop summary, fleet alert
                             state; 503 when no healthy replica
    GET  /debug/alerts       the router's own alert rules PLUS a fleet
                             block aggregating each replica's alert
                             summary (from its polled /health/detail)
    GET  /debug/history      router-process metrics history (same
                             handler as the API servers)
    GET  /debug/trace        recently-completed trace ids + the
                             router's own span traces
    GET  /debug/trace/{id}   the STITCHED fleet trace: router spans
                             merged with every attempted replica's
                             flight-recorder events into one causally-
                             ordered timeline with per-hop latency
                             attribution (router/trace.py)
    GET  /debug/explain/{id} the STITCHED root-cause explain: every
                             attempted replica's /debug/explain payload
                             (scheduler decision decomposition,
                             obs/decisions.py) under the router's
                             per-hop attribution, with a fleet verdict

Failover: a `ReplicaFailure` mid-request marks the replica unhealthy,
drops its affinity placements, and re-routes the request once to another
replica (excluding the failed one). Because `/generate` stream chunks
carry CUMULATIVE text, a client that already received chunks from the
failed replica just keeps receiving (superset) chunks from the new one.
Attempt k runs under the sub-request id `{trace_id}#f{k}` so both
replicas of a failover keep their own sealed trace.

Run: python -m intellillm_tpu.router.server --replica-urls ... | \
         --launch-replicas N [engine args passed through to replicas]
See docs/routing.md and docs/observability.md ("Distributed tracing").
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time
from collections import OrderedDict, deque
from typing import AsyncIterator, Dict, List, Optional, Tuple

from aiohttp import web

from intellillm_tpu.affinity import prompt_affinity_key
from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs.flight_recorder import FlightRecorder
from intellillm_tpu.obs.slo import _percentile, observe_hop_seconds
from intellillm_tpu.obs.trace_export import (get_trace_sink,
                                             sanitize_request_id)
from intellillm_tpu.router.metrics import DECISIONS, get_router_metrics
from intellillm_tpu.router.policy import (NoReplicaAvailable, RouterConfig,
                                          RoutingPolicy)
from intellillm_tpu.router.replica import (Replica, ReplicaFailure,
                                           ReplicaManager,
                                           launch_http_replica)
from intellillm_tpu.router.trace import (TraceBook, attempt_request_id,
                                         attribute_hops, stitch_trace)
from intellillm_tpu.utils import random_uuid

logger = init_logger(__name__)

TIMEOUT_KEEP_ALIVE = 5

REPLICA_ROLES = ("mixed", "prefill", "decode")


class _KVStore:
    """Router-side fleet KV registry for disaggregated serving: maps the
    router's content-addressed affinity key to the exported payload plus
    which replicas already hold the prefix. Small LRU — entries are
    whole KV slabs for shared prompt prefixes (system prompts), not a
    general response cache."""

    def __init__(self, max_entries: int = 32) -> None:
        self.max_entries = max_entries
        self._entries: "OrderedDict[int, dict]" = OrderedDict()
        self.evictions = 0

    def get(self, key: int) -> Optional[dict]:
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        return entry

    def put(self, key: int, payload: bytes, source: str) -> dict:
        entry = {
            "payload": payload,
            "source": source,
            # Replica-token-space prefix position, learned from the
            # first successful import (the router may be tokenizer-less
            # and cannot compute it itself).
            "prefix_pos": None,
            "imported": {source},
        }
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
        return entry

    def drop_replica(self, replica_id: str) -> None:
        """A dead replica's imported prefixes died with it."""
        for entry in self._entries.values():
            entry["imported"].discard(replica_id)

    def summary(self) -> dict:
        return {
            "entries": len(self._entries),
            "payload_bytes": sum(len(e["payload"])
                                 for e in self._entries.values()),
            "evictions": self.evictions,
        }


class Router:
    """Ties the policy, the replica fleet, and the length predictor into
    one request path. No HTTP here — `build_router_app` wraps it."""

    def __init__(self, config: RouterConfig, manager: ReplicaManager,
                 predictor=None, tokenizer=None) -> None:
        self.config = config
        self.manager = manager
        self.predictor = predictor
        self.tokenizer = tokenizer
        self.policy = RoutingPolicy(config)
        # Python-side decision counters so the aggregated /health/detail
        # works without prometheus_client.
        self.decisions: Dict[str, int] = {d: 0 for d in DECISIONS}
        # The router's OWN span recorder — separate from the process-
        # global engine recorder so an in-process replica's events for
        # the same trace id don't collide with the router's spans.
        self.recorder = FlightRecorder(hop="router")
        self.tracebook = TraceBook()
        # Disaggregated prefill/decode: the fleet KV registry (engages
        # only while the fleet has both roles healthy — see
        # ReplicaManager.disagg_active).
        self.kv_store = _KVStore()
        # Rolling router-side hop timings for the /health/detail trace
        # summary (seconds; small fixed window).
        self._hop_window: deque = deque(maxlen=256)

    def add_replica(self, replica: Replica, healthy: bool = False) -> None:
        self.manager.add(replica, healthy=healthy)
        self.policy.add_replica(replica.replica_id)

    # --- request path -----------------------------------------------------

    def _token_ids(self, prompt: str) -> List[int]:
        if self.tokenizer is not None:
            return list(self.tokenizer.encode(prompt))
        # Tokenizer-less routers still need affinity + length signals;
        # UTF-8 bytes are a stable stand-in (keys just won't match a
        # tokenized pool's — affinity still works ROUTER-side because
        # equal prompts yield equal byte ids).
        return list(prompt.encode("utf-8"))

    def _predict_len(self, prompt: str, token_ids: List[int]) -> int:
        if self.predictor is None:
            return max(len(token_ids), 1)
        try:
            return int(self.predictor.predict(prompt, token_ids))
        except Exception:
            logger.exception("length predictor failed; using prompt length")
            return max(len(token_ids), 1)

    def _count_decision(self, decision: str) -> None:
        self.decisions[decision] = self.decisions.get(decision, 0) + 1
        m = get_router_metrics()
        if m is not None:
            m.counter_decisions.labels(decision=decision).inc()

    def _payload_lora_int_id(self, payload: dict) -> int:
        """The request's adapter id for affinity keying
        (docs/multitenancy.md): direct `lora_int_id`, or `tenant`
        resolved through the freshest polled replica /health/detail
        tenants block (the registry lives engine-side; the router only
        mirrors it). Unresolvable naming keys as the base model (id 0)
        — the replica rejects it with a 400 on arrival."""
        lora = payload.get("lora_int_id")
        if lora:
            try:
                return int(lora)
            except (TypeError, ValueError):
                return 0
        tenant = payload.get("tenant")
        if not tenant:
            return 0
        for replica in self.manager.replicas.values():
            block = (replica.last_health or {}).get("tenants") or {}
            for spec in block.get("tenants") or []:
                if spec.get("tenant_id") == tenant:
                    return int(spec.get("lora_int_id") or 0)
        return 0

    def _warm_replicas(self, lora_int_id: int,
                       loads: Dict[str, float]) -> Optional[set]:
        """Candidates whose last (non-stale) health poll reported the
        adapter resident in a device slot — the adapter-locality
        override RoutingPolicy.choose applies on affinity-map misses."""
        if not lora_int_id:
            return None
        stale_after_s = 3.0 * self.manager.health_interval_s
        now = time.monotonic()
        warm: set = set()
        for rid in loads:
            replica = self.manager.get(rid)
            if (replica is None or replica.last_health_ts is None
                    or now - replica.last_health_ts > stale_after_s):
                continue
            block = (replica.last_health or {}).get("tenants") or {}
            if lora_int_id in (block.get("active_adapters") or []):
                warm.add(rid)
        return warm or None

    async def stream_request(self, payload: dict,
                             trace_id: Optional[str] = None
                             ) -> AsyncIterator[dict]:
        """Route `payload` and yield its (cumulative-text) chunks,
        failing over up to `max_retries` times. `trace_id` is the
        distributed trace id (client-supplied X-Request-Id or router-
        minted); every routing span lands in the router's recorder
        under it, and attempt k reaches its replica as the sub-request
        id `{trace_id}#f{k}`."""
        prompt = payload.get("prompt", "")
        token_ids = self._token_ids(prompt)
        # Adapter id is part of the affinity key — same (tokens, adapter)
        # keying as PrefixPool / the KV-export affinity_key, so "same
        # key" still means "same reusable prefix KV" under multi-LoRA
        # (a prefix computed under adapter A must not attract adapter
        # B's traffic).
        lora_int_id = self._payload_lora_int_id(payload)
        key = prompt_affinity_key(token_ids, self.config.block_size,
                                  self.config.affinity_blocks,
                                  lora_int_id=lora_int_id)
        predicted_len = self._predict_len(prompt, token_ids)
        trace_id = trace_id or random_uuid()
        self.recorder.record(trace_id, "received",
                             detail=f"prompt_tokens={len(token_ids)}")

        excluded: set = set()
        attempts = self.config.max_retries + 1
        last_error: Optional[Exception] = None
        first_chunk_seen = False
        for attempt in range(attempts):
            # Disaggregated path (first attempt only, prompt longer than
            # one block): route the decode leg among decode-role
            # replicas, after a prefill-role replica prefilled the
            # prefix and its KV moved over. Failover attempts replay the
            # FULL request on any healthy replica regardless of role —
            # prefill-role engines do not cap generation, so a replay
            # that lands on one still produces complete output.
            disagg = (attempt == 0 and "prefix_pos" not in payload
                      and len(token_ids) > self.config.block_size
                      and self.manager.disagg_active())
            loads = self.manager.healthy_loads(
                exclude=excluded, role="decode" if disagg else None)
            if disagg and not loads:
                disagg = False
                loads = self.manager.healthy_loads(exclude=excluded)
            try:
                replica_id, decision = self.policy.choose(
                    key, loads,
                    warm_replicas=self._warm_replicas(lora_int_id, loads))
            except NoReplicaAvailable:
                self.recorder.record(trace_id, "aborted",
                                     detail="no_replica_available")
                raise
            prefix_pos: Optional[int] = None
            if disagg:
                # The handoff (prefill leg + KV transfer) runs BEFORE
                # this attempt's route_decision span so decision→routed
                # pairs zip in order during hop attribution; a soft
                # failure returns None and the decode replica recomputes
                # the prefill locally (correctness unaffected).
                prefix_pos = await self._kv_handoff(
                    trace_id, key, prompt, replica_id, excluded,
                    predicted_len)
            if attempt > 0:
                decision = "failover"
            self._count_decision(decision)
            self.recorder.record(trace_id, "route_decision",
                                 detail=f"{decision}->{replica_id}")
            request_id = attempt_request_id(trace_id, attempt)
            replica = self.manager.get(replica_id)
            # Scale by the replica's reported calibration factor (from
            # its /health/detail predictor block) so the fleet load model
            # charges corrected lengths. The SAME scaled value must flow
            # through on_route / generate / on_complete — the accounting
            # is symmetric, and the factor may move between calls.
            scaled_len = max(
                int(round(predicted_len * replica.calibration_factor)), 1)
            self.manager.on_route(replica_id, scaled_len)
            self.tracebook.note_attempt(trace_id, attempt, replica_id,
                                        request_id, decision)
            self.recorder.record(
                trace_id, "routed",
                detail=f"attempt={attempt} replica={replica_id} "
                       f"request_id={request_id}")
            out_payload = payload
            if prefix_pos is not None:
                out_payload = {**payload, "prefix_pos": prefix_pos}
            try:
                async for chunk in replica.generate(
                        out_payload, predicted_len=scaled_len,
                        request_id=request_id):
                    if not first_chunk_seen:
                        first_chunk_seen = True
                        self.recorder.record(trace_id, "first_chunk",
                                             detail=f"replica={replica_id}")
                    yield chunk
                self.manager.on_complete(replica_id, scaled_len)
                self.recorder.record(trace_id, "finished",
                                     detail=f"replica={replica_id}")
                self._finish_trace(trace_id, failed_over=attempt > 0)
                return
            except ReplicaFailure as e:
                last_error = e
                logger.warning("replica %s failed serving request: %s",
                               replica_id, e)
                self.recorder.record(
                    trace_id, "replica_failed",
                    detail=f"replica={replica_id}: {e}"[:200])
                self.manager.on_complete(replica_id, scaled_len)
                self.manager.mark_failed(replica_id)
                # Its cached prefixes are gone with it: let its keys
                # re-seed instead of pinning to a corpse. Same for its
                # imported KV — the registry forgets it held anything.
                self.policy.affinity.drop_replica(replica_id)
                self.kv_store.drop_replica(replica_id)
                m = get_router_metrics()
                if m is not None:
                    m.counter_failovers.labels(replica=replica_id).inc()
                excluded.add(replica_id)
        self.recorder.record(trace_id, "aborted",
                             detail="retries_exhausted")
        self._finish_trace(trace_id, failed_over=True, failed=True)
        raise last_error if last_error is not None else NoReplicaAvailable(
            "request exhausted retries")

    def _finish_trace(self, trace_id: str, failed_over: bool,
                      failed: bool = False) -> None:
        """Terminal bookkeeping for one routed trace: router-side hop
        timings (router_queue / routing) into the hop histogram + the
        rolling window, and the span trace into the durable sink
        (failovers/failures are always kept — tail sampling)."""
        events = self.recorder.get_trace(trace_id)
        if not events:
            return
        received = decision0 = None
        routing = 0.0
        pending_decision = None
        terminal = events[-1]["ts"]
        for ev in events:
            if ev["event"] == "received" and received is None:
                received = ev["ts"]
            elif ev["event"] == "route_decision":
                pending_decision = ev["ts"]
                if decision0 is None:
                    decision0 = ev["ts"]
            elif ev["event"] == "routed" and pending_decision is not None:
                routing += max(ev["ts"] - pending_decision, 0.0)
                pending_decision = None
        if received is None:
            return
        hops = {
            "router_queue": (max(decision0 - received, 0.0)
                             if decision0 is not None else 0.0),
            "routing": routing,
        }
        observe_hop_seconds(hops)
        self._hop_window.append(
            {**hops, "e2e_s": max(terminal - received, 0.0)})
        rec = {
            "reason": ("error" if failed
                       else "rerouted" if failed_over else "finished"),
            "e2e_s": max(terminal - received, 0.0),
            "hops": hops,
        }
        get_trace_sink().maybe_export(trace_id, events, rec, hop="router")

    # --- disaggregated KV handoff ----------------------------------------

    async def _kv_handoff(self, trace_id: str, key: int, prompt: str,
                          decode_rid: str, excluded: set,
                          predicted_len: int) -> Optional[int]:
        """Ensure `decode_rid` holds the KV prefix for `prompt` before
        the decode leg routes to it. Registry outcomes:

        - local_hit: the decode replica already imported this prefix —
          no transfer, no prefill leg.
        - fleet_hit: another replica prefilled it earlier — import the
          registered payload (one kv_transfer span).
        - miss: run the prefill leg (max_tokens=1) on the least-loaded
          prefill-role replica, export (one kv_transfer span), register,
          then import (a second span).

        Returns the replica-token-space prefix_pos for the decode
        request, or None when the handoff soft-failed — the decode
        replica then recomputes the prefill locally, which its scheduler
        warns about and counts (prefill_recompute_count)."""
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        stats = get_kv_transfer_stats()
        entry = self.kv_store.get(key)
        if (entry is not None and decode_rid in entry["imported"]
                and entry["prefix_pos"] is not None):
            stats.record_cache("local_hit")
            return entry["prefix_pos"]
        if entry is None:
            stats.record_cache("miss")
            exported = await self._prefill_and_export(trace_id, key,
                                                      prompt,
                                                      excluded,
                                                      predicted_len)
            if exported is None:
                return None
            payload, source_rid = exported
            entry = self.kv_store.put(key, payload, source_rid)
        else:
            stats.record_cache("fleet_hit")

        token = stats.transfer_started()
        self.recorder.record(
            trace_id, "kv_transfer_start",
            detail=f"import key={key:#018x} -> {decode_rid} "
                   f"bytes={len(entry['payload'])}")
        result = None
        try:
            result = await self.manager.get(decode_rid).import_kv(
                entry["payload"])
            detail = (f"imported={result['imported']} "
                      f"blocks={result['num_blocks']}")
        except ReplicaFailure as e:
            logger.warning("kv import into %s failed: %s", decode_rid, e)
            detail = f"import failed: {e}"[:200]
        finally:
            stats.transfer_finished(token)
            self.recorder.record(trace_id, "kv_transfer_done",
                                 detail=detail)
        if result is None:
            return None
        entry["imported"].add(decode_rid)
        prefix_pos = result.get("prefix_pos")
        if prefix_pos:
            entry["prefix_pos"] = int(prefix_pos)
        return entry["prefix_pos"]

    async def _prefill_and_export(
            self, trace_id: str, key: int, prompt: str, excluded: set,
            predicted_len: int) -> Optional[Tuple[bytes, str]]:
        """The prefill leg of a registry miss: run `prompt` with
        max_tokens=1 on the least-loaded healthy prefill-role replica
        (under the sub-request id `{trace_id}#p0` so it gets its own
        sealed replica trace), then export the prefix KV. Returns
        (payload, replica_id) or None on soft failure."""
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        stats = get_kv_transfer_stats()
        loads = self.manager.healthy_loads(exclude=excluded,
                                           role="prefill")
        if not loads:
            return None
        prefill_rid = min(loads, key=loads.get)
        replica = self.manager.get(prefill_rid)
        sub_id = f"{trace_id}#p0"
        # The load charge is the prompt length scaled like any other
        # route: prefill cost tracks prompt tokens, and the charge is
        # released as soon as the leg completes.
        charge = max(int(round(predicted_len *
                               replica.calibration_factor)), 1)
        self._count_decision("disagg_prefill")
        self.recorder.record(trace_id, "route_decision",
                             detail=f"disagg_prefill->{prefill_rid}")
        self.manager.on_route(prefill_rid, charge)
        self.tracebook.note_attempt(trace_id, 0, prefill_rid, sub_id,
                                    "disagg_prefill")
        self.recorder.record(
            trace_id, "routed",
            detail=f"attempt=prefill replica={prefill_rid} "
                   f"request_id={sub_id}")
        try:
            async for _ in replica.generate(
                    {"prompt": prompt, "max_tokens": 1},
                    predicted_len=charge, request_id=sub_id):
                pass
        except ReplicaFailure as e:
            # Soft failure: the decode replica will recompute locally.
            # The health poller decides whether the replica is dead.
            logger.warning("disagg prefill leg failed on %s: %s",
                           prefill_rid, e)
            return None
        finally:
            self.manager.on_complete(prefill_rid, charge)

        token = stats.transfer_started()
        self.recorder.record(
            trace_id, "kv_transfer_start",
            detail=f"export key={key:#018x} from={prefill_rid}")
        payload = None
        try:
            payload = await replica.export_kv(prompt)
            detail = f"export bytes={len(payload)}"
        except ReplicaFailure as e:
            logger.warning("kv export from %s failed: %s", prefill_rid, e)
            detail = f"export failed: {e}"[:200]
        finally:
            stats.transfer_finished(token)
            self.recorder.record(trace_id, "kv_transfer_done",
                                 detail=detail)
        if payload is None:
            return None
        return payload, prefill_rid

    # --- observability ----------------------------------------------------

    async def stitched_trace(self, trace_id: str) -> Optional[dict]:
        """Fetch + stitch the fleet trace for `trace_id`: the router's
        spans merged with each attempted replica's flight-recorder
        events (router/trace.py). None when the router never saw it."""
        router_events = self.recorder.get_trace(trace_id)
        attempts = self.tracebook.attempts(trace_id) or []
        for att in attempts:
            replica = self.manager.replicas.get(att["replica_id"])
            att["events"] = (await replica.fetch_trace(att["request_id"])
                             if replica is not None else None)
        return stitch_trace(trace_id, router_events, attempts)

    async def stitched_explain(self, trace_id: str) -> Optional[dict]:
        """Fleet root-cause explain: each attempted replica's
        /debug/explain payload (scheduler decision decomposition,
        obs/decisions.py) stitched under the router's hop attribution,
        with a fleet-level verdict. None when the router never saw the
        trace."""
        router_events = self.recorder.get_trace(trace_id)
        attempts = self.tracebook.attempts(trace_id) or []
        if not router_events:
            return None
        hops = []
        verdicts = []
        for att in attempts:
            replica = self.manager.replicas.get(att["replica_id"])
            explain = (await replica.fetch_explain(att["request_id"])
                       if replica is not None else None)
            hops.append({
                "attempt": att.get("attempt"),
                "replica_id": att["replica_id"],
                "request_id": att["request_id"],
                "explain": explain,
            })
            if explain and explain.get("verdict"):
                verdicts.append(
                    f"{att['replica_id']}: {explain['verdict']}")
            att["events"] = (explain or {}).get("trace")
        failovers = max(len(attempts) - 1, 0)
        if failovers:
            verdicts.insert(0, f"rerouted {failovers}x by the router")
        return {
            "trace_id": trace_id,
            "attribution": attribute_hops(router_events, attempts),
            "attempts": hops,
            "verdict": ("; ".join(verdicts) if verdicts
                        else "no contention observed on any hop"),
        }

    def _trace_summary(self) -> dict:
        """Router-side hop timings + trace bookkeeping for
        /health/detail."""
        window = list(self._hop_window)
        out: Dict[str, object] = {
            "window": len(window),
            "live_traces": len(self.recorder.live_request_ids()),
            "recent_trace_ids": self.tracebook.recent_trace_ids(limit=8),
            "export": {
                "enabled": get_trace_sink().enabled,
                "path": get_trace_sink().path,
            },
        }
        for hop in ("router_queue", "routing", "e2e_s"):
            vals = sorted(r[hop] * 1e3 for r in window if hop in r)
            key = "e2e_ms" if hop == "e2e_s" else f"{hop}_ms"
            out[key] = ({
                "p50": round(_percentile(vals, 50), 3),
                "p99": round(_percentile(vals, 99), 3),
            } if vals else None)
        return out

    def fleet_alerts(self) -> dict:
        """Fleet-wide alert state: the router process's own rules plus
        each replica's alert summary as captured by the health poller
        (replica /health/detail bodies carry an "alerts" block). This is
        what lets serve_bench --scenario fleet assert "no alerts fired"
        without scraping every replica itself."""
        from intellillm_tpu.obs import get_alert_manager
        own = get_alert_manager().summary()
        per_replica: Dict[str, Optional[dict]] = {}
        firing: set = set()
        pending: set = set()
        page_firing = bool(own.get("page_firing"))
        firing.update(own.get("firing") or [])
        pending.update(own.get("pending") or [])
        # A replica's captured summary is only fleet state while the
        # replica is reachable: an unhealthy replica (or one whose poll
        # timestamp has gone stale) would otherwise pin its LAST
        # summary — firing or clean — into the fleet view forever.
        stale_after_s = 3.0 * self.manager.health_interval_s
        now = time.monotonic()
        for rid, replica in self.manager.replicas.items():
            summary = (replica.last_health or {}).get("alerts")
            stale = (not replica.healthy
                     or (replica.last_health_ts is not None
                         and now - replica.last_health_ts > stale_after_s))
            if stale:
                per_replica[rid] = ({**summary, "stale": True}
                                    if summary else None)
                continue
            per_replica[rid] = summary
            if not summary:
                continue
            firing.update(summary.get("firing") or [])
            pending.update(summary.get("pending") or [])
            page_firing = page_firing or bool(summary.get("page_firing"))
        # Divergence-canary verdict (router/replica.py run_canary): a
        # suspect replica is an output-integrity incident, which is
        # page-severity by the same logic as numerics_anomaly — the
        # fleet is serving two different answers to the same prompt.
        from intellillm_tpu.obs import get_canary_ledger
        canary = get_canary_ledger().snapshot()
        if canary.get("suspects"):
            firing.add("canary_divergence")
            page_firing = True
        return {
            "router": own,
            "replicas": per_replica,
            "canary": canary,
            "fleet": {
                "rules_firing": sorted(firing),
                "rules_pending": sorted(pending),
                "firing_total": len(firing),
                "page_firing": page_firing,
                "clean": not firing and not pending,
            },
        }

    async def fleet_workload(self, limit: int = 1024) -> dict:
        """Fleet-merged workload: every replica's captured records
        (Replica.fetch_workload — must-not-raise, so a dead replica
        contributes nothing), attempt-deduped by base trace id. Failover
        retries reach replicas as `{id}#f{k}` and disagg prefill legs as
        `{id}#p0`; the merge keeps one record per logical request, and
        for duplicates the finished attempt beats the rerouted/aborted
        one — the stream a replay should re-issue."""
        from intellillm_tpu.obs.workload import merge_workloads
        per_replica: Dict[str, Optional[int]] = {}
        shards = []
        for rid, replica in self.manager.replicas.items():
            records = await replica.fetch_workload(limit=limit)
            per_replica[rid] = len(records) if records is not None else None
            if records:
                shards.append(records)
        merged, deduped = merge_workloads(shards)
        if limit >= 0:
            merged = merged[-limit:]
        return {
            "fleet_merged": True,
            "replicas": per_replica,
            "attempts_deduped": deduped,
            "count": len(merged),
            "records": merged,
        }

    def snapshot(self) -> dict:
        healthy = [rid for rid, r in self.manager.replicas.items()
                   if r.healthy]
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        return {
            "replicas": self.manager.snapshot(),
            "healthy_replicas": sorted(healthy),
            "decisions": dict(self.decisions),
            "affinity_entries": len(self.policy.affinity),
            "tracing": self._trace_summary(),
            "alerts": self.fleet_alerts(),
            "kv_transfer": {
                "disagg_active": self.manager.disagg_active(),
                "registry": self.kv_store.summary(),
                **get_kv_transfer_stats().summary(),
            },
            "config": {
                "block_size": self.config.block_size,
                "affinity_blocks": self.config.affinity_blocks,
                "load_balance_slack": self.config.load_balance_slack,
                "max_retries": self.config.max_retries,
            },
        }

    async def stop(self) -> None:
        await self.manager.stop()


def build_router_app(router: Router) -> web.Application:
    from intellillm_tpu.entrypoints.debug_routes import (debug_history,
                                                         metrics)

    async def health(request: web.Request) -> web.Response:
        ok = any(r.healthy for r in router.manager.replicas.values())
        return web.Response(status=200 if ok else 503)

    async def generate(request: web.Request) -> web.StreamResponse:
        request_dict = await request.json()
        stream = bool(request_dict.pop("stream", False))
        # The distributed trace id: honor a (validated) client
        # X-Request-Id so client-side correlation works, else mint one;
        # echo it either way.
        trace_id = (sanitize_request_id(request.headers.get("X-Request-Id"))
                    or random_uuid())
        try:
            chunk_iter = router.stream_request(request_dict,
                                               trace_id=trace_id)
            if stream:
                response = web.StreamResponse(
                    headers={"Content-Type": "application/x-ndjson",
                             "X-Request-Id": trace_id})
                prepared = False
                async for chunk in chunk_iter:
                    if not prepared:
                        await response.prepare(request)
                        prepared = True
                    await response.write(
                        (json.dumps(chunk) + "\n").encode())
                if not prepared:
                    await response.prepare(request)
                await response.write_eof()
                return response
            final_chunk = None
            async for chunk in chunk_iter:
                final_chunk = chunk
            assert final_chunk is not None
            return web.json_response(final_chunk,
                                     headers={"X-Request-Id": trace_id})
        except NoReplicaAvailable as e:
            return web.json_response({"error": str(e)}, status=503,
                                     headers={"X-Request-Id": trace_id})
        except ReplicaFailure as e:
            # Retries exhausted. A prepared stream can't change status;
            # aiohttp just closes it, which clients see as truncation.
            return web.json_response({"error": str(e)}, status=502,
                                     headers={"X-Request-Id": trace_id})

    async def health_detail(request: web.Request) -> web.Response:
        body = {"router": router.snapshot()}
        ok = any(r.healthy for r in router.manager.replicas.values())
        body["status"] = "ok" if ok else "no_healthy_replica"
        return web.json_response(body, status=200 if ok else 503)

    async def debug_trace_list(request: web.Request) -> web.Response:
        from intellillm_tpu.entrypoints.debug_routes import parse_paging
        try:
            limit, offset = parse_paging(request)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({
            "live_trace_ids": router.recorder.live_request_ids(),
            "recent_trace_ids": router.tracebook.recent_trace_ids(limit),
            "recent_finished": router.recorder.recent_finished(
                limit, offset=offset),
        })

    async def debug_workload_fleet(request: web.Request) -> web.Response:
        """Fleet-merged, attempt-deduped workload across every replica
        (the per-process view lives on each replica's own
        /debug/workload). ?format=iwl emits the merged stream as one
        IWL1 document — the capture side of `serve_bench --scenario
        replay`."""
        from intellillm_tpu.entrypoints.debug_routes import parse_paging
        try:
            limit, _ = parse_paging(request, default_limit=1024)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        body = await router.fleet_workload(limit=limit)
        if request.query.get("format", "json") == "iwl":
            from intellillm_tpu.obs.workload import dump_iwl
            return web.Response(
                text=dump_iwl(body["records"], source="fleet"),
                content_type="text/plain")
        return web.json_response(body)

    async def debug_alerts(request: web.Request) -> web.Response:
        """The engine handler's body plus the fleet aggregation."""
        from intellillm_tpu.obs import get_alert_manager
        body = get_alert_manager().snapshot()
        fleet = router.fleet_alerts()
        body["fleet"] = fleet["fleet"]
        body["replicas"] = fleet["replicas"]
        return web.json_response(body)

    async def debug_numerics_fleet(request: web.Request) -> web.Response:
        """Fleet numerics view: the router's own (usually idle) sentinel
        + KV-audit snapshot, the divergence-canary ledger, and each
        replica's compact numerics block as captured by the health
        poller (full per-replica detail lives on each replica's own
        /debug/numerics)."""
        from intellillm_tpu.obs import (get_canary_ledger,
                                        numerics_debug_snapshot)
        body = numerics_debug_snapshot()
        body["canary"] = get_canary_ledger().snapshot()
        body["replicas"] = {
            rid: (r.last_health or {}).get("numerics")
            for rid, r in router.manager.replicas.items()}
        return web.json_response(body)

    async def debug_trace_stitched(request: web.Request) -> web.Response:
        trace_id = request.match_info["trace_id"]
        stitched = await router.stitched_trace(trace_id)
        if stitched is None:
            return web.json_response(
                {"error": f"no trace for trace_id={trace_id} "
                 "(never routed here, or evicted from the ring)"},
                status=404)
        return web.json_response(stitched)

    async def debug_explain_stitched(request: web.Request) -> web.Response:
        trace_id = request.match_info["trace_id"]
        explained = await router.stitched_explain(trace_id)
        if explained is None:
            return web.json_response(
                {"error": f"no trace for trace_id={trace_id} "
                 "(never routed here, or evicted from the ring)"},
                status=404)
        return web.json_response(explained)

    app = web.Application()
    app.router.add_get("/health", health)
    app.router.add_post("/generate", generate)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/health/detail", health_detail)
    app.router.add_get("/debug/trace", debug_trace_list)
    app.router.add_get("/debug/workload", debug_workload_fleet)
    app.router.add_get("/debug/trace/{trace_id}", debug_trace_stitched)
    app.router.add_get("/debug/explain/{trace_id}", debug_explain_stitched)
    app.router.add_get("/debug/history", debug_history)
    app.router.add_get("/debug/alerts", debug_alerts)
    app.router.add_get("/debug/numerics", debug_numerics_fleet)

    async def _start(app: web.Application) -> None:
        router.manager.start_polling()
        # Metrics history + alerts in the ROUTER process too: the
        # failover counter feeds the router_failover rule; attach order
        # (listener first) means rules evaluate on the first sample.
        from intellillm_tpu.obs import get_alert_manager, get_metrics_history
        history = get_metrics_history()
        history.register_collector(lambda: {
            "intellillm_router_failovers_total":
                float(router.decisions.get("failover", 0))})
        get_alert_manager().attach(history)
        history.attach()

    async def _cleanup(app: web.Application) -> None:
        await router.stop()

    app.on_startup.append(_start)
    app.on_cleanup.append(_cleanup)
    return app


def make_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="intellillm-tpu multi-replica router")
    parser.add_argument("--host", type=str, default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8100)
    parser.add_argument("--replica-urls", type=str, default=None,
                        help="comma-separated base URLs of already-running "
                        "engine servers to attach")
    parser.add_argument("--launch-replicas", type=int, default=0,
                        help="launch N api_server replica subprocesses; "
                        "unrecognized args are passed through to them")
    parser.add_argument("--replica-base-port", type=int, default=8200,
                        help="first port for --launch-replicas (replica i "
                        "listens on base+i)")
    parser.add_argument("--tokenizer", type=str, default=None,
                        help="tokenizer for affinity keys + length "
                        "prediction (omit for byte-level fallback)")
    parser.add_argument("--predictor-path", type=str, default=None,
                        help="trained LengthPredictor checkpoint dir; "
                        "missing/invalid falls back to the prompt-length "
                        "heuristic")
    parser.add_argument("--block-size", type=int, default=16,
                        help="KV block size of the replicas (affinity keys "
                        "are block-aligned)")
    parser.add_argument("--affinity-blocks", type=int, default=4,
                        help="leading prompt blocks hashed into the "
                        "affinity key")
    parser.add_argument("--load-balance-slack", type=float, default=256.0,
                        help="predicted-token imbalance tolerated before "
                        "affinity is overridden")
    parser.add_argument("--health-interval", type=float, default=2.0,
                        help="replica /health/detail poll period, seconds")
    parser.add_argument("--canary-every", type=int, default=None,
                        help="run the fleet divergence canary every N "
                        "health polls (0 disables; default: "
                        "INTELLILLM_CANARY_EVERY, off)")
    parser.add_argument("--canary-prompt", type=str, default=None,
                        help="deterministic greedy prompt for the "
                        "divergence canary (default: "
                        "INTELLILLM_CANARY_PROMPT)")
    parser.add_argument("--canary-drain", action="store_true",
                        help="drain a canary-divergent replica from "
                        "routing until it re-converges (default: "
                        "INTELLILLM_CANARY_DRAIN)")
    parser.add_argument("--max-retries", type=int, default=1,
                        help="re-routes after a replica failure")
    parser.add_argument("--replica-roles", type=str, default=None,
                        help="comma-separated disaggregated roles "
                        "(mixed|prefill|decode), aligned with "
                        "--replica-urls order then launched replicas; "
                        "launched replicas get --replica-role appended "
                        "to their engine args (docs/routing.md)")
    return parser


def build_router_from_args(args, engine_argv: List[str]) -> Router:
    tokenizer = None
    if args.tokenizer:
        from intellillm_tpu.transformers_utils.tokenizer import get_tokenizer
        tokenizer = get_tokenizer(args.tokenizer)

    from intellillm_tpu.research.predictor import load_predictor
    predictor = load_predictor(args.predictor_path, tokenizer)

    config = RouterConfig(
        block_size=args.block_size,
        affinity_blocks=args.affinity_blocks,
        load_balance_slack=args.load_balance_slack,
        max_retries=args.max_retries,
        health_interval_s=args.health_interval,
    )
    manager = ReplicaManager(
        health_interval_s=args.health_interval,
        canary_every=getattr(args, "canary_every", None),
        canary_prompt=getattr(args, "canary_prompt", None),
        canary_drain=(True if getattr(args, "canary_drain", False)
                      else None))
    router = Router(config, manager, predictor=predictor,
                    tokenizer=tokenizer)

    roles = [r.strip()
             for r in (getattr(args, "replica_roles", None) or "").split(",")
             if r.strip()]
    for role in roles:
        if role not in REPLICA_ROLES:
            raise SystemExit(f"--replica-roles: unknown role {role!r} "
                             f"(choose from {', '.join(REPLICA_ROLES)})")

    def role_for(index: int) -> str:
        return roles[index] if index < len(roles) else "mixed"

    urls = [u.strip() for u in (args.replica_urls or "").split(",")
            if u.strip()]
    for i, url in enumerate(urls):
        from intellillm_tpu.router.replica import HTTPReplica
        router.add_replica(HTTPReplica(f"replica-{i}", url,
                                       role=role_for(i)))
    for i in range(args.launch_replicas):
        replica = launch_http_replica(
            f"launched-{i}", args.replica_base_port + i, engine_argv,
            role=role_for(len(urls) + i), child_index=i)
        router.add_replica(replica)
    if not router.manager.replicas:
        raise SystemExit(
            "router needs replicas: pass --replica-urls or "
            "--launch-replicas")
    return router


def main() -> None:
    parser = make_arg_parser()
    # Unknown args are engine flags for --launch-replicas subprocesses.
    args, engine_argv = parser.parse_known_args()
    if engine_argv and not args.launch_replicas:
        parser.error(f"unrecognized arguments: {' '.join(engine_argv)} "
                     "(only valid with --launch-replicas)")
    router = build_router_from_args(args, engine_argv)
    web.run_app(build_router_app(router), host=args.host, port=args.port,
                keepalive_timeout=TIMEOUT_KEEP_ALIVE)


if __name__ == "__main__":
    main()
