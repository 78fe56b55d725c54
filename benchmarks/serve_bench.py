"""One-command serving benchmark: boot the OpenAI server on a dummy
checkpoint, warm it up, then sweep request rates with
`benchmark_serving.py`'s Poisson load generator.

This is the north-star harness (BASELINE.json: Llama-2-7B via
entrypoints/openai, aggregate output tok/s + p50 TTFT measured at the
HTTP boundary — reference `.buildkite/run-benchmarks.sh:25-30`). Example:

    python benchmarks/serve_bench.py --size 7b --quantization int8 \
        --kv-cache-dtype fp8_e5m2 --num-device-blocks 1600 \
        --max-num-seqs 96 --rates 2,4,8,inf

Prints one JSON line per rate plus a `serve_bench_summary` line.
"""
from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import math
import os
import random
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.benchmark_serving import (build_requests,  # noqa: E402
                                          compute_metrics, run_benchmark)
from benchmarks.common import save_dummy_checkpoint  # noqa: E402
from intellillm_tpu.utils import child_process_env  # noqa: E402


def launch_server(model_dir: str, args,
                  scheduling_policy: str = None) -> subprocess.Popen:
    cmd = [
        sys.executable, "-m",
        "intellillm_tpu.entrypoints.openai.api_server",
        "--model", model_dir,
        "--load-format", "dummy",
        "--served-model-name", f"dummy-{args.size}",
        "--port", str(args.port),
        "--max-model-len", str(args.max_model_len),
        "--max-num-seqs", str(args.max_num_seqs),
        "--num-decode-steps", str(args.num_decode_steps),
        "--block-size", str(args.block_size),
        "--kv-cache-dtype", args.kv_cache_dtype,
        "--max-paddings", "4096",
        "--swap-space", "0.05",
        "--disable-log-requests",
    ]
    if args.quantization:
        cmd += ["--quantization", args.quantization]
    if args.num_device_blocks:
        cmd += ["--num-device-blocks-override", str(args.num_device_blocks)]
    if args.disable_chunked_prefill:
        cmd += ["--disable-chunked-prefill"]
    if args.max_num_batched_tokens:
        cmd += ["--max-num-batched-tokens",
                str(args.max_num_batched_tokens)]
    if scheduling_policy:
        cmd += ["--scheduling-policy", scheduling_policy]
    if getattr(args, "sjf_starvation_s", None) is not None:
        cmd += ["--sjf-starvation-s", str(args.sjf_starvation_s)]
    if getattr(args, "predictor_path", None):
        cmd += ["--predictor-path", args.predictor_path]
    if getattr(args, "_spec_model_dir", None):
        cmd += ["--speculative-model", args._spec_model_dir,
                "--num-speculative-tokens",
                str(args.num_speculative_tokens)]
        if args.spec_k_min is not None:
            cmd += ["--spec-k-min", str(args.spec_k_min)]
        if args.spec_k_max is not None:
            cmd += ["--spec-k-max", str(args.spec_k_max)]
    env = child_process_env()
    env.setdefault("HF_HUB_OFFLINE", "1")
    # Server logs go to a file, not an undrained pipe (a full pipe buffer
    # would block the server's logging mid-benchmark).
    log = open(args.server_log, "wb")
    return subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT)


def snapshot_observability(base: str) -> dict:
    """Scrape /metrics and distill the step-phase histograms and XLA
    compile counters into a compact dict for the summary JSON, so BENCH
    files carry latency attribution next to throughput."""
    try:
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode(errors="replace")
    except Exception as e:
        return {"error": f"metrics scrape failed: {e}"}

    phase_sum: dict = {}
    phase_count: dict = {}
    out = {"step_phase_seconds": phase_sum, "step_phase_samples": phase_count,
           "xla_compiles": {}, "xla_cache_hits": {},
           "xla_compile_time_seconds": {}, "kernel_dispatch": {}}
    simple = {"intellillm_xla_compiles_total": ("xla_compiles", "program"),
              "intellillm_xla_cache_hits_total":
                  ("xla_cache_hits", "program"),
              "intellillm_kernel_dispatch_total":
                  ("kernel_dispatch", "path")}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        try:
            name_labels, value = line.rsplit(None, 1)
            value = float(value)
            name, _, labels = name_labels.partition("{")
            labels = dict(
                kv.split("=", 1) for kv in labels.rstrip("}").split(",")
                if "=" in kv) if labels else {}
            labels = {k: v.strip('"') for k, v in labels.items()}
        except ValueError:
            continue
        if name == "intellillm_step_phase_seconds_sum":
            phase_sum[labels.get("phase", "?")] = value
        elif name == "intellillm_step_phase_seconds_count":
            phase_count[labels.get("phase", "?")] = value
        elif name == "intellillm_step_time_seconds_sum":
            out["step_time_seconds_sum"] = value
        elif name == "intellillm_step_time_seconds_count":
            out["step_count"] = value
        elif name == "intellillm_xla_compile_time_seconds_sum":
            out["xla_compile_time_seconds"][
                labels.get("program", "?")] = value
        elif name == "intellillm_live_executables":
            out["live_executables"] = value
        elif name in simple:
            key, label = simple[name]
            out[key][labels.get(label, "?")] = value
    return out


def snapshot_router_metrics(base: str) -> dict:
    """Distill the router's `intellillm_router_*` families into a compact
    dict: per-replica request counts / predicted load / health, decision
    and failover counters."""
    try:
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode(errors="replace")
    except Exception as e:
        return {"error": f"router metrics scrape failed: {e}"}

    out = {"requests_total": {}, "decisions": {}, "failovers": {},
           "predicted_load_tokens": {}, "replica_healthy": {},
           "queue_depth": {}}
    families = {
        "intellillm_router_requests_total": ("requests_total", "replica"),
        "intellillm_router_routing_decisions_total":
            ("decisions", "decision"),
        "intellillm_router_failovers_total": ("failovers", "replica"),
        "intellillm_router_predicted_load_tokens":
            ("predicted_load_tokens", "replica"),
        "intellillm_router_replica_healthy": ("replica_healthy", "replica"),
    }
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        try:
            name_labels, value = line.rsplit(None, 1)
            value = float(value)
            name, _, labels = name_labels.partition("{")
            labels = dict(
                kv.split("=", 1) for kv in labels.rstrip("}").split(",")
                if "=" in kv) if labels else {}
            labels = {k: v.strip('"') for k, v in labels.items()}
        except ValueError:
            continue
        if name in families:
            key, label = families[name]
            out[key][labels.get(label, "?")] = value
        elif name == "intellillm_router_replica_queue_depth":
            out["queue_depth"].setdefault(
                labels.get("replica", "?"), {})[
                    labels.get("queue", "?")] = value
    return out


def snapshot_health_detail(base: str) -> dict:
    """Scrape the server-side /health/detail body (rolling SLO summary,
    device telemetry, watchdog state). A 503 still carries the body
    (stalled server — worth recording)."""
    try:
        with urllib.request.urlopen(base + "/health/detail", timeout=5) as r:
            return json.loads(r.read().decode(errors="replace"))
    except urllib.error.HTTPError as e:
        try:
            return json.loads(e.read().decode(errors="replace"))
        except Exception:
            return {"error": f"health/detail scrape failed: {e}"}
    except Exception as e:
        return {"error": f"health/detail scrape failed: {e}"}


def snapshot_efficiency(base: str) -> dict:
    """Scrape the full compute-efficiency ledger (/debug/efficiency):
    real/pad token totals, per-axis fill ratios, MFU, and the per-bucket
    pad-waste attribution — the numbers every bucketing/scheduler perf
    change is judged against."""
    try:
        with urllib.request.urlopen(base + "/debug/efficiency",
                                    timeout=5) as r:
            return json.loads(r.read().decode(errors="replace"))
    except Exception as e:
        return {"error": f"efficiency scrape failed: {e}"}


def snapshot_kernels(base: str, top: int = 8) -> dict:
    """Scrape the per-kernel cost ledger (/debug/kernels): per-(program,
    bucket) cost_analysis FLOPs / bytes / peak HBM, the cost-model-vs-
    analytic MFU cross-check, and any merged profiler capture — the
    before/after artifact a Pallas kernel pass is judged against
    (ROADMAP item 2)."""
    try:
        with urllib.request.urlopen(f"{base}/debug/kernels?top={top}",
                                    timeout=5) as r:
            return json.loads(r.read().decode(errors="replace"))
    except Exception as e:
        return {"error": f"kernels scrape failed: {e}"}


def snapshot_alerts(base: str) -> dict:
    """Scrape /debug/alerts. On a router this includes the fleet block
    (every replica's alert summary aggregated), so a fleet run can
    assert "no alerts fired" from one endpoint."""
    try:
        with urllib.request.urlopen(base + "/debug/alerts", timeout=5) as r:
            return json.loads(r.read().decode(errors="replace"))
    except Exception as e:
        return {"error": f"alerts scrape failed: {e}"}


def distill_alerts(alerts: dict) -> dict:
    """Compact alert verdict for the summary line: which rules are
    firing/pending and whether the run finished clean."""
    if not alerts or "error" in alerts:
        return {"error": (alerts or {}).get("error", "no alert data"),
                "clean": None}
    fleet = alerts.get("fleet")
    firing = sorted((fleet.get("rules_firing") or []) if fleet
                    else (alerts.get("firing") or []))
    pending = sorted((fleet.get("rules_pending") or []) if fleet
                     else (alerts.get("pending") or []))
    return {
        "firing": firing,
        "pending": pending,
        "page_firing": (fleet.get("page_firing") if fleet
                        else alerts.get("page_firing", False)),
        "clean": not firing and not pending,
        "fleet_aggregated": fleet is not None,
    }


def distill_contention(detail: dict) -> dict:
    """Compact the scheduler's contention ledger (the `contention` block
    of /health/detail, backed by intellillm_sched_deferred_seconds_total
    / intellillm_sched_decisions_total) into the A/B-comparable block:
    deferred-seconds-by-cause plus the preemption/requeue counts — the
    *why* next to every scenario's queue-wait numbers."""
    block = (detail or {}).get("contention")
    if not block:
        return {"error": (detail or {}).get(
            "error", "no contention block in /health/detail")}
    decisions = block.get("decisions") or {}
    return {
        "deferred_seconds_by_cause":
            block.get("deferred_seconds_by_cause") or {},
        "decisions": decisions,
        "preemptions": decisions.get("preempt_victim", 0),
        "requeues": decisions.get("requeue", 0),
    }


def snapshot_contention(base: str) -> dict:
    """distill_contention over a fresh /health/detail scrape."""
    return distill_contention(snapshot_health_detail(base))


def distill_numerics(detail: dict) -> dict:
    """Compact the output-integrity block (the `numerics` block of
    /health/detail, obs/numerics.py) for the summary: sentinel coverage
    + anomaly/quarantine counts and the KV-audit checksum/mismatch
    counters. wdiff diffs these with lower-is-better direction — a run
    is only comparable to a baseline if neither corrupted outputs."""
    block = (detail or {}).get("numerics")
    if not block:
        return {"error": (detail or {}).get(
            "error", "no numerics block in /health/detail")}
    return block


def snapshot_numerics(base: str) -> dict:
    """Scrape /debug/numerics. On a router this is the fleet view: the
    divergence-canary ledger plus each replica's compact block."""
    try:
        with urllib.request.urlopen(base + "/debug/numerics",
                                    timeout=5) as r:
            return json.loads(r.read().decode(errors="replace"))
    except Exception as e:
        return {"error": f"numerics scrape failed: {e}"}


def snapshot_fleet_traces(router_base: str, limit: int = 3) -> dict:
    """Sample stitched fleet traces from the router: recent trace ids
    from /debug/trace, each fetched via /debug/trace/{id} — the per-hop
    attribution (router_queue / routing / network / replica_queue /
    prefill / decode) is the fleet-level answer to "where did the
    latency go". Returns {"samples": [...], "hops_mean_ms": {...}}."""
    out = {"samples": [], "hops_mean_ms": {}}
    try:
        with urllib.request.urlopen(router_base + "/debug/trace",
                                    timeout=5) as r:
            listing = json.loads(r.read().decode(errors="replace"))
    except Exception as e:
        return {"error": f"trace listing scrape failed: {e}"}
    hop_sums, hop_counts = {}, {}
    for trace_id in (listing.get("recent_trace_ids") or [])[:limit]:
        try:
            with urllib.request.urlopen(
                    f"{router_base}/debug/trace/{trace_id}",
                    timeout=5) as r:
                stitched = json.loads(r.read().decode(errors="replace"))
        except Exception:
            continue
        attribution = stitched.get("attribution") or {}
        out["samples"].append({
            "trace_id": trace_id,
            "hops": stitched.get("hops"),
            "e2e_s": attribution.get("e2e_s"),
            "hops_s": attribution.get("hops_s"),
            "num_events": len(stitched.get("timeline") or []),
        })
        for hop, seconds in (attribution.get("hops_s") or {}).items():
            hop_sums[hop] = hop_sums.get(hop, 0.0) + seconds
            hop_counts[hop] = hop_counts.get(hop, 0) + 1
    out["hops_mean_ms"] = {
        hop: round(hop_sums[hop] / hop_counts[hop] * 1e3, 3)
        for hop in hop_sums}
    return out


def distill_device_telemetry(detail: dict) -> dict:
    """Compact memory-state record for the summary JSON: per-device
    peak/in-use bytes, the ledger, headroom, and total swap traffic."""
    dt = detail.get("device_telemetry") or {}
    return {
        "devices": dt.get("devices") or {},
        "ledger_bytes": dt.get("ledger_bytes") or {},
        "headroom_ratio": dt.get("headroom_ratio"),
        "low_hbm_warnings": dt.get("low_hbm_warnings"),
        "swap_bytes_total": dt.get("swap_bytes_total") or {},
    }


def wait_healthy(proc: subprocess.Popen, base: str, timeout: float,
                 server_log: str) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            with open(server_log, "rb") as f:
                out = f.read().decode(errors="replace")
            raise RuntimeError(f"server died during init:\n{out[-4000:]}")
        try:
            urllib.request.urlopen(base + "/health", timeout=2)
            return
        except Exception:
            time.sleep(2.0)
    raise TimeoutError(f"server not healthy after {timeout:.0f}s")


async def _ttft_under_load(api_url: str, model_name: str, background,
                           probe, probe_delay: float,
                           protocol: str = "openai"):
    """Steady decode stream + one long-prompt probe injected mid-run.

    The background requests all start at once (short prompts, long
    outputs) so the engine is in pure decode when the probe's long
    prefill arrives. With legacy homogeneous scheduling that prefill
    monopolizes whole steps — decode TPOT spikes and the probe still
    waits behind the running batch; with chunked prefill the prompt
    rides the per-step slack. Returns (elapsed_s, bg_results,
    probe_results)."""
    import aiohttp

    from benchmarks.benchmark_serving import send_request

    bg_results, probe_results = [], []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=6 * 3600)
    start = time.perf_counter()
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:
        bg_tasks = [
            asyncio.create_task(send_request(
                session, protocol, api_url, model_name, prompt,
                prompt_len, output_len, 1, bg_results))
            for prompt, prompt_len, output_len in background
        ]
        await asyncio.sleep(probe_delay)
        prompt, prompt_len, output_len = probe
        await send_request(session, protocol, api_url, model_name, prompt,
                           prompt_len, output_len, 1, probe_results)
        await asyncio.gather(*bg_tasks)
    return time.perf_counter() - start, bg_results, probe_results


def run_ttft_under_load(args, api_url: str, model_name: str, tokenizer,
                        requests, protocol: str = "openai") -> dict:
    """The ttft-under-load scenario: report the probe's TTFT next to the
    background stream's P99 TPOT — the pair of numbers chunked prefill
    trades against each other."""
    import copy

    probe_args = copy.copy(args)
    probe_args.num_prompts = 1
    probe_args.input_len = (args.probe_input_len
                            or max(args.input_len,
                                   args.max_model_len
                                   - args.probe_output_len - 1))
    probe_args.output_len = args.probe_output_len
    probe_args.seed = args.seed + 1
    (probe,) = build_requests(probe_args, tokenizer)

    # Warm the probe-shaped prefill program so the measured TTFT is
    # scheduling delay, not a first-compile stall.
    asyncio.run(run_benchmark(protocol, api_url, model_name, [probe],
                              float("inf")))

    elapsed, bg_results, probe_results = asyncio.run(_ttft_under_load(
        api_url, model_name, requests, probe, args.probe_delay,
        protocol=protocol))
    bg = compute_metrics(bg_results, elapsed)
    (pr,) = probe_results
    return {
        "scenario": "ttft-under-load",
        "seed": args.seed,
        "chunked_prefill": not args.disable_chunked_prefill,
        "max_num_batched_tokens": args.max_num_batched_tokens,
        "probe_input_len": probe[1],
        "probe_output_len": probe[2],
        "probe_delay_s": args.probe_delay,
        "probe_ttft_ms": round(pr.ttft * 1e3, 1),
        "probe_latency_s": round(pr.latency, 3),
        "background_completed": bg["completed"],
        "background_tpot_p99_ms": bg["tpot_percentiles_ms"]["p99"],
        "background_ttft_p99_ms": bg["ttft_percentiles_ms"]["p99"],
        "background": bg,
    }


# ---------------------------------------------------------------------------
# Workload capture & replay (docs/observability.md).
#
# `--scenario replay` re-issues a captured IWL1 stream (obs/workload.py)
# against a freshly booted server with the original inter-arrival gaps;
# `--scenario diurnal` synthesizes a seeded day-in-the-life stream (flash
# crowds, heavy-tailed lengths, adapter churn) in the same format.  Both
# are deterministic end to end: two replays of the same file issue the
# identical request sequence, and the server-side re-capture
# (/debug/workload?format=iwl) matches across repeats.
# ---------------------------------------------------------------------------


def _synth_prompt(tokenizer, prompt_len: int, prompt_hash: str):
    """Deterministically resynthesize a prompt from its fingerprint.

    Captures default to hashes, not raw text (privacy).  Replay only
    needs *a* stable prompt of the recorded token length, so we sample
    token ids from an RNG seeded by the fingerprint: every replay of the
    same record produces the same prompt string.  Returns
    (prompt, server_token_count) like build_requests."""
    rng = random.Random(int(prompt_hash or "0", 16))
    vocab = len(tokenizer)
    ids = [rng.randrange(vocab) for _ in range(max(1, prompt_len))]
    prompt = tokenizer.decode(ids, skip_special_tokens=True)
    if not prompt.strip():
        prompt = " ".join(str(rng.randrange(10)) for _ in range(
            max(1, prompt_len)))
    # Re-encode: the server budgets by *its* token count (see
    # build_requests in benchmark_serving.py).
    return prompt, len(tokenizer.encode(prompt))


def build_replay_stream(records, tokenizer, args):
    """Turn parsed IWL1 records into (requests, gaps, stream_digest).

    `requests` is the (prompt, prompt_len, output_len) list
    run_benchmark expects; `gaps[i]` is the sleep before issuing request
    i (recorded offsets divided by --speed); `stream_digest` is a sha256
    over the exact issue schedule so two replays can be compared without
    trusting wall clocks."""
    speed = max(float(args.speed), 1e-6)
    requests, gaps = [], []
    h = hashlib.sha256()
    prev_t = 0.0
    for rec in records:
        t = float(rec.get("t", 0.0))
        gap = max(0.0, (t - prev_t) / speed)
        prev_t = t
        plen = int(rec.get("prompt_len") or 1)
        if rec.get("prompt"):
            prompt = rec["prompt"]
            plen = len(tokenizer.encode(prompt))
        else:
            prompt, plen = _synth_prompt(tokenizer, plen,
                                         rec.get("prompt_hash") or "0")
        sampling = rec.get("sampling") or {}
        outcome = rec.get("outcome") or {}
        olen = int(sampling.get("max_tokens") or outcome.get("tokens")
                   or args.output_len)
        olen = max(1, min(olen, args.max_model_len - plen - 1))
        requests.append((prompt, plen, olen))
        gaps.append(round(gap, 6))
        h.update(json.dumps(
            [gaps[-1], rec.get("prompt_hash") or "", plen, olen],
            sort_keys=True).encode())
    return requests, gaps, h.hexdigest()[:16]


def _recapture_digest(records) -> str:
    """Order-insensitive digest of a re-captured workload shard.

    Concurrent arrivals can land in the server's log in either order,
    so the digest covers the sorted multiset of per-request tuples, not
    the sequence."""
    tuples = sorted(
        [rec.get("prompt_hash") or "", int(rec.get("prompt_len") or 0),
         (rec.get("sampling") or {}).get("max_tokens"),
         (rec.get("outcome") or {}).get("tokens"),
         (rec.get("outcome") or {}).get("reason")]
        for rec in records)
    return hashlib.sha256(
        json.dumps(tuples, sort_keys=True).encode()).hexdigest()[:16]


def _fetch_iwl(base: str) -> str:
    with urllib.request.urlopen(base + "/debug/workload?format=iwl",
                                timeout=10.0) as r:
        return r.read().decode()


def run_replay(args, model_dir, tokenizer, extra=None) -> dict:
    """Replay a captured IWL1 workload against one freshly booted server.

    Boots once, then runs the stream --replay-repeat times.  Each pass
    records client-side metrics plus a server-side re-capture digest
    from /debug/workload, so the summary can assert end-to-end
    determinism (identical issue schedule AND identical server-observed
    workload) instead of asking the reader to diff logs."""
    from intellillm_tpu.obs.workload import parse_iwl

    if not args.workload:
        raise SystemExit("--scenario replay requires --workload FILE")
    with open(args.workload) as f:
        header, records = parse_iwl(f.read())
    requests, gaps, stream_digest = build_replay_stream(
        records, tokenizer, args)

    proc = launch_server(model_dir, args)
    base = f"http://127.0.0.1:{args.port}"
    api_url = base + "/v1/completions"
    model_name = f"dummy-{args.size}"
    summary = {"scenario": "replay", "size": args.size,
               "seed": args.seed, "workload": args.workload,
               "speed": args.speed, "replay_repeat": args.replay_repeat,
               "num_requests": len(requests),
               "workload_header": {k: header.get(k) for k in
                                   ("iwl", "source", "raw_prompts",
                                    "requests")},
               "stream_digest": stream_digest,
               "max_num_seqs": args.max_num_seqs, "results": []}
    if extra:
        summary.update(extra)
    recaptures = []
    try:
        wait_healthy(proc, base, args.init_timeout, args.server_log)
        # Warm the batch/width ladder the replayed stream will hit (same
        # rationale as run_single's warm-up): two all-at-once passes over
        # a prefix so first-compile stalls don't skew repeat 1 vs 2.
        warm = requests[:max(4, min(args.max_num_seqs, len(requests)))]
        for _ in range(2):
            asyncio.run(run_benchmark("openai", api_url, model_name,
                                      warm, float("inf")))
        for rep in range(max(1, args.replay_repeat)):
            mark = time.time()
            elapsed, results = asyncio.run(run_benchmark(
                "openai", api_url, model_name, requests, float("inf"),
                gaps=gaps))
            m = compute_metrics(results, elapsed)
            m["repeat"] = rep
            recap = {"count": None, "digest": None}
            try:
                _, caught = parse_iwl(_fetch_iwl(base))
                shard = [r for r in caught
                         if float(r.get("ts") or 0.0) >= mark]
                recap = {"count": len(shard),
                         "digest": _recapture_digest(shard)}
                if args.workload_out:
                    from intellillm_tpu.obs.workload import dump_iwl
                    with open(args.workload_out, "w") as f:
                        f.write(dump_iwl(shard, source="replay"))
            except Exception as e:  # recapture is best-effort
                recap["error"] = str(e)
            m["recapture"] = recap
            recaptures.append(recap.get("digest"))
            summary["results"].append(m)
            print(json.dumps({"serve_bench_replay_repeat": rep, **m}),
                  flush=True)
        summary["recapture_digests"] = recaptures
        summary["recapture_match"] = (
            len(set(d for d in recaptures)) == 1
            and recaptures[0] is not None)
        summary["replay_deterministic"] = bool(summary["recapture_match"])
        summary["observability"] = snapshot_observability(base)
        detail = snapshot_health_detail(base)
        summary["slo"] = detail.get("slo") or {}
        summary["efficiency"] = snapshot_efficiency(base)
        summary["kernels"] = snapshot_kernels(base)
        summary["contention"] = distill_contention(detail)
        summary["numerics"] = distill_numerics(detail)
        summary["alerts"] = distill_alerts(snapshot_alerts(base))
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    print(json.dumps({"serve_bench_summary": summary}), flush=True)
    return summary


def synth_diurnal(args):
    """Synthesize a seeded diurnal workload as IWL1 records.

    ~60% of arrivals are spread uniformly over --diurnal-duration; the
    rest cluster into --diurnal-bursts gaussian flash crowds.  Prompt
    and output lengths are heavy-tailed (lognormal, clamped to the
    context window); requests churn across --num-tenants adapters with
    a Zipf-ish 1/k weighting so adapter-cache behaviour is exercised.
    Same --seed => byte-identical record list."""
    rng = random.Random(args.seed)
    n = args.num_prompts
    dur = max(0.001, float(args.diurnal_duration))
    bursts = max(0, int(args.diurnal_bursts))
    centers = [rng.uniform(0.15, 0.85) * dur for _ in range(bursts)]
    arrivals = []
    for i in range(n):
        if bursts and rng.random() < 0.4:
            c = centers[rng.randrange(bursts)]
            arrivals.append(min(dur, max(0.0,
                                         rng.gauss(c, dur * 0.02))))
        else:
            arrivals.append(rng.uniform(0.0, dur))
    arrivals.sort()
    tenants = max(1, args.num_tenants)
    weights = [1.0 / k for k in range(1, tenants + 1)]
    records = []
    for i, t in enumerate(arrivals):
        plen = int(min(args.max_model_len // 2, max(
            4, rng.lognormvariate(math.log(args.input_len), 0.6))))
        olen = int(min(args.max_model_len - plen - 1, max(
            1, rng.lognormvariate(math.log(args.output_len), 0.6))))
        adapter = rng.choices(range(tenants), weights=weights)[0]
        phash = hashlib.blake2b(
            f"{args.seed}:{i}".encode(), digest_size=8).hexdigest()
        records.append({
            "ts": round(t, 6), "t": round(t, 6),
            "id": f"diurnal-{args.seed}-{i}",
            "prompt_len": plen, "prompt_hash": phash,
            "sampling": {"max_tokens": olen, "temperature": 0.0,
                         "ignore_eos": True},
            "tenant": f"tenant-{adapter}" if adapter else None,
            "adapter": adapter, "priority": 0,
            "outcome": {"tokens": olen, "reason": "synthetic"},
        })
    return records


def run_diurnal(args, model_dir, tokenizer) -> dict:
    """Emit a synthetic diurnal IWL1 stream, then (unless --emit-only)
    replay it through run_replay."""
    from intellillm_tpu.obs.workload import dump_iwl

    records = synth_diurnal(args)
    out = args.workload_out or "/tmp/serve_bench_diurnal.iwl.jsonl"
    with open(out, "w") as f:
        f.write(dump_iwl(records, source="diurnal",
                         extra_header={"seed": args.seed}))
    block = {"scenario": "diurnal", "seed": args.seed,
             "num_requests": len(records), "workload_out": out,
             "diurnal_duration_s": args.diurnal_duration,
             "diurnal_bursts": args.diurnal_bursts,
             "num_tenants": args.num_tenants}
    print(json.dumps({"serve_bench_diurnal": block}), flush=True)
    if args.emit_only:
        summary = dict(block, emit_only=True)
        print(json.dumps({"serve_bench_summary": summary}), flush=True)
        return summary
    args.workload = out
    args.workload_out = None  # don't clobber the input mid-replay
    return run_replay(args, model_dir, tokenizer,
                      extra={"diurnal": block})


def launch_generate_replica(model_dir: str, args, port: int,
                            log_path: str,
                            role: str = None,
                            extra_args=None,
                            index: int = 0) -> subprocess.Popen:
    """Launch one demo api_server replica (plain /generate protocol —
    the surface the router fronts). `role` maps to --replica-role for
    disaggregated fleets; `extra_args` appends raw CLI flags (the
    multi-tenant scenario passes the LoRA/fairness knobs through it).
    Replica `index` of a fleet gets its own chip through its
    environment (utils.child_process_env)."""
    cmd = [
        sys.executable, "-m", "intellillm_tpu.entrypoints.api_server",
        "--model", model_dir,
        "--load-format", "dummy",
        "--host", "127.0.0.1",
        "--port", str(port),
        "--max-model-len", str(args.max_model_len),
        "--max-num-seqs", str(args.max_num_seqs),
        "--num-decode-steps", str(args.num_decode_steps),
        "--block-size", str(args.block_size),
        "--kv-cache-dtype", args.kv_cache_dtype,
        "--max-paddings", "4096",
        "--swap-space", "0.05",
        "--disable-log-requests",
    ]
    if args.quantization:
        cmd += ["--quantization", args.quantization]
    if args.num_device_blocks:
        cmd += ["--num-device-blocks-override", str(args.num_device_blocks)]
    if role and role != "mixed":
        cmd += ["--replica-role", role]
    if extra_args:
        cmd += list(extra_args)
    env = child_process_env(child_index=index)
    env.setdefault("HF_HUB_OFFLINE", "1")
    log = open(log_path, "wb")
    return subprocess.Popen(cmd, env=env, stdout=log,
                            stderr=subprocess.STDOUT)


def run_fleet(args, model_dir: str, tokenizer) -> dict:
    """The fleet scenario: N generate-protocol replicas behind the
    router, rate sweep through the router, and a per-replica SLO split
    (each replica's own /health/detail SLO summary) next to the router's
    routing counters — the view that shows whether affinity is
    concentrating work and the predictor is balancing it."""
    router_base = f"http://127.0.0.1:{args.port}"
    api_url = router_base + "/generate"
    summary = {"scenario": "fleet", "size": args.size,
               "num_replicas": args.num_replicas,
               "input_len": args.input_len, "output_len": args.output_len,
               "num_prompts": args.num_prompts, "seed": args.seed,
               "max_num_seqs": args.max_num_seqs,
               "quantization": args.quantization,
               "kv_cache_dtype": args.kv_cache_dtype, "results": []}
    replicas = []     # (name, base_url, proc, log_path)
    router_proc = None
    try:
        for i in range(args.num_replicas):
            port = args.replica_base_port + i
            log_path = f"{args.server_log}.replica{i}"
            proc = launch_generate_replica(model_dir, args, port, log_path,
                                           index=i)
            replicas.append((f"replica-{i}", f"http://127.0.0.1:{port}",
                             proc, log_path))
        for name, base, proc, log_path in replicas:
            wait_healthy(proc, base, args.init_timeout, log_path)

        router_log = args.server_log + ".router"
        router_cmd = [
            sys.executable, "-m", "intellillm_tpu.router.server",
            "--host", "127.0.0.1", "--port", str(args.port),
            "--replica-urls", ",".join(b for _, b, _, _ in replicas),
            "--tokenizer", model_dir,
            "--block-size", str(args.block_size),
            "--health-interval", "1.0",
        ]
        env = child_process_env()
        env.setdefault("HF_HUB_OFFLINE", "1")
        log = open(router_log, "wb")
        router_proc = subprocess.Popen(router_cmd, env=env, stdout=log,
                                       stderr=subprocess.STDOUT)
        # Router /health goes 200 once its first poll sees a healthy
        # replica, so this also proves the poll loop works.
        wait_healthy(router_proc, router_base, 120.0, router_log)

        requests = build_requests(args, tokenizer)
        # Warm every replica's compile ladder through the router (two
        # all-at-once passes spread load over the fleet).
        for _ in range(2):
            asyncio.run(run_benchmark("generate", api_url, None, requests,
                                      float("inf")))

        for rate_s in args.rates.split(","):
            rate = float(rate_s)
            elapsed, results = asyncio.run(run_benchmark(
                "generate", api_url, None, requests, rate,
                seed=args.seed))
            m = compute_metrics(results, elapsed)
            m["request_rate"] = rate_s
            summary["results"].append(m)
            print(json.dumps({"serve_bench_fleet_rate": rate_s, **m}),
                  flush=True)

        summary["router"] = {
            "metrics": snapshot_router_metrics(router_base),
            "health_detail": snapshot_health_detail(router_base),
        }
        # Fleet-aggregated alert state from the router: the bench's
        # "no alerts fired" assertion (or the list of what did).
        summary["alerts"] = distill_alerts(snapshot_alerts(router_base))
        # Per-hop latency splits: stitched trace samples from the
        # router's aggregator + each replica's own hop decomposition
        # (slo.hops_ms from its /health/detail).
        summary["trace_attribution"] = snapshot_fleet_traces(router_base)
        per_replica = {}
        for name, base, proc, log_path in replicas:
            detail = snapshot_health_detail(base)
            slo = detail.get("slo") or {}
            per_replica[name] = {
                "base": base,
                "status": detail.get("status"),
                "slo": slo,
                "hops_ms": slo.get("hops_ms"),
                "queue_depths": detail.get("queue_depths"),
                "kv_cache_usage": detail.get("kv_cache_usage"),
                "contention": distill_contention(detail),
                "numerics": distill_numerics(detail),
            }
        summary["per_replica_slo"] = per_replica
        summary["contention"] = {
            name: pr["contention"] for name, pr in per_replica.items()}
        # Fleet output-integrity verdict: the router's canary ledger
        # (suspect replicas, reference digest) + each replica's own
        # sentinel/KV-audit counters.
        fleet_numerics = snapshot_numerics(router_base)
        summary["numerics"] = {
            "canary": fleet_numerics.get("canary"),
            "replicas": {name: pr["numerics"]
                         for name, pr in per_replica.items()},
        }
        print(json.dumps({"serve_bench_fleet": {
            "per_replica_slo": per_replica,
            "router": summary["router"],
            "trace_attribution": summary["trace_attribution"],
            "alerts": summary["alerts"],
        }}), flush=True)
    finally:
        if router_proc is not None:
            router_proc.send_signal(signal.SIGKILL)
            router_proc.wait()
        for _, _, proc, _ in replicas:
            proc.send_signal(signal.SIGKILL)
            proc.wait()

    print(json.dumps({"serve_bench_summary": summary}), flush=True)
    return summary


def _run_role_fleet(args, model_dir, tokenizer, roles, label,
                    base_port) -> dict:
    """Boot one replica per entry in `roles` (passed through as
    --replica-role) behind the router (--replica-roles), run the
    ttft-under-load probe through the router's /generate protocol, and
    return the probe/background split plus the router's fleet
    kv_transfer block and each replica's own transfer counters (bytes
    move engine-side, so an HTTP fleet's byte counts live in the
    replica processes, not the router's)."""
    router_base = f"http://127.0.0.1:{args.port}"
    api_url = router_base + "/generate"
    replicas = []
    router_proc = None
    try:
        for i, role in enumerate(roles):
            port = base_port + i
            log_path = f"{args.server_log}.{label}{i}"
            proc = launch_generate_replica(model_dir, args, port, log_path,
                                           role=role, index=i)
            replicas.append((f"{label}-{i}-{role}",
                             f"http://127.0.0.1:{port}", proc, log_path))
        for name, base, proc, log_path in replicas:
            wait_healthy(proc, base, args.init_timeout, log_path)

        router_log = f"{args.server_log}.{label}.router"
        router_cmd = [
            sys.executable, "-m", "intellillm_tpu.router.server",
            "--host", "127.0.0.1", "--port", str(args.port),
            "--replica-urls", ",".join(b for _, b, _, _ in replicas),
            "--replica-roles", ",".join(roles),
            "--tokenizer", model_dir,
            "--block-size", str(args.block_size),
            "--health-interval", "1.0",
        ]
        env = child_process_env()
        env.setdefault("HF_HUB_OFFLINE", "1")
        log = open(router_log, "wb")
        router_proc = subprocess.Popen(router_cmd, env=env, stdout=log,
                                       stderr=subprocess.STDOUT)
        wait_healthy(router_proc, router_base, 120.0, router_log)

        requests = build_requests(args, tokenizer)
        # Warm every replica's compile ladder through the router. On the
        # disagg fleet this also seeds the KV registry: the repeat pass
        # turns registry misses into fleet/local hits.
        for _ in range(2):
            asyncio.run(run_benchmark("generate", api_url, None, requests,
                                      float("inf")))

        m = run_ttft_under_load(args, api_url, None, tokenizer, requests,
                                protocol="generate")
        detail = snapshot_health_detail(router_base)
        router_detail = (detail.get("router") or {}) if detail else {}
        per_replica_kv = {}
        per_replica_contention = {}
        per_replica_numerics = {}
        kv_bytes = {"export": 0, "import": 0}
        kv_seconds = {"export": 0.0, "import": 0.0}
        for name, base, proc, log_path in replicas:
            rd = snapshot_health_detail(base) or {}
            kv = rd.get("kv_transfer")
            per_replica_kv[name] = kv
            per_replica_contention[name] = distill_contention(rd)
            per_replica_numerics[name] = distill_numerics(rd)
            if kv:
                for d in ("export", "import"):
                    kv_bytes[d] += (kv.get("bytes_total") or {}).get(d, 0)
                    kv_seconds[d] += (kv.get("seconds_total")
                                      or {}).get(d, 0.0)
        return {
            "label": label,
            "roles": list(roles),
            "probe_ttft_ms": m["probe_ttft_ms"],
            "background_ttft_p99_ms": m["background_ttft_p99_ms"],
            "background_tpot_p99_ms": m["background_tpot_p99_ms"],
            "ttft_under_load": m,
            "router_kv_transfer": router_detail.get("kv_transfer"),
            "decisions": router_detail.get("decisions"),
            "kv_bytes": kv_bytes,
            "kv_seconds": {d: round(s, 6) for d, s in kv_seconds.items()},
            "per_replica_kv": per_replica_kv,
            "contention": per_replica_contention,
            "numerics": per_replica_numerics,
        }
    finally:
        if router_proc is not None:
            router_proc.send_signal(signal.SIGKILL)
            router_proc.wait()
        for _, _, proc, _ in replicas:
            proc.send_signal(signal.SIGKILL)
            proc.wait()


def run_disagg(args, model_dir, tokenizer) -> dict:
    """The disagg scenario: A/B the SAME ttft-under-load workload on
    (a) a disaggregated fleet — 1 prefill-role replica + --num-replicas
    decode-role replicas — and (b) a mixed fleet of equal size
    (--num-replicas + 1 mixed replicas), both behind the router. The
    pair of numbers to watch is the probe's TTFT (prefill interference)
    against the background stream's P99 TPOT (decode purity), next to
    what the isolation costs: KV-transfer bytes/seconds and the fleet
    prefix-cache hit counters (docs/routing.md)."""
    n = args.num_replicas
    disagg = _run_role_fleet(args, model_dir, tokenizer,
                             ["prefill"] + ["decode"] * n, "disagg",
                             args.replica_base_port)
    mixed = _run_role_fleet(args, model_dir, tokenizer,
                            ["mixed"] * (n + 1), "mixed",
                            args.replica_base_port + n + 1)
    comparison = {
        "probe_ttft_ms": {"disagg": disagg["probe_ttft_ms"],
                          "mixed": mixed["probe_ttft_ms"]},
        "background_ttft_p99_ms": {
            "disagg": disagg["background_ttft_p99_ms"],
            "mixed": mixed["background_ttft_p99_ms"]},
        "background_tpot_p99_ms": {
            "disagg": disagg["background_tpot_p99_ms"],
            "mixed": mixed["background_tpot_p99_ms"]},
        "kv_bytes": disagg["kv_bytes"],
        "kv_seconds": disagg["kv_seconds"],
        "cache_hits": (disagg["router_kv_transfer"]
                       or {}).get("cache_hits"),
    }
    summary = {"scenario": "disagg", "size": args.size,
               "num_decode_replicas": n, "seed": args.seed,
               "input_len": args.input_len, "output_len": args.output_len,
               "num_prompts": args.num_prompts,
               "max_num_seqs": args.max_num_seqs,
               "fleets": {"disagg": disagg, "mixed": mixed},
               "contention": {"disagg": disagg.get("contention"),
                              "mixed": mixed.get("contention")},
               "numerics": {"disagg": disagg.get("numerics"),
                            "mixed": mixed.get("numerics")},
               "comparison": comparison}
    print(json.dumps({"serve_bench_disagg": comparison}), flush=True)
    print(json.dumps({"serve_bench_summary": summary}), flush=True)
    return summary


def _make_bench_adapter(model_dir: str, out_dir: str, seed: int,
                        rank: int = 8) -> str:
    """Synthesize a tiny HF-PEFT-style LoRA checkpoint (q/v targets)
    against `model_dir`'s config — the multi-tenant scenario needs N
    distinct adapters, not N distinct base models."""
    import numpy as np
    import safetensors.numpy
    with open(os.path.join(model_dir, "config.json")) as f:
        cfg = json.load(f)
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv_heads = cfg.get("num_key_value_heads") or heads
    head_dim = hidden // heads
    dims = {"q_proj": (hidden, hidden),
            "v_proj": (hidden, kv_heads * head_dim)}
    rng = np.random.RandomState(seed)
    tensors = {}
    for li in range(cfg["num_hidden_layers"]):
        for t, (din, dout) in dims.items():
            base = f"base_model.model.model.layers.{li}.self_attn.{t}"
            tensors[f"{base}.lora_A.weight"] = rng.randn(
                rank, din).astype(np.float32) * 0.01
            tensors[f"{base}.lora_B.weight"] = rng.randn(
                dout, rank).astype(np.float32) * 0.01
    os.makedirs(out_dir, exist_ok=True)
    safetensors.numpy.save_file(
        tensors, os.path.join(out_dir, "adapter_model.safetensors"))
    with open(os.path.join(out_dir, "adapter_config.json"), "w") as f:
        json.dump({"r": rank, "lora_alpha": float(rank),
                   "target_modules": list(dims)}, f)
    return out_dir


def _post_json(base: str, path: str, body: dict, timeout: float = 30.0):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode(errors="replace"))


async def _tenant_request(session, base: str, tenant: str, prompt: str,
                          max_tokens: int, results: list) -> None:
    """One streamed /generate request measuring client-side TTFT and
    TPOT. `ignore_eos` + a fixed `max_tokens` make the token count
    known, so TPOT = (stream tail time) / (max_tokens - 1) regardless
    of how the server batches chunks."""
    payload = {"prompt": prompt, "tenant": tenant, "stream": True,
               "max_tokens": max_tokens, "ignore_eos": True,
               "temperature": 0.0}
    t0 = time.perf_counter()
    ttft = None
    async with session.post(base + "/generate", json=payload) as resp:
        resp.raise_for_status()
        async for line in resp.content:
            if line.strip() and ttft is None:
                ttft = time.perf_counter() - t0
    latency = time.perf_counter() - t0
    results.append({
        "tenant": tenant,
        "ttft_s": ttft,
        "latency_s": latency,
        "tpot_s": ((latency - ttft) / max(max_tokens - 1, 1)
                   if ttft is not None else None),
    })


async def _mt_phase(base: str, victim_tenants, victim_requests: int,
                    victim_output_len: int, hog_tenant=None,
                    hog_concurrency: int = 0,
                    hog_output_len: int = 0,
                    hog_start_delay: float = 1.5) -> list:
    """One load phase: each victim tenant streams `victim_requests`
    sequential requests (concurrency 1 per tenant — the latency probe)
    while the hog tenant, if any, floods `hog_concurrency` concurrent
    long-output requests. The flood starts `hog_start_delay` seconds
    after the victims — this is the noisy-NEIGHBOR scenario: the
    victims are established tenants when the hog arrives, so the
    fairness pass sees >= 2 present tenants and caps the hog at
    admission. (A hog that floods an EMPTY machine legitimately takes
    every seat — work-conserving fairness never evicts running work;
    see docs/multitenancy.md.) Hog tasks are cancelled once every
    victim finishes (the hog exists to create contention, not to be
    measured); the server aborts the dropped streams."""
    import aiohttp
    results: list = []
    hog_results: list = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=6 * 3600)
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:

        async def hog_request(i):
            await asyncio.sleep(hog_start_delay)
            await _tenant_request(session, base, hog_tenant,
                                  "busy " * 24 + str(i), hog_output_len,
                                  hog_results)

        hog_tasks = [
            asyncio.create_task(hog_request(i))
            for i in range(hog_concurrency)
        ] if hog_tenant else []

        async def victim_stream(tenant, salt):
            for i in range(victim_requests):
                await _tenant_request(
                    session, base, tenant,
                    f"measure {salt} {i} " + "ping " * 12,
                    victim_output_len, results)

        await asyncio.gather(*(victim_stream(t, si)
                               for si, t in enumerate(victim_tenants)))
        for task in hog_tasks:
            task.cancel()
        await asyncio.gather(*hog_tasks, return_exceptions=True)
    return results


def _mt_percentiles(rows, field: str) -> dict:
    vals = sorted(r[field] * 1e3 for r in rows
                  if r.get(field) is not None)
    if not vals:
        return {}
    def pick(q):
        return round(vals[min(len(vals) - 1,
                              max(0, int(q * len(vals) + 0.5) - 1))], 2)
    return {"p50": pick(0.50), "p99": pick(0.99), "n": len(vals)}


def run_multi_tenant(args, model_dir, tokenizer) -> dict:
    """The multi-tenant scenario (docs/multitenancy.md): N LoRA tenants
    on ONE replica — two victim tenants streaming latency-probe
    requests, one hot tenant flooding, plus background tenants so the
    registered adapter count exceeds --max-loras (device-slot churn).
    Phases: (1) victims solo, (2) victims + hog with fairness caps on,
    (3) same contention with --disable-tenant-fairness. The isolation
    verdict is victim TPOT p99 per phase: caps-on should hold within
    ~2x of solo while caps-off degrades unboundedly with hog size."""
    base = f"http://127.0.0.1:{args.port}"
    n = max(3, args.num_tenants)
    adapters = [
        _make_bench_adapter(model_dir,
                            os.path.join(model_dir, f"bench-adapter-{i}"),
                            seed=100 + i)
        for i in range(1, n + 1)
    ]
    tenant_ids = [f"tenant-{i}" for i in range(1, n + 1)]
    hog, victims = tenant_ids[0], tenant_ids[1:3]
    max_loras = max(2, n - 1)   # fewer slots than adapters → churn
    lora_flags = ["--enable-lora", "--max-loras", str(max_loras),
                  "--max-lora-rank", "8",
                  "--max-cpu-loras", str(n + 1)]

    def boot(extra, log_suffix):
        log_path = args.server_log + log_suffix
        proc = launch_generate_replica(model_dir, args, args.port,
                                       log_path,
                                       extra_args=lora_flags + extra)
        wait_healthy(proc, base, args.init_timeout, log_path)
        for i, (tid, path) in enumerate(zip(tenant_ids, adapters)):
            body = {"lora_name": tid, "lora_int_id": i + 1,
                    "lora_local_path": path}
            if tid == hog and args.tenant_hog_share_cap:
                body["token_share_cap"] = args.tenant_hog_share_cap
            _post_json(base, f"/tenants/{tid}/adapter", body)
        # Touch every tenant once: warms the compile ladder and pulls
        # each adapter through the loader before measurement.
        asyncio.run(_mt_phase(base, tenant_ids, 1,
                              args.victim_output_len))
        return proc

    summary = {"scenario": "multi-tenant", "size": args.size,
               "seed": args.seed,
               "num_tenants": n, "max_loras": max_loras,
               "hog": hog, "victims": victims,
               "hog_concurrency": args.hog_concurrency,
               "hog_output_len": args.hog_output_len,
               "victim_requests": args.victim_requests,
               "victim_output_len": args.victim_output_len,
               "tenant_hog_share_cap": args.tenant_hog_share_cap,
               "max_num_seqs": args.max_num_seqs}
    phases = {}
    proc = boot([], ".mt-fair")
    try:
        solo = asyncio.run(_mt_phase(
            base, victims, args.victim_requests, args.victim_output_len))
        phases["victim_solo"] = solo
        caps_on = asyncio.run(_mt_phase(
            base, victims, args.victim_requests, args.victim_output_len,
            hog_tenant=hog, hog_concurrency=args.hog_concurrency,
            hog_output_len=args.hog_output_len,
            hog_start_delay=args.hog_start_delay))
        phases["contention_caps_on"] = caps_on
        detail = snapshot_health_detail(base)
        summary["tenants_caps_on"] = detail.get("tenants")
        contention = {"caps_on": distill_contention(detail)}
        summary["numerics"] = distill_numerics(detail)
        summary["alerts_caps_on"] = distill_alerts(snapshot_alerts(base))
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    proc = boot(["--disable-tenant-fairness"], ".mt-unfair")
    try:
        caps_off = asyncio.run(_mt_phase(
            base, victims, args.victim_requests, args.victim_output_len,
            hog_tenant=hog, hog_concurrency=args.hog_concurrency,
            hog_output_len=args.hog_output_len,
            hog_start_delay=args.hog_start_delay))
        phases["contention_caps_off"] = caps_off
        detail = snapshot_health_detail(base)
        summary["tenants_caps_off"] = detail.get("tenants")
        contention["caps_off"] = distill_contention(detail)
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    per_phase = {}
    for phase, rows in phases.items():
        per_phase[phase] = {
            "tpot_ms": _mt_percentiles(rows, "tpot_s"),
            "ttft_ms": _mt_percentiles(rows, "ttft_s"),
            "per_tenant_tpot_ms": {
                t: _mt_percentiles([r for r in rows if r["tenant"] == t],
                                   "tpot_s")
                for t in sorted({r["tenant"] for r in rows})},
        }
    summary["victim_latency"] = per_phase
    summary["contention"] = contention

    def ratio(a, b):
        return (round(a / b, 3)
                if a is not None and b else None)
    solo_p99 = (per_phase.get("victim_solo", {})
                .get("tpot_ms", {}).get("p99"))
    on_p99 = (per_phase.get("contention_caps_on", {})
              .get("tpot_ms", {}).get("p99"))
    off_p99 = (per_phase.get("contention_caps_off", {})
               .get("tpot_ms", {}).get("p99"))
    stats_on = ((summary.get("tenants_caps_on") or {}).get("stats")
                or {})
    churn = {t: {"loads": (stats_on.get(t) or {}).get("adapter_loads"),
                 "evictions": (stats_on.get(t)
                               or {}).get("adapter_evictions"),
                 "deferred_tokens": (stats_on.get(t)
                                     or {}).get("deferred_tokens")}
             for t in tenant_ids}
    isolation = {
        "victim_tpot_p99_ms": {"solo": solo_p99, "caps_on": on_p99,
                               "caps_off": off_p99},
        "caps_on_vs_solo": ratio(on_p99, solo_p99),
        "caps_off_vs_solo": ratio(off_p99, solo_p99),
        "caps_off_vs_caps_on": ratio(off_p99, on_p99),
        "isolation_holds_2x": (on_p99 is not None and solo_p99
                               and on_p99 <= 2.0 * solo_p99),
        "adapter_churn": churn,
    }
    summary["isolation"] = isolation
    print(json.dumps({"serve_bench_multitenant": isolation}), flush=True)
    print(json.dumps({"serve_bench_summary": summary}), flush=True)
    return summary


def _compare_policies(args, model_dir, tokenizer, policies) -> dict:
    """Run the ttft-under-load scenario once per scheduling policy (one
    server lifecycle each) and print an SLO comparison block — the
    FCFS-vs-SJF view docs/scheduling.md describes."""
    rows = {}
    summaries = {}
    for policy in policies:
        s = run_single(args, model_dir, tokenizer, scheduling_policy=policy)
        summaries[policy] = s
        result = s["results"][0]
        slo = s.get("slo") or {}
        rows[policy] = {
            "probe_ttft_ms": result["probe_ttft_ms"],
            "background_ttft_p99_ms": result["background_ttft_p99_ms"],
            "background_tpot_p99_ms": result["background_tpot_p99_ms"],
            "queue_wait_p99_ms": (slo.get("queue_wait_ms") or {}).get("p99"),
            "goodput_ratio": slo.get("goodput_ratio"),
        }
    block = {"scenario": args.scenario, "policies": rows,
             "seed": args.seed,
             "sjf_starvation_s": args.sjf_starvation_s}
    base_row = rows.get("fcfs")
    if base_row is not None:
        for policy, row in rows.items():
            if policy == "fcfs":
                continue
            for key in ("probe_ttft_ms", "background_ttft_p99_ms",
                        "background_tpot_p99_ms"):
                if (row.get(key) is not None
                        and base_row.get(key) is not None):
                    row[f"{key}_delta_vs_fcfs"] = round(
                        row[key] - base_row[key], 1)
    if args.sjf_starvation_s is not None:
        deadline_ms = args.sjf_starvation_s * 1e3
        for row in rows.values():
            qw = row.get("queue_wait_p99_ms")
            row["queue_wait_under_deadline"] = (
                qw is not None and qw < deadline_ms)
    print(json.dumps({"serve_bench_policy_comparison": block}), flush=True)
    return {"policy_comparison": block, "summaries": summaries}


def _compare_spec(args, model_dir, tokenizer) -> dict:
    """Run the rate sweep twice — target-only, then with the draft model
    speculating — one server lifecycle each, and print a spec on/off
    comparison block. Greedy spec emits the target's exact stream, so
    the delta is pure serving throughput/latency, not a quality trade
    (with dummy weights acceptance is ~0: this measures the overhead
    floor; real checkpoints measure the win)."""
    spec_dir = args._spec_model_dir
    args._spec_model_dir = None
    baseline = run_single(args, model_dir, tokenizer)
    args._spec_model_dir = spec_dir
    spec = run_single(args, model_dir, tokenizer)

    def _row(summary):
        results = summary.get("results") or []
        rates = {}
        for m in results:
            rates[m.get("request_rate", "?")] = {
                "output_tok_s": m.get("output_tok_s"),
                "ttft_p99_ms": (m.get("ttft_percentiles_ms")
                                or {}).get("p99"),
                "tpot_p99_ms": (m.get("tpot_percentiles_ms")
                                or {}).get("p99"),
            }
        return rates

    base_rates, spec_rates = _row(baseline), _row(spec)
    for rate, row in spec_rates.items():
        base = base_rates.get(rate) or {}
        if (row.get("output_tok_s") is not None
                and base.get("output_tok_s")):
            row["output_tok_s_ratio_vs_off"] = round(
                row["output_tok_s"] / base["output_tok_s"], 3)
    block = {
        "seed": args.seed,
        "num_speculative_tokens": args.num_speculative_tokens,
        "spec_k_min": args.spec_k_min,
        "spec_k_max": args.spec_k_max,
        "spec_off": base_rates,
        "spec_on": spec_rates,
        # Acceptance/K/waste as the spec run ended (from /health/detail).
        "spec_stats": spec.get("spec"),
    }
    print(json.dumps({"serve_bench_spec_comparison": block}), flush=True)
    return {"spec_comparison": block,
            "summaries": {"spec_off": baseline, "spec_on": spec}}


def main(args) -> dict:
    from transformers import AutoTokenizer

    model_dir = args.model_dir or tempfile.mkdtemp(prefix="serve-bench-")
    if not os.path.exists(os.path.join(model_dir, "config.json")):
        # Only materialize the dummy checkpoint into an EMPTY dir — never
        # clobber an existing checkpoint passed via --model-dir.
        save_dummy_checkpoint(f"dummy:{args.size}", model_dir)
    tokenizer = AutoTokenizer.from_pretrained(model_dir)

    # Draft checkpoint for --speculative-size: its own dir, same dummy
    # materialization rule (vocab must match the target's, which holds
    # for the shared DUMMY_SIZES table).
    args._spec_model_dir = None
    if args.speculative_size:
        spec_dir = tempfile.mkdtemp(prefix="serve-bench-draft-")
        save_dummy_checkpoint(f"dummy:{args.speculative_size}", spec_dir)
        args._spec_model_dir = spec_dir

    summary = _dispatch(args, model_dir, tokenizer)
    if args.summary_out:
        # Machine-readable snapshot for `python -m
        # intellillm_tpu.tools.wdiff` (obs/diff.py) — compare two of
        # these to flag regressions between runs.
        with open(args.summary_out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True, default=str)
            f.write("\n")
    return summary


def _dispatch(args, model_dir, tokenizer) -> dict:
    if args.scenario == "fleet":
        return run_fleet(args, model_dir, tokenizer)

    if args.scenario == "disagg":
        return run_disagg(args, model_dir, tokenizer)

    if args.scenario == "multi-tenant":
        return run_multi_tenant(args, model_dir, tokenizer)

    if args.scenario == "replay":
        return run_replay(args, model_dir, tokenizer)

    if args.scenario == "diurnal":
        return run_diurnal(args, model_dir, tokenizer)

    if args.compare_spec:
        if not args._spec_model_dir:
            raise SystemExit("--compare-spec requires --speculative-size")
        if args.scenario != "rate-sweep":
            raise SystemExit(
                "--compare-spec only supports --scenario rate-sweep")
        return _compare_spec(args, model_dir, tokenizer)

    policies = [p.strip() for p in (args.scheduling_policy or "").split(",")
                if p.strip()]
    if len(policies) > 1:
        if args.scenario != "ttft-under-load":
            raise SystemExit(
                "--scheduling-policy accepts a comma-separated comparison "
                "axis only with --scenario ttft-under-load")
        return _compare_policies(args, model_dir, tokenizer, policies)
    return run_single(args, model_dir, tokenizer,
                      scheduling_policy=policies[0] if policies else None)


def run_single(args, model_dir, tokenizer, scheduling_policy=None) -> dict:
    proc = launch_server(model_dir, args,
                         scheduling_policy=scheduling_policy)
    base = f"http://127.0.0.1:{args.port}"
    api_url = base + "/v1/completions"
    model_name = f"dummy-{args.size}"
    summary = {"size": args.size, "input_len": args.input_len,
               "output_len": args.output_len,
               "num_prompts": args.num_prompts, "seed": args.seed,
               "max_num_seqs": args.max_num_seqs,
               "num_decode_steps": args.num_decode_steps,
               "quantization": args.quantization,
               "scheduling_policy": scheduling_policy or "fcfs",
               "sjf_starvation_s": args.sjf_starvation_s,
               "kv_cache_dtype": args.kv_cache_dtype, "results": []}
    try:
        wait_healthy(proc, base, args.init_timeout, args.server_log)

        requests = build_requests(args, tokenizer)
        # Warm-up: touch the *whole* (batch-bucket x block-width-bucket)
        # ladder before measuring. Trickled arrivals hit small batch
        # buckets (1, 2, 4, ...) that an all-at-once burst never
        # exercises, and mid-load concurrency (e.g. a steady 8 req/s
        # holding ~64 running) pairs those buckets with WIDER block
        # tables than short warm contexts produce — each combo is a
        # separate XLA executable, and a first-compile mid-measurement
        # stalls serving for tens of seconds (measured: one cold
        # (bs=64, width=32) decode compile collapsed a rate-8 run to
        # 188 tok/s). Warm outputs run past the first width-bucket
        # boundary (16 blocks) to cover both widths; the persistent
        # compile cache makes later boots fast.
        warm_out = max(args.output_len,
                       16 * args.block_size + 48 - args.input_len)
        # Never exceed the context limit (the server would reject the
        # request and abort the whole warm-up).
        warm_out = max(1, min(warm_out,
                              args.max_model_len - args.input_len - 1))
        warm = [(p, pl, warm_out) for p, pl, _ in requests]
        n_warm = 1
        while n_warm <= min(args.max_num_seqs, len(warm)):
            asyncio.run(run_benchmark("openai", api_url, model_name,
                                      warm[:n_warm], float("inf")))
            n_warm *= 2
        asyncio.run(run_benchmark(
            "openai", api_url, model_name,
            warm[:max(4, min(args.max_num_seqs, len(warm)))],
            float("inf")))

        if args.scenario == "ttft-under-load":
            m = run_ttft_under_load(args, api_url, model_name, tokenizer,
                                    requests)
            summary["results"].append(m)
            print(json.dumps({"serve_bench_ttft_under_load": m}),
                  flush=True)
        else:
            for rate_s in args.rates.split(","):
                rate = float(rate_s)
                elapsed, results = asyncio.run(run_benchmark(
                    "openai", api_url, model_name, requests, rate,
                    seed=args.seed))
                m = compute_metrics(results, elapsed)
                m["request_rate"] = rate_s
                summary["results"].append(m)
                print(json.dumps({"serve_bench_rate": rate_s, **m}),
                      flush=True)
        summary["observability"] = snapshot_observability(base)
        detail = snapshot_health_detail(base)
        # Structured warm-up outcome from the boot timeline: compiled
        # executable count + wall seconds, plus the machine-checked
        # "<30s warm-up" boot criterion.
        boot = detail.get("boot") or {}
        warmup = boot.get("warmup")
        summary["boot"] = boot
        summary["warmup_compile"] = (
            {**warmup, "under_30s": warmup.get("seconds", 1e9) < 30.0}
            if warmup else None)
        summary["slo"] = detail.get("slo") or {}
        summary["predictor"] = detail.get("predictor")
        # Spec-decode stats (acceptance rate, current K, verify waste)
        # from /health/detail; None when serving without a draft model.
        summary["spec"] = detail.get("spec")
        summary["device_telemetry"] = distill_device_telemetry(detail)
        summary["efficiency"] = snapshot_efficiency(base)
        summary["kernels"] = snapshot_kernels(base)
        summary["contention"] = distill_contention(detail)
        summary["numerics"] = distill_numerics(detail)
        summary["alerts"] = distill_alerts(snapshot_alerts(base))
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    print(json.dumps({"serve_bench_summary": summary}), flush=True)
    return summary


def make_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="Boot OpenAI server + sweep serving request rates.")
    p.add_argument("--size", type=str, default="7b",
                   help="dummy model size spec (see common.DUMMY_SIZES)")
    p.add_argument("--model-dir", type=str, default=None,
                   help="reuse an existing checkpoint dir")
    p.add_argument("--port", type=int, default=8077)
    p.add_argument("--rates", type=str, default="2,4,8,inf",
                   help="comma-separated requests/s (inf = all at once)")
    p.add_argument("--num-prompts", type=int, default=100)
    p.add_argument("--input-len", type=int, default=128)
    p.add_argument("--output-len", type=int, default=128)
    p.add_argument("--dataset", type=str, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-model-len", type=int, default=512)
    p.add_argument("--max-num-seqs", type=int, default=96)
    p.add_argument("--num-decode-steps", type=int, default=32)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-device-blocks", type=int, default=None)
    p.add_argument("--kv-cache-dtype", type=str, default="auto")
    p.add_argument("--quantization", type=str, default=None)
    p.add_argument("--init-timeout", type=float, default=1800.0)
    p.add_argument("--server-log", type=str,
                   default="/tmp/serve_bench_server.log")
    p.add_argument("--scenario", type=str, default="rate-sweep",
                   choices=["rate-sweep", "ttft-under-load", "fleet",
                            "disagg", "multi-tenant", "replay",
                            "diurnal"],
                   help="rate-sweep: Poisson sweep over --rates (the "
                        "default). ttft-under-load: start --num-prompts "
                        "short-prompt requests at once (steady decode "
                        "stream), inject one long-prompt probe after "
                        "--probe-delay, and report the probe's TTFT plus "
                        "the stream's P99 TPOT — the interference pair "
                        "chunked prefill is designed to improve. fleet: "
                        "boot --num-replicas demo servers behind the "
                        "multi-replica router, sweep --rates through the "
                        "router, and report per-replica SLO splits plus "
                        "the router's routing counters. disagg: A/B the "
                        "ttft-under-load workload on a disaggregated "
                        "fleet (1 prefill + --num-replicas decode "
                        "replicas) vs an equal-size mixed fleet, and "
                        "report the probe-TTFT/background-TPOT split "
                        "plus KV-transfer bytes/seconds and fleet "
                        "prefix-cache hit counts. multi-tenant: "
                        "--num-tenants LoRA tenants on one replica with "
                        "one hot tenant flooding; reports victim-tenant "
                        "TPOT p99 solo vs contention with fairness caps "
                        "on and off, per-tenant SLO splits, and adapter "
                        "churn counters (docs/multitenancy.md). "
                        "replay: re-issue a captured IWL1 workload "
                        "(--workload, from /debug/workload?format=iwl "
                        "or a rotated workload.jsonl) with the original "
                        "inter-arrival gaps; --replay-repeat N runs the "
                        "stream N times against one boot and checks the "
                        "server-side re-captures match (determinism). "
                        "diurnal: synthesize a seeded day-in-the-life "
                        "IWL1 stream (flash crowds, heavy-tailed "
                        "lengths, adapter churn) and replay it; "
                        "--emit-only just writes the file "
                        "(docs/observability.md).")
    p.add_argument("--num-replicas", type=int, default=2,
                   help="fleet scenario: engine replicas to launch; "
                        "disagg scenario: decode replicas per fleet")
    p.add_argument("--replica-base-port", type=int, default=8300,
                   help="fleet/disagg scenarios: replica i listens on "
                        "base+i")
    p.add_argument("--probe-input-len", type=int, default=None,
                   help="probe prompt length for ttft-under-load "
                        "(default: max-model-len - probe-output-len - 1)")
    p.add_argument("--probe-output-len", type=int, default=16)
    p.add_argument("--probe-delay", type=float, default=2.0,
                   help="seconds after the background burst before the "
                        "probe is sent")
    p.add_argument("--scheduling-policy", type=str, default=None,
                   help="pass --scheduling-policy to the server (fcfs | "
                        "sjf | sjf_remaining). With --scenario "
                        "ttft-under-load a comma-separated list (e.g. "
                        "'fcfs,sjf_remaining') runs the scenario once per "
                        "policy and prints an SLO comparison block")
    p.add_argument("--sjf-starvation-s", type=float, default=None,
                   help="pass --sjf-starvation-s to the server (SJF "
                        "aging deadline, seconds)")
    p.add_argument("--predictor-path", type=str, default=None,
                   help="pass --predictor-path to the server "
                        "(length-predictor checkpoint)")
    p.add_argument("--disable-chunked-prefill", action="store_true",
                   help="pass --disable-chunked-prefill to the server "
                        "(whole-prompt single-chunk admission)")
    p.add_argument("--max-num-batched-tokens", type=int, default=None,
                   help="pass --max-num-batched-tokens to the server "
                        "(per-step token budget; with chunked prefill "
                        "this caps mixed-step compute)")
    p.add_argument("--speculative-size", type=str, default=None,
                   help="dummy draft model size (see common.DUMMY_SIZES); "
                        "materializes a draft checkpoint and passes "
                        "--speculative-model to the server")
    p.add_argument("--num-speculative-tokens", type=int, default=4,
                   help="draft length K passed to the server with "
                        "--speculative-size")
    p.add_argument("--spec-k-min", type=int, default=None,
                   help="pass --spec-k-min to the server (adaptive-K "
                        "band floor)")
    p.add_argument("--spec-k-max", type=int, default=None,
                   help="pass --spec-k-max to the server (adaptive-K "
                        "band ceiling)")
    p.add_argument("--compare-spec", action="store_true",
                   help="with --speculative-size: run the rate sweep "
                        "twice (spec off, then on) and print a "
                        "serve_bench_spec_comparison block")
    p.add_argument("--num-tenants", type=int, default=4,
                   help="multi-tenant scenario: LoRA tenants to "
                        "register (adapters synthesized per tenant; "
                        "--max-loras is set to num-tenants - 1 so slot "
                        "churn is exercised)")
    p.add_argument("--hog-concurrency", type=int, default=40,
                   help="multi-tenant scenario: concurrent long-output "
                        "requests the hot tenant keeps in flight")
    p.add_argument("--hog-output-len", type=int, default=160,
                   help="multi-tenant scenario: output tokens per hog "
                        "request")
    p.add_argument("--hog-start-delay", type=float, default=1.5,
                   help="multi-tenant scenario: seconds after the "
                        "victim probes start before the hog floods "
                        "(victims must be resident for admission "
                        "fairness to see two tenants)")
    p.add_argument("--victim-requests", type=int, default=5,
                   help="multi-tenant scenario: sequential probe "
                        "requests per victim tenant per phase")
    p.add_argument("--victim-output-len", type=int, default=32,
                   help="multi-tenant scenario: output tokens per "
                        "victim probe request")
    p.add_argument("--tenant-hog-share-cap", type=float, default=0.2,
                   help="multi-tenant scenario: token_share_cap "
                        "registered for the hot tenant (0 disables)")
    p.add_argument("--workload", type=str, default=None,
                   help="replay scenario: IWL1 workload file to "
                        "re-issue (capture one from "
                        "/debug/workload?format=iwl)")
    p.add_argument("--speed", type=float, default=1.0,
                   help="replay scenario: time-compression factor for "
                        "recorded inter-arrival gaps (2.0 = replay "
                        "twice as fast)")
    p.add_argument("--replay-repeat", type=int, default=1,
                   help="replay scenario: run the stream N times "
                        "against one booted server and report whether "
                        "the server-side workload re-captures match "
                        "(the determinism check)")
    p.add_argument("--workload-out", type=str, default=None,
                   help="diurnal: where to write the synthesized IWL1 "
                        "stream (default /tmp/serve_bench_diurnal"
                        ".iwl.jsonl); replay: also save the last "
                        "server-side re-capture here")
    p.add_argument("--emit-only", action="store_true",
                   help="diurnal scenario: write the synthesized IWL1 "
                        "file and exit without booting a server")
    p.add_argument("--summary-out", type=str, default=None,
                   help="write the final summary dict as JSON to this "
                        "path (feed two of these to python -m "
                        "intellillm_tpu.tools.wdiff)")
    p.add_argument("--diurnal-duration", type=float, default=30.0,
                   help="diurnal scenario: seconds of simulated wall "
                        "time the synthesized arrivals span")
    p.add_argument("--diurnal-bursts", type=int, default=2,
                   help="diurnal scenario: number of gaussian flash "
                        "crowds mixed into the baseline arrival stream")
    return p


if __name__ == "__main__":
    main(make_arg_parser().parse_args())
