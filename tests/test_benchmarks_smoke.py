"""Benchmark-harness smoke tests: the serving harness must run end to
end on a tiny dummy engine (CPU via JAX_PLATFORMS)."""
import os
import subprocess
import sys

import pytest


def _run(args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + args, env=env,
                          capture_output=True, text=True, timeout=420)


def test_benchmark_serving_smoke():
    """serve_bench boots the OpenAI server (subprocess, dummy tiny model)
    and drives benchmark_serving's Poisson load generator through real
    HTTP — the whole north-star measurement path, minus the chip."""
    import json
    r = _run(["benchmarks/serve_bench.py", "--size", "tiny",
              "--num-prompts", "4", "--rates", "inf", "--input-len", "8",
              "--output-len", "8", "--max-model-len", "64",
              "--max-num-seqs", "4", "--num-decode-steps", "4",
              "--num-device-blocks", "64", "--port", "8733",
              "--init-timeout", "240"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    summary = None
    for line in r.stdout.splitlines():
        if line.startswith('{"serve_bench_summary"'):
            summary = json.loads(line)["serve_bench_summary"]
    assert summary is not None, r.stdout[-2000:]
    (m,) = summary["results"]
    assert m["completed"] == 4
    assert m["output_tok_s"] > 0
    assert m["ttft_percentiles_ms"]["p50"] > 0


def test_serve_bench_fleet_args_parse():
    """The fleet scenario's CLI surface stays wired (cheap guard; the
    full fleet boot lives in the slow smoke below)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.serve_bench import make_arg_parser
    args = make_arg_parser().parse_args(
        ["--scenario", "fleet", "--num-replicas", "3",
         "--replica-base-port", "9000"])
    assert args.scenario == "fleet"
    assert args.num_replicas == 3
    assert args.replica_base_port == 9000


@pytest.mark.slow
def test_serve_bench_fleet_smoke():
    """Fleet scenario end to end: 2 demo-server replicas behind the
    router, one rate through the router, per-replica SLO split + routing
    counters in the output."""
    import json
    r = _run(["benchmarks/serve_bench.py", "--size", "tiny",
              "--scenario", "fleet", "--num-replicas", "2",
              "--num-prompts", "4", "--rates", "inf", "--input-len", "8",
              "--output-len", "8", "--max-model-len", "64",
              "--max-num-seqs", "4", "--num-decode-steps", "4",
              "--num-device-blocks", "64", "--port", "8735",
              "--replica-base-port", "8741", "--init-timeout", "240",
              "--server-log", "/tmp/serve_bench_fleet.log"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    summary = None
    for line in r.stdout.splitlines():
        if line.startswith('{"serve_bench_summary"'):
            summary = json.loads(line)["serve_bench_summary"]
    assert summary is not None, r.stdout[-2000:]
    assert summary["scenario"] == "fleet"
    (m,) = summary["results"]
    assert m["completed"] == 4
    assert m["output_tok_s"] > 0
    per_replica = summary["per_replica_slo"]
    assert set(per_replica) == {"replica-0", "replica-1"}
    assert all("slo" in v for v in per_replica.values())
    router = summary["router"]["metrics"]
    # Warm-up (2x4) + measured (4) requests all went through the router.
    assert sum(router["requests_total"].values()) >= 12
    assert sum(router["decisions"].values()) >= 12
    assert all(v == 1.0 for v in router["replica_healthy"].values())
    # Fleet-aggregated alert state from the router's /debug/alerts: the
    # bench asserts "no page fired" the same way an operator would.
    alerts = summary["alerts"]
    assert alerts["fleet_aggregated"] is True
    assert alerts["page_firing"] is False


def test_serve_bench_multi_tenant_args_parse():
    """The multi-tenant scenario's CLI surface stays wired."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.serve_bench import make_arg_parser
    args = make_arg_parser().parse_args(
        ["--scenario", "multi-tenant", "--num-tenants", "4",
         "--hog-concurrency", "12", "--tenant-hog-share-cap", "0.3",
         "--hog-start-delay", "0.5"])
    assert args.scenario == "multi-tenant"
    assert args.num_tenants == 4
    assert args.hog_concurrency == 12
    assert args.tenant_hog_share_cap == 0.3
    assert args.hog_start_delay == 0.5


@pytest.mark.slow
def test_serve_bench_multi_tenant_smoke():
    """Multi-tenant scenario end to end (docs/multitenancy.md): 3 LoRA
    tenants on one tiny replica, hot-loaded adapters, a small hog, and
    the per-tenant SLO split + isolation block in the summary. Tiny
    sizes — this smoke proves the wiring, not the 2x isolation bound
    (that's the full CPU acceptance run's job)."""
    import json
    r = _run(["benchmarks/serve_bench.py", "--size", "tiny",
              "--scenario", "multi-tenant", "--num-tenants", "3",
              "--hog-concurrency", "4", "--hog-output-len", "24",
              "--hog-start-delay", "0.2",
              "--victim-requests", "2", "--victim-output-len", "8",
              "--input-len", "8", "--max-model-len", "64",
              "--max-num-seqs", "4", "--num-decode-steps", "4",
              "--num-device-blocks", "128", "--port", "8737",
              "--init-timeout", "240",
              "--server-log", "/tmp/serve_bench_mt.log"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    summary = None
    for line in r.stdout.splitlines():
        if line.startswith('{"serve_bench_summary"'):
            summary = json.loads(line)["serve_bench_summary"]
    assert summary is not None, r.stdout[-2000:]
    assert summary["scenario"] == "multi-tenant"
    assert summary["num_tenants"] == 3
    assert summary["hog"] == "tenant-1"
    phases = summary["victim_latency"]
    assert set(phases) == {"victim_solo", "contention_caps_on",
                           "contention_caps_off"}
    for phase in phases.values():
        assert phase["tpot_ms"]["n"] > 0
        assert phase["tpot_ms"]["p99"] is not None
        # Per-tenant SLO split: both victim tenants measured.
        assert set(phase["per_tenant_tpot_ms"]) == {"tenant-2", "tenant-3"}
    iso = summary["isolation"]
    assert set(iso["victim_tpot_p99_ms"]) == {"solo", "caps_on", "caps_off"}
    assert all(v is not None for v in iso["victim_tpot_p99_ms"].values())
    # Adapter churn counters from the caps-on run's /health/detail.
    churn = iso["adapter_churn"]
    assert set(churn) == {"tenant-1", "tenant-2", "tenant-3"}
    assert sum(c["loads"] or 0 for c in churn.values()) >= 3
    # Per-tenant stats block made it into the snapshot.
    stats = (summary["tenants_caps_on"] or {}).get("stats") or {}
    assert any(t.startswith("tenant-") for t in stats)


def test_serve_bench_replay_args_parse():
    """The replay/diurnal CLI surface stays wired (cheap guard; the
    full capture->replay roundtrip lives in the slow smoke below)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.serve_bench import make_arg_parser
    args = make_arg_parser().parse_args(
        ["--scenario", "replay", "--workload", "/tmp/w.iwl.jsonl",
         "--speed", "2.0", "--replay-repeat", "2",
         "--summary-out", "/tmp/s.json"])
    assert args.scenario == "replay"
    assert args.workload == "/tmp/w.iwl.jsonl"
    assert args.speed == 2.0
    assert args.replay_repeat == 2
    args = make_arg_parser().parse_args(
        ["--scenario", "diurnal", "--emit-only", "--seed", "7",
         "--diurnal-duration", "5", "--diurnal-bursts", "3",
         "--workload-out", "/tmp/d.iwl.jsonl"])
    assert args.scenario == "diurnal"
    assert args.emit_only and args.diurnal_bursts == 3


def test_diurnal_synth_is_seed_deterministic():
    """Same --seed => byte-identical synthesized workload (the property
    the replay determinism check stands on), different seed => a
    different stream. In-process: no tokenizer, no server."""
    import argparse
    import json as json_mod

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.serve_bench import synth_diurnal
    from intellillm_tpu.obs.workload import dump_iwl, parse_iwl

    def make_args(seed):
        return argparse.Namespace(
            seed=seed, num_prompts=32, diurnal_duration=10.0,
            diurnal_bursts=2, num_tenants=4, input_len=64,
            output_len=32, max_model_len=512)

    a = synth_diurnal(make_args(11))
    b = synth_diurnal(make_args(11))
    assert json_mod.dumps(a) == json_mod.dumps(b)
    c = synth_diurnal(make_args(12))
    assert json_mod.dumps(a) != json_mod.dumps(c)
    assert len(a) == 32
    # Arrivals sorted, lengths inside the context window, adapters churn.
    ts = [r["ts"] for r in a]
    assert ts == sorted(ts) and ts[-1] <= 10.0
    assert all(r["prompt_len"] + r["sampling"]["max_tokens"]
               < 512 for r in a)
    assert len({r["adapter"] for r in a}) > 1
    # The emitted document round-trips as IWL1.
    header, recs = parse_iwl(dump_iwl(a, source="diurnal"))
    assert header["requests"] == 32
    assert [r["id"] for r in recs] == [r["id"] for r in a]


@pytest.mark.slow
def test_serve_bench_replay_roundtrip_smoke():
    """The acceptance path end to end on CPU: synthesize a diurnal
    workload, replay it twice against one booted server, and require
    bit-identical server-side re-captures (replay_deterministic), then
    gate the summary through wdiff against itself (exit 0)."""
    import json
    import tempfile

    out_dir = tempfile.mkdtemp(prefix="replay-smoke-")
    summary_path = os.path.join(out_dir, "summary.json")
    r = _run(["benchmarks/serve_bench.py", "--size", "tiny",
              "--scenario", "diurnal", "--num-prompts", "6",
              "--input-len", "8", "--output-len", "8",
              "--diurnal-duration", "2", "--diurnal-bursts", "1",
              "--max-model-len", "64", "--max-num-seqs", "4",
              "--num-decode-steps", "4", "--num-device-blocks", "64",
              "--replay-repeat", "2", "--seed", "5", "--port", "8735",
              "--init-timeout", "240",
              "--workload-out", os.path.join(out_dir, "d.iwl.jsonl"),
              "--summary-out", summary_path])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    summary = json.load(open(summary_path))
    assert summary["scenario"] == "replay"
    assert summary["num_requests"] == 6
    assert summary["replay_deterministic"] is True
    assert len(set(summary["recapture_digests"])) == 1
    assert all(m["completed"] == 6 for m in summary["results"])
    assert all(m["recapture"]["count"] == 6 for m in summary["results"])
    # wdiff gates on the snapshot: identical inputs must pass (exit 0).
    w = _run(["-m", "intellillm_tpu.tools.wdiff", summary_path,
              summary_path])
    assert w.returncode == 0, w.stdout + w.stderr
    assert "PASS" in w.stdout
