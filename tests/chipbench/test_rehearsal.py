"""run.py end to end on the CPU backend at the configurations' tiny
presets: control flow and the shape of the output, never a device number.
Without the rehearsal flag a machine with no accelerator is refused."""
import json
import os
import subprocess
import sys

import pytest
from chipbench import REPO

from harness.manifest import Manifest

RUN = os.path.join(REPO, "benchmarks", "chip", "run.py")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, RUN, *args], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(x) for x in r.stdout.splitlines()]
    return r, lines


def test_no_accelerator_no_result():
    r, lines = run("--workload", "mistral7b-decode-closed", "--seed", "1",
                   "--seconds", "3", "--trace", "0", timeout=60)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_unknown_workload_is_refused():
    r, lines = run("--workload", "nope", "--seed", "1", "--seconds", "3",
                   "--trace", "0", "--rehearse-on-cpu", timeout=60)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "unknown workload" in r.stderr


def check_shape(r, lines, manifest, cell, group):
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert RESULT_KEYS <= set(last)
    assert last["rehearsal"] is True
    # A CPU run's numbers never stand under a device metric's name.
    assert last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    why = [x for x in lines[:-1] if x.get("event") in ("incorrect", "window")]
    assert last["correct"] is True and last["failed"] == 0, \
        json.dumps(why)[:3000]
    assert last["attempted"] > 0
    names = set(manifest.metrics_of_cell(cell, group))
    assert set(last["rehearsal_values"]) <= names
    for m in last["rehearsal_values"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    events = [x.get("event") for x in lines[:-1]]
    for ev in ("planned", "booted", "probes_before", "window_open",
               "probes_after", "window", "probes"):
        assert ev in events, ev
    by_event = {x["event"]: x for x in lines[:-1]}
    assert by_event["probes"]["ok"]
    assert all(by_event["probes"]["tokens_equal_before_after"])
    # the probes also ran inside the window, and their logprobs agree
    # with the alone run
    probes = by_event["probes"]
    assert probes["in_window"] >= 1
    in_window = [r for r in probes["comparisons"] if r["against"] == "window"]
    assert len(in_window) == probes["in_window"]
    assert all(r["compared"] >= 1
               and r["max_abs_dlogprob"] <= probes["logprob_tol"]
               for r in in_window)
    assert all(len(lp) == 16 for lp in probes["logprobs_in_window"])
    # asking for the chosen token's logprob adds no program variant
    assert all("('logprob_k', 1)" in e
               for e in by_event["window_open"]["executables"])
    assert by_event["window"]["executables_new_in_window"] == []
    return last, by_event


def test_closed_loop_cell_rehearses():
    manifest = Manifest()
    cell = "mistral7b-decode-closed"
    r, lines = run("--workload", cell, "--seed", str(2**31 + 5),
                   "--seconds", "5", "--trace", "0", "--rehearse-on-cpu")
    last, by_event = check_shape(r, lines, manifest, cell, "end_to_end")
    assert {"out_tok_s", "tpot_p50_ms", "setup_s"} == set(
        last["rehearsal_values"])
    assert by_event["planned"]["loop"] == "closed"
    assert by_event["window"]["requests_finished"] == last["attempted"]


def test_open_loop_cell_rehearses_with_a_trace(tmp_path):
    """An open-loop cell that exists only in a test manifest (the
    benchmark has none yet), traced: the per-layer group, and a trace
    with no device plane leaves the trace metrics out."""
    doc = json.loads(json.dumps(Manifest().doc))
    doc["workloads"].append({
        "name": "test-chat", "config": "mistral-7b-v0.2",
        "traffic": "chat-steady", "chips": 1, "why": "test"})
    doc["end_to_end"].append({
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": ["test-chat"]})
    doc["per_layer"] += [
        {"name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "scheduler",
         "moves": "ttft_p95_ms", "workloads": ["test-chat"]},
        {"name": "loadgen_late_p99_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "client", "moves": "ttft_p95_ms",
         "workloads": ["test-chat"]}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    manifest = Manifest(str(path))
    r, lines = run("--workload", "test-chat", "--seed", "11", "--seconds",
                   "6", "--trace", "1", "--rehearse-on-cpu",
                   "--manifest", str(path))
    last, by_event = check_shape(r, lines, manifest, "test-chat",
                                 "per_layer")
    vals = last["rehearsal_values"]
    assert {"queue_wait_p95_ms", "loadgen_late_p99_ms", "host_ms_per_step",
            "compiles_in_window"} <= set(vals)
    assert vals["compiles_in_window"]["value"] == 0.0
    assert vals["loadgen_late_p99_ms"]["value"] < 250.0
    # no device plane on a CPU: the trace readers return nothing
    assert not {"device_idle", "attn_dev_share"} & set(vals)
    assert "breakdown" not in last and "busy_s" not in last["device"]
    assert by_event["trace"]["error"].startswith("no plane matches")
    assert by_event["window"]["requests_due"] == 36      # 6 req/s x 6 s
    assert last["attempted"] == 36
