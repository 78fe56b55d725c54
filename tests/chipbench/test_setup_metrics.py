"""The ten set-up metrics (PR 50): each is `health_json` on `/health/detail`
at the run's end, which is the set-up's own sum because `compiles_in_window`
is 0.

data/setup_detail.json is the `boot` block of `/health/detail` at the end of
a warm-cache `falconh1-decode-closed` run on the chip (the builder's run
b50_f_change2, PR 50: `setup_s` 116.1), its `calls` cut to the first three
and of its `cache` block only the bytes. The expectations are read off that
file by hand.
"""
import json
import os
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, DATA_DIR, REPO

from harness import readers
from harness.manifest import Manifest

MANIFEST = Manifest()
CELLS = {"dsmoe16b-decode-closed", "mistral7b-decode-closed",
         "axk1-ep16-decode-closed", "lfm2moe-decode-closed",
         "smallthinker-ragdoc-closed", "solar2-ep8-decode-closed",
         "granite4h-ep4-decode-closed", "xing4-ep8-decode-closed",
         "falconh1-decode-closed"}
# name -> (layer, unit, better, what the fixture reads)
METRICS = {
    "setup_pre_engine_s": ("engine boot", "s", "lower", 33.499),
    "setup_weights_s": ("engine boot", "s", "lower", 10.196),
    "setup_first_calls_s": ("runner", "s", "lower", 73.116646),
    "setup_first_calls_after_boot_s": ("runner", "s", "lower", 73.116646),
    "setup_trace_s": ("runner", "s", "lower", 25.691443),
    "setup_lower_s": ("runner", "s", "lower", 36.129757),
    "setup_compile_s": ("runner", "s", "lower", 9.212214),
    "setup_cache_read_s": ("runner", "s", "lower", 16.632784),
    "setup_introspect_s": ("runner", "s", "lower", 0.339713),
    "setup_stage_accounted_share": ("runner", "%", "higher", 97.9112),
}


@pytest.fixture(scope="module")
def detail():
    with open(os.path.join(DATA_DIR, "setup_detail.json")) as f:
        return {"boot": json.load(f)}


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_its_number_from_health_detail(name, detail):
    file = MANIFEST.metric_file(name)
    assert file["reader"] == "health_json" and file["name"] == name
    value = readers.read_metric(file, {"detail_end": detail})
    assert value == pytest.approx(METRICS[name][3], rel=1e-9)


def test_the_fixtures_sums_agree(detail):
    """What the ten read hangs together in the recorded run."""
    def read(name):
        return readers.read_metric(MANIFEST.metric_file(name),
                                   {"detail_end": detail})

    setup = detail["boot"]["setup"]
    phases = setup["phases_s"]
    assert read("setup_pre_engine_s") == pytest.approx(
        phases["imports"] + phases["runtime_init"] + phases["engine_init"]
        + setup["unphased_s"], abs=2e-3)
    assert read("setup_weights_s") == pytest.approx(
        phases["init_model"] + phases["load_model"], abs=2e-3)
    tracked = [p for name, p in setup["by_program"].items()
               if name != "untracked"]
    assert read("setup_first_calls_s") == pytest.approx(
        sum(p["wall_s"] + p["by_stage_s"]["introspect"] for p in tracked),
        abs=1e-4)
    assert read("setup_first_calls_after_boot_s") == pytest.approx(
        read("setup_first_calls_s") - setup["first_calls"]["in_boot_s"],
        abs=1e-4)
    staged = sum(v for p in tracked for k, v in p["by_stage_s"].items()
                 if k != "rest")
    assert read("setup_stage_accounted_share") == pytest.approx(
        staged / read("setup_first_calls_s") * 100.0, abs=1e-3)
    # On the chip the instrumentation's check held.
    assert read("setup_stage_accounted_share") >= 90.0
    assert setup["unphased_s"] <= 2.0


@pytest.mark.parametrize("name", METRICS)
def test_manifest_entry_moves_setup_s_in_the_nine_cells(name):
    entry = MANIFEST.per_layer[name]
    layer, unit, better, _ = METRICS[name]
    assert (entry["layer"], entry["unit"], entry["better"]) == (
        layer, unit, better)
    assert entry["moves"] == "setup_s"
    assert entry["source"] == "program_span"
    # Contains, never equals: the next cell's PR appends itself.
    assert CELLS <= set(entry["workloads"])
    for cell in CELLS:
        assert name in MANIFEST.metrics_of_cell(cell, "per_layer")


def test_a_server_without_the_record_reports_nothing_and_does_not_raise():
    """The parent commit's /health/detail: three phases, no `setup`."""
    parent = {"boot": {"phases_s": {"weights_load": 14.2, "cache_init": 3.1,
                                    "warmup_compile": 7.0},
                       "total_s": 24.3, "complete": True}}
    for name in METRICS:
        value = readers.read_metric(MANIFEST.metric_file(name),
                                    {"detail_end": parent})
        assert value == (14.2 if name == "setup_weights_s" else None)


def test_a_rehearsal_prints_the_ten():
    """`run.py --rehearse-on-cpu --trace 1` of one cell: the control flow
    from the server's record to the result line, at the tiny preset."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "run.py"), "--workload",
         "mistral7b-decode-closed", "--seed", "3000000053", "--seconds", "3",
         "--trace", "1", "--rehearse-on-cpu"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    result = lines[-1]
    assert result["rehearsal"] is True and result["metrics"] == {}
    values = {k: v["value"] for k, v in result["rehearsal_values"].items()}
    assert set(METRICS) <= set(values)
    assert values["compiles_in_window"] == 0
    booted = next(x for x in lines if x.get("event") == "booted")
    for phase in ("imports", "engine_init", "runtime_init", "init_model",
                  "load_model", "weights_load", "cache_init",
                  "warmup_compile", "serve_start"):
        assert phase in booted["boot_phases_s"], phase
    assert values["setup_weights_s"] == booted["boot_phases_s"][
        "weights_load"]
    # The CPU has no warm-up and no introspection: every first call is
    # paid after the boot.
    assert values["setup_first_calls_s"] > 0
    assert values["setup_first_calls_after_boot_s"] == pytest.approx(
        values["setup_first_calls_s"])
    assert values["setup_introspect_s"] == 0
    assert values["setup_trace_s"] > 0 and values["setup_lower_s"] > 0
    assert values["setup_compile_s"] > 0
    assert 50.0 < values["setup_stage_accounted_share"] <= 100.0
    window = next(x for x in lines if x.get("event") == "window")
    assert values["setup_first_calls_s"] == pytest.approx(
        sum(window["compile_time_seconds"].values()), rel=1e-3)
    # The harness's `booted` is the server's `total_s` plus what the
    # harness spent before it spawned the child and one health poll.
    assert 0 < values["setup_pre_engine_s"] < booted["wall_s"]
