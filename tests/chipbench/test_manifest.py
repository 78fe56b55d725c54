"""BENCHMARK.json against the builder's contract and the data files."""
import json
import os
import re

import pytest
from chipbench import CHIP_DIR, REPO

from harness import loadgen, readers
from harness.manifest import Manifest, ManifestError
from harness.server import BENCH_KEYS, hf_config_of

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH_KEYS = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"num_experts_per_tok|expansion")

MANIFEST = Manifest()
DOC = MANIFEST.doc
CELLS = sorted(MANIFEST.cells)
CONFIGS = sorted(MANIFEST.config_entries)
ALL_METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(MANIFEST.path) <= 64 * 1024
    assert isinstance(DOC["run_seconds"], int)
    assert 1 <= DOC["run_seconds"] <= 51
    assert 1 <= len(DOC["paths"]) <= 16 and len(DOC["command"]) <= 32
    for p in DOC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    # The command names no file outside `paths`.
    script = DOC["command"][1]
    assert any(script.startswith(p + "/") for p in DOC["paths"])
    assert os.path.exists(os.path.join(REPO, script))


def test_every_file_under_paths_has_a_contract_name():
    for p in DOC["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert PATH.match(rel), rel


def test_shares_of_cells_and_chips():
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    assert 1 <= len(DOC["workloads"]) <= 24 and 1 <= len(DOC["configs"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in DOC["workloads"]}
    assert used == set(CONFIGS), "every configuration is used by some cell"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_entry_and_its_files(cell):
    w = MANIFEST.cell(cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(w[key]), w[key]
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    config = MANIFEST.config(w["config"])
    traffic = MANIFEST.traffic(w["traffic"])
    assert config["name"] == w["config"] and traffic["name"] == w["traffic"]
    assert traffic["loop"] in ("open", "closed")
    e2e = MANIFEST.metrics_of_cell(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(MANIFEST.metrics_of_cell(cell, "per_layer")) >= 1
    for name in e2e + MANIFEST.metrics_of_cell(cell, "per_layer"):
        if name != "setup_s":
            assert MANIFEST.metric_file(name)["reader"] in readers.READERS


@pytest.mark.parametrize("config", CONFIGS)
def test_config_entry_and_reduction(config):
    entry = MANIFEST.config_entries[config]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(entry["source"]) <= 200
    assert entry["source"].startswith("https://")
    assert any(entry["file"].startswith(p + "/") for p in DOC["paths"])
    assert len(entry["reduced"]) <= 16
    doc = MANIFEST.config(config)
    assert doc["source"] == entry["source"]
    # `reduced` lists exactly the keys whose published value the file
    # records as different from the value it runs.
    assert set(doc["published"]) == set(entry["reduced"])
    for key, published in doc["published"].items():
        assert doc[key] != published
        assert NAME.match(key) and not WIDTH_KEYS.search(key), key
    hf = hf_config_of(doc, rehearsal=False)
    assert not set(hf) & set(BENCH_KEYS)
    assert hf["architectures"] and hf["vocab_size"] > 3
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("metric", [m["name"] for m in ALL_METRICS])
def test_metric_entry(metric):
    m = next(x for x in ALL_METRICS if x["name"] == metric)
    is_e2e = metric in MANIFEST.end_to_end
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if is_e2e else {"layer", "moves"}
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if is_e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        # `moves` is an end-to-end metric reported by every cell that
        # reports this metric.
        assert m["moves"] in MANIFEST.end_to_end
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in MANIFEST.metrics_of_cell(cell, "end_to_end")
    for cell in m.get("workloads", []):
        assert cell in MANIFEST.cells
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


def test_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in ALL_METRICS]
    assert len(set(names)) == len(names)
    assert DOC["end_to_end"] and "setup_s" in MANIFEST.end_to_end
    assert MANIFEST.end_to_end["setup_s"]["bound"] <= 0.1
    assert 1 <= len(DOC["end_to_end"]) <= 16
    assert 1 <= len(DOC["per_layer"]) <= 128


def test_unknown_names_are_errors():
    with pytest.raises(ManifestError):
        MANIFEST.cell("no-such-cell")
    with pytest.raises(ManifestError):
        MANIFEST.traffic("no-such-mix")
    with pytest.raises(ManifestError):
        MANIFEST.metric_file("no-such-metric")


def test_a_later_pr_adds_a_cell_with_files_only(tmp_path):
    """A fourth, test-only cell: a configuration, a traffic mix and a
    per-layer metric that exist only as files in another directory and as
    entries of a manifest; no file of the benchmark is touched."""
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    base = MANIFEST.config("mistral-7b-v0.2")
    base.update(name="test-config", num_hidden_layers=2,
                published={"num_hidden_layers": 32})
    (tmp_path / "configs" / "test-config.json").write_text(json.dumps(base))
    mix = dict(MANIFEST.traffic("chat-steady"), name="test-mix",
               rate_rps=3.0, ramp_s=1.0)
    (tmp_path / "traffic" / "test-mix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "test_steps.json").write_text(json.dumps({
        "name": "test_steps", "reader": "prom_delta",
        "series": {"name": "intellillm_step_time_seconds_count"}}))
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({
        "name": "test-config", "source": base["source"],
        "file": "benchmarks/chip/configs/test-config.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    doc["workloads"].append({"name": "test-cell", "config": "test-config",
                             "traffic": "test-mix", "chips": 1,
                             "why": "test"})
    doc["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms",
                              "better": "lower", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["test-cell"]})
    doc["per_layer"].append({"name": "test_steps", "unit": "count",
                             "better": "higher",
                             "source": "program_counter",
                             "layer": "engine step loop",
                             "moves": "ttft_p95_ms",
                             "workloads": ["test-cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    m = Manifest(str(path), [str(tmp_path)])
    cell = m.cell("test-cell")
    assert m.config(cell["config"])["num_hidden_layers"] == 2
    traffic = m.traffic(cell["traffic"])
    assert traffic["loop"] == "open" and traffic["rate_rps"] == 3.0
    assert traffic["prompt_len"]["median"] == 512
    plan = loadgen.open_plan(traffic, 1, 10.0)
    assert sum(r.phase == "window" for r in plan) == 30
    assert m.metrics_of_cell("test-cell", "end_to_end") == [
        "tpot_p50_ms", "setup_s", "ttft_p95_ms"]
    assert "test_steps" in m.metrics_of_cell("test-cell", "per_layer")
    assert "test_steps" not in m.metrics_of_cell(CELLS[0], "per_layer")
    ctx = {"prom_start": readers.parse_prom(
               "intellillm_step_time_seconds_count 5\n"),
           "prom_end": readers.parse_prom(
               "intellillm_step_time_seconds_count 47\n")}
    assert readers.read_metric(m.metric_file("test_steps"), ctx) == 42.0
    # ... and the benchmark's own directory is as it was.
    assert not os.path.exists(os.path.join(CHIP_DIR, "traffic",
                                           "test-mix.json"))
