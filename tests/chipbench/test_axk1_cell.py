"""The cell `axk1-ep16-decode-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says; the benchmark's copy of the
reference is the repo's, byte for byte; the rehearsal runs the cell; and
`reference_check.py` runs its control flow on the CPU."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_mla, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "axk1-ep16-decode-closed", "ax-k1-ep16-l7"
NEW_METRICS = ("axk1_moe_dev_share", "moe_held_assign_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = Manifest()


def test_manifest_resolves_the_cell_and_its_files():
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-closed", 1)
    assert MANIFEST.traffic(cell["traffic"])["clients"] == 128
    e2e = MANIFEST.metrics_of_cell(CELL, "end_to_end")
    assert set(e2e) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    per_layer = MANIFEST.metrics_of_cell(CELL, "per_layer")
    assert set(NEW_METRICS) <= set(per_layer)
    assert "moe_dev_share" not in per_layer     # the other MoE cell's shapes
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share"):
        assert MANIFEST.per_layer[name]["workloads"][-1] == CELL


def test_new_entries_stand_at_the_end_of_their_lists():
    doc = MANIFEST.doc
    assert doc["configs"][-1]["name"] == CONFIG
    assert doc["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in doc["per_layer"][-len(NEW_METRICS):]] == list(
        NEW_METRICS)


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    assert doc["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840, "ep_size": 1}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["vocab_size"], doc["ep_size"], doc["ep_rank"]) == (
                7, 12, 20480, 16, 0)
    # the router's published width = held x ep_size
    assert doc["n_routed_experts"] * doc["ep_size"] == 192
    # the floors a model_config PR keeps to
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 4
    assert doc["n_routed_experts"] >= 8
    assert doc["vocab_size"] * 8 >= doc["published"]["vocab_size"]
    for key in ("topk_method", "rope_pairing", "rms_norm_eps_on_latents"):
        assert key in doc["assumed"]
    assert "one of 16 chips" in doc["deployment"].lower()
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    assert "--num-device-blocks-override" in serve["flags"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "axk1" and hf["ep_size"] == 16


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|num_experts_per_tok|"
                       r"expansion")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_benchmarks_reference_is_the_repos():
    mine = os.path.join(CHIP_DIR, "references",
                        "ax_k1_ep16_l7_reference.py")
    repos = os.path.join(REPO, "tests", "models", "axk1_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%fusion.1 = f32[12,2048,128]{2,1,0} fusion(...)", 0.30, 4),
        ("%_ragged_call_mla.3 = custom-call(...)", 0.05, 4),
        ("%_paged_attention_call_mla.7 = custom-call(...)", 0.10, 4),
        ("%_paged_attention_call.2 = custom-call(...)", 0.02, 4),
        ("%fusion.9 = f32[64,1408,128]{2,1,0} fusion(...)", 0.53, 4)]}
    ctx = {"trace": trace}
    read = lambda name: readers.read_metric(                    # noqa: E731
        MANIFEST.metric_file(name), ctx)
    assert read("axk1_moe_dev_share") == pytest.approx(30.0)
    # the attention metric the benchmark had counts the latent kernels by
    # their prefix: the cell needs no attention metric of its own
    assert read("attn_dev_share") == pytest.approx(17.0)
    assert "attn_dev_share" in MANIFEST.metrics_of_cell(CELL, "per_layer")
    assert read("moe_dev_share") == pytest.approx(53.0)
    prom = lambda here, absent: readers.parse_prom(              # noqa: E731
        f'intellillm_moe_assignments_total{{held="here"}} {here}\n'
        f'intellillm_moe_assignments_total{{held="absent"}} {absent}\n')
    ctx = {"prom_start": prom(10, 100), "prom_end": prom(70, 1000)}
    assert readers.read_metric(MANIFEST.metric_file(
        "moe_held_assign_share"), ctx) == pytest.approx(6.25)
    # a program without the counter (the parent): nothing, not an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom("")}
    assert readers.read_metric(MANIFEST.metric_file(
        "moe_held_assign_share"), empty) is None


def test_latent_walk_costs():
    """A.X-K1's decode walk: 2 x (576 + 512) x 64 operations and 640 x 2
    bytes a context token a row."""
    assert costs_mla.stored_width(512, 64) == 640
    assert costs_mla.latent_walk_flops([340], 64, 512, 64) == (
        2 * (576 + 512) * 64 * 340)
    assert costs_mla.latent_walk_bytes([340, 10], 512, 64, 2) == (
        640 * 2 * 350)
    assert costs_mla.latent_write_bytes(3, 16, 512, 64, 2) == (
        2 * 3 * 16 * 640 * 2)
    assert costs_mla.query_bytes(128, 64, 512, 64) == 128 * 64 * 2 * 1152


def _run(script, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(CHIP_DIR, script),
                        *args], env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=timeout)
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    return r, lines


def test_the_cell_rehearses():
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 26),
                    "--seconds", "4", "--trace", "0", "--rehearse-on-cpu")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["rehearsal_values"]) == {"out_tok_s", "tpot_p50_ms",
                                             "setup_s"}
    by_event = {x["event"]: x for x in lines[:-1] if "event" in x}
    assert by_event["window"]["executables_new_in_window"] == []
    assert {"mixed", "decode_fused", "decode_cont"} <= set(
        by_event["window"]["compiles"])
    assert by_event["probes"]["ok"]


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine, weights
    out of the engine, the reference layer by layer, the comparison, and a
    wrong reference that is seen to be wrong."""
    r, lines = _run("reference_check.py", "--seed", "3000000026",
                    "--rehearse-on-cpu", "--check", "4", "--perturb",
                    "drop_shared_expert,drop_mscale")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("drop_shared_expert", "drop_mscale"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
