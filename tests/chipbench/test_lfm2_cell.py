"""The cell `lfm2moe-decode-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says; the benchmark's copy of the
reference is the repo's, byte for byte; `costs_lfm2.py` counts what a hand
count gives; the rehearsal runs the cell; and `reference_check_lfm2.py` runs
its control flow on the CPU. Membership is asserted, never position: the
next addition may stand anywhere in the lists."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_lfm2, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "lfm2moe-decode-closed", "lfm2-8b-a1b-l14"
NEW_METRICS = ("lfm2_moe_dev_share", "lfm2_conv_dev_share",
               "state_slot_allocs_in_window")
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
    # the other MoE cells' shapes are not this configuration's
    assert not {"moe_dev_share", "axk1_moe_dev_share",
                "moe_held_assign_share"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share", "attn_dev_share", "device_idle"):
        assert name in per_layer
    assert sum(w["chips"] == 4 for w in MANIFEST.doc["workloads"]) == 0
    assert len(MANIFEST.doc["workloads"]) == 4


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    published = doc["published"]
    assert set(published) == {"num_hidden_layers", "layer_types"}
    assert published["num_hidden_layers"] == 24
    assert len(published["layer_types"]) == 24
    assert [i for i, t in enumerate(published["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    # layers 0-13 as published: 2 dense conv + three whole periods
    assert doc["num_hidden_layers"] == 14
    assert doc["layer_types"] == published["layer_types"][:14]
    assert doc["layer_types"].count("full_attention") == 3
    assert doc["num_dense_layers"] == 2
    # the floors a model_config PR keeps to: every expert, whole vocabulary
    assert (doc["num_experts"], doc["num_experts_per_tok"],
            doc["vocab_size"]) == (32, 4, 65536)
    assert doc["num_hidden_layers"] - doc["num_dense_layers"] >= 4
    for key in ("head_dim", "tie_word_embeddings", "routing",
                "rope_pairing", "conv_filter", "checkpoint_names",
                "architectures", "torch_dtype"):
        assert key in doc["assumed"]
    assert "first 14 layers" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    assert serve["flags"] == ["--num-device-blocks-override", "9000"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "lfm2_moe" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps every structural feature
    assert tiny["num_hidden_layers"] == 6 and tiny["num_dense_layers"] == 2
    assert tiny["layer_types"][:6].count("full_attention") == 1
    assert (tiny["num_experts"], tiny["num_experts_per_tok"]) == (8, 4)
    assert tiny["hidden_size"] // tiny["num_attention_heads"] == 16


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
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
                        "lfm2_8b_a1b_l14_reference.py")
    repos = os.path.join(REPO, "tests", "models", "lfm2_moe_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%fusion.1 = f32[32,1792,128]{2,1,0} fusion(...)", 0.40, 4),
        ("%fusion.2 = bf16[32,128,1792]{2,1,0} fusion(bf16[32,1792,2048] "
         "%p)", 0.20, 4),
        ("%_short_conv_call.3 = custom-call(...)", 0.03, 4),
        ("%fusion.4 = bf16[128,6144]{1,0} fusion(...)", 0.05, 4),
        ("%_paged_attention_call.2 = custom-call(...)", 0.06, 4),
        ("%_ragged_call.5 = custom-call(...)", 0.04, 4),
        ("%fusion.9 = bf16[128,2048]{1,0} fusion(...)", 0.22, 4)]}
    ctx = {"trace": trace}
    read = lambda name: readers.read_metric(                    # noqa: E731
        MANIFEST.metric_file(name), ctx)
    assert read("lfm2_moe_dev_share") == pytest.approx(60.0)
    assert read("lfm2_conv_dev_share") == pytest.approx(8.0)
    # the attention metric the benchmark had reads the same kernels' names
    assert read("attn_dev_share") == pytest.approx(10.0)
    prom = lambda n: readers.parse_prom(                         # noqa: E731
        f'intellillm_state_slot_events_total{{event="alloc"}} {n}\n'
        f'intellillm_state_slot_events_total{{event="free"}} {n - 3}\n')
    ctx = {"prom_start": prom(100), "prom_end": prom(1000)}
    assert readers.read_metric(MANIFEST.metric_file(
        "state_slot_allocs_in_window"), ctx) == pytest.approx(900.0)
    # a program without the counter (the parent): nothing, not an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom("")}
    assert readers.read_metric(MANIFEST.metric_file(
        "state_slot_allocs_in_window"), empty) is None


def test_costs_against_hand_counts():
    """A 64-wide head stored at 128 lanes: operations over 64, bytes over
    128; the convolution's step per row and channel: 7 operations, 7
    two-byte values and the filter."""
    assert costs_lfm2.stored_head(64) == 128
    assert costs_lfm2.paged_flops([340], 32, 64) == 4 * 32 * 64 * 340
    assert costs_lfm2.paged_bytes([340, 10], 8, 64, 2) == (
        2 * 8 * 128 * 2 * 350)
    assert costs_lfm2.ragged_write_bytes(3, 16, 8, 64, 2) == (
        2 * 2 * 3 * 8 * 16 * 128 * 2)
    assert costs_lfm2.query_bytes(128, 32, 64) == 2 * 128 * 32 * 128 * 2
    assert costs_lfm2.short_conv_flops(128, 2048) == 128 * 2048 * 7
    assert costs_lfm2.short_conv_bytes(128, 2048) == (
        128 * 2048 * 2 * 7 + 8 * 2048 * 4)
    assert costs_lfm2.short_conv_state_bytes(512, 2048) == (
        2 * 512 * 2 * 2048 * 2)
    assert costs_lfm2.conv_mixer_matmul_flops(128, 2048) == (
        2 * 128 * 2048 * 4 * 2048)
    assert costs_lfm2.conv_mixer_weight_bytes(2048) == 4 * 2048 * 2048 * 2


def _run(script, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(CHIP_DIR, script),
                        *args], env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=timeout)
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    return r, lines


def test_the_cell_rehearses():
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 30),
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
    r, lines = _run("reference_check_lfm2.py", "--seed", "3000000030",
                    "--rehearse-on-cpu", "--check", "4", "--perturb",
                    "drop_oldest_tap,drop_qk_norm")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("drop_oldest_tap", "drop_qk_norm"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8 and engine["state_slots"] == 16
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
