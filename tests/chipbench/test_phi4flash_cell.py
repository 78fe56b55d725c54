"""The cell `phi4flash-cot-closed` and what it brought: the manifest resolves
its configuration, traffic, reference and metric files; the configuration is
the catalog's entry with NOTHING cut; the byte counts the cell was sized by
follow from the file through `ModelConfig`; the traffic is ISSUE 52's; the
benchmark's copy of the reference is the repo's, byte for byte;
`costs_phi4flash.py` counts what a hand count gives; the metric files read
what they say; the cell and `reference_check_phi4flash.py` run their control
flow on the CPU. Membership is asserted, never a position in a list and never
a count of cells: the next addition may stand anywhere."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_phi4flash as costs
from harness import loadgen, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG, TRAFFIC = ("phi4flash-cot-closed", "phi-4-mini-flash-reasoning",
                         "cot-closed")
NEW_METRICS = ("phi4flash_scan_dev_share", "phi4flash_window_attn_dev_share",
               "shared_kv_walk_share", "phi4flash_state_bytes_per_row_step",
               "phi4flash_head_dev_share")
APPENDED = ("rows_per_step", "preemptions_in_window", "pad_share",
            "hbm_peak_gb", "fetch_wait_ms_per_step", "idle_dispatch_share",
            "state_slot_allocs_in_window", "setup_pre_engine_s",
            "setup_weights_s", "setup_first_calls_s",
            "setup_first_calls_after_boot_s", "setup_trace_s",
            "setup_lower_s", "setup_compile_s", "setup_cache_read_s",
            "setup_introspect_s", "setup_stage_accounted_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = Manifest()
RUN = os.path.join(CHIP_DIR, "run.py")


def test_manifest_resolves_the_cell_and_its_files():
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    e2e = MANIFEST.metrics_of_cell(CELL, "end_to_end")
    assert set(e2e) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    per_layer = set(MANIFEST.metrics_of_cell(CELL, "per_layer"))
    assert set(NEW_METRICS) | set(APPENDED) <= per_layer
    assert {"attn_dev_share", "device_idle", "compiles_in_window",
            "decode_ctx_tokens_per_row"} <= per_layer
    # the other cells' shapes and kernels are not this configuration's; the
    # five device-clock lists and the lists a cell's own test holds to that
    # cell (`window_*`, `ssm_*`) stay theirs
    assert not {"moe_dev_share", "ssm_dev_share", "ssm_state_pool_gb",
                "ssm_state_bytes_per_row_step", "window_attn_dev_share",
                "window_attended_share", "window_blocks_recycled_in_window",
                "mixer_state_bytes_share", "mhc_dev_share",
                "device_idle_window", "decode_dev_ms_per_step",
                "mixed_dev_ms_per_step"} & per_layer
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms" and entry["layer"] == "kernels"
        doc = MANIFEST.metric_file(name)
        assert doc["reader"] in readers.READERS
        if doc["reader"] == "trace_op_share":
            assert len(doc["regex_why"]) > 80
    for path in ("references/phi_4_mini_flash_reasoning_reference.py",
                 "reference_check_phi4flash.py",
                 "harness/costs_phi4flash.py"):
        assert os.path.exists(os.path.join(CHIP_DIR, path)), path


def test_configuration_cuts_nothing():
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["reduced"] == [] and doc["reduced"] == []
    assert doc["published"] == {}
    assert doc["num_hidden_layers"] == 32
    for key in ("architectures", "mamba_sizes", "mamba_form", "layer_layout",
                "memory", "cross_layers", "differential_attention",
                "stored_head", "position_encoding", "norms_and_biases",
                "state_dtype", "state_layout", "seeded_init",
                "checkpoint_names", "torch_dtype", "checked_against"):
        assert key in doc["assumed"], key
    for said in ("reduced = []", "7.71 GB", "1.31 GB", "2.62 GB", "0.21 GB",
                 "11.85 GB", "16.8 GB"):
        assert said in doc["deployment"], said
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    # one table width (max_model_len / block): one program a row bucket
    assert serve["flags"] == ["--num-device-blocks-override", "16000",
                              "--max-num-seqs", "64",
                              "--min-block-table-width", "256"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "phi4flash" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps the rule's five kinds of layer, the window, the
    # paired heads and whole lane tiles of scan channels
    assert tiny["num_hidden_layers"] == 8 and tiny["sliding_window"] == 32
    assert (tiny["num_attention_heads"], tiny["num_key_value_heads"]) == (
        8, 4)
    assert (2 * tiny["hidden_size"]) % 128 == 0
    assert tiny["mb_per_layer"] == hf["mb_per_layer"] == 2


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal: nothing is reduced."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    for key, value in row["config"].items():
        assert doc[key] == value, key


def test_the_issues_byte_counts_follow_from_the_file(tmp_path):
    """3,853 M parameters, 5,120 B of K/V a token a layer, 4,000 window
    blocks, 3.23 MB of state a slot, 16 walkers of which 7 hold nothing: from
    the configuration's file through `ModelConfig`, the model's own shapes
    and the cache engine's sizes."""
    import numpy as np

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig)
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.worker.cache_engine import CacheEngine
    doc = MANIFEST.config(CONFIG)
    (tmp_path / "config.json").write_text(json.dumps(
        hf_config_of(doc, rehearsal=False)))
    mc = ModelConfig(model=str(tmp_path), dtype="bfloat16",
                     load_format="dummy", max_model_len=4096)
    kinds = mc.get_layer_cache_kinds()
    assert [kinds.count(k) for k in ("state", "kv_window", "kv")] == [9, 8, 1]
    entries = mc.get_layer_cache_entries()
    assert entries.count(None) == 7                       # the GMUs
    assert entries.count(entries[17]) == 8                # layer 17 + 7 cross
    assert mc.get_cache_walkers() == (8, 8, 7)
    assert mc.get_cache_row_shape() == (2, 10, 128)
    assert CacheEngine.get_layer_block_size(16, "auto", mc) == 16 * 5_120
    assert mc.get_state_arrays() == (((3, 5120), "bfloat16"),
                                     ((40, 16, 128), "float32"))
    assert CacheEngine.get_state_slot_size(mc) == 9 * (327_680 + 30_720)
    assert 2 * 9 * 327_680 == 5_898_240        # a decode row-substep
    cc = CacheConfig(num_device_blocks_override=16000)
    cc.window_ahead_tokens, cc.num_device_blocks = 512, 16000
    cc.num_cpu_blocks = 0
    cc.size_window_group(mc)
    assert cc.num_window_device_blocks == 4000
    # a primary block brings a quarter of a block in each window layer
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig(), 512) == 16 * 5_120 * 3
    model = get_model_class(mc.hf_config.architectures)(mc)
    shapes = model._param_shapes()
    total = sum(int(np.prod(s)) for layer in shapes["layers"]
                for s in layer.values())
    total += sum(int(np.prod(shapes[k]))
                 for k in ("embed_tokens", "norm_w", "norm_b"))
    assert 3_852e6 < total < 3_855e6                      # 7.71 GB in bf16
    by_kind = costs.weight_bytes()
    assert abs(sum(by_kind.values()) - 2 * total) < 2e6   # but the biases


def test_traffic_is_the_issues():
    t = MANIFEST.traffic(TRAFFIC)
    assert (t["loop"], t["clients"], t["block"], t["queue"]) == (
        "closed", 64, 16, 2048)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.3, "min": 512, "max": 2048}
    assert t["output_len"] == {"dist": "lognormal", "median": 896,
                               "sigma": 0.12, "min": 704, "max": 1152}
    anchors = t["first_wave"]["anchors"]
    assert len(anchors) == 64
    assert anchors[:63] == [{"prompt_len": 1024, "output_len": 18 * (k + 1)}
                            for k in range(63)]
    assert anchors[63] == {"prompt_len": 2048, "output_len": 1152}
    ragdoc = MANIFEST.traffic("ragdoc-closed")
    assert t["ramp_until"] == ragdoc["ramp_until"]
    assert t["trace"] == ragdoc["trace"] and t["ramp_s"] == 60.0
    # every prompt crosses the window in its own prefill and stays, with
    # its output and the K-step's overshoot, under the model length
    pairs = loadgen.length_pairs(t, t["block"])
    assert min(p for p, _ in pairs) > 512
    assert max(p + o for p, o in pairs) + 8 < 4096
    assert 2048 + 1152 + 8 < 4096
    # the warm set: every row bucket, at the one table width the
    # configuration serves with
    width = int(MANIFEST.config(CONFIG)["serve"]["flags"][-1])
    assert width == 4096 // 16
    shapes = set()
    for step in t["warm"]:
        (n, ) = step["prompts"]
        assert 512 < n and -(-n // 16) <= width
        last = n % 512
        shapes.add(next(b for b in (16, 32, 64, 128, 256, 512)
                        if last <= b))
        shapes.add(512)
    assert shapes == {16, 32, 64, 128, 256, 512}
    assert "rehearsal" in t


def test_the_benchmarks_reference_is_the_repos():
    mine = os.path.join(CHIP_DIR, "references",
                        "phi_4_mini_flash_reasoning_reference.py")
    repos = os.path.join(REPO, "tests", "models", "phi4flash_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_costs_count_what_a_hand_count_gives():
    assert costs.state_bytes() == 327_680
    assert costs.sscan_flops(64) == 7 * 64 * 5120 * 16
    # a 64-row substep: every state in and out, dt, u and y rows, B and C
    # columns padded to a lane tile, A once
    assert costs.sscan_step_bytes(64) == (
        2 * 64 * 327_680 + 4 * 64 * (3 * 5120 + 16 * 128) + 4 * 5120 * 16)
    # a 512-row chunk of one run from position 0 writes its state once
    assert costs.sscan_chunk_bytes(512, 1, from_zero=1) == (
        327_680 + costs.vector_bytes(512))
    walks = costs.row_walk_bytes(1536)
    assert walks["full_own"] == 5_120 * 1536
    assert walks["full_shared"] == 7 * walks["full_own"]
    assert walks["window"] == 8 * 5_120 * (512 + 16)
    assert 60 < costs.shared_walk_share(1536) < 70
    step = costs.token_step_bytes(64, 1500)
    total = sum(step.values())
    assert 13.0e9 < total < 13.8e9
    assert 0.55 < step["weights"] / total < 0.60
    assert 0.27 < step["full_walks"] / total < 0.31
    assert 0.09 < step["window_walks"] / total < 0.12
    assert 0.02 < step["scan_state"] / total < 0.04


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%_sscan_step_call.3 = custom-call(f32[64,40,128], "
         "f32[65,40,16,128])", 0.03, 4),
        ("%_sscan_chunk_call.1 = custom-call(f32[512,40,128])", 0.01, 4),
        ("%_paged_attention_call.2 = custom-call(...)", 0.20, 4),
        ("%_paged_attention_call_window.2 = custom-call(...)", 0.08, 4),
        ("%_ragged_call_window.5 = custom-call(...)", 0.02, 4),
        ("%fusion.3 = f32[64,200064]{1,0} fusion(bf16[200064,2560] %h)",
         0.06, 4),
        ("%fusion.9 = bf16[64,20480]{1,0} fusion(...)", 0.60, 4)]}
    ctx = {"trace": trace}
    read = lambda name, c=ctx: readers.read_metric(             # noqa: E731
        MANIFEST.metric_file(name), c)
    assert read("phi4flash_scan_dev_share") == pytest.approx(4.0)
    assert read("phi4flash_window_attn_dev_share") == pytest.approx(10.0)
    assert read("phi4flash_head_dev_share") == pytest.approx(6.0)
    assert read("attn_dev_share") == pytest.approx(30.0)

    def prom(shared, walk, state, steps):
        series = (("shared_kv_walk_bytes", shared), ("kv_walk_bytes", walk),
                  ("state_bytes", state), ("row_steps", steps))
        return readers.parse_prom("".join(
            f'intellillm_dispatch_{name}_total{{program="decode_cont"}} {v}\n'
            f'intellillm_dispatch_{name}_total{{program="mixed"}} 7\n'
            for name, v in series))
    ctx = {"prom_start": prom(100, 200, 0, 0),
           "prom_end": prom(750, 1200, 5_898_240 * 50, 50)}
    assert read("shared_kv_walk_share", ctx) == pytest.approx(65.0)
    assert read("phi4flash_state_bytes_per_row_step", ctx) == 5_898_240
    # a program without the spans and counters (the parent): nothing, not
    # an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom(""), "detail_end": {},
             "trace": None}
    for name in NEW_METRICS:
        assert read(name, empty) is None


def _lines(r):
    return [json.loads(x) for x in r.stdout.splitlines()
            if x.startswith("{")]


def test_the_cell_rehearses():
    """run.py end to end on the CPU backend at the tiny preset: both block
    groups and the slots under a closed loop of four clients, the probes in
    the window, no executable new in it, and the two counter metrics this
    cell brought read off the server."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "5200000007",
         "--seconds", "6", "--trace", "1", "--rehearse-on-cpu"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = _lines(r)
    last = lines[-1]
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    values = last["rehearsal_values"]
    assert {"shared_kv_walk_share", "phi4flash_state_bytes_per_row_step",
            "state_slot_allocs_in_window", "preemptions_in_window"} <= set(
                values)
    # 3 scans x [16, 256] float32, in and out
    assert values["phi4flash_state_bytes_per_row_step"]["value"] == 98_304
    assert 0 < values["shared_kv_walk_share"]["value"] < 50
    by_event = {x["event"]: x for x in lines[:-1] if "event" in x}
    groups = by_event["booted"]["cache"]["groups"]
    assert (groups["kv"]["layers"], groups["kv"]["readers"]) == (1, 2)
    assert groups["kv_window"]["window"] == 32
    assert by_event["window"]["executables_new_in_window"] == []
    assert by_event["probes"]["ok"] and by_event["probes"]["in_window"] >= 1


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine, weights
    out of the engine as a view, the reference, the comparison, and a wrong
    reference that is seen to be wrong."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR,
                                      "reference_check_phi4flash.py"),
         "--seed", "5200000041", "--rehearse-on-cpu", "--check", "3",
         "--perturb", "cross_own_kv,memory_after_gate"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    last = _lines(r)[-1]
    assert last["rehearsal"] is True and last["sharp"] is True
    assert last["agrees"] is True and last["problems"] == []
    assert last["engine"]["max_abs"] < 1e-4          # float32 preset
    assert last["engine"]["values"] >= 100
    for how in ("cross_own_kv", "memory_after_gate"):
        assert last["wrong"][how]["breaks"] == ["max_abs"]
