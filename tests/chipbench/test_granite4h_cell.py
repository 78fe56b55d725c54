"""The cell `granite4h-ep4-decode-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says, within the guide's floors, and
the reference's share is the configuration's; the benchmark's copy of the
reference is the repo's, byte for byte; `costs_ssd.py` counts what a hand
count gives; the metric files read what they say; the rehearsal runs the
cell; and `reference_check_granite4h.py` runs its control flow on the CPU.
Membership is asserted, never a position in a list and never a count of
cells: the next addition may stand anywhere."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_ssd, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "granite4h-ep4-decode-closed", "granite-4.0-h-small-ep4-l10"
NEW_METRICS = ("ssm_dev_share", "granite4h_moe_dev_share",
               "ssm_step_rows_share", "ssm_state_bytes_per_row_step",
               "ssm_state_pool_gb")
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size", "ep_size"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
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
    # the other cells' shapes and kernels are not this configuration's
    assert not {"moe_dev_share", "axk1_moe_dev_share", "lfm2_moe_dev_share",
                "lfm2_conv_dev_share", "solar2_moe_dev_share",
                "linear_attn_dev_share", "linear_attn_step_rows_share",
                "state_bytes_per_row_step", "state_pool_gb"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share", "moe_held_assign_share",
                 "state_slot_allocs_in_window", "attn_dev_share",
                 "device_idle"):
        assert name in per_layer
    # The five device-clock metrics of PR 37 list the five older cells and
    # `tests/chipbench/test_device_clock_metrics.py` holds their lists to
    # exactly those: this cell is not appended there (PERF.md section 7).
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    published = doc["published"]
    assert set(published) == set(REDUCED)
    assert published["num_hidden_layers"] == 40
    assert published["layer_types"] == PERIOD * 4
    assert (published["num_local_experts"], published["vocab_size"],
            published["ep_size"]) == (72, 100352, 1)
    # the floors: a whole period, at least 8 experts held, at least an
    # eighth of the vocabulary
    assert (doc["num_hidden_layers"], doc["layer_types"]) == (10, PERIOD)
    assert doc["num_local_experts"] >= 8
    assert doc["vocab_size"] * 8 >= 100352
    # one of 4 chips: a quarter of the experts and of the vocabulary, the
    # router's width and top-10 as published
    assert (doc["num_local_experts"], doc["ep_size"], doc["ep_rank"]) == (
        18, 4, 0)
    assert doc["num_local_experts"] * doc["ep_size"] == 72
    assert doc["vocab_size"] * 4 == 100352
    assert doc["num_experts_per_tok"] == 10
    # every published width, and the four multipliers
    assert (doc["hidden_size"], doc["mamba_n_heads"], doc["mamba_d_head"],
            doc["mamba_d_state"], doc["mamba_d_conv"]) == (
                4096, 128, 64, 128, 4)
    assert (doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["intermediate_size"], doc["shared_intermediate_size"]) == (
                32, 8, 768, 1536)
    assert (doc["embedding_multiplier"], doc["residual_multiplier"],
            doc["attention_multiplier"], doc["logits_scaling"]) == (
                12, 0.22, 0.0078125, 16)
    for key in ("ep_rank", "time_step_limit", "gated_norm", "state_dtype",
                "state_layout", "mamba_chunk_size", "intermediate_size",
                "router", "fused_projections", "seeded_init",
                "checkpoint_names", "architectures", "torch_dtype"):
        assert key in doc["assumed"]
    assert "One of 4 chips that share each layer" in doc["deployment"]
    assert "17.8" in doc["deployment"] and "38.2 MB" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    assert serve["flags"] == ["--num-device-blocks-override", "9000",
                              "--max-num-seqs", "128"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "granitemoehybrid" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps every structural feature
    assert tiny["layer_types"] == ["mamba", "mamba", "attention", "mamba"]
    assert tiny["num_hidden_layers"] == 4
    assert tiny["mamba_n_heads"] * tiny["mamba_d_head"] == (
        tiny["mamba_expand"] * tiny["hidden_size"])
    assert (tiny["num_local_experts"], tiny["ep_size"],
            tiny["num_experts_per_tok"]) == (8, 2, 4)
    assert tiny["mamba_conv_bias"] and tiny["tie_word_embeddings"]


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    assert entry["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    # ep_size is this engine's key: the published model is the uncut one
    assert "ep_size" not in row["config"]
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|d_head|d_state|"
                       r"num_experts_per_tok|expand|expansion")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_benchmarks_reference_is_the_repos_and_holds_the_share():
    mine = os.path.join(CHIP_DIR, "references",
                        "granite_4_0_h_small_ep4_l10_reference.py")
    repos = os.path.join(REPO, "tests", "models",
                         "granitemoehybrid_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)
    from tests.models import granitemoehybrid_reference as ref
    doc = MANIFEST.config(CONFIG)
    assert list(ref.held_experts(doc)) == list(range(18))
    assert list(ref.held_experts(dict(doc, ep_rank=3))) == list(
        range(54, 72))


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%_ssd_step_call.3 = custom-call(f32[128,64,128], "
         "f32[129,64,128,128])", 0.40, 4),
        ("%_ssd_chunk_call.1 = custom-call(f32[512,64,128])", 0.10, 4),
        ("%fusion.1 = f32[18,768,128]{2,1,0} fusion(...)", 0.05, 4),
        ("%fusion.2 = bf16[18,128,768]{2,1,0} fusion(bf16[18,768,4096] "
         "%p)", 0.05, 4),
        ("%_grouped_gate_up_call.2 = custom-call(...)", 0.03, 4),
        ("%_grouped_down_call.2 = custom-call(...)", 0.02, 4),
        ("%_paged_attention_call.2 = custom-call(...)", 0.02, 4),
        # the shared MLP (1,536 wide) and the down-projection's output
        ("%fusion.7 = bf16[128,1536]{1,0} fusion(...)", 0.03, 4),
        ("%fusion.9 = bf16[128,4096]{1,0} fusion(...)", 0.30, 4)]}
    ctx = {"trace": trace}
    read = lambda name, c=ctx: readers.read_metric(             # noqa: E731
        MANIFEST.metric_file(name), c)
    assert read("ssm_dev_share") == pytest.approx(50.0)
    assert read("granite4h_moe_dev_share") == pytest.approx(15.0)
    assert read("attn_dev_share") == pytest.approx(2.0)

    def prom(step, chunk, rows):
        return readers.parse_prom(
            f'intellillm_linear_attn_rows_total{{form="step",'
            f'program="decode_cont"}} {step}\n'
            f'intellillm_linear_attn_rows_total{{form="chunk",'
            f'program="mixed"}} {chunk}\n'
            f'intellillm_dispatch_state_bytes_total{{program="decode_cont"}}'
            f' {rows * 75497472}\n'
            f'intellillm_dispatch_state_bytes_total{{program="mixed"}} 7\n'
            f'intellillm_dispatch_row_steps_total{{program="decode_cont"}}'
            f' {rows}\n'
            f'intellillm_dispatch_row_steps_total{{program="mixed"}} 9\n')
    ctx = {"prom_start": prom(100, 100, 10), "prom_end": prom(700, 500, 90),
           "detail_end": {"boot": {"cache": {"bytes": {
               "kv": 1, "state": 4890230784}}}}}
    assert read("ssm_step_rows_share", ctx) == pytest.approx(60.0)
    assert read("ssm_state_bytes_per_row_step", ctx) == pytest.approx(
        75497472.0)
    assert read("ssm_state_pool_gb", ctx) == pytest.approx(4.890230784)
    # a program without the counters or the key (the parent): nothing, not
    # an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom(""), "detail_end": {},
             "trace": None}
    for name in NEW_METRICS:
        assert read(name, empty) is None


def test_costs_against_hand_counts():
    """128 state channels x 8,192 channels float32: 4 MiB a sequence and
    layer; a token and state element is 5 operations; the step form moves
    every live row's state in and out."""
    assert costs_ssd.state_bytes(8192, 128) == 4 * 1024**2
    assert costs_ssd.ssd_flops(128, 8192, 128) == 5 * 128 * 8192 * 128
    assert costs_ssd.ssd_chunk_flops(400, 8192, 128) == 5 * 400 * 8192 * 128
    assert costs_ssd.vector_bytes(128, 8192, 128) == 4 * 128 * (
        3 * 8192 + 128 * 128)
    assert costs_ssd.ssd_step_bytes(128, 8192, 128) == (
        2 * 128 * 4 * 1024**2 + costs_ssd.vector_bytes(128, 8192, 128))
    # 9 layers of it are the state traffic the program's counter reports
    assert 9 * 2 * costs_ssd.state_bytes(8192, 128) == 75497472
    assert costs_ssd.ssd_chunk_bytes(448, 4, 8192, 128, from_zero=2) == (
        6 * 4 * 1024**2 + costs_ssd.vector_bytes(448, 8192, 128))


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
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 41),
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
    out of the engine, the reference layer by layer (the scan a token at a
    time), the comparison, and wrong references that are seen to be
    wrong."""
    r, lines = _run("reference_check_granite4h.py", "--seed", "3000000041",
                    "--rehearse-on-cpu", "--check", "4", "--perturb",
                    "no_residual_multiplier,usual_attention_scale,"
                    "drop_skip_D,drop_filter_bias")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("no_residual_multiplier", "usual_attention_scale",
                "drop_skip_D", "drop_filter_bias"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8 and engine["state_slots"] == 16
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
