"""The cell `ling3flash-ep8-ragdoc-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says, and the reference's share is the
configuration's; the benchmark's copy of the reference is the repo's, byte
for byte; `costs_ling3.py` counts what a hand count gives; the metric files
read what they say; the rehearsal runs the cell; and
`reference_check_ling3.py` runs its control flow on the CPU. Membership is
asserted, never a position in a list and never a count of cells: the next
addition may stand anywhere."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_kda, costs_ling3, costs_mla, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "ling3flash-ep8-ragdoc-closed", "ling-3.0-flash-ep8-l12"
NEW_METRICS = ("ling3_kda_dev_share", "ling3_moe_dev_share",
               "ling3_linear_attn_step_rows_share",
               "ling3_state_bytes_per_row_step",
               "ling3_mixer_state_bytes_share")
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size", "ep_size",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = Manifest()


def test_manifest_resolves_the_cell_and_its_files():
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ragdoc-closed", 1)
    traffic = MANIFEST.traffic(cell["traffic"])
    assert traffic["clients"] == 16
    assert (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]) == (
        4608, 7168)
    assert (traffic["output_len"]["min"], traffic["output_len"]["max"]) == (
        368, 704)
    e2e = MANIFEST.metrics_of_cell(CELL, "end_to_end")
    assert set(e2e) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    per_layer = MANIFEST.metrics_of_cell(CELL, "per_layer")
    assert set(NEW_METRICS) <= set(per_layer)
    # the other cells' shapes, kernels' readers and state are not this
    # configuration's
    assert not {"moe_dev_share", "axk1_moe_dev_share", "solar2_moe_dev_share",
                "smallthinker_moe_dev_share", "linear_attn_dev_share",
                "state_bytes_per_row_step", "mixer_state_bytes_share",
                "window_attn_dev_share", "state_pool_gb"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["layer"] == "kernels"
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share", "moe_held_assign_share",
                 "state_slot_allocs_in_window", "attn_dev_share",
                 "device_idle", "gc_pause_share", "setup_compile_s",
                 "setup_weights_s", "setup_stage_accounted_share"):
        assert name in per_layer
    # The five device-clock metrics of PR 37 list the five older cells and
    # `tests/chipbench/test_device_clock_metrics.py` holds their lists to
    # exactly those: this cell is not appended there (PERF.md section 7).
    assert "device_idle_window" not in per_layer
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    published = doc["published"]
    assert set(published) == REDUCED == set(doc["reduced"])
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"], published["ep_size"]) == (
                42, 512, 157184, 1)
    assert len(published["expert_swiglu_limit_list"]) == 42
    assert len(published["share_expert_swiglu_limit_list"]) == 42
    # two whole periods behind the two dense layers; no clamp among them
    assert (doc["num_hidden_layers"], doc["layer_group_size"],
            doc["first_k_dense_replace"]) == (12, 6, 2)
    assert doc["expert_swiglu_limit_list"] == [0] * 12
    assert doc["share_expert_swiglu_limit_list"] == [0] * 12
    assert not any(published["expert_swiglu_limit_list"][:12])
    assert not any(published["share_expert_swiglu_limit_list"][:12])
    # one of 8 chips: an eighth of the experts (one routing group) and of
    # the vocabulary, the router's width, groups and top-8 as published
    assert (doc["num_experts"], doc["ep_size"], doc["ep_rank"]) == (64, 8, 0)
    assert doc["num_experts"] * doc["ep_size"] == 512
    assert doc["num_experts"] == 512 // doc["n_group"]
    assert doc["vocab_size"] * 8 == 157184
    assert (doc["num_experts_per_tok"], doc["n_group"], doc["topk_group"],
            doc["num_shared_experts"]) == (8, 8, 4, 1)
    for key in ("ep_rank", "layer_rule", "kda_gate", "write_strength",
                "kda_details", "mla_details", "head_gate", "router",
                "swiglu_limits", "state_dtype", "mtp", "seeded_init",
                "checkpoint_names", "architectures", "torch_dtype"):
        assert key in doc["assumed"]
    assert "One of 8 chips that share each layer" in doc["deployment"]
    assert "9.47 GB" in doc["deployment"] and "21.7 MB" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 8192)
    flags = dict(zip(serve["flags"][::2], serve["flags"][1::2]))
    assert flags["--num-device-blocks-override"] == "16000"
    assert flags["--max-num-seqs"] == "16"
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "bailing_hybrid" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps every structural feature
    assert (tiny["num_hidden_layers"], tiny["layer_group_size"],
            tiny["first_k_dense_replace"]) == (12, 6, 2)
    assert (tiny["num_experts"], tiny["ep_size"], tiny["n_group"],
            tiny["topk_group"], tiny["num_experts_per_tok"]) == (4, 4, 4, 2,
                                                                 4)
    assert (tiny["num_attention_heads"], tiny["head_dim"],
            tiny["kv_lora_rank"], tiny["qk_rope_head_dim"],
            tiny["vocab_size"]) == (4, 32, 32, 16, 1024)


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    assert set(entry["reduced"]) == REDUCED
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    # ep_size is this engine's key: the published model is the uncut one
    assert "ep_size" not in row["config"]
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|num_experts_per_tok|"
                       r"expansion")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_config_class_describes_the_cells_caches(tmp_path):
    """At the published widths and the cell's depth: latent blocks of
    2,560 B a token beside state slots of 21.7 MB a sequence."""
    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.worker.cache_engine import CacheEngine
    doc = MANIFEST.config(CONFIG)
    (tmp_path / "config.json").write_text(json.dumps(
        hf_config_of(doc, rehearsal=False)))
    mc = ModelConfig(str(tmp_path), dtype="bfloat16", load_format="dummy",
                     max_model_len=8192)
    assert mc.get_layer_cache_kinds() == (["state"] * 5 + ["kv"]) * 2
    assert mc.get_cache_row_shape() == (1, 1, 640)
    assert CacheEngine.get_cache_block_size(
        16, "auto", mc, ParallelConfig()) == 16 * 2 * 640 * 2
    assert CacheEngine.get_state_slot_size(mc) == 10 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 21708800


def test_the_benchmarks_reference_is_the_repos_and_holds_the_share():
    mine = os.path.join(CHIP_DIR, "references",
                        "ling_3_0_flash_ep8_l12_reference.py")
    repos = os.path.join(REPO, "tests", "models",
                         "bailing_hybrid_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)
    from tests.models import bailing_hybrid_reference as ref
    doc = MANIFEST.config(CONFIG)
    assert list(ref.held_experts(doc)) == list(range(64))
    assert list(ref.held_experts(dict(doc, ep_rank=7))) == list(
        range(448, 512))
    assert [i for i in range(12) if ref.is_latent_layer(doc, i)] == [5, 11]
    assert [i for i in range(12) if not ref.is_moe_layer(doc, i)] == [0, 1]


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%_kda_step_call.3 = custom-call(f32[16,32,128], "
         "f32[17,32,128,128])", 0.05, 4),
        ("%_kda_chunk_call.1 = custom-call(f32[32,512,128])", 0.20, 4),
        ("%fusion.2 = bf16[64,128,768]{2,1,0} fusion(bf16[64,768,2560] "
         "%p)", 0.04, 4),
        ("%_grouped_gate_up_call.2 = custom-call(...)", 0.10, 4),
        ("%_grouped_down_call.2 = custom-call(...)", 0.06, 4),
        ("%_ragged_call_mla.2 = custom-call(...)", 0.07, 4),
        ("%_paged_attention_call_mla.2 = custom-call(...)", 0.03, 4),
        ("%fusion.9 = bf16[512,2560]{1,0} fusion(...)", 0.45, 4)]}
    ctx = {"trace": trace}
    read = lambda name, c=ctx: readers.read_metric(             # noqa: E731
        MANIFEST.metric_file(name), c)
    assert read("ling3_kda_dev_share") == pytest.approx(25.0)
    assert read("ling3_moe_dev_share") == pytest.approx(20.0)
    assert read("attn_dev_share") == pytest.approx(10.0)

    def prom(step, chunk, rows, walk):
        return readers.parse_prom(
            f'intellillm_linear_attn_rows_total{{form="step",'
            f'program="decode_cont"}} {step}\n'
            f'intellillm_linear_attn_rows_total{{form="chunk",'
            f'program="mixed"}} {chunk}\n'
            f'intellillm_dispatch_state_bytes_total{{program="decode_cont"}}'
            f' {rows * 41943040}\n'
            f'intellillm_dispatch_state_bytes_total{{program="mixed"}} 7\n'
            f'intellillm_dispatch_kv_walk_bytes_total{{program='
            f'"decode_cont"}} {walk}\n'
            f'intellillm_dispatch_kv_walk_bytes_total{{program="mixed"}} 5\n'
            f'intellillm_dispatch_row_steps_total{{program="decode_cont"}}'
            f' {rows}\n'
            f'intellillm_dispatch_row_steps_total{{program="mixed"}} 9\n')
    # 80 row-substeps at 6,144 tokens of context: 2,560 B a token
    walk = 80 * 6144 * 2560
    ctx = {"prom_start": prom(100, 1000, 10, 0),
           "prom_end": prom(180, 1920, 90, walk), "detail_end": {}}
    assert read("ling3_linear_attn_step_rows_share", ctx) == pytest.approx(
        8.0)
    assert read("ling3_state_bytes_per_row_step", ctx) == pytest.approx(
        41943040.0)
    assert read("ling3_mixer_state_bytes_share", ctx) == pytest.approx(
        100 * 41943040 / (41943040 + 6144 * 2560))
    # a program without the counters (the parent): nothing, not an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom(""), "detail_end": {},
             "trace": None}
    for name in NEW_METRICS:
        assert read(name, empty) is None


def test_costs_against_hand_counts():
    """32 heads of 128 x 128 float32: 2 MiB a sequence and layer; ten
    layers in and out are the state traffic the program's counter reports;
    a latent row is 640 lanes x 2 B; the weights are the issue's 9.47 GB."""
    assert costs_kda.state_bytes(32, 128, 128) == 2 * 1024**2 == 2097152
    assert 10 * 2 * costs_kda.state_bytes(32, 128, 128) == 41943040
    flops, nbytes = costs_ling3.kda_step(16)
    assert flops == 7 * 16 * 32 * 128 * 128
    assert nbytes == 2 * 16 * 2097152 + costs_kda.vector_bytes(16, 32, 128,
                                                               128)
    flops, nbytes = costs_ling3.kda_chunk(512)
    assert flops == 7 * 512 * 32 * 128 * 128
    assert nbytes == 2 * 2097152 + 4 * 512 * 32 * 6 * 128
    # a first chunk reads no state
    assert costs_ling3.kda_chunk(512, from_zero=1)[1] == nbytes - 2097152
    assert costs_mla.stored_width(512, 64) == 640
    flops, nbytes = costs_ling3.mla_paged(6144, 16)
    assert flops == 16 * 6144 * 2 * (576 + 512) * 32
    assert nbytes == 16 * 6144 * 640 * 2 + 16 * 32 * 2 * (640 + 512)
    # the chunk that ends a 7,168-token prompt: the issue's 0.26 TFLOP
    flops, _ = costs_ling3.mla_ragged(7168, 512)
    assert flops == sum(2 * 1088 * 32 * c for c in range(6657, 7169))
    assert 0.24e12 < flops < 0.26e12
    w = costs_ling3.weight_params()
    assert round(w["kda_mixer"] / 1e6, 2) == 63.05
    assert round(w["mla_mixer"] / 1e6, 2) == 31.97
    assert round(w["dense_mlp"] / 1e6, 2) == 47.19
    assert round(w["expert"] / 1e6, 3) == 5.898
    assert round(costs_ling3.weights_total_bytes() / 1e9, 2) == 9.47
    step = costs_ling3.token_step(6144)
    assert step["state_bytes"] > 16 * 41943040
    assert round(step["visited_experts_a_layer"], 1) == 14.3
    # a bound's time and a share of it
    assert costs_ling3.roofline_s(197e12, 819e9) == pytest.approx(1.0)
    assert costs_ling3.roofline_share(197e12, 0, 4.0) == pytest.approx(25.0)


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
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 54),
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
    cache = by_event["booted"]["cache"]
    assert (cache["kind"], cache["kv_layers"], cache["state_layers"],
            cache["num_state_slots"]) == ("latent", 2, 10, 16)
    assert cache["bytes"]["kv"] > 0 and cache["bytes"]["state"] > 0


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine (prompts
    in two and three chunks), weights out of the engine, the reference in
    blocks (the delta rule a token at a time, latent attention expanded),
    the comparison, and wrong references that are seen to be wrong."""
    r, lines = _run("reference_check_ling3.py", "--seed", "3000000054",
                    "--rehearse-on-cpu", "--requests", "4", "--perturb",
                    "softplus_gate,no_head_gate,write_strength_doubled")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("softplus_gate", "no_head_gate", "write_strength_doubled"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8 and engine["state_slots"] == 16
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
    assert max(engine["chunks_a_prompt"]) >= 2
