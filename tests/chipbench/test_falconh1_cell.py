"""The cell `falconh1-decode-closed` and what it brought: the manifest
resolves its configuration, traffic, reference and metric files; the
configuration is the catalog's entry with its depth cut and nothing else; the
byte counts the cell was sized by follow from the file through `ModelConfig`;
the benchmark's copy of the reference is the repo's, byte for byte;
`costs_falconh1.py` with one group is `costs_ssd.py`; the metric files read
what they say; and `reference_check_falconh1.py` runs its control flow on the
CPU. Membership is asserted, never a position in a list and never a count of
cells: the next addition may stand anywhere.

The cell's own rehearsal (`run.py --rehearse-on-cpu`, 40 s) is the builder's
to run by hand: tier-1 has no time left for it (ROADMAP R0)."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_falconh1, costs_ssd, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "falconh1-decode-closed", "falcon-h1-34b-l6"
NEW_METRICS = ("mixer_state_bytes_share", "falconh1_mlp_dev_share",
               "falconh1_head_dev_share", "falconh1_ssm_dev_share")
APPENDED = ("rows_per_step", "preemptions_in_window", "pad_share",
            "hbm_peak_gb", "fetch_wait_ms_per_step", "idle_dispatch_share",
            "state_slot_allocs_in_window")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = Manifest()


def test_manifest_resolves_the_cell_and_its_files():
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-closed", 1)
    assert len(cell["why"]) <= 200
    assert MANIFEST.traffic(cell["traffic"])["clients"] == 128
    e2e = MANIFEST.metrics_of_cell(CELL, "end_to_end")
    assert set(e2e) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    per_layer = MANIFEST.metrics_of_cell(CELL, "per_layer")
    assert set(NEW_METRICS) | set(APPENDED) <= set(per_layer)
    assert {"attn_dev_share", "device_idle", "compiles_in_window",
            "decode_ctx_tokens_per_row"} <= set(per_layer)
    # the other cells' shapes and kernels are not this configuration's; the
    # five device-clock lists stay the five older cells' and the four
    # `ssm_*` lists Granite's cell's (each held to its cells by that
    # cell's own test: PERF.md section 7)
    assert not {"moe_dev_share", "granite4h_moe_dev_share", "ssm_dev_share",
                "ssm_state_pool_gb",
                "moe_held_assign_share", "linear_attn_dev_share",
                "state_pool_gb", "mhc_dev_share", "device_idle_window",
                "decode_dev_ms_per_step"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "tpot_p50_ms"
        doc = MANIFEST.metric_file(name)
        assert doc["reader"] in readers.READERS
        if doc["reader"] == "trace_op_share":
            assert len(doc["regex_why"]) > 80
    reference = os.path.join(CHIP_DIR, "references",
                             "falcon_h1_34b_l6_reference.py")
    assert os.path.exists(reference)
    assert os.path.exists(os.path.join(CHIP_DIR,
                                       "reference_check_falconh1.py"))


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 72}
    # the pattern's period is one layer; the guide's floor is four periods
    assert 4 <= doc["num_hidden_layers"] <= 6
    assert CONFIG.endswith(f"-l{doc['num_hidden_layers']}")
    for key in ("ssm_multipliers_order", "ssm_multipliers_place",
                "key_multiplier_place", "gated_norm", "group_map",
                "time_step_limit", "state_dtype", "state_layout",
                "binds_nothing", "seeded_init", "checkpoint_names",
                "architectures", "torch_dtype"):
        assert key in doc["assumed"], key
    assert "first of twelve pipeline stages" in doc["deployment"]
    assert "12,288 B a token" in doc["deployment"]
    assert "25.35 MB a sequence" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    assert serve["flags"][-2:] == ["--max-num-seqs", "128"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "falcon_h1" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps both branches, two groups, the multipliers as
    # published and an untied head
    assert tiny["num_hidden_layers"] == 2 and tiny["mamba_n_groups"] == 2
    assert tiny["mamba_n_heads"] * tiny["mamba_d_head"] == tiny["mamba_d_ssm"]
    assert tiny["ssm_multipliers"] == hf["ssm_multipliers"]
    assert tiny["key_multiplier"] == hf["key_multiplier"]
    assert tiny["tie_word_embeddings"] is False


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal but for `num_hidden_layers`; no width is among the cuts."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|d_head|d_state|d_ssm|"
                       r"num_experts_per_tok|expand|expansion")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_issues_byte_counts_follow_from_the_file(tmp_path):
    """430.1 M parameters a layer, 12,288 B of K/V a token, 25.35 MB of
    state a slot: from the configuration's file through `ModelConfig`, the
    model's own shapes and the cache engine's sizes."""
    import jax
    import numpy as np

    from intellillm_tpu.config import ModelConfig, ParallelConfig
    from intellillm_tpu.models import get_model_class
    from intellillm_tpu.worker.cache_engine import CacheEngine
    doc = MANIFEST.config(CONFIG)
    layers = doc["num_hidden_layers"]
    (tmp_path / "config.json").write_text(json.dumps(
        hf_config_of(doc, rehearsal=False)))
    mc = ModelConfig(model=str(tmp_path), dtype="bfloat16",
                     load_format="dummy", max_model_len=4096)
    assert mc.get_layer_cache_kinds() == ["kv", "state"] * layers
    assert (mc.get_num_kv_layers(), mc.get_num_state_layers()) == (
        layers, layers)
    assert mc.get_state_arrays() == (((3, 5120), "bfloat16"),
                                     ((32, 256, 128), "float32"))
    assert CacheEngine.get_state_slot_size(mc) == layers * (
        4_194_304 + 30_720)
    assert 6 * (4_194_304 + 30_720) == 25_350_144
    block = CacheEngine.get_cache_block_size(16, "auto", mc, ParallelConfig())
    assert block == 16 * layers * 2_048 and 6 * 2_048 == 12_288
    model = get_model_class(mc.hf_config.architectures)(mc)
    shapes = model._param_shapes()
    a_layer = sum(int(np.prod(s)) for s in shapes["layers"][0].values())
    assert a_layer == 430_120_032                 # 860 MB in bf16
    tables = sum(int(np.prod(shapes[k])) for k in ("embed_tokens",
                                                   "lm_head"))
    assert tables == 2 * 261_120 * 5_120          # 5.35 GB
    assert jax.tree.leaves(shapes["norm"]) == [5_120]
    # a decode row-substep: the matrix in and out, every layer
    assert 2 * layers * 4_194_304 == 50_331_648 * layers // 6


def test_the_benchmarks_reference_is_the_repos():
    mine = os.path.join(CHIP_DIR, "references",
                        "falcon_h1_34b_l6_reference.py")
    repos = os.path.join(REPO, "tests", "models", "falcon_h1_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_costs_with_one_group_are_the_ungrouped_files():
    """`costs_falconh1.py` at G = 1 equals `costs_ssd.py`; with two groups B
    and C are 2 G N values a row; the parallel layer's walk and the context
    at which it outweighs the state."""
    for rows, inner, n in ((128, 8192, 128), (448, 4096, 256)):
        assert costs_falconh1.ssd_flops(rows, inner, n) == (
            costs_ssd.ssd_flops(rows, inner, n))
        assert costs_falconh1.vector_bytes(rows, inner, n) == (
            costs_ssd.vector_bytes(rows, inner, n))
        assert costs_falconh1.ssd_step_bytes(rows, inner, n) == (
            costs_ssd.ssd_step_bytes(rows, inner, n))
        assert costs_falconh1.ssd_chunk_bytes(
            rows, 4, inner, n, from_zero=2) == costs_ssd.ssd_chunk_bytes(
                rows, 4, inner, n, from_zero=2)
    assert costs_falconh1.state_bytes(4096, 256) == 4 * 1024**2
    assert (costs_falconh1.vector_bytes(1, 4096, 256, groups=2)
            - costs_falconh1.vector_bytes(1, 4096, 256, groups=1)
            == 4 * 256 * 128)
    assert costs_falconh1.ssd_flops(128, 4096, 256, groups=2) == (
        5 * 128 * 4096 * 256)
    assert costs_falconh1.kv_token_bytes(4, 128, 2, layers=6) == 12_288
    assert costs_falconh1.kv_walk_bytes(1000, 4, 128, 2, layers=6) == (
        12_288_000)
    assert costs_falconh1.crossover_context(4096, 256, 4, 128, 2) == 4096


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%_ssd_step_call.3 = custom-call(f32[128,32,128], "
         "f32[129,32,256,128])", 0.40, 4),
        ("%_paged_attention_call.2 = custom-call(...)", 0.05, 4),
        ("%fusion.1 = bf16[128,21504]{1,0} fusion(bf16[5120,21504] %p)",
         0.20, 4),
        ("%fusion.2 = bf16[128,5120]{1,0} fusion(bf16[128,21504] %a, "
         "bf16[21504,5120] %p)", 0.10, 4),
        ("%fusion.3 = f32[128,261120]{1,0} fusion(bf16[261120,5120] %h)",
         0.15, 4),
        ("%fusion.9 = bf16[128,9248]{1,0} fusion(...)", 0.10, 4)]}
    ctx = {"trace": trace}
    read = lambda name, c=ctx: readers.read_metric(             # noqa: E731
        MANIFEST.metric_file(name), c)
    assert read("falconh1_ssm_dev_share") == pytest.approx(40.0)
    assert read("falconh1_mlp_dev_share") == pytest.approx(30.0)
    assert read("falconh1_head_dev_share") == pytest.approx(15.0)
    assert read("attn_dev_share") == pytest.approx(5.0)

    def prom(state, walk):
        return readers.parse_prom(
            f'intellillm_dispatch_state_bytes_total{{program="decode_cont"}}'
            f' {state}\n'
            f'intellillm_dispatch_state_bytes_total{{program="mixed"}} 7\n'
            f'intellillm_dispatch_kv_walk_bytes_total{{program='
            f'"decode_cont"}} {walk}\n'
            f'intellillm_dispatch_kv_walk_bytes_total{{program="mixed"}} 9\n')
    ctx = {"prom_start": prom(100, 100), "prom_end": prom(1000, 200)}
    assert read("mixer_state_bytes_share", ctx) == pytest.approx(90.0)
    # a program without the counters (the parent): nothing, not an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom(""), "detail_end": {},
             "trace": None}
    for name in NEW_METRICS:
        assert read(name, empty) is None


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine, weights
    out of the engine, the reference layer by layer (the scan a token and a
    head at a time), the head in blocks, the comparison, and wrong
    references that are seen to be wrong."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    wrong = ("group0_for_every_head", "no_attention_branch")
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR,
                                      "reference_check_falconh1.py"),
         "--seed", "3000000041", "--rehearse-on-cpu", "--check", "4",
         "--perturb", ",".join(wrong)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in wrong:
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8 and engine["state_slots"] == 16
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
