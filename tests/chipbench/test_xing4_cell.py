"""The cell `xing4-ep8-decode-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says, within the guide's floors and at
its whole depth; the benchmark's copy of the reference is the repo's, byte
for byte; `costs_mhc.py` counts what a hand count gives; the metric files
read what they say; and the rehearsal runs the cell
(`reference_check_xing4.py`'s rehearsal is `test_xing4_reference_check.py`:
a file goes to one worker, and the two rehearsals together are its tail).
Membership is asserted, never a position in a list and never a count of
cells: the next addition may stand anywhere."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs_mhc, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG = "xing4-ep8-decode-closed", "xing4.0-29b-a4b-ep8"
NEW_METRICS = ("mhc_dev_share", "xing4_moe_dev_share",
               "mhc_stream_bytes_per_row_step", "mhc_col_error_max")
REDUCED = ["n_routed_experts", "vocab_size", "ep_size"]
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
    # the other cells' shapes, kernels and state are not this
    # configuration's
    assert not {"moe_dev_share", "axk1_moe_dev_share", "lfm2_moe_dev_share",
                "lfm2_conv_dev_share", "solar2_moe_dev_share",
                "granite4h_moe_dev_share", "ssm_dev_share",
                "linear_attn_dev_share", "state_bytes_per_row_step",
                "ssm_state_bytes_per_row_step", "state_pool_gb",
                "ssm_state_pool_gb", "state_slot_allocs_in_window",
                "window_attn_dev_share"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share", "moe_held_assign_share",
                 "attn_dev_share", "device_idle"):
        assert name in per_layer
    # The five device-clock metrics of PR 37 list the five older cells and
    # `tests/chipbench/test_device_clock_metrics.py` holds their lists to
    # exactly those: this cell is not appended there (PERF.md section 7).
    assert "decode_dev_ms_per_step" not in per_layer
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    published = doc["published"]
    assert set(published) == set(REDUCED)
    assert (published["n_routed_experts"], published["vocab_size"],
            published["ep_size"]) == (64, 131072, 1)
    # the floors: at least 8 experts held, at least an eighth of the
    # vocabulary; the depth is whole
    assert doc["num_hidden_layers"] == 40 and "num_hidden_layers" not in (
        published)
    assert doc["n_routed_experts"] >= 8
    assert doc["vocab_size"] * 8 >= 131072
    # one of 8 chips: an eighth of the experts and of the vocabulary, the
    # router's width and top-4 as published
    assert (doc["n_routed_experts"], doc["ep_size"], doc["ep_rank"]) == (
        8, 8, 0)
    assert doc["n_routed_experts"] * doc["ep_size"] == 64
    assert doc["vocab_size"] * 8 == 131072
    assert doc["num_experts_per_tok"] == 4
    # every published width, the residual path's constants, YaRN
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
            doc["v_head_dim"], doc["q_lora_rank"], doc["kv_lora_rank"]) == (
                3584, 32, 128, 64, 128, 768, 512)
    assert (doc["moe_intermediate_size"], doc["intermediate_size"],
            doc["first_k_dense_replace"], doc["routed_scaling_factor"],
            doc["n_shared_experts"]) == (1024, 9216, 2, 2, 1)
    assert (doc["hc_mult"], doc["hc_sinkhorn_iters"], doc["hc_eps"],
            doc["mhc_h_res_clamp_min"], doc["mhc_h_res_clamp_max"]) == (
                4, 20, 1e-6, -30, 30)
    assert (doc["rope_scaling"]["factor"],
            doc["rope_scaling"]["original_max_position_embeddings"]) == (
                64, 4096)
    for key in ("ep_rank", "architectures", "torch_dtype", "mix_statistic",
                "hc_eps", "clamp", "sweep_order", "gates",
                "coefficient_precision", "streams_in_and_out", "topk_method",
                "rope_pairing", "rms_norm_eps_on_latents", "mtp_layer",
                "checkpoint_names", "seeded_init"):
        assert key in doc["assumed"]
    assert "One of 8 chips that share each layer" in doc["deployment"]
    assert "nothing of the depth cut" in doc["deployment"]
    assert "51.2 KB" in doc["deployment"] and "10.6 GB" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 4096)
    assert serve["flags"][:1] == ["--num-device-blocks-override"]
    assert 3600 <= int(serve["flags"][1]) <= 4500
    assert serve["flags"][2:] == ["--max-num-seqs", "128"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "xing4_0" and "published" not in hf
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps every structural feature
    assert (tiny["num_hidden_layers"], tiny["first_k_dense_replace"]) == (
        4, 2)
    assert (tiny["hc_mult"], tiny["hc_sinkhorn_iters"]) == (4, 20)
    assert (tiny["n_routed_experts"], tiny["ep_size"],
            tiny["num_experts_per_tok"]) == (2, 8, 4)
    assert tiny["q_lora_rank"] and tiny["rope_scaling"]["type"] == "yarn"
    assert tiny["topk_method"] == "noaux_tc" and tiny["n_shared_experts"]


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    assert entry["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|num_experts_per_tok|"
                       r"expand|expansion|hc_mult")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_benchmarks_reference_is_the_repos():
    mine = os.path.join(CHIP_DIR, "references",
                        "xing4_0_29b_a4b_ep8_reference.py")
    repos = os.path.join(REPO, "tests", "models", "xing4_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        # the mixes: the streams, their float32 copy, the projection, a
        # sweep's operations and the sweeps' loop
        ("%pad_maximum_fusion.3 = bf16[128,1,4,3584]{3,2,1,0} fusion(...)",
         0.06, 4),
        ("%copy_convert_fusion.1 = f32[128,4,3584]{2,0,1} fusion(%p)",
         0.05, 4),
        ("%fusion.77 = f32[24,128]{1,0} fusion(f32[24,14336] %phi, "
         "f32[128,14336] %x)", 0.04, 4),
        ("%multiply_divide_fusion.11 = (f32[128]{0}, f32[128]{0}) "
         "fusion(...)", 0.03, 4),
        ("%while.3 = (s32[], f32[128]{0}, f32[128]{0}) while(...)", 0.02, 4),
        # the held experts, dense (gate, up, down: each names the stacked
        # weights among its operands) and grouped
        ("%fusion.1 = f32[8,128,1024]{2,1,0} fusion(bf16[128,3584] %x, "
         "bf16[304,1024,3584]{2,1,0} %w1, s32[] %at)", 0.06, 4),
        ("%fusion.2 = bf16[8,128,1024]{2,1,0} fusion(bf16[304,1024,3584] "
         "%w3, s32[] %at)", 0.05, 4),
        ("%fusion.3 = bf16[128,3584]{1,0} fusion(bf16[8,128,1024] %h, "
         "bf16[304,1024,3584]{2,1,0} %w2, s32[] %at)", 0.04, 4),
        ("%_grouped_gate_up_call.2 = custom-call(...)", 0.03, 4),
        ("%_grouped_down_call.2 = custom-call(...)", 0.02, 4),
        # attention: both latent kernels carry the prefix
        ("%_paged_attention_call_mla.2 = custom-call(...)", 0.10, 4),
        ("%_ragged_call_mla.1 = custom-call(...)", 0.02, 4),
        # none of them: the shared expert, the attention's output
        # projection, a dense layer, the q projection with its statistic
        ("%fusion.7 = bf16[128,1024]{1,0} fusion(...)", 0.03, 4),
        ("%fusion.9 = bf16[128,3584]{1,0} fusion(...)", 0.20, 4),
        ("%fusion.8 = bf16[128,9216]{1,0} fusion(...)", 0.15, 4),
        ("%fusion.5 = (f32[128]{0}, bf16[128,768]{1,0}) fusion(...)", 0.05,
         4)]}
    ctx = {"trace": trace}
    read = lambda name, c=ctx: readers.read_metric(             # noqa: E731
        MANIFEST.metric_file(name), c)
    assert read("mhc_dev_share") == pytest.approx(20.0)
    assert read("xing4_moe_dev_share") == pytest.approx(20.0)
    assert read("attn_dev_share") == pytest.approx(12.0)

    def prom(rows):
        return readers.parse_prom(
            f'intellillm_dispatch_stream_mix_bytes_total'
            f'{{program="decode_cont"}} {rows * 6881280}\n'
            f'intellillm_dispatch_stream_mix_bytes_total{{program="mixed"}}'
            f' 7\n'
            f'intellillm_dispatch_row_steps_total{{program="decode_cont"}}'
            f' {rows}\n'
            f'intellillm_dispatch_row_steps_total{{program="mixed"}} 9\n')
    ctx = {"prom_start": prom(10), "prom_end": prom(90),
           "detail_end": {"efficiency": {"mhc": {
               "stream_mix_bytes": 1,
               "col_error_max": 0.03125}}}}
    assert read("mhc_stream_bytes_per_row_step", ctx) == pytest.approx(
        6881280.0)
    assert read("mhc_col_error_max", ctx) == pytest.approx(0.03125)
    # a program without the counters or the key (the parent): nothing, not
    # an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom(""), "detail_end": {},
             "trace": None}
    for name in NEW_METRICS:
        assert read(name, empty) is None


def test_costs_against_hand_counts():
    """4 streams of 3,584 bf16 values: 28,672 B a row. The pre-mix reads
    them once, the post-mix reads and writes them: 86,016 B a row and
    sublayer, 6,881,280 over 80 sublayers (the program's counter)."""
    n, c = 4, 3584
    assert costs_mhc.stream_bytes(1, n, c, 2) == 28672
    assert costs_mhc.mhc_stream_bytes(1, n, c, 2) == 86016
    assert 80 * costs_mhc.mhc_stream_bytes(1, n, c, 2) == 6881280
    phi = 24 * 14336 * 4
    assert costs_mhc.mhc_pre_bytes(128, n, c, 2) == (
        128 * 28672 + 128 * 7168 + phi)
    assert costs_mhc.mhc_post_bytes(128, n, c, 2) == (
        2 * 128 * 28672 + 128 * 7168)
    # the streams are what both move but for the sublayer's own vector and
    # the projection's weights
    both = (costs_mhc.mhc_pre_bytes(128, n, c, 2)
            + costs_mhc.mhc_post_bytes(128, n, c, 2))
    assert both - costs_mhc.mhc_stream_bytes(128, n, c, 2) == (
        2 * 128 * 7168 + phi)
    assert costs_mhc.mhc_flops(1, n, c, 20) == (
        14336 * (2 + 48 + 2 + 8 + 2) + 20 * 64)
    # the program's own count is the benchmark's
    from intellillm_tpu.layers.hyper_connections import HyperConnections
    assert HyperConnections(4, 20, 1e-6, -30.0, 30.0, 1e-6).mix_bytes(
        c, 2) == costs_mhc.mhc_stream_bytes(1, n, c, 2)


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
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 45),
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
