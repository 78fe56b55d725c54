"""The cell `smallthinker-ragdoc-closed` and what it brought: the manifest
resolves its configuration, traffic and metric files; the configuration is
the catalog's entry cut exactly as it says; the traffic file's lengths all
cross the window; the benchmark's copy of the reference is the repo's, byte
for byte; `costs_window.py` counts what a hand count gives; the rehearsal
runs the cell; and `reference_check_smallthinker.py` runs its control flow
on the CPU. Membership is asserted, never a position in a list nor a list's
length: the next addition may stand anywhere and be of any number."""
import json
import os
import re
import subprocess
import sys

import pytest
from chipbench import CHIP_DIR, REPO

from harness import costs, costs_window, loadgen, readers
from harness.manifest import Manifest
from harness.server import hf_config_of

CELL, CONFIG, TRAFFIC = ("smallthinker-ragdoc-closed",
                         "smallthinker-21ba3b-l8", "ragdoc-closed")
NEW_METRICS = ("window_attn_dev_share", "smallthinker_moe_dev_share",
               "window_attended_share", "window_blocks_recycled_in_window")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MANIFEST = Manifest()


def test_manifest_resolves_the_cell_and_its_files():
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    e2e = MANIFEST.metrics_of_cell(CELL, "end_to_end")
    assert set(e2e) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    per_layer = MANIFEST.metrics_of_cell(CELL, "per_layer")
    assert set(NEW_METRICS) <= set(per_layer)
    # the other MoE cells' shapes are not this configuration's
    assert not {"moe_dev_share", "axk1_moe_dev_share", "lfm2_moe_dev_share",
                "moe_held_assign_share", "lfm2_conv_dev_share",
                "state_slot_allocs_in_window"} & set(per_layer)
    for name in NEW_METRICS:
        entry = MANIFEST.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert MANIFEST.metric_file(name)["reader"] in readers.READERS
        assert MANIFEST.metric_file(name)["name"] == name
    for name in ("rows_per_step", "preemptions_in_window", "pad_share",
                 "hbm_peak_gb", "fetch_wait_ms_per_step",
                 "idle_dispatch_share", "attn_dev_share", "device_idle",
                 "compiles_in_window", "decode_ctx_tokens_per_row"):
        assert name in per_layer
    assert MANIFEST.per_layer["window_attended_share"]["better"] == "lower"
    assert (MANIFEST.per_layer["window_blocks_recycled_in_window"]["layer"]
            == "cache manager")


def test_traffic_file_says_what_the_issue_asked():
    t = MANIFEST.traffic(TRAFFIC)
    assert (t["loop"], t["clients"], t["block"], t["queue"],
            t["pairing_seed"]) == ("closed", 16, 16, 1024, 1)
    # sigma 0.12 / 0.15 as the issue gives them (0.05 for both is the one
    # adjustment of the lengths it permits, if the sets of six spread too
    # widely: PERF.md 6)
    sigmas = (t["prompt_len"]["sigma"], t["output_len"]["sigma"])
    assert sigmas in ((0.12, 0.15), (0.05, 0.05))
    assert t["prompt_len"] == {"dist": "lognormal", "median": 5632,
                               "sigma": sigmas[0], "min": 4608, "max": 7168}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": sigmas[1], "min": 368, "max": 704}
    assert t["ramp_s"] == 60 and t["ramp_until"] == {
        "quiet_s": 10, "stall_gap_s": 2, "timeout_s": 900}
    assert t["trace"] == {"offset_s": 1, "seconds": 3}
    config = MANIFEST.config(CONFIG)
    window = config["sliding_window_size"]
    pairs = loadgen.length_pairs(t, t["block"])
    # every prompt crosses the window in its own prefill, and the longest
    # request with the K=8 decode's overshoot fits the model's length
    assert min(p for p, _ in pairs) > window
    assert max(p + o for p, o in pairs) + 8 <= config["serve"][
        "max_model_len"]
    # the first wave: 16 anchors and no other entry; the seats end their
    # first request evenly spread, the probes' seat (the last) the longest
    plan = loadgen.closed_plan(t, seed=7, total=64)
    wave = plan[:t["clients"]]
    assert [(r.prompt_len, r.output_len) for r in wave[:15]] == [
        (5632, 44 * (k + 1)) for k in range(15)]
    assert (wave[15].prompt_len, wave[15].output_len) == (7168, 704)
    assert all(r.prompt_len > window for r in plan)
    # every block after the wave is the same multiset
    blocks = [sorted((r.prompt_len, r.output_len) for r in plan[i:i + 16])
              for i in range(16, 64, 16)]
    assert blocks[0] == blocks[1] == blocks[2] == sorted(pairs)
    # the warm steps' prompts hold more than 256 blocks: the widest table
    assert all(n > 256 * 16 for s in t["warm"] for n in s["prompts"])
    last_chunks = sorted(n % 512 for s in t["warm"] for n in s["prompts"])
    assert last_chunks == [10, 24, 48, 100, 200]
    assert "rehearsal" in t


def test_configuration_states_its_cut():
    doc = MANIFEST.config(CONFIG)
    published = doc["published"]
    assert set(published) == {"num_hidden_layers", "rope_layout",
                              "sliding_window_layout"}
    assert published["num_hidden_layers"] == 52
    period = [0, 1, 1, 1]
    assert published["rope_layout"] == period * 13
    assert published["sliding_window_layout"] == period * 13
    # layers 0-7 as published: two whole periods
    assert doc["num_hidden_layers"] == 8
    assert doc["rope_layout"] == doc["sliding_window_layout"] == period * 2
    # the floors a model_config PR keeps to: every expert, whole vocabulary
    assert (doc["moe_num_primary_experts"],
            doc["moe_num_active_primary_experts"], doc["vocab_size"],
            doc["sliding_window_size"]) == (64, 6, 151936, 4096)
    for key in ("model_type", "router_input", "rope_pairing", "window_mask",
                "expert_gate", "routing", "checkpoint_names",
                "torch_dtype"):
        assert key in doc["assumed"]
    assert "first 8 layers" in doc["deployment"]
    assert "7.93 GB" in doc["deployment"]
    serve = doc["serve"]
    assert (serve["dtype"], serve["quantization"], serve["kv_cache_dtype"],
            serve["max_model_len"]) == ("bfloat16", None, "auto", 8192)
    assert serve["flags"] == ["--num-device-blocks-override", "16000"]
    hf = hf_config_of(doc, rehearsal=False)
    assert hf["model_type"] == "smallthinker" and "published" not in hf
    assert hf["architectures"] == ["SmallThinkerForCausalLM"]
    tiny = hf_config_of(doc, rehearsal=True)
    # the rehearsal keeps every structural feature
    assert tiny["num_hidden_layers"] == 8
    assert tiny["sliding_window_layout"] == period * 2
    assert (tiny["moe_num_primary_experts"],
            tiny["moe_num_active_primary_experts"],
            tiny["sliding_window_size"]) == (8, 2, 32)


def test_configuration_keeps_every_catalog_number():
    """Every key of the catalog's `config` is in the file under the same
    key, equal unless `reduced` names it; no width is among those."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    doc = MANIFEST.config(CONFIG)
    entry = MANIFEST.config_entries[CONFIG]
    assert entry["source"] == row["source_url"] == doc["source"]
    assert entry["reduced"] == ["num_hidden_layers", "rope_layout",
                                "sliding_window_layout"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|experts_per_tok|"
                       r"active.*experts|expansion")
    assert not [k for k in entry["reduced"] if width.search(k)]


def test_the_benchmarks_reference_is_the_repos():
    mine = os.path.join(CHIP_DIR, "references",
                        "smallthinker_21ba3b_l8_reference.py")
    repos = os.path.join(REPO, "tests", "models",
                         "smallthinker_reference.py")
    with open(mine, "rb") as a, open(repos, "rb") as b:
        assert a.read() == b.read()
    # it shares no code with the program
    with open(mine) as f:
        assert "intellillm_tpu" not in re.sub(r'""".*?"""', "", f.read(),
                                              flags=re.S)


def test_metric_files_read_what_they_say():
    trace = {"busy_s": 1.0, "op_s": 1.0, "window_s": 2.0, "ops": [
        ("%_ragged_call_window.7 = custom-call(...)", 0.30, 6),
        ("%_paged_attention_call_window.2 = custom-call(...)", 0.06, 6),
        ("%_ragged_call.5 = custom-call(...)", 0.12, 2),
        ("%_paged_attention_call.3 = custom-call(...)", 0.02, 2),
        ("%fusion.1 = f32[64,768,16]{2,1,0} fusion(...)", 0.10, 8),
        ("%fusion.2 = bf16[64,16,768]{2,1,0} fusion(bf16[64,768,2560] "
         "%p)", 0.05, 8),
        ("%_grouped_gate_up_call.4 = custom-call(...)", 0.08, 8),
        ("%_grouped_down_call.4 = custom-call(...)", 0.04, 8),
        ("%fusion.9 = bf16[512,2560]{1,0} fusion(...)", 0.23, 8)]}
    ctx = {"trace": trace}
    read = lambda name: readers.read_metric(                    # noqa: E731
        MANIFEST.metric_file(name), ctx)
    assert read("window_attn_dev_share") == pytest.approx(36.0)
    assert read("smallthinker_moe_dev_share") == pytest.approx(27.0)
    # the attention metric the benchmark had still reads the windowed calls
    assert read("attn_dev_share") == pytest.approx(50.0)
    # a trace without windowed calls (the parent on another cell): 0
    ctx = {"trace": dict(trace, ops=trace["ops"][2:])}
    assert read("window_attn_dev_share") == 0.0

    def prom(full, window, recycled):
        return readers.parse_prom("".join(
            f'intellillm_dispatch_attended_tokens_total{{program="{p}",'
            f'layers="{kind}"}} {n}\n'
            for p in ("mixed", "decode_cont")
            for kind, n in (("full", full), ("window", window)))
            + f'intellillm_window_blocks_total{{event="recycled"}} '
              f'{recycled}\n')

    ctx = {"prom_start": prom(1000, 900, 10),
           "prom_end": prom(7000, 4800, 410)}
    assert readers.read_metric(MANIFEST.metric_file(
        "window_attended_share"), ctx) == pytest.approx(65.0)
    assert readers.read_metric(MANIFEST.metric_file(
        "window_blocks_recycled_in_window"), ctx) == pytest.approx(400.0)
    # a program without the counters (the parent): nothing, not an error
    empty = {"prom_start": readers.parse_prom(""),
             "prom_end": readers.parse_prom("")}
    for name in ("window_attended_share",
                 "window_blocks_recycled_in_window"):
        assert readers.read_metric(MANIFEST.metric_file(name),
                                   empty) is None


def test_costs_against_hand_counts():
    """A row attends to min(context, window) tokens and moves the pages
    from its first visible position's to its last's, the edge page whole;
    without a window the counts are costs.py's."""
    cw = costs_window
    assert cw.attended_tokens(6000, 4096) == 4096
    assert cw.attended_tokens(3000, 4096) == cw.attended_tokens(3000) == 3000
    # context 6000: lo = 1904 = page 119; the last page is 374 (of 16)
    assert cw.walked_pages(6000, 16, 4096) == 375 - 119 == 256
    # an edge inside a page: context 6008, lo 1912, page 119 still whole
    assert cw.walked_pages(6008, 16, 4096) == 376 - 119 == 257
    assert cw.walked_pages(6000, 16) == 375 and cw.walked_pages(0, 16) == 0
    assert cw.walked_pages(100, 16, 4096) == 7
    ctx = [6000, 100]
    assert cw.window_attention_flops(ctx, 28, 128, 4096) == (
        4 * 28 * 128 * (4096 + 100))
    assert cw.window_attention_bytes(ctx, 4, 128, 2, 16, 4096) == (
        2 * 4 * 128 * 2 * 16 * (256 + 7))
    # no window: what costs.py counts (whole pages against tokens)
    assert cw.window_attention_flops(ctx, 28, 128) == (
        costs.paged_attention_flops(ctx, 28, 128))
    assert cw.window_attention_bytes([6000], 4, 128, 2, 16) == (
        costs.paged_attention_bytes([6000], 4, 128, 2))
    assert list(cw.chunk_context_lens(4608, 3)) == [4609, 4610, 4611]
    assert cw.ragged_write_bytes(3, 16, 4, 128, 2) == (
        2 * 2 * 3 * 4 * 16 * 128 * 2)
    assert cw.query_bytes(512, 28, 128) == 2 * 512 * 28 * 128 * 2
    assert cw.relu_grouped_ffn_flops(512 * 6, 2560, 768) == (
        2 * 3072 * 2560 * 768 * 3)
    assert cw.relu_grouped_ffn_weight_bytes(64, 2560, 768) == (
        3 * 64 * 2560 * 768 * 2)


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
    r, lines = _run("run.py", "--workload", CELL, "--seed", str(2**31 + 33),
                    "--seconds", "6", "--trace", "0", "--rehearse-on-cpu")
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
    groups = by_event["booted"]["cache"]["groups"]
    assert groups["kv"]["layers"] == 2 and groups["kv_window"]["layers"] == 6
    assert groups["kv_window"]["window"] == 32


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine, weights
    out of the engine, the reference layer by layer, the comparison, and
    wrong references that are seen to be wrong."""
    r, lines = _run("reference_check_smallthinker.py", "--seed",
                    "3000000033", "--rehearse-on-cpu", "--check", "4",
                    "--perturb", "window_dropped,rotary_on_full_layers")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("window_dropped", "rotary_on_full_layers"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    assert engine["num_decode_steps"] == 8 and engine["cache_groups"] == 2
    assert engine["window_blocks_recycled"] > 0
    assert {"mixed", "decode_fused", "decode_cont"} <= set(
        engine["compiles"])
    checked = next(x for x in lines if x.get("event") == "checked")
    assert checked["beyond_window"] is True
