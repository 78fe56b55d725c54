"""The six loop metrics (PR 24) read from a recorded /metrics pair.

data/loop_metrics_{start,end}.txt: the step-phase, step-time, step-gap and
dispatch series of a tiny CPU server at the two ends of a window of 12
engine steps (9 streamed requests through AsyncLLMEngine, K=8 decode;
buckets dropped). The expectations are the window's deltas worked out by
hand from the two files.
"""
import os

import pytest
from chipbench import DATA_DIR

from harness import readers
from harness.manifest import Manifest

MANIFEST = Manifest()
NAMES = ["loop_gap_ms_per_step", "host_loop_ms_per_step",
         "fetch_wait_ms_per_step", "loop_accounted_share",
         "idle_dispatch_share", "decode_ctx_tokens_per_row"]
NEW_SERIES = ("intellillm_step_gap_seconds", "intellillm_dispatch_")
NEW_PHASES = ("admit", "handoff", "fetch_wait", "process_outputs", "stats",
              "handback", "stream_out")

STEPS = 18 - 6
GAP_S = 0.015726417 - 0.006367315
STEP_S = 3.757076301 - 3.186938476
FETCH_WAIT_S = 0.239710921 - 0.126702817
# end - start of every phase but fetch_wait, in the files' order
HOST_S = ((0.000645553 - 0.000246669) + (0.005210175 - 0.001857936)
          + (0.003371603 - 0.002513084) + (0.027192114 - 0.010537129)
          + (3.458967900 - 3.035525000) + (0.003938883 - 0.001518997)
          + (0.008673990 - 0.003467379) + (0.007955423 - 0.003592368)
          + (0.004833851 - 0.002285682) + (0.015421617 - 0.001933926)
          + (0.001677193 - 0.000531484))


def _prom(name: str) -> readers.Series:
    with open(os.path.join(DATA_DIR, name)) as f:
        return readers.parse_prom(f.read())


@pytest.fixture(scope="module")
def ctx():
    return {"prom_start": _prom("loop_metrics_start.txt"),
            "prom_end": _prom("loop_metrics_end.txt")}


EXPECTED = {
    "loop_gap_ms_per_step": GAP_S / STEPS * 1e3,
    "host_loop_ms_per_step": HOST_S / STEPS * 1e3,
    "fetch_wait_ms_per_step": FETCH_WAIT_S / STEPS * 1e3,
    "loop_accounted_share": (HOST_S + FETCH_WAIT_S) / (STEP_S + GAP_S) * 100,
    # mixed 2 idle, decode_fused 2 idle, decode_cont 8 busy
    "idle_dispatch_share": 4 / 12 * 100,
    # decode_fused + decode_cont: context tokens over rows x substeps
    "decode_ctx_tokens_per_row":
        ((1740 - 696) + (16560 - 6624)) / ((120 - 48) + (480 - 192)),
}


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_the_windows_delta(name, ctx):
    value = readers.read_metric(MANIFEST.metric_file(name), ctx)
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


def test_the_recorded_window_is_accounted_for(ctx):
    share = readers.read_metric(
        MANIFEST.metric_file("loop_accounted_share"), ctx)
    # Over 100 by what the between-step phases took around the two waits
    # for traffic in this window (the 9 requests came in two waves): the
    # work at either side of a wait is counted (the last step's stats,
    # handback and stream_out, one empty step, the next admit and
    # handoff), the wait itself is no loop gap.
    assert 95.0 <= share <= 103.0


@pytest.mark.parametrize("name", NAMES)
def test_manifest_entry_and_file_agree(name):
    entry = MANIFEST.per_layer[name]
    body = MANIFEST.metric_file(name)
    assert body["name"] == name and body["reader"] == "prom_ratio"
    assert body["what"]
    assert entry["layer"] in ("engine step loop", "runner", "kernels")
    # appended after what the benchmark already had
    names = [m["name"] for m in MANIFEST.doc["per_layer"]]
    assert names.index(name) > names.index("hbm_peak_gb")
    # every cell that reports the metric it moves reports this one too
    for cell in entry.get("workloads", MANIFEST.cells):
        assert name in MANIFEST.metrics_of_cell(cell, "per_layer")


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_new_series_gives_nothing_or_less(name, ctx):
    """The parent commit exports neither the loop gap, the new phases nor
    the dispatch counters: a reader then returns nothing (the line leaves
    the metric out) or what the old phases give; it never raises."""
    def parent(prom):
        return {series: [(labels, v) for labels, v in rows
                         if labels.get("phase") not in NEW_PHASES]
                for series, rows in prom.items()
                if not series.startswith(NEW_SERIES)}

    old = {"prom_start": parent(ctx["prom_start"]),
           "prom_end": parent(ctx["prom_end"])}
    value = readers.read_metric(MANIFEST.metric_file(name), old)
    if name in ("host_loop_ms_per_step", "loop_accounted_share"):
        assert value is not None and value < EXPECTED[name]
    else:
        assert value is None
