"""The five device-clock metrics (PR 37) read from a recorded /metrics pair.

data/device_clock_{start,end}.txt: the device timeline's two counters
(`intellillm_device_busy_seconds_total{program}`,
`intellillm_device_idle_seconds_total{phase}`: obs/tracing.py) with the
dispatch, step-time and step-gap series of a tiny CPU server at the two
ends of a window of 8 engine steps (6 streamed requests through
AsyncLLMEngine, K=8 decode; buckets dropped). On the CPU backend a
program's first call compiles inside `execute`, so most of this window's
dry time lies there. The expectations are the window's deltas worked out
by hand from the two files.
"""
import os

import pytest
from chipbench import DATA_DIR

from harness import readers
from harness.manifest import Manifest

MANIFEST = Manifest()
NAMES = ["device_idle_window", "device_accounted_share",
         "decode_dev_ms_per_step", "mixed_dev_ms_per_step",
         "idle_dispatch_path_ms_per_step"]
CELLS = ["dsmoe16b-decode-closed", "mistral7b-decode-closed",
         "axk1-ep16-decode-closed", "lfm2moe-decode-closed",
         "smallthinker-ragdoc-closed"]
NEW_SERIES = ("intellillm_device_busy_seconds",
              "intellillm_device_idle_seconds")

STEPS = 20 - 12
PERIOD_S = ((4.123238142004993 - 3.498110065003857)
            + (0.018384157003310975 - 0.011483137001050636))
BUSY_MIXED_S = 0.07505577700067079 - 0.039803333005693275
BUSY_DECODE_S = ((0.329958792499383 - 0.3180773665008019)
                 + (0.19896812899241922 - 0.13525333699362818))
# schedule, prepare_inputs, execute (swap_copy 0): the way to a dispatch
IDLE_TO_DISPATCH_S = ((0.0022701279958710074 - 0.0013782309979433194)
                      + (0.024176856997655705 - 0.01611229699483374)
                      + (3.4922473574988544 - 2.98636912949587))
# admit, handoff, sample, process_outputs, detokenize, stats, handback,
# stream_out, loop_gap, unannotated (fetch_wait 0): the way back
IDLE_BACK_S = ((0.0005777409969596192 - 0.0003942599942092784)
               + (0.0014899929956300184 - 0.0008539699992979877)
               + (0.002387284992437344 - 0.0015059339930303395)
               + (0.004426791005244013 - 0.0030552759853890166)
               + (0.0021263679955154657 - 0.0013333330061868764)
               + (0.002402560996415559 - 0.0018244940001750365)
               + (0.002134625996404793 - 0.0014148269983706996)
               + (0.0004390580070321448 - 0.0002563259986345656)
               + (0.0006077650032239035 - 0.00041479800711385906)
               + (0.0024943060416262597 - 0.001630812039365992))
IDLE_S = IDLE_TO_DISPATCH_S + IDLE_BACK_S

EXPECTED = {
    "device_idle_window": IDLE_S / PERIOD_S * 100,
    "device_accounted_share":
        (BUSY_MIXED_S + BUSY_DECODE_S + IDLE_S) / PERIOD_S * 100,
    # decode_fused 3 - 2, decode_cont 11 - 6
    "decode_dev_ms_per_step": BUSY_DECODE_S / (1 + 5) * 1e3,
    # mixed 6 - 4
    "mixed_dev_ms_per_step": BUSY_MIXED_S / 2 * 1e3,
    "idle_dispatch_path_ms_per_step": IDLE_TO_DISPATCH_S / STEPS * 1e3,
}


def _prom(name: str) -> readers.Series:
    with open(os.path.join(DATA_DIR, name)) as f:
        return readers.parse_prom(f.read())


@pytest.fixture(scope="module")
def ctx():
    return {"prom_start": _prom("device_clock_start.txt"),
            "prom_end": _prom("device_clock_end.txt")}


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_the_windows_delta(name, ctx):
    value = readers.read_metric(MANIFEST.metric_file(name), ctx)
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


def test_busy_and_dry_time_tile_the_recorded_window(ctx):
    share = readers.read_metric(
        MANIFEST.metric_file("device_accounted_share"), ctx)
    # The window lies between two waits for traffic: the step in flight
    # at an edge and the begin_step that ends a wait are its only slack.
    assert 97.0 <= share <= 101.0
    idle = readers.read_metric(MANIFEST.metric_file("device_idle_window"),
                               ctx)
    assert 0.0 < idle < share


@pytest.mark.parametrize("name", NAMES)
def test_manifest_entry_and_file_agree(name):
    entry = MANIFEST.per_layer[name]
    body = MANIFEST.metric_file(name)
    assert body["name"] == name and body["reader"] == "prom_ratio"
    assert body["what"]
    assert entry["source"] == "program_span"
    assert entry["layer"] in ("device", "engine step loop")
    assert entry["workloads"] == CELLS
    # appended after what the benchmark already had
    names = [m["name"] for m in MANIFEST.doc["per_layer"]]
    assert names.index(name) > names.index(
        "window_blocks_recycled_in_window")
    for cell in entry["workloads"]:
        assert name in MANIFEST.metrics_of_cell(cell, "per_layer")
        # a cell reports the end-to-end metric its per-layer metric moves
        assert entry["moves"] in MANIFEST.metrics_of_cell(cell,
                                                          "end_to_end")


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_two_counters_gives_nothing(name, ctx):
    """The parent commit has no device timeline: every reader returns
    nothing (the line leaves the metric out) and none raises."""
    def parent(prom):
        return {series: rows for series, rows in prom.items()
                if not series.startswith(NEW_SERIES)}

    old = {"prom_start": parent(ctx["prom_start"]),
           "prom_end": parent(ctx["prom_end"])}
    assert readers.read_metric(MANIFEST.metric_file(name), old) is None


def test_a_device_that_never_ran_dry_still_prints_a_number(ctx):
    """The dispatch path's dry time stands over a step count, so a cell
    whose device is never idle reads 0 and not nothing."""
    def never_dry(prom):
        return {series: [(labels, 0.0 if series.startswith(
            "intellillm_device_idle_seconds") else v)
            for labels, v in rows] for series, rows in prom.items()}

    busy = {"prom_start": never_dry(ctx["prom_start"]),
            "prom_end": never_dry(ctx["prom_end"])}
    for name in ("idle_dispatch_path_ms_per_step", "device_idle_window"):
        assert readers.read_metric(MANIFEST.metric_file(name), busy) == 0.0
