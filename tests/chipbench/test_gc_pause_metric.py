"""`gc_pause_share` (PR 53): the collector's seconds over the loop's period.

The recorded scrapes of data/device_clock_{start,end}.txt come from a
program without `intellillm_gc_pause_seconds_total` (obs/heap.py), as
every parent commit is: the reader must give nothing there. With the
three generations' series laid over them the reader must sum all three
and divide by step time + loop gap.
"""
import os

import pytest
from chipbench import DATA_DIR

from harness import readers
from harness.manifest import Manifest

MANIFEST = Manifest()
NAME = "gc_pause_share"
SERIES = "intellillm_gc_pause_seconds_total"
PERIOD_S = ((4.123238142004993 - 3.498110065003857)
            + (0.018384157003310975 - 0.011483137001050636))
# the ten cells the benchmark had when the metric came
CELLS = ["dsmoe16b-decode-closed", "mistral7b-decode-closed",
         "axk1-ep16-decode-closed", "lfm2moe-decode-closed",
         "smallthinker-ragdoc-closed", "solar2-ep8-decode-closed",
         "granite4h-ep4-decode-closed", "xing4-ep8-decode-closed",
         "falconh1-decode-closed", "phi4flash-cot-closed"]
# generation -> (seconds at the window's start, at its end)
PAUSES = {"0": (0.25, 0.26), "1": (0.5, 0.5), "2": (3.0, 3.03)}


def _prom(name: str) -> readers.Series:
    with open(os.path.join(DATA_DIR, name)) as f:
        return readers.parse_prom(f.read())


@pytest.fixture(scope="module")
def parent_ctx():
    return {"prom_start": _prom("device_clock_start.txt"),
            "prom_end": _prom("device_clock_end.txt")}


def _with_pauses(ctx: dict, generations) -> dict:
    def lay(prom, at):
        rows = [({"model_name": "m", "generation": g}, PAUSES[g][at])
                for g in generations]
        return {**prom, SERIES: rows}

    return {"prom_start": lay(ctx["prom_start"], 0),
            "prom_end": lay(ctx["prom_end"], 1)}


def test_a_program_without_the_counter_gives_nothing(parent_ctx):
    assert SERIES not in parent_ctx["prom_end"]
    assert readers.read_metric(MANIFEST.metric_file(NAME),
                               parent_ctx) is None


@pytest.mark.parametrize("generations", [("0",), ("2",), ("0", "1", "2")])
def test_every_generation_counts(generations, parent_ctx):
    value = readers.read_metric(MANIFEST.metric_file(NAME),
                                _with_pauses(parent_ctx, generations))
    paused = sum(PAUSES[g][1] - PAUSES[g][0] for g in generations)
    assert value == pytest.approx(paused / PERIOD_S * 100, rel=1e-9)


def test_a_window_without_a_collection_reads_zero(parent_ctx):
    ctx = _with_pauses(parent_ctx, ("0", "1", "2"))
    ctx["prom_end"][SERIES] = ctx["prom_start"][SERIES]
    assert readers.read_metric(MANIFEST.metric_file(NAME), ctx) == 0.0


def test_manifest_entry_and_file_agree():
    entry, body = MANIFEST.per_layer[NAME], MANIFEST.metric_file(NAME)
    assert body["name"] == NAME and body["reader"] == "prom_ratio"
    assert body["numerator"] == {"name": SERIES}       # all generations
    assert body["denominator"] == MANIFEST.metric_file(
        "device_idle_window")["denominator"]
    assert body["scale"] == 100.0 and body["what"]
    assert (entry["unit"], entry["better"]) == ("%", "lower")
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "engine step loop"
    assert entry["moves"] == "out_tok_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_the_entry_stands_after_what_the_benchmark_had():
    """(Not "is the last": a later PR appends behind it.)"""
    names = [m["name"] for m in MANIFEST.doc["per_layer"]]
    assert names.index(NAME) > names.index("phi4flash_head_dev_share")
    assert MANIFEST.per_layer[NAME]["workloads"][:10] == CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_it_beside_what_it_moves(cell):
    assert NAME in MANIFEST.metrics_of_cell(cell, "per_layer")
    assert NAME not in MANIFEST.metrics_of_cell(cell, "end_to_end")
    assert "out_tok_s" in MANIFEST.metrics_of_cell(cell, "end_to_end")
