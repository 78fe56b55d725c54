"""trace_reduce on a small recorded trace (data/trace_small.json: two
device planes, a host plane, an async line; a `while` around 40
iterations of a fusion and a Pallas attention call; events out of order).
Every expectation below is worked out by hand from that layout:

    TPU:0  program A at 0 and again at 10000, program B at 25000
    TPU:1  program A at 0
    program A: copy [b, b+500)   while [b+1000, b+9000) holding, for
               i in 0..39, fusion [+200i, +120) and attention [+120, +50)
    program B: one fusion [25000, 26000)
"""
import json
import os

import pytest
from chipbench import DATA_DIR

from harness import readers, trace_reduce

NS = 1e-9


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(DATA_DIR, "trace_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(doc):
    return trace_reduce.reduce(doc)


def by_prefix(rows, prefix):
    [row] = [r for r in rows if r[0].startswith(prefix)]
    return row


def test_busy_is_the_union_of_op_intervals_averaged_over_chips(reduced):
    # TPU:0: 2 x (500 + 8000) + 1000 = 18000; TPU:1: 8500
    assert [d["busy_s"] for d in reduced["devices"]] == pytest.approx(
        [18000 * NS, 8500 * NS])
    assert reduced["busy_s"] == pytest.approx(13250 * NS)
    # first op starts at 0, the last ends at 26000
    assert reduced["window_s"] == pytest.approx(26000 * NS)
    assert [d["op_events"] for d in reduced["devices"]] == [165, 82]


def test_idle_share(reduced):
    idle = readers.read_metric({"reader": "trace_idle"}, {"trace": reduced})
    assert idle == pytest.approx((1 - 13250 / 26000) * 100)


def test_self_time_by_name_counts_an_enclosing_op_once(reduced):
    ops = reduced["ops"]
    # per chip-average: 3 programs A over 2 chips
    assert by_prefix(ops, "%fusion.7")[1:] == [pytest.approx(7200 * NS), 120]
    assert by_prefix(ops, "%_paged_attention_call.3")[1:] == [
        pytest.approx(3000 * NS), 120]
    # the while's own time is what its 40 iterations leave: 8000 - 40*170
    assert by_prefix(ops, "%while.1")[1:] == [pytest.approx(1800 * NS), 3]
    assert by_prefix(ops, "%copy.2")[1:] == [pytest.approx(750 * NS), 3]
    assert by_prefix(ops, "%fusion.9")[1:] == [pytest.approx(500 * NS), 1]
    # nothing is counted twice: self times add up to the busy time
    assert reduced["op_s"] == pytest.approx(reduced["busy_s"])
    # and nothing from the async line, the module line or the host
    assert not [r for r in ops if "copy-start" in r[0] or "jit_" in r[0]
                or "pjit" in r[0]]


def test_regex_shares(reduced):
    ctx = {"trace": reduced}
    attn = readers.read_metric(
        {"reader": "trace_op_share",
         "regex": r"^%_(ragged|paged_attention)_call"}, ctx)
    assert attn == pytest.approx(3000 / 13250 * 100)
    weights = readers.read_metric(
        {"reader": "trace_op_share", "regex": r"\[64,64\]"}, ctx)
    assert weights == pytest.approx((7200 + 750) / 13250 * 100)
    assert readers.read_metric(
        {"reader": "trace_op_share", "regex": "no such op"}, ctx) == 0.0


def test_programs_and_idle_gaps(reduced):
    mods = {r[0]: r[1:] for r in reduced["modules"]}
    assert mods["jit_step(123456789)"] == [pytest.approx(13500 * NS), 3]
    assert mods["jit_other(42)"] == [pytest.approx(500 * NS), 1]
    gaps = {r[0]: r[1:] for r in reduced["idle_gaps"]}
    # TPU:0: [19000, 25000) ends where program B starts
    assert gaps["before jit_other"] == [pytest.approx(3000 * NS), 1]
    # [9000, 10000) ends where the second program A starts
    assert gaps["before jit_step"] == [pytest.approx(500 * NS), 1]
    # [b+500, b+1000) inside each program A is ended by its while: 3 gaps
    [(name, rest)] = [(k, v) for k, v in gaps.items() if "while" in k]
    assert rest == [pytest.approx(750 * NS), 3]
    assert sum(v[0] for v in gaps.values()) == pytest.approx(
        ((26000 - 18000) + (9000 - 8500)) / 2 * NS)


def test_op_kinds_drop_numbers_layouts_and_layer_indices(reduced):
    kinds = {r[0]: r[1:] for r in reduced["op_kinds"]}
    assert kinds["copy bf16[64,64] layers.*.w1"] == [
        pytest.approx(750 * NS), 3]
    assert kinds["_paged_attention_call bf16[8,4,1,128], f32[8,4,1,128]"][1] \
        == 120
    assert kinds["fusion f32[128] norm"][1] == 1
    b = trace_reduce.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["fusion bf16[8,64]",
                                  pytest.approx(7200 * NS)]
    assert b["idle_gaps"][0][0] == "before jit_other"


def test_union_and_self_times_on_overlapping_and_nested_intervals():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]
    totals = trace_reduce.self_times(
        [["outer", 0, 100], ["mid", 10, 50], ["leaf", 20, 10],
         ["leaf", 40, 10], ["tail", 70, 20], ["next", 100, 5]])
    assert totals == {"outer": [30, 1], "mid": [30, 1], "leaf": [20, 2],
                      "tail": [20, 1], "next": [5, 1]}


def test_a_trace_without_device_operations_is_an_error(doc):
    host_only = {"planes": [p for p in doc["planes"]
                            if p["name"].startswith("/host")]}
    with pytest.raises(ValueError, match="no plane matches"):
        trace_reduce.reduce(host_only)
    no_ops = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_x(1)", 0, 5]]}]}]}
    with pytest.raises(ValueError, match="no events on a line"):
        trace_reduce.reduce(no_ops)


def test_describe_lists_planes_and_lines(doc):
    d = trace_reduce.describe(doc, k=2)
    assert [p["name"] for p in d["planes"]][:2] == [
        "/device:TPU:0", "/device:TPU:1"]
    lines = {ln["name"]: ln for ln in d["planes"][0]["lines"]}
    assert lines["XLA Ops"]["events"] == 165
    assert len(lines["XLA Ops"]["first"]) == 2
