"""The program's own trace reducer (intellillm_tpu/obs/kernels.py
`reduce_profile`, behind POST /debug/profiler/capture) against the
harness's (`harness/trace_reduce.py` `reduce`) on the same recorded
trace: the program may not import the benchmark and the benchmark must
not move with the program, so the arithmetic exists twice, and this test
holds the two to the same busy, idle and self times."""
import json
import os

import pytest
from chipbench import DATA_DIR

from harness import trace_reduce
from intellillm_tpu.obs.kernels import reduce_profile


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(DATA_DIR, "trace_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def both(doc):
    return trace_reduce.reduce(doc), reduce_profile(doc)


def test_busy_window_and_idle_agree(both):
    harness, program = both
    assert program["window_s"] == pytest.approx(harness["window_s"])
    assert program["busy_s"] == pytest.approx(harness["busy_s"])
    assert program["idle_s"] == pytest.approx(
        harness["window_s"] - harness["busy_s"])
    assert program["idle_share"] * 100 == pytest.approx(
        (1 - harness["busy_s"] / harness["window_s"]) * 100)
    assert program["devices"] == [
        {k: pytest.approx(v) if k == "busy_s" else v for k, v in d.items()}
        for d in harness["devices"]]


def test_self_times_agree_operation_by_operation(both):
    harness, program = both
    ours = {name: (sec, n) for name, sec, n in program["ops"]}
    theirs = {name: (sec, n) for name, sec, n in harness["ops"]}
    assert set(ours) == set(theirs)
    for name, (sec, n) in theirs.items():
        assert ours[name] == (pytest.approx(sec), n), name
    assert sum(sec for sec, _ in ours.values()) == pytest.approx(
        harness["op_s"])


def test_programs_agree_with_the_module_line(both):
    harness, program = both
    # the harness keeps the module's run id, the program strips it
    theirs = {name.split("(")[0]: (sec, n)
              for name, sec, n in harness["modules"]}
    ours = {name: (sec, n) for name, sec, n in program["programs"]}
    assert set(ours) == set(theirs) == {"jit_step", "jit_other"}
    for name, (sec, n) in theirs.items():
        assert ours[name] == (pytest.approx(sec), n)


def test_idle_gaps_sum_to_the_idle_time_and_have_a_phase(both, doc):
    harness, program = both
    assert sum(program["idle_by_phase"].values()) == pytest.approx(
        program["idle_s"])
    # the recorded host plane holds no intellillm/* annotation (its
    # `schedule` event is not the tracer's): all of it is unannotated
    assert set(program["idle_by_phase"]) == {"unannotated"}
    # between two programs the gaps are the harness's, by what ended them;
    # the program's also cover each chip's idle up to the window's edges
    between = sum(sec for _, sec, _ in harness["idle_gaps"])
    assert between <= program["idle_s"] + 1e-12
    ended_by = {prog for _, prog, _, _ in program["idle_by_phase_program"]}
    assert "end of window" in ended_by      # TPU:1 stops at 9000 of 26000
