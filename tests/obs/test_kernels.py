"""Unit suite for the per-kernel cost ledger (obs/kernels.py): bucket
keying, introspection parsing (faked cost/memory analysis objects),
top-K ordering, the CPU/no-TPU degradation contract (nulls, never an
exception on the dispatch path), the cost-model MFU window, and the
profiler capture's reducer (device busy union, self times, idle gaps by
host phase) on a hand-made doc and on a real CPU jax.profiler trace.

The module also prints its own wall-clock on teardown: the ledger tests
run inside the tier-1 870s budget, so the suite self-reports what it
costs (see docs/observability.md "Testing hooks")."""
import json
import math
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from intellillm_tpu.obs.kernels import (KernelLedger, _parse_cost_analysis,
                                        capture_profile, get_kernel_ledger,
                                        load_profile, reduce_profile)


@pytest.fixture(autouse=True, scope="module")
def _module_wallclock():
    t0 = time.perf_counter()
    yield
    # sys.__stderr__ bypasses pytest's capture: the tier-1 log always
    # shows what the ledger suite cost against the 870s budget.
    sys.__stderr__.write(
        f"\n[tier-1 budget] tests/obs/test_kernels.py wall-clock: "
        f"{time.perf_counter() - t0:.1f}s\n")


@pytest.fixture
def ledger():
    led = get_kernel_ledger()
    led.reset_for_testing()
    yield led
    led.reset_for_testing()


def _fake_fn(cost, mem, lower_raises=None):
    """A stand-in for a jitted function: .lower(...).compile() returns
    an object with cost_analysis()/memory_analysis()."""
    compiled = SimpleNamespace(cost_analysis=lambda: cost,
                               memory_analysis=lambda: mem)
    lowered = SimpleNamespace(compile=lambda: compiled)

    def lower(*args, **kwargs):
        if lower_raises is not None:
            raise lower_raises
        return lowered

    return SimpleNamespace(lower=lower)


_MEM = SimpleNamespace(argument_size_in_bytes=1000,
                       output_size_in_bytes=200,
                       temp_size_in_bytes=300,
                       generated_code_size_in_bytes=8)


def _dispatch_new(ledger, program, key, fn, elapsed=0.1):
    """Drive the prepare/commit pair the runner's _guarded_call uses."""
    pending = ledger.prepare(program, key, fn,
                             (np.ones((4,), np.float32),), {})
    assert pending is not None
    ledger.commit(pending, elapsed)


def test_keying_new_vs_seen_bucket(ledger):
    ledger.introspect_mode = "on"
    fn = _fake_fn([{"flops": 100.0, "bytes accessed": 50.0}], _MEM)
    _dispatch_new(ledger, "mixed", (8, 128), fn)
    # Same (program, key) again: counted, not re-introspected.
    assert ledger.prepare("mixed", (8, 128), fn, (), {}) is None
    # Same key under another program is a distinct executable.
    assert ledger.prepare("decode_fused", (8, 128), fn, (), {}) is not None

    snap = ledger.snapshot(top=8)
    entry = snap["executables"][0]
    assert entry["program"] == "mixed"
    assert entry["bucket"] == repr((8, 128))
    assert entry["flops"] == 100.0
    assert entry["bytes_accessed"] == 50.0
    assert entry["intensity_flops_per_byte"] == 2.0
    assert entry["hbm_peak_bytes"] == 1000 + 200 + 300 + 8
    assert entry["hbm_temp_bytes"] == 300
    assert entry["compile_seconds"] == pytest.approx(0.1)
    assert entry["dispatches"] == 2
    assert entry["analysis"] == "ok"


def test_cost_analysis_accepts_dict_and_list_forms():
    # jax returns a plain dict on some versions, [dict] on others.
    for raw in ({"flops": 7.0, "bytes accessed": 3.0},
                [{"flops": 7.0, "bytes accessed": 3.0}]):
        parsed = _parse_cost_analysis(raw)
        assert parsed["flops"] == 7.0
        assert parsed["bytes_accessed"] == 3.0
    # XLA's -1 means "unknown": normalized to null, never kept as a
    # negative that would poison sums.
    parsed = _parse_cost_analysis({"flops": -1, "bytes accessed": 4.0})
    assert parsed["flops"] is None
    # Empty / non-dict shapes: every value null, nothing raises.
    assert all(v is None for v in _parse_cost_analysis([]).values())
    assert _parse_cost_analysis(None) == {}
    assert _parse_cost_analysis("garbage") == {}


def test_top_k_ordering_analyzed_first_then_hottest(ledger):
    ledger.introspect_mode = "on"
    fn_small = _fake_fn([{"flops": 10.0, "bytes accessed": 5.0}], _MEM)
    fn_big = _fake_fn([{"flops": 900.0, "bytes accessed": 5.0}], _MEM)
    _dispatch_new(ledger, "mixed", ("small",), fn_small)
    _dispatch_new(ledger, "mixed", ("big",), fn_big)
    ledger.introspect_mode = "off"
    fn_null = _fake_fn(None, None)
    _dispatch_new(ledger, "decode_fused", ("null",), fn_null)
    for _ in range(3):
        assert ledger.prepare("decode_fused", ("null",), fn_null,
                              (), {}) is None

    snap = ledger.snapshot(top=2)
    assert snap["executables_total"] == 3
    assert [e["bucket"] for e in snap["executables"]] == [
        repr(("big",)), repr(("small",))]
    # Null entries sort after analyzed ones but are never dropped from
    # the aggregates.
    assert snap["programs"]["decode_fused"]["dispatches"] == 4
    assert snap["programs"]["decode_fused"]["flops_max"] is None


def test_failed_first_dispatch_forgets_the_key(ledger):
    ledger.introspect_mode = "on"
    fn = _fake_fn([{"flops": 1.0}], _MEM)
    pending = ledger.prepare("mixed", ("oom",), fn, (), {})
    assert pending is not None
    ledger.abandon(pending)  # dispatch raised
    # Retry is introspected fresh, not treated as a cache hit.
    assert ledger.prepare("mixed", ("oom",), fn, (), {}) is not None
    assert ledger.snapshot(top=1)["executables_total"] == 0


def test_introspection_failure_degrades_to_null_entry(ledger):
    """Satellite regression test: cost_analysis()/memory_analysis()
    raising or returning empty must produce a null entry — NaN-not-0 on
    gauges, None in JSON — and NEVER an exception on the dispatch
    path."""
    ledger.introspect_mode = "on"
    # lower() raises outright.
    fn_raise = _fake_fn(None, None, lower_raises=RuntimeError("no aot"))
    _dispatch_new(ledger, "mixed", ("raise",), fn_raise)  # must not throw
    # cost_analysis returns empty, memory_analysis raises.
    def _mem_raises():
        raise NotImplementedError("cpu")
    compiled = SimpleNamespace(cost_analysis=lambda: [],
                               memory_analysis=_mem_raises)
    fn_empty = SimpleNamespace(
        lower=lambda *a, **k: SimpleNamespace(compile=lambda: compiled))
    _dispatch_new(ledger, "mixed", ("empty",), fn_empty)

    snap = ledger.snapshot(top=8)
    by_bucket = {e["bucket"]: e for e in snap["executables"]}
    for bucket, status in ((repr(("raise",)), "error"),
                           (repr(("empty",)), "empty")):
        entry = by_bucket[bucket]
        assert entry["analysis"] == status
        for field in ("flops", "bytes_accessed", "hbm_peak_bytes",
                      "hbm_temp_bytes", "intensity_flops_per_byte"):
            assert entry[field] is None, (bucket, field)
    # The gauges read NaN (never 0) while no executable is analyzed.
    if ledger._metrics is not None:
        value = ledger._metrics.gauge_flops.labels("mixed")._value.get()
        assert math.isnan(value)
    # The JSON stays serializable with the nulls in place.
    json.dumps(snap)


def test_cpu_auto_mode_creates_null_entries(ledger, monkeypatch):
    """Default `auto` on the CPU backend: entries exist for every
    bucket, analysis fields are null — introspection's second compile
    is not paid on the tier-1 backend."""
    monkeypatch.delenv("INTELLILLM_KERNEL_INTROSPECT", raising=False)
    ledger.reset_for_testing()
    assert ledger.introspect_mode == "auto"
    import jax
    fn = jax.jit(lambda x: x + 1)
    x = np.ones((4,), np.float32)
    pending = ledger.prepare("mixed", ("cpu",), fn, (x,), {})
    fn(x)
    ledger.commit(pending, 0.05)
    entry = ledger.snapshot(top=1)["executables"][0]
    assert entry["analysis"] == "skipped"
    assert entry["flops"] is None and entry["bytes_accessed"] is None
    assert entry["compile_seconds"] == pytest.approx(0.05)


def test_mfu_costmodel_window_and_unknown_poisoning(ledger, monkeypatch):
    monkeypatch.setenv("INTELLILLM_PEAK_FLOPS", "1e6")
    ledger.reset_for_testing()
    ledger.introspect_mode = "on"
    fn = _fake_fn([{"flops": 5e3, "bytes accessed": 1.0}], _MEM)
    _dispatch_new(ledger, "mixed", ("a",), fn)
    # 5e3 FLOPs in 0.01s against a 1e6 FLOP/s peak: MFU = 0.5.
    assert ledger.record_step(0.01) == pytest.approx(0.5)
    assert ledger.snapshot(top=0)["mfu_costmodel"] == pytest.approx(0.5)
    if ledger._metrics is not None:
        assert ledger._metrics.gauge_mfu_costmodel._value.get() == \
            pytest.approx(0.5)

    # A dispatch with unknown FLOPs poisons the step: a partial sum
    # would silently understate MFU, so the window reads null instead.
    ledger.introspect_mode = "off"
    _dispatch_new(ledger, "mixed", ("null",), _fake_fn(None, None))
    assert ledger.record_step(0.01) is None
    assert ledger.snapshot(top=0)["mfu_costmodel"] is None
    if ledger._metrics is not None:
        assert math.isnan(
            ledger._metrics.gauge_mfu_costmodel._value.get())
    # Known steps rebuild the window afterwards.
    assert ledger.prepare("mixed", ("a",), fn, (), {}) is None
    assert ledger.record_step(0.01) == pytest.approx(0.5)


def _doc():
    """One chip, two programs of a fused-decode server, and the host's
    annotations on the same clock (ns):

        device  mixed        [1000, 3000): copy [1000,1200) then a while
                             [1200,3000) around two fusions of 400
                decode_cont  [9000, 12000): one fusion
        host    loop thread      stream_out [3100,4000) [4000,5000)
                                 admit [5100,5600)
                executor thread  schedule [6000,7000) holding
                                 prepare_inputs [6200,6800);
                                 execute [7000,9500); stats [12100,12500)
    """
    ops = [["%copy.1 = bf16[8,8] copy(%p)", 1000, 200],
           ["%while.2 = (s32[]) while(%t), body=%b", 1200, 1800],
           ["%fusion.3 = bf16[8,8] fusion(%x)", 1300, 400],
           ["%fusion.3 = bf16[8,8] fusion(%x)", 2000, 400],
           ["%fusion.9 = bf16[8,8] fusion(%y)", 9000, 3000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__decode_fn_single(77)", 1000, 2000],
                ["jit__decode_cont_fn(78)", 9000, 3000]]},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": [
                ["%copy-start.5 = copy-start(%p)", 0, 20000]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["intellillm/stream_out", 3100, 900],
                ["intellillm/stream_out", 4000, 1000],
                ["intellillm/admit", 5100, 500]]},
            {"name": "python", "events": [
                ["intellillm/schedule", 6000, 1000],
                ["intellillm/prepare_inputs", 6200, 600],
                ["intellillm/execute", 7000, 2500],
                ["intellillm/stats", 12100, 400]]}]},
        {"name": "/host:metadata", "lines": []}]}


def test_reduce_profile_busy_union_and_self_times():
    r = reduce_profile(_doc())
    assert r["window_s"] == pytest.approx(11000e-9)
    assert r["busy_s"] == pytest.approx(5000e-9)     # 2000 + 3000
    assert r["idle_s"] == pytest.approx(6000e-9)
    assert r["idle_share"] == pytest.approx(6 / 11)
    assert r["devices"] == [{"plane": "/device:TPU:0",
                             "busy_s": pytest.approx(5000e-9),
                             "op_events": 5}]
    ops = {name.split(" = ")[0]: (sec, n) for name, sec, n in r["ops"]}
    # the enclosing while keeps only what its body leaves: 1800 - 800
    assert ops["%while.2"] == (pytest.approx(1000e-9), 1)
    assert ops["%fusion.3"] == (pytest.approx(800e-9), 2)
    assert ops["%fusion.9"] == (pytest.approx(3000e-9), 1)
    assert ops["%copy.1"] == (pytest.approx(200e-9), 1)
    # self times add up to the busy time: nothing counted twice, nothing
    # from the module line, the async line or the host
    assert sum(sec for _, sec, _ in r["ops"]) == pytest.approx(r["busy_s"])
    assert not [n for n, _, _ in r["ops"] if "jit_" in n or "copy-start" in n]
    # programs under the engine's names, whole durations
    assert r["programs"] == [["decode_cont", pytest.approx(3000e-9), 1],
                             ["mixed", pytest.approx(2000e-9), 1]]


def test_reduce_profile_charges_the_idle_gap_to_host_phases():
    """The one gap, [3000, 9000), split over the innermost annotation at
    each instant; holes between the threads' annotations are the two
    thread crossings, any other hole is `unannotated`."""
    r = reduce_profile(_doc())
    ns = {phase: round(sec * 1e9) for phase, sec in r["idle_by_phase"].items()}
    assert ns == {
        "stream_out": 1900,         # [3100, 5000)
        "execute": 2000,            # [7000, 9000): the gap ends inside it
        "schedule": 400,            # [6000,6200) + [6800,7000): exclusive
        "prepare_inputs": 600,      # nested in schedule, innermost
        "admit": 500,
        "handoff": 400,             # after admit, before the executor's first
        "unannotated": 200,         # [3000,3100) + [5000,5100)
    }
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    assert list(r["idle_by_phase"])[0] == "execute"      # largest first
    # every share of the gap also says which program ended it
    assert {(p, prog) for p, prog, _, _ in r["idle_by_phase_program"]} == {
        (p, "decode_cont") for p in ns}
    assert r["annotations"] == {"stream_out": 2, "admit": 1, "schedule": 1,
                                "prepare_inputs": 1, "execute": 1,
                                "stats": 1}


def test_reduce_profile_names_the_return_crossing_and_needs_device_ops():
    doc = _doc()
    # a third program after the stats span, then streaming: the hole
    # between `stats` (executor) and `stream_out` (loop) is the handback
    doc["planes"][0]["lines"][1]["events"].append(
        ["%fusion.9 = bf16[8,8] fusion(%y)", 14000, 100])
    doc["planes"][1]["lines"][0]["events"].append(
        ["intellillm/stream_out", 12900, 1100])
    r = reduce_profile(doc)
    ns = {p: round(s * 1e9) for p, s in r["idle_by_phase"].items()}
    assert ns["handback"] == 400                  # [12500, 12900)
    assert ns["stats"] == 400 and ns["stream_out"] == 1900 + 1100
    assert ns["unannotated"] == 200 + 100         # ... and [12000, 12100)
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    by_program = {}
    for phase, prog, sec, _ in r["idle_by_phase_program"]:
        by_program[prog] = by_program.get(prog, 0) + round(sec * 1e9)
    assert by_program == {"decode_cont": 6000, "%fusion.9": 2000}
    with pytest.raises(ValueError, match="no device operations"):
        reduce_profile({"planes": [{"name": "/host:CPU", "lines": []}]})


@pytest.fixture(scope="module")
def cpu_trace_dir(tmp_path_factory):
    """A real jax.profiler trace on the CPU backend, started and stopped
    through `LLMEngine.start_profile` / `stop_profile` (the one place the
    server's traces start): three 'steps', each annotated by a StepTracer
    under the profile flag — the loop thread admits, another thread
    schedules, dispatches and waits."""
    import threading

    import jax
    import jax.numpy as jnp

    from intellillm_tpu.engine.llm_engine import LLMEngine
    from intellillm_tpu.obs.tracing import StepTracer

    logdir = str(tmp_path_factory.mktemp("cpu-trace"))
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    fn(x).block_until_ready()
    tracer = StepTracer(enabled=True)

    def step():
        tracer.end_crossing("handoff")
        tracer.begin_step()
        with tracer.span("schedule"):
            time.sleep(0.002)
        with tracer.span("execute"):
            y = fn(x)
        with tracer.span("sample"):
            with tracer.span("fetch_wait"):
                y.block_until_ready()
        tracer.end_step(work_pending=True)
        tracer.begin_crossing()

    engine = object.__new__(LLMEngine)
    engine._tracer = tracer
    assert engine.start_profile(logdir) == logdir
    try:
        for _ in range(3):
            with tracer.span("admit"):
                time.sleep(0.001)
            tracer.begin_crossing()
            t = threading.Thread(target=step)
            t.start()
            t.join()
            tracer.end_crossing("handback")
            with tracer.span("stream_out"):
                time.sleep(0.001)
    finally:
        engine.stop_profile()
    return logdir


def test_cpu_profile_carries_the_annotations_and_every_gap_has_a_phase(
        cpu_trace_dir):
    doc = load_profile(cpu_trace_dir)
    assert doc["file"].endswith(".xplane.pb")
    host = [p for p in doc["planes"] if p["name"] == "/host:CPU"]
    assert host, [p["name"] for p in doc["planes"]]
    names = {e[0] for ln in host[0]["lines"] for e in ln["events"]}
    # the tracer's spans are in the host plane, on its clock; python
    # frames of the host lines are not loaded
    assert {"intellillm/admit", "intellillm/schedule", "intellillm/execute",
            "intellillm/fetch_wait", "intellillm/sample",
            "intellillm/stream_out"} <= names
    assert not [n for n in names if n.startswith("$")]

    r = reduce_profile(doc)
    assert r["annotations"]["execute"] == 3
    assert r["annotations"]["fetch_wait"] == 3
    # the CPU client's thunks stand in for device operations, without the
    # thread pool's bookkeeping events
    assert r["busy_s"] > 0 and r["ops"]
    assert not [n for n, _, _ in r["ops"] if "::" in n or n.startswith("end: ")]
    assert r["programs"] == []          # no module line on this backend
    # every idle gap between the three steps' operations lands in a phase
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    assert r["idle_s"] > 0.005          # 2 x (1 + 1 + 2) ms of sleeps
    idle = r["idle_by_phase"]
    for phase in ("admit", "schedule", "stream_out"):
        assert idle[phase] >= 0.0015, idle
    # and the two thread crossings are named, not left unannotated
    assert idle.get("handoff", 0.0) > 0 and idle.get("handback", 0.0) > 0
    assert capture_profile(cpu_trace_dir)["busy_s"] == r["busy_s"]


def test_a_trace_started_by_the_engine_has_no_python_tracer_events(
        cpu_trace_dir):
    """`start_profile` turns jax's Python tracer off (under it the host
    runs 1.5-3x slower, and a trace shows idle the program does not
    have) and keeps the host tracer that records the annotations. Read
    raw, not through `load_profile`, which would filter them out."""
    import glob
    import os

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(cpu_trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events]
    assert sum(n.startswith("intellillm/") for n in names) >= 3 * 6
    # the Python tracer names a frame `$<file>:<line> <function>`
    assert not [n for n in names if n.startswith("$")][:5]
    assert not [n for n in names if ".py:" in n][:5]


def test_load_profile_missing_or_corrupt_is_none(tmp_path):
    assert load_profile(str(tmp_path / "nowhere")) is None
    assert capture_profile(str(tmp_path)) is None
    bad = tmp_path / "plugins" / "profile" / "x"
    bad.mkdir(parents=True)
    (bad / "host.xplane.pb").write_bytes(b"not a profile at all")
    # unreadable, or readable as an empty profile: nothing to reduce
    assert capture_profile(str(tmp_path)) is None


def test_merge_profile_stores_the_block_with_top_k_ops(ledger):
    reduced = reduce_profile(_doc())
    block = ledger.merge_profile(reduced, steps=4, top=2)
    assert block["steps"] == 4
    assert block["ops_total"] == 4
    assert [n.split(" = ")[0] for n, _, _ in block["ops"]] == [
        "%fusion.9", "%while.2"]
    assert block["idle_by_phase"]["execute"] == pytest.approx(2000e-9)
    assert len(reduced["ops"]) == 4         # the caller's block is not cut
    snap = ledger.snapshot(top=0)
    assert snap["profile"]["busy_s"] == pytest.approx(5000e-9)
    assert snap["profile"]["captured_at_step"] == 0
    json.dumps(snap)
    # a capture that left nothing readable: an empty block, not an error
    empty = ledger.merge_profile(None, steps=0)
    assert empty["ops_total"] == 0 and empty["idle_by_phase"] == {}
    assert ledger.snapshot(top=0)["profile"]["idle_share"] is None


def test_reset_for_testing_clears_everything(ledger):
    ledger.introspect_mode = "on"
    _dispatch_new(ledger, "mixed", ("k",),
                  _fake_fn([{"flops": 1.0}], _MEM))
    ledger.merge_profile(reduce_profile(_doc()), steps=1)
    ledger.record_step(0.01)
    ledger.reset_for_testing()
    snap = ledger.snapshot(top=4)
    assert snap["executables_total"] == 0
    assert snap["steps"] == 0
    assert snap["profile"] is None
    # The key space is forgotten too: the same bucket is "new" again.
    assert ledger.prepare("mixed", ("k",), _fake_fn(None, None),
                          (), {}) is not None


def test_disabled_ledger_is_a_noop(monkeypatch):
    monkeypatch.setenv("INTELLILLM_KERNEL_LEDGER", "0")
    led = get_kernel_ledger()
    led.reset_for_testing()
    try:
        assert led.prepare("mixed", ("k",), _fake_fn(None, None),
                           (), {}) is None
        assert led.record_step(0.01) is None
        assert led.snapshot(top=4)["enabled"] is False
    finally:
        monkeypatch.delenv("INTELLILLM_KERNEL_LEDGER")
        led.reset_for_testing()
