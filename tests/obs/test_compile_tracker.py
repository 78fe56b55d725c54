"""CompileTracker unit tests: exactly one compile per new jit bucket,
zero on cache hits, and snapshot contents."""
from intellillm_tpu.obs.compile_tracker import (CompileTracker,
                                                get_compile_tracker)


def test_first_call_is_compile_repeat_is_hit():
    t = CompileTracker(enabled=True)
    calls = []

    def fn(x, y=0):
        calls.append((x, y))
        return x + y

    assert t.call("prefill", (8, 16), fn, 1, y=2) == 3
    snap = t.snapshot()
    assert snap["compiles"] == {"prefill": 1}
    assert snap["cache_hits"] == {}
    assert snap["compile_time_seconds"]["prefill"] >= 0.0
    assert snap["live_executables"] == 1

    # Same bucket again: a cache hit, never a second compile.
    assert t.call("prefill", (8, 16), fn, 5, y=5) == 10
    snap = t.snapshot()
    assert snap["compiles"] == {"prefill": 1}
    assert snap["cache_hits"] == {"prefill": 1}
    assert calls == [(1, 2), (5, 5)]


def test_new_bucket_compiles_again():
    t = CompileTracker(enabled=True)
    fn = lambda: None  # noqa: E731
    t.call("decode_single", (8, 4), fn)
    t.call("decode_single", (16, 4), fn)  # different batch bucket
    t.call("decode_fused", (8, 4), fn)    # same key, different program
    snap = t.snapshot()
    assert snap["compiles"] == {"decode_single": 2, "decode_fused": 1}
    assert snap["cache_hits"] == {}
    assert snap["live_executables"] == 3


def test_many_hits_single_compile():
    t = CompileTracker(enabled=True)
    for _ in range(10):
        t.call("decode_cont", (4, 2, True), lambda: 1)
    snap = t.snapshot()
    assert snap["compiles"] == {"decode_cont": 1}
    assert snap["cache_hits"] == {"decode_cont": 9}


def test_kernel_dispatch_counts():
    t = CompileTracker(enabled=True)
    t.record_kernel_dispatch("pallas")
    t.record_kernel_dispatch("reference")
    t.record_kernel_dispatch("reference")
    assert t.snapshot()["kernel_dispatch"] == {"pallas": 1, "reference": 2}


def test_disabled_tracker_passes_through():
    t = CompileTracker(enabled=False)
    assert t.call("prefill", (1,), lambda v: v * 2, 21) == 42
    t.record_kernel_dispatch("pallas")
    snap = t.snapshot()
    assert snap["compiles"] == {}
    assert snap["kernel_dispatch"] == {}


def test_failed_first_dispatch_is_not_a_cache_hit():
    t = CompileTracker(enabled=True)

    def boom():
        raise RuntimeError("compile failed")

    try:
        t.call("prefill", (2,), boom)
    except RuntimeError:
        pass
    # A failed first dispatch (e.g. compile OOM) never produced an
    # executable: nothing is recorded and the retry counts as the
    # bucket's (one) real compile, not a hit.
    snap = t.snapshot()
    assert snap["compiles"] == {}
    assert snap["cache_hits"] == {}
    assert snap["live_executables"] == 0
    t.call("prefill", (2,), lambda: None)
    snap = t.snapshot()
    assert snap["compiles"] == {"prefill": 1}
    assert snap["cache_hits"] == {}
    # Only a successful dispatch claims the key: the next call is a hit.
    t.call("prefill", (2,), lambda: None)
    assert t.snapshot()["cache_hits"] == {"prefill": 1}


def test_global_tracker_reset():
    t = get_compile_tracker()
    assert get_compile_tracker() is t
    t.call("prefill", ("test-sentinel-key",), lambda: None)
    t.reset_for_testing()
    assert t.snapshot()["compiles"] == {}


# --- stages: a scripted feed of jax.monitoring events ---------------------

import json  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402
from jax import monitoring  # noqa: E402
from jax._src import monitoring as monitoring_registry  # noqa: E402

from intellillm_tpu.obs.boot import get_boot_timeline  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def span(event, seconds, fun_name="f", inside=()):
    """What jax's `log_elapsed_time` reports around a stage: a scalar as
    it opens, whatever happens inside, the duration as it closes."""
    monitoring.record_scalar(event, 0.0, fun_name=fun_name)
    for thunk in inside:
        thunk()
    monitoring.record_event_duration_secs(event, seconds, fun_name=fun_name)


def compile_events(trace=0.5, lower=0.25, backend=1.0, read=None,
                   result=MISS, name="step"):
    """One jitted function's first call, as jax reports it."""
    span(TRACE, trace, name)
    span(LOWER, lower, f"jit({name})")
    inside = []
    if result is not None:
        inside.append(lambda: monitoring.record_event(ASKED))
        if result != "not_cached":
            inside.append(lambda: monitoring.record_event(result))
    if read is not None:
        inside.append(lambda: monitoring.record_event_duration_secs(
            CACHE_READ, read))
    span(BACKEND, backend, f"jit({name})", inside)


@pytest.fixture
def tracker():
    t = CompileTracker(enabled=True)
    t.install_listeners()
    yield t
    t.reset_for_testing()


def test_stages_go_to_the_first_call_open_on_the_calling_thread(tracker):
    in_call = threading.Barrier(2, timeout=10)

    def first_call(trace):
        in_call.wait()          # both first calls are open at once
        compile_events(trace=trace, lower=0.25, backend=1.0)
        in_call.wait()

    threads = [threading.Thread(
        target=tracker.call, args=(program, (1, ), first_call, trace))
        for program, trace in (("mixed", 0.5), ("decode_fused", 2.0))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()
    by_program = tracker.setup_record()["by_program"]
    assert by_program["mixed"]["by_stage_s"]["trace"] == 0.5
    assert by_program["decode_fused"]["by_stage_s"]["trace"] == 2.0
    for program in ("mixed", "decode_fused"):
        assert by_program[program]["by_stage_s"]["lower"] == 0.25
        assert by_program[program]["by_stage_s"]["compile"] == 1.0
        assert by_program[program]["cache"]["miss"] == 1
    assert "untracked" not in by_program


def test_a_nested_trace_is_counted_once_and_once_by_name(tracker):
    def first_call():
        inner = [lambda: span(TRACE, 0.125, "_ssd_chunk_call"),
                 lambda: span(TRACE, 0.25, "_ssd_chunk_call",
                              [lambda: span(TRACE, 0.0625, "add")])]
        span(TRACE, 1.0, "mixed_step", inner)

    tracker.call("mixed", (1, ), first_call)
    record = tracker.setup_record()
    assert record["by_program"]["mixed"]["by_stage_s"]["trace"] == 1.0
    by_name = {f["fun_name"]: f for f in record["traced_functions"]}
    assert by_name["_ssd_chunk_call"] == {
        "fun_name": "_ssd_chunk_call", "seconds": 0.375, "count": 2}
    assert by_name["add"]["seconds"] == 0.0625
    assert "mixed_step" not in by_name


def test_introspection_is_its_own_stage_and_no_other(tracker):
    tracker.call("mixed", (1, ), compile_events)
    with tracker.introspection("mixed", (1, )) as took:
        compile_events(trace=0.0, lower=4.0, backend=2.0, read=1.0,
                       result=HIT)
    record = tracker.setup_record()
    stages = record["by_program"]["mixed"]["by_stage_s"]
    assert stages["introspect"] == pytest.approx(took["seconds"], abs=1e-5)
    assert (stages["trace"], stages["lower"], stages["compile"],
            stages["cache_read"]) == (0.5, 0.25, 1.0, 0.0)
    assert record["by_program"]["mixed"]["cache"]["hit"] == 0
    call, = record["calls"]
    assert call["stages"]["introspect"] == stages["introspect"]
    assert call["introspect_detail_s"] == {"lower": 4.0, "compile": 1.0,
                                           "cache_read": 1.0}
    first = record["first_calls"]
    assert first["total_s"] == pytest.approx(
        first["wall_s"] + stages["introspect"], abs=1e-5)
    assert "untracked" not in record["by_program"]


@pytest.mark.parametrize("backend, read, compile_s", [
    (3.0, 1.0, 2.0),        # a hit: what is left is the key's hashing
    (1.0, 1.5, 0.0),        # clocks disagree: never negative
])
def test_compile_is_backend_less_the_cache_read(tracker, backend, read,
                                                compile_s):
    tracker.call("mixed", (1, ), compile_events, backend=backend,
                 read=read, result=HIT)
    call, = tracker.setup_record()["calls"]
    assert call["stages"]["compile"] == compile_s
    assert call["stages"]["cache_read"] == read
    assert call["cache"] == {"hit": 1, "miss": 0, "not_cached": 0,
                             "not_asked": 0}


@pytest.mark.parametrize("result, expected", [
    (HIT, "hit"), (MISS, "miss"), ("not_cached", "not_cached"),
    (None, "not_asked")])
def test_each_executable_has_one_cache_result(tracker, result, expected):
    tracker.call("decode_cont", (1, ), compile_events, result=result)
    cache = tracker.setup_record()["first_calls"]["cache"]
    assert cache == {**dict.fromkeys(("hit", "miss", "not_cached",
                                      "not_asked"), 0), expected: 1}


def test_a_failed_first_call_leaves_no_stages(tracker):
    def boom():
        compile_events()
        raise RuntimeError("compile failed")

    with pytest.raises(RuntimeError):
        tracker.call("mixed", (1, ), boom)
    record = tracker.setup_record()
    assert record["by_program"] == {} and record["calls"] == []
    assert record["first_calls"]["count"] == 0
    # The retry is the bucket's one first call, and nothing leaks into it.
    tracker.call("mixed", (1, ), compile_events)
    assert tracker.setup_record()["by_program"]["mixed"]["by_stage_s"][
        "trace"] == 0.5


class CountingLock:
    def __init__(self):
        self.lock, self.taken = threading.Lock(), 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_seen_bucket_fires_no_listener_and_takes_no_new_lock(tracker):
    import jax
    import jax.numpy as jnp
    step = jax.jit(lambda x: x * 2)
    x = jnp.ones((4, ))
    tracker.call("decode_cont", (4, ), step, x)
    heard = []

    def hear(event, *args, **kwargs):
        heard.append(event)

    tracker.install_listeners = lambda: heard.append("install_listeners")
    monitoring.register_scalar_listener(hear)
    monitoring.register_event_duration_secs_listener(hear)
    monitoring.register_event_listener(hear)
    tracker._lock = CountingLock()
    before = tracker.setup_record()
    taken = tracker._lock.taken
    try:
        tracker.call("decode_cont", (4, ), step, x).block_until_ready()
    finally:
        monitoring.unregister_scalar_listener(hear)
        monitoring.unregister_event_duration_listener(hear)
        monitoring.unregister_event_listener(hear)
    # The two acquisitions the tracker always made: the key's look-up and
    # the hit's count. jax reports nothing for a compiled executable's
    # dispatch, so no listener ran.
    assert tracker._lock.taken - taken == 2
    assert heard == []
    assert tracker.setup_record() == before


def test_reset_for_testing_unregisters_the_listeners():
    def ours(listeners):
        return [cb for cb in listeners
                if getattr(cb, "__self__", None) is t]

    t = CompileTracker(enabled=True)
    t.install_listeners()
    t.install_listeners()                   # idempotent
    assert len(ours(monitoring_registry.get_event_duration_listeners())) == 1
    assert len(ours(monitoring_registry.get_event_listeners())) == 1
    assert len(ours(monitoring_registry.get_scalar_listeners())) == 1
    t.reset_for_testing()
    assert not ours(monitoring_registry.get_event_duration_listeners())
    assert not ours(monitoring_registry.get_event_listeners())
    assert not ours(monitoring_registry.get_scalar_listeners())
    compile_events()                        # nobody of ours is listening
    assert t.setup_record()["by_program"] == {}


def test_untracked_work_is_charged_to_the_open_boot_phase(tracker):
    boot = get_boot_timeline()
    with boot.phase("load_model"):
        compile_events(trace=0.5, lower=0.25, backend=3.0, read=1.0,
                       result=HIT, name="_init_weights")
        span(TRACE, 0.125, "eval_shape")    # traced, never compiled
    compile_events(trace=0.0, lower=0.0, backend=0.5, result="not_cached",
                   name="gather")
    record = tracker.setup_record()
    assert record["by_phase_stage_s"]["load_model"] == {
        "trace": 0.625, "lower": 0.25, "compile": 2.0, "cache_read": 1.0,
        "introspect": 0.0, "rest": 0.0}
    assert record["by_phase_stage_s"]["serving"]["compile"] == 0.5
    untracked = record["by_program"]["untracked"]
    assert untracked["count"] == 2
    assert untracked["wall_s"] == 4.375
    assert untracked["cache"] == {"hit": 1, "miss": 0, "not_cached": 1,
                                  "not_asked": 0}
    first = record["first_calls"]
    assert first["count"] == 0 and first["wall_s"] == 0.0
    assert first["untracked_s"] == 4.375
    assert first["by_stage_s"]["trace"] == 0.625
    assert record["untracked_functions"][0] == {
        "fun_name": "_init_weights", "seconds": 3.75, "count": 3}


def test_the_record_is_json_safe_and_keeps_the_newest_128_calls(tracker):
    for i in range(130):
        tracker.call("mixed", (i, ), compile_events)
    record = json.loads(json.dumps(tracker.setup_record()))
    assert len(record["calls"]) == 128
    assert record["calls"][-1]["bucket"] == "(129,)"
    assert record["first_calls"]["count"] == 130
    assert record["by_program"]["mixed"]["by_stage_s"]["trace"] == 65.0
    share = record["first_calls"]["accounted_share"]
    assert share is not None and 0.0 <= share
    for call in record["calls"]:
        assert set(call) >= {"program", "bucket", "where", "start_unix",
                             "wall_s", "stages", "cache"}
