"""BootTimeline on a scripted clock: the phases start at the process's
start, never overlap, and sum with `unphased_s` to `total_s`."""
import pytest

from intellillm_tpu.obs.boot import (BootTimeline, get_boot_timeline,
                                     seconds_since_process_start)


class Clock:
    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now

    def tick(self, seconds):
        self.now += seconds


def served_boot():
    """A server's boot, second by second. The process was 3 s old when
    the timeline was made."""
    clock = Clock()
    boot = BootTimeline(clock=clock, age_s=3.0)
    clock.tick(2.0)
    boot.mark("imports")                        # 5 s since the start
    clock.tick(0.5)                             # argument parsing
    with boot.phase("engine_init"):
        clock.tick(1.0)                         # configs, tokenizer
        with boot.phase("init_model"):
            clock.tick(0.25)
            with boot.phase("runtime_init"):
                clock.tick(4.0)
            clock.tick(0.25)
        with boot.phase("load_model"):
            clock.tick(8.0)
        clock.tick(0.5)
        with boot.phase("cache_init"):
            clock.tick(2.0)
        with boot.phase("warmup_compile"):
            clock.tick(16.0)
        clock.tick(1.0)                         # scheduler, telemetry
        boot.mark_complete()
    clock.tick(0.75)
    boot.mark_serving()
    return boot, clock


def test_phases_do_not_overlap_and_sum_with_unphased_to_total():
    boot, _ = served_boot()
    snap = boot.snapshot()
    assert snap["phases_s"] == {
        "imports": 5.0, "engine_init": 2.5, "init_model": 0.5,
        "runtime_init": 4.0, "load_model": 8.0, "weights_load": 8.5,
        "cache_init": 2.0, "warmup_compile": 16.0, "serve_start": 0.75}
    assert snap["unphased_s"] == 0.5
    assert snap["complete"] is True
    # Process start to the server answering, on the clock.
    assert snap["total_s"] == 39.25
    parts = {k: v for k, v in snap["phases_s"].items()
             if k != "weights_load"}
    assert sum(parts.values()) + snap["unphased_s"] == snap["total_s"]


def test_weights_load_is_init_model_plus_load_model():
    phases = served_boot()[0].snapshot()["phases_s"]
    assert phases["weights_load"] == (phases["init_model"]
                                      + phases["load_model"])


def test_where_names_the_innermost_open_phase():
    boot = BootTimeline(clock=Clock(), age_s=0.0)
    assert boot.where() == "serving"
    with boot.phase("engine_init"):
        assert boot.where() == "engine_init"
        with boot.phase("load_model"):
            assert boot.where() == "load_model"
        assert boot.where() == "engine_init"
    assert boot.where() == "serving"


def test_nothing_is_charged_after_the_boot_and_total_waits_for_it():
    clock = Clock()
    boot = BootTimeline(clock=clock, age_s=1.0)
    with boot.phase("load_model"):
        clock.tick(2.0)
        assert boot.snapshot()["total_s"] is None
    boot.mark_complete()
    clock.tick(5.0)
    boot.mark_complete()                        # the first one stands
    with boot.phase("cache_init"):              # a second engine's
        clock.tick(7.0)
        assert boot.where() == "cache_init"
    snap = boot.snapshot()
    assert snap["phases_s"] == {"load_model": 2.0, "weights_load": 2.0}
    assert snap["unphased_s"] == 1.0
    # Without an HTTP server the boot ends where it is complete.
    assert snap["total_s"] == 3.0


def test_mark_serving_needs_a_complete_boot_and_counts_once():
    clock = Clock()
    boot = BootTimeline(clock=clock, age_s=0.0)
    boot.mark_serving()
    assert "serve_start" not in boot.snapshot()["phases_s"]
    boot.mark_complete()
    clock.tick(0.5)
    boot.mark_serving()
    clock.tick(9.0)
    boot.mark_serving()
    assert boot.snapshot()["phases_s"]["serve_start"] == 0.5


def test_info_blocks_ride_the_snapshot():
    boot = BootTimeline(clock=Clock(), age_s=0.0)
    boot.set_info("warmup", {"executables": 2})
    assert boot.snapshot()["warmup"] == {"executables": 2}


def test_the_process_start_is_the_kernels():
    age = seconds_since_process_start()
    # pytest has been running for a moment, and not for a day.
    assert 0.0 < age < 86400.0
    assert seconds_since_process_start() >= age


def test_the_phase_gauge_follows_the_snapshot():
    pytest.importorskip("prometheus_client")
    from prometheus_client import REGISTRY
    boot, _ = served_boot()
    try:
        for phase, seconds in (("imports", 5.0), ("weights_load", 8.5),
                               ("serve_start", 0.75), ("unphased", 0.5)):
            assert REGISTRY.get_sample_value(
                "intellillm_boot_phase_seconds", {"phase": phase}) == seconds
    finally:
        boot.reset_for_testing()
    assert REGISTRY.get_sample_value("intellillm_boot_phase_seconds",
                                     {"phase": "imports"}) is None


def test_the_global_timeline_is_one_object():
    assert get_boot_timeline() is get_boot_timeline()
