"""`/health/detail` `boot.setup` through a real engine on the CPU: every
compiled program has a first call, each call's stages fit its wall time,
and the weights' init programs are the phase `load_model`'s untracked
work."""
import json

import jax
import pytest

from intellillm_tpu import LLM, SamplingParams
from intellillm_tpu.obs import get_boot_timeline, get_compile_tracker
from intellillm_tpu.obs.compile_tracker import STAGES


@pytest.fixture(scope="module")
def record(tiny_llama_dir):
    tracker, boot = get_compile_tracker(), get_boot_timeline()
    tracker.reset_for_testing()
    boot.reset_for_testing()
    # What an earlier test of this process compiled (the seeded weights'
    # init programs are the same for every tiny engine) must be compiled
    # again to be seen.
    jax.clear_caches()
    llm = LLM(model=tiny_llama_dir, load_format="dummy", dtype="float32",
              max_model_len=128, num_device_blocks_override=64)
    llm.generate(["the quick brown fox"],
                 SamplingParams(max_tokens=10, temperature=0.0))
    yield {"setup": tracker.setup_record(), "compiles": tracker.snapshot(),
           "boot": boot.snapshot()}
    tracker.reset_for_testing()


def test_every_compiled_program_has_its_first_calls(record):
    setup, compiles = record["setup"], record["compiles"]
    assert compiles["compiles"], "the engine compiled nothing"
    for program, n in compiles["compiles"].items():
        assert setup["by_program"][program]["count"] == n
        assert setup["by_program"][program]["wall_s"] == pytest.approx(
            compiles["compile_time_seconds"][program], abs=1e-5)
    first = setup["first_calls"]
    assert first["count"] == sum(compiles["compiles"].values())
    assert first["count"] == len(setup["calls"])
    assert first["wall_s"] == pytest.approx(
        sum(compiles["compile_time_seconds"].values()), abs=1e-4)
    # The CPU backend is not introspected (obs/kernels.py, `auto`).
    assert first["total_s"] == first["wall_s"]
    assert first["in_boot_s"] + first["after_boot_s"] == pytest.approx(
        first["total_s"], abs=1e-4)


def test_each_calls_stages_fit_its_wall_time(record):
    for call in record["setup"]["calls"]:
        assert set(call["stages"]) == set(STAGES)
        assert all(v >= 0.0 for v in call["stages"].values())
        staged = sum(v for k, v in call["stages"].items()
                     if k != "introspect")
        assert staged == pytest.approx(call["wall_s"], abs=1e-4)
        assert call["where"] == "serving"       # the CPU has no warm-up
        assert call["start_unix"] > 1.6e9
    # A step program is traced, lowered and compiled where it is first
    # called (its jitted function is this runner's own; a `join_gather`
    # of a shape an earlier test of the process used is served from
    # jax's caches and reads 0).
    mixed = record["setup"]["by_program"]["mixed"]
    assert all(mixed["by_stage_s"][stage] > 0
               for stage in ("trace", "lower", "compile"))
    assert sum(mixed["cache"].values()) >= mixed["count"]
    assert record["setup"]["first_calls"]["accounted_share"] > 0.5


def test_untracked_holds_the_weights_init(record):
    setup = record["setup"]
    load = setup["by_phase_stage_s"]["load_model"]
    assert load["trace"] > 0 and load["compile"] > 0
    assert load["rest"] == 0 and load["introspect"] == 0
    untracked = setup["by_program"]["untracked"]
    assert untracked["count"] >= 1
    assert untracked["wall_s"] == pytest.approx(
        setup["first_calls"]["untracked_s"], abs=1e-5)
    assert setup["untracked_functions"]
    # Everything's stages are the tracked programs' and the untracked's.
    for stage in STAGES:
        assert setup["first_calls"]["by_stage_s"][stage] == pytest.approx(
            sum(p["by_stage_s"][stage]
                for p in setup["by_program"].values()), abs=1e-4)


def test_the_boots_phases_are_in_the_record(record):
    setup, boot = record["setup"], record["boot"]
    assert setup["phases_s"] == boot["phases_s"]
    for phase in ("engine_init", "runtime_init", "init_model", "load_model",
                  "weights_load", "cache_init", "warmup_compile"):
        assert phase in setup["phases_s"], phase
    assert setup["pre_engine_s"] == pytest.approx(
        sum(setup["phases_s"].get(p, 0.0) for p in
            ("imports", "runtime_init", "engine_init"))
        + setup["unphased_s"], abs=2e-3)
    parts = sum(v for k, v in boot["phases_s"].items()
                if k != "weights_load")
    assert parts + boot["unphased_s"] == pytest.approx(boot["total_s"],
                                                       abs=0.02)


def test_the_record_is_json_safe_and_bounded(record):
    setup = json.loads(json.dumps(record["setup"]))
    assert setup == record["setup"]
    assert len(setup["calls"]) <= 128
    assert len(setup["traced_functions"]) <= 16
    assert len(setup["untracked_functions"]) <= 16
