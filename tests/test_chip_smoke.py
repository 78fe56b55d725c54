"""chip_smoke.py passes only on a chip.

Run here at a tiny size under JAX_PLATFORMS=cpu it is a rehearsal of its
control flow: every phase runs, then the device check refuses. A smoke
that passes without an accelerator is the fallback this repo removed.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, devices=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    return r, lines


def _assert_refused(r, lines):
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr
    assert not any("ok" in line for line in lines)


@pytest.fixture(scope="module")
def one_chip():
    """One rehearsal of the one-chip flow, read by the tests of its phases
    (a file is one worker's under `--dist loadfile`): the exit and the
    lines."""
    return _run("--size", "tiny", "--rehearse")


@pytest.fixture(scope="module")
def four_chip():
    return _run("--chips", "4", "--size", "tiny", "--rehearse", devices=4)


def _event(lines, phase, event):
    [line] = [x for x in lines
              if (x.get("phase"), x.get("event")) == (phase, event)]
    return line


def test_without_a_size_it_refuses_at_once():
    # As the driver runs it: nothing on stdout, non-zero, at once.
    r, lines = _run(timeout=120)
    _assert_refused(r, lines)
    assert r.stdout.strip() == ""


def test_one_chip_flow_rehearses_then_refuses(one_chip):
    r, lines = one_chip
    _assert_refused(r, lines)
    assert _event(lines, "device", None)["platform"] == "cpu"
    # It got as far as the device check, after every phase.
    assert lines[-1]["phase"] == "all"


def test_serve_phase_answers_what_was_asked(one_chip):
    answered = _event(one_chip[1], "serve", "answered")
    assert all(q["tokens"] == q["asked"] and q["finish"] == "length"
               for q in answered["requests"].values())
    assert answered["greedy_repeat_equal"] and answered["seeded_repeat_equal"]


def test_serve_phase_joins_on_the_device_and_drains_for_a_chunked_pass(
        one_chip):
    programs = _event(one_chip[1], "serve", "programs")
    # The prompt over the budget ran its chunks beside the decode batch
    # and joined it by the continuation behind its final chunk's step,
    # its first token read on the device.
    assert programs["decode_join_rows"]["device"] >= 1
    # D and D2, alone on an idle engine, took the fresh chunked pass.
    assert programs["pipeline_drains"]["chunked_prefill"] >= 1
    assert {"mixed", "decode_fused", "decode_cont"} <= set(
        programs["compiles"])


def test_parity_phase_co_batches_and_agrees(one_chip):
    [parity] = [x for x in one_chip[1]
                if x.get("phase") == "parity" and "tokens_equal" in x]
    assert parity["tokens_equal"] and parity["co_batched_steps"] >= 1


# taps over q, k and v of 4 heads of 128, and a matrix a head; taps over
# x, B and C (1,024 + 2 x 128), and the matrix [128, 1,024] in eight lane
# tiles. The CPU serves the oracles.
STATE_MODELS = {
    "solar_open2": ([[[3, 3 * 4 * 128], "float32"],
                     [[4, 128, 128], "float32"]],
                    {"reference:kda_chunk", "reference:kda_step"}),
    "granitemoehybrid": ([[[3, 1280], "float32"],
                          [[8, 128, 128], "float32"]],
                         {"reference:ssd_chunk", "reference:ssd_step"}),
}


@pytest.mark.parametrize("model", STATE_MODELS)
def test_state_phase_holds_the_slots_to_a_fresh_full_pass(one_chip, model):
    """Prefill + decode through the slots against a fresh full pass, the
    two arrays of a slot as each config describes them."""
    states = [x for x in one_chip[1]
              if x.get("phase") == "state" and "tokens_wrong" in x]
    assert [x["model"] for x in states] == list(STATE_MODELS)
    [state] = [x for x in states if x["model"] == model]
    arrays, oracles = STATE_MODELS[model]
    assert state["tokens_wrong"] == []
    assert state["max_abs_dlogprob"] < 1e-3
    assert state["slots_free_at_the_end"] == state["state_slots"] == 8
    assert state["state_arrays"] == arrays
    assert oracles <= set(state["kernel_dispatch"])


def test_four_chip_flow_rehearses_then_refuses(four_chip):
    r, lines = four_chip
    _assert_refused(r, lines)
    assert lines[0]["phase"] == "device"
    assert lines[0]["device_count"] == 4
    # Only the tp phase and what it is compared with: no server booted.
    assert not any(x.get("phase") in ("serve", "parity", "state")
                   for x in lines)
    assert lines[-1]["phase"] == "all"


def test_tp_phase_agrees_across_four_devices(four_chip):
    [tp] = [x for x in four_chip[1]
            if x.get("phase") == "tp" and "tokens_equal" in x]
    assert tp["tensor_parallel_size"] == 4 and tp["tokens_equal"]
    assert len(tp["param_bytes_per_device"]) == 4
    assert all(tp["pool_bytes_per_device"].values())
    assert tp["mixed_step_collectives"]
