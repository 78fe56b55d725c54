"""`benchmarks/chip/reference_check_xing4.py` runs its control flow on the
CPU (a file of its own beside `test_xing4_cell.py`: xdist hands a file to one
worker, and this rehearsal is a minute)."""
import json
import os
import subprocess
import sys

from chipbench import CHIP_DIR, REPO


def _run(script, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(CHIP_DIR, script),
                        *args], env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=timeout)
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    return r, lines


def test_reference_check_rehearses():
    """The chip command's control flow at the tiny preset: engine, weights
    out of the engine, the reference layer by layer, the comparison, and
    wrong references that are seen to be wrong."""
    r, lines = _run("reference_check_xing4.py", "--seed", "3000000045",
                    "--rehearse-on-cpu", "--check", "4", "--layers", "3",
                    "--perturb", "one_sweep,bias_in_the_weights")
    assert r.returncode == 0, r.stderr[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["agrees"] is True and last["broken"] == []
    assert last["stats"]["max_abs"] < 1e-4          # float32 preset
    assert last["stats"]["values"] >= 100
    assert last["perturbations_undetected"] == []
    for how in ("one_sweep", "bias_in_the_weights"):
        assert last["perturbations"][how]["broken"]
    engine = next(x for x in lines if x.get("event") == "engine")
    # 2 dense layers + 1 MoE layer (the preset's 4 cut to 3 for the time)
    assert engine["num_decode_steps"] == 8 and engine["layers"] == 3
    assert {"mixed", "decode_fused"} <= set(engine["compiles"])
    assert engine["mhc"]["stream_mix_bytes"] > 0
