"""Speculative-decoding throughput bracketing on dummy weights.

Real acceptance rates need real checkpoints (random draft/target weights
never agree), so this harness brackets the machinery instead
(reference role: `vllm/worker/spec_decode/` — which the reference never
measured either, since it never wired the scaffold):

  - floor  (a~0):  7B target + 1B draft, real acceptance — every round
                   pays draft K+1 + verify K+1 and emits ~1 token
  - ceiling (a=1): same pair with INTELLILLM_SPEC_FORCE_ACCEPT=1 —
                   every round emits K+1 tokens
  - baseline:      plain 7B fused decode at the same K
  - adaptive:      force-accept with a [1..K] band and a fast controller
                   clock — exercises the K-ladder warm-up plus runtime K
                   transitions under load (the floor/ceiling modes pin K)

Prints one JSON line per mode. Usage:
    python benchmarks/spec_bench.py [--k 4] [--bs 32] [--out 64]
                                    [--modes baseline,floor,ceiling,adaptive]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
# stdlib-only import: this parent stays off JAX, its children need the chip.
from intellillm_tpu.utils import child_process_env  # noqa: E402

_MODE_TIMEOUT_S = 2400.0


def _run_bench_child(env: dict, timeout_s: float):
    """Run one bench.py mode in its OWN process group; on timeout SIGKILL
    the whole group (the TPU runtime forks helpers that hold the device
    and the stderr pipe, so `subprocess.run(timeout=...)` killing only
    the direct child leaves the follow-up mode without a chip). Returns
    (returncode, stdout, stderr); raises TimeoutExpired carrying the
    output produced before the kill."""
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "bench.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            out, err = proc.communicate(timeout=10.0)
        except subprocess.TimeoutExpired:
            out, err = "", ""
        raise subprocess.TimeoutExpired(
            cmd=proc.args, timeout=timeout_s, output=out, stderr=err)


def run_mode(mode: str, args) -> dict:
    """Each mode runs in a subprocess: one TPU process at a time, and the
    force-accept env must not leak between modes."""
    env = child_process_env()
    env["INTELLILLM_BENCH_SIZE"] = "7b"
    env["INTELLILLM_BENCH_BS"] = str(args.bs)
    env["INTELLILLM_BENCH_OUT"] = str(args.out)
    env["INTELLILLM_BENCH_IN"] = str(args.input_len)
    if mode == "baseline":
        env["INTELLILLM_BENCH_K"] = str(args.k + 1)
    else:
        env["INTELLILLM_BENCH_SPEC"] = "1b"
        env["INTELLILLM_BENCH_SPEC_K"] = str(args.k)
        if mode == "ceiling":
            env["INTELLILLM_SPEC_FORCE_ACCEPT"] = "1"
        elif mode == "adaptive":
            # Full band + force-accept + a sub-second controller clock:
            # acceptance stays perfect so the controller grows K toward
            # k_max, crossing several ladder rungs during the run. The
            # mode's value vs ceiling shows what K transitions cost
            # (should be ~free: all rungs are boot-warmed).
            env["INTELLILLM_SPEC_FORCE_ACCEPT"] = "1"
            env["INTELLILLM_BENCH_SPEC_K_MIN"] = "1"
            env["INTELLILLM_BENCH_SPEC_K_MAX"] = str(args.k)
            env["INTELLILLM_SPEC_K_EVAL_S"] = "0.5"
            env["INTELLILLM_SPEC_K_GROW_PATIENCE"] = "2"
    t0 = time.time()
    try:
        rc, stdout, stderr = _run_bench_child(env, _MODE_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:
        tail = (e.stderr or "").strip().splitlines()[-3:]
        return {"mode": mode, "rc": None,
                "wall_s": round(time.time() - t0, 1), "result": None,
                "error": f"timeout after {_MODE_TIMEOUT_S:.0f}s",
                "stderr_tail": tail}
    line = None
    for ln in stdout.strip().splitlines():
        try:
            line = json.loads(ln)
        except json.JSONDecodeError:
            continue
    return {"mode": mode, "rc": rc,
            "wall_s": round(time.time() - t0, 1), "result": line}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--out", type=int, default=64)
    ap.add_argument("--input-len", type=int, default=128)
    ap.add_argument("--modes", default="baseline,floor,ceiling,adaptive")
    args = ap.parse_args()
    results = []
    for mode in args.modes.split(","):
        rec = run_mode(mode.strip(), args)
        print(json.dumps(rec), flush=True)
        results.append(rec)
    ok = [r for r in results if r["result"]]
    print(json.dumps({"spec_bench_summary": {
        r["mode"]: (r["result"] or {}).get("value") for r in results}}))
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
