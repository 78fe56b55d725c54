"""The state-space scan's two kernels at the Granite 4.0-H cell's shapes:
each held to the recurrence on the chip, then timed a call.

    python benchmarks/kernels/benchmark_ssd.py --out chiprun_out/ssd.json

`ssd_step` at 8 / 32 / 128 rows (a decode substep: every row its own slot,
128 heads of 64 over 128 state channels, a float32 pool of 128 slots + the
pad row) and `ssd_chunk` at 16 / 128 / 512 rows, twice: `runs` (runs of
57-171 rows of several sequences, some from position 0, some continuing a
slot, pad rows at the end) and `window` (what the cell's `mixed` steps hold:
a decode row each of up to 127 sequences first, then prompts), dt drawn
from 1e-4 to 4 and the heads' rates from 1 to 16. First the kernel's output
and pool are compared with the oracle's (`ops/linear_attention.py`: the
recurrence a row at a time; largest absolute difference, float32), then
`--calls` chained calls are timed on the host clock around a
`block_until_ready` (the pool is donated from call to call, as the step
programs do), and the bytes and operations of
`benchmarks/chip/harness/costs_ssd.py` are printed beside the time.
A CPU run is a rehearsal of control flow in interpret mode at a tiny size,
never a timing (`--tiny`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.abspath(os.path.join(HERE, "..", ".."))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "chip"))


def draw(rng, t, h, p, n, slots):
    import jax.numpy as jnp
    import numpy as np
    x = rng.normal(size=(t, h, p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-4), np.log(4.0), size=(t, h))).astype(
        np.float32)
    a = -rng.uniform(1.0, 16.0, size=h).astype(np.float32)
    b = rng.normal(size=(t, n)).astype(np.float32)
    c = rng.normal(size=(t, n)).astype(np.float32)
    pool = rng.normal(size=(slots + 1, h * p // 128, n, 128)).astype(
        np.float32)
    return [jnp.asarray(v) for v in (x, dt, a, b, c, pool)]


def rows_of(t, slots, mix):
    """(slots [t], positions [t], runs, from zero) of a `mixed` step.
    `runs`: runs of up to 171 rows, alternately from position 0 and
    continuing, the last eighth of the rows pad. `window`: a decode row
    each of min(127, t / 4) sequences, then prompts of up to 130 rows from
    position 0, the last sixteenth pad."""
    import numpy as np
    live = t - t // (8 if mix == "runs" else 16)
    row_slots = np.full(t, slots, np.int32)
    pos = np.zeros(t, np.int32)
    r, n_runs, zero = 0, 0, 0
    if mix == "window":
        r = n_runs = min(127, t // 4)
        row_slots[:r] = np.arange(r) % slots
        pos[:r] = 100 + np.arange(r)
    while r < live:
        if mix == "runs":
            n = min(171 if n_runs % 2 else 57, live - r)
            start = 0 if n_runs % 2 == 0 else 40
        else:
            n, start = min(130, live - r), 0
        row_slots[r:r + n] = n_runs % slots
        pos[r:r + n] = np.arange(start, start + n)
        zero += start == 0
        n_runs += 1
        r += n
    return row_slots, pos, n_runs, zero


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import costs_ssd
    from intellillm_tpu.ops import linear_attention as la
    from intellillm_tpu.ops.pallas import ssd
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.tiny:
        print("no accelerator: --tiny rehearses the control flow",
              file=sys.stderr)
        return 2
    h, p, n, slots = (8, 32, 16, 8) if args.tiny else (128, 64, 128, 128)
    inner = h * p
    step_rows = (8, ) if args.tiny else (8, 32, 128)
    chunk_rows = (32, ) if args.tiny else (16, 128, 512)
    peak = 819e9
    rows_out = []
    rng = np.random.default_rng(0)

    def timed(fn, pool, *rest):
        pool = jax.block_until_ready(fn(pool, *rest))[1]    # compile
        t0 = time.perf_counter()
        for _ in range(args.calls):
            y, pool = fn(pool, *rest)
        jax.block_until_ready((y, pool))
        return (time.perf_counter() - t0) / args.calls

    def run():
        for rows in step_rows:
            x, dt, a, b, c, pool = draw(rng, rows, h, p, n, slots)
            sl = jnp.asarray(rng.permutation(slots)[:rows].astype(np.int32))
            want = jax.block_until_ready(
                la.ssd_step_reference(x, dt, a, b, c, pool, sl))
            got = jax.block_until_ready(
                ssd.ssd_step(x, dt, a, b, c, pool, sl))
            fn = jax.jit(lambda q, *r: ssd.ssd_step(*r[:5], q, r[5]),
                         donate_argnums=0)
            sec = timed(fn, pool, x, dt, a, b, c, sl)
            nbytes = costs_ssd.ssd_step_bytes(rows, inner, n)
            rows_out.append({
                "kernel": "ssd_step", "rows": rows,
                "max_abs_y": float(jnp.abs(got[0] - want[0]).max()),
                "max_abs_pool": float(jnp.abs(got[1] - want[1])[:slots].max()),
                "us_a_call": sec * 1e6, "bytes": nbytes,
                "flops": costs_ssd.ssd_flops(rows, inner, n),
                "hbm_roofline_pct": nbytes / peak / sec * 100})
            print(json.dumps(rows_out[-1]), flush=True)
        for t in chunk_rows:
            for mix in ("runs", "window"):
                x, dt, a, b, c, pool = draw(rng, t, h, p, n, slots)
                sl, pos, n_runs, zero = rows_of(t, slots, mix)
                sl, pos = jnp.asarray(sl), jnp.asarray(pos)
                cont, last = la.run_structure(sl, pos)
                want = jax.block_until_ready(
                    la.ssd_chunk_reference(x, dt, a, b, c, pool, sl, pos))
                got = jax.block_until_ready(
                    ssd.ssd_chunk(x, dt, a, b, c, pool, sl, pos, cont, last))
                live = np.asarray(sl) < slots
                fn = jax.jit(lambda q, *r: ssd.ssd_chunk(*r[:5], q, *r[5:]),
                             donate_argnums=0)
                sec = timed(fn, pool, x, dt, a, b, c, sl, pos, cont, last)
                n_live = int(live.sum())
                nbytes = costs_ssd.ssd_chunk_bytes(n_live, n_runs, inner, n,
                                                   zero)
                rows_out.append({
                    "kernel": "ssd_chunk", "mix": mix, "rows": t,
                    "live_rows": n_live, "runs": n_runs,
                    "max_abs_y": float(
                        jnp.abs(got[0] - want[0])[live].max()),
                    "max_abs_pool": float(
                        jnp.abs(got[1] - want[1])[:slots].max()),
                    "us_a_call": sec * 1e6, "bytes": nbytes,
                    "flops": costs_ssd.ssd_chunk_flops(n_live, inner, n),
                    "hbm_roofline_pct": nbytes / peak / sec * 100})
                print(json.dumps(rows_out[-1]), flush=True)

    if on_chip:
        run()
    else:
        from jax.experimental.pallas import tpu as pltpu
        with pltpu.force_tpu_interpret_mode():
            run()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"platform": jax.default_backend(), "rows": rows_out},
                      f, indent=1)
    worst = max(max(r["max_abs_y"], r["max_abs_pool"]) for r in rows_out)
    # float32 against float32, the same arithmetic in another order of the
    # contraction's 128 terms.
    return 0 if worst < 1e-3 else 1


if __name__ == "__main__":
    sys.exit(main())
