"""The delta rule's two kernels at the Solar Open 2 cell's shapes: each held
to the recurrence on the chip, then timed a call.

    python benchmarks/kernels/benchmark_kda.py --out chiprun_out/kda.json

`kda_step` at 8 / 32 / 128 rows (a decode substep: every row its own slot,
64 heads of 128, a float32 pool of 128 slots + the pad row) and `kda_chunk`
at 16 / 128 / 512 rows (a `mixed` step: runs of 16-171 rows of several
sequences, some from position 0, some continuing a slot, pad rows at the
end), decays drawn down to 0.02 and up to 0.9999 a channel, b near 0 and
near 2. First the kernel's output and pool are compared with the oracle's
(`ops/linear_attention.py`: the recurrence a row at a time; largest
absolute difference, float32), then `--calls` chained calls are timed on the
host clock around a `block_until_ready` (the pool is donated from call to
call, as the step programs do), and the bytes and operations of
`benchmarks/chip/harness/costs_kda.py` are printed beside the time.
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


def draw(rng, t, h, d, slots):
    import jax.numpy as jnp
    import numpy as np
    q = rng.normal(size=(t, h, d)).astype(np.float32)
    q *= d ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    k = rng.normal(size=(t, h, d)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(t, h, d)).astype(np.float32)
    a = rng.uniform(0.02, 0.9999, size=(t, h, d))
    a[rng.random((t, h, d)) < 0.2] = 0.02
    a[rng.random((t, h, d)) < 0.2] = 0.9999
    beta = rng.choice([1e-3, 1.0, 1.999], size=(t, h)).astype(np.float32)
    pool = rng.normal(size=(slots + 1, h, d, d)).astype(np.float32)
    return [jnp.asarray(x) for x in
            (q, k, v, np.log(a).astype(np.float32), beta, pool)]


def runs_of(t, slots):
    """(slots [t], positions [t], runs, from zero) of a `mixed` step: runs
    of up to 171 rows, alternately from position 0 and continuing, the
    last eighth of the rows pad."""
    import numpy as np
    live = t - t // 8
    row_slots = np.full(t, slots, np.int32)
    pos = np.zeros(t, np.int32)
    r, n_runs, zero = 0, 0, 0
    while r < live:
        n = min(171 if n_runs % 2 else 57, live - r)
        start = 0 if n_runs % 2 == 0 else 40
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
    from harness import costs_kda
    from intellillm_tpu.ops import linear_attention as la
    from intellillm_tpu.ops.pallas import kda
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.tiny:
        print("no accelerator: --tiny rehearses the control flow",
              file=sys.stderr)
        return 2
    h, d, slots = (2, 128, 6) if args.tiny else (64, 128, 128)
    step_rows = (4, ) if args.tiny else (8, 32, 128)
    chunk_rows = (32, ) if args.tiny else (16, 128, 512)
    peak = 819e9
    rows_out = []
    rng = np.random.default_rng(0)

    def timed(fn, pool, *rest):
        pool = jax.block_until_ready(fn(pool, *rest))[1]    # compile
        t0 = time.perf_counter()
        for _ in range(args.calls):
            o, pool = fn(pool, *rest)
        jax.block_until_ready((o, pool))
        return (time.perf_counter() - t0) / args.calls

    def run():
        for b in step_rows:
            q, k, v, g, beta, pool = draw(rng, b, h, d, slots)
            sl = jnp.asarray(rng.permutation(slots)[:b].astype(np.int32))
            want = jax.block_until_ready(
                la.kda_step_reference(q, k, v, g, beta, pool, sl))
            got = jax.block_until_ready(
                kda.kda_step(q, k, v, g, beta, pool, sl))
            fn = jax.jit(lambda p, *a: kda.kda_step(*a[:5], p, a[5]),
                         donate_argnums=0)
            sec = timed(fn, pool, q, k, v, g, beta, sl)
            nbytes = costs_kda.kda_step_bytes(b, h, d, d)
            rows_out.append({
                "kernel": "kda_step", "rows": b,
                "max_abs_o": float(jnp.abs(got[0] - want[0]).max()),
                "max_abs_pool": float(jnp.abs(got[1] - want[1])[:slots].max()),
                "us_a_call": sec * 1e6, "bytes": nbytes,
                "flops": costs_kda.kda_flops(b, h, d, d),
                "hbm_roofline_pct": nbytes / peak / sec * 100})
            print(json.dumps(rows_out[-1]), flush=True)
        for t in chunk_rows:
            q, k, v, g, beta, pool = draw(rng, t, h, d, slots)
            sl, pos, n_runs, zero = runs_of(t, slots)
            sl, pos = jnp.asarray(sl), jnp.asarray(pos)
            cont, _ = la.run_structure(sl, pos)
            want = jax.block_until_ready(
                la.kda_chunk_reference(q, k, v, g, beta, pool, sl, pos))
            got = jax.block_until_ready(
                kda.kda_chunk(q, k, v, g, beta, pool, sl, pos, cont))
            live = np.asarray(sl) < slots
            fn = jax.jit(lambda p, *a: kda.kda_chunk(*a[:5], p, *a[5:]),
                         donate_argnums=0)
            sec = timed(fn, pool, q, k, v, g, beta, sl, pos, cont)
            n_live = int(live.sum())
            nbytes = costs_kda.kda_chunk_bytes(n_live, n_runs, h, d, d, zero)
            flops = costs_kda.kda_flops(n_live, h, d, d)
            rows_out.append({
                "kernel": "kda_chunk", "rows": t, "live_rows": n_live,
                "runs": n_runs,
                "max_abs_o": float(jnp.abs(got[0] - want[0])[live].max()),
                "max_abs_pool": float(jnp.abs(got[1] - want[1])[:slots].max()),
                "us_a_call": sec * 1e6, "bytes": nbytes, "flops": flops,
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
    worst = max(max(r["max_abs_o"], r["max_abs_pool"]) for r in rows_out)
    return 0 if worst < 1e-4 else 1


if __name__ == "__main__":
    sys.exit(main())
