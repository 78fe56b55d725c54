"""The two stream mixes of a hyper-connection sublayer at the Xing4.0 cell's
shapes: each held to the jnp oracle on the chip, then timed a call.

    python benchmarks/kernels/benchmark_mhc.py --out chiprun_out/mhc.json

128 rows (a decode substep) and 512 rows (a `mixed` step) x 4 streams x
3,584, in float32 (the streams as `models/xing4.py` holds them) and in
bfloat16 (the model's dtype: the form the published bytes are counted in),
seeded
parameters at the init's scale. First the seam's
results with the kernels (`ops/hyper_connections.py`, `set_use_pallas(True)`)
are compared with the oracle's (`set_use_pallas(False)`: the jnp functions
of `layers/hyper_connections.py`); then each form is timed on the host clock
around a `block_until_ready`: `--reps` calls chained inside ONE jitted loop
(a call is ~10 us: dispatched one at a time the host's ~30 us a dispatch is
what would be read), the loop run `--calls` times, the fastest kept. What
chains the calls: `mhc_post`, the `pair` (pre-mix, the sublayer the
identity, post-mix) and the `layer` (what a layer does: two pairs) carry
the streams;
`mhc_pre` carries its `alpha` (three numbers, moved by 1e-30 of a
coefficient and of the sum of `u`, so that XLA's form has to compute all of
`u`: a pass over `u`, an eighth of the call's bytes, and a scalar fusion on
top of the kernel; `pair` less `mhc_post` is the pre-mix without them).
Beside the time: the bytes of `benchmarks/chip/harness/costs_mhc.py` and
their share of `peaks.json`'s HBM rate. A loop of one kernel keeps its
operands where XLA's memory-space assignment puts them, which on a v5e is
VMEM for arrays of a few MB (`S(1)` in the compiled text, in the cell's
programs as well): a share over 100 % says the copies did not come from
HBM, not that the count of bytes is wrong.
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


def draw(rng, rows, n, c, dtype):
    """Streams, a sublayer's output and its parameters as the seam takes
    them, a stack of one layer (the seeded init's scale:
    tests/layers/test_hyper_connections.py `params`)."""
    import jax.numpy as jnp
    import numpy as np
    k = 2 * n + n * n
    normal = lambda *s: rng.normal(size=s).astype(np.float32)   # noqa: E731
    p = {"phi": normal(k, n * c) / np.sqrt(n * c),
         "alpha": rng.uniform(0.5, 1.5, 3).astype(np.float32),
         "b_pre": normal(n), "b_post": normal(n), "b_res": normal(n, n)}
    return (jnp.asarray(normal(rows, n * c)).astype(dtype),
            jnp.asarray(normal(rows, c)).astype(dtype),
            {key: jnp.asarray(v)[None] for key, v in p.items()})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--rows", default="128,512")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import costs, costs_mhc
    from intellillm_tpu.layers.hyper_connections import HyperConnections
    from intellillm_tpu.ops import hyper_connections as seam
    from intellillm_tpu.ops.dispatch import set_use_pallas
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.tiny:
        print("no accelerator: --tiny rehearses the control flow",
              file=sys.stderr)
        return 2
    n, c = (4, 128) if args.tiny else (4, 3584)
    reps = 2 if args.tiny else args.reps
    hc = HyperConnections(n=n, sweeps=20, eps=1e-6, clamp_min=-30.0,
                          clamp_max=30.0, rms_eps=1e-6)
    device = jax.devices()[0].device_kind
    rate = costs.peaks(device)["hbm_bytes_per_s"] if on_chip else None
    f32 = jnp.dtype(jnp.float32)
    rng = np.random.default_rng(0)
    rows_out = []

    def loops():
        """name -> (jitted loop of `reps` calls, its bytes a call)."""

        def pre(x, y, p):
            def body(_, alpha):
                u, coef, _ = seam.mhc_pre(x, {**p, "alpha": alpha}, hc, 0)
                return alpha + 1e-30 * (coef[0, :3] + u.astype(f32).sum())
            return jax.lax.fori_loop(0, reps, body, p["alpha"])

        def post(x, y, p):
            _, coef, _ = seam.mhc_pre(x, p, hc, 0)
            return jax.lax.fori_loop(
                0, reps, lambda _, x: seam.mhc_post(x, y, coef, hc), x)

        def pair(x, y, p):
            def body(_, x):
                u, coef, _ = seam.mhc_pre(x, p, hc, 0)
                return seam.mhc_post(x, u, coef, hc)
            return jax.lax.fori_loop(0, reps, body, x)

        def layer(x, y, p):
            def body(_, x):
                u, coef, _ = seam.mhc_pre(x, p, hc, 0)
                x = seam.mhc_post(x, u, coef, hc)
                u, coef, _ = seam.mhc_pre(x, p, hc, 0)
                return seam.mhc_post(x, u, coef, hc)
            return jax.lax.fori_loop(0, reps, body, x)

        def both(*a):
            return costs_mhc.mhc_pre_bytes(*a) + costs_mhc.mhc_post_bytes(*a)

        return {"mhc_pre": (pre, costs_mhc.mhc_pre_bytes),
                "mhc_post": (post, costs_mhc.mhc_post_bytes),
                "pair": (pair, both),
                "layer": (layer, lambda *a: 2 * both(*a))}

    def run():
        for rows, dtype in [(int(r), d) for r in args.rows.split(",")
                            for d in (jnp.bfloat16, f32)]:
            if args.tiny:
                rows = min(rows, 48)
            x, y, p = draw(rng, rows, n, c, dtype)
            results = {}
            for form, force in (("xla", False), ("pallas", True)):
                set_use_pallas(force)
                try:
                    u, coef, err = jax.block_until_ready(
                        jax.jit(lambda x, p: seam.mhc_pre(x, p, hc, 0))(x, p))
                    new = jax.block_until_ready(jax.jit(
                        lambda x, y, coef: seam.mhc_post(x, y, coef, hc))(
                            x, y, coef))
                    results[form] = [np.asarray(v, np.float32) for v in (
                        u, *seam.unpack(coef, hc), err, new)]
                    for name, (fn, nbytes) in loops().items():
                        fn = jax.jit(fn)
                        jax.block_until_ready(fn(x, y, p))      # compile
                        best = float("inf")
                        for _ in range(args.calls):
                            t0 = time.perf_counter()
                            jax.block_until_ready(fn(x, y, p))
                            best = min(best, time.perf_counter() - t0)
                        sec = best / reps
                        size = nbytes(rows, n, c, jnp.dtype(dtype).itemsize)
                        rows_out.append({
                            "kernel": name, "form": form, "rows": rows,
                            "dtype": jnp.dtype(dtype).name, "bytes": size})
                        if on_chip:     # a CPU run's time is no one's
                            rows_out[-1].update(
                                us_a_call=sec * 1e6,
                                hbm_roofline_pct=size / rate / sec * 100)
                        print(json.dumps(rows_out[-1]), flush=True)
                finally:
                    set_use_pallas(None)
            diff = {name: float(np.abs(a - b).max()) for name, a, b in zip(
                ("u", "post", "comb", "err", "streams"), results["pallas"],
                results["xla"])}
            rows_out.append({"rows": rows, "dtype": jnp.dtype(dtype).name,
                             "max_abs": diff})
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
            json.dump({"platform": jax.default_backend(), "device": device,
                       "reps": reps, "rows": rows_out}, f, indent=1)
    worst = [r["max_abs"] for r in rows_out if "max_abs" in r]
    # float32 coefficients against float32 in another order of summation;
    # a bfloat16 value of the streams or of `u` an ulp (2^-8 of O(4)).
    ok = all(max(d["post"], d["comb"], d["err"]) < 2e-5
             and max(d["u"], d["streams"]) < 0.07 for d in worst)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
