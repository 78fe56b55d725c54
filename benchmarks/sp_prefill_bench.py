"""Long-prompt prefill timing: flash path on the real chip, and the
ring/Ulysses SP dispatch on a mesh.

Sequence parallelism only exists across chips, so there are two modes:
(a) single-chip flash prefill wall time vs prompt length (the baseline
SP must beat at scale), and (b) ring/Ulysses vs flash on a virtual CPU
mesh for RELATIVE sanity (CPU time is not TPU time and is never quoted
as a device number).

Prints one JSON line per point; every timed call ends in
`block_until_ready`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def time_prefill(seq_len: int, size: str, sp_mode: str | None,
                 n_devices: int, iters: int = 3) -> float:
    import jax
    import jax.numpy as jnp

    from intellillm_tpu.ops.pallas.flash_attention import flash_attention
    b, h, d = 1, 32 if size == "7b" else 8, 128
    if sp_mode is None:
        q = jnp.zeros((b, seq_len, h, d), jnp.bfloat16)
        k = v = q
        ctx = jnp.full((b, ), seq_len, jnp.int32)
        scale = d ** -0.5

        if jax.default_backend() == "cpu":
            # Pallas TPU kernels only run under interpret mode on CPU.
            from jax.experimental.pallas import tpu as pltpu

            def run():
                with pltpu.force_tpu_interpret_mode():
                    return flash_attention(q, k, v, ctx, scale)
        else:
            def run():
                return flash_attention(q, k, v, ctx, scale)
    else:
        from jax.sharding import Mesh
        from intellillm_tpu.ops.ring_attention import ring_attention
        from intellillm_tpu.ops.ulysses_attention import ulysses_attention
        devs = np.array(jax.devices()[:n_devices])
        mesh = Mesh(devs.reshape(n_devices, 1), ("data", "model"))
        q = jnp.zeros((b, seq_len, h, d), jnp.bfloat16)
        k = v = q
        fn = ring_attention if sp_mode == "ring" else ulysses_attention

        def run():
            return fn(q, k, v, mesh=mesh, axis="data", causal=True)

    jax.block_until_ready(run())    # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    return min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="7b")
    ap.add_argument("--lengths", default="2048,4096,8192")
    ap.add_argument("--modes", default="flash")
    ap.add_argument("--n-devices", type=int, default=8)
    args = ap.parse_args()
    import jax
    backend = jax.default_backend()
    for mode in args.modes.split(","):
        for sl in (int(x) for x in args.lengths.split(",")):
            sp = None if mode == "flash" else mode
            t = time_prefill(sl, args.size, sp, args.n_devices)
            print(json.dumps({
                "metric": f"prefill-attn {mode} seq={sl} ({backend})",
                "value": round(t * 1e3, 2), "unit": "ms",
                "note": ("single-chip baseline" if sp is None else
                         f"{args.n_devices}-way mesh ({backend})"),
            }), flush=True)


if __name__ == "__main__":
    main()
