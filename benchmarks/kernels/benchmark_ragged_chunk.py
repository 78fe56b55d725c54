"""One `mixed` attention call's device time by what its rows are.

Times `ragged_paged_attention` (`_ragged_call` / `_ragged_call_window`) at
a serving cell's shapes on batches whose rows are runs of one sequence:

- one 512-row run (a prompt's chunk) that starts at token 0 / 2,560 /
  5,120, with and without a 4,096-token window;
- four runs of 128 rows with no context; three runs of 170 / 170 / 172
  rows behind 100 tokens (short prompts at the rows a step happens to
  put them: two of the four spans of 128 rows are whole);
- 128 single rows at ~340 tokens of context (every row a tile of one:
  what a decode row of a `mixed` step costs).

Every case is first held to the jnp reference on the same inputs (output
and pool). `--root DIR` imports the package from another checkout (the
parent's, unpacked beside this one; one that knows no `run_starts` is
called without), so one chip call times both:

    python benchmarks/kernels/benchmark_ragged_chunk.py --out a.json
    python benchmarks/kernels/benchmark_ragged_chunk.py \
        --root chipscratch/parent --out b.json
    python benchmarks/kernels/benchmark_ragged_chunk.py --table a.json b.json

Device time is read from a profiler trace of the timed calls: `kernel_us`
sums the launches' own events (the name the benchmark's attention shares
read), `program_us` everything the timed program ran on the device, the
operations around the launches included. A CPU run is a rehearsal of
control flow, never a timing (`--tiny`).
"""
from __future__ import annotations

import argparse
import functools
import glob
import inspect
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.join(HERE, "..", "..")
WINDOW = 4096


def cases(tiny: bool):
    """(name, rows, table width, [(tokens in the pool, rows)], window)."""
    if tiny:
        return [("run16@0", 16, 8, [(0, 16)], None),
                ("run16@40+window", 16, 8, [(40, 16)], 32),
                ("ones8@~20", 8, 8, [(17 + i, 1) for i in range(8)], None)]
    out = []
    for start in (0, 2560, 5120):
        for window in (None, WINDOW):
            out.append((f"run512@{start}" + ("+window" if window else ""),
                        512, 512, [(start, 512)], window))
    out.append(("4xrun128@0", 512, 64, [(0, 128)] * 4, None))
    out.append(("3xrun~170@100", 512, 64,
                [(100, 170), (100, 170), (100, 172)], None))
    out.append(("ones128@~340", 128, 64,
                [(276 + i, 1) for i in range(128)], None))
    return out


def main(args) -> None:
    if args.table:
        return table(args.table)
    sys.path.insert(0, os.path.abspath(args.root or CHECKOUT))
    import jax
    if jax.default_backend() == "tpu":
        return measure(args)
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():   # a rehearsal, not a timing
        measure(args)


def measure(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from intellillm_tpu.ops.pallas import ragged_paged_attention as rpa
    from intellillm_tpu.ops.ragged_attention import (
        ragged_fused_attention_reference)

    hkv, g = (int(x) for x in args.heads.split("x"))
    d, bs = 128, 16
    dtype = jnp.dtype(args.pool_dtype)
    layers = 2 if args.tiny else args.layers
    scale = d ** -0.5
    knows_runs = "run_starts" in inspect.signature(
        rpa.ragged_paged_attention).parameters
    result = {"root": args.root or ".", "heads": args.heads,
              "pool_dtype": str(dtype), "knows_runs": knows_runs,
              "device": jax.devices()[0].device_kind,
              "platform": jax.devices()[0].platform, "cases": {}}

    for name, rows, w, runs, window in cases(args.tiny):
        if args.only and args.only not in name:
            continue
        rng = np.random.default_rng(args.seed)
        nb = len(runs) * w + 1
        pool = lambda: jnp.asarray(
            rng.standard_normal((nb, hkv, bs, d), np.float32) * 0.5
        ).astype(dtype)
        k_cache, v_cache = pool(), pool()
        tables = np.zeros((rows, w), np.int32)
        ctx = np.zeros(rows, np.int32)
        slots = np.full(rows, -1, np.int32)
        starts = np.ones(rows, np.int32)
        row = 0
        for i, (have, n) in enumerate(runs):
            table = 1 + i * w + rng.permutation(w).astype(np.int32)
            pos = have + np.arange(n)
            tables[row:row + n] = table
            ctx[row:row + n] = pos + 1
            slots[row:row + n] = table[pos // bs] * bs + pos % bs
            starts[row + 1:row + n] = 0
            row += n
        q = jnp.asarray(rng.standard_normal((rows, 1, hkv * g, d),
                                            np.float32)).astype(jnp.bfloat16)
        k_new, v_new = (jnp.asarray(rng.standard_normal(
            (rows, hkv, d), np.float32) * 0.5).astype(dtype)
            for _ in range(2))
        lo = (None if window is None
              else jnp.asarray(np.clip(ctx - window, 0, None)))
        tables, ctx_d, slots, starts = (jnp.asarray(x) for x in
                                        (tables, ctx, slots, starts))
        kwargs = {} if lo is None else {"lo": lo}
        if knows_runs:
            kwargs["run_starts"] = starts

        out, kc, vc = jax.block_until_ready(rpa.ragged_paged_attention(
            q, k_new, v_new, jnp.copy(k_cache), jnp.copy(v_cache), slots,
            tables, ctx_d, scale, **kwargs))
        ref, kc_ref, vc_ref = ragged_fused_attention_reference(
            q, k_new, v_new, k_cache, v_cache, slots, tables, ctx_d, scale,
            lo=lo)
        entry = {
            "rows": rows, "width": w, "ctx_mean": float(ctx.mean()),
            "max_err": float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32)))),
            "pool_equal": bool(
                jnp.array_equal(kc.astype(jnp.float32),
                                kc_ref.astype(jnp.float32))
                and jnp.array_equal(vc.astype(jnp.float32),
                                    vc_ref.astype(jnp.float32)))}
        del kc, vc, kc_ref, vc_ref, ref

        @functools.partial(jax.jit, donate_argnums=(3, 4))
        def chained(q, k_new, v_new, kc, vc, slots, tables, ctx, extra):
            def layer(_, carry):
                q, kc, vc = carry
                return rpa.ragged_paged_attention(
                    q, k_new, v_new, kc, vc, slots, tables, ctx, scale,
                    **extra)
            return jax.lax.fori_loop(0, layers, layer, (q, kc, vc))

        state = [jnp.copy(k_cache), jnp.copy(v_cache)]

        def run():
            out, state[0], state[1] = chained(
                q, k_new, v_new, state[0], state[1], slots, tables, ctx_d,
                kwargs)
            return out

        for _ in range(2):
            jax.block_until_ready(run())
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(args.repeats):
                    o = run()
                jax.block_until_ready(o)
            entry.update(device_us(tmp, args.repeats * layers))
        result["cases"][name] = entry
        print(name, json.dumps(entry), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)


def device_us(trace_dir: str, calls: int) -> dict:
    """Device time a call: of the launches (`_ragged_call*` events) and of
    every operation of the timed program; empty where the trace has no
    device plane (a CPU run)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    kernel = program = launches = 0
    for plane in (ProfileData.from_file(paths[0]).planes if paths else ()):
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                # The loop's own event spans its body's: count leaves.
                if ev.name.startswith("%while"):
                    continue
                program += ev.duration_ns
                if "_ragged_call" in ev.name:
                    kernel += ev.duration_ns
                    launches += 1
    if not launches:
        return {}
    return {"kernel_us": kernel / calls / 1e3,
            "program_us": program / calls / 1e3,
            "launches_a_call": launches / calls}


def table(paths) -> None:
    """The markdown table `PERF.md` takes, from --out files (the first is
    the column the others are compared with)."""
    runs = []
    for path in paths:
        with open(path) as f:
            runs.append(json.load(f))
    print("| case | " + " | ".join(
        f"{r['root']} kernel / program us" for r in runs) + " | program x |")
    print("|---|" + "---|" * (len(runs) + 1))
    for name in runs[0]["cases"]:
        cells, base = [], runs[0]["cases"][name].get("program_us")
        for r in runs:
            c = r["cases"].get(name, {})
            cells.append(f"{c.get('kernel_us', float('nan')):.0f} / "
                         f"{c.get('program_us', float('nan')):.0f}")
        last = runs[-1]["cases"].get(name, {}).get("program_us")
        ratio = f"{base / last:.2f}" if base and last else "-"
        print(f"| {name} | " + " | ".join(cells) + f" | {ratio} |")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default="",
                        help="checkout to import intellillm_tpu from")
    parser.add_argument("--heads", default="4x7",
                        help="KV heads x query heads a KV head (4x7 "
                             "SmallThinker, 8x4 Mistral, 16x1 DeepSeek-MoE)")
    parser.add_argument("--pool-dtype", default="bfloat16")
    parser.add_argument("--layers", type=int, default=8,
                        help="kernel calls chained in one program")
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--only", default="", help="cases whose name has it")
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal sizes")
    parser.add_argument("--out", default="")
    parser.add_argument("--table", nargs="+", default=(),
                        help="print the table of these --out files")
    main(parser.parse_args())
