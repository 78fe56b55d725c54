"""A 2-d state pool's rows by slot (`ops/state_rows.py`), the form kept and
the forms it was chosen from, at the two state models' widths: each held
bit for bit to `.at[].get(mode="fill")` / `.at[].set(mode="drop")` on the
chip, then timed a call.

    python benchmarks/kernels/benchmark_state_rows.py --out chiprun_out/rows.json

Pools of 128 slots x 4,096 (LFM2) and x 73,728 (a delta-rule layer's
taps), bf16; 128 rows (a decode step's stage: every live row its own
slot, an eighth of the rows pad) and 512 rows (a `mixed` step: slots
repeat among the rows read, one row in four writes). Scatter forms:
`at_set` (XLA's own scatter: a row loop at the wide pool), `at_set_unique`
(the same with `unique_indices`), `tiles` (the pool viewed `[S, W / 128,
128]`), `columns` (XLA's scatter over column blocks of 24,576), `onehot`
(a selection product on the MXU, float32 accumulation), `kept`
(`scatter_rows`). Gather forms: `kept` (`gather_rows`, XLA's gather) and
`onehot`. `exact` is over drawn normal values, `exact_with_specials` over
the same with NaN, inf and -0.0 among them, in the pool and in the rows (a
product turns 0 x inf into NaN in every row that reads the pool, and -0.0
+ 0.0 into +0.0). `--calls` calls are timed on the host clock around a
`block_until_ready`, the pool donated from call to call as the step
programs do: a call that takes the device under ~0.1 ms reads the host's
dispatch (~100 us chained, ~200 us not), so only what lies above that
floor is device time. A CPU run is a rehearsal of control flow at a tiny
size, never a timing (`--tiny`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(HERE, "..", "..")))

COLUMNS = 24576


def scatter_forms():
    import jax.numpy as jnp

    from intellillm_tpu.ops.state_rows import scatter_rows

    def at_set(pool, slots, rows):
        return pool.at[slots].set(rows, mode="drop")

    def at_set_unique(pool, slots, rows):
        return pool.at[slots].set(rows, mode="drop", unique_indices=True)

    def tiles(pool, slots, rows):
        s, w = pool.shape
        return pool.reshape(s, w // 128, 128).at[slots].set(
            rows.reshape(-1, w // 128, 128), mode="drop",
            unique_indices=True).reshape(s, w)

    def columns(pool, slots, rows):
        return jnp.concatenate(
            [pool[:, c:c + COLUMNS].at[slots].set(rows[:, c:c + COLUMNS],
                                                  mode="drop")
             for c in range(0, pool.shape[1], COLUMNS)], axis=1)

    def onehot(pool, slots, rows):
        hit = slots[None, :] == jnp.arange(pool.shape[0])[:, None]
        new = jnp.dot(hit.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32)
        return jnp.where(hit.any(1)[:, None], new.astype(pool.dtype), pool)

    return {"at_set": at_set, "at_set_unique": at_set_unique,
            "tiles": tiles, "columns": columns, "onehot": onehot,
            "kept": scatter_rows}


def gather_forms():
    import jax.numpy as jnp

    from intellillm_tpu.ops.state_rows import gather_rows

    def onehot(pool, slots):
        hit = slots[:, None] == jnp.arange(pool.shape[0])[None, :]
        return jnp.dot(hit.astype(pool.dtype), pool,
                       preferred_element_type=jnp.float32).astype(pool.dtype)

    return {"kept": gather_rows, "onehot": onehot}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="a CPU rehearsal: 16 slots x 256, 2 calls")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print("no TPU: a timing needs the chip (--tiny rehearses)",
              file=sys.stderr)
        return 2
    slots_n, widths, row_counts, calls = (
        (16, (256, ), (16, 64), 2) if args.tiny
        else (128, (4096, 73728), (128, 512), args.calls))
    rng = np.random.default_rng(args.seed)
    bits = lambda a: np.asarray(a).view(np.uint16)     # noqa: E731

    def us_a_call(t0):
        if device.platform != "tpu":
            return None         # a rehearsal's clock says nothing
        return (time.perf_counter() - t0) / calls * 1e6

    def with_specials(a):
        a = np.array(a, np.float32)
        for value in (np.nan, np.inf, -np.inf, -0.0):
            a[rng.random(a.shape) < 0.001] = value
        return jnp.asarray(a, jnp.bfloat16)

    table = []
    for width in widths:
        for n_rows in row_counts:
            pool = jnp.asarray(rng.normal(size=(slots_n, width)),
                               jnp.bfloat16)
            rows = jnp.asarray(rng.normal(size=(n_rows, width)),
                               jnp.bfloat16)
            pool_s, rows_s = with_specials(pool), with_specials(rows)
            # Read: every slot as often as the rows go round, an eighth
            # pad. Written: distinct slots, the other rows past the pool.
            read = np.arange(n_rows, dtype=np.int32) % slots_n
            read[rng.random(n_rows) < 0.125] = slots_n
            written = np.full(n_rows, slots_n, np.int32)
            writers = rng.permutation(n_rows)[:min(n_rows // 4, slots_n)] \
                if n_rows > slots_n else np.flatnonzero(read < slots_n)
            written[writers] = rng.permutation(slots_n)[:len(writers)]
            read, written = jnp.asarray(read), jnp.asarray(written)

            want = bits(pool.at[written].set(rows, mode="drop"))
            want_s = bits(pool_s.at[written].set(rows_s, mode="drop"))
            for name, fn in scatter_forms().items():
                if width % COLUMNS and name == "columns":
                    continue
                step = jax.jit(fn, donate_argnums=(0, ))
                exact_s = bool((bits(step(jnp.array(pool_s), written,
                                          rows_s)) == want_s).all())
                cur = jax.block_until_ready(
                    step(jnp.array(pool), written, rows))
                exact = bool((bits(cur) == want).all())
                t0 = time.perf_counter()
                for _ in range(calls):
                    cur = step(cur, written, rows)
                jax.block_until_ready(cur)
                table.append({
                    "way": "scatter", "form": name, "width": width,
                    "rows": n_rows, "exact": exact,
                    "exact_with_specials": exact_s,
                    "us_a_call": us_a_call(t0)})
                print(json.dumps(table[-1]), flush=True)
            want = bits(pool.at[read].get(mode="fill", fill_value=0))
            want_s = bits(pool_s.at[read].get(mode="fill", fill_value=0))
            for name, fn in gather_forms().items():
                step = jax.jit(fn)
                exact_s = bool((bits(step(pool_s, read)) == want_s).all())
                out = jax.block_until_ready(step(pool, read))
                exact = bool((bits(out) == want).all())
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = step(pool, read)
                jax.block_until_ready(out)
                table.append({
                    "way": "gather", "form": name, "width": width,
                    "rows": n_rows, "exact": exact,
                    "exact_with_specials": exact_s,
                    "us_a_call": us_a_call(t0)})
                print(json.dumps(table[-1]), flush=True)
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": jax.device_count()},
              "slots": slots_n, "calls": calls, "table": table}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0 if all(r["exact"] and r["exact_with_specials"]
                    for r in table if r["form"] != "onehot") else 1


if __name__ == "__main__":
    sys.exit(main())
