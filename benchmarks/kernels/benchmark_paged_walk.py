"""Per-call device time of the two paged kernels at a serving cell's shapes.

Times `_paged_attention_call` (a 128-row decode step's pool walk) and
`_ragged_call` (a 512-row prefill-chunk step) on contexts drawn from a
traffic file of `benchmarks/chip/traffic/`, plus rows that fill their
whole table (`--full-width`: 256 blocks = 4,096 tokens, 16 full page
groups). Every kernel is first held to the jnp reference on the same
inputs. `--root DIR` imports the package from another checkout (the
parent's, unpacked beside this one), so one chip call times both:

    python benchmarks/kernels/benchmark_paged_walk.py --heads 8x4 --out a.json
    python benchmarks/kernels/benchmark_paged_walk.py --heads 8x4 \
        --root chipscratch/parent --out b.json

The cells' shapes and pools: Mistral `--heads 8x4`, DeepSeek-MoE `--heads
16x1` (both fp8, the default), LFM2 `--heads 8x4 --pool-dtype bfloat16
--head-values 64` (heads of 64 stored in 128 lanes), SmallThinker's
windowed layers `--heads 4x7 --pool-dtype bfloat16 --window 4096 --rows 16
--traffic ragdoc-closed --width 512 --blocks 8300`. The decode call's share
of its HBM roofline (`harness/costs.py` `paged_attention_bytes` over
`peaks.json`'s rate) is printed beside its time.

`--latent-heads N` times the latent (MLA) pair instead,
`_paged_attention_call_mla` and `_ragged_call_mla` (`ops/pallas/
mla_attention.py`): N query heads against ONE bf16 pool of stored width 640
whose first 512 lanes are the value (A.X-K1 `--latent-heads 64`, Xing4.0
`--latent-heads 32`), over the same seeded contexts, each held to its jnp
reference first, both with their share of the HBM roofline by
`harness/costs_mla.py`'s bytes.

Device time is read from a profiler trace of the timed calls (the sum of
the kernel's own events over the calls made); a host clock around the
same calls is printed beside it. A CPU run is a rehearsal of control
flow, never a timing (`--tiny`).
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.join(HERE, "..", "..")


def contexts(traffic: dict, rows: int, seed: int):
    """Decode rows at a uniform point of their output, and prefill-chunk
    rows (every position of whole prompts) with ~8 % pad rows."""
    import numpy as np
    sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "chip"))
    from harness.loadgen import length_pairs
    rng = np.random.default_rng(seed)
    pairs = length_pairs(traffic, traffic.get("block", 128))
    drawn = (pairs[i] for i in rng.integers(0, len(pairs), rows))
    decode = np.asarray([p + int(rng.random() * o) for p, o in drawn],
                        np.int32)
    live = rows - rows * 8 // 100
    prefill, seq_of = [], []
    for n, i in enumerate(rng.permutation(len(pairs))):
        p = pairs[i][0]
        prefill += list(range(1, p + 1))
        seq_of += [n] * p
    prefill = np.asarray(prefill[:live] + [0] * (rows - live), np.int32)
    seq_of = np.asarray(seq_of[:live] + [-1] * (rows - live), np.int32)
    return decode, prefill, seq_of


def main(args) -> None:
    sys.path.insert(0, os.path.abspath(args.root or CHECKOUT))
    import jax
    run = measure_latent if args.latent_heads else measure
    if jax.default_backend() == "tpu":
        return run(args)
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():   # a rehearsal, not a timing
        run(args)


def drawn_contexts(args, rows_d: int, rows_r: int, w: int, bs: int):
    """The traffic file's decode and prefill-chunk contexts (and the
    chunk rows' sequences), cut to the launches' rows and the table."""
    import numpy as np
    with open(os.path.join(CHECKOUT, "benchmarks", "chip", "traffic",
                           args.traffic + ".json")) as f:
        traffic = json.load(f)
    dec_ctx, pre_ctx, seq_of = contexts(traffic, max(rows_d, rows_r),
                                        args.seed)
    dec_ctx, pre_ctx, seq_of = (dec_ctx[:rows_d], pre_ctx[:rows_r],
                                seq_of[:rows_r])
    if args.full_width:
        dec_ctx = np.full_like(dec_ctx, w * bs)
    return np.minimum(dec_ctx, w * bs), np.minimum(pre_ctx, w * bs), seq_of


def tables_for(rng, owner, n_rows: int, w: int, nb: int):
    """Rows of one sequence share a table; pages are distinct across
    sequences; entries past a sequence's pages are 0."""
    import numpy as np
    t = np.zeros((n_rows, w), np.int32)
    perm = rng.permutation(nb - 1) + 1
    at, by_seq = 0, {}
    for r in range(n_rows):
        key = owner[r]
        if key < 0:
            continue
        if key not in by_seq:
            by_seq[key] = perm[at:at + w]
            at = at + w if at + 2 * w <= len(perm) else 0
        t[r] = by_seq[key]
    return t


def slots_for(tables, ctx, bs: int):
    """The slot of each row's own (last) token; -1 for a pad row."""
    import numpy as np
    last = np.maximum(ctx - 1, 0)
    return np.where(ctx > 0,
                    tables[np.arange(len(ctx)), last // bs] * bs + last % bs,
                    -1).astype(np.int32)


def time_calls(result: dict, runs, repeats: int, layers: int) -> None:
    """`runs`: (name, a function that makes `layers` calls, the kernel's
    name in a trace). Host clock and the trace's device time a call."""
    import jax
    for name, fn, marker in runs:
        for _ in range(2):
            jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(repeats):
            o = fn()
        jax.block_until_ready(o)
        host = (time.perf_counter() - t0) / (repeats * layers)
        result[f"{name}_host_us_per_call"] = host * 1e6
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(repeats):
                    o = fn()
                jax.block_until_ready(o)
            dev = kernel_device_us(tmp, marker)
        if dev is not None:
            result[f"{name}_device_us_per_call"] = dev["us_per_call"]
            result[f"{name}_device_calls"] = dev["calls"]


def finish(args, result: dict) -> None:
    print(json.dumps(result))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)


def measure_latent(args) -> None:
    """The latent pair: H heads against one pool `[NB, 1, BS, 640]`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from intellillm_tpu.ops import mla_attention as mla

    h, rank, rope, bs = args.latent_heads, 512, 64, 16
    sys.path.insert(0, os.path.join(CHECKOUT, "benchmarks", "chip"))
    from harness import costs, costs_mla
    dk = costs_mla.stored_width(rank, rope)
    dtype = jnp.dtype(jnp.bfloat16)
    rows_d, rows_r, w, layers = args.rows, args.chunk_rows, args.width, \
        args.layers
    if args.tiny:
        rows_d, rows_r, layers = 8, 16, 2
    rng = np.random.default_rng(args.seed)
    dec_ctx, pre_ctx, seq_of = drawn_contexts(args, rows_d, rows_r, w, bs)
    nb = max(args.blocks, rows_d * w + 1) if args.full_width else args.blocks
    # The lanes past rank + rope are the zero pad, in pool and query.
    lanes = np.arange(dk) < rank + rope
    draw = lambda *shape: rng.standard_normal(shape + (dk, ),
                                              np.float32) * lanes
    pool = jnp.asarray(draw(nb, 1, bs) * 0.5).astype(dtype)
    scale = (128 + rope) ** -0.5
    result = {"root": args.root or ".", "latent_heads": h,
              "pool_dtype": str(dtype), "width": w,
              "full_width": bool(args.full_width),
              "device": jax.devices()[0].device_kind,
              "platform": jax.devices()[0].platform}
    err = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                             - b.astype(jnp.float32))))

    # ---- decode ----------------------------------------------------------
    q = jnp.asarray(draw(rows_d, h)).astype(jnp.bfloat16)
    tables = jnp.asarray(tables_for(rng, np.arange(rows_d), rows_d, w, nb))
    ctx = jnp.asarray(dec_ctx)
    out, lse = mla.mla_paged_attention(q, pool, tables, ctx, scale, rank)
    ref, ref_lse = mla.mla_pool_attention_reference(q, pool, tables, ctx,
                                                    scale, rank)
    result["decode_max_err"] = err(out, ref)
    result["decode_lse_max_err"] = float(jnp.max(jnp.abs(
        jnp.where(ctx[:, None] > 0, lse - ref_lse, 0.0))))
    result["decode_ctx_mean"] = float(dec_ctx.mean())

    @jax.jit
    def decode_layers(q, pool, tables, ctx):
        def layer(_, q):
            out, _ = mla.mla_paged_attention(q, pool, tables, ctx, scale,
                                             rank)
            return q.at[:, :, :rank].set(out)
        return jax.lax.fori_loop(0, layers, layer, q)

    # ---- ragged ----------------------------------------------------------
    qr = jnp.asarray(draw(rows_r, h)).astype(jnp.bfloat16)
    new = jnp.asarray(draw(rows_r) * 0.5).astype(dtype)
    tables_r = tables_for(rng, seq_of, rows_r, w, nb)
    slots = jnp.asarray(slots_for(tables_r, pre_ctx, bs))
    tables_r, ctx_r = jnp.asarray(tables_r), jnp.asarray(pre_ctx)
    out_r, pool_k = mla.mla_ragged_paged_attention(
        qr, new, jnp.copy(pool), slots, tables_r, ctx_r, scale, rank)
    ref_r, pool_ref = mla.mla_fused_attention_reference(
        qr, new, pool, slots, tables_r, ctx_r, scale, rank)
    result["ragged_max_err"] = err(out_r, ref_r)
    result["ragged_pool_equal"] = bool(jnp.array_equal(
        pool_k.astype(jnp.float32), pool_ref.astype(jnp.float32)))
    result["ragged_ctx_mean_live"] = float(pre_ctx[pre_ctx > 0].mean())
    del pool_k, pool_ref

    @functools.partial(jax.jit, donate_argnums=(2, ))
    def ragged_layers(qr, new, pool, slots, tables, ctx):
        def layer(_, carry):
            q, pool = carry
            out, pool = mla.mla_ragged_paged_attention(
                q, new, pool, slots, tables, ctx, scale, rank)
            return q.at[:, :, :rank].set(out), pool
        return jax.lax.fori_loop(0, layers, layer, (qr, pool))

    state = {"pool": jnp.copy(pool)}

    def run_ragged():
        out, state["pool"] = ragged_layers(qr, new, state["pool"], slots,
                                           tables_r, ctx_r)
        return out

    time_calls(result, (
        ("decode", lambda: decode_layers(q, pool, tables, ctx),
         "_paged_attention_call_mla"),
        ("ragged", run_ragged, "_ragged_call_mla")), args.repeats, layers)
    # Each call's bytes (`costs_mla.py`: the rows' contexts once, the
    # query in and the latent out, the chunk rows' slab read and written)
    # over the chip's published HBM rate.
    rate = costs.peaks(result["device"])["hbm_bytes_per_s"] \
        if "decode_device_us_per_call" in result else None
    for name, ctxs, rows, extra in (
            ("decode", dec_ctx, rows_d, 0.0),
            ("ragged", pre_ctx, rows_r, costs_mla.latent_write_bytes(
                int((pre_ctx > 0).sum()), bs, rank, rope, dtype.itemsize))):
        nbytes = (costs_mla.latent_walk_bytes(ctxs.tolist(), rank, rope,
                                              dtype.itemsize)
                  + costs_mla.query_bytes(rows, h, rank, rope) + extra)
        result[f"{name}_bytes"] = nbytes
        if rate:
            floor_us = nbytes / rate * 1e6
            result[f"{name}_hbm_floor_us"] = floor_us
            result[f"{name}_hbm_roofline_share"] = (
                floor_us / result[f"{name}_device_us_per_call"])
    finish(args, result)


def measure(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from intellillm_tpu.ops.attention import decode_attention_reference
    from intellillm_tpu.ops.pallas import paged_attention as pa
    from intellillm_tpu.ops.pallas import ragged_paged_attention as rpa
    from intellillm_tpu.ops.ragged_attention import (
        ragged_fused_attention_reference)

    hkv, g = (int(x) for x in args.heads.split("x"))
    d, bs = 128, 16
    dtype = jnp.dtype(args.pool_dtype)
    rows_d, rows_r, w, layers = args.rows, args.chunk_rows, args.width, \
        args.layers
    if args.tiny:
        rows_d, rows_r, layers = 8, 16, 2
    rng = np.random.default_rng(args.seed)
    dec_ctx, pre_ctx, seq_of = drawn_contexts(args, rows_d, rows_r, w, bs)

    nb = max(args.blocks, rows_d * w + 1) if args.full_width else args.blocks
    # Heads narrower than their stored 128 lanes: the lanes past the
    # values hold zeros, in the pool and in the query.
    lanes = np.arange(d) < (args.head_values or d)
    draw = lambda *shape: rng.standard_normal(shape + (d, ),
                                              np.float32) * lanes
    pool = lambda: jnp.asarray(draw(nb, hkv, bs) * 0.5).astype(dtype)
    k_cache, v_cache = pool(), pool()

    result = {"root": args.root or ".", "heads": args.heads,
              "pool_dtype": str(dtype), "width": w,
              "full_width": bool(args.full_width),

              "device": jax.devices()[0].device_kind,
              "platform": jax.devices()[0].platform}
    scale = (args.head_values or d) ** -0.5
    # A sliding window: a row sees its last `window` positions only.
    lo_of = lambda c: ({"lo": jnp.asarray(np.maximum(c - args.window, 0))}
                       if args.window else {})

    # ---- decode ----------------------------------------------------------
    q = jnp.asarray(draw(rows_d, 1, hkv * g)).astype(jnp.bfloat16)
    tables = jnp.asarray(tables_for(rng, np.arange(rows_d), rows_d, w, nb))
    ctx = jnp.asarray(dec_ctx)
    out, lse = pa.paged_attention(q, k_cache, v_cache, tables, ctx, scale,
                                  return_lse=True, **lo_of(dec_ctx))
    ref, ref_lse = decode_attention_reference(
        q, k_cache, v_cache, tables, ctx, scale, return_lse=True,
        **lo_of(dec_ctx))
    result["decode_max_err"] = float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32))))
    result["decode_lse_max_err"] = float(jnp.max(jnp.abs(lse - ref_lse)))
    result["decode_ctx_mean"] = float(dec_ctx.mean())

    @jax.jit
    def decode_layers(q, k_cache, v_cache, tables, ctx):
        def layer(_, q):
            return pa.paged_attention(q, k_cache, v_cache, tables, ctx, scale,
                                      **lo_of(dec_ctx))
        return jax.lax.fori_loop(0, layers, layer, q)

    # ---- ragged ----------------------------------------------------------
    qr = jnp.asarray(draw(rows_r, 1, hkv * g)).astype(jnp.bfloat16)
    k_new = jnp.asarray(draw(rows_r, hkv) * 0.5).astype(dtype)
    v_new = jnp.asarray(draw(rows_r, hkv) * 0.5).astype(dtype)
    tables_r = tables_for(rng, seq_of, rows_r, w, nb)
    slots = jnp.asarray(slots_for(tables_r, pre_ctx, bs))
    tables_r, ctx_r = jnp.asarray(tables_r), jnp.asarray(pre_ctx)
    out_r, kc, vc = rpa.ragged_paged_attention(
        qr, k_new, v_new, jnp.copy(k_cache), jnp.copy(v_cache), slots,
        tables_r, ctx_r, scale, **lo_of(pre_ctx))
    ref_r, kc_ref, vc_ref = ragged_fused_attention_reference(
        qr, k_new, v_new, k_cache, v_cache, slots, tables_r, ctx_r, scale,
        **lo_of(pre_ctx))
    result["ragged_max_err"] = float(jnp.max(jnp.abs(
        out_r.astype(jnp.float32) - ref_r.astype(jnp.float32))))
    result["ragged_pool_equal"] = bool(
        jnp.array_equal(kc.astype(jnp.float32), kc_ref.astype(jnp.float32))
        and jnp.array_equal(vc.astype(jnp.float32),
                            vc_ref.astype(jnp.float32)))
    result["ragged_ctx_mean_live"] = float(pre_ctx[pre_ctx > 0].mean())
    del kc, vc, kc_ref, vc_ref

    @functools.partial(jax.jit, donate_argnums=(3, 4))
    def ragged_layers(qr, k_new, v_new, k_cache, v_cache, slots, tables, ctx):
        def layer(_, carry):
            q, kc, vc = carry
            return rpa.ragged_paged_attention(q, k_new, v_new, kc, vc, slots,
                                              tables, ctx, scale,
                                              **lo_of(pre_ctx))
        return jax.lax.fori_loop(0, layers, layer, (qr, k_cache, v_cache))

    def run_decode():
        return decode_layers(q, k_cache, v_cache, tables, ctx)

    state = {"kc": jnp.copy(k_cache), "vc": jnp.copy(v_cache)}

    def run_ragged():
        out, state["kc"], state["vc"] = ragged_layers(
            qr, k_new, v_new, state["kc"], state["vc"], slots, tables_r,
            ctx_r)
        return out

    time_calls(result, (("decode", run_decode, "_paged_attention_call"),
                        ("ragged", run_ragged, "_ragged_call")),
               args.repeats, layers)
    if "decode_device_us_per_call" in result:
        # What the decode rows attend to (from `lo` under a window), at
        # the lanes stored, over the chip's published HBM rate.
        from harness import costs
        attended = dec_ctx - np.maximum(dec_ctx - args.window, 0) \
            if args.window else dec_ctx
        nbytes = costs.paged_attention_bytes(attended.tolist(), hkv, d,
                                             dtype.itemsize)
        floor_us = nbytes / costs.peaks(result["device"])[
            "hbm_bytes_per_s"] * 1e6
        result["decode_kv_bytes"] = nbytes
        result["decode_hbm_floor_us"] = floor_us
        result["decode_hbm_roofline_share"] = (
            floor_us / result["decode_device_us_per_call"])
    finish(args, result)


def kernel_device_us(trace_dir: str, marker: str):
    """Mean device duration of the events on the TPU's op line whose name
    holds `marker`; None where the trace has no device plane (a CPU run)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    total, calls = 0, 0
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if marker in ev.name:
                    total += ev.duration_ns
                    calls += 1
    if not calls:
        return None
    return {"us_per_call": total / calls / 1e3, "calls": calls}


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default="",
                        help="checkout to import intellillm_tpu from")
    parser.add_argument("--heads", default="8x4",
                        help="KV heads x query heads a KV head (8x4 Mistral "
                             "and LFM2, 16x1 DeepSeek-MoE, 4x7 SmallThinker)")
    parser.add_argument("--head-values", type=int, default=0,
                        help="lanes of the 128 stored that carry values "
                             "(LFM2: 64); 0 = all")
    parser.add_argument("--window", type=int, default=0,
                        help="sliding window in tokens (the windowed "
                             "launches); 0 = none")
    parser.add_argument("--latent-heads", type=int, default=0,
                        help="time the latent (MLA) kernels at this many "
                             "query heads instead (64 A.X-K1, 32 Xing4.0); "
                             "--heads, --pool-dtype, --head-values and "
                             "--window do not apply")
    parser.add_argument("--rows", type=int, default=128,
                        help="decode rows")
    parser.add_argument("--chunk-rows", type=int, default=512,
                        help="rows of the prefill-chunk launch")
    parser.add_argument("--pool-dtype", default="float8_e5m2")
    parser.add_argument("--traffic", default="decode-closed")
    parser.add_argument("--width", type=int, default=64,
                        help="block-table width")
    parser.add_argument("--blocks", type=int, default=6535)
    parser.add_argument("--full-width", action="store_true",
                        help="every decode row fills its table")
    parser.add_argument("--layers", type=int, default=32,
                        help="kernel calls chained in one program")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal sizes")
    parser.add_argument("--out", default="")
    main(parser.parse_args())
