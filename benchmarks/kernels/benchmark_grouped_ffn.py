"""Device time a call of the dense and the grouped expert FFN, side by side.

Times `layers/moe.py` `moe_ffn_dense` and `moe_ffn_grouped` at the four
MoE configurations' widths and routing (DeepSeek-MoE-16B, the A.X-K1 share
of 12 held experts, LFM2-8B-A1B, SmallThinker-21BA3B) at 128 / 256 / 384 /
512 rows (`--rows 1,4,8,16,32,64,128` for the few-row side of the rule):
one expert layer-step, routing included, chained `--layers` times in one
program. The grouped form is first held to the dense one on the same
input; beside the times stand the held experts the first layer-step's rows
chose (`experts_chosen`: the grouped kernels' visits where the sorted rows
fit one tile) and the bytes of expert weights either form reads. `moe_ffn`'s
rule (`DENSE_ROWS`, `CHOSEN_SHARE_CUT`) is set from this table. `--root DIR` imports the
package from another checkout (the parent's, unpacked beside this one), so
one chip call times both:

    python benchmarks/kernels/benchmark_grouped_ffn.py --out a.json
    python benchmarks/kernels/benchmark_grouped_ffn.py \
        --root chipscratch/parent --out b.json

Device time is read from a profiler trace of the timed calls: the program's
own events on the device's module line over the layer-steps made (`--ops N`
adds the N longest operations by name); a host clock around the same calls
is printed beside it. A CPU run is a rehearsal of control flow, never a
timing (`--tiny`).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.join(HERE, "..", "..")

# name -> (held experts, router width, hidden, expert inner, top-k,
# Routing settings[, gate activation]) as the cells run them.
CONFIGS = {
    "dsmoe16b": (64, 64, 2048, 1408, 6, dict(renormalize=False)),
    "axk1-ep16": (12, 192, 7168, 2048, 8,
                  dict(scoring="sigmoid", n_group=8, topk_group=4,
                       scale=2.5, held_start=0)),
    "lfm2moe": (32, 32, 2048, 1792, 4,
                dict(scoring="sigmoid", sum_eps=1e-6)),
    "smallthinker": (64, 64, 2560, 768, 6, {}, "relu"),
}


def main(args) -> None:
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
    from intellillm_tpu.layers import moe

    result = {"root": args.root or ".", "layers": args.layers,
              "device": jax.devices()[0].device_kind,
              "platform": jax.devices()[0].platform, "table": []}
    for name in args.configs.split(","):
        n, n_router, d, i, top_k, settings, *activation = CONFIGS[name]
        activation = dict(activation=activation[0]) if activation else {}
        if args.tiny:
            d, i = 128, 128
        routing = moe.Routing(**settings)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
        # Logits of unit spread: a sigmoid router given wider ones scores
        # many experts exactly 1.0 and the ties all go to the lowest ids.
        gate_w = jax.random.normal(keys[0], (d, n_router),
                                   jnp.float32) * d ** -0.5
        w1, w2, w3 = (
            (jax.random.normal(k, (n, i, d), jnp.float32)
             * 0.02).astype(jnp.bfloat16) for k in keys[1:4])
        weights = (gate_w, w1, w2, w3)
        forms = {"dense": moe.moe_ffn_dense, "grouped": moe.moe_ffn_grouped}

        def chained(form):
            # The weights are arguments: closed over, they would be
            # constants of a 1 GB executable.
            def program(x, *weights):
                def layer(_, x):
                    out = form(x, *weights, top_k, routing=routing,
                               **activation)
                    # The next layer-step depends on this one, routes its
                    # rows a little differently and keeps their size.
                    return (x + 0.01 * out).astype(x.dtype)
                return jax.lax.fori_loop(0, args.layers, layer, x)
            return jax.jit(program)

        for rows in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(jax.random.fold_in(keys[4], rows),
                                  (rows, d), jnp.float32).astype(jnp.bfloat16)
            one = {f: jax.block_until_ready(jax.jit(
                lambda x, *weights, f=f: forms[f](
                    x, *weights, top_k, routing=routing,
                    **activation))(x, *weights))
                .astype(jnp.float32) for f in forms}
            local = (moe._route(x, gate_w, top_k, routing)[1]
                     - routing.held_start)
            chosen = int(jnp.unique(
                local[(local >= 0) & (local < n)]).size)
            expert_bytes = 3 * i * d * w1.dtype.itemsize
            line = {"config": name, "rows": rows, "experts_held": n,
                    "experts_chosen": chosen,
                    "dense_weight_bytes": n * expert_bytes,
                    "grouped_weight_bytes": chosen * expert_bytes,
                    "grouped_max_abs_diff": float(jnp.max(jnp.abs(
                        one["grouped"] - one["dense"]))),
                    "dense_max_abs": float(jnp.max(jnp.abs(one["dense"])))}
            for f in forms:
                line.update(timed(chained(forms[f]), (x, ) + weights, f,
                                  args))
            result["table"].append(line)
            print(json.dumps(line), flush=True)
        del w1, w2, w3
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)


def timed(program, inputs, form: str, args) -> dict:
    import jax
    calls = args.repeats * args.layers
    for _ in range(2):
        jax.block_until_ready(program(*inputs))
    t0 = time.perf_counter()
    for _ in range(args.repeats):
        out = program(*inputs)
    jax.block_until_ready(out)
    got = {f"{form}_host_us": (time.perf_counter() - t0) / calls * 1e6}
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(args.repeats):
                out = program(*inputs)
            jax.block_until_ready(out)
        device = device_us(tmp)
    if device is not None:
        got[f"{form}_device_us"] = device.pop("modules") / calls
        if args.ops:
            got[f"{form}_ops_us"] = {
                name: round(us / calls, 1) for name, us in sorted(
                    device.items(), key=lambda kv: -kv[1])[:args.ops]}
    return got


def device_us(trace_dir: str):
    """Summed device durations in us: the programs' events on the TPU's
    module line under "modules", and every operation's events on its op
    line by name (a loop's own event holds its body's: not a partition);
    None where the trace has no device plane (a CPU run)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    total = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                name = "modules" if line.name == "XLA Modules" else ev.name
                total[name] = total.get(name, 0.0) + ev.duration_ns / 1e3
    return total if "modules" in total else None


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default="",
                        help="checkout to import intellillm_tpu from")
    parser.add_argument("--configs", default=",".join(CONFIGS))
    parser.add_argument("--rows", default="128,256,384,512")
    parser.add_argument("--layers", type=int, default=8,
                        help="layer-steps chained in one program")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--ops", type=int, default=0,
                        help="also list this many operations a form, by "
                             "device time a layer-step")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal widths")
    parser.add_argument("--out", default="")
    main(parser.parse_args())
