"""Xing4.0's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_xing4.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_xing4.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) The procedure is `reference_check.py`'s, and
what is generic there is imported from it (the engine's boot, the cell's
traffic shape, the weight mapping of the DeepSeek-V3 block, the comparison);
this file brings the mixes' weights, the wrong references and the limits of
`xing4.0-29b-a4b-ep8`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`, 128 seats; two blocks of 128 greedy
   `decode-closed` requests, so that the `mixed` programs and the 128-row K=8
   decode programs produce the tokens through the forty layers' one
   latent pool;
2. the weights FROM THE ENGINE under the assumed checkpoint names;
3. the reference (`references/xing4_0_29b_a4b_ep8_reference.py`: expanded
   attention, Sinkhorn as a loop) teacher-forced on the engine's tokens,
   layer by layer, log-softmax compared at every position that produced a
   token;
4. `--perturb`: the reference computed WRONG one way at a time (the wrong
   computations of tests/models/test_xing4.py, and int8 weights); each must
   break a limit.

`--dtype float32 --layers 4` with `JAX_DEFAULT_MATMUL_PRECISION=highest` in
the environment is the sharp version (both sides float32: what is left is the
order of summation; 4 layers = 2 dense + 2 MoE, what float32 weights leave
room for beside the pools); it prints its reading, holds it to
`--sharp-limit`, and must see bfloat16 coefficients.

The last line of stdout is one JSON object; exit 0 = agrees (and every
perturbation failed), 1 = does not, 2 = could not run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import loadgen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.server import ROOT, hf_config_of, make_model_dir  # noqa: E402
from reference_check import (_int8_roundtrip, boot_engine, compare,  # noqa: E402
                             emit, published_layer as v3_block_layer,
                             reference_config, run_traffic)

CONFIG = "xing4.0-29b-a4b-ep8"
REFERENCE = os.path.join(HERE, "references",
                         "xing4_0_29b_a4b_ep8_reference.py")


def load_reference():
    """The benchmark's copy of the reference (`reference_check.py`'s
    loader would look for a file name with the configuration's dots)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- limits (nats of log-softmax), each with its reason ----------------------
#
# Both sides hold the same bf16 weights (the router, its choice bias and the
# mixes' parameters float32 on both); the engine computes in bf16 (bf16
# products accumulated in float32; activations, the four streams and the
# latents stored in bf16; softmax, norms, the router and the mixes'
# coefficients in float32), the reference in float32 at the highest matmul
# precision. The same engine in float32 at the highest precision agrees with
# the reference to the sharp limit below (PERF.md section 6, PR 45), so what
# these limits bound is bf16's own distance through FORTY layers (A.X-K1's
# cell: seven). Set from my chip run x45f (PR 45, seed 4500003101: all 40
# layers, 128 seats, 23,620 compared values of 16 requests), each limit
# between the engine's reading and the nearest wrong reference's reading
# against the same engine outputs (on the four longest requests, where the
# right reference reads 0.0113 / 0.066 / 0.193 / 0.9992):
#
#                          mean     p99     max     top-1
#   engine (bf16)          0.0116   0.0706  0.194   0.9994
#   hpre_uniform           0.0186   0.0978  0.318   0.9969
#   int8_weights           0.0220   0.1022  0.341   0.9977
#   one_sweep              0.0366   0.1383  0.344   0.9885
#   bias_in_the_weights    0.0424   0.1558  0.375   0.9761
#   hres_identity          0.0597   0.2096  0.403   0.9617
#   hpost_without_its_2    0.2454   0.7851  1.130   0.6098
#   rows_then_columns      0.0113   0.0676  0.194   0.9992   (not seen)
#   bf16_coefficients      0.0116   0.0665  0.190   0.9985   (not seen)
#
# A lower precision (int8 weights: per-channel round trip of every
# projection) has to break ONE of the limits, not each; here it breaks all
# four. What bf16 cannot see: the head sums the streams and every Hres row
# sums to 1, so the sweeps in the other order reach the logits only through
# later sublayers' Hpre (second order: 7.5e-6 at float32), and bfloat16
# coefficients are the engine's own precision; both belong to the float32
# run (`SHARP_PERTURBATIONS`), which sees them. One seed at this depth: a
# 40-layer run is ten chip-minutes (PERF.md section 6); fresh seeds will
# read a little higher, so the room above the engine's reading is the
# larger (20-35 %).
TOLERANCES = {
    "mean_abs": {
        "limit": 0.015,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~2e4 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first",
    },
    "p99_abs": {
        "limit": 0.085,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie",
    },
    "max_abs": {
        "limit": 0.26,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a wrong expert); an "
               "extreme value, the noisiest of the four over seeds",
    },
    "top1_agree_min": {
        "limit": 0.9985,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat: "
               "argmax agreement where rounding cannot decide it; a guard "
               "against a corrupted row",
    },
}

# The wrong references of tests/models/test_xing4.py that bf16 can see, and
# int8 weights. (`no_clamp` is not among them: the seeded weights' logits,
# N(0, 1) biases under gates of at most 1.5, never reach the clamp at 30, so
# a reference without it is the same reference; the CPU test draws logits
# beyond it. `rows_then_columns` and `bf16_coefficients` read as the right
# reference does at bf16, table above: the float32 run's.)
PERTURBATIONS = ("int8_weights", "hres_identity", "hpre_uniform",
                 "hpost_without_its_2", "one_sweep", "bias_in_the_weights")
# What the float32 run has to see (its one limit, `--sharp-limit`).
SHARP_PERTURBATIONS = ("bf16_coefficients", "rows_then_columns",
                       "projection_one_pass")


# --- the engine's weights, under the checkpoint names ------------------------

def published_layer(model, lp: dict, i: int) -> dict:
    """One layer of the engine's param tree -> {checkpoint name: float32
    array as a checkpoint holds it}: the DeepSeek-V3 block's
    (`reference_check.published_layer`), the choice bias and the two
    mixes'. No arithmetic."""
    import numpy as np
    w = v3_block_layer(model, lp, i)
    p = f"model.layers.{i}."
    if "expert_bias" in lp:
        w[p + "mlp.gate.e_score_correction_bias"] = np.asarray(
            lp["expert_bias"]).astype(np.float32)
    for hc in ("attn_hc", "ffn_hc"):
        for ours, theirs in (("phi", "phi.weight"), ("alpha", "alpha"),
                             ("b_pre", "b_pre"), ("b_post", "b_post"),
                             ("b_res", "b_res")):
            w[f"{p}{hc}.{theirs}"] = np.asarray(lp[hc][ours]).astype(
                np.float32)
    return w


# --- a wrong reference, one way at a time ------------------------------------

def _replaced(ref, name, new):
    old = getattr(ref, name)
    setattr(ref, name, new)
    return lambda: setattr(ref, name, old)


def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(cfg, weights, undo) of the reference computed wrong in one way."""
    import jax
    import jax.numpy as jnp
    cfg = json.loads(json.dumps(cfg))
    undo = lambda: None                                     # noqa: E731
    right = ref.coefficients

    def coefficients_with(change):
        return lambda c, w, prefix, x: change(*right(c, w, prefix, x))

    def bf16(x):
        # Not a pair of converts: the compiler may drop a float32 ->
        # bfloat16 -> float32 round trip as excess precision
        # (reference_check_solar2.py).
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v) if "proj" in k else v)
                   for k, v in layer_w.items()}
    elif name == "hres_identity":
        undo = _replaced(ref, "sinkhorn", lambda c, z: jnp.broadcast_to(
            jnp.eye(z.shape[-1], dtype=z.dtype), z.shape))
    elif name == "hpre_uniform":
        undo = _replaced(ref, "coefficients", coefficients_with(
            lambda pre, post, res: (jnp.full_like(pre, 0.25), post, res)))
    elif name == "hpost_without_its_2":
        undo = _replaced(ref, "coefficients", coefficients_with(
            lambda pre, post, res: (pre, post / 2.0, res)))
    elif name == "one_sweep":
        cfg["hc_sinkhorn_iters"] = 1
    elif name == "rows_then_columns":
        def swapped(c, z):
            m = jnp.exp(jnp.clip(z, c["mhc_h_res_clamp_min"],
                                 c["mhc_h_res_clamp_max"]))
            for _ in range(c["hc_sinkhorn_iters"]):
                m = m / (m.sum(axis=2, keepdims=True) + c["hc_eps"])
                m = m / (m.sum(axis=1, keepdims=True) + c["hc_eps"])
            return m
        undo = _replaced(ref, "sinkhorn", swapped)
    elif name == "bias_in_the_weights":
        def route(c, scores, bias):
            biased = scores + bias[None, :]
            _, experts = jax.lax.top_k(biased, c["num_experts_per_tok"])
            weights = jnp.take_along_axis(biased, experts, axis=1)
            weights = weights / weights.sum(axis=-1, keepdims=True)
            return weights * c["routed_scaling_factor"], experts
        undo = _replaced(ref, "route", route)
    elif name == "projection_one_pass":
        # The mixes' projection as a TPU computes a float32 dot by
        # default: ONE bfloat16 pass. (The explicit precision overrides
        # the reference's `default_matmul_precision("highest")`.)
        def coefficients(c, w, prefix, x):
            t, n, d = x.shape
            flat = x.reshape(t, n * d)
            r = 1.0 / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                               + c["rms_norm_eps"])
            m = jnp.dot(flat * r,
                        jnp.asarray(w[prefix + "phi.weight"], jnp.float32).T,
                        precision=jax.lax.Precision.DEFAULT)
            a = jnp.asarray(w[prefix + "alpha"], jnp.float32)
            pre = jax.nn.sigmoid(a[0] * m[:, :n] + w[prefix + "b_pre"])
            post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n]
                                        + w[prefix + "b_post"])
            res = ref.sinkhorn(c, a[2] * m[:, 2 * n:].reshape(t, n, n)
                               + w[prefix + "b_res"])
            return pre, post, res
        undo = _replaced(ref, "coefficients", coefficients)
    elif name == "bf16_coefficients":
        # What a mix computed in the streams' dtype would give: the
        # statistic, the projection and the three coefficient sets rounded
        # to bfloat16.
        def coefficients(c, w, prefix, x):
            t, n, d = x.shape
            flat = bf16(x.reshape(t, n * d))
            r = bf16(1.0 / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                    + c["rms_norm_eps"]))
            m = bf16(bf16(flat * r) @ bf16(jnp.asarray(
                w[prefix + "phi.weight"], jnp.float32)).T)
            a = jnp.asarray(w[prefix + "alpha"], jnp.float32)
            pre = bf16(jax.nn.sigmoid(a[0] * m[:, :n] + w[prefix + "b_pre"]))
            post = bf16(2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n]
                                             + w[prefix + "b_post"]))
            res = bf16(ref.sinkhorn(c, bf16(
                a[2] * m[:, 2 * n:].reshape(t, n, n) + w[prefix + "b_res"])))
            return pre, post, res
        undo = _replaced(ref, "coefficients", coefficients)
    else:
        raise KeyError(name)
    return cfg, layer_w, undo


# --- the reference pass ------------------------------------------------------

_PROGRAMS: dict = {}      # (layer kind's first index, length, config, how)


def reference_logprobs(ref, cfg, model, params, seqs, ep, hows=(None, ),
                       perturbed_on=None):
    """{how: {index: float32 log-softmax [T, vocab]}} of the reference
    (`how` None) and of each wrong reference in `hows`, teacher-forced on
    `seqs` {index: token list} (a wrong one on the requests `perturbed_on`
    alone), layer by layer in ONE pass: a layer's float32 weights go to
    the device once and every reference's streams move through them.
    Under `jax.jit`, a program a (layer kind, way of being wrong): every
    layer of a kind (dense, MoE) is handed over under that kind's first
    layer's names, and every sequence padded with token 0 to one length
    (the model is causal: what follows a position does not reach it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    length = -(-max(len(s) for s in seqs.values()) // 128) * 128
    padded = {i: list(s) + [0] * (length - len(s)) for i, s in seqs.items()}
    on = {how: (list(seqs) if how is None else list(perturbed_on or seqs))
          for how in hows}
    top = {"model.embed_tokens.weight": f32(params["embed_tokens"])}
    xs = {how: {i: ref.embed(cfg, top, jnp.asarray(padded[i]))
                for i in on[how]} for how in hows}
    del top
    first_dense = model.first_dense
    for layer in range(model.num_layers):
        lp = model.layer_params(params, layer)
        ci = 0 if layer < first_dense else first_dense
        right_w = published_layer(model, lp, ci)
        on_device = {k: jnp.asarray(v) for k, v in right_w.items()}
        for how in hows:
            lcfg, layer_w, undo = cfg, on_device, (lambda: None)
            if how:
                lcfg, changed, undo = perturb(how, cfg, right_w, ref)
                if changed is not right_w:          # (int8 weights)
                    layer_w = {k: jnp.asarray(v) for k, v in changed.items()}
            try:
                # (a wrong reference that only changes weights shares the
                # right one's programs)
                key = (ci, length, json.dumps(lcfg, sort_keys=True),
                       None if how == "int8_weights" else how)
                if key not in _PROGRAMS:
                    _PROGRAMS[key] = jax.jit(
                        lambda w, x, ci=ci, lcfg=lcfg: ref.layer(
                            lcfg, w, ci, x, np.arange(length), *ep))
                for i in on[how]:
                    xs[how][i] = jax.block_until_ready(
                        _PROGRAMS[key](layer_w, xs[how][i]))
            finally:
                undo()
            del layer_w
        del on_device, right_w
        gc.collect()
    head_w = {"model.norm.weight": f32(params["norm"]),
              "lm_head.weight": f32(params["lm_head"]).T}
    out = {}
    for how in hows:
        w = dict(head_w)
        if how == "int8_weights":
            w["lm_head.weight"] = _int8_roundtrip(w["lm_head.weight"])
        w = {k: jnp.asarray(v) for k, v in w.items()}
        out[how] = {i: np.asarray(jax.nn.log_softmax(
            ref.head(cfg, w, xs[how][i][:len(seqs[i])]), axis=-1))
            for i in on[how]}
    return out


def verdict(stats: dict) -> list:
    """Names of the limits `stats` breaks."""
    broken = [k for k in ("mean_abs", "p99_abs", "max_abs")
              if not stats[k] <= TOLERANCES[k]["limit"]]     # NaN breaks
    if not stats["top1_agree_min"] >= TOLERANCES["top1_agree_min"]["limit"]:
        broken.append("top1_agree_min")
    return broken


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="decode-closed")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--check", type=int, default=16,
                    help="requests compared with the reference")
    ap.add_argument("--seats", type=int, default=128,
                    help="concurrent requests (the closed loop's clients)")
    ap.add_argument("--perturb", default="all",
                    help="comma list of " + ",".join(PERTURBATIONS)
                    + ", or all / none")
    ap.add_argument("--dtype", default=None,
                    help="run the ENGINE in another dtype (float32: the "
                    "sharp check; the limits then do not apply)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth (float32 needs it to fit)")
    ap.add_argument("--sharp-limit", type=float, default=3e-6,
                    help="mean |d logprob| a float32 engine is held to: "
                    "between the engine's reading (6.7e-7 at 4 layers) and "
                    "what the sweeps in the other order read (7.5e-6; "
                    "bfloat16 coefficients 2.0e-4): my chip runs x45d / "
                    "x45g, PERF.md section 6, PR 45. Granite's and Solar "
                    "2's 2e-5 would let the sweeps' order pass")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)

    manifest = Manifest()
    config = manifest.config(CONFIG)
    traffic = manifest.traffic(args.traffic)
    if rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    serve = dict(config["serve"])
    if rehearsal:
        serve.update(config["rehearsal"]["serve"])
    if args.dtype:
        serve["dtype"] = args.dtype
    if args.layers:
        config = dict(config, name=f"{config['name']}-l{args.layers}",
                      num_hidden_layers=args.layers)
        config["rehearsal"] = dict(config["rehearsal"], hf_config=dict(
            config["rehearsal"]["hf_config"],
            num_hidden_layers=args.layers))
    hf = hf_config_of(config, rehearsal)
    model_dir = make_model_dir(config, rehearsal)
    seats = 8 if rehearsal else args.seats
    checked_block = seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check_xing4: no accelerator (platform "
              f"{platform}); a CPU rehearsal needs --rehearse-on-cpu",
              file=sys.stderr)
        return 2

    import random
    plan = []
    for _ in range(2):
        pairs = loadgen.length_pairs(traffic, checked_block)
        random.Random(args.seed + len(plan)).shuffle(pairs)
        plan += [loadgen.Planned(len(plan) + k, "closed", p, o)
                 for k, (p, o) in enumerate(pairs)]
    loadgen.fill_prompts(plan, args.seed, hf["vocab_size"])

    t0 = time.perf_counter()
    engine = boot_engine(model_dir, serve, args.seed, seats)
    boot_s = time.perf_counter() - t0
    done, steps, run_s = run_traffic(engine, plan, checked_block)
    from intellillm_tpu.obs import (get_compile_tracker,
                                    get_efficiency_tracker)
    from intellillm_tpu.ops.dispatch import kernel_selection
    compiles = get_compile_tracker().snapshot()
    emit({"event": "engine", "platform": platform,
          "device_kind": jax.devices()[0].device_kind,
          "dtype": serve["dtype"], "layers": hf["num_hidden_layers"],
          "boot_s": boot_s, "run_s": run_s, "steps": steps,
          "requests_done": len(done),
          "tokens": sum(len(t) for _, t, _ in done.values()),
          "kernel_selection": kernel_selection(),
          "kernel_dispatch": compiles.get("kernel_dispatch"),
          "compiles": compiles.get("compiles"),
          "num_decode_steps": engine.scheduler_config.num_decode_steps,
          "mhc": get_efficiency_tracker().snapshot().get("mhc"),
          "memory_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
              "peak_bytes_in_use")})
    if not rehearsal:
        refs = {k: v for k, v in (compiles.get("kernel_dispatch") or
                                  {}).items() if k.startswith("reference")}
        if refs:
            print(f"reference_check_xing4: kernel seams resolved to a "
                  f"reference: {refs}", file=sys.stderr)
            return 1

    # The engine's weights to the host, then the engine goes.
    model = engine.worker.model
    params = jax.tree.map(np.asarray, engine.worker.params)
    ep = ((hf["ep_size"], hf.get("ep_rank", 0))
          if hf.get("ep_size", 1) > 1 else (None, 0))
    engine.worker.params = None
    engine.worker.cache_engine = None
    del engine
    gc.collect()
    jax.clear_caches()

    by_len = sorted(done, key=lambda i: len(done[i][0]) + len(done[i][1]))
    n = min(args.check, len(by_len))
    picks = sorted({by_len[round(k * (len(by_len) - 1) / max(n - 1, 1))]
                    for k in range(n)})
    seqs = {i: done[i][0] + done[i][1] for i in picks}
    emit({"event": "checked", "requests": picks,
          "lengths": [len(seqs[i]) for i in picks],
          "prompt_lens": [len(done[i][0]) for i in picks]})

    ref = load_reference()
    cfg = reference_config(hf)
    # (the rehearsal's preset is float32 too: it is held to the sharp
    # limit, so that its wrong references are seen to be wrong)
    sharp = serve["dtype"] == "float32"
    names = ([] if args.perturb == "none"
             else list(SHARP_PERTURBATIONS if sharp else PERTURBATIONS)
             if args.perturb == "all" else args.perturb.split(","))
    few = sorted(seqs, key=lambda i: len(seqs[i]))[-4:]
    t0 = time.perf_counter()
    logprobs = reference_logprobs(ref, cfg, model, params, seqs, ep,
                                  hows=(None, *names), perturbed_on=few)
    stats = compare(done, logprobs[None])

    def sharp_verdict(st: dict) -> list:
        return ([] if st["mean_abs"] <= args.sharp_limit
                else ["sharp_mean_abs"])

    verdict_of = sharp_verdict if sharp else verdict
    broken = verdict_of(stats)
    emit({"event": "reference", "seconds": time.perf_counter() - t0,
          **stats, "broken": broken})
    if sharp:
        print(f"float32 engine against the float32 reference: mean "
              f"|d logprob| {stats['mean_abs']:.3g} (limit "
              f"{args.sharp_limit:g}), max {stats['max_abs']:.3g}")
    else:
        print("limits (nats of log-softmax):")
        for k, t in TOLERANCES.items():
            print(f"  {k} {'>=' if k.endswith('_min') else '<='} "
                  f"{t['limit']}: measured {stats[k]:.6g} -- {t['why']}")

    undetected, perturbed = [], {}
    if names:
        same = compare({i: done[i] for i in few},
                       {i: logprobs[None][i] for i in few})
        emit({"event": "reference_on_the_perturbed_requests",
              **{k: same[k] for k in ("values", "mean_abs", "p99_abs",
                                      "max_abs", "top1_agree_min")}})
    for name in names:
        p = compare(done, logprobs[name])
        p_broken = verdict_of(p)
        perturbed[name] = {k: p[k] for k in ("mean_abs", "p99_abs",
                                             "max_abs", "top1_agree_min")}
        perturbed[name]["broken"] = p_broken
        emit({"event": "perturbed", "how": name, **perturbed[name]})
        if not p_broken:
            undetected.append(name)
    ok = not broken and not undetected
    emit({"agrees": not broken, "ok": ok, "config": CONFIG,
          "seed": args.seed, "engine_dtype": serve["dtype"],
          "layers": hf["num_hidden_layers"],
          "stats": {k: stats[k] for k in ("values", "mean_abs", "p99_abs",
                                          "max_abs", "top1_agree_min",
                                          "top1_positions")},
          "broken": broken, "perturbations": perturbed,
          "perturbations_undetected": undetected,
          "rehearsal": rehearsal, "platform": platform})
    if rehearsal:
        return 0        # the tiny preset proves the control flow only
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    # Leave without the interpreter's teardown: with the engine's and the
    # runtime's threads still about, it has ended a finished run in
    # "terminate called ... exception not rethrown" (SIGABRT after the last
    # line was printed: reference_check_solar2.py).
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
