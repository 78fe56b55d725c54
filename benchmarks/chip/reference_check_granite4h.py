"""Granite 4.0-H's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_granite4h.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_granite4h.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) The procedure is `reference_check.py`'s,
and what is generic there is imported from it (the engine's boot, the cell's
traffic shape, the comparison); this file brings the weight mapping, the
wrong references and the limits of `granite-4.0-h-small-ep4-l10`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`, 128 seats; two blocks of 128 greedy
   `decode-closed` requests, so that the `mixed` programs (`ssd_chunk` over
   rows of many sequences) and the 128-row K=8 decode programs (`ssd_step`
   on the state pool by slot) produce the tokens;
2. the weights FROM THE ENGINE under HF's checkpoint names;
3. the reference (`references/granite_4_0_h_small_ep4_l10_reference.py`:
   the scan a token at a time) teacher-forced on the engine's tokens, layer
   by layer, log-softmax compared at every position that produced a token;
4. `--perturb`: the reference computed WRONG one way at a time; each must
   break a limit.

`--dtype float32 --layers 2` with `JAX_DEFAULT_MATMUL_PRECISION=highest` in
the environment is the sharp version (both sides float32: what is left is the
order of summation; 2 layers = one Mamba-2 layer and the attention layer,
what float32 weights leave room for); it prints its reading and holds it to
`--sharp-limit`.

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
                             emit, run_traffic)

CONFIG = "granite-4.0-h-small-ep4-l10"
REFERENCE = os.path.join(HERE, "references",
                         "granite_4_0_h_small_ep4_l10_reference.py")


def load_reference():
    """The benchmark's copy of the reference (`reference_check.py`'s
    loader would look for a file name with the configuration's dot)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- limits (nats of log-softmax), each with its reason ----------------------
#
# Both sides hold the same bf16 weights; the engine computes in bf16 (bf16
# products accumulated in float32; activations, the residual stream, K/V and
# the filter's taps stored in bf16; softmax, norms, the router, dt and the
# whole scan, state included, in float32), the reference in float32 at the
# highest matmul precision. The same engine in float32 at the highest
# precision agrees with the reference to ~1e-6 (PERF.md section 6, PR 41), so
# what these limits bound is bf16's own distance. Each was set between two
# readings on the chip (PERF.md section 6, PR 41, has every number): what the
# engine gave over its seeds, and what a reference computed one precision
# lower (int8 weights: per-channel round trip of every matrix; a bfloat16
# scan state) or with a term dropped or a multiplier left out gave against
# the same engine outputs. A lower precision has to break ONE of the limits,
# not each; a dropped term breaks several.
TOLERANCES = {
    "mean_abs": {
        "limit": 0.008,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~2e4 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.0068-0.0070 (Solar 2's reads 0.0043 at "
               "four layers and an untied head; this is ten layers and a "
               "tied one); a bfloat16 scan state 0.0093-0.0105 (nine layers "
               "of it over up to 976 tokens: this cell's bf16 limits can "
               "see one, Solar 2's could not), int8 weights 0.0139-0.0154, "
               "the usual softmax scale 0.050-0.053, a dropped term or "
               "multiplier 0.118-6.6",
    },
    "p99_abs": {
        "limit": 0.04,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie. Engine "
               "0.0224-0.0233; int8 weights 0.0485-0.0497, a dropped term "
               "or multiplier 0.128-17 (a bfloat16 state reads 0.032 or "
               "0.046 by the seed: it fails the mean either way)",
    },
    "max_abs": {
        "limit": 0.09,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a state slot read across "
               "a boundary, a wrong expert). The precision hardly moves it "
               "(engine 0.040-0.042, a bfloat16 state 0.054-0.062, int8 "
               "0.062-0.071); the usual softmax scale reads 0.151-0.188, a "
               "dropped term or multiplier 0.455-19",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat: "
               "argmax agreement where rounding cannot decide it; a guard "
               "against a corrupted row",
    },
}

# The wrong references of tests/models/test_granitemoehybrid.py, int8 weights
# and a bfloat16 state.
PERTURBATIONS = ("no_embedding_multiplier", "no_residual_multiplier",
                 "usual_attention_scale", "no_logits_scaling", "drop_skip_D",
                 "drop_filter_bias", "int8_weights", "bf16_state")
# What the float32 run has to see (its one limit, `--sharp-limit`): a lower
# precision inside the scan.
SHARP_PERTURBATIONS = ("bf16_state", )


# --- the engine's weights, under the checkpoint names ------------------------

def layer_kind(lp: dict) -> tuple:
    return ("mamba" if "in_proj" in lp else "attention", )


def published_layer(lp: dict, i: int) -> dict:
    """One layer of the engine's param tree -> {checkpoint name: float32
    array as a checkpoint holds it; the fused stacks hold the held experts
    alone}. No arithmetic: transposes and joins only."""
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    p = f"model.layers.{i}."
    w = {p + "input_layernorm.weight": f32(lp["input_norm"]),
         p + "post_attention_layernorm.weight": f32(lp["post_norm"])}
    if "in_proj" in lp:
        m = p + "mamba."
        w.update({m + "in_proj.weight": f32(lp["in_proj"]).T,
                  m + "conv1d.weight": f32(lp["conv_w"]).T[:, None, :],
                  m + "conv1d.bias": f32(lp["conv_b"]),
                  m + "dt_bias": f32(lp["dt_bias"]),
                  m + "A_log": f32(lp["A_log"]), m + "D": f32(lp["D"]),
                  m + "norm.weight": f32(lp["gate_norm"]),
                  m + "out_proj.weight": f32(lp["out_proj"]).T})
    else:
        for ours in "qkvo":
            w[f"{p}self_attn.{ours}_proj.weight"] = f32(lp[ours]).T
    e = p + "block_sparse_moe."
    w[e + "router.layer.weight"] = f32(lp["gate_router"]).T
    w[e + "input_linear.weight"] = np.concatenate(
        [f32(lp["w1"]), f32(lp["w3"])], axis=1)
    w[e + "output_linear.weight"] = f32(lp["w2"]).swapaxes(1, 2)
    s = p + "shared_mlp."
    w[s + "input_linear.weight"] = np.concatenate(
        [f32(lp["shared_gate"]).T, f32(lp["shared_up"]).T], axis=0)
    w[s + "output_linear.weight"] = f32(lp["shared_down"]).T
    return w


def reference_config(hf: dict) -> dict:
    """The keys the reference reads. `layer_types` is handed over whole:
    every layer of a kind runs under its kind's first layer's index and
    names, so the kinds share their programs."""
    return {k: hf[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "rms_norm_eps",
        "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling", "num_local_experts",
        "num_experts_per_tok", "ep_size", "ep_rank", "num_hidden_layers",
        "layer_types")}


# --- a wrong reference, one way at a time ------------------------------------

def scan_with_a_bf16_state(x, dt, a, b, c):
    """The reference's recurrence with its matrix state rounded to
    bfloat16 after every token: what a state pool in the activations'
    dtype would hold."""
    import jax
    import jax.numpy as jnp

    def step(s, r):
        x_t, dt_t, b_t, c_t = r
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        # Not a pair of converts: the compiler may drop a float32 ->
        # bfloat16 -> float32 round trip as excess precision
        # (reference_check_solar2.py).
        s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hpn,n->hp", s, c_t)

    h, p, n = x.shape[1], x.shape[2], b.shape[1]
    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (x, dt, b, c))
    return y


def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(cfg, weights, undo) of the reference computed wrong in one way."""
    import numpy as np
    cfg = json.loads(json.dumps(cfg))
    undo = lambda: None                                     # noqa: E731

    def zeroed(suffix):
        return {k: (np.zeros_like(v) if k.endswith(suffix) else v)
                for k, v in layer_w.items()}

    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v)
                       if v.ndim == 2 and not k.endswith("norm.weight")
                       else np.stack([_int8_roundtrip(m) for m in v])
                       if k.endswith(("input_linear.weight",
                                      "output_linear.weight"))
                       else v) for k, v in layer_w.items()}
    elif name == "no_embedding_multiplier":
        cfg["embedding_multiplier"] = 1.0
    elif name == "no_residual_multiplier":
        cfg["residual_multiplier"] = 1.0
    elif name == "usual_attention_scale":
        cfg["attention_multiplier"] = (
            cfg["hidden_size"] // cfg["num_attention_heads"]) ** -0.5
    elif name == "no_logits_scaling":
        cfg["logits_scaling"] = 1.0
    elif name == "drop_skip_D":
        layer_w = zeroed("mamba.D")
    elif name == "drop_filter_bias":
        layer_w = zeroed("conv1d.bias")
    elif name == "bf16_state":
        right = ref.scan
        ref.scan = scan_with_a_bf16_state

        def undo():
            ref.scan = right
    else:
        raise KeyError(name)
    return cfg, layer_w, undo


# --- the reference pass ------------------------------------------------------

_PROGRAMS: dict = {}      # (layer kind's first index, length, config, how)


def reference_logprobs(ref, cfg, params, seqs, how=None):
    """{index: float32 log-softmax [T, vocab]} of the reference,
    teacher-forced on `seqs` {index: token list}, layer by layer: one
    layer's float32 weights are on the device at a time. Under `jax.jit`,
    a program a (layer kind, sequence length)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hcfg, top = cfg, {"model.embed_tokens.weight": np.asarray(
        params["embed_tokens"]).astype(np.float32),
        "model.norm.weight": np.asarray(params["norm"]).astype(np.float32)}
    if how in ("no_embedding_multiplier", "no_logits_scaling"):
        hcfg, _, _ = perturb(how, cfg, {}, ref)
    if how == "int8_weights":
        top["model.embed_tokens.weight"] = _int8_roundtrip(
            top["model.embed_tokens.weight"])
    top = {k: jnp.asarray(v) for k, v in top.items()}
    xs = {i: ref.embed(hcfg, top, jnp.asarray(s)) for i, s in seqs.items()}
    layers = params["layers"]
    for lp in layers:
        ci = next(j for j, q in enumerate(layers)
                  if layer_kind(q) == layer_kind(lp))
        layer_w = published_layer(lp, ci)
        lcfg, undo = cfg, (lambda: None)
        if how:
            lcfg, layer_w, undo = perturb(how, cfg, layer_w, ref)
        layer_w = {k: jnp.asarray(v) for k, v in layer_w.items()}
        try:
            for i, s in seqs.items():
                key = (ci, len(s), json.dumps(lcfg, sort_keys=True),
                       how if how == "bf16_state" else None)
                if key not in _PROGRAMS:
                    _PROGRAMS[key] = jax.jit(
                        lambda w, x, ci=ci, lcfg=lcfg: ref.layer(
                            lcfg, w, ci, x))
                with jax.default_matmul_precision("highest"):
                    xs[i] = jax.block_until_ready(
                        _PROGRAMS[key](layer_w, xs[i]))
        finally:
            undo()
        del layer_w
        gc.collect()
    out = {}
    with jax.default_matmul_precision("highest"):
        for i in seqs:
            out[i] = np.asarray(jax.nn.log_softmax(
                ref.head(hcfg, top, xs[i]), axis=-1))
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
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="run the ENGINE with another cache dtype than the "
                    "configuration's (e.g. fp8_e5m2: must then disagree)")
    ap.add_argument("--dtype", default=None,
                    help="run the ENGINE in another dtype (float32: the "
                    "sharp check; the limits then do not apply)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth further (float32 needs it to fit)")
    ap.add_argument("--sharp-limit", type=float, default=2e-5,
                    help="mean |d logprob| a float32 engine is held to: "
                    "between the engine's reading and what a bfloat16 "
                    "state inside the scan reads (PERF.md section 6, "
                    "PR 41)")
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
    if args.kv_cache_dtype:
        serve["kv_cache_dtype"] = args.kv_cache_dtype
    if args.dtype:
        serve["dtype"] = args.dtype
    if args.layers:
        config = dict(config, name=f"{config['name']}-l{args.layers}",
                      num_hidden_layers=args.layers)
        config["rehearsal"] = dict(config["rehearsal"], hf_config=dict(
            config["rehearsal"]["hf_config"],
            num_hidden_layers=args.layers))
    hf = hf_config_of(config, rehearsal)
    if args.layers == 2:
        # one layer of each kind, the scan first
        config = dict(config, layer_types=["mamba", "attention"])
        config["rehearsal"] = dict(config["rehearsal"], hf_config=dict(
            config["rehearsal"]["hf_config"],
            layer_types=["mamba", "attention"]))
        hf = hf_config_of(config, rehearsal)
    hf["layer_types"] = hf["layer_types"][:hf["num_hidden_layers"]]
    model_dir = make_model_dir(config, rehearsal)
    seats = 8 if rehearsal else args.seats
    checked_block = seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check_granite4h: no accelerator (platform "
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
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.dispatch import kernel_selection
    compiles = get_compile_tracker().snapshot()
    bm = engine.scheduler.block_manager
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
          "state_slots": bm.num_state_slots,
          "memory_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
              "peak_bytes_in_use")})
    if not rehearsal:
        refs = {k: v for k, v in (compiles.get("kernel_dispatch") or
                                  {}).items() if k.startswith("reference")}
        if refs:
            print(f"reference_check_granite4h: kernel seams resolved to a "
                  f"reference: {refs}", file=sys.stderr)
            return 1

    # The engine's weights to the host, then the engine goes.
    params = jax.tree.map(np.asarray, engine.worker.params)
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
    t0 = time.perf_counter()
    stats = compare(done, reference_logprobs(ref, cfg, params, seqs))
    sharp = serve["dtype"] == "float32" and not rehearsal

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

    names = ([] if args.perturb == "none"
             else list(SHARP_PERTURBATIONS if sharp else PERTURBATIONS)
             if args.perturb == "all" else args.perturb.split(","))
    few = {i: seqs[i] for i in sorted(seqs, key=lambda i: len(seqs[i]))[-4:]}
    undetected, perturbed = [], {}
    if names:
        same = compare({i: done[i] for i in few},
                       reference_logprobs(ref, cfg, params, few))
        emit({"event": "reference_on_the_perturbed_requests",
              **{k: same[k] for k in ("values", "mean_abs", "p99_abs",
                                      "max_abs", "top1_agree_min")}})
    for name in names:
        p = compare(done, reference_logprobs(ref, cfg, params, few,
                                             how=name))
        p_broken = verdict_of(p)
        perturbed[name] = {k: p[k] for k in ("mean_abs", "p99_abs",
                                             "max_abs", "top1_agree_min")}
        perturbed[name]["broken"] = p_broken
        emit({"event": "perturbed", "how": name, **perturbed[name]})
        if not p_broken:
            undetected.append(name)
    agrees = not broken
    if args.kv_cache_dtype:
        agrees = bool(broken)       # one precision lower: has to disagree
    ok = agrees and not undetected
    emit({"agrees": not broken, "ok": ok, "config": CONFIG,
          "seed": args.seed, "engine_dtype": serve["dtype"],
          "engine_kv_cache_dtype": serve["kv_cache_dtype"],
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
