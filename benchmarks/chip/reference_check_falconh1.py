"""Falcon-H1's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_falconh1.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_falconh1.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) The procedure is `reference_check.py`'s,
and what is generic there is imported from it (the engine's boot, the cell's
traffic shape, the comparison); this file brings the weight mapping, the
wrong references and the limits of `falcon-h1-34b-l6`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`, 128 seats; two blocks of 128 greedy
   `decode-closed` requests, so that the `mixed` programs (`ssd_chunk` over
   rows of many sequences beside the ragged attention) and the 128-row K=8
   decode programs (`ssd_step` on the state pool by slot beside the paged
   attention) produce the tokens;
2. the weights FROM THE ENGINE under HF's checkpoint names;
3. the reference (`references/falcon_h1_34b_l6_reference.py`: the scan a
   token and a head at a time) teacher-forced on the engine's tokens, layer
   by layer, the head a block of the vocabulary at a time, log-softmax
   compared at every position that produced a token;
4. `--perturb`: the reference computed WRONG one way at a time; each must
   break a limit.

`--dtype float32 --layers 2 --vocab 32640` with
`JAX_DEFAULT_MATMUL_PRECISION=highest` in the environment is the sharp
version (both sides float32: what is left is the order of summation; 2
layers and an eighth of the vocabulary are what float32 weights leave room
for: a float32 embedding and head of 261,120 rows are 10.7 GB; the check's
cut, never the cell's); it prints its reading and holds it to
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

CONFIG = "falcon-h1-34b-l6"
REFERENCE = os.path.join(HERE, "references",
                         "falcon_h1_34b_l6_reference.py")


def load_reference():
    """The benchmark's copy of the reference."""
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
# the filter's taps stored in bf16; softmax, norms, dt, the multipliers at the
# residual writes and the logits, and the whole scan, state included, in
# float32), the reference in float32 at the highest matmul precision. The
# same engine in float32 at the highest precision agrees with the reference
# to ~1e-5 (PERF.md section 6, PR 48), so what these limits bound is bf16's
# own distance. Each was set between two readings on the chip (PERF.md
# section 6, PR 48, has every number): what the engine gave over its seeds,
# and what a reference computed one precision lower (int8 weights:
# per-channel round trip of every matrix) or with a term dropped, a
# multiplier left out or the groups misread gave against the same engine
# outputs. A lower precision has to break ONE of the limits, not each; a
# dropped term breaks several.
TOLERANCES = {
    "mean_abs": {
        "limit": 0.010,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~2e4 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.0063-0.0064 over four seeds (six layers of "
               "three writers each under an untied head; Granite's ten "
               "layers read 0.0068-0.0070); int8 weights 0.0173, group 0's "
               "B and C for every head 0.082, a dropped term or multiplier "
               "0.13-29",
    },
    "p99_abs": {
        "limit": 0.034,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie. Engine "
               "0.0205-0.0206; int8 weights 0.0569, group 0's B and C for "
               "every head 0.278, a dropped term or multiplier 0.44-125",
    },
    "max_abs": {
        "limit": 0.07,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a state slot read across "
               "a boundary, a page of another sequence). An extreme value, "
               "so the widest room above the engine's 0.0327-0.0341; int8 "
               "weights 0.0956, group 0's B and C for every head 0.425, a "
               "dropped term or multiplier 0.61-202",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat: "
               "argmax agreement where rounding cannot decide it; a guard "
               "against a corrupted row (engine 1.0; the wrong references "
               "0.0-0.98, int8 weights 1.0)",
    },
}

# The wrong references of tests/models/test_falcon_h1.py that the published
# multipliers allow (attention_in_multiplier is 1 as published) and int8
# weights.
PERTURBATIONS = ("group0_for_every_head", "no_attention_branch",
                 "no_ssm_out_multiplier", "no_key_multiplier",
                 "one_ssm_multiplier_at_1", "norm_before_gate", "no_rope",
                 "no_embedding_multiplier", "no_lm_head_multiplier",
                 "no_mlp_gate_multiplier", "int8_weights")
# What the float32 run has to see (its one limit, `--sharp-limit`): a lower
# precision inside the scan. The bf16 limits cannot see a bfloat16 state
# (it reads 0.0066 / 0.021 / 0.031 against the engine's 0.0064 / 0.021 /
# 0.033: bf16's own distance at the logits hides six layers of it over at
# most 976 tokens, as in Solar Open 2's cell; Granite's nine layers show).
SHARP_PERTURBATIONS = ("bf16_state", )


# --- the engine's weights, under the checkpoint names ------------------------

def published_layer(lp: dict, i: int) -> dict:
    """One layer of the engine's param tree -> {checkpoint name: float32
    array as a checkpoint holds it}. No arithmetic: transposes only."""
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    p = f"model.layers.{i}."
    m, a, f = p + "mamba.", p + "self_attn.", p + "feed_forward."
    w = {p + "input_layernorm.weight": f32(lp["input_norm"]),
         p + "pre_ff_layernorm.weight": f32(lp["pre_ff_norm"]),
         m + "in_proj.weight": f32(lp["in_proj"]).T,
         m + "conv1d.weight": f32(lp["conv_w"]).T[:, None, :],
         m + "conv1d.bias": f32(lp["conv_b"]),
         m + "dt_bias": f32(lp["dt_bias"]),
         m + "A_log": f32(lp["A_log"]), m + "D": f32(lp["D"]),
         m + "norm.weight": f32(lp["gate_norm"]),
         m + "out_proj.weight": f32(lp["out_proj"]).T}
    for ours in "qkvo":
        w[f"{a}{ours}_proj.weight"] = f32(lp[ours]).T
    for ours in ("gate", "up", "down"):
        w[f"{f}{ours}_proj.weight"] = f32(lp[ours]).T
    return w


def reference_config(hf: dict) -> dict:
    """The keys the reference reads."""
    return {k: hf[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "rms_norm_eps", "rope_theta",
        "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "ssm_multipliers", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "mlp_multipliers",
        "num_hidden_layers")}


# --- a wrong reference, one way at a time ------------------------------------

def scan_head_with_a_bf16_state(x, dt, a, b, c):
    """The reference's recurrence with its matrix state rounded to
    bfloat16 after every token: what a state pool in the activations'
    dtype would hold."""
    import jax
    import jax.numpy as jnp

    def step(s, r):
        x_t, dt_t, b_t, c_t = r
        s = jnp.exp(dt_t * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
        # Not a pair of converts: the compiler may drop a float32 ->
        # bfloat16 -> float32 round trip as excess precision
        # (reference_check_solar2.py).
        s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, s @ c_t

    _, y = jax.lax.scan(
        step, jnp.zeros((x.shape[1], b.shape[1]), jnp.float32),
        (x, dt, b, c))
    return y


def norm_before_gate(y, z, w, groups, eps):
    import jax
    import jax.numpy as jnp
    t, inner = y.shape
    y = y.reshape(t, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(t, inner) * jnp.asarray(w, jnp.float32)
            * jax.nn.silu(z))


# name -> (the reference's attribute, what stands in its place)
PATCHES = {
    "group0_for_every_head": ("head_groups", lambda heads, groups: [0] * heads),
    "norm_before_gate": ("gated_norm", norm_before_gate),
    "no_rope": ("rope", lambda x, theta: x),
    "bf16_state": ("scan_head", scan_head_with_a_bf16_state),
}
# name -> what the config reads in its place
CONFIG_EDITS = {
    "no_attention_branch": lambda c: c.update(attention_out_multiplier=0.0),
    "no_ssm_out_multiplier": lambda c: c.update(ssm_out_multiplier=1.0),
    "no_key_multiplier": lambda c: c.update(key_multiplier=1.0),
    # B's segment: 0.1768 as published
    "one_ssm_multiplier_at_1": lambda c: c["ssm_multipliers"].__setitem__(
        2, 1.0),
    "no_embedding_multiplier": lambda c: c.update(embedding_multiplier=1.0),
    "no_lm_head_multiplier": lambda c: c.update(lm_head_multiplier=1.0),
    "no_mlp_gate_multiplier": lambda c: c["mlp_multipliers"].__setitem__(
        0, 1.0),
}


def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(cfg, weights, undo) of the reference computed wrong in one way."""
    cfg = json.loads(json.dumps(cfg))
    undo = lambda: None                                     # noqa: E731
    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v)
                       if v.ndim == 2 and not k.endswith("norm.weight")
                       else v) for k, v in layer_w.items()}
    elif name in CONFIG_EDITS:
        CONFIG_EDITS[name](cfg)
    elif name in PATCHES:
        attr, wrong = PATCHES[name]
        right = getattr(ref, attr)
        setattr(ref, attr, wrong)

        def undo():
            setattr(ref, attr, right)
    else:
        raise KeyError(name)
    return cfg, layer_w, undo


# --- the reference pass ------------------------------------------------------

_PROGRAMS: dict = {}      # (length, config, how)


class Rows:
    """Log-softmax rows of the positions from `first` on, read by position
    as `compare` reads a whole sequence's."""

    def __init__(self, first: int, rows):
        self.first, self.rows = first, rows

    def __getitem__(self, pos):
        return self.rows[pos - self.first]


def _int8_table(table):
    """A [vocab, hidden] table through the int8 round trip, a block of its
    rows at a time."""
    import numpy as np
    return np.concatenate([
        _int8_roundtrip(np.asarray(table[v:v + 32768]).astype(np.float32))
        for v in range(0, table.shape[0], 32768)])


def reference_logprobs(ref, cfg, params, seqs, firsts, how=None):
    """{index: Rows of float32 log-softmax [positions, vocab]} of the
    reference, teacher-forced on `seqs` {index: token list}, from position
    `firsts[index]` on (the position that gave the first token), layer by
    layer: one layer's float32 weights are on the device at a time. Under
    `jax.jit`, a program a sequence length (every layer is of one kind)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hcfg = cfg
    if how in CONFIG_EDITS:
        hcfg, _, _ = perturb(how, cfg, {}, ref)
    embed, table = params["embed_tokens"], params["lm_head"]
    if how == "int8_weights":
        embed, table = _int8_table(embed), _int8_table(table)
    top = {"model.embed_tokens.weight": embed,
           "model.final_layernorm.weight": jnp.asarray(
               np.asarray(params["norm"]).astype(np.float32)),
           # on the device once, in its own dtype; the reference takes a
           # block of its rows to float32 at a time (the int8 round trip's
           # float32 table stays on the host: 5.3 GB, sent a block at a
           # time)
           "lm_head.weight": (table if how == "int8_weights"
                              else jnp.asarray(table))}
    # Every sequence at a whole number of 128 tokens, zeros after its end
    # (the pass is causal: what follows a position does not reach it), so
    # that the layer's program is compiled for at most eight lengths.
    padded = {i: list(s) + [0] * (-len(s) % 128) for i, s in seqs.items()}
    xs = {i: ref.embed(hcfg, top, s) for i, s in padded.items()}
    for lp in params["layers"]:
        layer_w = published_layer(lp, 0)
        lcfg, undo = cfg, (lambda: None)
        if how:
            lcfg, layer_w, undo = perturb(how, cfg, layer_w, ref)
        layer_w = {k: jnp.asarray(v) for k, v in layer_w.items()}
        try:
            for i, s in padded.items():
                key = (len(s), json.dumps(lcfg, sort_keys=True),
                       how if how in PATCHES else None)
                if key not in _PROGRAMS:
                    _PROGRAMS[key] = jax.jit(
                        lambda w, x, lcfg=lcfg: ref.layer(lcfg, w, 0, x))
                with jax.default_matmul_precision("highest"):
                    xs[i] = jax.block_until_ready(
                        _PROGRAMS[key](layer_w, xs[i]))
        finally:
            undo()
        del layer_w
        gc.collect()
    out = {}
    with jax.default_matmul_precision("highest"):
        for i, s in seqs.items():
            rows = np.arange(firsts[i], len(s) - 1)
            out[i] = Rows(firsts[i], np.asarray(jax.nn.log_softmax(
                ref.head(hcfg, top, xs[i], rows=rows), axis=-1)))
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
    ap.add_argument("--vocab", type=int, default=None,
                    help="cut the vocabulary (float32 needs it to fit: a "
                    "float32 embedding and head of 261,120 rows are 10.7 "
                    "GB; the check's cut, never the cell's)")
    ap.add_argument("--sharp-limit", type=float, default=2e-5,
                    help="mean |d logprob| a float32 engine is held to: "
                    "between the engine's reading (7.3e-7) and what a "
                    "bfloat16 state inside the scan reads (1.5e-3) "
                    "(PERF.md section 6, PR 48)")
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
    cut = {key: value for key, value in (
        ("num_hidden_layers", args.layers), ("vocab_size", args.vocab))
        if value}
    if cut:
        config = dict(config, **cut, name="-".join(
            [config["name"]] + [f"{k[0]}{v}" for k, v in cut.items()]))
        config["rehearsal"] = dict(config["rehearsal"], hf_config=dict(
            config["rehearsal"]["hf_config"], **cut))
    hf = hf_config_of(config, rehearsal)
    model_dir = make_model_dir(config, rehearsal)
    seats = 8 if rehearsal else args.seats
    checked_block = seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check_falconh1: no accelerator (platform "
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
            print(f"reference_check_falconh1: kernel seams resolved to a "
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
    firsts = {i: len(done[i][0]) - 1 for i in picks}
    stats = compare(done, reference_logprobs(ref, cfg, params, seqs, firsts))
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
                       reference_logprobs(ref, cfg, params, few, firsts))
        emit({"event": "reference_on_the_perturbed_requests",
              **{k: same[k] for k in ("values", "mean_abs", "p99_abs",
                                      "max_abs", "top1_agree_min")}})
    for name in names:
        p = compare(done, reference_logprobs(ref, cfg, params, few, firsts,
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
