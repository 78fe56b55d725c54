"""Phi-4-mini-flash's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_phi4flash.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_phi4flash.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) What is generic is `reference_check.py`'s
(the engine's boot, the comparison); this file brings the traffic, the
weight mapping, the wrong references and the limits of
`phi-4-mini-flash-reasoning`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`; `--check` greedy requests whose prompts
   (`PROMPTS`: 1,100 to 2,100 tokens, and one short one) go through two to
   five `mixed` chunks of 512 rows and whose 48 output tokens come from the
   K=8 decode programs, so that rows beyond the 512-token window (in the
   prefill's wrapped window and in the decode's), the full layer's stage
   read by the cross layers within a substep, and both forms of the scan
   (`sscan_chunk`, `sscan_step`) are all in the comparison;
2. the weights FROM THE ENGINE under the hub's checkpoint names (a view: a
   matrix is transposed when the reference asks for it);
3. the reference (`references/phi_4_mini_flash_reasoning_reference.py`)
   teacher-forced on the engine's tokens, every sequence padded to one
   length (the pass is causal), the head a block of the vocabulary at a
   time for the rows that gave a token; log-softmax compared there;
4. `--perturb`: the reference computed WRONG one way at a time (`WRONG`),
   and `eight_bit`, the reference one precision below the configuration's
   bf16 (every matrix through a per-channel int8 round trip AND the
   residual stream rounded to float8 e4m3 after every write: what the
   engine keeps in 16 bits kept in 8), which must break at least one limit.
   In the float32 run every wrong reference must break `--sharp-limit`; in
   the bf16 run at full depth their readings are printed and held to
   nothing: with seeded weights every writer adds a tenth of the stream,
   the stream's own bf16 rounding over 64 writes (mean 0.003-0.009) is as
   large as what they move (0.0007-0.005 in float32 at 16 layers; at bf16
   they read 0.005-0.013 beside the engine's 0.003-0.009), so they are the
   float32 run's to see, as a bfloat16 state is in the other scan families'
   checks.

`--dtype float32 --layers 16` with `JAX_DEFAULT_MATMUL_PRECISION=highest` in
the environment is the sharp version (both sides float32: what is left is
the order of summation). 32 layers of float32 are 15.4 GB and do not fit:
16 layers, which the model's own rule lays out as 5 scans, 4 window layers,
the full layer, 3 GMUs and 3 cross layers (8.7 GB), every width as
published; `--blocks 2000 --seats 8` leave the reference room beside
float32 pools. The check's cut, never the cell's. It holds its reading to
`--sharp-limit`.

The last line of stdout is one JSON object; exit 0 = agrees (and every wrong
reference failed), 1 = does not, 2 = could not run. Leaves by `os._exit`: an
engine's threads are not waited for.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from collections.abc import Mapping

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import loadgen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.server import ROOT, hf_config_of, make_model_dir  # noqa: E402
from reference_check import (boot_engine, compare, emit,  # noqa: E402
                             run_traffic)

CONFIG = "phi-4-mini-flash-reasoning"
REFERENCE = os.path.join(HERE, "references",
                         "phi_4_mini_flash_reasoning_reference.py")
# Prompt lengths, in the order sent: each of the first four crosses the
# window and a chunk boundary (3, 3, 5 and 2 chunks of 512); the last is
# under a chunk and over the window.
PROMPTS = (1100, 1300, 2100, 700, 530, 1560, 900, 1800)
REHEARSAL_PROMPTS = (70, 90, 45, 110, 36, 100, 60, 120)
OUTPUT_TOKENS, REHEARSAL_OUTPUT_TOKENS = 48, 24


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
# the filter's taps stored in bf16; softmax, norms, dt, the differential
# combination, the logits and the whole scan, state included, in float32),
# the reference in float32 at the highest matmul precision. The same engine
# in float32 at the highest precision agrees with the reference to ~1e-5
# (the sharp run), so what these limits bound is bf16's own distance over 32
# layers: the residual stream rounded to 8 bits of mantissa after each of
# its 64 writes. Each was set between two readings on the chip (PERF.md
# section 6, PR 52, has every number): what the engine gave over its seeds,
# and what the reference computed one precision lower gave against the same
# engine outputs (`eight_bit`: int8 weights and a float8 e4m3 stream; int8
# weights ALONE read what the engine reads, 0.0088 | 0.0088, because a writer is a
# tenth of the stream). The lower precision has to break ONE of the limits,
# not each.
TOLERANCES = {
    "mean_abs": {
        "limit": 0.020,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~1e3 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.0034-0.0088 over four seeds (the stream's "
               "rounding is a random walk over 64 writes, so seeds differ "
               "more than in the shallower cells); the reference in 8 bits "
               "0.044-0.079; the limit is the geometric mean of the "
               "engine's largest and the 8-bit reference's least",
    },
    "p99_abs": {
        "limit": 0.047,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie. Engine "
               "0.0116-0.0227; 8 bits 0.098-0.191",
    },
    "max_abs": {
        "limit": 0.058,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a state slot read across "
               "a boundary, a page of another sequence, a window cut a "
               "block early). An extreme value. Engine 0.0167-0.0246; "
               "8 bits 0.136-0.196",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat "
               "(~190 of 192 positions on three seeds, 1 on the fourth, "
               "whose weights repeat one token): argmax agreement where "
               "rounding cannot decide it; a guard against a corrupted row "
               "(engine 1.0 on every seed)",
    },
}
# Wrong references: name -> what is computed wrong.
WRONG = {
    "cross_own_kv": "the cross layers attend to keys and values computed "
                    "from THEIR OWN input (with the full layer's "
                    "projection) instead of the full layer's",
    "lambda_zero": "lambda = 0: a1 alone, no second softmax subtracted",
    "memory_after_gate": "m taken AFTER the memory scan's gate silu(z)",
    "window_496": "the window one block short: 496 tokens",
}


# --- 2: the engine's weights, under the hub's names --------------------------

class Published(Mapping):
    """The engine's param tree as {checkpoint name: [out, in] array}: a
    view, computed when asked (a 32-layer tree in float32 would not fit
    beside the engine); `how` may pass a matrix through a round trip
    (every matrix but the embedding, which is read by rows and blocks, and
    `A_log`, which is no product's operand)."""

    def __init__(self, model, params, how=None):
        self.names = {}
        self.how = how
        for i, (kind, lp) in enumerate(zip(model.kinds, params["layers"])):
            p = f"model.layers.{i}."
            a = p + "attn."
            add = self.names.__setitem__
            for ours, theirs in (("ln1", "input_layernorm"),
                                 ("ln2", "post_attention_layernorm")):
                add(p + theirs + ".weight", (lp[ours + "_w"], False))
                add(p + theirs + ".bias", (lp[ours + "_b"], False))
            add(p + "mlp.fc1.weight", (lp["fc1"], True))
            add(p + "mlp.fc2.weight", (lp["fc2"], True))
            if kind == "scan":
                for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
                    add(a + name + ".weight", (lp[name], True))
                add(a + "dt_proj.bias", (lp["dt_bias"], False))
                add(a + "conv1d.weight", (lp["conv_w"], "taps"))
                add(a + "conv1d.bias", (lp["conv_b"], False))
                add(a + "A_log", (lp["A_log"], True))
                add(a + "D", (lp["D"], False))
            elif kind == "gmu":
                add(a + "in_proj.weight", (lp["in_proj"], True))
                add(a + "out_proj.weight", (lp["out_proj"], True))
            else:
                q = "q" if kind == "cross" else "qkv"
                add(a + "Wqkv.weight", (lp[q], True))
                add(a + "Wqkv.bias", (lp[q + "_b"], False))
                add(a + "out_proj.weight", (lp["o"], True))
                add(a + "out_proj.bias", (lp["o_b"], False))
                add(a + "subln.weight", (lp["subln"], False))
                for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2"):
                    add(a + name, (lp[name], False))
        self.names["model.embed_tokens.weight"] = (params["embed_tokens"],
                                                   "table")
        self.names["model.final_layernorm.weight"] = (params["norm_w"],
                                                      False)
        self.names["model.final_layernorm.bias"] = (params["norm_b"], False)

    def __getitem__(self, name):
        import jax.numpy as jnp
        arr, form = self.names[name]
        if form == "table":         # any dtype, read by rows and blocks
            return arr
        if form == "taps":          # [taps, channels] -> [channels, 1, taps]
            return jnp.asarray(arr, jnp.float32).T[:, None, :]
        if form:
            arr = arr.T
            if self.how is not None and not name.endswith("A_log"):
                return self.how(arr)
        return arr

    def __iter__(self):
        return iter(self.names)

    def __len__(self):
        return len(self.names)


def _float8_stream(x):
    """The residual stream kept in float8 e4m3 (3 bits of mantissa)."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _int8_matrix(w):
    """[out, in]: per-output-channel int8 round trip, as float32."""
    import jax.numpy as jnp
    w = jnp.asarray(w, jnp.float32)
    top = jnp.max(jnp.abs(w), axis=1, keepdims=True)
    scale = jnp.maximum(top, 1e-12) / 127
    return jnp.round(w / scale) * scale


# --- 3: the reference, teacher-forced ----------------------------------------

class _Rows:
    """`lsm[pos]` for the positions that gave a token."""

    def __init__(self, first: int, rows):
        self.first, self.rows = first, rows

    def __getitem__(self, pos):
        return self.rows[pos - self.first]


def perturb(name, cfg, ref):
    """(cfg, undo) with the reference made wrong in the way `name`."""
    import jax
    if name == "window_496":
        return dict(cfg, sliding_window=cfg["sliding_window"] - 16), None
    attr, wrong = {
        "eight_bit": ("stream", _float8_stream),
        "cross_own_kv": ("cross_keys_and_values",
                         lambda c, w, full, u, shared:
                         ref.keys_and_values(c, w, full, u)),
        "lambda_zero": ("lambda_full", lambda w, prefix, layer: 0.0),
        "memory_after_gate": ("memory_of",
                              lambda y, z: y * jax.nn.silu(z)),
    }[name]
    right = getattr(ref, attr)
    setattr(ref, attr, wrong)
    return cfg, lambda: setattr(ref, attr, right)


def reference_logprobs(ref, cfg, weights, seqs, padded: int, how=None):
    """{index: `_Rows` of log-softmax} for `seqs` {index: (prompt,
    tokens)}; `how`: a wrong reference's name, or None."""
    import jax
    import numpy as np
    undo = None
    if how is not None:
        cfg, undo = perturb(how, cfg, ref)
    out = {}
    try:
        for i, (prompt, tokens) in seqs.items():
            ids = list(prompt) + list(tokens)
            first = len(prompt) - 1
            rows = list(range(first, len(ids) - 1))
            # One padded length (the pass is causal: what comes after a
            # position does not reach it): the reference's operations are
            # compiled for one shape.
            ids = ids + [0] * (padded - len(ids))
            logits = ref.forward(cfg, weights, ids, rows=rows)
            out[i] = _Rows(first, np.asarray(jax.nn.log_softmax(logits, -1)))
    finally:
        if undo:
            undo()
    return out


def verdict(stats: dict) -> list:
    """Names of the tolerances `stats` breaks."""
    broken = [k for k in ("mean_abs", "p99_abs", "max_abs")
              if not stats[k] <= TOLERANCES[k]["limit"]]     # NaN breaks
    if not stats["top1_agree_min"] >= TOLERANCES["top1_agree_min"]["limit"]:
        broken.append("top1_agree_min")
    return broken


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--check", type=int, default=4,
                    help="requests sent and compared (of PROMPTS, in order)")
    ap.add_argument("--perturb", default="all",
                    help="comma-separated wrong references, 'all' or 'none'")
    ap.add_argument("--dtype", default=None,
                    help="the engine's dtype (default: the configuration's)")
    ap.add_argument("--layers", type=int, default=None,
                    help="num_hidden_layers of the check (the sharp run: 16)")
    ap.add_argument("--blocks", type=int, default=None,
                    help="--num-device-blocks-override of the check")
    ap.add_argument("--seats", type=int, default=None,
                    help="--max-num-seqs of the check")
    ap.add_argument("--sharp-limit", type=float, default=2e-4,
                    help="float32 run: the largest |d logprob| allowed")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    sys.path.insert(0, ROOT)

    config = Manifest().config(CONFIG)
    if args.layers:
        config = dict(config, num_hidden_layers=args.layers,
                      name=f"{CONFIG}-l{args.layers}")
    serve = dict(config["serve"])
    if rehearsal:
        serve.update(config["rehearsal"]["serve"])
    if args.dtype:
        serve["dtype"] = args.dtype
    flags = [str(x) for x in serve.get("flags", [])]
    for flag, value in (("--num-device-blocks-override", args.blocks),
                        ("--max-num-seqs", args.seats)):
        if value is not None:
            at = flags.index(flag)
            flags[at + 1] = str(value)
    serve["flags"] = flags
    sharp = serve["dtype"] == "float32"
    hf = hf_config_of(config, rehearsal)
    model_dir = make_model_dir(config, rehearsal)

    import jax
    import numpy as np
    t0 = time.perf_counter()
    engine = boot_engine(model_dir, serve, args.seed, seats=64)
    emit({"event": "booted", "seconds": time.perf_counter() - t0,
          "platform": jax.default_backend(), "dtype": serve["dtype"],
          "layers": hf["num_hidden_layers"], "flags": flags,
          "matmul_precision": os.environ.get(
              "JAX_DEFAULT_MATMUL_PRECISION")})
    lens = (REHEARSAL_PROMPTS if rehearsal else PROMPTS)[:args.check]
    out_tokens = REHEARSAL_OUTPUT_TOKENS if rehearsal else OUTPUT_TOKENS
    plan = [loadgen.Planned(i, "check", n, out_tokens)
            for i, n in enumerate(lens)]
    loadgen.fill_prompts(plan, args.seed, hf["vocab_size"])
    done, steps, seconds = run_traffic(engine, plan, len(plan))
    from intellillm_tpu.obs import get_compile_tracker
    compiles = get_compile_tracker().snapshot()
    emit({"event": "served", "requests": len(done), "steps": steps,
          "seconds": seconds, "compiles": compiles["compiles"],
          "kernel_dispatch": compiles["kernel_dispatch"]})
    refs = {k: v for k, v in compiles["kernel_dispatch"].items()
            if k.startswith("reference")}
    problems = []
    if refs and not rehearsal:
        problems.append(f"kernel seams resolved to a reference: {refs}")
    if not (compiles["compiles"].get("mixed")
            and compiles["compiles"].get("decode_fused")):
        problems.append("the mixed and the K-step decode programs did not "
                        "both run")

    runner = engine.worker.model_runner
    model, params = runner.model, runner.params
    ref = load_reference()
    seqs = {i: (p, t) for i, (p, t, _) in done.items()}
    padded = max(len(p) + len(t) for p, t in seqs.values())
    t0 = time.perf_counter()
    right = compare(done, reference_logprobs(
        ref, hf, Published(model, params), seqs, padded))
    emit({"event": "reference", "seconds": time.perf_counter() - t0,
          **right})
    if sharp:
        broken = ["max_abs"] if not right["max_abs"] <= args.sharp_limit \
            else []
    else:
        broken = verdict(right)
    if broken:
        problems.append(f"the engine breaks {broken}")

    wrong = {}
    names = ([] if args.perturb == "none" else
             list(WRONG) if args.perturb == "all"
             else args.perturb.split(","))
    for name in names:
        st = compare(done, reference_logprobs(
            ref, hf, Published(model, params), seqs, padded, how=name))
        wrong[name] = {k: st[k] for k in ("mean_abs", "p99_abs", "max_abs",
                                          "top1_agree_min")}
        wrong[name]["breaks"] = (
            ["max_abs"] if sharp and st["max_abs"] > args.sharp_limit
            else [] if sharp else verdict(st))
        emit({"event": "wrong_reference", "name": name, **wrong[name]})
        if sharp and not wrong[name]["breaks"]:
            problems.append(f"the wrong reference {name!r} breaks no limit")
    if not sharp and args.perturb != "none":
        st = compare(done, reference_logprobs(
            ref, hf, Published(model, params, how=_int8_matrix), seqs,
            padded, how="eight_bit"))
        wrong["eight_bit"] = {
            **{k: st[k] for k in ("mean_abs", "p99_abs", "max_abs",
                                  "top1_agree_min")},
            "breaks": verdict(st)}
        emit({"event": "lower_precision", **wrong["eight_bit"]})
        if not wrong["eight_bit"]["breaks"]:
            problems.append("the reference in 8 bits breaks no limit")

    result = {"agrees": not problems, "problems": problems,
              "config": config["name"], "seed": args.seed,
              "dtype": serve["dtype"], "layers": hf["num_hidden_layers"],
              "sharp": sharp, "prompts": list(lens),
              "limits": ({"max_abs": args.sharp_limit} if sharp else
                         {k: v["limit"] for k, v in TOLERANCES.items()}),
              "engine": right, "wrong": wrong, "rehearsal": rehearsal}
    emit(result)
    return 0 if not problems else 1


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 2
    except BaseException:
        import traceback
        traceback.print_exc()
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
