"""A configuration's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check.py --config ax-k1-ep16-l7 --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check.py ...`;
`--rehearse-on-cpu` runs the same control flow at the configuration's tiny
preset on the CPU backend.) `run.py` has no place where a reference could
decide `correct` (PERF.md section 7: a `benchmark` issue adds the hook); until
it has, this command is how a configuration is held to its reference at the
published widths and the cell's sizes.

What it does, in one process:

1. Boots the engine in-process with the configuration's `serve` settings
   (dtype, kv-cache dtype, max_model_len, flags), `--load-format dummy` and
   `--seed`, and `--max-num-seqs 128`: the closed loop's 128 seats.
2. Sends the cell's traffic shape: two blocks of 128 greedy requests with
   `decode-closed`'s (prompt, output) lengths and seeded unique prompts, all
   at once. The first block is what is checked; the second keeps the batch
   128 rows wide as requests end, as the closed loop does, so the `mixed`
   programs and the 128-row K=8 decode programs produce the tokens. Each
   request keeps its tokens and top-5 logprobs: asking for logprobs selects
   the programs' `logprob_k` 8 variants (the sampler also returns the top
   8), not the `logprob_k` 1 executables the cell times; the layers, the
   kernels and the cache are the same code in both.
3. Takes the weights FROM THE ENGINE (so both sides hold the same bf16
   values), stops the engine and frees the device.
4. Computes the reference (`references/<config>_reference.py`: plain
   jax.numpy, float32, matmul precision "highest", expanded attention, no
   cache) teacher-forced on the engine's own tokens, layer by layer so that
   one layer's float32 weights are on the device at a time, for `--check`
   requests spanning the lengths, the longest among them.
5. Compares log-softmax values at every position that produced a token (the
   prefill's last position and every decoded one) for the chosen token and
   the top-5, and prints each tolerance with its reason.
6. `--perturb`: the same comparison with the reference computed WRONG in one
   way at a time (a lower precision or a dropped term); each must FAIL at
   least one tolerance, or the tolerances are too loose to mean anything.

The last line of stdout is one JSON object; exit 0 = agrees (and every
perturbation failed), 1 = does not, 2 = could not run.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import loadgen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.server import ROOT, hf_config_of, make_model_dir  # noqa: E402

# --- tolerances (nats of log-softmax), each with its reason ----------------
#
# Both sides hold the same bf16 weights; the engine computes in bf16 (the
# MXU multiplies bf16 values and accumulates in float32; activations and the
# latents are stored in bf16; softmax, norms and the router in float32), the
# reference in float32 at the highest matmul precision. The same engine in
# float32 at the highest precision agrees with the reference to 1e-5 (PERF.md
# section 6, PR 26), so what these limits bound is bf16's own distance.
#
# Each limit was set from two readings on the chip (my chip run p26g, PR 26,
# seed 3000002681; 23,620 values of 16 requests): what the engine gave, and
# what a reference computed one precision lower gave against the same engine
# outputs (int8 weights: per-channel round trip of every projection; fp8-e5m2
# latents: what an fp8 pool would hold). A lower precision has to break ONE
# of the limits, not each; a dropped term breaks three.
TOLERANCES = {
    "mean_abs": {
        "limit": 0.011,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~2e4 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.0090; int8 weights 0.0131, fp8 latents "
               "0.0171, a dropped term 0.049-0.129",
    },
    "p99_abs": {
        "limit": 0.09,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie. Engine 0.0725; "
               "fp8 latents 0.1008, a dropped term 0.19-0.42 (int8 weights "
               "0.082 pass this one and fail the mean)",
    },
    "max_abs": {
        "limit": 0.75,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a wrong expert). The "
               "precision hardly moves it (engine 0.26, int8 0.21, fp8 "
               "0.26: one expert swapped on a router near-tie), so it "
               "stands at about three times the engine's reading",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat "
               "(ten times the mean error): argmax agreement where "
               "rounding cannot decide it. Engine 0.9997; a dropped term "
               "0.80-0.98",
    },
}

PERTURBATIONS = ("fp8_latents", "int8_weights", "drop_rope_score",
                 "drop_mscale", "drop_routed_scale", "drop_shared_expert")


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_reference(config_name: str):
    path = os.path.join(HERE, "references",
                        config_name.replace("-", "_") + "_reference.py")
    spec = importlib.util.spec_from_file_location("chip_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- 1-2: the engine and the traffic ----------------------------------------

def boot_engine(model_dir: str, serve: dict, seed: int, seats: int):
    from intellillm_tpu.engine.arg_utils import EngineArgs
    from intellillm_tpu.engine.llm_engine import LLMEngine
    ap = argparse.ArgumentParser()
    EngineArgs.add_cli_args(ap)
    argv = ["--model", model_dir, "--load-format", "dummy",
            "--seed", str(seed % (2**31 - 1))]
    for key in ("dtype", "quantization", "kv_cache_dtype", "max_model_len"):
        if serve.get(key) is not None:
            argv += ["--" + key.replace("_", "-"), str(serve[key])]
    flags = [str(x) for x in serve.get("flags", [])]
    if "--max-num-seqs" not in flags:   # the rehearsal preset names its own
        argv += ["--max-num-seqs", str(seats)]
    argv += flags
    args = EngineArgs.from_cli_args(ap.parse_args(argv))
    return LLMEngine.from_engine_args(args)


def run_traffic(engine, plan, checked: int):
    """All requests at once; steps until the first `checked` have ended.
    Returns {index: (prompt, tokens, [top-k logprob dicts])}."""
    from intellillm_tpu.sampling_params import SamplingParams
    for req in plan:
        engine.add_request(
            str(req.index), None,
            SamplingParams(temperature=0.0, max_tokens=req.output_len,
                           ignore_eos=True, logprobs=5),
            prompt_token_ids=list(req.prompt))
    done, steps, t0 = {}, 0, time.perf_counter()
    while len(done) < checked and engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished and int(out.request_id) < checked:
                c = out.outputs[0]
                done[int(out.request_id)] = (
                    list(out.prompt_token_ids), list(c.token_ids),
                    [{int(t): float(getattr(v, "logprob", v))
                      for t, v in lp.items()} for lp in c.logprobs])
        steps += 1
    engine.abort_request([str(r.index) for r in plan
                          if r.index not in done])
    return done, steps, time.perf_counter() - t0


# --- 3: the engine's weights, under the published names ----------------------

def published_layer(model, lp: dict, i: int) -> dict:
    """One layer of the engine's param tree -> {published name: float32
    array [out, in]}. No arithmetic: transposes, and `W_kvb,h = [W_UK,h |
    W_UV,h]` put back together."""
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    p = f"model.layers.{i}."
    a = p + "self_attn."
    w = {p + "input_layernorm.weight": f32(lp["input_norm"]),
         p + "post_attention_layernorm.weight": f32(lp["post_attn_norm"]),
         a + "q_a_proj.weight": f32(lp["q_a"]).T,
         a + "q_a_layernorm.weight": f32(lp["q_a_norm"]),
         a + "q_b_proj.weight": f32(lp["q_b"]).T,
         a + "kv_a_proj_with_mqa.weight": f32(lp["kv_a"]).T,
         a + "kv_a_layernorm.weight": f32(lp["kv_a_norm"]),
         a + "o_proj.weight": f32(lp["o"]).T}
    kvb = np.concatenate([f32(lp["w_uk"]), f32(lp["w_uv"]).swapaxes(1, 2)],
                         axis=1)                       # [H, d_n + d_v, r]
    w[a + "kv_b_proj.weight"] = kvb.reshape(-1, kvb.shape[-1])
    m = p + "mlp."
    if "w1" in lp:
        w[m + "gate.weight"] = f32(lp["gate_router"]).T
        first = model.routing.held_start
        for j in range(model.n_held):
            e = f"{m}experts.{first + j}."
            w[e + "gate_proj.weight"] = f32(lp["w1"][j])
            w[e + "up_proj.weight"] = f32(lp["w3"][j])
            w[e + "down_proj.weight"] = f32(lp["w2"][j]).T
        for k in ("gate", "up", "down"):
            w[f"{m}shared_experts.{k}_proj.weight"] = f32(
                lp["shared_" + k]).T
    else:
        for k in ("gate", "up", "down"):
            w[f"{m}{k}_proj.weight"] = f32(lp[k]).T
    return w


def reference_config(hf: dict) -> dict:
    """The reference wants the published config: the router's whole width
    under `n_routed_experts`, and the share as arguments."""
    cfg = dict(hf)
    cfg["n_routed_experts"] = hf["n_routed_experts"] * hf.get("ep_size", 1)
    return cfg


# --- 6: a wrong reference, one way at a time ---------------------------------

def _int8_roundtrip(w):
    import numpy as np
    if w.ndim != 2:
        return w
    s = np.abs(w).max(axis=1, keepdims=True) / 127.0
    s = np.where(s == 0, 1.0, s)
    return (np.round(w / s).clip(-127, 127) * s).astype(np.float32)


def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(cfg, weights, undo) of the reference computed wrong in one way."""
    cfg = json.loads(json.dumps(cfg))
    undo = lambda: None                                     # noqa: E731
    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v) if "proj" in k or "lm_head" in k
                       else v) for k, v in layer_w.items()}
    elif name == "drop_rope_score":
        # k_rope = 0: the rope part of every score is gone.
        r = cfg["kv_lora_rank"]
        layer_w = dict(layer_w)
        for k, v in layer_w.items():
            if k.endswith("kv_a_proj_with_mqa.weight"):
                v = v.copy()
                v[r:] = 0.0
                layer_w[k] = v
    elif name == "drop_mscale":
        cfg["rope_scaling"]["mscale_all_dim"] = 0
        cfg["rope_scaling"]["mscale"] = 0
    elif name == "drop_routed_scale":
        cfg["routed_scaling_factor"] = 1.0
    elif name == "drop_shared_expert":
        cfg["n_shared_experts"] = 0
    elif name == "fp8_latents":
        # What an fp8-e5m2 pool would hold: c_kv after its norm and k_rope
        # after its rotation, rounded to 2 bits of mantissa. The reference
        # has no cache, so its two producers are wrapped for the pass.
        import jax.numpy as jnp
        norm, rope = ref.rms_norm, ref.rope
        r = cfg["kv_lora_rank"]

        def fp8(x):
            return x.astype(jnp.float8_e5m2).astype(jnp.float32)

        ref.rms_norm = lambda x, w, eps: (
            fp8(norm(x, w, eps)) if x.shape[-1] == r else norm(x, w, eps))
        ref.rope = lambda x, pos, c: (
            fp8(rope(x, pos, c)) if x.ndim == 2 else rope(x, pos, c))

        def undo():
            ref.rms_norm, ref.rope = norm, rope
    else:
        raise KeyError(name)
    return cfg, layer_w, undo


# --- 4-5: the reference pass and the comparison ------------------------------

_PROGRAMS: dict = {}      # (layer kind, length, config, fp8?) -> jitted layer


def reference_logprobs(ref, cfg, model, params, seqs, ep, how=None):
    """{index: float32 log-softmax [T, vocab]} of the reference,
    teacher-forced on `seqs` {index: token list}, layer by layer: one
    layer's float32 weights are on the device at a time.

    The reference's functions are called as they are, under `jax.jit` so
    that a layer is one compiled program a sequence length and not a
    thousand eager operations: every layer of a kind (dense, MoE) is
    handed over under that kind's first layer's names, so the kinds share
    their programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    top = {"model.embed_tokens.weight": f32(params["embed_tokens"])}
    xs = {i: ref.embed(top, jnp.asarray(s)) for i, s in seqs.items()}
    del top
    for li, lp in enumerate(params["layers"]):
        # the first layer of this layer's kind
        ci = li if "w1" not in lp else next(
            j for j, q in enumerate(params["layers"]) if "w1" in q)
        layer_w = published_layer(model, lp, ci)
        lcfg, undo = cfg, (lambda: None)
        if how:
            lcfg, layer_w, undo = perturb(how, cfg, layer_w, ref)
        layer_w = {k: jnp.asarray(v) for k, v in layer_w.items()}
        try:
            for i, s in seqs.items():
                # (a wrong reference that only changes weights shares the
                # right one's programs)
                key = (ci, len(s), json.dumps(lcfg, sort_keys=True),
                       how == "fp8_latents")
                if key not in _PROGRAMS:
                    _PROGRAMS[key] = jax.jit(
                        lambda w, x, ci=ci, n=len(s), lcfg=lcfg: ref.layer(
                            lcfg, w, ci, x, np.arange(n), *ep))
                xs[i] = jax.block_until_ready(_PROGRAMS[key](layer_w, xs[i]))
        finally:
            undo()
        del layer_w
        gc.collect()
    head_w = {"model.norm.weight": f32(params["norm"]),
              "lm_head.weight": f32(params["lm_head"]).T}
    if how == "int8_weights":
        head_w["lm_head.weight"] = _int8_roundtrip(head_w["lm_head.weight"])
    head_w = {k: jnp.asarray(v) for k, v in head_w.items()}
    out = {}
    for i in seqs:
        out[i] = np.asarray(jax.nn.log_softmax(
            ref.head(cfg, head_w, xs[i]), axis=-1))
    return out


def compare(done: dict, ref_lsm: dict) -> dict:
    import numpy as np
    diffs, agree, decided = [], 0, 0
    worst = None
    for i, (prompt, tokens, lps) in done.items():
        if i not in ref_lsm:
            continue
        lsm = ref_lsm[i]
        for j, (tok, lp) in enumerate(zip(tokens, lps)):
            pos = len(prompt) - 1 + j          # the position that gave tok
            row = lsm[pos]
            for t, v in lp.items():
                d = abs(float(row[t]) - v)
                diffs.append(d)
                if worst is None or d > worst[0]:
                    worst = (d, i, pos, t)
            order = np.argsort(row)[-2:]
            if row[order[1]] - row[order[0]] > 0.1:
                decided += 1
                agree += int(order[1] == tok)
    a = np.asarray(diffs)
    return {"values": int(a.size), "mean_abs": float(a.mean()),
            "p99_abs": float(np.quantile(a, 0.99)),
            "max_abs": float(a.max()),
            "worst_at": {"request": worst[1], "position": worst[2],
                         "token": worst[3]},
            "top1_agree_min": agree / decided if decided else 1.0,
            "top1_positions": decided}


def verdict(stats: dict) -> list:
    """Names of the tolerances `stats` breaks."""
    broken = [k for k in ("mean_abs", "p99_abs", "max_abs")
              if not stats[k] <= TOLERANCES[k]["limit"]]     # NaN breaks
    if not stats["top1_agree_min"] >= TOLERANCES["top1_agree_min"]["limit"]:
        broken.append("top1_agree_min")
    return broken


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ax-k1-ep16-l7")
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
    config = manifest.config(args.config)
    traffic = manifest.traffic(args.traffic)
    if rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    serve = dict(config["serve"])
    if rehearsal:
        serve.update(config["rehearsal"]["serve"])
    if args.kv_cache_dtype:
        serve["kv_cache_dtype"] = args.kv_cache_dtype
    hf = hf_config_of(config, rehearsal)
    model_dir = make_model_dir(config, rehearsal)
    seats = 8 if rehearsal else args.seats
    checked_block = seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check: no accelerator (platform {platform}); a "
              f"CPU rehearsal needs --rehearse-on-cpu", file=sys.stderr)
        return 2

    # The cell's lengths: one block is the multiset of (prompt, output)
    # pairs at the quantiles of the traffic file's distributions.
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
    emit({"event": "engine", "platform": platform,
          "device_kind": jax.devices()[0].device_kind,
          "boot_s": boot_s, "run_s": run_s, "steps": steps,
          "requests_done": len(done),
          "tokens": sum(len(t) for _, t, _ in done.values()),
          "kernel_selection": kernel_selection(),
          "kernel_dispatch": compiles.get("kernel_dispatch"),
          "compiles": compiles.get("compiles"),
          "num_decode_steps": engine.scheduler_config.num_decode_steps,
          "memory_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
              "peak_bytes_in_use")})
    if not rehearsal:
        refs = {k: v for k, v in (compiles.get("kernel_dispatch") or
                                  {}).items() if k.startswith("reference")}
        if refs:
            print(f"reference_check: kernel seams resolved to a reference: "
                  f"{refs}", file=sys.stderr)
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

    # The requests checked: spanning the lengths, the longest among them.
    by_len = sorted(done, key=lambda i: len(done[i][0]) + len(done[i][1]))
    n = min(args.check, len(by_len))
    picks = sorted({by_len[round(k * (len(by_len) - 1) / max(n - 1, 1))]
                    for k in range(n)})
    seqs = {i: done[i][0] + done[i][1] for i in picks}
    emit({"event": "checked", "requests": picks,
          "lengths": [len(seqs[i]) for i in picks],
          "prompt_lens": [len(done[i][0]) for i in picks]})

    ref = load_reference(args.config)
    cfg = reference_config(hf)
    t0 = time.perf_counter()
    stats = compare(done, reference_logprobs(ref, cfg, model, params, seqs,
                                             ep))
    broken = verdict(stats)
    emit({"event": "reference", "seconds": time.perf_counter() - t0,
          **stats, "broken": broken})
    print("tolerances (nats of log-softmax):")
    for k, t in TOLERANCES.items():
        print(f"  {k} {'>=' if k.endswith('_min') else '<='} {t['limit']}: "
              f"measured {stats[k]:.6g} -- {t['why']}")

    names = ([] if args.perturb == "none" else list(PERTURBATIONS)
             if args.perturb == "all" else args.perturb.split(","))
    # A wrong reference needs fewer requests to show: the four longest.
    few = {i: seqs[i] for i in sorted(seqs, key=lambda i: len(seqs[i]))[-4:]}
    undetected, perturbed = [], {}
    if names:
        # ... and the right reference on those same requests, for a like-
        # for-like reading beside each wrong one.
        same = compare({i: done[i] for i in few},
                       reference_logprobs(ref, cfg, model, params, few, ep))
        emit({"event": "reference_on_the_perturbed_requests",
              **{k: same[k] for k in ("values", "mean_abs", "p99_abs",
                                      "max_abs", "top1_agree_min")}})
    for name in names:
        p = compare(done, reference_logprobs(ref, cfg, model, params, few,
                                             ep, how=name))
        p_broken = verdict(p)
        perturbed[name] = {k: p[k] for k in ("mean_abs", "p99_abs",
                                             "max_abs", "top1_agree_min")}
        perturbed[name]["broken"] = p_broken
        emit({"event": "perturbed", "how": name, **perturbed[name]})
        if not p_broken:
            undetected.append(name)
    agrees = not broken
    if args.kv_cache_dtype:
        # The engine itself ran one precision lower: it has to disagree.
        agrees = bool(broken)
    ok = agrees and not undetected
    emit({"agrees": not broken, "ok": ok, "config": args.config,
          "seed": args.seed, "engine_kv_cache_dtype": serve["kv_cache_dtype"],
          "stats": {k: stats[k] for k in ("values", "mean_abs", "p99_abs",
                                          "max_abs", "top1_agree_min",
                                          "top1_positions")},
          "broken": broken, "perturbations": perturbed,
          "perturbations_undetected": undetected,
          "rehearsal": rehearsal, "platform": platform})
    if rehearsal:
        # The tiny float32 preset proves the control flow, not the limits.
        return 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
