"""SmallThinker's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_smallthinker.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_smallthinker.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) The procedure is `reference_check_lfm2.py`'s,
and what is generic there is imported from `reference_check.py` (the
engine's boot, the comparison); this file brings the weight mapping, the
wrong references and the limits of `smallthinker-21ba3b-l8`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`, 16 seats; one block of 16 greedy
   `ragdoc-closed` requests (prompts of 5.1-6.2k tokens: every one crosses
   the 4,096-token window in its own prefill, in 512-row chunks of the
   `mixed` program), outputs cut to `--max-output` tokens (256: sixteen
   block boundaries of the window group) through the K=8 decode and its
   continuation, the pipelined loop the server runs;
2. the weights FROM THE ENGINE under the family's published names;
3. the reference (`references/smallthinker_21ba3b_l8_reference.py`: full
   forward over the whole sequence, an explicit mask a layer in blocks of
   512 queries) teacher-forced on the engine's tokens, layer by layer,
   log-softmax compared at every position that produced a token (all of
   them beyond position 4,096);
4. `--perturb`: the reference computed WRONG one way at a time (every
   layer full: the window dropped; rotary on the full layers too; SiLU for
   the ReLU gate; int8 weights; fp8 K/V); each must break a limit.

`--dtype float32 --layers 4 --seats 4 --blocks 2048` with
`JAX_DEFAULT_MATMUL_PRECISION=highest` in the environment is the sharp
version (both sides float32: what is left is the order of summation); it
prints its reading and holds it to `--sharp-limit`.

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
                             emit, load_reference)

CONFIG = "smallthinker-21ba3b-l8"

# --- limits (nats of log-softmax), each with its reason ----------------------
#
# Both sides hold the same bf16 weights; the engine computes in bf16 (bf16
# products accumulated in float32; activations and K/V stored in bf16;
# softmax, norms and the router in float32), the reference in float32 at the
# highest matmul precision. The same engine in float32 at the highest
# precision agrees with the reference to ~1e-5 (PERF.md section 6, PR 33),
# so what these limits bound is bf16's own distance. Each was set between
# two readings on the chip (PERF.md section 6, PR 33, has every number):
# what the engine gave over its seeds, and what a reference computed one
# precision lower (int8 weights: per-channel round trip of every matrix;
# fp8-e5m2 K/V: what an fp8 pool would hold) or with a mechanism wrong gave
# against the same engine outputs. A lower precision has to break ONE of
# the limits, not each; a wrong mechanism breaks several. (My chip runs
# p33b / p33c1, PR 33: four requests of 4.6-7.0k-token prompts x 256 tokens
# out = 5,120 values a run; the wrong references on the longest request.)
TOLERANCES = {
    "mean_abs": {
        "limit": 0.015,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over ~5e3 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.01162-0.01173 over 3 seeds (0.01243 on the "
               "longest request alone); int8 weights 0.0200, fp8 K/V "
               "0.0428, SiLU for ReLU 0.0551, the window dropped 0.150, "
               "rotary on the full layers 0.194",
    },
    "p99_abs": {
        "limit": 0.09,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie and top-6 choices "
               "near one. Engine 0.0619-0.0638 (0.0678 on the longest "
               "request); fp8 K/V 0.138, a wrong mechanism 0.18-0.60 (int8 "
               "weights 0.0805 pass this one and fail the mean)",
    },
    "max_abs": {
        "limit": 0.3,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a stale block of the window group read "
               "inside the window, a wrong expert). The precision hardly "
               "moves it (engine 0.126-0.155, int8 0.161, fp8 0.206, SiLU "
               "0.230: they break other limits); the window dropped reads "
               "0.618, rotary on the full layers 0.810",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat: "
               "argmax agreement where rounding cannot decide it. Engine "
               "1.0 on every seed (636-639 positions); int8 0.994, fp8 "
               "0.988, SiLU 0.981, the window dropped 0.721, rotary 0.636",
    },
}

PERTURBATIONS = ("window_dropped", "rotary_on_full_layers", "silu_for_relu",
                 "int8_weights", "fp8_kv")


# --- the engine's weights, under the published names -------------------------

def published_layer(lp: dict, i: int) -> dict:
    """One layer of the engine's param tree -> {published name: float32
    array as a checkpoint holds it}. No arithmetic: transposes only."""
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    p = f"model.layers.{i}."
    a, m = p + "self_attn.", p + "block_sparse_moe."
    w = {p + "input_layernorm.weight": f32(lp["input_norm"]),
         p + "post_attention_layernorm.weight": f32(lp["post_attn_norm"]),
         m + "primary_router.weight": f32(lp["gate_router"]).T}
    for ours in "qkvo":
        w[f"{a}{ours}_proj.weight"] = f32(lp[ours]).T
    for j in range(lp["w1"].shape[0]):
        e = f"{m}experts.{j}."
        w[e + "gate.weight"] = f32(lp["w1"][j])
        w[e + "up.weight"] = f32(lp["w3"][j])
        w[e + "down.weight"] = f32(lp["w2"][j]).T
    return w


def reference_config(hf: dict) -> dict:
    """The published keys the reference reads."""
    return {k: hf[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "rms_norm_eps", "rope_theta",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "sliding_window_size", "num_hidden_layers")} | {
            "rope_layout": list(hf["rope_layout"]),
            "sliding_window_layout": list(hf["sliding_window_layout"])}


# --- a wrong reference, one way at a time ------------------------------------

def fp8_kv_attention(ref):
    """The reference's attention re-stated with K (after RoPE) and V
    rounded to fp8-e5m2: what an fp8 pool would hold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def fp8(x):
        return x.astype(jnp.float8_e5m2).astype(jnp.float32)

    def attention(cfg, w, prefix, i, x, positions):
        t = x.shape[0]
        h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        q = ref._linear(x, w[prefix + "q_proj.weight"]).reshape(t, h, d)
        k = ref._linear(x, w[prefix + "k_proj.weight"]).reshape(t, hkv, d)
        v = ref._linear(x, w[prefix + "v_proj.weight"]).reshape(t, hkv, d)
        if cfg["rope_layout"][i]:
            q, k = ref.rope(q, positions, cfg), ref.rope(k, positions, cfg)
        k = jnp.repeat(fp8(k), h // hkv, axis=1)
        v = jnp.repeat(fp8(v), h // hkv, axis=1)
        outs = []
        for s in range(0, t, ref.QUERY_BLOCK):
            rows = np.arange(s, min(s + ref.QUERY_BLOCK, t))
            scores = jnp.einsum("thd,shd->hts", q[rows], k) * d ** -0.5
            scores = jnp.where(ref.mask(cfg, i, rows, t)[None], scores,
                               -jnp.inf)
            outs.append(jnp.einsum("hts,shd->thd",
                                   jax.nn.softmax(scores, axis=-1), v))
        out = jnp.concatenate(outs, axis=0)
        return ref._linear(out.reshape(t, h * d),
                           w[prefix + "o_proj.weight"])

    return attention


def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(cfg, weights, layer keyword arguments, undo) of the reference
    computed wrong in one way."""
    import jax
    cfg = json.loads(json.dumps(cfg))
    kwargs: dict = {}
    undo = lambda: None                                     # noqa: E731
    n = cfg["num_hidden_layers"]
    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v)
                       if v.ndim == 2 and not k.endswith("norm.weight")
                       else v) for k, v in layer_w.items()}
    elif name == "window_dropped":
        cfg["sliding_window_layout"] = [0] * n
    elif name == "rotary_on_full_layers":
        cfg["rope_layout"] = [1] * n
    elif name == "silu_for_relu":
        kwargs = {"gate_act": jax.nn.silu}
    elif name == "fp8_kv":
        right = ref.attention
        ref.attention = fp8_kv_attention(ref)

        def undo():
            ref.attention = right
    else:
        raise KeyError(name)
    return cfg, layer_w, kwargs, undo


# --- the reference pass ------------------------------------------------------

def reference_logprobs(ref, cfg, params, seqs, first, how=None):
    """{index: float32 log-softmax [T - first[index], vocab]} of the
    reference, teacher-forced on `seqs` {index: token list}, layer by
    layer: one layer's float32 weights are on the device at a time; the
    head is computed from position `first[index]` on (the positions that
    produced a token: the logits of a 7k-token sequence over the whole
    vocabulary would be 4 GB). Eager, an operation at a time: the
    sequences differ in length and a jitted layer would compile for each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    top = {"model.embed_tokens.weight": f32(params["embed_tokens"])}
    with jax.default_matmul_precision("highest"):
        xs = {i: ref.embed(top, s) for i, s in seqs.items()}
        del top
        for li, lp in enumerate(params["layers"]):
            layer_w = published_layer(lp, li)
            lcfg, kwargs, undo = cfg, {}, (lambda: None)
            if how:
                lcfg, layer_w, kwargs, undo = perturb(how, cfg, layer_w, ref)
            layer_w = {k: jnp.asarray(v) for k, v in layer_w.items()}
            try:
                for i, s in seqs.items():
                    xs[i] = jax.block_until_ready(ref.layer(
                        lcfg, layer_w, li, xs[i], np.arange(len(s)),
                        **kwargs))
            finally:
                undo()
            del layer_w
            gc.collect()
        head = f32(params["lm_head"]).T
        if how == "int8_weights":
            head = _int8_roundtrip(head)
        head_w = {"model.norm.weight": jnp.asarray(f32(params["norm"])),
                  "lm_head.weight": jnp.asarray(head)}
        out = {}
        for i in seqs:
            out[i] = np.asarray(jax.nn.log_softmax(
                ref.head(cfg, head_w, xs[i][first[i]:]), axis=-1))
    return out


def from_first(done: dict, picks) -> dict:
    """`done` for `compare` against log-softmax rows that start at the
    position of each request's first token: the prompt stands for one
    position."""
    return {i: ([0], done[i][1], done[i][2]) for i in picks}


def run_traffic(engine, plan):
    """All requests at once through the loop the server runs (pipelined:
    `mixed`, one `decode_fused`, then `decode_cont`) until all have ended.
    Returns {index: (prompt, tokens, [top-k logprob dicts])}."""
    from intellillm_tpu.sampling_params import SamplingParams
    for req in plan:
        engine.add_request(
            str(req.index), None,
            SamplingParams(temperature=0.0, max_tokens=req.output_len,
                           ignore_eos=True, logprobs=5),
            prompt_token_ids=list(req.prompt))
    done, steps, t0 = {}, 0, time.perf_counter()
    pipelined = engine.pipeline_enabled
    while engine.has_unfinished_requests() or engine.has_inflight():
        outs = engine.step_pipelined() if pipelined else engine.step()
        for out in outs:
            if out.finished:
                c = out.outputs[0]
                done[int(out.request_id)] = (
                    list(out.prompt_token_ids), list(c.token_ids),
                    [{int(t): float(getattr(v, "logprob", v))
                      for t, v in lp.items()} for lp in c.logprobs])
        steps += 1
    return done, steps, time.perf_counter() - t0


def verdict(stats: dict) -> list:
    """Names of the limits `stats` breaks."""
    broken = [k for k in ("mean_abs", "p99_abs", "max_abs")
              if not stats[k] <= TOLERANCES[k]["limit"]]     # NaN breaks
    if not stats["top1_agree_min"] >= TOLERANCES["top1_agree_min"]["limit"]:
        broken.append("top1_agree_min")
    return broken


_STATS = ("values", "mean_abs", "p99_abs", "max_abs", "top1_agree_min",
          "top1_positions")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="ragdoc-closed")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--check", type=int, default=4,
                    help="requests compared with the reference")
    ap.add_argument("--seats", type=int, default=16,
                    help="concurrent requests (the closed loop's clients)")
    ap.add_argument("--max-output", type=int, default=256,
                    help="tokens decoded a request (the cell's outputs are "
                    "368-704: the reference's pass grows with them)")
    ap.add_argument("--perturb", default="all",
                    help="comma list of " + ",".join(PERTURBATIONS)
                    + ", or all / none")
    ap.add_argument("--perturb-requests", type=int, default=1,
                    help="requests (the longest checked) a wrong reference "
                    "is computed on (a 6k-token sequence takes ~55 s of "
                    "the chip a pass)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="run the ENGINE with another cache dtype than the "
                    "configuration's (e.g. fp8_e5m2: must then disagree)")
    ap.add_argument("--dtype", default=None,
                    help="run the ENGINE in another dtype (float32: the "
                    "sharp check; the limits then do not apply)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth further (float32 needs it to fit)")
    ap.add_argument("--blocks", type=int, default=None,
                    help="another --num-device-blocks-override than the "
                    "configuration's (float32 needs a smaller pool)")
    ap.add_argument("--sharp-limit", type=float, default=1e-4,
                    help="mean |d logprob| a float32 engine is held to")
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
    if args.blocks:
        flags = list(serve["flags"])
        flags[flags.index("--num-device-blocks-override") + 1] = str(
            args.blocks)
        serve["flags"] = flags
    if args.layers:
        config = dict(config, name=f"{config['name']}-l{args.layers}",
                      num_hidden_layers=args.layers,
                      rope_layout=config["rope_layout"][:args.layers],
                      sliding_window_layout=config["sliding_window_layout"][
                          :args.layers])
    hf = hf_config_of(config, rehearsal)
    model_dir = make_model_dir(config, rehearsal)
    seats = 4 if rehearsal else args.seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check_smallthinker: no accelerator (platform "
              f"{platform}); a CPU rehearsal needs --rehearse-on-cpu",
              file=sys.stderr)
        return 2

    import random
    pairs = loadgen.length_pairs(traffic, seats)
    random.Random(args.seed).shuffle(pairs)
    cap = 12 if rehearsal else args.max_output
    plan = [loadgen.Planned(k, "closed", p, min(o, cap))
            for k, (p, o) in enumerate(pairs)]
    loadgen.fill_prompts(plan, args.seed, hf["vocab_size"])

    t0 = time.perf_counter()
    engine = boot_engine(model_dir, serve, args.seed, seats)
    boot_s = time.perf_counter() - t0
    done, steps, run_s = run_traffic(engine, plan)
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.dispatch import kernel_selection
    compiles = get_compile_tracker().snapshot()
    bm = engine.scheduler.block_manager
    window = hf["sliding_window_size"]
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
          "window_blocks_recycled": bm.window_blocks_recycled,
          "cache_groups": len(bm.groups),
          "memory_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
              "peak_bytes_in_use")})
    problems = []
    if len(bm.groups) != 2 or not bm.window_blocks_recycled:
        problems.append("the window cache group gave no block back")
    programs = compiles.get("compiles") or {}
    if not rehearsal and not (programs.get("mixed")
                              and programs.get("decode_cont")):
        problems.append(f"mixed and decode_cont did not both run: "
                        f"{sorted(programs)}")
    refs = {k: v for k, v in (compiles.get("kernel_dispatch") or {}).items()
            if k.startswith("reference")}
    if not rehearsal and refs:
        problems.append(f"kernel seams resolved to a reference: {refs}")
    if problems:
        print(f"reference_check_smallthinker: {problems}", file=sys.stderr)
        return 1

    # The engine's weights to the host, then the engine goes.
    params = jax.tree.map(np.asarray, engine.worker.params)
    engine.worker.params = None
    engine.worker.cache_engine = None
    del engine, bm
    gc.collect()
    jax.clear_caches()

    by_len = sorted(done, key=lambda i: len(done[i][0]) + len(done[i][1]))
    n = min(args.check, len(by_len))
    picks = sorted({by_len[round(k * (len(by_len) - 1) / max(n - 1, 1))]
                    for k in range(n)})
    seqs = {i: done[i][0] + done[i][1] for i in picks}
    first = {i: len(done[i][0]) - 1 for i in picks}
    emit({"event": "checked", "requests": picks,
          "lengths": [len(seqs[i]) for i in picks],
          "prompt_lens": [len(done[i][0]) for i in picks],
          "beyond_window": all(first[i] >= window for i in picks)})

    ref = load_reference(CONFIG)
    cfg = reference_config(hf)
    t0 = time.perf_counter()
    stats = compare(from_first(done, picks),
                    reference_logprobs(ref, cfg, params, seqs, first))
    sharp = serve["dtype"] == "float32" and not rehearsal
    broken = ([] if stats["mean_abs"] <= args.sharp_limit
              else ["sharp_mean_abs"]) if sharp else verdict(stats)
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

    names = ([] if args.perturb == "none" or sharp else list(PERTURBATIONS)
             if args.perturb == "all" else args.perturb.split(","))
    few = {i: seqs[i] for i in sorted(seqs, key=lambda i: len(seqs[i]))[
        -args.perturb_requests:]}
    undetected, perturbed = [], {}
    if names:
        same = compare(from_first(done, few),
                       reference_logprobs(ref, cfg, params, few, first))
        emit({"event": "reference_on_the_perturbed_requests",
              **{k: same[k] for k in _STATS[:5]}})
    for name in names:
        p = compare(from_first(done, few),
                    reference_logprobs(ref, cfg, params, few, first,
                                       how=name))
        perturbed[name] = {k: p[k] for k in _STATS[1:5]}
        perturbed[name]["broken"] = verdict(p)
        emit({"event": "perturbed", "how": name, **perturbed[name]})
        if not perturbed[name]["broken"]:
            undetected.append(name)
    agrees = not broken
    if args.kv_cache_dtype:
        agrees = bool(broken)       # one precision lower: has to disagree
    ok = agrees and not undetected
    emit({"agrees": not broken, "ok": ok, "config": CONFIG,
          "seed": args.seed, "engine_dtype": serve["dtype"],
          "engine_kv_cache_dtype": serve["kv_cache_dtype"],
          "layers": hf["num_hidden_layers"],
          "stats": {k: stats[k] for k in _STATS},
          "broken": broken, "perturbations": perturbed,
          "perturbations_undetected": undetected,
          "rehearsal": rehearsal, "platform": platform})
    if rehearsal:
        return 0        # the tiny preset proves the control flow only
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
