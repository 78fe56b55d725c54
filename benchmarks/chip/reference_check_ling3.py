"""Ling 3.0's served path against its plain float32 reference.

    python3 benchmarks/chip/reference_check_ling3.py --seed <n>

(on the chip: `chiprun -- python3 benchmarks/chip/reference_check_ling3.py
...`; `--rehearse-on-cpu` runs the same control flow at the configuration's
tiny preset on the CPU backend.) The procedure is `reference_check.py`'s,
and what is generic there is imported from it (the engine's boot, the
comparison); this file brings the traffic, the weight mapping, the wrong
references and the limits of `ling-3.0-flash-ep8-l12`:

1. the engine in-process with the configuration's `serve` settings,
   `--load-format dummy`, `--seed`, 16 seats; `--requests` greedy requests at
   once, prompts of 1,040 to 1,900 tokens (three or four 512-row `mixed`
   chunks each: the matrix state goes through its slot between them and the
   latent rows of the earlier chunks are read back from the pool, beside the
   decode rows of the requests already past their prompt), 24 tokens out
   (three K=8 decode steps: `kda_step` on the pool by slot, the latent walk
   over 1-2k tokens + the stage);
2. the weights FROM THE ENGINE under the assumed checkpoint names;
3. the reference (`references/ling_3_0_flash_ep8_l12_reference.py`: the
   delta rule a token at a time, latent attention EXPANDED, the router
   written out) teacher-forced on the engine's tokens, computed in blocks:
   layer by layer, one layer's float32 weights on the device at a time,
   every sequence padded to one length (the pass is causal) so that a kind
   of layer is one program; log-softmax compared at every position that
   produced a token;
4. `--perturb`: the reference computed WRONG one way at a time; each must
   break a limit.

`--dtype float32 --layers 6` with `JAX_DEFAULT_MATMUL_PRECISION=highest` in
the environment is the sharp version (both sides float32: what is left is
the order of summation; 6 layers = layers 0-5 = one whole period: two KDA
layers over the dense MLP, three over experts, the latent layer over
experts, 8.3 GB of float32 weights); it prints its reading and holds it to
`--sharp-limit`.

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
from reference_check import (_int8_roundtrip, boot_engine, compare,  # noqa: E402
                             emit, run_traffic)

CONFIG = "ling-3.0-flash-ep8-l12"
REFERENCE = os.path.join(HERE, "references",
                         "ling_3_0_flash_ep8_l12_reference.py")

# --- limits (nats of log-softmax), each with its reason ----------------------
#
# Both sides hold the same bf16 weights; the engine computes in bf16 (bf16
# products accumulated in float32; activations, latent rows and the filters'
# taps stored in bf16; softmax, norms, the router, the gates and the whole
# delta rule, state included, in float32), the reference in float32 at the
# highest matmul precision. The same engine in float32 at the highest
# precision agrees with the reference to the order of summation (PERF.md
# section 6, PR 54), so what these limits bound is bf16's own distance over
# 12 layers and 1-2k tokens of context. Each was set between two readings
# on the chip (PERF.md section 6, PR 54, has every number): what the engine
# gave over its seeds, and what a reference computed one precision lower
# (int8 weights: per-channel round trip of every matrix) or with a term
# computed another way gave against the same engine outputs. A lower
# precision has to break ONE of the limits, not each.
TOLERANCES = {
    "mean_abs": {
        "limit": 0.0112,
        "why": "mean |d logprob| over every compared (position, token): "
               "rounding averages out over 1,440 values, so this is the "
               "steadiest statistic and the one a lower precision moves "
               "first. Engine 0.00911 / 0.00933 / 0.00914 over three seeds "
               "(12 layers, 1-2k tokens of context: twice Solar 2's 0.0043 "
               "at 4 layers); int8 weights 0.01316 / 0.01394 / 0.01350, a "
               "wrong term 0.0240-0.126",
    },
    "p99_abs": {
        "limit": 0.065,
        "why": "99th percentile of |d logprob|: the bulk of the rounding "
               "error including softmax rows near a tie. Engine 0.0464 / "
               "0.0502 / 0.0525; a wrong term 0.096-0.42 (int8 weights "
               "0.0566-0.0594 pass this one and fail the mean)",
    },
    "max_abs": {
        "limit": 0.12,
        "why": "largest |d logprob| anywhere: bounds a single corrupted row "
               "or position (a NaN, a lost block, a state slot read across "
               "a chunk boundary, a wrong expert). The precision hardly "
               "moves it (engine 0.0941 / 0.0869 / 0.0962, int8 0.078-"
               "0.097); a wrong term reads 0.132-0.69",
    },
    "top1_agree_min": {
        "limit": 0.99,
        "why": "share of positions where the reference's most likely token "
               "is the engine's choice, among positions where the "
               "reference's margin over its second choice exceeds 0.1 nat: "
               "argmax agreement where rounding cannot decide it (engine "
               "1.0 over 183-193 positions); a guard against a corrupted "
               "row",
    },
}

PERTURBATIONS = ("group_score_max", "softplus_gate", "no_head_gate",
                 "write_strength_doubled", "int8_weights")
# What the float32 run has to see (its one limit, `--sharp-limit`): the same
# wrong terms, and a lower precision inside the delta rule, which bf16's
# own distance at the logits could hide from the limits above.
SHARP_PERTURBATIONS = ("group_score_max", "softplus_gate", "no_head_gate",
                       "write_strength_doubled", "bf16_state")


def load_reference():
    spec = importlib.util.spec_from_file_location("chip_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- the engine's weights, under the checkpoint names ------------------------

def layer_kind(lp: dict) -> tuple:
    return ("kda" if "qkv" in lp else "mla", "moe" if "w1" in lp else "dense")


def published_layer(lp: dict, i: int, first_expert: int) -> dict:
    """One layer of the engine's param tree -> {checkpoint name: float32
    array as a checkpoint holds it}. No arithmetic: transposes, splits,
    and `W_kvb,h = [W_UK,h | W_UV,h]` put back together."""
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    p = f"model.layers.{i}."
    a = p + "attention."
    w = {p + "input_layernorm.weight": f32(lp["input_norm"]),
         p + "post_attention_layernorm.weight": f32(lp["post_norm"]),
         a + "dense.weight": f32(lp["o"]).T}
    if "qkv" in lp:
        third = lp["qkv"].shape[1] // 3
        for n, name in enumerate("qkv"):
            cut = slice(n * third, (n + 1) * third)
            w[f"{a}{name}_proj.weight"] = f32(lp["qkv"][:, cut]).T
            w[f"{a}{name}_conv1d.weight"] = f32(
                lp["conv_w"][:, cut]).T[:, None, :]
        for ours, theirs in (("f", "f_proj"), ("b_proj", "b_proj"),
                             ("g", "g_proj")):
            w[f"{a}{theirs}.weight"] = f32(lp[ours]).T
        w[a + "dt_bias"] = f32(lp["dt_bias"])
        w[a + "A_log"] = f32(lp["A_log"])
        w[a + "o_norm.weight"] = f32(lp["o_norm"])
    else:
        for ours, theirs in (("q", "q_proj"), ("kv_a", "kv_a_proj_with_mqa"),
                             ("attn_gate", "g_proj")):
            w[f"{a}{theirs}.weight"] = f32(lp[ours]).T
        w[a + "kv_a_layernorm.weight"] = f32(lp["kv_a_norm"])
        kvb = np.concatenate(
            [f32(lp["w_uk"]), f32(lp["w_uv"]).swapaxes(1, 2)], axis=1)
        w[a + "kv_b_proj.weight"] = kvb.reshape(-1, kvb.shape[-1])
    m = p + "mlp."
    if "w1" not in lp:
        for k in ("gate", "up", "down"):
            w[f"{m}{k}_proj.weight"] = f32(lp[k]).T
        return w
    w[m + "gate.weight"] = f32(lp["gate_router"]).T
    w[m + "gate.expert_bias"] = f32(lp["expert_bias"])
    for j in range(lp["w1"].shape[0]):
        e = f"{m}experts.{first_expert + j}."
        w[e + "gate_proj.weight"] = f32(lp["w1"][j])
        w[e + "up_proj.weight"] = f32(lp["w3"][j])
        w[e + "down_proj.weight"] = f32(lp["w2"][j]).T
    for ours, theirs in (("shared_gate", "gate_proj"),
                         ("shared_up", "up_proj"),
                         ("shared_down", "down_proj")):
        w[f"{m}shared_experts.{theirs}.weight"] = f32(lp[ours]).T
    return w


def reference_config(hf: dict) -> dict:
    """The keys the reference reads."""
    return {k: hf[k] for k in (
        "hidden_size", "num_attention_heads", "head_dim", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "rms_norm_eps", "kda_lower_bound", "layer_group_size",
        "first_k_dense_replace", "num_experts", "num_shared_experts",
        "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "ep_size", "ep_rank", "num_hidden_layers")}


# --- a wrong reference, one way at a time ------------------------------------

def perturb(name: str, cfg: dict, layer_w: dict, ref):
    """(weights, undo) of the reference computed wrong in one way."""
    import jax
    import jax.numpy as jnp
    undo = lambda: None                                     # noqa: E731

    def swap(attr, wrong):
        right = getattr(ref, attr)
        setattr(ref, attr, wrong)
        return lambda: setattr(ref, attr, right)

    if name == "int8_weights":
        layer_w = {k: (_int8_roundtrip(v)
                       if v.ndim == 2 and not k.endswith("norm.weight")
                       else v) for k, v in layer_w.items()}
    elif name == "group_score_max":
        # A.X-K1's group score: a group's largest, not its two largest.
        undo = swap("group_scores", lambda cfg, choice: choice.max(-1))
    elif name == "softplus_gate":
        # Solar Open 2's gate in the bounded one's place.
        def softplus_decay(cfg, w, prefix, x):
            t = x.shape[0]
            h, d = cfg["num_attention_heads"], cfg["head_dim"]
            raw = (ref._linear(x, w[prefix + "f_proj.weight"])
                   + jnp.asarray(w[prefix + "dt_bias"])).reshape(t, h, d)
            rate = jnp.exp(jnp.asarray(w[prefix + "A_log"]).reshape(-1))
            return -rate[None, :, None] * jax.nn.softplus(raw)
        undo = swap("log_decay", softplus_decay)
    elif name == "no_head_gate":
        undo = swap("head_gate", lambda w, prefix, x: jnp.ones(
            (x.shape[0], w[prefix + "g_proj.weight"].shape[0])))
    elif name == "write_strength_doubled":
        undo = swap("write_strength", lambda w, prefix, x: (
            2.0 * jax.nn.sigmoid(ref._linear(x, w[prefix + "b_proj.weight"]))))
    elif name == "bf16_state":
        # The matrix state rounded to bfloat16 after every token: what a
        # state pool in the activations' dtype would hold. Not a pair of
        # converts: the compiler may drop the round trip
        # (reference_check_solar2.py says what it did on the chip).
        def delta_rule(q, k, v, a, b):
            def step(s, x):
                q_t, k_t, v_t, a_t, b_t = x
                s = a_t[:, :, None] * s
                u = v_t - jnp.einsum("hk,hkv->hv", k_t, s)
                s = s + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
                s = jax.lax.reduce_precision(s, exponent_bits=8,
                                             mantissa_bits=7)
                return s, jnp.einsum("hk,hkv->hv", q_t, s)

            h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
            _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                                (q, k, v, a, b))
            return o
        undo = swap("delta_rule", delta_rule)
    else:
        raise KeyError(name)
    return layer_w, undo


# --- the reference pass ------------------------------------------------------

_PROGRAMS: dict = {}      # (layer kind's first index, length, how)


def reference_logprobs(ref, cfg, params, seqs, how=None):
    """{index: float32 log-softmax [T, vocab]} of the reference,
    teacher-forced on `seqs` {index: token list}, in blocks: layer by
    layer, one layer's float32 weights on the device at a time, every
    sequence padded to the longest's length rounded up to 128 (the pass is
    causal: what follows a position does not move it), so that a kind of
    layer is one program under `jax.jit`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f32(x):
        return np.asarray(x).astype(np.float32)

    length = -(-max(len(s) for s in seqs.values()) // 128) * 128
    positions = np.arange(length)
    top = {"model.word_embeddings.weight": f32(params["embed_tokens"])}
    xs = {i: ref.embed(top, jnp.asarray(s + [0] * (length - len(s))))
          for i, s in seqs.items()}
    del top
    layers = params["layers"]
    first_expert = (cfg["ep_rank"] * cfg["num_experts"]
                    if cfg["ep_size"] > 1 else 0)
    for lp in layers:
        ci = next(j for j, q in enumerate(layers)
                  if layer_kind(q) == layer_kind(lp))
        # the kind's first layer's index has to name the kind by the
        # reference's own rules
        assert (ref.is_latent_layer(cfg, ci), ref.is_moe_layer(cfg, ci)) == (
            "qkv" not in lp, "w1" in lp)
        layer_w = published_layer(lp, ci, first_expert)
        undo = lambda: None                                 # noqa: E731
        if how:
            layer_w, undo = perturb(how, cfg, layer_w, ref)
        layer_w = {k: jnp.asarray(v) for k, v in layer_w.items()}
        try:
            key = (ci, length, how if how != "int8_weights" else None)
            if key not in _PROGRAMS:
                _PROGRAMS[key] = jax.jit(
                    lambda w, x, ci=ci: ref.layer(cfg, w, ci, x, positions))
            with jax.default_matmul_precision("highest"):
                for i in seqs:
                    xs[i] = jax.block_until_ready(
                        _PROGRAMS[key](layer_w, xs[i]))
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
    with jax.default_matmul_precision("highest"):
        for i, s in seqs.items():
            out[i] = np.asarray(jax.nn.log_softmax(
                ref.head(cfg, head_w, xs[i][:len(s)]), axis=-1))
    return out


def verdict(stats: dict) -> list:
    """Names of the limits `stats` breaks."""
    broken = [k for k in ("mean_abs", "p99_abs", "max_abs")
              if not stats[k] <= TOLERANCES[k]["limit"]]     # NaN breaks
    if not stats["top1_agree_min"] >= TOLERANCES["top1_agree_min"]["limit"]:
        broken.append("top1_agree_min")
    return broken


def make_plan(seed: int, vocab: int, requests: int, rehearsal: bool):
    """`requests` prompts spread evenly over 1,040 to 1,900 tokens (three
    or four 512-row chunks; none a multiple of 16, so a chunk's last rows
    and the latent blocks' boundaries fall at every offset), 24 tokens
    out. The tiny preset: 70 to 130 tokens against a 64-token budget."""
    low, high, out = (70, 130, 24) if rehearsal else (1040, 1900, 24)
    plan = [loadgen.Planned(
        k, "closed", low + (high - low) * k // max(requests - 1, 1) + k % 7,
        out) for k in range(requests)]
    loadgen.fill_prompts(plan, seed, vocab)
    return plan


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=12,
                    help="requests sent at once and compared")
    ap.add_argument("--seats", type=int, default=16)
    ap.add_argument("--perturb", default="all",
                    help="comma list of " + ",".join(PERTURBATIONS)
                    + ", or all / none")
    ap.add_argument("--dtype", default=None,
                    help="run the ENGINE in another dtype (float32: the "
                    "sharp check; the limits then do not apply)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth further (float32 needs 6 to fit)")
    ap.add_argument("--sharp-limit", type=float, default=2e-5,
                    help="mean |d logprob| a float32 engine is held to: "
                    "between the engine's 3.99e-6 (max 1.7e-5) and what a "
                    "bfloat16 state (1.5e-3) or a wrong term (0.011-0.090) "
                    "reads (PERF.md section 6, PR 54)")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)

    config = Manifest().config(CONFIG)
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
    seats = args.seats

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if not rehearsal and platform != "tpu":
        print(f"reference_check_ling3: no accelerator (platform {platform}); "
              f"a CPU rehearsal needs --rehearse-on-cpu", file=sys.stderr)
        return 2

    plan = make_plan(args.seed, hf["vocab_size"], args.requests, rehearsal)
    t0 = time.perf_counter()
    engine = boot_engine(model_dir, serve, args.seed, seats)
    boot_s = time.perf_counter() - t0
    done, steps, run_s = run_traffic(engine, plan, len(plan))
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.ops.dispatch import kernel_selection
    compiles = get_compile_tracker().snapshot()
    bm = engine.scheduler.block_manager
    chunk = engine.scheduler_config.max_num_batched_tokens
    emit({"event": "engine", "platform": platform,
          "device_kind": jax.devices()[0].device_kind,
          "dtype": serve["dtype"], "layers": hf["num_hidden_layers"],
          "boot_s": boot_s, "run_s": run_s, "steps": steps,
          "requests_done": len(done),
          "prompt_lens": [len(done[i][0]) for i in sorted(done)],
          "chunks_a_prompt": [-(-len(done[i][0]) // chunk)
                              for i in sorted(done)],
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
            print(f"reference_check_ling3: kernel seams resolved to a "
                  f"reference: {refs}", file=sys.stderr)
            return 1

    # The engine's weights to the host, then the engine goes.
    params = jax.tree.map(np.asarray, engine.worker.params)
    engine.worker.params = None
    engine.worker.cache_engine = None
    del engine
    gc.collect()
    jax.clear_caches()

    seqs = {i: done[i][0] + done[i][1] for i in sorted(done)}
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
    undetected, perturbed = [], {}
    for name in names:
        p = compare(done, reference_logprobs(ref, cfg, params, seqs,
                                             how=name))
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
    # Leave without the interpreter's teardown (reference_check_solar2.py
    # says what it did to a finished run).
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
