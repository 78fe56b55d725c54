"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # tensor parallelism only, tp=4 vs tp=1

One chip, four phases, each a child process (this parent never imports
jax: a chip belongs to one process at a time, and the children need it):

1. `device`  — what JAX finds. No accelerator -> exit 2, nothing printed
               on stdout.
2. `serve`   — `python -m intellillm_tpu.entrypoints.openai.api_server`
               with a Llama-2-7B-shaped model (hidden 4096, inter 11008,
               32 layers, 32 heads of 128, vocab 32000), seeded random
               weights in int8, fp8 KV pool sized by the server's own
               memory profile, warm-up on. This parent sends
               /v1/completions requests (greedy and seeded-sampled,
               streamed and not, prompts of different lengths, one of
               them arriving while another decodes) and checks token
               counts, finish reasons, finite logprobs, determinism,
               that a step mixed prefill with decode rows, that the
               fused decode programs ran, and that no kernel seam
               resolved to a reference.
3. `parity`  — the same widths at 2 layers, built twice in one process:
               Pallas kernels on, then `set_use_pallas(False)`; the same
               prompts must give the same greedy tokens.
4. `state`   — two models with per-sequence state, one after the other:
               one Solar Open 2 period (a gated GQA layer and three
               delta-rule layers with a float32 matrix a head in the
               state slots) at 128-wide heads, and a Granite 4.0-H
               period cut to four layers (three Mamba-2 scan layers with
               a float32 matrix [128, inner] and a NoPE GQA layer); what
               prefill then K=8 decode through the slots produced must
               equal a fresh full pass over the same tokens (the step
               form on the pool against the chunk form from position 0).

Every stdout line is one JSON object. The last one is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
and appears only when every phase passed on a TPU; any failure exits
non-zero without it. `--size tiny --rehearse` runs the whole control flow
on whatever backend there is (CPU rehearsal) and then still refuses.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(REPO, ".cache", "chip_smoke")
BOOT_TIMEOUT_S = 900.0      # of the 1200 s the whole smoke may take

# (hidden, inter, heads, kv_heads, vocab): widths are never cut; depth is.
WIDTHS = {
    "7b": (4096, 11008, 32, 32, 32000),
    "tiny": (512, 1024, 4, 4, 1024),    # head 128: the kernels' lane width
}
# Per size: serve-phase depth and engine limits; parity/tp depth and the
# small limits that keep the reference's [rows, context, Hkv, D] gather
# (ops/attention.decode_attention_reference) inside one chip.
SIZES = {
    # The server runs at its defaults: 2048-row token budget, the model's
    # own 4096 context, pool sized by profiling.
    "7b": dict(serve_layers=32, quant="int8", kv="fp8_e5m2",
               serve_flags=[], long_prompt=2100,
               small_layers=2, small_mml=512, small_budget=256,
               small_seqs=16, small_blocks=512,
               small_prompts=(5, 33, 100, 300)),
    "tiny": dict(serve_layers=2, quant=None, kv="auto",
                 serve_flags=["--num-device-blocks-override", "128",
                              "--max-num-seqs", "8", "--swap-space", "0.01",
                              "--max-model-len", "256",
                              "--max-num-batched-tokens", "64"],
                 long_prompt=100,
                 small_layers=2, small_mml=256, small_budget=64,
                 small_seqs=8, small_blocks=128,
                 small_prompts=(5, 33, 40, 100)),
}


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def child_env() -> dict:
    """Children run this checkout's code (it is not installed on the chip
    machine) and share one compile cache: the one placed from outside,
    else the engine's fixed in-checkout directory."""
    # Imports no jax: the package resolves its exports lazily.
    from intellillm_tpu.utils import (DEFAULT_COMPILE_CACHE_DIR,
                                      child_process_env)
    env = child_process_env()
    env.setdefault("JAX_COMPILATION_CACHE_DIR", DEFAULT_COMPILE_CACHE_DIR)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def run_phase_child(phase: str, args, relay: bool = True) -> list:
    """Run `chip_smoke.py --phase <phase>` to its end; relay its JSON
    lines (unless told not to) and return them. A non-zero exit fails
    the smoke."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--size", args.size, "--seed", str(args.seed),
           "--chips", str(args.chips)]
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, env=child_env(), stdout=subprocess.PIPE,
                            text=True)
    lines = []
    for raw in proc.stdout:
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        obj = json.loads(raw)
        lines.append(obj)
        if relay:
            emit(obj)
    rc = proc.wait()
    check(rc == 0, f"phase {phase} exited {rc}")
    if relay:
        emit({"phase": phase, "wall_s": round(time.monotonic() - t0, 1),
              "rc": rc})
    return lines


# --- phase: device (child) ---------------------------------------------------

def phase_device(args) -> None:
    from intellillm_tpu.utils import runtime_facts
    emit({"phase": "device", **runtime_facts()})


# --- phase: serve (parent drives a server child) -----------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def stream_completion(url: str, body: dict, on_first_token=None,
                      timeout: float = 600.0) -> dict:
    """POST a streaming /v1/completions (with `logprobs`, so every chunk
    names its tokens); returns the tokens, their logprobs, the last
    finish_reason and the number of token-bearing chunks."""
    req = urllib.request.Request(
        url, data=json.dumps({**body, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    tokens, logprobs, finish, chunks = [], [], None, 0
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[len("data:"):].strip()
            if payload == "[DONE]":
                break
            choice = json.loads(payload)["choices"][0]
            new = (choice.get("logprobs") or {}).get("tokens") or []
            if new:
                tokens += new
                logprobs += choice["logprobs"]["token_logprobs"]
                chunks += 1
                if chunks == 1 and on_first_token is not None:
                    on_first_token()
            finish = choice.get("finish_reason") or finish
    return {"tokens": tokens, "token_logprobs": logprobs,
            "finish_reason": finish, "chunks": chunks}


def llama_config(size: str, layers: int):
    from transformers import LlamaConfig
    hidden, inter, heads, kv_heads, vocab = WIDTHS[size]
    return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                       intermediate_size=inter, num_hidden_layers=layers,
                       num_attention_heads=heads,
                       num_key_value_heads=kv_heads,
                       max_position_embeddings=4096,
                       tie_word_embeddings=False)


def make_model_dir(size: str, layers: int) -> str:
    """config.json + a word-level tokenizer for `--load-format dummy`."""
    from benchmarks.common import save_dummy_checkpoint
    out = os.path.join(WORK_DIR, f"llama-{size}-{layers}l")
    os.makedirs(out, exist_ok=True)
    return save_dummy_checkpoint(llama_config(size, layers), out)


def prompt_ids(seed: int, n: int, vocab: int) -> list:
    """n token ids from a tiny LCG (the parent stays off numpy's and
    jax's RNGs; ids avoid the three special tokens)."""
    out, x = [], (seed * 2654435761 + 12345) & 0xFFFFFFFF
    for _ in range(n):
        x = (x * 1664525 + 1013904223) & 0xFFFFFFFF
        out.append(3 + (x >> 8) % (vocab - 3))
    return out


def wait_healthy(proc, base: str, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"server exited {proc.returncode} before it was healthy")
        try:
            with urllib.request.urlopen(base + "/health", timeout=2):
                return
        except OSError:
            time.sleep(1.0)
    fail(f"server not healthy after {timeout_s:.0f}s")


def phase_serve(args, expect_tpu: bool) -> None:
    cfg = SIZES[args.size]
    vocab = WIDTHS[args.size][4]
    t_phase = time.monotonic()
    model_dir = make_model_dir(args.size, cfg["serve_layers"])
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m",
           "intellillm_tpu.entrypoints.openai.api_server",
           "--model", model_dir, "--load-format", "dummy",
           "--dtype", "bfloat16", "--seed", str(args.seed),
           "--host", "127.0.0.1", "--port", str(port),
           "--kv-cache-dtype", cfg["kv"],
           "--disable-log-requests"] + cfg["serve_flags"]
    if cfg["quant"]:
        cmd += ["--quantization", cfg["quant"]]
    log_path = os.path.join(WORK_DIR, "server.log")
    t_boot = time.monotonic()
    with open(log_path, "wb") as log:
        # cwd elsewhere on purpose: the child must find the package
        # through its environment, not through the current directory.
        proc = subprocess.Popen(cmd, env=child_env(), cwd=WORK_DIR,
                                stdout=log, stderr=subprocess.STDOUT)
    try:
        wait_healthy(proc, base, BOOT_TIMEOUT_S)
        boot_s = time.monotonic() - t_boot
        detail = http_json(base + "/health/detail")
        boot = detail["boot"]
        runtime, cache = boot["runtime"], boot["cache"]
        warm = boot.get("warmup") or {}
        emit({"phase": "serve", "event": "booted",
              "boot_wall_s": round(boot_s, 1),
              "boot_phases_s": boot["phases_s"], "runtime": runtime,
              "layers": cfg["serve_layers"], "widths": WIDTHS[args.size],
              "quantization": cfg["quant"], "cache": cache,
              "warmup": warm})
        if expect_tpu:
            check(runtime["platform"] == "tpu",
                  f"server ran on {runtime['platform']}, not the TPU")
            check(cache["profiled"] and cache["num_device_blocks"] > 0,
                  f"pool was not sized by profiling: {cache}")
            check(warm.get("executables", 0) >= 2,
                  f"warm-up compiled nothing: {warm}")

        comp = base + "/v1/completions"
        common = {"model": model_dir, "ignore_eos": True}
        t_req = time.monotonic()

        # A, A': greedy, not streamed, same prompt twice -> same tokens.
        p_a = prompt_ids(args.seed + 1, 40, vocab)
        greedy = {**common, "prompt": p_a, "max_tokens": 24,
                  "temperature": 0.0, "logprobs": 1}
        r_a = http_json(comp, greedy)
        r_a2 = http_json(comp, greedy)
        # B: greedy and streamed, long enough to still be decoding when C
        # arrives. C: seeded sampling, a prompt longer than the token
        # budget, sent from B's first token. It is admitted by
        # prefill-only steps, a chunk a step, while B's decode stays in
        # flight, and joins B's batch by continuation once its final
        # chunk has been fetched. (D and D2, sent to an idle engine, take
        # the chunked pass of a fresh schedule: `chunked_prefill` drains.)
        p_b = prompt_ids(args.seed + 2, 24, vocab)
        p_c = prompt_ids(args.seed + 3, cfg["long_prompt"], vocab)
        sampled = {**common, "prompt": p_c, "max_tokens": 16,
                   "temperature": 0.8, "seed": 1234, "logprobs": 1}
        side = {}

        def send_c():
            t = threading.Thread(
                target=lambda: side.update(c=http_json(comp, sampled)))
            t.start()
            side["thread"] = t

        r_b = stream_completion(
            comp, {**common, "prompt": p_b, "max_tokens": 96,
                   "temperature": 0.0, "logprobs": 1},
            on_first_token=send_c)
        check("thread" in side, "stream B produced no token")
        side["thread"].join(timeout=600)
        check("c" in side, "request C did not return")
        r_c = side["c"]
        # D, D2: C's request again, streamed, with nothing else running.
        # D2 must repeat D (same seed, same batch shapes); whether they
        # repeat C, whose rows ran beside B's, is reported.
        r_d = stream_completion(comp, sampled)
        r_d2 = stream_completion(comp, sampled)
        req_s = time.monotonic() - t_req

        def flat(resp):
            """A non-streamed answer in the streamed one's shape."""
            [choice] = resp["choices"]
            return {"tokens": choice["logprobs"]["tokens"],
                    "token_logprobs": choice["logprobs"]["token_logprobs"],
                    "finish_reason": choice["finish_reason"]}

        answers = {"A": (flat(r_a), 24), "A2": (flat(r_a2), 24),
                   "B": (r_b, 96), "C": (flat(r_c), 16),
                   "D": (r_d, 16), "D2": (r_d2, 16)}
        finite = all(lp is not None and lp == lp
                     and abs(lp) != float("inf")
                     for ans, _ in answers.values()
                     for lp in ans["token_logprobs"])
        greedy_equal = answers["A"][0]["tokens"] == answers["A2"][0]["tokens"]
        seeded_equal = answers["D"][0]["tokens"] == answers["D2"][0]["tokens"]
        co_batched_equal = (answers["C"][0]["tokens"]
                            == answers["D"][0]["tokens"])
        emit({"phase": "serve", "event": "answered",
              "requests_wall_s": round(req_s, 1),
              "requests": {k: {"tokens": len(ans["tokens"]), "asked": want,
                               "finish": ans["finish_reason"]}
                           for k, (ans, want) in answers.items()},
              "prompt_lens": {"A": len(p_a), "B": len(p_b),
                              "C": len(p_c), "D": len(p_c)},
              "stream_chunks": {"B": r_b["chunks"], "D": r_d["chunks"]},
              "greedy_repeat_equal": greedy_equal,
              "seeded_repeat_equal": seeded_equal,
              "seeded_co_batched_equals_alone": co_batched_equal,
              "logprobs_finite": finite})
        for name, (ans, want) in answers.items():
            check(len(ans["tokens"]) == want,
                  f"request {name}: {len(ans['tokens'])} tokens, "
                  f"asked {want}")
            check(ans["finish_reason"] == "length",
                  f"request {name}: finish_reason "
                  f"{ans['finish_reason']!r}, not 'length'")
        check(r_a["usage"] == {"prompt_tokens": len(p_a),
                               "completion_tokens": 24,
                               "total_tokens": len(p_a) + 24},
              f"usage is wrong: {r_a['usage']}")
        check(r_c["usage"] == {"prompt_tokens": len(p_c),
                               "completion_tokens": 16,
                               "total_tokens": len(p_c) + 16},
              f"usage is wrong: {r_c['usage']}")
        check(greedy_equal, "the same greedy request gave different tokens")
        check(seeded_equal, "the same seeded request gave different tokens")
        check(finite, "NaN or inf among the logprobs")

        detail = http_json(base + "/health/detail")
        kernels = http_json(base + "/debug/kernels?top=64")
        compiles = detail["compiles"]
        eff = detail["efficiency"]
        mem = detail["device_telemetry"]["devices"]
        relower = [e.get("introspect_seconds")
                   for e in kernels["executables"]]
        emit({"phase": "serve", "event": "programs",
              "compiles": compiles["compiles"],
              "compile_time_seconds": {
                  k: round(v, 1) for k, v in
                  compiles["compile_time_seconds"].items()},
              "cold_compile_s": round(
                  warm.get("seconds", 0.0)
                  + sum(compiles["compile_time_seconds"].values()), 1),
              "programs_compiled": warm.get("executables", 0)
              + sum(compiles["compiles"].values()),
              "kernel_dispatch": compiles["kernel_dispatch"],
              "kernel_selection": kernels.get("selection"),
              "kernel_ledger": {
                  "introspection": kernels.get("introspection"),
                  "programs_relowered": sum(
                      1 for s in relower if s is not None),
                  # The set-up record's stage: one source for one number.
                  "relower_s": round(
                      detail["boot"]["setup"]["first_calls"]["by_stage_s"][
                          "introspect"], 1)},
              "co_batched_steps": eff["co_batched_steps"],
              "decode_join_rows": eff["decode_join_rows"],
              "pipeline_drains": eff["pipeline_drains"],
              "dispatches": eff["dispatches"],
              "memory_stats": mem})
        check(eff["decode_join_rows"]["device"] >= 1,
              "the chunked prompt did not join the decode batch by the "
              "continuation behind its final chunk's step")
        check(eff["pipeline_drains"]["chunked_prefill"] >= 1,
              "D and D2 (an idle engine, no decode step in flight) did "
              "not take the chunked pass of a fresh schedule")
        for program in ("mixed", "decode_fused", "decode_cont"):
            check(compiles["compiles"].get(program, 0) >= 1,
                  f"program {program} never ran: {compiles['compiles']}")
        if expect_tpu:
            refs = {k: v for k, v in compiles["kernel_dispatch"].items()
                    if k.startswith("reference")}
            check(not refs, f"kernel seams resolved to a reference: {refs}")
            sel = kernels.get("selection") or {}
            check(sel.get("use_pallas") and sel.get("ragged"),
                  f"Pallas seams are off: {sel}")
    except BaseException:
        with open(log_path, "rb") as f:
            tail = f.read()[-8000:].decode(errors="replace")
        print(f"--- server log tail ({log_path}) ---\n{tail}",
              file=sys.stderr, flush=True)
        raise
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    emit({"phase": "serve", "wall_s": round(time.monotonic() - t_phase, 1),
          "rc": 0})


# --- phases: parity, tp (children; in-process engines) -----------------------

def build_small_engine(size: str, seed: int, tp: int = 1):
    """The reduced-depth engine both token comparisons build twice.
    float32: see exact_f32_matmuls."""
    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.engine.llm_engine import LLMEngine

    cfg = SIZES[size]
    hf = llama_config(size, cfg["small_layers"])
    model_config = ModelConfig.from_hf_config(
        hf, dtype="float32", max_model_len=cfg["small_mml"],
        load_format="dummy", quantization=cfg["quant"], seed=seed)
    cache_config = CacheConfig(
        block_size=16, num_device_blocks_override=cfg["small_blocks"],
        swap_space_gib=0.01, cache_dtype=cfg["kv"])
    scheduler_config = SchedulerConfig(
        max_num_batched_tokens=cfg["small_budget"],
        max_num_seqs=cfg["small_seqs"], max_model_len=cfg["small_mml"],
        max_paddings=4096, num_decode_steps=8, enable_chunked_prefill=True)
    return LLMEngine(model_config, cache_config,
                     ParallelConfig(tensor_parallel_size=tp),
                     scheduler_config, log_stats=False,
                     skip_tokenizer_init=True)


def greedy_tokens(engine, size: str, seed: int) -> dict:
    """Prompts of different lengths added together, the last longer than
    the token budget (so it runs chunk by chunk, beside the others' decode
    steps or, on a fresh schedule, in a step with their decode rows),
    greedy, through the engine's normal stepping loop."""
    from intellillm_tpu.sampling_params import SamplingParams

    cfg = SIZES[size]
    vocab = WIDTHS[size][4]
    lens = cfg["small_prompts"]
    for i, n in enumerate(lens):
        engine.add_request(
            request_id=f"parity-{i}", prompt=None,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=20,
                                           ignore_eos=True, logprobs=5),
            prompt_token_ids=prompt_ids(seed + 10 + i, n, vocab))
    out = {}
    while engine.has_unfinished_requests() or engine.has_inflight():
        step = (engine.step_pipelined if engine.pipeline_enabled
                else engine.step)
        for ro in step():
            if ro.finished:
                out[ro.request_id] = list(ro.outputs[0].token_ids)
                _TOP_LOGPROBS[ro.request_id] = ro.outputs[0].logprobs
    check(len(out) == len(lens) and all(len(t) == 20 for t in out.values()),
          f"engine returned {[(k, len(v)) for k, v in out.items()]}")
    return out


# request id -> per-token top-5 logprobs of the latest greedy_tokens run
# (what explain_divergence prints when two runs disagree).
_TOP_LOGPROBS: dict = {}


def explain_divergence(toks_a: dict, top_a: dict, toks_b: dict,
                       top_b: dict) -> None:
    """For each request whose tokens differ, print (stderr) where they
    part and both runs' top-5 logprobs there: a near-tie reads as two
    candidates a hair apart in both, a fault as a different
    distribution."""
    for rid in sorted(toks_a):
        a, b = toks_a[rid], toks_b[rid]
        if a == b:
            continue
        i = next(j for j in range(len(a)) if a[j] != b[j])

        def top(lps):
            return sorted(((round(float(lp), 4), int(t))
                           for t, lp in lps[i].items()), reverse=True)

        print(f"  {rid}: first differ at token {i}: {a[i]} vs {b[i]}\n"
              f"    first run  top-5 (logprob, id): {top(top_a[rid])}\n"
              f"    second run top-5 (logprob, id): {top(top_b[rid])}",
              file=sys.stderr, flush=True)


def per_device_bytes() -> list:
    import jax
    return [{"device": d.id,
             "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use")}
            for d in jax.devices()]


def drop_engine(engine) -> None:
    """Free the engine's device arrays before the next one is built."""
    import gc
    worker = engine.worker
    worker.model_runner.params = None
    worker.cache_engine.device_cache = None
    worker.params = worker.model_runner = worker.cache_engine = None
    del engine, worker
    gc.collect()


def exact_f32_matmuls() -> str:
    """Both sides of a token comparison run in float32 with full-precision
    matmuls. With random weights the logits are near-uniform (top-two gap
    ~0.3 of a logit), so greedy tokens are equal only when the two
    sides' logits agree to well under that. In the served bfloat16 they
    do not, for reasons that are neither side's fault: the reference
    rounds q*scale to bfloat16 before its dot where the kernels keep
    float32 (first chip run, PR 21: 3 argmax flips in 80 tokens), the
    TPU's default matmul rounds float32 operands to bfloat16, and tp=4
    rounds four partial sums where tp=1 rounds one. The kernels see the
    same shapes and the same fp8 pool either way, and for MHA the same
    float32 q block."""
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")
    return "highest"


def phase_parity(args) -> None:
    import jax

    from intellillm_tpu.ops.dispatch import kernel_selection, set_use_pallas
    cfg = SIZES[args.size]
    precision = exact_f32_matmuls()
    on_tpu = jax.default_backend() == "tpu"
    t0 = time.monotonic()
    # Kernels on: the backend default on a TPU. A CPU rehearsal cannot
    # run Mosaic, so there both builds take the reference (control flow
    # only — and the smoke refuses at the end anyway).
    engine = build_small_engine(args.size, args.seed)
    sel_on = kernel_selection()
    if on_tpu:
        check(sel_on["use_pallas"] and sel_on["ragged"],
              f"Pallas seams are off on a TPU backend: {sel_on}")
    toks_on = greedy_tokens(engine, args.size, args.seed)
    top_on = dict(_TOP_LOGPROBS)
    drop_engine(engine)
    t1 = time.monotonic()
    set_use_pallas(False)
    engine = build_small_engine(args.size, args.seed)
    sel_off = kernel_selection()
    toks_off = greedy_tokens(engine, args.size, args.seed)
    top_off = dict(_TOP_LOGPROBS)
    drop_engine(engine)
    from intellillm_tpu.obs import (get_compile_tracker,
                                    get_efficiency_tracker)
    emit({"phase": "parity", "layers": cfg["small_layers"],
          "widths": WIDTHS[args.size], "max_model_len": cfg["small_mml"],
          "token_budget": cfg["small_budget"],
          "max_num_seqs": cfg["small_seqs"], "quantization": cfg["quant"],
          "kv_cache_dtype": cfg["kv"], "dtype": "float32",
          "f32_matmul_precision": precision,
          "selection_on": sel_on, "selection_off": sel_off,
          "kernel_dispatch":
              get_compile_tracker().snapshot()["kernel_dispatch"],
          "prompt_lens": cfg["small_prompts"],
          "co_batched_steps":
              get_efficiency_tracker().snapshot()["co_batched_steps"],
          "tokens_equal": toks_on == toks_off,
          "first_tokens_on": {k: v[:6] for k, v in sorted(toks_on.items())},
          "kernels_on_s": round(t1 - t0, 1),
          "kernels_off_s": round(time.monotonic() - t1, 1)})
    # The prompts arrive together at an idle engine, so the long one's
    # chunks share the fresh schedule's steps with the others' decode rows.
    check(get_efficiency_tracker().snapshot()["co_batched_steps"] >= 1,
          "no step mixed prefill rows with decode rows")
    if toks_on != toks_off:
        explain_divergence(toks_on, top_on, toks_off, top_off)
        fail("greedy tokens differ, kernels on vs off")


# --- phase: state (child) ----------------------------------------------------

def solar2_config(size: str):
    """A Solar Open 2 period (GQA, KDA, KDA, KDA) at the size's hidden
    width and 128-wide heads (the lane width the delta rule's kernels
    need), 8 of 16 experts held: the per-sequence state is the filters'
    taps and a float32 matrix a head, one slot a sequence."""
    from intellillm_tpu.transformers_utils.configs import SolarOpen2Config
    hidden, _, _, _, vocab = WIDTHS[size]
    heads = hidden // 128
    return SolarOpen2Config(
        architectures=["SolarOpen2ForCausalLM"], vocab_size=vocab,
        hidden_size=hidden, moe_intermediate_size=256,
        num_hidden_layers=4, gqa_layers=[0], num_attention_heads=heads,
        num_key_value_heads=max(heads // 4, 1), head_dim=128,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": heads, "num_kv_heads": None},
        n_routed_experts=8, ep_size=2, ep_rank=0, num_experts_per_tok=4,
        max_position_embeddings=4096)


def granite4h_config(size: str):
    """A Granite 4.0-H period cut to (mamba, mamba, attention, mamba) at
    the size's hidden width: scan heads of 64 over 128 state channels (the
    widths the scan's kernels need), attention heads of 128 at a softmax
    scale of 1/128, 8 of 16 experts held, a shared MLP, the four
    multipliers as published."""
    from intellillm_tpu.transformers_utils.configs import (
        GraniteMoeHybridConfig)
    hidden, _, _, _, vocab = WIDTHS[size]
    heads = hidden // 128
    return GraniteMoeHybridConfig(
        architectures=["GraniteMoeHybridForCausalLM"], vocab_size=vocab,
        hidden_size=hidden, intermediate_size=256,
        shared_intermediate_size=512, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_attention_heads=heads, num_key_value_heads=max(heads // 4, 1),
        mamba_n_heads=2 * hidden // 64, mamba_d_head=64, mamba_d_state=128,
        num_local_experts=8, ep_size=2, ep_rank=0, num_experts_per_tok=4,
        max_position_embeddings=4096)


# (model, its config, the seams whose kernels a TPU has to run)
STATE_MODELS = (
    ("solar_open2", solar2_config, ("kda_chunk", "kda_step",
                                    "short_conv_silu")),
    ("granitemoehybrid", granite4h_config, ("ssd_chunk", "ssd_step",
                                            "short_conv_silu")))


def phase_state(args) -> None:
    for model, config_of, seams in STATE_MODELS:
        state_model(args, model, config_of(args.size), seams)


def state_model(args, model: str, hf_config, seams) -> None:
    """A model with per-sequence state (a float32 matrix in the state
    slots): what prefill then K=8 decode through the slots produced equals
    a fresh full pass. Each request's prompt + its first n tokens, sent as
    a new prompt, must choose token n + 1 again, at the same logprob: the
    first run reached that token through the step form on the pool by slot
    (and a chunked prefill for the long prompt), the second through the
    chunk form from position 0."""
    import jax

    from intellillm_tpu.config import (CacheConfig, ModelConfig,
                                       ParallelConfig, SchedulerConfig)
    from intellillm_tpu.engine.llm_engine import LLMEngine
    from intellillm_tpu.obs import get_compile_tracker
    from intellillm_tpu.sampling_params import SamplingParams
    precision = exact_f32_matmuls()
    cfg = SIZES[args.size]
    vocab = WIDTHS[args.size][4]
    t0 = time.monotonic()
    before = dict(get_compile_tracker().snapshot()["kernel_dispatch"])
    model_config = ModelConfig.from_hf_config(
        hf_config, dtype="float32",
        max_model_len=cfg["small_mml"], load_format="dummy", seed=args.seed)
    engine = LLMEngine(
        model_config,
        CacheConfig(block_size=16,
                    num_device_blocks_override=cfg["small_blocks"],
                    swap_space_gib=0.01),
        ParallelConfig(),
        SchedulerConfig(max_num_batched_tokens=cfg["small_budget"],
                        max_num_seqs=cfg["small_seqs"],
                        max_model_len=cfg["small_mml"], max_paddings=4096,
                        num_decode_steps=8, enable_chunked_prefill=True),
        log_stats=False, skip_tokenizer_init=True)

    def run(prompts: dict, n: int) -> dict:
        for rid, ids in prompts.items():
            engine.add_request(
                request_id=rid, prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=n, ignore_eos=True,
                    logprobs=1),
                prompt_token_ids=ids)
        out = {}
        while engine.has_unfinished_requests() or engine.has_inflight():
            step = (engine.step_pipelined if engine.pipeline_enabled
                    else engine.step)
            for ro in step():
                if ro.finished:
                    c = ro.outputs[0]
                    out[ro.request_id] = (
                        list(c.token_ids),
                        [float(getattr(lp[t], "logprob", lp[t]))
                         for t, lp in zip(c.token_ids, c.logprobs)])
        return out

    lens = cfg["small_prompts"]
    prompts = {f"state-{i}": prompt_ids(args.seed + 30 + i, n, vocab)
               for i, n in enumerate(lens)}
    first = run(prompts, 20)
    check(all(len(t) == 20 for t, _ in first.values()),
          f"engine returned {[(k, len(v[0])) for k, v in first.items()]}")
    worst, wrong = 0.0, []
    for cut in (1, 9, 19):          # inside and across the K=8 substeps
        again = run({rid: prompts[rid] + first[rid][0][:cut]
                     for rid in prompts}, 1)
        for rid in prompts:
            (tok, ), (lp, ) = again[rid]
            worst = max(worst, abs(lp - first[rid][1][cut]))
            if tok != first[rid][0][cut]:
                wrong.append((rid, cut, first[rid][0][cut], tok))
    bm = engine.scheduler.block_manager
    dispatch = {k: v for k, v in get_compile_tracker().snapshot()[
        "kernel_dispatch"].items() if v > before.get(k, 0)}
    emit({"phase": "state", "model": model, "layers": 4, "dtype": "float32",
          "f32_matmul_precision": precision, "prompt_lens": lens,
          "state_arrays": [[list(s), d] for s, d in
                           model_config.get_state_arrays()],
          "state_slots": bm.num_state_slots,
          "slots_free_at_the_end": len(bm._free_state_slots),
          "kernel_dispatch": dispatch,
          "max_abs_dlogprob": worst, "tokens_wrong": wrong,
          "wall_s": round(time.monotonic() - t0, 1)})
    drop_engine(engine)
    if jax.default_backend() == "tpu":
        check(all(dispatch.get(f"pallas:{k}") for k in seams)
              and not any(dispatch.get(f"reference:{k}") for k in seams),
              f"{model}: the state layers' seams did not run their "
              f"kernels: {dispatch}")
    check(len(bm._free_state_slots) == bm.num_state_slots,
          "a state slot was not given back")
    check(not wrong and worst < 1e-3,
          f"{model}: prefill + decode through the slots differs from a "
          f"fresh full pass: tokens {wrong}, max |d logprob| {worst:.3g}")


def phase_tp(args) -> None:
    import re

    import jax
    tp = args.chips
    # float32 activations: tp=4 sums four partial products where tp=1
    # sums one, and in bfloat16 that difference alone flips argmaxes.
    precision = exact_f32_matmuls()
    check(len(jax.devices()) >= tp,
          f"--chips {tp} but JAX sees {len(jax.devices())} devices")
    t0 = time.monotonic()
    before = per_device_bytes()
    engine = build_small_engine(args.size, args.seed, tp=tp)
    loaded = per_device_bytes()
    worker = engine.worker
    params_bytes = {}
    for leaf in jax.tree.leaves(worker.params):
        for shard in leaf.addressable_shards:
            params_bytes[shard.device.id] = (
                params_bytes.get(shard.device.id, 0) + shard.data.nbytes)
    pool_bytes = {}
    for k_cache, v_cache in worker.cache_engine.device_cache:
        for arr in (k_cache, v_cache):
            for shard in arr.addressable_shards:
                pool_bytes[shard.device.id] = (
                    pool_bytes.get(shard.device.id, 0) + shard.data.nbytes)
    toks_tp = greedy_tokens(engine, args.size, args.seed)
    top_tp = dict(_TOP_LOGPROBS)
    # The collectives the compiler put into the mixed step.
    text = mixed_step_text(worker)
    collectives = sorted(set(re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)\b", text)))
    custom_calls = text.count("tpu_custom_call")
    drop_engine(engine)
    t1 = time.monotonic()
    engine = build_small_engine(args.size, args.seed, tp=1)
    toks_1 = greedy_tokens(engine, args.size, args.seed)
    drop_engine(engine)
    emit({"phase": "tp", "tensor_parallel_size": tp,
          "layers": SIZES[args.size]["small_layers"],
          "widths": WIDTHS[args.size], "dtype": "float32",
          "f32_matmul_precision": precision,
          "quantization": SIZES[args.size]["quant"],
          "kv_cache_dtype": SIZES[args.size]["kv"],
          "bytes_in_use_before": before, "bytes_in_use_after_load": loaded,
          "param_bytes_per_device": params_bytes,
          "pool_bytes_per_device": pool_bytes,
          "mixed_step_collectives": collectives,
          "mixed_step_tpu_custom_calls": custom_calls,
          "tokens_equal": toks_tp == toks_1,
          "first_tokens_tp": {k: v[:6] for k, v in sorted(toks_tp.items())},
          "tp_s": round(t1 - t0, 1),
          "tp1_s": round(time.monotonic() - t1, 1)})
    check(len(params_bytes) == tp and all(params_bytes.values()),
          f"not every device holds weights: {params_bytes}")
    check(len(pool_bytes) == tp and all(pool_bytes.values()),
          f"not every device holds a pool shard: {pool_bytes}")
    check(all(d["bytes_in_use"] != 0 for d in loaded),
          f"a device is empty after load: {loaded}")
    check(collectives, "no collective in the tp mixed step")
    if toks_tp != toks_1:
        explain_divergence(toks_tp, top_tp, toks_1, dict(_TOP_LOGPROBS))
        fail(f"greedy tokens differ, tp={tp} vs tp=1")


def mixed_step_text(worker) -> str:
    """The compiled mixed step at the narrowest bucket, lowered from
    shapes as the memory profile does."""
    import jax

    runner = worker.model_runner
    b = w = runner.mixed_token_buckets[0]
    caches = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        worker.cache_engine.device_cache)
    return runner.lower_mixed_step(caches, b, w,
                                   do_random=False).compile().as_text()


# --- main -------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel phase (tp=4 "
                    "against tp=1) on a four-chip host")
    ap.add_argument("--size", default="7b", choices=sorted(SIZES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on whatever backend there is "
                    "(CPU rehearsal); still refuses at the end")
    ap.add_argument("--phase", default=None,
                    choices=("device", "parity", "state", "tp"),
                    help="internal: run one child phase in this process")
    args = ap.parse_args()

    if args.phase is not None:
        {"device": phase_device, "parity": phase_parity,
         "state": phase_state, "tp": phase_tp}[args.phase](args)
        return

    check(os.path.isdir(os.path.join(REPO, "intellillm_tpu")),
          f"the intellillm_tpu package is not next to {__file__}")
    os.makedirs(WORK_DIR, exist_ok=True)
    t0 = time.monotonic()
    # Nothing goes to stdout before the device is known: without an
    # accelerator this prints no result at all.
    [device] = run_phase_child("device", args, relay=False)
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        fail(f"JAX found no accelerator (platform {device['platform']!r})",
             code=2)
    emit(device)
    if args.chips == 4:
        check(device["device_count"] >= 4,
              f"--chips 4 but JAX sees {device['device_count']} device(s)")
        run_phase_child("tp", args)
    else:
        phase_serve(args, expect_tpu=on_tpu)
        run_phase_child("parity", args)
        run_phase_child("state", args)
    emit({"phase": "all", "wall_s": round(time.monotonic() - t0, 1)})
    if not on_tpu:
        fail("rehearsal complete; refusing: JAX found no accelerator "
             f"(platform {device['platform']!r})", code=2)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
