"""Real-checkpoint load validation on TPU.

Loads a REAL (safetensors, non-dummy) checkpoint through the full
quantize-on-load path on the TPU backend, records wall-clock load time,
and proves first-token correctness by comparing the greedy stream
against the same checkpoint served on CPU (the CPU path is golden-tested
against HF transformers).

The checkpoint is built locally (no network): examples/make_tiny_model.py
writes a genuine safetensors checkpoint + tokenizer, so the exercised
surface is hf_model_weights_iterator -> load_linear -> quantize_int8 ->
shard_params -> device placement — everything a real 7B load runs, at
tiny scale.

Usage:  python benchmarks/real_checkpoint_tpu.py [--model DIR]
Prints one JSON line with load/generate timings and the match verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)
# stdlib-only import: this parent stays off JAX, its children need the chip.
from intellillm_tpu.utils import child_process_env  # noqa: E402

_CHILD = r"""
import json, sys, time
model_dir, quant = sys.argv[1], sys.argv[2]
t0 = time.time()
from intellillm_tpu import LLM, SamplingParams
t_import = time.time() - t0
t0 = time.time()
llm = LLM(model=model_dir, dtype="bfloat16",
          quantization=None if quant == "none" else quant,
          num_device_blocks_override=128, max_model_len=128,
          max_num_seqs=8, swap_space=0.01)
t_load = time.time() - t0
prompts = ["hello my name is", "the capital of france is"]
t0 = time.time()
outs = llm.generate(prompts, SamplingParams(temperature=0.0,
                                            max_tokens=12))
t_gen = time.time() - t0
import jax
print(json.dumps({
    "backend": jax.devices()[0].platform,
    "import_s": round(t_import, 2), "load_s": round(t_load, 2),
    "generate_s": round(t_gen, 2),
    "tokens": [list(o.outputs[0].token_ids) for o in outs],
    "texts": [o.outputs[0].text for o in outs],
}))
"""


def run_backend(model_dir: str, quant: str, cpu: bool) -> dict:
    env = child_process_env()
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _CHILD, model_dir, quant],
                       capture_output=True, text=True, env=env,
                       timeout=1200)
    if r.returncode != 0:
        return {"error": r.stderr.strip().splitlines()[-1:][0]
                if r.stderr.strip() else "unknown"}
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="/tmp/tiny-llama-real")
    ap.add_argument("--quantization", default="int8")
    args = ap.parse_args()

    if not os.path.isdir(args.model):
        subprocess.run([sys.executable,
                        os.path.join(REPO, "examples", "make_tiny_model.py"),
                        "--arch", "llama", "--out", args.model],
                       check=True)

    cpu = run_backend(args.model, args.quantization, cpu=True)
    tpu = run_backend(args.model, args.quantization, cpu=False)
    match = (("tokens" in cpu and "tokens" in tpu)
             and all(c[0] == t[0] for c, t in zip(cpu["tokens"],
                                                  tpu["tokens"])))
    print(json.dumps({
        "metric": "real-checkpoint int8 load on TPU",
        "cpu": cpu, "tpu": tpu,
        "first_token_match": match,
    }))
    return 0 if match else 1


if __name__ == "__main__":
    sys.exit(main())
