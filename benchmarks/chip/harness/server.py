"""The system under test: the real OpenAI server as a child process.

The parent (run.py) never imports jax: a chip belongs to one process at a
time and the server child needs it. Everything the harness learns from
the server it learns over HTTP (`/health`, `/health/detail`, `/metrics`,
`/v1/completions`, `/debug/profiler/*`).

The pattern (child + token-id prompts + boot facts from `/health/detail`)
is the one `chip_smoke.py` proved on the chip in PR 21; it is copied here
so that the yardstick does not move when that file does.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

# benchmarks/chip/harness/server.py -> the checkout's root
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
WORK_DIR = os.path.join(ROOT, ".cache", "chipbench")

# Keys of a configuration file that belong to the benchmark and not to
# the model's published config.json.
BENCH_KEYS = ("name", "source", "entered_by_hand", "published", "assumed",
              "deployment", "serve", "rehearsal", "notes")


# What the compile cache of one checkout has to hold: every cell's
# programs go to the one directory (a Mistral-7B cell writes ~470 MB:
# a 32-layer fused-decode program is a 48 MB entry, my chip run c5, PR 23).
CACHE_BYTES_A_CHECKOUT_NEEDS = 2 * 1024**3


class HarnessError(RuntimeError):
    """A run that cannot produce a result line."""


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


def hf_config_of(config: dict, rehearsal: bool) -> dict:
    """The config.json the server reads: the configuration file's
    published keys as they are run; on a CPU rehearsal the file's tiny
    preset is laid over them."""
    hf = {k: v for k, v in config.items() if k not in BENCH_KEYS}
    if rehearsal:
        hf.update(config["rehearsal"]["hf_config"])
    return hf


def make_model_dir(config: dict, rehearsal: bool) -> str:
    """config.json + a self-contained word-level tokenizer covering the
    whole vocabulary, for `--load-format dummy`. Written once per
    configuration under .cache/chipbench/ (git-ignored); a directory whose
    config.json already says the same is left alone."""
    hf = hf_config_of(config, rehearsal)
    name = config["name"] + ("-rehearsal" if rehearsal else "")
    out = os.path.join(WORK_DIR, name)
    cfg_path = os.path.join(out, "config.json")
    text = json.dumps(hf, indent=1, sort_keys=True)
    if (os.path.exists(cfg_path) and open(cfg_path).read() == text
            and os.path.exists(os.path.join(out, "tokenizer.json"))):
        return out
    os.makedirs(out, exist_ok=True)
    # (copied from benchmarks/common.py save_dummy_checkpoint)
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast
    vocab = {"<pad>": 0, "</s>": 1, "<unk>": 2}
    for i in range(hf["vocab_size"] - len(vocab)):
        vocab[f"w{i:06d}"] = len(vocab)
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", eos_token="</s>",
        unk_token="<unk>").save_pretrained(out)
    with open(cfg_path, "w") as f:
        f.write(text)
    return out


def child_env(rehearsal: bool) -> tuple:
    """(environment, what was done about the compile cache's size cap).
    The child runs this checkout's code (it is not installed) and keeps
    its compile cache where it was put from outside, else at the engine's
    own fixed in-checkout path: the harness moves nothing."""
    env = dict(os.environ)
    paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if ROOT not in paths:
        env["PYTHONPATH"] = os.pathsep.join([ROOT] + paths)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".cache", "jax"))
    # A machine may cap the cache's size (the builder's chip tool sets
    # JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB). Under a cap smaller
    # than what the cells write, the least-recently-used eviction throws
    # every entry out before the next run reads it, every run compiles
    # everything, and a 32-layer cell's set-up (326 s, my chip run c4,
    # PR 23) no longer fits a run's time limit. So such a cap is raised
    # for the server, and the run says so on its `booted` line and on
    # stderr; none is ever lowered or added.
    cap = env.get("JAX_COMPILATION_CACHE_MAX_SIZE")
    note = {"found": cap, "set": cap}
    if cap is not None and 0 <= int(cap) < CACHE_BYTES_A_CHECKOUT_NEEDS:
        note["set"] = str(CACHE_BYTES_A_CHECKOUT_NEEDS)
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = note["set"]
        print(f"chipbench: JAX_COMPILATION_CACHE_MAX_SIZE={cap} is under "
              f"what the cells' programs need; the server runs with "
              f"{note['set']}", file=sys.stderr, flush=True)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("TRANSFORMERS_OFFLINE", "1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env, note


class Server:
    """One server child; `with Server(...) as s:` stops it and waits."""

    def __init__(self, config: dict, seed: int, *, rehearsal: bool,
                 profiling: bool, boot_timeout_s: float):
        self.config = config
        self.rehearsal = rehearsal
        self.boot_timeout_s = boot_timeout_s
        self.model_dir = make_model_dir(config, rehearsal)
        self.vocab = hf_config_of(config, rehearsal)["vocab_size"]
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        serve = dict(config["serve"])
        if rehearsal:
            serve.update(config["rehearsal"]["serve"])
        # The server's --seed is a 32-bit PRNG seed; the driver's seeds
        # are larger than 2**31.
        cmd = [sys.executable, "-m",
               "intellillm_tpu.entrypoints.openai.api_server",
               "--model", self.model_dir, "--load-format", "dummy",
               "--seed", str(seed % (2**31 - 1)),
               "--host", "127.0.0.1", "--port", str(self.port),
               "--disable-log-requests"]
        for key in ("dtype", "quantization", "kv_cache_dtype",
                    "max_model_len"):
            if serve.get(key) is not None:
                cmd += ["--" + key.replace("_", "-"), str(serve[key])]
        cmd += [str(x) for x in serve.get("flags", [])]
        if profiling:
            cmd.append("--enable-profiling")
        self.cmd = cmd
        self.log_path = os.path.join(WORK_DIR, f"server-{config['name']}.log")
        self.env, self.cache_cap = child_env(rehearsal)
        self.proc = None

    def __enter__(self) -> "Server":
        os.makedirs(WORK_DIR, exist_ok=True)
        with open(self.log_path, "wb") as log:
            # cwd elsewhere on purpose: the child finds the package
            # through its environment, not through the current directory.
            self.proc = subprocess.Popen(
                self.cmd, env=self.env, cwd=WORK_DIR,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        proc = self.proc
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        # Whatever the server started goes with it.
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + self.boot_timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise HarnessError(
                    f"server exited {self.proc.returncode} before it was "
                    f"healthy\n{self.log_tail()}")
            try:
                with urllib.request.urlopen(self.base + "/health", timeout=2):
                    return
            except (OSError, urllib.error.URLError):
                time.sleep(0.5)
        raise HarnessError(
            f"server not healthy after {self.boot_timeout_s:.0f}s\n"
            f"{self.log_tail()}")

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return ("--- server log tail ---\n"
                        + f.read()[-n:].decode(errors="replace"))
        except OSError:
            return "(no server log)"

    def detail(self) -> dict:
        return http_json(self.base + "/health/detail", timeout=60)
