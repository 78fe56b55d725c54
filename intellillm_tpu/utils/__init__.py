"""Shared utilities.

Role parity: reference `vllm/utils.py` (Counter, random_uuid, memory helpers).
TPU-first additions: shape-bucketing helpers (the XLA analogue of the
reference's CUDA-graph capture sizes, `vllm/worker/model_runner.py:26-28`).
"""
from __future__ import annotations

import enum
import os
import uuid
from typing import Any, Dict, Iterable, List, Optional, Sequence


class Device(enum.Enum):
    DEVICE = "device"  # TPU HBM
    CPU = "cpu"        # host memory (swap space)


class Counter:
    """Monotonic counter for request/sequence ids."""

    def __init__(self, start: int = 0) -> None:
        self.counter = start

    def __next__(self) -> int:
        i = self.counter
        self.counter += 1
        return i

    def reset(self) -> None:
        self.counter = 0


def random_uuid() -> str:
    return str(uuid.uuid4().hex)


def cdiv(a: int, b: int) -> int:
    return -(a // -b)


def round_up(x: int, mult: int) -> int:
    return cdiv(x, mult) * mult


def next_power_of_2(x: int) -> int:
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def pad_to_bucket(x: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= x. Buckets must be sorted ascending.

    This is how we bound the number of distinct shapes XLA compiles: every
    (batch, seq-len) is padded up to a bucket so jit caches a small, fixed
    set of executables — the TPU analogue of the reference's CUDA-graph
    batch-size capture list.
    """
    for b in buckets:
        if b >= x:
            return b
    return buckets[-1]


def default_batch_buckets(max_num_seqs: int) -> List[int]:
    """Power-of-two batch buckets up to max_num_seqs."""
    out = []
    b = 1
    while b < max_num_seqs:
        out.append(b)
        b *= 2
    out.append(max_num_seqs)
    return sorted(set(out))


def default_len_buckets(max_len: int, start: int = 16) -> List[int]:
    """Power-of-two sequence-length buckets up to max_len."""
    out = []
    b = start
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return sorted(set(out))


def flatten_2d(lst: Iterable[Iterable[Any]]) -> List[Any]:
    return [x for row in lst for x in row]


# What `get_device_memory_bytes` answers on the CPU backend, which has no
# accelerator memory to report: one v5e chip's HBM.
_CPU_STANDIN_MEMORY_BYTES = 16 * 1024**3

STR_DTYPE_TO_JNP = {
    "float32": "float32",
    "float": "float32",
    "bfloat16": "bfloat16",
    "float16": "float16",
    "half": "float16",
    "fp8_e5m2": "float8_e5m2",
}


def get_device_memory_bytes(device=None) -> int:
    """Total accelerator memory from the live device stats. A TPU that
    reports no `bytes_limit` is an error, not a guess: the KV pool is
    sized from this number. The CPU backend (tests, rehearsals) has no
    device memory and gets an explicit stand-in."""
    import jax

    dev = device or jax.local_devices()[0]
    if dev.platform == "cpu":
        return _CPU_STANDIN_MEMORY_BYTES
    stats = dev.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev.platform} device {dev} reports no memory_stats() "
            "bytes_limit; refusing to size the KV pool from a guess "
            "(pass --num-device-blocks-override to skip profiling)")
    return int(stats["bytes_limit"])


def get_used_device_memory_bytes(device=None) -> int:
    import jax

    dev = device or jax.local_devices()[0]
    try:
        stats = dev.memory_stats()
        if stats and "bytes_in_use" in stats:
            return int(stats["bytes_in_use"])
    except Exception:
        pass
    return 0


def in_test_cpu_mode() -> bool:
    import jax

    return jax.default_backend() == "cpu"


def parse_env_flag(raw):
    """Uniform boolean env parsing shared by the INTELLILLM_* knobs:
    returns True/False for recognized spellings, None for unset/empty or
    unrecognized values (callers decide the default and whether to warn).
    """
    if raw is None:
        return None
    val = raw.strip().lower()
    if not val:
        return None
    if val in ("0", "false", "off", "no"):
        return False
    if val in ("1", "true", "on", "yes"):
        return True
    return None


def pipeline_enabled_env() -> bool:
    """Single source of truth for the INTELLILLM_PIPELINE flag (default
    on) — the engine's stepping mode and the worker's continuation-program
    warm-up must agree, or the first pipelined step pays a mid-serving
    XLA compile."""
    flag = parse_env_flag(os.environ.get("INTELLILLM_PIPELINE"))
    return True if flag is None else flag


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Fixed on purpose: the directory is part of the cache key, so a path
# that moves between processes (pid, time, temp dir) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".cache", "jax")


def enable_persistent_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache so a second process (or
    a restart) skips recompiling the step programs. Where it lives is
    decided from outside: with JAX_COMPILATION_CACHE_DIR set, jax already
    uses that directory and no directory is set here; otherwise the
    cache sits at the fixed in-checkout path DEFAULT_COMPILE_CACHE_DIR.
    INTELLILLM_COMPILE_CACHE=0 switches it off. Returns the directory in
    use (None when off)."""
    if parse_env_flag(os.environ.get("INTELLILLM_COMPILE_CACHE")) is False:
        return None
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def runtime_facts() -> dict:
    """What this process runs on, as JAX reports it: versions, the
    device, the compile-cache directory and the batch-prep path. Rides
    the boot block of /health/detail so a launcher that must stay off
    JAX (one process per chip) can read it from its child."""
    import jax
    import jaxlib

    from intellillm_tpu import native
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform_version": dev.client.platform_version,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "batch_prep": native.path_taken(),
    }


# --- child processes ---------------------------------------------------------

# libtpu's spelling of "this process owns exactly these chips": bounds of
# the chip grid one process drives, keyed by how many chips it gets.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def count_local_chips() -> int:
    """TPU chips on this host, read off the device nodes without loading
    libtpu (a launcher that touched JAX would hold the chips its
    children need). 0 on a host with none."""
    import glob
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def child_process_env(base: Optional[Dict[str, str]] = None, *,
                      child_index: Optional[int] = None,
                      chips_per_child: int = 1,
                      num_chips: Optional[int] = None) -> Dict[str, str]:
    """Environment for a child that runs this checkout's code.

    - The checkout goes on PYTHONPATH, so `python -m intellillm_tpu...`
      works from any cwd without the package being installed.
    - With `child_index`, the child is given its own chips (child i of
      width w sees chips [i*w, (i+1)*w)): every process sees every chip
      by default and a mesh takes the first ones, so N replicas on one
      host would all claim chip 0 and all but one die on libtpu's lock.
      `num_chips` defaults to what this host has; 0 (a CPU host) leaves
      chip visibility alone. Asking for more chips than exist is an
      error here, not a crash in the child.
    """
    env = dict(os.environ if base is None else base)
    paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if _REPO_ROOT not in paths:
        env["PYTHONPATH"] = os.pathsep.join([_REPO_ROOT] + paths)
    if child_index is None:
        return env
    if num_chips is None:
        num_chips = count_local_chips()
    if num_chips == 0:
        return env
    first = child_index * chips_per_child
    if first + chips_per_child > num_chips:
        raise ValueError(
            f"child {child_index} needs chips {first}.."
            f"{first + chips_per_child - 1} but this host has {num_chips} "
            f"(children x chips per child exceeds the chips)")
    if chips_per_child not in _CHIP_BOUNDS:
        raise ValueError(
            f"no chip grid for {chips_per_child} chips per child "
            f"(supported: {sorted(_CHIP_BOUNDS)})")
    chips = ",".join(str(first + i) for i in range(chips_per_child))
    env["TPU_VISIBLE_CHIPS"] = chips
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _CHIP_BOUNDS[chips_per_child]
    env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    # Each single-process "slice" runs its own mesh controller.
    port = 8476 + child_index
    env["TPU_MESH_CONTROLLER_ADDRESS"] = f"localhost:{port}"
    env["TPU_MESH_CONTROLLER_PORT"] = str(port)
    return env
