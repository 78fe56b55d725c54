"""The start-up path hides nothing: what replaced bench.py's backend probe.

bench.py asks `jax.devices()` in-process and refuses to run without a TPU
unless a CPU run was asked for by name; the compile cache is placed from
outside or at one fixed in-checkout path; the native library is keyed on
its source; replica children get chips of their own; a TPU that reports
no memory limit is an error, not a 16 GiB guess.
"""
import json
import os
import subprocess
import sys

import pytest

import bench
from intellillm_tpu import native, utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Device:
    def __init__(self, platform, kind="TPU v5 lite", stats=None):
        self.platform = platform
        self.device_kind = kind
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_bench_refuses_without_tpu(monkeypatch, capsys):
    """No TPU and no explicit JAX_PLATFORMS=cpu: one error line, exit 2,
    nothing on stdout (no skip record, no exit 0)."""
    import jax
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda: [_Device("cpu", "cpu")])
    with pytest.raises(SystemExit) as exc:
        bench.require_device()
    assert exc.value.code == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert err.count("\n") == 1 and "no TPU" in err


def test_bench_record_names_device():
    """The tiny size runs on CPU only when JAX_PLATFORMS=cpu says so, and
    its record names platform, device kind and device count."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", INTELLILLM_BENCH_SIZE="tiny",
               INTELLILLM_BENCH_BS="2", INTELLILLM_BENCH_IN="8",
               INTELLILLM_BENCH_OUT="4", INTELLILLM_BENCH_K="2")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert rec["device_count"] >= 1
    assert rec["metric"] not in ("skipped", "error") and rec["value"] > 0


def _cache_dir_after_enable(env_value):
    """What the compile-cache config holds after the engine's call, in a
    fresh process (the setting is process-global)."""
    code = ("import jax\n"
            "from intellillm_tpu.utils import "
            "enable_persistent_compilation_cache as f\n"
            "print(repr((f(), jax.config.jax_compilation_cache_dir)))\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("INTELLILLM_COMPILE_CACHE", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return eval(r.stdout.strip().splitlines()[-1])


def test_compile_cache_env_set_is_untouched(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and none set in
    code."""
    outside = str(tmp_path / "placed-from-outside")
    returned, configured = _cache_dir_after_enable(outside)
    assert returned == outside and configured == outside


def test_compile_cache_default_is_fixed_in_checkout():
    """Unset: one fixed path inside the checkout — never a pid, time or
    temp-dir name, which would never hit."""
    returned, configured = _cache_dir_after_enable(None)
    assert returned == configured == os.path.join(REPO, ".cache", "jax")
    again, _ = _cache_dir_after_enable(None)
    assert again == returned
    assert utils.DEFAULT_COMPILE_CACHE_DIR == returned


def test_native_library_keyed_on_source_hash(tmp_path):
    """The library's name carries the source's hash: a stale or foreign
    .so in the tree is not picked up whatever its mtime, and changed
    source means another file."""
    src = tmp_path / "batch_prep.cc"
    src.write_text("int f() { return 1; }\n")
    first = native.library_path(str(src))
    stale = tmp_path / "libbatch_prep.so"
    stale.write_bytes(b"not a library")
    os.utime(stale, (2**31, 2**31))         # newer than any source
    assert native.library_path(str(src)) == first != str(stale)
    src.write_text("int f() { return 2; }\n")
    assert native.library_path(str(src)) != first
    # The library this process loaded is the one named after the source
    # git has.
    assert native.path_taken() == "native"
    assert os.path.exists(native.library_path())


def test_replica_children_get_their_own_chips():
    """Child i of width w sees chips [i*w, (i+1)*w), the checkout is on
    its path, and asking for more chips than the host has is an error."""
    base = {"PATH": "/usr/bin"}
    envs = [utils.child_process_env(base, child_index=i, num_chips=4)
            for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 4
    assert all(e["PYTHONPATH"].split(os.pathsep)[0] == REPO for e in envs)
    two = utils.child_process_env(base, child_index=1, chips_per_child=2,
                                  num_chips=4)
    assert two["TPU_VISIBLE_CHIPS"] == "2,3"
    assert two["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError, match="exceeds the chips"):
        utils.child_process_env(base, child_index=2, chips_per_child=2,
                                num_chips=4)
    # A CPU host (no chips) and a lone child keep chip visibility as is.
    assert "TPU_VISIBLE_CHIPS" not in utils.child_process_env(
        base, child_index=3, num_chips=0)
    assert "TPU_VISIBLE_CHIPS" not in utils.child_process_env(base)
    # The router's launcher reads the width off the engine flags.
    from intellillm_tpu.router.replica import _tensor_parallel_size
    assert _tensor_parallel_size(["--model", "m", "-tp", "2"]) == 2
    assert _tensor_parallel_size(["--tensor-parallel-size=4"]) == 4
    assert _tensor_parallel_size(["--model", "m"]) == 1


def test_device_memory_without_limit_is_an_error_on_tpu():
    """A TPU-named backend whose memory_stats() has no bytes_limit
    raises; the CPU test path keeps its explicit stand-in."""
    with pytest.raises(RuntimeError, match="bytes_limit"):
        utils.get_device_memory_bytes(_Device("tpu", stats={}))
    with pytest.raises(RuntimeError, match="bytes_limit"):
        utils.get_device_memory_bytes(_Device("tpu", stats=None))
    assert utils.get_device_memory_bytes(
        _Device("tpu", stats={"bytes_limit": 123})) == 123
    assert utils.get_device_memory_bytes(
        _Device("cpu", "cpu")) == 16 * 1024**3
