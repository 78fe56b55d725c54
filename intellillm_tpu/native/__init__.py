"""ctypes bindings for the native host-side batch-prep kernels.

Role parity: the reference builds its native runtime pieces (`csrc/`)
at install time via setup.py; here the single C++ translation unit
(`native/batch_prep.cc`) is compiled lazily with g++ on first use and
cached next to the source under a name that carries the source's hash.
Without a toolchain the pure-Python paths compute the same arrays
(`is_available()` returns False); which path a process took is logged
once and reported by `path_taken()`.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

from intellillm_tpu.logger import init_logger

logger = init_logger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "batch_prep.cc")

_lib: Optional[ctypes.CDLL] = None
_tried = False
# "native" or "python": which batch-prep path this process uses (None
# until the first call decides).
_path_taken: Optional[str] = None

_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")


def library_path(src: str = _SRC) -> str:
    """Where the library built from `src` lives: the name carries a hash
    of the source, so a library built from other source — a stale or
    foreign .so lying in the tree — is never picked up, whatever its
    mtime."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(src),
                        f"libbatch_prep.{digest}.so")


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _path_taken
    if _tried:
        return _lib
    _tried = True
    from intellillm_tpu.utils import parse_env_flag
    if parse_env_flag(os.environ.get("INTELLILLM_DISABLE_NATIVE")):
        _path_taken = "python"
        logger.info("Batch prep: pure-Python path "
                    "(INTELLILLM_DISABLE_NATIVE)")
        return None
    try:
        lib_path = library_path()
        if not os.path.exists(lib_path):
            # Build to a per-pid temp path and rename: concurrent
            # processes must never dlopen a half-written .so.
            tmp = f"{lib_path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                check=True, capture_output=True)
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
    except (OSError, subprocess.CalledProcessError) as e:
        # No source, no compiler, or a library that will not load: the
        # Python path computes the same arrays.
        _path_taken = "python"
        logger.info("Batch prep: pure-Python path (native library "
                    "unavailable: %s)", e)
        return None
    lib.build_decode_batch.argtypes = [
        _i32p, _i64p, _i32p, _i32p, _i32p,
        ctypes.c_int64, ctypes.c_int64,
        _i32p, _i32p, _i32p, _i32p,
    ]
    lib.build_prompt_slots.argtypes = [
        _i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int32, _i32p,
    ]
    _lib = lib
    _path_taken = "native"
    logger.info("Batch prep: native library %s", lib_path)
    return _lib


def path_taken() -> str:
    """"native" or "python" — decided (and logged) once per process."""
    _load()
    return _path_taken


def is_available() -> bool:
    return _load() is not None


def build_decode_batch(tables, tokens, positions, ctx, padded_n: int,
                       width: int):
    """tables: list of per-seq block-table lists; tokens/positions/ctx:
    per-seq int lists. Returns (token_ids [P,1], positions [P,1],
    context_lens [P], block_tables [P,W]) padded arrays."""
    lib = _load()
    n = len(tables)
    # Identical failure behavior in both paths: an oversized table means
    # the width bucketing and the scheduler disagree — fail loudly rather
    # than truncate the context (the C++ clamp is heap-safety defense
    # only).
    for t in tables:
        if len(t) > width:
            raise ValueError(
                f"block table of {len(t)} blocks exceeds padded width "
                f"{width}")
    out_tokens = np.zeros((padded_n, 1), np.int32)
    out_positions = np.zeros((padded_n, 1), np.int32)
    out_ctx = np.zeros(padded_n, np.int32)
    out_tables = np.zeros((padded_n, width), np.int32)
    if lib is None:
        for i in range(n):
            out_tokens[i, 0] = tokens[i]
            out_positions[i, 0] = positions[i]
            out_ctx[i] = ctx[i]
            out_tables[i, :len(tables[i])] = tables[i]
        return out_tokens, out_positions, out_ctx, out_tables

    # Marshal the Python lists in single C-level passes (fromiter/chain),
    # then the C++ kernel does the padded 2D fills.
    import itertools
    lens = np.fromiter((len(t) for t in tables), np.int64, count=n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    flat = np.fromiter(itertools.chain.from_iterable(tables), np.int32,
                       count=int(offsets[-1]))
    lib.build_decode_batch(flat, offsets,
                           np.asarray(tokens, np.int32),
                           np.asarray(positions, np.int32),
                           np.asarray(ctx, np.int32),
                           n, width,
                           out_tokens.reshape(-1), out_positions.reshape(-1),
                           out_ctx, out_tables.reshape(-1))
    return out_tokens, out_positions, out_ctx, out_tables


def build_prompt_slots(table, prefix_len: int, seq_len: int,
                       block_size: int, window_blocks: Optional[int],
                       pad_slot: int) -> np.ndarray:
    """Slot mapping for tokens [prefix_len, seq_len) of one prompt."""
    lib = _load()
    n_new = seq_len - prefix_len
    if lib is None:
        slots = np.empty(n_new, np.int32)
        k = 0
        for t in range(prefix_len, seq_len):
            logical = t // block_size
            if window_blocks:
                if t < seq_len - window_blocks * block_size:
                    slots[k] = pad_slot
                    k += 1
                    continue
                logical %= window_blocks
            slots[k] = table[logical] * block_size + t % block_size
            k += 1
        return slots
    out = np.empty(n_new, np.int32)
    lib.build_prompt_slots(np.asarray(table, np.int32), prefix_len,
                           seq_len, block_size, window_blocks or 0,
                           pad_slot, out)
    return out
