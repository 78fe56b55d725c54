"""Operations and bytes a kernel call needs, from its shapes, and the
table of peaks. Kept with the benchmark so that no PR that claims a gain
can change the yardstick.

Nothing reports a `<kernel>_roofline` metric yet: a roofline share needs
the rows and context lengths of every launch, which the program does not
record (PERF.md, Open questions, first item for the `tracing` issue).
These functions are what that metric will divide by kernel time.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error,
    not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def matmul_flops(rows: int, k: int, n: int) -> int:
    return 2 * rows * k * n


def matmul_bytes(rows: int, k: int, n: int, weight_bytes: float,
                 act_bytes: float = 2) -> float:
    """Weights read once, activations read and written once."""
    return k * n * weight_bytes + rows * (k + n) * act_bytes


def paged_attention_flops(context_lens, num_heads: int, head: int) -> int:
    """One query token a row: QK^T and PV over the row's context."""
    return sum(4 * num_heads * head * c for c in context_lens)


def paged_attention_bytes(context_lens, num_kv_heads: int, head: int,
                          kv_bytes: float) -> float:
    """Every row reads its own K and V once (rows share nothing)."""
    return sum(2 * num_kv_heads * head * c * kv_bytes for c in context_lens)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes_per_s
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
