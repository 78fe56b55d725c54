"""Operations and bytes of what `granite-4.0-h-small-ep4-l10` adds to the
kernels, from their shapes: one call of the state-space scan's step form
(`_ssd_step_call`) and of its chunk form (`_ssd_chunk_call`),
`ops/pallas/ssd.py`. Kept with the benchmark (as costs.py is) so that no PR
that claims a gain can change the yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`ssm_dev_share` matches), rows from
`intellillm_linear_attn_rows_total{form}`, state bytes from
`intellillm_dispatch_state_bytes_total`.

Counted is what the recurrence needs, not what a form of it spends: a
layer's state `[d_state, inner]` float32 (inner = heads x head width) is
read and written once a token (step) or once a run (chunk); the arithmetic
of a token and state element is the decay (1), the rank-1 write B (dt x)^T
(2) and the contraction with C (2) = 5 operations. The float32 peak of the
vector units is not in peaks.json: both forms are held to the HBM rate alone
(the step form's intensity is 5 / 8 of an operation a byte).
"""
from __future__ import annotations

STATE_BYTES = 4          # the matrix state is float32 whatever the model's


def state_bytes(inner: int, d_state: int) -> int:
    """One sequence's matrix state in one layer."""
    return inner * d_state * STATE_BYTES


def ssd_flops(rows: int, inner: int, d_state: int) -> int:
    """Either form, `rows` live tokens."""
    return 5 * rows * inner * d_state


def vector_bytes(rows: int, inner: int, d_state: int,
                 lane_tile: int = 128) -> int:
    """What a call reads and writes beside the state, float32: each
    channel's decay and dt x in, y out, and B and C as columns `[d_state,
    2]`, which the device pads to a lane tile."""
    return 4 * rows * (3 * inner + d_state * lane_tile)


def ssd_step_bytes(rows: int, inner: int, d_state: int) -> int:
    """`_ssd_step_call`: every live row's state in and out, and its
    vectors."""
    return (2 * rows * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state))


def ssd_chunk_flops(rows: int, inner: int, d_state: int) -> int:
    """`_ssd_chunk_call` walks the recurrence itself: the same count."""
    return ssd_flops(rows, inner, d_state)


def ssd_chunk_bytes(rows: int, runs: int, inner: int, d_state: int,
                    from_zero: int = 0) -> int:
    """`_ssd_chunk_call`: `runs` runs of rows each write their state
    once, and read it once unless they start at position 0 (`from_zero`
    of them); the rows' vectors as the step form's."""
    return ((2 * runs - from_zero) * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state))
