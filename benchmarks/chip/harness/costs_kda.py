"""Operations and bytes of what `solar-open2-ep8-l4` adds to the kernels,
from their shapes: one call of the delta rule's step form (`_kda_step_call`)
and of its chunk form (`_kda_chunk_call`), `ops/pallas/kda.py`. Kept with
the benchmark (as costs.py is) so that no PR that claims a gain can change
the yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`linear_attn_dev_share` matches), rows from
`intellillm_linear_attn_rows_total{form}`, state bytes from
`intellillm_dispatch_state_bytes_total`.

Counted is what the recurrence needs, not what a form of it spends: a
head's state `[d_k, d_v]` float32 is read and written once a token (step)
or once a run and chunk-boundary (chunk); the arithmetic of a token and head
is the decay (d_k d_v), S'^T k (2 d_k d_v), the rank-1 write (2 d_k d_v) and
S^T q (2 d_k d_v) = 7 d_k d_v. The chunk form's extra products (the WY
triangle, the float32 passes of the MXU) are work the chip does and the
recurrence does not need, so a share of the roofline reads them as lost
time. The float32 peak of the vector units is not in peaks.json: the step
form is held to the HBM rate alone (its intensity is 7 / 8 of an operation
a byte).
"""
from __future__ import annotations

STATE_BYTES = 4          # the matrix state is float32 whatever the model's


def state_bytes(heads: int, d_k: int, d_v: int) -> int:
    """One sequence's matrix state in one layer."""
    return heads * d_k * d_v * STATE_BYTES


def kda_flops(rows: int, heads: int, d_k: int, d_v: int) -> int:
    """Either form, `rows` live tokens."""
    return 7 * rows * heads * d_k * d_v


def vector_bytes(rows: int, heads: int, d_k: int, d_v: int,
                 lane_tile: int = 128) -> int:
    """What a call reads and writes beside the state, float32: the step
    form's transposed `[rows, 4, d_k, heads]` operand (heads padded to a
    lane tile), v in and o out."""
    padded_heads = -(-heads // lane_tile) * lane_tile
    return 4 * rows * (4 * d_k * padded_heads + 2 * heads * d_v)


def kda_step_bytes(rows: int, heads: int, d_k: int, d_v: int) -> int:
    """`_kda_step_call`: every live row's state in and out, and its
    vectors."""
    return (2 * rows * state_bytes(heads, d_k, d_v)
            + vector_bytes(rows, heads, d_k, d_v))


def kda_chunk_bytes(rows: int, runs: int, heads: int, d_k: int,
                    d_v: int, from_zero: int = 0) -> int:
    """`_kda_chunk_call`: `runs` runs of rows each write their state
    once, and read it once unless they start at position 0 (`from_zero`
    of them); the rows' q, k, b k, b v, g in and o out by head."""
    return ((2 * runs - from_zero) * state_bytes(heads, d_k, d_v)
            + 4 * rows * heads * (4 * d_k + 2 * d_v))
