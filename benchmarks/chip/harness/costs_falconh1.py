"""Operations and bytes of what `falcon-h1-34b-l6` adds to the kernels, from
their shapes: the state-space scan's step form (`_ssd_step_call`) and chunk
form (`_ssd_chunk_call`, `ops/pallas/ssd.py`) with `groups` groups of B and
C, and the K/V bytes a decode row's page walk moves in a layer that holds a
scan BESIDE its attention. With one group every count here is
`costs_ssd.py`'s (tests/chipbench/test_falconh1_cell.py holds them equal).
Kept with the benchmark so that no PR that claims a gain can change the
yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`ssm_dev_share` matches), rows from `intellillm_linear_attn_rows_total{form}`,
state bytes from `intellillm_dispatch_state_bytes_total`, walked K/V bytes
from `intellillm_dispatch_kv_walk_bytes_total`.

Counted is what the recurrence needs, as in costs_ssd.py: a layer's state
`[d_state, inner]` float32 read and written once a token (step) or once a run
(chunk); 5 operations a token and state element whatever the number of
groups (a head reads one group's B and C); B and C are `2 G N` values a row,
not `2 N`, as columns `[G N, 2]` the device pads to a lane tile.
"""
from __future__ import annotations

STATE_BYTES = 4          # the matrix state is float32 whatever the model's


def state_bytes(inner: int, d_state: int) -> int:
    """One sequence's matrix state in one layer."""
    return inner * d_state * STATE_BYTES


def ssd_flops(rows: int, inner: int, d_state: int, groups: int = 1) -> int:
    """Either form, `rows` live tokens: the groups change which column a
    head reads, not how many operations."""
    del groups
    return 5 * rows * inner * d_state


def vector_bytes(rows: int, inner: int, d_state: int, groups: int = 1,
                 lane_tile: int = 128) -> int:
    """What a call reads and writes beside the state, float32: each
    channel's decay and dt x in, y out, and B and C as columns `[groups x
    d_state, 2]`, which the device pads to a lane tile."""
    return 4 * rows * (3 * inner + groups * d_state * lane_tile)


def ssd_step_bytes(rows: int, inner: int, d_state: int,
                   groups: int = 1) -> int:
    """`_ssd_step_call`: every live row's state in and out, and its
    vectors."""
    return (2 * rows * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state, groups))


def ssd_chunk_bytes(rows: int, runs: int, inner: int, d_state: int,
                    groups: int = 1, from_zero: int = 0) -> int:
    """`_ssd_chunk_call`: `runs` runs of rows each write their state once,
    and read it once unless they start at position 0 (`from_zero` of
    them); the rows' vectors as the step form's."""
    return ((2 * runs - from_zero) * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state, groups))


def kv_token_bytes(num_kv_heads: int, head: int, kv_bytes: float,
                   layers: int = 1) -> float:
    """K and V of one token over `layers` layers with a paged pool."""
    return 2 * num_kv_heads * head * kv_bytes * layers


def kv_walk_bytes(walked_tokens: int, num_kv_heads: int, head: int,
                  kv_bytes: float, layers: int = 1) -> float:
    """What the attention kernels' page walks move: the program's own count
    of walked tokens (`intellillm_dispatch_walked_tokens_total`: whole
    pages) x a token's K/V bytes. The parallel layer's other cache."""
    return walked_tokens * kv_token_bytes(num_kv_heads, head, kv_bytes,
                                          layers)


def crossover_context(inner: int, d_state: int, num_kv_heads: int,
                      head: int, kv_bytes: float) -> float:
    """Tokens of context at which a decode row's K/V walk in a layer
    outweighs its state moved in and out: 4,096 at Falcon-H1-34B's widths
    in bf16."""
    return (2 * state_bytes(inner, d_state)
            / kv_token_bytes(num_kv_heads, head, kv_bytes))
