"""Operations and bytes of what `phi-4-mini-flash-reasoning` adds, from its
shapes: the Mamba-1 selective scan's step form (`_sscan_step_call`) and chunk
form (`_sscan_chunk_call`, `ops/pallas/selective_scan.py`), a decode row's
eight walks of the ONE full pool and eight walks of the window pools, and a
whole 64-row token-step. Kept with the benchmark so that no PR that claims a
gain can change the yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`phi4flash_scan_dev_share` matches), rows from
`intellillm_linear_attn_rows_total{form}`, state bytes from
`intellillm_dispatch_state_bytes_total`, walked K/V bytes from
`intellillm_dispatch_kv_walk_bytes_total` and, of them, the cross layers'
from `intellillm_dispatch_shared_kv_walk_bytes_total`.

Counted is what the recurrence needs: a layer's state `[d_state, inner]`
float32 read and written once a token (step) or once a run (chunk); per
token and state element one `exp` and six operations (dt x A, the product
with the state, B x u, the sum, the product with C, the sum over the state
channels); `dt`, `u` and `y` are rows of `inner` float32, B and C columns
`[d_state, 2]` which the device pads to a lane tile; `A` `[d_state, inner]`
float32 is read once a call.
"""
from __future__ import annotations

STATE_BYTES = 4          # the matrix state is float32 whatever the model's

# The published widths (configs/phi-4-mini-flash-reasoning.json).
HIDDEN, INTER, VOCAB = 2560, 10240, 200064
INNER, D_STATE, DT_RANK = 5120, 16, 160
SCAN_LAYERS, WINDOW_LAYERS, FULL_WALKERS, SHARED_WALKERS = 9, 8, 8, 7
GMU_LAYERS, CROSS_LAYERS = 7, 7
WINDOW = 512
KV_TOKEN_BYTES = 2 * 10 * 128 * 2     # K and V, 10 stored heads of 128, bf16


def state_bytes(inner: int = INNER, d_state: int = D_STATE) -> int:
    """One sequence's matrix state in one layer."""
    return inner * d_state * STATE_BYTES


def sscan_flops(rows: int, inner: int = INNER, d_state: int = D_STATE) -> int:
    """Either form, `rows` live tokens: 6 operations and one `exp` a state
    element (the `exp` counted as one)."""
    return 7 * rows * inner * d_state


def vector_bytes(rows: int, inner: int = INNER, d_state: int = D_STATE,
                 lane_tile: int = 128) -> int:
    """What a call reads and writes beside the states, float32: `dt` and
    `dt x` in, `y` out, B and C as columns `[d_state, 2]` padded to a lane
    tile, and `A` once."""
    return (4 * rows * (3 * inner + d_state * lane_tile)
            + 4 * inner * d_state)


def sscan_step_bytes(rows: int, inner: int = INNER,
                     d_state: int = D_STATE) -> int:
    """`_sscan_step_call`: every live row's state in and out, and its
    vectors."""
    return (2 * rows * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state))


def sscan_chunk_bytes(rows: int, runs: int, inner: int = INNER,
                      d_state: int = D_STATE, from_zero: int = 0) -> int:
    """`_sscan_chunk_call`: `runs` runs of rows each write their state
    once, and read it once unless they start at position 0 (`from_zero` of
    them); the rows' vectors as the step form's."""
    return ((2 * runs - from_zero) * state_bytes(inner, d_state)
            + vector_bytes(rows, inner, d_state))


def row_walk_bytes(context: int, block: int = 16) -> dict:
    """K/V bytes one decode row's sixteen walks move at `context` tokens
    (whole pages): the full pool's eight (layer 17's own and the seven
    cross layers', which hold nothing) and the eight window layers'."""
    pages = -(-context // block) * block
    window_pages = min(pages, -(-WINDOW // block) * block + block)
    return {"full_own": KV_TOKEN_BYTES * pages,
            "full_shared": SHARED_WALKERS * KV_TOKEN_BYTES * pages,
            "window": WINDOW_LAYERS * KV_TOKEN_BYTES * window_pages}


def shared_walk_share(context: int) -> float:
    """`shared_kv_walk_share` at one context: 7 c / (8 c + 8 x 512) by
    whole pages."""
    w = row_walk_bytes(context)
    return 100.0 * w["full_shared"] / sum(w.values())


def weight_bytes(weight_bytes_per_param: int = 2) -> dict:
    """The parameters a token-step reads, by kind of layer (bf16)."""
    mlp = 3 * HIDDEN * INTER
    scan = (HIDDEN * 2 * INNER + INNER * (DT_RANK + 2 * D_STATE)
            + DT_RANK * INNER + INNER * HIDDEN + 4 * INNER + 2 * INNER
            + INNER * D_STATE)
    attn = HIDDEN * (HIDDEN + 2 * HIDDEN // 2) + HIDDEN * HIDDEN
    cross = 2 * HIDDEN * HIDDEN
    gmu = 2 * HIDDEN * INNER
    b = weight_bytes_per_param
    return {"scan": SCAN_LAYERS * (scan + mlp) * b,
            "attention": (WINDOW_LAYERS + 1) * (attn + mlp) * b,
            "gmu": GMU_LAYERS * (gmu + mlp) * b,
            "cross": CROSS_LAYERS * (cross + mlp) * b,
            "head": VOCAB * HIDDEN * b}


def token_step_bytes(rows: int, mean_context: int) -> dict:
    """What one decode substep of `rows` live rows at `mean_context`
    tokens each moves through HBM, by part: the weights once, every row's
    walks, every row's nine states in and out."""
    walks = row_walk_bytes(mean_context)
    return {"weights": sum(weight_bytes().values()),
            "full_walks": rows * (walks["full_own"] + walks["full_shared"]),
            "window_walks": rows * walks["window"],
            "scan_state": rows * SCAN_LAYERS * 2 * state_bytes()}
