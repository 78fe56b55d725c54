"""Operations and bytes of a paged attention call under a sliding window
(`smallthinker-21ba3b-l8`: `_ragged_call_window` in the `mixed` program,
`_paged_attention_call_window` in the decode programs), from their shapes.
Kept with the benchmark (as costs.py is) so that no PR that claims a gain
can change the yardstick.

A row whose query stands at position p (its context counts p + 1 tokens)
attends to positions p - window < j <= p: `min(context, window)` tokens.
Its K and V come in whole pages of `block_size` tokens, from the page that
holds the first visible position (`lo = max(context - window, 0)`) to the
page that holds the last one: the edge page is moved whole and masked, so
the bytes count it whole and the operations count the visible tokens only.
With `window=None` both are what costs.py gives for the full layers.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the
share into PERF.md: kernel time from `breakdown.device_ops` (the operations
`window_attn_dev_share` matches), rows and contexts from
`intellillm_dispatch_row_steps_total`, `decode_ctx_tokens_per_row` and
`intellillm_dispatch_attended_tokens_total`.
"""
from __future__ import annotations


def attended_tokens(context: int, window=None) -> int:
    """Tokens a row of `context` tokens (its own included) attends to."""
    return context if window is None else min(context, window)


def walked_pages(context: int, block_size: int, window=None) -> int:
    """Pages of K (and of V) the row's walk moves: from the page of its
    first visible position to the page of its last."""
    if context <= 0:
        return 0
    lo = 0 if window is None else max(context - window, 0)
    return -(-context // block_size) - lo // block_size


def window_attention_flops(context_lens, num_heads: int, head: int,
                           window=None) -> int:
    """One query token a row: QK^T and PV over the tokens it attends to."""
    return sum(4 * num_heads * head * attended_tokens(c, window)
               for c in context_lens)


def window_attention_bytes(context_lens, num_kv_heads: int, head: int,
                           kv_bytes: float, block_size: int,
                           window=None) -> float:
    """Every row reads its own pages of K and V once, whole (rows share
    nothing: a chunk's rows each walk their context)."""
    return sum(2 * num_kv_heads * head * kv_bytes * block_size
               * walked_pages(c, block_size, window) for c in context_lens)


def ragged_write_bytes(rows: int, block_size: int, num_kv_heads: int,
                       head: int, kv_bytes: float) -> float:
    """The mixed step's fused write: a row's K and V slots are written by
    a read-modify-write of their [block_size, head] page slabs."""
    return 2 * 2 * rows * num_kv_heads * block_size * head * kv_bytes


def query_bytes(rows: int, num_heads: int, head: int,
                act_bytes: float = 2) -> float:
    """The query in and the output out of every row."""
    return 2 * rows * num_heads * head * act_bytes


def chunk_context_lens(start: int, size: int):
    """Contexts of the rows of a prefill chunk of positions
    [start, start + size): row i's counts its own token."""
    return range(start + 1, start + size + 1)


def relu_grouped_ffn_flops(assignments: int, hidden: int, inter: int) -> int:
    """`_grouped_gate_up_call` + `_grouped_down_call` over `assignments`
    routed (row, expert) pairs: gate, up and down products."""
    return 2 * assignments * hidden * inter * 3


def relu_grouped_ffn_weight_bytes(experts_hit: int, hidden: int, inter: int,
                                  weight_bytes: float = 2) -> float:
    """Each expert that has a row has its three matrices read about once
    (PERF.md section 6, PR 31)."""
    return 3 * experts_hit * hidden * inter * weight_bytes
