"""Operations and bytes of what `lfm2-8b-a1b-l14` adds to the kernels, from
their shapes: the paged attention calls at a 64-wide head stored in a
128-lane pool row, and the short convolution's step. Kept with the benchmark
(as costs.py is) so that no PR that claims a gain can change the yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`attn_dev_share` / `lfm2_conv_dev_share` match), rows and context from
`intellillm_dispatch_row_steps_total` / `decode_ctx_tokens_per_row`.

A K/V head has `head` = 64 lanes that carry values and is stored at `stored`
= 128 (the head zero-padded to one lane tile). The operations are counted
over the lanes that carry values: the zero lanes' products are work the chip
does and the model does not need, so a share of the roofline reads the
padding as lost time. The bytes are counted as stored: the chip moves the pad
lanes of K and V (a narrower pool row would be padded to the same tile by the
device). The other choice (two heads a tile) would halve both.
"""
from __future__ import annotations


def stored_head(head: int) -> int:
    return -(-head // 128) * 128


def paged_flops(context_lens, num_heads: int, head: int) -> int:
    """`_paged_attention_call` / `_ragged_call`, one query token a row:
    QK^T and PV over the row's context, over the `head` lanes that carry
    values."""
    return sum(4 * num_heads * head * c for c in context_lens)


def paged_bytes(context_lens, num_kv_heads: int, head: int,
                kv_bytes: float) -> float:
    """Every row reads its own K and V once at the stored width."""
    return sum(2 * num_kv_heads * stored_head(head) * c * kv_bytes
               for c in context_lens)


def ragged_write_bytes(rows: int, block_size: int, num_kv_heads: int,
                       head: int, kv_bytes: float) -> float:
    """The mixed step's fused write: a row's K and V slots are written by a
    read-modify-write of their [block_size, stored] page slabs."""
    return (2 * 2 * rows * num_kv_heads * block_size * stored_head(head)
            * kv_bytes)


def query_bytes(rows: int, num_heads: int, head: int,
                act_bytes: float = 2) -> float:
    """The query in and the output out of every row, at the stored width
    (the layer pads both)."""
    return 2 * rows * num_heads * stored_head(head) * act_bytes


def short_conv_flops(rows: int, hidden: int, taps: int = 3) -> int:
    """`_short_conv_call`, per row and channel: the gate B * x (1), the
    filter (`taps` products, `taps` - 1 sums), the gate C * c (1)."""
    return rows * hidden * (2 * taps + 1)


def short_conv_bytes(rows: int, hidden: int, taps: int = 3,
                     act_bytes: float = 2) -> float:
    """In: the in-projection's three thirds and the `taps` - 1 earlier
    gated inputs of every row, and the filter (float32, padded to 8
    sublanes). Out: y and g."""
    return (rows * hidden * act_bytes * (3 + (taps - 1) + 2)
            + 8 * hidden * 4)


def short_conv_state_bytes(rows: int, hidden: int, taps: int = 3,
                           act_bytes: float = 2) -> float:
    """What a step moves of the state beside the kernel: each row's slot
    gathered and written back (`mixed`), or nothing (the decode programs
    carry the rows' states in the loop and move them once a K steps)."""
    return 2 * rows * (taps - 1) * hidden * act_bytes


def conv_mixer_matmul_flops(rows: int, hidden: int) -> int:
    """The in-projection (hidden -> 3 hidden) and the out-projection."""
    return 2 * rows * hidden * 4 * hidden


def conv_mixer_weight_bytes(hidden: int, weight_bytes: float = 2) -> float:
    return 4 * hidden * hidden * weight_bytes
