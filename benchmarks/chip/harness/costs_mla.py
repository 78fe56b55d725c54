"""Operations and bytes of the latent-attention (MLA) kernels, from their
shapes. Kept with the benchmark (as costs.py is) so that no PR that claims a
gain can change the yardstick.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md: kernel time from `breakdown.device_ops` (the operations
`attn_dev_share` matches: the kernels' names carry its prefix), rows and
context from
`intellillm_dispatch_row_steps_total` / `decode_ctx_tokens_per_row`.

The absorbed form (intellillm_tpu/layers/mla_attention.py): every one of the
`num_heads` query heads scores a context token against ONE stored vector of
`width` = kv_lora_rank + qk_rope_head_dim values and accumulates its first
kv_lora_rank values, so a context token costs a row
2 * (width + kv_lora_rank) * num_heads operations and `stored_width` * bytes
(the vector is stored zero-padded to whole 128-lane tiles, 576 -> 640, and
the pad lanes are read too: they are counted, because the chip moves them).
"""
from __future__ import annotations


def stored_width(kv_lora_rank: int, rope_dim: int) -> int:
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def latent_walk_flops(context_lens, num_heads: int, kv_lora_rank: int,
                      rope_dim: int) -> int:
    """QK^T over the kv_lora_rank + rope_dim lanes that carry values and PV
    over kv_lora_rank, for one query token a row."""
    per_token = 2 * ((kv_lora_rank + rope_dim) + kv_lora_rank) * num_heads
    return sum(per_token * c for c in context_lens)


def latent_walk_bytes(context_lens, kv_lora_rank: int, rope_dim: int,
                      kv_bytes: float) -> float:
    """Every row reads its own context once (rows share nothing), each
    token one stored vector: no second (V) stream."""
    return sum(stored_width(kv_lora_rank, rope_dim) * c * kv_bytes
               for c in context_lens)


def latent_write_bytes(rows: int, block_size: int, kv_lora_rank: int,
                       rope_dim: int, kv_bytes: float) -> float:
    """The mixed step's fused write: a row's slot is written by a
    read-modify-write of its [block_size, stored_width] page slab (read
    once, written once)."""
    return (2 * rows * block_size * stored_width(kv_lora_rank, rope_dim) *
            kv_bytes)


def query_bytes(rows: int, num_heads: int, kv_lora_rank: int, rope_dim: int,
                act_bytes: float = 2) -> float:
    """The absorbed query in ([H, stored_width]) and the attended latent
    out ([H, kv_lora_rank]) of every row."""
    return rows * num_heads * act_bytes * (
        stored_width(kv_lora_rank, rope_dim) + kv_lora_rank)
