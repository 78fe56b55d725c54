"""A step's rows of a 2-d state pool, read and written by slot.

A state layer keeps a few rows of activations a sequence as one row a
slot of a pool `[slots, width]` (`ModelConfig.get_state_arrays`: LFM2's
`[128, 4096]`, a delta-rule layer's taps `[128, 73728]`). Every program
reads its rows' slots and writes them back through the two functions
here: the K-step decode programs once a step around their stage
(`worker/model_runner.py` `_decode_fn`), `mixed` in the layer
(`layers/short_conv.py`, `layers/linear_attention.py`).

A slot outside the pool (a pad row's, a row's that does not write) reads
zeros and writes nothing. Both ways only move bytes (a gather, a
select): the result is bit for bit what `pool.at[slots].get(mode="fill",
fill_value=0)` / `pool.at[slots].set(rows, mode="drop")` give, for every
dtype, NaN, inf and -0.0 included, and a slot nobody writes keeps its
bytes.

Why `scatter_rows` is not that `.at[].set`: at a row of 147 KB XLA's TPU
compiler expands the scatter into a loop of one `dynamic-update-slice` a
row, 13 us a turn on a v5e whatever the row holds (a 512-row `mixed` step
of three such layers paid 20.6 ms for it, PERF.md section 6, PR 40);
rows up to 48 KB get a native scatter. A gather it splits by columns
by itself and never loops. So the write is turned into a read: each slot
looks up the row that writes it and takes that row or its own old bytes,
one pass over the pool at any width (~0.17 ms a taps pool). A selection
product on the MXU is as fast and not exact: 0 x inf = NaN would carry
one slot's overflow into every row, and -0.0 + 0.0 = +0.0.
"""
from __future__ import annotations

import jax.numpy as jnp


def gather_rows(pool: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """`[B, W]`: row b is `pool[slots[b]]`, zeros where the slot lies
    outside the pool. Slots may repeat."""
    return pool.at[slots].get(mode="fill", fill_value=0)


def scatter_rows(pool: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """The pool `[S, W]` with `rows[b]` `[B, W]` in slot `slots[b]`; a
    row whose slot is not in `[0, S)` writes nothing. The slots that are
    written are distinct."""
    hit = slots[None, :] == jnp.arange(pool.shape[0],
                                       dtype=slots.dtype)[:, None]  # [S, B]
    writer = jnp.argmax(hit, axis=1)
    new = rows.at[writer].get(mode="promise_in_bounds")
    return jnp.where(hit.any(axis=1)[:, None], new.astype(pool.dtype), pool)
