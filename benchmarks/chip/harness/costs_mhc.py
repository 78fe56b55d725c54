"""Operations and bytes of what `xing4.0-29b-a4b-ep8` adds to a step, from
its shapes: the two mixes of a manifold-constrained hyper-connection
sublayer (`intellillm_tpu/layers/hyper_connections.py` `mhc_pre` /
`mhc_post`). Kept with the benchmark (as costs.py is) so that no PR that
claims a gain can change the yardstick.

The mixes are XLA fusions, not kernels, and no reader reports a
`<kernel>_roofline` metric yet (costs.py says why; a `benchmark` issue adds
the reader). Until then the builder of a PR divides these by the mixes' time
in a `--trace 1` run by hand and writes the share into PERF.md: the time from
`breakdown.device_ops` (the operations `mhc_dev_share` matches), rows from
`intellillm_dispatch_row_steps_total`, bytes from
`intellillm_dispatch_stream_mix_bytes_total`.

Counted is what the published form needs, not what a form of it spends: the
pre-mix reads the `n` streams of a row once (statistic, projection and the
`n`-term sum all from one read) and writes the mixed vector; the post-mix
reads the streams and the sublayer's output and writes the streams. The
projection's weights `[2n + n^2, nC]` float32 are read once a call whatever
the rows. A mix fused across the sublayer boundary (post-mix of one with the
pre-mix of the next: one read of the streams, one write) moves 2/3 of the
streams' bytes; that is a form's saving and is not counted here. The
arithmetic is far under the chip's ridge (about 3 operations a byte), so a
mix is held to the HBM rate alone.
"""
from __future__ import annotations

COEFF_BYTES = 4          # Phi and the coefficients are float32


def stream_bytes(rows: int, n: int, c: int, itemsize: int) -> int:
    """The `n` streams of `rows` rows, once."""
    return rows * n * c * itemsize


def mhc_pre_bytes(rows: int, n: int, c: int, itemsize: int) -> int:
    """One sublayer's pre-mix: the streams read once, the mixed vector
    written, the projection's weights read."""
    return (stream_bytes(rows, n, c, itemsize) + rows * c * itemsize
            + (2 * n + n * n) * n * c * COEFF_BYTES)


def mhc_post_bytes(rows: int, n: int, c: int, itemsize: int) -> int:
    """One sublayer's post-mix: the streams read and written, the
    sublayer's output read."""
    return 2 * stream_bytes(rows, n, c, itemsize) + rows * c * itemsize


def mhc_stream_bytes(rows: int, n: int, c: int, itemsize: int) -> int:
    """The streams' share of both (what the program's counter
    intellillm_dispatch_stream_mix_bytes_total counts a sublayer)."""
    return 3 * stream_bytes(rows, n, c, itemsize)


def mhc_flops(rows: int, n: int, c: int, sweeps: int) -> int:
    """One sublayer, both mixes: the statistic (2 a value), the projection
    (2 a value and coefficient), the pre-mix (2 a value), the post-mix
    (2 n a value for the n x n combination, 2 for the output's share), and
    the sweeps (2 normalisations of n x n divisions and n x (n - 1) + n
    additions a sweep: ~4 n^2 a sweep)."""
    values = rows * n * c
    return (values * (2 + 2 * (2 * n + n * n) + 2 + 2 * n + 2)
            + rows * sweeps * 4 * n * n)
