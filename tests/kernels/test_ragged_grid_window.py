"""The ragged kernel's arithmetic of a row that walks alone
(`_row_update`, shared with the decode kernel): pool dtype x heads x ALiBi,
every row a tile of one, under a sliding window (split from
test_ragged_paged_attention.py, which says how these run; the grid's other
half is test_ragged_grid.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.kernels._ragged_cases import (GRID_HEADS, GRID_POOLS, _grid_case,
                                         check_parity_grid, requires_tpu)


@requires_tpu
@pytest.mark.parametrize("windowed", [True], ids=["window"])
@pytest.mark.parametrize("use_alibi", [False, True], ids=["rope", "alibi"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", sorted(GRID_POOLS))
def test_ragged_parity_grid(pool, heads, use_alibi, windowed):
    check_parity_grid(pool, heads, use_alibi, windowed)


@requires_tpu
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", ["bf16", "fp8_e5m2"])
def test_ragged_narrow_pool_loses_nothing_of_the_float32_dot_form(pool,
                                                                 heads):
    """The same values in a float32 pool are the float32-dot form on the
    same inputs: a row that walks alone reads within 2e-5 of it from the
    narrow pool, whatever the dots' operands are made of."""
    from intellillm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    args, kwargs = _grid_case(pool, heads, True, True, jnp.float32)
    out = jax.block_until_ready(ragged_paged_attention(
        *args, 128 ** -0.5, **kwargs))[0]
    wide = [a.astype(jnp.float32) if a.dtype == GRID_POOLS[pool] else a
            for a in args]
    out_wide = jax.block_until_ready(ragged_paged_attention(
        *wide, 128 ** -0.5, **kwargs))[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_wide),
                               rtol=0, atol=2e-5)
