"""The ragged kernel's arithmetic of a row that walks alone
(`_row_update`, shared with the decode kernel): pool dtype x heads x ALiBi,
every row a tile of one, over the whole context (split from
test_ragged_paged_attention.py, which says how these run; the grid's other
half is test_ragged_grid_window.py)."""
import pytest

from tests.kernels._ragged_cases import (GRID_HEADS, GRID_POOLS,
                                         check_parity_grid, requires_tpu)


@requires_tpu
@pytest.mark.parametrize("windowed", [False], ids=["full"])
@pytest.mark.parametrize("use_alibi", [False, True], ids=["rope", "alibi"])
@pytest.mark.parametrize("heads", sorted(GRID_HEADS))
@pytest.mark.parametrize("pool", sorted(GRID_POOLS))
def test_ragged_parity_grid(pool, heads, use_alibi, windowed):
    check_parity_grid(pool, heads, use_alibi, windowed)
