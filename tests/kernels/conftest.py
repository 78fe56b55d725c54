"""Kernel-test fixtures.

On CPU the Pallas TPU kernels run under `force_tpu_interpret_mode`, so
the whole kernel grid is exercised (numerics, masking, block-table walk)
without TPU hardware; on a real TPU the same tests compile and run the
Mosaic kernels natively.
"""
import jax
import pytest
from jax.experimental.pallas import tpu as pltpu


@pytest.fixture(autouse=True)
def _tpu_interpret_on_cpu():
    if jax.default_backend() == "tpu":
        yield
    else:
        with pltpu.force_tpu_interpret_mode():
            yield
