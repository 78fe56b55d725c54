"""Fixtures for the ops-layer tests.

Unlike tests/kernels/ (whose autouse fixture gates the WHOLE directory
on interpret mode), only the parity suite here needs to execute Mosaic
kernels — the reference-seam tests run everywhere on plain CPU — so the
interpret gate is an opt-in fixture.
"""
import jax
import pytest
from jax.experimental.pallas import tpu as pltpu


@pytest.fixture
def tpu_interpret():
    """Run the test body with Pallas TPU kernels executable: natively on
    TPU, under `force_tpu_interpret_mode` on CPU."""
    if jax.default_backend() == "tpu":
        yield
    else:
        with pltpu.force_tpu_interpret_mode():
            yield
