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


@pytest.fixture(scope="module", autouse=True)
def _drop_the_modules_executables():
    """An interpreted kernel is hundreds of small programs and host
    callbacks: a few hundred cases leave a worker holding gigabytes of
    them, and a later file's large CPU compile in the same process has
    been seen to die in the compiler (a segmentation fault in
    `backend_compile`, PR 42). Nothing of a module's is needed after it."""
    yield
    jax.clear_caches()
