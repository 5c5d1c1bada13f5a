"""Real-TPU test tier (run on a chip; NOT part of the CPU suite).

The CPU suite (tests/) exercises Pallas kernels in interpret mode and
compiles them for a described chip (tests/test_chip_compile.py); neither
executes anything on a TPU. This tier does. Usage, on a machine with a
chip (one pytest process — a chip belongs to one process at a time):

    python -m pytest tests_tpu/ -q

Every test here needs the chip, so one session fixture asks JAX for its
backend — when the first test starts, never while a module is imported
or collected — and skips the tier where there is no TPU.
"""

import pytest


@pytest.fixture(scope="session", autouse=True)
def require_tpu():
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        pytest.skip(f"requires a TPU backend (found {backend!r})")
