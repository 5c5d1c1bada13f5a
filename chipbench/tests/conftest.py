"""CPU only, small, fast. Run with ``python -m pytest chipbench/tests -q``
from the repo's root (the repo's ``pytest.ini`` and the tier-1 command do
not reach this directory)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import pytest  # noqa: E402

# No persistent compile cache in the tests (the runner would switch it on).
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def data_dir():
    """The checkout's own prepared corpus (tokenised once, then reused)."""
    from chipbench.runners import train
    from chipbench.tests import helpers

    path = os.path.join(helpers.CHIPBENCH, "_data")
    train.prepare_data(path)
    return path
