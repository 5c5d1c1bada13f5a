"""Test harness: JAX-CPU with 8 virtual devices.

The reference's answer to "test multi-node without a cluster" is to simulate
N processes on one machine (README.md:5, ipynb:15 — torchrun
--nproc_per_node on a single VM). The JAX equivalent (SURVEY.md §4 Tier 1)
is the host-platform device-count spoof: 8 virtual CPU devices, so every
mesh/sharding/collective path compiles and executes in CI with no TPU.
Must run before jax initializes its backend, hence top of conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The environment variable above is honoured; the config API also covers
# a jax that something imported before this file ran.
jax.config.update("jax_platforms", "cpu")
# Tests call the CLI mains (train.main, sample.main, serve), and those
# place JAX's persistent compilation cache (utils/compile_cache.py). The
# suite must neither fill nor read it: off, whatever a main sets.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def char_dataset(tmp_path_factory):
    """A prepared synthetic char-level dataset (offline Tier-0 fixture)."""
    from nanosandbox_tpu.data.prepare import prepare_char_dataset

    root = tmp_path_factory.mktemp("data")
    out = root / "shakespeare_char"
    stats = prepare_char_dataset(str(out), allow_synthetic=True,
                                 url="http://invalid.localhost/nope")
    assert stats["train_tokens"] > 1000
    return str(root)


@pytest.fixture()
def tiny_cfg(char_dataset, tmp_path):
    from nanosandbox_tpu.config import TrainConfig

    return TrainConfig(
        out_dir=str(tmp_path / "out"),
        data_dir=char_dataset,
        dataset="shakespeare_char",
        n_layer=2, n_head=2, n_embd=64, block_size=64,
        batch_size=8, max_iters=20, lr_decay_iters=20,
        eval_interval=0, eval_iters=2, log_interval=5,
        warmup_iters=2, learning_rate=1e-3, min_lr=1e-4,
        dropout=0.0, compute_dtype="float32", device="auto",
        tensorboard=False, seed=0)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
