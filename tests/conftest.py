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
    flags += " --xla_force_host_platform_device_count=8"
# The suite's programs are tiny and many (a serving file alone compiles
# ~1,300), so the CPU backend's time goes to LLVM's optimisation passes,
# not to running what they optimise. Level 0 compiles the same HLO (what
# the tests read of a compiled program) with about a third less CPU time;
# results may differ from level 3's in the last bits. The described-chip
# tests' TPU programs compile to the same text under it.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()

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


# Files by their time in a six-worker run (8-core CPU host), longest first.
# Under `-n 6 --dist loadfile` the run ends when the last file handed out
# does; xdist's own order (most tests first) handed out the longest files,
# which hold few tests, last. Files missing here go first, before the
# longest: a new file costs the wall less at the start than at the end.
LONGEST_FIRST = (
    "test_tp_serve.py", "test_scan_decode.py", "test_afmoe.py",
    "test_serve.py", "test_spec.py", "test_scan_parity.py",
    "test_paged.py", "test_distributed.py", "test_deepseek_v3.py",
    "test_shardcheck.py", "test_disagg.py", "test_chip_compile.py",
    "test_flash_decode.py", "test_granite.py", "test_ring_attention.py",
    "test_attention.py", "test_faults.py", "test_train_tracing.py",
    "test_lfm2.py", "test_sched.py", "test_fleet.py", "test_sample.py",
    "test_train_smoke.py", "test_parallel.py", "test_ouro.py",
    "test_flight.py", "test_convert.py", "test_model.py", "test_obs.py",
    "test_families.py", "test_experts.py", "test_lockcheck.py",
    "test_profiler.py", "test_checkpoint.py", "test_bench.py",
    "test_metrics.py", "test_analysis.py", "test_prepare_real.py",
    "test_tracecheck.py", "test_data.py", "test_config.py",
    "test_deploy.py", "test_ops.py", "test_notebook.py",
)


def pytest_configure(config):
    # xdist hands files out in collection order only without its reorder
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, -1))
