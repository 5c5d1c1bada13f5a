"""chipbench/flops.py against hand counts for GPT-2 124M."""

import json
import os

import pytest

from chipbench import flops
from chipbench.tests import helpers

S124 = {"n_layer": 12, "n_head": 12, "n_embd": 768, "vocab_size": 50304,
        "block_size": 1024, "bias": False}


def test_param_count_by_hand():
    wte, wpe = 50304 * 768, 1024 * 768
    layer = 768 * 2304 + 768 * 768 + 768 * 3072 + 3072 * 768 + 2 * 768
    assert flops.n_params(S124) == wte + wpe + 12 * layer + 768 == 124_373_760
    with_bias = dict(S124, bias=True)
    assert flops.n_params(with_bias) - flops.n_params(S124) == \
        12 * (2304 + 768 + 3072 + 768 + 2 * 768) + 768


def test_flops_per_token_by_hand():
    n = 124_373_760 - 1024 * 768
    causal_attention = 6 * 12 * 12 * 64 * 1024        # half of 12 L H Q T
    assert flops.train_flops_per_token(S124) == 6 * n + causal_attention
    # the program's own count (full attention) is the larger one
    assert 6 * n + 2 * causal_attention > flops.train_flops_per_token(S124)


def test_flash_kernel_cost_by_hand():
    c = flops.flash_attention_cost(S124, batch=16)
    one_matmul = 2 * 1024 * 1024 * 64                 # T x T x D
    assert c["ops"] == 12 * 16 * 12 * 6 * one_matmul / 2
    tensor = 16 * 12 * 1024 * 64 * 2                  # bf16
    stats = 16 * 12 * 1024 * 4
    assert c["bytes"] == 12 * (12 * tensor + 2 * stats)
    peaks = flops.load_peaks("TPU v5 lite")
    least = flops.least_seconds(c, peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(c["ops"] / 197e12)
    assert 4.0e-3 < least["seconds"] < 5.5e-3


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")


def test_every_configuration_counts_like_the_program(tmp_path):
    """n_params agrees with the parameter tree the benchmark makes."""
    from chipbench import weights

    for name in os.listdir(os.path.join(helpers.CHIPBENCH, "configs")):
        with open(os.path.join(helpers.CHIPBENCH, "configs", name)) as f:
            c = json.load(f)
        sizes = {k: c[k] for k in ("n_layer", "n_head", "n_embd", "vocab_size",
                                   "bias")} | {"block_size": c["n_positions"]}
        total = 0
        for shape, _ in weights.param_shapes(sizes).values():
            n = 1
            for d in shape:
                n *= d
            total += n
        assert total == flops.n_params(sizes), name
