"""The ``afmoe`` runner's CPU rehearsal on a tiny cell added as files and
entries (``tests/tiny/*tiny-afmoe*``), the three planted faults turning
``correct`` false there, its cost functions against hand counts, and its
readers on a record."""

import json
import os
import types

import pytest

from chipbench import flops_afmoe
from chipbench import run as harness
from chipbench.tests import helpers
from chipbench.tests.test_harness import _half_left_out

CELL = "tiny-afmoe.train-b2-t128"


def _args(seed=2**31 + 29, seconds=1.0, trace=0):
    return types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                                 trace=trace)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = helpers.copy_root(str(tmp_path_factory.mktemp("root")))
    helpers.add_tiny(root)          # copies every file under tests/tiny
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-afmoe", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-afmoe.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-afmoe", "traffic": "train-b2-t128",
        "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:    # the new cell's own metrics
        if "trinity-mini-ep8.train-b2-t8192" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _no_routed(monkeypatch):
    """The routed experts' sum left out: shared expert only."""
    import jax.numpy as jnp

    from nanosandbox_tpu.ops import moe
    monkeypatch.setattr(moe, "combine", lambda y, w, plan: jnp.zeros(
        (w.shape[0], y.shape[1]), jnp.float32))


def _no_window(monkeypatch):
    """The window ignored in sliding layers (positions kept)."""
    from nanosandbox_tpu.models import afmoe
    real = afmoe.causal_attention_gqa
    monkeypatch.setattr(
        afmoe, "causal_attention_gqa",
        lambda q, k, v, H, G, *, window, **kw: real(q, k, v, H, G,
                                                    window=None, **kw))


@pytest.mark.parametrize("fault", ["none", "half_batch_left_out",
                                   "routed_experts_left_out",
                                   "window_ignored"])
def test_a_whole_run_and_the_faults_it_must_catch(tiny_root, data_dir, fault,
                                                  monkeypatch):
    broken = None
    if fault == "half_batch_left_out":
        broken = _half_left_out
    elif fault == "routed_experts_left_out":
        _no_routed(monkeypatch)
    elif fault == "window_ignored":
        _no_window(monkeypatch)
    out = harness.drive(_args(), require_chip=False, root=tiny_root,
                        data_dir=data_dir, break_step=broken)
    assert set(out["check"]) == {"loss_gap", "grad_norm_gap", "g1_leaf_gap",
                                 "dp_leaf_gap"}
    assert out["attempted"] > 0 and out["failed"] == 0 and not out["faults"]
    assert out["metrics"]["train_tok_s_chip"]["value"] > 0
    assert out["correct"] is (fault == "none"), out
    over = {k for k, c in out["check"].items() if not c["value"] <= c["limit"]}
    if fault != "none":
        assert {"grad_norm_gap", "g1_leaf_gap"} & over, out["check"]


def test_the_record_feeds_the_unlisted_readers_and_its_own(tiny_root, data_dir,
                                                           monkeypatch):
    """A traced run on the CPU has no device plane: the counters' metric is
    read, the device metrics find nothing and are left out, nothing raises."""
    found = harness.find_cell(CELL, tiny_root)
    names = {m["name"] for m in harness.metrics_of(found, "per_layer")}
    assert {"compile_s", "host_input_ms", "train_step_mfu_pct",
            "step_gap_p95_ms", "device_idle_pct", "dev_ms_attn_sliding",
            "dev_ms_attn_full", "dev_ms_moe_experts", "dev_ms_moe_route",
            "dev_ms_moe_shared", "dev_ms_rest_of_step",
            "attn_gqa_window_roofline", "moe_gmm_roofline",
            "moe_load_max_over_mean", "balance_bias_s"} <= names
    assert "dev_ms_attn" not in names and "flash_attn_roofline" not in names
    out = harness.drive(_args(trace=1), require_chip=False, root=tiny_root,
                        data_dir=data_dir)
    assert out["correct"]
    load = out["metrics"]["moe_load_max_over_mean"]
    assert load["value"] >= 1.0 and load["dropped"] == 0
    assert load["rows_bound"] % 512 == 0 and load["steps_counted"] >= 6
    assert "moe_gmm_roofline" not in out["metrics"]
    fit = out["metrics"]["balance_bias_s"]
    assert fit["value"] > 0 and fit["rows"] == 32
    # 4,096 tokens, 8 experts, 2 a token: the reference's fit leaves every
    # expert within a tenth of the even share on its own rows
    assert fit["fullest_over_even"] < 1.1
    assert all(abs(v - 1) < 0.05 for v in fit["held_share_by_layer"])


SIZES = dict(n_layer=5, n_head=32, n_kv_head=4, head_dim=128, n_embd=2048,
             vocab_size=25024, block_size=8192,
             layer_types=["sliding", "sliding", "sliding", "full", "sliding"],
             sliding_window=2048, num_dense_layers=1, intermediate_size=6144,
             moe_intermediate_size=1024, num_experts=128,
             num_experts_per_tok=8, experts_held=(0, 16))


def test_cost_functions_against_hand_counts():
    # the issue's table: 705.5 M parameters; 2.21 GFLOP a token
    attn = 2048 * 4096 * 3 + 2 * 2048 * 512            # 27.26 M
    dense_layer = attn + 3 * 2048 * 6144 + 4 * 2048 + 256
    expert_layer = (attn + 17 * 3 * 2048 * 1024 + 2049 * 128
                    + 4 * 2048 + 256)
    assert flops_afmoe.n_params(SIZES) == (
        dense_layer + 4 * expert_layer + 2 * 25024 * 2048 + 2048) == 705_474_304
    # pairs a query: window 1,792.1, full 4,096.5
    assert flops_afmoe.attention_pairs(8192, 2048) == (
        2048 * 2049 // 2 + 6144 * 2048)
    assert flops_afmoe.attention_pairs(8192, None) == 8192 * 8193 // 2
    assert flops_afmoe.attention_pairs(4, 2) == 1 + 2 + 2 + 2
    multiply = (5 * attn + 3 * 2048 * 6144 + 25024 * 2048
                + 4 * (2 * 3 * 2048 * 1024 + 2048 * 128))  # 1 shared + 1 held
    pairs = (4 * flops_afmoe.attention_pairs(8192, 2048)
             + flops_afmoe.attention_pairs(8192, None)) / 8192
    want = 6 * multiply + 12 * 32 * 128 * pairs
    assert flops_afmoe.train_flops_per_token(SIZES) == pytest.approx(want)
    assert want == pytest.approx(2.2139e9, rel=1e-4)
    # counted rows instead of expected ones
    assert flops_afmoe.train_flops_per_token(SIZES, 2.0) - want == (
        pytest.approx(6 * 4 * 3 * 2048 * 1024))
    assert flops_afmoe.expected_rows_held(SIZES, 16384) == 16384
    c = flops_afmoe.attention_cost(SIZES, 2, 2048)
    assert c["ops"] == 12 * 128 * 32 * 2 * flops_afmoe.attention_pairs(8192, 2048)
    assert c["bytes"] == 6 * 2 * 8192 * 36 * 128 * 2 + 2 * 2 * 32 * 8192 * 4
    whole = flops_afmoe.attention_step_cost(SIZES, 2)
    assert whole["ops"] == 4 * c["ops"] + flops_afmoe.attention_cost(
        SIZES, 2, None)["ops"]
    g = flops_afmoe.gmm_cost(SIZES, 16384)
    assert g["ops"] == 9 * 2 * 16384 * 2048 * 1024
    assert g["bytes"] == 9 * 2 * (16384 * 3072 + 16 * 2048 * 1024)
