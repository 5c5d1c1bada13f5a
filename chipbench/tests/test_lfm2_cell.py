"""The ``lfm2`` runner's CPU rehearsal on a tiny cell added as files and
entries (``tests/tiny/*tiny-lfm2*``), the planted faults turning ``correct``
false there, its cost functions against hand counts, and its readers on a
record."""

import json
import os
import types

import pytest

from chipbench import flops_lfm2
from chipbench import run as harness
from chipbench.tests import helpers
from chipbench.tests.test_harness import _half_left_out

CELL = "tiny-lfm2.train-b2-t128"
REAL = "lfm2-8b-a1b-ep4.train-b2-t8192"
OWN = {"dev_ms_lfm2_conv", "dev_ms_lfm2_attn", "dev_ms_lfm2_moe_route",
       "dev_ms_lfm2_moe_experts", "dev_ms_lfm2_rest",
       "lfm2_conv_mix_roofline", "lfm2_attn_roofline", "lfm2_gmm_roofline",
       "lfm2_moe_load_max_over_mean", "lfm2_balance_bias_s"}


def _args(seed=2**31 + 31, seconds=1.0, trace=0):
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
        "name": "tiny-lfm2", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-lfm2.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-lfm2", "traffic": "train-b2-t128",
        "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:    # the real cell's own metrics
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _no_routed(monkeypatch):
    """The routed experts' sum left out."""
    import jax.numpy as jnp

    from nanosandbox_tpu.ops import moe
    monkeypatch.setattr(moe, "combine", lambda y, w, plan, mover: jnp.zeros(
        (w.shape[0], y.shape[1]), jnp.float32))


def _one_tap(monkeypatch):
    """The convolution's earlier taps left out: L read as 1."""
    from nanosandbox_tpu.ops import short_conv
    monkeypatch.setattr(short_conv, "causal_taps",
                        lambda u, w: u * w[:, -1])


def _kv_mod(monkeypatch):
    """Query head i reads KV head i % G (the query heads reordered so that
    the kernels' i // (H // G) lands there)."""
    from nanosandbox_tpu.models import lfm2
    real = lfm2.causal_attention_gqa

    def broken(q, k, v, H, G, **kw):
        B, T, HD = q.shape
        D, rep = HD // H, H // G
        to = lambda x: x.reshape(B, T, rep, G, D).swapaxes(2, 3).reshape(
            B, T, HD)
        back = lambda x: x.reshape(B, T, G, rep, D).swapaxes(2, 3).reshape(
            B, T, HD)
        return back(real(to(q), k, v, H, G, **kw))

    monkeypatch.setattr(lfm2, "causal_attention_gqa", broken)


FAULTS = {"routed_experts_left_out": _no_routed, "one_tap": _one_tap,
          "kv_head_by_modulo": _kv_mod}


@pytest.mark.parametrize("fault", ["none", "half_batch_left_out", *FAULTS])
def test_a_whole_run_and_the_faults_it_must_catch(tiny_root, data_dir, fault,
                                                  monkeypatch):
    broken = _half_left_out if fault == "half_batch_left_out" else None
    if fault in FAULTS:
        FAULTS[fault](monkeypatch)
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


def test_the_record_feeds_the_unlisted_readers_and_its_own(tiny_root, data_dir):
    """A traced run on the CPU has no device plane: the counters' metric is
    read, the device metrics find nothing and are left out, nothing raises."""
    found = harness.find_cell(CELL, tiny_root)
    names = {m["name"] for m in harness.metrics_of(found, "per_layer")}
    assert OWN | {"compile_s", "host_input_ms", "train_step_mfu_pct",
                  "step_gap_p95_ms", "device_idle_pct"} <= names
    assert not {"dev_ms_attn", "dev_ms_moe_shared", "moe_gmm_roofline"} & names
    out = harness.drive(_args(trace=1), require_chip=False, root=tiny_root,
                        data_dir=data_dir)
    assert out["correct"]
    load = out["metrics"]["lfm2_moe_load_max_over_mean"]
    assert load["value"] >= 1.0 and load["dropped"] == 0
    assert load["rows_bound"] % 512 == 0 and load["steps_counted"] >= 6
    assert not {"lfm2_gmm_roofline", "lfm2_conv_mix_roofline",
                "lfm2_attn_roofline"} & set(out["metrics"])
    fit = out["metrics"]["lfm2_balance_bias_s"]
    assert fit["value"] > 0 and fit["rows"] == 32
    assert fit["fullest_over_even"] < 1.1
    assert all(abs(v - 1) < 0.05 for v in fit["held_share_by_layer"])


def test_the_real_cells_files_say_what_the_issue_says():
    found = harness.find_cell(REAL)
    assert found.cell["runner"] == "train_lfm2" and found.entry["chips"] == 1
    c = found.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["router_num_experts"],
            c["num_experts_per_tok"], c["conv_L_cache"], c["rope_theta"]) == (
                2048, 32, 8, 7168, 1792, 32, 4, 3, 1000000)
    changed = {k for k, v in c["published"].items() if c[k] != v}
    assert changed == set(c["reduced"]) == set(c["reduced_why"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    own = {m["name"] for m in found.bench["per_layer"]
           if m.get("workloads") == [REAL]}
    assert own == OWN


SIZES = dict(n_layer=6, n_head=32, n_kv_head=8, head_dim=64, n_embd=2048,
             vocab_size=16384, block_size=8192,
             layer_types=["conv", "conv", "full", "conv", "conv", "conv"],
             conv_L_cache=3, num_dense_layers=1, intermediate_size=7168,
             moe_intermediate_size=1792, num_experts=32,
             num_experts_per_tok=4, experts_held=(0, 8))


def test_cost_functions_against_hand_counts():
    # the issue's table: 612.7 M parameters
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3        # 16.78 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64   # 10.49 M
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32 + 32     # 88.15 M
    norms = 2 * 2048
    assert flops_lfm2.n_params(SIZES) == (
        conv + 3 * 2048 * 7168 + norms
        + 4 * (conv + experts + norms) + (attn + experts + norms)
        + 16384 * 2048 + 2048) == 612_753_696
    # a token multiplies: its mixers, one held expert a layer on average
    # (4 * 8 / 32), the router, the dense MLP, the tied head
    multiply = (5 * conv + (attn - 128) + 3 * 2048 * 7168
                + 5 * (3 * 2048 * 1792 + 2048 * 32) + 16384 * 2048)
    pairs = 8192 * 8193 // 2 / 8192                    # one full layer
    want = 6.0 * multiply + 12.0 * 32 * 64 * pairs
    assert flops_lfm2.train_flops_per_token(SIZES) == pytest.approx(want)
    assert 1.4e9 < want < 1.5e9                        # the issue's 1.46 G
    # eleven (B, T, d) tensors in bfloat16 a conv layer: 738 MB at 2 x 8192
    cost = flops_lfm2.conv_mix_cost(SIZES, 2)
    assert cost["bytes"] == 11 * 2 * 8192 * 2048 * 2
    least = flops_lfm2.least_seconds(cost, {"bf16_flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.000901, rel=1e-2)


def test_the_conv_readers_share_reads_the_parts_map():
    """lfm2:conv_mix_roofline_pct on a record: the required bytes' time over
    the device time of the part ``conv_mix``; nothing where the map lacks
    the part or the record the family's sizes."""
    from chipbench.reducers import lfm2 as readers

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    metric = {"params": {"part": "conv_mix"}}

    def run(by_part, sizes=SIZES):
        return {"record": {"sizes": sizes, "batch_rows": 2, "chips": 1},
                "peaks": peaks, "program": {"device_ms": by_part}}

    value, extra = readers.conv_mix_roofline_pct(
        run({"conv_mix": [9.01, 30.0], "conv": [50.0, 10.0]}), metric)
    assert value == pytest.approx(50.0, rel=1e-2)      # 5 layers x 0.901 ms
    assert extra["bound"] == "memory" and extra["ops_per_step"] == 30.0
    assert readers.conv_mix_roofline_pct(run({"conv": [50.0, 10.0]}),
                                         metric) is None
    assert readers.conv_mix_roofline_pct(run(None), metric) is None
    assert readers.conv_mix_roofline_pct(
        run({"conv_mix": [9.0, 3.0]}, sizes={"n_layer": 12}), metric) is None
