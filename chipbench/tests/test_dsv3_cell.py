"""The ``deepseek_v3`` runner's CPU rehearsal on a tiny cell added as files
and entries (``tests/tiny/*tiny-dsv3*``), the planted faults turning
``correct`` false there, its cost functions against hand counts, and its
readers on a record."""

import json
import os
import types

import pytest

from chipbench import flops_dsv3
from chipbench import run as harness
from chipbench.tests import helpers
from chipbench.tests.test_harness import _half_left_out

CELL = "tiny-dsv3.train-b2-t128"
REAL = "moonlight-16b-a3b-ep8.train-b2-t8192"
OWN = {"dev_ms_dsv3_attn", "dev_ms_dsv3_moe_route", "dev_ms_dsv3_moe_experts",
       "dev_ms_dsv3_moe_shared", "dev_ms_dsv3_rest", "dsv3_mla_roofline",
       "dsv3_gmm_roofline", "dsv3_moe_load_max_over_mean",
       "dsv3_balance_bias_s"}


def _args(seed=2**31 + 35, seconds=1.0, trace=0):
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
        "name": "tiny-dsv3", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-dsv3.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-dsv3", "traffic": "train-b2-t128",
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


def _no_rope_term(monkeypatch):
    """The rotary term of the score left out: s = q_nope k_nope^T alone."""
    import jax.numpy as jnp

    from nanosandbox_tpu.models import deepseek_v3 as family
    real = family.causal_attention_mla
    monkeypatch.setattr(
        family, "causal_attention_mla",
        lambda qn, qp, kn, kp, v, H, **kw: real(
            qn, jnp.zeros_like(qp), kn, kp, v, H, **kw))


# The fourth fault the limits are read against on the chip, the score scaled
# by 128 ** -0.5, moves nothing a comparison can see at this size (d 64,
# weights of 0.02: scores near zero, attention uniform under either scale);
# tests/test_deepseek_v3.py holds the reference's form of it to be another
# computation, chipbench/read_limits_dsv3.py reads it at the cell's size.
FAULTS = {"routed_experts_left_out": _no_routed,
          "rope_term_left_out": _no_rope_term}


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
    # at this size (d 64, weights of 0.02) scores are near zero and attention
    # near uniform: the score's fault shows in the parameters' change alone;
    # the others in the gradient too
    if fault == "rope_term_left_out":
        assert "dp_leaf_gap" in over, out["check"]
    elif fault != "none":
        assert {"grad_norm_gap", "g1_leaf_gap"} & over, out["check"]


def test_the_record_feeds_the_unlisted_readers_and_its_own(tiny_root, data_dir):
    """A traced run on the CPU has no device plane: the counters' metric is
    read, the device metrics find nothing and are left out, nothing raises."""
    found = harness.find_cell(CELL, tiny_root)
    names = {m["name"] for m in harness.metrics_of(found, "per_layer")}
    assert OWN | {"compile_s", "host_input_ms", "train_step_mfu_pct",
                  "step_gap_p95_ms", "device_idle_pct"} <= names
    assert not {"dev_ms_attn", "dev_ms_moe_shared", "moe_gmm_roofline",
                "lfm2_gmm_roofline"} & names
    out = harness.drive(_args(trace=1), require_chip=False, root=tiny_root,
                        data_dir=data_dir)
    assert out["correct"]
    load = out["metrics"]["dsv3_moe_load_max_over_mean"]
    assert load["value"] >= 1.0 and load["dropped"] == 0
    assert load["rows_bound"] % 512 == 0 and load["steps_counted"] >= 6
    assert not {"dsv3_gmm_roofline", "dsv3_mla_roofline"} & set(out["metrics"])
    fit = out["metrics"]["dsv3_balance_bias_s"]
    assert fit["value"] > 0 and fit["rows"] == 96      # 48 batches of 2
    assert fit["fullest_over_even"] < 1.1
    assert all(abs(v - 1) < 0.05 for v in fit["held_share_by_layer"])


def test_a_program_without_the_family_is_refused_at_once(tiny_root, data_dir,
                                                         monkeypatch):
    """An older commit under these files: non-zero before the corpus is
    folded, a trainer built or anything compiled."""
    from chipbench.runners import train_afmoe
    from nanosandbox_tpu import models

    monkeypatch.setattr(models, "FAMILIES", {
        k: v for k, v in models.FAMILIES.items() if k != "deepseek_v3"})
    monkeypatch.setattr(train_afmoe, "prepare_folded",
                        lambda *a: pytest.fail("the corpus was folded"))
    with pytest.raises(SystemExit, match="no model_family 'deepseek_v3'"):
        harness.drive(_args(), require_chip=False, root=tiny_root,
                      data_dir=data_dir)


def test_the_real_cells_files_say_what_the_issue_says():
    found = harness.find_cell(REAL)
    assert found.cell["runner"] == "train_dsv3" and found.entry["chips"] == 1
    c = found.config
    assert (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["n_shared_experts"], c["router_num_experts"],
            c["num_experts_per_tok"], c["rope_theta"],
            c["routed_scaling_factor"]) == (
                2048, 16, 512, 128, 64, 128, 11264, 1408, 2, 64, 6, 50000,
                2.446)
    changed = {k for k, v in c["published"].items() if c[k] != v}
    assert changed == set(c["reduced"]) == set(c["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    own = {m["name"] for m in found.bench["per_layer"]
           if m.get("workloads") == [REAL]}
    assert own == OWN


SIZES = dict(n_layer=6, n_head=16, n_embd=2048, vocab_size=20480,
             block_size=8192, kv_lora_rank=512, qk_nope_head_dim=128,
             qk_rope_head_dim=64, v_head_dim=128, num_dense_layers=1,
             intermediate_size=11264, moe_intermediate_size=1408,
             n_shared_experts=2, num_experts=64, num_experts_per_tok=6,
             experts_held=(0, 8))


def test_cost_functions_against_hand_counts():
    # the issue's table: 668.9 M parameters
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256
            + 2048 * 2048)                              # 13.76 M
    shared = 3 * 2048 * 2816
    experts = 8 * 3 * 2048 * 1408 + 2048 * 64 + 64
    norms = 2 * 2048
    assert flops_dsv3.n_params(SIZES) == (
        attn + 3 * 2048 * 11264 + norms
        + 5 * (attn + shared + experts + norms)
        + 2 * 20480 * 2048 + 2048) == 668_890_432
    # a token multiplies: attention's four matrices, the shared expert and
    # 6 * 8 / 64 of a routed expert a layer on average, the router, the dense
    # MLP, the head
    multiply = (6 * (attn - 512) + 3 * 2048 * 11264
                + 5 * (shared + 0.75 * 3 * 2048 * 1408 + 2048 * 64)
                + 20480 * 2048)
    pairs = 6 * 8193 / 2                               # six full layers
    want = 6.0 * multiply + 6.0 * 16 * (192 + 128) * pairs
    assert flops_dsv3.train_flops_per_token(SIZES) == pytest.approx(want)
    assert 2.6e9 < want < 2.7e9                        # the issue's 2.64 G
    # the kernels' required work, one layer, 2 rows: 2 * 320 forward and
    # 2 * 640 backward a causal pair and head
    cost = flops_dsv3.mla_attention_cost(SIZES, 2)
    assert cost["ops"] == (2 * 320 + 2 * 640) * 16 * 2 * (8192 * 8193 // 2)
    tensors = 16 * (3 * 192 + 3 * 128 + 6 * 128) + 3 * 64
    assert cost["bytes"] == 2 * 8192 * tensors * 2 + 2 * 2 * 16 * 8192 * 4
    least = flops_dsv3.least_seconds(cost, {"bf16_flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(0.01046, rel=1e-2)


def test_the_latent_kernels_readers_on_a_record():
    """dsv3:mla_roofline_pct and dsv3:attention_ms: nothing where the record
    lacks the family's sizes (a parent's) or the trace the kernels."""
    from chipbench.reducers import dsv3 as readers

    metric = {"params": {"parts": ["attn_mla", "mla_prep"],
                         "step_program": "^jit_traced\\(",
                         "pattern": "^%attn_mla[.0-9]* = "}}
    no_ops = types.SimpleNamespace(ops=[])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    theirs = {"record": {"sizes": {"n_layer": 6, "layer_types": []},
                         "batch_rows": 2, "chips": 1},
              "trace": no_ops, "peaks": peaks}
    assert readers.mla_roofline_pct(theirs, metric) is None
    mine = {"record": {"sizes": SIZES, "batch_rows": 2, "chips": 1},
            "trace": no_ops, "peaks": peaks,
            "program": {"parts_s": 0.0,
                        "device_ms": {"attn_mla": [200.0, 90.0],
                                      "mla_prep": [12.0, 30.0],
                                      "mlp": [50.0, 10.0]}}}
    assert readers.mla_roofline_pct(mine, metric) is None   # no device plane
    value, extra = readers.attention_ms(mine, metric)
    assert value == 212.0 and extra["ms_by_part"] == {"attn_mla": 200.0,
                                                      "mla_prep": 12.0}
    assert "kernels_ms" not in extra
    assert readers.attention_ms({**mine, "program": {"parts_s": 0.0, "device_ms": None}},
                                metric) is None
