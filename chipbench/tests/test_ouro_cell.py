"""The ``ouro`` runner's CPU rehearsal on a tiny cell added as files and
entries (``tests/tiny/*tiny-ouro*``), the five planted faults turning
``correct`` false there, its cost functions against hand counts, and its
readers on a record."""

import json
import os
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from chipbench import flops_ouro
from chipbench import run as harness
from chipbench.tests import helpers
from chipbench.tests.test_harness import _half_left_out

CELL = "tiny-ouro.train-b2-t128"
REAL = "ouro-2.6b-pp8.train-b2-t8192"
OWN = {"dev_ms_ouro_attn", "dev_ms_ouro_mlp", "dev_ms_ouro_exits",
       "dev_ms_ouro_rest", "ouro_attn_roofline", "ouro_exits_roofline"}


def _args(seed=2**31 + 39, seconds=1.0, trace=0):
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
        "name": "tiny-ouro", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-ouro.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-ouro", "traffic": "train-b2-t128",
        "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:    # the real cell's own metrics
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


# -- the five faults, each in the program's place ------------------------------

def _one_pass_fewer(monkeypatch):
    """Three passes of the stack for four."""
    from chipbench.runners import train_ouro

    real = train_ouro.train_config
    monkeypatch.setattr(train_ouro, "train_config", lambda ctx: real(
        ctx).replace(total_ut_steps=real(ctx).total_ut_steps - 1))


def _gate_ignored(monkeypatch):
    """The exit distribution uniform whatever the gate says."""
    from nanosandbox_tpu.models import ouro

    monkeypatch.setattr(ouro, "exit_log_probs", lambda z: jnp.full_like(
        z, -jnp.log(float(z.shape[0]))))


def _last_exit_only(monkeypatch):
    """The loss of the last exit alone."""
    from nanosandbox_tpu.models import ouro

    real = ouro.exit_loss
    monkeypatch.setattr(ouro, "exit_loss", lambda nll, aux: (
        jnp.mean(nll[-1]), real(nll, aux)[1]))


def _unnormed(monkeypatch):
    """The state passed on to the next pass before the final norm (the
    exits still read the normed one)."""
    from nanosandbox_tpu.models import ouro

    class Unnormed(ouro.Pass):
        @nn.compact
        def __call__(self, h, _):
            for i in range(self.cfg.n_layer):
                h = ouro.Block(self.cfg, name=f"h_{i}")(h)
            normed = ouro.rms_norm(self.cfg, "ln_f")(h)
            z = nn.Dense(1, dtype=jnp.float32, name="exit_gate",
                         precision=jax.lax.Precision.HIGHEST)(normed)[..., 0]
            return h, (normed, z)

    monkeypatch.setattr(ouro, "Pass", Unnormed)


FAULTS = {"one_pass_fewer": _one_pass_fewer, "gate_ignored": _gate_ignored,
          "last_exit_only": _last_exit_only, "state_passed_unnormed":
          _unnormed}


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
    assert out["correct"] is (fault == "none"), out["check"]


def test_the_record_feeds_the_unlisted_readers_and_its_own(tiny_root, data_dir):
    """A traced run on the CPU has no device plane: the device metrics find
    nothing and are left out, nothing raises; the exits' instants are
    left by the trainer for the logged steps."""
    from nanosandbox_tpu.obs import process_tracer

    found = harness.find_cell(CELL, tiny_root)
    names = {m["name"] for m in harness.metrics_of(found, "per_layer")}
    assert OWN | {"compile_s", "host_input_ms", "train_step_mfu_pct",
                  "step_gap_p95_ms", "device_idle_pct"} <= names
    assert not {"dev_ms_attn", "dev_ms_dsv3_attn", "lfm2_attn_roofline",
                "dev_ms_route_router"} & names
    process_tracer().clear()
    out = harness.drive(_args(trace=1), require_chip=False, root=tiny_root,
                        data_dir=data_dir)
    assert out["correct"]
    assert not OWN & set(out["metrics"])
    instants = [s for s in process_tracer().spans() if s.name == "ouro_exits"]
    assert instants and all(len(s.args["exit_p"]) == 4 for s in instants)
    init = [s for s in process_tracer().spans() if s.name == "trainer_init"]
    assert {"loops": 4, "layers_held": 2, "remat_policy": "save_attention"
            }.items() <= init[-1].args.items()


def test_a_program_without_the_family_is_refused_at_once(tiny_root, data_dir,
                                                         monkeypatch):
    """An older commit under these files: non-zero before the corpus is
    folded, a trainer built or anything compiled."""
    from chipbench.runners import train_ouro
    from nanosandbox_tpu import models

    monkeypatch.setattr(models, "FAMILIES", {
        k: v for k, v in models.FAMILIES.items() if k != "ouro"})
    monkeypatch.setattr(train_ouro, "prepare_folded",
                        lambda *a: pytest.fail("the corpus was folded"))
    with pytest.raises(SystemExit, match="no model_family 'ouro'"):
        harness.drive(_args(), require_chip=False, root=tiny_root,
                      data_dir=data_dir)


def test_the_real_cells_files_say_what_the_issue_says():
    found = harness.find_cell(REAL)
    assert found.cell["runner"] == "train_ouro" and found.entry["chips"] == 1
    assert found.entry["traffic"] == "train-b2-t8192"
    c = found.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["total_ut_steps"], c["rope_theta"],
            c["rms_norm_eps"]) == (2048, 16, 16, 128, 5632, 49152, 4,
                                   1000000, 1e-6)
    changed = {k for k, v in c["published"].items() if c[k] != v}
    assert changed == set(c["reduced"]) == set(c["reduced_why"]) == {
        "num_hidden_layers"}
    assert c["num_hidden_layers"] in range(4, 10)
    own = {m["name"] for m in found.bench["per_layer"]
           if m.get("workloads") == [REAL]}
    assert own == OWN


SIZES = dict(n_layer=6, n_head=16, n_kv_head=16, head_dim=128, n_embd=2048,
             intermediate_size=5632, vocab_size=49152, block_size=8192,
             total_ut_steps=4)


def test_cost_functions_against_hand_counts():
    # the issue's count: 509.7 M parameters
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048      # 51.39 M
    assert flops_ouro.n_params(SIZES) == (
        6 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1) == 509_661_185
    # a token: 6 per parameter APPLIED (4 passes of the layers' matrices, 4
    # heads, 4 gates) and 12 * H * D a causal pair in each of 24 layers
    applied = 4 * (6 * (layer - 4 * 2048) + 49152 * 2048 + 2048)
    want = 6.0 * applied + 12.0 * 16 * 128 * 24 * 8193 / 2
    assert flops_ouro.train_flops_per_token(SIZES) == pytest.approx(want)
    assert 12.1e9 < want < 12.3e9                    # the issue's 12.2 G
    exits = flops_ouro.exits_cost(SIZES, 16384)
    assert exits["ops"] == 6.0 * 4 * (49152 * 2048 + 2048) * 16384
    least = flops_ouro.least_seconds(exits, {"bf16_flops_per_s": 197e12,
                                             "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "compute"
    assert 0.19 < exits["ops"] / want / 16384 < 0.21   # a fifth of the step


def test_the_readers_on_a_record(monkeypatch):
    """Nothing where the record lacks the family's sizes (a parent's) or the
    trace the kernels; the exits' instants in the extras where the program
    left them; the four parts sum to the step's device time."""
    from chipbench.reducers import ouro as readers
    from chipbench.reducers import program

    no_ops = types.SimpleNamespace(ops=[])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = {"params": {"step_program": "^jit_traced\\(",
                       "pattern": "^%attn_full[.0-9]* = "}}
    theirs = {"record": {"sizes": {"n_layer": 6}, "batch_rows": 2,
                         "chips": 1}, "trace": no_ops, "peaks": peaks,
              "program": {"spans": [], "parts_s": 0.0, "device_ms": None}}
    assert readers.attention_roofline_pct(theirs, roof) is None
    assert readers.exits_roofline_pct(theirs, {"params": {"part": "exits"}}
                                      ) is None
    assert readers.exits_ms(theirs, {"params": {"parts": ["exits"]}}) is None
    inst = [types.SimpleNamespace(name="ouro_exits", args={
        "exit_p": [0.5, 0.25, 0.125, 0.125], "exit_nll": [11.0, 10.0, 9.0,
                                                           8.0]}),
        types.SimpleNamespace(name="ouro_exits", args={
            "exit_p": [0.3, 0.3, 0.2, 0.2], "exit_nll": [9.0, 8.0, 7.0,
                                                         6.0]})]
    parts = {"attn_full": [900.0, 100.0], "mlp": [700.0, 50.0],
             "exits": [500.0, 80.0], "ln": [60.0, 40.0],
             "optimizer": [30.0, 20.0], "unscoped": [10.0, 5.0]}
    mine = {"record": {"sizes": SIZES, "batch_rows": 2, "chips": 1},
            "trace": no_ops, "peaks": peaks,
            "program": {"spans": inst, "parts_s": 0.0, "device_ms": parts}}
    value, extra = readers.exits_ms(mine, {"params": {"parts": ["exits"]}})
    assert value == 500.0 and extra["instants"] == 2
    assert extra["exit_p"] == pytest.approx([0.4, 0.275, 0.1625, 0.1625])
    assert extra["exit_nll"] == [10.0, 9.0, 8.0, 7.0]
    pct, extra = readers.exits_roofline_pct(mine, {"params": {"part":
                                                              "exits"}})
    cost = flops_ouro.exits_cost(SIZES, 16384)["ops"]
    assert pct == pytest.approx(100 * cost / 197e12 / 0.5)
    assert extra["bound"] == "compute"
    assert readers.attention_roofline_pct(mine, roof) is None  # no device
    files = {}
    for name in OWN:
        with open(os.path.join(helpers.CHIPBENCH, "layer_metrics",
                               f"{name}.json")) as f:
            files[name] = json.load(f)
    total = 0.0
    for name in ("dev_ms_ouro_attn", "dev_ms_ouro_mlp", "dev_ms_ouro_exits",
                 "dev_ms_ouro_rest"):
        module, fn = files[name]["reducer"].split(":")
        assert module == "ouro"
        total += getattr(readers, fn)(mine, files[name])[0]
    assert total == sum(ms for ms, _ in parts.values())
    assert program.device_ms_of_parts is readers.device_ms_of_parts
