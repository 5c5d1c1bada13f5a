"""The harness is driven by data: a configuration, a cell, a per-layer
metric with a new reader and a new runner are each added as files and
entries in a temporary copy, with no file of the benchmark edited. And the
whole run at a toy size on the CPU: ``correct`` comes out true for the
program as it is and false with the timed path broken underneath."""

import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench.tests import helpers


def _args(workload, seed=2**31 + 17, seconds=1.0, trace=0):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)


def _edit_bench(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    fn(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


FAKE_RUNNER = '''
def run(ctx):
    return {"attempted": 3, "failed": 0, "faults": [], "memory_peak_bytes": 1,
            "check": {"answer_gap": {"value": 0.0, "limit": 0.0}},
            "setup_s": 1.5, "values": {"train_tok_s_chip": 7.0},
            "steps": 3, "seen_config": ctx.config["name"],
            "seen_traffic": ctx.traffic["name"], "compile_in_setup": {}}
'''
NEW_READER = '''
def read(run, metric):
    return float(run["record"]["steps"] * metric["params"]["times"]), {"of": run["record"]["seen_config"]}


def nothing(run, metric):
    return None
'''


def test_config_cell_metric_reader_and_runner_are_added_as_files(tmp_path, monkeypatch):
    root = helpers.copy_root(str(tmp_path))
    cb = os.path.join(root, "chipbench")
    before = {f: open(os.path.join(dp, f)).read()
              for dp, _, fs in os.walk(cb) for f in fs}
    os.makedirs(os.path.join(cb, "runners"))
    os.makedirs(os.path.join(cb, "reducers"))
    for rel, text in {
        "runners/fake.py": FAKE_RUNNER,
        "reducers/newreader.py": NEW_READER,
        "configs/new-model.json": json.dumps({"name": "new-model"}),
        "traffic/new-mix.json": json.dumps({"name": "new-mix"}),
        "workloads/new-model.new-mix.json": json.dumps({
            "name": "new-model.new-mix", "config": "new-model",
            "traffic": "new-mix", "chips": 1, "runner": "fake"}),
        "layer_metrics/steps_times_two.json": json.dumps({
            "name": "steps_times_two", "reducer": "newreader:read",
            "params": {"times": 2}}),
        "layer_metrics/never_there.json": json.dumps({
            "name": "never_there", "reducer": "newreader:nothing", "params": {}}),
    }.items():
        with open(os.path.join(cb, rel), "w") as f:
            f.write(text)

    def add(bench):
        bench["configs"].append({"name": "new-model", "source": "x", "reduced": [],
                                 "file": "chipbench/configs/new-model.json", "why": "t"})
        bench["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                                   "traffic": "new-mix", "chips": 1, "why": "t"})
        for name in ("steps_times_two", "never_there"):
            bench["per_layer"].append({
                "name": name, "unit": "steps", "better": "higher",
                "source": "program_counter", "layer": "trainer step",
                "moves": "train_tok_s_chip", "workloads": ["new-model.new-mix"]})

    _edit_bench(root, add)
    # the trace of a fake run: none was written
    monkeypatch.setattr("chipbench.trace.load", lambda d: __import__(
        "chipbench.trace", fromlist=["Trace"]).Trace())
    out = harness.drive(_args("new-model.new-mix"), require_chip=False, root=root)
    assert out["correct"] and out["attempted"] == 3
    assert out["metrics"] == {"train_tok_s_chip": {"value": 7.0, "unit": "tokens/s"},
                              "setup_s": {"value": 1.5, "unit": "s"}}
    assert list(out)[-1] == "check"
    traced = harness.drive(_args("new-model.new-mix", trace=1),
                           require_chip=False, root=root)
    # the new metric is read by its own reader; a reader with nothing to
    # read is left out; the other cells' metrics do not list this cell...
    assert traced["metrics"]["steps_times_two"] == {
        "value": 6.0, "unit": "steps", "of": "new-model"}
    assert "never_there" not in traced["metrics"]
    # ...and nothing that was there has been edited
    for f, text in before.items():
        found = [os.path.join(dp, f) for dp, _, fs in os.walk(cb) if f in fs]
        assert open(found[0]).read() == text


def test_every_entry_of_benchmark_json_has_its_files():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["paths"] == ["chipbench"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        found = harness.find_cell(w["name"])
        assert found.config["name"] == w["config"]
        assert found.traffic["name"] == w["traffic"]
        assert set(found.cell["check"]["limits"]) <= {
            "loss_gap", "grad_norm_gap", "g1_leaf_gap", "dp_leaf_gap"}
        assert harness.metrics_of(found, "per_layer")
    for c in bench["configs"]:
        with open(os.path.join(helpers.REPO, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for m in bench["per_layer"]:
        with open(os.path.join(helpers.CHIPBENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for k in ("unit", "layer", "moves", "better", "source"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e
        module, fn = spec["reducer"].split(":")
        assert hasattr(harness.plug_in(helpers.CHIPBENCH, "reducers", module), fn)


def test_no_chip_no_run(tmp_path):
    """On this CPU the command itself exits non-zero and prints no result."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2-124m.train-b16-t1024", "--seed", "1", "--seconds", "1"],
        cwd=helpers.REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = helpers.copy_root(str(tmp_path_factory.mktemp("root")))
    return root, helpers.add_tiny(root)


def _state_unchanged(step):
    def broken(state, x, y, rng):
        _, metrics = step(state, x, y, rng)
        return state, metrics
    return broken


def _half_left_out(step):
    import jax.numpy as jnp

    def broken(state, x, y, rng):
        h = x.shape[0] // 2
        return step(state, jnp.concatenate([x[:h], x[:h]]),
                    jnp.concatenate([y[:h], y[:h]]), rng)
    return broken


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch_left_out"])
def test_a_whole_run_and_the_faults_it_must_catch(tiny_root, data_dir, fault):
    root, cell = tiny_root
    broken = {"none": None, "state_unchanged": _state_unchanged,
              "half_batch_left_out": _half_left_out}[fault]
    out = harness.drive(_args(cell), require_chip=False, root=root,
                        data_dir=data_dir, break_step=broken)
    assert set(out["check"]) == {"loss_gap", "grad_norm_gap", "g1_leaf_gap",
                                 "dp_leaf_gap"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["train_tok_s_chip"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["correct"] is (fault == "none"), out
    over = {k for k, c in out["check"].items() if not c["value"] <= c["limit"]}
    if fault == "state_unchanged":
        # nothing moved: the change reads 1, and the state counts no steps
        assert "dp_leaf_gap" in over and out["check"]["dp_leaf_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
        assert any("state counts" in f for f in out["faults"])
    if fault == "half_batch_left_out":
        assert {"grad_norm_gap", "g1_leaf_gap"} <= over and not out["faults"]
