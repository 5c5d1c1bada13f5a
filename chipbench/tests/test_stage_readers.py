"""The reader of the stages inside ``moe_route``
(``chipbench/reducers/stages.py``) on synthetic device events and synthetic
maps: the seven metrics partition what the accepted reader gives the part,
nested events are counted once, a program without a stage map gives None, and
the seven files name stages the program has. No recorded trace, no trainer."""

import json
import os

import pytest

from chipbench.reducers import program, stages
from chipbench.tests import helpers
from chipbench.tests.test_program_readers import make_run, on_trace
from chipbench.trace import Event

CELLS = ["trinity-mini-ep8.train-b2-t8192", "lfm2-8b-a1b-ep4.train-b2-t8192",
         "moonlight-16b-a3b-ep8.train-b2-t8192"]
NAMES = ["dev_ms_route_router", "dev_ms_route_plan", "dev_ms_route_dispatch",
         "dev_ms_route_combine", "dev_ms_route_weights",
         "dev_ms_route_accumulate", "dev_ms_route_unstaged"]


def spec_of(name):
    with open(os.path.join(helpers.CHIPBENCH, "layer_metrics", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def step(at):
    """One step's ops on chip 0: the router's fusion, then the walk (a loop
    whose event spans its body's: a gather, the mover's kernel under combine,
    an expert matmul, a cast of the expert matrices), a copy the compiler
    made, and an op of another part."""
    def ev(name, a, b):
        return Event(name, on_trace(at + a), on_trace(at + b))
    return [
        ev("%fusion.1 = f32[8] fusion(...)", 0, 1),
        ev("%sort.2 = (s32[8]) sort(...)", 1, 1.5),
        ev("%while.3 = (f32[8]) while(...)", 2, 9),
        ev("%fusion.4 = bf16[8] fusion(...)", 2.5, 4.5),
        ev('%moe_rows.5 = f32[8] custom-call(...), custom_call_target='
           '"tpu_custom_call"', 4.5, 5.5),
        ev("%gmm.6 = bf16[8] custom-call(...)", 5.5, 7),
        ev("%fusion.7 = bf16[8] fusion(...)", 7, 8.5),
        ev("%copy.8 = f32[8] copy(...)", 9, 9.25),
        ev("%fusion.9 = f32[8] fusion(...)", 9.25, 10),
    ]


PARTS = {"fusion.1": "moe_route", "sort.2": "moe_route",
         "while.3": "moe_route", "fusion.4": "moe_route",
         "moe_rows.5": "moe_route", "gmm.6": "moe_experts",
         "fusion.7": "moe_route", "copy.8": "moe_route", "fusion.9": "mlp"}
STAGES = {"fusion.1": "route_router", "sort.2": "route_plan",
          "while.3": "route_accumulate", "fusion.4": "route_dispatch",
          "moe_rows.5": "route_combine", "fusion.7": "route_weights",
          "copy.8": "unstaged"}


def staged_run():
    run = make_run([], PARTS, ops=step(101) + step(113))
    run["program"].update(stages=STAGES, stages_s=0.0)
    return run


def test_the_seven_partition_the_part():
    run = staged_run()
    got = {n: stages.device_ms_of_stages(run, spec_of(n)) for n in NAMES}
    values = {n: v for n, (v, _) in got.items()}
    assert values == {
        "dev_ms_route_router": pytest.approx(1.0),
        "dev_ms_route_plan": pytest.approx(0.5),
        "dev_ms_route_dispatch": pytest.approx(2.0),
        "dev_ms_route_combine": pytest.approx(1.0),
        "dev_ms_route_weights": pytest.approx(1.5),
        # the loop's event spans 7 ms; 6 of them are its body's
        "dev_ms_route_accumulate": pytest.approx(1.0),
        "dev_ms_route_unstaged": pytest.approx(0.25)}
    part, _ = program.device_ms_of_parts(
        run, {"params": {"parts": ["moe_route"]}})
    assert sum(values.values()) == pytest.approx(part, abs=1e-9)
    assert part == pytest.approx(7.25)
    extra = got["dev_ms_route_combine"][1]
    assert extra["kernel_ms"] == pytest.approx(1.0)
    assert extra["ops_per_step"] == 1
    assert extra["share_of_part_pct"] == pytest.approx(100.0 / 7.25)
    assert got["dev_ms_route_dispatch"][1]["kernel_ms"] == 0.0
    assert sum(e["share_of_part_pct"] for _, e in got.values()) == \
        pytest.approx(100.0)
    for _, e in got.values():
        assert e["map_s"] == 0.0 and e["read_s"] >= 0.0


def test_an_op_of_another_part_or_of_no_map_is_in_no_stage():
    run = staged_run()
    run["trace"].ops[0].append(Event("%fusion.77 = f32[8] fusion(...)",
                                     on_trace(111.5), on_trace(112.5)))
    whole = sum(stages.device_ms_of_stages(run, spec_of(n))[0] for n in NAMES)
    assert whole == pytest.approx(7.25)
    # a stage with no op in the trace reads 0, as a part does
    run = staged_run()
    run["program"]["stages"] = {k: v for k, v in STAGES.items()
                                if v != "unstaged"}
    value, extra = stages.device_ms_of_stages(
        run, spec_of("dev_ms_route_unstaged"))
    assert value == 0.0 and extra["ops_per_step"] == 0


def test_nothing_to_read_without_a_stage_map_or_a_device(monkeypatch):
    m = spec_of("dev_ms_route_plan")
    # the parent's program: opscopes without step_stages
    from nanosandbox_tpu.obs import opscopes
    monkeypatch.delattr(opscopes, "step_stages")
    run = make_run([], PARTS, ops=step(101))
    assert stages.device_ms_of_stages(run, m) is None
    assert run["program"]["stages"] is None
    monkeypatch.undo()
    # no provider in this process: no map
    opscopes.set_provider(None)
    assert stages.device_ms_of_stages(make_run([], PARTS, ops=step(101)),
                                      m) is None
    run = staged_run()
    run["trace"].ops[0] = []
    assert stages.device_ms_of_stages(run, m) is None
    run = staged_run()
    run["record"]["steps"] = 0
    assert stages.device_ms_of_stages(run, m) is None


def test_the_stage_map_is_the_programs_own():
    from nanosandbox_tpu.obs import opscopes
    opscopes.set_provider(lambda: (PARTS, STAGES))
    try:
        run = make_run([], ops=step(101) + step(113))
        del run["program"]["parts"]
        value, extra = stages.device_ms_of_stages(
            run, spec_of("dev_ms_route_dispatch"))
        assert value == pytest.approx(2.0)
        assert run["program"]["stages"] is STAGES
        # the accepted reader's map is the same provider's, asked once
        assert program.step_parts(run) is PARTS
    finally:
        opscopes.set_provider(None)


def test_the_seven_files_name_stages_the_program_has():
    from nanosandbox_tpu.obs import opscopes
    with open(os.path.join(helpers.REPO, "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    named = []
    for name in NAMES:
        spec, entry = spec_of(name), entries[name]
        assert spec["reducer"] == "stages:device_ms_of_stages"
        assert entry["workloads"] == CELLS
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ms", "lower", "device_trace")
        named += spec["params"]["stages"]
    assert sorted(named) == sorted(opscopes.STAGES + (opscopes.UNSTAGED,))
    assert [m["name"] for m in bench["per_layer"]][-7:] == NAMES
