"""The trace reduction on synthetic events (no recorded trace needed)."""

import pytest

from chipbench import trace
from chipbench.reducers import device
from chipbench.trace import Event, Trace

MS = 1e6  # ns


def make_trace():
    # two steps of 10 ms on chip 0 with a 2 ms hole between them, in a
    # window of 25 ms that starts 1 ms before the first step
    ops = [Event("%fusion.1 = f32[8] fusion(...)", 1 * MS, 6 * MS),
           Event('%attn.3 = (bf16[2]) custom-call(...), custom_call_target="tpu_custom_call"', 6 * MS, 8 * MS),
           Event("%all-gather.2 = f32[8] all-gather(...)", 8 * MS, 11 * MS),
           Event("%fusion.9 = f32[8] fusion(...)", 10 * MS, 11 * MS),
           Event("%fusion.1 = f32[8] fusion(...)", 13 * MS, 18 * MS),
           Event('%attn.3 = (bf16[2]) custom-call(...), custom_call_target="tpu_custom_call"', 18 * MS, 20 * MS),
           Event("%all-gather.2 = f32[8] all-gather(...)", 20 * MS, 23 * MS),
           Event("%fusion.9 = f32[8] fusion(...)", 22 * MS, 23 * MS)]
    modules = [Event("jit_traced(123)", 1 * MS, 11 * MS),
               Event("jit__threefry_fold_in(9)", 11 * MS, 11.001 * MS),
               Event("jit_traced(123)", 13 * MS, 23 * MS)]
    host = [Event("window", 0.0, 25 * MS), Event("dispatch", 0.0, 0.9 * MS),
            Event("loader_next", 11.2 * MS, 12.9 * MS),
            Event("drain", 23 * MS, 25 * MS)]
    return Trace(modules=[modules], ops=[ops], host=host, window=(0.0, 25 * MS))


def test_union_gaps_subtract():
    ev = [Event("a", 0, 4), Event("b", 2, 6), Event("c", 8, 9)]
    assert trace.union(ev) == [(0, 6), (8, 9)]
    assert trace.covered(trace.union(ev)) == 7
    assert trace.gaps(trace.union(ev), (0, 10)) == [(6, 8), (9, 10)]
    assert trace.gaps([], (3, 5)) == [(3, 5)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.clip(ev, (3, 8.5)) == [Event("a", 3, 4), Event("b", 3, 6),
                                        Event("c", 8, 8.5)]


def test_busy_idle_and_attribution():
    tr = make_trace()
    assert trace.busy_seconds(tr, 0) == pytest.approx(20e-3)
    gaps = trace.idle_gaps(tr, 0)
    assert gaps == [(0.0, 1 * MS), (11 * MS, 13 * MS), (23 * MS, 25 * MS)]
    assert [trace.attribute(g, tr.host) for g in gaps] == \
        ["dispatch", "loader_next", "drain"]
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(10e-3)]
    assert ["attn.*", pytest.approx(4e-3)] in b["device_ops"]
    assert dict(map(tuple, b["idle_gaps"])) == pytest.approx(
        {"loader_next": 2e-3, "drain": 2e-3, "dispatch": 1e-3})


def _run(tr, **rec):
    record = {"steps": 2, "chips": 1, "batch_rows": 16,
              "sizes": {"n_layer": 12, "n_head": 12, "n_embd": 768,
                        "vocab_size": 50304, "block_size": 1024, "bias": False}}
    record.update(rec)
    return {"trace": tr, "record": record,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_the_synthetic_trace():
    tr = make_trace()
    step = {"params": {"step_program": r"^jit_traced\("}}
    assert device.device_idle_pct(_run(tr), {}) == pytest.approx(20.0)
    # fewer than two gaps: nothing to read, nothing reported
    assert device.step_gap_p95_ms(_run(tr), step) is None
    # all-gather runs 3 ms a step, 1 ms of it under fusion.9: 2 ms exposed
    m = {"params": {"pattern": "^%(all-gather|all-reduce|reduce-scatter)"}}
    assert device.collective_exposed_pct(_run(tr), m) == pytest.approx(100 * 4 / 25)
    m = {"params": {"pattern": "^%send"}}
    assert device.collective_exposed_pct(_run(tr), m) is None
    m = {"params": {"step_program": r"^jit_traced\(", "pattern": "tpu_custom_call",
                    "cost": "flash_attention_cost"}}
    value, extra = device.kernel_roofline_pct(_run(tr), m)
    # 2 ms of kernel a step against a least time of ~4.7 ms: over 100 % here
    # is the synthetic trace's doing; what is checked is the arithmetic
    assert extra["kernel_ms_per_step"] == pytest.approx(2.0)
    assert extra["events_per_step"] == 1
    assert value == pytest.approx(100 * (12 * 16 * 12 * 6 * 2 * 1024 * 1024 * 64 / 2 / 197e12) / 2e-3)
    m["params"]["pattern"] = "no such kernel"
    assert device.kernel_roofline_pct(_run(tr), m) is None


def test_step_gaps():
    mods = [Event("jit_traced(1)", i * 10 * MS, i * 10 * MS + 9 * MS) for i in range(30)]
    mods[20] = Event("jit_traced(1)", 205 * MS, 209 * MS)   # one late step
    tr = Trace(modules=[mods], ops=[[]], host=[], window=(0.0, 300 * MS))
    value, extra = device.step_gap_p95_ms(
        _run(tr), {"params": {"step_program": r"^jit_traced\("}})
    assert extra["n"] == 29 and extra["median"] == pytest.approx(10.0)
    assert 10.0 < value <= 15.0
