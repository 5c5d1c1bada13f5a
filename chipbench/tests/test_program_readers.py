"""The readers of the program's own spans, counters and part map
(``chipbench/reducers/program.py``) on synthetic spans, instants, device
events and a synthetic map: the arithmetic, the clock anchor, and None where
the program offers nothing. No recorded trace, no trainer."""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench.reducers import program
from chipbench.tests import helpers
from chipbench.trace import Event, Trace

MS = 1e6  # ns
# The host's clock (perf_counter) and the trace's differ by this much.
OFFSET = 7_000_000_000.0
_sid = iter(range(1, 10_000))


def span(name, t0_ms, dur_ms, *, parent=0, track=None, step=None, **args):
    """A span as the program's tracer keeps it, on the HOST's clock."""
    return SimpleNamespace(sid=next(_sid), name=name, t0_ns=t0_ms * MS,
                           dur_ns=dur_ms * MS, parent=parent, track=track,
                           step=step, args=args)


def instant(phase, t0_ms, seconds=0.0):
    return span("jax_compile", t0_ms, 0, phase=phase, seconds=seconds)


def make_run(spans, parts=None, *, steps=2, ops=None, window_ms=(100.0, 125.0)):
    """A window of 25 ms that is [100, 125] ms on the host's clock and the
    same stretch moved by OFFSET on the trace's; the annotation opens 4 us
    before the runner reads its clock and closes 6 us after."""
    w0, w1 = window_ms
    tr = Trace(modules=[[]], ops=[ops if ops is not None else []], host=[],
               window=(w0 * MS + OFFSET - 4e3, w1 * MS + OFFSET + 6e3))
    return {"trace": tr, "peaks": None, "compile": None,
            "record": {"steps": steps, "window_t0": w0 / 1e3,
                       "window_t1": w1 / 1e3},
            "program": {"spans": spans, "parts": parts, "parts_s": 0.25}}


def on_trace(ms):
    return ms * MS + OFFSET


# -- the clock anchor ----------------------------------------------------------

def test_anchor_moves_a_host_stamp_onto_the_trace():
    run = make_run([])
    offset, skew = program.trace_offset_ns(run)
    # the two ends err by -4 us and +6 us: the mean is 1 us off, and the
    # difference is what the reader reports as skew
    assert offset == pytest.approx(OFFSET + 1e3)
    assert skew == pytest.approx(10e3)
    assert 110 * MS + offset == pytest.approx(on_trace(110), abs=2e3)
    # no window on either clock: no anchor
    run["trace"].window = (0.0, 0.0)
    assert program.trace_offset_ns(run) is None
    run = make_run([])
    del run["record"]["window_t0"]
    assert program.trace_offset_ns(run) is None


INPUT = {"params": {"spans": ["loader_wait", "to_global"]}}


def test_idle_is_attributed_to_the_span_known_on_both_clocks():
    # chip 0 runs 101..111 and 113..123 ms: idle 100..101, 111..113 and
    # 123..125 (plus the annotation's few microseconds at either end)
    ops = [Event("%fusion.1 = f32[8] fusion(...)", on_trace(101), on_trace(111)),
           Event("%fusion.1 = f32[8] fusion(...)", on_trace(113), on_trace(123))]
    iter_ = span("train_iter", 110.5, 2.4)            # covers 110.5..112.9
    spans = [
        iter_,
        span("to_global", 111.2, 1.0, parent=iter_.sid),   # 111.2..112.2, inside
        span("loader_wait", 99.0, 1.5),                # 0.5 ms of it in the window
        span("loader_full", 100.0, 25.0, track="loader_prefetch"),  # not main
        span("train-log-readback", 123.5, 1.0),        # 123.5..124.5
        span("trainer_init", 10.0, 50.0),              # long before the window
    ]
    value, extra = program.idle_ms_under_spans(make_run(spans, ops=ops), INPUT)
    by = {k: 2 * v for k, v in extra["idle_ms_by_span"].items()}   # two steps
    # nested spans are not counted twice: to_global keeps its 1.0 ms, the
    # train_iter round it gets what is left of 111..112.9
    assert by["to_global"] == pytest.approx(1.0, abs=0.01)
    assert by["train_iter"] == pytest.approx(0.9, abs=0.01)
    assert by["loader_wait"] == pytest.approx(0.5, abs=0.01)
    assert by["train-log-readback"] == pytest.approx(1.0, abs=0.01)
    assert "loader_full" not in by and "trainer_init" not in by
    # the metric: idle under the input path's own spans, a step
    assert value == pytest.approx((1.0 + 0.5) / 2, abs=0.01)
    assert sum(by.values()) == pytest.approx(3.4, abs=0.01)
    assert extra["idle_ms_unspanned"] == pytest.approx((5.01 - 3.4) / 2, abs=0.01)
    assert extra["anchor_skew_us"] == pytest.approx(10.0)
    # idle, but none of it under the named spans: 0, which is a reading
    other = {"params": {"spans": ["no_such_span"]}}
    assert program.idle_ms_under_spans(make_run(spans, ops=ops), other)[0] == 0.0


def test_idle_reader_has_nothing_without_spans_or_device():
    ops = [Event("%fusion.1 = f32[8] fusion(...)", on_trace(101), on_trace(111))]
    assert program.idle_ms_under_spans(make_run([], ops=ops), INPUT) is None
    run = make_run([span("to_global", 111.2, 1.0)])
    run["trace"].ops = []
    assert program.idle_ms_under_spans(run, INPUT) is None
    # a chip that never idles in the window reads 0
    busy = [Event("%fusion.1 = f32[8] fusion(...)", on_trace(99), on_trace(126))]
    value, extra = program.idle_ms_under_spans(
        make_run([span("to_global", 111.2, 1.0)], ops=busy), INPUT)
    assert value == 0.0 and extra["idle_ms_by_span"] == {}


# -- set-up ---------------------------------------------------------------------

def test_trainer_init_with_its_children():
    first = span("trainer_init", 1.0, 5.0)            # an earlier trainer
    init = span("trainer_init", 20.0, 40.0)
    spans = [first, init,
             span("dataset_open", 21.0, 1.0, parent=init.sid),
             span("abstract_state", 25.0, 30.0, parent=init.sid),
             span("make_mesh", 2.0, 1.0, parent=first.sid),
             span("trainer_init", 130.0, 5.0)]         # after the window: not it
    value, extra = program.trainer_init_s(make_run(spans), {})
    assert value == pytest.approx(0.040)
    assert extra == {"dataset_open_s": pytest.approx(0.001),
                     "abstract_state_s": pytest.approx(0.030)}
    assert program.trainer_init_s(make_run([span("to_global", 1, 1)]), {}) is None


def test_compile_phases_as_they_stood_at_the_window():
    spans = [instant("trace", 10, 0.5), instant("lower", 11, 0.25),
             instant("cache_load", 12, 2.0), instant("hit", 12),
             instant("backend", 12.5, 2.1), instant("miss", 30),
             instant("trace", 40, 0.125),
             instant("cache_load", 130, 9.0), instant("hit", 130)]  # the reference's
    load = {"params": {"phases": ["cache_load"]}}
    value, extra = program.compile_phase_s(make_run(spans), load)
    assert value == pytest.approx(2.0)
    by_phase = {"trace": 0.625, "lower": 0.25, "cache_load": 2.0,
                "backend": 2.1}
    assert extra == {"s_by_phase": by_phase, "cache_hits": 1,
                     "cache_misses": 1}
    both = {"params": {"phases": ["trace", "lower"]}}
    value, extra = program.compile_phase_s(make_run(spans), both)
    assert value == pytest.approx(0.875) and extra["s_by_phase"] == by_phase
    # a cold run met no cache load: that reads 0, not nothing
    cold = [instant("trace", 10, 0.5), instant("miss", 11)]
    assert program.compile_phase_s(make_run(cold), load)[0] == 0.0
    # no listeners at all: nothing to read
    assert program.compile_phase_s(make_run([span("to_global", 1, 1)]), load) is None


# -- the input path -------------------------------------------------------------

def test_input_path_spans_per_step_and_per_batch():
    spans = [span("loader_wait", 101, 0.5, depth=2), span("loader_wait", 113, 1.5, depth=0),
             span("loader_wait", 90, 50.0, depth=0),     # began before the window
             span("to_global", 102, 1.0), span("to_global", 103, 1.0),
             span("to_global", 114, 1.0), span("to_global", 115, 3.0),
             span("loader_fill", 101, 4.0, track="loader_prefetch"),
             span("loader_fill", 107, 5.0, track="loader_prefetch"),
             span("loader_fill", 113, 6.0, track="loader_prefetch"),
             span("loader_full", 105, 1.5, track="loader_prefetch"),
             span("loader_full", 119, 4.5, track="loader_prefetch"),
             # the last wait lasts until the loader is closed: 0 of it counts
             span("loader_full", 125, 9000.0, track="loader_prefetch")]
    run = make_run(spans, steps=2)
    value, extra = program.loader_wait_ms(run, {"params": {"spans": ["loader_wait"]}})
    assert value == pytest.approx(1.0)
    assert extra == {"spans": 2, "starved": 1, "mean_depth": 1.0}
    value, extra = program.span_ms_per_step(run, {"params": {"spans": ["to_global"]}})
    assert value == pytest.approx(3.0) and extra == {"spans": 4}
    value, extra = program.span_ms_mean(run, {"params": {"spans": ["loader_fill"]}})
    assert value == pytest.approx(5.0) and extra == {"spans": 3}
    # the producer's slack beside its work, per batch made
    value, extra = program.span_ms_mean(run, {"params": {
        "spans": ["loader_fill"], "beside": ["loader_full", "no_such_span"]}})
    assert value == pytest.approx(5.0)
    assert extra == {"spans": 3, "loader_full_ms": pytest.approx(2.0),
                     "no_such_span_ms": 0.0}
    for reader in (program.loader_wait_ms, program.span_ms_per_step,
                   program.span_ms_mean):
        assert reader(run, {"params": {"spans": ["no_such_span"]}}) is None


# -- device time by part -------------------------------------------------------

def test_self_times_of_nested_events_sum_to_their_union():
    ev = [Event("while", 0, 10), Event("body.a", 1, 4), Event("body.b", 4, 9),
          Event("inner", 5, 6), Event("after", 12, 15)]
    order, own = program.self_times(ev)
    assert dict(zip((e.name for e in order), own)) == {
        "while": 2, "body.a": 3, "body.b": 4, "inner": 1, "after": 3}
    assert sum(own) == 13


def test_device_ms_by_part():
    def step(at):
        return [
            Event("%fusion.1 = f32[8] fusion(...)", on_trace(at), on_trace(at + 4)),
            Event('%attn.3 = (bf16[2]) custom-call(...), custom_call_target="tpu_custom_call"',
                  on_trace(at + 4), on_trace(at + 6)),
            Event("%while.2 = (f32[8]) while(...)", on_trace(at + 6), on_trace(at + 9)),
            Event("%fusion.7 = f32[8] fusion(...)", on_trace(at + 6.5), on_trace(at + 8.5)),
            Event("%copy-done.4 = f32[8] copy-done(...)", on_trace(at + 9), on_trace(at + 9.5)),
            Event("%fusion.99 = f32[8] fusion(...)", on_trace(at + 9.5), on_trace(at + 10)),
        ]
    parts = {"fusion.1": "mlp", "attn.3": "attn", "while.2": "lm_head_loss",
             "fusion.7": "lm_head_loss", "copy-done.4": "unscoped"}
    run = make_run([], parts, ops=step(101) + step(113))
    read = program.device_ms_of_parts
    value, extra = read(run, {"params": {"parts": ["mlp"]}})
    assert value == pytest.approx(4.0)
    assert extra == {"share_of_step_pct": pytest.approx(40.0),
                     "ops_per_step": 1, "map_s": 0.25}
    # the loop's own time and its body's, counted once
    value, extra = read(run, {"params": {"parts": ["lm_head_loss"]}})
    assert value == pytest.approx(3.0) and extra["ops_per_step"] == 2
    assert read(run, {"params": {"parts": ["attn"]}})[0] == pytest.approx(2.0)
    # a part with no op in the trace reads 0; an op the map lacks is 'unmapped'
    assert read(run, {"params": {"parts": ["optimizer", "grad_norm"]}})[0] == 0.0
    value, extra = read(run, {"params": {"parts": ["ln", "embed", "unscoped", "unmapped"]}})
    assert value == pytest.approx(1.0)
    assert extra["ms_by_part"] == {"unscoped": pytest.approx(0.5),
                                   "unmapped": pytest.approx(0.5)}
    # the parts sum to the chip's busy time a step
    total = sum(read(run, {"params": {"parts": [p]}})[0] for p in
                ("mlp", "attn", "lm_head_loss", "unscoped", "unmapped"))
    assert total == pytest.approx(10.0)


def test_device_reader_has_nothing_without_a_map_or_a_device():
    ops = [Event("%fusion.1 = f32[8] fusion(...)", on_trace(101), on_trace(105))]
    m = {"params": {"parts": ["mlp"]}}
    assert program.device_ms_of_parts(make_run([], None, ops=ops), m) is None
    assert program.device_ms_of_parts(make_run([], {"fusion.1": "mlp"}, ops=[]), m) is None
    run = make_run([], {"fusion.1": "mlp"}, ops=ops, steps=0)
    assert program.device_ms_of_parts(run, m) is None


# -- a program that offers nothing (the parent of the PR that added these) ------

def test_every_reader_returns_none_when_the_program_offers_nothing(monkeypatch):
    monkeypatch.setattr(program, "_process_spans", lambda: [])
    monkeypatch.setattr(program, "_parts_or_none", lambda: None)
    ops = [Event("%fusion.1 = f32[8] fusion(...)", on_trace(101), on_trace(105))]
    for name in sorted(os.listdir(os.path.join(helpers.CHIPBENCH, "layer_metrics"))):
        with open(os.path.join(helpers.CHIPBENCH, "layer_metrics", name),
                  encoding="utf-8") as f:
            spec = json.load(f)
        module, function = spec["reducer"].split(":")
        if module != "program":
            continue
        run = make_run([], ops=ops)
        del run["program"]            # read from the (empty) process
        assert getattr(program, function)(run, spec) is None, spec["name"]


def test_the_twelve_metrics_are_declared_for_the_two_training_cells():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if os.path.exists(os.path.join(
        helpers.CHIPBENCH, "layer_metrics", f"{m['name']}.json")) and json.load(open(
            os.path.join(helpers.CHIPBENCH, "layer_metrics", f"{m['name']}.json"),
            encoding="utf-8"))["reducer"].startswith("program:")]
    assert len(mine) == 12
    cells = ["gpt2-124m.train-b16-t1024", "gpt2-medium.train-b8-t1024"]
    for m in mine:
        assert m["workloads"] == cells, m["name"]
        spec = json.load(open(os.path.join(
            helpers.CHIPBENCH, "layer_metrics", f"{m['name']}.json"), encoding="utf-8"))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)


def test_process_spans_come_from_the_programs_tracer():
    from nanosandbox_tpu.obs import process_tracer

    with process_tracer().span("probe_span_for_the_reader_test"):
        pass
    run = make_run([])
    del run["program"]
    names = [s.name for s in program.program_side(run)["spans"]]
    assert "probe_span_for_the_reader_test" in names
