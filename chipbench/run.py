"""The benchmark's one command.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It finds the cell in ``BENCHMARK.json``, the cell's
own file under ``chipbench/workloads/``, its configuration and its traffic
mix by their names, checks that JAX sees the chip the cell asks for, hands
over to the runner the cell names (``chipbench/runners/<runner>.py``), and
prints the contract's one JSON line last on standard output. With
``--trace 1`` it reads the per-layer metrics through the reader each
metric's file names (``chipbench/reducers/<module>.py``). Nothing in this
file names a cell, a configuration or a metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # as near to the start of the process as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json "
                     f"(known: {[e['name'] for e in entries]})")


def find_cell(workload: str, root: str = ROOT) -> SimpleNamespace:
    """Everything the files say about one cell."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    entry = _named(bench["workloads"], workload, "workload")
    config_entry = _named(bench["configs"], entry["config"], "config")
    here = os.path.join(root, "chipbench")
    cell = _load(os.path.join(here, "workloads", f"{workload}.json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"chipbench: {workload}: {key!r} differs between "
                             "BENCHMARK.json and the cell's file")
    return SimpleNamespace(
        bench=bench, entry=entry, cell=cell,
        config=_load(os.path.join(root, config_entry["file"])),
        traffic=_load(os.path.join(here, "traffic", f"{entry['traffic']}.json")),
        here=here)


def plug_in(here: str, kind: str, name: str):
    """The module ``<here>/<kind>/<name>.py``, found by its file: a later PR
    adds a runner or a reader by adding that file and nothing else."""
    import importlib.util

    qualified = f"chipbench.{kind}.{name}"
    path = os.path.join(here, kind, f"{name}.py")
    if here == HERE or not os.path.exists(path):
        return importlib.import_module(qualified)
    spec = importlib.util.spec_from_file_location(qualified, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(found: SimpleNamespace, kind: str) -> list[dict]:
    """The cell's metrics of one kind: those that list it under
    ``workloads``, and those with no such key whose end-to-end metric the
    cell reports."""
    name = found.entry["name"]

    def reported(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in found.bench["end_to_end"] if reported(m)]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in found.bench["per_layer"]
            if reported(m) and m["moves"] in moved]


def look_for_the_chip(chips: int) -> dict:
    """The device as JAX reports it; no TPU, a kind without published peaks
    or another count than the cell's ends the run before any work."""
    import jax

    from chipbench import flops

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{d.platform!r}); the benchmark measures nowhere else")
    if len(devices) != chips:
        raise SystemExit(f"chipbench: the cell asks for {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    try:
        flops.load_peaks(d.device_kind)
    except KeyError as e:
        raise SystemExit(f"chipbench: {e.args[0]}")
    return describe_device()


def describe_device() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileClock:
    """Seconds the backend compiled for, and persistent-cache hits, from
    ``jax.monitoring`` (copied from chip_smoke.py's clock)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "count": self.count,
                "cache_hits": self.cache_hits}


def read_layer_metrics(found, run: dict) -> dict:
    out = {}
    for m in metrics_of(found, "per_layer"):
        spec = _load(os.path.join(found.here, "layer_metrics", f"{m['name']}.json"))
        module, function = spec["reducer"].split(":")
        reader = getattr(plug_in(found.here, "reducers", module), function)
        got = reader(run, spec)
        if got is None:
            continue  # nothing to read: the metric is left out of the line
        value, extra = got if isinstance(got, tuple) else (got, {})
        if value is None or not math.isfinite(value):
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"], **extra}
    return out


def make_ctx(found, seed: int, seconds: float, trace, data_dir=None,
             break_step=None):
    """What a runner is handed: the cell's files, the run's arguments, the
    compile clock, and where to keep what the run makes."""
    import jax

    work_dir = os.path.join(found.here, "_work")
    trace_dir = os.path.join(work_dir, "trace")
    ctx = SimpleNamespace(
        seed=seed, seconds=seconds, trace=bool(trace), t_start=T_START,
        cell=found.cell, config=found.config, traffic=found.traffic,
        work_dir=work_dir, clock=CompileClock(), break_step=break_step,
        data_dir=data_dir or os.path.join(found.here, "_data"))

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    ctx.start_trace, ctx.stop_trace = start_trace, jax.profiler.stop_trace
    return ctx, trace_dir


def read_traced_side(found, record: dict, trace_dir: str, device: dict):
    """The per-layer metrics and the breakdown of a ``--trace 1`` run;
    ``device`` gains ``busy_s`` and ``window_s``. The trace is deleted once
    read: a checkout keeps no traces."""
    from chipbench import trace as tracing

    tr = tracing.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = read_layer_metrics(found, {
        "record": record, "trace": tr, "compile": record["compile_in_setup"],
        "peaks": _peaks_or_none(device["kind"])})
    chips = range(len(tr.ops))
    device["busy_s"] = sum(tracing.busy_seconds(tr, c)
                           for c in chips) / max(len(chips), 1)
    device["window_s"] = tr.window_s
    if tr.ops:
        longest = sorted(tracing.idle_gaps(tr, 0), key=lambda g: g[0] - g[1])
        log("longest idle gaps on chip 0 (ms into the window, ms long, "
            "host span): " + ", ".join(
                f"({(a - tr.window[0]) / 1e6:.1f}, {(b - a) / 1e6:.2f}, "
                f"{tracing.attribute((a, b), tr.host) or 'no_span'})"
                for a, b in longest[:6]))
    return metrics, {"breakdown": tracing.breakdown(tr)}


def drive(args, *, require_chip: bool = True, root: str = ROOT,
          data_dir: str | None = None, break_step=None) -> dict:
    """One run, from the files to the result line's object. The tests pass
    ``require_chip=False``, a ``root`` of their own and, to see ``correct``
    come out false, ``break_step``: a wrapper round the compiled step."""
    found = find_cell(args.workload, root)
    device = (look_for_the_chip(found.entry["chips"]) if require_chip
              else describe_device())
    ctx, trace_dir = make_ctx(found, args.seed, args.seconds, args.trace,
                              data_dir, break_step)
    runner = plug_in(found.here, "runners", found.cell["runner"])
    record = runner.run(ctx)

    check = record["check"]
    correct = (not record["faults"] and record["failed"] == 0
               and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in check.values()))

    device = {**device, "memory_peak_bytes": record["memory_peak_bytes"]}
    values = {**record["values"], "setup_s": record["setup_s"]}
    if args.trace:
        metrics, extra = read_traced_side(found, record, trace_dir, device)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(found, "end_to_end")}
        extra = {}

    for fault in record["faults"]:
        log(f"fault: {fault}")
    log(f"correct: {correct}; compared, each beside its limit:")
    for name, c in check.items():
        log(f"  {name}: {c['value']!r} (limit {c['limit']!r})")
    return {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics, "device": device,
            **extra, "faults": record["faults"], "check": check}


def _peaks_or_none(kind: str):
    from chipbench import flops

    try:
        return flops.load_peaks(kind)
    except KeyError:
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = drive(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
