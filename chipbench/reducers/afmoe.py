"""Readers of the ``afmoe`` cells' kernels and expert counters.

The kernels' shares of their rooflines need cost functions that know the
family's shapes (``chipbench/flops_afmoe.py``; ``device:kernel_roofline_pct``
calls ``chipbench/flops.py``, GPT-2's). The counters come from the runner's
record (``record["moe"]``), which it fills from the step's own metrics. A
record without them, or a trace without the kernels, gives every reader
nothing to read: it returns None.
"""

from __future__ import annotations

from chipbench import flops_afmoe, trace
from chipbench.reducers.device import _step_events
# The cell's six ``dev_ms_*`` files name this module and read through the
# accepted reader unchanged: ``tests/test_program_readers.py`` counts the
# metrics whose file says ``program:`` and pins them at PR 27's twelve.
from chipbench.reducers.program import device_ms_of_parts  # noqa: F401


def _kernel_seconds(run: dict, metric: dict, pattern: str):
    """(device seconds a step of the events matching ``pattern`` on chip 0
    inside the traced steps, events a step), or None."""
    tr = run["trace"]
    if not tr.ops or run["peaks"] is None:
        return None
    steps = _step_events(run, metric)
    if not steps:
        return None
    hits = trace.clip(trace.matching(tr.ops[0], pattern),
                      (steps[0].start, steps[-1].end))
    if not hits:
        return None
    return (sum(e.end - e.start for e in hits) / 1e9 / len(steps),
            len(hits) / len(steps))


def _share(cost: dict, seconds: float, peaks: dict) -> dict:
    least = flops_afmoe.least_seconds(cost, peaks)
    return {"pct": 100.0 * least["seconds"] / seconds,
            "bound": least["bound"], "kernel_ms_per_step": seconds * 1e3}


def attention_roofline_pct(run: dict, metric: dict):
    """Least time for the required operations and bytes of ALL layers'
    grouped-query flash kernels (forward, dQ, dK/dV) over their summed
    device time a step; the window layers' and the full layers' own shares
    beside it. ``params.patterns``: {"sliding": ..., "full": ...}."""
    rec = run["record"]
    sizes = rec.get("sizes") or {}
    if "layer_types" not in sizes:
        return None
    rows = rec["batch_rows"] // rec["chips"]
    seconds, events, extra, cost = 0.0, 0.0, {}, {"ops": 0.0, "bytes": 0.0}
    for kind, pattern in metric["params"]["patterns"].items():
        n_layers = sizes["layer_types"].count(kind)
        got = _kernel_seconds(run, metric, pattern)
        if got is None or not n_layers:
            continue
        one = flops_afmoe.attention_cost(
            sizes, rows, sizes["sliding_window"] if kind == "sliding" else None)
        mine = {k: n_layers * v for k, v in one.items()}
        part = _share(mine, got[0], run["peaks"])
        extra[f"{kind}_roofline_pct"] = part["pct"]
        extra[f"{kind}_kernel_ms_per_step"] = part["kernel_ms_per_step"]
        extra[f"{kind}_events_per_step"] = got[1]
        seconds += got[0]
        events += got[1]
        cost = {k: cost[k] + mine[k] for k in cost}
    if not seconds:
        return None
    whole = _share(cost, seconds, run["peaks"])
    return whole["pct"], {"bound": whole["bound"],
                          "kernel_ms_per_step": whole["kernel_ms_per_step"],
                          "events_per_step": events, **extra}


def gmm_roofline_pct(run: dict, metric: dict):
    """Least time for the grouped matmuls' required operations and bytes
    (nine products an expert layer, on the COUNTED mean of rows held a
    layer) over the device time of the events matching ``params.pattern``;
    the expected rows (tokens * k * held / E) and what they would give
    beside it."""
    rec = run["record"]
    moe, sizes = rec.get("moe"), rec.get("sizes") or {}
    if not moe or "layer_types" not in sizes:
        return None
    got = _kernel_seconds(run, metric, metric["params"]["pattern"])
    if got is None:
        return None
    layers = sizes["n_layer"] - sizes["num_dense_layers"]

    def share(rows):
        one = flops_afmoe.gmm_cost(sizes, rows)
        return _share({k: layers * v for k, v in one.items()}, got[0],
                      run["peaks"])

    counted, expected = share(moe["rows_held_mean"]), share(moe["rows_expected"])
    return counted["pct"], {
        "bound": counted["bound"],
        "kernel_ms_per_step": counted["kernel_ms_per_step"],
        "events_per_step": got[1], "rows_held_mean": moe["rows_held_mean"],
        "rows_expected": moe["rows_expected"],
        "pct_by_expected_rows": expected["pct"]}


def balance_bias_s(run: dict, metric: dict):
    """Seconds of set-up the runner's span ``balance_bias`` took (the
    reference fitting the selection bias); what the fit left on its own rows
    beside it."""
    rec = run["record"]
    took = [t1 - t0 for n, t0, t1 in rec.get("spans") or ()
            if n == "balance_bias"]
    if not took or "balance" not in rec:
        return None
    return sum(took), dict(rec["balance"])


def moe_load_max_over_mean(run: dict, metric: dict):
    """The fullest held expert's rows over the mean held expert's, over
    expert layers and logged steps; rows held a step, the buffer's rows and
    the dropped (token, slot) pairs (0, or the run is at fault) beside it."""
    moe = run["record"].get("moe")
    if not moe:
        return None
    return moe["load_max_over_mean"], {
        k: moe[k] for k in ("rows_held_mean", "rows_held_by_layer",
                            "rows_held_max", "rows_expected", "rows_bound",
                            "dropped", "steps_counted",
                            "load_max_over_mean_worst")}
