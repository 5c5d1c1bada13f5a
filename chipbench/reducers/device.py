"""Readers of the device trace."""

import statistics

from chipbench import flops, trace


def _step_events(run, metric, chip=0):
    tr = run["trace"]
    if not tr.modules:
        return []
    return trace.clip(trace.matching(tr.modules[chip],
                                     metric["params"]["step_program"]),
                      tr.window)


def step_gap_p95_ms(run: dict, metric: dict):
    """95th percentile of the start-to-start gaps of successive train-step
    programs on chip 0, with their count and median."""
    starts = [e.start for e in _step_events(run, metric)]
    gaps = [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]
    if len(gaps) < 2:
        return None
    p95 = statistics.quantiles(gaps, n=20)[-1] if len(gaps) >= 20 else max(gaps)
    return p95, {"n": len(gaps), "median": statistics.median(gaps)}


def device_idle_pct(run: dict, metric: dict):
    """1 - (union of the intervals in which an operation runs) / window, on
    the chip that idles most."""
    tr = run["trace"]
    if not tr.ops or tr.window_s <= 0:
        return None
    idle = [1.0 - trace.busy_seconds(tr, c) / tr.window_s
            for c in range(len(tr.ops))]
    return 100.0 * max(idle)


def kernel_roofline_pct(run: dict, metric: dict):
    """Least time the chip could take for the kernel's required operations
    and bytes (chipbench/flops.py, by the function ``params.cost`` names)
    over the summed device time of the events matching ``params.pattern``,
    per step, on chip 0. Says which bound sets the least time."""
    tr = run["trace"]
    if not tr.ops or run["peaks"] is None:
        return None
    steps = _step_events(run, metric)
    if not steps:
        return None
    inside = (steps[0].start, steps[-1].end)
    hits = trace.clip(trace.matching(tr.ops[0], metric["params"]["pattern"]),
                      inside)
    if not hits:
        return None
    rec = run["record"]
    kernel_s = sum(e.end - e.start for e in hits) / 1e9 / len(steps)
    rows_here = rec["batch_rows"] // rec["chips"]
    cost = getattr(flops, metric["params"]["cost"])(rec["sizes"], rows_here)
    least = flops.least_seconds(cost, run["peaks"])
    return 100.0 * least["seconds"] / kernel_s, {
        "bound": least["bound"], "kernel_ms_per_step": kernel_s * 1e3,
        "events_per_step": len(hits) / len(steps)}


def collective_exposed_pct(run: dict, metric: dict):
    """Time in collective operations on a chip during which no other
    operation runs there, over the window; the worst chip."""
    tr = run["trace"]
    if not tr.ops or tr.window_s <= 0:
        return None
    worst, found = 0.0, False
    for ops in tr.ops:
        ops = trace.clip(ops, tr.window)
        coll = trace.matching(ops, metric["params"]["pattern"])
        if not coll:
            continue
        found = True
        names = {id(e) for e in coll}
        other = trace.union([e for e in ops if id(e) not in names])
        exposed = trace.covered(trace.subtract(trace.union(coll), other))
        worst = max(worst, exposed / 1e9 / tr.window_s)
    return 100.0 * worst if found else None
