"""Readers of the runner's own host spans."""


def span_ms_per_step(run: dict, metric: dict):
    """Mean milliseconds a step spends in the spans named by
    ``params.spans`` (host clock, the runner's spans round its own calls)."""
    names = set(metric["params"]["spans"])
    if not run["record"].get("spans"):
        return None
    steps = run["record"]["steps"]
    w0, w1 = run["record"]["window_t0"], run["record"]["window_t1"]
    spans = [(t0, t1) for n, t0, t1 in run["record"]["spans"]
             if n in names and w0 <= t0 <= w1]
    if not spans or not steps:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in spans) / steps
