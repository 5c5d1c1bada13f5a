"""Readers of what the program records about itself: the spans of its
process-wide tracer (``nanosandbox_tpu.obs.process_tracer``), the instants
its compile-cache listeners leave there, and the map from the train step's
instructions to parts of the model (``nanosandbox_tpu.obs.opscopes``).

A reader runs in the run's own process, after the window, so it takes all
three from memory. The program stamps ``time.perf_counter_ns()``; the device
trace has a clock of its own. One interval is known on both: the runner's
``cb:window`` span is ``record["window_t0"/"window_t1"]`` on the first and
``Trace.window`` on the second, and that moves every span onto the trace.

A program without the tracer, the listeners or the map (the parent of the PR
that added them) gives every reader nothing to read: it returns None and the
metric is left out of the line. Tests hand the program's side in as
``run["program"]``: ``{"spans": [...], "parts": {...} or None}``, spans being
anything with ``sid, name, t0_ns, dur_ns, parent, track, args``.
"""

from __future__ import annotations

import time

from chipbench import trace

COMPILE_INSTANT = "jax_compile"


# -- the program's side, read once a run ---------------------------------------

def _parts_or_none():
    try:
        from nanosandbox_tpu.obs import opscopes
    except ImportError:
        return None
    return opscopes.step_parts()


def program_side(run: dict) -> dict:
    """``{"spans": [...], "parts": ...}``, read at the first call and kept in
    ``run`` (the readers of one run share it). ``parts`` is made only when a
    reader asks for it (``step_parts``): it costs a lowering."""
    side = run.get("program")
    if side is None:
        side = run["program"] = {"spans": _process_spans()}
    return side


def _process_spans() -> list:
    try:
        from nanosandbox_tpu.obs import tracer
    except ImportError:
        return []
    get = getattr(tracer, "process_tracer", None)
    return list(get().spans()) if get is not None else []


def step_parts(run: dict):
    """The train step's map, and in ``side["parts_s"]`` what making it cost
    (a lowering answered by the executable in memory, or a compile where
    that one came out of the cache under older scope names)."""
    side = program_side(run)
    if "parts" not in side:
        t0 = time.perf_counter()
        side["parts"] = _parts_or_none()
        side["parts_s"] = time.perf_counter() - t0
    return side["parts"]


def _window_ns(run: dict):
    rec = run["record"]
    if "window_t0" not in rec or "window_t1" not in rec:
        return None
    return rec["window_t0"] * 1e9, rec["window_t1"] * 1e9


def _in_window(run: dict, names) -> list:
    """Spans of those names that began inside the window (host clock)."""
    w = _window_ns(run)
    if w is None:
        return []
    names = set(names)
    return [s for s in program_side(run)["spans"]
            if s.name in names and s.dur_ns is not None
            and w[0] <= s.t0_ns <= w[1]]


# -- the clock anchor ----------------------------------------------------------

def trace_offset_ns(run: dict):
    """(offset, skew): add ``offset`` to a ``perf_counter_ns`` stamp to get
    the trace's nanoseconds. The window's two ends each give one estimate (the
    annotation opens a little before the runner reads its clock and closes a
    little after, so the two err in opposite directions); the offset is their
    mean and ``skew`` their difference, which a reader reports."""
    w, tr = _window_ns(run), run["trace"]
    if w is None or tr.window[1] <= tr.window[0]:
        return None
    a0, a1 = tr.window[0] - w[0], tr.window[1] - w[1]
    return (a0 + a1) / 2.0, a1 - a0


# -- set-up ---------------------------------------------------------------------

def trainer_init_s(run: dict, metric: dict):
    """Seconds of the last ``trainer_init`` span that ended before the
    window, with its children by name."""
    w = _window_ns(run)
    spans = program_side(run)["spans"]
    whole = [s for s in spans if s.name == "trainer_init"
             and s.dur_ns is not None
             and (w is None or s.t0_ns + s.dur_ns <= w[0])]
    if not whole:
        return None
    init = whole[-1]
    children: dict[str, float] = {}
    for s in spans:
        if s.parent == init.sid and s.dur_ns is not None:
            key = f"{s.name}_s"
            children[key] = children.get(key, 0.0) + s.dur_ns / 1e9
    return init.dur_ns / 1e9, children


def compile_phase_s(run: dict, metric: dict):
    """Seconds the phases in ``params.phases`` (trace, lower, backend,
    cache_load) had taken by the window's start, summed from the
    ``jax_compile`` instants the program's ``jax.monitoring`` listeners leave
    in its tracer: one each time a phase has accrued another 50 ms, so the sum
    is right to within that much, and one for every cache lookup. What came
    later (the reference's compiles) is left out. With every phase's seconds
    and the cache's hits and misses up to then. A process whose listeners left
    nothing gives None; one that never met a phase (no cache load in a cold
    run) reads 0."""
    w = _window_ns(run)
    events = [s for s in program_side(run)["spans"]
              if s.name == COMPILE_INSTANT]
    if not events or w is None:
        return None
    by_phase: dict[str, float] = {}
    count = {"hit": 0, "miss": 0}
    for s in events:
        if s.t0_ns > w[0]:
            continue
        phase = s.args.get("phase")
        if phase in count:
            count[phase] += 1
        else:
            by_phase[phase] = by_phase.get(phase, 0.0) + s.args["seconds"]
    return sum(by_phase.get(p, 0.0) for p in metric["params"]["phases"]), {
        "s_by_phase": by_phase, "cache_hits": count["hit"],
        "cache_misses": count["miss"]}


# -- the input path -------------------------------------------------------------

def span_ms_per_step(run: dict, metric: dict):
    """Mean milliseconds a step of the window spends in the program's spans
    named by ``params.spans``: their summed time over ``record["steps"]``."""
    hits = _in_window(run, metric["params"]["spans"])
    steps = run["record"].get("steps")
    if not hits or not steps:
        return None
    return sum(s.dur_ns for s in hits) / 1e6 / steps, {"spans": len(hits)}


def span_ms_mean(run: dict, metric: dict):
    """Mean milliseconds of one span of ``params.spans`` begun in the window
    (work of a helper thread, which no step waits for one to one); with the
    mean of each of ``params.beside`` (the thread's slack: how long it then
    waited for room in the queue, up to the window's end, since the last wait
    lasts until the loader is closed), 0 where it never did."""
    hits = _in_window(run, metric["params"]["spans"])
    if not hits:
        return None
    extra = {"spans": len(hits)}
    end = _window_ns(run)[1]
    for name in metric["params"].get("beside", ()):
        extra[f"{name}_ms"] = sum(
            min(s.t0_ns + s.dur_ns, end) - s.t0_ns
            for s in _in_window(run, [name])) / 1e6 / len(hits)
    return sum(s.dur_ns for s in hits) / 1e6 / len(hits), extra


def loader_wait_ms(run: dict, metric: dict):
    """``span_ms_per_step`` of the loader's wait, with how often the loop
    found the queue empty and how many batches it found waiting."""
    got = span_ms_per_step(run, metric)
    if got is None:
        return None
    value, extra = got
    depths = [s.args["depth"] for s in _in_window(run, metric["params"]["spans"])
              if "depth" in s.args]
    if depths:
        extra["starved"] = sum(1 for d in depths if d == 0)
        extra["mean_depth"] = sum(depths) / len(depths)
    return value, extra


# -- idle time of the chip, by what the program was doing ----------------------

def idle_ms_under_spans(run: dict, metric: dict):
    """Milliseconds a step that chip 0 sits idle in the window while the
    program's main thread is inside a span of ``params.spans``, the spans
    moved onto the trace's clock by the window anchor. Nested spans are not
    counted twice: idle time goes to the shortest span over it. Spans with a
    track of their own (the prefetch thread, which waits most of the time by
    design) are left out. With the same for every span name, the idle time
    no span covers and the anchor's skew."""
    tr, steps = run["trace"], run["record"].get("steps")
    anchor = trace_offset_ns(run)
    if anchor is None or not tr.ops or not steps:
        return None
    offset, skew = anchor
    spans = [s for s in program_side(run)["spans"]
             if s.track is None and s.dur_ns]
    if not spans:
        return None
    idle = trace.idle_gaps(tr, 0)
    taken: list[tuple[float, float]] = []
    by_name: dict[str, float] = {}
    for s in sorted(spans, key=lambda s: s.dur_ns):
        a, b = s.t0_ns + offset, s.t0_ns + s.dur_ns + offset
        if b <= tr.window[0] or a >= tr.window[1]:
            continue
        over = [(max(a, g0), min(b, g1)) for g0, g1 in idle
                if g1 > a and g0 < b]
        fresh = trace.subtract(over, taken)
        got = trace.covered(fresh)
        if got > 0:
            by_name[s.name] = by_name.get(s.name, 0.0) + got
            taken = trace.union([trace.Event("", x, y) for x, y in taken + fresh])
    per_step = 1e6 * steps
    mine = sum(by_name.get(n, 0.0) for n in metric["params"]["spans"])
    return mine / per_step, {
        "anchor_skew_us": skew / 1e3,
        "idle_ms_unspanned": (trace.covered(idle) - sum(by_name.values()))
        / per_step,
        "idle_ms_by_span": {k: v / per_step for k, v in
                            sorted(by_name.items(), key=lambda kv: -kv[1])}}


# -- device time by part of the model ------------------------------------------

def self_times(events) -> tuple[list, list[float]]:
    """Each event's duration less the part its direct children cover (a
    loop's event spans the events of its body): the self times of properly
    nested events sum to their union. In the order of ``sorted(events)`` by
    (start, -end), which is what is returned beside them."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    own = [e.end - e.start for e in order]
    stack: list[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e.end, order[stack[-1]].end) - e.start
        stack.append(i)
    return order, own


def _device_ms_by_part(run: dict):
    """{part: [ms a step, ops a step]} of chip 0 in the window, kept in
    ``run``; ops the map does not know go under 'unmapped'."""
    side = program_side(run)
    if "device_ms" in side:
        return side["device_ms"]
    tr, steps = run["trace"], run["record"].get("steps")
    parts = step_parts(run)
    side["device_ms"] = None
    if not tr.ops or not tr.ops[0] or not steps or not parts:
        return None
    order, own = self_times(trace.clip(tr.ops[0], tr.window))
    out: dict[str, list[float]] = {}
    for e, t in zip(order, own):
        name = e.name.split(" = ", 1)[0].strip().lstrip("%")
        cell = out.setdefault(parts.get(name, "unmapped"), [0.0, 0.0])
        cell[0] += t / 1e6 / steps
        cell[1] += 1.0 / steps
    side["device_ms"] = out
    return out


def device_ms_of_parts(run: dict, metric: dict):
    """Device milliseconds a step of the ops whose part (``opscopes.PARTS``,
    'unscoped', or 'unmapped' for an op the map lacks) is in
    ``params.parts``; with the share of the step's device time, the ops a
    step and, where several parts are summed, each one's milliseconds."""
    by_part = _device_ms_by_part(run)
    if by_part is None:
        return None
    wanted = metric["params"]["parts"]
    whole = sum(ms for ms, _ in by_part.values())
    mine = {p: by_part[p] for p in wanted if p in by_part}
    value = sum(ms for ms, _ in mine.values())
    extra = {"share_of_step_pct": 100.0 * value / whole if whole else 0.0,
             "ops_per_step": sum(n for _, n in mine.values()),
             "map_s": program_side(run)["parts_s"]}
    if len(wanted) > 1:
        extra["ms_by_part"] = {p: ms for p, (ms, _) in mine.items()}
    return value, extra
