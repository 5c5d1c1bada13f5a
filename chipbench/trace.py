"""Reduction of a profiler trace (``.xplane.pb``) to intervals.

What a TPU trace holds (looked at by hand, PR 26): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event for every
execution of a compiled program (``jit_<function>(<hash>)``) and whose line
``XLA Ops`` has one for every operation inside it, named by its HLO text
(``%fusion.24 = f32[...] fusion(...)``). The host's plane ``/host:CPU`` has
a line ``python`` with the runner's ``cb:<span>`` annotations. All on one
clock, in nanoseconds.

Everything below works on plain lists of ``Event(name, start, end)``, so the
tests drive it with synthetic events and need no recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import NamedTuple


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float    # ns


@dataclass
class Trace:
    """``modules`` and ``ops``: per chip, in plane order. ``host``: the
    runner's spans (``cb:`` stripped). ``window``: (start, end) of the
    traced window on the trace's clock."""
    modules: list[list[Event]] = field(default_factory=list)
    ops: list[list[Event]] = field(default_factory=list)
    host: list[Event] = field(default_factory=list)
    window: tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


SPAN_PREFIX = "cb:"
WINDOW_SPAN = "window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    tr = Trace()
    planes = sorted((p for p in data.planes
                     if re.fullmatch(r"/device:TPU:\d+", p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        for key, into in (("XLA Modules", tr.modules), ("XLA Ops", tr.ops)):
            into.append([Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in lines[key].events] if key in lines else [])
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    tr.host.append(Event(e.name[len(SPAN_PREFIX):], e.start_ns,
                                         e.start_ns + e.duration_ns))
    tr.host.sort(key=lambda e: e.start)
    whole = [e for e in tr.host if e.name == WINDOW_SPAN]
    if whole:
        tr.window = (whole[0].start, whole[-1].end)
    else:
        every = [e for dev in tr.ops + tr.modules for e in dev]
        if every:
            tr.window = (min(e.start for e in every), max(e.end for e in every))
    return tr


# -- interval arithmetic --------------------------------------------------------

def clip(events, window):
    """Events cut to the window; those outside it dropped."""
    w0, w1 = window
    out = []
    for e in events:
        s, t = max(e.start, w0), min(e.end, w1)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events) -> list[tuple[float, float]]:
    """Merged, sorted intervals covered by any event."""
    out: list[list[float]] = []
    for s, t in sorted((e.start, e.end) for e in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def covered(intervals) -> float:
    return sum(t - s for s, t in intervals)


def gaps(intervals, window) -> list[tuple[float, float]]:
    """The parts of the window that no interval covers."""
    w0, w1 = window
    out, at = [], w0
    for s, t in intervals:
        if s > at:
            out.append((at, min(s, w1)))
        at = max(at, t)
        if at >= w1:
            break
    if at < w1:
        out.append((at, w1))
    return [(s, t) for s, t in out if t > s]


def subtract(intervals, minus) -> list[tuple[float, float]]:
    """``intervals`` with every part that ``minus`` covers taken out."""
    out = []
    for s, t in intervals:
        out.extend(gaps([(a, b) for a, b in minus if b > s and a < t], (s, t)))
    return out


def busy_seconds(tr: Trace, chip: int) -> float:
    """Seconds of the window in which an operation ran on the chip."""
    events = tr.ops[chip] or tr.modules[chip]
    return covered(union(clip(events, tr.window))) / 1e9


def idle_gaps(tr: Trace, chip: int) -> list[tuple[float, float]]:
    events = tr.ops[chip] or tr.modules[chip]
    return gaps(union(clip(events, tr.window)), tr.window)


def attribute(gap, host_events) -> str:
    """The host span that covers most of the gap ('' where none does). The
    span of the whole window is no answer and is left out."""
    best, name = 0.0, ""
    for e in host_events:
        if e.name == WINDOW_SPAN:
            continue
        over = min(e.end, gap[1]) - max(e.start, gap[0])
        if over > best:
            best, name = over, e.name
    return name


def op_label(hlo_name: str) -> str:
    """'%fusion.24 = f32[...] fusion(...)' -> 'fusion.24'. The calls of one
    kernel (custom calls of one name: 'attn.37', 'attn.38', ...) are summed
    under 'attn.*', or they would fill the breakdown's ten places."""
    label = hlo_name.split(" = ", 1)[0].lstrip("%")
    if " custom-call(" in hlo_name:
        return re.sub(r"(\.\d+)?$", ".*", label, count=1)
    return label


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def breakdown(tr: Trace, chip: int = 0, top: int = 10) -> dict:
    """The operations that took most device time in the window, and the
    longest idle gaps by what the host was doing in each."""
    if chip >= len(tr.ops):  # a trace with no device in it
        return {"device_ops": [], "idle_gaps": []}
    total: dict[str, float] = {}
    for e in clip(tr.ops[chip], tr.window):
        k = op_label(e.name)
        total[k] = total.get(k, 0.0) + (e.end - e.start)
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    by_host: dict[str, float] = {}
    for g in idle_gaps(tr, chip):
        k = attribute(g, tr.host) or "no_span"
        by_host[k] = by_host.get(k, 0.0) + (g[1] - g[0])
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}
