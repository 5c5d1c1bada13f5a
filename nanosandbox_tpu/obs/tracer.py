"""Span tracer: host-side begin/end spans with Chrome trace-event export.

Answers "why was THIS request slow" — the causality question the metric
registry's aggregates cannot. The engine records spans from
dispatch-time state it already holds on the host (admission wave
composition, decode step tick, spec verify round), so tracing adds NO
device readback and no host sync: every recorded value is an
already-host-resident int/float/str (the jaxlint contract), and a
record is one dict build + one deque append under a lock.

Semantics that matter for the pipelined engine: a ``decode_step`` span
is OPENED at dispatch and CLOSED at its retire — which, with one step
in flight, happens AFTER the next step's dispatch. The exported
timeline therefore shows step k overlapping step k+1, which is the
truth of the pipeline, not a prettified synchronous story. Request
spans (``queued`` -> ``generate``) carry the request id; eviction +
backfill reuse a slot but never a span, so an exported request track is
exactly one request's life.

Export is Chrome trace-event JSON (the ``{"traceEvents": [...]}``
variant), loadable in Perfetto / chrome://tracing: complete events
(``ph: "X"``) on one track per request (tid = rid + 1, named) plus an
engine track (tid 0) for waves/steps/verify rounds.

The training path records into ONE tracer per process
(``process_tracer()``, like ``registry.global_registry()``): the trainer,
the batch loader, ``tracecheck.host_sync`` and the compile-cache
listeners all write there, and a benchmark reads it in memory after its
window. Times are ``time.perf_counter_ns()``; every span carries the
training iteration it belongs to (``step``), and in a tracer made with
``nest=True`` (this one; not the serve engine's, whose request spans open
and close out of order and across threads) the span that was open on its
thread when it began (``parent``), so a window's spans can be laid on a
device trace through any one interval known on both clocks.
With an ``annotate`` hook (the trainer gives
``jax.profiler.TraceAnnotation``; this file imports no jax) every span
is also written into whatever profiler trace is being recorded, under
its own name.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

ENGINE_TRACK = 0  # tid for engine-wide spans; request rid r rides tid r+1
# Named tracks (``track="loader_prefetch"``: spans of a helper thread) ride
# tids from here up, clear of any request id.
NAMED_TRACK_BASE = 1 << 30


@dataclass
class Span:
    sid: int
    name: str
    cat: str
    t0_ns: int                      # time.perf_counter_ns() at begin
    dur_ns: Optional[int] = None    # None while open
    rid: Optional[int] = None
    args: dict = field(default_factory=dict)
    parent: int = 0                 # nest=True: sid open on this thread at begin
    step: Optional[int] = None      # training iteration; None outside the loop
    track: Optional[str] = None     # a helper thread's own export track

    # Seconds on time.perf_counter()'s clock.
    @property
    def t0(self) -> float:
        return self.t0_ns / 1e9

    @property
    def dur(self) -> Optional[float]:
        return None if self.dur_ns is None else self.dur_ns / 1e9

    @property
    def t1(self) -> Optional[float]:
        return None if self.dur_ns is None else (self.t0_ns + self.dur_ns) / 1e9

    @property
    def t1_ns(self) -> Optional[int]:
        return None if self.dur_ns is None else self.t0_ns + self.dur_ns


class SpanTracer:
    """Bounded in-memory ring of completed spans + the open-span table.

    ``enabled=False`` turns every call into a constant-time no-op (the
    overhead-pin test measures the enabled path; the escape hatch exists
    for experiments, not because the enabled path is hot).

    ``nest=True`` keeps, per thread, the stack of spans begun and not yet
    ended there, and gives each span its ``parent`` and, by default, its
    parent's ``step``. It is for code whose spans nest on one thread (the
    training path); without it nothing is tracked and ``parent`` stays 0."""

    def __init__(self, capacity: int = 8192, enabled: bool = True,
                 annotate: Optional[Callable] = None, nest: bool = False):
        self.enabled = enabled
        self.nest = nest
        # annotate(name) -> context manager entered at begin and left at
        # end, on the recording thread (jax.profiler.TraceAnnotation).
        self.annotate = annotate
        self._t0_ns = time.perf_counter_ns()   # export epoch: ts are relative
        self._ring: deque = deque(maxlen=capacity)
        self._open: Dict[int, Span] = {}
        self._annotations: Dict[int, object] = {}
        self._sid = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()   # .stack: sids begun on this thread

    # ------------------------------------------------------------- record
    def _parent(self) -> tuple:
        """(this thread's stack, sid of its innermost span still open, that
        span's step); (None, 0, None) without ``nest``. A span that another
        thread ended is pruned here, under the lock."""
        if not self.nest:
            return None, 0, None
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        while stack and stack[-1] not in self._open:
            stack.pop()
        if not stack:
            return stack, 0, None
        return stack, stack[-1], self._open[stack[-1]].step

    def begin(self, name: str, cat: str = "engine", *,
              rid: Optional[int] = None, args: Optional[dict] = None,
              step: Optional[int] = None, track: Optional[str] = None,
              ) -> int:
        """Open a span; returns its id (0 when disabled). The caller
        holds only the sid — ending by id keeps the hot path free of
        span-object bookkeeping. With ``nest``, ``step`` defaults to the
        parent's."""
        if not self.enabled:
            return 0
        sid = next(self._sid)
        annotation = None
        if self.annotate is not None:
            annotation = self.annotate(name)
            annotation.__enter__()
        t0 = time.perf_counter_ns()
        with self._lock:
            stack, parent, parent_step = self._parent()
            if step is None:
                step = parent_step
            self._open[sid] = Span(
                sid=sid, name=name, cat=cat, t0_ns=t0, rid=rid,
                args=dict(args or {}), parent=parent, step=step, track=track)
            if stack is not None:
                stack.append(sid)
            if annotation is not None:
                self._annotations[sid] = annotation
        return sid

    def end(self, sid: int, args: Optional[dict] = None) -> None:
        """Close a span by id. Unknown/zero sids are ignored so a
        disabled tracer's 0 handles (and double-ends on teardown paths)
        never raise in the serving loop."""
        if not self.enabled or sid == 0:
            return
        now = time.perf_counter_ns()
        with self._lock:
            sp = self._open.pop(sid, None)
            if sp is None:
                return
            sp.dur_ns = now - sp.t0_ns
            if args:
                sp.args.update(args)
            self._ring.append(sp)
            annotation = self._annotations.pop(sid, None)
        stack = getattr(self._local, "stack", None)
        if stack:
            if stack[-1] == sid:   # ended innermost first: the usual case
                stack.pop()
            elif sid in stack:     # out of order: gone from wherever it sits
                stack.remove(sid)
        if annotation is not None:
            annotation.__exit__(None, None, None)

    @contextmanager
    def span(self, name: str, cat: str = "engine", **kw):
        """``with tracer.span("to_global", cat="train") as sid:`` — begin
        and end round a block, ended on an exception too."""
        sid = self.begin(name, cat, **kw)
        try:
            yield sid
        finally:
            self.end(sid)

    def instant(self, name: str, cat: str = "engine", *,
                rid: Optional[int] = None,
                args: Optional[dict] = None) -> None:
        """A zero-duration marker (renders as a thin slice)."""
        if not self.enabled:
            return
        sid = next(self._sid)
        now = time.perf_counter_ns()
        with self._lock:
            _, parent, step = self._parent()
            self._ring.append(Span(
                sid=sid, name=name, cat=cat, t0_ns=now, dur_ns=0, rid=rid,
                args=dict(args or {}), parent=parent, step=step))

    # ------------------------------------------------------------ queries
    def spans(self, rid: Optional[int] = None,
              last_s: Optional[float] = None) -> List[Span]:
        """Completed spans, optionally filtered to one request id and/or
        the trailing ``last_s`` seconds, oldest first."""
        with self._lock:
            out = list(self._ring)
        if rid is not None:
            out = [s for s in out if s.rid == rid]
        if last_s is not None:
            horizon = time.perf_counter_ns() - last_s * 1e9
            out = [s for s in out if s.t1_ns >= horizon]
        return out

    def _open_snapshot(self, rid: int) -> List[Span]:
        """Point-in-time copies of one request's still-open spans, with
        duration-so-far and an ``incomplete`` marker. /trace?rid=N must
        show a request SITTING IN THE QUEUE — that is the admission-
        pressure diagnosis the endpoint exists for — not 404 until the
        request is done."""
        now = time.perf_counter_ns()
        with self._lock:
            return [Span(sid=sp.sid, name=sp.name, cat=sp.cat, t0_ns=sp.t0_ns,
                         dur_ns=now - sp.t0_ns, rid=sp.rid,
                         args={**sp.args, "incomplete": True},
                         parent=sp.parent, step=sp.step, track=sp.track)
                    for sp in self._open.values() if sp.rid == rid]

    def open_count(self) -> int:
        """Spans begun but not ended — the orphan detector: after a
        drain this must be zero (a leak means some finish path forgot
        its end, exactly the eviction/backfill bug class)."""
        with self._lock:
            return len(self._open)

    def clear(self) -> None:
        """Drop completed spans (benchmarks clear between warmup and the
        timed window, like reset_latency_stats). Open spans survive —
        they belong to in-flight work."""
        with self._lock:
            self._ring.clear()

    # ------------------------------------------------------------- export
    def export_chrome(self, rid: Optional[int] = None,
                      last_s: Optional[float] = None) -> dict:
        """Chrome trace-event JSON for Perfetto / chrome://tracing.

        With ``rid``: that request's spans PLUS the engine-track spans
        overlapping its lifetime (the decode steps / waves / verify
        rounds that explain its latency). Without: everything in the
        ring (optionally time-bounded)."""
        spans = self.spans(last_s=last_s)
        if rid is not None:
            mine = ([s for s in spans if s.rid == rid]
                    + self._open_snapshot(rid))
            if mine:
                lo = min(s.t0 for s in mine)
                hi = max(s.t1 for s in mine)
                engine_ctx = [s for s in spans
                              if s.rid is None and s.t1 is not None
                              and s.t1 >= lo and s.t0 <= hi]
                spans = sorted(mine + engine_ctx, key=lambda s: s.t0)
            else:
                spans = []
        events: List[dict] = []
        tracks: Dict[int, str] = {}
        named: Dict[str, int] = {}
        for s in spans:
            if s.rid is not None:
                tid, label = s.rid + 1, f"request {s.rid}"
            elif s.track is not None:
                tid = named.setdefault(s.track, NAMED_TRACK_BASE + len(named))
                label = s.track
            else:
                tid, label = ENGINE_TRACK, "engine"
            tracks.setdefault(tid, label)
            ev = {
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": round((s.t0_ns - self._t0_ns) / 1e3, 3),
                "dur": round((s.dur_ns or 0) / 1e3, 3),
                "pid": 0,
                "tid": tid,
                "args": dict(s.args),
            }
            if s.rid is not None:
                ev["args"]["rid"] = s.rid
            if s.parent:
                ev["args"]["parent"] = s.parent
            if s.step is not None:
                ev["args"]["step"] = s.step
            events.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "args": {"name": name}}
                for tid, name in sorted(tracks.items())]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


# Process-wide tracer of the training path: the trainer, the loader,
# tracecheck.host_sync and the compile-cache listeners record here, and
# Trainer.tracer is this object. Room for a whole benchmark window (a few
# spans a step, a few hundred steps) with the set-up before it.
_PROCESS = SpanTracer(capacity=16384, nest=True)


def process_tracer() -> SpanTracer:
    return _PROCESS
