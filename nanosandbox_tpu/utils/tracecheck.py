"""Runtime retrace-budget guard + deliberate-host-sync accounting.

The static half of the compile-set contract lives in
``nanosandbox_tpu.analysis`` (jaxlint); this module is the RUNTIME
half. The failure mode both defend against: a Python scalar or
unbucketed shape specializes a jitted step, XLA silently recompiles per
distinct value, and "as fast as the hardware allows" becomes
one-compile-per-request — with nothing in CI to notice.

``compile_budget`` replaces the engine's old hand-rolled
``self.trace_counts[...] += 1`` counters (a trace-time side effect
inside the jitted body — exactly what jaxlint's impure-trace rule
flags) with a wrapper OUTSIDE the traced function: jax calls the
wrapped Python body once per trace, so counting calls counts traces,
and overflowing the declared budget raises ``CompileBudgetExceeded``
immediately — a loud failure at the retrace instead of a silent 10x
serving slowdown.

    reg = TraceBudgetRegistry()
    decode = jax.jit(reg.guard("decode", 1)(decode_fn))
    ...
    reg.counts()             # {"decode": 1}
    with reg.frozen():       # post-warmup: ANY new trace raises
        serve_forever()

``host_sync`` is the blessed wrapper for a DELIBERATE device->host
readback (jaxlint recognizes it and does not flag the call): it reads
the scalar, counts the sync under a name, and lets callers report how
many syncs a window contained (train.py's profiler window does).
"""

from __future__ import annotations

import functools
import threading
from contextlib import contextmanager
from typing import Callable, Dict, Optional


def _obs_counter(metric: str, help: str, name: str):
    """The ``{name=...}`` child of a process-global counter family —
    the obs-registry mirror of this module's ledgers. Lazy import:
    obs.registry is stdlib-only but lives above utils in the package
    graph, and tracecheck must stay importable on a bare Python."""
    from nanosandbox_tpu.obs.registry import global_registry

    return global_registry().counter(metric, help,
                                     labelnames=("name",)).labels(name=name)


class CompileBudgetExceeded(RuntimeError):
    """A guarded function traced more often than its declared budget —
    some call-site input (shape, dtype, Python scalar, pytree
    structure) is not from the closed set the budget promises."""


class _Budget:
    __slots__ = ("name", "max_traces", "traces")

    def __init__(self, name: str, max_traces: int):
        self.name = name
        self.max_traces = max_traces
        self.traces = 0


class TraceBudgetRegistry:
    """A family of named retrace budgets (typically one per Engine or
    Trainer instance, so tests with many engines don't share state).

    Thread-safe: the serve engine traces on a background stepping
    thread while /stats reads counts on HTTP handler threads.
    """

    def __init__(self):
        self._budgets: Dict[str, _Budget] = {}
        self._lock = threading.Lock()
        self._frozen = False

    # ------------------------------------------------------------- budgets

    def register(self, name: str, max_traces: int) -> None:
        if max_traces < 0:
            raise ValueError(f"max_traces must be >= 0, got {max_traces}")
        with self._lock:
            b = self._budgets.get(name)
            if b is None:
                self._budgets[name] = _Budget(name, max_traces)
            else:
                b.max_traces = max_traces

    def guard(self, name: str, max_traces: int,
              ) -> Callable[[Callable], Callable]:
        """Decorator: count every call of the wrapped function (== every
        TRACE once the result is jitted) against the named budget.

        Wrap the function handed TO jax.jit, not the jitted result:

            self._decode = jax.jit(reg.guard("decode", 1)(self._decode_fn))
        """
        self.register(name, max_traces)

        def deco(fn: Callable) -> Callable:
            @functools.wraps(fn)
            def traced(*args, **kwargs):
                self.bump(name)
                return fn(*args, **kwargs)
            traced.__tracecheck_name__ = name
            return traced
        return deco

    def bump(self, name: str) -> int:
        """Record one trace; raises on budget overflow or when frozen.

        A REJECTED trace (frozen registry, or past budget) does NOT
        consume the counter: the raise aborts the jax trace before any
        program is compiled, so counting it would make counts() lie
        about the real compile set — /stats would overreport programs,
        and assert_within_budget() would fail permanently on an engine
        that survived (and kept serving past) one rejected leak."""
        n = None
        with self._lock:
            b = self._budgets.setdefault(name, _Budget(name, 0))
            if self._frozen:
                raise CompileBudgetExceeded(
                    f"retrace of {name!r} (would be trace "
                    f"#{b.traces + 1}) inside a frozen registry: the "
                    "compile set was declared complete (e.g. post-warmup "
                    "serving), so some input left the closed shape/dtype "
                    "set")
            if b.traces + 1 > b.max_traces:
                attempt, budget = b.traces + 1, b.max_traces
            else:
                b.traces += 1
                n = b.traces
        if n is not None:
            # Accepted trace: mirror into the process-global metric
            # registry so a Prometheus scrape sees compiles process-wide
            # (per-engine views stay on each engine's own registry).
            # Compiles are rare by contract, so this is never hot.
            _obs_counter("compile_traces_total",
                         "Accepted jit traces, by guarded program name "
                         "(every budget registry in the process).",
                         name).inc()
            return n
        raise CompileBudgetExceeded(
            f"{name!r} would trace {attempt} times, budget {budget}: a "
            "call-site input is specializing the trace (unbucketed "
            "shape, Python scalar operand, or changed pytree "
            "structure). Find the leak with `python -m "
            "nanosandbox_tpu.analysis` (nonstatic-shape rule) or "
            "raise the budget if the compile set legitimately grew.")

    # ------------------------------------------------------------- queries

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {n: b.traces for n, b in self._budgets.items()}

    def budgets(self) -> Dict[str, int]:
        with self._lock:
            return {n: b.max_traces for n, b in self._budgets.items()}

    def assert_within_budget(self) -> None:
        """Re-check every budget (bump already enforces; this is the
        test-suite's one-line postcondition)."""
        with self._lock:
            over = [(b.name, b.traces, b.max_traces)
                    for b in self._budgets.values()
                    if b.traces > b.max_traces]
        if over:
            raise CompileBudgetExceeded(
                "; ".join(f"{n!r}: {t} traces > budget {m}"
                          for n, t, m in over))

    @contextmanager
    def frozen(self):
        """Inside this context ANY new trace raises — the post-warmup
        serving contract: /healthz went green meaning every program is
        compiled, so a compile after that point is a shape leak eating
        a live request's latency."""
        with self._lock:
            prev, self._frozen = self._frozen, True
        try:
            yield self
        finally:
            with self._lock:
                self._frozen = prev


# Module-level convenience for code without a natural registry owner.
_GLOBAL = TraceBudgetRegistry()


def compile_budget(name: str, max_traces: int, *,
                   registry: Optional[TraceBudgetRegistry] = None,
                   ) -> Callable[[Callable], Callable]:
    """``@compile_budget("step", 1)`` on the function handed to jax.jit:
    raises CompileBudgetExceeded past ``max_traces`` traces. Uses the
    process-global registry unless one is passed."""
    return (registry or _GLOBAL).guard(name, max_traces)


def global_registry() -> TraceBudgetRegistry:
    return _GLOBAL


# ------------------------------------------------------- host-sync ledger

_sync_lock = threading.Lock()
_sync_counts: Dict[str, int] = {}


def host_sync(name: str, value=None) -> Optional[float]:
    """The BLESSED deliberate device->host readback: reads ``value``
    back as a Python float (a hard sync: it waits for everything queued
    before it) and counts the sync under ``name`` so windows can be audited. jaxlint's
    host-sync rule recognizes this call and does not flag it; a raw
    float()/np.asarray in a hot path does get flagged."""
    with _sync_lock:
        _sync_counts[name] = _sync_counts.get(name, 0) + 1
    # Mirror into the process-global metric registry: /metrics carries
    # host_syncs_total{name=...} so "did serving start syncing?" is a
    # scrape query, not a log grep. Deliberate syncs are rare (that is
    # the point of the ledger), so this path is never hot.
    _obs_counter("host_syncs_total",
                 "Deliberate device->host readbacks through the blessed "
                 "tracecheck.host_sync wrapper, by ledger name.",
                 name).inc()
    if value is None:
        return None
    # The wait itself, as a span of the process tracer named by the
    # ledger kind: where a device trace shows the chip idle after a
    # readback, this says which readback it was.
    from nanosandbox_tpu.obs.tracer import process_tracer

    with process_tracer().span(name, cat="host_sync"):
        return float(value)


def sync_counts() -> Dict[str, int]:
    with _sync_lock:
        return dict(_sync_counts)


def sync_delta(mark: Dict[str, int]) -> Dict[str, int]:
    """Per-kind ledger growth since ``mark`` (a prior ``sync_counts()``
    snapshot), positive entries only — the "how many syncs did this
    window contain" computation both profiler windows (train.py's
    --profile_steps and the serve engine's POST /profile) report."""
    return {k: v - mark.get(k, 0) for k, v in sync_counts().items()
            if v - mark.get(k, 0) > 0}


def sync_count(name: Optional[str] = None) -> int:
    """Total recorded deliberate host syncs (or just ``name``'s) —
    train.py snapshots this around the profiler window to report how
    many syncs the traced region contained."""
    with _sync_lock:
        if name is not None:
            return _sync_counts.get(name, 0)
        return sum(_sync_counts.values())
