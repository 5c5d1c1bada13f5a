"""One place that decides where JAX's persistent compilation cache lives.

Every entry point that compiles for a device (train.main, sample,
``python -m nanosandbox_tpu.serve``, bench.py, chip_smoke.py) calls
``enable_compile_cache()`` before its first compile. The directory is
part of the cache key, so it is never a temporary name, a pid or a time:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this code
    sets nothing, so the cache can be placed from outside;
  * otherwise — ``<checkout>/.jax_cache`` (gitignored).

The CPU test-suite turns the cache off as a whole (tests/conftest.py).

The same call registers, once per process, ``jax.monitoring`` listeners
that record what the first call of a jitted function is made of (tracing,
lowering, the backend's compile or the load out of this cache) as
instants of the process tracer, so that a reader can tell what had
accrued by a given moment (the start of a benchmark's window):

    jax_compile  args.phase = trace|lower|backend|cache_load, args.seconds
    jax_compile  args.phase = hit|miss (one for every cache lookup)

A phase leaves one instant each time it has accrued ``INSTANT_EVERY_S``
more: JAX reports a trace for every small function it stages, thousands
in a set-up, and one instant each would push the set-up's own spans out
of the ring. What a phase's instants sum to at any moment is what it has
taken so far, to within ``INSTANT_EVERY_S``.

A cache entry is keyed on the program with its debug info stripped, so a
hit may hand back an executable compiled by an older version of the code
that computed the very same under other scope names. After a change of
names alone (``jax.named_scope``, a module renamed) clear the cache
directory, or a device trace, and ``Trainer.step_op_parts``' map of it,
show the old names (the map then calls much of the step ``unscoped``).
"""

from __future__ import annotations

import os
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
INSTANT_EVERY_S = 0.05
_listening = False


def _listen() -> None:
    """Register the listeners above; a second call registers nothing."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    from nanosandbox_tpu.obs import process_tracer

    tracer = process_tracer()
    pending = dict.fromkeys(_PHASES.values(), 0.0)
    lock = threading.Lock()

    def on_duration(event, duration, **kw):
        phase = _PHASES.get(event)
        if phase is None:
            return
        with lock:
            pending[phase] += duration
            due = pending[phase]
            if due < INSTANT_EVERY_S:
                return
            pending[phase] = 0.0
        tracer.instant("jax_compile", cat="compile",
                       args={"phase": phase, "seconds": due})

    def on_event(event, **kw):
        result = _RESULTS.get(event)
        if result is not None:
            tracer.instant("jax_compile", cat="compile",
                           args={"phase": result, "seconds": 0.0})

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def enable_compile_cache() -> str:
    """Returns the directory the cache is kept in."""
    _listen()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
