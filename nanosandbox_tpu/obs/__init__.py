"""Unified telemetry spine: metric registry + span tracer.

Three halves (ISSUE 5), all pure host-side stdlib — no jax import, no
device readback, nothing for jaxlint to flag:

  registry.py — process/instance MetricRegistry: named Counter / Gauge /
                Histogram families with labels, JSON ``snapshot()``, and
                Prometheus text exposition. Engines, the Trainer, the
                tracecheck ledgers and warn_once all publish here.
  tracer.py   — SpanTracer: begin/end spans recorded from already-
                host-resident dispatch-time state, bounded ring,
                request-id correlation, Chrome trace-event JSON export
                (Perfetto-loadable) per request or per time window.
                ``process_tracer()`` is the training path's one tracer
                (trainer, loader, host_sync, compile-cache listeners).
  opscopes.py — which part of the model (attn, mlp, ln, embed,
                lm_head_loss, optimizer, grad_norm) each instruction of
                a compiled step belongs to, from the scope path XLA
                keeps as ``op_name``; how a device trace's op times are
                named. One level down for ``moe_route``: its six stages
                (router, plan, dispatch, combine, weights, accumulate),
                a second map out of the same lowering.

Two more halves (ISSUE 10), same contract:

  flight.py   — FlightRecorder: bounded per-request lifecycle ledger
                (submit -> queue -> block-reserve -> admit ->
                prefill[hit|miss] -> retire* -> finish|reject|shed)
                with JSONL export, plus WatchdogPanel: anomaly
                detectors (TTFT spike, admission stall, pool thrash,
                post-freeze retrace, stuck slot) that snapshot the
                ledger + span ring on a trip.
  slo.py      — SLOLedger: per-request deadline_s / slo_class
                accounting — attainment, goodput tokens, deadline
                margins — published through the registry.
  vitals.py   — register_process_vitals: RSS / open fds / uptime /
                jax live-buffer gauges, sampled per scrape.

The serving surface (serve/http.py) exposes all of it: ``GET
/metrics`` (Prometheus scrape), ``GET /trace?rid=N`` (one request's
timeline), ``GET /debug/requests|slots|kvpool|scheduler`` (flight
ledger + live introspection), ``POST /profile`` (an on-demand
jax.profiler window over the live serve loop).
"""

from nanosandbox_tpu.obs.flight import (TERMINAL_EVENTS, FlightRecorder,
                                        WatchdogPanel)
from nanosandbox_tpu.obs.registry import (DEFAULT_BUCKETS, MetricFamily,
                                          MetricRegistry, global_registry,
                                          render_prometheus)
from nanosandbox_tpu.obs.slo import SLOLedger, validate_slo_class
from nanosandbox_tpu.obs.tracer import (ENGINE_TRACK, Span, SpanTracer,
                                        process_tracer)
from nanosandbox_tpu.obs.vitals import register_process_vitals

__all__ = ["MetricRegistry", "MetricFamily", "SpanTracer", "Span",
           "global_registry", "render_prometheus", "DEFAULT_BUCKETS",
           "ENGINE_TRACK", "FlightRecorder", "WatchdogPanel",
           "TERMINAL_EVENTS", "SLOLedger", "validate_slo_class",
           "register_process_vitals", "process_tracer"]
