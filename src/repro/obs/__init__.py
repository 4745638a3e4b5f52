"""Observability: host-side tracing + metrics export for serving.

The paper's headline numbers (66 tok/s, 4.2x speedup) are whole-run
averages over an opaque pipeline; this package is the layer that breaks
such numbers down — *where does a round spend its time, and what did
each request live through?* Two modules:

* ``obs.trace`` — a low-overhead ring-buffered structured tracer
  (``Tracer`` / ``TraceConfig``). The serving engine emits per-request
  lifecycle spans (queued -> prefill -> decode-round* -> retired, plus
  preempted/resumed/verify/fault events), per-round scheduler phase
  spans (admit / dispatch / sync / walk) and, on a track of their own,
  the admission's sub-spans (``ADMIT_SPANS``: admit.faults,
  admit.expire, admit.group, admit.inputs, admit.pages, admit.prefill,
  admit.first_sync, admit.install, admit.emit), all stamped from the
  engine's own clock so fault-injected skew shows up in traces. Exports
  Chrome/Perfetto ``trace_event`` JSON (open in chrome://tracing or
  ui.perfetto.dev). The phases and sub-spans are also mirrored into
  the JAX profiler as ``TraceAnnotation`` s of the same names, and the
  jitted programs name their layers with ``jax.named_scope`` (embed,
  enc.attn, enc.ffn, dec.cross.kv, dec.layers, dec.attn, dec.cross,
  dec.ffn, head, sampler), so one ``jax.profiler`` capture of a traced
  engine (``deploy(..., trace=TraceConfig())`` under
  ``jax.profiler.start_trace(dir)`` ... ``stop_trace()``, opened in
  TensorBoard's profile plugin or ui.perfetto.dev) lays the host's
  phases and the device's layers on one clock; see ``obs.trace``.
* ``obs.metrics`` — the single nearest-rank ``percentile`` definition
  (shared by ``serving.latency_percentiles`` and the SLA controller), a
  fixed log-bucket ``Histogram`` with merge, and Prometheus
  text-exposition renderers over ``EngineMetrics`` snapshots plus
  histograms (single-snapshot and per-replica labelled).
* ``obs.promhttp`` — a stdlib daemon-thread HTTP server exposing any
  ``prometheus()``-shaped renderer at ``GET /metrics`` (the live
  scrape endpoint behind ``launch.serve --metrics-port``).

This package imports nothing from ``repro.serving`` (serving imports
it), so it can also observe future subsystems (mesh replicas, the
background pump) without a cycle.
"""

from .metrics import (Histogram, percentile, render_prometheus,
                      render_prometheus_labeled)
from .promhttp import MetricsServer
from .trace import (ADMIT_SPANS, ADMIT_TID, PHASES, SCHED_TID, TraceConfig,
                    TraceEvent, Tracer)

__all__ = ["Histogram", "MetricsServer", "percentile", "render_prometheus",
           "render_prometheus_labeled", "ADMIT_SPANS", "ADMIT_TID", "PHASES",
           "SCHED_TID", "TraceConfig", "TraceEvent", "Tracer"]
