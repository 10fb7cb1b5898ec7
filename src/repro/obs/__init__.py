"""Observability subsystem: span tracing, the unified metrics registry,
and trace reporting.

- :mod:`repro.obs.trace` — nested span tracer with Chrome trace-event /
  Perfetto JSON export; zero-cost (and bit-identical) when disabled.  Its
  wall spans are mirrored as ``jax.profiler.TraceAnnotation``s of the same
  name, so a profiler session shows them on the device trace's clock.  The batcher
  spans every tick as ``serve.tick`` ⊃ ``serve.admit`` (⊃ ``serve.prefill``,
  ``serve.scatter``, ``serve.first_token``), ``serve.decode``,
  ``serve.sample`` and ``serve.feedback``, with ``serve.queue`` from
  submit to admission; ``serve.tick`` and ``serve.admit`` carry ``syncs``,
  the times the host waited on the device: two a decode tick (the step,
  then one read of every slot's sampled token) and two an admission.
  ``serve.admit`` carries the ``pages`` it reserved (KV pages, where the
  batcher pages an attention KV cache); there ``serve.tick`` also carries
  ``kv_pages_held``, the pool pages its live slots hold when it ends.
- :mod:`repro.obs.metrics` — the metrics registry that is the single
  source of truth for discovery-variable names, plus labeled runtime
  instruments.
- :mod:`repro.obs.report` — ``python -m repro.obs.report trace.json``
  summarizes an exported trace (top spans, queue-time breakdown, SLO
  burn, tuner rounds) and validates it against the trace-event schema.
"""

from repro.obs import trace
from repro.obs.metrics import REGISTRY, MetricSpec, MetricsRegistry, declare, discovery_names
from repro.obs.trace import (
    NULL_SPAN,
    TRACK_ENV,
    TRACK_KERNEL,
    TRACK_SERVE,
    TRACK_SIM,
    TRACK_TUNER,
    Tracer,
    active,
    enabled,
    span,
    start,
    stop,
    trace_to,
)

__all__ = [
    "trace",
    "REGISTRY",
    "MetricSpec",
    "MetricsRegistry",
    "declare",
    "discovery_names",
    "NULL_SPAN",
    "TRACK_ENV",
    "TRACK_KERNEL",
    "TRACK_SERVE",
    "TRACK_SIM",
    "TRACK_TUNER",
    "Tracer",
    "active",
    "enabled",
    "span",
    "start",
    "stop",
    "trace_to",
]
