"""Unified observability: spans and metrics.

Two pillars, one package (round-5 verdict: the stack could build fast
paths but not *see* them):

- :mod:`~tensorflowonspark_tpu.obs.spans` — host-side span tracer
  (ring buffer, Chrome-trace export, percentile summaries) that bridges
  into ``jax.profiler`` annotations so host phases and XLA ops share a
  timeline. Wired into the serving engine's request phases and the
  train/feed hot paths.
- :mod:`~tensorflowonspark_tpu.obs.registry` — counters/gauges/
  histograms with a Prometheus text exporter, served at ``/metrics``
  by the HTTP server and each node runtime;
  ``utils.metrics.MetricsWriter`` is a sink of the registry
  (``Registry.publish``), not a parallel system.

Plus the cluster-wide plane (docs/OBSERVABILITY.md):

- :mod:`~tensorflowonspark_tpu.obs.cluster` — run-scoped trace
  context, heartbeat clock sync, Prometheus text parsing, and the
  driver-side :class:`MetricsAggregator` behind
  ``TFCluster.cluster_stats()`` and the driver ``/metrics`` endpoint.
- :mod:`~tensorflowonspark_tpu.obs.flightrec` — per-process failure
  flight recorder (rolling snapshots + event-triggered dumps).
- :mod:`~tensorflowonspark_tpu.obs.trace_merge` — clock-aligned merge
  of driver + node traces into one timeline (``tools/trace_merge.py``).

And the request-level plane (ISSUE 16, docs/OBSERVABILITY.md):

- :mod:`~tensorflowonspark_tpu.obs.reqtrace` — per-request distributed
  tracing with tail-sampled retention (``X-TFOS-Trace`` propagation,
  ``GET /debugz/trace/<id>``).
- :mod:`~tensorflowonspark_tpu.obs.history` — bounded windowed
  time-series rings over metric scrapes (rates, percentiles, JSONL
  spill) — the autotune controller's read substrate.
- :mod:`~tensorflowonspark_tpu.obs.slo` — declarative SLOs with
  multi-window burn-rate evaluation over History.
- :mod:`~tensorflowonspark_tpu.obs.snapshot` — one-command incident
  bundle (``tools/obs_snapshot.py``).
"""

from tensorflowonspark_tpu.obs.history import History
from tensorflowonspark_tpu.obs.registry import (
    CONTENT_TYPE,
    Registry,
    default_registry,
    sanitize_name,
)
from tensorflowonspark_tpu.obs.slo import SLO, SLOEvaluator
from tensorflowonspark_tpu.obs.spans import (
    SpanTracer,
    get_tracer,
    record,
    span,
    step_span,
    traced,
)

__all__ = [
    "CONTENT_TYPE",
    "History",
    "Registry",
    "SLO",
    "SLOEvaluator",
    "SpanTracer",
    "default_registry",
    "get_tracer",
    "record",
    "sanitize_name",
    "span",
    "step_span",
    "traced",
]
