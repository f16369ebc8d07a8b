"""Unified observability: tracing, metrics, decision auditing, and the
fleet layer on top (docs/observability.md).

Process-local pillars:

* ``obs.trace``     — nested span tracer into any captured
  ``jax.profiler`` trace and a Chrome-trace/Perfetto buffer, jit-compile
  tagging; global instance ``obs.tracer``. Fleet-aware: real ``os.getpid()``
  stamps, process-name metadata events, epoch offsets for
  cross-process merge.
* ``obs.metrics``   — counters/gauges/histograms with labels +
  Prometheus text exposition; the engine's ``EngineStats`` is a view
  over a ``MetricsRegistry``; ``obs.metrics.default_registry`` hosts
  process-lifetime infrastructure metrics (ft heartbeats).
* ``obs.decisions`` — structured audit log of every
  ``models/backend.py:select_backend`` call; global ``obs.decisions.log``.

Fleet layers (offline — they read exported artifacts, never the hot
path):

* ``obs.aggregate`` — versioned ``repro.obs/v1`` metrics snapshots,
  associative cross-replica merge, fleet Prometheus rendering.
* ``obs.slo``       — declarative SLO targets + error budgets over a
  registry or snapshot; ``python -m repro.obs.slo --check`` for CI.
* ``python -m repro.obs`` — trace merge + per-request cross-process
  timelines + snapshot aggregation CLI.

Invariant (design.md §4.6): purely observational. The pillars are
write-only from the serving/dispatch hot paths — nothing reads them
back into scheduling, selection, or sampling — and everything except
the always-on metrics counters is off by default with one-flag-check
overhead (a span also asks whether a profile is being captured). Aggregation and SLO evaluation read metrics *offline* (a
snapshot or exported file), never from the hot path.
"""

from repro.obs import (aggregate, decisions, metrics,  # noqa: F401
                       slo, trace, validate)
from repro.obs.decisions import DecisionLog
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, render_all)
from repro.obs.trace import Tracer, merge_traces, request_spans, tracer

__all__ = [
    "aggregate", "decisions", "metrics", "slo", "trace", "validate",
    "DecisionLog", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "render_all", "Tracer", "merge_traces",
    "request_spans", "tracer",
]
