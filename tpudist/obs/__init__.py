"""tpudist.obs — distributed observability: metrics, spans, aggregation,
exporters, and the health plane.

The subsystem every layer reports through (see docs/OBSERVABILITY.md):

* :mod:`tpudist.obs.registry` — counters / gauges / log-bucket histograms
  with MetricLogger-style lazy device accumulation (recording never
  syncs; one batched ``device_get`` per snapshot).
* :mod:`tpudist.obs.spans` — ``with obs.span("train_step"):`` Chrome-trace
  timelines, optional ``jax.effects_barrier()`` fencing, composes with
  the XProf trace from :func:`tpudist.utils.metrics.maybe_profile`.
* :mod:`tpudist.obs.aggregate` — workers publish snapshots through the
  coord KV store; rank 0 merges them into a cluster view.
* :mod:`tpudist.obs.export` — metric-row JSONL, Prometheus text, and a
  stdlib-only HTTP ``/metrics`` + ``/healthz`` endpoint.
* :mod:`tpudist.obs.health` — rank-0 straggler/staleness classification
  over the published snapshots, with hysteresis.
* :mod:`tpudist.obs.recorder` — bounded flight-recorder ring and crash
  post-mortem bundles (``with obs.recorder.guard("trainer"): ...``).
* :mod:`tpudist.obs.events` — per-request distributed tracing: trace
  contexts riding the serve fleet's wire format, the request-event
  ring each process records lifecycle transitions into, fleet-wide
  timeline merge (``python -m tpudist.obs.timeline`` renders it), and
  SLO burn-rate accounting (:class:`SLOTracker`).
* :mod:`tpudist.obs.xla` — XLA compile/memory/cost telemetry: compile
  counts and durations, per-device HBM gauges, live MFU.
* :mod:`tpudist.obs.tsdb` — bounded in-memory time-series store scraped
  from the registry/merged snapshots on a cadence, with
  rate/delta/quantile_over_time queries (:class:`TSDB`,
  :class:`FleetScraper`).
* :mod:`tpudist.obs.alerts` — declarative alert rules (query +
  predicate + hold) with a pending->firing->resolved lifecycle; the
  sim's scenario matrix regression-tests the shipped defaults.
* :mod:`tpudist.obs.console` — ``python -m tpudist.obs.console``: live
  terminal dashboard (topology, sparklines, firing alerts, recent
  trace terminals); ``--once`` renders a single frame for CI.

Module-level conveniences bind to one process-global registry, tracer and
flight recorder, so library code can just ``from tpudist import obs;
obs.counter(...)``.  Env knobs (parsed by
:func:`tpudist.utils.config.env_flag`, so ``=0`` and ``=false`` really
mean off): ``TPUDIST_OBS_FENCE`` fences spans with
``jax.effects_barrier()``; ``TPUDIST_POSTMORTEM_DIR`` picks where crash
bundles land.
"""

from __future__ import annotations

from tpudist.obs.aggregate import (
    MetricsPublisher,
    collect,
    collect_and_merge,
    merge_snapshots,
)
from tpudist.obs.alerts import (
    AlertManager,
    AlertRule,
    autoscale_rules,
    default_rules,
    load_rules,
    rules_hash,
)
from tpudist.obs.events import (
    EventPublisher,
    RequestEventLog,
    SLOTracker,
    TraceContext,
    collect_events,
    group_timelines,
    is_complete,
    merge_events,
    timeline_for_rid,
)
from tpudist.obs.export import (
    MetricsServer,
    jsonl_line,
    snapshot_to_jsonl,
    to_prometheus,
)
from tpudist.obs.health import HealthMonitor, HealthWatcher
from tpudist.obs.recorder import POSTMORTEM_SCHEMA, FlightRecorder
from tpudist.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    hist_quantile,
    summarize,
)
from tpudist.obs.spans import (
    ROUTINE_SCOPES,
    SpanTracer,
    atomic_write_json,
    routine,
    scope_of,
)
from tpudist.obs.tsdb import TSDB, FleetScraper
from tpudist.obs.xla import (
    install_compile_telemetry,
    mfu,
    note_compile,
    note_step,
    peak_tflops,
    update_memory_gauges,
)

__all__ = [
    "AlertManager",
    "AlertRule",
    "Counter",
    "EventPublisher",
    "FleetScraper",
    "FlightRecorder",
    "Gauge",
    "HealthMonitor",
    "HealthWatcher",
    "Histogram",
    "MetricRegistry",
    "MetricsPublisher",
    "MetricsServer",
    "POSTMORTEM_SCHEMA",
    "ROUTINE_SCOPES",
    "RequestEventLog",
    "SLOTracker",
    "SpanTracer",
    "TSDB",
    "TraceContext",
    "atomic_write_json",
    "autoscale_rules",
    "collect",
    "collect_and_merge",
    "collect_events",
    "counter",
    "default_rules",
    "events",
    "gauge",
    "group_timelines",
    "histogram",
    "hist_quantile",
    "install_compile_telemetry",
    "is_complete",
    "jsonl_line",
    "load_rules",
    "merge_events",
    "merge_snapshots",
    "mfu",
    "note_compile",
    "note_step",
    "peak_tflops",
    "recorder",
    "registry",
    "routine",
    "rules_hash",
    "scope_of",
    "slo",
    "snapshot",
    "snapshot_to_jsonl",
    "span",
    "summarize",
    "timeline_for_rid",
    "to_prometheus",
    "tracer",
    "update_memory_gauges",
]

# process-global registry + tracer + event ring + SLO tracker + flight
# recorder: instrumentation all over the stack reports here;
# snapshot()/tracer.dump()/events.snapshot()/recorder.dump() read it out
registry = MetricRegistry()
tracer = SpanTracer()
events = RequestEventLog()
slo = SLOTracker(registry=registry)
recorder = FlightRecorder(registry=registry, tracer=tracer,
                          request_events=events)

counter = registry.counter
gauge = registry.gauge
histogram = registry.histogram
snapshot = registry.snapshot
span = tracer.span
