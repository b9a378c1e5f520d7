"""Unified observability subsystem (docs/observability.md).

Six pieces, one kill-switch (``OTPU_OBS=0``):

* ``registry``  — typed thread-safe metrics (counters/gauges/histograms,
  labels, JSON snapshot, Prometheus text exposition). Always live: the
  legacy ``utils.profiling`` counter shims are views over it.
* ``trace``     — low-overhead structured spans (lock-free ring buffer,
  trace/span/parent ids, Chrome trace-event + flow-event export,
  ``jax.profiler`` alignment). No-ops under the kill-switch.
* ``context``   — Dapper-style trace-context propagation: per-request
  trace ids minted at the serving entry, per-fit run ids at fit entry,
  carried via contextvars with tail-biased retention
  (``OTPU_TRACE_SAMPLE``).
* ``flight``    — anomaly flight recorder: a rate-limited ``dump()``
  writing a versioned JSON black-box bundle (spans, breaker states,
  queue depths, knobs, all-thread stacks), fired automatically at the
  typed-anomaly raise sites (``OTPU_FLIGHT=0`` disables).
* ``report``    — per-run structured reports (``model.run_report_``,
  ``ServingContext.report()``), linking into the trace ring via the
  top-k slowest trace trees.
* ``server``    — opt-in stdlib ``/metrics`` + ``/healthz`` +
  ``/debug/flight`` + ``/debug/stacks`` endpoint on serving processes
  (``OTPU_OBS_PORT``). Never binds under the kill-switch.
* ``fleetobs``  — the fleet telemetry plane (its own kill-switch,
  ``OTPU_FLEETOBS``): router-side /metrics aggregation over every
  replica's scrape, cross-process trace assembly, the SLO burn-rate
  engine, fleet incident bundles and the FleetDigest load-signal
  snapshot (docs/observability.md §fleet telemetry).
* ``prof``      — the goodput & memory attribution plane (its own
  kill-switch, ``OTPU_PROF``): five-way step-time decomposition with
  per-epoch bottleneck classification, the named device-memory ledger
  (``otpu_device_bytes{owner=}``) with its account against the allocator
  (marks where spans close, the interval that set the peak, a census of
  the live arrays), and on-demand deep-profile capture
  (``POST /debug/profile``) — docs/observability.md §goodput.
"""

from orange3_spark_tpu.obs.registry import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, MetricsRegistry, get_registry,
)
from orange3_spark_tpu.obs.report import RunReport  # noqa: F401
from orange3_spark_tpu.obs.server import (  # noqa: F401
    TelemetryServer, maybe_start_from_env,
)
from orange3_spark_tpu.obs.trace import (  # noqa: F401
    export_chrome_trace, instant, span, span_iter, validate_chrome_trace,
)
from orange3_spark_tpu.obs import context, flight, trace  # noqa: F401
from orange3_spark_tpu.obs.context import (  # noqa: F401
    current_trace_id, trace_scope,
)


def obs_enabled() -> bool:
    """The master switch (``OTPU_OBS``): spans/endpoint on or off. The
    registry and the legacy counter shims stay live either way."""
    return trace.enabled()
