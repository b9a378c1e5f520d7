"""resilience/ — fault injection, bounded retries, dispatch watchdog,
crash-resumable fits (docs/resilience.md).

Spark's real production moat is not throughput, it is that a 100-epoch job
survives a flaky executor (RDD lineage recompute, straggler re-launch —
PAPERS.md: Zaharia et al.; Dean & Barroso tail-tolerance). The opposite
failure mode is a device call that never returns: a run killed from outside
at rc=124, a fit aborted mid-epoch, its diagnostics lost. This package
makes every long-running path survive *injected* faults with
measured, bounded overhead:

* ``faults``   — deterministic, seedable injectors (transient chunk-source
  IOErrors, straggler chunks, corrupted spill records, wedged dispatches,
  flaky AOT builds), activated programmatically via ``inject_faults(...)``
  or process-wide via ``OTPU_FAULT_SPEC`` so the same tier-1 tests and
  bench arms drive them.
* ``retry``    — exponential backoff + jitter + max-attempts, applied to
  chunk-source reads (``resilient_source`` wraps every streaming fit's
  source at entry) and to ``ExecutableCache`` AOT builds. Per-cause
  counters land in ``utils.profiling.resilience_counters()`` and
  ``exec.PipelineStats.retries``.
* ``watchdog`` — budget-bounded device syncs: a dispatch that exceeds
  ``OTPU_DISPATCH_BUDGET_S`` raises a typed ``DispatchWedgedError``
  carrying stage/step/beat diagnostics instead of hanging the process
  forever.
* ``overload`` — overload protection & graceful degradation: admission
  control with projected-wait shedding (``OverloadShedError``), the
  closed/open/half-open ``CircuitBreaker`` (replacing the serving
  first-failure blacklist and fast-failing repeated wedges), adaptive
  micro-batch coalescing, and memory-pressure brownout watermarks
  feeding the ``_DeviceCache`` degrade ladder.
* ``numerics`` — the per-epoch non-finite training guard
  (``NumericalDivergenceError`` naming epoch and chunk ordinal instead
  of silently training to NaN).

Crash-resumable fits: ``checkpoint_every_epochs`` on
``StreamingLinearParams``/``HashedLinearParams`` snapshots training state
atomically at epoch boundaries (``utils.fault.StreamCheckpointer``,
write-to-temp + rename), so a fit SIGKILLed mid-epoch resumes at the last
boundary and converges to the uninterrupted result.

Kill-switch: ``OTPU_RESILIENCE=0`` restores legacy fail-fast behavior
everywhere — no retries, no watchdog budget, no CRC verification, no
epoch-cadence snapshots. Fault *injection* stays active under the
kill-switch (the injectors are the test driver; the mitigations are what
the switch disables), which is what lets the acceptance tests demonstrate
that they FAIL without the subsystem.
"""

from __future__ import annotations

from orange3_spark_tpu.resilience.faults import (
    FaultSpec,
    TransientBuildError,
    TransientSourceError,
    active_fault_spec,
    inject_faults,
    resilience_enabled,
)
from orange3_spark_tpu.resilience.retry import (
    RetryPolicy,
    is_transient,
    resilient_source,
    retry_call,
)
from orange3_spark_tpu.resilience.numerics import (
    NumericalDivergenceError,
    check_finite_training,
)
from orange3_spark_tpu.resilience.overload import (
    AdaptiveCoalescer,
    AdmissionController,
    CircuitBreaker,
    OverloadShedError,
    brownout_level,
    request_deadline,
)
from orange3_spark_tpu.resilience.watchdog import (
    DispatchWedgedError,
    dispatch_budget_s,
    guarded_block_until_ready,
)
from orange3_spark_tpu.utils.fault import StreamCheckpointer

__all__ = [
    "AdaptiveCoalescer",
    "AdmissionController",
    "CircuitBreaker",
    "DispatchWedgedError",
    "FaultSpec",
    "NumericalDivergenceError",
    "OverloadShedError",
    "RetryPolicy",
    "StreamCheckpointer",
    "TransientBuildError",
    "TransientSourceError",
    "active_fault_spec",
    "brownout_level",
    "check_finite_training",
    "dispatch_budget_s",
    "guarded_block_until_ready",
    "inject_faults",
    "is_transient",
    "request_deadline",
    "resilience_enabled",
    "resilient_source",
    "retry_call",
]
