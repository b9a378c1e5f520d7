"""Dispatch-queue bounding for Python-level step loops.

JAX dispatch is async: a Python loop that fires one multi-device program per
iteration can pile dozens of in-flight executions (each an n-participant
rendezvous) onto the runtime. XLA:CPU's in-process collective runtime has
been observed to wedge a rendezvous under that pressure on oversubscribed
hosts (root-caused in round 3 at GBT's 40-round boosting loop: hang or
SIGABRT at suite scale). Every sequential step loop therefore calls
``bound_dispatch`` — one synchronization per ``period`` steps costs a single
dispatch latency (the steps are data-dependent anyway) and caps the queue.
"""

from __future__ import annotations

import time

from orange3_spark_tpu.utils.profiling import count_dispatch

#: steps between synchronizations; small enough to cap rendezvous pressure,
#: large enough that the sync cost vanishes against real step times
DISPATCH_SYNC_PERIOD = 16

#: liveness heartbeat — every step loop and prefetch worker ticks this.
#: /healthz (obs/server.py) and the dispatch watchdog's diagnostics read it
#: to distinguish "long compile" from "a device call is blocked forever".
_last_beat = time.monotonic()


def beat() -> None:
    """Record forward progress (a dispatch, a parsed chunk, a DMA)."""
    global _last_beat
    _last_beat = time.monotonic()


def last_beat() -> float:
    """Monotonic timestamp of the most recent progress tick."""
    return _last_beat


def bound_dispatch(step: int, token, period: int = DISPATCH_SYNC_PERIOD) -> None:
    """Block on ``token`` every ``period``-th ``step`` (1-based count).

    Also ticks the process-wide dispatch counter (utils/profiling.py):
    every sequential step loop calls this once per dispatched program, so
    the counter is the bench line's ``dispatches`` field for free — only
    the one-shot fused-scan sites (which never loop) tick it explicitly.

    The periodic sync is the ONE place every step loop can block forever
    on a wedged device, so it routes through the resilience watchdog
    (resilience/watchdog.py): with ``OTPU_DISPATCH_BUDGET_S`` set, a sync
    exceeding the budget raises a typed ``DispatchWedgedError`` with
    diagnostics instead of hanging the process (no budget/no fault spec =
    a plain ``block_until_ready``, same as ever).
    """
    beat()
    count_dispatch()
    if step % period == 0:
        from orange3_spark_tpu.obs.prof import note_sync
        from orange3_spark_tpu.obs.trace import stage
        from orange3_spark_tpu.resilience.watchdog import maybe_guarded_block

        # the one place a step loop blocks on the device: a "dispatch"
        # span here puts the device-pacing wait on the obs timeline,
        # nested under the surrounding chunk/epoch/fit spans. The same
        # blocked seconds feed the goodput accountant as device_compute
        # — the driver only ever observes device pace by blocking here
        # (obs/prof.py; a bare contextvar read when no fit is live)
        with stage("dispatch", index=step) as blocked:
            maybe_guarded_block(token, step=step)
        note_sync(blocked.seconds)
        beat()
