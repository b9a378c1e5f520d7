"""Dispatch watchdog — typed errors instead of infinite hangs.

The wedge signature: a jitted step (or its periodic
``block_until_ready`` sync) simply never returns, and the whole harness
hangs until an outer ``timeout -k`` reaps it at rc=124 — losing the run
AND the diagnostics. Python cannot interrupt a blocked C call, so the
watchdog inverts the wait: the potentially-wedging sync runs on a daemon
monitor thread while the CALLING thread waits on it with a budget
(``OTPU_DISPATCH_BUDGET_S``). On budget exhaustion the caller raises a
typed ``DispatchWedgedError`` carrying stage timings and last-good-chunk
diagnostics (the ``utils.profiling`` exec counters + the liveness beat
age) and moves on — fall back, checkpoint, or exit cleanly; the abandoned
waiter thread parks harmlessly in the runtime. The budget is OFF by
default (0 = a long compile must never be misread as a wedge on a slow
host) and inert under the ``OTPU_RESILIENCE=0`` kill-switch.

``utils.dispatch.bound_dispatch`` routes every step loop's periodic sync
through ``maybe_guarded_block`` — one chokepoint, zero overhead when no
budget and no fault spec are active. The ``wedge`` fault kind
(resilience/faults.py) injects the never-returning dispatch here: the
monitor thread holds for ``hold_s`` before syncing, which under a budget
reproduces the hang signature deterministically and without a budget
degrades to a finite stall (legacy behavior, finitely simulated — tests
must be able to demonstrate the fail-fast ladder without hanging CI).
"""

from __future__ import annotations

import threading
import time

import jax

from orange3_spark_tpu.resilience.faults import (
    active_fault_spec,
    resilience_enabled,
)

__all__ = [
    "DispatchWedgedError",
    "dispatch_budget_s",
    "guarded_block_until_ready",
    "maybe_guarded_block",
]


class DispatchWedgedError(RuntimeError):
    """A device dispatch/sync exceeded its budget — the process would
    previously have hung forever. Carries the evidence a post-mortem
    needs: ``stage``/``step`` locate the wedge, ``budget_s``/``waited_s``
    quantify it, and ``diagnostics`` holds the last-good-progress
    counters (dispatches issued, chunks prefetched, seconds since the
    last liveness beat)."""

    def __init__(self, *, stage: str, step: int | None, budget_s: float,
                 waited_s: float, diagnostics: dict,
                 trace_id: str | None = None):
        self.stage = stage
        self.step = step
        self.budget_s = budget_s
        self.waited_s = waited_s
        self.diagnostics = diagnostics
        self.trace_id = trace_id
        at = f" at step {step}" if step is not None else ""
        if trace_id:
            at += f" [trace {trace_id}]"
        super().__init__(
            f"device dispatch wedged: {stage}{at} exceeded its "
            f"{budget_s:.3g}s budget (waited {waited_s:.3g}s; last "
            f"liveness beat {diagnostics.get('last_beat_age_s', '?')}s "
            f"ago, {diagnostics.get('dispatches', '?')} dispatches / "
            f"{diagnostics.get('prefetch_items', '?')} chunks completed "
            "before the wedge). The process is still alive — fall back, "
            "resume from the last checkpoint, or set "
            "OTPU_DISPATCH_BUDGET_S=0 to restore unbounded waits."
        )


def dispatch_budget_s() -> float:
    """Seconds a guarded sync may block (0 = watchdog disabled). Env
    ``OTPU_DISPATCH_BUDGET_S`` (utils/knobs.py — malformed values fall
    back to the declared 0 default); forced to 0 by the kill-switch."""
    if not resilience_enabled():
        return 0.0
    from orange3_spark_tpu.utils import knobs

    return float(knobs.get_float("OTPU_DISPATCH_BUDGET_S"))


def _diagnostics() -> dict:
    from orange3_spark_tpu.utils.dispatch import last_beat
    from orange3_spark_tpu.utils.profiling import exec_counters

    c = exec_counters()
    return {
        "last_beat_age_s": round(time.monotonic() - last_beat(), 3),
        "dispatches": c["dispatches"],
        "prefetch_items": c["prefetch_items"],
        "prefetch_prep_s": round(c["prefetch_prep_s"], 3),
        "prefetch_wait_s": round(c["prefetch_wait_s"], 3),
    }


def guarded_block_until_ready(token, *, step: int | None = None,
                              stage: str = "step",
                              budget_s: float | None = None):
    """``jax.block_until_ready(token)`` bounded by the watchdog budget.

    The sync runs on a daemon monitor thread; this thread waits up to the
    budget and raises ``DispatchWedgedError`` on exhaustion (the waiter is
    abandoned — it is blocked in the runtime and cannot be interrupted,
    but the PROCESS can now act). A worker-side exception re-raises here;
    an injected ``wedge`` hold is applied on the worker, so the budget
    clock genuinely races it."""
    spec = active_fault_spec()
    hold = spec.take_wedge() if spec is not None else None
    budget = dispatch_budget_s() if budget_s is None else (
        budget_s if resilience_enabled() else 0.0)
    if budget <= 0:
        # legacy unbounded wait; an injected wedge degrades to a finite
        # stall so the fail-fast ladder stays testable without hanging CI
        if hold is not None:
            time.sleep(hold)
        return jax.block_until_ready(token)
    # circuit breaker on repeated wedges (resilience/overload.py): once a
    # budgeted sync has wedged, later guarded syncs fast-fail typed in
    # ~0 s instead of each burning the full budget — until the breaker's
    # seeded cooldown admits a half-open probe sync, whose success
    # re-admits the backend automatically
    from orange3_spark_tpu.resilience.overload import wedge_breaker

    from orange3_spark_tpu.obs.context import (
        current_trace_id, flag_current_trace,
    )

    breaker = wedge_breaker()
    if not breaker.allow():
        diag = _diagnostics()
        diag["breaker_state"] = breaker.state()
        flag_current_trace()     # tail retention keeps the killed trace
        raise DispatchWedgedError(
            stage=stage, step=step, budget_s=budget, waited_s=0.0,
            diagnostics=diag, trace_id=current_trace_id(),
        )
    done = threading.Event()
    err: list = []

    def waiter():
        try:
            if hold is not None:
                time.sleep(hold)
            jax.block_until_ready(token)
        except BaseException as e:  # noqa: BLE001 - re-raised on caller
            err.append(e)
        finally:
            done.set()

    t0 = time.perf_counter()
    threading.Thread(target=waiter, daemon=True,
                     name="otpu-dispatch-waiter").start()
    if not done.wait(budget):
        from orange3_spark_tpu.utils.profiling import record_wedge

        record_wedge()
        breaker.record_failure()
        flag_current_trace()
        # a DISTINCT name: `err` is the waiter closure's result list, and
        # rebinding it here would turn the abandoned waiter's eventual
        # err.append(e) into an AttributeError on this exception object
        wedge_err = DispatchWedgedError(
            stage=stage, step=step, budget_s=budget,
            waited_s=time.perf_counter() - t0, diagnostics=_diagnostics(),
            trace_id=current_trace_id(),
        )
        # black box (obs/flight.py): the waiter thread is still parked in
        # the runtime RIGHT NOW, so the bundle's stacks catch it, and the
        # wedged dispatch span is still open on this thread
        from orange3_spark_tpu.obs.flight import auto_dump

        auto_dump("dispatch_wedged", wedge_err)
        raise wedge_err
    if err:
        raise err[0]
    breaker.record_success()
    return token


def maybe_guarded_block(token, *, step: int | None = None,
                        stage: str = "step"):
    """The ``bound_dispatch`` hook: plain ``block_until_ready`` when no
    budget and no fault spec are active (the common case — two dict
    lookups of overhead), the guarded path otherwise."""
    if active_fault_spec() is None and dispatch_budget_s() <= 0:
        return jax.block_until_ready(token)
    return guarded_block_until_ready(token, step=step, stage=stage)
