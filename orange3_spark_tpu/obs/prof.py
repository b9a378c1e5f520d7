"""Goodput & memory attribution plane (docs/observability.md §goodput).

The obs stack through PR 11 can say *that* a request was slow or a fit
wedged; this module answers **where the time and the HBM went**. Three
coupled pieces, one kill-switch (``OTPU_PROF=0`` restores the pre-prof
behavior bitwise — no accounting, no ledger ticks, deep capture refused):

* **Step-time decomposition** (:class:`GoodputAccountant`) — an
  always-on, low-overhead accountant fed by the existing exec
  chokepoints: ``PipelinedExecutor`` queue waits (input), the
  ``bound_dispatch`` periodic sync (the one place the driver observes
  device pace), explicit barriers (epoch walls, the fused-replay final
  sync) and the codec/plan encode seconds off ``PipelineStats``. Each
  fit's wall decomposes into five disjoint fractions —
  ``device_compute`` / ``input_wait`` / ``host_encode`` / ``sync_wait``
  / ``framework`` — that sum to 1.0 by construction (``framework`` is
  the measured residual: python step-issue overhead, seeding, report
  building). Per epoch the bottleneck is classified input-bound vs
  compute-bound vs sync-bound with hysteresis (``OTPU_PROF_HYST``) so a
  fit oscillating at a boundary never flaps. Exposed as
  ``otpu_goodput_fraction{stage=}`` gauges, a ``goodput`` section in
  every ``RunReport``, and per-replica through the fleet digest.

  Attribution semantics (the host's view of an async pipeline): queue
  waits are *input*; the periodic dispatch sync is *device compute*
  (the driver only ever observes the device by blocking on it, and the
  periodic sync blocks exactly while the device drains queued steps);
  explicit barriers (epoch-boundary ``block_until_ready``, the
  fused-replay final sync) are *synchronization*; encode/plan seconds
  run on the prefetch thread, so only the part that could not hide
  behind device work — ``min(encode_s, input_wait)`` — is charged as
  *host_encode* (the rest was free).

* **Device-memory ledger** (:class:`DeviceMemoryLedger`) — a registry
  of named device-resident allocations: ``_DeviceCache`` chunks
  (codec-aware bytes, the owner ``cache_chunks``), model/optimizer
  state (``model_state``), serving ``ExecutableCache`` entries
  (``serve_executables``, bytes best-effort via the executable's
  ``memory_analysis``), the fused replay's stack of the cache
  (``replay_plans``), tables put from the host (``tables``) and the
  table a staged canvas hands back (``canvas_out``). Live bytes per
  owner ride ``otpu_device_bytes{owner=}``; per-fit peak watermarks land
  in the report's ``device_memory`` section. **The HBM account**: where a
  fit closes a span built with ``hbm=True`` the ledger takes a *mark*
  (:func:`hbm_mark`) — the allocator's own ``memory_stats()`` of the
  fullest device beside the ledger's total, never a wait for the
  device — names the interval in which the allocator's peak rose after
  the span that closed it (``high_water``: the bytes that lived only
  inside it are its ``transient_bytes``), and, when the peak has risen
  64 MiB over the last one, takes a *census* of ``jax.live_arrays()`` on
  that device: every array under the owner whose entry was handed that
  very array, ``unnamed`` otherwise, and what the allocator holds beyond
  them as ``runtime_held_bytes``.

* **On-demand deep capture** (:func:`capture`) — ``POST
  /debug/profile?duration_ms=`` on the obs server (loopback only,
  rate-limited by ``OTPU_PROF_RATE_S`` → 429, serialized → 409) runs
  ``jax.profiler.trace`` plus a goodput+ledger+registry snapshot into
  one atomic artifact directory under ``OTPU_PROF_DIR``
  (``capture-<ns>-<reason>/`` with ``snapshot.json`` + ``jax_trace/``;
  written into a ``.tmp`` sibling and renamed, so a reader never sees a
  half-written capture). ``utils.profiling.profile_trace`` routes
  through the same serialized + rate-limited + atomic path
  (:func:`trace_capture`), keeping its public signature; manual pulls:
  ``tools/obs_dump.py --profile``, rendered by ``tools/goodput_view.py``.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import logging
import os
import threading
import time

from orange3_spark_tpu.obs import trace as _trace
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.utils import knobs

__all__ = [
    "BOTTLENECKS",
    "CaptureBusyError",
    "CaptureDisabledError",
    "CaptureRateLimitedError",
    "DeviceMemoryLedger",
    "GoodputAccountant",
    "LEDGER",
    "LiveArraysAllocator",
    "PROF_SCHEMA_VERSION",
    "STAGES",
    "attach_fit_report",
    "begin_fit",
    "capture",
    "capture_snapshot",
    "current",
    "end_fit",
    "force_disabled",
    "force_enabled",
    "hbm_mark",
    "last_goodput",
    "ledger_release",
    "ledger_set",
    "ledger_set_owned",
    "ledger_set_tree",
    "note_input_wait",
    "note_mesh",
    "note_sync",
    "prof_enabled",
    "refreshed_enabled",
    "reset_rate_limit",
    "trace_capture",
    "tree_chip_bytes",
]

log = logging.getLogger("orange3_spark_tpu")

PROF_SCHEMA_VERSION = 1

#: the five disjoint wall fractions, in reporting order
STAGES = ("device_compute", "input_wait", "host_encode", "sync_wait",
          "framework")

#: stage -> bottleneck label. host_encode counts toward input_bound
#: (exposed encode IS input-pipeline slowness — the fix is the same:
#: feed the device faster); framework classifies as its own label, so a
#: compile/python-dominated run is never mislabeled as one of the
#: measured waits it dwarfs.
BOTTLENECKS = {
    "input_wait": "input_bound",
    "host_encode": "input_bound",
    "device_compute": "compute_bound",
    "sync_wait": "sync_bound",
    "framework": "framework_bound",
}

_M_GOODPUT = REGISTRY.gauge(
    "otpu_goodput_fraction",
    "per-stage fraction of the last finished fit's wall "
    "(device_compute/input_wait/host_encode/sync_wait/framework)")
_M_DEVICE_BYTES = REGISTRY.gauge(
    "otpu_device_bytes",
    "live device-resident bytes per ledger owner (cache_chunks / "
    "model_state / serve_executables / replay_plans / tables / "
    "canvas_out)")
_M_MESH_DEVICES = REGISTRY.gauge(
    "otpu_mesh_devices",
    "devices along each axis of the mesh the last started fit ran on "
    "(axis=data|model): (1,1) is one chip, (2,2) a table sharded over "
    "model and rows over data")
_M_CAPTURES = REGISTRY.counter(
    "otpu_prof_captures_total",
    "deep-profile capture attempts, by outcome "
    "(ok/busy/rate_limited/error)")


def prof_enabled() -> bool:
    """The ``OTPU_PROF`` kill-switch, re-resolved per call (the
    OTPU_DONATE convention: chokepoints re-read, never a cached latch).
    Called once per fit entry / ledger mutation / capture — never inside
    the per-step hot path (that path gates on :func:`current` being
    None, a bare contextvar read)."""
    return knobs.get_bool("OTPU_PROF")


# Alias so chokepoints read the same way as trace.refreshed_enabled().
refreshed_enabled = prof_enabled


@contextlib.contextmanager
def _force(value: str):
    """Env-backed temporary OTPU_PROF override (the bench A/B arms)."""
    prev = os.environ.get("OTPU_PROF")
    os.environ["OTPU_PROF"] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("OTPU_PROF", None)
        else:
            os.environ["OTPU_PROF"] = prev


def force_disabled():
    """Temporarily disable the prof plane (the bench A/B's off arm)."""
    return _force("0")


def force_enabled():
    """Temporarily force the prof plane ON (the on arm must measure real
    accounting even under an ambient OTPU_PROF=0)."""
    return _force("1")


# ===================================================== goodput accounting
class GoodputAccountant:
    """One fit's wall-time decomposition. Created at fit entry
    (:func:`begin_fit`), fed by the exec chokepoints through the
    module-level :func:`note_sync` / :func:`note_input_wait` hooks (a
    contextvar lookup — no knob read on the hot path), closed by
    :meth:`finish`.

    The measured buckets are *driver-thread blocked seconds* and are
    disjoint by construction (the driver can only block in one place at
    a time); ``host_encode`` is carved out of ``input_wait`` at result
    time (``min(encode_s, input_wait_raw)`` — encode hidden behind
    device work cost the fit nothing); ``framework`` is the residual.
    Fractions therefore sum to exactly 1.0 (bench-gated at ±0.02 after
    rounding)."""

    def __init__(self, kind: str = "fit", hysteresis: float | None = None):
        self.kind = kind
        self.hysteresis = float(
            hysteresis if hysteresis is not None
            else knobs.get_float("OTPU_PROF_HYST"))
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        # cumulative driver-thread blocked seconds
        self._dev = 0.0          # periodic dispatch syncs (device pace)
        self._sync = 0.0         # explicit barriers
        self._wait = 0.0         # prefetch queue waits
        self._encode = 0.0       # external cumulative feed (prefetch thread)
        # per-epoch classification state
        self._mark = (0.0, 0.0, 0.0, 0.0, self._t0)
        self.epochs: list[dict] = []
        self.bottleneck: str | None = None
        self._wm = LEDGER.watermark()
        # the watermark dict is walked on EVERY ledger mutation: an
        # accountant abandoned by an ABORTED fit (no finish, no
        # end_fit) must still close its watermark when it dies — the
        # next begin_fit drops the contextvar's ref, GC does the rest.
        # Deferred (lock-free) close: GC finalizers must never take the
        # ledger lock. The callback holds no reference back to this
        # accountant, so the finalizer cannot keep it alive.
        import weakref

        weakref.finalize(self, LEDGER.defer_watermark_close,
                         self._wm._key)
        self._result: dict | None = None

    # ------------------------------------------------------------- feeds
    def add(self, stage: str, seconds: float) -> None:
        """Accumulate driver-blocked seconds into one measured bucket."""
        if seconds <= 0.0:
            return
        with self._lock:
            if stage == "device_compute":
                self._dev += seconds
            elif stage == "sync_wait":
                self._sync += seconds
            elif stage == "input_wait":
                self._wait += seconds
            else:
                raise ValueError(
                    f"goodput: unknown measured stage {stage!r} "
                    f"(framework/host_encode are derived, not fed)")

    def feed_encode(self, encode_s: float) -> None:
        """Set the CUMULATIVE encode/plan seconds (prefetch-thread work,
        read off PipelineStats at epoch boundaries / finish)."""
        with self._lock:
            self._encode = max(self._encode, float(encode_s))

    # -------------------------------------------------------- epoch feed
    @staticmethod
    def _decompose(wall, dev, sync, wait, encode):
        """(seconds per stage, disjoint, clamped to wall)."""
        host_encode = min(max(encode, 0.0), max(wait, 0.0))
        input_wait = max(wait - host_encode, 0.0)
        measured = dev + sync + input_wait + host_encode
        if wall > 0 and measured > wall:
            # overlapping/duplicated measurement can only ever overshoot
            # by noise; scale down so the buckets stay a partition
            scale = wall / measured
            dev, sync = dev * scale, sync * scale
            input_wait, host_encode = (input_wait * scale,
                                       host_encode * scale)
            measured = wall
        return {
            "device_compute": dev,
            "input_wait": input_wait,
            "host_encode": host_encode,
            "sync_wait": sync,
            "framework": max(wall - measured, 0.0),
        }

    def _classify(self, fractions: dict) -> str:
        """Hysteresis classifier over the SUMMED label fractions: the
        incumbent keeps the title unless a challenger's fraction beats
        it by ``hysteresis`` (absolute). A fresh accountant (no
        incumbent) takes the plain argmax; nothing measured at all
        (wall 0) reads framework_bound."""
        cands: dict[str, float] = {}
        for stage, label in BOTTLENECKS.items():
            cands[label] = cands.get(label, 0.0) + fractions.get(stage,
                                                                 0.0)
        best = max(cands, key=cands.get)
        if cands[best] <= 0.0:
            return "framework_bound"
        if self.bottleneck is None or self.bottleneck not in cands:
            return best
        if cands[best] > cands[self.bottleneck] + self.hysteresis:
            return best
        return self.bottleneck

    def epoch_boundary(self, epoch: int, *,
                       encode_s: float | None = None) -> dict:
        """Close one epoch's window: per-epoch stage deltas, classify
        with hysteresis, record. Emits a ``bottleneck`` instant on
        CHANGE only (the timeline shows regime shifts, not every
        epoch)."""
        if encode_s is not None:
            self.feed_encode(encode_s)
        now = time.perf_counter()
        with self._lock:
            dev0, sync0, wait0, enc0, t0 = self._mark
            wall = max(now - t0, 0.0)
            secs = self._decompose(wall, self._dev - dev0,
                                   self._sync - sync0,
                                   self._wait - wait0,
                                   self._encode - enc0)
            self._mark = (self._dev, self._sync, self._wait,
                          self._encode, now)
        fracs = {s: (v / wall if wall > 0 else 0.0)
                 for s, v in secs.items()}
        prev = self.bottleneck
        label = self._classify(fracs)
        self.bottleneck = label
        entry = {"epoch": int(epoch), "bottleneck": label,
                 "wall_s": round(wall, 6),
                 "fractions": {s: round(f, 4) for s, f in fracs.items()}}
        self.epochs.append(entry)
        if label != prev and prev is not None:
            _trace.instant("bottleneck", epoch=int(epoch), was=prev,
                           now=label)
        return entry

    # ------------------------------------------------------------ result
    def finish(self, *, encode_s: float | None = None,
               wall_s: float | None = None) -> dict:
        """Freeze the decomposition (idempotent — first call wins), set
        the ``otpu_goodput_fraction`` gauges, publish as the process's
        :func:`last_goodput`."""
        global _last_goodput
        if self._result is not None:
            return self._result
        if encode_s is not None:
            self.feed_encode(encode_s)
        wall = (float(wall_s) if wall_s is not None
                else time.perf_counter() - self._t0)
        with self._lock:
            secs = self._decompose(wall, self._dev, self._sync,
                                   self._wait, self._encode)
        # fractions off UNROUNDED seconds, then rounded: the residual
        # construction makes them sum to 1.0 exactly, rounding moves the
        # sum by < 5 * 5e-5 — comfortably inside the ±0.02 bench gate
        fracs = {s: round(v / wall, 4) if wall > 0 else 0.0
                 for s, v in secs.items()}
        if self.bottleneck is None:
            self.bottleneck = self._classify(fracs)
        self._result = {
            "schema": PROF_SCHEMA_VERSION,
            "kind": self.kind,
            "wall_s": round(wall, 6),
            "fractions": fracs,
            "seconds": {s: round(v, 6) for s, v in secs.items()},
            "bottleneck": self.bottleneck,
            "epochs": list(self.epochs),
            "peak_device_bytes": self._wm.close(),
        }
        for s, f in fracs.items():
            _M_GOODPUT.set(f, stage=s)
        _last_goodput = self._result
        return self._result


#: the current fit's accountant on this thread of control (contextvars:
#: the dispatch hook reads it lock-free; None = prof off or no fit live)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "otpu_prof_accountant", default=None)
_last_goodput: dict | None = None


def current() -> GoodputAccountant | None:
    return _CURRENT.get()


def begin_fit(kind: str = "fit") -> GoodputAccountant | None:
    """Fit-entry chokepoint: a live accountant under ``OTPU_PROF``,
    None under the kill-switch (every downstream hook then no-ops on a
    bare contextvar read — the PR-11 path, bitwise). Always (re)sets
    the contextvar, so an earlier fit that aborted mid-flight cannot
    leave its stale accountant collecting this fit's waits."""
    if not prof_enabled():
        _CURRENT.set(None)
        return None
    acc = GoodputAccountant(kind)
    # plain set, NOT a reset token: fits never nest, and a token chain
    # would keep every abandoned (aborted-fit) accountant alive through
    # its predecessor reference — defeating the watermark finalizer
    _CURRENT.set(acc)
    return acc


def end_fit(acc: GoodputAccountant | None) -> None:
    """Clear the contextvar (finish() may run before or after). An
    accountant abandoned without finish() (an aborted fit, the bench
    A/B arms) closes its ledger watermark here — the watermark dict is
    iterated on EVERY ledger mutation, so a leak is a per-process
    slowdown, not just bookkeeping."""
    if acc is None:
        return
    if acc._result is None:
        acc._wm.close()
    if _CURRENT.get() is acc:
        _CURRENT.set(None)


def note_sync(seconds: float, *, barrier: bool = False) -> None:
    """The ``bound_dispatch`` / explicit-barrier hook: charge driver
    seconds blocked on the device. Periodic syncs are device pace
    (``device_compute``); explicit barriers (``barrier=True``) are
    ``sync_wait``. A bare contextvar read when no fit is live."""
    acc = _CURRENT.get()
    if acc is not None:
        acc.add("sync_wait" if barrier else "device_compute", seconds)


def note_input_wait(seconds: float) -> None:
    """The ``PipelinedExecutor`` consumer hook: driver seconds blocked
    on the prefetch queue."""
    acc = _CURRENT.get()
    if acc is not None:
        acc.add("input_wait", seconds)


def note_mesh(mesh, **table_specs) -> None:
    """A fit says, once at its start, what it runs on: the mesh's shape on
    ``otpu_mesh_devices{axis=}`` and one ``mesh`` event on the fit's own
    trace carrying that shape and the ``PartitionSpec`` of each table it
    was handed by name — read off the arrays, not off what was asked for.
    Rides the OTPU_PROF kill-switch like the ledger."""
    if not prof_enabled():
        return
    shape = {str(axis): int(n) for axis, n in mesh.shape.items()}
    for axis, n in shape.items():
        _M_MESH_DEVICES.set(n, axis=axis)
    _trace.instant("mesh", **shape, **table_specs)


def last_goodput() -> dict | None:
    """The most recent finished fit's decomposition (what a serving
    process's deep capture reports when no fit is live)."""
    return _last_goodput


# ===================================================== device-memory ledger
#: a census is taken at a mark whose live peak stands this far above the
#: peak at the last census: a few times in a warm job, never in a steady one
CENSUS_RISE_BYTES = 64 << 20
#: groups a census keeps, largest first
CENSUS_GROUPS = 16
#: marks kept a fit, the newest (the high-water interval is found as the
#: marks are taken and does not need the list)
MARKS_PER_FIT = 128


def _device_stats() -> list | None:
    """The allocator's own bookkeeping, one dict a local device
    (``bytes_in_use``, ``peak_bytes_in_use``, ``peak_bytes_reserved``):
    read on the host, no wait for the device. None where a backend keeps
    none (the CPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats()
        if not st:
            return None
        out.append(st)
    return out


def _shard_bytes(x) -> int:
    """What one device holds of a jax array: one shard of
    ``sharding.shard_shape`` (a replicated axis repeats the shard, a
    sharded one divides it) AS THE DEVICE LAYS IT OUT — an ``f32[N, 5]``
    that the TPU pads to eight sublanes takes the bytes of ``[N, 8]``, and
    the allocator counts those. The buffer's size is known when it is
    enqueued: no wait. A backend that does not say counts the shard's
    elements."""
    import math

    sharding = x.sharding
    try:
        return x.on_device_size_in_bytes() // len(sharding.device_set)
    except Exception:  # noqa: BLE001 - not every backend / array kind says
        return math.prod(sharding.shard_shape(x.shape)) * x.dtype.itemsize


def _live_by_device() -> dict:
    """Every live array under each device that holds a shard of it, with
    that shard's bytes (:func:`tree_chip_bytes`' rule):
    ``{device: [(array, bytes)]}``. Shapes and shardings only: no wait."""
    import jax

    out: dict = {}
    for a in jax.live_arrays():
        nbytes = _shard_bytes(a)
        for d in a.sharding.device_set:
            out.setdefault(d, []).append((a, nbytes))
    return out


class LiveArraysAllocator:
    """Stands in for ``memory_stats()`` where a backend keeps none (the
    CPU: tests, rehearsals) — ``LEDGER.allocator = LiveArraysAllocator()``:
    a device's bytes in use are the per-chip sum of ``jax.live_arrays()``,
    its live peak their running maximum over the calls, and there is no
    temp. A walk of the live arrays a call: not for a hot path."""

    def __init__(self):
        self._peaks: dict = {}

    def __call__(self) -> list:
        import jax

        live = _live_by_device()
        out = []
        for d in jax.local_devices():
            in_use = sum(n for _a, n in live.get(d, ()))
            peak = self._peaks[d] = max(self._peaks.get(d, 0), in_use)
            out.append({"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                        "peak_bytes_reserved": 0})
        return out


class DeviceMemoryLedger:
    """Named device-resident allocations: ``set(owner, name, nbytes)`` /
    ``release(owner, name)``, live bytes per owner on
    ``otpu_device_bytes{owner=}``, a running peak, per-fit peaks via
    :meth:`watermark`, and the account against the runtime's allocator
    (:meth:`mark`: marks, the high-water interval, the census). The bytes
    are PER CHIP: a sharded array counts by what its
    shards take on the fullest device (:func:`tree_chip_bytes`), since a
    chip runs out of its own 16 GB and not of the mesh's sum; the array's
    global size is kept beside it (``global_nbytes=``, ``peak_global()``,
    the snapshot's ``*global_bytes``) and equals the per-chip figure for
    everything that lives on one device. Thread-safe; every mutation is a
    no-op under
    ``OTPU_PROF=0`` (release always applies, so a mid-process kill-
    switch flip cannot strand entries)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple[str, str], int] = {}
        self._owners: dict[str, int] = {}
        self._total = 0
        self._peak = 0
        # the same entries by their arrays' global size (see the class
        # docstring); an entry set without one counts its per-chip bytes
        self._global: dict[tuple[str, str], int] = {}
        self._total_global = 0
        self._peak_global = 0
        # the arrays an entry was handed, weakly (the census names a live
        # array by them), and where an owner trades its arrays for new ones
        # every step, a weak reference to the function that says which it
        # holds now: neither keeps an array alive
        self._held: dict[tuple[str, str], list] = {}
        self._held_now: dict[tuple[str, str], object] = {}
        self._watermarks: dict[int, "DeviceMemoryLedger._Watermark"] = {}
        self._wm_seq = 0
        # GC-finalizer inbox: weakref.finalize callbacks run
        # synchronously on whatever thread triggered cyclic GC — which
        # can be a thread ALREADY inside this ledger's (non-reentrant)
        # lock, since the methods allocate while holding it. Finalizers
        # therefore only append here (deque.append is atomic, no lock)
        # and every ledger operation drains the inbox at lock entry.
        self._pending: collections.deque = collections.deque()
        #: where a mark reads the allocator: a function -> one stats dict a
        #: local device, or None (then no mark is taken). The tests' scripted
        #: allocators and :class:`LiveArraysAllocator` go here.
        self.allocator = _device_stats
        self._reset_marks_locked()

    def _reset_marks_locked(self) -> None:
        self._marks = collections.deque(maxlen=MARKS_PER_FIT)
        self._marks_last: list = []
        self._fit_seq = 0
        self._mark_seq = 0
        self._last_mark: dict | None = None
        # (bytes in use, live peak, temp peak) a device at the last mark
        self._prev_stats: list = []
        self._high_since_mark = self._total
        self._high_water: dict | None = None
        self._high_water_temp: dict | None = None
        self._census: dict | None = None
        self._census_peak = 0
        self.censuses_taken = 0

    # ------------------------------------------- finalizer-safe deferral
    def defer_release(self, owner: str, name: str) -> None:
        """Release an entry from a GC-finalizer context: lock-free
        enqueue, applied by the next ledger operation."""
        self._pending.append(("release", owner, name))

    def defer_watermark_close(self, key: int) -> None:
        self._pending.append(("wm", key, None))

    def _drain_pending_locked(self) -> None:
        touched: set[str] = set()
        while self._pending:
            try:
                kind, a, b = self._pending.popleft()
            except IndexError:
                break
            if kind == "release":
                if self._forget_locked((a, b)) is not None:
                    touched.add(a)
            else:
                self._watermarks.pop(a, None)
        for owner in touched:
            _M_DEVICE_BYTES.set(self._owners[owner], owner=owner)

    def _forget_locked(self, key: tuple[str, str]) -> int | None:
        """Drop one entry with everything kept beside it; -> its bytes."""
        prev = self._entries.pop(key, None)
        if prev is not None:
            self._total -= prev
            self._total_global -= self._global.pop(key, prev)
            self._owners[key[0]] -= prev
            self._held.pop(key, None)
            self._held_now.pop(key, None)
        return prev

    class _Watermark:
        """Max ledger total observed since creation (a fit's HBM peak)."""

        def __init__(self, ledger: "DeviceMemoryLedger", key: int,
                     start: int):
            self._ledger = ledger
            self._key = key
            self.high = start

        def peak(self) -> int:
            return self.high

        def close(self) -> int:
            with self._ledger._lock:
                self._ledger._watermarks.pop(self._key, None)
            return self.high

    def watermark(self) -> "DeviceMemoryLedger._Watermark":
        with self._lock:
            self._drain_pending_locked()
            self._wm_seq += 1
            wm = self._Watermark(self, self._wm_seq, self._total)
            self._watermarks[self._wm_seq] = wm
            return wm

    # -------------------------------------------------------- mutations
    # The gauge writes happen INSIDE the ledger lock: published outside
    # it, two racing mutations of one owner could land their .set calls
    # out of order and pin phantom bytes on the gauge the fleet digest
    # (and the ROADMAP-3 autoscaler) reads until the owner next moves.
    # Lock order is ledger -> metric; nothing takes them the other way.
    def set(self, owner: str, name: str, nbytes: int,
            global_nbytes: int | None = None, *, arrays=None,
            extend: bool = False, now=None) -> None:
        """``arrays``: the pytree the bytes were counted from — the entry
        keeps weak references to its leaves, by which a census knows them
        from strangers of the same shape (``extend=True`` adds them to
        those it has; else they take their place, and an entry set from a
        byte count alone holds none). ``now``: for an owner that trades
        its arrays for new ones at every step (a donated state), a
        function -> the pytree it holds at this moment, asked at a census
        only and itself held weakly: it lives with its owner's frame."""
        if not prof_enabled():
            return
        import weakref

        import jax

        nbytes = max(int(nbytes), 0)
        global_nbytes = (nbytes if global_nbytes is None
                         else max(int(global_nbytes), 0))
        refs = [weakref.ref(x) for x in jax.tree.leaves(arrays)
                if isinstance(x, jax.Array)]
        with self._lock:
            self._drain_pending_locked()
            key = (owner, name)
            prev = self._entries.get(key, 0)
            self._total += nbytes - prev
            self._total_global += global_nbytes - self._global.get(key, prev)
            self._entries[key] = nbytes
            self._global[key] = global_nbytes
            if extend:
                self._held.setdefault(key, []).extend(refs)
            else:
                self._held[key] = refs
            if now is not None:
                self._held_now[key] = weakref.ref(now)
            else:
                self._held_now.pop(key, None)
            self._peak = max(self._peak, self._total)
            self._peak_global = max(self._peak_global, self._total_global)
            self._high_since_mark = max(self._high_since_mark, self._total)
            for wm in self._watermarks.values():
                wm.high = max(wm.high, self._total)
            self._owners[owner] = self._owners.get(owner, 0) + nbytes - prev
            _M_DEVICE_BYTES.set(self._owners[owner], owner=owner)

    def release(self, owner: str, name: str) -> None:
        with self._lock:
            self._drain_pending_locked()
            if self._forget_locked((owner, name)) is not None:
                _M_DEVICE_BYTES.set(self._owners[owner], owner=owner)

    # ------------------------------------------------------------- views
    def get(self, owner: str, name: str, *,
            global_size: bool = False) -> int | None:
        with self._lock:
            self._drain_pending_locked()
            return (self._global if global_size
                    else self._entries).get((owner, name))

    def owner_bytes(self) -> dict[str, int]:
        out: dict[str, int] = {}
        with self._lock:
            self._drain_pending_locked()
            for (owner, _name), v in self._entries.items():
                out[owner] = out.get(owner, 0) + v
        return dict(sorted(out.items()))

    def total(self) -> int:
        with self._lock:
            self._drain_pending_locked()
            return self._total

    def peak(self) -> int:
        with self._lock:
            return self._peak

    def peak_global(self) -> int:
        """High-water mark of the entries' GLOBAL sizes: what ``peak()``
        read before a sharded array counted per chip."""
        with self._lock:
            return self._peak_global

    def snapshot(self, max_entries: int = 64) -> dict:
        """The ledger table (flight bundles, reports, captures): per-
        owner totals plus the largest entries by name — an OOM-adjacent
        post-mortem finally names the tenant."""
        with self._lock:
            self._drain_pending_locked()
            # ONE lock hold for entries + owners + total: a snapshot
            # racing mutators must stay internally consistent (owner
            # sums == total == entry sums), or a post-mortem reader
            # chases phantom leaks
            entries = sorted(
                ({"owner": o, "name": n, "bytes": v,
                  "global_bytes": self._global.get((o, n), v)}
                 for (o, n), v in self._entries.items()),
                key=lambda e: -e["bytes"])
            owners: dict[str, int] = {}
            for (owner, _name), v in self._entries.items():
                owners[owner] = owners.get(owner, 0) + v
            total, peak = self._total, self._peak
            total_global, peak_global = (self._total_global,
                                         self._peak_global)
            # the account against the allocator: the marks of the last
            # finished fit and of the current one, oldest first, the
            # interval that set each of the allocator's two peaks, and the
            # last census (all None / empty where no mark was taken)
            marks = [dict(m) for m in self._marks_last]
            marks += [dict(m) for m in self._marks]
            high_water, high_water_temp, census = (
                self._high_water, self._high_water_temp, self._census)
            censuses_taken = self.censuses_taken
        dropped = max(len(entries) - max_entries, 0)
        out = {
            "prof_schema": PROF_SCHEMA_VERSION,
            "owners": dict(sorted(owners.items())),
            "total_bytes": total,
            "peak_bytes": peak,
            "total_global_bytes": total_global,
            "peak_global_bytes": peak_global,
            "entries": entries[:max_entries],
            "marks": marks,
            "high_water": high_water,
            "high_water_temp": high_water_temp,
            "census": census,
            "censuses_taken": censuses_taken,
        }
        if dropped:
            out["entries_truncated"] = dropped
        return out

    # ------------------------------------------- the account (HBM marks)
    def mark(self, name: str, *, first: bool = False) -> dict | None:
        """Read the allocator where the program stands (``name``: the span
        it has just closed) and account for the interval since the previous
        mark. One record: the fullest device's ``bytes_in_use``,
        ``peak_bytes_in_use`` (live buffers' high-water mark) and
        ``peak_bytes_reserved`` (program temp's), the ledger's total and
        the highest total since the previous mark. Where a peak rose in the
        interval, the interval is kept as the one that set it
        (``high_water`` / ``high_water_temp``: ``span`` this mark's name,
        ``since`` the previous one's). ``first=True`` opens a
        fit: the marks so far become the last fit's. No mark where the
        allocator tells nothing (``memory_stats()`` is None on the CPU).
        NEVER waits for the device; rides the spans (off with
        ``OTPU_OBS=0``) and the ``OTPU_PROF`` switch; diagnostics only, so
        it never raises."""
        if not (_trace.enabled() and prof_enabled()):
            return None
        try:
            stats = self.allocator()
            if stats is None:       # a backend whose allocator tells nothing
                return None
            stats = [(int(st.get("bytes_in_use", 0)),
                      int(st.get("peak_bytes_in_use", 0)),
                      int(st.get("peak_bytes_reserved", 0))) for st in stats]
            # the fullest device by the harness's rule (both peaks, the
            # later device on a tie)
            dev = max(range(len(stats)),
                      key=lambda i: (stats[i][1] + stats[i][2], i))
            in_use, peak, temp = stats[dev]
            with self._lock:
                self._drain_pending_locked()
                if first and self._marks:
                    self._marks_last = list(self._marks)
                    self._marks.clear()
                    self._fit_seq += 1
                before = self._last_mark
                in_use_a, peak_a, temp_a = (
                    self._prev_stats[dev] if dev < len(self._prev_stats)
                    else (0, 0, 0))
                self._mark_seq += 1
                rec = {"n": self._mark_seq, "name": name,
                       "fit": self._fit_seq, "t_ns": time.perf_counter_ns(),
                       "device": dev, "bytes_in_use": in_use,
                       "peak_bytes_in_use": peak,
                       "peak_bytes_reserved": temp,
                       "ledger_bytes": self._total,
                       "ledger_high_bytes": max(self._high_since_mark,
                                                self._total)}
                self._high_since_mark = self._total
                if peak > peak_a:
                    # named at the fuller of the interval's two marks;
                    # what the peak stood above both lived inside the span
                    after = in_use >= in_use_a or before is None
                    named = (rec if after else before)["ledger_bytes"]
                    fuller = max(in_use, in_use_a)
                    self._high_water = {
                        "mark": rec["n"], "span": name,
                        "since": before["name"] if before else None,
                        "fit": rec["fit"],
                        "device": dev, "peak_bytes_in_use": peak,
                        "rise_bytes": peak - peak_a,
                        "bytes_in_use_before": in_use_a,
                        "bytes_in_use_after": in_use,
                        "fuller_mark": "after" if after else "before",
                        "named_bytes": named,
                        "unnamed_bytes": fuller - named,
                        "transient_bytes": peak - fuller,
                        "ledger_high_bytes": rec["ledger_high_bytes"]}
                if temp > temp_a:
                    self._high_water_temp = {
                        "mark": rec["n"], "span": name,
                        "since": before["name"] if before else None,
                        "fit": rec["fit"],
                        "device": dev, "peak_bytes_reserved": temp,
                        "rise_bytes": temp - temp_a}
                self._prev_stats = stats
                self._last_mark = rec
                self._marks.append(rec)
                census_due = peak - self._census_peak > CENSUS_RISE_BYTES
                if census_due:
                    self._census_peak = peak
            if census_due:
                self._take_census(rec)
            _trace.instant("hbm_mark", span=name, **{
                k: rec[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "peak_bytes_reserved", "ledger_bytes",
                                    "ledger_high_bytes")})
            return rec
        except Exception as e:  # noqa: BLE001 - the account is best-effort
            log.debug("prof: hbm mark %r failed (%s: %s)", name,
                      type(e).__name__, e)
            return None

    def _take_census(self, rec: dict) -> None:
        """Walk ``jax.live_arrays()`` once and account for the mark's
        device: each array by its shard's bytes there, under the owner
        whose entry was handed THAT array (``unnamed`` otherwise), grouped
        by (owner, dtype, shard shape); what the allocator holds beyond
        them (in-flight outputs, buffers a donated call has yet to hand
        back) is ``runtime_held_bytes``."""
        import jax

        device = jax.local_devices()[rec["device"]]
        holders: dict[int, tuple] = {}      # id -> (array, owner): alive
        with self._lock:
            for (owner, _name), refs in self._held.items():
                for ref in refs:
                    x = ref()
                    if x is not None:
                        holders[id(x)] = (x, owner)
            asked = [(owner, ref()) for (owner, _name), ref
                     in self._held_now.items()]
        for owner, fn in asked:
            if fn is not None:
                for x in jax.tree.leaves(fn()):
                    holders[id(x)] = (x, owner)
        owners: dict[str, int] = {}
        groups: dict[tuple, list] = {}
        live = n_arrays = 0
        for a, nbytes in _live_by_device().get(device, ()):
            held = holders.get(id(a))
            owner = held[1] if held and held[0] is a else "unnamed"
            owners[owner] = owners.get(owner, 0) + nbytes
            shape = tuple(a.sharding.shard_shape(a.shape))
            g = groups.setdefault((owner, str(a.dtype), shape), [0, 0])
            g[0] += 1
            g[1] += nbytes
            live += nbytes
            n_arrays += 1
        ranked = sorted(groups.items(), key=lambda kv: -kv[1][1])
        census = {
            "mark": rec["n"], "span": rec["name"], "fit": rec["fit"],
            "device": rec["device"], "bytes_in_use": rec["bytes_in_use"],
            "live_bytes": live, "arrays": n_arrays,
            "runtime_held_bytes": rec["bytes_in_use"] - live,
            "owners": dict(sorted(owners.items())),
            "groups": [{"owner": o, "dtype": dt, "shape": list(sh),
                        "count": c, "bytes": b}
                       for (o, dt, sh), (c, b) in ranked[:CENSUS_GROUPS]],
            "groups_dropped": max(len(ranked) - CENSUS_GROUPS, 0),
        }
        with self._lock:
            self._census = census
            self.censuses_taken += 1

    def clear(self) -> None:
        """Tests only: forget every entry (gauges re-zero per owner)."""
        with self._lock:
            self._drain_pending_locked()
            owners = {o for (o, _n) in self._entries}
            self._entries.clear()
            self._global.clear()
            self._owners.clear()
            self._held.clear()
            self._held_now.clear()
            self._total = self._total_global = 0
            self._peak = self._peak_global = 0
            self._reset_marks_locked()
            for o in owners:
                _M_DEVICE_BYTES.set(0, owner=o)


#: the process-wide ledger every subsystem registers into
LEDGER = DeviceMemoryLedger()


class _LedgerGuard:
    """Frame-scoped release guard (see :func:`ledger_guard`)."""

    __slots__ = ("__weakref__", "finalizer")


def ledger_guard(owner: str, name: str) -> _LedgerGuard:
    """An object whose death releases the named ledger entry — bind it
    to the owning stack frame so an exception path cannot strand the
    entry (release is idempotent: an explicit release first makes the
    guard's firing a no-op). ``guard.finalizer.detach()`` hands
    ownership elsewhere (e.g. to a model's own finalizer) when the
    happy path wants the entry to outlive the frame. The finalizer body
    is the LOCK-FREE deferred release: cyclic GC may run it on a thread
    already holding the ledger lock."""
    import weakref

    g = _LedgerGuard()
    g.finalizer = weakref.finalize(g, LEDGER.defer_release, owner, name)
    return g


def ledger_release_on_gc(owner: str, name: str) -> None:
    """Finalizer-safe release for ``weakref.finalize`` callbacks: only
    a lock-free enqueue (see ``DeviceMemoryLedger.defer_release``) —
    a finalizer that took the ledger lock could self-deadlock the
    thread whose in-lock allocation triggered the GC pass."""
    LEDGER.defer_release(owner, name)


def tree_device_bytes(tree) -> int:
    """Total ``nbytes`` across a pytree's array leaves: their GLOBAL size
    (codec-encoded dict leaves count as stored)."""
    import jax

    return int(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree)))


def tree_chip_bytes(tree) -> int:
    """What a pytree's array leaves take on ONE chip — the ledger's sizing
    rule. A jax array counts by the shard one device holds of it, as the
    device lays it out (:func:`_shard_bytes`); anything else counts its
    ``nbytes`` as before."""
    import jax

    return int(sum(_shard_bytes(x) if isinstance(x, jax.Array)
                   else getattr(x, "nbytes", 0)
                   for x in jax.tree.leaves(tree)))


def ledger_set_tree(owner: str, name: str, tree, *, now=None) -> None:
    """One entry for a pytree of device arrays: per chip, with its global
    size beside it, and the arrays themselves known to the entry (weakly:
    see ``DeviceMemoryLedger.set`` for ``now``)."""
    LEDGER.set(owner, name, tree_chip_bytes(tree), tree_device_bytes(tree),
               arrays=tree, now=now)


def ledger_set(owner: str, name: str, nbytes: int,
               global_nbytes: int | None = None, *, arrays=None,
               extend: bool = False) -> None:
    LEDGER.set(owner, name, nbytes, global_nbytes, arrays=arrays,
               extend=extend)


def ledger_release(owner: str, name: str) -> None:
    LEDGER.release(owner, name)


_OWNED_SEQ = itertools.count()


def ledger_set_owned(owner: str, obj, tree) -> None:
    """An entry of its own for ``tree`` that goes when ``obj`` dies (a
    table and its arrays): set here, released by ``obj``'s finalizer."""
    if not prof_enabled():
        return
    import weakref

    name = f"{type(obj).__name__}-{next(_OWNED_SEQ)}"
    ledger_set_tree(owner, name, tree)
    weakref.finalize(obj, LEDGER.defer_release, owner, name)


def hbm_mark(name: str, *, first: bool = False) -> None:
    """One mark of the device-memory ledger (``DeviceMemoryLedger.mark``):
    what a span built with ``hbm=True`` calls as it closes, and what a site
    without a span of its own calls by hand."""
    LEDGER.mark(name, first=first)


_trace._hbm_mark = hbm_mark


def attach_fit_report(report, acc: GoodputAccountant | None, *,
                      encode_s: float | None = None,
                      cache_key: str | None = None) -> None:
    """Fit-end chokepoint: freeze the accountant, attach the ``goodput``
    and ``device_memory`` sections to the RunReport (absent — not null —
    under the kill-switch, so a PR-11 consumer sees the PR-11 dict).
    ``cache_key`` names the fit's own ``cache_chunks`` ledger entry so
    the bench can cross-check it against the legacy ``cache_bytes``
    stage key without ambiguity from other live caches: both are the
    cache's GLOBAL size; ``cache_entry_chip_bytes`` is what one chip
    holds of it (the same number off a mesh)."""
    if acc is None:
        return
    result = acc.finish(encode_s=encode_s)
    dm = LEDGER.snapshot()
    dm["peak_bytes_fit"] = result["peak_device_bytes"]
    if cache_key is not None:
        dm["cache_entry_bytes"] = LEDGER.get("cache_chunks", cache_key,
                                             global_size=True)
        dm["cache_entry_chip_bytes"] = LEDGER.get("cache_chunks", cache_key)
    if report is not None:
        report.goodput = result
        report.device_memory = dm
    end_fit(acc)


# ========================================================== deep capture
class CaptureDisabledError(RuntimeError):
    """Deep capture refused: the prof plane is off (``OTPU_PROF=0``)."""


class CaptureBusyError(RuntimeError):
    """A deep capture is already running — captures are serialized (one
    ``jax.profiler`` session at a time; the endpoint answers 409)."""


class CaptureRateLimitedError(RuntimeError):
    """Inside the ``OTPU_PROF_RATE_S`` window since the last capture
    (the endpoint answers 429)."""


_capture_lock = threading.Lock()
_rate_lock = threading.Lock()
_last_capture = 0.0            # monotonic; 0 = never


def reset_rate_limit() -> None:
    """Tests: forget the last capture time."""
    global _last_capture
    with _rate_lock:
        _last_capture = 0.0


def _claim_rate_slot() -> tuple[float, float]:
    """Claim the rate slot BEFORE the (slow) capture — two concurrent
    requests produce one capture; returns ``(previous stamp, claimed
    stamp)`` so a failed capture can hand the slot back."""
    global _last_capture
    min_gap = float(knobs.get_float("OTPU_PROF_RATE_S"))
    now = time.monotonic()
    with _rate_lock:
        if _last_capture and now - _last_capture < min_gap:
            _M_CAPTURES.inc(1, outcome="rate_limited")
            raise CaptureRateLimitedError(
                f"deep capture rate-limited: last capture "
                f"{now - _last_capture:.1f}s ago "
                f"(OTPU_PROF_RATE_S={min_gap})")
        prev, _last_capture = _last_capture, now
    return prev, now


def _release_rate_slot(prev: float, claimed_at: float) -> None:
    global _last_capture
    with _rate_lock:
        if _last_capture == claimed_at:
            _last_capture = prev


@contextlib.contextmanager
def _capture_session():
    """The shared serialize + rate-slot + outcome accounting EVERY deep
    capture runs under (one definition, so :func:`capture` and
    :func:`trace_capture` cannot drift): non-blocking lock → busy
    (409-class), rate window → rate_limited (429-class), a failing
    capture hands its claimed slot back and ticks ``error``, a clean
    one ticks ``ok``. The body owns only the artifact work."""
    if not _capture_lock.acquire(blocking=False):
        _M_CAPTURES.inc(1, outcome="busy")
        raise CaptureBusyError(
            "a deep capture is already running (captures serialize — "
            "one jax.profiler session at a time)")
    try:
        prev, claimed_at = _claim_rate_slot()
        try:
            yield
        except BaseException:
            # one transiently-failed capture must not silence the
            # whole rate window (the flight recorder's convention)
            _release_rate_slot(prev, claimed_at)
            _M_CAPTURES.inc(1, outcome="error")
            raise
        _M_CAPTURES.inc(1, outcome="ok")
    finally:
        _capture_lock.release()


def capture_snapshot(reason: str, duration_ms: float | None = None,
                     **extra) -> dict:
    """The JSON half of a deep capture: the last goodput decomposition,
    the ledger table with its account against the allocator (marks,
    high-water interval, census), the full registry and the resolved knob
    table — everything a profile needs for context."""
    snap = {
        "prof_schema": PROF_SCHEMA_VERSION,
        "written_at": time.time(),
        "pid": os.getpid(),
        "reason": reason,
        "duration_ms": duration_ms,
        "goodput": last_goodput(),
        "ledger": LEDGER.snapshot(),
        "registry": REGISTRY.snapshot(),
        "knobs": knobs.resolved(),
    }
    if extra:
        snap["extra"] = extra
    return snap


def _jax_trace(out_dir: str):
    """The profiler context, guarded: a jax build without a working
    profiler must degrade the capture to snapshot-only, not kill it."""
    try:
        import jax

        return jax.profiler.trace(out_dir)
    except Exception as e:  # noqa: BLE001 - profiler is best-effort
        log.warning("prof: jax.profiler unavailable (%s: %s); capture "
                    "carries the snapshot only", type(e).__name__, e)
        return None


def capture(duration_ms: float | None = None, *, reason: str = "manual",
            body=None) -> dict:
    """One serialized, rate-limited deep capture into an atomic artifact
    dir. ``duration_ms`` holds the jax profiler open that long (clamped
    to ``OTPU_PROF_MAX_MS``) — the serving shape, capturing whatever the
    process runs meanwhile; ``body`` (a callable) is traced instead when
    given (the tool shape). Returns ``{"path", "reason", "duration_ms",
    "snapshot"}``."""
    if not prof_enabled():
        raise CaptureDisabledError(
            "deep capture disabled (OTPU_PROF=0)")
    with _capture_session():
        max_ms = float(knobs.get_float("OTPU_PROF_MAX_MS"))
        if duration_ms is not None:
            duration_ms = min(max(float(duration_ms), 0.0), max_ms)
        directory = knobs.get_str("OTPU_PROF_DIR")
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in reason)[:48]
        final = os.path.join(directory,
                             f"capture-{time.time_ns()}-{safe}")
        tmp = f"{final}.tmp-{os.getpid()}"
        try:
            os.makedirs(os.path.join(tmp, "jax_trace"), exist_ok=True)
            _trace.instant("profile_capture", reason=reason,
                           duration_ms=duration_ms)
            traced_err = None
            ctx = _jax_trace(os.path.join(tmp, "jax_trace"))
            try:
                if ctx is not None:
                    ctx.__enter__()
                try:
                    if body is not None:
                        body()
                    elif duration_ms:
                        time.sleep(duration_ms / 1e3)
                finally:
                    if ctx is not None:
                        ctx.__exit__(None, None, None)
            except Exception as e:  # noqa: BLE001 - snapshot still lands
                traced_err = f"{type(e).__name__}: {e}"
            snap = capture_snapshot(reason, duration_ms)
            if traced_err:
                snap["jax_trace_error"] = traced_err
            with open(os.path.join(tmp, "snapshot.json"), "w") as f:
                json.dump(snap, f, default=str)
            os.rename(tmp, final)   # atomic publish: never a torn capture
        except BaseException:
            # a failed write must leave no .tmp litter retention never
            # prunes; the session hands the rate slot back
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
            raise
        return {"path": final, "reason": reason,
                "duration_ms": duration_ms, "snapshot": snap}


def _merge_move(src: str, dst: str) -> None:
    """Move a completed capture tree into place: plain rename when the
    destination is fresh; merge dirs recursively otherwise (files
    overwrite via ``os.replace`` — e.g. a repeat run's snapshot.json)."""
    if not os.path.exists(dst):
        os.rename(src, dst)
        return
    if os.path.isdir(src) and os.path.isdir(dst):
        for name in os.listdir(src):
            _merge_move(os.path.join(src, name), os.path.join(dst, name))
        os.rmdir(src)
    else:
        os.replace(src, dst)


@contextlib.contextmanager
def trace_capture(log_dir: str):
    """The ``utils.profiling.profile_trace`` back end: the same
    serialized + rate-limited capture machinery, writing into the
    CALLER's directory atomically (trace into a ``.tmp`` sibling,
    rename/merge on exit) and dropping a ``snapshot.json`` beside the
    profile. Under ``OTPU_PROF=0`` this is a bare ``jax.profiler.trace``
    — the pre-prof behavior, bitwise."""
    import jax

    if not prof_enabled():
        with jax.profiler.trace(log_dir):
            yield
        return
    body_err: BaseException | None = None
    with _capture_session():
        tmp = f"{log_dir.rstrip(os.sep)}.tmp-{os.getpid()}"
        try:
            os.makedirs(tmp, exist_ok=True)
            _trace.instant("profile_capture", reason="profile_trace")
            try:
                with jax.profiler.trace(tmp):
                    yield
            except BaseException as e:  # noqa: BLE001 - re-raised below
                # the profiler's __exit__ already stopped and wrote the
                # trace — a failing body is the capture you MOST want a
                # profile of, so PUBLISH the artifact (error noted in
                # the snapshot), then re-raise the body's exception
                # AFTER the session closed clean (outcome stays ok)
                body_err = e
            snap = capture_snapshot("profile_trace")
            if body_err is not None:
                snap["body_error"] = (f"{type(body_err).__name__}: "
                                      f"{body_err}")
            with open(os.path.join(tmp, "snapshot.json"), "w") as f:
                json.dump(snap, f, default=str)
            # publish: one rename when the caller's dir is fresh;
            # repeat runs into the SAME dir merge recursively (jax
            # nests plugins/profile/<ts>/ — a flat child replace would
            # ENOTEMPTY on the shared plugins/ level). Either way
            # nothing lands until the capture finished.
            _merge_move(tmp, log_dir)
        except BaseException:
            # the CAPTURE itself failed (profiler refused, full disk,
            # unmovable dir): no artifact landed — leave no .tmp
            # litter; the session hands the rate slot back
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
            raise
    if body_err is not None:
        raise body_err
