"""AOT executable cache — compiled-once programs for the serving path.

``jax.jit`` caches compiled programs too, but per (function, shape) with
no eviction, no explicit warmup, and no visibility: a serving process
cannot ask "is this bucket compiled?", bound the memory a long-lived
ladder of models holds, or report compile time separately from request
latency. This cache makes the executable a first-class entry:

* built via the AOT path — ``jit(fn).lower(abstract_args).compile()`` —
  so a bucket can be compiled at WARMUP time from pure
  ``ShapeDtypeStruct``s (no example batch needed, no first-request
  compile spike);
* keyed explicitly on (model fingerprint, kind, bucket shape, dtype,
  sharding) by the caller (serve/context.py owns key construction);
* LRU-bounded (``max_entries``) — retired models' executables fall out
  instead of accumulating for the life of the process;
* counted: hits/misses/evictions/compile-seconds tick the process-wide
  ``utils.profiling`` serve aggregate, the source of the serving bench's
  ``bucket_hits``/``recompiles`` fields.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import OrderedDict
from concurrent.futures import Future
from typing import Any, Callable

from orange3_spark_tpu.obs import prof
from orange3_spark_tpu.utils.profiling import record_serve

_MISSING = object()
#: countless LRU placeholder for keys that own no executable (pad-path
#: buckets, failed builds); never returned as a build product
_PAD_MARKER = "pad-marker"


def _ledger_name(key) -> str:
    """Stable short ledger-entry name for one cache key (keys are long
    tuples carrying fingerprints/shardings — the crc names the entry,
    the bytes are what the post-mortem reads)."""
    return f"exe-{zlib.crc32(repr(key).encode()) & 0xFFFFFFFF:08x}"


def _entry_device_bytes(entry) -> int:
    """Best-effort device bytes of one cached build product: AOT
    executables report via ``memory_analysis()`` where the backend
    implements it (temp + output buffers — the serving-path residency);
    anything else counts 0 but still appears as a named tenant."""
    objs = entry if isinstance(entry, (tuple, list)) else (entry,)
    total = 0
    for obj in objs:
        ma = getattr(obj, "memory_analysis", None)
        if not callable(ma):
            continue
        try:
            m = ma()
            total += int(getattr(m, "temp_size_in_bytes", 0) or 0)
            total += int(getattr(m, "output_size_in_bytes", 0) or 0)
            total += int(getattr(m, "generated_code_size_in_bytes", 0)
                         or 0)
        except Exception:  # noqa: BLE001 - sizing is best-effort
            continue
    return total


def _build_resilient(key, build):
    """One AOT build with the resilience wrap: fault injection inside the
    attempt (so a retried attempt consumes the injected budget) and
    bounded transient-error retries around it. ``retry_call`` is a plain
    single attempt under the kill-switch."""
    from orange3_spark_tpu.resilience.faults import active_fault_spec
    from orange3_spark_tpu.resilience.retry import retry_call

    def attempt():
        spec = active_fault_spec()
        if spec is not None:
            spec.maybe_fail_aot_build(key)
        return build()

    return retry_call(attempt, cause="aot_build")


class ExecutableCache:
    """Thread-safe LRU of compiled executables (or any build product).

    ``get_or_build(key, build)`` returns the cached entry or runs
    ``build()`` — serialized PER KEY: two threads racing the same first
    request pay one XLA compile (the second waits on the first's future),
    while hits and builds for OTHER keys proceed concurrently. The lock
    only guards the bookkeeping dicts, never a multi-second compile —
    a cold model warming up cannot head-of-line-block an already-warmed
    model's 2 ms hits.

    ``on_evict(key)`` (optional) fires outside the lock for every entry
    the LRU drops — the owning context uses it to release per-model /
    per-graph pins whose executables are all gone.

    Builds retry transient failures with bounded backoff
    (resilience/retry.py): a transient fault during a warmup compile costs
    a retry instead of blacklisting the model for the process lifetime.
    Fail-fast under ``OTPU_RESILIENCE=0``; the ``aot_build`` fault kind
    injects the transient failure deterministically for tests/bench.
    """

    def __init__(self, max_entries: int = 64,
                 on_evict: Callable[[Any], None] | None = None):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self.on_evict = on_evict
        self._lock = threading.RLock()
        self._entries: OrderedDict[Any, Any] = OrderedDict()
        self._building: dict[Any, Future] = {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> list:
        with self._lock:
            return list(self._entries)

    def get_or_build(self, key, build: Callable[[], Any]):
        with self._lock:
            entry = self._entries.get(key, _MISSING)
            if entry is not _MISSING and entry is not _PAD_MARKER:
                self._entries.move_to_end(key)
                record_serve(aot_hits=1)
                return entry
            # a _PAD_MARKER here is a failed build's LRU placeholder
            # (see _blacklist/mark): it keeps the eviction bookkeeping
            # honest but must NOT satisfy a build — a breaker's
            # half-open probe re-attempts the build through this path,
            # and the real entry then replaces the marker in place
            fut = self._building.get(key)
            if fut is None:
                fut = self._building[key] = Future()
                owner = True
            else:
                owner = False
        if not owner:
            # someone else is compiling this key: wait for IT alone; the
            # shared compile counts once (their miss), we count a hit
            entry = fut.result()
            record_serve(aot_hits=1)
            return entry
        t0 = time.perf_counter()
        try:
            entry = _build_resilient(key, build)
        except BaseException as e:
            with self._lock:
                del self._building[key]
            fut.set_exception(e)
            raise
        dt = time.perf_counter() - t0
        evicted = []
        # size OUTSIDE the lock (memory_analysis can walk HLO), but
        # ledger set/release INSIDE it: they must serialize with a
        # concurrent clear()/mark() eviction of the same key, or a
        # delayed set re-creates the entry for an executable the cache
        # no longer holds (lock order is always cache -> ledger)
        nbytes = _entry_device_bytes(entry)
        with self._lock:
            record_serve(aot_misses=1, aot_compile_s=dt)
            self._entries[key] = entry
            del self._building[key]
            while len(self._entries) > self.max_entries:
                evicted.append(self._entries.popitem(last=False)[0])
            if evicted:
                record_serve(aot_evictions=len(evicted))
            # device-memory ledger (obs/prof.py): every cached
            # executable is a named serve_executables tenant, released
            # when it leaves the cache (eviction, mark-forced eviction,
            # or clear)
            prof.ledger_set("serve_executables", _ledger_name(key),
                            nbytes)
            for k in evicted:
                prof.ledger_release("serve_executables", _ledger_name(k))
        fut.set_result(entry)
        if self.on_evict is not None:
            for k in evicted:
                self.on_evict(k)
        return entry

    def mark(self, key) -> None:
        """Insert a countless marker entry: pad-path buckets own no AOT
        executable (the model's internal jits hold the real compiles), but
        a marker gives them LRU presence so ``on_evict`` pruning covers
        pad-served models too. No aot hit/miss ticks — no compile happened
        here; evictions it forces still count (real entries may fall)."""
        evicted = []
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return
            self._entries[key] = _PAD_MARKER
            while len(self._entries) > self.max_entries:
                evicted.append(self._entries.popitem(last=False)[0])
            if evicted:
                record_serve(aot_evictions=len(evicted))
            for k in evicted:
                prof.ledger_release("serve_executables", _ledger_name(k))
        if self.on_evict is not None:
            for k in evicted:
                self.on_evict(k)

    def clear(self) -> None:
        with self._lock:
            dropped = list(self._entries)
            self._entries.clear()
            for k in dropped:
                prof.ledger_release("serve_executables", _ledger_name(k))
        if self.on_evict is not None:
            # same contract as LRU eviction: every dropped key fires, so
            # the owning context releases its per-model/per-graph pins
            # instead of holding them for the context's lifetime
            for k in dropped:
                self.on_evict(k)
