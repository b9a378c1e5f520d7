"""PipelinedExecutor — the chunk pipeline's measured overlap engine.

JAX dispatch is async, so a streaming fit gets double buffering "for free"
only if the host work (parse, encode, ``device_put`` enqueue) for chunk t+1
actually runs while the device executes step t. This module makes that
overlap a first-class, MEASURED property instead of a hoped-for one:

* a bounded daemon-thread producer runs ``prep`` over the item stream and
  hands results through a ``depth``-bounded queue (depth 2 = classic double
  buffering: one chunk on device, one staged);
* the producer's busy time (``prep_s``) and the consumer's blocked time
  (``wait_s``) are accumulated; their ratio is the overlap efficiency:

      overlap_pct = 100 * max(0, 1 - wait_s / prep_s)

  100% means every second of host prep was hidden behind device compute
  (the consumer never waited); 0% means the pipeline degenerated to serial
  (the consumer waited out every prep). The pipeline-fill wait for the
  first item counts against overlap — that prep is genuinely exposed.

Semantics preserved from the old ``io.streaming.prefetch_map`` (which now
delegates here): results are yielded in order; a producer exception
re-raises at the consuming ``next()``; closing the generator early stops
the worker. ``prep`` and the native parser both release the GIL, so the
worker genuinely overlaps even on a single-core host.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Iterator

from orange3_spark_tpu.obs import context as obs_context
from orange3_spark_tpu.obs import prof
from orange3_spark_tpu.obs.trace import stage
from orange3_spark_tpu.utils.dispatch import beat

_EOF = object()


@dataclasses.dataclass
class PipelineStats:
    """Counters for one pipelined stream (final once ``done`` is True)."""

    items: int = 0        # results yielded to the consumer
    prep_s: float = 0.0   # producer time inside prep (parse/pad/device_put)
    wait_s: float = 0.0   # consumer time blocked waiting on the queue
    wall_s: float = 0.0   # consumer wall from first wait to stream end
    # producer time spent ENCODING chunks for the compressed cache
    # (io/codec.py) — a subset of prep_s, attributed by the prep callback
    # itself so the cache-codec cost is visible next to parse/DMA
    encode_s: float = 0.0
    # transient source reads retried by the resilience layer
    # (resilience/retry.resilient_source threads this stats object in)
    retries: int = 0
    done: bool = False

    @property
    def overlap_pct(self) -> float:
        """Share of producer time hidden behind consumer compute, 0-100."""
        if self.prep_s <= 0.0:
            return 0.0
        return 100.0 * min(max(1.0 - self.wait_s / self.prep_s, 0.0), 1.0)

    def merge(self, other: "PipelineStats") -> "PipelineStats":
        """Fold another stream's counters in (multi-phase fits aggregate
        their per-phase pipelines into one fit-level overlap number)."""
        self.items += other.items
        self.prep_s += other.prep_s
        self.wait_s += other.wait_s
        self.wall_s += other.wall_s
        self.encode_s += other.encode_s
        self.retries += other.retries
        return self


class PipelinedExecutor:
    """Bounded background-thread prefetch with measured overlap.

    ``prep(item)`` runs on the worker thread — for the streaming fits it is
    encode (pad, label/dense narrowing, the categoricals' hash + bit-pack
    under the cache codec) + ``device_put``, the parse being the pull of the
    next item on the same thread — so chunk t+1 is ready and its DMA
    enqueued while the device steps on chunk t. ``depth`` bounds how far the producer runs
    ahead (double buffering at the default 2); ``depth=0`` still prefetches
    with a queue of one.

    Stats land on ``self.stats`` as the stream progresses and are recorded
    into the process-wide ``utils.profiling`` aggregate when the stream
    ends (``record=False`` opts out — e.g. microbenches that must not
    pollute a surrounding fit's numbers).
    """

    def __init__(self, prep: Callable, *, depth: int = 2,
                 name: str = "chunk-prefetch", record: bool = True):
        self.prep = prep
        self.depth = max(1, depth)
        self.name = name
        self.record = record
        self.stats = PipelineStats()

    def run(self, items: Iterator) -> Iterator:
        """Yield ``prep(item)`` for every item, in order, prefetched."""
        stats = self.stats
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        prep = self.prep
        # the consumer's trace context (the fit's run id) — the worker
        # thread adopts it so its "prefetch" spans carry the same trace
        # id as the fit/epoch/chunk spans they feed (obs/context.py)
        trace_ctx = obs_context.current_trace()

        def worker():
            with obs_context.adopt(trace_ctx):
                self._produce(iter(items), q, stop, prep, stats)

        t = threading.Thread(target=worker, daemon=True, name=self.name)
        t.start()
        t_start = time.perf_counter()
        try:
            while True:
                # the consumer's blocked time is one "input_wait" span a
                # result (the first of a stream is the pipeline fill);
                # wait_s is the sum of their durations
                with stage("input_wait", stats, "wait_s",
                           stats.items) as wait:
                    got = q.get()
                # goodput attribution (obs/prof.py): the consumer is the
                # fit's thread of control, so this wait IS input_wait —
                # fed live (not at stream end) so per-epoch bottleneck
                # classification sees intra-epoch waits
                prof.note_input_wait(wait.seconds)
                if (isinstance(got, tuple) and len(got) == 2
                        and got[0] is _EOF):
                    if got[1] is not None:
                        raise got[1]
                    return
                stats.items += 1
                yield got
        finally:
            stop.set()
            stats.wall_s = time.perf_counter() - t_start
            stats.done = True
            if self.record:
                from orange3_spark_tpu.utils.profiling import record_pipeline

                record_pipeline(stats)

    @staticmethod
    def _produce(it, q, stop, prep, stats) -> None:
        """The worker-thread body (runs under the adopted trace context)."""
        try:
            while True:
                # time the PULL too: the upstream iterator is where the
                # parse/rechunk work lives (prep is encode + h2d: pad, the
                # cache codec's narrowing and hash + bit-pack, device_put),
                # and both run on this thread — prep_s must carry the
                # whole host-side cost or overlap_pct overstates waits
                # (it is the sum of the "prefetch" spans, the pull that
                # finds the stream's end among them)
                with stage("prefetch", stats, "prep_s", stats.items):
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                    out = prep(item)
                beat()  # parse/DMA progress feeds the stall watchdog
                while not stop.is_set():
                    try:
                        q.put(out, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            payload = (_EOF, None)
        except BaseException as e:  # noqa: BLE001 - re-raised on consumer
            payload = (_EOF, e)
        while not stop.is_set():
            try:
                q.put(payload, timeout=0.1)
                return
            except queue.Full:
                continue


def prefetch_iter(prep: Callable, items: Iterator, *, depth: int = 2,
                  stats_into: PipelineStats | None = None) -> Iterator:
    """One-shot functional form: run ``items`` through a fresh
    ``PipelinedExecutor``; ``stats_into`` receives the stream's counters
    (merged) when it ends."""
    ex = PipelinedExecutor(prep, depth=depth)
    try:
        yield from ex.run(items)
    finally:
        if stats_into is not None:
            stats_into.merge(ex.stats)
