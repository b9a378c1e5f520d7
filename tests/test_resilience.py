"""Resilience subsystem (docs/resilience.md): fault injection, bounded
retries, dispatch watchdog, spill CRC, micro-batch deadlines, and
crash-resumable (SIGKILL-and-resume) streaming fits. The mitigation tests
here FAIL under ``OTPU_RESILIENCE=0`` by construction — the kill-switch
tests pin the legacy fail-fast ladder explicitly."""

import os
import pickle
import signal
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

from orange3_spark_tpu.io.codec import SpillCorruptionError
from orange3_spark_tpu.io.streaming import (
    DiskChunkCache,
    StreamingLinearEstimator,
    array_chunk_source,
)
from orange3_spark_tpu.resilience import (
    DispatchWedgedError,
    FaultSpec,
    RetryPolicy,
    TransientSourceError,
    inject_faults,
    resilience_enabled,
    resilient_source,
    retry_call,
)
from orange3_spark_tpu.utils.fault import StreamCheckpointer
from orange3_spark_tpu.utils.profiling import (
    reset_resilience_counters,
    resilience_counters,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Keep real backoff sleeps out of tier-1 (tests that pin the
    schedule use an injected fake clock instead), and start each test
    with a fresh dispatch breaker — a wedge in a NEIGHBORING test's
    budgeted sync would otherwise fast-fail this test's first guarded
    sync for the breaker's cooldown window (resilience/overload.py)."""
    from orange3_spark_tpu.resilience.overload import reset_wedge_breaker

    monkeypatch.setenv("OTPU_RETRY_BASE_S", "0.001")
    reset_resilience_counters()
    reset_wedge_breaker()
    yield
    reset_wedge_breaker()


def _data(n=2048, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal(d).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    return X, y


def _fit(session, src, **kw):
    params = dict(loss="logistic", epochs=4, step_size=0.1, chunk_rows=512)
    params.update({k: kw.pop(k) for k in list(kw)
                   if k in ("epochs", "checkpoint_every_epochs",
                            "replay_granularity")})
    return StreamingLinearEstimator(**params).fit_stream(
        src, n_features=4, session=session, **kw)


# ------------------------------------------------------------ fault spec
def test_fault_spec_grammar():
    spec = FaultSpec.parse(
        "source_io:chunk=2,fails=2;slow_source:every=3,delay_ms=1;"
        "wedge:at=2,hold_s=0.5;aot_build:fails=1;spill_corrupt:record=0")
    assert [c.kind for c in spec.clauses] == [
        "source_io", "slow_source", "wedge", "aot_build", "spill_corrupt"]
    assert spec.has_source_faults
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec.parse("explode:at=1")
    with pytest.raises(ValueError, match="malformed fault arg"):
        FaultSpec.parse("source_io:chunk")
    # seeded probabilistic targeting is deterministic (crc32, not hash())
    a = FaultSpec.parse("source_io:p=0.5,seed=7").clauses[0]
    b = FaultSpec.parse("source_io:p=0.5,seed=7").clauses[0]
    hits = [i for i in range(64) if a.targets(i)]
    assert hits == [i for i in range(64) if b.targets(i)]
    assert 8 < len(hits) < 56      # roughly half, both tails impossible


# ----------------------------------------------------------- retry policy
def test_retry_backoff_schedule_pinned():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.05, max_delay_s=0.3,
                    multiplier=2.0, jitter=0.0)
    assert [p.delay(i) for i in range(5)] == [0.05, 0.1, 0.2, 0.3, 0.3]
    # jitter: deterministic per (seed, retry_index), bounded by the knob
    j = RetryPolicy(base_delay_s=0.1, jitter=0.5, seed=3)
    d0 = j.delay(0)
    assert d0 == j.delay(0) and 0.1 <= d0 <= 0.15
    assert RetryPolicy(jitter=0.5, seed=4).delay(0) != d0


def test_retry_call_attempt_counts_fake_clock():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise TransientSourceError("blip")
        return "ok"

    pol = RetryPolicy(max_attempts=4, base_delay_s=0.05, max_delay_s=1.0,
                      multiplier=2.0, jitter=0.0)
    assert retry_call(flaky, cause="t", policy=pol,
                      sleep=slept.append) == "ok"
    assert calls["n"] == 3 and slept == [0.05, 0.1]   # exact schedule
    assert resilience_counters()["retries_by_cause"]["t"] == 2


def test_retry_call_exhausts_and_classifies():
    def always():
        raise TransientSourceError("down")

    pol = RetryPolicy(max_attempts=3, jitter=0.0)
    with pytest.raises(TransientSourceError):
        retry_call(always, cause="t", policy=pol, sleep=lambda s: None)
    assert resilience_counters()["retries"] == 2    # 3 attempts = 2 retries

    def fatal():
        raise ValueError("not transient")

    reset_resilience_counters()
    with pytest.raises(ValueError):
        retry_call(fatal, cause="t", policy=pol, sleep=lambda s: None)
    assert resilience_counters()["retries"] == 0    # no retry on non-IO

    def missing():                      # permanent OSError family: a
        raise FileNotFoundError("no.csv")  # mistyped path won't appear
        #                                    on retry 3 — fail fast

    with pytest.raises(FileNotFoundError):
        retry_call(missing, cause="t", policy=pol, sleep=lambda s: None)
    assert resilience_counters()["retries"] == 0


def test_retry_call_kill_switch_fail_fast(monkeypatch):
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    assert not resilience_enabled()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise TransientSourceError("blip")

    with pytest.raises(TransientSourceError):
        retry_call(flaky, cause="t", sleep=lambda s: None)
    assert calls["n"] == 1                          # single attempt


# -------------------------------------------------------- source retries
def test_transient_source_faults_absorbed_bitwise(session):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    ref = _fit(session, src)
    with inject_faults("source_io:chunk=2,fails=2"):
        m = _fit(session, src)
    # recovery must not change the numbers: bitwise, not just close
    np.testing.assert_array_equal(np.asarray(m.coef), np.asarray(ref.coef))
    res = resilience_counters()
    assert res["retries_by_cause"]["source"] == 2   # exactly the 2 fails
    assert res["faults_by_kind"]["source_io"] == 2


def test_transient_source_fault_fail_fast_with_kill_switch(
        session, monkeypatch):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    with inject_faults("source_io:chunk=2,fails=2"):
        with pytest.raises(TransientSourceError):
            _fit(session, src)


def test_fail_always_source_exhausts_bounded(session, monkeypatch):
    monkeypatch.setenv("OTPU_RETRY_ATTEMPTS", "3")
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    with inject_faults("source_io:chunk=1,fails=-1"):
        with pytest.raises(TransientSourceError):
            _fit(session, src)
    # bounded: max_attempts=3 -> exactly 2 retries, then surface
    assert resilience_counters()["retries_by_cause"]["source"] == 2


def test_straggler_chunks_absorbed_and_counted(session):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    ref = _fit(session, src)
    with inject_faults("slow_source:every=2,delay_ms=1"):
        m = _fit(session, src)
    np.testing.assert_array_equal(np.asarray(m.coef), np.asarray(ref.coef))
    assert resilience_counters()["faults_by_kind"]["slow_source"] >= 2
    assert resilience_counters()["retries"] == 0    # slowness != failure


def test_resilient_source_stats_thread_retries():
    from orange3_spark_tpu.exec.pipeline import PipelineStats

    stats = PipelineStats()

    def src():
        yield from ((np.zeros((4, 2), np.float32),) for _ in range(5))

    with inject_faults("source_io:chunk=3,fails=1"):
        wrapped = resilient_source(
            src, policy=RetryPolicy(jitter=0.0, base_delay_s=0.0),
            stats=stats, sleep=lambda s: None)
        assert len(list(wrapped())) == 5
    assert stats.retries == 1
    merged = PipelineStats().merge(stats)
    assert merged.retries == 1                      # merge carries them


# ------------------------------------------------------ dispatch watchdog
def test_wedged_dispatch_raises_typed_error(session, monkeypatch):
    monkeypatch.setenv("OTPU_DISPATCH_BUDGET_S", "0.2")
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    t0 = time.perf_counter()
    with inject_faults("wedge:at=1,hold_s=20"):
        with pytest.raises(DispatchWedgedError) as ei:
            _fit(session, src)
    # within the budget (not the 20 s hold), with the diagnostics payload
    assert time.perf_counter() - t0 < 10.0
    e = ei.value
    assert e.budget_s == pytest.approx(0.2)
    assert e.waited_s >= 0.2 and e.stage == "step"
    assert {"last_beat_age_s", "dispatches",
            "prefetch_items"} <= set(e.diagnostics)
    assert resilience_counters()["wedges"] == 1


def test_wedge_kill_switch_restores_unbounded_wait(session, monkeypatch):
    # OTPU_RESILIENCE=0: the same injected wedge (held finite so CI can't
    # hang) stalls the fit instead of raising — the legacy ladder
    monkeypatch.setenv("OTPU_DISPATCH_BUDGET_S", "0.1")
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    t0 = time.perf_counter()
    with inject_faults("wedge:at=1,hold_s=0.5"):
        m = _fit(session, src)          # no DispatchWedgedError
    assert m.n_steps_ == 16
    assert time.perf_counter() - t0 >= 0.5          # it really stalled


# ------------------------------------------------------------- spill CRC
def test_spill_v2_crc_roundtrip_and_flip(tmp_path):
    cache = DiskChunkCache(str(tmp_path), ((8, 3), (8,)), keep_file=True)
    rng = np.random.default_rng(0)
    recs = [(rng.standard_normal((8, 3)).astype(np.float32),
             rng.standard_normal(8).astype(np.float32)) for _ in range(3)]
    for i, r in enumerate(recs):
        cache.append(r, 8 - i)
    cache.finalize()
    for i, r in enumerate(recs):        # writer-side reads verify clean
        arrs, nv = cache.read(i)
        np.testing.assert_array_equal(np.asarray(arrs[0]), r[0])
        assert nv == 8 - i
    path = cache.path
    att = DiskChunkCache.attach(path)
    assert att._version == 2 and att.n_records == 3
    arrs, _ = att.read(1)
    np.testing.assert_array_equal(np.asarray(arrs[1]), recs[1][1])
    att.delete()
    # flip one payload byte of record 1 on disk -> descriptive error
    # naming the ordinal; record 0 stays readable
    with open(path, "r+b") as f:
        f.seek(cache._data_start + cache.record_bytes + cache._offsets[0])
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x40]))
    att = DiskChunkCache.attach(path)
    att.read(0)
    with pytest.raises(SpillCorruptionError, match="record 1 of 3"):
        att.read(1)
    assert resilience_counters()["crc_failures"] == 1
    # kill-switch: legacy decode-anything behavior
    os.environ["OTPU_RESILIENCE"] = "0"
    try:
        arrs, _ = att.read(1)           # garbage decodes silently
        assert arrs[0].shape == (8, 3)
    finally:
        os.environ.pop("OTPU_RESILIENCE")
    att.delete()
    cache.delete()


def test_spill_truncated_tail_refused(tmp_path):
    cache = DiskChunkCache(str(tmp_path), ((8, 3),), keep_file=True)
    for _ in range(2):
        cache.append((np.ones((8, 3), np.float32),), 8)
    cache.finalize()
    path = cache.path
    with open(path, "r+b") as f:
        f.truncate(cache._data_start + cache.record_bytes
                   + cache.record_bytes // 2)
    with pytest.raises(SpillCorruptionError, match="truncated"):
        DiskChunkCache.attach(path)
    cache.delete()


def test_spill_v1_and_v0_stay_readable(tmp_path):
    import json as _json
    import struct

    # synthesize a version-1 file byte for byte (the pre-CRC layout the
    # PR-4 writer emitted: u32 n_valid + 4 pad zeros, same offsets)
    arr = np.arange(24, dtype=np.float32).reshape(8, 3)
    header = _json.dumps({"version": 1, "shapes": [[8, 3]],
                          "dtypes": ["float32"]}).encode()
    head = b"OTPUSPL1" + struct.pack("<I", len(header)) + header
    head += b"\0" * (-len(head) % 8)
    v1 = tmp_path / "v1.otpu"
    with open(v1, "wb") as f:
        f.write(head + struct.pack("<Ixxxx", 7) + arr.tobytes())
    att = DiskChunkCache.attach(str(v1))
    assert att._version == 1
    arrs, nv = att.read(0)              # no CRC check on v1
    np.testing.assert_array_equal(np.asarray(arrs[0]), arr)
    assert nv == 7
    att.delete()
    # version 0: headerless flat f32, caller-supplied shapes
    v0 = tmp_path / "v0.otpu"
    with open(v0, "wb") as f:
        f.write(arr.tobytes())
    att = DiskChunkCache.attach(str(v0), shapes=((8, 3),))
    arrs, nv = att.read(0)
    np.testing.assert_array_equal(np.asarray(arrs[0]), arr)
    assert nv == 8
    att.delete()


def test_spill_corruption_injection_fails_replay(session, tmp_path):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with inject_faults("spill_corrupt:record=1,mode=flip"):
            with pytest.raises(SpillCorruptionError, match="record 1"):
                _fit(session, src, cache_device=True, cache_device_bytes=1,
                     cache_spill_dir=str(tmp_path))


def test_spill_truncate_injection_caught_at_finalize(session, tmp_path):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with inject_faults("spill_corrupt:record=2,mode=truncate"):
            with pytest.raises(SpillCorruptionError, match="truncated"):
                _fit(session, src, cache_device=True, cache_device_bytes=1,
                     cache_spill_dir=str(tmp_path))


# --------------------------------------------------- serving resilience
def test_executable_cache_build_retry_and_kill_switch(monkeypatch):
    from orange3_spark_tpu.resilience.faults import TransientBuildError
    from orange3_spark_tpu.serve.cache import ExecutableCache

    cache = ExecutableCache(max_entries=4)
    builds = {"n": 0}

    def build():
        builds["n"] += 1
        return "exe"

    with inject_faults("aot_build:fails=1"):
        assert cache.get_or_build(("k1",), build) == "exe"
    assert builds["n"] == 1             # injected fail preceded the build
    assert resilience_counters()["retries_by_cause"]["aot_build"] == 1
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    with inject_faults("aot_build:fails=1"):
        with pytest.raises(TransientBuildError):
            cache.get_or_build(("k2",), build)


def test_microbatch_future_deadline_on_wedged_dispatch():
    import threading

    from orange3_spark_tpu.serve.microbatch import (
        MicroBatcher, MicroBatchTimeoutError,
    )

    class StubRec:
        fingerprint = "f0"

    release = threading.Event()

    class StubCtx:
        def _dispatch(self, kind, rec, arrays, rows, meta):
            release.wait(10.0)          # a wedged device dispatch
            return np.zeros((rows,), np.float32)

    mb = MicroBatcher(StubCtx(), max_wait_ms=1.0, deadline_s=0.2)
    try:
        arrays = (np.zeros((4, 2), np.float32), None, None)
        fut = mb.submit("array", StubRec(), arrays, 4,
                        meta=(None, None, np.float32))
        assert fut is not None
        t0 = time.perf_counter()
        with pytest.raises(MicroBatchTimeoutError) as ei:
            fut.result()
        assert time.perf_counter() - t0 < 5.0       # deadline, not hang
        assert ei.value.group_key[0] == "array"     # names the group
        assert ei.value.group_key[1] == "f0"
        # an explicit caller timeout still works and still types the error
        with pytest.raises(MicroBatchTimeoutError):
            fut.result(timeout=0.05)
    finally:
        release.set()
        mb.close(timeout_s=2.0)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_microbatch_worker_death_mid_flight():
    """Kill the dispatch thread mid-flight: the in-queue request's future
    times out typed (never resolves), and later submits shed to direct
    dispatch instead of parking futures behind a dead worker."""
    import threading

    from orange3_spark_tpu.serve.microbatch import (
        MicroBatcher, MicroBatchTimeoutError,
    )

    class StubRec:
        fingerprint = "f1"

    hold = threading.Event()

    class StubCtx:
        def _dispatch(self, kind, rec, arrays, rows, meta):
            hold.wait(10.0)
            return np.zeros((rows,), np.float32)

    mb = MicroBatcher(StubCtx(), max_wait_ms=1.0, deadline_s=0.4)
    arrays = (np.zeros((2, 2), np.float32), None, None)
    f1 = mb.submit("array", StubRec(), arrays, 2,
                   meta=(None, None, np.float32))
    assert f1 is not None
    time.sleep(0.05)                    # worker is now inside _dispatch
    mb._q.put(object())                 # poison: kills the worker loop
    f2 = mb.submit("array", StubRec(), arrays, 2,
                   meta=(None, None, np.float32))
    hold.set()                          # f1 completes; worker then dies
    assert np.asarray(f1.result()).shape == (2,)
    if f2 is not None:                  # enqueued before the death: the
        with pytest.raises(MicroBatchTimeoutError):  # deadline saves it
            f2.result()
    for _ in range(100):                # thread death is asynchronous
        if not mb._thread.is_alive():
            break
        time.sleep(0.01)
    assert not mb._thread.is_alive()
    assert mb.submit("array", StubRec(), arrays, 2,
                     meta=(None, None, np.float32)) is None


# -------------------------------------------- crash-resumable fits
def test_checkpoint_every_epochs_cadence_and_kill_switch(
        session, tmp_path, monkeypatch):
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    saves = []

    class Rec(StreamCheckpointer):
        def save(self, step, state, meta=None):
            saves.append(step)
            super().save(step, state, meta)

    ck = Rec(str(tmp_path / "a.ckpt"), every_steps=10 ** 9)
    _fit(session, src, epochs=3, checkpoint_every_epochs=1,
         checkpointer=ck)
    assert saves == [4, 8, 12]          # every epoch boundary (spe=4)
    assert ck.load() == (0, None)       # deleted on success
    saves.clear()
    ck2 = Rec(str(tmp_path / "b.ckpt"), every_steps=10 ** 9)
    _fit(session, src, epochs=4, checkpoint_every_epochs=2,
         checkpointer=ck2, cache_device=True)
    assert saves == [8, 16]             # K=2 through the HBM replay path
    saves.clear()
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    ck3 = Rec(str(tmp_path / "c.ckpt"), every_steps=10 ** 9)
    _fit(session, src, epochs=3, checkpoint_every_epochs=1,
         checkpointer=ck3)
    assert saves == []                  # kill-switch: cadence inert


def test_epoch_checkpoint_resume_bitwise(session, tmp_path):
    """Crash at an epoch boundary snapshot -> the resumed fit replays the
    identical step sequence and lands bitwise on the uninterrupted fit."""
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    ref = _fit(session, src, epochs=4)
    ck = StreamCheckpointer(str(tmp_path / "r.ckpt"), every_steps=10 ** 9)
    served = {"n": 0}

    def crashing():
        for c in src():
            if served["n"] == 9:        # mid-epoch 3 (spe=4)
                raise RuntimeError("injected crash")
            served["n"] += 1
            yield c

    with pytest.raises(RuntimeError, match="injected crash"):
        _fit(session, crashing, epochs=4, checkpoint_every_epochs=1,
             checkpointer=ck)
    step, state = ck.load()
    assert step == 8 and state is not None      # last epoch boundary
    resumed = _fit(session, src, epochs=4, checkpoint_every_epochs=1,
                   checkpointer=ck)
    assert resumed.n_steps_ == ref.n_steps_
    np.testing.assert_array_equal(
        np.asarray(resumed.coef), np.asarray(ref.coef))


_SIGKILL_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
import numpy as np
from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.io.streaming import (
    StreamingLinearEstimator, array_chunk_source,
)
from orange3_spark_tpu.utils.fault import StreamCheckpointer

ckpt_path, out_path, slow_s = sys.argv[2], sys.argv[3], float(sys.argv[4])
rng = np.random.default_rng(0)
X = rng.standard_normal((2048, 4)).astype(np.float32)
y = (X @ rng.standard_normal(4).astype(np.float32) > 0).astype(np.float32)
base = array_chunk_source(X, y, chunk_rows=512)

def src():
    for c in base():
        time.sleep(slow_s)      # pace the fit so the parent can SIGKILL it
        yield c

ck = StreamCheckpointer(ckpt_path, every_steps=10 ** 9)
m = StreamingLinearEstimator(
    loss="logistic", epochs=8, step_size=0.1, chunk_rows=512,
    checkpoint_every_epochs=1,
).fit_stream(src, n_features=4, session=TpuSession.builder_get_or_create(),
             checkpointer=ck)
np.save(out_path, np.asarray(m.coef))
"""


def test_sigkill_mid_epoch_resumes_and_matches(session, tmp_path):
    """THE acceptance drill: a real subprocess fit is SIGKILLed mid-epoch;
    the restarted fit resumes from the latest epoch-boundary checkpoint
    and matches the uninterrupted fit's theta to <= 1e-6."""
    ckpt_path = str(tmp_path / "kill.ckpt")
    out_path = str(tmp_path / "coef.npy")
    env = dict(os.environ)
    env["PYTHONPATH"] = ""              # no site-injected plugin hangs
    env.pop("OTPU_RESILIENCE", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGKILL_CHILD, REPO, ckpt_path, out_path,
         "0.12"],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        # wait for a snapshot covering >= 2 epochs (step >= 8), then KILL
        deadline = time.monotonic() + 120
        step = 0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                pytest.fail("child finished before it could be killed — "
                            "raise slow_s")
            if os.path.exists(ckpt_path):
                try:
                    with open(ckpt_path, "rb") as f:
                        step = pickle.load(f)["step"]
                except Exception:  # noqa: BLE001 - racing the writer
                    step = 0
                if step >= 8:
                    break
            time.sleep(0.05)
        assert step >= 8, "no epoch-boundary snapshot appeared in time"
        proc.send_signal(signal.SIGKILL)
        assert proc.wait(timeout=60) == -signal.SIGKILL
    finally:
        if proc.poll() is None:
            proc.kill()
    assert not os.path.exists(out_path)     # it really died mid-fit
    # the snapshot survived the SIGKILL intact (atomic temp + rename) and
    # sits exactly on an epoch boundary (spe=4)
    step, state = StreamCheckpointer(ckpt_path).load()
    assert step >= 8 and step % 4 == 0 and state is not None
    # resume in-process with the same data/params; reference fit clean
    X, y = _data()
    src = array_chunk_source(X, y, chunk_rows=512)
    ref = _fit(session, src, epochs=8)
    resumed = _fit(session, src, epochs=8, checkpoint_every_epochs=1,
                   checkpointer=StreamCheckpointer(ckpt_path))
    assert resumed.n_steps_ == ref.n_steps_ == 32
    np.testing.assert_allclose(np.asarray(resumed.coef),
                               np.asarray(ref.coef), rtol=0, atol=1e-6)


# -------------------------------------------------------------- tooling
def test_fault_matrix_tool_outcomes(session):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from fault_matrix import run_matrix
    finally:
        sys.path.pop(0)
    rows = run_matrix(rows=2048, session=session)
    by = {r["cell"]: r for r in rows}
    assert set(by) == {"clean", "source_io", "source_fatal", "straggler",
                       "spill_corrupt", "wedge", "aot_build", "overload",
                       "mem_pressure", "drift", "label_skew",
                       "trainer_crash"}
    assert by["clean"]["outcome"] == "ok"
    assert by["source_io"]["outcome"] == "recovered"
    assert by["source_io"]["retries"] == 2
    assert by["source_fatal"]["outcome"] == "raised:TransientSourceError"
    assert by["straggler"]["outcome"] == "recovered"
    assert by["spill_corrupt"]["outcome"] == "raised:SpillCorruptionError"
    assert by["wedge"]["outcome"] == "raised:DispatchWedgedError"
    assert by["aot_build"]["outcome"] == "recovered"
    assert by["overload"]["outcome"] == "raised:OverloadShedError"
    assert by["mem_pressure"]["outcome"] == "recovered"
    assert by["drift"]["outcome"] == "raised:DriftDetectedError"
    assert by["label_skew"]["outcome"] == "recovered"
    assert by["trainer_crash"]["outcome"] == "raised:TrainerCrashInjected"
    assert not any(r["outcome"].startswith("UNEXPECTED") for r in rows)
