"""exec/ subsystem: PipelinedExecutor correctness + measured overlap,
epoch batching parity, compilation-cache wiring, and a kill-and-resume
drill through the pipelined path."""

import os
import threading
import time

import numpy as np
import pytest

from orange3_spark_tpu.exec.compile_cache import (
    cache_entries,
    cache_report,
    enable_compilation_cache,
)
from orange3_spark_tpu.exec.pipeline import PipelinedExecutor, PipelineStats
from orange3_spark_tpu.io.streaming import array_chunk_source
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator,
)


def _criteo_shaped(n, n_dense=4, n_cat=6, card=50, seed=0):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n, n_dense)).astype(np.float32)
    cats = rng.integers(0, card, size=(n, n_cat)).astype(np.float32)
    y = (dense[:, 0] + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return np.concatenate([dense, cats], axis=1), y


# ------------------------------------------------------------- correctness
def test_pipeline_order_and_stats():
    ex = PipelinedExecutor(lambda x: x * 2, depth=3)
    assert list(ex.run(iter(range(50)))) == [2 * i for i in range(50)]
    assert ex.stats.done
    assert ex.stats.items == 50
    assert ex.stats.wall_s > 0


def test_pipeline_slow_producer_low_overlap():
    """Producer-bound stream (consumer never works): every prep second is
    exposed — overlap must be ~0, never accidentally high."""

    def slow_prep(x):
        time.sleep(0.004)
        return x

    ex = PipelinedExecutor(slow_prep, depth=2)
    for _ in ex.run(iter(range(30))):
        pass  # instant consumer
    assert ex.stats.prep_s > 0
    assert ex.stats.overlap_pct < 30.0


def test_pipeline_slow_consumer_overlap_measured():
    """The tier-1 overlap contract: with the consumer busy longer than the
    producer's prep, prep hides behind consumer work and the MEASURED
    overlap is strictly positive (double buffering actually engaged)."""

    def prep(x):
        time.sleep(0.002)
        return x

    ex = PipelinedExecutor(prep, depth=2)
    for _ in ex.run(iter(range(30))):
        time.sleep(0.005)  # "device step" dominates
    assert ex.stats.items == 30
    assert ex.stats.overlap_pct > 0.0
    # generous bound: after pipeline fill, prep should be mostly hidden
    assert ex.stats.overlap_pct > 50.0


def test_pipeline_worker_exception_reraises():
    def boom(x):
        if x == 5:
            raise RuntimeError("parse failed")
        return x

    ex = PipelinedExecutor(boom, depth=2)
    it = ex.run(iter(range(10)))
    got = []
    with pytest.raises(RuntimeError, match="parse failed"):
        for v in it:
            got.append(v)
    assert got == [0, 1, 2, 3, 4]


def test_pipeline_early_close_stops_worker():
    n_alive0 = threading.active_count()
    ex = PipelinedExecutor(lambda x: x, depth=2)
    it = ex.run(iter(range(100000)))
    assert next(it) == 0
    it.close()
    deadline = time.time() + 5.0
    while threading.active_count() > n_alive0 and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= n_alive0
    assert ex.stats.done


def test_pipeline_depth_bounds_producer_lead():
    """The queue bounds how far the producer runs ahead — the memory
    contract double buffering depends on (depth staged chunks, not the
    whole stream)."""
    produced = []

    def prep(x):
        produced.append(x)
        return x

    ex = PipelinedExecutor(prep, depth=2)
    it = ex.run(iter(range(100)))
    next(it)
    time.sleep(0.2)  # give the worker every chance to overrun
    # 1 yielded + 2 queued + 1 in-flight put
    assert len(produced) <= 4
    it.close()


def test_stats_merge_aggregates():
    a = PipelineStats(items=2, prep_s=1.0, wait_s=0.25)
    b = PipelineStats(items=3, prep_s=1.0, wait_s=0.25)
    a.merge(b)
    assert a.items == 5
    assert a.overlap_pct == pytest.approx(75.0)


# ---------------------------------------------------- epoch batching parity
def test_epochs_per_dispatch_parity_and_fewer_dispatches(session):
    """Folding K replay epochs into one scan dispatch must walk the exact
    same step sequence (bit-identical theta) while dispatching fewer
    programs."""
    from orange3_spark_tpu.utils.profiling import (
        exec_counters, reset_exec_counters,
    )

    Xall, y = _criteo_shaped(4096, seed=3)
    kw = dict(n_dims=1 << 12, n_dense=4, n_cat=6, epochs=9, step_size=0.05,
              chunk_rows=1024, fused_replay=True,
              replay_granularity="epoch")
    results = {}
    for K in (1, 4):
        reset_exec_counters()
        m = StreamingHashedLinearEstimator(
            **kw, epochs_per_dispatch=K
        ).fit_stream(array_chunk_source(Xall, y, chunk_rows=1024),
                     session=session, cache_device=True)
        results[K] = (np.asarray(m.theta["emb"]),
                      exec_counters()["dispatches"], m.n_steps_)
    np.testing.assert_array_equal(results[1][0], results[4][0])
    assert results[1][2] == results[4][2]
    assert results[4][1] < results[1][1]


def test_epochs_per_dispatch_streaming_linear_parity(session):
    from orange3_spark_tpu.io.streaming import StreamingLinearEstimator

    rng = np.random.default_rng(5)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    thetas = []
    for K in (1, 3):
        m = StreamingLinearEstimator(
            loss="logistic", epochs=7, chunk_rows=512,
            replay_granularity="epoch", epochs_per_dispatch=K,
        ).fit_stream(array_chunk_source(X, y, chunk_rows=512),
                     n_features=6, session=session, cache_device=True)
        thetas.append(np.asarray(m.coef))
    np.testing.assert_array_equal(thetas[0], thetas[1])


# ------------------------------------------------------- compilation cache
def test_compilation_cache_roundtrip(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "cc")
    # what jax does itself at start-up when the variable is set
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)
    info = enable_compilation_cache()
    try:
        assert info["enabled"]
        assert info["dir"] == d
        assert info["pre_entries"] == 0

        @jax.jit
        def f(x):
            return x * 3 + 1

        f(jnp.ones((16,))).block_until_ready()
        rep = cache_report(info)
        # first run compiles: entries appear, and that is a MISS
        assert rep["cache_entries"] >= 1
        assert rep["cache_hit"] is False
        # a second process starting now would find a warm cache
        info2 = enable_compilation_cache()
        assert info2["pre_entries"] == rep["cache_entries"]
        assert cache_report(info2)["cache_hit"] is True
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_cache_entries_missing_dir():
    assert cache_entries("/nonexistent/otpu_cc_probe") == 0


# ------------------------------------------ kill-and-resume, pipelined path
def test_kill_and_resume_through_pipelined_path(
        session, tmp_path, make_killing_checkpointer):
    """StreamCheckpointer drill with the prefetcher active
    (prefetch_depth=2): kill after the 2nd snapshot mid-fit, resume, and
    land on bit-identical parameters vs an uninterrupted fit."""
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    Xall, y = _criteo_shaped(6144, seed=9)
    kw = dict(n_dims=1 << 12, n_dense=4, n_cat=6, epochs=2, step_size=0.05,
              chunk_rows=1024, prefetch_depth=2)

    ref = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session
    )

    path = str(tmp_path / "pipelined.ckpt")
    killer = make_killing_checkpointer(path, every_steps=3, die_after=2)
    with pytest.raises(RuntimeError, match="injected fault"):
        StreamingHashedLinearEstimator(**kw).fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024), session=session,
            checkpointer=killer,
        )
    assert os.path.exists(path)

    resumed = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        checkpointer=StreamCheckpointer(path, every_steps=3),
    )
    assert resumed.n_steps_ == ref.n_steps_
    np.testing.assert_array_equal(
        np.asarray(resumed.theta["emb"]), np.asarray(ref.theta["emb"])
    )
    assert not os.path.exists(path)  # completed fit deletes its snapshot
