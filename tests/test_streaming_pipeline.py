"""Round-3 chunk-pipeline features: label-in-chunk zero-copy feed, HBM chunk
cache (Spark persist() analogue), holdout windowing, device-side evaluation,
prefetch overlap, and string-categorical native ingest (SURVEY §2b "Data
ingest" + BASELINE config 2)."""

import numpy as np
import pytest

from orange3_spark_tpu.io.streaming import (
    array_chunk_source,
    csv_raw_chunk_source,
    prefetch_map,
)
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator,
)
from orange3_spark_tpu.ops.hashing import STRING_CODE_MASK, strings_to_u32


def _criteo_shaped(n, n_dense=4, n_cat=6, card=50, seed=0):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n, n_dense)).astype(np.float32)
    cats = rng.integers(0, card, size=(n, n_cat)).astype(np.float32)
    effects = rng.normal(0, 1.2, size=(n_cat, card))
    logit = dense[:, 0] - 0.5 * dense[:, 1]
    for j in range(n_cat):
        logit = logit + effects[j, cats[:, j].astype(int)]
    y = (logit + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return np.concatenate([dense, cats], axis=1), y


def _raw_source(Xall, y, chunk_rows):
    """Raw label-in-chunk chunks: [n, 1 + d] with the label as column 0."""
    full = np.concatenate([y[:, None], Xall], axis=1).astype(np.float32)

    def open_stream():
        for s in range(0, len(full), chunk_rows):
            yield full[s:s + chunk_rows]

    return open_stream


KW = dict(n_dims=1 << 12, n_dense=4, n_cat=6, epochs=2, step_size=0.05,
          chunk_rows=1024)


def test_label_in_chunk_matches_split_path(session):
    """Shipping the label inside the chunk (sliced in-jit, masked by a traced
    n_valid) must produce bit-identical parameters to the (X, y, w) path."""
    Xall, y = _criteo_shaped(5000, seed=1)
    split = StreamingHashedLinearEstimator(**KW).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session
    )
    fused = StreamingHashedLinearEstimator(
        **KW, label_in_chunk=True
    ).fit_stream(_raw_source(Xall, y, 1024), session=session)
    np.testing.assert_array_equal(
        np.asarray(split.theta["emb"]), np.asarray(fused.theta["emb"])
    )
    np.testing.assert_array_equal(
        np.asarray(split.theta["coef"]), np.asarray(fused.theta["coef"])
    )


def test_cache_device_matches_streaming(session):
    """HBM-cached replay epochs must walk the exact same step sequence as
    re-streaming from the source every epoch."""
    Xall, y = _criteo_shaped(4000, seed=2)
    kw = dict(KW, epochs=3)
    streamed = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        cache_device=False,
    )
    cached = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        cache_device=True,
    )
    assert streamed.n_steps_ == cached.n_steps_
    np.testing.assert_array_equal(
        np.asarray(streamed.theta["emb"]), np.asarray(cached.theta["emb"])
    )


def test_cache_budget_overflow_degrades_to_streaming(session):
    """A cache budget smaller than the dataset must fall back to streaming
    (never a partial/reordered replay) and still produce identical numbers."""
    Xall, y = _criteo_shaped(4000, seed=2)
    kw = dict(KW, epochs=2)
    ref = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
    )
    with pytest.warns(RuntimeWarning, match="cache overflowed"):
        tiny = StreamingHashedLinearEstimator(**kw).fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024), session=session,
            cache_device=True, cache_device_bytes=1,  # nothing fits
        )
    assert tiny.device_chunks_ == []
    np.testing.assert_array_equal(
        np.asarray(ref.theta["emb"]), np.asarray(tiny.theta["emb"])
    )


def test_holdout_chunks_excluded_from_training(session):
    """The last holdout_chunks device batches never reach the optimizer, in
    any epoch; they come back for device-side evaluation."""
    Xall, y = _criteo_shaped(5120, seed=3)   # exactly 5 chunks of 1024
    kw = dict(KW, epochs=3)
    model = StreamingHashedLinearEstimator(**kw).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        cache_device=True, holdout_chunks=1,
    )
    assert model.n_steps_ == 3 * 4          # 4 train chunks x 3 epochs
    assert len(model.holdout_chunks_) == 1
    assert len(model.device_chunks_) == 4
    ev = model.evaluate_device(model.holdout_chunks_)
    assert 0.0 < ev["logloss"] < 1.5
    assert "auc" in ev


def test_evaluate_device_matches_evaluate_stream(session):
    """The on-device reduction must agree with the host-side streaming
    evaluator (same binned-AUC estimator, same loss)."""
    Xall, y = _criteo_shaped(4096, seed=4)
    model = StreamingHashedLinearEstimator(**KW).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        cache_device=True,
    )
    host = model.evaluate_stream(lambda: iter([(Xall, y)]))
    dev = model.evaluate_device(model.device_chunks_)
    assert dev["logloss"] == pytest.approx(host["logloss"], abs=2e-3)
    assert dev["accuracy"] == pytest.approx(host["accuracy"], abs=2e-3)
    assert dev["auc"] == pytest.approx(host["auc"], abs=2e-3)


def test_binary_k1_theta_and_proba_shapes(session):
    """Binary logistic collapses to a single-logit table (half the gather
    bytes) while predict_proba still reports both classes."""
    Xall, y = _criteo_shaped(2000, seed=5)
    model = StreamingHashedLinearEstimator(**KW).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session
    )
    assert model.theta["emb"].shape[1] == 1
    proba = model.predict_proba(Xall[:100])
    assert proba.shape == (100, 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-5)
    # multiclass keeps the softmax width
    est3 = StreamingHashedLinearEstimator(**dict(KW, n_classes=3))
    y3 = (y + (Xall[:, 0] > 1.0)).astype(np.float32)
    m3 = est3.fit_stream(
        array_chunk_source(Xall, y3, chunk_rows=1024), session=session
    )
    assert m3.theta["emb"].shape[1] == 3


def test_model_axis_sharded_embedding_matches_replicated(session):
    """Fitting with the embedding table sharded P('model', None) on a 4x2
    mesh must reproduce the data-parallel-only fit exactly — the model axis
    is a layout choice, not an algorithm change (SURVEY §2b 'Parallelism
    strategies': the axis needs a real tenant, this is it)."""
    import jax
    from orange3_spark_tpu.core.session import TpuSession

    Xall, y = _criteo_shaped(4000, seed=7)
    ref = StreamingHashedLinearEstimator(**KW).fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session
    )

    devs = np.asarray(jax.devices()).reshape(4, 2)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    sess2 = TpuSession(mesh)
    with sess2.use():
        sharded = StreamingHashedLinearEstimator(**KW).fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024), session=sess2
        )
    assert sess2.mesh.shape["model"] == 2
    # the table really is sharded over 'model'
    emb_sh = sharded.theta["emb"].sharding
    assert emb_sh.spec[0] == "model", emb_sh
    np.testing.assert_allclose(
        np.asarray(ref.theta["emb"]), np.asarray(sharded.theta["emb"]),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(ref.theta["coef"]), np.asarray(sharded.theta["coef"]),
        rtol=1e-5, atol=1e-6,
    )


def test_prefetch_map_order_exceptions_and_close():
    assert list(prefetch_map(lambda x: x * 2, iter(range(50)), depth=3)) == [
        x * 2 for x in range(50)
    ]

    def boom(x):
        if x == 5:
            raise ValueError("boom at 5")
        return x

    it = prefetch_map(boom, iter(range(10)), depth=2)
    got = []
    with pytest.raises(ValueError, match="boom at 5"):
        for v in it:
            got.append(v)
    assert got == [0, 1, 2, 3, 4]

    # early close must not hang the worker
    it = prefetch_map(lambda x: x, iter(range(1000)), depth=2)
    assert next(it) == 0
    it.close()


def test_fastcsv_categorical_end_to_end(session, tmp_path):
    """Hex-string categoricals (real Criteo's format) through the NATIVE
    parser: crc32&24bit codes must equal the host strings_to_u32 on-ramp
    exactly, and the hashed estimator must learn from them."""
    rng = np.random.default_rng(6)
    n, card = 4096, 40
    levels = np.array([f"{v:08x}" for v in rng.integers(0, 2**32, card)])
    cats = levels[rng.integers(0, card, size=(n, 2))]
    dense = rng.standard_normal((n, 2)).astype(np.float32)
    eff = rng.normal(0, 1.5, size=card)
    lvl_idx = np.searchsorted(np.sort(levels), cats)  # effect per level
    logit = dense[:, 0] + eff[lvl_idx[:, 0]] + eff[lvl_idx[:, 1]]
    y = (logit > 0).astype(np.float32)

    path = tmp_path / "hexcats.csv"
    with open(path, "w") as f:
        f.write("label,i0,i1,c0,c1\n")
        for i in range(n):
            f.write(f"{int(y[i])},{dense[i,0]:.6g},{dense[i,1]:.6g},"
                    f"{cats[i,0]},{cats[i,1]}\n")

    src = csv_raw_chunk_source(
        str(path), chunk_rows=1024, categorical_cols=("c0", "c1")
    )
    # parity: parsed codes == host strings_to_u32 codes
    first = next(src())
    want = strings_to_u32(cats[:1024]).astype(np.float32)
    np.testing.assert_array_equal(first[:, 3:], want)
    assert first[:, 3:].max() <= STRING_CODE_MASK

    est = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=2, n_cat=2, epochs=8, step_size=0.05,
        chunk_rows=1024, label_in_chunk=True,
    )
    model = est.fit_stream(src, session=session, cache_device=True)
    ev = model.evaluate_device(model.device_chunks_)
    assert ev["accuracy"] > 0.85, ev


def test_dense_streaming_cache_device_matches_streaming(session):
    """cache_device on the dense streaming fit replays HBM batches for
    epochs 2+ and lands on the same numbers as re-streaming the source."""
    import numpy as np

    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source,
    )

    rng = np.random.default_rng(6)
    X = rng.standard_normal((4096, 8)).astype(np.float32)
    y = (X @ rng.standard_normal(8) > 0).astype(np.float32)
    src = array_chunk_source(X, y, chunk_rows=1024)

    def fit(cache):
        est = StreamingLinearEstimator(
            loss="logistic", epochs=4, step_size=0.05, chunk_rows=1024,
        )
        return est.fit_stream(src, n_features=8, session=session,
                              cache_device=cache)

    m_cache, m_stream = fit(True), fit(False)
    assert m_cache.n_steps_ == m_stream.n_steps_ == 16
    np.testing.assert_allclose(
        np.asarray(m_cache.coef), np.asarray(m_stream.coef),
        rtol=1e-5, atol=1e-7,
    )
    logits = X @ np.asarray(m_cache.coef) + np.asarray(m_cache.intercept)
    acc = np.mean(np.argmax(logits, axis=1) == y)
    assert acc > 0.9


def test_dense_streaming_cache_budget_overflow_degrades(session):
    """A cache budget below one batch degrades to pure streaming with
    identical numbers (no partial replay / double counting)."""
    import numpy as np

    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source,
    )

    rng = np.random.default_rng(7)
    X = rng.standard_normal((2048, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    src = array_chunk_source(X, y, chunk_rows=512)

    def fit(cache, budget=8 << 30):
        est = StreamingLinearEstimator(
            loss="logistic", epochs=3, step_size=0.05, chunk_rows=512,
        )
        return est.fit_stream(src, n_features=6, session=session,
                              cache_device=cache,
                              cache_device_bytes=budget)

    with pytest.warns(RuntimeWarning, match="cache overflowed"):
        m_over = fit(True, budget=1024)   # smaller than one batch
    m_plain = fit(False)
    assert m_over.n_steps_ == m_plain.n_steps_ == 12
    np.testing.assert_array_equal(
        np.asarray(m_over.coef), np.asarray(m_plain.coef)
    )


def test_streaming_kmeans_cache_device_matches_streaming(session):
    import numpy as np

    from orange3_spark_tpu.io.streaming import (
        StreamingKMeans, array_chunk_source,
    )

    rng = np.random.default_rng(8)
    centers_true = rng.normal(0, 6, (3, 4)).astype(np.float32)
    X = np.concatenate([
        centers_true[i] + rng.standard_normal((500, 4)).astype(np.float32)
        for i in range(3)
    ])
    rng.shuffle(X)
    src = array_chunk_source(X, None, chunk_rows=256)

    def fit(cache):
        return StreamingKMeans(k=3, epochs=3, chunk_rows=256, seed=1
                               ).fit_stream(src, n_features=4,
                                            session=session,
                                            cache_device=cache)

    m_c, m_s = fit(True), fit(False)
    assert m_c.n_iter_ == m_s.n_iter_
    np.testing.assert_array_equal(
        np.asarray(m_c.centers), np.asarray(m_s.centers)
    )


def test_streaming_kmeans_cache_preseed_and_overflow(session):
    """The subtle cache paths: (a) a leading all-dead batch is skipped in
    epoch 1 but stepped by later epochs — cached and streamed fits must
    agree; (b) a budget below one batch degrades to pure streaming."""
    import numpy as np

    from orange3_spark_tpu.io.streaming import (
        StreamingKMeans, array_chunk_source,
    )

    rng = np.random.default_rng(9)
    X = np.concatenate([
        rng.normal(i * 8, 1, (300, 3)).astype(np.float32) for i in range(2)
    ])
    rng.shuffle(X)
    w = np.ones(len(X), np.float32)
    w[:128] = 0.0   # first rechunked batch is entirely dead

    src = array_chunk_source(X, None, w, chunk_rows=128)

    def fit(cache, budget=8 << 30):
        return StreamingKMeans(k=2, epochs=3, chunk_rows=128, seed=2
                               ).fit_stream(src, n_features=3,
                                            session=session,
                                            cache_device=cache,
                                            cache_device_bytes=budget)

    m_c, m_s = fit(True), fit(False)
    assert m_c.n_iter_ == m_s.n_iter_
    np.testing.assert_array_equal(
        np.asarray(m_c.centers), np.asarray(m_s.centers)
    )
    with pytest.warns(RuntimeWarning, match="cache overflowed"):
        m_o = fit(True, budget=64)   # smaller than one batch: degrade
    assert m_o.n_iter_ == m_s.n_iter_
    np.testing.assert_array_equal(
        np.asarray(m_o.centers), np.asarray(m_s.centers)
    )


def test_negative_row_weights_rejected_at_ingest():
    """_rechunk is the single ingest choke point: negative weights would
    silently break the global 'w == 0 means dead row' invariant (e.g. the
    KMeans replay's pre-seed-batches-are-no-ops property) — reject loudly
    (round-4 advisor finding)."""
    from orange3_spark_tpu.io.streaming import _rechunk

    X = np.ones((8, 3), np.float32)
    y = np.ones((8,), np.float32)
    w = np.ones((8,), np.float32)
    w[3] = -0.5

    with pytest.raises(ValueError, match="negative row weights"):
        list(_rechunk(iter([(X, y, w)]), rows=4))
    # non-negative weights (incl. zeros) pass untouched
    w[3] = 0.0
    out = list(_rechunk(iter([(X, y, w)]), rows=4))
    assert len(out) == 2 and out[0][2].shape == (4,)


def _write_parquet(path, Xall, y, row_group_size=600):
    import pyarrow as pa
    import pyarrow.parquet as pq

    cols = {"label": y}
    for j in range(Xall.shape[1]):
        cols[f"f{j}"] = Xall[:, j]
    pq.write_table(pa.table(cols), str(path), row_group_size=row_group_size)


def test_parquet_chunk_source_streams_row_groups(tmp_path):
    """Round-group-at-a-time parquet ingest (SURVEY §2b "Data ingest" —
    the out-of-core regime was CSV-only through round 4): chunks must
    reassemble the exact data, split the class column, respect chunk_rows
    across row-group boundaries, and re-iterate for multi-epoch fits."""
    from orange3_spark_tpu.io.streaming import (
        parquet_chunk_source, parquet_raw_chunk_source,
    )

    Xall, y = _criteo_shaped(5000, seed=3)
    p = tmp_path / "d.parquet"
    _write_parquet(p, Xall, y)   # 600-row groups: 1000-row chunks cross them

    src = parquet_chunk_source(str(p), class_col="label", chunk_rows=1000)
    for _ in range(2):           # re-iterable (epochs restart the stream)
        chunks = list(src())
        assert [len(c[0]) for c in chunks] == [1000] * 5
        np.testing.assert_allclose(
            np.concatenate([c[0] for c in chunks]), Xall, rtol=1e-6)
        np.testing.assert_array_equal(
            np.concatenate([c[1] for c in chunks]), y)

    raw = list(parquet_raw_chunk_source(str(p), chunk_rows=1000)())
    full = np.column_stack([y] + [Xall[:, j] for j in range(Xall.shape[1])])
    np.testing.assert_allclose(np.concatenate(raw), full, rtol=1e-6)

    with pytest.raises(ValueError, match="class_col"):
        next(parquet_chunk_source(str(p), class_col="nope")())


def test_parquet_fit_stream_matches_array_source(session, tmp_path):
    """A fit_stream fed from parquet must produce bit-identical parameters
    to the same data fed from memory — including through the DISK-SPILL
    replay path (cache too small to hold the dataset), closing the last
    ingest gap vs SURVEY §2b (round-4 verdict item 4)."""
    from orange3_spark_tpu.io.streaming import parquet_raw_chunk_source

    Xall, y = _criteo_shaped(4096, seed=7)
    p = tmp_path / "d.parquet"
    _write_parquet(p, Xall, y)

    kw = dict(KW, epochs=3, label_in_chunk=True, fused_replay=False)
    ref = StreamingHashedLinearEstimator(**kw).fit_stream(
        _raw_source(Xall, y, 1024), session=session, cache_device=True)
    st: dict = {}
    spilled = StreamingHashedLinearEstimator(**kw).fit_stream(
        parquet_raw_chunk_source(str(p), chunk_rows=1024), session=session,
        cache_device=True, cache_device_bytes=1 << 16,
        cache_spill_dir=str(tmp_path), stage_times=st,
    )
    assert st.get("replay_source") == "disk"
    np.testing.assert_array_equal(
        np.asarray(ref.theta["emb"]), np.asarray(spilled.theta["emb"]))
    np.testing.assert_array_equal(
        np.asarray(ref.theta["coef"]), np.asarray(spilled.theta["coef"]))


def test_score_stream_writes_parquet(session, tmp_path):
    """Streaming transform-and-write: scores a chunk stream row-group-at-
    a-time to parquet (bounded host memory), trims padding, drops masked
    rows, matches the in-device scores exactly."""
    import jax.numpy as jnp
    import pyarrow.parquet as pq

    from orange3_spark_tpu.io.streaming import score_stream

    rng = np.random.default_rng(13)
    n, d = 5000, 4
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    w = np.ones(n, np.float32)
    w[::10] = 0.0                      # masked rows must not be written
    wv = jnp.asarray([1.0, -0.5, 0.25, 0.0])

    def score_fn(Xd):
        return jax.nn.sigmoid(Xd @ wv)

    import jax

    out = str(tmp_path / "scored.parquet")
    total = score_stream(score_fn, array_chunk_source(X, y, w, chunk_rows=900),
                         out, session=session, chunk_rows=1024)
    live = w > 0
    assert total == int(live.sum())
    t = pq.read_table(out)
    assert t.column_names == [f"f{j}" for j in range(d)] + ["label",
                                                            "prediction"]
    got = t.column("prediction").to_numpy()
    exp = np.asarray(jax.nn.sigmoid(jnp.asarray(X[live]) @ wv))
    np.testing.assert_allclose(got, exp, rtol=1e-6)
    np.testing.assert_array_equal(t.column("label").to_numpy(), y[live])
    np.testing.assert_allclose(t.column("f0").to_numpy(), X[live][:, 0])

    # [n, k] scores fan out into suffixed columns; features skippable
    def score2(Xd):
        z = Xd @ wv
        return jnp.stack([1 - jax.nn.sigmoid(z), jax.nn.sigmoid(z)], axis=1)

    out2 = str(tmp_path / "scored2.parquet")
    score_stream(score2, array_chunk_source(X, y, w, chunk_rows=900),
                 out2, session=session, chunk_rows=1024,
                 include_features=False, prediction_col="probability")
    t2 = pq.read_table(out2)
    assert t2.column_names == ["label", "probability_0", "probability_1"]


def test_score_stream_edge_cases(session, tmp_path):
    """All-masked chunks skip cleanly; conflicting args and failed runs
    leave no partial file behind."""
    import glob

    import jax
    import jax.numpy as jnp

    from orange3_spark_tpu.io.streaming import score_stream

    rng = np.random.default_rng(14)
    X = rng.standard_normal((3000, 3)).astype(np.float32)
    w = np.ones(3000, np.float32)
    w[:1024] = 0.0                        # the FIRST rechunked chunk is dead

    def score_fn(Xd):
        return jax.nn.sigmoid(Xd @ jnp.asarray([1.0, 0.0, -1.0]))

    out = str(tmp_path / "s.parquet")
    total = score_stream(score_fn, array_chunk_source(X, None, w,
                                                      chunk_rows=1024),
                         out, session=session, chunk_rows=1024)
    assert total == int((w > 0).sum())

    with pytest.raises(ValueError, match="include_features"):
        score_stream(score_fn, array_chunk_source(X, None, w), out,
                     session=session, feature_names=("a", "b", "c"),
                     include_features=False)

    def boom(Xd):
        raise RuntimeError("mid-stream death")

    with pytest.raises(RuntimeError, match="mid-stream"):
        score_stream(boom, array_chunk_source(X, None, None,
                                              chunk_rows=1024),
                     str(tmp_path / "dead.parquet"), session=session,
                     chunk_rows=1024)
    assert not glob.glob(str(tmp_path / "dead.parquet*")), \
        "failed run must leave no partial file"
