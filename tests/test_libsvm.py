"""libsvm reader/writer/chunk-source (spark.read.format('libsvm') role)."""

import numpy as np
import pytest

from orange3_spark_tpu.io.libsvm import (
    libsvm_chunk_source,
    read_libsvm,
    write_libsvm,
)


def _write(path, lines):
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def test_read_libsvm_dense(tmp_path, session):
    p = _write(tmp_path / "a.svm", [
        "1 1:0.5 3:2.0",
        "0 2:1.5",
        "# comment",
        "1 1:1.0 2:1.0 3:1.0",
    ])
    t = read_libsvm(p, session=session)
    X, Y, _ = t.to_numpy()
    np.testing.assert_allclose(
        X, [[0.5, 0.0, 2.0], [0.0, 1.5, 0.0], [1.0, 1.0, 1.0]]
    )
    np.testing.assert_allclose(Y[:, 0], [1, 0, 1])


def test_read_libsvm_zero_based_and_errors(tmp_path, session):
    p = _write(tmp_path / "z.svm", ["1 0:2.0 2:3.0"])
    t = read_libsvm(p, zero_based=True, session=session)
    X, _, _ = t.to_numpy()
    np.testing.assert_allclose(X, [[2.0, 0.0, 3.0]])
    with pytest.raises(ValueError, match="zero_based"):
        read_libsvm(p, session=session)  # 1-based parse of a 0-based file


def test_write_read_roundtrip(tmp_path, session):
    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.table import TpuTable

    rng = np.random.default_rng(0)
    X = (rng.standard_normal((40, 6)) * (rng.random((40, 6)) > 0.6)
         ).astype(np.float32)
    y = rng.integers(0, 2, 40).astype(np.float32)
    dom = Domain([ContinuousVariable(f"f{i}") for i in range(6)],
                 ContinuousVariable("label"))
    t = TpuTable.from_numpy(dom, X, y, session=session)
    t = t.filter(t.column("f0") <= 10.0)  # all live; exercise the mask path
    p = str(tmp_path / "rt.svm")
    write_libsvm(t, p)
    back = read_libsvm(p, n_features=6, session=session)
    Xb, Yb, _ = back.to_numpy()
    np.testing.assert_allclose(Xb, X, rtol=1e-6)
    np.testing.assert_allclose(Yb[:, 0], y)


def test_libsvm_chunk_source_fixed_nnz(tmp_path, session):
    p = _write(tmp_path / "c.svm", [
        "1 1:10 2:20 3:30",
        "0 5:50",
        "1 1:1 2:2 3:3 4:4",     # truncates to nnz=3
    ])
    src = libsvm_chunk_source(p, nnz_per_row=3, chunk_rows=2)
    chunks = list(src())
    assert [c.shape for c in chunks] == [(2, 7), (1, 7)]
    c0 = chunks[0]
    np.testing.assert_allclose(c0[0], [1, 0, 1, 2, 10, 20, 30])
    np.testing.assert_allclose(c0[1], [0, 4, -1, -1, 50, 0, 0])
    np.testing.assert_allclose(chunks[1][0], [1, 0, 1, 2, 1, 2, 3])
    # re-iterable
    assert len(list(src())) == 2


def test_value_weighted_hashed_fit_learns_from_libsvm(tmp_path, session):
    """End-to-end: libsvm file -> fixed-nnz chunks -> value-weighted hashed
    fit (MLlib SparseVector semantics: forward = sum(emb[hash(idx)]*val))."""
    import numpy as np

    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(0)
    n, d, nnz = 3000, 200, 6
    w_true = rng.normal(0, 1.5, d).astype(np.float32)
    lines = []
    X_dense = np.zeros((n, d), np.float32)
    for r in range(n):
        idx = np.sort(rng.choice(d, nnz, replace=False))
        val = rng.normal(1.0, 0.5, nnz).astype(np.float32)
        X_dense[r, idx] = val
        z = float(X_dense[r] @ w_true)
        y = int(z + 0.3 * rng.standard_normal() > 0)
        lines.append(
            f"{y} " + " ".join(f"{i+1}:{v:.6g}" for i, v in zip(idx, val))
        )
    p = tmp_path / "vw.svm"
    p.write_text("\n".join(lines) + "\n")

    src = libsvm_chunk_source(str(p), nnz_per_row=nnz, chunk_rows=512)
    est = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=0, n_cat=nnz, epochs=12, step_size=0.1,
        chunk_rows=512, label_in_chunk=True, value_weighted=True,
    )
    model = est.fit_stream(src, session=session, cache_device=True)
    ev = model.evaluate_device(model.device_chunks_)
    assert ev["accuracy"] > 0.85, ev
    assert ev["auc"] > 0.9, ev


def test_value_weighted_forward_and_gradient_match_numpy(session):
    """The value-weighted forward is sum(emb[idx] * val) and its autodiff
    gradient the scatter-add of val * dz — against numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from orange3_spark_tpu.models.hashed_linear import _hashed_logits
    from orange3_spark_tpu.ops.hashing import column_salts, hash_columns

    rng = np.random.default_rng(1)
    N, C, D, k = 64, 5, 256, 1
    emb = jnp.asarray(rng.standard_normal((D, k)), jnp.float32)
    theta = {"emb": emb, "coef": jnp.zeros((0, k), jnp.float32),
             "intercept": jnp.zeros((k,), jnp.float32)}
    cats = jnp.asarray(rng.integers(0, 999, (N, C)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((N, C)), jnp.float32)
    idx = hash_columns(cats, jnp.asarray(column_salts(C, 0)), D)
    dense = jnp.zeros((N, 0), jnp.float32)

    def loss(theta):
        z = _hashed_logits(theta, dense, idx, jnp.float32, vals)
        return jnp.sum(jnp.tanh(z))

    out, grads = jax.value_and_grad(loss)(theta)
    e, i, v = (np.asarray(a, np.float64) if a.dtype != jnp.int32
               else np.asarray(a) for a in (emb, idx, vals))
    z = (e[i, 0] * v).sum(axis=1)
    np.testing.assert_allclose(out, np.tanh(z).sum(), rtol=1e-5, atol=1e-5)
    want = np.zeros((D, k))
    np.add.at(want[:, 0], i, v * (1.0 - np.tanh(z) ** 2)[:, None])
    np.testing.assert_allclose(np.asarray(grads["emb"]), want,
                               rtol=1e-5, atol=1e-6)


def test_value_weighted_rejects_dense_block(session):
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.io.streaming import array_chunk_source

    est = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=3, n_cat=4, value_weighted=True,
    )
    with pytest.raises(ValueError, match="n_dense must be 0"):
        est.fit_stream(
            array_chunk_source(np.zeros((8, 11), np.float32),
                               np.zeros(8, np.float32), chunk_rows=8),
            session=session,
        )


def test_value_weighted_hash_is_position_independent(session):
    """The same (index, value) pair must produce the same logit whichever
    SLOT it occupies — libsvm packs pairs positionally, so value-weighted
    fits share one salt across slots."""
    import numpy as np

    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    est = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=0, n_cat=3, epochs=2, step_size=0.1,
        value_weighted=True, chunk_rows=8,
    )
    rng = np.random.default_rng(2)
    Xall = np.concatenate([
        rng.integers(0, 50, (64, 3)).astype(np.float32),
        rng.normal(1, 0.3, (64, 3)).astype(np.float32),
    ], axis=1)
    y = rng.integers(0, 2, 64).astype(np.float32)
    model = est.fit_stream(
        array_chunk_source(Xall, y, chunk_rows=8), session=session
    )
    # feature 7 with value 2.0 in slot 0 vs slot 2 (others padded out)
    a = np.array([[7, -1, -1, 2.0, 0.0, 0.0]], np.float32)
    b = np.array([[-1, -1, 7, 0.0, 0.0, 2.0]], np.float32)
    np.testing.assert_allclose(model._logits(a), model._logits(b), rtol=1e-6)
