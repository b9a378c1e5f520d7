"""LinearSVC / LinearRegression / KMeans / PCA vs sklearn numerics (SURVEY §4)."""

from functools import partial

import numpy as np
import pytest

from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.datasets import load_iris, make_blobs, make_classification
from orange3_spark_tpu.models.kmeans import KMeans
from orange3_spark_tpu.models.linear_regression import LinearRegression
from orange3_spark_tpu.models.linear_svc import LinearSVC
from orange3_spark_tpu.models.pca import PCA


# ------------------------------------------------------------------ LinearSVC
def test_linear_svc_binary(session):
    t = make_classification(500, 8, n_classes=2, seed=5, noise=0.1, session=session)
    model = LinearSVC(max_iter=100, reg_param=0.01, loss="squared_hinge").fit(t)
    pred = model.predict(t)
    y = t.to_numpy()[1][:, 0]
    assert np.mean(pred == y) > 0.95


def test_linear_svc_rejects_multiclass(session, iris):
    with pytest.raises(ValueError, match="binary"):
        LinearSVC().fit(iris)


def test_linear_svc_transform_appends(session):
    t = make_classification(200, 4, n_classes=2, seed=6, session=session)
    out = LinearSVC(max_iter=50).fit(t).transform(t)
    names = [v.name for v in out.domain.attributes]
    assert "rawPrediction" in names and "prediction" in names


# ---------------------------------------------------------- LinearRegression
def _regression_data(session, n=400, d=6, seed=7, noise=0.01):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    true_w = rng.standard_normal(d).astype(np.float32)
    y = X @ true_w + 2.5 + noise * rng.standard_normal(n).astype(np.float32)
    t = TpuTable.from_arrays(X, y, session=session)
    return t, X, y, true_w


def test_linreg_normal_matches_sklearn(session):
    t, X, y, _ = _regression_data(session)
    model = LinearRegression(solver="normal").fit(t)

    from sklearn.linear_model import LinearRegression as SkLin

    sk = SkLin().fit(X, y)
    np.testing.assert_allclose(np.asarray(model.coef), sk.coef_, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(float(model.intercept), sk.intercept_, rtol=1e-3)


def test_linreg_lbfgs_close_to_normal(session):
    t, X, y, _ = _regression_data(session)
    m1 = LinearRegression(solver="normal").fit(t)
    m2 = LinearRegression(solver="l-bfgs", max_iter=200, tol=1e-8).fit(t)
    np.testing.assert_allclose(
        np.asarray(m1.coef), np.asarray(m2.coef), rtol=1e-2, atol=1e-3
    )


def test_linreg_ridge_matches_sklearn(session):
    t, X, y, _ = _regression_data(session)
    lam = 0.5
    model = LinearRegression(solver="normal", reg_param=lam).fit(t)

    from sklearn.linear_model import Ridge

    # sklearn Ridge penalizes alpha*||w||^2 on the SUM of squares; ours is on
    # the mean (MLlib convention), so alpha = lam * n matches.
    sk = Ridge(alpha=lam * len(X)).fit(X, y)
    np.testing.assert_allclose(np.asarray(model.coef), sk.coef_, rtol=1e-3, atol=1e-4)


# ------------------------------------------------------------------- KMeans
def test_kmeans_recovers_blobs(session):
    t, true_assign = make_blobs(1000, 5, n_centers=4, seed=8, spread=0.3, session=session)
    model = KMeans(k=4, max_iter=50, seed=0).fit(t)
    pred = model.predict(t)
    # adjusted rand index vs ground truth should be near 1 for tight blobs
    from sklearn.metrics import adjusted_rand_score

    assert adjusted_rand_score(true_assign, pred) > 0.95
    assert model.training_cost_ is not None and model.training_cost_ > 0


def test_kmeans_matches_sklearn_cost(session):
    t, _ = make_blobs(600, 4, n_centers=3, seed=9, spread=0.5, session=session)
    model = KMeans(k=3, max_iter=100, seed=1).fit(t)

    from sklearn.cluster import KMeans as SkKMeans

    X = t.to_numpy()[0]
    sk = SkKMeans(n_clusters=3, n_init=5, random_state=0).fit(X)
    # our single-init cost within 5% of sklearn's best-of-5
    assert model.compute_cost(t) <= sk.inertia_ * 1.05


def test_kmeans_random_init_and_transform(session):
    t, _ = make_blobs(300, 3, n_centers=2, seed=10, session=session)
    model = KMeans(k=2, init_mode="random", max_iter=30).fit(t)
    out = model.transform(t)
    assert out.domain.attributes[-1].name == "cluster"
    clusters = np.asarray(out.column("cluster"))[: t.n_rows]
    assert set(np.unique(clusters)) <= {0.0, 1.0}


def test_kmeans_respects_filter(session):
    t, _ = make_blobs(400, 3, n_centers=2, seed=11, session=session)
    X = t.to_numpy()[0]
    # shift a far-away outlier cluster into rows we then filter out
    X2 = X.copy()
    X2[:50] += 100.0
    t2 = TpuTable.from_numpy(t.domain, X2, session=session)
    import jax.numpy as jnp

    filtered = t2.filter(jnp.arange(t2.n_pad) >= 50)
    model = KMeans(k=2, max_iter=50, seed=2).fit(filtered)
    centers = model.cluster_centers_
    assert np.all(np.abs(centers) < 50), "outlier rows leaked into centers"


# ------------------------------------------- KMeans: the vector-unit pass
# The cell's limits (benchmark/cells/taxi_canvas_refit_staged.json) on the
# cost and the cluster sizes against a float64 Lloyd; centres to 1e-5.
# The paths: "vpu" the pass as d*k = 40 dispatches it on this backend (the
# vector-unit wrapper, the MXU's pass inside it off the TPU), "vpu_kernel"
# the Pallas kernel in interpret mode, "mxu" the path over the threshold.
COST_LIMIT, SIZES_LIMIT, CENTERS_RTOL = 4e-5, 5e-5, 1e-5
IMPLS = ("mxu", "vpu", "vpu_kernel")


def _use_path(impl, monkeypatch) -> list:
    """Steer the pass to ``impl``; the list returned gathers the kernel's
    calls (``vpu_kernel``), so that a test can see that it ran."""
    from orange3_spark_tpu.models import kmeans

    calls = []
    if impl == "mxu":
        monkeypatch.setattr(kmeans, "VPU_MAX_DK", 0)
    if impl == "vpu_kernel":
        def kernel(X, w, c, dtype, sums):
            calls.append(X.shape)
            return kmeans._pass_pallas(X, w, c, dtype, sums, interpret=True)

        monkeypatch.setattr(kmeans, "_local_pass", kernel)
    return calls


def _one_device_rows():
    """The rows of a one-device session's table, as ``KMeans`` passes
    them (this process has more devices, so none is assumed)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    return NamedSharding(Mesh(np.asarray(jax.devices()[:1]), ("data",)),
                         P("data", None))


def _lloyd64(X, w, C, tol, max_iter):
    """Plain float64 Lloyd with the program's stopping rule and argmin's
    tie rule -> (centres, n_iter, cost, live sizes)."""
    X, C = X.astype(np.float64), C.astype(np.float64)
    k = len(C)
    for it in range(1, max_iter + 1):
        a = ((X[:, None] - C[None]) ** 2).sum(-1).argmin(1)
        hw = (a[:, None] == np.arange(k)) * w[:, None]
        cnt = hw.sum(0)
        new = np.where(cnt[:, None] > 0,
                       hw.T @ X / np.maximum(cnt, 1e-12)[:, None], C)
        done = np.all(np.sqrt(((new - C) ** 2).sum(1)) < tol)
        C = new
        if done:
            break
    d2 = ((X[:, None] - C[None]) ** 2).sum(-1)
    a = d2.argmin(1)
    return C, it, float((d2.min(1) * w).sum()), np.bincount(a[w > 0], minlength=k)


def _pass_case(case, seed=42, n=6500, d=4, k=10):
    """Seeded blobs at d*k under the threshold, and the case's twist."""
    if case == "whole_groups":  # rows in whole groups of 128, a last
        n = 6400                # grid step that hangs over the table's end
    rng = np.random.default_rng(seed)
    true = 5.0 * rng.standard_normal((k, d))
    X = true[rng.integers(k, size=n)] + 0.4 * rng.standard_normal((n, d))
    w = np.ones(n)
    C0 = true + 0.3 * rng.standard_normal((k, d))
    if case == "padding":      # weightless rows far out must weigh nothing
        X[-700:] = 1e3
        w[-700:] = 0.0
    if case == "empty":        # a centre no row is nearest keeps its place
        C0[4] = 100.0
    if case == "ties":         # a duplicate centre: every tie to the lower id
        C0[7] = C0[3]
    return X.astype(np.float32), w.astype(np.float32), C0.astype(np.float32)


def _run_lloyd(X, w, C0, tol=1e-4, max_iter=20, rows=None,
               compute_dtype=np.float32):
    """``_lloyd``'s body under a jit of its own: traced anew, so that the
    path a test picks is the one compiled."""
    import jax
    import jax.numpy as jnp

    from orange3_spark_tpu.models import kmeans

    fit = jax.jit(lambda X, w, c: kmeans._lloyd.__wrapped__(
        X, w, c, jnp.float32(tol), k=len(C0), max_iter=max_iter,
        compute_dtype=jnp.dtype(compute_dtype),
        rows=rows or _one_device_rows()))
    return fit(X, w, jnp.asarray(C0))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 1,024 rows, two to a grid step: the kernel's blocks,
    steps and ragged last step on a table of thousands of rows."""
    from orange3_spark_tpu.models import kmeans
    from orange3_spark_tpu.ops import stats

    monkeypatch.setattr(stats, "ROW_BLOCK", 1024)
    monkeypatch.setattr(kmeans, "_STEP_BYTES", 1 << 15)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "case", ["blobs", "whole_groups", "padding", "empty", "ties"])
def test_lloyd_paths_agree_with_float64(case, impl, small_blocks, monkeypatch):
    import jax
    import jax.numpy as jnp

    from orange3_spark_tpu.models import kmeans

    calls = _use_path(impl, monkeypatch)
    X, w, C0 = _pass_case(case)
    centers, assign, cost, n_iter = _run_lloyd(X, w, C0)
    assert bool(calls) == (impl == "vpu_kernel")
    centers, assign = np.asarray(centers), np.asarray(assign)
    C64, it64, cost64, sizes64 = _lloyd64(X, w, C0, 1e-4, 20)
    assert int(n_iter) == it64
    assert np.linalg.norm(centers - C64) / np.linalg.norm(C64) < CENTERS_RTOL
    assert abs(float(cost) - cost64) / cost64 < COST_LIMIT
    sizes = np.bincount(assign[w > 0], minlength=len(C0))
    assert np.abs(sizes - sizes64).sum() / (2.0 * sizes64.sum()) < SIZES_LIMIT
    if case == "empty":
        np.testing.assert_array_equal(centers[4], C0[4])
        assert sizes[4] == 0
    if case == "ties":         # at the seed centres every tie is centre 3's
        a0, _ = jax.jit(lambda X, c, w: kmeans._pass(
            X, w, c, jnp.dtype(np.float32), False, _one_device_rows()))(
                X, C0, w)
        sizes0 = np.bincount(np.asarray(a0), minlength=len(C0))
        assert sizes0[7] == 0 and sizes0[3] > 0


@pytest.mark.parametrize("impl", ["vpu", "vpu_kernel"])
def test_lloyd_restarts_in_lockstep_equal_each_alone(impl, small_blocks,
                                                     monkeypatch):
    """``n_init = 3``: the vmapped restarts (``_lloyd.plain`` under vmap in
    ``KMeans._fit``) give each restart's own fit."""
    import jax
    import jax.numpy as jnp

    from orange3_spark_tpu.models import kmeans

    calls = _use_path(impl, monkeypatch)
    X, w, C0 = _pass_case("blobs")
    inits = np.stack([C0, C0[::-1].copy(), C0 + 0.5])
    run = partial(kmeans._lloyd.__wrapped__, tol=jnp.float32(1e-4), k=10,
                  max_iter=20, compute_dtype=jnp.dtype(np.float32),
                  rows=_one_device_rows())
    batched = jax.jit(jax.vmap(lambda c: run(X, w, c)))(jnp.asarray(inits))
    for r, c0 in enumerate(inits):
        alone = jax.jit(lambda c: run(X, w, c))(jnp.asarray(c0))
        for got, want in zip(batched, alone):
            np.testing.assert_allclose(np.asarray(got)[r], np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
    assert bool(calls) == (impl == "vpu_kernel")


@pytest.mark.parametrize("impl", ["vpu", "vpu_kernel"])
def test_lloyd_row_sharded_over_four_devices_equals_one(impl, small_blocks,
                                                        monkeypatch):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    calls = _use_path(impl, monkeypatch)
    X, w, C0 = _pass_case("padding", n=8192)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    sharded = _run_lloyd(jax.device_put(X, rows),
                         jax.device_put(w, NamedSharding(mesh, P("data"))),
                         C0, rows=rows)
    one = _run_lloyd(X, w, C0)
    np.testing.assert_allclose(np.asarray(sharded[0]), np.asarray(one[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sharded[1]), np.asarray(one[1]))
    assert int(sharded[3]) == int(one[3])
    # each device's kernel passed its quarter of the rows
    assert ((2048, 4) in calls) == (impl == "vpu_kernel")


@pytest.mark.parametrize("impl", ["vpu", "vpu_kernel"])
def test_lloyd_bfloat16_differs_by_more_than_the_centers_limit(
        impl, small_blocks, monkeypatch):
    """The cell's control: ``compute_dtype='bfloat16'`` rounds the pass's
    operands, and the centres move by more than the cell's 1e-4."""
    calls = _use_path(impl, monkeypatch)
    rng = np.random.default_rng(7)
    X = rng.standard_normal((8192, 4)).astype(np.float32)
    w = np.ones(len(X), np.float32)
    C0 = X[rng.choice(len(X), 10, replace=False)]
    f32 = np.asarray(_run_lloyd(X, w, C0)[0])
    bf16 = np.asarray(_run_lloyd(X, w, C0, compute_dtype="bfloat16")[0])
    assert np.linalg.norm(bf16 - f32) / np.linalg.norm(f32) > 1e-4
    assert bool(calls) == (impl == "vpu_kernel")


def test_lloyd_reads_the_row_sharding_of_a_concrete_table(monkeypatch):
    """A caller that passes no ``rows`` (BisectingKMeans, PIC) gets its
    table's own sharding, and a traced table without one, in a process of
    more than one device, the MXU's pass that GSPMD partitions."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from orange3_spark_tpu.models import kmeans

    seen = []
    real = kmeans._vpu_pass
    monkeypatch.setattr(kmeans, "_vpu_pass", lambda *a: seen.append(a[-1])
                        or real(*a))
    X, w, C0 = _pass_case("blobs", n=8192)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    kmeans._lloyd(Xs, w, jnp.asarray(C0), jnp.float32(1e-4), k=10,
                  max_iter=3)
    kmeans._assign(jnp.asarray(X), jnp.asarray(C0), w)   # one device's
    split, whole = seen[0], seen[-1]
    assert kmeans._row_axis(split) == "data" and split.mesh == mesh
    assert kmeans._row_axis(whole) is None
    seen.clear()
    jax.jit(lambda X: kmeans._assign(X, jnp.asarray(C0), w))(Xs)
    assert seen == [] and jax.device_count() > 1


# ---------------------------------------------------------------------- PCA
def test_pca_matches_sklearn(session, iris):
    model = PCA(k=2).fit(iris)
    Z = model.transform(iris).to_numpy()[0]

    from sklearn.decomposition import PCA as SkPCA

    X = iris.to_numpy()[0]
    sk = SkPCA(n_components=2).fit(X)
    Zsk = sk.transform(X)
    # components are sign-ambiguous; compare |projections|
    for j in range(2):
        corr = np.corrcoef(Z[:, j], Zsk[:, j])[0, 1]
        assert abs(corr) > 0.999
    np.testing.assert_allclose(
        np.asarray(model.explained_variance),
        sk.explained_variance_ * (len(X) - 1) / len(X),  # population vs sample
        rtol=1e-3,
    )


def test_pca_transform_domain(session, iris):
    out = PCA(k=3).fit(iris).transform(iris)
    assert [v.name for v in out.domain.attributes] == ["PC1", "PC2", "PC3"]
    assert out.domain.class_var.name == "iris"  # class var preserved


def test_pca_k_too_large(session, iris):
    with pytest.raises(ValueError):
        PCA(k=10).fit(iris)


def test_kmeans_multi_init_beats_bad_seed(session, iris):
    """seed=0 single-init hits a local minimum on iris; n_init=3 escapes it."""
    single = KMeans(k=3, max_iter=100, seed=0).fit(iris)
    multi = KMeans(k=3, max_iter=100, seed=0, n_init=3).fit(iris)
    assert multi.training_cost_ <= single.training_cost_
    from sklearn.cluster import KMeans as SkKMeans

    X = iris.to_numpy()[0]
    sk = SkKMeans(n_clusters=3, n_init=10, random_state=0).fit(X)
    assert multi.training_cost_ <= sk.inertia_ * 1.01


def test_pca_explained_variance_ratio(session, iris):
    model = PCA(k=2).fit(iris)
    from sklearn.decomposition import PCA as SkPCA

    sk = SkPCA(n_components=2).fit(iris.to_numpy()[0])
    np.testing.assert_allclose(
        model.explained_variance_ratio_, sk.explained_variance_ratio_, rtol=1e-3
    )


def test_kmeans_constant_data_does_not_crash(session):
    X = np.ones((64, 3), dtype=np.float32)
    t = TpuTable.from_arrays(X, None, session=session)
    model = KMeans(k=3, max_iter=10, seed=0).fit(t)
    assert np.all(np.isfinite(model.cluster_centers_))


def test_fit_linear_max_iter_zero_finite_loss(session, iris):
    from orange3_spark_tpu.models.logistic_regression import LogisticRegression

    est = LogisticRegression(max_iter=0)
    model = est.fit(iris)
    assert model.n_iter_ == 0  # and final_loss must be finite (ln 3 at init)


def test_linear_regression_training_summary(session):
    """MLlib LinearRegressionTrainingSummary: r2/RMSE/MAE vs sklearn
    metrics, inference stats vs scipy.linregress exact OLS values."""
    from orange3_spark_tpu.models.linear_regression import LinearRegression

    rng = np.random.default_rng(4)
    n = 250
    x = rng.standard_normal(n).astype(np.float32)
    y = (1.2 * x + 0.4 * rng.standard_normal(n) - 0.7).astype(np.float32)
    t = TpuTable.from_arrays(x[:, None], y, session=session)
    m = LinearRegression(solver="normal", reg_param=0.0).fit(t)

    from scipy.stats import linregress
    from sklearn.metrics import mean_absolute_error, mean_squared_error, r2_score

    yhat = m.predict(t)
    np.testing.assert_allclose(float(m.r2_), r2_score(y, yhat), rtol=1e-4)
    np.testing.assert_allclose(float(m.root_mean_squared_error_),
                               np.sqrt(mean_squared_error(y, yhat)),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m.mean_absolute_error_),
                               mean_absolute_error(y, yhat), rtol=1e-4)

    ref = linregress(x, y)
    np.testing.assert_allclose(float(m.coefficient_standard_errors_[0]),
                               ref.stderr, rtol=2e-3)
    np.testing.assert_allclose(float(m.coefficient_standard_errors_[1]),
                               ref.intercept_stderr, rtol=2e-3)
    np.testing.assert_allclose(float(m.t_values_[0]),
                               ref.slope / ref.stderr, rtol=2e-3)
    np.testing.assert_allclose(float(m.p_values_[0]), ref.pvalue,
                               rtol=5e-2, atol=1e-12)

    # explainedVariance: Spark centers SSreg on the LABEL mean — pin the
    # through-origin case where prediction and label means differ
    m0 = LinearRegression(solver="normal", reg_param=0.0,
                          fit_intercept=False).fit(t)
    yhat0 = m0.predict(t)
    np.testing.assert_allclose(
        float(m0.explained_variance_),
        np.mean((yhat0 - y.mean()) ** 2), rtol=1e-4)

    # regularized or iterative fits: summary yes, inference stats no
    mr = LinearRegression(solver="normal", reg_param=0.05).fit(t)
    assert mr.r2_ is not None and mr.p_values_ is None
    ml = LinearRegression(solver="l-bfgs").fit(t)
    assert ml.r2_ is not None and ml.p_values_ is None


def test_logreg_summary_matches_sklearn(session):
    """model.summary (MLlib TrainingSummary role): metrics agree with
    sklearn on the same predictions."""
    from orange3_spark_tpu.models.logistic_regression import LogisticRegression

    rng = np.random.default_rng(5)
    n = 300
    X = rng.standard_normal((n, 3)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ [1.0, -1.0, 0.5])))
    y = (rng.random(n) < p).astype(np.float32)
    t = TpuTable.from_arrays(X, y, session=session)
    m = LogisticRegression(max_iter=100).fit(t)
    s = m.summary(t)

    from sklearn.metrics import accuracy_score, f1_score, roc_auc_score

    pred = m.predict(t)
    prob = m.predict_proba(t)[:, 1]
    np.testing.assert_allclose(s["accuracy"], accuracy_score(y, pred),
                               rtol=1e-5)
    np.testing.assert_allclose(s["f1"], f1_score(y, pred, average="weighted"),
                               rtol=1e-4)
    np.testing.assert_allclose(s["areaUnderROC"], roc_auc_score(y, prob),
                               rtol=1e-4)
    assert 0.5 < s["areaUnderPR"] <= 1.0
