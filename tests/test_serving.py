"""serve/ subsystem tests: bucket ladder, AOT cache, padding parity,
recompile-regression guard, micro-batcher, and the mask-based pad strip.

Parity contract (docs/serving.md §1): a served call's live-row outputs
are BITWISE equal to (a) a second served call of the same bucket and
(b) the unserved call run AT THE BUCKET SHAPE — serve's pad rows perturb
nothing — and within ``SERVED_ULP`` ulp (at unit scale) of the unserved
call at its own shape. XLA does not promise the same arithmetic across
shapes: measured raw-vs-raw with serve/ nowhere in the loop, the unserved
program at 40 rows (5 per device on the 8-device test mesh) and at 64
rows (8 per device) differ by one ulp in softmax/projection outputs under
jax 0.9.0, as the one-row-per-device shape always did. Discrete outputs
(labels, cluster ids) stay bitwise against the exact-shape path.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from orange3_spark_tpu.core.domain import (
    ContinuousVariable, DiscreteVariable, Domain,
)
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.models.kmeans import KMeans
from orange3_spark_tpu.models.logistic_regression import LogisticRegression
from orange3_spark_tpu.models.pca import PCA
from orange3_spark_tpu.serve import (
    BucketLadder, ExecutableCache, ServingContext, active_serving_context,
)
from orange3_spark_tpu.serve.context import _fingerprint
from orange3_spark_tpu.utils.profiling import (
    reset_serve_counters, serve_counters,
)


# --------------------------------------------------------------- helpers
def _host(a):
    return np.asarray(jax.device_get(a))


def _subtable(table, n, session):
    X = _host(table.X)[:n]
    Y = _host(table.Y)[:n] if table.Y is not None else None
    return TpuTable.from_numpy(table.domain, X, Y, session=session)


#: float tolerance of a served call against the unserved call at its own
#: (different) shape, in float32 ulp at the scale max(|x|, 1)
SERVED_ULP = 2


def _assert_served_parity(served, raw_at_bucket, raw_exact):
    np.testing.assert_array_equal(served, raw_at_bucket)
    tol = SERVED_ULP * np.finfo(np.float32).eps
    np.testing.assert_allclose(served, raw_exact, rtol=tol, atol=tol)


def _bucket_padded(table, n, n_pad, session):
    """The raw path's view of a bucket-padded batch: zero rows with W=0
    appended up to ``n_pad`` — built WITHOUT serve/ so it can referee."""
    X = np.zeros((n_pad, table.n_attrs), np.float32)
    X[:n] = _host(table.X)[:n]
    Y = None
    if table.Y is not None:
        Y = np.zeros((n_pad, table.Y.shape[1]), np.float32)
        Y[:n] = _host(table.Y)[:n]
    W = np.zeros(n_pad, np.float32)
    W[:n] = 1.0
    return TpuTable.from_numpy(table.domain, X, Y, None, W, session)


@pytest.fixture(scope="module")
def models(session, iris):
    return {
        "logreg": LogisticRegression(max_iter=50).fit(iris),
        "kmeans": KMeans(k=3, seed=0).fit(iris),
        "pca": PCA(k=2).fit(iris),
    }


# ---------------------------------------------------------- bucket ladder
def test_ladder_pow2_rungs_and_lookup():
    lad = BucketLadder(min_bucket=256, max_bucket=4096)
    assert lad.buckets() == (256, 512, 1024, 2048, 4096)
    assert lad.bucket_for(1) == 256
    assert lad.bucket_for(256) == 256
    assert lad.bucket_for(257) == 512
    assert lad.bucket_for(4096) == 4096
    assert lad.bucket_for(4097) is None  # serve bypass above the ladder


def test_ladder_fixed_and_none_modes():
    fixed = BucketLadder(min_bucket=64, mode="fixed", fixed_step=64,
                         max_bucket=256)
    assert fixed.buckets() == (64, 128, 192, 256)
    assert fixed.bucket_for(1) == 64
    assert fixed.bucket_for(65) == 128
    assert fixed.bucket_for(192) == 192
    none = BucketLadder(min_bucket=1, mode="none", max_bucket=100)
    assert none.buckets() == ()
    assert none.bucket_for(37) == 37
    assert none.bucket_for(101) is None


def test_ladder_validation():
    with pytest.raises(ValueError, match="mode"):
        BucketLadder(mode="log10")
    with pytest.raises(ValueError, match="min_bucket"):
        BucketLadder(min_bucket=512, max_bucket=256)
    with pytest.raises(ValueError, match="fixed_step"):
        BucketLadder(mode="fixed", fixed_step=0)


# ------------------------------------------------------------- AOT cache
def test_cache_lru_eviction_and_counters():
    reset_serve_counters()
    cache = ExecutableCache(max_entries=2)
    built = []

    def builder(k):
        def build():
            built.append(k)
            return k
        return build

    assert cache.get_or_build("a", builder("a")) == "a"
    assert cache.get_or_build("b", builder("b")) == "b"
    assert cache.get_or_build("a", builder("a")) == "a"   # hit, refreshes a
    assert cache.get_or_build("c", builder("c")) == "c"   # evicts b (LRU)
    assert "b" not in cache and "a" in cache
    assert cache.get_or_build("b", builder("b")) == "b"   # rebuild
    assert built == ["a", "b", "c", "b"]
    c = serve_counters()
    assert c["aot_hits"] == 1
    assert c["aot_misses"] == 4
    assert c["aot_evictions"] == 2       # b then a fell out


def test_cache_build_serialized_across_threads():
    cache = ExecutableCache(max_entries=4)
    builds = []

    def build():
        builds.append(threading.get_ident())
        return "x"

    with ThreadPoolExecutor(8) as ex:
        out = list(ex.map(lambda _: cache.get_or_build("k", build), range(16)))
    assert out == ["x"] * 16
    assert len(builds) == 1   # two racing first requests pay ONE compile


def test_cache_build_does_not_block_other_keys():
    """Build serialization is per KEY: one model's multi-second compile
    must not head-of-line-block hits (or builds) on other keys."""
    cache = ExecutableCache(max_entries=4)
    started, release = threading.Event(), threading.Event()

    def slow_build():
        started.set()
        assert release.wait(5), "slow build never released"
        return "slow"

    with ThreadPoolExecutor(1) as ex:
        slow = ex.submit(cache.get_or_build, "cold", slow_build)
        assert started.wait(5)
        # while 'cold' is compiling, another key builds and hits freely
        assert cache.get_or_build("warm", lambda: "w") == "w"
        assert cache.get_or_build("warm", lambda: "nope") == "w"
        release.set()
        assert slow.result(timeout=5) == "slow"
    assert "cold" in cache and "warm" in cache


def test_lru_eviction_releases_model_pins(session, iris):
    """The pins follow the LRU: once a model's last cached executable is
    evicted, the context drops its record (and fingerprint-keyed state)
    instead of pinning the retired model forever."""
    m1 = LogisticRegression(max_iter=5).fit(iris)
    m2 = LogisticRegression(max_iter=5).fit(iris)
    t = _subtable(iris, 9, session)
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=64),
                        max_entries=1) as ctx:
        m1.predict(t)
        fp1 = _fingerprint(m1)
        assert any(r.fingerprint == fp1 for r in ctx._records.values())
        m2.predict(t)   # its build evicts m1's only executable
        assert not any(r.fingerprint == fp1 for r in ctx._records.values())


def test_state_hot_reload_keys_fresh_executables(session, iris):
    """An in-place checkpoint reload (load_state_pytree) moves the model's
    serving fingerprint, so cached executables with the OLD weights baked
    in cannot keep serving."""
    m_good = LogisticRegression(max_iter=200, reg_param=1e-4).fit(iris)
    m = LogisticRegression(max_iter=2, reg_param=1.0).fit(iris)
    t = _subtable(iris, 33, session)
    good = np.asarray(m_good.predict(t))
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
        served_old = np.asarray(m.predict(t))    # caches m's executables
        m.load_state_pytree(m_good.state_pytree)
        served_new = np.asarray(m.predict(t))
    assert not np.array_equal(served_new, served_old) or np.array_equal(
        served_old, good)
    np.testing.assert_array_equal(served_new, good)


# --------------------------------------------------------- padding parity
# Four sizes span the ladder (the tiny-pad boundary, two interior buckets,
# the full table) — enough to catch any per-bucket divergence while keeping
# the suite's XLA-compile bill inside the tier-1 wall budget. 33 is the one
# whose natural pad (40) and bucket (64) lower to different arithmetic.
SIZES = (9, 33, 64, 150)
LADDER = dict(min_bucket=16, max_bucket=4096)


@pytest.mark.parametrize("n", SIZES)
def test_parity_logreg_predict_bitwise(session, iris, models, n):
    model = models["logreg"]
    t = _subtable(iris, n, session)
    raw = np.asarray(model.predict(t))
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
        served = np.asarray(model.predict(t))
    np.testing.assert_array_equal(served, raw)


@pytest.mark.parametrize("n", SIZES)
def test_parity_logreg_transform_bitwise(session, iris, models, n):
    model = models["logreg"]
    t = _subtable(iris, n, session)
    raw = model.transform(t)
    bucket = BucketLadder(**LADDER).bucket_for(n)
    raw_b = model.transform(_bucket_padded(iris, n, bucket, session))
    with ServingContext(BucketLadder(**LADDER)):
        served = model.transform(t)
        again = model.transform(t)
    assert served.n_rows == n
    assert [v.name for v in served.domain.attributes] \
        == [v.name for v in raw.domain.attributes]
    np.testing.assert_array_equal(_host(again.X)[:n], _host(served.X)[:n])
    _assert_served_parity(_host(served.X)[:n], _host(raw_b.X)[:n],
                          _host(raw.X)[:n])


@pytest.mark.parametrize("n", SIZES)
def test_parity_kmeans_predict_bitwise(session, iris, models, n):
    model = models["kmeans"]
    t = _subtable(iris, n, session)
    raw = np.asarray(model.predict(t))
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
        served = np.asarray(model.predict(t))
    np.testing.assert_array_equal(served, raw)


@pytest.mark.parametrize("n", SIZES)
def test_parity_pca_transform_bitwise(session, iris, models, n):
    model = models["pca"]
    t = _subtable(iris, n, session)
    raw = model.transform(t)
    bucket = BucketLadder(**LADDER).bucket_for(n)
    raw_b = model.transform(_bucket_padded(iris, n, bucket, session))
    with ServingContext(BucketLadder(**LADDER)):
        served = model.transform(t)
        again = model.transform(t)
    np.testing.assert_array_equal(_host(again.X)[:n], _host(served.X)[:n])
    _assert_served_parity(_host(served.X)[:n], _host(raw_b.X)[:n],
                          _host(raw.X)[:n])


def test_parity_tiny_batch_vs_bucket_shape(session, iris, models):
    """n <= 8 (global pad 8: one row per device): parity referees against
    the raw path AT THE BUCKET SHAPE, pinning that serve's pad rows perturb
    nothing (module docstring)."""
    model = models["logreg"]
    n, bucket = 5, 16
    t = _subtable(iris, n, session)
    ref_t = _bucket_padded(iris, n, bucket, session)
    raw_p = np.asarray(model.predict(ref_t))[:n]
    raw_x = _host(model.transform(ref_t).X)[:n]
    with ServingContext(BucketLadder(min_bucket=bucket, max_bucket=4096)):
        np.testing.assert_array_equal(np.asarray(model.predict(t)), raw_p)
        np.testing.assert_array_equal(
            _host(model.transform(t).X)[:n], raw_x)


def test_parity_hashed_linear_array_path(session):
    """hashed_linear serves through ``served_array`` (state as arguments,
    not jit constants): logits/predict bitwise across mixed batch sizes."""
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(3)
    n, nd, nc = 600, 3, 2
    Xall = np.concatenate(
        [rng.normal(size=(n, nd)).astype(np.float32),
         rng.integers(0, 50, size=(n, nc)).astype(np.float32)], axis=1)
    y = (Xall[:, 0] > 0.2).astype(np.float32)
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=nd, n_cat=nc, epochs=2, chunk_rows=256,
    ).fit_stream(array_chunk_source(Xall, y, chunk_rows=256),
                 session=session)
    sizes = (9, 77, 256, 600)
    raws = {k: model.predict(Xall[:k]) for k in sizes}   # no context: raw
    with ServingContext(BucketLadder(min_bucket=64, max_bucket=2048)):
        for k in sizes:
            np.testing.assert_array_equal(model.predict(Xall[:k]), raws[k])


def test_parity_hookless_model_pads_through_raw(session, iris):
    """A model without a ``_device_predict`` hook (random forest) still
    buckets: serve pads the TABLE so the model's internal jits cache per
    bucket shape, and outputs stay bitwise (trees are row-wise)."""
    from orange3_spark_tpu.models.random_forest import RandomForestClassifier

    model = RandomForestClassifier(num_trees=5, max_depth=4, seed=0).fit(iris)
    for k in (9, 150):
        t = _subtable(iris, k, session)
        raw = np.asarray(model.predict(t))
        with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
            served = np.asarray(model.predict(t))
        np.testing.assert_array_equal(served, raw)


# ------------------------------------------------- recompile regression
def test_served_predict_compiles_at_most_once_per_bucket(
        session, iris, models, xla_compiles):
    """THE recompile-regression guard: a mixed-size request trace through
    the served predict path compiles at most one executable per touched
    bucket — and a repeat of the trace compiles NOTHING."""
    model = models["logreg"]
    tables = [_subtable(iris, k, session) for k in (9, 20, 33, 60, 90, 150)]
    for t in tables:
        model.predict(t)   # raw-path jits compile outside the counted window
    with ServingContext(BucketLadder(min_bucket=32, max_bucket=256)) as ctx:
        buckets = {ctx.ladder.bucket_for(t.n_rows) for t in tables}
        c0 = xla_compiles()
        for t in tables:
            model.predict(t)
        first_pass = xla_compiles() - c0
        assert first_pass <= len(buckets), (
            f"{first_pass} compiles for {len(buckets)} buckets")
        c1 = xla_compiles()
        for t in tables:
            model.predict(t)
        assert xla_compiles() - c1 == 0, "repeat trace recompiled"


def test_warmup_precompiles_ladder(session, iris, models, xla_compiles):
    model = models["logreg"]
    template = _subtable(iris, 9, session)
    with ServingContext(BucketLadder(min_bucket=32, max_bucket=128)) as ctx:
        r = ctx.warmup(model, template)
        # 3 rungs x (transform + predict)
        assert r == {"compiled": 6, "buckets": [32, 64, 128]}
        c0 = xla_compiles()
        for k in (9, 33, 100):
            model.predict(_subtable(iris, k, session))
            model.transform(_subtable(iris, k, session))
        assert xla_compiles() - c0 == 0, "warmed bucket recompiled"


def test_served_transform_keys_on_domain(session, iris, models):
    """Two same-shape tables with DIFFERENT domains must not share a
    cached transform executable: the output domain is derived from the
    input domain at build time, so a key without the domain would stamp
    the second table's output with the first table's column metadata."""
    model = models["logreg"]
    t1 = _subtable(iris, 33, session)
    d2 = Domain(
        [ContinuousVariable(v.name + "_r") for v in iris.domain.attributes],
        iris.domain.class_vars,
    )
    t2 = TpuTable.from_numpy(d2, _host(iris.X)[:33], _host(iris.Y)[:33],
                             session=session)
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=256)):
        o1 = model.transform(t1)
        o2 = model.transform(t2)
    n_in = len(iris.domain.attributes)
    assert [v.name for v in o1.domain.attributes[:n_in]] \
        == [v.name for v in iris.domain.attributes]
    assert [v.name for v in o2.domain.attributes[:n_in]] \
        == [v.name + "_r" for v in iris.domain.attributes]


def test_microbatch_group_key_separates_labeled_requests():
    """A labeled (Y present) and an unlabeled predict on the same model
    must not merge — their row blocks cannot concatenate."""
    from orange3_spark_tpu.serve.microbatch import _Request

    class Rec:
        fingerprint = ("M", 1)

    X = np.zeros((4, 3), np.float32)
    W = np.ones(4, np.float32)
    Y = np.zeros((4, 1), np.float32)
    labeled = _Request("predict", Rec(), (X, Y, W), 4, ("s", None, X.dtype))
    unlabeled = _Request("predict", Rec(), (X, None, W), 4,
                         ("s", None, X.dtype))
    same = _Request("predict", Rec(), (X + 1, Y + 1, W), 4,
                    ("s", None, X.dtype))
    assert labeled.group_key != unlabeled.group_key
    assert labeled.group_key == same.group_key


def test_oversized_batch_bypasses_serving(session, iris, models):
    """Requests above max_bucket run the raw path untouched (the d2h pad
    round-trip would dominate; the raw path amortizes its own compile)."""
    model = models["logreg"]
    t = _subtable(iris, 150, session)
    reset_serve_counters()
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=64)):
        raw_equal = np.asarray(model.predict(t))
    c = serve_counters()
    assert c["request_rows"] == 0 and c["aot_misses"] == 0
    np.testing.assert_array_equal(raw_equal, np.asarray(model.predict(t)))


# ----------------------------------------------------------- micro-batch
def test_microbatch_coalesces_and_scatters(session, iris, models):
    model = models["logreg"]
    tables = [_subtable(iris, k, session) for k in (9, 17, 25)]
    refs = [np.asarray(model.predict(t)) for t in tables]
    reset_serve_counters()
    with ServingContext(BucketLadder(min_bucket=64, max_bucket=4096),
                        micro_batch=True, max_batch=4096, max_wait_ms=50.0):
        with ThreadPoolExecutor(12) as ex:
            outs = list(ex.map(
                lambda t: np.asarray(model.predict(t)), tables * 4))
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, refs[i % 3])
    c = serve_counters()
    assert c["mb_requests"] == 12
    assert 1 <= c["mb_batches"] < c["mb_requests"], (
        f"no coalescing: {c['mb_batches']} batches "
        f"for {c['mb_requests']} requests")


def test_microbatch_oversized_request_direct_dispatches(
        session, iris, models):
    model = models["logreg"]
    t = _subtable(iris, 100, session)
    raw = np.asarray(model.predict(t))
    reset_serve_counters()
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096),
                        micro_batch=True, max_batch=32):
        served = np.asarray(model.predict(t))   # 100 > max_batch: direct
    np.testing.assert_array_equal(served, raw)
    assert serve_counters()["mb_requests"] == 0


def test_unservable_model_falls_back_and_blacklists(session, iris):
    """A predict hook that cannot trace device-pure must fall back to the
    raw path (same answer, no exception) and be blacklisted so later
    requests skip the doomed build."""

    from orange3_spark_tpu.models.base import Model

    class BadHook(Model):
        def __init__(self, inner):
            self.inner = inner
            self.params = inner.params

        def _device_predict(self, table):
            raise RuntimeError("not device-pure")   # build must fail

        def predict(self, table):
            return self.inner.predict.__serve_raw__(self.inner, table)

    inner = LogisticRegression(max_iter=20).fit(iris)
    model = BadHook(inner)
    t = _subtable(iris, 33, session)
    want = np.asarray(inner.predict(t))
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)) as ctx:
        got = np.asarray(model.predict(t))
        np.testing.assert_array_equal(got, want)
        assert any(kind == "predict" for _, kind in ctx._unservable)
        # second call takes the blacklist short-circuit, same answer
        np.testing.assert_array_equal(np.asarray(model.predict(t)), want)


# ------------------------------------------------------- context plumbing
def test_context_stack_nesting(session):
    assert active_serving_context() is None
    a, b = ServingContext(), ServingContext()
    with a:
        assert active_serving_context() is a
        with b:
            assert active_serving_context() is b   # innermost wins
        assert active_serving_context() is a
    assert active_serving_context() is None


def test_staged_graph_shares_executable_cache(session, iris):
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_transform_path

    g = WorkflowGraph()
    src = g.add(OWTable(iris))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    lr = g.add(WIDGET_REGISTRY["OWLogisticRegression"](max_iter=30))
    g.connect(src, "data", sc, "data")
    g.connect(sc, "data", lr, "data")
    g.run()
    staged = stage_transform_path(g, src, lr)
    raw = staged(iris)
    reset_serve_counters()
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
        s1 = staged(iris)
        s2 = staged(iris)
    np.testing.assert_array_equal(_host(s1.X), _host(raw.X))
    np.testing.assert_array_equal(_host(s2.X), _host(raw.X))
    c = serve_counters()
    assert c["aot_misses"] == 1 and c["aot_hits"] == 1


def test_staged_graph_first_lowered_inside_context(session, iris):
    """Regression: the staged AOT build traces the fused program, whose
    serve-wrapped stage transforms must NOT re-enter routing — a tracer-
    backed table in served_transform raises TracerArrayConversionError.
    Unlike the test above, the staged program's FIRST call (and therefore
    its first lowering) happens with the context already active."""
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_transform_path

    g = WorkflowGraph()
    src = g.add(OWTable(iris))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    lr = g.add(WIDGET_REGISTRY["OWLogisticRegression"](max_iter=30))
    g.connect(src, "data", sc, "data")
    g.connect(sc, "data", lr, "data")
    g.run()
    staged = stage_transform_path(g, src, lr)
    with ServingContext(BucketLadder(min_bucket=16, max_bucket=4096)):
        s1 = staged(iris)          # cold: lowering happens in-context
    raw = staged(iris)
    np.testing.assert_array_equal(_host(s1.X), _host(raw.X))


# ------------------------------------------------- mask-based pad stripping
def test_predictions_to_numpy_strips_by_validity_mask(session):
    """The satellite fix: a serving-bucketed table whose caller did NOT
    track logical rows (n_rows == n_pad) still strips its trailing
    zero-weight pad run; interior zero-weight (filtered) rows survive."""
    from orange3_spark_tpu.models.base import predictions_to_numpy

    domain = Domain([ContinuousVariable("prediction")],
                    DiscreteVariable("y", ("0", "1")))
    n_pad, n_live = 16, 10
    X = np.arange(n_pad, dtype=np.float32)[:, None]
    W = np.zeros(n_pad, np.float32)
    W[:n_live] = 1.0
    W[3] = 0.0     # interior filtered row: LOGICAL, must be kept
    t = TpuTable.from_numpy(domain, X, np.zeros(n_pad, np.float32),
                            None, W, session)
    # simulate the untracked-count serving table: n_rows == n_pad
    t = TpuTable(t.domain, t.X, t.Y, t.W, t.metas, t.n_pad, t.session)
    out = predictions_to_numpy(t)
    np.testing.assert_array_equal(out, X[:n_live, 0])

    # caller DID track rows (n_rows < n_pad): n_rows slicing wins, and
    # zero-weight rows INSIDE the logical range are kept as ever
    t2 = TpuTable.from_numpy(domain, X[:12], np.zeros(12, np.float32),
                             None, W[:12], session)
    assert t2.n_rows < t2.n_pad
    out2 = predictions_to_numpy(t2)
    assert out2.shape[0] == t2.n_rows == 12


def test_predictions_to_numpy_all_masked(session):
    from orange3_spark_tpu.models.base import predictions_to_numpy

    domain = Domain([ContinuousVariable("prediction")])
    t = TpuTable.from_numpy(domain, np.ones((8, 1), np.float32),
                            None, None, np.zeros(8, np.float32), session)
    t = TpuTable(t.domain, t.X, t.Y, t.W, t.metas, t.n_pad, t.session)
    assert predictions_to_numpy(t).shape == (0,)
