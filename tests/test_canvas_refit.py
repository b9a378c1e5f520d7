"""The canvas scale -> PCA(4) -> KMeans(10) re-fitted as ONE staged program
(``workflow.staging.stage_graph(refit=True)``): the deployment
``nyc_taxi_canvas_pca4_km10`` of the benchmark at a small size on the CPU
mesh, through the benchmark's own job kind, against the plain float64
reference (``benchmark/reference/canvas_pca_kmeans.py``) and against the
eager widget walk.

Tolerances: against the reference, the cell's own limits
(``benchmark/cells/taxi_canvas_refit_staged.json``, set from chip readings
and a bfloat16 control); between two float32 runs of one algorithm (staged
against eager, refreshed models against the staged table) 2e-5 absolute on
standardised values of order 1 — reduction order, nothing else differs."""

import importlib

import jax
import numpy as np
import pytest

from benchmark import harness
from orange3_spark_tpu.obs import trace
from orange3_spark_tpu.obs.registry import REGISTRY

CELL = "taxi_canvas_refit_staged"
ROWS = 4096
F32_ATOL = 2e-5
SPANS = ("canvas_refit", "canvas_dispatch", "canvas_drain", "canvas_models")


def make_job(tmp, seed: int):
    spec = harness.load_cell(CELL, rehearse=True)
    config, traffic = spec["config"], spec["traffic"]
    config.update(rows=ROWS, template_rows=ROWS, sample_rows=1024)
    kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    job = kind.Job(config, traffic, seed, str(tmp))
    job.prepare()
    return job, spec["limits"]


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """(job, limits, its graph, one staged refit's table and states)."""
    job, limits = make_job(tmp_path_factory.mktemp("canvas"), 3_500_000_011)
    table, states = job.staged.run(replacements={job.src: job.table})
    graph_nodes = job.staged._refit_nodes
    return job, limits, graph_nodes, table, states


def counters() -> dict:
    get = REGISTRY.get
    return {
        "refits": get("otpu_canvas_refits_total").total(),
        "staged": get("otpu_canvas_dispatches_total").value(mode="staged"),
        "eager": get("otpu_canvas_dispatches_total").value(mode="eager"),
        "fallbacks": get("otpu_canvas_refit_fallbacks_total").total(),
        "iterations": get("otpu_kmeans_iterations_total").value(fit="staged"),
    }


@pytest.mark.parametrize("seed", [11, 2_400_000_019, 3_500_000_113])
def test_staged_refit_agrees_with_the_reference(seed, tmp_path):
    job, limits = make_job(tmp_path, seed)
    answer = job.run()["answer"]
    ref = job.reference_for_check()
    try:
        compared, ok = harness.judge(job.compare([answer], ref), limits)
    finally:
        ref["rows"].close()
    assert ok, compared
    assert answer["sizes"].sum() == ROWS and 1 <= answer["n_iter"] <= 20


def test_bfloat16_compute_fails_a_tolerance(tmp_path):
    job, limits = make_job(tmp_path, 11)
    ref = job.reference_for_check()
    try:
        compared, ok = harness.judge(job.reading("control_program", ref),
                                     limits)
    finally:
        ref["rows"].close()
    assert not ok, compared


def test_states_come_with_the_table_from_one_dispatch(fitted):
    job, _, _, table, states = fitted
    sc, pca, km = (states[job.nodes[n]] for n in ("scaler", "pca", "kmeans"))
    assert set(sc) == {"idxs", "shift", "scale"}
    assert set(pca) == {"components", "mean", "explained_variance",
                        "total_variance"}
    assert set(km) == {"centers", "cost", "n_iter", "init_centers",
                       "cluster_sizes"}
    assert km["centers"].shape == km["init_centers"].shape == (10, 4)
    assert table.X.shape == (ROWS, 5) and table.n_rows == ROWS
    # the state handed back is the state that produced the table
    scores, cluster = np.asarray(table.X[:, :4]), np.asarray(table.X[:, 4])
    d2 = ((scores[:, None, :] - np.asarray(km["centers"])[None]) ** 2).sum(-1)
    assert np.mean(cluster != d2.argmin(1)) < 1e-3
    np.testing.assert_array_equal(
        np.bincount(cluster.astype(int), minlength=10),
        np.asarray(km["cluster_sizes"]))


def test_staged_refit_equals_the_eager_run(fitted):
    """Scaler and PCA equal the eager widgets' fits of the same table; the
    eager KMeans seeds on the host, so it is the eager Lloyd from the
    staged fit's own initial centres that has to agree."""
    from orange3_spark_tpu.models.kmeans import _lloyd
    from orange3_spark_tpu.models.pca import PCA
    from orange3_spark_tpu.models.preprocess import StandardScaler

    job, _, _, _, states = fitted
    sc, pca, km = (states[job.nodes[n]] for n in ("scaler", "pca", "kmeans"))
    scaler = StandardScaler(**job.cfg["scaler"]).fit(job.table)
    scaled = scaler.transform(job.table)
    np.testing.assert_allclose(sc["shift"], scaler.shift, atol=F32_ATOL)
    np.testing.assert_allclose(sc["scale"], scaler.scale, rtol=1e-5)
    eager_pca = PCA(k=4).fit(scaled)
    np.testing.assert_allclose(pca["explained_variance"],
                               eager_pca.explained_variance, rtol=1e-4)
    flip = np.sign(np.sum(np.asarray(pca["components"])
                          * np.asarray(eager_pca.components), axis=0))
    np.testing.assert_allclose(np.asarray(pca["components"]) * flip,
                               eager_pca.components, atol=1e-3)
    # Lloyd in the staged fit's own basis, from its own initial centres
    from orange3_spark_tpu.models.pca import PCAModel

    staged_scores = PCAModel(eager_pca.params, pca["components"],
                             pca["mean"], pca["explained_variance"],
                             pca["total_variance"]).transform(scaled)
    centers, _, cost, n_iter = _lloyd(
        staged_scores.X, staged_scores.W, jax.numpy.copy(km["init_centers"]),
        jax.numpy.float32(job.km["tol"]), k=10, max_iter=20)
    np.testing.assert_allclose(centers, km["centers"], atol=F32_ATOL)
    assert int(n_iter) == int(km["n_iter"])
    np.testing.assert_allclose(float(cost), float(km["cost"]), rtol=1e-5)


def test_refreshed_model_ports_transform_to_the_staged_table(fitted):
    job, _, nodes, table, states = fitted
    t = job.table
    for name in ("scaler", "pca", "kmeans"):
        model = nodes[job.nodes[name]].outputs["model"]
        t = model.transform(t)
    np.testing.assert_allclose(np.asarray(t.X[:, :4]),
                               np.asarray(table.X[:, :4]), atol=F32_ATOL)
    assert np.mean(np.asarray(t.X[:, 4]) != np.asarray(table.X[:, 4])) < 1e-3
    km = nodes[job.nodes["kmeans"]].outputs["model"]
    assert km.n_iter_ == int(states[job.nodes["kmeans"]]["n_iter"])
    assert km.training_cost_ == pytest.approx(
        float(states[job.nodes["kmeans"]]["cost"]))


def test_one_dispatch_a_refit_zero_fallbacks_and_its_iterations(fitted):
    job = fitted[0]
    before = counters()
    answers = [job.run()["answer"] for _ in range(3)]
    after = counters()
    assert after["refits"] - before["refits"] == 3
    assert after["staged"] - before["staged"] == 3
    # the counter is the process's: another file's hostile widget may have
    # counted before this one under --dist loadfile; this job adds none
    assert after["fallbacks"] == before["fallbacks"]
    assert after["eager"] == before["eager"]
    assert after["iterations"] - before["iterations"] == sum(
        a["n_iter"] for a in answers)
    assert job.staged.refit_fallbacks == []


def test_eager_run_counts_a_dispatch_a_table_widget(fitted, session):
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph

    job = fitted[0]
    g = WorkflowGraph()
    src = g.add(OWTable(job.table))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    pca = g.add(WIDGET_REGISTRY["OWPCA"](k=4))
    g.connect(src, "data", sc, "data")
    g.connect(sc, "data", pca, "data")
    before = counters()
    g.run()
    after = counters()
    assert after["eager"] - before["eager"] == 2      # not the source
    assert after["staged"] == before["staged"]


@pytest.mark.parametrize("call", ["first", "second"])
def test_spans_once_a_call_under_one_trace_id(call, tmp_path):
    """``canvas_stage`` marks the (re)trace: in the first call only."""
    job, _ = make_job(tmp_path, 17)
    if call == "second":
        job.run()
    trace.clear()
    job.run()
    spans = [e for e in trace.events() if e[0] == "X"]
    by_name = {n: [e for e in spans if e[1] == n] for n in SPANS}
    assert all(len(v) == 1 for v in by_name.values()), {
        n: len(v) for n, v in by_name.items()}
    root = by_name["canvas_refit"][0]
    assert root[8] is None and root[6] is not None
    for n in SPANS[1:]:
        assert by_name[n][0][6] == root[6]          # one trace id
        assert by_name[n][0][8] == root[7]          # children of the root
    staged = [e for e in spans if e[1] == "canvas_stage"]
    assert len(staged) == (1 if call == "first" else 0)
    assert all(e[6] == root[6] for e in staged)


def test_refit_marks_its_spans_and_names_its_tables(tmp_path, monkeypatch):
    """The HBM account of a staged refit (obs/prof.py): a mark before the
    dispatch and one as each of its three spans closes; the resident table
    is a ``tables`` entry, the table handed back a ``canvas_out`` entry for
    as long as it lives, both known to a census by their arrays."""
    import gc

    from orange3_spark_tpu.obs import prof

    monkeypatch.setenv("OTPU_PROF", "1")
    led = prof.DeviceMemoryLedger()
    led.allocator = prof.LiveArraysAllocator()      # the CPU's tells nothing
    monkeypatch.setattr(prof, "LEDGER", led)
    job, _ = make_job(tmp_path, 11)
    before = len(led.snapshot()["marks"])
    table, _states = job.staged.run(replacements={job.src: job.table})
    snap = led.snapshot()
    assert [m["name"] for m in snap["marks"][before:]] == [
        "between_fits", "canvas_dispatch", "canvas_drain", "canvas_models"]
    out_bytes = prof.tree_chip_bytes((table.X, table.Y, table.W))
    assert snap["owners"]["canvas_out"] == out_bytes
    assert snap["owners"]["tables"] >= prof.tree_chip_bytes(
        (job.table.X, job.table.W))
    drain = snap["marks"][-2]
    assert drain["ledger_bytes"] - snap["marks"][-4]["ledger_bytes"] \
        == out_bytes
    led._take_census(led.mark("census"))
    census = led.snapshot()["census"]["owners"]
    assert census["canvas_out"] == out_bytes
    assert census["tables"] == snap["owners"]["tables"]
    del table
    gc.collect()
    assert "canvas_out" not in led.snapshot()["owners"]


def test_without_refit_a_staged_call_is_as_before(fitted):
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_graph

    job = fitted[0]
    g = WorkflowGraph()
    src = g.add(OWTable(job.table))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    g.connect(src, "data", sc, "data")
    staged = stage_graph(g, sc)
    before = counters()
    trace.clear()
    table, states = staged.run()
    assert states == {} and table.X.shape == (ROWS, 8)
    assert not [e for e in trace.events() if e[1] in SPANS]
    after = counters()
    assert after["refits"] == before["refits"]
    assert after["staged"] - before["staged"] == 1


@pytest.mark.parametrize("seed", [5, 23])
def test_reference_blocked_lloyd_equals_unblocked(seed):
    from benchmark.datagen import taxi
    from benchmark.reference import canvas_pca_kmeans as ref

    X = taxi.rows(3000, seed)
    fits = []
    for block_rows in (257, 3000):
        with ref.Rows(X, block_rows=block_rows) as rows:
            st = rows.fit_scaler_pca(4)
            rows.project(st, st["components"])
            init = rows.draw_init(10, seed)
            fits.append((st, rows.lloyd(init, max_iter=20, tol=1e-4),
                         rows.rows_at(np.arange(3000))[1]))
    (st0, fit0, a0), (st1, fit1, a1) = fits
    np.testing.assert_allclose(st1["cov"], st0["cov"], atol=1e-12)
    np.testing.assert_allclose(fit1["centers"], fit0["centers"], atol=1e-10)
    np.testing.assert_array_equal(fit1["sizes"], fit0["sizes"])
    np.testing.assert_array_equal(a1, a0)
    assert fit1["n_iter"] == fit0["n_iter"]
    assert fit1["cost"] == pytest.approx(fit0["cost"], rel=1e-12)


@pytest.mark.parametrize("n", [100, 4096, 4096 + 37])
def test_rows_dot_adds_block_products(n, monkeypatch, session):
    """Blocks of 256 rows here (2^14 in the program): whole blocks, the
    rows past the last one, a table of under two blocks, row-sharded
    operands and a vmapped caller (KMeans' restarts) all give A.T @ B."""
    from orange3_spark_tpu.ops import stats

    monkeypatch.setattr(stats, "ROW_BLOCK", 256)
    rng = np.random.default_rng(n)
    A = rng.standard_normal((n, 5)).astype(np.float32)
    B = (rng.standard_normal((n, 3)) + 2.0).astype(np.float32)
    want = A.astype(np.float64).T @ B.astype(np.float64)
    got = jax.jit(lambda a, b: stats.rows_dot(a, b))(A, B)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    if n % 8 == 0:
        rows = session.row_sharding
        got = jax.jit(lambda a, b: stats.rows_dot(a, b))(
            jax.device_put(A, rows), jax.device_put(B, rows))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    both = jax.jit(jax.vmap(lambda a: stats.rows_dot(a, B)))(
        np.stack([A, 2 * A]))
    np.testing.assert_allclose(both[1], 2 * want, rtol=1e-5, atol=2e-4)
