"""The job kind ``canvas_fit``: its three broken timed paths for
``test_correct.py`` (registered as this module is imported, which is before
any test runs: that module's ``FAULTS`` table is closed), its work function
by hand and against what the reference itself reads, its reference's
workers against one process, and the readers on a program without the
spans and counters.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import test_correct
from benchmark.work import canvas as work

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- the timed path broken underneath
def _lloyd_iteration_returns_its_centres(monkeypatch):
    """Lloyd's second iteration hands its centres on unchanged (the loop
    then sees no move and stops): ``_lloyd``'s own body, traced with a
    ``while_loop`` that keeps the carry's centres in that iteration."""
    import jax
    import jax.numpy as jnp

    from orange3_spark_tpu.models import kmeans

    raw = kmeans._lloyd.__wrapped__
    real_while = jax.lax.while_loop

    def second_unchanged(cond, body, init):
        def broken_body(carry):
            new = body(carry)
            return (jnp.where(carry[2] == 1, carry[0], new[0]),) + new[1:]
        return real_while(cond, broken_body, init)

    def broken(*args, **kwargs):
        with pytest.MonkeyPatch.context() as inner:
            inner.setattr(jax.lax, "while_loop", second_unchanged)
            return raw(*args, **kwargs)

    monkeypatch.setattr(kmeans, "_lloyd", broken)


def _half_table(monkeypatch):
    """The second half of the resident table's rows gets weight 0."""
    from orange3_spark_tpu.core.table import TpuTable

    real = TpuTable.from_numpy.__func__

    def broken(cls, domain, X, Y=None, metas=None, W=None, session=None):
        W = np.ones(X.shape[0], np.float32)
        W[X.shape[0] // 2:] = 0.0
        return real(cls, domain, X, Y, metas, W, session)

    monkeypatch.setattr(TpuTable, "from_numpy", classmethod(broken))


def _altered_centre(monkeypatch):
    """A centre altered where the fit's state is produced: the table was
    made with the true one."""
    import jax.numpy as jnp

    from orange3_spark_tpu.models import kmeans

    real = kmeans.KMeansModel.state_pytree.fget

    def broken(self):
        state = dict(real(self))
        state["centers"] = jnp.asarray(state["centers"]).at[0].multiply(1.05)
        return state

    monkeypatch.setattr(kmeans.KMeansModel, "state_pytree", property(broken))


test_correct.FAULTS.setdefault("canvas_fit", {
    "state_unchanged": _lloyd_iteration_returns_its_centres,
    "half_batch": _half_table,
    "answer_altered": _altered_centre,
})


# ---- the work function
def test_row_bytes_by_hand():
    # 8 columns read twice (32 + 32), the weight (4), 4 scores written
    # (16), 19 further iterations and the last assignment read them
    # (19 x 16 + 16), the cluster column written (4)
    assert work.row_bytes(8, 4, 20) == 32 + 4 + 32 + 16 + 19 * 16 + 16 + 4
    assert work.row_bytes(8, 4, 1) == 32 + 4 + 32 + 16 + 16 + 4
    w = work.job_work(rows=1 << 27, d=8, pca_k=4, k=10, iterations=20,
                      peaks=PEAKS)
    assert w["bound"] == "bytes" and w["bytes"] == (1 << 27) * 408
    assert w["job_least_s"] == pytest.approx((1 << 27) * 408 / 819e9)
    assert w["program_least_s"] == w["job_least_s"]


@pytest.mark.parametrize("iterations", [1, 7, 20])
def test_bound_is_under_what_the_reference_reads(iterations):
    """The reference (float64, the same algorithm written plainly) reads
    the 4-byte columns four times (sums, squares, the standardised rows'
    moments, projection), writes 8-byte scores and reads them once a Lloyd
    pass (iterations + the last assignment) and once for the initial
    centres: a lower bound lies under that at every iteration count."""
    d, p = 8, 4
    reference = 4 * 4 * d + 8 * p + 8 * p * (iterations + 1) + 8 * p + 1
    assert work.row_bytes(d, p, iterations) < reference


# ---- the reference's workers
def test_reference_workers_equal_one_process(tmp_path):
    from benchmark.datagen import taxi
    from benchmark.reference import canvas_pca_kmeans as ref

    where = ("taxi_test", 5000, 7, str(tmp_path))
    taxi.ensure_table(*where)
    X = np.load(taxi.table_path(*where), mmap_mode="r")
    fits = []
    for workers in (0, 2):
        with ref.Rows(X, fault="half_batch", block_rows=512,
                      workers=workers) as rows:
            st = rows.fit_scaler_pca(4)
            rows.project(st, st["components"])
            init = rows.draw_init(10, 3)
            assert rows.init_gap(init) < 1e-7
            fit = rows.lloyd(init, max_iter=20, tol=1e-4)
            fits.append((st, init, fit, rows.rows_at(np.arange(0, 5000, 7))))
    (st0, init0, fit0, at0), (st1, init1, fit1, at1) = fits
    np.testing.assert_allclose(st1["cov"], st0["cov"], rtol=0, atol=1e-12)
    np.testing.assert_array_equal(init1, init0)
    np.testing.assert_allclose(fit1["centers"], fit0["centers"], atol=1e-10)
    assert fit1["n_iter"] == fit0["n_iter"]
    np.testing.assert_array_equal(fit1["sizes"], fit0["sizes"])
    assert fit0["sizes"].sum() == 2500          # live rows only
    np.testing.assert_array_equal(at1[1], at0[1])
    np.testing.assert_array_equal(at1[2], at0[2])


# ---- the readers on a program without what they read
def test_readers_read_nothing_without_spans_or_counters(monkeypatch):
    import importlib

    from orange3_spark_tpu.obs import trace
    from orange3_spark_tpu.obs.registry import REGISTRY

    monkeypatch.setattr(trace, "events", lambda: [])
    monkeypatch.setattr(REGISTRY, "get", lambda name: None)
    run = {"jobs": [{}], "trace": None, "work": None, "traced_jobs": 1,
           "config": {}}
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == ["taxi_canvas_refit_staged"]]
    assert len(names) == 6
    for name in names:
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read(run) is None, name
