"""The (data 2, model 2) fit of a hashed table one chip cannot hold —
``criteo_1tb_svc_h30_2x2``, the benchmark's four-chip cell — at its
rehearsal size on four of the eight virtual CPU devices: the fit through
the benchmark's own job kind (``fit_stream`` on ``SPMDPartitioner``'s
session -> ``evaluate_device``) against the sharded plain reference and
against the one-device fit of the same chunks, where the three tables
stand, what the compiled step exchanges, what the device-memory ledger
counts, and the sharded reference against the one-device reference.

Tolerances: the fit's numbers are |program - reference| / |reference| as
the cell compares them; on XLA:CPU they read 2e-7 to 2e-6 (float32
summation order: the program sums a row's occurrences sorted, the
reference in chunk order), and the cell's limits on the chip are 2.8e-6
to 1e-4, so 1e-5 here holds the fit an order closer than any limit but
the holdout's. Layouts of ONE program differ by reduction order over
'data' only: 1e-6 absolute on weights of order 1e-2."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.obs import prof, trace
from orange3_spark_tpu.obs.registry import REGISTRY

CELL = "criteo_svc_h30_fit_replay8_2x2"
SEED = 2_800_000_031
FIT_TOL = 1e-5
LAYOUT_ATOL = 1e-6


def make_job(tmp, *, kind=None, **est):
    spec = harness.load_cell(CELL, rehearse=True)
    config, traffic = spec["config"], spec["traffic"]
    config["estimator"].update(est)
    mod = importlib.import_module(
        f"benchmark.jobs.{kind or traffic['job']}")
    job = mod.Job(config, traffic, SEED, str(tmp))
    job.prepare()
    return job


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("h30")


@pytest.fixture(scope="module")
def fits(data_dir):
    """(job, record of one job on the (2,2) mesh, its table on the host,
    where the tables stood) — the configuration as the cell runs it."""
    job = make_job(data_dir)
    assert dict(job.part.mesh.shape) == {"data": 2, "model": 2}
    record = job.run()
    specs = job.model.table_specs_
    job.take_last()
    return job, record, job.last_emb, specs


@pytest.fixture(scope="module")
def reference(fits):
    return fits[0].reference_for_check()


def test_fit_on_2x2_matches_the_sharded_reference(fits, reference):
    job, record, emb, _ = fits
    assert record["resolved"]["sparse_lowering"] == "sort"
    assert record["resolved"]["replay_source"] == "fused"
    numbers = job.compare([record["answer"]], reference, emb)
    assert set(numbers) == {"final_loss", "dense_leaf", "emb_slice",
                            "emb_table", "holdout_loss"}
    assert all(v <= FIT_TOL for v in numbers.values()), numbers
    assert record["answer"]["n_steps"] == 48


def test_fit_on_2x2_matches_the_one_device_fit(fits, data_dir):
    """The same chunks through the same entry points on ONE device (the
    one-chip cells' job kind, a one-device session)."""
    _, record, emb, _ = fits
    one = make_job(data_dir, kind="fit_stream")
    with TpuSession(TpuSession.default_mesh(jax.devices()[:1])).use():
        alone = one.run()
    assert one.model.theta["emb"].sharding.device_set == {jax.devices()[0]}
    one.take_last()
    np.testing.assert_allclose(emb, one.last_emb, rtol=0, atol=LAYOUT_ATOL)
    for k in ("coef", "intercept"):
        np.testing.assert_allclose(record["answer"][k], alone["answer"][k],
                                   rtol=0, atol=LAYOUT_ATOL)
    assert record["answer"]["final_loss"] == pytest.approx(
        alone["answer"]["final_loss"], rel=1e-5)
    assert record["answer"]["holdout_loss"] == pytest.approx(
        alone["answer"]["holdout_loss"], rel=1e-6)


# ------------------------------------------------------------- placement
@pytest.fixture(scope="module")
def stepped(fits):
    """The fit's own state after two steps on the (2,2) session."""
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator, _encode_chunk_np, _hashed_step,
        _init_fit_state, _put_encoded,
    )

    job = fits[0]
    session = job.part.session
    p = StreamingHashedLinearEstimator(epochs=1, **job.est_kw).params
    theta, opt, salts_np, salts, kw = _init_fit_state(p, session)
    rng = np.random.default_rng(5)
    rows = p.chunk_rows
    X = np.concatenate([
        (rng.random((rows, 1)) < 0.3).astype(np.float32),
        rng.standard_normal((rows, p.n_dense)).astype(np.float32),
        rng.integers(0, 1 << 20, (rows, p.n_cat)).astype(np.float32),
    ], axis=1)
    chunk = _put_encoded(_encode_chunk_np(kw["codec"], X, salts_np), session)
    one = jnp.zeros((1,), jnp.float32)
    args = (chunk, jnp.int32(rows), one, one, salts,
            jnp.float32(p.reg_param), jnp.float32(p.step_size))
    lowered = _hashed_step.donated.lower(theta, opt, *args, **kw)
    for _ in range(2):
        theta, opt, loss = _hashed_step(theta, opt, *args, **kw)
    assert np.isfinite(float(loss)) and int(opt["step"]) == 2
    tables = {"emb": theta["emb"], "acc": opt["slots"]["emb"]["acc"],
              "t": opt["t"]}
    return p, session, tables, lowered


@pytest.mark.parametrize("name", ["emb", "acc", "t"])
def test_table_holds_half_its_rows_per_model_shard(stepped, fits, name):
    p, session, tables, _ = stepped
    table = tables[name]
    assert table.sharding.spec[0] == "model"
    assert fits[3][name].startswith("PartitionSpec('model'")
    shards = {s.device: s for s in table.addressable_shards}
    assert len(shards) == 4
    mesh = session.mesh.devices                  # [data, model]
    whole = np.asarray(table)
    assert np.any(whole != 0)
    for m in range(2):
        rows = slice(m * p.n_dims // 2, (m + 1) * p.n_dims // 2)
        held = [np.asarray(shards[mesh[d, m]].data) for d in range(2)]
        assert held[0].shape[0] == p.n_dims // 2
        assert shards[mesh[0, m]].index[0] == rows
        # the same bytes on both `data` replicas, and they are these rows
        np.testing.assert_array_equal(held[0], held[1])
        np.testing.assert_array_equal(held[0], whole[rows])


def test_compiled_step_gathers_no_table(stepped):
    """What GSPMD makes of the step from the arguments' shardings alone:
    no operation whose result has the table's whole shape, so no
    all-gather of one; what is all-gathered over `data` is M-sized at
    most: the chunk's occurrence indices ahead of the key sort and the
    per-occurrence gradients ahead of the sort that carries them to
    sorted order (tests/test_tpu_compile.py reads the same off the v5e
    compile at 2^30)."""
    import re

    p, _session, _tables, lowered = stepped
    text = lowered.compile().as_text()
    assert " all-reduce(" in text
    assert not re.search(rf"= \(?\w+\[{p.n_dims}[,\]]", text)
    gathered = re.findall(r"= (\w+\[[\d,]*\])\S* all-gather(?:-start)?\(",
                          text)
    m = p.chunk_rows * p.n_cat
    assert set(gathered) <= {f"s32[{m}]", f"f32[{m}]"}, gathered


# ----------------------------------------------------------------- ledger
def test_ledger_reads_a_sharded_array_per_chip(stepped):
    _p, _session, tables, _ = stepped
    emb = tables["emb"]
    assert prof.tree_device_bytes(emb) == emb.nbytes
    assert prof.tree_chip_bytes(emb) == emb.nbytes // 2
    assert prof.tree_chip_bytes(tables) == sum(
        t.nbytes for t in tables.values()) // 2
    before, before_global = prof.LEDGER.total(), prof.LEDGER.snapshot()[
        "total_global_bytes"]
    prof.ledger_set_tree("model_state", "test-sharded", tables)
    try:
        assert prof.LEDGER.get("model_state", "test-sharded") == \
            prof.tree_chip_bytes(tables)
        assert prof.LEDGER.get("model_state", "test-sharded",
                               global_size=True) == 2 * prof.LEDGER.get(
            "model_state", "test-sharded")
        snap = prof.LEDGER.snapshot()
        assert snap["total_bytes"] - before == prof.tree_chip_bytes(tables)
        assert snap["total_global_bytes"] - before_global == \
            prof.tree_device_bytes(tables)
        assert snap["peak_global_bytes"] >= snap["peak_bytes"]
    finally:
        prof.ledger_release("model_state", "test-sharded")
    assert prof.LEDGER.total() == before
    assert prof.LEDGER.snapshot()["total_global_bytes"] == before_global


def test_ledger_reads_an_unsharded_array_as_before(stepped):
    _p, session, _tables, _ = stepped
    on_one = jnp.ones((1024, 3), jnp.float32)
    everywhere = jax.device_put(np.ones((1024, 3), np.float32),
                                session.replicated)
    host = np.ones((7,), np.float64)
    for tree in (on_one, everywhere, host, {"a": on_one, "b": [host, 5]}):
        assert prof.tree_chip_bytes(tree) == prof.tree_device_bytes(tree)
    before = prof.LEDGER.total()
    prof.ledger_set("cache_chunks", "test-plain", 12345)
    try:
        assert prof.LEDGER.get("cache_chunks", "test-plain") == 12345
        assert prof.LEDGER.get("cache_chunks", "test-plain",
                               global_size=True) == 12345
        assert prof.LEDGER.total() == before + 12345
    finally:
        prof.ledger_release("cache_chunks", "test-plain")


def test_fit_says_what_mesh_it_runs_on(fits):
    """One `mesh` event a fit, on the fit's own trace, and the gauge of
    the fit started last."""
    def mesh_events():
        return [e for e in trace.events() if e[0] == "i" and e[1] == "mesh"]

    n = len(mesh_events())
    fits[0].run()
    events = mesh_events()
    assert len(events) == n + 1
    args = events[-1][5]
    assert (args["data"], args["model"]) == (2, 2)
    assert args["emb"] == "PartitionSpec('model', None)"
    assert args["acc"] == "PartitionSpec('model', None)"
    assert args["t"] == "PartitionSpec('model',)"
    assert events[-1][6] is not None             # carries the fit's trace id
    gauge = REGISTRY.get("otpu_mesh_devices")
    assert gauge.value(axis="data") == 2 and gauge.value(axis="model") == 2


def test_obs_off_changes_no_answer(fits, data_dir, monkeypatch):
    _, record, emb, _ = fits
    monkeypatch.setenv("OTPU_OBS", "0")
    job = make_job(data_dir)
    off = job.run()
    job.take_last()
    monkeypatch.delenv("OTPU_OBS")
    trace.refreshed_enabled()
    np.testing.assert_array_equal(job.last_emb, emb)
    for k in ("coef", "intercept", "emb_slice"):
        np.testing.assert_array_equal(off["answer"][k], record["answer"][k])
    assert off["answer"]["final_loss"] == record["answer"]["final_loss"]
    assert off["answer"]["holdout_loss"] == record["answer"]["holdout_loss"]


# ------------------------------------------------- the reference, sharded
def test_sharded_reference_equals_the_one_device_reference(fits, reference):
    """Per table row the additions happen in the same order on the device
    that owns the row as on one device, a gathered row is one non-zero
    term plus zeros, and everything else is computed replicated: equal to
    the bit on XLA:CPU."""
    from benchmark.reference import hashed_linear

    job = fits[0]
    alone = hashed_linear.fit(
        (job._chunk, job.n_chunks), n_dims=job.n_dims,
        n_dense=job.est_kw["n_dense"], epochs=job.epochs,
        holdout_chunks=job.holdout_chunks,
        step_size=job.est_kw["step_size"],
        reg_param=job.est_kw["reg_param"], loss=job.est_kw["loss"])
    assert len(reference["emb"].sharding.device_set) == 4
    assert reference["emb"].sharding.shard_shape(
        reference["emb"].shape) == (job.n_dims // 4,)
    np.testing.assert_array_equal(np.asarray(reference["emb"]),
                                  np.asarray(alone["emb"]))
    for k in ("coef", "intercept"):
        np.testing.assert_array_equal(reference[k], alone[k])
    for k in ("final_loss", "holdout_loss", "holdout_accuracy"):
        assert reference[k] == alone[k], k
