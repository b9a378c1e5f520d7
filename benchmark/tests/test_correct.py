"""``correct`` has to come out false where it should (CPU, the files' own
rehearsal sizes; the benchmark's runs never run these).

- The control: the program with its own lower-precision path switched on
  (``compute_dtype`` = the configuration's ``control_precision``), put
  through the comparison a run makes, fails at least one limit of the cell;
  the program as the configuration states it passes them all.
- The faults: a whole run of the harness (``--rehearse`` skips only the
  look for a chip) with the timed path broken underneath — a step that
  returns its state unchanged; half of every batch left out, the mean taken
  over the rest; an answer altered where it is produced — prints
  ``"correct": false``. (One chip: there is no exchange to leave out.)

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json

import pytest

from benchmark import harness

SEED = 2_400_000_019


def cells() -> dict:
    """workload -> job kind, for every cell of BENCHMARK.json and every
    cell kept out of it that still has its files (``harness.load_bench``)."""
    bench = harness.load_bench()
    return {w["name"]: harness.read_json(
        harness.HERE, "traffic", w["traffic"] + ".json")["job"]
        for w in bench["workloads"]}


CELLS = cells()


def run_harness(workload: str, capsys) -> dict:
    rc = harness.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", "0.2", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path))
    return str(tmp_path)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload, data_dir, capsys):
    line = run_harness(workload, capsys)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert "metrics" not in line          # no device metric from a CPU run


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct(workload, data_dir):
    import importlib

    spec = harness.load_cell(workload, rehearse=True)
    config, traffic = spec["config"], spec["traffic"]
    kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    job = kind.Job(config, traffic, SEED, data_dir)
    job.prepare()
    ref = job.reference_for_check()
    mode = ("control_program" if "control_program" in job.modes
            else "control_reference")
    _, ok = harness.judge(job.reading(mode, ref), spec["limits"])
    assert not ok
    _, ok = harness.judge(job.reading("program", ref), spec["limits"])
    assert ok


# ---- the timed path broken underneath, by job kind
def _skip_second_step(monkeypatch):
    import jax.numpy as jnp

    from orange3_spark_tpu.models import hashed_linear as hl

    real = hl._hashed_step

    def broken(theta, opt_state, *a, **kw):
        if int(opt_state["step"]) == 1:       # every job's second step
            return theta, opt_state, jnp.float32(0.0)
        return real(theta, opt_state, *a, **kw)

    monkeypatch.setattr(hl, "_hashed_step", broken)


def _half_chunks(monkeypatch):
    from benchmark.jobs import fit_stream

    real = fit_stream.Job._source

    def broken(self):
        inner = real(self)
        return lambda: (c[:c.shape[0] // 2] for c in inner())

    monkeypatch.setattr(fit_stream.Job, "_source", broken)


def _altered_coef(monkeypatch):
    from orange3_spark_tpu.models import hashed_linear as hl

    real = hl.StreamingHashedLinearEstimator.fit_stream

    def broken(self, *a, **kw):
        model = real(self, *a, **kw)
        theta = dict(model.theta)
        theta["coef"] = theta["coef"].at[0].multiply(1.05)
        model.theta = theta
        return model

    monkeypatch.setattr(hl.StreamingHashedLinearEstimator, "fit_stream",
                        broken)


def _skip_second_round(monkeypatch):
    from orange3_spark_tpu.models import gbt

    real, calls = gbt._gbt_round, {"n": 0}

    def broken(F, *a, **kw):
        calls["n"] += 1
        F_new, tree, imp = real(F, *a, **kw)
        second = calls["n"] % kw["p"].max_iter == 2
        return (F if second else F_new), tree, imp

    monkeypatch.setattr(gbt, "_gbt_round", broken)


def _half_table(monkeypatch):
    """The second half of every training table's rows gets weight 0."""
    import numpy as np

    from orange3_spark_tpu.core.table import TpuTable

    real = TpuTable.from_numpy.__func__

    def broken(cls, domain, X, Y=None, metas=None, W=None, session=None):
        if X.shape[0] >= 4096:                # the training rows, not the
            W = np.ones(X.shape[0], np.float32)      # holdout's 1,024
            W[X.shape[0] // 2:] = 0.0
        return real(cls, domain, X, Y, metas, W, session)

    monkeypatch.setattr(TpuTable, "from_numpy", classmethod(broken))


def _altered_proba(monkeypatch):
    from orange3_spark_tpu.models import gbt, random_forest

    for cls in (gbt.GBTClassifierModel,
                random_forest.RandomForestClassifierModel):
        real = cls.predict_proba

        def broken(self, table, real=real):
            proba = real(self, table).copy()
            k = max(1, proba.shape[0] // 10)         # a tenth of the rows
            proba[:k] = proba[:k, ::-1]
            return proba

        monkeypatch.setattr(cls, "predict_proba", broken)


FAULTS = {
    "fit_stream": {"state_unchanged": _skip_second_step,
                   "half_batch": _half_chunks,
                   "answer_altered": _altered_coef},
    "tree_fit": {"state_unchanged": _skip_second_round,
                 "half_batch": _half_table,
                 "answer_altered": _altered_proba},
}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_is_not_correct(workload, fault, data_dir, monkeypatch,
                              capsys):
    FAULTS[CELLS[workload]][fault](monkeypatch)
    line = run_harness(workload, capsys)
    assert line["correct"] is False, (fault, line["compared"])
    over = [k for k, c in line["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, line["compared"]


def test_limits_files_name_their_readings():
    for workload in CELLS:
        cell = harness.read_json(harness.HERE, "cells", workload + ".json")
        assert set(cell["limits"]) == set(cell["readings"]), workload
        for name, r in cell["readings"].items():
            assert r["lower"] < cell["limits"][name] < r["upper"], (
                workload, name)
