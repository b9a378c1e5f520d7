"""TRUE multi-process multihost test (round-3 verdict item 3).

Spawns 2 subprocesses with ``jax.distributed.initialize`` on CPU (4 fake
devices each -> one 8-device global mesh across processes, gloo
collectives), each reading its ``process_row_slice`` of a shared CSV and
contributing it through ``put_sharded``'s ``process_count>1`` branch —
the code path a single-process ``force_global`` test cannot exercise
(there, local block == global array by construction, so block ordering
and per-process shape bugs are invisible).

Asserts the assembled global array AND a real sharded LogisticRegression
fit match the single-process ground truth.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_mp_worker.py")
N_ROWS, N_COLS = 1000, 4


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.fixture(scope="module")
def mp_results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mp")
    rng = np.random.default_rng(0)
    X = rng.standard_normal((N_ROWS, N_COLS)).astype(np.float32)
    w_true = np.asarray([1.5, -2.0, 0.7, 0.0], np.float32)
    y = (X @ w_true + 0.3 * rng.standard_normal(N_ROWS) > 0).astype(np.float32)
    csv = tmp / "shared.csv"
    header = ",".join([f"f{i}" for i in range(N_COLS)] + ["y"])
    # %.9g round-trips float32 exactly: the workers train on IDENTICAL
    # bits to the in-memory reference fits (no quantization slack needed
    # in the equivalence tolerances below)
    np.savetxt(csv, np.column_stack([X, y]), delimiter=",",
               header=header, comments="", fmt="%.9g")

    port = _free_port()
    out = tmp / "out.npz"
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(i), "2", str(port), str(csv),
             str(out)],
            env=_worker_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=300)
            logs.append(stdout)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    if any(p.returncode != 0 for p in procs):
        joined = "\n".join(logs)
        raise AssertionError(f"worker failed:\n{joined}")
    return X, y, np.load(out)


def test_two_process_global_assembly(mp_results):
    X, y, res = mp_results
    assert int(res["process_count"]) == 2
    # global array = concatenation of both process blocks: its column sums
    # equal the FULL dataset's (padding rows are zeros)
    np.testing.assert_allclose(res["colsum"], X.sum(axis=0), rtol=1e-4)
    assert int(res["global_rows"]) >= N_ROWS
    # shard_paths round-robins 2 files across 2 processes
    assert int(res["n_shard_paths"]) == 1


def test_two_process_streaming_fit_matches_equivalent_chunks(mp_results,
                                                             session):
    """Distributed STREAMING ingest: each process streams 128-row padded
    chunks of its own row block in lockstep, so every global device batch
    is [proc0 chunk; proc1 chunk]. A single-process fit over explicitly
    concatenated equivalent chunks must land on the same numbers."""
    X, y, res = mp_results

    from orange3_spark_tpu.io.streaming import StreamingLinearEstimator

    half = N_ROWS // 2
    blocks = [(X[:half], y[:half]), (X[half:], y[half:])]
    pad = 128   # session.pad_rows(125) on the 8-device mesh

    chunks = []
    for i in range(4):                       # 500 local rows -> 4 chunks
        xs, ys, ws = [], [], []
        for Xb, yb in blocks:
            seg_x = Xb[i * pad:(i + 1) * pad]
            seg_y = yb[i * pad:(i + 1) * pad]
            n = len(seg_x)
            xp = np.zeros((pad, N_COLS), np.float32)
            xp[:n] = seg_x
            yp = np.zeros((pad,), np.float32)
            yp[:n] = seg_y
            wp = np.zeros((pad,), np.float32)
            wp[:n] = 1.0
            xs.append(xp)
            ys.append(yp)
            ws.append(wp)
        chunks.append((np.concatenate(xs), np.concatenate(ys),
                       np.concatenate(ws)))

    def source():
        yield from chunks

    ref = StreamingLinearEstimator(
        loss="logistic", epochs=2, step_size=0.1, chunk_rows=2 * pad,
    ).fit_stream(source, n_features=N_COLS, session=session)

    assert int(res["stream_steps"]) == ref.n_steps_ == 8
    # identical input bits (%.9g CSV); the residual slack covers gloo
    # cross-process reduction ordering vs the in-process reference
    np.testing.assert_allclose(
        res["stream_coef"], np.asarray(ref.coef), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        res["stream_intercept"], np.asarray(ref.intercept),
        rtol=1e-4, atol=1e-5,
    )


def test_two_process_sharded_fit_matches_single_process(mp_results, session):
    """The fit ran SPMD over blocks no single process ever held together;
    its coefficients must match the single-process fit of the full data."""
    X, y, res = mp_results

    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.logistic_regression import (
        LogisticRegression,
    )

    domain = Domain(
        [ContinuousVariable(f"f{i}") for i in range(N_COLS)],
        DiscreteVariable("y", ("0", "1")),
    )
    table = TpuTable.from_numpy(domain, X, y, session=session)
    ref = LogisticRegression(max_iter=100, reg_param=1e-3).fit(table)
    np.testing.assert_allclose(
        res["coef"], np.asarray(ref.coef), rtol=5e-3, atol=5e-4
    )
    np.testing.assert_allclose(
        res["intercept"], np.asarray(ref.intercept), rtol=5e-3, atol=5e-4
    )
