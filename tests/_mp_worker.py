"""Worker for tests/test_multiprocess.py — one PROCESS of a 2-process
jax.distributed CPU world (the real multi-host ingest path; SURVEY.md §2b
"Data ingest"). Run as:

    python tests/_mp_worker.py <process_id> <num_processes> <port> \
        <csv_path> <out_npz>

Each process reads ONLY its ``process_row_slice`` of the shared CSV,
contributes it via ``put_sharded`` (the ``process_count>1`` branch —
``jax.make_array_from_process_local_data``), and runs a REAL sharded fit
(LogisticRegression over the global table). Process 0 writes results for
the parent test to compare against the single-process ground truth.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"   # a test harness: it pins the CPU
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def main() -> None:
    pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    csv_path, out_npz = sys.argv[4], sys.argv[5]
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=n_proc, process_id=pid
    )
    assert jax.process_count() == n_proc

    import jax.numpy as jnp
    import numpy as np

    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.io.multihost import (
        process_row_slice, put_sharded, shard_paths,
    )
    from orange3_spark_tpu.io.native import NativeCsvReader
    from orange3_spark_tpu.models.logistic_regression import (
        LogisticRegression,
    )

    session = TpuSession.builder_get_or_create()

    # --- ingest: THIS process parses only its contiguous row block -------
    with NativeCsvReader(csv_path, header=True) as r:
        full = np.concatenate(list(r.chunks(1 << 16)))
    n_total = full.shape[0]
    sl = process_row_slice(n_total)
    block = full[sl]
    # equal per-process contribution (put_sharded contract): n_total is
    # chosen divisible by n_proc in the parent test
    assert block.shape[0] == n_total // n_proc

    X_local, y_local = block[:, :-1], block[:, -1]

    # --- raw global assembly through the process_count>1 branch ---------
    pad_local = session.pad_rows(len(block)) // 1  # local rows, padded
    Xp = np.zeros((pad_local, X_local.shape[1]), np.float32)
    Xp[: len(block)] = X_local
    Xg = put_sharded(Xp, session.row_sharding)
    assert Xg.shape[0] == n_proc * pad_local, Xg.shape
    colsum = np.asarray(jax.jit(lambda a: jnp.sum(a, axis=0))(Xg))

    # --- a real sharded fit over the globally-assembled table ------------
    domain = Domain(
        [ContinuousVariable(f"f{i}") for i in range(X_local.shape[1])],
        DiscreteVariable("y", ("0", "1")),
    )
    table = TpuTable.from_numpy(domain, X_local, y_local, session=session)
    model = LogisticRegression(max_iter=100, reg_param=1e-3).fit(table)
    coef = np.asarray(model.coef)
    intercept = np.asarray(model.intercept)

    # --- distributed STREAMING fit: each process streams chunks of its
    # own row block in lockstep; every global device batch is the
    # concatenation of the processes' local chunks (Spark's ingest model:
    # executors read their splits, the fit sees the union) -------------
    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source,
    )

    local_chunk = 125   # 500 local rows -> 4 lockstep chunks per process
    sm = StreamingLinearEstimator(
        loss="logistic", epochs=2, step_size=0.1, chunk_rows=local_chunk,
    ).fit_stream(
        array_chunk_source(X_local, y_local, chunk_rows=local_chunk),
        n_features=X_local.shape[1], session=session,
    )

    sp = shard_paths([csv_path, csv_path + ".b"])
    if pid == 0:
        np.savez(
            out_npz,
            colsum=colsum, coef=coef, intercept=intercept,
            stream_coef=np.asarray(sm.coef),
            stream_intercept=np.asarray(sm.intercept),
            stream_steps=sm.n_steps_,
            n_shard_paths=len(sp), global_rows=Xg.shape[0],
            process_count=jax.process_count(),
        )
    print(f"worker {pid} done", flush=True)


if __name__ == "__main__":
    main()
