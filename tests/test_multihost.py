"""Multi-host ingest (io/multihost.py): the make_array_from_process_local_data
assembly path, process row-slicing, and file-shard assignment — exercised
single-process (the multi-process branch runs with force_global=True, where
one process's local block IS the global array)."""

import jax
import numpy as np
import pytest

from orange3_spark_tpu.io.multihost import (
    process_row_slice,
    put_sharded,
    shard_paths,
)


def test_put_sharded_global_assembly_matches_device_put(session):
    x = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
    sh = session.row_sharding
    a = put_sharded(x, sh)
    b = put_sharded(x, sh, force_global=True)  # multi-process code path
    assert b.shape == (64, 3)
    assert b.sharding == sh
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_put_sharded_feeds_table_and_fit(session):
    """A table built through the global-assembly path must behave like the
    plain one end to end (fit + predict)."""
    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.logistic_regression import LogisticRegression

    rng = np.random.default_rng(0)
    X = rng.standard_normal((400, 4)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    dom = Domain([ContinuousVariable(f"f{i}") for i in range(4)],
                 DiscreteVariable("y", ("0", "1")))
    t = TpuTable.from_numpy(dom, X, y, session=session)
    m = LogisticRegression(max_iter=100).fit(t)
    assert np.mean(m.predict(t) == y) > 0.95


def test_process_row_slice_partitions_exactly(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    slices = []
    for pi in range(4):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        slices.append(process_row_slice(10))
    covered = [i for s in slices for i in range(s.start, s.stop)]
    assert covered == list(range(10))          # disjoint, complete, ordered
    sizes = [s.stop - s.start for s in slices]
    assert max(sizes) - min(sizes) <= 1        # near-equal


def test_shard_paths_round_robin(monkeypatch):
    paths = [f"part-{i:03d}.csv" for i in range(7)]
    monkeypatch.setattr(jax, "process_count", lambda: 3)
    seen = []
    for pi in range(3):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        seen.append(shard_paths(paths))
    flat = sorted(p for sub in seen for p in sub)
    assert flat == sorted(paths)               # every file exactly once
    assert all(len(s) in (2, 3) for s in seen)


def test_single_process_defaults():
    assert process_row_slice(100) == slice(0, 100)
    assert shard_paths(["b", "a"]) == ["a", "b"]


def test_shard_row_groups_partitions_single_parquet(tmp_path, monkeypatch):
    """Single-file parquet multihost splitting: the per-process row-group
    slices are contiguous, disjoint, exhaustive — and streaming each
    process's slice reassembles exactly the whole file (Spark's parquet
    input splits)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    import jax

    from orange3_spark_tpu.io.multihost import shard_row_groups
    from orange3_spark_tpu.io.streaming import parquet_raw_chunk_source

    p = str(tmp_path / "d.parquet")
    data = np.arange(70, dtype=np.float32)
    pq.write_table(pa.table({"v": data}), p, row_group_size=10)  # 7 groups

    monkeypatch.setattr(jax, "process_count", lambda: 3)
    slices = []
    for pi in range(3):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        slices.append(shard_row_groups(p))
    assert [len(s) for s in slices] == [3, 2, 2]     # 7 groups over 3 procs
    assert sorted(sum(slices, [])) == list(range(7))
    for s in slices:                                  # contiguous ranges
        assert s == list(range(s[0], s[0] + len(s)))

    got = np.concatenate([
        np.concatenate(list(parquet_raw_chunk_source(
            p, chunk_rows=8, row_groups=tuple(s))()))
        for s in slices
    ])
    np.testing.assert_array_equal(got[:, 0], data)


# ======================================================================
# ISSUE 18: lockstep sharded ingestion, partitioners, gang launcher
# ======================================================================

import os  # noqa: E402
import sys  # noqa: E402

from orange3_spark_tpu.io.multihost import (  # noqa: E402
    RaggedHostBlockError,
    lockstep_rows,
)


def _shared_csv(tmp_path, n, d=4, seed=0, name="shared.csv"):
    """%.9g round-trips float32 exactly — bitwise comparisons below are
    against the same bits every reader decodes."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    p = str(tmp_path / name)
    header = ",".join([f"f{i}" for i in range(d)] + ["y"])
    np.savetxt(p, np.column_stack([X, y]), delimiter=",", fmt="%.9g",
               header=header, comments="")
    return p, X, y


def test_put_sharded_ragged_block_raises_typed(session):
    """A block that can't tile the local row shards must fail TYPED and
    name the fix (the weight-mask pad convention), not as an opaque jax
    assembly error; a tiling block passes through the same branch."""
    bad = np.zeros((10, 3), np.float32)          # 10 % 8 local shards != 0
    with pytest.raises(RaggedHostBlockError) as ei:
        put_sharded(bad, session.row_sharding, force_global=True)
    msg = str(ei.value)
    assert "w=0" in msg and "lockstep_rows" in msg
    ok = put_sharded(np.ones((16, 3), np.float32), session.row_sharding,
                     force_global=True)
    assert ok.shape == (16, 3)


def test_lockstep_rows_is_largest_slice(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    widths = []
    for pi in range(4):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        s = process_row_slice(10)
        widths.append(s.stop - s.start)
    assert lockstep_rows(10) == max(widths) == 3
    assert lockstep_rows(12) == 3                # even split: no padding


def test_sharded_csv_kill_switch_is_plain_source(tmp_path, monkeypatch):
    """OTPU_MULTIHOST=0: the single-path form IS csv_chunk_source —
    byte-identical chunks, same tuple shapes."""
    from orange3_spark_tpu.io.streaming import (
        csv_chunk_source, sharded_csv_chunk_source,
    )
    p, X, y = _shared_csv(tmp_path, 1000)
    monkeypatch.setenv("OTPU_MULTIHOST", "0")
    got = list(sharded_csv_chunk_source(p, "y", shard_total_rows=1000,
                                        chunk_rows=256)())
    ref = list(csv_chunk_source(p, "y", chunk_rows=256)())
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g[0], r[0])
        np.testing.assert_array_equal(g[1], r[1])


def test_sharded_csv_single_process_matches_plain(tmp_path):
    """Switch ON, one process: same values as the plain stream (the
    pass-through re-chunk), w None on pure chunks."""
    from orange3_spark_tpu.io.streaming import (
        csv_chunk_source, sharded_csv_chunk_source,
    )
    p, X, y = _shared_csv(tmp_path, 1000)
    got = list(sharded_csv_chunk_source(p, "y", shard_total_rows=1000,
                                        chunk_rows=256)())
    ref = list(csv_chunk_source(p, "y", chunk_rows=256)())
    assert [len(c[0]) for c in got] == [len(c[0]) for c in ref]
    np.testing.assert_array_equal(np.concatenate([c[0] for c in got]), X)
    np.testing.assert_array_equal(np.concatenate([c[1] for c in got]), y)
    assert all(c[2] is None for c in got)


def test_sharded_csv_two_process_lockstep_schedule(tmp_path, monkeypatch):
    """The lockstep contract: 1001 rows over 2 processes — rows split
    501/500, yet BOTH processes must emit the identical chunk schedule
    ([256, 245]); the short process tops up with one dead w=0 row. Naive
    slice-at-parser-chunk-granularity would emit different chunk counts
    per process and deadlock the global collectives."""
    from orange3_spark_tpu.io.streaming import sharded_csv_chunk_source
    p, X, y = _shared_csv(tmp_path, 1001)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    per_proc = []
    for pi in range(2):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        per_proc.append(list(sharded_csv_chunk_source(
            p, "y", shard_total_rows=1001, chunk_rows=256)()))
    sched0 = [len(c[0]) for c in per_proc[0]]
    sched1 = [len(c[0]) for c in per_proc[1]]
    assert sched0 == sched1 == [256, 245]        # identical on every rank
    X0 = np.concatenate([c[0] for c in per_proc[0]])
    np.testing.assert_array_equal(X0, X[:501])
    X1 = np.concatenate([c[0] for c in per_proc[1]])
    np.testing.assert_array_equal(X1[:500], X[501:])
    np.testing.assert_array_equal(X1[500], np.zeros(4, np.float32))
    w_last = per_proc[1][-1][2]
    assert w_last is not None
    assert w_last[-1] == 0.0                     # the dead row is masked
    assert w_last[:-1].min() == 1.0              # real rows keep weight


def test_sharded_csv_overstated_rows_raises(tmp_path):
    from orange3_spark_tpu.io.streaming import sharded_csv_chunk_source
    p, _, _ = _shared_csv(tmp_path, 100)
    src = sharded_csv_chunk_source(p, "y", shard_total_rows=500,
                                   chunk_rows=64)
    with pytest.raises(ValueError, match="overstates"):
        list(src())


def test_parquet_shard_flag_splits_and_kill_switch_doesnt(tmp_path,
                                                          monkeypatch):
    """``shard=True`` makes the parquet source pick this process's
    contiguous row-group range itself; under OTPU_MULTIHOST=0 the flag is
    inert (full file)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from orange3_spark_tpu.io.streaming import parquet_raw_chunk_source

    p = str(tmp_path / "d.parquet")
    data = np.arange(70, dtype=np.float32)
    pq.write_table(pa.table({"v": data}), p, row_group_size=10)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    parts = []
    for pi in range(2):
        monkeypatch.setattr(jax, "process_index", lambda pi=pi: pi)
        parts.append(np.concatenate(
            list(parquet_raw_chunk_source(p, chunk_rows=16, shard=True)())))
    np.testing.assert_array_equal(np.concatenate(parts)[:, 0], data)
    assert len(parts[0]) == 40 and len(parts[1]) == 30   # 4+3 groups

    monkeypatch.setenv("OTPU_MULTIHOST", "0")
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    full = np.concatenate(
        list(parquet_raw_chunk_source(p, chunk_rows=16, shard=True)()))
    np.testing.assert_array_equal(full[:, 0], data)


def test_data_parallel_partitioner_fit_and_kill_switch_parity(tmp_path,
                                                              monkeypatch):
    """The partitioner plugs into fit_stream as a session factory + source
    facade, and OTPU_MULTIHOST=0 reproduces the stock path BITWISE."""
    from orange3_spark_tpu.io.streaming import StreamingLinearEstimator
    from orange3_spark_tpu.parallel import DataParallelPartitioner

    p, X, y = _shared_csv(tmp_path, 2048)

    def fit():
        part = DataParallelPartitioner()
        src = part.shard_csv(p, "y", n_total=2048, chunk_rows=256)
        est = StreamingLinearEstimator(loss="logistic", epochs=3,
                                       step_size=0.1, chunk_rows=256)
        m = est.fit_stream(src, n_features=4, session=part.session)
        return part, np.asarray(m.coef), np.asarray(m.intercept)

    monkeypatch.setenv("OTPU_MULTIHOST", "1")
    part_on, coef_on, icpt_on = fit()
    assert part_on.enabled and part_on.mesh.shape["data"] == 8

    monkeypatch.setenv("OTPU_MULTIHOST", "0")
    part_off, coef_off, icpt_off = fit()
    assert not part_off.enabled
    np.testing.assert_array_equal(coef_on, coef_off)     # bitwise pin
    np.testing.assert_array_equal(icpt_on, icpt_off)

    # the fit means something: it separates the planted boundary
    scores = X @ coef_on + icpt_on
    pred = scores.argmax(axis=1) if scores.ndim == 2 else (scores > 0)
    assert np.mean(pred == y) > 0.9


def test_spmd_partitioner_mesh_and_state_sharding(monkeypatch):
    from orange3_spark_tpu.parallel import SPMDPartitioner

    monkeypatch.setenv("OTPU_MULTIHOST", "1")
    part = SPMDPartitioner(model_parallel=2)
    assert part.mesh.shape["data"] == 4 and part.mesh.shape["model"] == 2
    # the hashed table shards over the model axis, everything else
    # (and every vector) replicates
    emb_sh = part.state_sharding("emb", np.zeros((32, 4), np.float32))
    assert emb_sh.spec[0] == part.model_axis
    assert part.state_sharding("bias", np.zeros((4,), np.float32)
                               ) == part.session.replicated
    assert part.state_sharding("emb", np.zeros((4,), np.float32)
                               ) == part.session.replicated
    st = part.shard_state({"emb": np.ones((32, 4), np.float32),
                           "opt": {"m": np.zeros((4,), np.float32)}})
    assert st["emb"].sharding.spec[0] == part.model_axis
    with pytest.raises(ValueError, match="does not divide"):
        SPMDPartitioner(model_parallel=3)


def test_partitioner_partition_runs_donated_step(monkeypatch):
    from orange3_spark_tpu.parallel import DataParallelPartitioner

    monkeypatch.setenv("OTPU_MULTIHOST", "1")
    part = DataParallelPartitioner()
    step = part.partition(lambda st, x: {"w": st["w"] + x.sum()})
    st = part.shard_state({"w": np.float32(1.0)})
    Xb, yb, wb = part.shard_batch(np.ones((16, 2), np.float32))
    assert Xb.sharding.spec[0] == part.data_axis and yb is None and wb is None
    out = step(st, Xb)
    assert float(out["w"]) == 33.0


def test_launcher_lost_host_is_typed(tmp_path):
    """A dead rank with no restart budget surfaces as HostLostError
    carrying rank + exit code — never a hang."""
    from orange3_spark_tpu.parallel.launcher import (
        HostLostError, MultihostLauncher,
    )

    def argv(rank, n, coord):
        code = "import sys; sys.exit(3)" if rank == 1 else "pass"
        return [sys.executable, "-c", code]

    lau = MultihostLauncher(argv, 2, env=dict(os.environ),
                            log_dir=str(tmp_path / "logs"),
                            max_gang_restarts=0, wall_s=60.0)
    with pytest.raises(HostLostError) as ei:
        lau.run()
    assert ei.value.rank == 1
    assert ei.value.returncode == 3
    assert ei.value.restarts == 0


def test_launcher_wall_budget_wedge_is_typed(tmp_path):
    from orange3_spark_tpu.parallel.launcher import (
        HostLostError, MultihostLauncher,
    )
    argv = lambda r, n, c: [sys.executable, "-c", "import time; time.sleep(60)"]
    lau = MultihostLauncher(argv, 2, env=dict(os.environ),
                            log_dir=str(tmp_path / "logs"),
                            max_gang_restarts=0, wall_s=0.5)
    with pytest.raises(HostLostError, match="wedged"):
        lau.run()


def test_launcher_gang_restart_recovers(tmp_path, monkeypatch):
    """First gang loses rank 1 (exactly once, marker-armed); the launcher
    restarts the whole gang with backoff and the second attempt succeeds."""
    from orange3_spark_tpu.parallel.launcher import MultihostLauncher

    marker = str(tmp_path / "rank1.died")

    def argv(rank, n, coord):
        if rank == 1:
            code = (f"import os, sys\n"
                    f"m = {marker!r}\n"
                    "if not os.path.exists(m):\n"
                    "    open(m, 'w').close()\n"
                    "    sys.exit(9)\n")
        else:
            code = "pass"
        return [sys.executable, "-c", code]

    monkeypatch.setenv("OTPU_RETRY_BASE_S", "0.01")
    lau = MultihostLauncher(argv, 2, env=dict(os.environ),
                            log_dir=str(tmp_path / "logs"),
                            max_gang_restarts=2, wall_s=60.0)
    res = lau.run()
    assert res.n_processes == 2
    assert res.hosts_lost == 1
    assert res.gang_restarts == 1
    assert res.gang_starts == 2


def test_align_checkpoints_common_step_and_donor_copy(tmp_path):
    """A kill between two ranks' epoch saves: the gang must re-enter at
    ONE step. The min saved step wins; the ahead rank gets a donor copy
    (replicated state — any rank's snapshot at S is every rank's)."""
    import pickle
    from orange3_spark_tpu.parallel.launcher import MultihostLauncher

    def put(rank, step):
        with open(tmp_path / f"rank{rank}.ckpt", "wb") as f:
            pickle.dump({"step": step, "state": {"w": float(step)},
                         "meta": None}, f)

    put(0, 16)
    put(1, 8)
    assert MultihostLauncher.align_checkpoints(str(tmp_path), 2) == 8
    for rank in range(2):
        with open(tmp_path / f"rank{rank}.ckpt", "rb") as f:
            blob = pickle.load(f)
        assert blob["step"] == 8                 # both resume at 8
        assert blob["state"] == {"w": 8.0}

    # a rank with NO snapshot forces a clean from-scratch restart
    put(0, 16)
    os.unlink(tmp_path / "rank1.ckpt")
    assert MultihostLauncher.align_checkpoints(str(tmp_path), 2) == 0
    assert not os.path.exists(tmp_path / "rank0.ckpt")


def test_multihost_drill_smoke():
    """tools/multihost_drill.py end to end (single-process gang): the
    SIGKILL'd host is detected typed, the gang restarts from the aligned
    epoch snapshot, loses 0 steps, and converges bitwise to the
    uninterrupted reference — with per-host goodput attribution."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "multihost_drill", os.path.join(repo, "tools", "multihost_drill.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = mod.run_drill(procs=1, rows=1024, epochs=3, chunk_rows=128)
    assert out["hosts_lost"] == 1
    assert out["gang_restarts"] == 1
    assert out["resume_parity_bitwise"] is True
    assert out["lost_work_steps"] == 0
    assert out["resumed_from_step"] == 8         # one epoch = 8 chunks
    for h in out["hosts"].values():
        assert "goodput" in h and "device_memory" in h
