"""The work functions against shapes worked out by hand, and against what
the program itself keeps at the rehearsal size: a lower bound may not lie
above what a real implementation moves."""

import pytest

from benchmark import harness
from benchmark.work import hashed_linear as work

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_chunk_bytes_by_hand():
    # 2^29 rows -> 29 index bits; 26 x 29 = 754 bits -> 24 words = 96 B;
    # + 1 label byte + 13 bfloat16 counts = 123 B a row
    assert work.chunk_bytes(262144, 13, 26, 1 << 29) == 262144 * 123
    # 2^16 rows -> 16 bits; 26 x 16 = 416 bits -> 13 words = 52 B; + 27
    assert work.chunk_bytes(2048, 13, 26, 1 << 16) == 2048 * 79


def test_step_is_bound_by_bytes_and_counts_each_touched_row_once():
    b = work.step_bytes(262144, 13, 26, 1 << 29, distinct_rows=1_000_000)
    assert b == 262144 * 123 + 24 * 1_000_000
    o = work.step_ops(262144, 13, 26, 1_000_000)
    s, bound = work.least_seconds(b, o, PEAKS)
    assert bound == "bytes" and s == pytest.approx(b / 819e9)


def test_job_sums_steps_of_every_epoch_and_the_holdout_once():
    w = work.job_work(chunk_rows=2048, n_dense=13, n_cat=26, n_dims=1 << 16,
                      distinct_rows=[100] * 8, epochs=8, holdout_chunks=2,
                      peaks=PEAKS)
    one = work.step_bytes(2048, 13, 26, 1 << 16, 100)
    assert w["steps"] == 48 and w["step_bytes"] == 48 * one
    ev = 2 * work.eval_bytes(2048, 13, 26, 1 << 16, 100)
    assert w["job_least_s"] == pytest.approx((48 * one + ev) / 819e9)


def test_lower_bound_is_under_what_the_program_caches(tmp_path):
    """The program's device cache of the same chunks (its own
    ``cache_bytes``) holds at least the bytes the work function says a step
    must read of them; a chunk touches at most rows x columns table rows."""
    import importlib

    spec = harness.load_cell("criteo_svc_fit_replay8", rehearse=True)
    config, traffic = spec["config"], spec["traffic"]
    kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    job = kind.Job(config, traffic, 7, str(tmp_path))
    job.prepare()
    rec = job.run()
    est = job.est_kw
    per_chunk = work.chunk_bytes(job.chunk_rows, est["n_dense"],
                                 est["n_cat"], job.n_dims)
    assert rec["resolved"]["cache_bytes"] >= per_chunk * job.n_chunks
    w = job.work(PEAKS)
    most = job.n_chunks * job.chunk_rows * est["n_cat"]
    assert 0 < w["step_bytes"] <= job.epochs * (
        per_chunk * job.n_chunks + 24 * most)
    assert w["bound"] == "bytes" and w["job_least_s"] > w["steps_least_s"]


def test_tree_levels_by_hand():
    from benchmark.work import trees

    # 2^20 rows x 28 features, 3 statistics: 28 bin bytes + 12 + 1 a row
    assert trees.level_bytes_least(1 << 20, 28, 3) == (1 << 20) * 41
    assert trees.level_ops_least(1 << 20, 28, 3) == (1 << 20) * 84
    # the kernel at level 4: 16 nodes x 32 bins = 512 columns of one-hot
    assert trees.kernel_ops(1 << 20, 28, 3, 16, 32) == \
        2 * (1 << 20) * 28 * 3 * 512
    assert trees.kernel_bytes(1 << 20, 28, 3, 16, 32) == \
        (1 << 20) * (112 + 12) + 4 * 28 * 3 * 512


def test_tree_job_counts_every_level_of_every_tree():
    from benchmark.work import trees

    w = trees.job_work(rows=1 << 20, holdout_rows=1 << 16, d=28, bins=32,
                       fits=[(20, 3, 5), (20, 2, 5)], peaks=PEAKS)
    assert w["kernel_calls"] == 200
    levels = 100 * (1 << 20) * (41 + 37) / 819e9
    predict = 40 * (1 << 16) * 116 / 819e9
    assert w["job_least_s"] == pytest.approx(levels + predict)
    # the root level is bound by bytes, the deeper ones by operations
    assert w["bound"] == "bytes+ops"
    assert w["kernel_least_s"] > w["job_least_s"]
