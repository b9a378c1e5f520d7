"""Work functions of the histogram tree fits, from shapes.

One level of one tree over N rows, d features, s statistics a row, 2^l
nodes of b bins:
- what the ALGORITHM needs (a lower bound for any implementation): read
  every row's d bin ids (1 B each at <= 256 bins), its s float32 statistics
  and its node position (1 B at depth <= 8), N * (d + 4 s + 1) bytes, and
  add each statistic into d histograms, N * d * s operations — bound by
  bytes on any chip (41 MB against 88 M adds at the HIGGS shape);
- what the KERNEL as written needs (``ops/histogram.py``: a one-hot
  [N, 2^l b] built in fast memory and contracted with the statistics on the
  matrix unit): 2 * N * d * s * 2^l * b operations, and N * (4 d + 4 s)
  bytes of int32 keys and float32 statistics in, 4 d s 2^l b out. Bound by
  operations from the second level on. Its float32 contraction at
  ``Precision.HIGHEST`` costs six bfloat16 passes, so this share cannot
  pass ~17% while that stands.
A fit: GBT = rounds x levels with s = 3; forest = trees x levels with s =
classes. Prediction reads the holdout once per tree: rows * (4 d + 4) bytes.
"""

from __future__ import annotations

from benchmark.work import least_seconds


def level_bytes_least(rows: int, d: int, s: int) -> int:
    return rows * (d + 4 * s + 1)


def level_ops_least(rows: int, d: int, s: int) -> int:
    return rows * d * s


def kernel_ops(rows: int, d: int, s: int, nodes: int, bins: int) -> int:
    return 2 * rows * d * s * nodes * bins


def kernel_bytes(rows: int, d: int, s: int, nodes: int, bins: int) -> int:
    return rows * (4 * d + 4 * s) + 4 * d * s * nodes * bins


def job_work(*, rows: int, holdout_rows: int, d: int, bins: int,
             fits: list, peaks: dict) -> dict:
    """``fits``: [(trees, statistics a row, depth)] of one job. -> least seconds of
    the whole job (algorithm) and of its histogram kernel calls (kernel)."""
    job_s = kernel_s = 0.0
    calls = 0
    bounds = set()
    for trees, s, depth in fits:
        for level in range(depth):
            t, _ = least_seconds(level_bytes_least(rows, d, s),
                                 level_ops_least(rows, d, s), peaks)
            job_s += trees * t
            t, bound = least_seconds(
                kernel_bytes(rows, d, s, 2 ** level, bins),
                kernel_ops(rows, d, s, 2 ** level, bins), peaks)
            kernel_s += trees * t
            calls += trees
            bounds.add(bound)
        job_s += trees * holdout_rows * (4 * d + 4) / peaks["hbm_bytes_per_s"]
    return {"job_least_s": job_s, "kernel_least_s": kernel_s,
            "kernel_calls": calls, "bound": "+".join(sorted(bounds))}
