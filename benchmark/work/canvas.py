"""Work function of the canvas refit scale -> PCA(p) -> KMeans(k) over N
rows of d float32 columns: the bytes and operations the ALGORITHM needs — a
lower bound that no implementation of the same float32 fit can go under,
so that a share of it cannot pass 100% after a later change fuses passes
that today's program runs apart. What it must move, a row:

- read the row once for the statistics: 4 d bytes. The mean, the deviation
  and the covariance of the standardised rows all follow from one pass's
  sums (sum of x, of x xT); whether a float32 implementation dares that is
  its business, the bound only says that less than one read cannot do;
- read its weight once: 4 bytes;
- read it again to project it, because the basis depends on every row:
  4 d bytes, and write its p scores: 4 p bytes. Lloyd's first iteration can
  run in that same pass;
- every further iteration that was RUN reads the p scores again (or the d
  columns, which is more): 4 p bytes x (iterations - 1);
- the returned centres exist only after the last iteration, so the cluster
  column needs one more read of the scores, 4 p bytes, and is written,
  4 bytes (the scores already stand in the returned table).

Operations: 2 d + 2 d^2 for the sums, 2 d + 2 d p to standardise and
project, 3 p k + k + 2 p an iteration for distances, argmin and the
centres' sums, 3 p k + k for the last assignment. At d 8, p 4, k 10 and 20
iterations that is ~3,300 operations against 408 bytes a row: bound by
bytes on any chip whose FLOP/s : B/s ratio is under ~8 (v5e: 240). The
seeding (k rows out of a sample) and the d x d ``eigh`` do not grow with N
and are left out, which only lowers the bound.
"""

from __future__ import annotations

from benchmark.work import least_seconds


def row_bytes(d: int, pca_k: int, iterations: int) -> int:
    return (4 * d + 4 + 4 * d + 4 * pca_k
            + 4 * pca_k * max(iterations - 1, 0) + 4 * pca_k + 4)


def row_ops(d: int, pca_k: int, k: int, iterations: int) -> int:
    return (2 * d + 2 * d * d + 2 * d + 2 * d * pca_k
            + iterations * (3 * pca_k * k + k + 2 * pca_k)
            + 3 * pca_k * k + k)


def job_work(*, rows: int, d: int, pca_k: int, k: int, iterations: int,
             peaks: dict) -> dict:
    """One refit of ``iterations`` Lloyd iterations actually run. -> bytes,
    operations, least seconds (of the job, which is one program), the
    bound."""
    b = rows * row_bytes(d, pca_k, iterations)
    o = rows * row_ops(d, pca_k, k, iterations)
    least, bound = least_seconds(b, o, peaks)
    return {"bytes": b, "ops": o, "iterations": iterations, "bound": bound,
            "job_least_s": least, "program_least_s": least}
