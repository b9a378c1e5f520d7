"""Plain reference of the canvas scale -> PCA(k) -> KMeans(k): numpy, float64,
imports nothing of the program.

The semantics, as MLlib states them and the configuration's ``guarantees``
repeat them:

- StandardScaler(withMean, withStd): column mean and POPULATION standard
  deviation over the live rows (weight > 0 counts by its weight); a column
  whose deviation is under 1e-12 keeps scale 1.
- PCA(k): covariance of the standardised rows about their own mean,
  ``eigh``, the k largest eigenvalues' vectors, each with its
  largest-magnitude entry positive (the sign ``eigh`` leaves open).
- KMeans(k, maxIter, tol): Lloyd from GIVEN initial centres; an iteration
  assigns every row to its nearest centre (first of equals), moves every
  centre to the weighted mean of its rows (a centre without rows stays),
  and the loop ends after ``max_iter`` iterations or when every centre
  moved by at most ``tol`` (MLlib: squared distance <= tol^2). Cost,
  cluster sizes and assignments are those of the RETURNED centres.

Two things a fit leaves open are taken from the answer under test after
being proved legitimate, never trusted: the initial centres
(``Rows.init_gap``: each is a live row of this reference's own score
table, all distinct) and the basis of the principal subspace
(``subspace_gap``: orthonormal, and it captures the variance of the
reference's own top-k eigenvectors). Why the basis: the trips' correlation
matrix has five eigenvalues within 1e-4 of each other at 2^27 rows (five
independent columns), so WHICH three of those directions join the first
component is decided by the last bits of the covariance in any precision,
and the clustering can only be followed in the basis the answer chose.

Every pass runs over row blocks, so that 2^27 rows fit the host. A table
handed over as ``np.memmap`` (``np.load(path, mmap_mode='r')``) of
``PARALLEL_ROWS`` rows or more is split over spawned numpy workers, each
mapping its own rows of the file and keeping its own rows of the score
table (numpy's threads do not scale here: OpenBLAS serialises concurrent
calls); a smaller or in-memory table is one shard in this process. Partial
sums are added in block and shard order.

``Rows(precision='bfloat16')`` (the control) rounds the rows, the
standardised rows, the scores and the centres to bfloat16 wherever a
lower-precision implementation would hold them; the planted faults are
``Rows(fault='half_batch')`` (the second half of the rows weighs 0) and
``lloyd(skip_step=True)`` (the second iteration returns its centres
unchanged).
"""

from __future__ import annotations

import multiprocessing
import os

import numpy as np

BLOCK_ROWS = 1 << 14
PARALLEL_ROWS = 1 << 24
WORKERS = max(1, min(16, (os.cpu_count() or 2) - 1))


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def _add(parts: list) -> tuple:
    """Element-wise sum of equally shaped tuples, in list order."""
    out = list(parts[0])
    for p in parts[1:]:
        out = [a + b for a, b in zip(out, p)]
    return tuple(out)


class _Shard:
    """Rows [lo, hi) of a table of ``n_rows``, their weights, and (after
    ``project``) their rows of the score table and of the assignment."""

    def __init__(self, X: np.ndarray, lo: int, n_rows: int,
                 precision: str, fault: str | None, block_rows: int):
        self.X, self.lo, self.precision = X, lo, precision
        self.block_rows = block_rows
        self.w = np.ones(X.shape[0], np.float64)
        if fault == "half_batch":
            self.w[max(n_rows // 2 - lo, 0):] = 0.0
        self.unit = bool((self.w == 1.0).all())
        self.S = self.assign = None

    def _blocks(self):
        n = self.X.shape[0]
        return [(s, min(s + self.block_rows, n))
                for s in range(0, n, self.block_rows)]

    def _x(self, s, e):
        x = self.X[s:e]
        return to_bf16(x) if self.precision == "bfloat16" \
            else x.astype(np.float64)

    def _z(self, s, e, mean, scale):
        z = (self._x(s, e) - mean) * scale
        return to_bf16(z) if self.precision == "bfloat16" else z

    def sums(self):
        return _add([((self._x(s, e) * self.w[s:e, None]).sum(0),
                      self.w[s:e].sum()) for s, e in self._blocks()])

    def squares(self, mean):
        return _add([((((self._x(s, e) - mean) ** 2)
                       * self.w[s:e, None]).sum(0),)
                     for s, e in self._blocks()])

    def z_moments(self, mean, scale):
        """Sum of the standardised rows and of their outer products, in
        one pass: the covariance about their mean follows (the mean of
        standardised rows is ~1e-16, so nothing cancels)."""
        def one(s, e):
            z, ww = self._z(s, e, mean, scale), self.w[s:e, None]
            zw = z * ww
            return zw.sum(0), zw.T @ z
        return _add([one(s, e) for s, e in self._blocks()])

    def project(self, mean, scale, pca_mean, comps):
        self.S = np.empty((self.X.shape[0], comps.shape[1]), np.float64)
        for s, e in self._blocks():
            sc = (self._z(s, e, mean, scale) - pca_mean) @ comps
            self.S[s:e] = to_bf16(sc) if self.precision == "bfloat16" else sc
        self.assign = np.zeros(self.X.shape[0], np.int8)
        return (0,)

    def _d2(self, s, e, c):
        """Squared distances [rows, k] of the block's score rows to ``c``."""
        x = self.S[s:e]
        if self.precision == "bfloat16":
            cross = to_bf16(x) @ to_bf16(c).T
        else:
            cross = x @ c.T
        return (x * x).sum(1)[:, None] - 2.0 * cross + (c * c).sum(1)

    def nearest(self, c):
        """Each centre's squared distance to its nearest LIVE row."""
        best = np.full(len(c), np.inf)
        for s, e in self._blocks():
            d2 = self._d2(s, e, c)
            d2[self.w[s:e] <= 0] = np.inf
            best = np.minimum(best, d2.min(0))
        return (best,)

    def lloyd_pass(self, c, last):
        """All rows against centres ``c``: -> (sums [k, d], weights [k]);
        the ``last`` pass, under the returned centres, also keeps every
        row's nearest centre (the first of equals) for ``rows_at`` and
        -> (..., live rows [k], cost). A row's distances are held less its
        own |x|^2, which no argmin needs, in buffers made once a pass:
        [k, rows], so that the minimum runs along memory."""
        k, cap = len(c), self.block_rows
        dist, hot = np.empty((k, cap)), np.empty((k, cap))
        low, ones = np.empty(cap), np.ones(cap)
        bf16 = self.precision == "bfloat16"
        c2 = (c * c).sum(1)[:, None]
        cm = -2.0 * (to_bf16(c) if bf16 else c)
        eye = np.arange(k)[:, None]

        def one(s, e):
            x, ww = self.S[s:e], self.w[s:e]
            d, h, b = dist[:, :e - s], hot[:, :e - s], low[:e - s]
            np.matmul(cm, (to_bf16(x) if bf16 else x).T, out=d)
            d += c2
            np.min(d, axis=0, out=b)
            np.equal(d, b, out=h)
            if (h @ ones[:e - s]).sum() != e - s:   # equal distances:
                np.equal(d.argmin(0), eye, out=h)   # the first wins
            if self.unit:               # every row weighs 1
                out = (h @ x, h @ ones[:e - s])
            else:
                out = (h @ (x * ww[:, None]), h @ ww)
            if last:
                self.assign[s:e] = h.argmax(0)
                out += (h @ (ww > 0),
                        ((b + (x * x).sum(1)) * ww).sum())
            return out

        return _add([one(s, e) for s, e in self._blocks()])

    def rows_at(self, idx):
        """(score rows, assignments, live) at the GLOBAL row numbers
        ``idx`` that fall in this shard."""
        mine = idx[(idx >= self.lo) & (idx < self.lo + self.X.shape[0])]
        mine = mine - self.lo
        return self.S[mine], self.assign[mine], self.w[mine] > 0


def _serve(conn, filename, dtype, shape, offset, lo, hi, *shard_args):
    """A worker's loop: map rows [lo, hi) of the table's file, then answer
    (method, args) with the shard's result until told ``None``."""
    X = np.memmap(filename, dtype=dtype, mode="r", offset=offset,
                  shape=shape)[lo:hi]
    shard = _Shard(X, lo, shape[0], *shard_args)
    while True:
        try:
            msg = conn.recv()
        except EOFError:                # the caller went without a word
            return
        if msg is None:
            return
        try:
            conn.send((True, getattr(shard, msg[0])(*msg[1])))
        except Exception as e:          # told to the caller, who raises
            conn.send((False, repr(e)))


class Rows:
    """A table's rows behind the reference's passes; ``close()`` ends the
    workers (a context manager does)."""

    def __init__(self, X: np.ndarray, *, precision: str = "float64",
                 fault: str | None = None, block_rows: int = BLOCK_ROWS,
                 workers: int | None = None):
        self.n = X.shape[0]
        self._conns, self._procs, self._shards = [], [], []
        if workers is None:
            workers = WORKERS if (isinstance(X, np.memmap)
                                  and self.n >= PARALLEL_ROWS) else 0
        args = (precision, fault, block_rows)
        if not workers:
            self._shards = [_Shard(X, 0, self.n, *args)]
            return
        if not isinstance(X, np.memmap):
            raise ValueError("workers map the table's file: hand over "
                             "np.load(path, mmap_mode='r')")
        ctx = multiprocessing.get_context("spawn")
        cuts = np.linspace(0, self.n, workers + 1).astype(np.int64)
        # one BLAS thread a worker: the workers are the parallelism
        kept = os.environ.get("OPENBLAS_NUM_THREADS")
        os.environ["OPENBLAS_NUM_THREADS"] = "1"
        try:
            for lo, hi in zip(cuts[:-1], cuts[1:]):
                ours, theirs = ctx.Pipe()
                p = ctx.Process(
                    target=_serve, daemon=True,
                    args=(theirs, X.filename, X.dtype.str, X.shape,
                          X.offset, int(lo), int(hi), *args))
                p.start()
                theirs.close()
                self._conns.append(ours)
                self._procs.append(p)
        finally:
            if kept is None:
                del os.environ["OPENBLAS_NUM_THREADS"]
            else:
                os.environ["OPENBLAS_NUM_THREADS"] = kept

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        self.close()

    def close(self) -> None:
        for c in self._conns:
            try:
                c.send(None)
            except OSError:             # a worker that died has no pipe
                pass
            c.close()
        for p in self._procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
        self._conns, self._procs, self._shards = [], [], []

    def _each(self, method: str, *args) -> list:
        """The shards' results, in shard order."""
        if self._shards:
            return [getattr(s, method)(*args) for s in self._shards]
        for c in self._conns:
            c.send((method, args))
        out = []
        for c in self._conns:
            ok, value = c.recv()
            if not ok:
                raise RuntimeError(f"reference worker: {value}")
            out.append(value)
        return out

    # ---------------------------------------------------- scaler and PCA
    def fit_scaler_pca(self, k: int) -> dict:
        """Moments -> standardise -> covariance -> eigh. -> mean, std,
        scale, pca_mean (of the standardised rows), cov, eigenvalues (all,
        largest first), components [d, k] with their signs fixed."""
        tot_x, tot = _add(self._each("sums"))
        mean = tot_x / tot
        std = np.sqrt(_add(self._each("squares", mean))[0] / tot)
        scale = 1.0 / np.where(std > 1e-12, std, 1.0)
        z_sum, z_outer = _add(self._each("z_moments", mean, scale))
        pca_mean = z_sum / tot
        cov = z_outer / tot - np.outer(pca_mean, pca_mean)
        vals, vecs = np.linalg.eigh(cov)
        order = np.argsort(vals)[::-1]
        comps = vecs[:, order[:k]]
        big = np.abs(comps).argmax(0)
        comps = comps * np.sign(comps[big, np.arange(k)])
        return {"mean": mean, "std": std, "scale": scale,
                "pca_mean": pca_mean, "cov": cov,
                "eigenvalues": np.maximum(vals[order], 0.0),
                "components": comps, "total": float(tot)}

    def project(self, st: dict, components: np.ndarray) -> None:
        """Make the score table: the standardised rows, about their mean,
        in the given basis (the reference's own ``st['components']`` or an
        answer's). The shards keep it; Lloyd and ``rows_at`` read it."""
        self._each("project", st["mean"], st["scale"], st["pca_mean"],
                   np.asarray(components, np.float64))

    # ------------------------------------------------------------ KMeans
    def init_gap(self, init: np.ndarray) -> float:
        """Legitimacy of given initial centres: the largest distance from
        a centre to its nearest LIVE row of the score table, over the
        centres' largest norm; ``inf`` where two centres coincide or a
        value is not finite."""
        c = np.asarray(init, np.float64)
        if not np.isfinite(c).all():
            return float("inf")
        pair = ((c[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        if (pair[~np.eye(len(c), dtype=bool)] == 0).any():
            return float("inf")
        d2 = np.min([r[0] for r in self._each("nearest", c)], axis=0)
        return float(np.sqrt(max(d2.max(), 0.0))
                     / max(np.sqrt((c * c).sum(1).max()), 1e-30))

    def lloyd(self, init: np.ndarray, *, max_iter: int, tol: float,
              skip_step: bool = False) -> dict:
        """Lloyd from ``init`` over the score table. -> centers, cost,
        sizes (live rows a cluster) and n_iter; the shards keep the
        assignment under the returned centres."""
        c = np.asarray(init, np.float64).copy()
        n_iter, converged = 0, False
        while n_iter < max_iter and not converged:
            sums, cw = _add(self._each("lloyd_pass", c, False))
            new = np.where(cw[:, None] > 0,
                           sums / np.maximum(cw, 1e-12)[:, None], c)
            if skip_step and n_iter == 1:
                new = c.copy()
            converged = bool((((new - c) ** 2).sum(1) <= tol * tol).all())
            c, n_iter = new, n_iter + 1
        _, _, sizes, cost = _add(self._each("lloyd_pass", c, True))
        return {"centers": c, "cost": float(cost), "sizes": sizes,
                "n_iter": n_iter}

    def rows_at(self, idx: np.ndarray) -> tuple:
        """(score rows, assignments, live) at the sorted global rows
        ``idx``."""
        parts = self._each("rows_at", np.asarray(idx, np.int64))
        return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))

    def draw_init(self, k: int, seed: int) -> np.ndarray:
        """k distinct live rows of the score table by a seeded draw: the
        initial centres of a fit the reference makes in the program's
        place (readings of the control and the faults)."""
        rng = np.random.default_rng([int(seed), 7])
        while True:
            idx = np.sort(rng.choice(self.n, size=min(4 * k, self.n),
                                     replace=False))
            rows, _, live = self.rows_at(idx)
            picked = np.unique(rows[live], axis=0)
            if len(picked) >= k:
                return picked[rng.permutation(len(picked))[:k]]


def subspace_gap(st: dict, components: np.ndarray) -> float:
    """How far a [d, k] basis is from a principal one: the larger of its
    distance from orthonormal and the share of the top-k variance (the
    reference's own eigenvalues) that it captures more or less of."""
    v = np.asarray(components, np.float64)
    k = v.shape[1]
    ortho = float(np.abs(v.T @ v - np.eye(k)).max())
    best = float(st["eigenvalues"][:k].sum())
    captured = float(np.trace(v.T @ st["cov"] @ v))
    return max(ortho, abs(best - captured) / best)
