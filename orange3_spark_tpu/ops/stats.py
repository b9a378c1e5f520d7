"""Shared weighted-statistics kernels.

One definition of weighted mean/variance for the whole framework (describe,
standardization, Gramian centering) so numerics can never silently diverge
between call sites. All reductions contract over the sharded row axis — GSPMD
inserts the ICI all-reduce (MLlib computes the same moments with a
MultivariateOnlineSummarizer treeAggregate; SURVEY.md §2b, reconstructed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: rows one partial product of ``rows_dot`` contracts
ROW_BLOCK = 1 << 14


def rows_dot(A, B):
    """``A.T @ B`` contracted over the (long, sharded) row axis with float32
    products AND float32-exact sums: ``[N, p], [N, q] -> [p, q]``.

    One ``dot`` over all N rows is neither on the TPU. Its default rounds
    both operands to bfloat16; ``Precision.HIGHEST`` keeps the products but
    loses the SUM — at N = 2^27 it read 2.5e-3 of relative error on KMeans'
    centre sums and 5.2e-3 on PCA's Gramian, worse than the default's 1e-5
    (PERF.md, PR 35). So the rows are contracted ``ROW_BLOCK`` at a time
    and the partial products added as float32 values (1.5e-7 to 8e-7
    there, at the same seconds). The barrier keeps XLA from folding
    sum-of-dots back into the one dot (it does: 2.5e-3 again). Rows past
    the last whole block, and tables of under two blocks, take the plain
    ``HIGHEST`` dot, which is exact at that length."""
    n = A.shape[0]
    nb = n // ROW_BLOCK
    exact = jax.lax.Precision.HIGHEST
    if nb < 2:
        return jnp.dot(A.T, B, precision=exact)
    m = nb * ROW_BLOCK
    parts = jnp.einsum(
        "nbi,nbj->nij", A[:m].reshape(nb, ROW_BLOCK, A.shape[1]),
        B[:m].reshape(nb, ROW_BLOCK, B.shape[1]), precision=exact)
    out = jnp.sum(jax.lax.optimization_barrier(parts), axis=0)
    if m < n:
        out = out + jnp.dot(A[m:].T, B[m:], precision=exact)
    return out


#: guard for total-weight division on empty/fully-filtered tables
EPS_TOTAL_WEIGHT = 1e-12


@jax.jit
def weighted_moments(X, w):
    """Per-column weighted moments of row-sharded X.

    Returns (mean[d], var[d], total_weight[]) — population variance, the
    MLlib convention for standardization.
    """
    tot = jnp.maximum(jnp.sum(w), EPS_TOTAL_WEIGHT)
    wcol = w[:, None]
    mean = jnp.sum(X * wcol, axis=0) / tot
    var = jnp.sum((X - mean) ** 2 * wcol, axis=0) / tot
    return mean, var, tot


@jax.jit
def weighted_quantiles(X, w, qs):
    """Per-column weighted quantiles (DataFrame.approxQuantile parity).

    Exact (not sketch-based like Spark's Greenwald-Khanna): a full device sort
    per column — O(N log N) on-device beats a host-side streaming sketch until
    N no longer fits HBM, and it keeps the op usable inside jitted pipelines
    (QuantileDiscretizer, GBT binning). Padding/filtered rows (w==0) are
    excluded by the cumulative-weight search (including q=0, which returns the
    smallest LIVE value, not a padding zero). Columns with zero total weight
    return 0.0.

    X: f32[N, d] row-sharded; w: f32[N] or f32[N, d] per-cell weights
    (per-cell lets Imputer batch its per-column missing masks into one call).
    Returns f32[q, d].
    """
    W2 = w[:, None] * jnp.ones_like(X) if w.ndim == 1 else w
    order = jnp.argsort(X, axis=0)                       # [N, d]
    Xs = jnp.take_along_axis(X, order, axis=0)
    ws = jnp.take_along_axis(W2, order, axis=0)
    cw = jnp.cumsum(ws, axis=0)
    tot_raw = cw[-1]                                     # [d]
    tot = jnp.maximum(tot_raw, EPS_TOTAL_WEIGHT)
    # clip the target above zero so leading zero-weight (padding) runs — where
    # cw is still exactly 0 — are never selected, even at q=0
    targets = jnp.maximum(qs[:, None] * tot[None, :], EPS_TOTAL_WEIGHT)
    idx = jnp.sum(cw[None, :, :] < targets[:, None, :], axis=1)
    idx = jnp.clip(idx, 0, X.shape[0] - 1)
    out = jnp.take_along_axis(Xs, idx, axis=0)
    return jnp.where(tot_raw[None, :] > 0, out, 0.0)


@jax.jit
def inv_std_scale(X, w):
    """1/std per column (1.0 for constant columns) — MLlib-style scale-only
    standardization factor."""
    _, var, _ = weighted_moments(X, w)
    std = jnp.sqrt(var)
    return jnp.where(std > 1e-12, 1.0 / std, 1.0)


def two_sided_z_pvalue(z):
    """2·Φ̄(|z|) — two-sided normal test, on device via erfc."""
    return jax.scipy.special.erfc(jnp.abs(z) / jnp.sqrt(jnp.float32(2.0)))


def two_sided_t_pvalue(t, df):
    """2·sf_t(|t|; df) — two-sided Student-t test via the regularized
    incomplete beta identity, on device."""
    df = jnp.maximum(df, 1.0)
    return jax.scipy.special.betainc(df / 2.0, 0.5, df / (df + t * t))
