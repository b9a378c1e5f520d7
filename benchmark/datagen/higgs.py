"""HIGGS-shaped rows from a seed: ``bench_suite.gen_higgs`` copied (28
standard-normal features, a signal of pairwise products, a radial term and a
signed term that trees can learn and a linear model cannot, label noise
0.5), so that the yardstick does not move when the program's copy does."""

from __future__ import annotations

import numpy as np


def rows(n_rows: int, n_feat: int, seed: int) -> tuple:
    """-> (X f32[n_rows, n_feat], y f32[n_rows] in {0, 1})."""
    rng = np.random.default_rng(int(seed))
    X = rng.standard_normal((n_rows, n_feat), dtype=np.float32)
    z = (X[:, 0] * X[:, 1] - X[:, 2] * X[:, 3]
         + 0.8 * (X[:, 4] ** 2 - 1.0)
         + 0.6 * np.sign(X[:, 5]) * X[:, 6])
    y = (z + 0.5 * rng.standard_normal(n_rows).astype(np.float32) > 0
         ).astype(np.float32)
    return X, y
