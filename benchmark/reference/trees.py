"""Plain reference of the histogram tree fits (BASELINE config 3): quantile
binning, level-by-level growth of perfect depth-D trees from per-(feature,
node, bin) sums, Newton-boosted GBT and a bootstrap forest with per-level
feature subsets, and prediction. Straightforward jax.numpy: histograms are
``segment_sum``s, one tree at a time, one level at a time. Imports nothing
of the program and takes nothing it has made.

What the configuration states, restated here independently (unit row
weights, N rows, d features, b bins, depth D):
- edge q of a feature (q = 1..b-1) is its ceil(q/b * N)-th smallest value;
  a value's bin is the number of edges strictly below it;
- a level's candidates are "bins <= c go left" for every feature and c;
  a candidate needs weight >= 1 on both sides; the best is the first
  maximum in (feature, bin) order and splits only if its gain is > 0; a
  node that does not split sends every row left;
- GBT ('newton'): F0 = logit(mean y) clipped to [1e-6, 1 - 1e-6]; per round
  g = sigmoid(F) - y, h = max(p(1-p), 1e-6); gain = 0.5 (G_l^2/(H_l+1) +
  G_r^2/(H_r+1) - G^2/(H+1)); leaf value -G/(H+1); F += 0.1 * value;
  probability = sigmoid(F0 + 0.1 * sum of the rows' leaf values);
- forest ('gini'): tree t draws (k_b, k_f) = split(split(PRNGKey(seed), T)[t]),
  row weights Poisson(1) from k_b, a feature mask Bernoulli(sqrt(d)/d) of
  shape [D, d] from k_f (a level with no feature kept keeps all); stats are
  the weighted class counts; gain = gini(node) - gini(left) - gini(right)
  with gini(S) = c - sum S_i^2 / c; a leaf's distribution is its class
  counts over their sum (uniform where empty); probability = mean over trees.

Boosting is a chain of greedy choices: on 3 of 16 seeds the program's fit
on the chip leaves this file's own fit at some node and every later round
differs from there on (PERF.md section 7, PR 25). ``check_gbt`` follows
GIVEN trees round by round and reads each split, leaf value and probability
where it was made: a witness for that look, not what decides ``correct``.
The forest's statistics are whole numbers and its histograms exact.

``precision='bfloat16'`` is the control: the per-row statistics are rounded
to bfloat16 before they are summed — what a single-pass MXU contraction
would do to them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


def bin_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """f32[d, n_bins - 1]."""
    n = X.shape[0]
    srt = np.sort(X, axis=0)
    q = np.linspace(0.0, 1.0, n_bins + 1, dtype=np.float32)[1:-1]
    target = q * np.float32(n)
    idx = np.clip(np.ceil(target).astype(np.int64) - 1, 0, n - 1)
    return np.ascontiguousarray(srt[idx].T)


def bin_rows(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """i32[n, d]: the number of edges strictly below each value."""
    return np.stack([np.searchsorted(edges[j], X[:, j], side="left")
                     for j in range(X.shape[1])], axis=1).astype(np.int32)


def _gain(Hc, mode: str):
    """Hc f32[d, nodes, bins, s] cumulative over bins -> gains, -inf where a
    side is lighter than one row."""
    total = Hc[:, :, -1:, :]
    left, right = Hc, total - Hc
    if mode == "gini":
        def imp(S):
            c = jnp.sum(S, -1)
            return c - jnp.sum(S * S, -1) / jnp.maximum(c, EPS)
        gain = imp(total) - imp(left) - imp(right)
        wl, wr = jnp.sum(left, -1), jnp.sum(right, -1)
    else:
        def score(S):
            return S[..., 0] ** 2 / jnp.maximum(S[..., 1] + 1.0, EPS)
        gain = 0.5 * (score(left) + score(right) - score(total))
        wl, wr = left[..., 2], right[..., 2]
    return jnp.where((wl >= 1.0) & (wr >= 1.0), gain, -jnp.inf)


def _flat_gains(B, S, pos, keep, nodes: int, n_bins: int, mode: str):
    """-> gains f32[nodes, d * n_bins] in (feature, bin) order, and each
    node's sums f32[nodes, s]."""
    d = B.shape[1]
    H = jnp.stack([jax.ops.segment_sum(S, pos * n_bins + B[:, j],
                                       num_segments=nodes * n_bins)
                   for j in range(d)])
    Hc = jnp.cumsum(H.reshape(d, nodes, n_bins, -1), axis=2)
    gains = jnp.where(keep[:, None, None] > 0, _gain(Hc, mode), -jnp.inf)
    return (gains.transpose(1, 0, 2).reshape(nodes, d * n_bins),
            Hc[0, :, -1, :])


def _go(B, feat, cut, pos):
    right = jnp.take_along_axis(B, feat[pos][:, None], 1)[:, 0] > cut[pos]
    return 2 * pos + right.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nodes", "n_bins", "mode"))
def _level(B, S, pos, keep, *, nodes: int, n_bins: int, mode: str):
    """One level: -> (feature i32[nodes], bin i32[nodes], new pos)."""
    flat, _ = _flat_gains(B, S, pos, keep, nodes, n_bins, mode)
    best = jnp.argmax(flat, axis=1)
    split = jnp.take_along_axis(flat, best[:, None], 1)[:, 0] > 0.0
    feat = jnp.where(split, best // n_bins, 0).astype(jnp.int32)
    cut = jnp.where(split, best % n_bins, n_bins).astype(jnp.int32)
    return feat, cut, _go(B, feat, cut, pos)


@functools.partial(jax.jit, static_argnames=("nodes", "n_bins"))
def _level_gap(B, S, pos, feat, cut, *, nodes: int, n_bins: int):
    """One level of GIVEN Newton splits: by how much each node's given
    split falls short of the best candidate, as a share of the scores it is
    a difference of (G_l^2/(H_l+1) + G_r^2/(H_r+1) at the best) — the unit
    rounding acts in. A node given as a leaf is held against gain 0.
    -> (shortfall f32[nodes], new pos)."""
    keep = jnp.ones((B.shape[1],), jnp.float32)
    flat, tot = _flat_gains(B, S, pos, keep, nodes, n_bins, "newton")
    best = jnp.max(flat, axis=1)
    given = jnp.take_along_axis(
        flat, (feat * n_bins + jnp.minimum(cut, n_bins - 1))[:, None],
        1)[:, 0]
    given = jnp.where(cut >= n_bins, 0.0, given)
    best = jnp.maximum(best, 0.0)          # no valid candidate: a leaf
    score = tot[:, 0] ** 2 / jnp.maximum(tot[:, 1] + 1.0, EPS)
    short = (best - given) / jnp.maximum(2.0 * best + score, EPS)
    return short, _go(B, feat, cut, pos)


def grow(B, S, keep, *, depth: int, n_bins: int, mode: str):
    """-> (features [levels][nodes], cuts, leaf sums f32[2^depth, s], leaf
    of each row)."""
    pos = jnp.zeros((B.shape[0],), jnp.int32)
    feats, cuts = [], []
    for level in range(depth):
        f, c, pos = _level(B, S, pos, keep[level], nodes=2 ** level,
                           n_bins=n_bins, mode=mode)
        feats.append(f)
        cuts.append(c)
    leaf = jax.ops.segment_sum(S, pos, num_segments=2 ** depth)
    return feats, cuts, leaf, pos


@jax.jit
def _route(B, feats, cuts):
    pos = jnp.zeros((B.shape[0],), jnp.int32)
    for f, c in zip(feats, cuts):
        pos = _go(B, f, c, pos)
    return pos


def _stats(S, precision: str):
    if precision == "float32":
        return S
    return S.astype(jnp.dtype(precision)).astype(jnp.float32)


def fit_gbt(B, y, B_eval, *, rounds: int, depth: int, n_bins: int,
            step: float = 0.1, precision: str = "float32",
            fault: str | None = None) -> tuple:
    """-> (probability of class 1 for the rows of ``B_eval``, the trees as
    ``check_gbt`` takes them)."""
    B, B_eval = jnp.asarray(B), jnp.asarray(B_eval)
    y = jnp.asarray(y, jnp.float32)
    f0 = _prior_logit(y)
    F = jnp.full(y.shape, f0)
    margin = jnp.full((B_eval.shape[0],), f0)
    keep = jnp.ones((depth, B.shape[1]), jnp.float32)
    trees = {"f0": float(f0), "feature": [], "split_bin": [], "leaf": []}
    for r in range(rounds):
        p = jax.nn.sigmoid(F)
        S = jnp.stack([p - y, jnp.maximum(p * (1 - p), 1e-6),
                       jnp.ones_like(y)], 1)
        if fault == "half_batch":
            S = S.at[S.shape[0] // 2:].set(0.0)
        feats, cuts, leaf, pos = grow(B, _stats(S, precision), keep,
                                      depth=depth, n_bins=n_bins,
                                      mode="newton")
        value = -leaf[:, 0] / jnp.maximum(leaf[:, 1] + 1.0, EPS)
        trees["feature"].append(np.concatenate(feats))
        trees["split_bin"].append(np.concatenate(cuts))
        trees["leaf"].append(np.asarray(value))
        margin = margin + step * value[_route(B_eval, feats, cuts)]
        if fault == "skip_step" and r == 1:
            continue                      # the round leaves F as it was
        F = F + step * value[pos]
    trees = {k: np.stack(v) if k != "f0" else v for k, v in trees.items()}
    return np.asarray(jax.nn.sigmoid(margin)), trees


def _prior_logit(y):
    prior = jnp.clip(jnp.mean(y), 1e-6, 1 - 1e-6)
    return jnp.log(prior / (1 - prior))


def check_gbt(B, y, B_eval, proba, trees: dict, *, depth: int, n_bins: int,
              step: float = 0.1) -> dict:
    """Follow GIVEN boosted trees (level order: ``feature``, ``split_bin``
    i32[T, 2^D - 1], ``leaf`` f32[T, 2^D], ``f0``) round by round and read
    each choice where it was made: ``split`` — the mean over all nodes of a
    given split's shortfall against the best candidate of its node
    (``_level_gap``), ``split_widest`` the widest, ``split_off`` the number
    of nodes that fall short at all; ``leaf`` — the widest gap
    of a given leaf value against -G/(H+1) of its rows, as a share of the
    tree's largest; ``proba`` — the mean gap of the given probabilities
    against routing the rows of ``B_eval`` through the given trees. A
    witness, not a comparison: a sound float32 fit in another row order
    reads ``split_widest`` up to 3.3e-4 and the bfloat16 control as little
    as 4.4e-4 (CPU, 37 seeds); ``split_off`` reads 0 to 2 against 12 to 33
    (PERF.md section 6, PR 25)."""
    B, B_eval = jnp.asarray(B), jnp.asarray(B_eval)
    y = jnp.asarray(y, jnp.float32)
    f0 = _prior_logit(y)
    out = {"proba": 0.0,
           "leaf": abs(float(f0) - float(trees["f0"])) / abs(float(f0))}
    shorts = []
    F = jnp.full(y.shape, f0)
    margin = jnp.full((B_eval.shape[0],), f0)
    for feature, split_bin, given in zip(trees["feature"],
                                         trees["split_bin"], trees["leaf"]):
        p = jax.nn.sigmoid(F)
        S = jnp.stack([p - y, jnp.maximum(p * (1 - p), 1e-6),
                       jnp.ones_like(y)], 1)
        pos = jnp.zeros((B.shape[0],), jnp.int32)
        feats, cuts = [], []
        for level in range(depth):
            lo, hi = 2 ** level - 1, 2 ** (level + 1) - 1
            f = jnp.asarray(feature[lo:hi], jnp.int32)
            c = jnp.asarray(split_bin[lo:hi], jnp.int32)
            short, pos = _level_gap(B, S, pos, f, c, nodes=2 ** level,
                                    n_bins=n_bins)
            shorts.append(np.asarray(short, np.float64))
            feats.append(f)
            cuts.append(c)
        leaf = jax.ops.segment_sum(S, pos, num_segments=2 ** depth)
        value = -leaf[:, 0] / jnp.maximum(leaf[:, 1] + 1.0, EPS)
        given = jnp.asarray(given, jnp.float32)
        out["leaf"] = max(out["leaf"], float(
            jnp.max(jnp.abs(given - value)) / jnp.max(jnp.abs(value))))
        F = F + step * value[pos]
        margin = margin + step * given[_route(B_eval, feats, cuts)]
    gap = np.abs(np.asarray(jax.nn.sigmoid(margin), np.float64)
                 - np.asarray(proba, np.float64))
    out["proba"] = float(np.mean(gap))
    shorts = np.concatenate(shorts)
    out["split"] = float(np.mean(shorts))
    out["split_widest"] = float(np.max(shorts))
    out["split_off"] = int(np.sum(shorts > 0))
    return out


def fit_forest(B, y, B_eval, *, trees: int, depth: int, n_bins: int,
               seed: int = 0, precision: str = "float32",
               fault: str | None = None) -> np.ndarray:
    B, B_eval = jnp.asarray(B), jnp.asarray(B_eval)
    n, d = B.shape
    onehot = jax.nn.one_hot(jnp.asarray(y).astype(jnp.int32), 2,
                            dtype=jnp.float32)
    keep_p = np.sqrt(d) / d
    total = jnp.zeros((B_eval.shape[0],), jnp.float32)
    for key in jax.random.split(jax.random.PRNGKey(seed), trees):
        kb, kf = jax.random.split(key)
        boot = jax.random.poisson(kb, 1.0, (n,)).astype(jnp.float32)
        if fault == "half_batch":
            boot = boot.at[n // 2:].set(0.0)
        keep = jax.random.bernoulli(kf, keep_p, (depth, d)).astype(
            jnp.float32)
        keep = jnp.where(jnp.sum(keep, 1, keepdims=True) > 0, keep, 1.0)
        feats, cuts, leaf, _ = grow(
            B, _stats(onehot * boot[:, None], precision), keep, depth=depth,
            n_bins=n_bins, mode="gini")
        tot = jnp.sum(leaf, -1, keepdims=True)
        probs = jnp.where(tot > 0, leaf / jnp.maximum(tot, EPS), 0.5)
        total = total + probs[_route(B_eval, feats, cuts), 1]
    return np.asarray(total / trees)
