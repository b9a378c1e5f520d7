"""Gradient-boosted trees — parity with ``pyspark.ml.classification.GBTClassifier``
and GBTRegressor.

MLlib boosts depth-limited trees on residuals with variance-based splits
(SURVEY.md §2b; reconstructed, mount empty). This implementation boosts on
GRADIENT/HESSIAN histograms (XGBoost-style second-order gains and leaf
values) — a strict quality upgrade at identical per-round cost, since the
histogram machinery (_tree.py) is shared with RandomForest. Each round is one
jitted device program (bin lookup reused, no rebinning); the margin vector F
stays device-resident across rounds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.core.domain import ContinuousVariable, DiscreteVariable, Domain
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.models._tree import (
    normalize_importances,
    Tree,
    bin_features,
    compute_bin_edges,
    grow_tree,
    leaf_newton_values,
    tree_apply,
)
from orange3_spark_tpu.models.base import Estimator, Model, Params
from orange3_spark_tpu.obs.trace import span_iter
from orange3_spark_tpu.utils.dispatch import bound_dispatch

EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class GBTParams(Params):
    max_iter: int = 20            # MLlib maxIter (number of trees)
    max_depth: int = 5            # MLlib maxDepth
    step_size: float = 0.1        # MLlib stepSize (learning rate)
    max_bins: int = 32            # MLlib maxBins
    min_instances_per_node: float = 1.0
    min_info_gain: float = 0.0
    subsampling_rate: float = 1.0 # MLlib subsamplingRate
    reg_lambda: float = 1.0       # newton leaf regularization (beyond MLlib)
    seed: int = 0


from functools import partial  # noqa: E402


@partial(jax.jit, static_argnames=("p", "loss", "depth", "n_bins"))
def _gbt_round(F, B, edges, W, y, boot_key, *, p: GBTParams, loss: str,
               depth: int, n_bins: int):
    """One boosting round. Module-level + GBTParams as a static arg (frozen
    dataclass, hashable) so repeated fits with the same hyper-params and
    shapes hit the jit cache instead of recompiling."""
    N, d = B.shape
    feat_keep = jnp.ones((depth, d), jnp.float32)
    boot = (
        jax.random.poisson(boot_key, p.subsampling_rate, (N,)).astype(jnp.float32)
        if p.subsampling_rate != 1.0 else jnp.ones((N,), jnp.float32)
    )
    w = W * boot
    if loss == "logistic":
        prob = jax.nn.sigmoid(F)
        g = (prob - y) * w
        h = jnp.maximum(prob * (1 - prob), 1e-6) * w
    else:  # squared
        g = (F - y) * w
        h = w
    S = jnp.stack([g, h, w], axis=1)
    tree, leaf_idx, imp = grow_tree(
        B, S, edges, feat_keep, jnp.float32(p.min_info_gain),
        depth=depth, n_bins=n_bins, gain_mode="newton", reg=p.reg_lambda,
        min_instances=p.min_instances_per_node,
    )
    values = leaf_newton_values(tree.leaf_value, p.reg_lambda)  # [L]
    F_new = F + p.step_size * values[leaf_idx]
    # store leaf scalar values in leaf_value[..., :1] for serving
    tree = tree._replace(leaf_value=values[:, None])
    # per-tree-normalized, as MLlib's ensemble featureImportances expects
    return F_new, tree, normalize_importances(imp)


def _boost(B, edges, W, y, depth, n_bins, p: GBTParams, loss: str):
    """Sequential boosting loop; rounds share one cached jitted program."""
    N, _ = B.shape
    key = jax.random.PRNGKey(p.seed)
    if loss == "logistic":
        pos_w = jnp.sum(jnp.where(y > 0, W, 0.0))
        tot_w = jnp.maximum(jnp.sum(W), EPS)
        prior = jnp.clip(pos_w / tot_w, 1e-6, 1 - 1e-6)
        f0 = jnp.log(prior / (1 - prior))
    else:
        f0 = jnp.sum(y * W) / jnp.maximum(jnp.sum(W), EPS)
    F = jnp.full((N,), f0)

    trees = []
    imps = []
    for r in span_iter("gbt_round", range(p.max_iter)):
        key, sub = jax.random.split(key)
        F, tree, imp = _gbt_round(F, B, edges, W, y, sub, p=p, loss=loss,
                                  depth=depth, n_bins=n_bins)
        trees.append(tree)
        imps.append(imp)
        # rounds are heavyweight: keep at most 4 in flight
        # (utils/dispatch.py has the full story on the XLA:CPU rendezvous
        # wedge this prevents)
        bound_dispatch(r + 1, F, period=4)
    jax.block_until_ready(trees)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    # MLlib ensemble featureImportances: mean of per-tree-normalized,
    # renormalized
    imp = normalize_importances(jnp.mean(jnp.stack(imps), axis=0))
    return float(f0), stacked, imp


@jax.jit
def _gbt_margin(X, f0, step_size, forest: Tree):
    leaves = jax.vmap(lambda t: tree_apply(X, t))(forest)            # [T, N]
    vals = jnp.take_along_axis(forest.leaf_value[..., 0], leaves, 1)  # [T, N]
    return f0 + step_size * jnp.sum(vals, axis=0)


class GBTClassifierModel(Model):
    def __init__(self, params, f0, forest: Tree, class_values):
        self.params = params
        self.f0 = f0
        self.forest = forest
        self.class_values = tuple(class_values)

    @property
    def state_pytree(self):
        return {"f0": jnp.float32(self.f0), **self.forest._asdict()}

    def _margin(self, X):
        return _gbt_margin(X, self.f0, self.params.step_size, self.forest)

    def predict_proba(self, table: TpuTable) -> np.ndarray:
        p1 = jax.nn.sigmoid(self._margin(table.X))
        return np.asarray(jnp.stack([1 - p1, p1], 1))[: table.n_rows]

    def predict(self, table: TpuTable) -> np.ndarray:
        return np.asarray((self._margin(table.X) > 0).astype(jnp.float32))[
            : table.n_rows
        ]

    def transform(self, table: TpuTable) -> TpuTable:
        p1 = jax.nn.sigmoid(self._margin(table.X))
        pred = (p1 > 0.5).astype(jnp.float32)
        new_attrs = list(table.domain.attributes) + [
            ContinuousVariable(f"probability_{self.class_values[0]}"),
            ContinuousVariable(f"probability_{self.class_values[1]}"),
            DiscreteVariable("prediction", self.class_values),
        ]
        new_domain = Domain(new_attrs, table.domain.class_vars, table.domain.metas)
        X = jnp.concatenate(
            [table.X, (1 - p1)[:, None], p1[:, None], pred[:, None]], axis=1
        )
        return table.with_X(X, new_domain)


class GBTClassifier(Estimator):
    """Binary classifier (MLlib GBTClassifier is binary-only too)."""

    ParamsCls = GBTParams
    params: GBTParams

    def _fit(self, table: TpuTable) -> GBTClassifierModel:
        p = self.params
        y = table.y
        cvar = table.domain.class_var
        class_values = (
            cvar.values if isinstance(cvar, DiscreteVariable) and cvar.values
            else ("0", "1")
        )
        if len(class_values) != 2:
            raise ValueError("GBTClassifier is binary (MLlib parity)")
        edges = compute_bin_edges(table.X, table.W, p.max_bins)
        B = bin_features(table.X, edges)
        f0, forest, imp = _boost(B, edges, table.W, y, p.max_depth, p.max_bins, p,
                            loss="logistic")
        model = GBTClassifierModel(p, f0, forest, class_values)
        model.feature_importances_ = imp   # MLlib featureImportances
        return model


class GBTRegressorModel(Model):
    def __init__(self, params, f0, forest: Tree):
        self.params = params
        self.f0 = f0
        self.forest = forest

    @property
    def state_pytree(self):
        return {"f0": jnp.float32(self.f0), **self.forest._asdict()}

    def predict(self, table: TpuTable) -> np.ndarray:
        m = _gbt_margin(table.X, self.f0, self.params.step_size, self.forest)
        return np.asarray(m)[: table.n_rows]

    def transform(self, table: TpuTable) -> TpuTable:
        yhat = _gbt_margin(table.X, self.f0, self.params.step_size, self.forest)
        new_domain = Domain(
            list(table.domain.attributes) + [ContinuousVariable("prediction")],
            table.domain.class_vars, table.domain.metas,
        )
        X = jnp.concatenate([table.X, yhat[:, None]], axis=1)
        return table.with_X(X, new_domain)


class GBTRegressor(Estimator):
    ParamsCls = GBTParams
    params: GBTParams

    def _fit(self, table: TpuTable) -> GBTRegressorModel:
        p = self.params
        edges = compute_bin_edges(table.X, table.W, p.max_bins)
        B = bin_features(table.X, edges)
        f0, forest, imp = _boost(B, edges, table.W, table.y, p.max_depth, p.max_bins,
                            p, loss="squared")
        model = GBTRegressorModel(p, f0, forest)
        model.feature_importances_ = imp   # MLlib featureImportances
        return model
