"""PCA — parity with ``pyspark.ml.feature.PCA``.

MLlib computes a distributed Gramian (RowMatrix.computeCovariance via
treeAggregate) then a local SVD (SURVEY.md §2b row "PCA"; reconstructed,
mount empty). Identical shape here: one ICI-all-reduced [d,d] Gramian matmul,
then ``jnp.linalg.eigh`` on the replicated covariance — d is small, N is the
distributed dimension.

Transform follows Orange's PCA widget semantics: the output table's
attributes ARE the principal components (PC1..PCk); original columns are
replaced (Spark instead appends a vector column — same information, flat
columnar form).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.models.base import Estimator, Model, Params
from orange3_spark_tpu.parallel.collectives import distributed_gramian


@dataclasses.dataclass(frozen=True)
class PCAParams(Params):
    k: int = 2          # MLlib k: number of principal components
    center: bool = True # Orange centers; MLlib PCA does too (covariance)


class PCAModel(Model):
    def __init__(self, params, components, mean, explained_variance, total_variance):
        self.params = params
        self.components = components                  # f32[d, k] (columns = PCs)
        self.mean = mean                              # f32[d]
        self.explained_variance = explained_variance  # f32[k]
        self.total_variance = total_variance          # f32[] trace of covariance

    @property
    def state_pytree(self):
        return {
            "components": self.components,
            "mean": self.mean,
            "explained_variance": self.explained_variance,
            "total_variance": self.total_variance,
        }

    @property
    def explained_variance_ratio_(self) -> np.ndarray:
        ev = np.asarray(self.explained_variance)
        tot = float(self.total_variance)
        return ev / tot if tot > 0 else ev

    @staticmethod
    @jax.jit
    def _project(X, components, mean):
        # [N,d]@[d,k] on the MXU, float32 products (the TPU's default
        # rounds the operands to bfloat16)
        return jnp.dot(X - mean, components,
                       precision=jax.lax.Precision.HIGHEST)

    def transform(self, table: TpuTable) -> TpuTable:
        Z = self._project(table.X, self.components, self.mean)
        k = self.components.shape[1]
        new_domain = Domain(
            [ContinuousVariable(f"PC{i + 1}") for i in range(k)],
            table.domain.class_vars,
            table.domain.metas,
        )
        return table.with_X(Z, new_domain)


class PCA(Estimator):
    ParamsCls = PCAParams
    params: PCAParams

    def _fit(self, table: TpuTable) -> PCAModel:
        p = self.params
        if p.k > table.n_attrs:
            raise ValueError(f"k={p.k} exceeds n_features={table.n_attrs}")
        with jax.named_scope("pca/cov"):
            G, mean, tot = distributed_gramian(table.X, table.W,
                                               center=p.center)
        return self._finalize(G / tot, mean)

    def _finalize(self, cov, mean) -> PCAModel:
        p = self.params
        with jax.named_scope("pca/eigh"):
            eigvals, eigvecs = jnp.linalg.eigh(cov)   # ascending
        order = jnp.argsort(eigvals)[::-1][: p.k]
        components = eigvecs[:, order]
        explained = jnp.maximum(eigvals[order], 0.0)
        total = jnp.maximum(jnp.trace(cov), 0.0)
        if not p.center:
            mean = jnp.zeros_like(mean)
        return PCAModel(p, components, mean, explained, total)

    def fit_stream(self, source, *, session=None,
                   chunk_rows: int = 1 << 18,
                   stage_times: dict | None = None) -> PCAModel:
        """Out-of-core fit: ONE pass accumulating the (shift-centered)
        weighted Gramian — one MXU matmul per chunk — plus column means
        over a chunk stream (io/streaming.stream_feature_stats), then the
        same eigh finalize as the in-memory path; the 1B-row taxi
        pipeline's PCA no longer needs the rows in memory.

        The Gramian fold donates its accumulator (exec/donate.py sweep:
        the running [d, d] stats never leave HBM and the fold reuses the
        buffer) and the parse/DMA of chunk t+1 overlaps the fold of chunk
        t; ``stage_times`` receives the pass's measured ``overlap_pct``
        and ``dispatches`` (exec/pipeline.py)."""
        from orange3_spark_tpu.io.streaming import stream_feature_stats

        # validate k BEFORE the pass — an invalid k must fail in one chunk,
        # not after a multi-hour out-of-core Gramian sweep
        first = next(iter(source()), None)
        if first is not None:
            X0 = first[0] if isinstance(first, tuple) else first
            if self.params.k > X0.shape[1]:
                raise ValueError(f"k={self.params.k} exceeds n_features="
                                 f"{X0.shape[1]}")
        st = stream_feature_stats(source, session=session,
                                  chunk_rows=chunk_rows, gramian=True,
                                  stage_times=stage_times)
        cov = jnp.asarray(
            st["cov"] if self.params.center else st["second_moment"],
            jnp.float32)
        return self._finalize(cov, jnp.asarray(st["mean"], jnp.float32))
