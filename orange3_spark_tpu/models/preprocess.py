"""Feature transformers — ``pyspark.ml.feature`` capability parity.

The reference's transformer widgets wrap MLlib feature Estimators/Transformers
(SURVEY.md §2b row "Feature transformers"; reconstructed, mount empty).
TPU-native redesign: every fitted state is a small pytree of device arrays;
every transform is a jitted columnar op over the one sharded X matrix, so a
chain of transformers fuses into a single XLA program when staged.

Column addressing: ``input_cols=None`` means "all continuous attributes" for
scalers/imputer, matching the common Spark VectorAssembler-then-scale idiom
without needing an assembled vector column (our table IS the assembled
matrix). VectorAssembler is therefore a thin select/concat for API parity.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.core.domain import (
    ContinuousVariable,
    DiscreteVariable,
    Domain,
    StringVariable,
)
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.models.base import Estimator, Model, Params, Transformer
from orange3_spark_tpu.ops.stats import weighted_moments, weighted_quantiles


def _col_indices(table: TpuTable, input_cols: Sequence[str] | None) -> np.ndarray:
    if input_cols is None:
        idxs = [
            i for i, v in enumerate(table.domain.attributes)
            if isinstance(v, ContinuousVariable)
        ]
    else:
        idxs = [table.domain.index(c) for c in input_cols]
    return np.asarray(idxs, dtype=np.int32)


def _select_cols(table: TpuTable, idxs: np.ndarray):
    """``table.X[:, idxs]``; every column in order is the table itself. A
    take would copy it whole — on the TPU as thousands of gathers: 4.3 GB
    of temp and minutes of compile at 2^27 x 8 (PERF.md, PR 35)."""
    if np.array_equal(idxs, np.arange(table.X.shape[1])):
        return table.X
    return jnp.take(table.X, idxs, axis=1)


def _scale_transform(X, idxs, shift, scale):
    """X'[:, idxs] = (X[:, idxs] - shift) * scale, fused as one scatter-free op."""
    full_shift = jnp.zeros((X.shape[1],), X.dtype).at[idxs].set(shift)
    full_scale = jnp.ones((X.shape[1],), X.dtype).at[idxs].set(scale)
    return (X - full_shift) * full_scale


_scale_transform_jit = jax.jit(_scale_transform)


# ---------------------------------------------------------------------------
# Scalers
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StandardScalerParams(Params):
    with_mean: bool = False  # MLlib withMean (False default, like Spark)
    with_std: bool = True    # MLlib withStd
    input_cols: tuple | None = None


class _ColumnScaleModel(Model):
    """Shared shift-and-scale fitted state."""

    def __init__(self, params, idxs, shift, scale):
        self.params = params
        self.idxs = idxs
        self.shift = shift
        self.scale = scale

    @property
    def state_pytree(self):
        return {"idxs": self.idxs, "shift": self.shift, "scale": self.scale}

    def transform(self, table: TpuTable) -> TpuTable:
        X = _scale_transform_jit(table.X, self.idxs, self.shift, self.scale)
        return table.with_X(X)


class StandardScalerModel(_ColumnScaleModel):
    @property
    def mean(self):
        return self.shift

    @property
    def std(self):
        return 1.0 / self.scale


class StandardScaler(Estimator):
    ParamsCls = StandardScalerParams
    params: StandardScalerParams

    def _fit(self, table: TpuTable) -> StandardScalerModel:
        p = self.params
        idxs = _col_indices(table, p.input_cols)
        Xsel = _select_cols(table, idxs)
        with jax.named_scope("scale/moments"):
            mean, var, _ = weighted_moments(Xsel, table.W)
        return self._finalize(mean, var, jnp.asarray(idxs))

    def _finalize(self, mean, var, idxs) -> StandardScalerModel:
        p = self.params
        mean = jnp.asarray(mean, jnp.float32)
        std = jnp.sqrt(jnp.asarray(var, jnp.float32))
        scale = jnp.where(std > 1e-12, 1.0 / std, 1.0) if p.with_std \
            else jnp.ones_like(std)
        shift = mean if p.with_mean else jnp.zeros_like(mean)
        return StandardScalerModel(p, idxs, shift, scale)

    def fit_stream(self, source, *, session=None,
                   chunk_rows: int = 1 << 18) -> StandardScalerModel:
        """Out-of-core fit: ONE pass of per-column moments over a chunk
        stream (io/streaming.stream_feature_stats) — same population-
        variance convention as the in-memory fit, at any row count. The
        stream's columns are the features (``input_cols`` must be unset;
        select columns in the source)."""
        if self.params.input_cols is not None:
            raise ValueError("fit_stream scales every stream column; "
                             "select columns in the source instead of "
                             "input_cols")
        from orange3_spark_tpu.io.streaming import stream_feature_stats

        st = stream_feature_stats(source, session=session,
                                  chunk_rows=chunk_rows)
        return self._finalize(st["mean"], st["var"],
                              jnp.arange(len(st["mean"]), dtype=jnp.int32))


@dataclasses.dataclass(frozen=True)
class MinMaxScalerParams(Params):
    min: float = 0.0  # MLlib min
    max: float = 1.0  # MLlib max
    input_cols: tuple | None = None


class MinMaxScaler(Estimator):
    ParamsCls = MinMaxScalerParams
    params: MinMaxScalerParams

    def _fit(self, table: TpuTable) -> _ColumnScaleModel:
        p = self.params
        idxs = _col_indices(table, p.input_cols)
        Xsel = _select_cols(table, idxs)
        live = (table.W > 0)[:, None]
        big = jnp.float32(np.finfo(np.float32).max)
        mn = jnp.min(jnp.where(live, Xsel, big), axis=0)
        mx = jnp.max(jnp.where(live, Xsel, -big), axis=0)
        return self._finalize(mn, mx, jnp.asarray(idxs))

    def _finalize(self, mn, mx, idxs) -> "MinMaxScalerModel":
        p = self.params
        mn = jnp.asarray(mn, jnp.float32)
        rng = jnp.asarray(mx, jnp.float32) - mn
        scale = jnp.where(rng > 1e-12, (p.max - p.min) / rng, 0.0)
        return MinMaxScalerModel(p, idxs, mn, scale)

    def fit_stream(self, source, *, session=None,
                   chunk_rows: int = 1 << 18) -> "MinMaxScalerModel":
        """Out-of-core fit: one pass of per-column min/max over a chunk
        stream; see ``StandardScaler.fit_stream`` for the column rule."""
        if self.params.input_cols is not None:
            raise ValueError("fit_stream scales every stream column; "
                             "select columns in the source instead of "
                             "input_cols")
        from orange3_spark_tpu.io.streaming import stream_feature_stats

        st = stream_feature_stats(source, session=session,
                                  chunk_rows=chunk_rows)
        return self._finalize(st["min"], st["max"],
                              jnp.arange(len(st["min"]), dtype=jnp.int32))


class MinMaxScalerModel(_ColumnScaleModel):
    params: "MinMaxScalerParams"

    def transform(self, table: TpuTable) -> TpuTable:
        X = table.X
        p = self.params
        idxs, mn, scale = self.idxs, self.shift, self.scale
        Xsel = jnp.take(X, idxs, axis=1)
        # Spark maps constant columns (scale==0) to the output-range midpoint;
        # both constants derive from params so checkpoint restore is lossless
        mid_fill = p.min + 0.5 * (p.max - p.min)
        scaled = jnp.where(scale > 0, (Xsel - mn) * scale + p.min, mid_fill)
        Xout = X.at[:, idxs].set(scaled)
        return table.with_X(Xout)


@dataclasses.dataclass(frozen=True)
class MaxAbsScalerParams(Params):
    input_cols: tuple | None = None


class MaxAbsScaler(Estimator):
    ParamsCls = MaxAbsScalerParams

    def _fit(self, table: TpuTable) -> _ColumnScaleModel:
        p = self.params
        idxs = _col_indices(table, p.input_cols)
        Xsel = _select_cols(table, idxs)
        live = (table.W > 0)[:, None]
        mabs = jnp.max(jnp.where(live, jnp.abs(Xsel), 0.0), axis=0)
        scale = jnp.where(mabs > 1e-12, 1.0 / mabs, 1.0)
        return _ColumnScaleModel(p, jnp.asarray(idxs), jnp.zeros_like(scale), scale)


# ---------------------------------------------------------------------------
# Imputer
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ImputerParams(Params):
    strategy: str = "mean"       # MLlib strategy: 'mean' | 'median' | 'mode'
    missing_value: float = float("nan")  # MLlib missingValue
    input_cols: tuple | None = None


class ImputerModel(Model):
    def __init__(self, params, idxs, fill):
        self.params = params
        self.idxs = idxs
        self.fill = fill  # f32[len(idxs)]

    @property
    def state_pytree(self):
        return {"idxs": self.idxs, "fill": self.fill}

    def transform(self, table: TpuTable) -> TpuTable:
        X = table.X
        Xsel = jnp.take(X, self.idxs, axis=1)
        mv = self.params.missing_value
        miss = jnp.isnan(Xsel) if np.isnan(mv) else (Xsel == mv)
        Xout = X.at[:, self.idxs].set(jnp.where(miss, self.fill, Xsel))
        return table.with_X(Xout)


class Imputer(Estimator):
    ParamsCls = ImputerParams
    params: ImputerParams

    def _fit(self, table: TpuTable) -> ImputerModel:
        p = self.params
        idxs = _col_indices(table, p.input_cols)
        Xsel = _select_cols(table, idxs)
        mv = p.missing_value
        miss = jnp.isnan(Xsel) if np.isnan(mv) else (Xsel == mv)
        w_eff = jnp.where(miss, 0.0, table.W[:, None])
        if p.strategy == "mean":
            tot = jnp.maximum(jnp.sum(w_eff, axis=0), 1e-12)
            fill = jnp.sum(jnp.where(miss, 0.0, Xsel) * w_eff, axis=0) / tot
        elif p.strategy == "median":
            # one batched weighted-quantile call; per-cell weights zero out
            # each column's own missing entries
            Xclean = jnp.where(miss, 0.0, Xsel)
            fill = weighted_quantiles(Xclean, w_eff, jnp.asarray([0.5]))[0]
        elif p.strategy == "mode":
            # mode over observed values: host-side exact (small unique sets)
            Xh = np.asarray(jax.device_get(Xsel))
            Wh = np.asarray(jax.device_get(w_eff))
            fills = []
            for j in range(Xh.shape[1]):
                vals = Xh[Wh[:, j] > 0, j]
                if len(vals) == 0:
                    fills.append(0.0)
                else:
                    uniq, counts = np.unique(vals, return_counts=True)
                    fills.append(float(uniq[np.argmax(counts)]))
            fill = jnp.asarray(fills, dtype=jnp.float32)
        else:
            raise ValueError(f"unknown strategy {p.strategy!r}")
        return ImputerModel(p, jnp.asarray(idxs), fill)

    def fit_stream(self, source, *, session=None,
                   chunk_rows: int = 1 << 18) -> ImputerModel:
        """Out-of-core mean-imputer fit: one missing-aware stats pass
        (per-CELL observation masks — a missing cell drops out of its
        column only). 'median'/'mode' need a sketch or a value table and
        stay in-memory; column rule as in ``StandardScaler.fit_stream``."""
        p = self.params
        if p.strategy != "mean":
            raise ValueError(
                f"fit_stream supports strategy='mean' only (got "
                f"{p.strategy!r}); median/mode need the rows in memory")
        if p.input_cols is not None:
            raise ValueError("fit_stream imputes every stream column; "
                             "select columns in the source instead of "
                             "input_cols")
        from orange3_spark_tpu.io.streaming import stream_feature_stats

        st = stream_feature_stats(source, session=session,
                                  chunk_rows=chunk_rows,
                                  missing_value=p.missing_value)
        fill = jnp.asarray(st["mean"], jnp.float32)
        return ImputerModel(p, jnp.arange(len(st["mean"]), dtype=jnp.int32),
                            fill)


# ---------------------------------------------------------------------------
# Discretization & encoding
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BucketizerParams(Params):
    splits: tuple = ()           # MLlib splits: boundaries incl. +-inf allowed
    input_col: str = ""


class Bucketizer(Transformer):
    """Stateless: bin one column by explicit split points (MLlib Bucketizer)."""

    ParamsCls = BucketizerParams

    def __init__(self, params: BucketizerParams | None = None, **kwargs):
        self.params = params or BucketizerParams(**kwargs)
        if len(self.params.splits) < 3:
            raise ValueError("need >= 3 split points (>= 2 buckets)")

    def transform(self, table: TpuTable) -> TpuTable:
        p = self.params
        j = table.domain.index(p.input_col)
        splits = jnp.asarray(p.splits, dtype=jnp.float32)
        binned = jnp.clip(
            jnp.searchsorted(splits, table.X[:, j], side="right") - 1,
            0, len(p.splits) - 2,
        ).astype(jnp.float32)
        n_bins = len(p.splits) - 1
        var = DiscreteVariable(
            f"{p.input_col}_binned", tuple(str(i) for i in range(n_bins))
        )
        new_domain = Domain(
            list(table.domain.attributes) + [var],
            table.domain.class_vars, table.domain.metas,
        )
        X = jnp.concatenate([table.X, binned[:, None]], axis=1)
        return table.with_X(X, new_domain)


@dataclasses.dataclass(frozen=True)
class QuantileDiscretizerParams(Params):
    num_buckets: int = 2         # MLlib numBuckets
    input_col: str = ""


class QuantileDiscretizer(Estimator):
    """Fit quantile split points, return a Bucketizer (MLlib behavior)."""

    ParamsCls = QuantileDiscretizerParams
    params: QuantileDiscretizerParams

    def _fit(self, table: TpuTable) -> Bucketizer:
        p = self.params
        j = table.domain.index(p.input_col)
        qs = jnp.linspace(0.0, 1.0, p.num_buckets + 1)[1:-1]
        inner = weighted_quantiles(table.X[:, j : j + 1], table.W, qs)[:, 0]
        splits = (-np.inf,) + tuple(np.unique(np.asarray(inner)).tolist()) + (np.inf,)
        return Bucketizer(BucketizerParams(splits=splits, input_col=p.input_col))


@dataclasses.dataclass(frozen=True)
class OneHotEncoderParams(Params):
    input_cols: tuple = ()       # discrete attribute names
    drop_last: bool = True       # MLlib dropLast
    handle_invalid: str = "error"  # MLlib handleInvalid: 'error' | 'keep'


class OneHotEncoderModel(Model):
    def __init__(self, params, col_idx, sizes):
        self.params = params
        self.col_idx = col_idx   # list[int]
        self.sizes = sizes       # list[int] categories per column

    @property
    def state_pytree(self):
        return {}

    def transform(self, table: TpuTable) -> TpuTable:
        p = self.params
        pieces, new_vars = [], []
        keep = [
            i for i in range(table.n_attrs) if i not in set(self.col_idx)
        ]
        Xkeep = jnp.take(table.X, jnp.asarray(keep, dtype=jnp.int32), axis=1)
        pieces.append(Xkeep)
        new_vars.extend(table.domain.attributes[i] for i in keep)
        for j, size, name in zip(
            self.col_idx, self.sizes, p.input_cols, strict=True
        ):
            if p.handle_invalid == "error":
                # under drop_last an unseen index would silently alias the
                # dropped last category (one_hot -> all zeros), so check
                live_vals = jnp.where(table.W > 0, table.X[:, j], 0.0)
                mx = int(np.asarray(jnp.max(live_vals)).item())
                if mx >= size:
                    raise ValueError(
                        f"column {name!r} has category index {mx} >= {size} "
                        "unseen at fit (handle_invalid='error')"
                    )
            width = size - 1 if p.drop_last else size
            var = table.domain.attributes[j]
            values = (
                var.values if isinstance(var, DiscreteVariable) and var.values
                else tuple(str(i) for i in range(size))
            )
            onehot = jax.nn.one_hot(
                table.X[:, j].astype(jnp.int32), size, dtype=jnp.float32
            )[:, :width]
            pieces.append(onehot)
            new_vars.extend(
                ContinuousVariable(f"{name}_{values[c]}") for c in range(width)
            )
        new_domain = Domain(new_vars, table.domain.class_vars, table.domain.metas)
        return table.with_X(jnp.concatenate(pieces, axis=1), new_domain)


class OneHotEncoder(Estimator):
    ParamsCls = OneHotEncoderParams
    params: OneHotEncoderParams

    def _fit(self, table: TpuTable) -> OneHotEncoderModel:
        p = self.params
        if not p.input_cols:
            raise ValueError("OneHotEncoder needs input_cols")
        col_idx, sizes = [], []
        for name in p.input_cols:
            var = table.domain[name]
            j = table.domain.index(name)
            col_idx.append(j)
            if isinstance(var, DiscreteVariable) and var.values:
                sizes.append(len(var.values))
            else:  # infer category count from data (Spark OHE fit behavior)
                sizes.append(int(np.asarray(jnp.max(table.X[:, j])).item()) + 1)
        return OneHotEncoderModel(p, col_idx, sizes)


@dataclasses.dataclass(frozen=True)
class StringIndexerParams(Params):
    input_col: str = ""          # a meta (string) column
    order: str = "frequencyDesc" # MLlib stringOrderType
    handle_invalid: str = "error" # 'error' | 'keep' (maps unseen -> n)


class StringIndexerModel(Model):
    def __init__(self, params, labels):
        self.params = params
        self.labels = tuple(labels)

    @property
    def state_pytree(self):
        return {}

    def transform(self, table: TpuTable) -> TpuTable:
        p = self.params
        meta_names = [v.name for v in table.domain.metas]
        mj = meta_names.index(p.input_col)
        strings = np.asarray(table.metas[:, mj], dtype=object)
        live = np.asarray(jax.device_get(table.W))[: len(strings)] > 0
        lut = {s: i for i, s in enumerate(self.labels)}
        out = np.zeros(len(strings), dtype=np.float32)
        for i, s in enumerate(strings):
            if s in lut:
                out[i] = lut[s]
            elif not live[i]:
                out[i] = 0.0  # dead (filtered) rows never error
            elif p.handle_invalid == "keep":
                out[i] = len(self.labels)
            else:
                raise ValueError(f"unseen label {s!r} (handle_invalid='error')")
        pad = np.zeros(table.n_pad, dtype=np.float32)
        pad[: len(out)] = out
        col = jax.device_put(pad, table.session.vector_sharding)
        values = self.labels + (("__unknown__",) if p.handle_invalid == "keep" else ())
        var = DiscreteVariable(f"{p.input_col}_idx", values)
        new_domain = Domain(
            list(table.domain.attributes) + [var],
            table.domain.class_vars, table.domain.metas,
        )
        X = jnp.concatenate([table.X, col[:, None]], axis=1)
        return table.with_X(X, new_domain)


class StringIndexer(Estimator):
    """Meta string column -> discrete index attribute (host-side fit: strings
    never live on device — same boundary Orange draws for metas)."""

    ParamsCls = StringIndexerParams
    params: StringIndexerParams

    def _fit(self, table: TpuTable) -> StringIndexerModel:
        p = self.params
        if table.metas is None:
            raise ValueError("table has no meta columns")
        meta_names = [v.name for v in table.domain.metas]
        if p.input_col not in meta_names:
            raise ValueError(f"no meta column {p.input_col!r}")
        strings = np.asarray(table.metas[:, meta_names.index(p.input_col)], dtype=object)
        # frequency ordering counts only live rows (filter semantics — the
        # scalers/imputer honor W the same way)
        live = np.asarray(jax.device_get(table.W))[: len(strings)] > 0
        uniq, counts = np.unique(strings[live].astype(str), return_counts=True)
        if p.order == "frequencyDesc":
            order = np.lexsort((uniq, -counts))
        elif p.order == "alphabetAsc":
            order = np.argsort(uniq)
        else:
            raise ValueError(f"unknown order {p.order!r}")
        return StringIndexerModel(p, uniq[order].tolist())


# ---------------------------------------------------------------------------
# Stateless transformers
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NormalizerParams(Params):
    p: float = 2.0               # MLlib p (row norm)


class Normalizer(Transformer):
    ParamsCls = NormalizerParams

    def transform(self, table: TpuTable) -> TpuTable:
        ord_ = self.params.p
        norms = jnp.linalg.norm(table.X, ord=ord_, axis=1, keepdims=True)
        X = table.X / jnp.maximum(norms, 1e-12)
        return table.with_X(X)


@dataclasses.dataclass(frozen=True)
class BinarizerParams(Params):
    threshold: float = 0.0       # MLlib threshold
    input_cols: tuple | None = None


class Binarizer(Transformer):
    ParamsCls = BinarizerParams

    def transform(self, table: TpuTable) -> TpuTable:
        idxs = jnp.asarray(_col_indices(table, self.params.input_cols))
        Xsel = jnp.take(table.X, idxs, axis=1)
        binz = (Xsel > self.params.threshold).astype(jnp.float32)
        return table.with_X(table.X.at[:, idxs].set(binz))


class VectorAssembler(Transformer):
    """Column projection for API parity: our table IS the assembled matrix."""

    def __init__(self, input_cols: Sequence[str]):
        self.params = Params()
        self.input_cols = tuple(input_cols)

    def transform(self, table: TpuTable) -> TpuTable:
        return table.select(self.input_cols)


@dataclasses.dataclass(frozen=True)
class FeatureHasherParams(Params):
    num_features: int = 256      # MLlib numFeatures (power of two)
    input_cols: tuple = ()       # continuous and/or discrete attribute names


class FeatureHasher(Transformer):
    ParamsCls = FeatureHasherParams
    """MLlib FeatureHasher: continuous cols add their value at hash(name);
    discrete cols add 1.0 at hash(name + '=' + category).

    Hash buckets are computed host-side from column METADATA only (names and
    category sets — tiny), then the row-wise scatter happens on device as a
    dense [n_cols_or_cats, num_features] matmul: one-hot-via-matmul keeps the
    op on the MXU instead of a gather/scatter.
    """

    def transform(self, table: TpuTable) -> TpuTable:
        import zlib

        p = self.params
        nf = p.num_features
        cols = p.input_cols or tuple(v.name for v in table.domain.attributes)
        cont_idx, cont_bucket = [], []
        disc_idx, disc_maps = [], []
        for name in cols:
            var = table.domain[name]
            j = table.domain.index(name)
            if isinstance(var, DiscreteVariable):
                buckets = [
                    zlib.crc32(f"{name}={v}".encode()) % nf for v in var.values
                ]
                disc_idx.append(j)
                disc_maps.append(buckets)
            else:
                cont_idx.append(j)
                cont_bucket.append(zlib.crc32(name.encode()) % nf)
        out = jnp.zeros((table.n_pad, nf), dtype=jnp.float32)
        if cont_idx:
            # projection matrix [n_cont, nf]: row j has 1 at its bucket
            Pm = np.zeros((len(cont_idx), nf), dtype=np.float32)
            for r, b in enumerate(cont_bucket):
                Pm[r, b] = 1.0
            Xc = jnp.take(table.X, jnp.asarray(cont_idx, dtype=jnp.int32), axis=1)
            out = out + Xc @ jnp.asarray(Pm)
        for j, buckets in zip(disc_idx, disc_maps, strict=True):
            k = len(buckets)
            onehot = jax.nn.one_hot(table.X[:, j].astype(jnp.int32), k, dtype=jnp.float32)
            Pm = np.zeros((k, nf), dtype=np.float32)
            for r, b in enumerate(buckets):
                Pm[r, b] = 1.0
            out = out + onehot @ jnp.asarray(Pm)
        new_domain = Domain(
            [ContinuousVariable(f"hash_{i}") for i in range(nf)],
            table.domain.class_vars, table.domain.metas,
        )
        return table.with_X(out, new_domain)


# ---------------------------------------------------------------------------
# Target encoding (pyspark.ml.feature.TargetEncoder, Spark 4.0)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TargetEncoderParams(Params):
    input_cols: tuple = ()        # discrete attribute names
    target_type: str = "binary"   # MLlib targetType: 'binary' | 'continuous'
    smoothing: float = 0.0        # MLlib smoothing (shrink toward the prior)
    handle_invalid: str = "error" # 'error' | 'keep' (unseen -> global prior)


class TargetEncoderModel(Model):
    """Per-category target means, smoothing-shrunk toward the global prior:
    enc[c] = (sum_y[c] + smoothing * prior) / (count[c] + smoothing)."""

    def __init__(self, params, col_idx, tables, prior):
        self.params = params
        self.col_idx = col_idx     # list[int]
        self.tables = tables       # list[f32[k+1]] (last slot = unseen)
        self.prior = prior

    @property
    def state_pytree(self):
        return {f"enc_{j}": t for j, t in zip(self.col_idx, self.tables)}

    def transform(self, table: TpuTable) -> TpuTable:
        p = self.params
        X = table.X
        new_attrs = list(table.domain.attributes)
        for j, enc, name in zip(self.col_idx, self.tables,
                                p.input_cols, strict=True):
            k = enc.shape[0] - 1
            raw = X[:, j].astype(jnp.int32)
            if p.handle_invalid == "error":
                live = jnp.where(table.W > 0, raw, 0)
                mx = int(np.asarray(jnp.max(live)).item())
                if mx >= k:
                    raise ValueError(
                        f"column {name!r} has unseen category {mx} "
                        "(handle_invalid='error')"
                    )
            idx = jnp.clip(raw, 0, k - 1)
            idx = jnp.where((raw < 0) | (raw >= k), k, idx)  # unseen slot
            X = X.at[:, j].set(jnp.take(enc, idx))
            new_attrs[j] = ContinuousVariable(f"{name}_te")
        domain = Domain(new_attrs, table.domain.class_vars, table.domain.metas)
        return table.with_X(X, domain)


class TargetEncoder(Estimator):
    """Mean target encoding per category — the hashed/one-hot alternative
    for high-cardinality categoricals (segment_sum over the sharded rows;
    the per-category reduction GSPMD all-reduces over ICI)."""

    ParamsCls = TargetEncoderParams
    params: TargetEncoderParams

    def _fit(self, table: TpuTable) -> TargetEncoderModel:
        p = self.params
        if not p.input_cols:
            raise ValueError("TargetEncoder needs input_cols")
        y = table.y
        W = table.W
        prior = float(jnp.sum(y * W) / jnp.maximum(jnp.sum(W), 1e-12))
        col_idx, tables = [], []
        for name in p.input_cols:
            var = table.domain[name]
            j = table.domain.index(var)
            col_idx.append(j)
            if isinstance(var, DiscreteVariable) and var.values:
                k = len(var.values)
            else:
                k = int(np.asarray(
                    jnp.max(jnp.where(W > 0, table.X[:, j], 0.0))).item()) + 1
            idx = jnp.clip(table.X[:, j].astype(jnp.int32), 0, k - 1)
            sum_y = jax.ops.segment_sum(y * W, idx, num_segments=k)
            cnt = jax.ops.segment_sum(W, idx, num_segments=k)
            enc = (sum_y + p.smoothing * prior) / jnp.maximum(
                cnt + p.smoothing, 1e-12
            )
            enc = jnp.where(cnt > 0, enc, prior)
            # slot k serves unseen categories at transform time
            tables.append(jnp.concatenate([enc, jnp.asarray([prior])]))
        return TargetEncoderModel(p, col_idx, tables, prior)
