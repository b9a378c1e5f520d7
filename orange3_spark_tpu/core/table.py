"""TpuTable — the distributed DataFrame replacement.

The reference's data plane is a Spark SQL DataFrame: rows partitioned across
JVM executors, schema host-side, operations lazy until an action forces them
(SURVEY.md §2 layer 2; reconstructed, mount empty). The TPU-native redesign is
**columnar, dense, and statically shaped**:

* all numeric cells live in one ``X: f32[N_pad, d]`` device array sharded
  ``P('data', None)`` over the mesh — one big array keeps every downstream op
  a single fused XLA computation feeding the MXU, instead of per-partition
  Python tasks;
* the row count is padded up to a multiple of the data-axis size; a weight
  vector ``W`` carries both user row-weights and the padding mask (padding
  rows have ``W == 0``), so filters become weight-zeroing instead of
  shape-changing compaction (XLA needs static shapes; Spark's shrinking
  partitions have no XLA analogue);
* free-text/meta columns stay host-side in numpy (they never participate in
  compute, exactly like Orange keeps metas out of X).

Conversion to/from numpy (the ``Orange.data.Table`` bridge role) is a
device_put/device_get of the one array — not the DataFrame→pandas→Table relay
the reference funnels every result through.
"""

from __future__ import annotations

from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.core.domain import (
    ContinuousVariable,
    DiscreteVariable,
    Domain,
    StringVariable,
    Variable,
)
from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.obs import prof


class TpuTable:
    """Columnar table over GSPMD-sharded arrays.

    Attributes
    ----------
    domain : Domain            column metadata (host)
    X : f32[N_pad, n_attrs]    features, sharded P('data', None)
    Y : f32[N_pad, n_class]    targets (may be None), sharded P('data', None)
    W : f32[N_pad]             row weights; 0 marks padding / filtered rows
    metas : object[n_rows, m]  host-side meta columns (unpadded)
    n_rows : int               logical (unpadded) row count
    """

    def __init__(self, domain, X, Y, W, metas, n_rows, session=None):
        self.domain = domain
        self.X = X
        self.Y = Y
        self.W = W
        self.metas = metas
        self.n_rows = int(n_rows)
        self.session = session or TpuSession.active()

    # ------------------------------------------------------------ construct
    @classmethod
    def from_numpy(
        cls,
        domain: Domain,
        X: np.ndarray,
        Y: np.ndarray | None = None,
        metas: np.ndarray | None = None,
        W: np.ndarray | None = None,
        session: TpuSession | None = None,
    ) -> "TpuTable":
        session = session or TpuSession.active()
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2:
            raise ValueError(f"X must be 2-D, got shape {X.shape}")
        n = X.shape[0]
        if X.shape[1] != len(domain.attributes):
            raise ValueError(
                f"X has {X.shape[1]} columns, domain has {len(domain.attributes)}"
            )
        n_pad = session.pad_rows(n)
        Xp = np.zeros((n_pad, X.shape[1]), dtype=np.float32)
        Xp[:n] = X
        if Y is not None:
            Y = np.asarray(Y, dtype=np.float32)
            if Y.ndim == 1:
                Y = Y[:, None]
            if Y.shape[1] != len(domain.class_vars):
                raise ValueError(
                    f"Y has {Y.shape[1]} columns, domain has {len(domain.class_vars)} class vars"
                )
            Yp = np.zeros((n_pad, Y.shape[1]), dtype=np.float32)
            Yp[:n] = Y
        elif domain.class_vars:
            raise ValueError("domain has class_vars but Y is None")
        else:
            Yp = None
        if W is None:
            Wp = np.zeros((n_pad,), dtype=np.float32)
            Wp[:n] = 1.0
        else:
            W = np.asarray(W, dtype=np.float32)
            Wp = np.zeros((n_pad,), dtype=np.float32)
            Wp[:n] = W
        # put_sharded == device_put single-process; on multi-host deployments
        # each process contributes its local block and the table's arrays are
        # the GLOBAL assembly (io/multihost.py)
        from orange3_spark_tpu.io.multihost import put_sharded

        row = session.row_sharding
        vec = session.vector_sharding
        Xd = put_sharded(Xp, row)
        Yd = put_sharded(Yp, row) if Yp is not None else None
        Wd = put_sharded(Wp, vec)
        if metas is not None:
            metas = np.asarray(metas, dtype=object)
            if metas.ndim == 1:
                metas = metas[:, None]
        table = cls(domain, Xd, Yd, Wd, metas, n, session)
        # the HBM account (obs/prof.py): the arrays put here are a ledger
        # entry for as long as the table lives, and the put is a mark
        prof.ledger_set_owned("tables", table, (Xd, Yd, Wd))
        prof.hbm_mark("table_put")
        return table

    @classmethod
    def from_arrays(cls, X, Y=None, *, attr_names=None, class_name="y",
                    class_values=None, session=None) -> "TpuTable":
        """Convenience: build a Domain from bare arrays (continuous attrs)."""
        X = np.asarray(X)
        names = attr_names or [f"x{i}" for i in range(X.shape[1])]
        attrs = [ContinuousVariable(n) for n in names]
        cvar = None
        if Y is not None:
            if class_values is not None:
                cvar = DiscreteVariable(class_name, class_values)
            else:
                cvar = ContinuousVariable(class_name)
        return cls.from_numpy(Domain(attrs, cvar), X, Y, session=session)

    # -------------------------------------------------------------- export
    def to_numpy(self) -> tuple[np.ndarray, np.ndarray | None, np.ndarray]:
        """Gather to host and strip padding: (X, Y, W). The collect() action."""
        n = self.n_rows
        X = np.asarray(jax.device_get(self.X))[:n]
        Y = np.asarray(jax.device_get(self.Y))[:n] if self.Y is not None else None
        W = np.asarray(jax.device_get(self.W))[:n]
        return X, Y, W

    # ------------------------------------------------------------ properties
    @property
    def n_pad(self) -> int:
        return self.X.shape[0]

    @property
    def n_attrs(self) -> int:
        return self.X.shape[1]

    def __len__(self) -> int:
        return self.n_rows

    @property
    def y(self):
        """First class column as a flat [N_pad] device vector."""
        if self.Y is None:
            raise ValueError("table has no class variable")
        return self.Y[:, 0]

    @property
    def valid_mask(self):
        """f32[N_pad] 1.0 where the row is live (unfiltered, not padding)."""
        return (self.W > 0).astype(jnp.float32)

    # ------------------------------------------------------------ DataFrame ops
    def select(self, columns: Sequence[str | Variable]) -> "TpuTable":
        """Column projection (DataFrame.select). Gathers attr columns on device."""
        attrs, idxs = [], []
        for c in columns:
            var = self.domain[c]
            if not isinstance(var, (ContinuousVariable, DiscreteVariable)):
                raise ValueError(f"cannot select non-numeric column {var.name!r}")
            if var in self.domain.class_vars:
                raise ValueError("use select on attributes; class vars stay put")
            attrs.append(var)
            idxs.append(self.domain.index(var))
        new_domain = Domain(attrs, self.domain.class_vars, self.domain.metas)
        X = jnp.take(self.X, jnp.asarray(idxs), axis=1)
        return TpuTable(new_domain, X, self.Y, self.W, self.metas, self.n_rows, self.session)

    def filter(self, predicate: Callable[["TpuTable"], jax.Array] | jax.Array) -> "TpuTable":
        """Row filter (DataFrame.filter): zero the weights of dropped rows.

        Shapes stay static (XLA requirement); downstream weighted ops see the
        filtered table exactly as Spark sees a smaller DataFrame. Use
        ``compacted()`` to physically drop rows at a host boundary.
        """
        mask = predicate(self) if callable(predicate) else predicate
        W = jnp.where(mask.astype(bool), self.W, 0.0)
        return TpuTable(self.domain, self.X, self.Y, W, self.metas, self.n_rows, self.session)

    # Spark spells DataFrame.filter as where() too
    def where(self, predicate) -> "TpuTable":
        return self.filter(predicate)

    def fillna(self, value) -> "TpuTable":
        """Replace NaNs (DataFrame.fillna / na.fill): a float fills every
        attribute column; a {column_name: float} dict fills per column.
        Device-pure (one where per filled column)."""
        if isinstance(value, dict):
            X, Y = self.X, self.Y
            for name, v in value.items():
                try:
                    var = self.domain[name]
                except KeyError as e:
                    raise ValueError(f"fillna: unknown column {name!r}") from e
                if var in self.domain.class_vars:
                    j = list(self.domain.class_vars).index(var)
                    col = jnp.where(jnp.isnan(Y[:, j]), jnp.float32(v), Y[:, j])
                    Y = Y.at[:, j].set(col)
                else:
                    j = self.domain.index(var)
                    col = jnp.where(jnp.isnan(X[:, j]), jnp.float32(v), X[:, j])
                    X = X.at[:, j].set(col)
            return TpuTable(self.domain, X, Y, self.W, self.metas,
                            self.n_rows, self.session)
        X = jnp.where(jnp.isnan(self.X), jnp.float32(value), self.X)
        return self.with_X(X)

    def dropna(self, subset: Sequence[str] | None = None) -> "TpuTable":
        """Drop rows with NaNs (DataFrame.dropna / na.drop): weight-zeroes
        them under the static-shape rule, like filter()."""
        if subset is None:
            bad = jnp.any(jnp.isnan(self.X), axis=1)
            if self.Y is not None:
                bad = bad | jnp.any(jnp.isnan(self.Y), axis=1)
        else:
            bad = jnp.zeros((self.n_pad,), bool)
            for name in subset:
                try:
                    bad = bad | jnp.isnan(self.column(name))  # attr OR class
                except (KeyError, ValueError) as e:
                    raise ValueError(
                        f"dropna: unknown column {name!r}"
                    ) from e
        return self.with_weights(jnp.where(bad, 0.0, self.W))

    def with_weights(self, W) -> "TpuTable":
        return TpuTable(self.domain, self.X, self.Y, W, self.metas, self.n_rows, self.session)

    def with_X(self, X, domain: Domain | None = None) -> "TpuTable":
        return TpuTable(domain or self.domain, X, self.Y, self.W, self.metas,
                        self.n_rows, self.session)

    def count(self) -> int:
        """Number of live rows (DataFrame.count action — forces compute)."""
        return int(jnp.sum(self.W > 0))

    def compacted(self) -> "TpuTable":
        """Physically drop filtered rows (host round-trip; the collect boundary)."""
        X, Y, W = self.to_numpy()
        live = W > 0
        metas = self.metas[live[: len(self.metas)]] if self.metas is not None else None
        return TpuTable.from_numpy(
            self.domain, X[live], Y[live] if Y is not None else None,
            metas, W[live], self.session,
        )

    def column(self, key: str | Variable):
        """One attribute or class column as an [N_pad] device vector."""
        var = self.domain[key]
        if var in self.domain.class_vars:
            j = list(self.domain.class_vars).index(var)
            return self.Y[:, j]
        j = self.domain.index(var)
        return self.X[:, j]

    # ------------------------------------------------------------- actions
    def head(self, k: int = 5) -> np.ndarray:
        """First k LIVE rows (respects filters, like DataFrame.head).

        Scans device chunks host-ward until k live rows are found, so a
        billion-row table never transfers more than the prefix it needs.
        """
        k = min(k, self.n_rows)
        out: list[np.ndarray] = []
        chunk = max(1024, 4 * k)
        start = 0
        while start < self.n_rows and sum(len(c) for c in out) < k:
            stop = min(start + chunk, self.n_rows)
            Xc = np.asarray(jax.device_get(self.X[start:stop]))
            Wc = np.asarray(jax.device_get(self.W[start:stop]))
            out.append(Xc[Wc > 0])
            start = stop
        return np.concatenate(out, axis=0)[:k] if out else np.empty((0, self.n_attrs))

    def describe(self) -> dict[str, np.ndarray]:
        """Weighted per-column mean/std/min/max (DataFrame.describe action)."""
        stats = _describe_jit(self.X, self.W)
        return {k: np.asarray(v) for k, v in stats.items()}

    def approx_quantile(self, cols, probabilities) -> np.ndarray:
        """DataFrame.approxQuantile — exact here, not Greenwald-Khanna: one
        batched device sort beats a host sketch while the column fits HBM
        (ops/stats.weighted_quantiles). Returns [n_cols, n_probs]."""
        from orange3_spark_tpu.ops.stats import weighted_quantiles

        if isinstance(cols, str):
            cols = [cols]
        # column() resolves attributes AND class vars (X vs Y storage)
        Xsel = jnp.stack([self.column(c) for c in cols], axis=1)
        qs = jnp.asarray(list(probabilities), jnp.float32)
        return np.asarray(weighted_quantiles(Xsel, self.W, qs)).T

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"TpuTable[{self.n_rows} rows x {self.n_attrs} attrs, "
            f"{len(self.domain.class_vars)} class vars, "
            f"sharded over {self.session.data_parallelism} devices]"
        )


@jax.jit
def _describe_jit(X, W):
    from orange3_spark_tpu.ops.stats import weighted_moments

    mean, var, _ = weighted_moments(X, W)
    big = jnp.float32(np.finfo(np.float32).max)
    live = W[:, None] > 0
    mn = jnp.min(jnp.where(live, X, big), axis=0)
    mx = jnp.max(jnp.where(live, X, -big), axis=0)
    return {"mean": mean, "std": jnp.sqrt(var), "min": mn, "max": mx}
