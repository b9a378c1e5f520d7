"""Window functions — the ``pyspark.sql.Window`` wrangling subset.

Spark evaluates window expressions by shuffling each partition to one
executor and scanning it in order (SURVEY.md §2b "Distributed dataframe";
reconstructed, mount empty). The TPU-native redesign keeps the static-shape
rule: ONE device lexsort by (partition, liveness, order-rank) puts every
partition's rows adjacent and ordered, the windowed quantity is computed
positionally on the sorted view (iota/segment arithmetic/shifted cumsum —
all VPU ops), and one inverse-permutation gather puts results back in row
order. No per-partition loops, no ragged shapes.

Semantics matching Spark: rows with a NULL/NaN partition key form their own
group; NaN values are ignored by ``running_sum`` (null-skipping sum); dead
rows (W == 0) sort behind their partition and report NaN everywhere.

``Window(table, partition_by, order_by)`` computes the sorted view once and
shares it across its methods; the module-level functions are one-shot
conveniences. All results are [N_pad] device vectors aligned with the
table's rows — compose with ``relational.with_column`` to append them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from orange3_spark_tpu.core.domain import DiscreteVariable
from orange3_spark_tpu.core.table import TpuTable

__all__ = ["Window", "row_number", "lag", "lead", "running_sum"]


class Window:
    """Shared sorted view over one (partition_by, order_by) spec."""

    def __init__(self, table: TpuTable, partition_by: str, order_by: str, *,
                 ascending: bool = True):
        kvar = table.domain[partition_by]
        if not isinstance(kvar, DiscreteVariable):
            raise ValueError(f"partition_by {partition_by!r} must be discrete")
        self._table = table
        raw = table.column(partition_by)
        n_groups = max(len(kvar.values), 1)
        # Spark groups NULL keys together: NaN keys get their own id past
        # every real category (the raw NaN->int cast is backend-UNDEFINED
        # and would silently merge them into partition 0)
        part = jnp.where(
            jnp.isnan(raw), n_groups, raw.astype(jnp.int32)
        ).astype(jnp.int32)
        val = table.column(order_by)
        if not ascending:
            val = -val
        # NULLS LAST in either direction (Spark's asc/desc default)
        val = jnp.where(jnp.isnan(val), jnp.inf, val)
        live = table.W > 0
        # stable lexsort: partition id, dead-row bump (dead rows land after
        # every live row of their partition), then the order value
        order = jnp.lexsort(
            (val, jnp.where(live, 0, 1).astype(jnp.int32), part)
        )
        self._order = order
        self._inv = jnp.argsort(order)
        self._part_s = part[order]
        self._live_s = live[order]
        pos = jnp.arange(part.shape[0])
        is_start = jnp.concatenate(
            [jnp.asarray([True]), self._part_s[1:] != self._part_s[:-1]]
        )
        self._seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
        self._pos = pos

    # ------------------------------------------------------------- queries
    def row_number(self):
        """1-based rank of each live row within its partition (Spark
        ``row_number().over(...)``)."""
        rn = (self._pos - self._seg_start + 1).astype(jnp.float32)
        rn = jnp.where(self._live_s, rn, jnp.nan)
        return rn[self._inv]

    def _shift(self, col: str, offset: int):
        v_sorted = self._table.column(col)[self._order]
        shifted = jnp.roll(v_sorted, offset)
        n = self._part_s.shape[0]
        same_part = jnp.roll(self._part_s, offset) == self._part_s
        in_range = (self._pos - offset >= 0) if offset > 0 else (
            self._pos - offset < n
        )
        ok = same_part & in_range & self._live_s & jnp.roll(self._live_s, offset)
        return jnp.where(ok, shifted, jnp.nan)[self._inv]

    def lag(self, col: str, offset: int = 1):
        """Value of ``col`` ``offset`` rows earlier in the partition's
        order; NaN at partition starts (Spark ``lag``)."""
        return self._shift(col, offset)

    def lead(self, col: str, offset: int = 1):
        """Value of ``col`` ``offset`` rows later in the partition's order;
        NaN at partition ends (Spark ``lead``)."""
        return self._shift(col, -offset)

    def running_sum(self, col: str):
        """Null-skipping cumulative sum over the partition's order — Spark
        ``sum(col).over(rowsBetween(unboundedPreceding, currentRow))``."""
        v = self._table.column(col)[self._order]
        v = jnp.where(self._live_s & ~jnp.isnan(v), v, 0.0)  # nulls skipped
        total = jnp.cumsum(v)
        base = jnp.where(
            self._seg_start > 0, total[self._seg_start - 1], 0.0
        )
        out = jnp.where(self._live_s, total - base, jnp.nan)
        return out[self._inv]


# ----------------------------------------------------------- one-shot forms
def row_number(table: TpuTable, partition_by: str, order_by: str, *,
               ascending: bool = True):
    return Window(table, partition_by, order_by,
                  ascending=ascending).row_number()


def lag(table: TpuTable, col: str, partition_by: str, order_by: str, *,
        offset: int = 1, ascending: bool = True):
    return Window(table, partition_by, order_by,
                  ascending=ascending).lag(col, offset)


def lead(table: TpuTable, col: str, partition_by: str, order_by: str, *,
         offset: int = 1, ascending: bool = True):
    return Window(table, partition_by, order_by,
                  ascending=ascending).lead(col, offset)


def running_sum(table: TpuTable, col: str, partition_by: str, order_by: str, *,
                ascending: bool = True):
    return Window(table, partition_by, order_by,
                  ascending=ascending).running_sum(col)
