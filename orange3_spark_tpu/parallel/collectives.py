"""treeAggregate → psum: the distributed reduction backbone.

Spark's MLlib drives every iterative fit through ``RDD.treeAggregate`` — a
multi-level shuffle reduce over executors (SURVEY.md §2b "Collectives
backend"; reconstructed, mount empty). On TPU the same role is played by XLA
collectives over ICI: ``lax.psum`` under ``shard_map`` for explicit SPMD, or
GSPMD-inserted all-reduces when a jitted computation consumes P('data')
-sharded rows and produces replicated outputs. Both paths are provided:

* ``tree_aggregate`` — explicit shard_map+psum, the literal treeAggregate
  analogue, for callers that want hand-controlled SPMD;
* plain jit + NamedSharding inputs everywhere else — idiomatic GSPMD, letting
  XLA choose reduce-scatter/all-reduce scheduling on the ICI torus.

There is deliberately NO custom transport layer (no NCCL/MPI translation):
the mesh + collectives ARE the communication backend, multi-host included
(same program, DCN-spanning mesh).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from orange3_spark_tpu.core.session import TpuSession


def tree_aggregate(
    seq_op: Callable[..., Any],
    *arrays,
    session: TpuSession | None = None,
):
    """Per-shard map + global psum — MLlib ``treeAggregate(zero, seqOp, combOp)``.

    ``seq_op`` receives each array's local shard (rows on this device) and
    returns a pytree of partial sums; the pytree is psum'd over the data axis
    and returned replicated. All arrays must be row-sharded P('data', ...).
    """
    session = session or TpuSession.active()
    axis = session.data_axis

    def shard_fn(*shards):
        partial_sums = seq_op(*shards)
        return jax.tree.map(lambda x: jax.lax.psum(x, axis), partial_sums)

    specs = tuple(P(axis) if a.ndim == 1 else P(axis, *(None,) * (a.ndim - 1))
                  for a in arrays)
    return jax.shard_map(
        shard_fn, mesh=session.mesh, in_specs=specs, out_specs=P()
    )(*arrays)


def data_parallel_sum(values, session: TpuSession | None = None):
    """Sum row-sharded arrays over all rows, returning replicated results."""
    return tree_aggregate(
        lambda *xs: tuple(jnp.sum(x, axis=0) for x in xs), *values,
        session=session,
    )


@partial(jax.jit, static_argnames=("center",))
def _gramian_kernel(X, W, center: bool):
    from orange3_spark_tpu.ops.stats import rows_dot, weighted_moments

    w = W[:, None]
    mean, _, tot = weighted_moments(X, W)
    Xc = X - mean if center else X  # center is trace-time static
    # (d,d) matmul contraction over the sharded row axis — GSPMD turns this
    # into local matmuls + one all-reduce over ICI (the treeAggregate moment).
    # rows_dot, because one dot over 2^27 rows is not float32-exact on the
    # TPU (ops/stats.py); ONE operand used twice, because a row block's
    # product needs its operands as arrays and two [N, d] temporaries are
    # one too many at that size
    Y = Xc * jnp.sqrt(w)
    G = rows_dot(Y, Y)
    return G, mean, tot


def distributed_gramian(X, W, center: bool = True):
    """Weighted Gramian  Xᶜᵀ diag(W) Xᶜ  with one ICI all-reduce.

    The building block for PCA (covariance eigendecomposition) and linear
    model normal equations, replacing MLlib's RowMatrix.computeGramianMatrix.
    Returns (G, mean, total_weight), all replicated.
    """
    return _gramian_kernel(X, W, center)
