"""KMeans — parity with ``pyspark.ml.clustering.KMeans``.

MLlib runs Lloyd's algorithm with k-means|| initialization, one
treeAggregate per iteration to sum per-cluster centroids (SURVEY.md §2b row
"KMeans"; reconstructed, mount empty). TPU-native redesign:

* at small d and d·k (``_on_vpu``) an iteration is ONE pass over the rows on
  the vector unit: each row's Σ(x−c)² to every centre, its nearest centre,
  and the per-cluster weighted sums, weights and cost, accumulated in the
  same pass (a Pallas kernel on the TPU, ``_pass_pallas``) — no matmul, no
  [N, k] one-hot, one read of the rows;
* above it the MXU's tools: assignment by the matmul identity
  |x-c|² = |x|² - 2x·c + |c|² (the 2x·c term an [N,d]@[d,k] matmul), the
  centre update one-hot(assign)ᵀ @ X in ``rows_dot``'s blocked products;
* either way the row-axis sums are float32 partials of at most
  ``ROW_BLOCK`` rows added as float32 values, all-reduced over the data
  axis (the treeAggregate moment). GSPMD does not partition a Pallas
  call, so over a row-split table each device runs the kernel on its own
  rows (``shard_map``); ``_rows_of`` reads that split off a concrete
  table, and a traced table's caller passes its session's;
* the whole Lloyd loop is a single jitted ``lax.while_loop`` with the MLlib
  convergence test (all center moves < tol).

Init: 'random' samples k distinct live rows; 'k-means||' is served by
kmeans++ on a host-side sample (≤ init_sample_size rows) — same quality goal
(spread seeds) without a multi-round distributed sampling pass.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orange3_spark_tpu.core.domain import DiscreteVariable, Domain
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.exec.donate import donating_jit
from orange3_spark_tpu.models.base import concrete_or_none, Estimator, Model, Params
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.ops import stats
from orange3_spark_tpu.ops.stats import rows_dot

_M_ITERATIONS = REGISTRY.counter(
    "otpu_kmeans_iterations_total",
    "Lloyd iterations run by finished KMeans fits, by fit: eager (counted "
    "at the end of the fit) | staged (a staged refit, workflow/staging.py: "
    "counted when its states come back)")
_M_PASSES = REGISTRY.counter(
    "otpu_kmeans_passes_total",
    "Lloyd passes over the rows of finished KMeans fits (the iterations and "
    "the final assignment), by how: vpu (_on_vpu: d <= VPU_MAX_D and d*k <= "
    "VPU_MAX_DK, one vector-unit pass) | mxu (matmul assignment + blocked "
    "one-hot products)")

#: d·k and width d at or under which a Lloyd pass runs on the vector unit:
#: its work is ~6·d·k operations a row, where the MXU's cross term is
#: under-filled until d and k reach the hundreds; past 8 columns its
#: per-cluster accumulators outgrow the registers and it loses at every k.
#: The sweeps of PERF.md §6 (TPU v5e, ms a pass, vector unit / MXU), over
#: 2^24 rows: (4, 10) 0.91 / 4.92, (8, 16) 2.51 / 5.07, (8, 32) 4.77 /
#: 9.10, (16, 64) 27.6 / 17.9, (32, 128) 100.7 / 65.4; over 2^22 + 77 rows
#: (padded): (1, 256) 2.58 / 32.7, (2, 128) 1.91 / 22.7, (4, 64) 1.50 /
#: 9.71, (8, 32) 1.63 / 5.33, (16, 16) 6.78 / 3.67, (32, 8) 12.3 / 4.01;
#: over 2^22 rows: (16, 4) 1.42 / 1.02, (32, 2) 2.74 / 1.56
VPU_MAX_DK = 256
VPU_MAX_D = 8

_LANES = 128
#: bytes of the rows one grid step of ``_pass_pallas`` reads
_STEP_BYTES = 1 << 21
#: vregs of the rows one inner step of ``_pass_kernel`` holds
_X_VREGS = 16


def _on_vpu(d: int, k: int) -> bool:
    return d <= VPU_MAX_D and d * k <= VPU_MAX_DK


@dataclasses.dataclass(frozen=True)
class KMeansParams(Params):
    k: int = 2                    # MLlib k
    max_iter: int = 20            # MLlib maxIter
    tol: float = 1e-4             # MLlib tol (center movement)
    init_mode: str = "k-means||"  # MLlib initMode: 'random' | 'k-means||'
    seed: int = 0                 # MLlib seed
    n_init: int = 1               # restarts, best-cost wins (vmapped — beyond
                                  # MLlib, which is single-init; ~free on TPU)
    init_sample_size: int = 8192  # host sample for the ++-style init
    compute_dtype: str = "float32"


def live_cluster_sizes(W, assign, num_segments: int):
    """MLlib ``summary.clusterSizes``: live ROW counts per cluster (Spark
    counts rows, not weights — W only gates padding/filtered membership).
    THE one implementation, shared by KMeans / BisectingKMeans / GMM."""
    # a compare-and-sum over [N, k], fused into one reduction: a
    # segment_sum here is a scatter-add of N values, 1.2 s at 2^27 rows on
    # a v5e where this pass reads its two inputs once (PERF.md, PR 35)
    hit = (assign[:, None] == jnp.arange(num_segments)) & (W > 0)[:, None]
    return jnp.sum(hit, axis=0, dtype=jnp.int32).astype(jnp.float32)


def _assign_mxu(X, centers, w, compute_dtype):
    """Nearest-center ids + weighted cost by the matmul identity."""
    Xc = X.astype(compute_dtype)
    Cc = centers.astype(compute_dtype)
    # [N,k] on the MXU. HIGHEST: the TPU's default rounds both operands of
    # a float32 matmul to bfloat16, which is compute_dtype='bfloat16' under
    # another name (bfloat16 operands pass through unchanged)
    cross = jnp.dot(Xc, Cc.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    x2 = jnp.sum(X * X, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)
    d2 = x2 - 2.0 * cross + c2
    assign = jnp.argmin(d2, axis=1)
    cost = jnp.sum(jnp.min(d2, axis=1) * w)
    return assign, cost


def _pass_mxu(X, w, centers, compute_dtype, sums: bool):
    """The MXU's pass: the matmul assignment, then (``sums``) the centre
    update one-hot(assign)ᵀ @ X -> ``(sums [k, d], weights [k], cost)``,
    else ``(assign [N], cost)``."""
    assign, cost = _assign_mxu(X, centers, w, compute_dtype)
    if not sums:
        return assign, cost
    onehot = jax.nn.one_hot(assign, centers.shape[0],
                            dtype=jnp.float32) * w[:, None]      # [N,k]
    # [k,d] MXU matmuls over row blocks, all-reduced by GSPMD
    return rows_dot(onehot, X), jnp.sum(onehot, axis=0), cost


def _pass_kernel(c_ref, x_ref, w_ref, *refs, k, d, compute_dtype, sums,
                 tail):
    """One grid step of ``_pass_pallas``: ``x_ref`` holds ``lane_rows``
    groups of 128 table rows, each as d lane-vectors (row ``t*d + i`` is
    column i of group t), ``w_ref`` their weights, ``c_ref`` the centres
    as ``[k, d, 8, 128]`` vregs. ``_tile_groups(d)`` groups at a time
    (several vregs an operation, so that the chains of dependent operations
    interleave), the loop over centres unrolled: Σ_i (x_i − c_ji)² for every
    centre j, the first nearest (strict ``<``, argmin's tie rule), then
    either the assignment (``sums=False``) or each cluster's weighted sums
    and weight, and the cost, into accumulators that a ``ROW_BLOCK``-row
    block empties into one float32 partial each: lane b of the step's
    ``part`` row e (e = j·(d+1) + i for the sums and weights, the last row
    for the cost). The last step holds ``tail`` groups of the table: the
    rest of its buffers, whatever they hold, is zeroed first (weight 0)."""
    outs, acc_ref = refs[:-1], refs[-1]
    part = outs[-1]
    cost_row = acc_ref.shape[0] - 1
    block = stats.ROW_BLOCK
    n_blocks = w_ref.shape[0] * _LANES // block
    g = _tile_groups(d)
    tile = 8 * g
    tiles = block // (tile * _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    if tail < w_ref.shape[0]:
        @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
        def _():
            x_ref[tail * d:, :] = jnp.zeros(
                (x_ref.shape[0] - tail * d, _LANES), jnp.float32)
            w_ref[tail:, :] = jnp.zeros(
                (w_ref.shape[0] - tail, _LANES), jnp.float32)

    def fold(v):            # [..., g, 8, 128] -> [..., 8, 128]
        return v.sum(axis=-3)

    def one_block(b, totals):
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def one_tile(t, carry):
            t0 = pl.multiple_of((b * tiles + t) * tile, tile)
            cols = jnp.stack([x_ref[pl.ds(t0 * d + i, tile, stride=d), :]
                              for i in range(d)]).reshape(d, g, 8, _LANES)
            w = w_ref[pl.ds(t0, tile), :].reshape(g, 8, _LANES)
            xa = cols
            if np.dtype(compute_dtype) != np.float32:
                xa = cols.astype(compute_dtype).astype(jnp.float32)
            best = a = None
            for j in range(k):
                dj = jnp.sum(jnp.square(xa - c_ref[j][:, None]), axis=0)
                if best is None:
                    best, a = dj, jnp.zeros(dj.shape, jnp.int32)
                else:
                    lt = dj < best
                    best, a = jnp.where(lt, dj, best), jnp.where(lt, j, a)
            acc_ref[cost_row] += fold(best * w)
            if not sums:
                outs[0][pl.ds(t0, tile), :] = a.reshape(tile, _LANES)
                return carry
            cols1 = jnp.concatenate([cols, jnp.ones_like(w)[None]])
            for j in range(k):
                hw = jnp.where(a == j, w, 0.0)
                acc_ref[pl.ds(j * (d + 1), d + 1)] += fold(hw * cols1)
            return carry

        jax.lax.fori_loop(0, tiles, one_tile, 0)
        tot = jnp.sum(jnp.sum(acc_ref[...], axis=1), axis=1, keepdims=True)
        return jnp.where(lane == b, tot, totals)

    part[0] = jax.lax.fori_loop(0, n_blocks, one_block,
                                jnp.zeros(part.shape[1:], jnp.float32))


def _tile_groups(d: int) -> int:
    """Groups of 1,024 rows one inner step of the kernel holds: about
    ``_X_VREGS`` vregs of the rows, a power of two dividing a block."""
    g = 1
    while 2 * g * d <= _X_VREGS and 2 * g * 8 * _LANES <= stats.ROW_BLOCK:
        g *= 2
    return g


@partial(jax.jit, static_argnames=("compute_dtype", "sums", "interpret"))
def _pass_pallas(X, w, centers, compute_dtype, sums, interpret=False):
    """The vector-unit pass as a Pallas kernel over one device's rows. A
    jit of its own, so that a program lowers each kernel once: the fit's
    last pass and a transform of the same rows by the same centres are
    then one call, which XLA merges (two lowerings of one kernel differ
    in their call sites and are not merged).

    The rows are read as ``[N/128 · d, 128]``: on the TPU an ``[N, d]``
    array of d = 1, 2, 4 or 8 columns and N a multiple of 1,024 rows is
    laid out that way already (its rows minor, in tiles of d x 128), so
    the view is a bitcast and the pass reads the table where it lies;
    other widths are relaid out. Any other N is padded to a multiple of
    1,024, a copy of the table each pass: XLA's own relayout of such an
    N is slower still and compiles for a time that grows with N. The
    grid's last step may hang over the table's end (the kernel zeroes
    what it holds past it). ``interpret`` runs the kernel on any backend
    (tests)."""
    n, d = X.shape
    k = centers.shape[0]
    block = stats.ROW_BLOCK
    per_block = block // _LANES
    pad = -n % (8 * _LANES)
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        w = jnp.pad(w, (0, pad))
    groups = (n + pad) // _LANES
    lane_rows = max(per_block, _STEP_BYTES // (4 * _LANES * d)
                    // per_block * per_block)
    lane_rows = min(lane_rows, -(-groups // per_block) * per_block)
    steps = -(-groups // lane_rows)
    Xp = X.reshape(groups, _LANES, d).transpose(0, 2, 1).reshape(
        groups * d, _LANES)
    wp = w.reshape(groups, _LANES)
    c = jnp.broadcast_to(
        centers.astype(compute_dtype).astype(jnp.float32)[:, :, None, None],
        (k, d, 8, _LANES))
    # the cost's row last; without sums a spare row before it (Mosaic
    # reduces no accumulator of a single row)
    n_part = 1 + (k * (d + 1) if sums else 1)

    def rows(s):
        return s, 0

    out_shape = [jax.ShapeDtypeStruct((steps, n_part, _LANES), jnp.float32)]
    out_specs = [pl.BlockSpec((1, n_part, _LANES), lambda s: (s, 0, 0))]
    if not sums:
        out_shape.insert(0, jax.ShapeDtypeStruct((groups, _LANES), jnp.int32))
        out_specs.insert(0, pl.BlockSpec((lane_rows, _LANES), rows))
    vmem = 4 * 8 * _LANES * (                        # bytes of the vregs:
        2 * lane_rows // 8 * (d + 1 + (not sums))    # rows, weights (and
                                                     # assignment), x2
        + 2 * k * d                                  # centres, x2
        + n_part                                     # accumulators
        + 2 * n_part // 8 + 2)                       # partials, x2
    outs = pl.pallas_call(
        partial(_pass_kernel, k=k, d=d, compute_dtype=compute_dtype,
                sums=sums, tail=groups - (steps - 1) * lane_rows),
        grid=(steps,),
        in_specs=[pl.BlockSpec((k, d, 8, _LANES), lambda s: (0, 0, 0, 0)),
                  pl.BlockSpec((lane_rows * d, _LANES), rows),
                  pl.BlockSpec((lane_rows, _LANES), rows)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n_part, 8, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        interpret=interpret,
    )(c, Xp, wp)
    tot = jnp.sum(outs[-1][:, :, :lane_rows // per_block], axis=(0, 2))
    if not sums:
        assign = outs[0].reshape(groups * _LANES)
        return (assign[:n] if pad else assign), tot[-1]
    sc = tot[:-1].reshape(k, d + 1)
    return sc[:, :d], sc[:, d], tot[-1]


def _local_pass(X, w, centers, compute_dtype, sums: bool):
    """One device's vector-unit pass: the Pallas kernel on the TPU, the
    MXU's pass on every other backend."""
    return jax.lax.platform_dependent(
        X, w, centers,
        tpu=partial(_pass_pallas, compute_dtype=compute_dtype, sums=sums),
        default=partial(_pass_mxu, compute_dtype=compute_dtype, sums=sums))


def _row_axis(rows):
    """The mesh axis ``rows`` splits the table's rows over, or None where
    no axis of more than one device splits them."""
    axis = rows.spec[0] if rows is not None and rows.spec else None
    names = axis if isinstance(axis, tuple) else (axis,)
    if axis is None or np.prod([rows.mesh.shape[a] for a in names]) == 1:
        return None
    return axis


def _vpu_pass(X, w, centers, compute_dtype, sums: bool, rows):
    """One pass of the rows on the vector unit -> ``(sums [k, d], weights
    [k], cost)``, or ``(assign [N], cost)`` with ``sums=False``. Where
    ``rows`` splits them over devices, each device passes its own rows
    (``shard_map``) and the partials are all-reduced: a Pallas call is
    not partitioned by GSPMD."""
    compute_dtype = jnp.dtype(compute_dtype)
    axis = _row_axis(rows)
    if axis is None:
        return _local_pass(X, w, centers, compute_dtype, sums)

    def shard(X, w, centers):
        out = _local_pass(X, w, centers, compute_dtype, sums)
        if sums:
            return jax.lax.psum(out, axis)
        return out[0], jax.lax.psum(out[1], axis)

    return jax.shard_map(
        shard, mesh=rows.mesh, in_specs=(P(axis, None), P(axis), P()),
        out_specs=P() if sums else (P(axis), P()),
        check_vma=False)(X, w, centers)


def _pass(X, w, centers, compute_dtype, sums: bool, rows):
    """A Lloyd pass by the path ``_on_vpu`` picks. ``rows`` is the
    sharding of the table's rows (``_rows_of``). It is None only for a
    traced table whose caller gave none: in a process of more than one
    device those rows may be split, so the pass is then the MXU's, which
    GSPMD partitions."""
    k, d = centers.shape
    if _on_vpu(d, k) and (rows is not None or jax.device_count() == 1):
        return _vpu_pass(X, w, centers, compute_dtype, sums, rows)
    return _pass_mxu(X, w, centers, compute_dtype, sums)


def _rows_of(X, rows):
    """The sharding of the table's rows a pass runs under: ``rows`` where
    the caller gives it (a traced table's session), else a concrete
    array's own; None where neither is known."""
    if rows is not None or isinstance(X, jax.core.Tracer):
        return rows
    s = getattr(X, "sharding", None)
    if isinstance(s, NamedSharding):
        return NamedSharding(s.mesh, P(s.spec[0] if s.spec else None, None))
    if s is not None and len(s.device_set) == 1:     # one device holds them
        return NamedSharding(Mesh(np.asarray(list(s.device_set)), ("rows",)),
                             P(None, None))
    return None


@partial(jax.jit, static_argnames=("compute_dtype", "rows"))
def _assign_fused(X, centers, w, compute_dtype=jnp.float32, rows=None):
    return _pass(X, w, centers, compute_dtype, False, rows)


def _assign(X, centers, w, compute_dtype=jnp.float32, rows=None):
    """Nearest-center ids + weighted cost: one vector-unit pass where
    ``_on_vpu``, else the matmul identity. ``rows``: the sharding of the
    table's rows, needed where X is traced (``_rows_of``)."""
    return _assign_fused(X, centers, w, compute_dtype, _rows_of(X, rows))


@donating_jit(static_argnames=("k", "max_iter", "compute_dtype", "rows"),
              donate_argnums=(2,))
def _lloyd_fused(X, w, centers0, tol, *, k: int, max_iter: int,
                 compute_dtype=jnp.float32, rows=None):
    """Fused Lloyd loop. ``centers0`` is DONATED — every caller builds the
    seed centers fresh (host kmeans++ / device D² sampling), and the loop
    round-trips a same-shaped centers array, so XLA reuses the buffer. The
    vmapped restart path calls ``_lloyd.plain`` (donation under vmap
    tracing is a no-op)."""
    def body(carry):
        centers, _, it, _ = carry
        sums, counts, cost = _pass(X, w, centers, compute_dtype, True, rows)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1e-12)[:, None], centers
        )
        move = jnp.sqrt(jnp.sum((new_centers - centers) ** 2, axis=1))
        converged = jnp.all(move < tol)
        return new_centers, cost, it + 1, converged

    def keep_going(carry):
        _, _, it, converged = carry
        return (it < max_iter) & ~converged

    centers, cost, n_iter, _ = jax.lax.while_loop(
        keep_going, body, (centers0, jnp.float32(jnp.inf), 0, False)
    )
    # final stats at the converged centers
    assign, cost = _pass(X, w, centers, compute_dtype, False, rows)
    return centers, assign, cost, n_iter


def _lloyd(X, w, centers0, tol, *, rows=None, **kw):
    """``_lloyd_fused`` under the sharding of the table's rows: ``rows``,
    needed where X is traced, else X's own (``_rows_of``)."""
    return _lloyd_fused(X, w, centers0, tol, rows=_rows_of(X, rows), **kw)


_lloyd.donated, _lloyd.plain = _lloyd_fused.donated, _lloyd_fused.plain
_lloyd.__wrapped__ = _lloyd_fused.__wrapped__


def kmeanspp_seed(sample: np.ndarray, k: int, rng) -> np.ndarray:
    """kmeans++ seeding on a host-side sample -> f32[k, d] centers.

    Distances/probabilities run in float64 (float32 D² vectors can fail
    numpy's choice() sum-to-1 tolerance on large samples) and the result is
    jitter-padded when the sample has fewer than k distinct points (exact
    duplicate centers would never win an argmin tie and stay empty forever).
    Shared by KMeans._init_centers and io.streaming.StreamingKMeans.
    """
    sample = np.asarray(sample, dtype=np.float64)
    m = len(sample)
    centers = [sample[rng.integers(m)]]
    d2 = np.sum((sample - centers[0]) ** 2, axis=1)
    for _ in range(1, min(k, m)):
        s = d2.sum()
        if s > 0:
            p = d2 / s
            p = p / p.sum()  # exact renormalization for choice()
            centers.append(sample[rng.choice(m, p=p)])
        else:  # all remaining points identical to a seed: pick uniformly
            centers.append(sample[rng.integers(m)])
        d2 = np.minimum(d2, np.sum((sample - centers[-1]) ** 2, axis=1))
    out = np.stack(centers)
    if out.shape[0] < k:  # fewer rows than k: pad with PER-ROW random jitter
        # (a shared constant offset would make the pads exact duplicates of
        # each other — precisely the dead-center failure this guards against)
        extra = out[rng.integers(out.shape[0], size=k - out.shape[0])]
        # jitter scaled to the value's magnitude: an absolute 1e-3 rounds
        # away in float32 when |center| ~ 1e5+ and the pads collapse back
        # into exact duplicates
        jitter = rng.normal(size=extra.shape) * 1e-3 * (1.0 + np.abs(extra))
        out = np.concatenate([out, extra + jitter], axis=0)
    return out.astype(np.float32)


class KMeansModel(Model):
    def __init__(self, params, centers):
        self.params = params
        self.centers = centers  # f32[k, d]
        self.n_iter_: int | None = None
        self.training_cost_: float | None = None  # MLlib summary.trainingCost

    @property
    def state_pytree(self):
        return {"centers": self.centers}

    def load_fit_summary(self, summary, fit: str = "eager") -> None:
        """``cost``, ``n_iter``, ``cluster_sizes``, ``init_centers`` as
        the fit left them on the device; the host mirrors (and the
        iteration counter) follow once they are concrete."""
        self.fit_summary = summary
        self.cluster_sizes_ = summary["cluster_sizes"]
        self.n_iter_ = concrete_or_none(summary["n_iter"], int)
        self.training_cost_ = concrete_or_none(summary["cost"])
        if self.n_iter_ is not None:
            _M_ITERATIONS.inc(self.n_iter_, fit=fit)
            k, d = self.centers.shape
            _M_PASSES.inc(self.n_iter_ + 1,
                          how="vpu" if _on_vpu(d, k) else "mxu")

    @property
    def cluster_centers_(self) -> np.ndarray:
        return np.asarray(self.centers)

    def predict(self, table: TpuTable) -> np.ndarray:
        assign, _ = _assign(table.X, self.centers, table.W,
                            rows=table.session.row_sharding)
        return np.asarray(assign)[: table.n_rows]

    def _device_predict(self, table: TpuTable):
        """Serving hook (serve/context.py): per-row cluster ids, device-pure
        — assignment is row-wise (argmin over centers), so bucket padding
        cannot perturb live rows."""
        assign, _ = _assign(table.X, self.centers, table.W,
                            rows=table.session.row_sharding)
        return assign

    def compute_cost(self, table: TpuTable) -> float:
        _, cost = _assign(table.X, self.centers, table.W,
                          rows=table.session.row_sharding)
        return float(cost)

    def transform(self, table: TpuTable) -> TpuTable:
        """Append the 'cluster' prediction column (Spark's predictionCol)."""
        assign, _ = _assign(table.X, self.centers, table.W,
                            rows=table.session.row_sharding)
        k = self.centers.shape[0]
        new_attrs = list(table.domain.attributes) + [
            DiscreteVariable("cluster", tuple(str(i) for i in range(k)))
        ]
        new_domain = Domain(new_attrs, table.domain.class_vars, table.domain.metas)
        X = jnp.concatenate([table.X, assign[:, None].astype(jnp.float32)], axis=1)
        return table.with_X(X, new_domain)


def device_sample_live(X, W, cap: int, key):
    """Tracer-safe uniform subsample of up to ``cap`` LIVE rows (gumbel-max
    top-k over the live mask): the device twin of the eager inits'
    host-side 8192-row sampling. Seeding on the sample instead of the full
    data turns the D² init's k distance passes from k x N rows into
    k x cap rows — at 10M rows that was the dominant cost of a staged
    REFIT (round-4 measurement: the fused fit program spent more time
    seeding than Lloyd's took to converge). Returns (Xs [cap, d],
    Ws [cap]) where dead/past-live picks carry Ws=0."""
    N = X.shape[0]
    live = W > 0
    g = jnp.where(live, jax.random.gumbel(key, (N,)), -jnp.inf)
    # approx_max_k: the winners of ~cap buckets of rows, each uniform over
    # its bucket's live rows — a uniform sample all the same, where an
    # exact top_k sorts all N (0.44 s at 2^27 rows on a v5e against 2 ms,
    # and 34 s of compile; PERF.md, PR 35). Exact on the CPU
    gv, idx = jax.lax.approx_max_k(g, min(cap, N))
    return X[idx], jnp.isfinite(gv).astype(jnp.float32)


def device_d2_seed(X, W, k: int, k0, k1) -> jnp.ndarray:
    """Device-pure categorical D²-sampling (kmeans++) seeding — tracer-safe,
    shared by KMeans (k-means|| init) and GaussianMixture (means init)
    under staged refit, where the host-sample init cannot run."""
    N, d = X.shape
    live = W > 0
    # first center: uniform over live rows via gumbel-max
    g = jax.random.gumbel(k0, (N,))
    i0 = jnp.argmax(jnp.where(live, g, -jnp.inf))
    centers = jnp.zeros((k, d), X.dtype).at[0].set(X[i0])
    d2 = jnp.where(live, jnp.sum((X - X[i0]) ** 2, axis=1), 0.0)

    def body(c, carry):
        centers, d2, key = carry
        key, kc, ku = jax.random.split(key, 3)
        mask = live & (d2 > 0)
        logits = jnp.where(mask, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
        cat = jax.random.categorical(kc, logits)
        # all remaining live points coincide with a seed: uniform pick
        gu = jax.random.gumbel(ku, (N,))
        uni = jnp.argmax(jnp.where(live, gu, -jnp.inf))
        idx = jnp.where(jnp.any(mask), cat, uni)
        # duplicate centers get per-coordinate jitter scaled to
        # magnitude (same dead-center guard as kmeanspp_seed)
        newc = X[idx] + jnp.where(
            jnp.any(mask), 0.0,
            1e-3 * (1.0 + jnp.abs(X[idx]))
            * jax.random.normal(ku, (d,), X.dtype),
        )
        centers = centers.at[c].set(newc)
        d2 = jnp.minimum(d2, jnp.sum((X - newc) ** 2, axis=1))
        d2 = jnp.where(live, d2, 0.0)
        return centers, d2, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers, d2, k1))
    return centers


class KMeans(Estimator):
    ParamsCls = KMeansParams
    params: KMeansParams

    def _device_init_centers(self, X, W) -> jnp.ndarray:
        """Device-pure center init — used when the fit itself is being
        TRACED (staged refit, workflow/staging.py): the host-sample init
        below cannot run on tracers (and it ships no sample device→host,
        which the eager init does). Honors ``init_mode``: 'random' is
        a gumbel-max uniform draw of k live rows; 'k-means||' is
        categorical D²-sampling (kmeans++) in a fori_loop. Seeded and
        deterministic, but a different random stream than the host init
        (documented)."""
        p = self.params
        key = jax.random.PRNGKey(p.seed)
        k0, k1 = jax.random.split(key)
        if p.init_mode == "random":
            # k distinct uniform live rows (device_sample_live's gumbel-max
            # top-k). Picks past the live count would land on DEAD rows —
            # the exact stranded-center failure the eager path guards
            # against — so they are replaced by jittered duplicates of the
            # first (live) pick, mirroring the eager live-center padding.
            centers, ws = device_sample_live(X, W, p.k, k0)
            dead = ws == 0
            base = centers[0]                     # live whenever any row is
            jit_ = (1e-3 * (1.0 + jnp.abs(base))
                    * jax.random.normal(k1, centers.shape, X.dtype))
            return jnp.where(dead[:, None], base[None, :] + jit_, centers)
        if p.init_mode != "k-means||":
            raise ValueError(f"unknown init_mode {p.init_mode!r}")
        # seed on a uniform live subsample (the eager path's
        # init_sample_size-row sampling, on device): D² passes then cost
        # k x sample rows, not k x N — the difference between a staged
        # refit that beats the eager walk and one that loses to it at
        # 10M rows
        ks, k0b = jax.random.split(k0)
        Xs, Ws = device_sample_live(X, W, p.init_sample_size, ks)
        return device_d2_seed(Xs, Ws, p.k, k0b, k1)

    def _init_centers(self, table: TpuTable) -> jnp.ndarray:
        p = self.params
        if isinstance(table.X, jax.core.Tracer):
            return self._device_init_centers(table.X, table.W)
        rng = np.random.default_rng(p.seed)
        # sample only live rows — filtered (w=0) rows must not seed centers,
        # or a center stranded on a dead outlier never receives points and
        # Lloyd's keeps it forever
        live = np.flatnonzero(np.asarray(jax.device_get(table.W)) > 0)
        n = len(live)
        if n == 0:
            raise ValueError("cannot fit KMeans: table has no live rows")
        if p.init_mode == "random":
            idx = live[rng.choice(n, size=min(p.k, n), replace=False)]
            centers = np.asarray(jax.device_get(table.X[np.sort(idx)]))
        elif p.init_mode == "k-means||":
            # kmeans++ on a host sample: same seed-spreading intent as
            # MLlib's distributed k-means|| oversampling rounds.
            m = min(n, p.init_sample_size)
            idx = live[rng.choice(n, size=m, replace=False)] if m < n else live
            # gather the sample ON DEVICE, then pull only those m rows host-ward
            # (never device_get the full [N,d] table)
            sample = np.asarray(jax.device_get(table.X[np.sort(idx)]))
            centers = kmeanspp_seed(sample, p.k, rng)
        else:
            raise ValueError(f"unknown init_mode {p.init_mode!r}")
        if centers.shape[0] < p.k:  # fewer rows than k: pad with jitter
            extra = centers[rng.integers(centers.shape[0], size=p.k - centers.shape[0])]
            centers = np.concatenate([centers, extra + 1e-3], axis=0)
        return jax.device_put(centers.astype(np.float32), table.session.replicated)

    def _fit(self, table: TpuTable) -> KMeansModel:
        p = self.params
        lloyd_kw = dict(k=p.k, max_iter=p.max_iter,
                        compute_dtype=jnp.dtype(p.compute_dtype),
                        rows=table.session.row_sharding)
        tol = jnp.float32(p.tol)
        if p.n_init <= 1:
            with jax.named_scope("kmeans/init"):
                init = self._init_centers(table)
            with jax.named_scope("kmeans/lloyd"):
                # _lloyd consumes its seed centres: the summary keeps a copy
                centers, assign, cost, n_iter = _lloyd(
                    table.X, table.W, jnp.copy(init), tol, **lloyd_kw)
        else:
            # all restarts advance in lockstep inside one vmapped while_loop —
            # n_init independent Lloyd runs for roughly the cost of one.
            # Donation under a vmap trace is a silent no-op, so call the
            # undonated twin rather than compile a donating executable
            # whose aliasing can never engage.
            with jax.named_scope("kmeans/init"):
                inits = jnp.stack([
                    self.replace_seed(s)._init_centers(table)
                    for s in range(p.seed, p.seed + p.n_init)
                ])
            with jax.named_scope("kmeans/lloyd"):
                centers_v, assign_v, cost_v, iter_v = jax.vmap(
                    lambda c0: _lloyd.plain(table.X, table.W, c0, tol,
                                            **lloyd_kw)
                )(inits)
            best = jnp.argmin(cost_v)
            centers, cost, n_iter = centers_v[best], cost_v[best], iter_v[best]
            assign, init = assign_v[best], inits[best]
        model = KMeansModel(p, centers)
        model.load_fit_summary({
            "cost": cost, "n_iter": n_iter, "init_centers": init,
            # reuses the converged Lloyd assignment — no extra distance pass
            "cluster_sizes": live_cluster_sizes(table.W, assign, p.k),
        })
        return model

    def replace_seed(self, seed: int) -> "KMeans":
        return KMeans(self.params.replace(seed=seed))
