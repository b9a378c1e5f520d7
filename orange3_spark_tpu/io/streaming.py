"""Out-of-core streaming fit — the 1B-row path (Criteo / NYC-Taxi configs).

Spark streams these workloads by construction: rows live partitioned on the
cluster and every L-BFGS iteration treeAggregates over all executors
(SURVEY.md §3 step 3; reconstructed, mount empty). A single TPU host can't
hold 1B rows either, so the TPU-native path is a **chunk pipeline**:

    native fastcsv chunk (C++ threads, f32 row-major)
      -> jax.device_put onto the data-axis sharding   (host->HBM DMA)
      -> one jitted minibatch update step             (MXU)

with three overlap properties:

* every chunk has the SAME padded shape, so the update step compiles once
  and is reused for the whole stream;
* JAX dispatch is async — while the TPU runs step t, the C++ parser and the
  DMA for chunk t+1 proceed on host threads (double buffering for free);
* the optimizer state lives on device; nothing but the raw chunk crosses
  the host boundary, once.

``StreamingLinearEstimator`` fits logistic / squared / hinge losses with
adam over epochs of chunks and returns the SAME model classes the in-memory
estimators produce, so downstream transform/evaluate/save code sees no
difference.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import uuid
import warnings
from functools import partial
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.exec.donate import donating_jit
from orange3_spark_tpu.exec.pipeline import PipelineStats, prefetch_iter
from orange3_spark_tpu.io.multihost import put_sharded
from orange3_spark_tpu.obs import prof
from orange3_spark_tpu.obs.report import RunReport
from orange3_spark_tpu.obs.trace import refreshed_enabled as obs_enabled
from orange3_spark_tpu.obs.trace import span, span_iter, traced
from orange3_spark_tpu.resilience.numerics import check_finite_training
from orange3_spark_tpu.utils.dispatch import bound_dispatch
from orange3_spark_tpu.utils.profiling import count_dispatch
from orange3_spark_tpu.models.base import Estimator, Params

# (X [n,d], y [n] or None) or (X, y, w) — sources may carry row weights
Chunk = tuple


def csv_chunk_source(
    path: str, class_col: str = "", *, chunk_rows: int = 1 << 20,
    delimiter: str = ",", header: bool = True, n_threads: int = 0,
) -> Callable[[], Iterator[Chunk]]:
    """Re-iterable chunk source over a CSV file via the native parser.

    Returns a zero-arg callable (epochs need to restart the stream)."""
    from orange3_spark_tpu.io.native import NativeCsvReader

    def open_stream() -> Iterator[Chunk]:
        with NativeCsvReader(path, delimiter=delimiter, header=header,
                             n_threads=n_threads) as r:
            if class_col:
                if class_col not in r.colnames:
                    raise ValueError(
                        f"class_col {class_col!r} not in {r.colnames}"
                    )
                ci = r.colnames.index(class_col)
                keep = [j for j in range(r.ncols) if j != ci]
                for c in r.chunks(chunk_rows):
                    yield np.ascontiguousarray(c[:, keep]), c[:, ci]
            else:
                for c in r.chunks(chunk_rows):
                    yield c, None

    return open_stream


def csv_raw_chunk_source(
    path: str, *, chunk_rows: int = 1 << 20, delimiter: str = ",",
    header: bool = True, n_threads: int = 0,
    categorical_cols: tuple = (),
) -> Callable[[], Iterator[np.ndarray]]:
    """Re-iterable source of RAW [n, ncols] f32 chunks — no host-side
    label split, so the parser's output buffer is device_put as-is (zero
    host copies). Pair with an estimator's ``label_in_chunk`` mode, which
    slices the label column inside the jit. ``categorical_cols`` marks
    string columns for parse-time crc32 hashing (io/native.py)."""
    from orange3_spark_tpu.io.native import NativeCsvReader

    def open_stream() -> Iterator[np.ndarray]:
        with NativeCsvReader(path, delimiter=delimiter, header=header,
                             n_threads=n_threads,
                             categorical_cols=categorical_cols) as r:
            yield from r.chunks(chunk_rows)

    return open_stream


def sharded_csv_chunk_source(
    path, class_col: str = "", *, shard_total_rows: int | None = None,
    chunk_rows: int = 1 << 20, delimiter: str = ",", header: bool = True,
    n_threads: int = 0,
) -> Callable[[], Iterator[Chunk]]:
    """Per-host CSV ingest for multi-process fits (docs/multihost.md).

    Single shared file: every process streams only its contiguous
    ``io.multihost.process_row_slice(shard_total_rows)`` row block (the
    parse STOPS at the block's end, so rows past it are never decoded),
    then re-chunks the block into an emission schedule that is IDENTICAL
    on every gang member: ``ceil(lockstep_rows/chunk_rows)`` chunks, all
    of ``chunk_rows`` rows but the last. A process holding fewer rows than
    the common per-host target tops up with dead rows (features 0, label
    0, weight 0 — the weight-mask pad convention ``put_sharded`` names),
    so all processes run the same chunk schedule and the global
    collectives stay in lockstep.

    ``path`` may also be a LIST of paths: file-per-executor splitting via
    ``io.multihost.shard_paths`` (round-robin; ``shard_total_rows`` is
    ignored). In that mode the caller owns row-count balance across
    processes — ragged totals raise typed at ``put_sharded``.

    Under ``OTPU_MULTIHOST=0`` (the kill-switch) the single-path form IS
    ``csv_chunk_source`` — the pre-multihost stream, bitwise. With the
    switch on in a single process over a file holding exactly
    ``shard_total_rows`` rows, the emitted chunks are the parser's own
    buffers unchanged (same values, zero extra copies).

    Yields ``(X, y, w)`` triples (``array_chunk_source``'s form): ``w`` is
    ``None`` on pure-data chunks and a 0-mask tail on padded ones."""
    from orange3_spark_tpu.io.multihost import (lockstep_rows,
                                                process_row_slice,
                                                shard_paths)
    from orange3_spark_tpu.utils import knobs

    if isinstance(path, (list, tuple)):
        multi = knobs.get_bool("OTPU_MULTIHOST")
        paths = (shard_paths(path) if multi
                 else sorted(str(p) for p in path))

        def open_paths() -> Iterator[Chunk]:
            for p in paths:
                yield from csv_chunk_source(
                    p, class_col, chunk_rows=chunk_rows,
                    delimiter=delimiter, header=header,
                    n_threads=n_threads)()

        return open_paths

    if not knobs.get_bool("OTPU_MULTIHOST"):
        return csv_chunk_source(path, class_col, chunk_rows=chunk_rows,
                                delimiter=delimiter, header=header,
                                n_threads=n_threads)
    if shard_total_rows is None:
        raise ValueError(
            "sharded_csv_chunk_source over a single shared file needs "
            "shard_total_rows (the file's exact row count) to assign "
            "process row blocks")
    n_total = int(shard_total_rows)
    has_y = bool(class_col)
    inner = csv_chunk_source(path, class_col, chunk_rows=chunk_rows,
                             delimiter=delimiter, header=header,
                             n_threads=n_threads)

    def open_stream() -> Iterator[Chunk]:
        sl = process_row_slice(n_total)
        target = lockstep_rows(n_total)
        if target == 0:
            return
        k = -(-target // chunk_rows)
        sizes = [chunk_rows] * (k - 1) + [target - chunk_rows * (k - 1)]
        pend: list[tuple] = []      # sliced (X, y, w) pieces pending emit
        pend_n = 0

        def take(s: int) -> Chunk:
            nonlocal pend_n
            pieces, got = [], 0
            while got < s:
                X, y, w = pend[0]
                need = s - got
                if len(X) <= need:
                    pend.pop(0)
                    pieces.append((X, y, w))
                    got += len(X)
                else:
                    pieces.append((X[:need],
                                   None if y is None else y[:need],
                                   None if w is None else w[:need]))
                    pend[0] = (X[need:],
                               None if y is None else y[need:],
                               None if w is None else w[need:])
                    got = s
            pend_n -= s
            if len(pieces) == 1:
                return pieces[0]
            Xo = np.concatenate([p[0] for p in pieces])
            yo = (np.concatenate([p[1] for p in pieces]) if has_y
                  else None)
            if all(p[2] is None for p in pieces):
                wo = None
            else:
                wo = np.concatenate([
                    np.ones(len(p[0]), np.float32) if p[2] is None else p[2]
                    for p in pieces])
            return Xo, yo, wo

        pos = have = si = 0
        n_feat = None
        it = inner()
        try:
            for c in it:
                X, y = c[0], c[1]
                base, n = pos, len(X)
                pos += n
                if n_feat is None:
                    n_feat = X.shape[1]
                lo, hi = max(sl.start, base), min(sl.stop, base + n)
                if hi > lo:
                    pend.append((X[lo - base:hi - base],
                                 None if y is None else y[lo - base:hi - base],
                                 None))
                    pend_n += hi - lo
                    have += hi - lo
                    while si < len(sizes) and pend_n >= sizes[si]:
                        yield take(sizes[si])
                        si += 1
                if pos >= sl.stop:
                    break       # our block is done — stop parsing
        finally:
            it.close()
        if have < sl.stop - sl.start:
            raise ValueError(
                f"sharded_csv_chunk_source: {path!r} exhausted at row "
                f"{pos} — shard_total_rows={n_total} overstates the file, "
                f"process {sl} holds only {have} rows")
        dead = target - have
        if dead:
            if n_feat is None:
                raise ValueError(
                    f"sharded_csv_chunk_source: {path!r} holds no data "
                    "rows — cannot shape the lockstep dead-row padding")
            pend.append((np.zeros((dead, n_feat), np.float32),
                         np.zeros((dead,), np.float32) if has_y else None,
                         np.zeros((dead,), np.float32)))
            pend_n += dead
        while si < len(sizes) and pend_n >= sizes[si]:
            yield take(sizes[si])
            si += 1

    return open_stream


def parquet_chunk_source(
    path: str, class_col: str = "", *, chunk_rows: int = 1 << 20,
    columns: tuple | None = None, row_groups: tuple | None = None,
    shard: bool = False,
) -> Callable[[], Iterator[Chunk]]:
    """Re-iterable chunk source over a parquet file, read ROW-GROUP-AT-A-
    TIME — the out-of-core ingest regime was CSV-only through round 4
    (round-4 verdict missing #2; SURVEY §2b "Data ingest": sharded
    "Arrow/parquet -> numpy" loading — spark.read.parquet streams at any
    scale, so must we). ``pyarrow.ParquetFile.iter_batches`` decodes one
    row group at a time into ``chunk_rows``-sized record batches, so host
    memory stays bounded by the row-group size however large the file is;
    ``io/readers.py:read_parquet`` remains the whole-file path for tables
    that fit. Yields ``(X [n,d] f32, y [n] f32 | None)`` with ``class_col``
    split out; returns a zero-arg callable (epochs restart the stream).
    ``row_groups`` restricts the stream to those group indices — pass
    ``io.multihost.shard_row_groups(path)`` for single-file multihost
    ingest (Spark's parquet input splits), or just ``shard=True`` to have
    the source pick this process's contiguous group range itself (inert
    under ``OTPU_MULTIHOST=0`` or an explicit ``row_groups``; row-group
    splitting has no lockstep padding, so the caller owns group balance
    across processes — ragged totals raise typed at ``put_sharded``)."""
    import pyarrow.parquet as pq

    def open_stream() -> Iterator[Chunk]:
        groups = row_groups
        if shard and groups is None:
            from orange3_spark_tpu.io.multihost import shard_row_groups
            from orange3_spark_tpu.utils import knobs
            if knobs.get_bool("OTPU_MULTIHOST"):
                groups = shard_row_groups(path)
        pf = pq.ParquetFile(path)
        try:
            names = list(columns) if columns else [
                f.name for f in pf.schema_arrow]
            ci = -1
            if class_col:
                if class_col not in names:
                    raise ValueError(
                        f"class_col {class_col!r} not in {names}")
                ci = names.index(class_col)
            for batch in pf.iter_batches(batch_size=chunk_rows,
                                         columns=names,
                                         row_groups=list(groups)
                                         if groups is not None
                                         else None):
                cols = [
                    batch.column(j).to_numpy(zero_copy_only=False)
                    .astype(np.float32, copy=False)
                    for j in range(batch.num_columns)
                ]
                y = cols.pop(ci) if ci >= 0 else None
                yield np.column_stack(cols), y
        finally:
            pf.close()

    return open_stream


def parquet_raw_chunk_source(
    path: str, *, chunk_rows: int = 1 << 20, columns: tuple | None = None,
    row_groups: tuple | None = None, shard: bool = False,
) -> Callable[[], Iterator[np.ndarray]]:
    """Parquet twin of ``csv_raw_chunk_source``: RAW [n, ncols] f32 chunks
    with no host-side label split, for estimators' ``label_in_chunk`` mode
    (the label column is sliced inside the jit). Row-group-at-a-time like
    ``parquet_chunk_source``, so the 1B-row streaming/spill path works
    from parquet exactly as from CSV; ``row_groups`` +
    ``io.multihost.shard_row_groups`` (or ``shard=True`` to auto-pick this
    process's range, inert under ``OTPU_MULTIHOST=0``) give single-file
    multihost ingest."""
    import pyarrow.parquet as pq

    def open_stream() -> Iterator[np.ndarray]:
        groups = row_groups
        if shard and groups is None:
            from orange3_spark_tpu.io.multihost import shard_row_groups
            from orange3_spark_tpu.utils import knobs
            if knobs.get_bool("OTPU_MULTIHOST"):
                groups = shard_row_groups(path)
        pf = pq.ParquetFile(path)
        try:
            for batch in pf.iter_batches(batch_size=chunk_rows,
                                         columns=list(columns)
                                         if columns else None,
                                         row_groups=list(groups)
                                         if groups is not None
                                         else None):
                yield np.column_stack([
                    batch.column(j).to_numpy(zero_copy_only=False)
                    .astype(np.float32, copy=False)
                    for j in range(batch.num_columns)
                ])
        finally:
            pf.close()

    return open_stream


def prefetch_map(fn: Callable, items: Iterator, *, depth: int = 2,
                 stats_into: PipelineStats | None = None) -> Iterator:
    """Run ``fn`` over ``items`` on a daemon thread, yielding results in
    order through a bounded queue.

    This is the chunk pipeline's overlap engine: with
    ``fn = encode + device_put`` (pad, the cache codec's narrowing, hash and
    bit-pack; the parse is the pull of ``items`` on the same thread) the
    host prepares (and DMAs) chunk t+1 while the device runs step t. The native parser and ``device_put`` both
    release the GIL, so the worker genuinely overlaps the main thread's
    dispatch work even on a single-core host (the transfer's wait-on-DMA
    time is free CPU for the parser). Worker exceptions re-raise at the
    consuming ``next()``; closing the generator early stops the worker.

    Thin delegate over ``exec.pipeline.PipelinedExecutor`` — the one
    overlap engine, now with MEASURED overlap (``stats_into`` receives the
    stream's counters; every stream also folds into the process aggregate
    read by ``utils.profiling.exec_counters``)."""
    return prefetch_iter(fn, items, depth=depth, stats_into=stats_into)


def array_chunk_source(X: np.ndarray, y: np.ndarray | None = None,
                       w: np.ndarray | None = None,
                       *, chunk_rows: int = 1 << 16) -> Callable[[], Iterator[Chunk]]:
    """Chunk an in-memory array (testing / small data)."""

    def open_stream() -> Iterator[Chunk]:
        for s in range(0, len(X), chunk_rows):
            e = min(s + chunk_rows, len(X))
            yield (X[s:e],
                   None if y is None else y[s:e],
                   None if w is None else w[s:e])

    return open_stream


@donating_jit(static_argnames=("gramian",), donate_argnums=(0,))
def _feature_stats_step(acc, X, w, *, gramian: bool):
    """Fold one padded chunk into the running per-column stats (and the
    weighted Gramian when asked — an MXU matmul per chunk). Moments
    accumulate on Z = X - shift (shift ≈ the data's column means, taken
    from the first chunk): the single-pass identity var = E[z²] - E[z]²
    is catastrophically cancellative in f32 when mean² ≫ var (epoch
    timestamps: mean ~1.5e9, std ~1e5 — ss would retain ZERO variance
    bits unshifted), and near-zero-mean Z restores the lost precision.
    min/max stay on the raw X."""
    live = (w > 0)[:, None]
    Z = X - acc["shift"][None, :]
    wZ = Z * w[:, None]
    big = jnp.float32(np.finfo(np.float32).max)
    out = {
        "shift": acc["shift"],
        "n": acc["n"] + jnp.sum(w),
        "s": acc["s"] + jnp.sum(wZ, axis=0),
        "ss": acc["ss"] + jnp.sum(wZ * Z, axis=0),
        "mn": jnp.minimum(acc["mn"],
                          jnp.min(jnp.where(live, X, big), axis=0)),
        "mx": jnp.maximum(acc["mx"],
                          jnp.max(jnp.where(live, X, -big), axis=0)),
    }
    if gramian:
        out["g"] = acc["g"] + Z.T @ wZ
    return out


@donating_jit(static_argnames=("nan_missing",), donate_argnums=(0,))
def _feature_stats_step_missing(acc, X, w, mv, *, nan_missing: bool):
    """Missing-aware fold (the streaming Imputer fit): per-CELL
    observation masks — a missing cell drops out of that column's
    count/sum/min/max without killing the row for other columns. Same
    shifted accumulation as ``_feature_stats_step``."""
    miss = jnp.isnan(X) if nan_missing else (X == mv)
    obs = (~miss) & (w > 0)[:, None]
    Z = jnp.where(obs, X - acc["shift"][None, :], 0.0)
    wobs = jnp.where(obs, w[:, None], 0.0)
    wZ = Z * wobs
    big = jnp.float32(np.finfo(np.float32).max)
    return {
        "shift": acc["shift"],
        "n": acc["n"] + jnp.sum(wobs, axis=0),
        "s": acc["s"] + jnp.sum(wZ, axis=0),
        "ss": acc["ss"] + jnp.sum(wZ * Z, axis=0),
        "mn": jnp.minimum(acc["mn"],
                          jnp.min(jnp.where(obs, X, big), axis=0)),
        "mx": jnp.maximum(acc["mx"],
                          jnp.max(jnp.where(obs, X, -big), axis=0)),
    }


@jax.jit
def _first_chunk_shift(X, w):
    """Weighted column means of the first chunk — the accumulation shift
    (any vector near the data's location works; all-dead chunk -> 0)."""
    tot = jnp.sum(w)
    s = jnp.sum(X * w[:, None], axis=0)
    return jnp.where(tot > 0, s / jnp.maximum(tot, 1e-12), 0.0)


@partial(jax.jit, static_argnames=("nan_missing",))
def _first_chunk_shift_missing(X, w, mv, *, nan_missing: bool):
    """Missing-aware shift: per-column observed means (a NaN missing
    value would otherwise poison the plain shift, and a sentinel like
    -999 would drag it far from the data)."""
    miss = jnp.isnan(X) if nan_missing else (X == mv)
    obs = (~miss) & (w > 0)[:, None]
    wobs = jnp.where(obs, w[:, None], 0.0)
    tot = jnp.sum(wobs, axis=0)
    s = jnp.sum(jnp.where(obs, X, 0.0) * wobs, axis=0)
    return jnp.where(tot > 0, s / jnp.maximum(tot, 1e-12), 0.0)


def stream_feature_stats(source: Callable[[], Iterator[Chunk]],
                         *, session: TpuSession | None = None,
                         chunk_rows: int = 1 << 18,
                         gramian: bool = False,
                         missing_value: float | None = None,
                         stage_times: dict | None = None) -> dict:
    """Single-pass per-column statistics over a chunk stream — the
    out-of-core fit for the feature transformers and PCA (BASELINE
    config 5 is KMeans + PCA at 1B TAXI rows: StreamingKMeans existed,
    but scaler/PCA fits were in-memory only — a 1B-row pipeline could
    not be fitted end to end before this).

    One jitted fold per chunk (donated accumulator, so the running stats
    never leave HBM; ``gramian=True`` adds one [chunk,d]ᵀ@[chunk,d] MXU
    matmul per chunk for PCA); parse/pad/DMA of chunk t+1 overlaps the
    device fold of chunk t via ``prefetch_map``; accumulation is shifted
    by the first chunk's column means (see ``_feature_stats_step``) so
    f32 keeps its precision on large-mean columns. Returns host floats:
    ``count`` (total weight), ``mean``, ``var`` (population, the MLlib
    standardization convention — the same quantity
    ``ops.stats.weighted_moments`` computes), ``min``/``max`` over live
    rows, and with ``gramian=True`` the population ``cov``
    (E[(x-μ)(x-μ)ᵀ]) and raw ``second_moment`` (E[x·xᵀ]).

    ``missing_value`` (NaN or a sentinel float) switches to per-CELL
    observation masks — the streaming Imputer fit: a missing cell leaves
    that column's count/mean/var/min/max, other columns keep the row.
    ``count`` is then a per-column array; incompatible with ``gramian``
    (a Gramian over ragged observations is not the covariance).

    ``stage_times``: optional dict receiving the pass's pipeline metrics —
    ``overlap_pct`` (measured host-prep/device-fold overlap, see
    ``exec.pipeline``) and ``dispatches`` (fold programs dispatched)."""
    if missing_value is not None and gramian:
        raise ValueError("gramian=True and missing_value are incompatible")
    from orange3_spark_tpu.resilience.retry import resilient_source

    session = session or TpuSession.builder_get_or_create()
    pad_rows = session.pad_rows(chunk_rows)
    row_sh = session.row_sharding
    vec_sh = session.vector_sharding

    def prep(chunk):
        X_np, _, w_np = chunk
        n_features = X_np.shape[1]
        Xp, _, wp = _pad_chunk(X_np, None, w_np, pad_rows, n_features)
        return put_sharded(Xp, row_sh), put_sharded(wp, vec_sh)

    acc = None
    pstats = PipelineStats()
    # transient source-read faults are absorbed by bounded retries on the
    # prefetch thread (resilience/retry.py; counted into pstats.retries)
    source = resilient_source(source, stats=pstats)
    n_folds = 0
    for step, (Xd, wd) in enumerate(
            prefetch_map(prep, _rechunk(source(), pad_rows), depth=2,
                         stats_into=pstats)):
        if acc is None:
            n_features = Xd.shape[1]
            big = np.float32(np.finfo(np.float32).max)
            acc = {
                "shift": (_first_chunk_shift_missing(
                    Xd, wd, jnp.float32(missing_value),
                    nan_missing=bool(np.isnan(missing_value)))
                    if missing_value is not None
                    else _first_chunk_shift(Xd, wd)),
                "n": jnp.zeros((n_features,) if missing_value is not None
                               else (), jnp.float32),
                "s": jnp.zeros((n_features,), jnp.float32),
                "ss": jnp.zeros((n_features,), jnp.float32),
                "mn": jnp.full((n_features,), big, jnp.float32),
                "mx": jnp.full((n_features,), -big, jnp.float32),
                **({"g": jnp.zeros((n_features, n_features), jnp.float32)}
                   if gramian else {}),
            }
        if missing_value is not None:
            acc = _feature_stats_step_missing(
                acc, Xd, wd, jnp.float32(missing_value),
                nan_missing=bool(np.isnan(missing_value)))
        else:
            acc = _feature_stats_step(acc, Xd, wd, gramian=gramian)
        n_folds = step + 1
        bound_dispatch(n_folds, acc["n"], period=8)
    if acc is None:
        raise ValueError("stream produced no chunks")
    if stage_times is not None:
        stage_times["overlap_pct"] = round(pstats.overlap_pct, 1)
        stage_times["dispatches"] = n_folds
    host = jax.device_get(acc)          # ONE blocking transfer, not eight
    # scalar total weight normally; per-column observed weight under
    # missing_value — the identical formulas broadcast over both
    n_raw = np.asarray(host["n"], np.float64)
    n = np.maximum(n_raw, 1e-12)
    shift = np.asarray(host["shift"], np.float64)
    mean_z = np.asarray(host["s"], np.float64) / n
    var = np.maximum(
        np.asarray(host["ss"], np.float64) / n - mean_z ** 2, 0.0)
    mean = shift + mean_z
    mn = np.asarray(host["mn"])
    mx = np.asarray(host["mx"])
    if n.ndim:
        # missing mode: an all-missing column has no mean — fill 0, the
        # in-memory Imputer's convention (sum 0 over eps weight). min/max
        # get the SAME dead-column fill: without it the ±FLT_MAX
        # accumulator init sentinels (3.4e38) would leak into the result
        dead = n_raw <= 0
        mean[dead] = 0.0
        var[dead] = 0.0
        mn = mn.copy()
        mx = mx.copy()
        mn[dead] = 0.0
        mx[dead] = 0.0
    out = {
        # the UNCLAMPED weight: an all-missing column / empty stream must
        # report 0, not the division epsilon
        "count": float(n_raw) if n_raw.ndim == 0
        else n_raw.astype(np.float32),
        "mean": mean.astype(np.float32),
        "var": var.astype(np.float32),
        "min": mn,
        "max": mx,
    }
    if gramian:
        # Gz/n = E[z zᵀ]; centered cov is shift-invariant:
        #   cov = E[z zᵀ] - μz μzᵀ
        # and the raw second moment restores the shift:
        #   E[x xᵀ] = E[z zᵀ] + c μzᵀ + μz cᵀ + c cᵀ
        Ezz = np.asarray(host["g"], np.float64) / n
        cov = Ezz - np.outer(mean_z, mean_z)
        out["cov"] = cov.astype(np.float32)
        out["second_moment"] = (
            Ezz + np.outer(shift, mean_z) + np.outer(mean_z, shift)
            + np.outer(shift, shift)
        ).astype(np.float32)
    return out


def score_stream(score_fn, source: Callable[[], Iterator[Chunk]],
                 out_path: str, *, session: TpuSession | None = None,
                 chunk_rows: int = 1 << 18,
                 feature_names: tuple | None = None,
                 prediction_col: str = "prediction",
                 include_features: bool = True,
                 row_group_rows: int | None = None) -> int:
    """Streaming ``model.transform(df).write.parquet(path)``: score a
    chunk stream and write the results parquet ROW-GROUP-AT-A-TIME —
    the missing half of the 1B-row loop (ingest/fit/evaluate streamed;
    scored OUTPUT previously had to fit in memory).

    ``score_fn(X_device) -> [n] or [n, k]`` per padded chunk (a fitted
    model's prediction head); each chunk's scores are trimmed of padding
    and appended through one ``pyarrow.ParquetWriter`` — host memory
    stays bounded by the chunk size at any output scale, and the device
    scoring of chunk t overlaps the parse/DMA of chunk t+1 through the
    usual prefetch engine. Columns: the features (``feature_names`` or
    ``f0..``; skip with ``include_features=False``), the label when the
    source carries one, and ``prediction_col`` (``_0.._k-1`` suffixes
    for [n, k] scores). Returns the row count written; the file appears
    atomically (tmp + rename)."""
    import pyarrow as pa
    from pyarrow import parquet as pq

    if feature_names and not include_features:
        raise ValueError("feature_names conflicts with "
                         "include_features=False")
    if jax.process_count() > 1:
        raise NotImplementedError(
            "score_stream writes one local file; in multi-process mode "
            "score each process's shard to its own path explicitly")
    from orange3_spark_tpu.resilience.retry import resilient_source

    session = session or TpuSession.builder_get_or_create()
    source = resilient_source(source)
    pad_rows = session.pad_rows(chunk_rows)
    row_sh = session.row_sharding

    def prep(chunk):
        X_np, y_np, w_np = chunk
        n = len(X_np)
        Xp, _, _ = _pad_chunk(X_np, None, None, pad_rows, X_np.shape[1])
        return put_sharded(Xp, row_sh), X_np, y_np, w_np, n

    writer = None
    names: list = []
    tmp = f"{out_path}.tmp{os.getpid()}"
    total = 0
    ok = False
    label_in_schema = False
    try:
        for step, (Xd, X_np, y_np, w_np, n) in enumerate(prefetch_map(
                prep, _rechunk(source(), pad_rows), depth=2)):
            scores = np.asarray(jax.device_get(score_fn(Xd)))[:n]
            bound_dispatch(step + 1, scores, period=8)
            if w_np is not None:          # masked rows stay out of output
                live = np.asarray(w_np) > 0
                X_np, scores = X_np[live], scores[live]
                y_np = None if y_np is None else y_np[live]
                n = len(X_np)
            if writer is not None and (y_np is None) == label_in_schema:
                # the parquet schema is fixed by the FIRST chunk; a source
                # whose label presence flips mid-stream would otherwise
                # die inside pa.table with a names/columns length mismatch
                raise ValueError(
                    f"chunk {step} is {'un' if y_np is None else ''}labeled "
                    f"but the schema-defining first chunk was "
                    f"{'' if label_in_schema else 'un'}labeled — a stream's "
                    "label presence must be uniform across chunks"
                )
            if writer is None:
                d = X_np.shape[1]
                names = list(feature_names) if feature_names else \
                    [f"f{j}" for j in range(d)] if include_features else []
                if include_features and len(names) != d:
                    raise ValueError(
                        f"{len(names)} feature_names for {d} columns")
                label_in_schema = y_np is not None
                if y_np is not None:
                    names.append("label")
                if scores.ndim == 2:
                    names += [f"{prediction_col}_{j}"
                              for j in range(scores.shape[1])]
                else:
                    names.append(prediction_col)
                schema = pa.schema([pa.field(c, pa.float32())
                                    for c in names])
                writer = pq.ParquetWriter(tmp, schema)
            if n == 0:
                continue   # fully masked chunk: schema exists, nothing to write
            cols = ([X_np[:, j] for j in range(X_np.shape[1])]
                    if include_features else [])
            if y_np is not None:
                cols.append(np.asarray(y_np, np.float32))
            if scores.ndim == 2:
                cols += [scores[:, j] for j in range(scores.shape[1])]
            else:
                cols.append(scores)
            table = pa.table([pa.array(np.asarray(c, np.float32))
                              for c in cols], names=names)
            writer.write_table(table, row_group_size=row_group_rows or n)
            total += n
        if writer is None:
            raise ValueError("stream produced no chunks")
        ok = True
    finally:
        if writer is not None:
            writer.close()
        if not ok:
            try:
                os.unlink(tmp)   # no multi-GB orphans from failed runs
            except OSError:
                pass
    os.replace(tmp, out_path)
    return total


@dataclasses.dataclass(frozen=True)
class StreamingLinearParams(Params):
    loss: str = "logistic"       # 'logistic' | 'squared' | 'squared_hinge'
    n_classes: int = 2           # k for logistic
    epochs: int = 1
    step_size: float = 0.01
    reg_param: float = 0.0       # L2
    chunk_rows: int = 1 << 18    # padded device batch per step
    seed: int = 0
    # Defer epoch-1 training into the replay program (the hashed
    # estimator's schedule, models/hashed_linear.py): the streaming pass
    # becomes pure ingest and the replay carries ALL ``epochs`` passes —
    # identical step sequence, bit-identical results, but zero step
    # dispatches before the fused scan and none interleaved with ingest.
    # Needs cache_device.
    # Checkpointing composes only with replay_granularity='epoch'
    # (epoch-boundary snapshots between the per-epoch dispatches, same
    # contract as the hashed estimator); otherwise a checkpointered fit
    # silently keeps the default schedule.
    defer_epoch1: bool = False
    # 'all': every replay pass in ONE scan dispatch (cheapest). 'epoch':
    # one n_epochs=1 scan dispatch per pass — a dispatch per epoch instead
    # of per chunk, and the one that admits epoch-boundary checkpointing.
    replay_granularity: str = "all"   # 'all' | 'epoch'
    # With replay_granularity='epoch': fold K epochs into each scan
    # dispatch (n_replay/K dispatches instead of n_replay) — the
    # dispatch-amortization dial between 'epoch' (K=1) and 'all'
    # (K=n_replay). Identical step sequence at any K; checkpoint cadence
    # is preserved by clamping groups at snapshot boundaries
    # (run_epoch_replay). Ignored under granularity 'all'.
    epochs_per_dispatch: int = 1
    # Crash-resumable fits (docs/resilience.md): with a checkpointer
    # passed to fit_stream, K > 0 switches the snapshot cadence from
    # per-step (checkpointer.every_steps) to EPOCH BOUNDARIES every K
    # epochs — atomic write-to-temp + rename, so a fit SIGKILLed
    # mid-epoch resumes at the last boundary and replays the identical
    # step sequence (bitwise-equal final theta; pinned in
    # tests/test_resilience.py). Inert under OTPU_RESILIENCE=0 (the
    # legacy fail-fast ladder) and without a checkpointer.
    checkpoint_every_epochs: int = 0
    # Cache/spill storage precision (io/codec.py; resolved ONCE at fit
    # entry, OTPU_CACHE_DTYPE kill-switch): 'f32' is the legacy layout,
    # bit-for-bit; 'bf16' stores the cached/spilled feature matrix as
    # bfloat16 — HALF the HBM/disk/DMA bytes, decoded by the step's
    # existing astype(compute_dtype) widen (models/_linear._make_objective)
    # so the math stays f32. The dense path has no statically-bounded
    # integer columns, so 'packed'/'auto' resolve to bf16 here; the full
    # packed-int codec lives on the hashed estimator.
    cache_dtype: str = "f32"     # 'f32' | 'bf16' | 'packed' | 'auto'


#: per-process ledger-entry numbering for _DeviceCache instances
_CACHE_LEDGER_SEQ = itertools.count()


class _DeviceCache:
    """Epoch-1 HBM batch cache shared by the streaming estimators — one
    place for the budget/degrade rule: batches accumulate until ``budget``
    bytes. With ``may_exclude_tail > 0`` (an owner that excludes that
    many TRAILING batches after ingest — the hashed estimator's holdout
    tail), a batch that would overflow is NOT cached — and neither is any later
    batch, so misses form a contiguous SUFFIX of the offer sequence (the
    cached list must stay a gap-free prefix of the stream, or replay
    would reorder it) — and the run is provisionally ``degraded``.
    ``forgive_tail(k)`` (called alongside the holdout ``exclude()``)
    clears the misses when they all sit inside the excluded last-k-offers
    window, so a tail that was never going to be replayed no longer
    degrades the run (previously ONE transient overflow latched
    ``degraded`` forever and dropped everything). Misses are tracked by
    OFFER ORDINAL, never by object identity — a missed batch is dead by
    exclusion time and CPython recycles ids, so an id match there could
    silently bless an incomplete cache. ``settle()``, called once ingest
    + exclusion are done, finalizes: a surviving miss drops the WHOLE
    cache — a PARTIAL replay would reorder/double-count batches, which
    is why ``enabled`` can never un-latch past a real (non-forgiven)
    miss. A miss older than the excludable tail can never be forgiven,
    so the cache drops THE MOMENT a miss ages out of the window (and
    immediately when ``may_exclude_tail == 0``) — the latch — freeing
    the HBM for the rest of the ingest pass instead of pinning a doomed
    budget's worth until settle."""

    def __init__(self, enabled: bool, budget: int, *,
                 may_exclude_tail: int = 0):
        self.enabled = enabled
        self.budget = budget
        self.may_exclude_tail = may_exclude_tail
        self.batches: list = []
        self.nbytes = 0            # global: what the budget gates read
        self.chip_nbytes = 0       # on one chip: what the ledger reads
        self.degraded = False
        self.offered = 0           # total offer() calls
        self.first_miss: int | None = None   # ordinal of the first miss
        # device-memory ledger entry (obs/prof.py owner "cache_chunks"):
        # codec-aware bytes PER CHIP (rows sharded over 'data' divide;
        # the global ``nbytes`` beside them), updated on every nbytes
        # change, released by
        # finalize when the cache dies (an aborted fit leaks no entry;
        # the GC-safe deferred form — finalizers must not take the
        # ledger lock)
        self.ledger_key = f"chunk_cache-{next(_CACHE_LEDGER_SEQ)}"
        import weakref

        weakref.finalize(self, prof.ledger_release_on_gc, "cache_chunks",
                         self.ledger_key)

    def _ledger_sync(self, added: tuple | None = None) -> None:
        """The entry's bytes, and the cached arrays themselves (a census
        of the live arrays names them by identity): ``added`` joins those
        the entry has, else the whole list takes their place."""
        prof.ledger_set("cache_chunks", self.ledger_key,
                        self.chip_nbytes, self.nbytes,
                        arrays=self.batches if added is None else added,
                        extend=added is not None)

    def offer(self, batch: tuple) -> None:
        if not self.enabled:
            return
        self.offered += 1
        # memory-pressure brownout ladder (resilience/overload.py; inert —
        # level 0 — unless a pressure source is configured): 1 = admit
        # only to HALF the budget, 2 = stop admitting (the existing miss/
        # latch machinery routes replay to the spill or the re-streamed
        # source), 3 = drop the cache NOW, freeing the HBM it holds
        from orange3_spark_tpu.resilience.overload import brownout_level

        lvl = brownout_level()
        if lvl >= 3:
            self.enabled = False
            self.degraded = True
            self.batches = []
            self.nbytes = self.chip_nbytes = 0
            self.first_miss = None
            self._ledger_sync()
            return
        budget = self.budget // 2 if lvl == 1 else self.budget
        sz = self._size(batch)
        if (lvl < 2 and self.first_miss is None
                and self.nbytes + sz <= budget):
            self.batches.append(batch)
            self.nbytes += sz
            self.chip_nbytes += prof.tree_chip_bytes(batch)
            self._ledger_sync(added=batch)
        else:
            if self.first_miss is None:
                self.first_miss = self.offered - 1
            self.degraded = True
            if self.offered - self.first_miss > self.may_exclude_tail:
                # the miss can no longer sit inside the excludable tail:
                # no forgiveness is possible — drop NOW, legacy-style
                self.enabled = False
                self.batches = []
                # honest accounting for downstream gates
                self.nbytes = self.chip_nbytes = 0
                self.first_miss = None
                self._ledger_sync()

    def forgive_tail(self, k: int) -> None:
        """The last ``k`` offers were excluded from training (holdout):
        misses wholly inside that tail never needed replaying — clear the
        warn state. A miss that starts EARLIER is a real train-chunk gap
        and stays latched for ``settle()`` to resolve."""
        if self.first_miss is not None and self.first_miss >= self.offered - k:
            self.first_miss = None
            self.degraded = False

    @staticmethod
    def _size(batch: tuple) -> int:
        # tree-flatten, not a flat scan: compressed chunks carry a DICT
        # of encoded blocks as their 1st element, and skipping it would
        # under-count the budget the replay-fusion gate reads
        import jax

        return sum(b.nbytes for b in jax.tree.leaves(batch)
                   if hasattr(b, "nbytes"))

    def exclude(self, drop_ids: set) -> None:
        """Remove CACHED batches whose FIRST element's id() is in
        ``drop_ids`` (these are alive in the caller's hands, so identity
        is sound here), keeping ``nbytes`` accurate — holdout exclusion
        must not leave the budget accounting stale, downstream gates read
        nbytes. Miss forgiveness is ``forgive_tail``'s job."""
        kept = []
        for b in self.batches:
            if id(b[0]) in drop_ids:
                self.nbytes -= self._size(b)
                self.chip_nbytes -= prof.tree_chip_bytes(b)
            else:
                kept.append(b)
        self.batches = kept
        self._ledger_sync()

    def settle(self) -> None:
        """End-of-ingest resolution: a cache still missing batches cannot
        replay (partial replay reorders/double-counts), so it drops whole
        — freeing the HBM for whatever replay path the owner falls back
        to — and stays ``degraded``; a complete cache stays live."""
        if self.first_miss is not None:
            self.enabled = False
            self.degraded = True
            self.batches = []
            self.nbytes = self.chip_nbytes = 0
            self.first_miss = None
            self._ledger_sync()


def _spill_cleanup(f, path: str, named: list) -> None:
    """Module-level so ``weakref.finalize`` holds no reference to the
    cache object: close the fd (frees the unlinked inode) and, for a
    named (``keep_file=True``) spill an aborted fit left behind, unlink
    the file — the spill-dir hygiene guarantee."""
    try:
        f.close()
    except Exception:  # noqa: BLE001 - cleanup must never raise
        pass
    if named and named[0]:
        try:
            os.unlink(path)
        except OSError:
            pass


class DiskChunkCache:
    """Epoch-1 on-disk spill of padded chunks — the 1B-row overflow path.
    When a many-epoch streaming fit outgrows the HBM chunk cache, every
    later epoch would otherwise re-run the source, i.e. re-PARSE the CSV
    (at 1B rows x 100 epochs: hours of single-core parse per fit). This
    cache writes each already-padded chunk once, sequentially, on the
    prefetch thread during epoch 1 (overlapping device steps), and replays
    epochs 2+ at disk/page-cache bandwidth — the fixed-shape records need
    zero parsing, just a read + DMA.

    Format (version 2, self-describing): an ``OTPUSPL1`` magic + JSON
    header (shapes + dtypes, 8-byte padded), then fixed-size records —
    each a little-endian u32 live-row count, a u32 CRC32 of the record's
    payload bytes, then the fields' raw bytes in declaration order, every
    field 8-byte aligned. The CRC occupies what version 1 left as pad
    bytes, so the record layout (and every field offset) is IDENTICAL to
    v1 — v2 only gives meaning to four zero bytes. ``read`` verifies the
    CRC (resilience kill-switch-gated) and raises a descriptive
    ``SpillCorruptionError`` naming the record ordinal instead of
    decoding a truncated or bit-flipped record into a 100-epoch replay;
    ``finalize``/``attach`` likewise refuse a file whose size is not a
    whole number of records (a crash mid-write). ``dtypes`` defaults to
    all-f32 (the legacy layout); the cache-codec path stores bf16 / u8 /
    bit-packed-u32 fields directly, so spill I/O shrinks with the cache
    (io/codec.py). Version-1 files (same layout, no CRC) and headerless
    flat-f32 files (version 0) remain readable through :meth:`attach`,
    which sniffs the magic/header and skips verification for them.

    Single writer (the prefetch thread), then ``finalize()`` flips it to a
    read-only memmap. By default the file is unlinked the moment it is
    opened (POSIX anonymous-file idiom): fd and memmap stay valid and a
    crashed fit can never leak a dataset-sized spill on disk. Either way a
    ``weakref.finalize`` closes the fd (and unlinks a ``keep_file=True``
    spill) when the object dies without ``delete()`` — an aborted fit
    (exception mid-epoch-1) leaks neither the inode nor a named file."""

    MAGIC = b"OTPUSPL1"

    def __init__(self, dir_path: str, shapes: tuple, dtypes: tuple | None = None,
                 *, keep_file: bool = False):
        import json as _json
        import struct
        import weakref

        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = ([np.dtype(np.float32)] * len(self.shapes)
                       if dtypes is None
                       else [np.dtype(d) for d in dtypes])
        if len(self.dtypes) != len(self.shapes):
            raise ValueError("one dtype per field")
        self._init_layout()
        self._version = 2
        os.makedirs(dir_path, exist_ok=True)
        self.path = os.path.join(dir_path, f"spill_{uuid.uuid4().hex}.otpu")
        self._f: object | None = open(self.path, "w+b")
        header = _json.dumps({
            "version": 2,
            "shapes": self.shapes,
            "dtypes": [dt.name for dt in self.dtypes],
        }).encode()
        head = self.MAGIC + struct.pack("<I", len(header)) + header
        head += b"\0" * (-len(head) % 8)
        self._f.write(head)
        self._data_start = len(head)
        self._named = [bool(keep_file)]
        if not keep_file:
            os.unlink(self.path)
        self._finalizer = weakref.finalize(
            self, _spill_cleanup, self._f, self.path, self._named)
        self.n_valid: list[int] = []
        self._mm: np.memmap | None = None
        self._crc_ok: set[int] = set()   # record ordinals already verified

    def _init_layout(self) -> None:
        """Record layout: u32 n_valid + u32 payload CRC32 (v1 wrote pad
        zeros there — same offsets), then each field at the next 8-aligned
        offset — alignment keeps the read-side dtype views (and the DMA
        they feed) on natural boundaries."""
        self._field_bytes = [int(np.prod(s)) * dt.itemsize
                             for s, dt in zip(self.shapes, self.dtypes)]
        #: bytes of one record's ARRAYS — what a device_put of the record
        #: costs in HBM (record_bytes adds the n_valid word + alignment,
        #: an on-disk detail no memory gate should price)
        self.payload_bytes = sum(self._field_bytes)
        self._offsets, ofs = [], 8
        for nb in self._field_bytes:
            self._offsets.append(ofs)
            ofs += -(-nb // 8) * 8
        self.record_bytes = ofs

    @classmethod
    def attach(cls, path: str, shapes: tuple | None = None,
               dtypes: tuple | None = None) -> "DiskChunkCache":
        """Open an EXISTING spill file read-only. Version-1 files are
        self-describing; headerless files are the legacy flat-f32 format
        (version 0: records = fields' f32 bytes back to back, no stored
        live-row counts) and need the caller's ``shapes`` — their
        ``n_valid`` reads as the full padded row count."""
        import json as _json
        import struct

        import weakref

        obj = cls.__new__(cls)
        obj._f = open(path, "rb")
        obj.path = path
        obj._named = [False]       # attach never owns/removes the file
        obj._finalizer = weakref.finalize(
            obj, _spill_cleanup, obj._f, path, obj._named)
        obj._mm = None
        obj._crc_ok = set()
        magic = obj._f.read(len(cls.MAGIC))
        if magic == cls.MAGIC:
            (hlen,) = struct.unpack("<I", obj._f.read(4))
            layout = _json.loads(obj._f.read(hlen))
            obj.shapes = [tuple(s) for s in layout["shapes"]]
            # bfloat16 etc. resolve through ml_dtypes-registered names
            from orange3_spark_tpu.io.codec import BF16

            obj.dtypes = [np.dtype(BF16) if d == "bfloat16" else np.dtype(d)
                          for d in layout["dtypes"]]
            obj._init_layout()
            head = len(cls.MAGIC) + 4 + hlen
            obj._data_start = head + (-head % 8)
            obj._version = int(layout.get("version", 1))
        else:
            if shapes is None:
                raise ValueError(
                    "headerless (version-0) spill files need shapes=")
            obj.shapes = [tuple(s) for s in shapes]
            obj.dtypes = ([np.dtype(np.float32)] * len(obj.shapes)
                          if dtypes is None
                          else [np.dtype(d) for d in dtypes])
            obj._field_bytes = [int(np.prod(s)) * dt.itemsize
                                for s, dt in zip(obj.shapes, obj.dtypes)]
            obj.payload_bytes = sum(obj._field_bytes)
            obj._offsets, ofs = [], 0
            for nb in obj._field_bytes:
                obj._offsets.append(ofs)
                ofs += nb
            obj.record_bytes = ofs
            obj._data_start = 0
            obj._version = 0
        n_bytes = os.path.getsize(path) - obj._data_start
        n_rec = n_bytes // obj.record_bytes if obj.record_bytes else 0
        if obj._version >= 1 and obj.record_bytes \
                and n_bytes % obj.record_bytes:
            # a versioned file is written in whole records; a ragged tail
            # means the writer crashed mid-record (or the file was cut) —
            # refuse rather than silently drop/garble the final record.
            # Version-0 files keep the legacy lenient floor: they carry
            # no contract to check against.
            from orange3_spark_tpu.io.codec import SpillCorruptionError

            raise SpillCorruptionError(
                f"spill file {path!r} is truncated: {n_bytes} data bytes "
                f"is not a whole number of {obj.record_bytes}-byte "
                f"records — record {n_rec} (of {n_rec + 1} started) was "
                "cut mid-write"
            )
        obj._mm = np.memmap(obj._f, dtype=np.uint8, mode="r",
                            offset=obj._data_start,
                            shape=(n_rec, obj.record_bytes))
        if obj._version >= 1:
            import struct as _s

            obj.n_valid = [
                _s.unpack_from("<I", obj._mm[i, :4].tobytes())[0]
                for i in range(n_rec)
            ]
        else:
            obj.n_valid = [obj.shapes[0][0]] * n_rec
        return obj

    def append(self, arrays: tuple, n_valid: int) -> None:
        import struct
        import zlib

        arrs = []
        for a, shape, dt in zip(arrays, self.shapes, self.dtypes):
            a = np.ascontiguousarray(a, dtype=dt)
            if a.shape != shape:
                raise ValueError(f"spill record shape {a.shape} != {shape}")
            arrs.append(a)
        # one extra pass over the record's bytes BEFORE writing: the CRC
        # must land in the header word, and crc32 runs at memory speed —
        # noise against the disk write it guards
        crc = 0
        written = 8
        for a, ofs, nb in zip(arrs, self._offsets, self._field_bytes):
            pad = ofs - written
            if pad:
                crc = zlib.crc32(b"\0" * pad, crc)
            crc = zlib.crc32(a, crc)
            written = ofs + nb
        tail = self.record_bytes - written
        if tail:
            crc = zlib.crc32(b"\0" * tail, crc)
        # write-side fault injection (resilience/faults.py spill_corrupt):
        # the CRC above covers the TRUE bytes, so a flipped byte trips the
        # read-side check exactly like real silent corruption would
        from orange3_spark_tpu.resilience.faults import active_fault_spec

        spec = active_fault_spec()
        action = (spec.take_spill_corrupt(len(self.n_valid))
                  if spec is not None else None)
        rec_start = self._f.tell()
        self._f.write(struct.pack("<II", int(n_valid), crc & 0xFFFFFFFF))
        written = 8
        for a, ofs, nb in zip(arrs, self._offsets, self._field_bytes):
            pad = ofs - written
            if pad:
                self._f.write(b"\0" * pad)
            a.tofile(self._f)
            written = ofs + nb
        tail = self.record_bytes - written
        if tail:
            self._f.write(b"\0" * tail)
        if action == "flip":
            end = self._f.tell()
            pos = rec_start + self._offsets[0]
            self._f.seek(pos)
            b = self._f.read(1)
            self._f.seek(pos)
            self._f.write(bytes([b[0] ^ 0x01]))
            self._f.seek(end)
        elif action == "truncate":
            # a crash mid-write: only half the record reaches disk (the
            # bookkeeping below still counts it, as the dead writer's
            # in-memory state did) — caught by finalize/attach
            self._f.truncate(rec_start + self.record_bytes // 2)
            self._f.seek(rec_start + self.record_bytes // 2)
        self.n_valid.append(int(n_valid))

    @property
    def n_records(self) -> int:
        return len(self.n_valid)

    def finalize(self) -> None:
        if self._mm is None and self._f is not None and self.n_valid:
            self._f.flush()
            expected = (self._data_start
                        + self.n_records * self.record_bytes)
            actual = os.fstat(self._f.fileno()).st_size
            if actual != expected:
                # a record the writer believes it appended never fully
                # reached disk (crash/injection mid-write) — refuse to
                # replay a stream that is missing bytes
                from orange3_spark_tpu.io.codec import SpillCorruptionError

                raise SpillCorruptionError(
                    f"spill file {self.path!r} holds {actual} bytes where "
                    f"{expected} were written ({self.n_records} records x "
                    f"{self.record_bytes} B): record "
                    f"{max(0, (actual - self._data_start) // self.record_bytes)}"
                    " was truncated mid-write"
                )
            self._mm = np.memmap(self._f, dtype=np.uint8, mode="r",
                                 offset=self._data_start,
                                 shape=(self.n_records, self.record_bytes))

    def read(self, i: int) -> tuple[tuple, int]:
        """Record i as typed array views into the memmap (the device_put
        reads pages straight out of it — no intermediate host copy).
        Version-2 records verify their payload CRC32 first (skipped under
        ``OTPU_RESILIENCE=0`` and for pre-CRC versions): a mismatch
        raises ``SpillCorruptionError`` naming the record ordinal instead
        of decoding garbage into the replay."""
        rec = self._mm[i]
        if getattr(self, "_version", 0) >= 2 and i not in self._crc_ok:
            from orange3_spark_tpu.resilience.faults import (
                resilience_enabled,
            )

            if resilience_enabled():
                import struct
                import zlib

                stored = struct.unpack_from("<I", rec[4:8].tobytes())[0]
                computed = zlib.crc32(rec[8:]) & 0xFFFFFFFF
                if stored != computed:
                    from orange3_spark_tpu.io.codec import (
                        SpillCorruptionError,
                    )
                    from orange3_spark_tpu.utils.profiling import (
                        record_crc_failure,
                    )

                    record_crc_failure()
                    err = SpillCorruptionError(
                        f"spill record {i} of {self.n_records} in "
                        f"{self.path!r} failed CRC verification (stored "
                        f"0x{stored:08x} != computed 0x{computed:08x}): "
                        "the record was corrupted on disk. Delete the "
                        "spill and re-run the fit (OTPU_RESILIENCE=0 "
                        "skips verification)."
                    )
                    # black box (obs/flight.py): freeze the replay's
                    # state — spans, registry, knobs, stacks — at the
                    # corruption, before the raise unwinds the fit
                    from orange3_spark_tpu.obs.flight import auto_dump

                    auto_dump("spill_corruption", err)
                    raise err
                # the file is immutable after finalize(): verify each
                # record ONCE, not once per replay epoch — a 100-epoch
                # disk replay must not pay a 99x recurring CRC tax on a
                # path whose whole value is "read + DMA, no parse"
                self._crc_ok.add(i)
        out = []
        for shape, dt, ofs, nb in zip(self.shapes, self.dtypes,
                                      self._offsets, self._field_bytes):
            out.append(rec[ofs:ofs + nb].view(dt).reshape(shape))
        return tuple(out), self.n_valid[i]

    def delete(self) -> None:
        """Release the backing storage (closes the fd; a ``keep_file``
        spill's named file is unlinked here or, failing that, by the
        finalizer/atexit path)."""
        self._mm = None
        if self._f is not None:
            f, self._f = self._f, None
            if self._finalizer is not None:
                self._finalizer()   # close + unlink-if-named, exactly once
            else:
                f.close()


def warn_cache_overflow(cache_device_bytes: int, epochs_left: int,
                        detail: str = "") -> None:
    """THE cache-overflow warning — one wording for every streaming
    estimator (a silent 100x parse multiplier is the failure mode; a
    drifting copy-pasted message is how the warning itself rots)."""
    warnings.warn(
        f"device chunk cache overflowed cache_device_bytes="
        f"{cache_device_bytes}: each of the remaining {epochs_left} "
        f"epochs will re-run the source end to end (for a CSV source, a "
        f"full re-parse per epoch). {detail}".rstrip(),
        RuntimeWarning, stacklevel=3,
    )


def _rechunk(stream: Iterator[Chunk], rows: int) -> Iterator[tuple]:
    """Normalize a stream of (X, y[, w]) chunks of arbitrary sizes into
    batches of EXACTLY ``rows`` rows (the final one may be short) — source
    chunk sizes then never have to match the device batch size.

    Row weights must be non-negative (MLlib's weightCol contract); this is
    the single ingest choke point for every streaming estimator, so the
    check here is what makes "w == 0 means dead/padding row" a global
    invariant — the KMeans replay's pre-seed-batches-are-no-ops property
    (``_kmeans_replay_epochs``) depends on it (round-4 advisor finding)."""
    bx, by, bw = [], [], []
    have = 0
    any_y = any_w = False

    def flush(upto):
        nonlocal bx, by, bw, have
        X = np.concatenate(bx) if len(bx) > 1 else bx[0]
        y = (np.concatenate(by) if len(by) > 1 else by[0]) if any_y else None
        w = (np.concatenate(bw) if len(bw) > 1 else bw[0]) if any_w else None
        out = (X[:upto],
               None if y is None else y[:upto],
               None if w is None else w[:upto])
        rest_x, rest_y, rest_w = X[upto:], \
            None if y is None else y[upto:], None if w is None else w[upto:]
        bx = [rest_x] if len(rest_x) else []
        by = [rest_y] if (rest_y is not None and len(rest_y)) else []
        bw = [rest_w] if (rest_w is not None and len(rest_w)) else []
        have = len(rest_x)
        return out

    for chunk in stream:
        X, y, w = (chunk + (None, None))[:3]
        bx.append(X)
        if y is not None:
            by.append(y)
            any_y = True
        if w is not None:
            if len(w) and np.min(w) < 0:
                raise ValueError(
                    "negative row weights are not supported (weights mean "
                    "row multiplicity/importance; w == 0 marks dead rows)"
                )
            bw.append(w)
            any_w = True
        have += len(X)
        while have >= rows:
            yield flush(rows)
    if have:
        yield flush(have)


def _pad_chunk(X_np, y_np, w_np, pad_rows: int, n_features: int):
    """Pad a chunk to EXACTLY pad_rows (padding rows carry w=0); full chunks
    pass through without a copy. Shared by every streaming estimator."""
    n = X_np.shape[0]
    if n == pad_rows:
        Xp = np.ascontiguousarray(X_np, dtype=np.float32)
        yp = (np.zeros((n,), np.float32) if y_np is None
              else np.ascontiguousarray(y_np, dtype=np.float32))
        wp = (np.ones((n,), np.float32) if w_np is None
              else np.ascontiguousarray(w_np, dtype=np.float32))
    else:
        Xp = np.zeros((pad_rows, n_features), np.float32)
        Xp[:n] = X_np
        yp = np.zeros((pad_rows,), np.float32)
        if y_np is not None:
            yp[:n] = y_np
        wp = np.zeros((pad_rows,), np.float32)
        wp[:n] = 1.0 if w_np is None else w_np
    return Xp, yp, wp


# one module-level optimizer so the jitted step has a stable identity; the
# learning rate is applied by scaling adam's unit-lr updates with the traced
# ``lr`` argument (adam(lr) == lr * adam(1.0) updates)
_ADAM_UNIT = optax.adam(1.0)


@donating_jit(static_argnames=("loss_kind",), donate_argnums=(0, 1))
def _stream_step(theta, opt_state, X, y, w, reg, lr, *, loss_kind: str):
    # ONE loss implementation for in-memory and streaming fits: the row
    # losses come from _linear._make_objective (col_scale=1 — streaming
    # fits un-standardized, matching MLlib's online estimators)
    from orange3_spark_tpu.models._linear import EPS_TOTAL_WEIGHT, _make_objective

    objective = _make_objective(loss_kind, fit_intercept=True, compute_dtype=jnp.float32)
    sum_w = jnp.maximum(jnp.sum(w), EPS_TOTAL_WEIGHT)
    col_scale = jnp.ones((X.shape[1],), jnp.float32)

    def loss_fn(theta):
        return objective(theta, X, y, w, reg, sum_w, col_scale)

    loss, g = jax.value_and_grad(loss_fn)(theta)
    updates, opt_state = _ADAM_UNIT.update(g, opt_state, theta)
    updates = jax.tree.map(lambda u: lr * u, updates)
    return optax.apply_updates(theta, updates), opt_state, loss


@dataclasses.dataclass(frozen=True)
class StreamingKMeansParams(Params):
    k: int = 8
    epochs: int = 1
    chunk_rows: int = 1 << 18
    decay: float = 1.0           # MLlib StreamingKMeans decayFactor
    seed: int = 0
    # Defer epoch-1 updates into the fused replay (the hashed/linear
    # estimators' schedule): pass 0 seeds the centers and ingests into the
    # cache/spill with ZERO update dispatches, then the replay carries all
    # ``epochs`` passes. Identical to the default schedule except for
    # batches streamed BEFORE the first live chunk seeded the centers
    # ("pre-seed" batches): the default's epoch 1 skips their update while
    # its replay epochs step them (a no-op for centers, a decay tick for
    # counts); under defer every pass is a replay pass, so pre-seed
    # batches get p.epochs decay ticks instead of p.epochs - 1. Fits with
    # no pre-seed batches (any normal stream whose first chunk has a live
    # row) are bit-identical.
    defer_epoch1: bool = False
    # 'all': every replay pass in ONE scan dispatch; 'epoch': one
    # n_epochs=1 dispatch per pass (the hardware-robust granularity — see
    # StreamingLinearParams.replay_granularity).
    replay_granularity: str = "all"   # 'all' | 'epoch'
    # K replay epochs per scan dispatch under granularity 'epoch' — see
    # StreamingLinearParams.epochs_per_dispatch.
    epochs_per_dispatch: int = 1


@donating_jit(static_argnames=("loss_kind", "n_epochs"),
              donate_argnums=(0, 1))
def _stream_replay_epochs(theta, opt_state, Xs, ys, ws, reg, lr, *,
                          loss_kind: str, n_epochs: int):
    """Epochs 2+ over the HBM batch cache as ONE XLA program — an
    epoch-level scan around a batch-level scan, the dense twin of
    models/hashed_linear.py's fused replay (same rationale: replay cost
    becomes pure device time regardless of per-dispatch latency).
    Returns per-(epoch, batch) losses; [-1, -1] matches the loop path's
    final loss."""
    def body(carry, xs):
        theta, opt = carry
        X, y, w = xs
        theta, opt, loss = _stream_step(theta, opt, X, y, w, reg, lr,
                                        loss_kind=loss_kind)
        return (theta, opt), loss

    def epoch(carry, _):
        carry, losses = jax.lax.scan(body, carry, (Xs, ys, ws))
        return carry, losses

    (theta, opt_state), losses = jax.lax.scan(
        epoch, (theta, opt_state), None, length=n_epochs
    )
    return theta, opt_state, losses


def check_replay_granularity(value: str) -> None:
    """Reject typo'd enum values at fit entry: every granularity
    comparison is an exact string match, so 'epochs'/'Epoch' would
    silently behave as 'all' AND silently disable the defer+checkpointer
    composition the caller asked for."""
    if value not in ("all", "epoch"):
        raise ValueError(
            f"replay_granularity must be 'all' or 'epoch', got {value!r}"
        )


def resolve_epoch_checkpointing(params, checkpointer) -> int:
    """THE resolver for ``checkpoint_every_epochs`` (docs/resilience.md),
    shared by the linear and hashed estimators so the arming rule cannot
    drift: the epoch cadence is live only with a checkpointer, a positive
    K, and outside the ``OTPU_RESILIENCE=0`` kill-switch. Returns K (the
    cadence) or 0 (legacy per-step ``maybe_save`` cadence)."""
    from orange3_spark_tpu.resilience.faults import resilience_enabled

    k = getattr(params, "checkpoint_every_epochs", 0)
    return (k if (checkpointer is not None and k > 0
                  and resilience_enabled()) else 0)


def epoch_boundary_snapshot(checkpointer, every_epochs: int, epoch: int,
                            defer: bool, n_steps: int, resume_from: int,
                            snapshot, meta) -> None:
    """One epoch-boundary save decision for every streaming epoch path
    (live stream / HBM replay / disk replay) in every estimator — the
    fused-replay twin lives in ``run_epoch_replay``. A defer fit's
    step-free ingest pass contributes zero trained epochs; pure
    fast-forward epochs (``n_steps <= resume_from``) rewrite nothing."""
    trained = epoch + 1 - (1 if defer else 0)
    if (every_epochs and trained > 0 and trained % every_epochs == 0
            and n_steps > resume_from):
        checkpointer.save(n_steps, snapshot(), meta=meta)


def run_epoch_replay(n_replay, spe, n_steps, resume_from, checkpointer,
                     dispatch_epochs, snapshot, ckpt_meta,
                     epochs_per_dispatch: int = 1,
                     every_epochs: int = 0):
    """The per-epoch replay protocol shared by the streaming estimators
    (linear, hashed, kmeans): fast-forward whole checkpointed epochs
    without dispatching them, dispatch the remaining epochs in groups of
    ``epochs_per_dispatch`` scans (K=1 is the hardware-robust per-epoch
    granularity; larger K folds K epochs into ONE ``lax.scan`` dispatch —
    the dispatch-amortization lever between 'epoch' and 'all'), bound the
    in-flight dispatch queue (each dispatch pins the full chunk stack, so
    period=2 keeps one executing + one queued), and snapshot at epoch
    boundaries every ~``checkpointer.every_steps`` steps rounded to whole
    epochs. Groups never cross a snapshot boundary — they are clamped so
    checkpoint cadence is IDENTICAL at every K (resume compatibility: a
    snapshot written at K=4 resumes correctly under K=1 and vice versa).
    ONE implementation so the three estimators' checkpoint/resume
    semantics cannot drift.

    ``dispatch_epochs(k)`` runs k epochs in one dispatch and returns the
    value to block on; ``snapshot()`` returns the state dict to
    checkpoint. Returns ``(n_steps, last, n_dispatched)`` — ``last`` is
    None when every epoch was fast-forwarded (resume-at-completion).

    ``every_epochs``: explicit epoch-cadence snapshots (the params'
    ``checkpoint_every_epochs`` knob, docs/resilience.md) — overrides the
    every_steps-derived cadence when > 0."""
    save_every = ((every_epochs or max(1, checkpointer.every_steps // spe))
                  if checkpointer is not None else 0)
    group = max(1, int(epochs_per_dispatch))
    last = None
    n_disp = 0
    rep = 0
    while rep < n_replay:
        if n_steps + spe <= resume_from:
            n_steps += spe          # checkpointed epoch: skip, no dispatch
            rep += 1
            continue
        k = min(group, n_replay - rep)
        if save_every:
            # clamp to the next snapshot boundary: snapshots land BETWEEN
            # dispatches, so a group spanning one would silently skip it
            k = min(k, save_every - (rep % save_every))
        last = dispatch_epochs(k)
        n_steps += k * spe
        rep += k
        n_disp += 1
        bound_dispatch(n_disp, last, period=2)
        if save_every and rep % save_every == 0:
            checkpointer.save(n_steps, snapshot(), meta=ckpt_meta)
    return n_steps, last, n_disp


@donating_jit(static_argnames=("k", "n_epochs"), donate_argnums=(0, 1))
def _kmeans_replay_epochs(centers, counts, Xs, ws, decay, *,
                          k: int, n_epochs: int):
    """Replay epochs over the HBM batch cache as ONE XLA program — the
    KMeans twin of ``_stream_replay_epochs`` (epoch-level scan around a
    batch-level scan; replay cost becomes pure device time regardless of
    per-dispatch latency). Pre-seed batches ride the stack like any other:
    their all-zero weights (no positive weight by the pre-seed definition,
    no negative weight by ``_rechunk``'s ingest validation) make the
    update a centers no-op + a counts decay tick, exactly what the
    per-chunk replay loop does to them. Returns per-(epoch, batch) costs."""
    def body(carry, xs):
        centers, counts = carry
        X, w = xs
        centers, counts, cost = _kmeans_stream_step(
            centers, counts, X, w, decay, k=k)
        return (centers, counts), cost

    def epoch(carry, _):
        carry, costs = jax.lax.scan(body, carry, (Xs, ws))
        return carry, costs

    (centers, counts), costs = jax.lax.scan(
        epoch, (centers, counts), None, length=n_epochs
    )
    return centers, counts, costs


@donating_jit(static_argnames=("k",), donate_argnums=(0, 1))
def _kmeans_stream_step(centers, counts, X, w, decay, *, k: int):
    """One aggregated mini-batch update (Sculley 2010 / MLlib StreamingKMeans):
    per-center sums from this chunk fold into running counts with decay."""
    from orange3_spark_tpu.models.kmeans import _assign

    assign, cost = _assign(X, centers, w)
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32) * w[:, None]
    n_i = jnp.sum(onehot, axis=0)                       # [k]
    sum_i = onehot.T @ X                                # [k, d] MXU
    counts = decay * counts + n_i
    centers = jnp.where(
        n_i[:, None] > 0,
        centers + (sum_i - n_i[:, None] * centers) / jnp.maximum(counts, 1e-12)[:, None],
        centers,
    )
    return centers, counts, cost


class StreamingKMeans(Estimator):
    """Out-of-core KMeans over a chunk stream (the NYC-Taxi-1B path) —
    MLlib's StreamingKMeans role: aggregated mini-batch center updates with
    a decay factor, returning the standard KMeansModel."""

    ParamsCls = StreamingKMeansParams
    params: StreamingKMeansParams

    def _fit(self, table):
        X, _, W = table.to_numpy()
        return self.fit_stream(
            array_chunk_source(X, None, W, chunk_rows=self.params.chunk_rows),
            n_features=X.shape[1], session=table.session,
        )

    @traced("fit", model="streaming_kmeans")
    def fit_stream(self, source: Callable[[], Iterator[Chunk]], *,
                   n_features: int, session: TpuSession | None = None,
                   cache_device: bool = False,
                   cache_device_bytes: int = 8 << 30,
                   cache_spill_dir: str | None = None):
        """cache_device: retain epoch-1 device batches in HBM and replay
        them for epochs 2+ (skips host re-parse/re-DMA; degrades past
        ``cache_device_bytes`` — same contract as the other streaming
        estimators). cache_spill_dir: epoch-1 disk spill of the padded
        chunks; on cache overflow (the Taxi-1B regime, BASELINE config 5)
        epochs 2+ replay records at disk bandwidth instead of re-parsing
        the source."""
        from orange3_spark_tpu.models.kmeans import KMeansModel, KMeansParams

        p = self.params
        check_replay_granularity(p.replay_granularity)
        report = (RunReport("fit_stream", estimator=type(self).__name__,
                            k=p.k, epochs=p.epochs)
                  if obs_enabled() else None)
        # goodput accountant (obs/prof.py): wall decomposition fed by
        # the dispatch/prefetch chokepoints; None under OTPU_PROF=0
        acc = prof.begin_fit()
        from orange3_spark_tpu.resilience.retry import resilient_source

        source = resilient_source(source)
        session = session or TpuSession.active()
        pad_rows = session.pad_rows(p.chunk_rows)
        row_sh = session.row_sharding
        vec_sh = session.vector_sharding
        rng = np.random.default_rng(p.seed)
        centers = None
        counts = jnp.zeros((p.k,), jnp.float32)
        decay = jnp.float32(p.decay)
        n_steps = 0
        # defer-epoch-1 (see StreamingKMeansParams.defer_epoch1): pass 0
        # seeds + ingests only; the loop runs one extra iteration and the
        # replay carries all p.epochs update passes
        defer = p.defer_epoch1 and cache_device and p.epochs > 0
        n_replay = p.epochs - 1 + (1 if defer else 0)
        cache = _DeviceCache(cache_device and (p.epochs > 1 or defer),
                             cache_device_bytes)
        spill: DiskChunkCache | None = None
        if (cache_device and cache_spill_dir is not None
                and (p.epochs > 1 or defer)):
            spill = DiskChunkCache(
                cache_spill_dir, ((pad_rows, n_features), (pad_rows,))
            )
        use_disk = False
        for epoch in span_iter("epoch", range(p.epochs + (1 if defer else 0))):
            if epoch > 0 and (cache.enabled or use_disk):
                if centers is None:
                    raise ValueError("stream produced no live rows")
                # pre_seed batches were SKIPPED in epoch 1 (streamed before
                # seeding) but streaming epochs 2+ step them (centers exist
                # by then) — replay must step them too for exact parity
                if cache.enabled:
                    batches = iter(cache.batches)
                else:
                    def _rec(i):
                        arrs, _n = spill.read(i)
                        return (put_sharded(np.asarray(arrs[0]), row_sh),
                                put_sharded(np.asarray(arrs[1]), vec_sh),
                                None)

                    # read+DMA of record t+1 overlaps the device step on
                    # record t — same overlap engine as the live stream
                    batches = prefetch_map(_rec, iter(range(spill.n_records)),
                                           depth=2)
                for Xd, wd, _pre_seed in batches:
                    with span("chunk", n_steps):
                        centers, counts, cost = _kmeans_stream_step(
                            centers, counts, Xd, wd, decay, k=p.k
                        )
                        n_steps += 1
                        bound_dispatch(n_steps, cost)
                check_finite_training(None, centers, epoch=epoch,
                                      chunk=n_steps,
                                      estimator="StreamingKMeans")
                continue
            for X_np, _, w_np in _rechunk(source(), pad_rows):
                n = X_np.shape[0]
                pre_seed = False
                if centers is None:
                    # kmeans++ seeding on (a capped sample of) the first chunk
                    from orange3_spark_tpu.models.kmeans import kmeanspp_seed

                    live = (np.arange(n) if w_np is None
                            else np.flatnonzero(np.asarray(w_np) > 0))
                    if len(live) < 1:
                        # no live rows to seed from: the batch is skipped
                        # THIS epoch but must still enter the cache/spill —
                        # streaming epochs 2+ would step it
                        pre_seed = True
                        if not cache.enabled and spill is None:
                            continue  # pure streaming: skip pad/DMA too
                    else:
                        if len(live) > 8192:
                            live = rng.choice(live, 8192, replace=False)
                        centers = jax.device_put(
                            kmeanspp_seed(np.asarray(X_np, np.float32)[live],
                                          p.k, rng),
                            session.replicated,
                        )
                Xp, _, wp = _pad_chunk(X_np, None, w_np, pad_rows, n_features)
                if epoch == 0 and spill is not None:
                    spill.append((Xp, wp), n)
                Xd = put_sharded(Xp, row_sh)
                wd = put_sharded(wp, vec_sh)
                if epoch == 0:
                    cache.offer((Xd, wd, pre_seed))
                if pre_seed or (epoch == 0 and defer):
                    continue        # defer: ingest-only pass, no update
                with span("chunk", n_steps):
                    centers, counts, cost = _kmeans_stream_step(
                        centers, counts, Xd, wd, decay, k=p.k
                    )
                    n_steps += 1
                    bound_dispatch(n_steps, cost)  # queue cap (dispatch.py)
            if epoch == 0:
                if spill is not None:
                    spill.finalize()
                # no excludable tail here: an over-budget offer already
                # latched the degrade at the overflow point (the hashed
                # estimator's holdout un-latch doesn't apply)
                if cache.degraded and (p.epochs > 1 or defer):
                    use_disk = spill is not None and spill.n_records > 0
                    if not use_disk:
                        warn_cache_overflow(cache_device_bytes, n_replay)
            if (epoch == 0 and n_replay > 0 and cache.enabled
                    and cache.batches and centers is not None
                    and 2 * cache.nbytes <= cache_device_bytes):
                # remaining update passes as scan program(s) — same
                # transient stack + half-budget rule as the other
                # streaming estimators' fused replay
                spe = len(cache.batches)
                Xs = jnp.stack([b[0] for b in cache.batches])
                ws = jnp.stack([b[1] for b in cache.batches])
                if p.replay_granularity == "epoch":
                    def _disp_km(n_ep):
                        nonlocal centers, counts
                        centers, counts, _c = _kmeans_replay_epochs(
                            centers, counts, Xs, ws, decay, k=p.k,
                            n_epochs=n_ep,
                        )
                        return centers

                    n_steps, _, _ = run_epoch_replay(
                        n_replay, spe, n_steps, 0, None, _disp_km,
                        None, None,
                        epochs_per_dispatch=p.epochs_per_dispatch,
                    )
                else:
                    centers, counts, _costs = _kmeans_replay_epochs(
                        centers, counts, Xs, ws, decay, k=p.k,
                        n_epochs=n_replay,
                    )
                    count_dispatch()   # one-shot fused scan: no loop ticks
                    n_steps += n_replay * spe
                del Xs, ws
                break
        if spill is not None:
            spill.delete()
        if centers is None:
            raise ValueError("stream produced no live rows")
        # streaming epoch-1 and fused-replay paths end here: one final
        # non-finite guard (typed divergence instead of NaN centers)
        check_finite_training(None, centers, epoch=p.epochs - 1,
                              chunk=n_steps, final=True,
                              estimator="StreamingKMeans")
        model = KMeansModel(KMeansParams(k=p.k), centers)
        model.n_iter_ = n_steps
        prof.attach_fit_report(report, acc, cache_key=cache.ledger_key)
        if report is not None:
            report.stage_times["n_steps"] = n_steps
            model.run_report_ = report.finish()
        # training_cost_ stays None: a per-chunk cost is NOT the full-dataset
        # trainingCost the attribute means — use model.compute_cost(table)
        return model


class StreamingLinearEstimator(Estimator):
    """Minibatch-over-chunks trainer producing the standard model classes.

    fit_stream(source, n_features) -> LogisticRegressionModel /
    LinearRegressionModel / LinearSVCModel depending on ``loss``.
    """

    ParamsCls = StreamingLinearParams
    params: StreamingLinearParams

    def _fit(self, table):  # Estimator protocol: in-memory table fallback
        from orange3_spark_tpu.models.base import infer_class_values

        X, Y, W = table.to_numpy()
        y = Y[:, 0] if Y is not None else None
        class_values = (
            infer_class_values(table) if self.params.loss == "logistic" else None
        )
        return self.fit_stream(
            array_chunk_source(X, y, W, chunk_rows=self.params.chunk_rows),
            n_features=X.shape[1],
            session=table.session,
            class_values=class_values,
        )

    @traced("fit", model="streaming_linear")
    def fit_stream(self, source: Callable[[], Iterator[Chunk]], *,
                   n_features: int, session: TpuSession | None = None,
                   class_values: tuple | None = None, checkpointer=None,
                   cache_device: bool = False,
                   cache_device_bytes: int = 8 << 30,
                   cache_spill_dir: str | None = None):
        """checkpointer: optional utils.fault.StreamCheckpointer — snapshots
        (theta, opt_state) every N steps and, if a snapshot exists at start,
        resumes from it (skipping already-consumed batches), so a killed fit
        restarted with the same source/params lands on identical numbers.

        cache_device: retain device-put batches in HBM during epoch 1 and
        replay them for epochs 2+ — skips the host re-parse/re-DMA of every
        later epoch (the hashed estimator's ``cache_device``, per-chunk
        replay form). Degrades if the stream outgrows
        ``cache_device_bytes``: with ``cache_spill_dir`` set, epochs 2+
        replay padded records off the epoch-1 disk spill (read + DMA, no
        re-parse); without it, every epoch re-runs the source, loudly."""
        p = self.params
        check_replay_granularity(p.replay_granularity)
        # the run report rides the OTPU_OBS kill-switch (its two counter
        # snapshots are this path's only per-fit obs cost)
        report = (RunReport("fit_stream", estimator=type(self).__name__,
                            loss=p.loss, epochs=p.epochs)
                  if obs_enabled() else None)
        # goodput accountant (obs/prof.py): wall decomposition fed by
        # the dispatch/prefetch chokepoints; None under OTPU_PROF=0
        acc = prof.begin_fit()
        from orange3_spark_tpu.resilience.retry import resilient_source

        # THE source chokepoint (docs/resilience.md): fault injection +
        # bounded transient-read retries wrap every epoch's stream
        source = resilient_source(source)
        session = session or TpuSession.active()
        if p.loss == "logistic":
            if class_values is not None:
                k = max(2, len(class_values))
                # keep coef width and label list consistent (transform builds
                # one probability column per class value)
                if len(class_values) < k:
                    class_values = tuple(class_values) + tuple(
                        f"__class_{i}__" for i in range(len(class_values), k)
                    )
            else:
                k = p.n_classes
        else:
            k = 1
        theta = {
            "coef": jnp.zeros((n_features, k), jnp.float32),
            "intercept": jnp.zeros((k,), jnp.float32),
        }
        opt_state = _ADAM_UNIT.init(theta)
        resume_from = 0
        ckpt_meta = {"params": p.to_dict(), "n_features": n_features, "k": k}
        # epoch-cadence snapshots (checkpoint_every_epochs, the
        # crash-resume contract): when armed, per-step maybe_save is
        # replaced by atomic saves at epoch boundaries every K epochs.
        # Inert under the OTPU_RESILIENCE=0 kill-switch (legacy cadence).
        ckpt_epochs = resolve_epoch_checkpointing(p, checkpointer)
        if checkpointer is not None:
            step0, saved = checkpointer.load(expect_meta=ckpt_meta)
            if saved is not None:
                theta = jax.tree.map(jnp.asarray, saved["theta"])
                opt_state = jax.tree.map(
                    lambda tmpl, v: jnp.asarray(v) if isinstance(
                        tmpl, (jax.Array, np.ndarray)) else v,
                    opt_state, saved["opt_state"],
                )
                resume_from = step0
        pad_rows = session.pad_rows(p.chunk_rows)
        row_sh = session.row_sharding
        vec_sh = session.vector_sharding
        reg = jnp.float32(p.reg_param)
        lr = jnp.float32(p.step_size)
        n_steps = 0
        last_loss = None
        # defer-epoch-1 (see StreamingLinearParams.defer_epoch1): pass 0 is
        # ingest-only and the loop below runs one extra iteration so the
        # replay carries all p.epochs training passes. Checkpointing
        # composes only at epoch granularity (same contract and resume
        # semantics as models/hashed_linear.py fit_stream).
        ckpt_epoch_ok = p.replay_granularity == "epoch"
        defer = (p.defer_epoch1 and cache_device and p.epochs > 0
                 and (checkpointer is None or ckpt_epoch_ok)
                 and (resume_from == 0 or ckpt_epoch_ok))
        n_replay = p.epochs - 1 + (1 if defer else 0)
        cache = _DeviceCache(cache_device and (p.epochs > 1 or defer),
                             cache_device_bytes)
        # cache precision (io/codec.py), resolved once at fit entry: bf16
        # halves the cached/spilled/DMA'd X bytes; the step widens it back
        # via the objective's astype (in-scan decode). 'f32' = the legacy
        # path, bit-for-bit; 'packed' has no integer columns to pack here
        # and behaves as bf16.
        from orange3_spark_tpu.io.codec import BF16, resolve_cache_dtype

        cache_bf16 = resolve_cache_dtype(p.cache_dtype, session) != "f32"
        x_store = np.dtype(BF16) if cache_bf16 else np.dtype(np.float32)
        spill: DiskChunkCache | None = None
        if (cache_device and cache_spill_dir is not None
                and (p.epochs > 1 or defer)):
            spill = DiskChunkCache(
                cache_spill_dir,
                ((pad_rows, n_features), (pad_rows,), (pad_rows,)),
                (x_store, np.float32, np.float32),
            )
        use_disk = False

        def run_step(Xd, yd, wd):
            nonlocal theta, opt_state, n_steps, last_loss
            with span("chunk", n_steps):
                theta, opt_state, loss = _stream_step(
                    theta, opt_state, Xd, yd, wd, reg, lr,
                    loss_kind=p.loss,
                )
                n_steps += 1
                last_loss = loss
                bound_dispatch(n_steps, loss)  # utils/dispatch.py: queue cap
            if checkpointer is not None and not ckpt_epochs:
                checkpointer.maybe_save(
                    n_steps, {"theta": theta, "opt_state": opt_state},
                    meta=ckpt_meta,
                )

        def epoch_snapshot(epoch):
            # non-finite guard (resilience/numerics.py) BEFORE the save:
            # a divergent epoch must raise typed, never checkpoint NaN
            # state a resume would silently continue from
            check_finite_training(last_loss, theta, epoch=epoch,
                                  chunk=n_steps,
                                  estimator="StreamingLinearEstimator")
            # one shared save decision (epoch_boundary_snapshot) — called
            # at the end of every trained epoch, whatever path ran it
            epoch_boundary_snapshot(
                checkpointer, ckpt_epochs, epoch, defer, n_steps,
                resume_from,
                lambda: {"theta": theta, "opt_state": opt_state},
                ckpt_meta,
            )

        for epoch in span_iter("epoch", range(p.epochs + (1 if defer else 0))):
            if epoch > 0 and cache.enabled:
                # pure-HBM epoch: replay cached batches, zero host work
                for Xd, yd, wd in cache.batches:
                    if n_steps < resume_from:
                        n_steps += 1
                        continue
                    run_step(Xd, yd, wd)
                epoch_snapshot(epoch)
                continue
            if epoch > 0 and use_disk:
                # overflow epoch off the disk spill: read + DMA, no parse.
                # Checkpoint fast-forward skips whole records WITHOUT
                # reading them; the rest prefetch-overlap the device steps
                skip = min(max(resume_from - n_steps, 0), spill.n_records)
                n_steps += skip

                def _rec(i):
                    arrs, _n = spill.read(i)
                    return (put_sharded(np.asarray(arrs[0]), row_sh),
                            put_sharded(np.asarray(arrs[1]), vec_sh),
                            put_sharded(np.asarray(arrs[2]), vec_sh))

                for Xd, yd, wd in prefetch_map(
                        _rec, iter(range(skip, spill.n_records)), depth=2):
                    run_step(Xd, yd, wd)
                epoch_snapshot(epoch)
                continue
            for X_np, y_np, w_np in _rechunk(source(), pad_rows):
                if n_steps < resume_from and not (
                        epoch == 0 and (cache.enabled or spill is not None
                                        or defer)):
                    # checkpoint fast-forward BEFORE any pad/DMA work —
                    # except while building the cache/spill, whose batches
                    # must be retained even when their step is skipped,
                    # and except a defer ingest pass: it contributes ZERO
                    # steps, so counting its chunks here would corrupt the
                    # resume offset (even after a mid-ingest cache
                    # overflow, when cache.enabled has flipped off — this
                    # estimator has no excludable tail, so a miss latches
                    # at the offer exactly as before)
                    n_steps += 1
                    continue
                # every device batch is EXACTLY pad_rows tall (last one padded
                # with w=0): one compiled _stream_step serves the whole stream
                if p.loss == "logistic" and y_np is not None and len(y_np):
                    y_max = int(y_np.max())
                    if y_max >= k:
                        raise ValueError(
                            f"label {y_max} out of range for k={k} classes; "
                            "set n_classes= (or pass class_values=) to the "
                            "true class count"
                        )
                Xp, yp, wp = _pad_chunk(X_np, y_np, w_np, pad_rows, n_features)
                if cache_bf16:
                    Xp = Xp.astype(x_store)   # encode once: spill AND HBM
                if epoch == 0 and spill is not None:
                    # live PRE-pad rows (the DiskChunkCache contract);
                    # replay neutralizes padding via w=0 either way
                    spill.append((Xp, yp, wp), X_np.shape[0])
                Xd = put_sharded(Xp, row_sh)
                yd = put_sharded(yp, vec_sh)
                wd = put_sharded(wp, vec_sh)
                if epoch == 0:
                    cache.offer((Xd, yd, wd))
                if epoch == 0 and defer:
                    continue        # ingest-only pass: no step dispatch
                if n_steps < resume_from:
                    n_steps += 1  # fast-forward past checkpointed batches
                    continue
                run_step(Xd, yd, wd)
            epoch_snapshot(epoch)
            if epoch == 0:
                if spill is not None:
                    spill.finalize()
                # no excludable tail here: an over-budget offer already
                # latched the degrade at the overflow point (the hashed
                # estimator's holdout un-latch doesn't apply)
                if cache.degraded and (p.epochs > 1 or defer):
                    use_disk = spill is not None and spill.n_records > 0
                    if not use_disk:
                        warn_cache_overflow(cache_device_bytes, n_replay)
            if (epoch == 0 and n_replay > 0 and cache.enabled
                    and cache.batches
                    and ((checkpointer is None and resume_from == 0)
                         or ckpt_epoch_ok)
                    and 2 * cache.nbytes <= cache_device_bytes
                    # off-boundary snapshots (written by a run whose
                    # fusion gate differed) resume via the per-batch
                    # replay, which skips at step grain
                    and resume_from % len(cache.batches) == 0):
                # remaining epochs as scan program(s): ONE dispatch with
                # granularity 'all', one per epoch with 'epoch' (the
                # transient batch stack is a second device copy — same
                # half-budget rule as the hashed estimator). Per-step
                # checkpointered fits keep the per-batch loop for
                # step-granular snapshots; 'epoch' fits snapshot at epoch
                # boundaries between dispatches (run_epoch_replay).
                spe = len(cache.batches)
                if n_steps + n_replay * spe <= resume_from:
                    # the snapshot already covers every replay epoch —
                    # don't build the (potentially GBs) transient stack
                    # just to skip it
                    n_steps += n_replay * spe
                    break
                stacks = tuple(
                    jnp.stack([b[i] for b in cache.batches])
                    for i in range(3)
                )
                if p.replay_granularity == "epoch":
                    def _disp_lin(n_ep):
                        nonlocal theta, opt_state
                        theta, opt_state, losses = _stream_replay_epochs(
                            theta, opt_state, *stacks, reg, lr,
                            loss_kind=p.loss, n_epochs=n_ep,
                        )
                        return losses[-1, -1]

                    n_steps, last, _ = run_epoch_replay(
                        n_replay, spe, n_steps, resume_from, checkpointer,
                        _disp_lin,
                        lambda: {"theta": theta, "opt_state": opt_state},
                        ckpt_meta,
                        epochs_per_dispatch=p.epochs_per_dispatch,
                        every_epochs=ckpt_epochs,
                    )
                    if last is not None:
                        last_loss = last
                else:
                    theta, opt_state, losses = _stream_replay_epochs(
                        theta, opt_state, *stacks, reg, lr,
                        loss_kind=p.loss, n_epochs=n_replay,
                    )
                    count_dispatch()   # one-shot fused scan: no loop ticks
                    n_steps += n_replay * spe
                    last_loss = losses[-1, -1]
                del stacks
                break
        if spill is not None:
            spill.delete()
        # the fused-replay paths break out before another epoch_snapshot:
        # one final guard (loss AND theta — a last-step divergence only
        # shows in theta) so a replay that diverged still raises typed
        check_finite_training(last_loss, theta, epoch=p.epochs - 1,
                              chunk=n_steps, final=True,
                              estimator="StreamingLinearEstimator")
        model = self._wrap_model(theta, k, class_values)
        model.n_steps_ = n_steps
        model.final_loss_ = float(last_loss) if last_loss is not None else None
        prof.attach_fit_report(report, acc, cache_key=cache.ledger_key)
        if report is not None:
            report.stage_times["n_steps"] = n_steps
            report.stage_times["replay_source"] = (
                "disk" if use_disk else "hbm" if cache.enabled else "stream")
            model.run_report_ = report.finish()
        if checkpointer is not None:
            # a finished fit's snapshot must not fast-forward a FUTURE fit
            # (same path, same config, different data) past its early batches
            checkpointer.delete()
        return model

    def _wrap_model(self, theta, k, class_values=None):
        p = self.params
        if p.loss == "logistic":
            from orange3_spark_tpu.models.logistic_regression import (
                LogisticRegressionModel,
                LogisticRegressionParams,
            )

            return LogisticRegressionModel(
                LogisticRegressionParams(), theta["coef"], theta["intercept"],
                class_values or tuple(str(i) for i in range(k)),
            )
        if p.loss == "squared":
            from orange3_spark_tpu.models.linear_regression import (
                LinearRegressionModel,
                LinearRegressionParams,
            )

            return LinearRegressionModel(
                LinearRegressionParams(), theta["coef"][:, 0],
                theta["intercept"][0],
            )
        from orange3_spark_tpu.models.linear_svc import (
            LinearSVCModel,
            LinearSVCParams,
        )

        return LinearSVCModel(
            LinearSVCParams(), theta["coef"], theta["intercept"],
            class_values or ("0", "1"),
        )
