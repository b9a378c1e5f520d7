"""chip_smoke.py — the quickest proof that the main path still runs on the chip.

One process, no arguments: drives the system through the entry points a user
calls (``fit_stream`` / ``ServingContext`` / ``Estimator.fit`` / the workflow
graph) at the published widths of BASELINE configs 2, 3 and 5, checks each
phase's RESULT, and prints one JSON line per phase. Any exception ends the run
non-zero. The LAST line of stdout, only on success, is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Anything but a TPU is exit code != 0 before any work (no CPU fall-back).

    python chip_smoke.py                # one chip: fit, serve, trees, canvas
    python chip_smoke.py --four-chips   # four chips: the sharded fits only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

``--rehearse`` only shrinks sizes and skips the platform assertion; the same
code runs. One process per chip: nothing here spawns a process that needs it.
Data comes from ``--seed``; the CSV and the compile cache stay inside the
checkout (``.bench_data/``, ``.jax_cache/`` unless JAX_COMPILATION_CACHE_DIR).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    fit_rows: int        # Criteo-shaped CSV rows (label + 13 dense + 26 cat)
    n_dims: int          # hashed table rows
    chunk_rows: int
    epochs: int
    auc_floor: float     # CPU AUC at this seed/size/epochs, less 0.01
    serve_rows: tuple    # request sizes
    ladder: tuple        # (min_bucket, max_bucket)
    tree_rows: int       # HIGGS-shaped rows x 28
    tree_auc_floor: float
    canvas_rows: int     # taxi-shaped rows x 8


# BASELINE config 2 / 3 / 5 at their published widths. auc_floor: the same
# seed (0), size and epochs on the CPU here gave holdout AUC 0.73018
# (PR 22, CPU run of phase_fit at full size) — the floor is that less 0.01,
# a constant, never computed on the chip.
REAL = Sizes(fit_rows=2_097_152, n_dims=1 << 22, chunk_rows=1 << 18, epochs=8,
             auc_floor=0.7202,
             serve_rows=(1, 33, 256, 4096, 100_000), ladder=(64, 1 << 17),
             tree_rows=1_048_576, tree_auc_floor=0.65,
             canvas_rows=2_097_152)
REHEARSE = Sizes(fit_rows=16_384, n_dims=1 << 16, chunk_rows=2048, epochs=8,
                 auc_floor=0.55,
                 serve_rows=(1, 33, 256, 1024, 2000), ladder=(64, 2048),
                 tree_rows=8192, tree_auc_floor=0.65,
                 canvas_rows=16_384)

N_DENSE, N_CAT, HOLDOUT_CHUNKS = 13, 26, 2
#: served vs unserved float tolerance (docs/serving.md §1): 2 float32 ulp at
#: the scale max(|x|, 1)
SERVED_TOL = 2 * float(np.finfo(np.float32).eps)
#: sharded (4,1)/(2,2) vs one-device theta: the cross-device gradient sum
#: reorders float adds over 48 adagrad steps
SHARDED_ATOL = 1e-4


class Clock:
    """Seconds of one phase split into generate / compile / run. Compile
    seconds are jax.monitoring's compile-path durations (trace, lower, and
    backend compile — which includes a persistent-cache retrieval); run is
    the rest of the wall. Callers ``block_until_ready`` before every read."""

    compile_s = 0.0
    cache_hits = 0

    @classmethod
    def install(cls) -> None:
        import jax

        def on_duration(key: str, dur: float, **_kw) -> None:
            if key.startswith("/jax/core/compile/"):
                cls.compile_s += dur
            elif key.endswith("cache_retrieval_time_sec"):
                cls.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __init__(self):
        from orange3_spark_tpu.utils.profiling import xla_compile_count

        self._count = xla_compile_count
        self.t0 = time.perf_counter()
        self.c0, self.h0 = Clock.compile_s, Clock.cache_hits
        self.n0 = xla_compile_count()
        self.generate_s = 0.0

    def generated(self) -> None:
        """Everything since the phase began was data generation."""
        self.generate_s = time.perf_counter() - self.t0

    def compiles(self) -> int:
        return self._count() - self.n0

    def fields(self) -> dict:
        wall = time.perf_counter() - self.t0
        compile_s = Clock.compile_s - self.c0
        return {"seconds": {
            "generate": round(self.generate_s, 3),
            "compile": round(compile_s, 3),
            "run": round(max(wall - self.generate_s - compile_s, 0.0), 3),
            "wall": round(wall, 3)},
            "xla_compiles": self.compiles(),
            "compile_cache_hits": Clock.cache_hits - self.h0}


MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")


def memory(devices) -> dict:
    """Per device, from ``memory_stats()`` (None where the backend reports
    none, as the CPU does). On the v5e runtime ``peak_bytes_in_use`` counts
    live buffers only; a program's temp is carved from a reserved region
    whose high-water mark is ``peak_bytes_reserved`` (PR 22, measured: a
    program with a 5.37 GB temp moved only the latter). The HBM a phase
    needed is the sum of the two."""
    stats = [d.memory_stats() or {} for d in devices]
    return {k: [s.get(k) for s in stats] for k in MEMORY_KEYS}


def emit(phase: str, clock: Clock, devices, **fields) -> None:
    print(json.dumps({"phase": phase, **fields, **clock.fields(),
                      **memory(devices)}), flush=True)


def make_estimator(sz: Sizes):
    import bench
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    # bench.py's Criteo configuration; everything not named is the library
    # default — replay_granularity, fused_replay, sparse_lowering, and the
    # session-resolved cache codec ('auto')
    return StreamingHashedLinearEstimator(
        n_dims=sz.n_dims, n_dense=N_DENSE, n_cat=N_CAT, epochs=sz.epochs,
        chunk_rows=sz.chunk_rows, label_in_chunk=True,
        step_size=bench.STEP_SIZE, reg_param=bench.REG_PARAM,
        optim_update="sparse_adagrad", cache_dtype="auto")


def fit_criteo(sz: Sizes, path: str, session=None):
    """-> (model, fit_stream's own stage seconds: parse/h2d/encode on the
    prefetch thread, epoch 1 and the fused replay on the main one)."""
    import jax

    from orange3_spark_tpu.io.streaming import csv_raw_chunk_source

    stages: dict = {}
    model = make_estimator(sz).fit_stream(
        csv_raw_chunk_source(path, chunk_rows=sz.chunk_rows),
        session=session, cache_device=True, holdout_chunks=HOLDOUT_CHUNKS,
        stage_times=stages)
    jax.block_until_ready(model.theta)
    return model, {k: np.round(v, 3).tolist() for k, v in stages.items()
                   if k.endswith("_s")}


# ------------------------------------------------------------------- fit
def phase_fit(sz: Sizes, seed: int, devices):
    import bench
    from orange3_spark_tpu.optim.sparse import resolve_sparse_lowering

    clock = Clock()
    path = bench.ensure_criteo_csv(sz.fit_rows, seed)
    clock.generated()
    model, stages = fit_criteo(sz, path)
    ev = model.evaluate_device(model.holdout_chunks_)
    p, codec = model.params, model.cache_codec_
    emit("fit", clock, devices,
         rows=sz.fit_rows, cols=1 + N_DENSE + N_CAT, n_dims=sz.n_dims,
         chunk_rows=sz.chunk_rows, epochs=sz.epochs,
         train_chunks=len(model.device_chunks_),
         holdout_chunks=len(model.holdout_chunks_),
         replay_granularity=p.replay_granularity,
         sparse_lowering=resolve_sparse_lowering(p.sparse_lowering),
         cache_dtype=codec.mode if codec is not None else "f32",
         n_steps=model.n_steps_, final_loss=model.final_loss_,
         holdout_auc=ev["auc"], holdout_logloss=ev["logloss"],
         auc_floor=sz.auc_floor, stage_seconds=stages)
    assert np.isfinite(model.final_loss_), model.final_loss_
    for name, leaf in model.theta.items():
        assert leaf.devices() == set(devices), (name, leaf.devices())
        assert bool(np.isfinite(np.asarray(leaf)).all()), name
    assert ev["auc"] >= sz.auc_floor, (ev["auc"], sz.auc_floor)
    return model, path


# ----------------------------------------------------------------- serve
def phase_serve(sz: Sizes, model, path: str, devices):
    from orange3_spark_tpu.io.streaming import csv_raw_chunk_source
    from orange3_spark_tpu.serve import BucketLadder, ServingContext

    clock = Clock()
    first = next(csv_raw_chunk_source(path, chunk_rows=max(sz.serve_rows))())
    X = np.ascontiguousarray(first[:, 1:])        # drop the label column
    assert X.shape[0] >= max(sz.serve_rows), X.shape
    clock.generated()

    ladder = BucketLadder(min_bucket=sz.ladder[0], max_bucket=sz.ladder[1])
    buckets = sorted({ladder.bucket_for(n) for n in sz.serve_rows})
    assert None not in buckets, "a request size bypasses the ladder"

    def answers() -> dict:
        return {n: (model.predict(X[:n]), model.predict_proba(X[:n]))
                for n in sz.serve_rows}

    raw = answers()
    with ServingContext(ladder):
        c0 = clock.compiles()
        first_pass = answers()
        compiles_first = clock.compiles() - c0
        second_pass = answers()
        compiles_second = clock.compiles() - c0 - compiles_first
    max_diff = max(float(np.max(np.abs(first_pass[n][1] - raw[n][1])))
                   for n in sz.serve_rows)
    emit("serve", clock, devices,
         request_rows=list(sz.serve_rows), buckets=buckets,
         compiles_first_pass=compiles_first,
         compiles_second_pass=compiles_second,
         served_vs_unserved_max_abs_diff=max_diff, tolerance=SERVED_TOL)
    assert compiles_first <= len(buckets), (compiles_first, buckets)
    assert compiles_second == 0, compiles_second
    for n in sz.serve_rows:
        (pred, proba), (pred2, proba2) = first_pass[n], second_pass[n]
        assert pred.shape == (n,) and proba.shape == (n, 2), (n, proba.shape)
        # two served calls of one bucket: bitwise
        np.testing.assert_array_equal(pred, pred2)
        np.testing.assert_array_equal(proba, proba2)
        # against the unserved call at its own shape: the stated tolerance
        np.testing.assert_allclose(proba, raw[n][1], rtol=SERVED_TOL,
                                   atol=SERVED_TOL)
        clear = np.abs(raw[n][1][:, 1] - model.params.threshold) > SERVED_TOL
        np.testing.assert_array_equal(pred[clear], raw[n][0][clear])


# ----------------------------------------------------------------- trees
def _auc(scores, labels) -> float:
    order = np.argsort(scores)
    ranks = np.empty(len(scores), np.float64)
    ranks[order] = np.arange(1, len(scores) + 1)
    npos = float(labels.sum())
    nneg = len(labels) - npos
    return float((ranks[labels > 0.5].sum() - npos * (npos + 1) / 2)
                 / (npos * nneg))


def phase_trees(sz: Sizes, seed: int, devices):
    import jax
    import jax.numpy as jnp

    import bench_suite
    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.gbt import GBTClassifier
    from orange3_spark_tpu.models.random_forest import RandomForestClassifier
    from orange3_spark_tpu.ops.histogram import _hist_xla, node_histograms

    clock = Clock()
    session = TpuSession.builder_get_or_create()
    n_feat, n_bins, nodes = 28, 32, 16
    holdout = max(sz.tree_rows // 16, 1024)
    X, y = bench_suite.gen_higgs(sz.tree_rows + holdout, n_feat, seed)
    domain = Domain([ContinuousVariable(f"f{i}") for i in range(n_feat)],
                    DiscreteVariable("signal", ("0", "1")))
    table = TpuTable.from_numpy(domain, X[:sz.tree_rows], y[:sz.tree_rows],
                                session=session)
    eval_table = TpuTable.from_numpy(domain, X[sz.tree_rows:],
                                     y[sz.tree_rows:], session=session)
    jax.block_until_ready(table.X)
    clock.generated()

    # bench_suite config 3's settings, through Estimator.fit
    aucs = {}
    for name, est in (
        ("gbt", GBTClassifier(max_iter=20, max_depth=5, max_bins=n_bins)),
        ("rf", RandomForestClassifier(num_trees=20, max_depth=5,
                                      max_bins=n_bins)),
    ):
        model = est.fit(table)
        jax.block_until_ready(model.state_pytree)
        proba = model.predict_proba(eval_table)
        assert proba.shape == (holdout, 2) and np.isfinite(proba).all()
        aucs[name] = _auc(proba[:, 1], y[sz.tree_rows:])
        del model
    # the fits' own HBM, before the XLA reference below reserves its temp
    # (15.8 GB at this level shape by AOT memory_analysis(); the forest
    # program with the kernel inside needs 11.1 GB of temp)
    after_fits = memory(devices)

    # which histogram backend does the default pick here, and is it right:
    # one level of the growth loop at the fit's shape (quantile bins are
    # uniform by construction, so uniform random bins stand in for them)
    rng = np.random.default_rng(seed)
    B = jnp.asarray(rng.integers(0, n_bins, (sz.tree_rows, n_feat)), jnp.int32)
    S = jnp.asarray(rng.standard_normal((sz.tree_rows, 3)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, nodes, sz.tree_rows), jnp.int32)
    hist = jax.jit(functools.partial(node_histograms, nodes=nodes,
                                     n_bins=n_bins))
    took_pallas = "tpu_custom_call" in hist.lower(B, S, pos).compile().as_text()
    got = np.asarray(hist(B, S, pos))
    ref = np.asarray(jax.jit(functools.partial(
        _hist_xla, nodes=nodes, n_bins=n_bins))(B, S, pos))
    hist_err = float(np.max(np.abs(got - ref)))
    emit("trees", clock, devices,
         rows=sz.tree_rows, features=n_feat, max_iter=20, num_trees=20,
         max_depth=5, max_bins=n_bins, holdout_rows=holdout,
         gbt_holdout_auc=aucs["gbt"], rf_holdout_auc=aucs["rf"],
         histogram_backend="pallas" if took_pallas else "xla",
         hist_level_shape=[sz.tree_rows, n_feat, 3, nodes, n_bins],
         hist_vs_xla_max_abs_err=hist_err, memory_after_fits=after_fits)
    assert took_pallas == (devices[0].platform == "tpu"), took_pallas
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-2)
    assert min(aucs.values()) > sz.tree_auc_floor, aucs


# ---------------------------------------------------------------- canvas
def phase_canvas(sz: Sizes, seed: int, devices):
    import jax

    import bench
    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.serve import (
        BucketLadder, ServedWorkflow, ServingContext,
    )
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_graph

    clock = Clock()
    session = TpuSession.builder_get_or_create()
    k, n_pca = 10, 4
    X = bench.gen_taxi(sz.canvas_rows, seed + 2)
    domain = Domain([ContinuousVariable(c) for c in bench.TAXI_COLUMNS])
    table = TpuTable.from_numpy(domain, X, session=session)
    jax.block_until_ready(table.X)
    clock.generated()

    g = WorkflowGraph()
    src = g.add(OWTable(table))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    pca = g.add(WIDGET_REGISTRY["OWPCA"](k=n_pca))
    km = g.add(WIDGET_REGISTRY["OWKMeans"](k=k, max_iter=10))
    g.connect(src, "data", sc, "data")
    g.connect(sc, "data", pca, "data")
    g.connect(pca, "data", km, "data")

    # eager, widget by widget (fits scaler / PCA / KMeans)
    eager = np.asarray(g.run()[km]["data"].X)[:sz.canvas_rows]
    # the whole DAG re-fitted and applied in ONE staged call
    refit = stage_graph(g, km, refit=True)
    staged = np.asarray(refit().X)[:sz.canvas_rows]
    assert refit.refit_fallbacks == [], refit.refit_fallbacks
    assert staged.shape == eager.shape and np.isfinite(staged).all()

    def kmeans_cost(out):
        Z, lab = out[:, -1 - n_pca:-1], out[:, -1].astype(np.int64)
        assert lab.min() >= 0 and lab.max() < k, (lab.min(), lab.max())
        cnt = np.maximum(np.bincount(lab, minlength=k), 1)[:, None]
        cen = np.stack([np.bincount(lab, Z[:, j], minlength=k)
                        for j in range(n_pca)], 1) / cnt
        return float(((Z - cen[lab]) ** 2).sum()), len(np.unique(lab))

    # scaler -> PCA columns: same fit on the same data, within tolerance;
    # KMeans seeds differently in-trace (device D^2 sampling, documented),
    # so its clustering is compared by cost, not by label
    pre_diff = float(np.max(np.abs(staged[:, :-1] - eager[:, :-1])))
    (cost_e, live_e), (cost_s, live_s) = kmeans_cost(eager), kmeans_cost(staged)

    # the same fitted DAG served once as ONE bucketed executable
    wf = ServedWorkflow.from_graph(g, km, name="smoke-taxi-dag")
    req = TpuTable.from_numpy(domain, X[:256], session=session)
    with ServingContext(BucketLadder(min_bucket=64, max_bucket=512)):
        served = np.asarray(wf.predict(req))
    emit("canvas", clock, devices,
         rows=sz.canvas_rows, cols=X.shape[1], stages=wf.n_stages,
         staged_vs_eager_max_abs_diff=pre_diff,
         kmeans_cost_eager=cost_e, kmeans_cost_staged=cost_s,
         kmeans_live_clusters=[live_e, live_s], served_rows=len(served))
    assert pre_diff <= 1e-3, pre_diff
    assert live_s >= 2 and 1 / 3 < cost_s / cost_e < 3, (cost_s, cost_e)
    # a staged refit puts its freshly fitted models on the widgets' ports
    # (workflow/staging.py), so what is served afterwards is the staged
    # fit, not the eager one it replaced
    np.testing.assert_array_equal(served, staged[:256, -1])


# ------------------------------------------------------------ four chips
def phase_four_chips(sz: Sizes, seed: int, devices):
    import bench
    from orange3_spark_tpu.parallel.partitioner import (
        DataParallelPartitioner, SPMDPartitioner,
    )

    assert len(devices) == 4, f"--four-chips needs 4 devices, got {devices}"
    clock = Clock()
    path = bench.ensure_criteo_csv(sz.fit_rows, seed)
    clock.generated()
    emit("four_chips_data", clock, devices, rows=sz.fit_rows)

    def shards_on(tree) -> list:
        import jax

        return [sorted(s.device.id for s in leaf.addressable_shards)
                for leaf in jax.tree.leaves(tree)]

    ref = None
    for name, part in (
        ("one_device", DataParallelPartitioner(devices[:1])),
        ("data_parallel_4x1", DataParallelPartitioner(devices)),
        ("spmd_2x2", SPMDPartitioner(devices, model_parallel=2)),
    ):
        clock = Clock()
        model, stages = fit_criteo(sz, path, session=part.session)
        theta = {k: np.asarray(v) for k, v in model.theta.items()}
        want = sorted(d.id for d in part.mesh.devices.flat)
        theta_shards = shards_on(model.theta)
        chunk_shards = shards_on([c[0] for c in model.device_chunks_])
        diff = (0.0 if ref is None else
                max(float(np.max(np.abs(theta[k] - ref[k]))) for k in theta))
        emit(name, clock, devices,
             mesh=dict(part.mesh.shape), rows=sz.fit_rows, n_dims=sz.n_dims,
             epochs=sz.epochs, final_loss=model.final_loss_,
             stage_seconds=stages, emb_sharding=str(model.theta["emb"].sharding.spec),
             table_specs=model.table_specs_,
             theta_devices=theta_shards[0], chunk_devices=chunk_shards[0],
             theta_max_abs_diff_vs_one_device=diff, tolerance=SHARDED_ATOL)
        assert np.isfinite(model.final_loss_)
        assert all(s == want for s in theta_shards), theta_shards
        assert all(s == want for s in chunk_shards), chunk_shards
        # where the three table-sized arrays stood when the last step
        # handed them back: rows over 'model' on the (2,2) mesh — the
        # accumulator and the last-seen steps beside the weight, or each
        # chip would hold them whole
        model_axis = part.mesh.shape["model"]
        for table in ("emb", "acc", "t"):
            spec = model.table_specs_[table]
            assert spec.startswith("PartitionSpec('model'") == (
                model_axis > 1), (name, table, spec)
        in_use = memory(part.mesh.devices.flat)["bytes_in_use"]
        assert all(b is None or b > 0 for b in in_use), in_use
        if ref is None:
            ref = theta
        else:
            for k in theta:
                np.testing.assert_allclose(theta[k], ref[k], rtol=0,
                                           atol=SHARDED_ATOL, err_msg=k)
        del model
        gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the (4,1) and (2,2) sharded fits and the "
                         "one-device fit they are compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, no platform assertion (CPU rehearsal)")
    args = ap.parse_args()
    sz = REHEARSE if args.rehearse else REAL

    # the repo first: alone in a directory this fails before touching a chip
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io import native
    from orange3_spark_tpu.utils.profiling import install_compile_counter

    import jax

    devices = jax.devices()          # asked once; everything below uses it
    if devices[0].platform != "tpu" and not args.rehearse:
        raise SystemExit(
            f"chip_smoke: jax found no TPU (platform "
            f"{devices[0].platform!r}); this script does not fall back")

    cache = TpuSession.enable_compilation_cache()   # before the first jit
    install_compile_counter()
    Clock.install()
    native.get_lib()     # builds native/fastcsv.cpp; raises if it cannot
    print(json.dumps({
        "phase": "setup", "seed": args.seed, "rehearse": args.rehearse,
        "jax": jax.__version__, "csv_reader": "native fastcsv",
        "compile_cache_dir": cache["dir"],
        "compile_cache_entries": cache["pre_entries"],
        "devices": [str(d) for d in devices]}), flush=True)

    if args.four_chips:
        phase_four_chips(sz, args.seed, devices)
    else:
        model, path = phase_fit(sz, args.seed, devices)
        phase_serve(sz, model, path, devices)
        del model
        gc.collect()
        phase_trees(sz, args.seed, devices)
        gc.collect()
        phase_canvas(sz, args.seed, devices)

    sys.stderr.flush()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
