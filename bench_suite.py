"""Benchmark suite — BASELINE configs 3-5 (bench.py owns config 2).

Prints ONE JSON line per config:

  3 HIGGS-proxy    GBTClassifier + RandomForestClassifier fit wall + AUC
  4 MovieLens-proxy ALS rank-16 over 25M ratings, fit wall + RMSE
  5 Taxi-proxy      KMeans+PCA feature pipeline, eager widget-graph wall vs
                    staged single-XLA-computation wall
  6 dispatch        epochs_per_dispatch K in {1,4,16} replay amortization
  7 serving ladders bucket-ladder sweep (none/pow2/fixed-64)
  8 optim sweep     adam vs dense/sparse adagrad + sgd/ftrl arms (optim/)
  9 cache codec     f32 vs bf16 vs packed chunk-cache precision (io/codec)

No published reference numbers exist (BASELINE.json: empty mount,
`published: {}`), so every `vs_baseline` is null — the honest fields are the
absolute wall-clocks, quality metrics, and rows/s. Shapes follow the
BASELINE configs' datasets (synthetic, same dimensionality); row counts are
sized to one chip's HBM and can be overridden with --rows-scale.

Run: python bench_suite.py [--config 3|4|5|6|7|8|9|all] [--rows-scale 1.0]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- config 3
def gen_higgs(n_rows: int, n_feat: int = 28, seed: int = 0):
    """HIGGS-shaped (X f32[n, n_feat], y f32[n]): a nonlinear signal of
    pairwise products + a radial term (tree-learnable, linear-model-opaque)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_rows, n_feat), dtype=np.float32)
    z = (X[:, 0] * X[:, 1] - X[:, 2] * X[:, 3]
         + 0.8 * (X[:, 4] ** 2 - 1.0)
         + 0.6 * np.sign(X[:, 5]) * X[:, 6])
    y = (z + 0.5 * rng.standard_normal(n_rows).astype(np.float32) > 0
         ).astype(np.float32)
    return X, y


def bench_higgs_trees(scale: float) -> dict:
    """HIGGS-11M proxy: 28 features (21 kinematic + 7 derived), binary
    signal-vs-background with nonlinear structure only trees can see."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.gbt import GBTClassifier
    from orange3_spark_tpu.models.random_forest import RandomForestClassifier

    n_rows = int(11_000_000 * scale)
    n_feat = 28
    session = TpuSession.builder_get_or_create()
    _log(f"[higgs] generating {n_rows} x {n_feat} ...")
    X, y = gen_higgs(n_rows, n_feat)
    rng = np.random.default_rng(1)
    domain = Domain(
        [ContinuousVariable(f"f{i}") for i in range(n_feat)],
        DiscreteVariable("signal", ("0", "1")),
    )
    holdout = min(1 << 18, n_rows // 4)
    table = TpuTable.from_numpy(domain, X[:-holdout], y[:-holdout],
                                session=session)
    eval_table = TpuTable.from_numpy(domain, X[-holdout:], y[-holdout:],
                                     session=session)

    def auc(scores, labels):
        order = np.argsort(scores)
        ranks = np.empty_like(order, dtype=np.float64)
        ranks[order] = np.arange(1, len(scores) + 1)
        npos = labels.sum()
        nneg = len(labels) - npos
        return float((ranks[labels > 0.5].sum() - npos * (npos + 1) / 2)
                     / (npos * nneg))

    out = {"metric": "higgs_trees_fit", "unit": "s", "vs_baseline": None,
           "rows": n_rows, "features": n_feat}
    for name, est in (
        ("gbt", GBTClassifier(max_iter=20, max_depth=5, max_bins=32)),
        ("rf", RandomForestClassifier(num_trees=20, max_depth=5, max_bins=32)),
    ):
        _log(f"[higgs] warm-up {name} (compile at the timed shape) ...")
        # identical shape/statics: the timed fit reuses the jit; drain the
        # warm fit's async tail so it cannot bleed into the timed window
        jax.block_until_ready(est.fit(table).state_pytree)
        _log(f"[higgs] timed {name} fit ...")
        t0 = time.perf_counter()
        model = est.fit(table)
        jax.block_until_ready(model.state_pytree)
        dt = time.perf_counter() - t0
        proba = model.predict_proba(eval_table)
        out[f"{name}_fit_s"] = round(dt, 2)
        out[f"{name}_rows_per_sec_per_chip"] = round(
            (n_rows - holdout) / dt / session.n_devices, 1
        )
        out[f"{name}_holdout_auc"] = round(auc(proba[:, 1], y[-holdout:]), 4)
    # Pallas-vs-XLA histogram kernel A/B at a tree-realistic shape (the
    # level-wise growth hot loop) — evidence for the kernel's value on
    # REAL hardware each bench run; skipped off-TPU where the Pallas
    # lowering doesn't apply
    if jax.default_backend() == "tpu":
        import jax.numpy as jnp

        from orange3_spark_tpu.ops.histogram import _hist_pallas, _hist_xla

        nb, nodes, nh = 32, 16, min(n_rows, 1 << 20)
        B = jnp.asarray(rng.integers(0, nb, (nh, n_feat)), jnp.int32)
        S = jnp.asarray(rng.random((nh, 3)), jnp.float32)
        pos = jnp.asarray(rng.integers(0, nodes, nh), jnp.int32)
        walls = {}
        for name_, fn in (("pallas", _hist_pallas), ("xla", _hist_xla)):
            jf = jax.jit(lambda B, S, pos, f=fn: f(
                B, S, pos, nodes=nodes, n_bins=nb))
            jax.block_until_ready(jf(B, S, pos))  # compile
            t0 = time.perf_counter()
            for _ in range(10):
                r = jf(B, S, pos)
            jax.block_until_ready(r)
            walls[name_] = (time.perf_counter() - t0) / 10 * 1e3
            out[f"hist_{name_}_ms"] = round(walls[name_], 3)
        out["hist_pallas_speedup"] = round(
            walls["xla"] / max(walls["pallas"], 1e-9), 2)
    out["value"] = out["gbt_fit_s"]
    return out


# ---------------------------------------------------------------- config 4
def bench_movielens_als(scale: float) -> dict:
    """MovieLens-25M proxy: 25M ratings over 162k users x 59k items,
    low-rank + noise, explicit feedback, rank-16 ALS."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.models.als import ALS, ratings_table

    n_ratings = int(25_000_000 * scale)
    n_users, n_items, true_rank, rank = 162_541, 59_047, 12, 16
    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(1)
    _log(f"[als] generating {n_ratings} ratings ...")
    Ut = rng.normal(0, 0.6, (n_users, true_rank)).astype(np.float32)
    Vt = rng.normal(0, 0.6, (n_items, true_rank)).astype(np.float32)
    uu = rng.integers(0, n_users, n_ratings, dtype=np.int64)
    ii = rng.integers(0, n_items, n_ratings, dtype=np.int64)
    rr = (np.einsum("nk,nk->n", Ut[uu], Vt[ii]) + 3.5
          + 0.3 * rng.standard_normal(n_ratings).astype(np.float32))
    ratings = np.stack(
        [uu.astype(np.float32), ii.astype(np.float32), rr], axis=1
    ).astype(np.float32)
    holdout = min(1 << 18, n_ratings // 4)
    t = ratings_table(ratings[:-holdout], session)
    t_eval = ratings_table(ratings[-holdout:], session)

    est = ALS(rank=rank, max_iter=10, reg_param=0.05,
              n_users=n_users, n_items=n_items, seed=2)
    _log("[als] warm-up (compile at the timed shape/statics) ...")
    # max_iter is a static arg: warm-up must use the SAME value; drain it
    jax.block_until_ready(est.fit(t).state_pytree)
    _log("[als] timed fit ...")
    t0 = time.perf_counter()
    model = est.fit(t)
    jax.block_until_ready(model.state_pytree)
    dt = time.perf_counter() - t0

    def rmse(tbl):
        scored = model.transform(tbl)
        X, _, W = scored.to_numpy()
        pred, r = X[:, -1], X[:, 2]
        live = (W > 0) & np.isfinite(pred)
        return float(np.sqrt(np.mean((pred[live] - r[live]) ** 2)))

    return {
        "metric": "movielens_als_fit", "unit": "s", "value": round(dt, 2),
        "vs_baseline": None,
        "ratings": n_ratings, "rank": rank, "iters": 10,
        "ratings_per_sec_per_chip": round(
            (n_ratings - holdout) * 10 * 2 / dt / session.n_devices, 1
        ),  # each iter scans all ratings twice (user + item half-steps)
        "train_rmse": round(rmse(t), 4),
        "holdout_rmse": round(rmse(t_eval), 4),
        "noise_floor": 0.3,
    }


# ---------------------------------------------------------------- config 5
def bench_taxi_pipeline(scale: float) -> dict:
    """NYC-Taxi-1B proxy: scaler -> PCA -> KMeans feature pipeline over
    10M x 8 trip features; the workflow staged into ONE XLA computation vs
    eager widget-by-widget execution."""
    import jax
    import numpy as np

    from bench import TAXI_COLUMNS, gen_taxi
    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_graph

    n_rows = int(10_000_000 * scale)
    session = TpuSession.builder_get_or_create()
    _log(f"[taxi] generating {n_rows} x 8 ...")
    X = gen_taxi(n_rows)
    domain = Domain([ContinuousVariable(c) for c in TAXI_COLUMNS])
    table = TpuTable.from_numpy(domain, X, session=session)

    def build():
        g = WorkflowGraph()
        src = g.add(OWTable(table))
        sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
        pca = g.add(WIDGET_REGISTRY["OWPCA"](k=4))
        km = g.add(WIDGET_REGISTRY["OWKMeans"](k=10, max_iter=10))
        g.connect(src, "data", sc, "data")
        g.connect(sc, "data", pca, "data")
        g.connect(pca, "data", km, "data")
        return g, src, sc, pca, km

    _log("[taxi] eager workflow warm-up (compiles each widget's fit) ...")
    g_warm, *_ = build()
    jax.block_until_ready(g_warm.run()[list(g_warm.nodes)[-1]]["data"].X)

    # timed eager fit on a FRESH graph: widget jits are already compiled,
    # so this measures the warm per-widget dispatch walk — the same warm
    # basis the staged timings below use
    g, src, sc, pca, km = build()
    _log("[taxi] eager workflow run (fits scaler/PCA/KMeans) ...")
    t0 = time.perf_counter()
    out_eager = g.run()[km]["data"]
    jax.block_until_ready(out_eager.X)
    wall_fit_eager = time.perf_counter() - t0

    # transform path: eager widget-by-widget re-execution vs staged single
    # XLA computation on the same batch. Warm calls are BLOCKED before the
    # timed window — dispatch is async, and an unblocked warm execution
    # otherwise queues ahead of the timed call and inflates it (this very
    # bias produced a bogus 0.26x staged 'slowdown' at 10M in an earlier
    # round-4 run; the clean measurement has staged ahead at every scale)
    staged = stage_graph(g, km)
    jax.block_until_ready(staged().X)  # compile + drain
    t0 = time.perf_counter()
    out_staged = staged()
    jax.block_until_ready(out_staged.X)
    wall_staged = time.perf_counter() - t0

    # fit-in-trace: the whole pipeline INCLUDING the scaler/PCA/KMeans fits
    # as one XLA program (stage_graph refit=True) vs the eager widget walk
    # measured above as wall_fit_eager
    # the eagerly fitted models: a staged refit puts its own on the ports
    eager_models = [g.nodes[nid].outputs["model"] for nid in (sc, pca, km)]
    refit_staged = stage_graph(g, km, refit=True)
    jax.block_until_ready(refit_staged().X)  # compile + drain
    t0 = time.perf_counter()
    out_refit = refit_staged()
    jax.block_until_ready(out_refit.X)
    wall_fit_staged = time.perf_counter() - t0
    n_fallbacks = len(refit_staged.refit_fallbacks)

    def eager_transform():
        t = table
        for model in eager_models:
            t = model.transform(t)
        return t

    jax.block_until_ready(eager_transform().X)  # warm + drain
    t0 = time.perf_counter()
    out_e2 = eager_transform()
    jax.block_until_ready(out_e2.X)
    wall_eager_tr = time.perf_counter() - t0

    np.testing.assert_allclose(
        np.asarray(out_staged.X[:1024]), np.asarray(out_e2.X[:1024]),
        rtol=1e-4, atol=1e-4,
    )
    return {
        "metric": "taxi_kmeans_pca_pipeline", "unit": "s",
        # microseconds: at the contract test's 20,000 rows the staged
        # transform takes 0.4-2 ms, and a millisecond rounding reads 0.0
        "value": round(wall_staged, 6), "vs_baseline": None,
        "rows": n_rows,
        "workflow_fit_s": round(wall_fit_eager, 2),
        "workflow_fit_staged_s": round(wall_fit_staged, 3),
        "fit_staged_speedup": round(
            wall_fit_eager / max(wall_fit_staged, 1e-9), 2
        ),
        "refit_fallbacks": n_fallbacks,
        "transform_eager_s": round(wall_eager_tr, 3),
        "transform_staged_s": round(wall_staged, 3),
        "staged_speedup": round(wall_eager_tr / max(wall_staged, 1e-9), 2),
        "staged_rows_per_sec_per_chip": round(
            n_rows / wall_staged / session.n_devices, 1
        ),
    }


# ------------------------------------------------- dispatch-overhead bench
def bench_dispatch_overhead(scale: float) -> dict:
    """Epoch-batching microbench (exec/ subsystem): the same cached-replay
    fit at epochs_per_dispatch K in {1, 4, 16} — one ``n_epochs=K`` scan
    per dispatch, so the replay's dispatch count drops K-fold while the
    step sequence stays bit-identical — the JSON's theta_max_abs_diff
    reports the measured cross-K embedding-table divergence (0.0 expected;
    the hard gate lives in tests/test_exec_pipeline.py's parity test).
    The K=16 wall is the amortization ceiling this knob buys; the deltas
    bound the pure dispatch overhead. One JSON line, sweep inline."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.utils.profiling import (
        exec_counters, reset_exec_counters,
    )

    n_rows = max(1 << 17, int((1 << 17) * scale))
    n_dense, n_cat, dims = 4, 8, 1 << 14
    chunk = 1 << 14
    epochs = 33          # 32 replay epochs: divisible by every swept K
    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(7)
    dense = rng.standard_normal((n_rows, n_dense)).astype(np.float32)
    cats = rng.integers(0, 1000, (n_rows, n_cat)).astype(np.float32)
    y = (dense[:, 0] + 0.3 * rng.standard_normal(n_rows) > 0
         ).astype(np.float32)
    Xall = np.concatenate([dense, cats], axis=1)

    sweep = {}
    theta_ref = None
    max_diff = 0.0
    for K in (1, 4, 16):
        est = StreamingHashedLinearEstimator(
            n_dims=dims, n_dense=n_dense, n_cat=n_cat, epochs=epochs,
            step_size=0.05, chunk_rows=chunk,
            fused_replay=True, replay_granularity="epoch",
            epochs_per_dispatch=K,
        )
        src = array_chunk_source(Xall, y, chunk_rows=chunk)
        _log(f"[dispatch] warm-up K={K} ...")
        warm = est.fit_stream(src, session=session, cache_device=True)
        jax.block_until_ready(warm.theta["emb"])
        _log(f"[dispatch] timed K={K} ...")
        reset_exec_counters()
        t0 = time.perf_counter()
        model = est.fit_stream(src, session=session, cache_device=True)
        jax.block_until_ready(model.theta["emb"])
        wall = time.perf_counter() - t0
        sweep[str(K)] = {
            "wall_s": round(wall, 3),
            "dispatches": exec_counters()["dispatches"],
        }
        emb = np.asarray(model.theta["emb"])
        if theta_ref is None:
            theta_ref = emb
        else:
            max_diff = max(max_diff, float(np.abs(emb - theta_ref).max()))
    return {
        "metric": "dispatch_overhead_epochs_per_dispatch", "unit": "s",
        "value": sweep["1"]["wall_s"], "vs_baseline": None,
        "rows": n_rows, "epochs": epochs, "chunk_rows": chunk,
        "sweep": sweep,
        "k16_speedup_vs_k1": round(
            sweep["1"]["wall_s"] / max(sweep["16"]["wall_s"], 1e-9), 2),
        # 0.0 = the swept lowerings are bit-identical (the donation/
        # batching parity contract, asserted per run)
        "theta_max_abs_diff": max_diff,
    }


# --------------------------------------------------- optimizer A/B bench
def bench_optim_sweep(scale: float) -> dict:
    """Optimizer-lever sweep (optim/ subsystem): the same cached-replay
    hashed fit under the legacy dense-adam path, the dense-adagrad twin,
    and the touched-row sparse-adagrad path — wall + per-replay-epoch
    time per arm, plus the sparse-vs-dense-twin embedding parity (the
    rules are the same math; only the lowering differs). The headline A/B
    at full Criteo scale lives in ``bench.py`` (pure_step_ms vs
    pure_step_ms_dense in one JSON line); this config is the small-scale
    sweep that also covers sgd/ftrl arms."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    n_rows = max(1 << 16, int((1 << 17) * scale))
    n_dense, n_cat, dims = 4, 8, 1 << 16
    chunk = 1 << 14
    epochs = 9
    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(9)
    dense = rng.standard_normal((n_rows, n_dense)).astype(np.float32)
    cats = rng.integers(0, 5000, (n_rows, n_cat)).astype(np.float32)
    y = (dense[:, 0] + 0.3 * rng.standard_normal(n_rows) > 0
         ).astype(np.float32)
    Xall = np.concatenate([dense, cats], axis=1)

    def arm(optim):
        est = StreamingHashedLinearEstimator(
            n_dims=dims, n_dense=n_dense, n_cat=n_cat, epochs=epochs,
            step_size=0.05, reg_param=1e-4, chunk_rows=chunk,
            optim_update=optim,
        )
        src = array_chunk_source(Xall, y, chunk_rows=chunk)
        _log(f"[optim] warm-up {optim} ...")
        est.fit_stream(src, session=session, cache_device=True)
        _log(f"[optim] timed {optim} ...")
        st: dict = {}
        t0 = time.perf_counter()
        model = est.fit_stream(src, session=session, cache_device=True,
                               stage_times=st)
        jax.block_until_ready(model.theta["emb"])
        wall = time.perf_counter() - t0
        return model, {
            "wall_s": round(wall, 3),
            "replay_fused_s": st.get("replay_fused_s"),
            "optim_update": st.get("optim_update"),
            "sparse_lowering": st.get("sparse_lowering"),
        }

    sweep = {}
    models = {}
    for optim in ("adam", "dense_adagrad", "sparse_adagrad",
                  "sparse_sgd", "sparse_ftrl"):
        models[optim], sweep[optim] = arm(optim)
    twin_diff = float(np.abs(
        np.asarray(models["sparse_adagrad"].theta["emb"])
        - np.asarray(models["dense_adagrad"].theta["emb"])).max())
    rf = {k: v["replay_fused_s"] for k, v in sweep.items()}
    return {
        "metric": "hashed_optim_update_sweep", "unit": "s",
        "value": sweep["sparse_adagrad"]["wall_s"], "vs_baseline": None,
        "rows": n_rows, "epochs": epochs, "n_hashed_dims": dims,
        "sweep": sweep,
        "sparse_replay_speedup_vs_adam": (
            round(rf["adam"] / rf["sparse_adagrad"], 2)
            if rf.get("adam") and rf.get("sparse_adagrad") else None),
        # sparse-vs-dense-twin parity, measured per run (the hard gates
        # live in tests/test_sparse_optim.py)
        "adagrad_twin_max_abs_diff": twin_diff,
    }


# ---------------------------------------------------------------- config 9
def bench_cache_codec_sweep(scale: float) -> dict:
    """Cache-codec sweep (io/codec.py): the SAME chunk stream cached at
    f32 (legacy), bf16 (dense block halved) and packed (bf16 + lossless
    bit-packed hashed indices) — per arm: fit wall, fused
    replay wall, measured cache bytes and the f32-equivalent compression
    ratio, plus the max-|theta| divergence vs the f32 arm (packed differs
    from bf16 by NOTHING — the int packing is lossless, pinned hard in
    tests/test_cache_codec.py; bf16 differs from f32 only through the
    bounded dense-feature rounding). The headline capacity criterion at
    Criteo scale lives in bench.py (compression_ratio field); this config
    is the small-scale ladder that also shows the CPU decode-tax trade."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.codec import force_cache_dtype
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    n_rows = max(1 << 16, int((1 << 17) * scale))
    n_dense, n_cat, dims = 4, 8, 1 << 16
    chunk = 1 << 14
    epochs = 9
    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(17)
    dense = rng.lognormal(size=(n_rows, n_dense)).astype(np.float32)
    cats = rng.integers(0, 50_000, (n_rows, n_cat)).astype(np.float32)
    y = (np.log(dense[:, 0]) + 0.3 * rng.standard_normal(n_rows) > 0
         ).astype(np.float32)
    Xall = np.concatenate([dense, cats], axis=1)

    def arm(cache):
        with force_cache_dtype(cache):
            est = StreamingHashedLinearEstimator(
                n_dims=dims, n_dense=n_dense, n_cat=n_cat, epochs=epochs,
                step_size=0.05, reg_param=1e-4, chunk_rows=chunk,
                optim_update="sparse_adagrad",
            )
            src = array_chunk_source(Xall, y, chunk_rows=chunk)
            _log(f"[cache-codec] warm-up {cache} ...")
            est.fit_stream(src, session=session, cache_device=True)
            _log(f"[cache-codec] timed {cache} ...")
            st: dict = {}
            t0 = time.perf_counter()
            model = est.fit_stream(src, session=session, cache_device=True,
                                   stage_times=st)
            jax.block_until_ready(model.theta["emb"])
            wall = time.perf_counter() - t0
        return model, {
            "wall_s": round(wall, 3),
            "replay_fused_s": st.get("replay_fused_s"),
            "cache_dtype": st.get("cache_dtype"),
            "cache_bytes": st.get("cache_bytes"),
            "compression_ratio": (
                round(st["cache_raw_bytes"] / st["cache_bytes"], 3)
                if st.get("cache_bytes") else None),
            "encode_s": (round(st["encode_s"], 3)
                         if "encode_s" in st else None),
        }

    sweep = {}
    models = {}
    for cache in ("f32", "bf16", "packed"):
        models[cache], sweep[cache] = arm(cache)
    emb32 = np.asarray(models["f32"].theta["emb"])
    for cache in ("bf16", "packed"):
        sweep[cache]["theta_max_abs_diff_vs_f32"] = float(np.abs(
            np.asarray(models[cache].theta["emb"]) - emb32).max())
    rf = {k: v["replay_fused_s"] for k, v in sweep.items()}
    return {
        "metric": "hashed_cache_codec_sweep", "unit": "s",
        "value": sweep["packed"]["wall_s"], "vs_baseline": None,
        "rows": n_rows, "epochs": epochs, "n_hashed_dims": dims,
        "sweep": sweep,
        "packed_compression_ratio": sweep["packed"]["compression_ratio"],
        # packed-replay-vs-f32-replay: the CPU decode-tax / TPU bandwidth
        # trade, measured (>1 = packed replay faster)
        "packed_replay_speedup_vs_f32": (
            round(rf["f32"] / rf["packed"], 3)
            if rf.get("f32") and rf.get("packed") else None),
        # the int packing is lossless: packed must equal bf16 exactly
        "packed_equals_bf16": bool(np.array_equal(
            np.asarray(models["packed"].theta["emb"]),
            np.asarray(models["bf16"].theta["emb"]))),
    }


# --------------------------------------------------- serving-ladder bench
def bench_serving_ladders(scale: float) -> dict:
    """Bucket-ladder sweep (serve/ subsystem): the same mixed-size predict
    trace through three ServingContext ladders —

      none      identity ladder: every request size is its own bucket (the
                unbucketed baseline, but THROUGH the serve path so cache/
                counters behave identically);
      pow2      the default log-ladder (compile count ~log of size range);
      fixed-64  64-row steps: tightest padding waste, linearly many
                executables.

    Per ladder: XLA compile count over the sweep (warmup is on-demand
    here — first touch of each bucket), p50/p99 request latency, wall,
    and padding overhead. The expected shape: compiles none >> fixed-64 >
    pow2, pad_overhead pow2 > fixed-64 > none = 1.0."""
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.serve import BucketLadder, ServingContext
    from orange3_spark_tpu.utils.profiling import (
        install_compile_counter, reset_serve_counters, serve_counters,
        xla_compile_count,
    )

    n_rows = max(1 << 15, int((1 << 17) * scale))
    n_dense, n_cat, dims = 4, 8, 1 << 14
    session = TpuSession.builder_get_or_create()
    install_compile_counter()
    rng = np.random.default_rng(13)
    dense = rng.standard_normal((n_rows, n_dense)).astype(np.float32)
    cats = rng.integers(0, 1000, (n_rows, n_cat)).astype(np.float32)
    y = (dense[:, 0] + 0.3 * rng.standard_normal(n_rows) > 0
         ).astype(np.float32)
    Xall = np.concatenate([dense, cats], axis=1)
    _log("[serving-ladders] fitting the hashed model ...")
    model = StreamingHashedLinearEstimator(
        n_dims=dims, n_dense=n_dense, n_cat=n_cat, epochs=2,
        step_size=0.05, chunk_rows=1 << 14,
    ).fit_stream(array_chunk_source(Xall, y, chunk_rows=1 << 14),
                 session=session)

    n_requests = 48
    sizes = np.exp(rng.uniform(np.log(16), np.log(4096), n_requests)
                   ).astype(np.int64)
    offs = rng.integers(0, n_rows - int(sizes.max()), n_requests)
    trace = [(int(o), int(s)) for o, s in zip(offs, sizes)]
    total_rows = sum(s for _, s in trace)

    ladders = {
        "none": BucketLadder(mode="none", max_bucket=1 << 13),
        "pow2": BucketLadder(min_bucket=64, max_bucket=1 << 13),
        "fixed64": BucketLadder(mode="fixed", fixed_step=64,
                                max_bucket=1 << 13),
    }
    sweep = {}
    for name, ladder in ladders.items():
        _log(f"[serving-ladders] ladder {name} ...")
        reset_serve_counters()
        c0 = xla_compile_count()
        lat = []
        with ServingContext(ladder, max_entries=256):
            t0 = time.perf_counter()
            for off, sz in trace:
                t1 = time.perf_counter()
                out = model.predict(Xall[off:off + sz])
                assert out.shape[0] == sz
                lat.append((time.perf_counter() - t1) * 1e3)
            wall = time.perf_counter() - t0
        sc = serve_counters()
        sweep[name] = {
            "recompiles": xla_compile_count() - c0,
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
            "wall_s": round(wall, 3),
            "pad_overhead": (round(sc["pad_overhead"], 3)
                             if sc["pad_overhead"] else None),
            "bucket_hits": sc["bucket_hits"],
        }
    return {
        "metric": "serving_bucket_ladder_sweep", "unit": "s",
        "value": sweep["pow2"]["wall_s"], "vs_baseline": None,
        "requests": n_requests, "trace_rows": total_rows,
        "distinct_sizes": len(set(s for _, s in trace)),
        "sweep": sweep,
        "pow2_compile_reduction": round(
            sweep["none"]["recompiles"]
            / max(sweep["pow2"]["recompiles"], 1), 2),
    }


def main():
    from bench import device_fields
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.native import tune_malloc

    tune_malloc()  # dedicated bench process: keep big buffers resident
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="all",
                    choices=["3", "4", "5", "6", "7", "8", "9", "all"])
    ap.add_argument("--rows-scale", type=float, default=1.0)
    args = ap.parse_args()
    # runs in THIS process on whatever platform jax gives; any config's
    # exception ends the run non-zero (bench.py docstring, "Devices")
    TpuSession.enable_compilation_cache()
    benches = {"3": bench_higgs_trees, "4": bench_movielens_als,
               "5": bench_taxi_pipeline, "6": bench_dispatch_overhead,
               "7": bench_serving_ladders, "8": bench_optim_sweep,
               "9": bench_cache_codec_sweep}
    keys = (["3", "4", "5", "6", "7", "8", "9"] if args.config == "all"
            else [args.config])
    for k in keys:
        out = benches[k](args.rows_scale)
        out.update(device_fields())
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
