"""Benchmark harness — BASELINE config 2 (Criteo-shaped CTR LogisticRegression).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

The headline metric (BASELINE.json `configs[1]`) is rows/sec/chip on a
Criteo-shaped click-through fit: 13 dense numerics + 26 categorical columns
hashed to 2^22 dimensions. Dense representation is impossible at that width;
this bench exercises the REAL 1B-row pipeline end to end:

    synthetic Criteo CSV on disk (cached)
      -> native fastcsv chunk parse (C++, single pass, zero host copies)
      -> device DMA (prefetch thread overlaps parse/DMA with device steps)
      -> jitted hashed-sparse step (device-side murmur hash, k=1 sigmoid
         embedding gather, scatter-add gradient, adam)
      -> epochs 2+ replay HBM-cached chunks (Spark's `dataset.persist()`
         before an iterative MLlib fit — same trick, same fairness)
      -> held-out tail evaluated ON DEVICE (logloss/accuracy/AUC)

value = UNIQUE dataset rows / total wall / chips — the convention a user
feels: "how fast does the whole fit chew my dataset, end to end, epochs
included". The rows×passes rate (train_rows x epochs / wall — how Spark's
L-BFGS quotes rows/sec, one dataset scan per iteration) is reported as
the secondary `train_rows_x_epochs_per_sec_per_chip`; it is NOT the
headline because with fused replay a marginal epoch costs ~30 ms of
device time, so that numerator grows almost linearly in the epoch count
chosen — a convention, not a measurement.

vs_baseline: BASELINE.json records NO published reference numbers
(`published: {}`), so the denominator is a documented proxy: a
32-executor Spark/MLlib cluster sustaining ~8M sparse rows/sec on hashed
CTR LogReg ≈ 250k rows/sec per chip-equivalent of a v5e-8 — against the
headline dataset rate that proxy is generous to Spark (its 8M rows/s is
itself a passes convention), making vs_baseline conservative for us.
The JSON carries `"baseline": "proxy-estimate"` so the convention is
machine-visible, and the extra fields (stage seconds, input_gbps,
wall_s, holdout_*) are the defensible absolute numbers.

Devices: the chosen config runs IN THIS PROCESS on whatever platform jax
gives; every record names it (`platform`, `device_kind`, `device_count`
from `jax.devices()`). Nothing here probes, waits for, locks, re-routes
or shrinks: a run meant for the CPU says so from outside
(`JAX_PLATFORMS=cpu`, as the tests do), a platform that cannot
initialise is an error, and any exception ends the run non-zero with no
metric line. A number from a CPU run is a count/correctness rehearsal,
never a device rate (PERF.md).

Other BASELINE configs: bench_suite.py (HIGGS trees, MovieLens ALS,
Taxi KMeans+PCA). This file stays the driver's single headline entry.
"""

import argparse
import json
import os
import sys
import time

SPARK_PROXY_ROWS_PER_SEC_PER_CHIP = 250_000.0
# Provenance of the vs_baseline denominator, embedded in every emitted
# JSON line (baseline_value/baseline_note): BASELINE.json records NO
# published reference numbers (empty mount), so the denominator is this
# documented proxy — a 32-executor Spark/MLlib cluster sustaining ~8M
# sparse rows/sec on hashed CTR LogReg / 32 chip-equivalents of a v5e-8.
BASELINE_NOTE = (
    "proxy estimate, no published reference (BASELINE.json empty mount): "
    "32-executor Spark/MLlib cluster at ~8M sparse rows/s on hashed CTR "
    "LogReg ~= 250k rows/s per chip-equivalent; the 8M rows/s is itself "
    "a passes convention, so vs_baseline is conservative for us")

N_ROWS = 8_000_000
N_DENSE = 13
N_CAT = 26
N_DIMS = 1 << 22     # 5.2M distinct codes: 2^20 would alias ~5 codes/bucket
CHUNK_ROWS = 1 << 18


def chunk_rows_for(n_rows: int) -> int:
    """CHUNK_ROWS — or, for a dataset smaller than one chunk (the contract
    tests' 30,000 rows), the power of two that holds it. A chunk is padded
    to its full length and the step sorts a padded occurrence like a real
    one: three sorts of rows x 26 pairs a streamed step, ~2 s each on
    XLA:CPU at 2^18 rows, where the gathers they replaced were nearly free
    there (the reverse of the chip: a known CPU loss, PERF.md §6 PR 31).
    The smaller chunk runs the same program: 30,000 rows still pad (to
    32,768), so ``n_valid`` masks dead rows behind the sentinel; the slot
    bound is rows x 26 + 1 as ever; and the block loop takes the one trip
    it took at 2^18 (780,000 occurrences are under one SLOT_BLOCK either
    way). What it no longer shows is a static bound of 7 blocks with 1
    live: tests/test_sparse_optim.py (an injected SLOT_BLOCK) and the AOT
    compiles of tests/test_tpu_compile.py at 2^18 rows hold that."""
    return min(CHUNK_ROWS, 1 << max(n_rows - 1, 1).bit_length())
# 100 dataset passes = MLlib LogisticRegression's default maxIter (its
# L-BFGS scans the cached RDD once per iteration — the convention this
# metric quotes). Quality is epoch-flat once converged (measured 16 vs 48
# epochs on the 2M-row config: holdout AUC 0.741 -> 0.742, logloss
# 0.592 -> 0.591), and with the fused replay a marginal epoch costs ~30 ms
# of device time, so the honest sustained-throughput config is MLlib's own.
EPOCHS = 100
STEP_SIZE = 0.04
REG_PARAM = 1e-5     # mild L2 on the table: rare-code variance control
HOLDOUT_CHUNKS = 2           # last ~512k rows held out for eval
# generated data stays inside the checkout (git-ignored) unless placed
# elsewhere from outside
DATA_DIR = os.environ.get("OTPU_BENCH_DIR") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".bench_data")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_fields() -> dict:
    """The device every record names, as jax reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def gen_criteo_csv(path: str, n_rows: int, seed: int = 0) -> None:
    """Write a Criteo-shaped CSV: label + 13 skewed numerics + 26 categorical
    codes whose per-level latent effects drive the label (real CTR shape:
    most signal lives in the categoricals)."""
    import numpy as np
    import pyarrow as pa
    from pyarrow import csv as pacsv

    rng = np.random.default_rng(seed)
    card = 200_000           # per-column cardinality (codes up to 2*10^5)
    eff_card = 1024          # latent effects live on code % eff_card
    effects = rng.normal(0.0, 0.9, size=(N_CAT, eff_card)).astype(np.float32)
    w_dense = rng.normal(0.0, 0.4, size=N_DENSE).astype(np.float32)

    names = (["label"] + [f"i{j}" for j in range(N_DENSE)]
             + [f"c{j}" for j in range(N_CAT)])
    schema = pa.schema(
        [pa.field("label", pa.int8())]
        + [pa.field(f"i{j}", pa.float32()) for j in range(N_DENSE)]
        + [pa.field(f"c{j}", pa.int32()) for j in range(N_CAT)]
    )
    tmp = path + ".tmp"
    gen_chunk = 1_000_000
    opts = pacsv.WriteOptions(quoting_style="none")
    with pacsv.CSVWriter(tmp, schema, write_options=opts) as wr:
        done = 0
        while done < n_rows:
            n = min(gen_chunk, n_rows - done)
            dense = rng.lognormal(0.0, 1.0, size=(n, N_DENSE)).astype(np.float32)
            cats = rng.integers(0, card, size=(n, N_CAT), dtype=np.int32)
            logit = (dense - 1.6) @ w_dense - 0.5
            for j in range(N_CAT):
                logit += effects[j, cats[:, j] % eff_card]
            y = (logit + 0.5 * rng.standard_normal(n).astype(np.float32) > 0)
            cols = ([pa.array(y.astype(np.int8))]
                    + [pa.array(dense[:, j]) for j in range(N_DENSE)]
                    + [pa.array(cats[:, j]) for j in range(N_CAT)])
            wr.write_table(pa.table(cols, names=names))
            done += n
            _log(f"  gen {done/1e6:.0f}M/{n_rows/1e6:.0f}M rows")
    os.replace(tmp, path)


def ensure_criteo_csv(n_rows: int, seed: int = 0) -> str:
    """Generate (once) and return the bench CSV path. Pure numpy/pyarrow:
    touches no jax backend."""
    os.makedirs(DATA_DIR, exist_ok=True)
    path = os.path.join(
        DATA_DIR, f"criteo_{n_rows}x{N_DENSE}d{N_CAT}c_s{seed}.csv")
    if not os.path.exists(path):
        _log(f"generating {path} ...")
        t0 = time.perf_counter()
        gen_criteo_csv(path, n_rows, seed)   # writes .tmp, then os.replace —
        #                                a killed run leaves no final file
        _log(f"  generated in {time.perf_counter() - t0:.1f}s "
             f"({os.path.getsize(path) / 1e9:.2f} GB)")
    return path


def bench_criteo(n_rows: int, epochs: int = EPOCHS, *, dims: int = N_DIMS,
                 step_size: float = STEP_SIZE, reg: float = REG_PARAM,
                 cache_bytes: int = 8 << 30) -> dict:
    import jax

    from orange3_spark_tpu.io.native import tune_malloc

    tune_malloc()  # dedicated bench process: keep chunk buffers resident

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.exec.compile_cache import cache_report
    from orange3_spark_tpu.io.streaming import csv_raw_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.utils.profiling import (
        exec_counters, reset_exec_counters,
    )

    path = ensure_criteo_csv(n_rows)
    chunk_rows = chunk_rows_for(n_rows)

    # persistent compilation cache BEFORE the first jit: the warm phase's
    # scan/eval compiles load from disk on every run after the first
    cache_info = TpuSession.enable_compilation_cache()
    session = TpuSession.builder_get_or_create()
    n_chips = session.n_devices

    if dims & (dims - 1):
        raise ValueError(f"dims must be a power of two (hash mask), got {dims}")

    # OTPU_FUSED_REPLAY selects the cached-epoch replay lowering (for an
    # explicit A/B; unset = the cheapest):
    #   "1"/unset  epochs 2+ as ONE scan dispatch
    #   "epoch"    one scan dispatch per OTPU_EPOCHS_PER_DISPATCH epochs
    #   "0"        per-chunk steps (most dispatches, no scan program)
    replay_env = os.environ.get("OTPU_FUSED_REPLAY", "1")
    fused_env = replay_env != "0"
    granularity = "epoch" if replay_env == "epoch" else "all"
    # epoch batching (exec subsystem): under granularity 'epoch', fold K
    # epochs into each scan dispatch — ~n_epochs/K dispatches instead of
    # n_epochs. Identical numerics at any K (pinned by tests).
    epochs_per_dispatch = max(
        1, int(os.environ.get("OTPU_EPOCHS_PER_DISPATCH", "4")))

    # defer_epoch1: the streaming pass is pure ingest and ALL `epochs`
    # training passes run inside the replay program — bit-identical
    # results (tests/test_hashed_defer.py), and epoch 1 sheds one step
    # dispatch per chunk. Tied to fused replay (per-chunk replay gains
    # nothing from deferring), and safe at every bench scale: the harness
    # pre-arms the disk spill whenever overflow is predicted, so the
    # replay always has a parse-free source to carry the full `epochs`
    # passes.
    #
    # Not on a CPU backend: there deferring serializes the parse AHEAD of
    # all training for nothing. The CPU run interleaves epoch-1 steps with
    # the prefetch pipeline instead: parse/pad of chunk t+1 overlaps the
    # step on chunk t (the JSON's overlap_pct), one replay pass moves into
    # that overlapped window, and results stay bit-identical (the defer
    # contract, exercised in reverse).
    defer = fused_env and jax.devices()[0].platform != "cpu"
    # Optimizer rule (optim/ subsystem): the dense-adam update tax was the
    # replay wall on XLA:CPU (the full-table moment sweeps + in-loss L2;
    # not re-decided on a chip yet), so the bench default is the touched-row
    # sparse path. OTPU_OPTIM_UPDATE pins a rule ('adam' reproduces the
    # pre-optim records; a dense_* name the full-sweep twin; the rule is
    # surfaced in the JSON's optim_update field). The dense A/B arm below
    # measures the legacy path in the SAME run.
    optim_update = os.environ.get("OTPU_OPTIM_UPDATE", "sparse_adagrad")
    # Cache precision (io/codec.py): the bench default is the full
    # compressed codec — bf16 dense block, u8 label, bit-packed hashed
    # indices — so the HBM cache, the disk spill and the h2d DMA move
    # fewer bytes and the fused-replay gate admits more rows.
    # OTPU_CACHE_DTYPE pins a mode ('f32' restores the legacy cache
    # exactly — the kill-switch); the f32 A/B arm below measures the
    # legacy cache's step over the SAME data in the same run.
    cache_dtype = os.environ.get("OTPU_CACHE_DTYPE", "packed")
    def make_est(e, defer_epoch1=None, optim=None):
        return StreamingHashedLinearEstimator(
            n_dims=dims, n_dense=N_DENSE, n_cat=N_CAT,
            epochs=e, step_size=step_size, reg_param=reg,
            chunk_rows=chunk_rows,
            label_in_chunk=True, prefetch_depth=2,
            fused_replay=fused_env, replay_granularity=granularity,
            epochs_per_dispatch=epochs_per_dispatch,
            defer_epoch1=defer if defer_epoch1 is None else defer_epoch1,
            optim_update=optim_update if optim is None else optim,
            cache_dtype=cache_dtype,
        )

    source = csv_raw_chunk_source(path, chunk_rows=chunk_rows)

    # the many-epoch config is priced on FUSED replay (~30 ms/epoch device
    # time); if the chunk cache cannot hold the dataset (plus the transient
    # stack copy fusion needs), replay epochs come off the DISK SPILL
    # (cache_spill_dir below) at read+DMA cost instead — still bounded,
    # but ~disk-bandwidth per epoch, so cap the epoch count LOUDLY rather
    # than silently running a multi-hour bench. This check runs BEFORE any
    # warm-up so the warm_replay below never materializes a dataset-sized
    # stack the timed fit would not use (round-3 advisor finding).
    n_chunks = -(-n_rows // session.pad_rows(chunk_rows))
    holdout_chunks = max(min(HOLDOUT_CHUNKS, n_chunks - 1), 0)
    cache_budget = cache_bytes
    # per-chunk cache bytes under the RESOLVED codec — one shared estimator
    # so this pre-gate cannot disagree with fit_stream's fusion gate,
    # which reads the REAL cache.nbytes
    from orange3_spark_tpu.models.hashed_linear import (
        estimate_cached_chunk_bytes,
    )
    row_cache_bytes = estimate_cached_chunk_bytes(make_est(epochs).params,
                                                  session)
    # static f32-vs-encoded per-chunk ratio (reported when an overflowed
    # run drops the measured cache; sizes are layout-determined so it
    # equals the measured ratio). Pinned via force_cache_dtype because the
    # env kill-switch outranks the param by design.
    from orange3_spark_tpu.io.codec import force_cache_dtype
    with force_cache_dtype("f32"):
        _raw_ratio_est = (estimate_cached_chunk_bytes(
            make_est(epochs).params, session) / row_cache_bytes
            if row_cache_bytes else None)
    # fit_stream's fusion gate reads cache.nbytes AFTER holdout exclusion,
    # so the estimate here must count TRAIN chunks only or the two gates
    # disagree in a boundary window (warm would be skipped for a fit that
    # still fuses, putting the scan compile back inside the timed window)
    est_cache_bytes = (n_chunks - holdout_chunks) * row_cache_bytes
    will_overflow = n_chunks * row_cache_bytes > cache_budget
    replay_fusible = not will_overflow and 2 * est_cache_bytes <= cache_budget
    if epochs > 16 and not replay_fusible:
        _log(f"WARN: dataset cache ~{est_cache_bytes/1e9:.1f} GB cannot "
             f"fuse replay within the {cache_budget/1e9:.1f} GB budget; "
             f"reducing epochs {epochs} -> 16 (disk-spill replay)")
        epochs = 16
    # clamp K to a divisor of the replay span: a remainder group would be a
    # DIFFERENT static n_epochs — a second scan compile landing inside the
    # timed window that warm_replay (which warms only the K-sized program)
    # cannot cover. Placed after the final `epochs` and defer schedule are
    # known (the span is `epochs` under defer, `epochs - 1` otherwise).
    if granularity == "epoch":
        n_rep_est = max(epochs if defer else epochs - 1, 1)
        while n_rep_est % epochs_per_dispatch:
            epochs_per_dispatch -= 1

    # warm-up. Which programs the timed fit will actually dispatch depends
    # on the schedule:
    #   * fully-fused defer fit (the common config): the ONLY training
    #     program is the replay scan warm_replay compiles below — a warm
    #     "fit" would execute per-chunk steps the timed fit never runs,
    #     re-creating the step-before-scan order the defer exists to
    #     avoid, and waste a stack-of-1 scan compile. Warm only the
    #     eval program (zero chunk through the device-put path).
    #   * any config with per-chunk steps in play (per-chunk replay rung,
    #     non-fusible cache, disk-replay partial tail when overflowing):
    #     one real chunk through a non-defer fit compiles _hashed_step +
    #     the csv/h2d path outside the timed window.
    def head_source():
        it = source()
        yield next(it)

    warm_skipped = None
    if fused_env and replay_fusible:
        # warm the replay scan at the timed fit's exact static shapes
        # (n_epochs + train chunk count), then warm the eval program with
        # the scan's OUTPUT theta — the same provenance the timed
        # model.evaluate_device sees, so neither compile lands inside the
        # measured window (an init-provenance theta could miss the jit
        # cache under GSPMD placement). warm_replay mirrors the schedule:
        # for a non-defer fit (the CPU path) it also runs one zero-chunk
        # step first, compiling _hashed_step at the timed shapes.
        from orange3_spark_tpu.models.hashed_linear import (
            HashedLinearModel, resolve_chunk_codec, warm_eval_chunk,
        )
        import numpy as np

        # host-side warm: parse ONE chunk and discard it — builds/loads the
        # fastcsv shared library and opens the reader outside the timed
        # window (the old warm fit did this implicitly; the defer warm
        # never touches the source otherwise)
        next(head_source())

        est_w = make_est(epochs)
        warm_state = est_w.warm_replay(n_chunks - holdout_chunks,
                                       session=session,
                                       cache_device_bytes=cache_budget)
        if warm_state is None:
            # zero train chunks after holdout, or fused_replay disabled on
            # the params: neither the replay scan nor the eval program can
            # be pre-compiled, so those compiles land INSIDE the timed
            # window — flag the line so the record is interpretable
            # (round-4 advisor finding)
            warm_skipped = ("warm_replay returned None: replay-scan and "
                            "eval compiles land inside the timed window")
            _log(f"WARN: {warm_skipped}")
        else:
            theta_w, salts_w = warm_state
            m0 = HashedLinearModel(est_w.params, theta_w, salts_w,
                                   ("0", "1"))
            # the zero chunk goes through the fit's ENCODED cache layout
            # (io/codec.py) so the eval program compiled here is the one
            # the timed evaluate_device dispatches
            m0.cache_codec_ = resolve_chunk_codec(est_w.params, session)
            m0.evaluate_device([warm_eval_chunk(est_w.params, session)])
    else:
        # non-fusible or per-chunk config: the timed fit trains through
        # per-chunk steps (and, when overflowing, the grouped disk scan
        # compiles at its own group shape mid-run — a known, logged cost),
        # so warm the step + csv/h2d path with one real chunk. There is no
        # replay scan to pre-compile here: replay either streams/loops
        # per-chunk (no scan program) or is disabled.
        warm = make_est(1, defer_epoch1=False).fit_stream(
            head_source, session=session, cache_device=True,
            holdout_chunks=0
        )
        warm.evaluate_device([warm.device_chunks_[0]])  # compile eval too

    _log(f"timed fit: {epochs} epochs ...")
    stage_times: dict = {}
    est = make_est(epochs)
    reset_exec_counters()   # dispatches/overlap measured over the timed window
    t0 = time.perf_counter()
    # the spill write costs an epoch-1 sequential disk pass, so only arm it
    # when the cache genuinely cannot hold the dataset (predictable here:
    # the bench knows n_rows; a degraded-without-spill fit would re-parse
    # the CSV every epoch instead)
    model = est.fit_stream(
        source, session=session,
        cache_device=True, cache_device_bytes=cache_budget,
        cache_spill_dir=DATA_DIR if will_overflow else None,
        holdout_chunks=holdout_chunks,
        stage_times=stage_times,
    )
    jax.block_until_ready(model.theta)
    wall_fit = time.perf_counter() - t0

    t0 = time.perf_counter()
    # tiny --rows runs can leave no chunk for holdout; skip eval then
    ev = (model.evaluate_device(model.holdout_chunks_)
          if model.holdout_chunks_ else {})
    wall_eval = time.perf_counter() - t0
    # snapshot BEFORE the self-diagnosis probes: their extra dispatches
    # must not inflate the timed window's dispatch count
    timed_counters = exec_counters()
    cache_rep = cache_report(cache_info)

    # ---- goodput & memory attribution (obs/prof.py): the timed fit's
    # wall decomposition + the device-memory ledger view, read off the
    # frozen run report BEFORE the probes touch the ledger. The contract
    # gates: fractions sum to 1.0 ± 0.02, and the ledger's cache entry
    # agrees with the legacy cache_bytes stage key within 1%.
    _rep = getattr(model, "run_report_", None)
    _rep_d = _rep.to_dict() if _rep is not None else {}
    goodput_rec = _rep_d.get("goodput")
    _dm = _rep_d.get("device_memory") or {}
    ledger_rec = ({
        "owners": _dm.get("owners"),
        "total_bytes": _dm.get("total_bytes"),
        "peak_bytes_fit": _dm.get("peak_bytes_fit"),
        "cache_entry_bytes": _dm.get("cache_entry_bytes"),
        "unnamed_bytes": (_dm.get("high_water") or {}
                          ).get("unnamed_bytes"),
    } if _dm else None)

    # -------- self-diagnosis probes (outside the timed window) --------
    # (a) pure step rate: replay 20 cached steps, block ONCE — separates
    #     "the step is slow" from "per-step dispatch/sync overhead"
    # (b) blocked h2d: one chunk-sized device_put, waited to completion —
    #     the TRUE DMA bandwidth (in-fit h2d_s only times the async enqueue)
    pure_step_ms = h2d_blocked_gbps = pure_step_ms_dense = None
    pure_step_ms_f32cache = None
    obs_overhead_pct = pure_step_ms_obs = None
    prof_overhead_pct = pure_step_ms_prof = None
    obs_ab_retried = prof_ab_retried = False
    obs_overhead_pct_first = prof_overhead_pct_first = None
    if model.device_chunks_:
        # the probes run AFTER the timed window; a probe that dies is an
        # error like any other (no measured line is printed)
        from orange3_spark_tpu.models.hashed_linear import (
            _ADAM_UNIT, _hashed_step, _init_fit_state,
        )
        from orange3_spark_tpu.optim.sparse import init_optim_state
        import jax.numpy as jnp
        import numpy as np

        chunks = model.device_chunks_[:4]
        probe_rows = float(np.mean([int(c[1]) for c in chunks]))
        salts = jnp.asarray(model.salts)
        buf = np.empty((chunk_rows, 1 + N_DENSE + N_CAT), np.float32)
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(buf))
        h2d_blocked_gbps = round(
            buf.nbytes / (time.perf_counter() - t0) / 1e9, 3)

        def probe_setup(est_arm):
            """Shared step-probe state (step_rate + the obs A/B arm):
            a fresh theta/opt for the arm's resolved rule and the
            per-chunk arg builder — ONE definition so the two probes
            cannot drift onto different calling conventions."""
            theta = jax.tree.map(jnp.copy, model.theta)
            _, _, _, _, kw = _init_fit_state(est_arm.params, session)
            opt = (_ADAM_UNIT.init(theta)
                   if kw["optim_update"] == "adam"
                   else init_optim_state(kw["optim_update"], theta))

            def args(c):
                return (c[0], c[1], c[2], c[3], salts,
                        jnp.float32(reg), jnp.float32(step_size),
                        jnp.float32(0.0))

            return theta, opt, kw, args

        def step_rate(est_arm, n_probe, chs):
            """Per-chunk step time of one arm over device-cached
            chunks — compile outside the timing, block once."""
            theta, opt, kw, args = probe_setup(est_arm)
            theta, opt, loss = _hashed_step(
                theta, opt, *args(chs[0]), **kw)
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for i in range(n_probe):
                theta, opt, loss = _hashed_step(
                    theta, opt, *args(chs[i % len(chs)]), **kw)
            jax.block_until_ready(loss)
            return round((time.perf_counter() - t0) / n_probe * 1e3, 2)

        pure_step_ms = step_rate(est, 10, chunks)

        # ---- obs A/B arm (obs/ subsystem) ----
        # the SAME instrumented step loop, spans+registry ON vs the
        # OTPU_OBS=0 kill-switch. Per-step blocked walls, compared by
        # their MINIMUM: scheduler noise only ever ADDS time, so the
        # per-arm floor converges on the true step cost and the
        # difference isolates the instrumentation itself. The
        # acceptance criterion is < 2% step-time overhead.
        from orange3_spark_tpu.obs import trace as obs_trace
        from orange3_spark_tpu.obs.trace import span as obs_span
        from orange3_spark_tpu.utils.profiling import count_dispatch

        def obs_ab_floors_ms(n_pairs, chs):
            """Interleaved per-step blocked walls: one obs-on step,
            one obs-off step, alternating, min per arm. Interleaving
            exposes both arms to the SAME load window (a preempted
            stretch inflates both, not just one), and the minimum
            discards the inflated samples — the difference of the two
            floors isolates the instrumentation itself."""
            theta, opt, kw, args = probe_setup(est)
            # no warm step: the pure_step_ms probe above already
            # compiled this exact program, and min-of-N absorbs any
            # residual first-iteration jitter
            best_on = best_off = None
            for i in range(2 * n_pairs):
                on = i % 2 == 0
                # pair the arms on the SAME chunk: the sparse step's
                # time is data-dependent, and with an even chunk
                # count i % len(chs) would hand each arm a disjoint
                # chunk set — workload bias masquerading as overhead
                c = chs[(i // 2) % len(chs)]
                t0 = time.perf_counter()
                if on:
                    # force-enable symmetrically with the off arm's
                    # force_disabled: under ambient OTPU_OBS=0 the
                    # span would no-op and the A/B would bank a
                    # vacuous no-op-vs-no-op overhead claim
                    with obs_trace.force_enabled():
                        with obs_span("chunk", i):
                            theta, opt, loss = _hashed_step(
                                theta, opt, *args(c), **kw)
                            count_dispatch()
                else:
                    with obs_trace.force_disabled():
                        with obs_span("chunk", i):   # no-op arm
                            theta, opt, loss = _hashed_step(
                                theta, opt, *args(c), **kw)
                            count_dispatch()
                jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
                if on:
                    best_on = dt if best_on is None else min(best_on, dt)
                else:
                    best_off = (dt if best_off is None
                                else min(best_off, dt))
            return best_on * 1e3, best_off * 1e3

        # the min-of-N floor only converges once N outruns the host's
        # scheduler noise. 3 pairs left the contract-size gate flaky
        # (observed: the same tree measured 4.2% in a full suite run
        # and -7.4% quiet); the 12-pair floor that papered over that
        # cost ~25 s of extra steps per contract run. The structured
        # retry below is the flake net now — a preemption stretch
        # does not reproduce, a real regression does — so 6 pairs
        # suffice at every size and the suite keeps the wall time
        n_pairs = 6
        on_ms, off_ms = obs_ab_floors_ms(n_pairs, chunks)
        # structured retry: on a loaded CI box one preemption stretch
        # can still straddle the floors and fake a >=2% overhead. A
        # REAL regression reproduces; noise does not — so a failing
        # first measurement earns exactly one re-measure, the second
        # reading is the record, and both land in the JSON so a
        # banked retry is auditable, never silent
        obs_ab_retried = False
        obs_overhead_pct_first = None
        if off_ms and 100.0 * (on_ms - off_ms) / off_ms >= 2.0:
            obs_ab_retried = True
            obs_overhead_pct_first = round(
                100.0 * (on_ms - off_ms) / off_ms, 2)
            on_ms, off_ms = obs_ab_floors_ms(n_pairs, chunks)
        pure_step_ms_obs = round(on_ms, 2)
        if off_ms:
            obs_overhead_pct = round(
                100.0 * (on_ms - off_ms) / off_ms, 2)

        # ---- prof A/B arm (obs/prof.py): the goodput accountant's
        # per-step surface (one dispatch-sync attribution + one
        # ledger update, what a real fit step pays) vs the
        # OTPU_PROF=0 kill-switch, same interleaved min-floor
        # mechanics as the obs A/B above. The < 2% criterion rides
        # prof_overhead_pct.
        from orange3_spark_tpu.obs import prof as _prof

        def prof_ab_floors_ms(n_pairs, chs):
            theta, opt, kw, args = probe_setup(est)
            best_on = best_off = None
            for i in range(2 * n_pairs):
                on = i % 2 == 0
                c = chs[(i // 2) % len(chs)]
                forced = (_prof.force_enabled() if on
                          else _prof.force_disabled())
                with forced:
                    acc = _prof.begin_fit()
                    t0 = time.perf_counter()
                    theta, opt, loss = _hashed_step(
                        theta, opt, *args(c), **kw)
                    # the per-step prof surface, BOTH arms: under
                    # the kill-switch these no-op (a contextvar
                    # read / an env check) — the difference of the
                    # floors isolates the accounting itself
                    _prof.note_sync(1e-9)
                    _prof.ledger_set("cache_chunks",
                                     "prof_ab_probe", 1024)
                    jax.block_until_ready(loss)
                    dt = time.perf_counter() - t0
                    _prof.end_fit(acc)
                if on:
                    best_on = dt if best_on is None else min(best_on, dt)
                else:
                    best_off = (dt if best_off is None
                                else min(best_off, dt))
            _prof.ledger_release("cache_chunks", "prof_ab_probe")
            return best_on * 1e3, best_off * 1e3

        on_ms_p, off_ms_p = prof_ab_floors_ms(n_pairs, chunks)
        # same one-retry policy as the obs A/B above
        prof_ab_retried = False
        prof_overhead_pct_first = None
        if off_ms_p and 100.0 * (on_ms_p - off_ms_p) / off_ms_p >= 2.0:
            prof_ab_retried = True
            prof_overhead_pct_first = round(
                100.0 * (on_ms_p - off_ms_p) / off_ms_p, 2)
            on_ms_p, off_ms_p = prof_ab_floors_ms(n_pairs, chunks)
        pure_step_ms_prof = round(on_ms_p, 2)
        if off_ms_p:
            prof_overhead_pct = round(
                100.0 * (on_ms_p - off_ms_p) / off_ms_p, 2)
        if est.params.optim_update != "adam":
            # dense A/B arm: the legacy dense-adam path over the SAME
            # cached chunks, same probe mechanics — the like-for-like
            # pair the sparse-update acceptance criterion is judged on
            pure_step_ms_dense = step_rate(make_est(epochs, optim="adam"),
                                           6, chunks)
        if stage_times.get("cache_dtype", "f32") != "f32":
            # cache-codec A/B arm (io/codec.py): the SAME head of the
            # dataset re-parsed and cached at legacy f32, stepped with
            # the same rule — 'compressed replay no slower than f32'
            # is judged on pure_step_ms vs this
            def head_n(k):
                def gen():
                    it = source()
                    for i, c in enumerate(it):
                        if i >= k:
                            break
                        yield c
                return gen

            from orange3_spark_tpu.io.codec import force_cache_dtype

            with force_cache_dtype("f32"):
                m_f32 = make_est(1, defer_epoch1=False).fit_stream(
                    head_n(len(chunks)), session=session,
                    cache_device=True,
                    # the arm honors the SAME budget as the timed fit
                    # (a second uncapped f32 copy next to the live
                    # packed cache is an HBM hazard on real devices)
                    cache_device_bytes=cache_budget,
                    holdout_chunks=0)
                if m_f32.device_chunks_:
                    # full-scale records get the 6-step mean; tiny
                    # (contract-sized) runs keep the probe cheap —
                    # at that scale the number is a smoke, not a record
                    pure_step_ms_f32cache = step_rate(
                        make_est(epochs),
                        6 if n_rows > 100_000 else 3,
                        m_f32.device_chunks_[:len(chunks)])
                # else: the f32 head doesn't even fit the budget the
                # compressed cache ran in — the arm has nothing
                # comparable to measure and the field stays null

    holdout_rows = sum(int(c[1]) for c in (model.holdout_chunks_ or []))
    train_rows = n_rows - holdout_rows
    rows_streamed = train_rows * epochs  # real rows through training
    wall = wall_fit + wall_eval
    dataset_rate = n_rows / wall / n_chips
    row_bytes = (1 + N_DENSE + N_CAT) * 4  # device-feed bytes per row
    epoch_s = stage_times.get("epoch_s", [])
    # fused replay (epochs 2+ in ONE dispatch) reports a single wall for
    # the whole phase; per-epoch is that divided across the replay epochs
    replay_fused_s = stage_times.get("replay_fused_s")
    # with defer_epoch1 the replay phase carries ALL `epochs` passes (the
    # streaming pass is ingest-only); without it, `epochs - 1`
    n_replay_passes = epochs if defer else epochs - 1
    if replay_fused_s is not None and n_replay_passes > 0:
        device_epoch = replay_fused_s / n_replay_passes
    elif len(epoch_s) > 1:
        device_epoch = sum(epoch_s[1:]) / (len(epoch_s) - 1)
    else:
        device_epoch = None
    # analytic HBM traffic of one device step (k=1 table): chunk read
    # (41 f32 cols) + embedding gather/scatter (26 idx/row: value read +
    # grad write + index reads) + 6 adam passes over the table;
    # divided by the measured HBM-replay step time.
    hbm_gbps = None
    steps_per_epoch = model.n_steps_ // max(epochs, 1)
    if device_epoch and steps_per_epoch:
        step_s = device_epoch / steps_per_epoch
        step_bytes = chunk_rows * (41 * 4 + 26 * 12) + 6 * dims * 4
        hbm_gbps = round(step_bytes / step_s / 1e9, 1)
    return {
        "metric": "criteo_hashed_logreg_rows_per_sec_per_chip",
        # HEADLINE = unique dataset rows / wall / chips. The rows x passes
        # rate (Spark's L-BFGS convention) is the secondary field below —
        # with fused replay it grows ~linearly in the epoch count chosen,
        # so it cannot carry vs_baseline honestly (round-3 verdict weak #1)
        "value": round(dataset_rate, 1),
        "unit": "rows/s/chip",
        "vs_baseline": round(
            dataset_rate / SPARK_PROXY_ROWS_PER_SEC_PER_CHIP, 3
        ),
        # no published reference numbers exist (empty mount) — the
        # denominator is the documented 250k rows/s/chip-equivalent proxy,
        # with its constant + derivation embedded for provenance
        "baseline": "proxy-estimate",
        "baseline_value": SPARK_PROXY_ROWS_PER_SEC_PER_CHIP,
        "baseline_note": BASELINE_NOTE,
        **device_fields(),
        "rows": n_rows,
        "train_rows": train_rows,
        "epochs": epochs,
        "rows_streamed": rows_streamed,
        "train_rows_x_epochs_per_sec_per_chip": round(
            rows_streamed / wall / n_chips, 1
        ),
        # pure replay-phase sustained rate: rows through training per second
        # during the fused HBM-replay epochs alone (no host involvement) —
        # the device's own training throughput, independent of the
        # host-bound first pass
        "device_replay_rows_per_sec_per_chip": (
            round(train_rows * n_replay_passes
                  / stage_times["replay_fused_s"] / n_chips, 1)
            if stage_times.get("replay_fused_s") else None),
        # ---- optimizer A/B (optim/ subsystem) ----
        # the RESOLVED rule + dedup lowering the timed fit ran
        "optim_update": stage_times.get("optim_update"),
        "sparse_lowering": stage_times.get("sparse_lowering"),
        # dense arm of the same run: the legacy dense-adam step over the
        # SAME cached chunks (probe-derived per-chunk rate; the sparse
        # pair is pure_step_ms / the timed replay rate above)
        "pure_step_ms_dense": pure_step_ms_dense,
        "device_replay_rows_per_sec_per_chip_dense": (
            round(probe_rows / (pure_step_ms_dense / 1e3) / n_chips, 1)
            if pure_step_ms_dense else None),
        "optim_step_speedup": (
            round(pure_step_ms_dense / pure_step_ms, 2)
            if pure_step_ms_dense and pure_step_ms else None),
        # ---- cache-codec economics (io/codec.py) ----
        # what the HBM chunk cache actually held this run: resolved dtype
        # mode, encoded bytes, f32-equivalent ratio, and how many rows the
        # budget holds at the measured bytes/row — the ISSUE-4 capacity
        # criterion is compression_ratio (>= 1.8x on this config). The
        # f32-arm step probe above closes the 'no slower' half.
        "cache_dtype": stage_times.get("cache_dtype"),
        "cache_bytes": stage_times.get("cache_bytes"),
        "compression_ratio": (
            round(stage_times["cache_raw_bytes"]
                  / stage_times["cache_bytes"], 3)
            if stage_times.get("cache_bytes") else
            # overflowed run (cache dropped): the static per-chunk ratio —
            # sizes are layout-determined, so this equals the measured one
            round(_raw_ratio_est, 3) if _raw_ratio_est else None),
        "cache_rows_capacity": (
            int(cache_budget * stage_times["cache_chunks"]
                * session.pad_rows(chunk_rows)
                // stage_times["cache_bytes"])
            if stage_times.get("cache_bytes") else None),
        "pure_step_ms_f32cache": pure_step_ms_f32cache,
        "cache_step_speedup": (
            round(pure_step_ms_f32cache / pure_step_ms, 2)
            if pure_step_ms_f32cache and pure_step_ms else None),
        # prefetch-thread seconds encoding chunks for the compressed cache
        # (overlaps device work like parse_s)
        "encode_s": (round(stage_times["encode_s"], 2)
                     if "encode_s" in stage_times else None),
        "n_hashed_dims": dims,
        "wall_s": round(wall, 2),
        "eval_s": round(wall_eval, 2),
        # parse_s/h2d_s accumulate on the prefetch thread and OVERLAP device
        # work (their sum can exceed wall); epoch walls are the direct
        # measurements. Under defer_epoch1 (flagged below, the default
        # since round 4 session 3) pass 1 is INGEST-ONLY (parse+DMA, zero
        # step dispatches) and all `epochs` training passes live in the
        # replay wall; in earlier records epoch1_s included per-chunk
        # training — compare across rounds via the flag.
        "defer_epoch1": defer,
        # ---- execution-pipeline instrumentation (exec/ subsystem) ----
        # measured host-prep/device-compute overlap of the fit's prefetch
        # streams (100 = all parse/pad/DMA hidden behind device work)
        "overlap_pct": stage_times.get("overlap_pct"),
        # device programs dispatched inside the timed fit+eval window —
        # THE number epoch batching shrinks (r05 ran one dispatch per
        # replay epoch on the hardware rung)
        "dispatches": timed_counters["dispatches"],
        "epochs_per_dispatch": (epochs_per_dispatch
                                if granularity == "epoch" else None),
        # persistent compilation cache: True = every program this run
        # needed was served from disk (no new cache entries written)
        "cache_hit": cache_rep["cache_hit"],
        "cache_entries": cache_rep["cache_entries"],
        "parse_s": round(stage_times.get("parse_s", 0.0), 2),
        "h2d_s": round(stage_times.get("h2d_s", 0.0), 2),
        "epoch1_s": round(epoch_s[0], 2) if epoch_s else None,
        "device_epoch_s": (round(device_epoch, 3)
                           if device_epoch is not None else None),
        "replay_fused_s": (round(replay_fused_s, 2)
                           if replay_fused_s is not None else None),
        # per-phase walls: [epoch1, fused-replay] under fused replay (one
        # dispatch, nothing to drift); with fused_replay off this is one
        # wall per epoch and a drift across them means the backend is
        # degrading mid-run, not the program
        "epoch_walls_s": [round(t, 2) for t in epoch_s],
        "pure_step_ms": pure_step_ms,
        # ---- obs A/B (obs/ subsystem): spans+registry on vs OTPU_OBS=0
        # over the same instrumented step loop; the < 2% criterion rides
        # obs_overhead_pct (negative = measurement noise, spans free)
        "pure_step_ms_obs": pure_step_ms_obs,
        "obs_overhead_pct": obs_overhead_pct,
        # one structured re-measure when the first floor pair lands past
        # the 2% gate (scheduler noise, not instrumentation, is the
        # common cause at ms-scale steps); both readings are banked
        "obs_ab_retried": obs_ab_retried,
        "obs_overhead_pct_first": obs_overhead_pct_first,
        # ---- goodput & memory attribution (obs/prof.py): the timed
        # fit's five-way wall decomposition (fractions sum to 1.0, the
        # contract pins ±0.02) + bottleneck classification; the ledger
        # view with the fit's own cache entry (pinned == cache_bytes
        # within 1%); and the same-run OTPU_PROF on/off step A/B (< 2%)
        "goodput": goodput_rec,
        "ledger": ledger_rec,
        "pure_step_ms_prof": pure_step_ms_prof,
        "prof_overhead_pct": prof_overhead_pct,
        "prof_ab_retried": prof_ab_retried,
        "prof_overhead_pct_first": prof_overhead_pct_first,
        "h2d_blocked_gbps": h2d_blocked_gbps,
        **({"warm_skipped": warm_skipped} if warm_skipped else {}),
        # overflow diagnostics: did the HBM chunk cache degrade, and what
        # actually fed the replay epochs ('fused'|'hbm'|'disk'|'stream')
        "cache_overflow": stage_times.get("cache_overflow"),
        "replay_source": stage_times.get("replay_source"),
        "disk_replay_group": stage_times.get("disk_replay_group"),
        "spill_s": (round(stage_times["spill_s"], 2)
                    if "spill_s" in stage_times else None),
        "input_gbps": round(n_rows * row_bytes / wall / 1e9, 3),
        "device_hbm_gbps_est": hbm_gbps,
        "final_logloss": (None if model.final_loss_ is None
                          else round(model.final_loss_, 4)),
        "holdout_logloss": round(ev["logloss"], 4) if "logloss" in ev else None,
        "holdout_accuracy": round(ev["accuracy"], 4) if "accuracy" in ev else None,
        "holdout_auc": (round(ev["auc"], 4) if "auc" in ev else None),
    }


def _traced_requests_total() -> int:
    """Current otpu_traced_requests_total (obs/context.py coverage
    counter) — the serving/overload configs delta this around their
    measured windows."""
    from orange3_spark_tpu.obs.registry import REGISTRY

    m = REGISTRY.get("otpu_traced_requests_total")
    return int(m.total()) if m is not None else 0


def bench_serving(n_rows: int, *, dims: int = 1 << 18) -> dict:
    """Serving bench (serve/ subsystem): the predict hot path on the Criteo
    CTR model under a MIXED-batch-size request trace.

    Three phases over the same deterministic trace of request sizes
    (log-uniform 16..8192 rows — the "millions of users" shape: many
    concurrent small/medium batches, few analytical ones):

      raw       no ServingContext — every distinct request size compiles
                its own XLA program (the pathology this PR removes);
      bucketed  ServingContext with the default pow2 ladder, warmed —
                requests pad to a handful of bucket shapes sharing AOT
                executables (warmup compiles COUNT toward its recompile
                total: the claim is fewer compiles, not hidden ones);
      coalesced bucketed + micro-batcher, the trace's small requests
                submitted from a thread pool — measures the merge factor
                and the coalesced throughput.

    Headline value = bucketed serving rows/sec/chip; `recompiles` vs
    `recompiles_unbucketed` carries the ISSUE's >=5x acceptance criterion;
    p50_ms/p99_ms are per-request latencies (the raw p99 shows the
    compile spikes, the bucketed p99 shows none after warmup)."""
    import concurrent.futures

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import csv_raw_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.serve import BucketLadder, ServingContext
    from orange3_spark_tpu.utils.profiling import (
        install_compile_counter, reset_serve_counters, serve_counters,
        xla_compile_count,
    )

    path = ensure_criteo_csv(n_rows)
    session = TpuSession.builder_get_or_create()
    n_chips = session.n_devices
    compile_counter_live = install_compile_counter()

    # quick fit on the CSV head — the model under serve is the bench's
    # REAL CTR model (hashed-sparse logreg), just not fitted to convergence
    # (serving latency does not depend on fit quality)
    fit_chunks = 4
    def head_source():
        it = csv_raw_chunk_source(path, chunk_rows=chunk_rows_for(n_rows))()
        for i, c in enumerate(it):
            if i >= fit_chunks:
                break
            yield c
    est = StreamingHashedLinearEstimator(
        n_dims=dims, n_dense=N_DENSE, n_cat=N_CAT, epochs=1,
        step_size=STEP_SIZE, chunk_rows=chunk_rows_for(n_rows),
        label_in_chunk=True,
    )
    _log(f"[serving] fitting the CTR model on {fit_chunks} chunks ...")
    model = est.fit_stream(head_source, session=session)

    # request pool: 512k parsed rows, label column stripped (raw chunks
    # are plain [n, 1+39] f32 arrays, label first — label_in_chunk layout)
    pool = []
    for chunk in head_source():
        pool.append(np.asarray(chunk)[:, 1:])
        if sum(p.shape[0] for p in pool) >= (1 << 19):
            break
    pool = np.ascontiguousarray(
        np.concatenate(pool)[: 1 << 19].astype(np.float32))

    # deterministic mixed-size trace: log-uniform over [16, 8192] — many
    # distinct sizes (the raw path compiles one program per distinct size)
    rng = np.random.default_rng(11)
    n_requests = int(os.environ.get("OTPU_SERVE_REQUESTS", "120"))
    max_req = min(8192, pool.shape[0])
    if max_req < 16:
        raise SystemExit(
            f"--rows {n_rows} leaves only a {pool.shape[0]}-row request "
            "pool; the serving trace needs at least 16 rows")
    sizes = np.exp(
        rng.uniform(np.log(16), np.log(max_req), n_requests)).astype(np.int64)
    offs = rng.integers(0, pool.shape[0] - int(sizes.max()) + 1, len(sizes))
    trace = [(int(o), int(s)) for o, s in zip(offs, sizes)]
    _log(f"[serving] trace: {len(trace)} requests, "
         f"{len(set(s for _, s in trace))} distinct sizes, "
         f"{sum(s for _, s in trace)} total rows")

    def run_trace() -> tuple[list, float]:
        lat = []
        t0 = time.perf_counter()
        for off, sz in trace:
            t1 = time.perf_counter()
            out = model.predict(pool[off:off + sz])
            assert out.shape[0] == sz
            lat.append((time.perf_counter() - t1) * 1e3)
        return lat, time.perf_counter() - t0

    def pctl(lat, q):
        return round(float(np.percentile(np.asarray(lat), q)), 3)

    total_rows = sum(s for _, s in trace)

    # ---- phase 1: raw (unbucketed) — per-shape jit compiles ----
    _log("[serving] raw (unbucketed) trace ...")
    c0 = xla_compile_count()
    lat_raw, wall_raw = run_trace()
    recompiles_raw = xla_compile_count() - c0

    # ---- phase 2: bucketed + warmed AOT cache ----
    from orange3_spark_tpu.obs import flight

    ladder = BucketLadder(min_bucket=256, max_bucket=1 << 14)
    reset_serve_counters()
    traced0 = _traced_requests_total()
    flight0 = flight.bundles_written()
    ctx = ServingContext(ladder)
    with ctx:
        _log("[serving] warmup (AOT-compiling the bucket ladder) ...")
        c0 = xla_compile_count()
        t0 = time.perf_counter()
        warm = ctx.warmup(model, n_cols=pool.shape[1],
                          kinds=("array",), session=session)
        warmup_s = time.perf_counter() - t0
        _log(f"[serving] bucketed trace (warmed {warm['compiled']} "
             f"buckets in {warmup_s:.1f}s) ...")
        lat_b, wall_b = run_trace()
        recompiles_b = xla_compile_count() - c0   # warmup compiles INCLUDED
        sc = serve_counters()
    # per-request trace coverage (obs/context.py): every bucketed-phase
    # request should have minted a trace id at its serving entry
    traced_requests = _traced_requests_total() - traced0

    # ---- phase 3: bucketed + micro-batch, concurrent small requests ----
    small = [(o, s) for o, s in trace if s <= 1024] * 2
    mb_rows = sum(s for _, s in small)
    with ServingContext(ladder, micro_batch=True, max_batch=8192,
                        max_wait_ms=2.0) as ctx_mb:
        ctx_mb.warmup(model, n_cols=pool.shape[1], kinds=("array",),
                      session=session)
        reset_serve_counters()
        _log(f"[serving] coalesced trace ({len(small)} concurrent "
             f"requests) ...")
        with concurrent.futures.ThreadPoolExecutor(16) as ex:
            t0 = time.perf_counter()
            futs = [ex.submit(model.predict, pool[o:o + s]) for o, s in small]
            for f in futs:
                f.result()
            wall_mb = time.perf_counter() - t0
    mb = serve_counters()

    rate = total_rows / wall_b / n_chips
    return {
        "metric": "criteo_serving_predict_rows_per_sec_per_chip",
        "value": round(rate, 1),
        "unit": "rows/s/chip",
        "vs_baseline": None,   # no published serving reference (BASELINE.json)
        "baseline_value": None,
        "baseline_note": ("no published serving reference exists "
                          "(BASELINE.json empty mount); vs_baseline is null "
                          "by construction"),
        **device_fields(),
        "rows": n_rows,
        "requests": len(trace),
        "distinct_sizes": len(set(s for _, s in trace)),
        "trace_rows": total_rows,
        # ---- the acceptance-criterion pair ----
        "recompiles": recompiles_b,
        "recompiles_unbucketed": recompiles_raw,
        "compile_reduction": (round(recompiles_raw / recompiles_b, 2)
                              if recompiles_b else None),
        "compile_counter": ("jax.monitoring" if compile_counter_live
                            else "unavailable"),
        # ---- latency/throughput, bucketed serving path ----
        "p50_ms": pctl(lat_b, 50),
        "p99_ms": pctl(lat_b, 99),
        "wall_s": round(wall_b, 3),
        "warmup_s": round(warmup_s, 2),
        "warmup_buckets": warm["compiled"],
        "bucket_hits": sc["bucket_hits"],
        "bucket_misses": sc["bucket_misses"],
        "aot_hits": sc["aot_hits"],
        "pad_overhead": (round(sc["pad_overhead"], 3)
                         if sc["pad_overhead"] else None),
        # ---- raw-path comparison ----
        "p50_ms_unbucketed": pctl(lat_raw, 50),
        "p99_ms_unbucketed": pctl(lat_raw, 99),
        "wall_s_unbucketed": round(wall_raw, 3),
        "unbucketed_rows_per_sec_per_chip": round(
            total_rows / wall_raw / n_chips, 1),
        # ---- micro-batcher phase ----
        "mb_requests": mb["mb_requests"],
        "mb_batches": mb["mb_batches"],
        "mb_merge_factor": (round(mb["mb_merge_factor"], 2)
                            if mb["mb_merge_factor"] else None),
        "mb_rows_per_sec_per_chip": round(mb_rows / wall_mb / n_chips, 1),
        # ---- trace-context + flight-recorder coverage (ISSUE 9) ----
        "traced_requests": traced_requests,
        "trace_coverage": round(traced_requests / len(trace), 3),
        "flight_bundles_written": flight.bundles_written() - flight0,
    }


def bench_dense_logreg() -> dict:
    """Secondary bench: dense in-memory L-BFGS LogReg."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.logistic_regression import LogisticRegression

    n_rows, n_features, n_iters = 4_000_000, 40, 20
    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n_rows, n_features), dtype=np.float32)
    true_w = rng.standard_normal((n_features,)).astype(np.float32)
    y = (X @ true_w + 0.5 * rng.standard_normal(n_rows).astype(np.float32) > 0
         ).astype(np.float32)
    domain = Domain(
        [ContinuousVariable(f"f{i}") for i in range(n_features)],
        DiscreteVariable("click", ("0", "1")),
    )
    table = TpuTable.from_numpy(domain, X, y, session=session)
    est = LogisticRegression(
        max_iter=n_iters, tol=0.0, reg_param=1e-6, compute_dtype="bfloat16"
    )
    # warm-up, DRAINED: an unblocked warm fit's async tail would queue
    # ahead of the timed fit (the bias root-caused in bench_suite.py)
    jax.block_until_ready(est.fit(table).state_pytree)
    t0 = time.perf_counter()
    model = est.fit(table)
    jax.block_until_ready(model.state_pytree)
    dt = time.perf_counter() - t0
    iters = model.n_iter_ or n_iters
    v = n_rows * iters / dt / session.n_devices
    return {
        "metric": "logreg_fit_rows_per_sec_per_chip",
        "value": round(v, 1),
        "unit": "rows/s/chip",
        "vs_baseline": round(v / SPARK_PROXY_ROWS_PER_SEC_PER_CHIP, 3),
        "baseline_value": SPARK_PROXY_ROWS_PER_SEC_PER_CHIP,
        "baseline_note": BASELINE_NOTE,
        **device_fields(),
    }


def bench_fault(*, rows: int = 262_144, epochs: int = 4) -> dict:
    """Resilience A/B (docs/resilience.md): the SAME small streaming fit
    runs clean and then under injected faults (transient chunk-source
    IOErrors absorbed by bounded retries + straggler chunks), reporting
    ``recovery_overhead_pct`` — the wall-clock price of surviving the
    faults — and asserting the recovered fit is BITWISE equal to the
    fault-free one (the whole point: recovery must not change the
    numbers). A third mini-fit demonstrates the dispatch watchdog: a
    wedged dispatch raises a typed DispatchWedgedError within its budget
    instead of hanging the harness (the round-4 rc=124 signature)."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source,
    )
    from orange3_spark_tpu.resilience import (
        DispatchWedgedError, inject_faults,
    )
    from orange3_spark_tpu.utils.profiling import (
        reset_resilience_counters, resilience_counters,
    )

    session = TpuSession.builder_get_or_create()
    chunk_rows = 1 << 14
    n_features = 16
    rng = np.random.default_rng(0)
    X = rng.standard_normal((rows, n_features)).astype(np.float32)
    w_true = rng.standard_normal(n_features).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)
    est_kw = dict(loss="logistic", epochs=epochs, step_size=0.05,
                  chunk_rows=chunk_rows)
    src = array_chunk_source(X, y, chunk_rows=chunk_rows)

    def fit():
        m = StreamingLinearEstimator(**est_kw).fit_stream(
            src, n_features=n_features, session=session,
            cache_device=True,
        )
        jax.block_until_ready(m.coef)
        return m

    fit()                                   # warm-up: compile out of band
    t0 = time.perf_counter()
    ref = fit()
    wall_clean = time.perf_counter() - t0

    reset_resilience_counters()
    # transient faults on two epoch-1 chunks (fail-twice-then-succeed,
    # absorbed by retry) + a mild straggler on every 8th chunk; short
    # backoff so the overhead number measures RECOVERY, not sleep policy
    os.environ.setdefault("OTPU_RETRY_BASE_S", "0.02")
    t0 = time.perf_counter()
    with inject_faults("source_io:every=7,fails=2;"
                       "slow_source:every=8,delay_ms=5"):
        faulted = fit()
    wall_fault = time.perf_counter() - t0
    res = resilience_counters()
    parity = bool(np.array_equal(np.asarray(ref.coef),
                                 np.asarray(faulted.coef)))

    # watchdog demo: the first guarded sync of a tiny fit wedges for 30 s;
    # the budget converts the hang into a typed error in ~0.25 s. The
    # demo fit's chunk size guarantees >= 20 steps whatever --rows/
    # --epochs chose, so the period-16 guarded sync always runs
    watchdog_raised = False
    wedge_kw = dict(est_kw, chunk_rows=max(256, rows * epochs // 20))
    old_budget = os.environ.get("OTPU_DISPATCH_BUDGET_S")
    os.environ["OTPU_DISPATCH_BUDGET_S"] = "0.25"
    try:
        with inject_faults("wedge:at=1,hold_s=30"):
            try:
                StreamingLinearEstimator(**wedge_kw).fit_stream(
                    src, n_features=n_features, session=session)
            except DispatchWedgedError:
                watchdog_raised = True
    finally:
        if old_budget is None:
            os.environ.pop("OTPU_DISPATCH_BUDGET_S", None)
        else:
            os.environ["OTPU_DISPATCH_BUDGET_S"] = old_budget

    v = rows * epochs / wall_fault / session.n_devices
    return {
        "metric": "fault_recovery_streaming_fit_rows_per_sec_per_chip",
        "value": round(v, 1),
        "unit": "rows/s/chip",
        # a resilience A/B has no external baseline: the clean arm IS the
        # denominator, reported as recovery_overhead_pct
        "vs_baseline": None,
        **device_fields(),
        "rows": rows,
        "epochs": epochs,
        "wall_clean_s": round(wall_clean, 3),
        "wall_fault_s": round(wall_fault, 3),
        "recovery_overhead_pct": round(
            100.0 * (wall_fault - wall_clean) / max(wall_clean, 1e-9), 1),
        "faults_injected": res["faults_injected"],
        "retries": res["retries"],
        "retry_wait_s": round(res["retry_wait_s"], 3),
        "parity_bitwise": parity,
        "watchdog_raised": watchdog_raised,
    }


def bench_overload(*, requests: int = 64, service_ms: float = 25.0) -> dict:
    """Overload-protection A/B (docs/resilience.md, resilience/overload.py):
    an OPEN-LOOP burst of mixed-size predict requests arrives faster than
    the (injected-slow) serving path can drain, raw vs
    admission-controlled.

      raw       OTPU_RESILIENCE=0 — the legacy unbounded queue: every
                request eventually completes, but p99 is the whole
                backlog's service time (queueing-theory blowup);
      admitted  admission control with a 120 ms request deadline — a
                request whose projected queue wait exceeds its deadline
                sheds IMMEDIATELY with a typed OverloadShedError, the
                adaptive coalescer grows its merge window to drain the
                rest, and completed-request p99 stays bounded.

    The injected ``overload:delay_ms`` fault makes per-dispatch service
    time deterministic, so the A/B measures the CONTROL LOGIC, not the
    host's XLA latency du jour. The line also drills the circuit breaker
    (a flaky-AOT backend re-admitted through half-open where the old
    blacklist stayed dead) and the memory-pressure brownout ladder (an
    injected mem_pressure fraction degrades the HBM chunk cache instead
    of dying). ``p99_bound_factor`` (raw p99 / admitted p99), goodput and
    shed fraction are the headline fields; zero hung or lost futures is
    part of the claim."""
    import concurrent.futures

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source,
    )
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.resilience import (
        OverloadShedError, inject_faults,
    )
    from orange3_spark_tpu.resilience.overload import (
        current_brownout_level, shed_total,
    )
    from orange3_spark_tpu.serve import BucketLadder, ServingContext

    session = TpuSession.builder_get_or_create()
    n_chips = session.n_devices
    n_dense, n_cat = 4, 4
    rng = np.random.default_rng(7)
    rows_fit = 1 << 14
    X = np.concatenate([
        rng.standard_normal((rows_fit, n_dense)).astype(np.float32),
        rng.integers(0, 1000, (rows_fit, n_cat)).astype(np.float32),
    ], axis=1)
    y = (rng.random(rows_fit) < 0.3).astype(np.float32)
    _log("[overload] fitting the tiny CTR model ...")
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 14, n_dense=n_dense, n_cat=n_cat, epochs=1,
        step_size=0.05, chunk_rows=4096,
    ).fit_stream(array_chunk_source(X, y, chunk_rows=4096), session=session)

    # deterministic open-loop burst: mixed sizes, 2 ms arrival spacing —
    # far faster than the injected ~25 ms/dispatch service rate
    sizes = np.exp(rng.uniform(np.log(64), np.log(256), requests)
                   ).astype(np.int64)
    offs = rng.integers(0, rows_fit - int(sizes.max()), requests)
    stagger_s = 0.002
    ladder = BucketLadder(min_bucket=64, max_bucket=1 << 12)

    def run_arm(env: dict, label: str) -> dict:
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        lat_ok, lat_shed, lost = [], [], 0
        try:
            with ServingContext(ladder, micro_batch=True, max_batch=256,
                                max_wait_ms=1.0) as ctx:
                ctx.warmup(model, n_cols=n_dense + n_cat,
                           kinds=("array",), session=session)

                def one(i: int):
                    time.sleep(i * stagger_s)    # the arrival schedule
                    o, s = int(offs[i]), int(sizes[i])
                    t0 = time.perf_counter()
                    try:
                        out = model.predict(X[o:o + s])
                        assert out.shape[0] == s
                        return "ok", (time.perf_counter() - t0) * 1e3
                    except OverloadShedError:
                        return "shed", (time.perf_counter() - t0) * 1e3

                _log(f"[overload] {label} arm: {requests} requests ...")
                t0 = time.perf_counter()
                with inject_faults(f"overload:delay_ms={service_ms}"):
                    # no `with` block: shutdown(wait=False) — a genuinely
                    # hung future must be REPORTED as hung_futures, not
                    # deadlock the bench joining its blocked thread
                    ex = concurrent.futures.ThreadPoolExecutor(requests)
                    try:
                        futs = [ex.submit(one, i) for i in range(requests)]
                        done, pending = concurrent.futures.wait(
                            futs, timeout=120.0)
                        lost = len(pending)
                        for f in done:
                            kind, ms = f.result()
                            (lat_ok if kind == "ok" else lat_shed).append(ms)
                    finally:
                        ex.shutdown(wait=False)
                wall = time.perf_counter() - t0
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return {"lat_ok": lat_ok, "sheds": len(lat_shed), "lost": lost,
                "wall_s": wall, "completed": len(lat_ok),
                "rows_total": int(sizes.sum())}

    def pctl(lat, q):
        return round(float(np.percentile(np.asarray(lat), q)), 3)

    from orange3_spark_tpu.obs import flight

    flight0 = flight.bundles_written()
    # ---- arm 1: legacy unbounded (the kill-switch contract) ----
    raw = run_arm({"OTPU_RESILIENCE": "0"}, "raw (OTPU_RESILIENCE=0)")
    # ---- arm 2: admission-controlled ----
    shed0 = shed_total()
    traced0 = _traced_requests_total()
    adm = run_arm({
        "OTPU_RESILIENCE": "1",
        "OTPU_ADMISSION_DEADLINE_S": "0.1",
        "OTPU_ADMISSION_SERVICE_MS": str(service_ms),
    }, "admission-controlled")
    typed_sheds = shed_total() - shed0
    traced_requests = _traced_requests_total() - traced0

    # ---- circuit-breaker drill: flaky AOT backend re-admitted ----
    _log("[overload] circuit-breaker half-open drill ...")
    clk = [0.0]
    os.environ.setdefault("OTPU_RETRY_BASE_S", "0.02")
    breaker_readmitted = False
    with ServingContext(ladder, breaker_clock=lambda: clk[0]) as ctx2:
        with inject_faults("aot_build:fails=4,key=array"):
            model.predict(X[:64])        # build exhausts retries -> open
        st = ctx2.breaker_states()
        was_open = st.get("HashedLinearModel:array") == "open"
        clk[0] += 30.0                   # past the seeded cooldown
        model.predict(X[:64])            # half-open probe build succeeds
        breaker_readmitted = (
            was_open and ctx2.breaker_states()
            .get("HashedLinearModel:array") == "closed")

    # ---- brownout drill: injected memory pressure degrades, not dies ----
    _log("[overload] memory-pressure brownout drill ...")
    Xs = rng.standard_normal((8192, 8)).astype(np.float32)
    ys = (Xs @ rng.standard_normal(8).astype(np.float32) > 0
          ).astype(np.float32)
    with inject_faults("mem_pressure:frac=0.97,after=2"):
        m2 = StreamingLinearEstimator(
            loss="logistic", epochs=2, step_size=0.05, chunk_rows=1024,
        ).fit_stream(array_chunk_source(Xs, ys, chunk_rows=1024),
                     n_features=8, session=session, cache_device=True)
        jax.block_until_ready(m2.coef)
    brownout_reached = current_brownout_level()

    p99_raw = pctl(raw["lat_ok"], 99) if raw["lat_ok"] else None
    p99_adm = pctl(adm["lat_ok"], 99) if adm["lat_ok"] else None
    factor = (round(p99_raw / p99_adm, 2)
              if p99_raw and p99_adm else None)
    goodput_rows = adm["rows_total"]
    return {
        "metric": "overload_admission_p99_bound_factor",
        "value": factor if factor is not None else 0,
        "unit": "x",
        # an overload A/B has no external baseline: the raw arm IS the
        # denominator, reported as p99_bound_factor
        "vs_baseline": None,
        **device_fields(),
        "requests": requests,
        "service_ms_injected": service_ms,
        # ---- the acceptance-criterion fields ----
        "p99_ms_admitted": p99_adm,
        "p50_ms_admitted": pctl(adm["lat_ok"], 50) if adm["lat_ok"] else None,
        "p99_ms_raw": p99_raw,
        "p50_ms_raw": pctl(raw["lat_ok"], 50) if raw["lat_ok"] else None,
        "p99_bound_factor": factor,
        "sheds": adm["sheds"],
        "typed_sheds": typed_sheds,
        "shed_fraction": round(adm["sheds"] / requests, 3),
        "completed": adm["completed"],
        "hung_futures": adm["lost"],
        "lost_futures": requests - adm["completed"] - adm["sheds"]
        - adm["lost"],
        # completed-request rows (avg size x completes) over the arm wall
        "goodput_rows_per_s_per_chip": round(
            (goodput_rows / requests) * adm["completed"]
            / adm["wall_s"] / n_chips, 1),
        # ---- the legacy (kill-switch) contract ----
        "legacy_unbounded": (raw["sheds"] == 0 and raw["lost"] == 0
                             and raw["completed"] == requests),
        "raw_wall_s": round(raw["wall_s"], 3),
        "admitted_wall_s": round(adm["wall_s"], 3),
        # ---- breaker + brownout drills ----
        "breaker_readmitted": breaker_readmitted,
        "brownout_level_reached": brownout_reached,
        # ---- trace-context + flight-recorder coverage (ISSUE 9) ----
        "traced_requests": traced_requests,
        "trace_coverage": round(traced_requests / requests, 3),
        "flight_bundles_written": flight.bundles_written() - flight0,
    }


def bench_fleet(*, requests: int = 64, service_ms: float = 30.0,
                straggler_ms: float = 400.0) -> dict:
    """Serving-fleet A/B (fleet/ subsystem, docs/serving.md §fleet): the
    multi-replica layer's four claims, measured over REAL local replica
    subprocesses:

      scaling   an open-ended closed-loop burst against 1 replica vs
                OTPU_FLEET_REPLICAS replicas — aggregate throughput must
                scale (>= 2.5x is the acceptance bar). Replicas pin
                JAX_PLATFORMS=cpu and OTPU_ADMISSION_MAX_INFLIGHT=1 with
                a deterministic injected per-dispatch service time
                (``overload:delay_ms`` — one replica IS one accelerator,
                dispatches serialize on it), so the A/B measures the
                fleet mechanics, not the 1-core host's XLA latency;
      hedging   the same burst against a fleet with ONE injected
                straggler replica (its own OTPU_FAULT_SPEC carries a
                ~13x service delay), unhedged vs EWMA-p95 tail hedging —
                hedged p99 <= 0.5x unhedged p99 is the bar;
      kill      SIGKILL a replica mid-burst: zero lost / zero hung
                requests (failover-with-exclusion absorbs the burst,
                stragglers fail TYPED), the supervisor restarts it, the
                router re-admits it through /readyz + breaker half-open;
      rollout   a rolling version swap under continuous traffic with
                ZERO failed requests, then a poisoned version that
                auto-rolls back leaving CURRENT (and traffic) untouched.

    Plus the cross-process trace claim: every scaling-burst response
    echoed the router-minted trace id out of the replica's own obs
    context (trace_coverage == 1.0), and the OTPU_FLEET=0 kill-switch
    serves bitwise-identically on the single-process path."""
    import concurrent.futures
    import shutil
    import threading

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.fleet import FleetFrontend
    from orange3_spark_tpu.fleet.rollout import (
        Rollout, publish_version, read_current,
    )
    from orange3_spark_tpu.fleet.router import FleetRouter, HedgeSchedule
    from orange3_spark_tpu.fleet.rpc import (
        NoReplicaAvailableError, ReplicaDrainingError,
        ReplicaUnavailableError,
    )
    from orange3_spark_tpu.fleet.supervisor import ReplicaManager
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.obs.registry import REGISTRY
    from orange3_spark_tpu.utils import knobs

    session = TpuSession.builder_get_or_create()
    n_chips = session.n_devices
    # the scaling/hedge/kill/rollout arms predate the ISSUE-17 coalescer
    # and their bars (scaling_factor, hedged p99, per-request failover
    # accounting) are defined over UNmerged dispatches — pin it off here
    # and measure it in its own wire arms below, where merging is the
    # claim instead of a confound
    saved_coalesce = os.environ.get("OTPU_FLEET_COALESCE")
    os.environ["OTPU_FLEET_COALESCE"] = "0"
    rng = np.random.default_rng(7)
    n_dense = n_cat = 4
    rows_fit = 1 << 13

    def make_xy(seed):
        r = np.random.default_rng(seed)
        X = np.concatenate([
            r.standard_normal((rows_fit, n_dense)).astype(np.float32),
            r.integers(0, 500, (rows_fit, n_cat)).astype(np.float32),
        ], axis=1)
        y = (r.random(rows_fit) < 0.3).astype(np.float32)
        return X, y

    X, y = make_xy(7)

    def fit(epochs):
        return StreamingHashedLinearEstimator(
            n_dims=1 << 12, n_dense=n_dense, n_cat=n_cat, epochs=epochs,
            step_size=0.05, chunk_rows=2048,
        ).fit_stream(array_chunk_source(X, y, chunk_rows=2048),
                     session=session)

    _log("[fleet] fitting the CTR model ...")
    model = fit(1)
    root = os.path.join(DATA_DIR,
                        f"fleet_models_{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    publish_version(model, root, n_cols=n_dense + n_cat)
    n_replicas = int(knobs.get_int("OTPU_FLEET_REPLICAS"))
    # replicas model one-accelerator-per-replica: CPU backend (never
    # contend for the parent's device), serialized dispatches, and the
    # deterministic injected service time the A/B is judged on
    base_env = {"JAX_PLATFORMS": "cpu",
                "OTPU_ADMISSION_MAX_INFLIGHT": "1",
                "OTPU_FAULT_SPEC": f"overload:delay_ms={service_ms}"}
    sizes = np.exp(rng.uniform(np.log(64), np.log(256), requests)
                   ).astype(np.int64)
    offs = rng.integers(0, rows_fit - int(sizes.max()), requests)
    burst_rows = int(sizes.sum())

    def counter_total(name):
        m = REGISTRY.get(name)
        return int(m.total()) if m is not None else 0

    def burst(router, n_req=requests, threads=8):
        lat, outcomes = [], []

        def one(i):
            o, s = int(offs[i % requests]), int(sizes[i % requests])
            t0 = time.perf_counter()
            try:
                # shape check on the hot path; bitwise parity is pinned
                # by the kill arm / tests, not per burst request
                out = router.predict(X[o:o + s])
            except (ReplicaUnavailableError, ReplicaDrainingError,
                    NoReplicaAvailableError):
                return "typed", (time.perf_counter() - t0) * 1e3
            dt = (time.perf_counter() - t0) * 1e3
            return ("ok" if out.shape[0] == s else "wrong"), dt

        t0 = time.perf_counter()
        # no `with` block: shutdown(wait=False) — a genuinely hung RPC
        # must be REPORTED in 'pending', not deadlock the bench joining
        # its blocked worker (the bench_overload PR-8 convention)
        ex = concurrent.futures.ThreadPoolExecutor(threads)
        try:
            futs = [ex.submit(one, i) for i in range(n_req)]
            done, pending = concurrent.futures.wait(futs, timeout=300.0)
        finally:
            ex.shutdown(wait=False)
        wall = time.perf_counter() - t0
        for f in done:
            kind, ms = f.result()
            outcomes.append(kind)
            if kind == "ok":
                lat.append(ms)
        return {"lat": lat, "outcomes": outcomes, "wall_s": wall,
                "pending": len(pending)}

    def pctl(lat, q):
        return round(float(np.percentile(np.asarray(lat), q)), 3)

    # ---- arm 1: single replica ----
    def single_arm():
        _log("[fleet] single-replica arm ...")
        mgr1 = ReplicaManager(root, n_replicas=1, ladder_max=1 << 9,
                              env=base_env)
        mgr1.start()
        assert mgr1.wait_ready(timeout_s=120), "single replica never ready"
        r1 = FleetRouter(mgr1.endpoints(), hedging=False)
        r1.refresh()
        b = burst(r1)
        r1.close()
        mgr1.stop_all()
        assert b["outcomes"].count("ok") == requests, b["outcomes"]
        return b

    b1 = single_arm()
    thr_1 = burst_rows / b1["wall_s"] / n_chips

    # ---- arm 2: N replicas (+ kill + rollout on the same fleet) ----
    _log(f"[fleet] {n_replicas}-replica arm ...")
    mgrN = ReplicaManager(root, n_replicas=n_replicas, ladder_max=1 << 9,
                          env=base_env)
    mgrN.start()
    assert mgrN.wait_ready(timeout_s=180), "fleet never ready"
    rN = FleetRouter(mgrN.endpoints(), hedging=False)
    rN.refresh()
    req0 = counter_total("otpu_fleet_requests_total")
    prop0 = counter_total("otpu_fleet_trace_propagated_total")
    bN = burst(rN)
    traced_requests = counter_total("otpu_fleet_requests_total") - req0
    propagated = counter_total("otpu_fleet_trace_propagated_total") - prop0
    thr_n = burst_rows / bN["wall_s"] / n_chips
    assert bN["outcomes"].count("ok") == requests, bN["outcomes"]
    scaling = thr_n / thr_1

    # structured re-measure (the obs/prof A/B one-retry policy): on a
    # loaded CI box one preemption stretch inside either arm's burst can
    # fake sub-linear scaling. A REAL scaling regression reproduces;
    # noise does not — so a first reading under the contract's 2.5x gate
    # earns exactly one re-measure of BOTH arms (a fresh single-replica
    # fleet, a second burst over the live N-replica fleet), the second
    # reading is the record, and both land in the JSON so a banked retry
    # is auditable, never silent.
    scaling_retried = False
    scaling_factor_first = None
    if scaling < 2.5:
        scaling_retried = True
        scaling_factor_first = round(scaling, 2)
        _log(f"[fleet] scaling {scaling:.2f}x under the 2.5x gate -- "
             "re-measuring both arms once")
        b1 = single_arm()
        thr_1 = burst_rows / b1["wall_s"] / n_chips
        bN = burst(rN)
        assert bN["outcomes"].count("ok") == requests, bN["outcomes"]
        thr_n = burst_rows / bN["wall_s"] / n_chips
        scaling = thr_n / thr_1

    # ---- fleet-telemetry arm (ISSUE 11): collector A/B + SLO drill ----
    # collector overhead: the SAME burst with the scrape loop on vs off,
    # interleaved pairs with min wall per arm (the criteo obs-A/B
    # convention — the injected service time makes walls service-bound,
    # so the scraper's host cost is the measurand, not XLA noise)
    _log("[fleet] collector-overhead A/B ...")
    from orange3_spark_tpu.obs import fleetobs as fobs

    col = fobs.FleetCollector(mgrN.endpoints(), router=rN, scrape_s=0.5)
    walls_on: list = []
    walls_off: list = []
    for _ in range(4):
        col.start()
        walls_on.append(burst(rN)["wall_s"])
        col.stop()
        walls_off.append(burst(rN)["wall_s"])
    wall_on, wall_off = min(walls_on), min(walls_off)
    collector_overhead_pct = round(
        (wall_on - wall_off) / wall_off * 100.0, 2)
    # one fresh sweep pins the aggregation + staleness view the record
    # embeds: every replica fresh, per-replica rpc counters summing to
    # at least the bursts this fleet absorbed. Staleness is captured
    # HERE, while the fleet lives — a post-teardown read would see every
    # replica minutes stale and bank a vacuous count
    fleet_digest = col.scrape_once()
    fleetz = col.fleetz()
    ages = [a for a in col.staleness().values() if a is not None]
    scrape_stale_n = len(col.stale_replicas())
    fleet_agg_rpc = fleetz["aggregates"].get(
        "otpu_fleet_rpc_requests_total", 0.0)

    # goodput & memory attribution (obs/prof.py, ISSUE 12): the parent's
    # CTR fit carries the goodput decomposition; the digest carries every
    # replica's per-owner device bytes (their serving executables) — the
    # fleet-wide view tools/fleet_top.py renders
    from orange3_spark_tpu.obs.prof import LEDGER as _LEDGER

    _fit_rep = getattr(model, "run_report_", None)
    _fit_rep_d = _fit_rep.to_dict() if _fit_rep is not None else {}
    goodput_rec = _fit_rep_d.get("goodput")
    ledger_rec = {
        "parent_owners": _LEDGER.owner_bytes(),
        "replicas": {r.replica: r.device_bytes
                     for r in fleet_digest.replicas},
    }

    # SLO burn drill: a deliberately-tight latency objective (p99 <= 1ms
    # against the injected 30ms service time) burns budget on every
    # request — the multi-window engine must page, and the alert must
    # write EXACTLY ONE rate-limited fleet incident bundle carrying
    # every live replica's flight pull
    _log("[fleet] SLO burn drill ...")
    fobs.reset_fleet_rate_limit()

    def _slo_bundles():
        m = REGISTRY.get("otpu_flight_bundles_total")
        if m is None:
            return 0
        return int(sum(v for k, v in m.per_label("reason").items()
                       if k.startswith("slo_")))

    slo_bundles0 = _slo_bundles()
    slo_engine = fobs.SLOEngine(
        fobs.parse_slo_spec("burn_drill:target=99.0,p99_ms=1"),
        fast_s=5.0, slow_s=20.0)
    rS = FleetRouter(mgrN.endpoints(), hedging=False, slo=slo_engine)
    rS.refresh()
    colS = fobs.FleetCollector(mgrN.endpoints(), router=rS,
                               slo=slo_engine, scrape_s=0.25)
    for _i in range(24):
        rS.predict(X[:64])
    slo_verdicts = slo_engine.evaluate()
    colS.scrape_once()
    colS.join_incident_dump()     # the dump runs on a dedicated thread
    rS.close()
    slo_alerts = len(slo_engine.alerts)
    fleet_incident_bundles = _slo_bundles() - slo_bundles0
    fleet_bundle_replicas = None
    if colS.last_incident_path:
        with open(colS.last_incident_path) as f:
            fb = json.load(f)
        fleet_bundle_replicas = len(fb.get("live_replicas", []))

    # kill-switch: OTPU_FLEETOBS=0 must serve bitwise-identically on the
    # bare PR-10 path (no collector thread, no span, no SLO sample)
    ref_fobs = np.asarray(rN.predict(X[:128]))
    saved_fobs = os.environ.get("OTPU_FLEETOBS")
    os.environ["OTPU_FLEETOBS"] = "0"
    try:
        off_fobs = np.asarray(rN.predict(X[:128]))
        col_off = fobs.FleetCollector(mgrN.endpoints()).start()
        fleetobs_parity = (bool(np.array_equal(ref_fobs, off_fobs))
                           and not col_off.active)
    finally:
        if saved_fobs is None:
            os.environ.pop("OTPU_FLEETOBS", None)
        else:
            os.environ["OTPU_FLEETOBS"] = saved_fobs

    # ---- kill arm: SIGKILL one replica mid-burst ----
    _log("[fleet] SIGKILL-mid-burst arm ...")
    # the reference answer comes from the HEALTHY FLEET, not the parent
    # process: replicas are pinned to CPU while the parent may sit on a
    # TPU backend, and a cross-backend bitwise compare would flip
    # threshold-adjacent labels — the kill arm's claim is that failover
    # answers match what the fleet answered before the kill
    expect64 = np.asarray(rN.predict(X[:64]))
    restarts0 = counter_total("otpu_fleet_replica_restarts_total")
    kill_req = max(24, requests // 2)
    kill_outcomes: list = []

    def kone(i):
        time.sleep(i * 0.008)
        try:
            out = rN.predict(X[:64])
            return "ok" if np.array_equal(out, expect64) else "wrong"
        except (ReplicaUnavailableError, ReplicaDrainingError,
                NoReplicaAvailableError):
            return "typed"
        except Exception:  # noqa: BLE001 - an UNTYPED escape is 'lost'
            return "lost"

    # shutdown(wait=False): a hung future is reported, never a deadlock
    ex = concurrent.futures.ThreadPoolExecutor(8)
    try:
        t_kill0 = time.perf_counter()
        futs = [ex.submit(kone, i) for i in range(kill_req)]
        time.sleep(0.1)
        mgrN.kill(0)
        done, pending = concurrent.futures.wait(futs, timeout=120.0)
        kill_hung = len(pending)
        kill_outcomes = [f.result() for f in done]
    finally:
        ex.shutdown(wait=False)
    deadline = time.monotonic() + 90
    readmitted = False
    while time.monotonic() < deadline:
        rN.refresh()
        ep = rN.endpoint(0)
        if ep.ready and ep.breaker.state() != "open":
            readmitted = True
            break
        time.sleep(0.25)
    kill_recovery_s = time.perf_counter() - t_kill0
    replica_restarted = (counter_total("otpu_fleet_replica_restarts_total")
                         > restarts0)

    # ---- rollout arm: zero-downtime swap + poisoned-version rollback ----
    _log("[fleet] rollout arm ...")
    model2 = fit(2)
    v2 = publish_version(model2, root, n_cols=n_dense + n_cat)
    stop = threading.Event()
    ro_fails: list = []
    ro_oks: list = []

    def traffic():
        while not stop.is_set():
            try:
                rN.predict(X[:64])
                ro_oks.append(1)
            except Exception as e:  # noqa: BLE001 - the claim is zero
                ro_fails.append(repr(e))
            time.sleep(0.01)

    th = threading.Thread(target=traffic)
    th.start()
    try:
        ro_res = Rollout(rN, root, canary_input=X[:16]).roll(v2)
    finally:
        stop.set()
        th.join(timeout=10)
    # the rolled-out fleet's own answer is the rollback reference (same
    # backend as every replica — see the kill arm's expect64 note)
    v2_ref = np.asarray(rN.predict(X[:64]))
    # poisoned version: a garbage payload must auto-roll back
    bad = os.path.join(root, ".staging-bad")
    os.makedirs(bad, exist_ok=True)
    with open(os.path.join(bad, "model.pkl"), "wb") as f:
        f.write(b"poisoned payload, not a pickle")
    bad_final = os.path.join(root, "v0099")
    os.replace(bad, bad_final)
    rb_res = Rollout(rN, root, canary_input=X[:16]).roll("v0099")
    current_after = read_current(root)
    # after the rolled-back roll the fleet must still answer exactly as
    # the completed v2 rollout did — nothing about the poisoned attempt
    # may have leaked into serving
    post_ok = bool(np.array_equal(np.asarray(rN.predict(X[:64])), v2_ref))
    rN.close()
    mgrN.stop_all()

    # ---- hedge arm: one injected straggler replica, unhedged vs hedged ----
    _log("[fleet] hedge arm (1 straggler) ...")
    strag_env = {n_replicas - 1: {
        "OTPU_FAULT_SPEC": f"overload:delay_ms={straggler_ms}"}}
    mgrH = ReplicaManager(root, n_replicas=n_replicas, ladder_max=1 << 9,
                          env=base_env, per_replica_env=strag_env)
    mgrH.start()
    assert mgrH.wait_ready(timeout_s=180), "hedge fleet never ready"
    rU = FleetRouter(mgrH.endpoints(), hedging=False)
    rU.refresh()
    bU = burst(rU)
    rU.close()
    hedges0 = counter_total("otpu_fleet_hedges_total")
    wins0 = counter_total("otpu_fleet_hedge_wins_total")
    rH = FleetRouter(mgrH.endpoints(), hedging=True,
                     schedule=HedgeSchedule(floor_ms=2 * service_ms))
    rH.refresh()
    bH = burst(rH)
    rH.close()
    mgrH.stop_all()
    hedges = counter_total("otpu_fleet_hedges_total") - hedges0
    hedge_wins = counter_total("otpu_fleet_hedge_wins_total") - wins0
    p99_u, p99_h = pctl(bU["lat"], 99), pctl(bH["lat"], 99)

    # ---- wire A/B arms (ISSUE 17): fresh-TCP vs keep-alive vs fastpath ----
    # a dedicated 1-replica fleet with NO injected service time: the
    # measurand is the WIRE (connection setup, body encode, coalescer
    # amortization), so the replica must answer as fast as it can. Arms
    # interleave round-robin and each arm keeps its min-round p50 (the
    # min-floor convention: OS scheduling noise inflates, never
    # deflates, so the floor is the honest per-arm number).
    _log("[fleet] wire A/B arms ...")
    mgrW = ReplicaManager(root, n_replicas=1, ladder_max=1 << 9,
                          env={"JAX_PLATFORMS": "cpu"})
    mgrW.start()
    assert mgrW.wait_ready(timeout_s=120), "wire replica never ready"
    WIRE_ARMS = {
        "fresh": {"OTPU_FLEET_FASTWIRE": "0"},
        "keepalive": {"OTPU_FLEET_FASTWIRE": "1", "OTPU_FLEET_SHM": "0",
                      "OTPU_FLEET_COALESCE": "0"},
        # the shipped fast path: pooled conns + SHM + cross-caller
        # coalescing (a 0.5 ms collect window lets a concurrent burst
        # merge before dispatch)
        "fastpath": {"OTPU_FLEET_FASTWIRE": "1", "OTPU_FLEET_SHM": "1",
                     "OTPU_FLEET_COALESCE": "1",
                     "OTPU_FLEET_COALESCE_WAIT_MS": "0.5"},
    }
    _WIRE_KEYS = sorted({k for env in WIRE_ARMS.values() for k in env}
                        | {"OTPU_FLEET_SHM_MIN_BYTES"})

    def _with_wire_env(env, fn):
        saved = {k: os.environ.get(k) for k in _WIRE_KEYS}
        for k in _WIRE_KEYS:
            os.environ.pop(k, None)
        os.environ.update(env)
        try:
            return fn()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def wire_burst(threads=16, per_thread=30, rows=64):
        router = FleetRouter(mgrW.endpoints(), hedging=False)
        router.refresh()
        for _ in range(5):
            router.predict(X[:rows])
        lat: list = []
        outcomes: list = []
        lock = threading.Lock()

        def worker():
            mine, outs = [], []
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    out = router.predict(X[:rows])
                    outs.append("ok" if out.shape[0] == rows
                                else "wrong")
                except (ReplicaUnavailableError, ReplicaDrainingError,
                        NoReplicaAvailableError):
                    outs.append("typed")
                except Exception:  # noqa: BLE001 - untyped escape = lost
                    outs.append("lost")
                mine.append((time.perf_counter() - t0) * 1e3)
            with lock:
                lat.extend(mine)
                outcomes.extend(outs)

        ts = [threading.Thread(target=worker) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120.0)
        hung = sum(1 for t in ts if t.is_alive())
        co = router.coalescer.stats()
        pool = {}
        for ep in router.endpoints:
            p = getattr(ep.client, "pool", None)
            if p is not None:
                s = p.stats()
                for k in ("opened", "reused", "stale_retries"):
                    pool[k] = pool.get(k, 0) + s[k]
        router.close()
        return {"lat": lat, "outcomes": outcomes, "hung": hung,
                "coalesce": co, "pool": pool}

    wire_rounds: dict = {name: [] for name in WIRE_ARMS}
    wire_last: dict = {}
    for _round in range(3):               # interleaved: 3 round-robins
        for name, env in WIRE_ARMS.items():
            res = _with_wire_env(env, wire_burst)
            wire_rounds[name].append(pctl(res["lat"], 50))
            wire_last[name] = res
    wire_p50 = {name: min(v) for name, v in wire_rounds.items()}
    co_members = wire_last["fastpath"]["coalesce"]["members"]
    co_dispatches = wire_last["fastpath"]["coalesce"]["dispatches"]
    coalesce_merge_factor = wire_last["fastpath"]["coalesce"][
        "merge_factor"]
    wire_outcomes = [o for r in wire_last.values() for o in r["outcomes"]]
    wire_hung = sum(r["hung"] for r in wire_last.values())
    conn_reuse = wire_last["fastpath"]["pool"]
    _reuse_total = conn_reuse.get("opened", 0) + conn_reuse.get("reused", 0)

    # FASTWIRE=0 bitwise parity: the same rows through the legacy wire
    # and through the fast path with SHM FORCED (floor 0 exercises the
    # segment codec even for this small payload) must match bit for bit
    def _wire_ref():
        router = FleetRouter(mgrW.endpoints(), hedging=False)
        router.refresh()
        try:
            return np.asarray(router.predict(X[:200]))
        finally:
            router.close()

    ref_legacy = _with_wire_env(WIRE_ARMS["fresh"], _wire_ref)
    ref_fast = _with_wire_env(
        dict(WIRE_ARMS["fastpath"], OTPU_FLEET_SHM_MIN_BYTES="0"),
        _wire_ref)
    fastwire_parity = bool(np.array_equal(ref_legacy, ref_fast))
    mgrW.stop_all()

    # ---- kill-switch parity: OTPU_FLEET=0 is the single-process path ----
    saved_fleet = os.environ.get("OTPU_FLEET")
    os.environ["OTPU_FLEET"] = "0"
    try:
        fe = FleetFrontend(model2)
        kill_switch_parity = bool(np.array_equal(
            fe.predict(X[:256]), model2.predict(X[:256])))
        kill_switch_local = fe.mode == "local" and fe.manager is None
        fe.close()
    finally:
        if saved_fleet is None:
            os.environ.pop("OTPU_FLEET", None)
        else:
            os.environ["OTPU_FLEET"] = saved_fleet
    shutil.rmtree(root, ignore_errors=True)
    if saved_coalesce is None:
        os.environ.pop("OTPU_FLEET_COALESCE", None)
    else:
        os.environ["OTPU_FLEET_COALESCE"] = saved_coalesce

    from orange3_spark_tpu.obs import flight

    return {
        "metric": "fleet_n_replica_scaling",
        "value": round(scaling, 2),
        "unit": "x",
        # a fleet A/B has no external baseline: the single-replica arm IS
        # the denominator, reported as the scaling factor
        "vs_baseline": None,
        "baseline_value": None,
        "baseline_note": ("single-replica arm of the same run is the "
                          "denominator (aggregate throughput scaling); no "
                          "published multi-replica reference exists "
                          "(BASELINE.json empty mount)"),
        **device_fields(),
        "replicas": n_replicas,
        "requests": requests,
        "burst_rows": burst_rows,
        "service_ms_injected": service_ms,
        # ---- scaling (the headline) ----
        "throughput_single_rows_per_s_per_chip": round(thr_1, 1),
        "throughput_fleet_rows_per_s_per_chip": round(thr_n, 1),
        "scaling_factor": round(scaling, 2),
        # one structured re-measure when the first reading lands under
        # the contract gate; both readings ride the record (auditable)
        "scaling_retried": scaling_retried,
        "scaling_factor_first": scaling_factor_first,
        "wall_single_s": round(b1["wall_s"], 3),
        "wall_fleet_s": round(bN["wall_s"], 3),
        # ---- hedging ----
        "straggler_ms_injected": straggler_ms,
        "p50_ms_unhedged": pctl(bU["lat"], 50),
        "p99_ms_unhedged": p99_u,
        "p50_ms_hedged": pctl(bH["lat"], 50),
        "p99_ms_hedged": p99_h,
        "hedged_p99_ratio": round(p99_h / p99_u, 3) if p99_u else None,
        "hedges_issued": hedges,
        "hedge_wins": hedge_wins,
        # ---- kill drill ----
        "kill_requests": kill_req,
        "kill_completed": kill_outcomes.count("ok"),
        "kill_typed_failures": kill_outcomes.count("typed"),
        "kill_wrong_results": kill_outcomes.count("wrong"),
        "kill_hung": kill_hung,
        # lost = a request that escaped with an UNTYPED error (done and
        # pending always partition the futures, so len-arithmetic could
        # never be nonzero — the claim is 'typed errors only')
        "kill_lost": kill_outcomes.count("lost"),
        "replica_restarted": replica_restarted,
        "killed_replica_readmitted": readmitted,
        "kill_recovery_s": round(kill_recovery_s, 2),
        # ---- rollout drill ----
        "rollout_outcome": ro_res["outcome"],
        "rollout_failed_requests": len(ro_fails),
        "rollout_traffic_requests": len(ro_oks),
        "rollout_version": ro_res["version"],
        "rollback_outcome": rb_res["outcome"],
        "rollback_current_untouched": current_after == v2,
        "rollback_post_traffic_ok": post_ok,
        # ---- cross-process trace propagation (acceptance) ----
        "traced_requests": traced_requests,
        "trace_coverage": (round(propagated / traced_requests, 3)
                           if traced_requests else None),
        "flight_bundles_written": flight.bundles_written(),
        # ---- fleet telemetry plane (ISSUE 11) ----
        "collector_overhead_pct": collector_overhead_pct,
        "wall_scrape_on_s": round(wall_on, 3),
        "wall_scrape_off_s": round(wall_off, 3),
        "scrape_stale_replicas": scrape_stale_n,
        "scrape_age_max_s": round(max(ages), 3) if ages else None,
        "fleet_agg_rpc_requests": fleet_agg_rpc,
        "fleet": {"aggregates": fleetz["aggregates"],
                  "replicas": fleetz["replicas"],
                  "digest": fleet_digest.to_dict()},
        "slo_alerts": slo_alerts,
        "slo_verdicts": slo_verdicts,
        "slo_burn_long": round(
            slo_verdicts[0]["rules"]["fast"]["burn_long"], 2),
        "slo_budget_remaining": slo_verdicts[0]["budget_remaining"],
        "fleet_incident_bundles": fleet_incident_bundles,
        "fleet_bundle_replicas": fleet_bundle_replicas,
        "fleet_bundle_path": colS.last_incident_path,
        "fleetobs_kill_switch_parity": fleetobs_parity,
        # ---- goodput & memory attribution (ISSUE 12) ----
        "goodput": goodput_rec,
        "ledger": ledger_rec,
        # ---- wire fast path (ISSUE 17) ----
        "wire_fresh_p50_ms": wire_p50["fresh"],
        "wire_keepalive_p50_ms": wire_p50["keepalive"],
        "wire_fastpath_p50_ms": wire_p50["fastpath"],
        "wire_keepalive_speedup": round(
            wire_p50["fresh"] / wire_p50["keepalive"], 3),
        # the acceptance ratio: keep-alive+SHM+coalesce p50 vs fresh-TCP
        # p50 on the same small concurrent predicts (bar: >= 3x)
        "wire_fastpath_speedup": round(
            wire_p50["fresh"] / wire_p50["fastpath"], 3),
        "coalesce_merge_factor": round(coalesce_merge_factor, 2),
        "coalesce_members": co_members,
        "coalesce_dispatches": co_dispatches,
        "coalesce_sheds": wire_last["fastpath"]["coalesce"]["sheds"],
        "wire_requests": len(wire_outcomes),
        "wire_ok": wire_outcomes.count("ok"),
        "wire_typed_failures": wire_outcomes.count("typed"),
        "wire_lost": wire_outcomes.count("lost"),
        "wire_wrong": wire_outcomes.count("wrong"),
        "wire_hung": wire_hung,
        "wire_conn_reuse_pct": round(
            100.0 * conn_reuse.get("reused", 0) / _reuse_total, 2)
            if _reuse_total else 0.0,
        "wire_conn_stale_retries": conn_reuse.get("stale_retries", 0),
        "fastwire_kill_switch_parity": fastwire_parity,
        # ---- kill-switch contract ----
        "kill_switch_local_parity": kill_switch_parity,
        "kill_switch_no_subprocesses": kill_switch_local,
    }


def bench_tenancy(*, service_ms: float = 20.0) -> dict:
    """Control-plane A/B (fleet/control.py, serve/tenancy.py): the
    multi-tenant fleet control plane's three claims.

      fairness   the SAME 2-tenant skewed burst (heavy offers 8x the
                 light tenant's load into a 2-slot admission controller)
                 first-come-first-served vs weighted-fair: under
                 OTPU_TENANCY=0 the light tenant's p99 is the heavy
                 backlog's service time; with OTPU_TENANT_SPEC giving
                 light weight 4 and capping heavy at 1 in-flight slot,
                 the burster sheds TYPED (TenantQuotaShedError carrying
                 tenant/usage/quota) while light p99 stays bounded —
                 >= 3x tighter is the acceptance bar;
      elasticity a real 1-replica fleet under closed-loop load: the
                 Autoscaler consumes the collector's digest through its
                 hysteresis bands, grows the fleet to >= 2 replicas via
                 the crash-restart spawn path, then — load gone, past
                 cooldown — drains back to min via drain-then-stop with
                 ZERO failed trickle requests during scale-down;
      parity     OTPU_TENANCY=0 + OTPU_AUTOSCALE=0 is the PR-19 fleet
                 bitwise: a scoped caller's predict matches the
                 unscoped answer bit-for-bit, no fair-share state is
                 ever built, and the autoscaler refuses to step.

    The injected ``overload:delay_ms`` makes per-dispatch service time
    deterministic (the bench_overload convention), so both A/Bs measure
    the CONTROL LOGIC, not the host's XLA latency du jour. Zero hung
    and zero lost requests across every arm is part of the claim."""
    import concurrent.futures
    import shutil
    import threading

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.fleet.control import Autoscaler
    from orange3_spark_tpu.fleet.rollout import publish_version
    from orange3_spark_tpu.fleet.router import FleetRouter
    from orange3_spark_tpu.fleet.rpc import (
        NoReplicaAvailableError, ReplicaDrainingError,
        ReplicaUnavailableError,
    )
    from orange3_spark_tpu.fleet.supervisor import ReplicaManager
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.obs import fleetobs as fobs
    from orange3_spark_tpu.resilience import OverloadShedError, inject_faults
    from orange3_spark_tpu.serve import BucketLadder, ServingContext
    from orange3_spark_tpu.serve.tenancy import (
        TenantQuotaShedError, tenant_scope,
    )

    session = TpuSession.builder_get_or_create()
    n_dense = n_cat = 4
    rng = np.random.default_rng(7)
    rows_fit = 1 << 13
    X = np.concatenate([
        rng.standard_normal((rows_fit, n_dense)).astype(np.float32),
        rng.integers(0, 500, (rows_fit, n_cat)).astype(np.float32),
    ], axis=1)
    y = (rng.random(rows_fit) < 0.3).astype(np.float32)
    _log("[tenancy] fitting the tiny CTR model ...")
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=n_dense, n_cat=n_cat, epochs=1,
        step_size=0.05, chunk_rows=2048,
    ).fit_stream(array_chunk_source(X, y, chunk_rows=2048), session=session)
    ladder = BucketLadder(min_bucket=64, max_bucket=1 << 10)

    # ---- fairness A/B: 2 tenants, heavy offers 8x light's load ----
    n_light, n_heavy = 12, 96          # the 8x skew the claim is about
    _ARM_KEYS = ("OTPU_RESILIENCE", "OTPU_ADMISSION_MAX_INFLIGHT",
                 "OTPU_ADMISSION_MAX_QUEUE", "OTPU_TENANCY",
                 "OTPU_TENANT_SPEC")

    def run_arm(env: dict, label: str) -> dict:
        saved = {k: os.environ.get(k) for k in _ARM_KEYS}
        for k in _ARM_KEYS:
            os.environ.pop(k, None)
        os.environ.update(env)
        light_lat, heavy_lat = [], []
        outcomes: list = []
        lock = threading.Lock()
        try:
            # micro_batch=False: dispatches (and their admission slots)
            # run on the CALLER's thread, which carries the tenant scope
            with ServingContext(ladder, micro_batch=False) as ctx:
                ctx.warmup(model, n_cols=n_dense + n_cat,
                           kinds=("array",), session=session)

                def one(tenant: str, i: int):
                    if tenant == "light":
                        time.sleep(i * 0.03)   # light arrives spaced out
                    t0 = time.perf_counter()
                    try:
                        with tenant_scope(tenant):
                            out = model.predict(X[:64])
                        assert out.shape[0] == 64
                        kind = "ok"
                    except TenantQuotaShedError:
                        kind = "tenant_shed"
                    except OverloadShedError:
                        kind = "shed"
                    except Exception:  # noqa: BLE001 - untyped = lost
                        kind = "lost"
                    ms = (time.perf_counter() - t0) * 1e3
                    with lock:
                        outcomes.append((tenant, kind))
                        if kind == "ok":
                            (light_lat if tenant == "light"
                             else heavy_lat).append(ms)

                _log(f"[tenancy] {label} arm: {n_heavy} heavy + "
                     f"{n_light} light requests ...")
                with inject_faults(f"overload:delay_ms={service_ms}"):
                    # no `with` block: shutdown(wait=False) — a hung
                    # future is REPORTED, never a bench deadlock (PR-8)
                    ex = concurrent.futures.ThreadPoolExecutor(
                        n_light + 12)
                    try:
                        futs = [ex.submit(one, "heavy", i)
                                for i in range(n_heavy)]
                        futs += [ex.submit(one, "light", i)
                                 for i in range(n_light)]
                        done, pending = concurrent.futures.wait(
                            futs, timeout=120.0)
                        hung = len(pending)
                    finally:
                        ex.shutdown(wait=False)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return {"light_lat": light_lat, "heavy_lat": heavy_lat,
                "outcomes": outcomes, "hung": hung}

    def pctl(lat, q):
        return round(float(np.percentile(np.asarray(lat), q)), 3)

    UNFAIR = {"OTPU_RESILIENCE": "1", "OTPU_ADMISSION_MAX_INFLIGHT": "2",
              "OTPU_ADMISSION_MAX_QUEUE": "256", "OTPU_TENANCY": "0"}
    FAIR = dict(UNFAIR, OTPU_TENANCY="1",
                OTPU_TENANT_SPEC="light:weight=4;"
                                 "heavy:weight=1,max_inflight=1")

    def fairness_ab():
        u = run_arm(UNFAIR, "unfair (OTPU_TENANCY=0)")
        f = run_arm(FAIR, "weighted-fair")
        p99_u = pctl(u["light_lat"], 99) if u["light_lat"] else None
        p99_f = pctl(f["light_lat"], 99) if f["light_lat"] else None
        factor = (round(p99_u / p99_f, 2) if p99_u and p99_f else None)
        return u, f, p99_u, p99_f, factor

    unfair, fair, light_p99_u, light_p99_f, factor = fairness_ab()
    # structured re-measure (the bench_fleet one-retry policy): one
    # preemption stretch inside the fair arm's light stream can fake a
    # sub-3x reading; a REAL fairness regression reproduces
    fairness_retried = False
    fairness_factor_first = None
    if factor is None or factor < 3.0:
        fairness_retried = True
        fairness_factor_first = factor
        _log(f"[tenancy] fairness {factor}x under the 3x gate -- "
             "re-measuring both arms once")
        unfair, fair, light_p99_u, light_p99_f, factor = fairness_ab()
    heavy_typed_sheds = sum(1 for t, k in fair["outcomes"]
                            if t == "heavy" and k == "tenant_shed")
    all_outcomes = unfair["outcomes"] + fair["outcomes"]
    lost = sum(1 for _t, k in all_outcomes if k == "lost")
    hung = unfair["hung"] + fair["hung"]
    completed = sum(1 for _t, k in all_outcomes if k == "ok")

    # ---- elasticity drill: a real fleet breathes with offered load ----
    _log("[tenancy] autoscale drill: 1-replica fleet under load ...")
    root = os.path.join(DATA_DIR,
                        f"tenancy_models_{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    publish_version(model, root, n_cols=n_dense + n_cat)
    base_env = {"JAX_PLATFORMS": "cpu",
                "OTPU_ADMISSION_MAX_INFLIGHT": "1",
                "OTPU_FAULT_SPEC": "overload:delay_ms=30"}
    mgr = ReplicaManager(root, n_replicas=1, ladder_max=1 << 9,
                         env=base_env)
    mgr.start()
    assert mgr.wait_ready(timeout_s=120), "autoscale replica never ready"
    # coalescing OFF for the drill: its one-leader-per-replica cap would
    # serialize the 8 loaders into one wire dispatch at a time and the
    # replica would never see the backlog the autoscaler keys on
    saved_coalesce = os.environ.get("OTPU_FLEET_COALESCE")
    os.environ["OTPU_FLEET_COALESCE"] = "0"
    router = FleetRouter(mgr.endpoints(), hedging=False)
    router.refresh()
    scaler = Autoscaler(mgr, router, min_replicas=1, max_replicas=3,
                        up_x=2.0, down_x=0.5, cooldown_s=2.0)

    def scrape_step():
        # a fresh collector each step so NEW endpoints are scraped too —
        # the long-lived supervisor loop rebinds the same way
        col = fobs.FleetCollector(mgr.endpoints(), router=router)
        return scaler.step(col.scrape_once())

    stop = threading.Event()
    load_failures: list = []

    def loader(rows):
        while not stop.is_set():
            try:
                router.predict(X[:rows])
            except (ReplicaUnavailableError, ReplicaDrainingError,
                    NoReplicaAvailableError, OverloadShedError):
                pass                      # typed under churn is fine here
            except Exception as e:  # noqa: BLE001 - untyped = a failure
                load_failures.append(repr(e))

    # distinct row counts per loader — a mixed-shape offered load, not
    # eight copies of one request
    threads = [threading.Thread(target=loader, args=(16 + 8 * i,))
               for i in range(8)]
    for t in threads:
        t.start()
    peak = 1
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        router.refresh()
        scrape_step()
        peak = max(peak, len(mgr.handles))
        if peak >= 3 and mgr.wait_ready(timeout_s=1):
            break
        time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    load_hung = sum(1 for t in threads if t.is_alive())

    # scale-down: load gone, trickle traffic must see ZERO failures
    # while the autoscaler drains the extra replicas back to min
    _log(f"[tenancy] scale-down drill from {len(mgr.handles)} "
         "replicas ...")
    trickle_ok, trickle_failures = 0, []
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            out = router.predict(X[:64])
            assert out.shape[0] == 64
            trickle_ok += 1
        except Exception as e:  # noqa: BLE001 - the claim is ZERO
            trickle_failures.append(repr(e))
        router.refresh()
        scrape_step()
        if len(mgr.handles) <= scaler.min_replicas:
            break
        time.sleep(0.3)
    final_replicas = len(mgr.handles)
    decisions = [d.to_dict() for d in scaler.decisions]
    scaler_state = scaler.state()
    router.close()
    mgr.stop_all()
    if saved_coalesce is None:
        os.environ.pop("OTPU_FLEET_COALESCE", None)
    else:
        os.environ["OTPU_FLEET_COALESCE"] = saved_coalesce
    shutil.rmtree(root, ignore_errors=True)
    elasticity = round(peak / max(final_replicas, 1), 2)

    # ---- kill-switch parity: both OFF is the PR-19 fleet bitwise ----
    saved = {k: os.environ.get(k) for k in
             ("OTPU_TENANCY", "OTPU_AUTOSCALE")}
    os.environ["OTPU_TENANCY"] = "0"
    os.environ["OTPU_AUTOSCALE"] = "0"
    try:
        with ServingContext(ladder, micro_batch=False) as ctx:
            ctx.warmup(model, n_cols=n_dense + n_cat,
                       kinds=("array",), session=session)
            ref = np.asarray(model.predict(X[:256]))
            with tenant_scope("ghost"):   # a scope must change NOTHING
                scoped = np.asarray(model.predict(X[:256]))
            fair_never_built = ctx.admission._fair_share is None
        stepped = Autoscaler(mgr, router, min_replicas=1, max_replicas=3,
                             up_x=2.0, down_x=0.5, cooldown_s=2.0).step(
            {"replicas": {"replica-0": {"up": True, "stale": False,
                                        "queue_depth": 99, "inflight": 9,
                                        "shed_total": 9,
                                        "brownout_level": 3}}})
        parity = (bool(np.array_equal(ref, scoped)) and fair_never_built
                  and stepped is None)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    return {
        "metric": "tenancy_fairness_p99_bound_factor",
        "value": factor if factor is not None else 0,
        "unit": "x",
        # a fairness A/B has no external baseline: the unfair arm IS
        # the denominator, reported as fairness_p99_bound_factor
        "vs_baseline": None,
        **device_fields(),
        "requests": len(all_outcomes),
        "service_ms_injected": service_ms,
        # ---- weighted-fair tenancy (the headline) ----
        "fairness_p99_bound_factor": factor,
        "fairness_retried": fairness_retried,
        "fairness_p99_bound_factor_first": fairness_factor_first,
        "light_p99_ms_unfair": light_p99_u,
        "light_p99_ms_fair": light_p99_f,
        "light_p50_ms_fair": (pctl(fair["light_lat"], 50)
                              if fair["light_lat"] else None),
        "heavy_typed_sheds": heavy_typed_sheds,
        "heavy_completed_fair": sum(1 for t, k in fair["outcomes"]
                                    if t == "heavy" and k == "ok"),
        "light_completed_fair": sum(1 for t, k in fair["outcomes"]
                                    if t == "light" and k == "ok"),
        "completed": completed,
        "hung": hung,
        "lost": lost,
        # ---- digest-driven elasticity ----
        "autoscale_peak_replicas": peak,
        "autoscale_final_replicas": final_replicas,
        "autoscale_min_replicas": scaler.min_replicas,
        "autoscale_max_replicas": scaler.max_replicas,
        "autoscale_decisions": len(decisions),
        "autoscale_decision_log": decisions,
        "autoscale_state": scaler_state,
        "autoscale_scaledown_failures": len(trickle_failures),
        "autoscale_scaledown_trickle_ok": trickle_ok,
        "autoscale_load_failures": len(load_failures),
        "autoscale_load_hung": load_hung,
        "elasticity_factor": elasticity,
        # ---- kill-switch contract ----
        "tenancy_kill_switch_parity": parity,
    }


def bench_online() -> dict:
    """Guarded continuous learning (online/ subsystem, ISSUE 14): the
    train-while-serve loop's five claims, drilled end-to-end over an
    in-process two-replica fleet (subprocess mechanics are bench_fleet's
    beat — this arm measures the ONLINE control plane):

      learn     a label-shift stream (the CTR rule inverts mid-stream):
                the incremental trainer consumes the tapped request/label
                log and the continuously-updated candidate must BEAT the
                frozen model's holdout AUC in the same run, then promote
                through the full gate ladder with zero failed requests;
      drift     an injected feature shift (``drift:shift,after``) on the
                tapped stream: the candidate is rejected TYPED by the
                drift gate BEFORE any replica flips — quarantined,
                CURRENT untouched;
      slo       a candidate that passes drift+shadow but burns SLO
                budget during its roll: the canary/SLO half auto-rolls
                back with ZERO failed requests and quarantines it;
      resume    ``trainer_crash:at=N`` kills the fit thread typed; a new
                trainer resumes from the checkpoint WITHOUT re-reading
                the consumed log and converges bitwise to the
                uninterrupted run;
      unguarded OTPU_RESILIENCE=0 repeats the drift drill and SHIPS the
                bad candidate (the gates were the protection), and
                OTPU_ONLINE=0 serves bitwise-identically with an empty
                log (kill-switch parity)."""
    import shutil
    import threading

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.fleet import rollout as ro
    from orange3_spark_tpu.fleet.replica import ReplicaRuntime
    from orange3_spark_tpu.fleet.router import FleetRouter, ReplicaEndpoint
    from orange3_spark_tpu.io.reqlog import RequestLog
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.obs import fleetobs as fobs
    from orange3_spark_tpu.online import OnlineLoop
    from orange3_spark_tpu.online.trainer import (
        IncrementalTrainer, OnlineTrainerError,
    )
    from orange3_spark_tpu.resilience.faults import inject_faults
    from orange3_spark_tpu.serve import BucketLadder, ServingContext

    session = TpuSession.builder_get_or_create()
    rng = np.random.default_rng(3)
    n_dense = n_cat = 4
    X = np.concatenate([
        rng.standard_normal((4096, n_dense)).astype(np.float32),
        rng.integers(0, 500, (4096, n_cat)).astype(np.float32),
    ], axis=1)
    y0 = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    y1 = 1.0 - y0                    # the label rule inverts mid-stream
    _log("[online] fitting the frozen CTR model ...")
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=n_dense, n_cat=n_cat, epochs=1,
        step_size=0.05, chunk_rows=1024,
    ).fit_stream(array_chunk_source(X, y0, chunk_rows=1024),
                 session=session)
    root = os.path.join(DATA_DIR,
                        f"online_{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    store = os.path.join(root, "store")
    holdout_shifted = array_chunk_source(X[2048:], y1[2048:],
                                         chunk_rows=1024)

    def drive(loop, y, chunks=8, epochs=1):
        """Serve traffic through the parent ServingContext (the tap
        point) and feed labels back through the tap."""
        for _ in range(epochs):
            for i in range(0, chunks * 256, 256):
                model.predict(X[i:i + 256])
                rid = loop.tap.last_request_id()
                if rid is not None:
                    loop.tap.tap_label(rid, y[i:i + 256])

    def wait_steps(loop, n, budget_s=180.0):
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < budget_s
               and loop.trainer.status()["steps"] < n
               and not loop.trainer.status()["died"]):
            time.sleep(0.1)

    # ---- in-process two-replica fleet over the version store ----
    ro.publish_version(model, store, n_cols=n_dense + n_cat)
    runtimes, eps = [], []
    for i in range(2):
        rt = ReplicaRuntime(store, name=f"replica-{i}", session=session,
                            ladder=BucketLadder(min_bucket=64,
                                                max_bucket=512))
        rt.activate()
        srv = rt.serve_background()
        runtimes.append(rt)
        eps.append(ReplicaEndpoint(i, "127.0.0.1", srv.port))
    router = FleetRouter(eps, hedging=False)
    router.refresh()

    def traffic_during(fn):
        """Run ``fn`` under continuous router traffic; returns
        (fn_result, ok_count, failures)."""
        stop = threading.Event()
        oks: list = []
        fails: list = []

        def _t():
            while not stop.is_set():
                try:
                    router.predict(X[:64])
                    oks.append(1)
                except Exception as e:  # noqa: BLE001 - claim is zero
                    fails.append(repr(e))
                time.sleep(0.01)

        th = threading.Thread(target=_t)
        th.start()
        try:
            res = fn()
        finally:
            stop.set()
            th.join(timeout=10)
        return res, len(oks), fails

    trainer_kw = {"chunk_rows": 256, "join_window": 64, "ckpt_steps": 4}
    ladder = BucketLadder(min_bucket=64, max_bucket=512)

    # ---- arm 1: learn + guarded promotion (zero failed requests) ----
    # shadow bound 0.95: a candidate adapting to an INVERTED label rule
    # legitimately disagrees with the stale serving model on most rows —
    # the gate is kept armed but bounds only total divergence here
    _log("[online] learn arm: label-shift stream + guarded promotion ...")
    loopA = OnlineLoop(model, store, os.path.join(root, "a.log"),
                       session=session, reference_X=X,
                       holdout_source=holdout_shifted,
                       router=router, canary_input=X[:16],
                       min_examples=512, trainer_kw=trainer_kw,
                       shadow_kw={"disagree_threshold": 0.95})
    with ServingContext(ladder), loopA:
        drive(loopA, y1, epochs=3)
        wait_steps(loopA, 24)
        metr_frozen = model.evaluate_stream(holdout_shifted)
        cand = loopA.trainer.candidate_model()
        metr_cont = cand.evaluate_stream(holdout_shifted)
        resA, okA, failsA = traffic_during(loopA.publish_cycle)
        statusA = loopA.trainer.status()
    auc_frozen = metr_frozen["auc"]
    auc_cont = metr_cont["auc"]
    current_after_promo = ro.read_current(store)
    router.refresh()

    # ---- arm 2: injected drift rejected before any replica flips ----
    _log("[online] drift arm: injected feature shift ...")
    versions_before = [ep.version for ep in router.endpoints]
    with inject_faults("drift:shift=8,after=4"):
        loopB = OnlineLoop(model, store, os.path.join(root, "b.log"),
                           session=session, reference_X=X,
                           holdout_source=holdout_shifted,
                           router=router, canary_input=X[:16],
                           min_examples=512, trainer_kw=trainer_kw)
        with ServingContext(ladder), loopB:
            drive(loopB, y0)
            wait_steps(loopB, 8)
            resB = loopB.publish_cycle()
    router.refresh()
    drift_no_flip = ([ep.version for ep in router.endpoints]
                     == versions_before)
    drift_current_untouched = ro.read_current(store) == current_after_promo

    # ---- arm 3: past the gates, tripped by SLO burn -> auto-rollback ----
    # the burn must START during the roll: an alert that fires earlier is
    # a RISING edge the engine holds active (no fresh alert for
    # Rollout._check_slo to see). The traffic thread watches for the
    # first replica hold (set_admitted False — the roll's first
    # observable move) and burns error budget from that instant; the
    # alert then fires fresh inside _check_slo after the first flip
    _log("[online] slo arm: burn during roll -> rollback ...")
    slo = fobs.SLOEngine(
        fobs.parse_slo_spec("online_drill:target=99.0,p99_ms=1"),
        fast_s=60.0, slow_s=240.0)
    loopC = OnlineLoop(model, store, os.path.join(root, "c.log"),
                       session=session, reference_X=X,
                       holdout_source=array_chunk_source(
                           X[2048:], y0[2048:], chunk_rows=1024),
                       router=router, canary_input=X[:16],
                       slo_engine=slo, min_examples=512,
                       trainer_kw=trainer_kw,
                       drift_kw={"holdout_drop": 0.2},
                       shadow_kw={"disagree_threshold": 0.95})
    roll_seen = threading.Event()

    def burn_when_rolling():
        while not roll_seen.is_set():
            if any(not ep.admitted for ep in router.endpoints):
                roll_seen.set()
            time.sleep(0.005)
        for _ in range(64):
            slo.record(True, latency_s=0.5)

    with ServingContext(ladder), loopC:
        drive(loopC, y0)
        wait_steps(loopC, 8)
        burner = threading.Thread(target=burn_when_rolling)
        burner.start()
        try:
            resC, okC, failsC = traffic_during(loopC.publish_cycle)
        finally:
            roll_seen.set()
            burner.join(timeout=10)
    slo_current_untouched = ro.read_current(store) == current_after_promo

    # ---- arm 4: trainer crash -> typed death -> checkpoint resume ----
    _log("[online] resume arm: trainer_crash + checkpoint resume ...")
    rlog = RequestLog(os.path.join(root, "r.log"))
    for i in range(0, 2048, 256):
        rid = rlog.append_request(X[i:i + 256])
        rlog.append_label(rid, y0[i:i + 256])
    # ckpt every 2 steps so the at=3 crash lands AFTER a snapshot — the
    # drill claims resume-from-checkpoint, not replay-from-scratch
    trainer_kw = dict(trainer_kw, ckpt_steps=2)
    tref = IncrementalTrainer(model, rlog, session=session,
                              checkpoint_path=os.path.join(root, "ref.ckpt"),
                              **trainer_kw)
    tref.consume_available()
    ref_leaves = [np.asarray(v) for v
                  in tref.candidate_model().state_pytree.values()]
    crash_typed = False
    with inject_faults("trainer_crash:at=3"):
        tcrash = IncrementalTrainer(
            model, rlog, session=session,
            checkpoint_path=os.path.join(root, "crash.ckpt"), **trainer_kw)
        tcrash.start()
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < 120
               and not tcrash.status()["died"]):
            time.sleep(0.1)
        try:
            tcrash.result()
        except OnlineTrainerError:
            crash_typed = True
    tres = IncrementalTrainer(model, rlog, session=session,
                              checkpoint_path=os.path.join(root, "crash.ckpt"),
                              **trainer_kw)
    resumed_from = tres.status()["resumed_from_step"]
    tres.consume_available()
    res_leaves = [np.asarray(v) for v
                  in tres.candidate_model().state_pytree.values()]
    resume_parity = all(np.array_equal(a, b)
                        for a, b in zip(ref_leaves, res_leaves))
    rlog.close()

    # ---- arm 5: unguarded loop ships the bad model; kill-switch ----
    _log("[online] unguarded + kill-switch arms ...")
    saved_res = os.environ.get("OTPU_RESILIENCE")
    os.environ["OTPU_RESILIENCE"] = "0"
    try:
        with inject_faults("drift:shift=8,after=4"):
            loopU = OnlineLoop(model, os.path.join(root, "ustore"),
                               os.path.join(root, "u.log"),
                               session=session, reference_X=X,
                               holdout_source=holdout_shifted,
                               min_examples=512, trainer_kw=trainer_kw)
            with ServingContext(ladder), loopU:
                drive(loopU, y0)
                wait_steps(loopU, 8)
                resU = loopU.publish_cycle()
    finally:
        if saved_res is None:
            os.environ.pop("OTPU_RESILIENCE", None)
        else:
            os.environ["OTPU_RESILIENCE"] = saved_res
    unguarded_ships_bad = resU["outcome"] == "published"

    saved_onl = os.environ.get("OTPU_ONLINE")
    os.environ["OTPU_ONLINE"] = "0"
    try:
        loopK = OnlineLoop(model, os.path.join(root, "kstore"),
                           os.path.join(root, "k.log"),
                           session=session, reference_X=X,
                           min_examples=1, trainer_kw=trainer_kw)
        with ServingContext(ladder), loopK:
            ref_out = model.predict(X[:256])
            kill_log_empty = (loopK.log.size_bytes
                              == loopK.log.data_start)
            kill_cycle = loopK.publish_cycle()["outcome"]
    finally:
        if saved_onl is None:
            os.environ.pop("OTPU_ONLINE", None)
        else:
            os.environ["OTPU_ONLINE"] = saved_onl
    with ServingContext(ladder):
        kill_parity = bool(np.array_equal(ref_out, model.predict(X[:256])))

    router.close()
    for rt in runtimes:
        rt.close()
    quarantined = ro.list_quarantined(store)
    shutil.rmtree(root, ignore_errors=True)

    auc_gain = round(auc_cont - auc_frozen, 3)
    return {
        "metric": "online_guarded_loop",
        "value": auc_gain,
        "unit": "auc",
        # the frozen model's same-run holdout AUC is the denominator; no
        # external continuous-learning reference exists for this layout
        "vs_baseline": None,
        "baseline_value": None,
        "baseline_note": ("frozen-model arm of the same run is the "
                          "baseline (holdout AUC on the shifted stream); "
                          "no published train-while-serve reference "
                          "exists (BASELINE.json empty mount)"),
        **device_fields(),
        # ---- learn + guarded promotion ----
        "auc_frozen": round(auc_frozen, 4),
        "auc_continuous": round(auc_cont, 4),
        "auc_gain": auc_gain,
        "online_steps": statusA["steps"],
        "online_examples": statusA["examples"],
        "label_join_counts": statusA["join_counts"],
        "trainer_examples_per_s": statusA["examples_per_s"],
        "promotion_outcome": resA["outcome"],
        "promotion_version": resA.get("version"),
        "promotion_failed_requests": len(failsA),
        "promotion_traffic_requests": okA,
        "promotion_current": current_after_promo,
        # ---- drift rejection ----
        "drift_outcome": resB["outcome"],
        "drift_error": (resB.get("error") or "")[:200],
        "drift_quarantined": bool(resB.get("quarantined")),
        "drift_current_untouched": drift_current_untouched,
        "drift_no_replica_flip": drift_no_flip,
        # ---- SLO-tripped rollback ----
        "slo_rollback_outcome": resC["outcome"],
        "slo_rollback_failed_requests": len(failsC),
        "slo_rollback_traffic_requests": okC,
        "slo_quarantined": bool(resC.get("quarantined")),
        "slo_current_untouched": slo_current_untouched,
        # ---- crash + resume ----
        "trainer_crash_typed": crash_typed,
        "trainer_resumed_from_step": resumed_from,
        "resume_parity_bitwise": resume_parity,
        # ---- unguarded + kill-switch ----
        "unguarded_ships_bad": unguarded_ships_bad,
        "kill_switch_parity": kill_parity,
        "kill_switch_log_empty": kill_log_empty,
        "kill_switch_cycle": kill_cycle,
        "quarantined_versions": quarantined,
    }


def bench_multihost(*, rows: int = 49_152, epochs: int = 16,
                    hosts: int | None = None,
                    chunk_rows: int = 1024) -> dict:
    """Pod-scale multihost A/B (docs/multihost.md): 1-process vs N-process
    data-parallel streaming fits on the Criteo CSV, same run.

    The N arm is a REAL ``MultihostLauncher`` gang (one OS process per
    rank, ``jax.distributed`` over gloo; ``multihost_mode=multiprocess``);
    the in-process arms stage 1 host's rows at global chunk C and N hosts'
    rows at global chunk N*C (equal steps/epoch) for the kill-switch pin.
    This is a CPU drill: this process fits in jax itself before it spawns
    the gang, so it must run under ``JAX_PLATFORMS=cpu`` (with forced host
    devices for a pod-shaped mesh) — on a chip the launcher refuses, one
    process per chip. The rates it prints are therefore counts of a
    rehearsal, not device rates.

    Pins carried in the record: theta parity ON-vs-OFF (the
    ``OTPU_MULTIHOST=0`` kill-switch arm must be BITWISE at equal
    schedule), and the lost-host drill (``tools/multihost_drill.run_drill``:
    SIGKILL one rank after its epoch snapshot → typed detect → gang
    restart → 0 lost work, resumed theta bitwise). Per-host goodput and
    device-memory ledger attribution ride ``multihost_hosts`` (the PR-12
    digest, per rank)."""
    import tempfile as _tempfile

    import jax
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import (
        StreamingLinearEstimator, array_chunk_source, csv_chunk_source,
    )
    from orange3_spark_tpu.parallel.launcher import MultihostLauncher
    from orange3_spark_tpu.parallel.partitioner import (
        DataParallelPartitioner,
    )
    from orange3_spark_tpu.utils import knobs
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    n_hosts = int(hosts or knobs.get_int("OTPU_MULTIHOST_PROCS") or 4)
    rows -= rows % (n_hosts * chunk_rows)     # exact steps, no ragged tail
    rows_1p = rows // n_hosts
    csv_path = ensure_criteo_csv(rows)
    n_feat = 1 + N_DENSE + N_CAT - 1          # label split out

    def fit_arm(arm_rows, arm_chunk, n_epochs, *, multihost: str,
                src=None):
        """One streaming fit in the per-chunk replay regime (an
        epoch-checkpointed multihost worker's schedule: HBM cache +
        per-step snapshots armed), under OTPU_MULTIHOST=multihost, over
        the CSV's first ``arm_rows`` rows (or ``src``).
        Returns (wall_s, model)."""
        saved = os.environ.get("OTPU_MULTIHOST")
        os.environ["OTPU_MULTIHOST"] = multihost
        try:
            part = DataParallelPartitioner()
            src = src or part.shard_csv(csv_path, "label", n_total=arm_rows,
                                        chunk_rows=arm_chunk)
            est = StreamingLinearEstimator(
                loss="logistic", epochs=n_epochs, step_size=0.05,
                chunk_rows=arm_chunk, seed=0)
            with _tempfile.TemporaryDirectory() as td:
                ck = StreamCheckpointer(os.path.join(td, "mh.ckpt"),
                                        every_steps=10 ** 9)
                t0 = time.perf_counter()
                model = est.fit_stream(src, n_features=n_feat,
                                       session=part.session,
                                       cache_device=True, checkpointer=ck)
                jax.block_until_ready(model.coef)
                return time.perf_counter() - t0, model
        finally:
            if saved is None:
                os.environ.pop("OTPU_MULTIHOST", None)
            else:
                os.environ["OTPU_MULTIHOST"] = saved

    def replay_rate(arm_rows, arm_chunk):
        """Device-replay rows/s: wall(E) − wall(1) isolates epochs 2..E
        (pure per-chunk device replay) from parse+DMA ingest."""
        fit_arm(arm_rows, arm_chunk, 1, multihost="1")      # compile warm
        t1, _ = fit_arm(arm_rows, arm_chunk, 1, multihost="1")
        tE, model = fit_arm(arm_rows, arm_chunk, epochs, multihost="1")
        return arm_rows * (epochs - 1) / max(tE - t1, 1e-9), tE, model

    # ---- arm 1: one host's work (global chunk C) --------------------
    v_1p, wall_1p, _ = replay_rate(rows_1p, chunk_rows)
    # ---- arm N: N hosts' work (global chunk N*C, same mesh) ---------
    v_np, wall_np, model_on = replay_rate(rows, n_hosts * chunk_rows)

    # ---- kill-switch pin: OFF arm, identical schedule → bitwise -----
    _, model_off = fit_arm(rows, n_hosts * chunk_rows, epochs,
                           multihost="0")
    kill_parity = (
        np.array_equal(np.asarray(model_on.coef),
                       np.asarray(model_off.coef))
        and np.array_equal(np.asarray(model_on.intercept),
                           np.asarray(model_off.intercept)))
    theta_diff = float(np.max(np.abs(
        np.asarray(model_on.coef) - np.asarray(model_off.coef))))

    # real N-process gang over the same CSV; ranks inherit this process's
    # environment
    import glob as _glob

    def run_gang(n_epochs):
        """-> (per-host records, rank 0's global theta)."""
        out_dir = _tempfile.mkdtemp(prefix="otpu-mh-bench-")

        def argv(rank, n, coord):
            return [sys.executable, "-m",
                    "orange3_spark_tpu.parallel.mh_worker",
                    "--rank", str(rank), "--nprocs", str(n),
                    "--coord", coord, "--csv", csv_path,
                    "--class-col", "label", "--n-total", str(rows),
                    "--n-features", str(n_feat),
                    "--chunk-rows", str(chunk_rows),
                    "--epochs", str(n_epochs), "--step-size", "0.05",
                    "--out-dir", out_dir]

        MultihostLauncher(argv, n_hosts,
                          log_dir=os.path.join(out_dir, "logs")).run()
        hosts = {}
        for p in sorted(_glob.glob(os.path.join(out_dir, "host_*.json"))):
            with open(p) as f:
                hosts[os.path.splitext(os.path.basename(p))[0]] = (
                    json.load(f))
        return hosts, np.load(os.path.join(out_dir, "theta.npz"))

    # aggregate rate from the slowest rank's fit wall (the gang finishes
    # together)
    gang_hosts, _ = run_gang(epochs)
    gang_wall = max(h["fit_wall_s"] for h in gang_hosts.values())
    v_np = rows * epochs / gang_wall
    v_1p = rows_1p * epochs / wall_1p
    # gang-vs-single-process theta parity, over ONE epoch: the gang's step
    # t trains on [host0's chunk t; host1's chunk t; ...] (each host
    # streams its own contiguous row block), so its in-process twin is the
    # same fit over the rows in THAT order. Only the reduction order
    # differs (gloo, device count) — ≤1e-6 after one epoch; this fit
    # amplifies that float noise ~1000x per epoch (the same in-process fit
    # on 8 vs 4 devices differs by 6e-8 after 2 epochs, 9e-2 after 16), so
    # a longer horizon would compare chaos, not programs
    _, theta = run_gang(1)
    Xall, yall = next(csv_chunk_source(csv_path, "label",
                                       chunk_rows=rows)())
    order = (np.arange(rows).reshape(n_hosts, -1, chunk_rows)
             .transpose(1, 0, 2).reshape(-1))
    _, model_twin = fit_arm(
        rows, n_hosts * chunk_rows, 1, multihost="0",
        src=array_chunk_source(Xall[order], yall[order],
                               chunk_rows=n_hosts * chunk_rows))
    theta_diff = max(theta_diff, float(np.max(np.abs(
        theta["coef"] - np.asarray(model_twin.coef)))))

    # ---- lost-host drill (tools/multihost_drill): typed detect, gang
    # restart, 0 lost work, bitwise resume --------------------------------
    import tools.multihost_drill as mh_drill

    drill = mh_drill.run_drill(procs=n_hosts, rows=2048, epochs=3,
                               chunk_rows=256)

    rep = getattr(model_on, "run_report_", None)
    rep = rep if isinstance(rep, dict) else (
        rep.to_dict() if rep is not None else {})
    spe = rows // (n_hosts * chunk_rows)
    return {
        "metric": "multihost_agg_replay_rows_per_sec",
        "value": round(v_np, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        **device_fields(),
        "multihost_mode": "multiprocess",
        "multihost_hosts_n": n_hosts,
        "rows": rows,
        "epochs": epochs,
        "chunk_rows_per_host": chunk_rows,
        "steps_per_epoch": spe,
        "wall_1p_s": round(wall_1p, 3),
        "wall_np_s": round(wall_np, 3),
        "replay_rows_per_s_1p": round(v_1p, 1),
        "replay_rows_per_s_np": round(v_np, 1),
        "multihost_scaling": round(v_np / max(v_1p, 1e-9), 2),
        "theta_max_abs_diff": theta_diff,
        "multihost_parity_bitwise": bool(kill_parity),
        "kill_switch_parity": bool(kill_parity),
        "goodput": rep.get("goodput", {}),
        "ledger": rep.get("device_memory", {}),
        "multihost_hosts": gang_hosts,
        "drill_procs": drill["procs"],
        "drill_hosts_lost": drill["hosts_lost"],
        "drill_gang_restarts": drill["gang_restarts"],
        "drill_resume_parity_bitwise": drill["resume_parity_bitwise"],
        "drill_resumed_from_step": drill["resumed_from_step"],
        "drill_lost_work_steps": drill["lost_work_steps"],
    }


# ------------------------------------------------- taxi pipeline (r8)
TAXI_COLUMNS = ("dist", "dur", "fare", "lon", "lat", "hour", "dow", "pax")


def gen_taxi(n_rows: int, seed: int = 2):
    """NYC-Taxi-shaped f32[n, 8]: lognormal distances/fares, correlated
    duration, uniform lat/lon and small-integer hour/dow/pax."""
    import numpy as np

    rng = np.random.default_rng(seed)
    dist = rng.lognormal(0.5, 1.0, n_rows).astype(np.float32)
    dur = (dist * 3.2 + rng.lognormal(0, 0.4, n_rows)).astype(np.float32)
    fare = (2.5 + 1.8 * dist + 0.4 * dur
            + rng.standard_normal(n_rows)).astype(np.float32)
    return np.stack(
        [dist, dur, fare,
         rng.uniform(-74.05, -73.75, n_rows).astype(np.float32),
         rng.uniform(40.6, 40.9, n_rows).astype(np.float32),
         rng.integers(0, 24, n_rows).astype(np.float32),
         rng.integers(0, 7, n_rows).astype(np.float32),
         rng.integers(1, 7, n_rows).astype(np.float32)], axis=1)


def bench_taxi_pipeline(*, rows: int = 2_000_000, requests: int = 24,
                        request_rows: int = 256) -> dict:
    """NYC-Taxi KMeans+PCA pipeline promoted to a first-class config
    (ROADMAP item 5): the bench_suite config-5 fit/transform arms (eager
    widget walk vs ONE staged XLA program), a STREAMING-FIT arm (each
    stage fitted out-of-core over a chunk stream, stages chained
    chunk-wise), and the whole-workflow SERVING A/B this round adds —
    the fitted scaler -> PCA -> KMeans DAG wrapped as a ServedWorkflow
    and driven fused (one bucketed AOT dispatch per request,
    OTPU_WORKFLOW_SERVE=1) vs stage-by-stage (the =0 kill-switch: each
    stage re-enters the per-model serving path), interleaved per request
    on the same warmed process. Headline serving claim:
    ``workflow_fused_speedup`` (staged p50 / fused p50) with the device
    dispatch counts pinned from the serve counters (1 vs n_stages)."""
    import jax
    import numpy as np

    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.serve import (
        BucketLadder, ServedWorkflow, ServingContext,
    )
    from orange3_spark_tpu.io.streaming import (
        StreamingKMeans, array_chunk_source,
    )
    from orange3_spark_tpu.models.pca import PCA
    from orange3_spark_tpu.models.preprocess import StandardScaler
    from orange3_spark_tpu.utils.profiling import (
        reset_serve_counters, serve_counters,
    )
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_graph

    n_rows = int(rows)
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

    g, src, sc, pca, km = build()
    _log("[taxi] eager workflow run (fits scaler/PCA/KMeans) ...")
    t0 = time.perf_counter()
    out_eager = g.run()[km]["data"]
    jax.block_until_ready(out_eager.X)
    wall_fit_eager = time.perf_counter() - t0

    # transform: eager widget-by-widget vs the staged single XLA program
    # (warm calls BLOCKED before each timed window — the bench_suite
    # config-5 convention; an unblocked warm dispatch queues ahead of the
    # timed call and inflates it)
    staged = stage_graph(g, km)
    jax.block_until_ready(staged().X)
    t0 = time.perf_counter()
    out_staged = staged()
    jax.block_until_ready(out_staged.X)
    wall_staged = time.perf_counter() - t0

    # the eagerly fitted models: a staged refit puts its own on the ports
    eager_models = [g.nodes[nid].outputs["model"] for nid in (sc, pca, km)]
    refit_staged = stage_graph(g, km, refit=True)
    jax.block_until_ready(refit_staged().X)
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

    jax.block_until_ready(eager_transform().X)
    t0 = time.perf_counter()
    out_e2 = eager_transform()
    jax.block_until_ready(out_e2.X)
    wall_eager_tr = time.perf_counter() - t0

    np.testing.assert_allclose(
        np.asarray(out_staged.X[:1024]), np.asarray(out_e2.X[:1024]),
        rtol=1e-4, atol=1e-4,
    )

    # ---- streaming-fit arm: each stage out-of-core over a chunk stream,
    # stages chained CHUNK-WISE (a stage's fitted state maps the next
    # stage's chunks — no full materialization of any interior table)
    _log("[taxi] streaming-fit arm ...")
    cr = 1 << 16
    t0 = time.perf_counter()
    scaler_s = StandardScaler(with_mean=True).fit_stream(
        array_chunk_source(X, chunk_rows=cr), session=session,
        chunk_rows=cr)
    sh = np.asarray(scaler_s.shift)
    scl = np.asarray(scaler_s.scale)

    def scaled_source():
        for c in array_chunk_source(X, chunk_rows=cr)():
            Xc = np.asarray(c[0] if isinstance(c, tuple) else c)
            yield (((Xc - sh) * scl).astype(np.float32), None, None)

    pca_s = PCA(k=4).fit_stream(scaled_source, session=session,
                                chunk_rows=cr)
    comp = np.asarray(pca_s.components)
    pmean = np.asarray(pca_s.mean)

    def proj_source():
        for Xc, _y, _w in scaled_source():
            yield (((Xc - pmean) @ comp).astype(np.float32), None, None)

    km_s = StreamingKMeans(k=10, epochs=2, chunk_rows=cr, seed=0) \
        .fit_stream(proj_source, n_features=4, session=session)
    jax.block_until_ready(km_s.centers)
    wall_fit_stream = time.perf_counter() - t0
    # semantics: the one-pass streaming moments must agree with the
    # in-memory scaler fit (same population-variance convention)
    scaler_b = eager_models[0]
    stream_scaler_diff = float(np.max(np.abs(
        np.asarray(scaler_b.shift) - sh)))

    # ---- whole-workflow serving A/B: fused DAG vs stage-by-stage ----
    _log("[taxi] workflow serving A/B (fused vs stage-by-stage) ...")
    models = eager_models
    wf = ServedWorkflow.from_stages(models, table, name="taxi-dag")
    rng2 = np.random.default_rng(11)
    reqs = [
        TpuTable.from_numpy(
            domain,
            X[int(o):int(o) + request_rows], session=session)
        for o in rng2.integers(0, n_rows - request_rows, requests)
    ]
    serve_arms = (("fused", "1"), ("staged", "0"))
    saved_wf = os.environ.get("OTPU_WORKFLOW_SERVE")

    def serve_ab():
        lat: dict = {name: [] for name, _ in serve_arms}
        disp: dict = {}
        outs: dict = {}
        with ServingContext(BucketLadder(min_bucket=64, max_bucket=512)):
            for name, flag in serve_arms:   # warm + pin dispatch counts
                os.environ["OTPU_WORKFLOW_SERVE"] = flag
                wf.predict(reqs[0])
                reset_serve_counters()
                outs[name] = np.asarray(wf.predict(reqs[0]))
                c = serve_counters()
                disp[name] = c.get("bucket_hits", 0) \
                    + c.get("bucket_misses", 0)
            for t in reqs:                  # interleaved: drift hits both
                for name, flag in serve_arms:
                    os.environ["OTPU_WORKFLOW_SERVE"] = flag
                    t1 = time.perf_counter()
                    wf.predict(t)
                    lat[name].append((time.perf_counter() - t1) * 1e3)
        p50 = {n: round(float(np.percentile(np.asarray(v), 50)), 4)
               for n, v in lat.items()}
        parity = bool(np.allclose(outs["fused"], outs["staged"],
                                  rtol=1e-4, atol=1e-4))
        return p50, disp, parity

    try:
        p50, disp, serve_parity = serve_ab()
        fused_speedup = p50["staged"] / max(p50["fused"], 1e-9)
        # structured re-measure (the obs/prof one-retry policy): a
        # preemption stretch across the interleaved loop can fake a
        # sub-2x reading; a real fusion regression reproduces
        workflow_ab_retried = False
        workflow_fused_speedup_first = None
        if fused_speedup < 2.0:
            workflow_ab_retried = True
            workflow_fused_speedup_first = round(fused_speedup, 3)
            _log(f"[taxi] fused speedup {fused_speedup:.2f}x under the "
                 "2x gate -- re-measuring once")
            p50, disp, serve_parity = serve_ab()
            fused_speedup = p50["staged"] / max(p50["fused"], 1e-9)
    finally:
        if saved_wf is None:
            os.environ.pop("OTPU_WORKFLOW_SERVE", None)
        else:
            os.environ["OTPU_WORKFLOW_SERVE"] = saved_wf

    return {
        "metric": "taxi_kmeans_pca_pipeline", "unit": "s",
        # 4 decimals: at contract-test row counts the staged transform is
        # ~1 ms and 3 decimals can round a real measurement to 0.0
        "value": round(wall_staged, 4),
        "vs_baseline": None,
        "baseline_value": None,
        "baseline_note": (
            "A/B config: the eager widget-by-widget walk of the same run "
            "is the denominator for the staged/fused claims; no published "
            "taxi-pipeline reference exists (BASELINE.json empty mount)"),
        **device_fields(),
        "rows": n_rows,
        # ---- fit arms ----
        "workflow_fit_s": round(wall_fit_eager, 2),
        "workflow_fit_staged_s": round(wall_fit_staged, 3),
        "fit_staged_speedup": round(
            wall_fit_eager / max(wall_fit_staged, 1e-9), 2),
        "refit_fallbacks": n_fallbacks,
        # ---- streaming-fit arm ----
        "streaming_fit_s": round(wall_fit_stream, 3),
        "streaming_fit_rows_per_s_per_chip": round(
            n_rows / wall_fit_stream / session.n_devices, 1),
        "streaming_scaler_max_abs_diff": stream_scaler_diff,
        # ---- transform arms ----
        "transform_eager_s": round(wall_eager_tr, 3),
        "transform_staged_s": round(wall_staged, 3),
        "staged_speedup": round(wall_eager_tr / max(wall_staged, 1e-9), 2),
        "staged_rows_per_sec_per_chip": round(
            n_rows / wall_staged / session.n_devices, 1),
        # ---- whole-workflow serving A/B (the r8 headline) ----
        "serve_requests": requests,
        "request_rows": request_rows,
        "workflow_n_stages": wf.n_stages,
        "serve_fused_p50_ms": p50["fused"],
        "serve_staged_p50_ms": p50["staged"],
        "workflow_fused_speedup": round(fused_speedup, 3),
        "workflow_ab_retried": workflow_ab_retried,
        "workflow_fused_speedup_first": workflow_fused_speedup_first,
        "dispatch_fused": disp["fused"],
        "dispatch_staged": disp["staged"],
        "workflow_parity": serve_parity,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="criteo",
                    choices=["criteo", "dense_logreg", "serving", "fault",
                             "overload", "fleet", "tenancy", "online",
                             "multihost", "taxi_pipeline"])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--epochs", type=int, default=EPOCHS)
    # None = per-config default (criteo N_DIMS, serving's lighter 1<<18 —
    # serving measures dispatch latency, not table capacity)
    ap.add_argument("--dims", type=int, default=None)
    ap.add_argument("--step-size", type=float, default=STEP_SIZE)
    ap.add_argument("--reg", type=float, default=REG_PARAM)
    ap.add_argument("--cache-bytes", type=int, default=8 << 30,
                    help="HBM chunk-cache budget; set below the dataset "
                         "size to exercise/measure the disk-spill overflow "
                         "path")
    ap.add_argument("--profile", default="",
                    help="write a jax.profiler trace (utils.profiling."
                         "profile_trace) of the timed fit to this directory")
    args = ap.parse_args()
    rows = args.rows

    # the persistent compile cache goes on before the first jit of ANY
    # config (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)
    from orange3_spark_tpu.core.session import TpuSession

    TpuSession.enable_compilation_cache()

    def run():
        if args.config == "criteo":
            return bench_criteo(rows, args.epochs,
                                dims=(N_DIMS if args.dims is None
                                      else args.dims),
                                step_size=args.step_size, reg=args.reg,
                                cache_bytes=args.cache_bytes)
        if args.config == "serving":
            return bench_serving(
                rows, **({} if args.dims is None else {"dims": args.dims}))
        if args.config == "fault":
            # the --dims convention: an untouched global default means
            # "use the fault config's own size", an explicit flag wins
            return bench_fault(
                rows=(args.rows if args.rows != N_ROWS else 262_144),
                epochs=(args.epochs if args.epochs != EPOCHS else 4))
        if args.config == "overload":
            return bench_overload()
        if args.config == "fleet":
            return bench_fleet()
        if args.config == "tenancy":
            return bench_tenancy()
        if args.config == "online":
            return bench_online()
        if args.config == "multihost":
            # same --dims convention as fault: the untouched global
            # defaults mean "use the multihost config's own geometry"
            return bench_multihost(
                rows=(args.rows if args.rows != N_ROWS else 49_152),
                epochs=(args.epochs if args.epochs != EPOCHS else 16))
        if args.config == "taxi_pipeline":
            # same --rows convention as fault: the untouched global
            # default means "use the taxi config's own size"
            return bench_taxi_pipeline(
                rows=(args.rows if args.rows != N_ROWS else 2_000_000))
        return bench_dense_logreg()

    if args.profile:
        from orange3_spark_tpu.utils.profiling import profile_trace

        with profile_trace(args.profile):
            out = run()
    else:
        out = run()
    # every config's record carries the full metrics-registry snapshot
    # (obs/ subsystem) — the same structure /metrics exposes, embedded so
    # a banked JSON line is self-diagnosing without a live process
    from orange3_spark_tpu.obs import REGISTRY

    out["obs"] = REGISTRY.snapshot()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
