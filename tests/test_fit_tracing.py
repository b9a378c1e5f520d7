"""The hashed fit's span vocabulary (docs/observability.md): host spans on
the fit thread and on the prefetch worker under one trace id, stage seconds
as sums of span durations, the ``otpu:`` twins in a profiler trace, the
named scopes of the step's phases in the lowered programs, and the
benchmark's readers of the program's ring."""

import collections
import importlib
import os
import re
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orange3_spark_tpu.obs import trace

N, N_DENSE, N_CAT, CHUNK = 2048, 3, 4, 256
VARIANTS = {
    # device cache, 3 epochs, 1 holdout chunk: epochs 2-3 are one replay
    "replay": dict(est=dict(epochs=3), fit=dict(cache_device=True,
                                                holdout_chunks=1)),
    # the same with one replay dispatch per epoch
    "replay_by_epoch": dict(est=dict(epochs=3, replay_granularity="epoch"),
                            fit=dict(cache_device=True, holdout_chunks=1)),
    # one streaming pass, no cache, no holdout
    "one_pass": dict(est=dict(epochs=1), fit=dict()),
    # a cache that overflows into a disk spill: epoch 2 reads records back
    "spill": dict(est=dict(epochs=2), fit=dict(cache_device=True,
                                               cache_device_bytes=1 << 12),
                  spill=True),
}
#: stage_times key -> the span whose durations it sums
STAGE_OF = {"parse_s": "parse", "encode_s": "encode", "h2d_s": "h2d",
            "prefetch_wait_s": "input_wait", "prefetch_prep_s": "prefetch"}


@pytest.fixture(autouse=True)
def time_limit():
    """Every test of this file has a time limit of its own (the container
    has no pytest-timeout): a hang fails this test, not the whole run."""
    def on_alarm(signum, frame):
        raise TimeoutError("test exceeded its 300 s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def _data():
    rng = np.random.default_rng(7)
    X = np.concatenate([
        rng.standard_normal((N, N_DENSE)).astype(np.float32),
        rng.integers(0, 60, (N, N_CAT)).astype(np.float32)], axis=1)
    y = (X[:, 0] + 0.1 * rng.standard_normal(N) > 0).astype(np.float32)
    return X, y


def _fit(session, variant: str, tmp_dir, *, stage_times=None):
    """One tiny fit of the variant. -> (model, stage_times)"""
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    X, y = _data()
    v = VARIANTS[variant]

    def source():
        for i in range(0, N, CHUNK):
            yield X[i:i + CHUNK], y[i:i + CHUNK], None

    est = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=N_DENSE, n_cat=N_CAT, chunk_rows=CHUNK,
        loss="squared_hinge", optim_update="sparse_adagrad",
        reg_param=1e-4, step_size=0.05, prefetch_depth=2, **v["est"])
    kw = dict(v["fit"])
    if v.get("spill"):
        kw["cache_spill_dir"] = str(tmp_dir / f"spill-{variant}")
    model = est.fit_stream(source, session=session, stage_times=stage_times,
                           **kw)
    return model, stage_times


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def traced_fit(request, session, tmp_path_factory):
    """One fit a variant with spans on and a caller's ``stage_times``,
    then an evaluation. -> the ring's events, the fit's trace id, ..."""
    trace.refresh()
    assert trace.enabled()
    trace.clear()
    tmp = tmp_path_factory.mktemp("fit")
    fit_thread = threading.get_ident()
    model, st = _fit(session, request.param, tmp, stage_times={})
    if model.holdout_chunks_:
        model.evaluate_device(model.holdout_chunks_)
    evs = [e for e in trace.events() if e[0] == "X"]
    root = [e for e in evs if e[1] == "fit" and e[8] is None]
    assert len(root) == 1
    return dict(variant=request.param, events=evs, fit=root[0],
                trace_id=root[0][6], fit_thread=fit_thread, stage_times=st,
                theta=jax.tree.map(np.asarray, model.theta))


def _named(run, name):
    return [e for e in run["events"] if e[1] == name]


def _expected_spans(variant: str) -> dict:
    """span name -> (thread, parent span name) as the taxonomy states."""
    table = {
        "parse": ("worker", "prefetch"), "encode": ("worker", "prefetch"),
        "h2d": ("worker", "prefetch"), "prefetch": ("worker", None),
        "input_wait": ("fit", "epoch"), "chunk": ("fit", "epoch"),
        "epoch": ("fit", "fit"), "epoch_barrier": ("fit", "epoch"),
        "finite_check": ("fit", ("epoch", "fit")),
        "finalize": ("fit", "fit"),
    }
    if variant.startswith("replay"):
        table.update({
            "replay_stack": ("fit", "epoch"), "replay": ("fit", "epoch"),
            "replay_drain": ("fit", "replay"),
            "evaluate": ("fit", None), "eval_chunk": ("fit", "evaluate")})
    if variant == "replay_by_epoch":
        table["replay_dispatch"] = ("fit", "replay")
    if variant == "spill":
        table["spill"] = ("worker", "prefetch")
    return table


def test_every_span_has_its_thread_parent_and_trace_id(traced_fit):
    run = traced_fit
    by_id = {e[7]: e for e in run["events"]}
    for name, (thread, parents) in _expected_spans(run["variant"]).items():
        spans = _named(run, name)
        assert spans, f"{run['variant']}: no {name!r} span"
        parents = parents if isinstance(parents, tuple) else (parents,)
        for e in spans:
            on_fit_thread = e[4] == run["fit_thread"]
            assert on_fit_thread == (thread == "fit"), (name, thread)
            parent = by_id[e[8]][1] if e[8] is not None else None
            assert parent in parents, (name, parent, parents)
            if name not in ("evaluate", "eval_chunk"):   # after the fit
                assert e[6] == run["trace_id"], name
    if run["variant"] == "one_pass":
        assert not _named(run, "replay") and not _named(run, "spill")
    # the args the taxonomy names
    finals = sorted(e[5]["final"] for e in _named(run, "finite_check"))
    assert finals[-1] is True and finals[0] is False
    assert [e[5]["i"] for e in _named(run, "input_wait")][:2] == [0, 1]
    for e in _named(run, "replay"):
        assert e[5]["n_epochs"] == 2 and e[5]["steps"] == 2 * 7


@pytest.mark.parametrize("key", sorted(STAGE_OF))
def test_stage_seconds_are_sums_of_span_durations(traced_fit, key):
    run = traced_fit
    total = sum(e[3] for e in _named(run, STAGE_OF[key])) * 1e-9
    # the prefetch_* keys are reported rounded to the millisecond
    tol = 5.1e-4 if key.startswith("prefetch") else 1e-9
    assert run["stage_times"][key] == pytest.approx(total, abs=tol)


def test_replay_fused_s_covers_stack_and_replay(traced_fit):
    run = traced_fit
    st = run["stage_times"]
    if not run["variant"].startswith("replay"):
        assert "replay_fused_s" not in st
        return
    total = sum(e[3] for n in ("replay_stack", "replay")
                for e in _named(run, n)) * 1e-9
    assert st["replay_fused_s"] == pytest.approx(total, abs=5.1e-4)
    assert st["epoch_s"][-1] == st["replay_fused_s"]


@pytest.mark.parametrize("variant", ["replay", "one_pass"])
def test_obs_off_records_nothing_and_still_fills_stage_times(
        session, tmp_path, variant):
    trace.clear()
    with trace.force_disabled():
        _model, st = _fit(session, variant, tmp_path, stage_times={})
    assert trace.events() == []
    for key in STAGE_OF:
        assert st[key] >= 0.0, key
    assert st["parse_s"] > 0 and st["h2d_s"] > 0 and st["encode_s"] > 0
    assert len(st["epoch_s"]) == (2 if variant == "replay" else 1)


@pytest.mark.parametrize("obs", [False, True])
@pytest.mark.parametrize("with_stage_times", [False, True])
def test_theta_is_bitwise_equal_however_it_is_watched(
        session, tmp_path, traced_fit, obs, with_stage_times):
    """Spans, the caller's dict and the epoch barrier it buys change no
    bit of the result."""
    watch = trace.force_enabled() if obs else trace.force_disabled()
    with watch:
        model, _ = _fit(session, traced_fit["variant"], tmp_path,
                        stage_times={} if with_stage_times else None)
    theta = jax.tree.map(np.asarray, model.theta)
    for leaf in ("emb", "coef", "intercept"):
        np.testing.assert_array_equal(theta[leaf], traced_fit["theta"][leaf])


@pytest.mark.parametrize("variant", ["replay", "one_pass"])
def test_profiler_trace_holds_an_otpu_twin_of_every_span(
        session, tmp_path, variant):
    """Under ``jax.profiler`` the host plane carries one ``otpu:<name>``
    annotation a ring span: the program's spans are on the trace's clock
    and selectable by prefix."""
    import glob

    from jax.profiler import ProfileData

    _fit(session, variant, tmp_path)            # compile outside the trace
    trace.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    log_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        model, _ = _fit(session, variant, tmp_path, stage_times={})
        if model.holdout_chunks_:
            model.evaluate_device(model.holdout_chunks_)
    finally:
        jax.profiler.stop_trace()
    ring = collections.Counter(e[1] for e in trace.events() if e[0] == "X")
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    twins: collections.Counter = collections.Counter()
    bare = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace.ANNOTATION_PREFIX):
                    twins[e.name[len(trace.ANNOTATION_PREFIX):]] += 1
                elif e.name in ring:
                    bare += 1
    assert twins == ring
    assert bare == 0, "an annotation without the otpu: prefix"
    assert twins["parse"] and twins["input_wait"] and twins["finalize"]


# ------------------------------------------------------- device scopes
def _lowered_text(session, *, replay: bool) -> str:
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator, _hashed_replay_epochs, _hashed_step,
        _init_fit_state,
    )

    p = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=N_DENSE, n_cat=N_CAT, chunk_rows=CHUNK,
        loss="squared_hinge", optim_update="sparse_adagrad",
        reg_param=1e-4, cache_dtype="f32").params
    theta, opt, salts_np, salts, kw = _init_fit_state(p, session)
    assert kw["codec"] is None and kw["sparse_lowering"] == "sort"
    X, y = _data()
    chunk = (jnp.asarray(X[:CHUNK]), jnp.int32(CHUNK),
             jnp.asarray(y[:CHUNK]), jnp.ones((CHUNK,), jnp.float32))
    reg, lr = jnp.float32(1e-4), jnp.float32(0.05)
    if not replay:
        lowered = _hashed_step.plain.lower(
            theta, opt, *chunk, salts, reg, lr, **kw)
    else:
        stacks = jax.tree.map(lambda a: jnp.stack([a, a]), chunk)
        lowered = _hashed_replay_epochs.plain.lower(
            theta, opt, stacks, salts, reg, lr, n_epochs=2, **kw)
    return lowered.as_text(debug_info=True)


STEP_SCOPES = ("step/decode", "step/forward", "step/loss_grad",
               "step/dense_leaf", "step/sort", "step/segment", "step/gather",
               "step/rule", "step/scatter")


@pytest.mark.parametrize("replay", [False, True], ids=["step", "replay"])
def test_lowered_programs_carry_the_phase_scopes(session, replay):
    text = _lowered_text(session, replay=replay)
    scopes = STEP_SCOPES
    if replay:
        scopes += ("replay/epoch", "replay/chunk")
    for scope in scopes:
        # an op_name reads jit(f)/step/gather/..., a scan body's starts
        # at the scope ("replay/chunk/..."), autodiff's reads jvp(scope)
        assert re.search(rf'[/("]{scope}[/)]', text), \
            f"{scope} is in no op_name"
    if not replay:
        assert "replay/" not in text


def test_eval_finalize_and_histogram_scopes(session):
    from orange3_spark_tpu.ops.histogram import node_histograms
    from orange3_spark_tpu.optim.sparse import _finalize_emb

    text = _finalize_emb.lower(
        jnp.ones((8, 1)), jnp.zeros((8,), jnp.int32), jnp.int32(3),
        jnp.float32(0.9)).as_text(debug_info=True)
    assert "finalize/decay" in text
    B = jnp.zeros((64, 2), jnp.int32)
    S = jnp.ones((64, 2), jnp.float32)
    pos = jnp.zeros((64,), jnp.int32)
    text = jax.jit(lambda B, S, pos: node_histograms(
        B, S, pos, nodes=1, n_bins=4)).lower(B, S, pos).as_text(
            debug_info=True)
    assert "hist/xla" in text or "hist/kernel" in text


def test_eval_chunk_scope(session):
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator, _hashed_eval_chunk, _init_fit_state,
    )

    p = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=N_DENSE, n_cat=N_CAT, chunk_rows=CHUNK,
        loss="squared_hinge", cache_dtype="f32").params
    theta, _opt, _s, salts, _kw = _init_fit_state(p, session)
    X, y = _data()
    text = _hashed_eval_chunk.lower(
        theta, jnp.asarray(X[:CHUNK]), jnp.int32(CHUNK),
        jnp.asarray(y[:CHUNK]), jnp.ones((CHUNK,), jnp.float32), salts,
        loss_kind="squared_hinge", n_dims=p.n_dims, n_dense=N_DENSE,
        label_in_chunk=False).as_text(debug_info=True)
    assert "eval/chunk" in text


def test_gbt_round_loop_records_one_span_a_round(session):
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.models.gbt import GBTClassifier

    rng = np.random.default_rng(3)
    X = rng.standard_normal((256, 3)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    table = TpuTable.from_arrays(X, y, session=session)
    trace.clear()
    GBTClassifier(max_iter=3, max_depth=2, max_bins=8).fit(table)
    rounds = [e for e in trace.events() if e[1] == "gbt_round"]
    assert [e[5]["i"] for e in rounds] == [0, 1, 2]


# -------------------------------------------- the benchmark's readers
def _ring_job(trace_id: str, t0: int, *, replay: bool) -> list:
    """A hand-built fit trace: (ph, name, t0, dur, thread, args, trace_id,
    span_id, parent_id) tuples with round numbers, times in ns."""
    ms = 1_000_000
    spans = [("fit", 0, 1000, None), ("epoch", 1, 900, 1),
             ("input_wait", 2, 100, 2), ("input_wait", 300, 20, 2),
             ("chunk", 400, 200, 2),
             ("parse", 5, 30, 9), ("parse", 305, 50, 9),
             ("encode", 40, 40, 9), ("encode", 360, 60, 9),
             ("h2d", 90, 5, 9), ("h2d", 430, 15, 9)]
    if replay:
        spans += [("replay_stack", 610, 10, 2), ("replay", 620, 180, 2)]
    return [("X", name, t0 + s * ms, d * ms, 1, None, trace_id, i + 1,
             parent) for i, (name, s, d, parent) in enumerate(spans)]


def _reader(name: str):
    return importlib.import_module(f"benchmark.metrics.{name}")


READINGS = {   # reader -> (a replay job's value, a one-pass job's value)
    "ingest_parse_s": (0.08, 0.08), "ingest_encode_s": (0.10, 0.10),
    "ingest_h2d_s": (0.02, 0.02), "ingest_exposed_s": (0.12, 0.12),
    "ingest_fill_s": (0.10, 0.10), "replay_stack_s": (0.01, None),
    "fit_tail_s": (0.20, 0.40),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_on_a_hand_built_ring(name):
    read = _reader(name).read
    trace.clear()
    assert read({"jobs": [{}]}) is None            # an empty ring
    sec = 1_000_000_000
    warm = _ring_job("fit-1-1", 1 * sec, replay=True)
    for e in warm:      # the warm job's spans read ten times as long
        trace.flush_buffered([e[:3] + (e[3] * 10,) + e[4:]])
    trace.flush_buffered(_ring_job("fit-1-2", 20 * sec, replay=True))
    trace.flush_buffered(_ring_job("fit-1-3", 40 * sec, replay=False))
    # a trace that is no fit (a serving request) is not a job
    trace.flush_buffered([("X", "serve", 60 * sec, sec, 1, None, "req-1",
                           99, None)])
    replay, one_pass = READINGS[name]
    # one job in the window: the last fit alone
    got = read({"jobs": [{}]})
    assert got == (pytest.approx(one_pass) if one_pass is not None else None)
    # two jobs: the mean of the two, the warm job left out
    both = [v for v in (replay, one_pass) if v is not None]
    assert read({"jobs": [{}, {}]}) == pytest.approx(sum(both) / len(both))
    trace.clear()


def test_hbm_named_gb_reads_the_ledgers_peak():
    from orange3_spark_tpu.obs import prof

    read = _reader("hbm_named_gb").read
    before = prof.LEDGER.peak()
    prof.ledger_set("model_state", "test-hbm-named", before + 3_000_000_000)
    try:
        assert read({"jobs": []}) == pytest.approx(
            prof.LEDGER.peak() / 1e9)
        assert read({"jobs": []}) >= (before + 3_000_000_000) / 1e9
    finally:
        prof.ledger_release("model_state", "test-hbm-named")


def test_readers_agree_with_stage_times_on_a_real_fit(traced_fit):
    """The acceptance identity on a real ring: parse + encode + h2d of the
    spans is the ``ingest_s`` the job kind reports from ``stage_times``;
    the fill is part of the exposed wait."""
    run = traced_fit
    trace.clear()
    trace.flush_buffered([e for e in run["events"]])
    window = {"jobs": [{}]}
    st = run["stage_times"]
    parts = sum(_reader(n).read(window) for n in
                ("ingest_parse_s", "ingest_encode_s", "ingest_h2d_s"))
    assert parts == pytest.approx(
        st["parse_s"] + st["encode_s"] + st["h2d_s"], rel=1e-6)
    fill = _reader("ingest_fill_s").read(window)
    assert 0 < fill <= _reader("ingest_exposed_s").read(window)
    assert _reader("fit_tail_s").read(window) > 0
    stack = _reader("replay_stack_s").read(window)
    assert (stack is not None) == run["variant"].startswith("replay")
    trace.clear()
