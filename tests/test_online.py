"""Guarded continuous learning (docs/serving.md §online): the request
log + bounded label joiner, the serving tap, the incremental trainer
(checkpoint/resume), the drift/shadow promotion gates, quarantine, the
OnlineLoop outcomes, and the shutdown races. The gate tests FAIL under
``OTPU_RESILIENCE=0`` by construction — the kill-switch tests pin the
unguarded ladder explicitly, and ``OTPU_ONLINE=0`` pins the whole
subsystem inert."""

import os
import re
import signal
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from orange3_spark_tpu.fleet import rollout as ro
from orange3_spark_tpu.io.reqlog import (
    KIND_LABEL,
    KIND_REQUEST,
    LabelJoiner,
    RequestLog,
    RequestLogCorruptionError,
)
from orange3_spark_tpu.io.streaming import array_chunk_source
from orange3_spark_tpu.online import (
    DriftDetectedError,
    DriftDetector,
    IncrementalTrainer,
    OnlineLoop,
    OnlineTap,
    OnlineTrainerError,
    ShadowMismatchError,
    ShadowScorer,
    TrainerCrashInjected,
    feature_stats,
    maybe_tap_request,
    tap_scope,
)
from orange3_spark_tpu.resilience import inject_faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 128


# ------------------------------------------------------------ request log
def _two_records(tmp_path, name="a.log"):
    log = RequestLog(str(tmp_path / name))
    rng = np.random.default_rng(0)
    X = rng.standard_normal((8, 3)).astype(np.float32)
    rid = log.append_request(X)
    log.append_label(rid, np.ones(8, np.float32))
    log.close()
    return log, X


def test_reqlog_roundtrip_offsets_and_resume(tmp_path):
    log, X = _two_records(tmp_path)
    recs = list(log.read_from(0, verify=True))
    assert [r[2] for r in recs] == [KIND_REQUEST, KIND_LABEL]
    assert recs[0][3] == recs[1][3] == 0          # labels join on req_id
    np.testing.assert_array_equal(recs[0][4], X)
    np.testing.assert_array_equal(recs[1][4][:, 0], np.ones(8))
    # the per-record next_offset IS the resume cursor: reading from it
    # yields exactly the records after that one
    tail = list(log.read_from(recs[0][0], verify=True))
    assert len(tail) == 1 and tail[0][2] == KIND_LABEL
    assert list(log.read_from(recs[1][0], verify=True)) == []
    # reopening appends, never truncates
    log2 = RequestLog(log.path)
    log2.append_request(X)
    log2.close()
    assert len(list(log2.read_from(0, verify=True))) == 3


def test_reqlog_partial_tail_is_end_of_stream(tmp_path):
    log, _X = _two_records(tmp_path)
    with open(log.path, "r+b") as f:
        f.truncate(os.path.getsize(log.path) - 4)   # appender mid-write
    recs = list(log.read_from(0, verify=True))
    assert len(recs) == 1 and recs[0][2] == KIND_REQUEST


def test_reqlog_crc_corruption_typed_and_killswitch(tmp_path, monkeypatch):
    log, _X = _two_records(tmp_path)
    with open(log.path, "r+b") as f:            # flip one payload byte
        f.seek(log.data_start + 32)
        b = f.read(1)
        f.seek(log.data_start + 32)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(RequestLogCorruptionError) as ei:
        list(log.read_from(0, verify=True))
    assert ei.value.ordinal == 0 and ei.value.offset == log.data_start
    assert "CRC" in str(ei.value)
    # verify=None follows the resilience kill-switch
    with pytest.raises(RequestLogCorruptionError):
        list(log.read_from(0))
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    assert len(list(log.read_from(0))) == 2     # legacy: trust the bytes


def test_reqlog_impossible_geometry_typed(tmp_path):
    log, _X = _two_records(tmp_path)
    with open(log.path, "r+b") as f:            # rows*cols*4 != payload
        f.seek(log.data_start + 4)
        f.write(struct.pack("<I", 7))
    with pytest.raises(RequestLogCorruptionError) as ei:
        list(log.read_from(0, verify=True))
    assert "geometry" in str(ei.value)


# ------------------------------------------------------------ label joiner
def test_label_joiner_window_accounting():
    j = LabelJoiner(window=2)
    X = {i: np.full((4, 2), i, np.float32) for i in range(4)}
    y = np.arange(4, dtype=np.float32)[:, None]
    assert j.offer(KIND_REQUEST, 0, X[0]) is None
    got = j.offer(KIND_LABEL, 0, y)
    np.testing.assert_array_equal(got[0], X[0])
    np.testing.assert_array_equal(got[1], y[:, 0])
    # req 1 evicted by 2+3 filling the window -> its label is "late"
    for rid in (1, 2, 3):
        j.offer(KIND_REQUEST, rid, X[rid])
    assert j.offer(KIND_LABEL, 1, y) is None
    # a label whose req_id was never logged is an "orphan"
    assert j.offer(KIND_LABEL, 99, y) is None
    # joined-but-row-mismatched labels are pipeline corruption: orphan
    assert j.offer(KIND_LABEL, 2, y[:3]) is None
    assert j.counts == {"joined": 1, "late": 1, "orphan": 2}


def test_label_joiner_state_roundtrip():
    j = LabelJoiner(window=4)
    j.offer(KIND_REQUEST, 0, np.zeros((2, 2), np.float32))
    j.offer(KIND_LABEL, 5, np.zeros((2, 1), np.float32))   # orphan
    j2 = LabelJoiner(window=4)
    j2.load_state(j.state())
    assert j2.counts == j.counts
    got = j2.offer(KIND_LABEL, 0, np.ones((2, 1), np.float32))
    assert got is not None and j2.counts["joined"] == 1


# ------------------------------------------------------------- serving tap
def test_tap_global_install_scope_and_kill_switch(tmp_path, monkeypatch):
    log = RequestLog(str(tmp_path / "tap.log"))
    X = np.ones((4, 2), np.float32)
    maybe_tap_request(X)                        # no tap installed: no-op
    assert log.size_bytes == log.data_start
    tap = OnlineTap(log).install()
    try:
        maybe_tap_request(X)
        assert tap.last_request_id() == 0
        # the replica boundary logs once; the inner serving-context tap
        # sees the scope and skips — never a double log
        with tap_scope(X):
            maybe_tap_request(X)
            maybe_tap_request(X)
        assert len(list(log.read_from(0, verify=True))) == 2
        monkeypatch.setenv("OTPU_ONLINE", "0")  # THE kill-switch
        assert tap.tap_request(X) is None
        tap.tap_label(0, np.ones(4, np.float32))
        assert len(list(log.read_from(0, verify=True))) == 2
    finally:
        tap.uninstall()
        log.close()
    maybe_tap_request(X)                        # uninstalled: no-op again


def test_tap_drift_injector_shifts_logged_features(tmp_path):
    log = RequestLog(str(tmp_path / "drift.log"))
    tap = OnlineTap(log).install()
    X = np.zeros((4, 2), np.float32)
    try:
        with inject_faults("drift:shift=8,after=1"):
            tap.tap_request(X)                  # ordinal 0: before onset
            tap.tap_request(X)                  # ordinal 1: shifted
        recs = list(log.read_from(0, verify=True))
        np.testing.assert_array_equal(recs[0][4], X)
        np.testing.assert_array_equal(recs[1][4], X + 8.0)
    finally:
        tap.uninstall()
        log.close()


# -------------------------------------------------------------- drift gate
class _Scorer:
    """Stub model: always predicts class ``cls``; fixed holdout metric."""

    def __init__(self, cls=0, auc=0.9):
        self.cls = cls
        self.auc = auc

    def predict_proba(self, X):
        p = np.zeros((X.shape[0], 2), np.float32)
        p[:, self.cls] = 1.0
        return p

    def evaluate_stream(self, source):
        return {"auc": self.auc, "accuracy": self.auc}


def test_drift_feature_shift_typed_and_names_columns():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((2048, 3)).astype(np.float32)
    det = DriftDetector(feature_stats(X), z_threshold=6.0,
                        holdout_drop=0.02)
    z = det.check_features(X[:256])             # clean traffic passes
    assert len(z) == 3 and max(z) < 6.0
    shifted = X[:256].copy()
    shifted[:, 1] += 5.0
    with pytest.raises(DriftDetectedError) as ei:
        det.check_features(shifted)
    assert ei.value.kind == "feature_shift"
    assert ei.value.features == [1]             # names the moved column
    assert ei.value.z_scores[0] > 6.0
    assert "column(s) 1" in str(ei.value)


def test_drift_holdout_regression_typed():
    det = DriftDetector(feature_stats(np.zeros((8, 2))), z_threshold=6.0,
                        holdout_drop=0.02)
    src = array_chunk_source(np.zeros((8, 2), np.float32),
                             np.zeros(8, np.float32), chunk_rows=8)
    ok = det.check_holdout(_Scorer(auc=0.89), _Scorer(auc=0.90), src)
    assert ok["metric"] == "auc" and ok["drop"] == pytest.approx(0.01)
    with pytest.raises(DriftDetectedError) as ei:
        det.check_holdout(_Scorer(auc=0.80), _Scorer(auc=0.90), src)
    assert ei.value.kind == "holdout_regression"
    assert ei.value.metric_drop == pytest.approx(0.10)


def test_drift_gate_inert_under_resilience_off(monkeypatch):
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    det = DriftDetector(feature_stats(np.zeros((8, 2))), z_threshold=1.0)
    det.check(recent_X=np.full((8, 2), 99.0),
              candidate=_Scorer(auc=0.1), serving=_Scorer(auc=0.9),
              holdout_source=array_chunk_source(
                  np.zeros((8, 2), np.float32), np.zeros(8, np.float32),
                  chunk_rows=8))              # unguarded: nothing raises


# ------------------------------------------------------------- shadow gate
def test_shadow_disagreement_typed_and_sampling_deterministic():
    chunks = [(i, np.zeros((16, 2), np.float32)) for i in range(8)]
    scorer = ShadowScorer(_Scorer(cls=0), sample=1.0,
                          disagree_threshold=0.25)
    res = scorer.score(_Scorer(cls=0), chunks)  # agreeing twin passes
    assert res["chunks_scored"] == 8 and res["disagreement"] == 0.0
    with pytest.raises(ShadowMismatchError) as ei:
        scorer.score(_Scorer(cls=1), chunks)
    assert ei.value.disagreement == 1.0
    assert ei.value.rows_scored == 8 * 16
    # the seeded per-ordinal coin: same subset every run
    half = ShadowScorer(_Scorer(cls=0), sample=0.5,
                        disagree_threshold=1.0)
    n1 = half.score(_Scorer(cls=0), chunks)["sampled"]
    n2 = half.score(_Scorer(cls=0), chunks)["sampled"]
    assert n1 == n2 and 0 < n1 < 8


def test_shadow_sheds_first_under_overload_never_fails():
    from orange3_spark_tpu.resilience.overload import OverloadShedError

    class _Shedding(_Scorer):
        def predict_proba(self, X):
            raise OverloadShedError(reason="injected", queue_depth=3,
                                    inflight=1, est_wait_s=9.9,
                                    deadline_s=0.001)

    scorer = ShadowScorer(_Scorer(cls=0), sample=1.0,
                          disagree_threshold=0.0)
    res = scorer.score(_Shedding(cls=1),
                       [(i, np.zeros((4, 2), np.float32))
                        for i in range(3)])
    assert res == {"rows_scored": 0, "chunks_scored": 0, "chunks_shed": 3,
                   "disagreement": 0.0, "sampled": 3}


def test_shadow_gate_inert_under_resilience_off(monkeypatch):
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    scorer = ShadowScorer(_Scorer(cls=0), sample=1.0,
                          disagree_threshold=0.0)
    res = scorer.score(_Scorer(cls=1),
                       [(0, np.zeros((4, 2), np.float32))])
    assert res["chunks_scored"] == 0            # unguarded: never scores


# -------------------------------------------------------------- quarantine
def test_quarantine_ledger_and_roll_refusal(tmp_path):
    root = str(tmp_path / "store")
    for v in ("v0001", "v0002"):
        os.makedirs(os.path.join(root, v))
    ro.set_current(root, "v0001")
    assert ro.list_quarantined(root) == []
    ro.quarantine(root, "v0002", "DriftDetectedError:feature_shift",
                  detail={"error": "z=50"})
    assert ro.is_quarantined(root, "v0002")
    assert not ro.is_quarantined(root, "v0001")
    assert ro.list_quarantined(root) == ["v0002"]
    meta = ro.read_quarantine_meta(root, "v0002")
    assert meta["reason"] == "DriftDetectedError:feature_shift"
    assert meta["error"] == "z=50"
    # idempotent, first reason wins
    ro.quarantine(root, "v0002", "later-reason")
    assert ro.read_quarantine_meta(root, "v0002")["reason"] \
        == "DriftDetectedError:feature_shift"
    # a quarantined version is never re-promoted — typed refusal before
    # any replica is touched (no router needed to prove it)
    with pytest.raises(ro.RolloutError) as ei:
        ro.Rollout(None, root).roll("v0002")
    assert ei.value.step == "quarantine"
    assert "never re-promoted" in str(ei.value)
    assert ro.read_current(root) == "v0001"


def test_sigterm_mid_current_swap_leaves_no_torn_pointer(tmp_path):
    """Satellite drill: kill a process mid CURRENT swap; the pointer
    must still parse and point at a published version (the atomic
    tmp+rename invariant)."""
    root = tmp_path / "store"
    for v in ("v0001", "v0002"):
        (root / v).mkdir(parents=True)
    code = (
        "import sys\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "from orange3_spark_tpu.fleet import rollout as ro\n"
        f"root = {str(root)!r}\n"
        "print('ready', flush=True)\n"
        "i = 0\n"
        "while True:\n"
        "    ro.set_current(root, 'v0001' if i % 2 == 0 else 'v0002')\n"
        "    i += 1\n")
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, env=env)
    try:
        assert p.stdout.readline().strip() == b"ready"
        time.sleep(0.3)                         # mid-swap, guaranteed
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=10)
    finally:
        p.kill()
        p.stdout.close()
    cur = ro.read_current(str(root))
    assert cur in ("v0001", "v0002")            # never torn, never empty
    assert (root / cur).is_dir()


# ------------------------------------------------- fault grammar (online)
def test_online_fault_grammar_hooks():
    from orange3_spark_tpu.resilience.faults import active_fault_spec

    spec_str = ("drift:shift=2.5,after=2;label_skew:flip=0.5,seed=3;"
                "trainer_crash:at=2")
    with inject_faults(spec_str):
        spec = active_fault_spec()
        assert spec.take_drift_shift(0) is None
        assert spec.take_drift_shift(1) is None
        assert spec.take_drift_shift(2) == 2.5  # sustained from onset
        assert spec.take_drift_shift(7) == 2.5
        mask = spec.take_label_flip(4, 64)
        import zlib

        assert mask == [
            zlib.crc32(f"3:4:{r}".encode()) / 0xFFFFFFFF < 0.5
            for r in range(64)]                 # the seeded coin, exactly
        assert [spec.take_trainer_crash() for _ in range(3)] \
            == [False, True, False]             # 1-based at=N, once
    from orange3_spark_tpu.resilience.faults import active_fault_spec as a

    assert a() is None                          # scope-bounded


# ------------------------------------------------------ incremental trainer
@pytest.fixture(scope="module")
def ctr(session):
    """One tiny hashed-CTR serving model + its traffic (module-shared;
    geometry matches tools/online_top.py so the step program compiles
    once per suite)."""
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(7)
    n = 1024
    X = np.concatenate([
        rng.standard_normal((n, 2)).astype(np.float32),
        rng.integers(0, 50, (n, 2)).astype(np.float32),
    ], axis=1)
    y = (X[:, 0] + 0.25 * X[:, 1] > 0).astype(np.float32)
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 8, n_dense=2, n_cat=2, epochs=1, step_size=0.05,
        chunk_rows=CHUNK,
    ).fit_stream(array_chunk_source(X, y, chunk_rows=CHUNK),
                 session=session)
    return model, X, y


def _fill_log(log, X, y, chunk=CHUNK):
    for i in range(0, X.shape[0], chunk):
        rid = log.append_request(X[i:i + chunk])
        log.append_label(rid, y[i:i + chunk])


def _trainer(model, log, session, path, **kw):
    kw.setdefault("chunk_rows", CHUNK)
    kw.setdefault("join_window", 32)
    kw.setdefault("ckpt_steps", 100)
    return IncrementalTrainer(model, log, session=session,
                              checkpoint_path=str(path), **kw)


def _theta_equal(a, b):
    sa, sb = a.state_pytree, b.state_pytree
    return set(sa) == set(sb) and all(
        np.array_equal(np.asarray(sa[k]), np.asarray(sb[k])) for k in sa)


def test_trainer_consumes_log_into_standby_candidate(ctr, session,
                                                     tmp_path):
    model, X, y = ctr
    theta0 = {k: np.asarray(v).copy()
              for k, v in model.state_pytree.items()}
    log = RequestLog(str(tmp_path / "req.log"))
    _fill_log(log, X[:512], y[:512])
    tr = _trainer(model, log, session, tmp_path / "ck")
    assert tr.consume_available() == 8          # 4 requests + 4 labels
    st = tr.status()
    assert st["steps"] == 4 and st["examples"] == 512
    assert st["join_counts"]["joined"] == 4
    assert st["lag_bytes"] == 0 and st["buffered_rows"] == 0
    assert st["last_loss"] is not None
    assert tr.result()["steps"] == 4            # healthy: result==status
    cand = tr.candidate_model()
    assert cand.n_steps_ == 4
    assert not _theta_equal(cand, model)        # the standby moved...
    for k, v in model.state_pytree.items():     # ...the serving model not
        np.testing.assert_array_equal(np.asarray(v), theta0[k])
    # tailing: nothing new -> no records, no steps
    assert tr.consume_available() == 0 and tr.status()["steps"] == 4
    _fill_log(log, X[512:640], y[512:640])
    assert tr.consume_available() == 2 and tr.status()["steps"] == 5
    log.close()


def test_trainer_crash_typed_then_checkpoint_resume_bitwise(ctr, session,
                                                            tmp_path):
    model, X, y = ctr
    log = RequestLog(str(tmp_path / "req.log"))
    _fill_log(log, X[:768], y[:768])            # 6 steps worth
    ref = _trainer(model, log, session, tmp_path / "ref.ck", ckpt_steps=2)
    ref.consume_available()
    assert ref.status()["steps"] == 6
    # at=3 lands AFTER the step-2 snapshot: the resume has work to skip
    crash = _trainer(model, log, session, tmp_path / "crash.ck",
                     ckpt_steps=2)
    with inject_faults("trainer_crash:at=3"):
        with pytest.raises(TrainerCrashInjected):
            crash.consume_available()
    assert crash.status()["steps"] == 2
    # a fresh trainer on the same checkpoint resumes mid-log: no
    # re-reading the consumed prefix, and (steps being deterministic)
    # the SAME candidate bitwise as the uninterrupted run
    resumed = _trainer(model, log, session, tmp_path / "crash.ck",
                       ckpt_steps=2)
    assert resumed.resumed_from_step == 2
    assert resumed.status()["offset"] > 0
    resumed.consume_available()
    assert resumed.status()["steps"] == 6
    assert _theta_equal(resumed.candidate_model(), ref.candidate_model())
    log.close()


@pytest.mark.parametrize("older", ["no-block-counter", "retired-emb_update"])
def test_trainer_resumes_checkpoint_without_block_counter(session,
                                                         tmp_path, older):
    """The crash-recovery loop across an upgrade: a trainer snapshot
    written before the sparse rules' opt_state carried 'blocks' (same
    online-trainer-v1 meta) must resume, to the same candidate bitwise —
    and so must one whose serving model a program before PR 30 pickled,
    with the retired ``emb_update`` still on its params."""
    import pickle

    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(11)
    X = np.concatenate([
        rng.standard_normal((768, 2)).astype(np.float32),
        rng.integers(0, 50, (768, 2)).astype(np.float32),
    ], axis=1)
    y = (X[:, 0] > 0).astype(np.float32)
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 8, n_dense=2, n_cat=2, epochs=1, step_size=0.05,
        chunk_rows=CHUNK, optim_update="sparse_adagrad",
    ).fit_stream(array_chunk_source(X, y, chunk_rows=CHUNK),
                 session=session)
    log = RequestLog(str(tmp_path / "req.log"))
    _fill_log(log, X, y)                        # 6 steps worth
    ref = _trainer(model, log, session, tmp_path / "ref.ck", ckpt_steps=2)
    ref.consume_available()
    crash = _trainer(model, log, session, tmp_path / "old.ck", ckpt_steps=2)
    with inject_faults("trainer_crash:at=3"):
        with pytest.raises(TrainerCrashInjected):
            crash.consume_available()
    with open(tmp_path / "old.ck", "rb") as f:
        blob = pickle.load(f)
    if older == "no-block-counter":
        assert blob["state"]["opt"].pop("blocks") > 0
    else:
        # the snapshot's meta names no estimator parameter; the model
        # does: a frozen dataclass unpickles by __dict__.update, so the
        # parent's field lands on the instance and has to be inert
        assert blob["meta"][0] == "online-trainer-v1"
        model = pickle.loads(pickle.dumps(model))
        model.params.__dict__["emb_update"] = "sorted"
        assert "emb_update" not in model.params.to_dict()
    with open(tmp_path / "old.ck", "wb") as f:
        pickle.dump(blob, f)
    resumed = _trainer(model, log, session, tmp_path / "old.ck",
                       ckpt_steps=2)
    assert resumed.resumed_from_step == 2
    resumed.consume_available()
    assert resumed.status()["steps"] == 6
    assert _theta_equal(resumed.candidate_model(), ref.candidate_model())
    log.close()


def test_trainer_thread_death_is_typed_not_a_hang(ctr, session, tmp_path):
    model, X, y = ctr
    log = RequestLog(str(tmp_path / "req.log"))
    tr = _trainer(model, log, session, tmp_path / "ck")
    with inject_faults("trainer_crash:at=1"):
        tr.start()
        _fill_log(log, X[:CHUNK], y[:CHUNK])
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not tr.status()["died"]:
            time.sleep(0.01)
    assert tr.status()["died"] and not tr.status()["alive"]
    with pytest.raises(OnlineTrainerError) as ei:
        tr.result()
    assert ei.value.phase == "train"
    assert "TrainerCrashInjected" in ei.value.detail
    log.close()


def test_trainer_label_skew_injector_flips_training_labels(ctr, session,
                                                           tmp_path):
    model, X, y = ctr
    log = RequestLog(str(tmp_path / "req.log"))
    _fill_log(log, X[:256], y[:256])
    clean = _trainer(model, log, session, tmp_path / "clean.ck")
    clean.consume_available()
    skewed = _trainer(model, log, session, tmp_path / "skew.ck")
    with inject_faults("label_skew:flip=1.0"):
        skewed.consume_available()
    # all-flipped labels train a DIFFERENT candidate from the same log
    assert not _theta_equal(clean.candidate_model(),
                            skewed.candidate_model())
    log.close()


# ------------------------------------------------------------- online loop
def _mk_loop(model, X, y, tmp_path, session, **kw):
    kw.setdefault("reference_X", X)
    kw.setdefault("holdout_source",
                  array_chunk_source(X, y, chunk_rows=CHUNK))
    kw.setdefault("min_examples", CHUNK)
    kw.setdefault("trainer_kw", {"chunk_rows": CHUNK, "join_window": 32,
                                 "ckpt_steps": 100})
    # a candidate ADAPTING to live labels legitimately disagrees with
    # the frozen serving model; the default bound is for twin models
    kw.setdefault("shadow_kw", {"disagree_threshold": 0.95})
    return OnlineLoop(model, str(tmp_path / "store"),
                      str(tmp_path / "req.log"), session=session, **kw)


def _drive(loop, X, y, lo, hi):
    for i in range(lo, hi, CHUNK):
        rid = loop.tap.tap_request(X[i:i + CHUNK])
        loop.tap.tap_label(rid, y[i:i + CHUNK])


def test_loop_storeside_outcomes_gates_and_kill_switch(ctr, session,
                                                       tmp_path,
                                                       monkeypatch):
    model, X, y = ctr
    loop = _mk_loop(model, X, y, tmp_path, session)
    root = loop.store_root
    # no examples yet -> skipped, store untouched
    assert loop.publish_cycle()["outcome"] == "skipped"
    assert ro.list_versions(root) == []
    # clean traffic -> published; the SERVING model bootstraps the store
    # first so CURRENT can never point at an unvetted candidate
    _drive(loop, X, y, 0, 512)
    loop.trainer.consume_available()
    res = loop.publish_cycle()
    assert res["outcome"] == "published" and res["version"] == "v0002"
    assert ro.list_versions(root) == ["v0001", "v0002"]
    assert ro.read_current(root) == "v0001"
    assert ro.read_version_meta(root, "v0001")["online_baseline"] is True
    assert ro.read_version_meta(root, "v0002")["online_steps"] == 4
    # drifted traffic -> typed rejection + quarantine, CURRENT untouched
    with inject_faults("drift:shift=50"):
        _drive(loop, X, y, 512, 1024)
    loop.trainer.consume_available()
    res = loop.publish_cycle()
    assert res["outcome"] == "rejected_drift" and res["quarantined"]
    assert "DriftDetectedError" in res["error"]
    bad = res["version"]
    assert ro.is_quarantined(root, bad)
    assert ro.read_quarantine_meta(root, bad)["reason"].startswith(
        "DriftDetectedError:feature_shift")
    assert ro.read_current(root) == "v0001"
    st = loop.status()
    assert st["store"]["quarantined"] == [bad]
    assert st["last_outcome"] == "rejected_drift"
    assert st["cycles"] == 3
    # OTPU_ONLINE=0: the whole loop is inert
    monkeypatch.setenv("OTPU_ONLINE", "0")
    assert loop.publish_cycle()["outcome"] == "disabled"
    monkeypatch.delenv("OTPU_ONLINE")
    loop.close()
    assert loop.publish_cycle()["outcome"] == "closed"
    loop.close()                                # idempotent


def test_loop_unguarded_ships_the_bad_candidate(ctr, session, tmp_path,
                                                monkeypatch):
    """The control arm: OTPU_RESILIENCE=0 disables the gates and the
    drifted candidate publishes cleanly — the whole reason they exist."""
    model, X, y = ctr
    loop = _mk_loop(model, X, y, tmp_path, session)
    with inject_faults("drift:shift=50"):
        _drive(loop, X, y, 0, 512)
    loop.trainer.consume_available()
    monkeypatch.setenv("OTPU_RESILIENCE", "0")
    res = loop.publish_cycle()
    assert res["outcome"] == "published"        # no gate fired
    assert ro.list_quarantined(loop.store_root) == []
    monkeypatch.delenv("OTPU_RESILIENCE")
    loop.close()


def test_loop_trainer_death_is_a_cycle_outcome(ctr, session, tmp_path):
    model, X, y = ctr
    loop = _mk_loop(model, X, y, tmp_path, session)
    with inject_faults("trainer_crash:at=1"):
        with loop:                              # __enter__ starts the thread
            _drive(loop, X, y, 0, CHUNK)
            deadline = time.monotonic() + 30
            while (time.monotonic() < deadline
                   and not loop.trainer.status()["died"]):
                time.sleep(0.01)
            res = loop.publish_cycle()
            assert res["outcome"] == "trainer_dead"
            assert "TrainerCrashInjected" in res["error"]
    # __exit__ swallowed the dead trainer (teardown must not raise);
    # the evidence stays readable
    assert loop.status()["trainer"]["died"]


def test_loop_close_races_serving_exit_and_publisher(ctr, session,
                                                     tmp_path):
    """Satellite drill: trainer thread vs ServingContext.__exit__ vs a
    concurrent publisher — every interleaving ends in a result or a
    typed outcome, never a hang, and teardown order is the REVERSE of
    the bench's `with serving, loop` nesting."""
    from orange3_spark_tpu.serve import BucketLadder, ServingContext

    model, X, y = ctr
    sc = ServingContext(BucketLadder(min_bucket=64, max_bucket=CHUNK))
    loop = _mk_loop(model, X, y, tmp_path, session)
    sc.__enter__()
    loop.__enter__()
    results, errors = [], []
    try:
        for i in range(0, 512, CHUNK):          # the REAL serving tap path
            model.predict(X[i:i + CHUNK])
            rid = loop.tap.last_request_id()
            assert rid is not None
            loop.tap.tap_label(rid, y[i:i + CHUNK])
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and loop.trainer.status()["examples"] < 512):
            time.sleep(0.01)

        def hammer():
            try:
                end = time.monotonic() + 25
                while time.monotonic() < end:
                    r = loop.publish_cycle()
                    results.append(r)
                    if r["outcome"] == "closed":
                        return
            except BaseException as e:  # noqa: BLE001 - the assertion
                errors.append(e)

        th = threading.Thread(target=hammer)
        th.start()
        time.sleep(0.2)                         # publisher mid-flight...
    finally:
        sc.__exit__(None, None, None)           # ...serving exits FIRST
        loop.close()
    th.join(30)
    assert not th.is_alive(), "publisher hung across close()"
    assert not errors, errors
    allowed = {"published", "skipped", "rejected_shadow", "rejected_drift",
               "closed"}
    assert results and {r["outcome"] for r in results} <= allowed
    assert results[-1]["outcome"] == "closed"
    assert not loop.trainer.status()["alive"]
    # the store survived the race coherent: CURRENT (if any) parses and
    # points at a published version
    cur = ro.read_current(loop.store_root)
    if cur is not None:
        assert cur in ro.list_versions(loop.store_root)


def test_loop_resumes_after_trainer_sigkill_equivalent(ctr, session,
                                                       tmp_path):
    """A NEW OnlineLoop over the same log+checkpoint (the restarted
    process) resumes the trainer mid-log instead of replaying it."""
    model, X, y = ctr
    loop = _mk_loop(model, X, y, tmp_path, session,
                    trainer_kw={"chunk_rows": CHUNK, "join_window": 32,
                                "ckpt_steps": 2})
    _drive(loop, X, y, 0, 512)
    with inject_faults("trainer_crash:at=3"):
        with pytest.raises(TrainerCrashInjected):
            loop.trainer.consume_available()
    loop.close()
    loop2 = _mk_loop(model, X, y, tmp_path, session,
                     trainer_kw={"chunk_rows": CHUNK, "join_window": 32,
                                 "ckpt_steps": 2})
    try:
        assert loop2.trainer.resumed_from_step == 2
        loop2.trainer.consume_available()
        assert loop2.trainer.status()["steps"] == 4
        res = loop2.publish_cycle()
        assert res["outcome"] == "published"
    finally:
        loop2.close()


# ----------------------------------------------------------------- tooling
def test_online_top_status_probe(session):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from online_top import run_status
    finally:
        sys.path.pop(0)
    status = run_status(rows=512, session=session)
    tr = status["trainer"]
    assert tr["steps"] >= 4 and not tr["died"]
    assert tr["join_counts"]["joined"] >= 4
    assert status["last_outcome"] in ("published", "skipped")
    assert status["cycles"] == 1


# ------------------------------------------------------- docs ladder guard
def test_online_typed_errors_listed_in_degradation_ladder():
    """CI guard (satellite): every typed error class raised under
    ``online/`` (and the request log) must appear in the resilience
    doc's degradation ladder — an operator paged by one of these names
    greps the ladder first."""
    pat = re.compile(r"^class (\w+(?:Error|Injected))\b", re.M)
    names = set()
    online_dir = os.path.join(REPO, "orange3_spark_tpu", "online")
    paths = [os.path.join(online_dir, f) for f in os.listdir(online_dir)
             if f.endswith(".py")]
    paths.append(os.path.join(REPO, "orange3_spark_tpu", "io",
                              "reqlog.py"))
    for p in paths:
        with open(p, encoding="utf-8") as f:
            names |= set(pat.findall(f.read()))
    assert {"DriftDetectedError", "ShadowMismatchError",
            "OnlineTrainerError", "TrainerCrashInjected",
            "RequestLogCorruptionError"} <= names
    with open(os.path.join(REPO, "docs", "resilience.md"),
              encoding="utf-8") as f:
        doc = f.read()
    assert "## Degradation ladder" in doc
    ladder = doc.split("## Degradation ladder", 1)[1].split("\n## ", 1)[0]
    missing = sorted(n for n in names if n not in ladder)
    assert not missing, (
        f"typed online errors {missing} are raised under online/ but "
        "not listed in docs/resilience.md's degradation ladder — add "
        "them to the ladder (or stop raising them)")
