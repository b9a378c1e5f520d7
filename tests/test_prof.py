"""obs/prof.py — the goodput & memory attribution plane (ISSUE 12).

Covers the acceptance drills:

* the goodput decomposition of a REAL cached streaming fit — fractions
  partition the wall (sum 1.0 ± 0.02), the ledger's cache entry equals
  the legacy ``cache_bytes`` stage key;
* bottleneck-classifier hysteresis on synthetic stage feeds (no
  flapping at the boundary, decisive switches still switch);
* ledger concurrency — 8 threads racing register/release/snapshot;
* the ``POST /debug/profile`` contract — 200/409/429/503, atomic
  artifact dir;
* ``OTPU_PROF=0`` restores the PR-11 behavior bitwise (theta, report
  keys, gauges, and ``profile_trace`` falling back to the bare
  ``jax.profiler.trace``);
* ``utils.profiling.profile_trace`` routed through the capture path
  (serialized + rate-limited + atomic, public signature unchanged);
* the fleet digest's per-replica goodput/device-bytes parse;
* flight bundles carrying the ledger table (old bundles still render);
* ``tools/bench_trend.py`` / ``tools/goodput_view.py`` smokes;
* the endpoint-inventory doc-drift guard (every ``do_GET``/``do_POST``
  route across the obs + fleet servers appears in
  docs/observability.md, both directions).
"""

import json
import os
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from orange3_spark_tpu.obs import prof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def prof_env(tmp_path, monkeypatch):
    """Fresh prof plane: own artifact dir, rate limit reset, and reset
    again on exit so later tests see a clean window."""
    monkeypatch.setenv("OTPU_PROF_DIR", str(tmp_path / "prof"))
    monkeypatch.delenv("OTPU_PROF", raising=False)
    prof.reset_rate_limit()
    yield tmp_path
    prof.reset_rate_limit()


def _fit_hashed(session, epochs=3, rows=4096, prof_on=True):
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(3)
    X = np.concatenate([
        rng.standard_normal((rows, 4)).astype(np.float32),
        rng.integers(0, 500, (rows, 4)).astype(np.float32),
    ], axis=1)
    y = (rng.random(rows) < 0.3).astype(np.float32)
    est = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=4, n_cat=4, epochs=epochs,
        step_size=0.05, chunk_rows=512)
    ctx = prof.force_enabled() if prof_on else prof.force_disabled()
    with ctx:
        return est.fit_stream(array_chunk_source(X, y, chunk_rows=512),
                              session=session, cache_device=True)


# ------------------------------------------------- goodput decomposition
def test_fit_goodput_fractions_partition_the_wall(session, prof_env):
    model = _fit_hashed(session)
    d = model.run_report_.to_dict()
    assert d["report_schema"] == 2
    gp = d["goodput"]
    fracs = gp["fractions"]
    assert set(fracs) == {"device_compute", "input_wait", "host_encode",
                          "sync_wait", "framework"}
    assert abs(sum(fracs.values()) - 1.0) <= 0.02
    assert all(f >= 0.0 for f in fracs.values())
    assert gp["bottleneck"] in ("input_bound", "compute_bound",
                                "sync_bound", "framework_bound")
    # per-epoch classification recorded with hysteresis-stable labels
    assert gp["epochs"], "no epoch boundaries recorded"
    for e in gp["epochs"]:
        assert abs(sum(e["fractions"].values()) - 1.0) <= 0.02
    # the goodput gauges reflect the finished fit
    from orange3_spark_tpu.obs.registry import REGISTRY

    g = REGISTRY.get("otpu_goodput_fraction")
    total = sum(g.value(stage=s) for s in prof.STAGES)
    assert abs(total - 1.0) <= 0.02


def test_fit_ledger_cache_entry_matches_stage_times(session, prof_env):
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(4)
    rows = 4096
    X = np.concatenate([
        rng.standard_normal((rows, 4)).astype(np.float32),
        rng.integers(0, 500, (rows, 4)).astype(np.float32),
    ], axis=1)
    y = (rng.random(rows) < 0.3).astype(np.float32)
    stage_times: dict = {}
    with prof.force_enabled():
        model = StreamingHashedLinearEstimator(
            n_dims=1 << 12, n_dense=4, n_cat=4, epochs=2,
            step_size=0.05, chunk_rows=512,
        ).fit_stream(array_chunk_source(X, y, chunk_rows=512),
                     session=session, cache_device=True,
                     stage_times=stage_times)
    dm = model.run_report_.to_dict()["device_memory"]
    assert dm["cache_entry_bytes"] == stage_times["cache_bytes"]
    # the ledger counts PER CHIP: the session's rows are sharded over its
    # 'data' axis, so one chip holds that share of the cache
    on_chip = dm["cache_entry_chip_bytes"]
    assert on_chip * session.data_parallelism >= stage_times["cache_bytes"]
    assert on_chip <= stage_times["cache_bytes"]
    assert dm["owners"]["cache_chunks"] >= on_chip
    assert "model_state" in dm["owners"]
    assert dm["peak_bytes_fit"] >= on_chip
    assert dm["peak_global_bytes"] >= dm["cache_entry_bytes"]
    # reconciliation is REPORTED, never asserted — but it must be there
    rec = dm["reconciliation"]
    assert rec["ledger_bytes"] >= on_chip
    assert "delta_vs_live_bytes" in rec


# ------------------------------------------------- hysteresis classifier
def test_bottleneck_hysteresis_no_flap_at_boundary():
    """Feeds oscillating ±2% around input==compute equality must keep
    ONE label; a decisive challenger (past the margin) must flip it."""
    acc = prof.GoodputAccountant(hysteresis=0.1)
    # epoch 0: decisively input-bound
    first = acc._classify({"input_wait": 0.6, "device_compute": 0.2,
                           "sync_wait": 0.0})
    acc.bottleneck = first
    assert first == "input_bound"
    # boundary oscillation: compute edges ahead by < hysteresis, back
    # and forth — the label must NOT flap
    for delta in (+0.02, -0.02, +0.04, -0.04, +0.08, -0.08) * 3:
        label = acc._classify({"input_wait": 0.4,
                               "device_compute": 0.4 + delta,
                               "sync_wait": 0.0})
        acc.bottleneck = label
        assert label == "input_bound", delta
    # a decisive move past the margin flips it exactly once
    label = acc._classify({"input_wait": 0.3, "device_compute": 0.55,
                           "sync_wait": 0.0})
    acc.bottleneck = label
    assert label == "compute_bound"
    # and holds through the reverse boundary oscillation
    for delta in (+0.05, -0.05, +0.09, -0.09):
        label = acc._classify({"input_wait": 0.45 + delta,
                               "device_compute": 0.45,
                               "sync_wait": 0.0})
        acc.bottleneck = label
        assert label == "compute_bound", delta


def test_bottleneck_synthetic_epoch_feed(monkeypatch):
    """End-to-end through epoch_boundary: synthetic add() feeds drive
    the per-epoch classification and the instants fire on CHANGE only."""
    monkeypatch.setenv("OTPU_PROF", "1")
    acc = prof.GoodputAccountant(hysteresis=0.1)
    # epoch 0: all input wait
    acc.add("input_wait", 0.5)
    e0 = acc.epoch_boundary(0)
    assert e0["bottleneck"] == "input_bound"
    # epoch 1: device dominates decisively
    acc.add("device_compute", 5.0)
    e1 = acc.epoch_boundary(1)
    assert e1["bottleneck"] == "compute_bound"
    # epoch 2: sync dominates decisively
    acc.add("sync_wait", 50.0)
    e2 = acc.epoch_boundary(2)
    assert e2["bottleneck"] == "sync_bound"
    res = acc.finish(wall_s=60.0)
    assert res["bottleneck"] == "sync_bound"
    assert [e["epoch"] for e in res["epochs"]] == [0, 1, 2]


def test_goodput_framework_bound_when_nothing_measured():
    acc = prof.GoodputAccountant(hysteresis=0.1)
    res = acc.finish(wall_s=1.0)
    assert res["fractions"]["framework"] == 1.0
    assert res["bottleneck"] == "framework_bound"


# --------------------------------------------------- ledger concurrency
def test_ledger_register_release_snapshot_race(monkeypatch):
    """8 threads hammer set/release/snapshot on one ledger; every
    snapshot must be internally consistent and the final state exact."""
    monkeypatch.setenv("OTPU_PROF", "1")
    led = prof.DeviceMemoryLedger()
    errors: list = []
    stop = threading.Event()

    def mutator(tid):
        try:
            for i in range(2000):
                led.set(f"owner{tid % 4}", f"e{tid}-{i % 8}",
                        (i % 64) * 1024)
                if i % 3 == 0:
                    led.release(f"owner{tid % 4}", f"e{tid}-{(i + 4) % 8}")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                snap = led.snapshot()
                assert snap["total_bytes"] >= 0
                assert sum(snap["owners"].values()) == snap["total_bytes"]
                assert snap["peak_bytes"] >= snap["total_bytes"]
                led.reconcile()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=mutator, args=(t,))
               for t in range(6)] + [threading.Thread(target=reader)
                                     for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads[:6]:
        t.join(30)
    stop.set()
    for t in threads[6:]:
        t.join(30)
    assert not errors, errors
    # final consistency: entries sum == total == owner sums
    snap = led.snapshot(max_entries=10_000)
    assert sum(e["bytes"] for e in snap["entries"]) == snap["total_bytes"]
    # release everything -> zero
    for e in snap["entries"]:
        led.release(e["owner"], e["name"])
    assert led.total() == 0


def test_ledger_watermark_tracks_fit_peak(monkeypatch):
    monkeypatch.setenv("OTPU_PROF", "1")
    led = prof.DeviceMemoryLedger()
    led.set("a", "x", 100)
    wm = led.watermark()
    led.set("a", "y", 900)
    led.release("a", "y")
    led.set("a", "z", 50)
    assert wm.close() == 1000
    assert led.total() == 150


# ------------------------------------------------- /debug/profile contract
def _post(url, timeout=120):
    """POST with a deadline sized to a LOADED CI box, plus one structured
    retry on a pure socket timeout. A capture itself takes milliseconds;
    what the old 30 s deadline occasionally lost to was the obs server's
    accept/handler thread being starved by a co-scheduled suite member —
    that stall does not reproduce, a genuinely wedged endpoint does, so
    the retry is the flake net and a real hang still fails (typed)."""
    req = urllib.request.Request(url, method="POST", data=b"")
    for attempt in (0, 1):
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.load(e)
        except (TimeoutError, urllib.error.URLError) as e:
            reason = getattr(e, "reason", e)
            if attempt == 0 and isinstance(reason, (TimeoutError, OSError)):
                continue
            raise
    raise AssertionError("unreachable")


def test_debug_profile_endpoint_contract(session, prof_env, monkeypatch):
    from orange3_spark_tpu.obs.server import TelemetryServer

    srv = TelemetryServer(0).start()
    try:
        monkeypatch.setenv("OTPU_PROF", "1")
        # pin the rate window far above any loaded-box stall: the 429
        # branch below must see the second POST INSIDE the window even
        # when the suite wedges this test for a minute between requests
        monkeypatch.setenv("OTPU_PROF_RATE_S", "3600")
        code, body = _post(srv.url + "/debug/profile?duration_ms=5")
        assert code == 200, body
        assert os.path.isdir(body["path"])
        with open(os.path.join(body["path"], "snapshot.json")) as f:
            snap = json.load(f)
        assert snap["prof_schema"] == prof.PROF_SCHEMA_VERSION
        assert "ledger" in snap and "registry" in snap and "knobs" in snap
        # no torn .tmp sibling left behind (the atomic-dir contract)
        parent = os.path.dirname(body["path"])
        assert not [n for n in os.listdir(parent) if ".tmp" in n]
        # rate limit: an immediate second capture answers 429
        code2, body2 = _post(srv.url + "/debug/profile?duration_ms=5")
        assert code2 == 429 and body2["error"] == "rate_limited"
        # serialization: while one capture runs, a second answers 409
        prof.reset_rate_limit()
        assert prof._capture_lock.acquire(blocking=False)
        try:
            code3, body3 = _post(srv.url + "/debug/profile?duration_ms=5")
            assert code3 == 409 and body3["error"] == "capture_busy"
        finally:
            prof._capture_lock.release()
        # kill-switch: 503, and NO capture counter tick for it
        monkeypatch.setenv("OTPU_PROF", "0")
        prof.reset_rate_limit()
        code4, body4 = _post(srv.url + "/debug/profile")
        assert code4 == 503 and body4["error"] == "prof_disabled"
    finally:
        srv.stop()


def test_debug_profile_rejects_concurrent_capture_409_live(
        session, prof_env, monkeypatch):
    """Two REAL concurrent captures: exactly one wins, the loser gets
    CaptureBusyError (the one-at-a-time contract, not just the lock)."""
    monkeypatch.setenv("OTPU_PROF", "1")
    monkeypatch.setenv("OTPU_PROF_RATE_S", "0")
    results: list = []
    started = threading.Event()

    def long_capture():
        def body():
            started.set()
            import time as _t

            _t.sleep(0.4)
        try:
            results.append(("ok", prof.capture(reason="racer", body=body)))
        except Exception as e:  # noqa: BLE001
            results.append(("err", e))

    t = threading.Thread(target=long_capture)
    t.start()
    assert started.wait(10)
    with pytest.raises(prof.CaptureBusyError):
        prof.capture(duration_ms=1, reason="loser")
    t.join(30)
    assert results and results[0][0] == "ok"


# -------------------------------------------------- OTPU_PROF=0 parity
def test_kill_switch_restores_pr11_behavior(session, prof_env):
    from orange3_spark_tpu.obs.registry import REGISTRY

    m_on = _fit_hashed(session, epochs=2, prof_on=True)
    d_on = m_on.run_report_.to_dict()
    assert "goodput" in d_on and "device_memory" in d_on
    REGISTRY.get("otpu_device_bytes").reset()
    m_off = _fit_hashed(session, epochs=2, prof_on=False)
    d_off = m_off.run_report_.to_dict()
    # bitwise theta parity: the accounting observes, never steers
    import jax

    for a, b in zip(jax.tree.leaves(m_on.theta),
                    jax.tree.leaves(m_off.theta)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the PR-11 report dict: no goodput/device_memory keys, same rest
    assert "goodput" not in d_off and "device_memory" not in d_off
    assert set(d_on) - set(d_off) == {"goodput", "device_memory"}
    # no ledger gauge children were ticked by the kill-switched fit
    g = REGISTRY.get("otpu_device_bytes")
    assert all(v == 0 for v in (g.value(owner=o) for o in (
        "cache_chunks", "model_state", "replay_plans")))


def test_profile_trace_routes_through_capture_path(prof_env, monkeypatch):
    import jax.numpy as jnp

    from orange3_spark_tpu.utils.profiling import profile_trace

    monkeypatch.setenv("OTPU_PROF", "1")
    out = str(prof_env / "pt")
    with profile_trace(out):
        jnp.zeros(8).block_until_ready()
    # atomic publish: the final dir exists, carries the snapshot, and
    # no .tmp sibling survived
    assert os.path.isdir(out)
    assert os.path.exists(os.path.join(out, "snapshot.json"))
    assert not [n for n in os.listdir(str(prof_env)) if ".tmp" in n]
    # rate-limited like every capture
    with pytest.raises(prof.CaptureRateLimitedError):
        with profile_trace(str(prof_env / "pt2")):
            pass
    # kill-switch: the bare jax.profiler.trace wrapper — no snapshot,
    # no rate limit, no serialization ceremony
    monkeypatch.setenv("OTPU_PROF", "0")
    out0 = str(prof_env / "pt0")
    with profile_trace(out0):
        jnp.zeros(8).block_until_ready()
    assert os.path.isdir(out0)
    assert not os.path.exists(os.path.join(out0, "snapshot.json"))


def test_aborted_fit_releases_model_state_entry(session, prof_env):
    """A fit that raises (divergence) must not strand its model_state
    ledger entry — the flight bundle written for the anomaly is exactly
    where a phantom tenant would mislead (the ledger_guard contract)."""
    import gc

    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    rng = np.random.default_rng(5)
    X = np.concatenate([
        rng.standard_normal((1024, 4)).astype(np.float32),
        rng.integers(0, 500, (1024, 4)).astype(np.float32),
    ], axis=1)
    y = (rng.random(1024) < 0.3).astype(np.float32)

    def poisoned_source():
        yield X[:512], y[:512], None
        # NON-transient: the resilience layer must not absorb it
        raise RuntimeError("poisoned mid-fit")

    gc.collect()    # an earlier test's model may still await collection
    before = prof.LEDGER.owner_bytes().get("model_state", 0)
    with prof.force_enabled():
        with pytest.raises(RuntimeError, match="poisoned"):
            StreamingHashedLinearEstimator(
                n_dims=1 << 10, n_dense=4, n_cat=4, epochs=2,
                step_size=0.05, chunk_rows=512,
            ).fit_stream(lambda: poisoned_source(), session=session)
    gc.collect()    # the frame-scoped guard fires once the tb is gone
    assert prof.LEDGER.owner_bytes().get("model_state", 0) == before


def test_trace_capture_preserves_artifact_when_body_raises(
        prof_env, monkeypatch):
    """Profiling a failing fit is the capture you MOST want: the trace
    and snapshot must still publish, with the body error noted."""
    import jax.numpy as jnp

    from orange3_spark_tpu.utils.profiling import profile_trace

    monkeypatch.setenv("OTPU_PROF", "1")
    out = str(prof_env / "failing")
    with pytest.raises(RuntimeError, match="boom"):
        with profile_trace(out):
            jnp.zeros(4).block_until_ready()
            raise RuntimeError("boom")
    assert os.path.isdir(out)
    with open(os.path.join(out, "snapshot.json")) as f:
        snap = json.load(f)
    assert snap["body_error"].startswith("RuntimeError: boom")
    assert not [n for n in os.listdir(str(prof_env)) if ".tmp" in n]


def test_end_fit_closes_abandoned_watermark(monkeypatch):
    """begin_fit/end_fit without finish() (the bench A/B shape, an
    aborted fit) must not leak watermarks — the watermark dict is
    walked on EVERY ledger mutation."""
    import gc

    monkeypatch.setenv("OTPU_PROF", "1")

    def open_watermarks():
        # finalizer releases are DEFERRED (lock-free inbox): any ledger
        # operation drains them — total() is the cheapest
        prof.LEDGER.total()
        return len(prof.LEDGER._watermarks)

    # drain any abandoned accountant a previous test left in the
    # contextvar (its watermark closes via the same finalizer)
    prof.end_fit(prof.begin_fit())
    gc.collect()
    before = open_watermarks()
    for _ in range(16):
        prof.end_fit(prof.begin_fit())
    assert open_watermarks() == before
    # an ABORTED fit never reaches end_fit: the accountant's own
    # finalizer closes the watermark once the next begin_fit drops the
    # contextvar reference and GC collects it
    for _ in range(8):
        prof.begin_fit()          # abandoned, no end_fit
    prof.end_fit(prof.begin_fit())
    gc.collect()
    assert open_watermarks() == before


# ------------------------------------------------- fleet digest surface
def test_fleet_digest_carries_goodput_and_device_bytes():
    from orange3_spark_tpu.obs.fleetobs import FleetCollector
    from orange3_spark_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    g = reg.gauge("otpu_goodput_fraction", "gp")
    for stage, v in (("device_compute", 0.7), ("input_wait", 0.2),
                     ("host_encode", 0.0), ("sync_wait", 0.0),
                     ("framework", 0.1)):
        g.set(v, stage=stage)
    d = reg.gauge("otpu_device_bytes", "dev")
    d.set(1 << 20, owner="serve_executables")
    d.set(1 << 10, owner="model_state")

    class Client:
        name = "replica-0"

        def get_text(self, path, timeout_s=None):
            return 200, reg.to_prometheus()

    col = FleetCollector([Client()], scrape_s=10.0)
    digest = col.scrape_once()
    load = digest.replicas[0]
    assert load.goodput == {"device_compute": 0.7, "input_wait": 0.2,
                            "host_encode": 0.0, "sync_wait": 0.0,
                            "framework": 0.1}
    assert load.device_bytes == {"serve_executables": float(1 << 20),
                                 "model_state": float(1 << 10)}
    # the digest round-trips to_dict (the supervisor-hook consumers)
    rd = digest.to_dict()["replicas"][0]
    assert rd["goodput"]["device_compute"] == 0.7


# ------------------------------------------------ flight bundle + tools
def test_flight_bundle_carries_ledger_table(monkeypatch, tmp_path):
    monkeypatch.setenv("OTPU_PROF", "1")
    prof.LEDGER.set("model_state", "flight_test", 4096)
    try:
        from orange3_spark_tpu.obs import flight

        bundle = flight.collect_bundle("test")
        dm = bundle["device_memory"]
        assert dm["owners"].get("model_state", 0) >= 4096
        assert any(e["name"] == "flight_test" for e in dm["entries"])
        # the viewer renders it, and an OLD bundle (no key) still renders
        import tools.flight_view as fv

        assert "device-memory ledger" in fv.render(bundle)
        old = {k: v for k, v in bundle.items() if k != "device_memory"}
        assert "flight bundle" in fv.render(old)
    finally:
        prof.LEDGER.release("model_state", "flight_test")


def test_bench_trend_flags_ratio_regressions_only(tmp_path):
    import tools.bench_trend as bt

    def bank(n, value, speedup):
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "criteo_hashed_logreg_rows_per_sec_per_chip",
                       "value": value, "unit": "rows/s/chip",
                       "optim_step_speedup": speedup},
        }))
        return str(p)

    # rows/s collapses 10x (container delta — NOT a regression signal);
    # the same-run ratio drops 40% (IS the regression signal)
    paths = [bank(1, 350000.0, 2.4), bank(2, 35000.0, 1.4)]
    trend = bt.run_trend(paths)
    assert trend["rounds"] == [1, 2]
    regs = trend["regressions"]
    assert len(regs) == 1
    assert regs[0]["key"] == "optim_step_speedup"
    assert regs[0]["drop_pct"] > 20
    # a <20% ratio wiggle does not flag
    paths2 = [bank(1, 1000.0, 2.0), bank(2, 900.0, 1.9)]
    assert not bt.run_trend(paths2)["regressions"]
    # and the REAL banked rounds parse without crashing
    real = bt.run_trend(root=REPO)
    assert real["rounds"], "no BENCH_r*.json found in the repo root?"


def test_goodput_view_demo_smoke(session, prof_env, monkeypatch):
    monkeypatch.setenv("OTPU_PROF", "1")
    import tools.goodput_view as gv

    out = gv.run_view(session=session, rows=2048)
    assert out["fractions_sum"] is not None
    assert abs(out["fractions_sum"] - 1.0) <= 0.02
    assert out["ledger_owners"] and "cache_chunks" in out["ledger_owners"]
    # file mode: render a dumped report
    from orange3_spark_tpu.obs.report import RunReport  # noqa: F401

    path = str(prof_env / "report.json")
    model = _fit_hashed(session, epochs=2, rows=2048)
    model.run_report_.to_json(path)
    out2 = gv.run_view(path)
    assert out2["source"] == "report"
    assert out2["bottleneck"] is not None


def test_obs_dump_profile_flag(session, prof_env, monkeypatch):
    monkeypatch.setenv("OTPU_PROF", "1")
    import tools.obs_dump as od

    out = od.run_dump(rows=2048, session=session,
                      trace_out=str(prof_env / "trace.json"), profile=True)
    assert out["profile_path"] and os.path.isdir(out["profile_path"])
    assert out["profile_valid"] is True


# ------------------------------------------- endpoint-inventory guard
_ROUTE_RE = re.compile(r'route\s*==\s*"(/[a-z_/]+)"')
_DOC_ROUTE_RE = re.compile(r"^\|\s*`(?:GET|POST)\s+(/\S+)`")


def test_endpoint_inventory_doc_drift():
    """Every do_GET/do_POST route literal across the obs server and the
    fleet RPC server appears in docs/observability.md's endpoint
    inventory — and every inventory row names a route the source still
    serves (two directions, the knob/metric guards' spirit)."""
    served = set()
    for rel in ("orange3_spark_tpu/obs/server.py",
                "orange3_spark_tpu/fleet/rpc.py"):
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            served.update(_ROUTE_RE.findall(f.read()))
    assert served, "route grep found nothing — pattern rotted?"
    documented = set()
    with open(os.path.join(REPO, "docs", "observability.md"),
              encoding="utf-8") as f:
        for line in f:
            m = _DOC_ROUTE_RE.match(line.strip())
            if m:
                documented.add(m.group(1))
    missing = served - documented
    assert not missing, (
        f"served routes missing from the docs/observability.md endpoint "
        f"inventory: {sorted(missing)}")
    stale = documented - served
    assert not stale, (
        f"documented routes no server serves any more: {sorted(stale)}")
