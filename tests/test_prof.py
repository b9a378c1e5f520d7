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


@pytest.fixture()
def fresh_ledger(monkeypatch):
    """A ledger of this test's own: the process-wide one carries the marks
    and peaks of every test before it."""
    led = prof.DeviceMemoryLedger()
    # the CPU's allocator tells nothing: the live arrays' own sum stands in
    led.allocator = prof.LiveArraysAllocator()
    monkeypatch.setattr(prof, "LEDGER", led)
    return led


class ScriptedAllocator:
    """``memory_stats()`` of one device for a ledger's marks: ``in_use()``
    says the bytes in use, the live peak follows them as an allocator's
    would (``bump`` lifts it above them: bytes that came and went between
    two marks), ``temp`` is the reserved region's."""

    def __init__(self, in_use):
        self.in_use, self.peak, self.temp = in_use, 0, 0

    def bump(self, nbytes):
        self.peak = max(self.peak, self.in_use() + nbytes)

    def __call__(self):
        self.peak = max(self.peak, self.in_use())
        return [{"bytes_in_use": self.in_use(),
                 "peak_bytes_in_use": self.peak,
                 "peak_bytes_reserved": self.temp}]


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


def test_fit_ledger_cache_entry_matches_stage_times(session, prof_env,
                                                    fresh_ledger,
                                                    monkeypatch):
    monkeypatch.setattr(prof, "CENSUS_RISE_BYTES", 1024)
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
    # the account against the allocator rides every report: the fit's
    # marks, the interval that set the live peak, and a census whose
    # owners (named + unnamed) and runtime-held bytes add up to what the
    # allocator had in use at its mark
    assert {"between_fits", "model_state", "chunk", "replay_stack",
            "model_handover"} <= {m["name"] for m in dm["marks"]}
    hw = dm["high_water"]
    assert (hw["named_bytes"] + hw["unnamed_bytes"] + hw["transient_bytes"]
            == hw["peak_bytes_in_use"])
    census = dm["census"]
    assert set(census) >= {"mark", "span", "fit", "device", "bytes_in_use",
                           "live_bytes", "runtime_held_bytes", "owners",
                           "groups", "groups_dropped", "arrays"}
    assert (sum(census["owners"].values()) + census["runtime_held_bytes"]
            == census["bytes_in_use"])
    at = next(m for m in dm["marks"] if m["n"] == census["mark"])
    assert at["bytes_in_use"] == census["bytes_in_use"]
    assert census["owners"]["cache_chunks"] > 0
    assert census["owners"]["model_state"] > 0


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
    led.allocator = prof.LiveArraysAllocator()
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
                rec = led.mark("race")
                assert rec["ledger_high_bytes"] >= rec["ledger_bytes"] >= 0
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


# ------------------------------------------------------ the HBM account
def _ledger_in_use(led):
    """An allocator that has exactly what the ledger was told in use."""
    return ScriptedAllocator(led.total)


def test_census_names_handed_array_not_a_stranger(fresh_ledger,
                                                  monkeypatch):
    """The array handed to ``ledger_set_tree`` counts under its owner; one
    of the same shape and dtype that no entry was handed is ``unnamed``."""
    import jax.numpy as jnp

    monkeypatch.setenv("OTPU_PROF", "1")
    monkeypatch.setattr(prof, "CENSUS_RISE_BYTES", 0)
    mine = jnp.full((1000, 3), 7.0, jnp.float32)
    stranger = jnp.full((1000, 3), 9.0, jnp.float32)
    prof.ledger_set_tree("model_state", "mine", {"w": mine})
    fresh_ledger.mark("handed")
    census = fresh_ledger.snapshot()["census"]
    groups = {(g["owner"], g["dtype"], tuple(g["shape"])): g
              for g in census["groups"]}
    assert groups[("model_state", "float32", (1000, 3))]["count"] == 1
    assert groups[("model_state", "float32", (1000, 3))]["bytes"] == 12000
    assert groups[("unnamed", "float32", (1000, 3))]["count"] >= 1
    assert census["owners"]["model_state"] == 12000
    assert census["owners"]["unnamed"] >= stranger.nbytes
    # released: the same array is a stranger now
    prof.ledger_release("model_state", "mine")
    fresh_ledger.mark("released")
    fresh_ledger._take_census(fresh_ledger.snapshot()["marks"][-1])
    assert "model_state" not in fresh_ledger.snapshot()["census"]["owners"]
    del mine, stranger


def test_census_follows_a_donated_state_through_now(fresh_ledger,
                                                    monkeypatch):
    """An owner whose arrays are donated away every step says which it
    holds ``now``: the census names the successor, and the ledger keeps
    neither the function nor the arrays alive."""
    import gc
    import weakref

    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("OTPU_PROF", "1")
    state = jnp.zeros((2048,), jnp.float32)

    def now():
        return state

    prof.ledger_set_tree("model_state", "s", state, now=now)
    state = jax.jit(lambda x: x + 1, donate_argnums=0)(state)
    fresh_ledger._take_census(fresh_ledger.mark("step"))
    assert fresh_ledger.snapshot()["census"]["owners"]["model_state"] == 8192
    gone = weakref.ref(now)
    del now
    gc.collect()
    assert gone() is None
    fresh_ledger._take_census(fresh_ledger.mark("frame_gone"))
    assert "model_state" not in fresh_ledger.snapshot()["census"]["owners"]


def test_census_counts_a_shard_on_the_fullest_device(fresh_ledger,
                                                     monkeypatch):
    """On a (2,2) mesh a table sharded over ``model`` counts by the half
    one device holds, on the device the allocator says is fullest."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setenv("OTPU_PROF", "1")
    devices = jax.local_devices()
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    table = jax.device_put(np.ones((4096, 4), np.float32),
                           NamedSharding(mesh, P("model", None)))
    alone = jax.device_put(np.ones((512,), np.float32), devices[5])
    prof.ledger_set_tree("model_state", "t", table)
    assert fresh_ledger.total() == 4096 * 4 * 4 // 2
    # the allocator: device 3 the fullest (it holds a shard), then device 5
    stats = [{"bytes_in_use": 0, "peak_bytes_in_use": 0,
              "peak_bytes_reserved": 0} for _ in devices]
    stats[3] = {"bytes_in_use": 40000, "peak_bytes_in_use": 50000,
                "peak_bytes_reserved": 1000}
    fresh_ledger.allocator = lambda: stats
    rec = fresh_ledger.mark("put")
    assert rec["device"] == 3 and rec["peak_bytes_reserved"] == 1000
    fresh_ledger._take_census(rec)
    census = fresh_ledger.snapshot()["census"]
    assert census["owners"]["model_state"] == 4096 * 4 * 4 // 2
    named = [g for g in census["groups"] if g["owner"] == "model_state"]
    assert named == [{"owner": "model_state", "dtype": "float32",
                      "shape": [2048, 4], "count": 1, "bytes": 32768}]
    assert (sum(census["owners"].values()) + census["runtime_held_bytes"]
            == 40000)
    # an array that lives on another device is not on this one's account
    stats[5] = {"bytes_in_use": 60000, "peak_bytes_in_use": 60000,
                "peak_bytes_reserved": 0}
    rec = fresh_ledger.mark("elsewhere")
    assert rec["device"] == 5
    fresh_ledger._take_census(rec)
    census = fresh_ledger.snapshot()["census"]
    assert "model_state" not in census["owners"]
    assert census["owners"]["unnamed"] >= alone.nbytes


def test_transient_bytes_name_the_closing_span(fresh_ledger, monkeypatch):
    """The live peak rises between two marks whose ``bytes_in_use`` are
    equal: every byte of the rise lived inside the span the second mark
    closes, and the interval carries its name; the temp peak's interval
    is kept separately."""
    monkeypatch.setenv("OTPU_PROF", "1")
    alloc = fresh_ledger.allocator = _ledger_in_use(fresh_ledger)
    fresh_ledger.set("model_state", "m", 6_000_000)
    fresh_ledger.mark("replay_drain")
    hw = fresh_ledger.snapshot()["high_water"]
    assert hw["span"] == "replay_drain" and hw["transient_bytes"] == 0
    assert hw["since"] is None          # the process's first mark
    alloc.bump(2_000_000)               # an undonated output, dropped
    fresh_ledger.mark("finalize")
    snap = fresh_ledger.snapshot()
    hw = snap["high_water"]
    assert hw["span"] == "finalize" and hw["rise_bytes"] == 2_000_000
    assert hw["since"] == "replay_drain"
    assert hw["transient_bytes"] == 2_000_000
    assert hw["named_bytes"] == 6_000_000 and hw["unnamed_bytes"] == 0
    assert hw["peak_bytes_in_use"] == 8_000_000
    assert snap["high_water_temp"] is None
    # temp rises in a later interval; the live peak's interval stays
    alloc.temp = 1_000_000
    fresh_ledger.mark("replay")
    snap = fresh_ledger.snapshot()
    assert snap["high_water"]["span"] == "finalize"
    assert snap["high_water_temp"]["span"] == "replay"
    assert snap["high_water_temp"]["mark"] != snap["high_water"]["mark"]
    # an entry set and released inside an interval: the ledger's high
    fresh_ledger.set("replay_plans", "stack", 500_000)
    fresh_ledger.release("replay_plans", "stack")
    rec = fresh_ledger.mark("replay_stack")
    assert rec["ledger_bytes"] == 6_000_000
    assert rec["ledger_high_bytes"] == 6_500_000
    # where the fuller mark is the earlier one, the named bytes are its
    fresh_ledger.set("cache_chunks", "c", 3_000_000)
    fresh_ledger.mark("chunk")
    fresh_ledger.release("cache_chunks", "c")
    alloc.bump(4_000_000)
    fresh_ledger.mark("evaluate")
    hw = fresh_ledger.snapshot()["high_water"]
    assert hw["fuller_mark"] == "before" and hw["named_bytes"] == 9_000_000
    assert hw["transient_bytes"] == 1_000_000
    assert (hw["named_bytes"] + hw["unnamed_bytes"] + hw["transient_bytes"]
            == hw["peak_bytes_in_use"] == 10_000_000)


def test_second_fit_of_same_shapes_takes_no_census(session, prof_env,
                                                   fresh_ledger,
                                                   monkeypatch):
    """A census is a walk of ``jax.live_arrays()``: the first fit takes a
    few as its peak rises, a second fit of the same shapes none."""
    import gc

    import jax

    monkeypatch.setattr(prof, "CENSUS_RISE_BYTES", 4096)
    fresh_ledger.allocator = _ledger_in_use(fresh_ledger)
    walks = []
    real = jax.live_arrays
    monkeypatch.setattr(jax, "live_arrays",
                        lambda *a: walks.append(1) or real(*a))
    model = _fit_hashed(session)
    first = len(walks)
    assert first == fresh_ledger.censuses_taken >= 1
    del model
    gc.collect()
    model = _fit_hashed(session)
    assert len(walks) == first == fresh_ledger.censuses_taken
    marks = fresh_ledger.snapshot()["marks"]
    assert {m["fit"] for m in marks} == {0, 1}


def test_fit_marks_where_its_spans_close(session, prof_env, fresh_ledger):
    """Every site of the hashed fit, in the order the fit passes them, and
    each mark an ``hbm_mark`` instant with the same numbers in the ring."""
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )
    from orange3_spark_tpu.obs import trace

    rng = np.random.default_rng(5)
    X = np.concatenate([
        rng.standard_normal((2048, 4)).astype(np.float32),
        rng.integers(0, 500, (2048, 4)).astype(np.float32)], axis=1)
    y = (rng.random(2048) < 0.3).astype(np.float32)
    trace.clear()
    with prof.force_enabled():
        model = StreamingHashedLinearEstimator(
            n_dims=1 << 12, n_dense=4, n_cat=4, epochs=3, step_size=0.05,
            chunk_rows=512, optim_update="sparse_adagrad",
        ).fit_stream(array_chunk_source(X, y, chunk_rows=512),
                     session=session, cache_device=True, holdout_chunks=1)
        model.evaluate_device(model.holdout_chunks_)
    marks = fresh_ledger.snapshot()["marks"]
    assert [m["name"] for m in marks] == (
        ["between_fits", "model_state"] + ["chunk"] * 3 + ["finite_check",
         "replay_stack", "replay_drain", "replay", "finite_check",
         "finalize", "model_handover", "eval_chunk", "evaluate"])
    assert [m["n"] for m in marks] == list(range(1, len(marks) + 1))
    # the stack is an entry while it lives: the ledger's high of its span
    stack = next(m for m in marks if m["name"] == "replay_stack")
    assert stack["ledger_bytes"] > marks[5]["ledger_bytes"]
    instants = [e for e in trace.events()
                if e[0] == "i" and e[1] == "hbm_mark"]
    assert [e[5]["span"] for e in instants] == [m["name"] for m in marks]
    for e, m in zip(instants, marks):
        assert e[5]["bytes_in_use"] == m["bytes_in_use"]
        assert e[5]["peak_bytes_in_use"] == m["peak_bytes_in_use"]
        assert e[5]["ledger_bytes"] == m["ledger_bytes"]


def test_prof_off_takes_no_mark_and_fit_is_bitwise_same(session, prof_env,
                                                        fresh_ledger):
    off = _fit_hashed(session, prof_on=False)
    snap = fresh_ledger.snapshot()
    assert snap["marks"] == [] and snap["high_water"] is None
    assert snap["census"] is None and snap["censuses_taken"] == 0
    with prof.force_disabled():
        assert fresh_ledger.mark("off") is None
    on = _fit_hashed(session, prof_on=True)
    assert fresh_ledger.snapshot()["marks"]
    import jax

    for a, b in zip(jax.tree.leaves(off.theta), jax.tree.leaves(on.theta)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_marks_ride_the_spans_off_with_obs(fresh_ledger, monkeypatch):
    from orange3_spark_tpu.obs import trace

    monkeypatch.setenv("OTPU_PROF", "1")
    with trace.force_disabled():
        with trace.span("chunk", hbm=True):
            pass
        assert fresh_ledger.mark("by_hand") is None
    assert fresh_ledger.snapshot()["marks"] == []
    with trace.force_enabled():
        with trace.span("chunk", hbm=True):
            pass
        with trace.stage("replay", hbm=True):
            pass
        with trace.span("parse"):
            pass
    assert [m["name"] for m in fresh_ledger.snapshot()["marks"]] == [
        "chunk", "replay"]


@pytest.mark.parametrize("allocator", ["scripted", "live_arrays"])
def test_mark_never_waits_for_the_device(fresh_ledger, monkeypatch,
                                         allocator):
    """Neither a mark nor its census may block on the device: with every
    wait patched to raise, both still come through — from the allocator's
    own numbers and from the live arrays' sum where there is none."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("OTPU_PROF", "1")
    monkeypatch.setattr(prof, "CENSUS_RISE_BYTES", 0)
    x = jnp.ones((4096,), jnp.float32)
    prof.ledger_set_tree("model_state", "x", x)
    if allocator == "scripted":
        fresh_ledger.allocator = _ledger_in_use(fresh_ledger)

    def no_wait(*a, **kw):
        raise AssertionError("a mark waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", no_wait)
    monkeypatch.setattr(jax, "device_get", no_wait)
    monkeypatch.setattr(type(x), "block_until_ready", no_wait)
    monkeypatch.setattr(np, "asarray", no_wait)
    rec = fresh_ledger.mark("chunk")
    assert rec is not None and rec["bytes_in_use"] >= x.nbytes
    census = fresh_ledger.snapshot()["census"]
    assert census["mark"] == rec["n"]
    assert census["owners"]["model_state"] == x.nbytes


def test_marks_are_bounded(fresh_ledger, monkeypatch):
    monkeypatch.setenv("OTPU_PROF", "1")
    fresh_ledger.allocator = _ledger_in_use(fresh_ledger)
    for fit in range(3):
        fresh_ledger.mark("between_fits", first=True)
        for i in range(3 * prof.MARKS_PER_FIT):
            fresh_ledger.mark("chunk")
    marks = fresh_ledger.snapshot()["marks"]
    assert len(marks) == 2 * prof.MARKS_PER_FIT
    assert {m["fit"] for m in marks} == {1, 2}       # the last two fits
    assert marks[-1]["n"] == 3 * (3 * prof.MARKS_PER_FIT + 1)
    # a failing allocator costs the fit nothing: no mark, no exception

    def broken():
        raise RuntimeError("no stats today")

    fresh_ledger.allocator = broken
    assert fresh_ledger.mark("chunk") is None
    # nor does a backend without allocator statistics (the CPU's own)
    fresh_ledger.allocator = prof._device_stats
    assert fresh_ledger.mark("chunk") is None
    assert fresh_ledger.snapshot()["marks"][-1]["n"] == marks[-1]["n"]


def test_cache_hands_its_arrays_to_the_ledger(fresh_ledger, monkeypatch):
    """The census knows the cached chunks by identity — offered one by
    one, the holdout's taken out again — and a chunk of the same shape
    that the cache never held is a stranger."""
    import jax.numpy as jnp

    from orange3_spark_tpu.io.streaming import _DeviceCache

    monkeypatch.setenv("OTPU_PROF", "1")

    def chunk(v):
        return ({"dense": jnp.full((256, 4), v, jnp.bfloat16),
                 "cat": jnp.full((256, 3), v, jnp.uint32)},
                jnp.int32(256), jnp.zeros((1,)), jnp.zeros((1,)))

    def cached_bytes():
        fresh_ledger._take_census(fresh_ledger.mark("offer"))
        snap = fresh_ledger.snapshot()
        return (snap["census"]["owners"].get("cache_chunks", 0),
                snap["owners"].get("cache_chunks", 0))

    cache = _DeviceCache(True, 1 << 20, may_exclude_tail=1)
    chunks = [chunk(i) for i in range(4)]
    stranger = chunk(9)
    for c in chunks:
        cache.offer(c)
    one = prof.tree_chip_bytes(chunks[0])
    assert cached_bytes() == (4 * one, 4 * one)
    cache.exclude({id(chunks[-1][0])})          # the holdout tail
    assert cached_bytes() == (3 * one, 3 * one)
    census = fresh_ledger.snapshot()["census"]
    assert census["owners"]["unnamed"] >= 2 * one   # holdout + stranger
    del cache, stranger
    import gc

    gc.collect()
    assert cached_bytes() == (0, 0)


def test_owned_entry_goes_with_its_object(session, fresh_ledger,
                                          monkeypatch):
    """A table put from the host is a ``tables`` entry while it lives and
    its put a mark; the entry goes with the table."""
    import gc

    from orange3_spark_tpu.core.table import TpuTable

    monkeypatch.setenv("OTPU_PROF", "1")
    t = TpuTable.from_arrays(np.ones((64, 3), np.float32), session=session)
    snap = fresh_ledger.snapshot()
    assert snap["owners"]["tables"] == prof.tree_chip_bytes((t.X, t.W))
    assert snap["marks"][-1]["name"] == "table_put"
    fresh_ledger._take_census(fresh_ledger.mark("census"))
    assert (fresh_ledger.snapshot()["census"]["owners"]["tables"]
            == snap["owners"]["tables"])
    del t
    gc.collect()
    assert "tables" not in fresh_ledger.snapshot()["owners"]
    with prof.force_disabled():
        TpuTable.from_arrays(np.ones((8, 3), np.float32), session=session)
    assert "tables" not in fresh_ledger.snapshot()["owners"]


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
