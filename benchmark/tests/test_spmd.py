"""What the four-chip cell adds beside the one-chip cells' files: its three
per-layer readers against a hand-built trace of four device planes
(``xplane_fixture_4dev.textproto``), its work function, and its job kind's
refusal to run on fewer devices than its mesh has."""

import importlib
import os

import pytest

from benchmark import harness, xplane
from benchmark.work import hashed_linear, hashed_linear_spmd

CELL = "criteo_svc_h30_fit_replay8_2x2"
FIXTURE = os.path.join(harness.HERE, "xplane_fixture_4dev.textproto")
ONE_PLANE = os.path.join(harness.HERE, "xplane_fixture.textproto")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
SHAPES = dict(chunk_rows=2048, n_dense=13, n_cat=26, n_dims=1 << 16,
              distinct_rows=[100] * 8, epochs=8, holdout_chunks=2)


def reduced(path: str, window=None) -> dict:
    from jax.profiler import ProfileData

    with open(path) as f:
        return xplane.reduce(ProfileData.from_text_proto(f.read()),
                             window=window)


def reader(name: str):
    return importlib.import_module(f"benchmark.metrics.{name}").read


@pytest.fixture(scope="module")
def run():
    return {"trace": reduced(FIXTURE, "traced_window"), "traced_jobs": 1,
            "jobs": [{}]}


def test_four_planes_are_read(run):
    trace = run["trace"]
    assert trace["devices"] == 4 and trace["window_s"] == pytest.approx(1e-5)
    assert trace["busy_s_per_device"] == pytest.approx(
        [9e-6, 8e-6, 8e-6, 7e-6])


def test_collective_s_is_self_time_over_devices_and_jobs(run):
    # 4 all-reduces of 2 us, 2 + 2 halves of an all-gather of 0.5 us each;
    # the fusion that only NAMES %all-reduce.3 as an operand is not one
    assert reader("collective_s")(run) == pytest.approx(10e-6 / 4)
    assert reader("collective_s")({**run, "traced_jobs": 2}) == \
        pytest.approx(10e-6 / 4 / 2)


@pytest.mark.parametrize("name, collective", [
    ("%all-reduce.16 = s32[1048576]{0:T(1024)S(1)} all-reduce(%fusion.8), "
     "channel_id=4, replica_groups=[2,2]<=[4]", True),
    ("%all-reduce.17 = (f32[8,1]{0,1:T(1,128)S(1)}, f32[8,1]{0,1}) "
     "all-reduce(%bitcast.126, %bitcast.127), channel_id=2", True),
    ("%all-gather-start.5 = (s32[3407872]{0}, s32[6815744]{0}) "
     "all-gather-start(%custom-call.20), dimensions={0}", True),
    ("%all-gather-done.5 = s32[6815744]{0} all-gather-done("
     "%all-gather-start.5)", True),
    ("%reduce-scatter.1 = f32[4]{0} reduce-scatter(%p), dimensions={0}",
     True),
    ("%collective-permute-done.2 = f32[4]{0} collective-permute-done(%s)",
     True),
    ("%all-to-all.9 = f32[4]{0} all-to-all(%p), dimensions={0}", True),
    ("%fusion.23 = f32[131072]{0} fusion(%all-reduce.3, %custom-call.25), "
     "kind=kLoop, calls=%fused_computation.55", False),
    ("%get-tuple-element.314 = f32[8,1]{0,1} get-tuple-element("
     "%all-reduce.17), index=1", False),
    ("%while.1 = (f32[8]{0}) while(%tuple), body=%all-reduce-body", False),
], ids=lambda v: v.split(" ")[0] if isinstance(v, str) else str(v))
def test_collective_names(name, collective):
    from benchmark.metrics import collective_s

    assert collective_s.is_collective(name) is collective


def test_chip_busy_spread_is_busiest_less_idlest_over_the_window(run):
    assert reader("chip_busy_spread_pct")(run) == pytest.approx(
        100.0 * (9e-6 - 7e-6) / 1e-5)


def test_readers_read_nothing_off_one_plane():
    one = {"trace": reduced(ONE_PLANE), "traced_jobs": 1, "jobs": [{}]}
    assert reader("collective_s")(one) is None
    assert reader("chip_busy_spread_pct")(one) is None
    none = {"trace": None, "traced_jobs": 0, "jobs": []}
    assert reader("collective_s")(none) is None
    assert reader("chip_busy_spread_pct")(none) is None


def test_no_collective_in_four_planes_reads_nothing(run):
    trace = dict(run["trace"])
    trace["ops"] = {k: v for k, v in trace["ops"].items()
                    if "fusion" in k.split(" = ")[0]}
    assert reader("collective_s")({**run, "trace": trace}) is None


def test_state_gb_per_chip_reads_the_per_chip_ledger(monkeypatch):
    from orange3_spark_tpu.obs import prof

    read = reader("state_gb_per_chip")
    before = prof.LEDGER.peak()
    # an array of 8 GB sharded over two chips: 4 GB on each
    prof.ledger_set("model_state", "test-state-gb", before + 4_000_000_000,
                    before + 8_000_000_000)
    try:
        assert read({}) == pytest.approx(prof.LEDGER.peak() / 1e9)
        assert (before + 4e9) / 1e9 <= read({}) < (before + 8e9) / 1e9
        assert prof.LEDGER.peak_global() >= before + 8_000_000_000
    finally:
        prof.ledger_release("model_state", "test-state-gb")
    # a program whose ledger counts global sizes has nothing to read
    monkeypatch.delattr(prof.DeviceMemoryLedger, "peak_global")
    assert read({}) is None


def test_work_scales_with_the_chip_count_only():
    one = hashed_linear.job_work(peaks=PEAKS, **SHAPES)
    four = hashed_linear_spmd.job_work(
        peaks=hashed_linear_spmd.aggregate(PEAKS, 4), **SHAPES)
    same = hashed_linear_spmd.job_work(
        peaks=hashed_linear_spmd.aggregate(PEAKS, 1), **SHAPES)
    for key in ("step_bytes", "step_ops", "steps", "bound"):
        assert four[key] == one[key] == same[key]
    for key in ("steps_least_s", "job_least_s"):
        assert four[key] == pytest.approx(one[key] / 4)
        assert same[key] == pytest.approx(one[key])
    assert (four["chips"], same["chips"]) == (4, 1)
    assert hashed_linear_spmd.job_work(peaks=PEAKS, **SHAPES)["chips"] == 1


def test_job_refuses_fewer_devices_than_its_mesh(tmp_path, monkeypatch):
    import jax

    from benchmark.jobs import fit_stream_spmd

    spec = harness.load_cell(CELL, rehearse=True)
    job = fit_stream_spmd.Job(spec["config"], spec["traffic"], 1,
                              str(tmp_path))
    first = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: first)
    with pytest.raises(SystemExit) as err:
        job.prepare()
    assert "xla_force_host_platform_device_count=4" in str(err.value)
    assert "--rehearse" in str(err.value)


def test_cell_is_the_benchmarks_one_four_chip_cell():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    config = harness.load_cell(CELL, rehearse=False)["config"]
    assert config["estimator"]["n_dims"] == 1 << 30
    assert config["layout"]["mesh"] == {"data": 2, "model": 2}
    assert config["layout"]["chips"] == four[0]["chips"]
