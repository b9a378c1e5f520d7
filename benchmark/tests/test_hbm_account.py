"""The four readers of the program's HBM account (``hbm_live_peak_gb``,
``hbm_temp_peak_gb``, ``hbm_unnamed_gb``, ``hbm_transient_gb``): nothing
to read on a program whose ledger takes no marks, and on a rehearsal run
of a cell the identity they are built for — the live peak is what the
ledger names at the high-water mark, plus the unnamed, plus the transient.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib

import pytest

from benchmark import harness
from benchmark.tests.test_correct import run_harness

NAMES = ("hbm_live_peak_gb", "hbm_temp_peak_gb", "hbm_unnamed_gb",
         "hbm_transient_gb")
CELLS = ("criteo_svc_fit_replay8", "criteo_svc_fit_1pass",
         "criteo_svc_h30_fit_replay8_2x2", "taxi_canvas_refit_staged")


def read(name: str):
    return importlib.import_module(f"benchmark.metrics.{name}").read({})


@pytest.fixture
def ledger(monkeypatch):
    """A ledger of the test's own (the process's carries every run before
    it), with spans and the account switched on."""
    from orange3_spark_tpu.obs import prof, trace

    monkeypatch.setenv("OTPU_PROF", "1")
    led = prof.DeviceMemoryLedger()
    led.allocator = prof.LiveArraysAllocator()      # the CPU's tells nothing
    monkeypatch.setattr(prof, "LEDGER", led)
    with trace.force_enabled():
        yield led


def test_entries_list_every_cell():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        m = entries[name]
        assert m["workloads"] == list(CELLS)
        assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
            "device", "fit_rows_per_s", "GB", "program_counter")


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_without_marks(name, ledger, monkeypatch):
    from orange3_spark_tpu.obs import prof

    # no fit has run: the ledger has no marks
    assert ledger.snapshot()["marks"] == []
    assert read(name) is None
    # a program from before the account: a snapshot without its keys, or
    # no ledger at all
    monkeypatch.setattr(ledger, "snapshot", lambda: {"owners": {}})
    assert read(name) is None
    monkeypatch.delattr(prof, "LEDGER")
    assert read(name) is None


@pytest.mark.parametrize("workload", ["criteo_svc_fit_replay8",
                                      "taxi_canvas_refit_staged"])
def test_identity_on_a_rehearsal_run(workload, ledger, tmp_path,
                                     monkeypatch, capsys):
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path))
    line = run_harness(workload, capsys)
    assert line["correct"] is True, line["compared"]
    live, temp, unnamed, transient = (read(n) for n in NAMES)
    snap = ledger.snapshot()
    hw = snap["high_water"]
    assert live == snap["marks"][-1]["peak_bytes_in_use"] / 1e9 > 0
    assert hw["peak_bytes_in_use"] / 1e9 == live
    assert live == pytest.approx(hw["named_bytes"] / 1e9 + unnamed
                                 + transient, abs=1e-12)
    assert temp == 0.0          # the CPU has no reserved region to read
    # the readers take no mark of their own: the last one is a fit's
    assert snap["marks"][-1]["name"] in ("evaluate", "canvas_models")
    names = {m["name"] for m in snap["marks"]}
    if workload.startswith("taxi"):
        assert {"canvas_dispatch", "canvas_drain", "canvas_models"} <= names
        assert snap["owners"].keys() <= {"tables", "canvas_out"}
    else:
        assert {"chunk", "replay_stack", "replay", "replay_drain",
                "finalize", "model_handover", "evaluate"} <= names
