"""``BENCHMARK.json`` against the static rules of the benchmark's contract,
and against the files it names: every cell, configuration, traffic mix, job
kind, reference, work function and per-layer reader is found by name."""

import importlib
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["BENCHMARK.json", "with_put_off"])
def bench(request):
    """``BENCHMARK.json`` as committed, and with the entries of the cells
    kept out of it: those have to be ready to be pasted in."""
    if request.param == "BENCHMARK.json":
        return harness.read_json(harness.ROOT, "BENCHMARK.json")
    both = harness.load_bench()
    assert both.pop("put_off")
    return both


def line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 48 << 10
    assert 1 <= bench["run_seconds"] <= 51
    assert all(line(w) for w in bench["command"])
    cells = len(bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, cells // 4)


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        held = harness.read_json(harness.ROOT, c["file"])
        assert held["name"] == c["name"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        for kind in ("reference", "work"):
            importlib.import_module(f"benchmark.{kind}.{held[kind]}")


def test_workloads(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        traffic = harness.read_json(harness.HERE, "traffic",
                                    w["traffic"] + ".json")
        importlib.import_module(f"benchmark.jobs.{traffic['job']}")
        assert harness.read_json(harness.HERE, "cells",
                                 w["name"] + ".json")["limits"]


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in SOURCES
        importlib.import_module(f"benchmark.metrics.{m['name']}").read
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:      # setup_s, one more end-to-end, one per-layer
        assert sum(cell in m.get("workloads", cells)
                   for m in bench["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])
