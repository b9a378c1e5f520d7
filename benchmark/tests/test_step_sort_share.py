"""``step_sort_share`` against the program's own counter (CPU, the files'
own rehearsal sizes): nothing to read on a program without the counter or
before a fit has finished, and after two jobs of the cell the share
the cell's schedule gives — a replay cell streams its first epoch (a sort
a step) and replays the rest in one dispatch that builds each cached
chunk's keys once: 2 / epochs; the one-pass cell sorts in every step.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib

import pytest

from benchmark import harness
from benchmark.metrics import step_sort_share

SEED = 2_900_000_011
COUNTER = "otpu_sparse_sorts_total"


@pytest.fixture
def registry():
    from orange3_spark_tpu.obs.registry import REGISTRY

    REGISTRY.reset([COUNTER])
    return REGISTRY


def test_nothing_to_read(registry, monkeypatch):
    assert step_sort_share.read({}) is None        # no fit has finished
    monkeypatch.setattr(registry, "get", lambda name: None)
    assert step_sort_share.read({}) is None        # a program without it


@pytest.mark.parametrize("workload", [
    w["name"] for w in harness.load_bench()["workloads"]
    if w["name"].startswith("criteo_svc")])
def test_share_is_what_the_schedule_gives(workload, registry, tmp_path):
    """Two jobs of the cell's own kind with the lowering a TPU resolves
    'auto' to ('sort'; the CPU's 'plan' sorts on the host and counts
    nothing)."""
    spec = harness.load_cell(workload, rehearse=True)
    config, traffic = spec["config"], spec["traffic"]
    config["estimator"]["sparse_lowering"] = "sort"
    kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    job = kind.Job(config, traffic, SEED, str(tmp_path))
    job.prepare()
    for _ in range(2):
        record = job.run()
        assert record["resolved"]["sparse_lowering"] == "sort"
    share = step_sort_share.read({})
    steps = registry.get(COUNTER).value(which="steps")
    if job.epochs == 1:
        assert share == 1.0 and steps == 2 * 8
    else:
        assert job.cache_device and job.epochs == 8 and steps == 2 * 48
        assert record["resolved"]["replay_source"] == "fused"
        assert share == 2 / job.epochs == 0.25
    assert workload in next(m["workloads"]
                            for m in harness.load_bench()["per_layer"]
                            if m["name"] == "step_sort_share")
