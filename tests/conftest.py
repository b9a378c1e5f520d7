"""Test harness: 8 fake CPU devices so every mesh/psum/shard_map path runs in
plain pytest without a TPU — the analogue of PySpark's local[N] test master
(SURVEY.md §4)."""

import os
import sys

# Round-2 "pytest -q SIGABRT at dot 243", root-caused in round 3: XLA:CPU's
# in-process collective runtime can wedge a multi-device rendezvous when an
# unthrottled dispatch loop piles dozens of 8-participant programs onto an
# oversubscribed 1-core host (reproduced at test_gbt_regressor's 40-round
# loop; abort arrives from a non-Python worker thread and the C++ message
# dies in pytest's fd-level capture). Two-part fix: the dispatch loops bound
# their in-flight depth (models/gbt.py _boost), and the stuck/terminate
# timeouts here give slow-but-progressing collectives minutes instead of the
# default seconds — while still ABORTING (visibly) on a genuine deadlock
# rather than hanging CI forever. (XLA aborts the PROCESS on a flag it does
# not know; these three exist in the pinned jaxlib 0.9.0.)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=900"
    + " --xla_cpu_collective_timeout_seconds=900"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests are CPU-mesh by design: the harness, not the program, pins the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import pytest  # noqa: E402

from orange3_spark_tpu.core.session import TpuSession  # noqa: E402


@pytest.fixture(scope="session")
def session() -> TpuSession:
    assert len(jax.devices()) == 8, "expected 8 fake CPU devices"
    return TpuSession.builder_get_or_create()


@pytest.fixture(scope="session")
def iris(session):
    from orange3_spark_tpu.datasets import load_iris

    return load_iris(session)


@pytest.fixture()
def make_killing_checkpointer():
    """Factory fixture for kill-and-resume drills: builds a fault-injecting
    StreamCheckpointer that dies right AFTER the ``die_after``-th snapshot
    lands — the nastiest resume point (state on disk, process gone).
    Raising after ``super().save`` is load-bearing: the resume test must
    find that snapshot on disk. A fixture (not an importable helper) so
    tests need no `import tests.conftest`, which only resolves when the
    repo root happens to be on sys.path."""
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    def _make(path: str, every_steps: int, die_after: int):
        class Killer(StreamCheckpointer):
            saves = 0

            def save(self, step, state, meta=None):
                super().save(step, state, meta)
                Killer.saves += 1
                if Killer.saves >= die_after:
                    raise RuntimeError("injected fault")

        return Killer(path, every_steps=every_steps)

    return _make


@pytest.fixture()
def xla_compiles():
    """Recompile-regression guard: counts XLA backend compilations via the
    process-wide ``jax.monitoring`` listener (utils/profiling.py). Yields
    a zero-arg callable returning the number of backend compiles since the
    fixture was set up — the serving tests assert the bucketed predict
    path compiles AT MOST ONCE PER BUCKET, so a silent per-request or
    per-size recompile regression fails here instead of surfacing as a
    mystery latency cliff in the bench."""
    from orange3_spark_tpu.utils.profiling import (
        install_compile_counter, xla_compile_count,
    )

    install_compile_counter()
    base = xla_compile_count()
    yield lambda: xla_compile_count() - base
