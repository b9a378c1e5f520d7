"""What the cells on a mesh need of a CPU run: four virtual devices (the
harness initialises jax before it makes a job, so they come from here),
the process-wide default session pinned to ONE of them — the one-chip
cells rehearse on one device, as they did before there were four — and
the job kind ``fit_stream_spmd`` under ``test_correct.py``'s three broken
timed paths (the subclass runs ``fit_stream``'s code, so the same three
breaks reach it).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os

import pytest

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")


@pytest.fixture(autouse=True, scope="session")
def one_device_by_default():
    import jax

    from orange3_spark_tpu.core.session import TpuSession

    devices = jax.devices()
    assert len(devices) >= 4, devices
    TpuSession.stop()
    TpuSession.builder_get_or_create(TpuSession.default_mesh(devices[:1]))
    yield
    TpuSession.stop()


@pytest.fixture(autouse=True, scope="session")
def spmd_job_kind_has_the_faults_of_fit_stream():
    from benchmark.tests import test_correct

    test_correct.FAULTS.setdefault("fit_stream_spmd",
                                   test_correct.FAULTS["fit_stream"])
