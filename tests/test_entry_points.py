"""The entry points a user (or the driver) starts from, each in a fresh
interpreter: every public module imports FIRST, the compile cache is placed
from outside, and chip_smoke.py rehearses green on the CPU while its real
mode refuses anything but a TPU."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(args, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # tests are CPU runs, said from outside
    env.pop("XLA_FLAGS", None)        # a user's process: one device
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


@pytest.mark.parametrize("module", [
    "orange3_spark_tpu",
    "orange3_spark_tpu.models.pca",
    "orange3_spark_tpu.models.kmeans",
    "orange3_spark_tpu.models.gbt",
    "orange3_spark_tpu.models.als",
    "orange3_spark_tpu.models.hashed_linear",
    "orange3_spark_tpu.workflow.graph",
    "orange3_spark_tpu.workflow.staging",
    "orange3_spark_tpu.serve",
    "orange3_spark_tpu.fleet",
    "orange3_spark_tpu.parallel",
    "orange3_spark_tpu.io.streaming",
])
def test_public_module_imports_first(module):
    """No import-order luck: the suite imports in an order that happened to
    work; a user's first import is any of these (the README quick start
    begins with workflow.graph)."""
    r = _python(["-c", f"import {module}"])
    assert r.returncode == 0, r.stderr[-2000:]


_CACHE_PROBE = """
import jax, json
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]
from orange3_spark_tpu.exec.compile_cache import enable_compilation_cache
a = enable_compilation_cache()
b = enable_compilation_cache()
jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
print(json.dumps({"dir": a["dir"], "again": b["dir"], "enabled": a["enabled"],
                  "set_dir_in_code": "jax_compilation_cache_dir" in calls,
                  "jax_dir": jax.config.jax_compilation_cache_dir}))
"""


def _cache_probe(env_extra):
    import json

    r = _python(["-c", _CACHE_PROBE], env_extra)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_env_places_it_and_code_sets_no_dir(tmp_path):
    d = str(tmp_path / "placed")
    out = _cache_probe({"JAX_COMPILATION_CACHE_DIR": d})
    assert out["enabled"] and out["dir"] == d and out["jax_dir"] == d
    assert out["set_dir_in_code"] is False
    assert os.listdir(d), "the program wrote no cache entry there"


def test_compile_cache_unset_is_fixed_dir_in_checkout():
    out = _cache_probe({"JAX_COMPILATION_CACHE_DIR": ""})
    want = os.path.join(REPO, ".jax_cache")
    assert out["enabled"] and out["dir"] == want and out["jax_dir"] == want


def test_compile_cache_path_is_stable_across_calls_and_processes():
    a = _cache_probe({"JAX_COMPILATION_CACHE_DIR": ""})
    b = _cache_probe({"JAX_COMPILATION_CACHE_DIR": "", "TMPDIR": "/var/tmp"})
    assert a["dir"] == a["again"] == b["dir"] == b["again"]


def _last_line(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return lines[-1] if lines else ""


def test_chip_smoke_rehearses_green_on_cpu(tmp_path):
    import json

    r = _python(["chip_smoke.py", "--rehearse"],
                {"OTPU_BENCH_DIR": str(tmp_path)}, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    phases = [json.loads(ln)["phase"] for ln in r.stdout.splitlines()
              if ln.startswith('{"phase"')]
    assert phases == ["setup", "fit", "serve", "trees", "canvas"]
    assert json.loads(_last_line(r.stdout)) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_real_mode_refuses_the_cpu():
    r = _python(["chip_smoke.py"], timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout and '"phase"' not in r.stdout


@pytest.mark.parametrize("child_env,ok", [
    ({}, False),                          # children would need the chip
    ({"JAX_PLATFORMS": "cpu"}, True),     # the CPU drills' arrangement
])
def test_one_process_per_chip_is_checked_before_spawning(monkeypatch,
                                                         child_env, ok):
    """A process whose jax already holds an accelerator may not spawn
    children that need it (fleet replicas, gang ranks): loud, not a hang."""
    import jax

    from orange3_spark_tpu.utils.procs import require_free_accelerator

    jax.devices()                         # backends initialised, on the CPU
    require_free_accelerator({}, "on the CPU anything goes")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if ok:
        require_free_accelerator(child_env, "drill")
    else:
        with pytest.raises(RuntimeError, match="One process per chip"):
            require_free_accelerator(child_env, "fleet replica spawn")


@pytest.mark.parametrize("name", ["higgs", "taxi", "criteo"])
def test_smoke_data_is_made_from_the_seed(name, tmp_path):
    """chip_smoke.py's data comes from --seed through these generators:
    the same seed gives the same bytes, another seed other ones."""
    import numpy as np

    import bench
    import bench_suite

    def make(seed):
        if name == "higgs":
            return np.concatenate(
                [a.reshape(len(a), -1) for a in bench_suite.gen_higgs(
                    256, 28, seed)], axis=1)
        if name == "taxi":
            return bench.gen_taxi(256, seed)
        path = str(tmp_path / f"criteo_{seed}_{os.urandom(4).hex()}.csv")
        bench.gen_criteo_csv(path, 256, seed)
        return np.loadtxt(path, delimiter=",", skiprows=1)

    a, again, other = make(3), make(3), make(4)
    np.testing.assert_array_equal(a, again)
    assert a.shape == other.shape and not np.array_equal(a, other)
