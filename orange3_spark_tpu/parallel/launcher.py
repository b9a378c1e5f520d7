"""Fleet-style multi-process training coordinator (docs/multihost.md).

``MultihostLauncher`` is to TRAINING what ``fleet/supervisor.py`` is to
serving: it spawns N training processes as one GANG rendezvousing through
``jax.distributed.initialize``, watches them, and owns the cross-host
resilience ladder —

  * a process that dies (crash, SIGKILL, OOM) is detected TYPED within the
    poll interval: the collective the survivors are blocked in can never
    complete, so the launcher kills the remainder of the gang instead of
    letting it hang (the PR-6 watchdog pattern, applied across processes);
  * the whole gang restarts after a seeded exponential backoff
    (``resilience/retry.py RetryPolicy`` — the supervisor's schedule), up
    to ``OTPU_MULTIHOST_RESTARTS`` times;
  * before each restart the per-rank epoch-boundary checkpoints are
    ALIGNED to the newest step every rank holds (a kill can land between
    two ranks' saves) so the resumed gang re-enters lockstep at one common
    step — each worker's shard source then fast-forwards through the
    replayed prefix exactly like ``resilient_source`` replays a lost
    chunk;
  * a gang still running past ``OTPU_MULTIHOST_WALL_S`` is a WEDGE, not
    a slow fit: it is killed and counted as a lost host.

Budget exhausted -> :class:`HostLostError` (typed, carrying the rank, exit
code and log tail) — never a hang.

Devices: one OS process per rank, and an accelerator belongs to ONE process
at a time. Ranks inherit the launcher's environment; on a chip the process
that calls ``run()`` must stay off jax (a parent that has touched jax holds
the chip, and a rank that needs it then fails or hangs) with one rank per
chip. The CPU drills (``tools/multihost_drill.py``, ``bench.py --config
multihost``) pin ``JAX_PLATFORMS=cpu`` explicitly.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
import shutil
import socket
import subprocess
import tempfile
import time

from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.resilience.retry import RetryPolicy
from orange3_spark_tpu.utils import knobs
from orange3_spark_tpu.utils.procs import (
    kill_process_group, require_free_accelerator,
)

__all__ = ["HostLostError", "GangResult", "MultihostLauncher"]

_M_GANGS = REGISTRY.counter(
    "otpu_multihost_gang_starts_total",
    "Training-gang launches (initial attempts plus restarts).")
_M_LOST = REGISTRY.counter(
    "otpu_multihost_hosts_lost_total",
    "Training processes lost mid-gang (crash/SIGKILL/wall-budget wedge).")
_M_RESTARTS = REGISTRY.counter(
    "otpu_multihost_gang_restarts_total",
    "Gang restarts taken after a lost host (resume from aligned "
    "epoch-boundary checkpoints).")


class HostLostError(RuntimeError):
    """A training host died (or wedged) and the restart budget is spent.

    Typed — the launcher never lets a dead rank surface as a hang: the
    surviving ranks' collectives are killed with it. Carries the first
    failed ``rank`` (-1 for a wall-budget wedge with no dead process),
    its exit code, the restarts already taken, and the rank's log tail."""

    def __init__(self, rank: int, returncode, restarts: int, tail: str = ""):
        self.rank, self.returncode, self.restarts = rank, returncode, restarts
        self.tail = tail
        what = (f"wedged past the OTPU_MULTIHOST_WALL_S budget"
                if rank < 0 else
                f"rank {rank} exited {returncode}")
        super().__init__(
            f"multihost gang lost: {what} after {restarts} gang "
            f"restart(s); log tail:\n{tail}")


@dataclasses.dataclass
class GangResult:
    """One successful gang run (possibly after restarts)."""
    n_processes: int
    gang_starts: int
    gang_restarts: int
    hosts_lost: int
    wall_s: float
    coord_addr: str
    log_paths: list


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tail(path: str, n_bytes: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n_bytes))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


class MultihostLauncher:
    """Spawn and supervise one N-process training gang.

    ``argv_for_rank(rank, n_processes, coord_addr) -> list[str]`` builds
    each rank's command line (usually ``python -m
    orange3_spark_tpu.parallel.mh_worker ...``). Ranks log to per-rank
    files under ``log_dir`` (pipes would deadlock a chatty gang)."""

    def __init__(self, argv_for_rank, n_processes: int | None = None, *,
                 env: dict | None = None, log_dir: str | None = None,
                 max_gang_restarts: int | None = None,
                 wall_s: float | None = None,
                 coord_port: int | None = None,
                 align_ckpt_dir: str | None = None,
                 poll_s: float = 0.05, seed: int = 0):
        self.argv_for_rank = argv_for_rank
        self.n = int(n_processes
                     or (knobs.get_int("OTPU_MULTIHOST_PROCS") or 2))
        self.env = dict(env) if env is not None else dict(os.environ)
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="otpu-mh-")
        os.makedirs(self.log_dir, exist_ok=True)
        self.max_gang_restarts = (knobs.get_int("OTPU_MULTIHOST_RESTARTS")
                                  if max_gang_restarts is None
                                  else int(max_gang_restarts))
        self.wall_s = (knobs.get_float("OTPU_MULTIHOST_WALL_S")
                       if wall_s is None else float(wall_s))
        self.coord_port = (knobs.get_int("OTPU_MULTIHOST_COORD_PORT")
                           if coord_port is None else int(coord_port))
        self.align_ckpt_dir = align_ckpt_dir
        self.poll_s = poll_s
        # the supervisor's seeded backoff schedule, one ladder per gang
        self._policy = RetryPolicy.from_env(seed=seed)

    # ------------------------------------------------------------ restarts
    @staticmethod
    def align_checkpoints(ckpt_dir: str, n_processes: int) -> int:
        """Coordinated-resume rule: every rank must re-enter the gang at
        ONE common step (a kill can land after rank 0's epoch save but
        before rank 1's — mismatched resume points diverge the lockstep
        collectives). The common step is the newest one ALL ranks can
        reach: the minimum saved step. A rank holding a different step
        gets a COPY of a common-step donor snapshot — legal because the
        data-parallel optimizer state is replicated, so any rank's
        snapshot at step S is every rank's state at step S. If no rank
        holds a usable snapshot (common == 0) all checkpoints are
        dropped and the gang restarts from scratch. Returns the common
        step."""
        steps = {}
        for rank in range(n_processes):
            path = os.path.join(ckpt_dir, f"rank{rank}.ckpt")
            try:
                with open(path, "rb") as f:
                    steps[path] = int(pickle.load(f)["step"])
            except (OSError, KeyError, ValueError, EOFError,
                    pickle.UnpicklingError):
                steps[path] = 0
        common = min(steps.values()) if steps else 0
        if common == 0:
            for path in steps:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            return 0
        donor = next(p for p, s in steps.items() if s == common)
        for path, step in steps.items():
            if step != common:
                shutil.copyfile(donor, path)
        return common

    # ----------------------------------------------------------------- run
    def run(self) -> GangResult:
        require_free_accelerator(self.env, "multihost gang launch")
        t0 = time.perf_counter()
        restarts = lost = 0
        log_paths = [os.path.join(self.log_dir, f"rank{r}.log")
                     for r in range(self.n)]
        while True:
            _M_GANGS.inc()
            port = self.coord_port or _free_port()
            coord = f"127.0.0.1:{port}"
            procs, logs = [], []
            try:
                for r in range(self.n):
                    f = open(log_paths[r], "ab")
                    logs.append(f)
                    procs.append(subprocess.Popen(
                        self.argv_for_rank(r, self.n, coord),
                        stdout=f, stderr=subprocess.STDOUT,
                        env=self.env, start_new_session=True))
                failed_rank, failed_rc = self._watch(procs)
            finally:
                for p in procs:
                    if p.poll() is None:
                        kill_process_group(p, grace_s=0.0, drain_s=2.0)
                for f in logs:
                    f.close()
            if failed_rank is None:
                return GangResult(
                    n_processes=self.n,
                    gang_starts=restarts + 1,
                    gang_restarts=restarts,
                    hosts_lost=lost,
                    wall_s=round(time.perf_counter() - t0, 3),
                    coord_addr=coord,
                    log_paths=log_paths)
            lost += 1
            _M_LOST.inc()
            tail = _tail(log_paths[max(failed_rank, 0)])
            if restarts >= self.max_gang_restarts:
                raise HostLostError(failed_rank, failed_rc, restarts, tail)
            _M_RESTARTS.inc()
            if self.align_ckpt_dir:
                self.align_checkpoints(self.align_ckpt_dir, self.n)
            time.sleep(self._policy.delay(restarts))
            restarts += 1

    def _watch(self, procs) -> tuple:
        """Poll the gang. Returns ``(None, None)`` when every rank exited
        0; otherwise the first failed rank and its exit code (``(-1,
        None)`` for a wall-budget wedge)."""
        deadline = time.monotonic() + self.wall_s
        while True:
            codes = [p.poll() for p in procs]
            for r, rc in enumerate(codes):
                if rc is not None and rc != 0:
                    return r, rc
            if all(rc == 0 for rc in codes):
                return None, None
            if time.monotonic() >= deadline:
                return -1, None
            time.sleep(self.poll_s)
