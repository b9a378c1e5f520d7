"""Child-process helpers: the one-process-per-chip check and the
process-group kill with a bounded pipe drain — ONE copy of each.

A killed child's descendants can inherit its stdout pipe and outlive it; a
plain ``subprocess.run`` timeout then blocks forever in its post-kill
``communicate()`` — inside the exact code that exists to bound the wait.
Every caller that launches a killable child in its own process group (the
fleet supervisor's replicas, the multihost launcher's ranks,
tools/replay_hlo.py's cells) goes through ``kill_process_group`` so the
subtle parts — group kill, bounded second wait, salvaging output already
flushed before the kill — cannot drift apart across copies."""

from __future__ import annotations

import os
import signal
import subprocess
import sys


def require_free_accelerator(child_env: dict, what: str) -> None:
    """Refuse to spawn device-using children from a process that already
    holds an accelerator.

    A chip belongs to ONE process at a time: a parent whose jax backend is
    initialised on an accelerator holds it, and a child that needs it then
    fails or hangs at start-up. The arrangements that work are (a) the
    parent stays off jax, one child per chip, or (b) the children are pinned
    to the CPU from outside (``JAX_PLATFORMS=cpu`` in their environment, as
    the CPU drills do), or (c) everything runs in this process
    (``fleet/inproc.py``). Anything else raises here, loudly, instead of
    hanging there."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    platform = jax.default_backend()
    if platform == "cpu" or child_env.get("JAX_PLATFORMS") == "cpu":
        return
    raise RuntimeError(
        f"{what}: this process has initialised jax on {platform!r} and holds "
        "the device, so a child process that needs it would fail or hang. "
        "One process per chip: keep the parent off jax (one child per chip), "
        "pin the children to the CPU with JAX_PLATFORMS=cpu, or serve "
        "in-process (FleetFrontend's in-process lanes).")


def kill_process_group(proc: subprocess.Popen, *, grace_s: float = 0.0,
                       drain_s: float = 30.0) -> str:
    """Kill ``proc``'s process group and return whatever stdout text can
    still be drained. ``grace_s`` > 0 sends SIGTERM first and gives the
    child that long to clean up its OWN subtree (e.g. replay_hlo killing
    its detached TPU cells) before the SIGKILL; ``drain_s`` bounds the
    post-kill pipe read — an escaped descendant can hold the pipe open
    forever, and lines already flushed must never be discarded."""
    def _sig(s) -> None:
        try:
            os.killpg(proc.pid, s)
        except ProcessLookupError:
            pass

    out = ""
    if grace_s > 0:
        _sig(signal.SIGTERM)
        try:
            out, _ = proc.communicate(timeout=grace_s)
            return out or ""
        except subprocess.TimeoutExpired:
            pass
    _sig(signal.SIGKILL)
    try:
        out, _ = proc.communicate(timeout=drain_s)
    except subprocess.TimeoutExpired as e:
        ob = e.stdout or ""
        out = ob.decode("utf-8", "replace") if isinstance(ob, bytes) else ob
    return out or ""
