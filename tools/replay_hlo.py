"""Fused-replay fault mechanism experiment: HLO-dump comparison.

The fault diagnosis tool's one recorded run (BENCH_HW_r4.jsonl,
2026-07-31, before PR 1; the tool went in PR 30): the giant
fused-replay scan died UNAVAILABLE whenever ANY program executed before it in the same process, while the
identical Python call ran clean standalone — and n_epochs=1 scans were
immune in every order. That run could NOT say *why*: does the poisoned
process compile a *different* XLA program (program-content hypothesis:
e.g. donation/aliasing or layout decisions change once other buffers are
live), or the *same* program that only the runtime then fails to run
(runtime-state hypothesis: allocator fragmentation)? PR 22's chip_smoke.py
did not reproduce the fault on a directly attached v5e; this tool stays
for the day it returns.

This tool answers with XLA's own dump: two fresh subprocess cells run the
replay scan with ``--xla_dump_to`` — one standalone (clean), one after a
one-chunk ``fit_stream`` (poisoned, expected to fault AFTER compile; the
dump is written at compile time so the fault does not cost the evidence).
The dumped ``after_optimizations`` HLO of the replay modules is compared
modulo volatile ids:

* identical HLO + fault reproduced  => RUNTIME-STATE: the same compiled
  program faults only when executions preceded it — fence it (per-epoch
  granularity), nothing to fix in our lowering.
* different HLO                     => PROGRAM-CONTENT: diff the dumps,
  the divergence names the mechanism.

Prints one ``{"metric": "replay_fault_hlo", ...}`` JSON line. This parent
never imports jax: one cell at a time holds the chip.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: pgids of in-flight cell subprocesses — killed by the SIGTERM handler so
#: a terminated parent cannot orphan a live cell that still holds the chip
_LIVE_CELLS: set[int] = set()


def _sigterm_handler(signum, frame):  # noqa: ARG001
    import signal as _signal

    for pid in list(_LIVE_CELLS):
        try:
            os.killpg(pid, _signal.SIGKILL)
        except ProcessLookupError:
            pass
    os._exit(143)


_CELL_SRC = r"""
import sys, time
sys.path.insert(0, __REPO__)
import jax
import numpy as np

chunk_rows = __CHUNK_ROWS__
stages = __STAGES__

from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator,
)

sess = TpuSession.builder_get_or_create()
assert jax.default_backend() == "tpu", jax.default_backend()

def make_est(e):
    return StreamingHashedLinearEstimator(
        n_dims=1 << 22, n_dense=13, n_cat=26, epochs=e,
        chunk_rows=chunk_rows, label_in_chunk=True, prefetch_depth=2,
    )

for stage in stages:
    t0 = time.perf_counter()
    if stage == "fitnp":
        Xnp = np.zeros((chunk_rows, 40), np.float32)
        def np_source():
            yield Xnp
        make_est(1).fit_stream(
            np_source, session=sess, cache_device=True, holdout_chunks=0)
    elif stage == "replay":
        make_est(100).warm_replay(6, session=sess)
    else:
        raise ValueError(stage)
    print(f"STAGE_OK {stage} {time.perf_counter()-t0:.1f}s", flush=True)
print("CELL_OK", flush=True)
"""


def run_cell(name: str, stages: list, dump_dir: str, chunk_rows: int,
             wall_s: float) -> dict:
    shutil.rmtree(dump_dir, ignore_errors=True)
    os.makedirs(dump_dir, exist_ok=True)
    src = (_CELL_SRC
           .replace("__REPO__", repr(REPO))
           .replace("__CHUNK_ROWS__", str(chunk_rows))
           .replace("__STAGES__", repr(list(stages))))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_dump_to={dump_dir}"
                        + " --xla_dump_hlo_as_text").strip()
    t0 = time.time()
    # own process group + group kill + bounded second wait: a wedged cell's
    # descendants can inherit the pipes, and a plain subprocess.run would
    # block forever in its post-kill communicate(). The cell's pgid is
    # tracked in _LIVE_CELLS so OUR OWN SIGTERM can take the cell down with
    # us — otherwise a killed replay_hlo would orphan a live cell that
    # still holds the chip.
    proc = subprocess.Popen([sys.executable, "-c", src],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=REPO, env=env,
                            start_new_session=True)
    _LIVE_CELLS.add(proc.pid)
    try:
        try:
            out, err = proc.communicate(timeout=wall_s)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            import signal as _signal

            rc = "wall-timeout"
            try:
                os.killpg(proc.pid, _signal.SIGKILL)
            except ProcessLookupError:
                pass
            try:
                out, err = proc.communicate(timeout=30)
            except subprocess.TimeoutExpired as e2:
                def _dec(b):
                    return (b or b"").decode("utf-8", "replace") \
                        if isinstance(b, bytes) else (b or "")
                out, err = _dec(e2.stdout), _dec(e2.stderr)
    finally:
        _LIVE_CELLS.discard(proc.pid)
    out, err = out or "", err or ""
    res = {
        "cell": name, "stages": stages,
        "ok": rc == 0 and "CELL_OK" in out,
        "stages_completed": [ln.split()[1] for ln in out.splitlines()
                             if ln.startswith("STAGE_OK ")],
        "rc": rc,
        "device_fault": "UNAVAILABLE" in err or "UNAVAILABLE" in out,
        "wall_s": round(time.time() - t0, 1),
    }
    if not res["ok"]:
        tail = err.strip().splitlines()[-1:] if err.strip() else []
        res["error_tail"] = tail[0][-200:] if tail else ""
    return res


#: volatile tokens in dumped HLO text: module/computation/op unique ids
#: (``jit_foo.123``, ``%fusion.4``) — anchored to an identifier character
#: before the dot so FLOAT LITERALS (``1.25``, digit before the dot)
#: survive canonicalization: a constant that differs between the clean and
#: poisoned programs is exactly the evidence this tool must not erase
_ID_RE = re.compile(r"(?<=[A-Za-z_])\.\d+")
_META_RE = re.compile(r"metadata=\{[^}]*\}")
#: dump FILENAMES additionally carry a per-process module counter prefix
_MODNUM_RE = re.compile(r"^module_\d+\.")


def _canon_hlo(text: str) -> str:
    return _META_RE.sub("", _ID_RE.sub("", text))


def replay_dumps(dump_dir: str) -> dict[str, str]:
    """{canonical module key -> sha256 of canonicalized after-optimizations
    HLO} for every dumped module belonging to the replay scan program."""
    out = {}
    for p in sorted(glob.glob(os.path.join(
            dump_dir, "*replay*after_optimizations*.txt"))):
        base = _ID_RE.sub("", _MODNUM_RE.sub("", os.path.basename(p)))
        with open(p) as f:
            out[base] = hashlib.sha256(
                _canon_hlo(f.read()).encode()).hexdigest()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk-rows", type=int, default=1 << 18)
    ap.add_argument("--wall-s", type=float, default=600.0)
    ap.add_argument("--dump-root", default="/tmp/otpu_hlo")
    args = ap.parse_args()

    import signal

    signal.signal(signal.SIGTERM, _sigterm_handler)
    _compare(args)


def _compare(args) -> None:
    clean_dir = f"{args.dump_root}_clean"
    poison_dir = f"{args.dump_root}_poisoned"
    cells = [
        ("clean", ["replay"], clean_dir),
        ("poisoned", ["fitnp", "replay"], poison_dir),
    ]
    results = []
    for name, stages, dump_dir in cells:
        res = run_cell(name, stages, dump_dir, args.chunk_rows, args.wall_s)
        print(json.dumps(res), flush=True)
        results.append(res)
    by = {r["cell"]: r for r in results}

    clean = replay_dumps(clean_dir)
    poison = replay_dumps(poison_dir)
    shared = sorted(set(clean) & set(poison))
    differing = [k for k in shared if clean[k] != poison[k]]
    only_clean = sorted(set(clean) - set(poison))
    only_poison = sorted(set(poison) - set(clean))
    identical = bool(shared) and not differing \
        and not only_clean and not only_poison
    reproduced = by["poisoned"]["device_fault"]
    if not shared:
        verdict = "inconclusive: no replay modules dumped in both cells"
    elif identical and reproduced:
        verdict = ("runtime-state: identical optimized HLO faults only "
                   "when executions preceded it")
    elif identical:
        verdict = ("fault not reproduced this window; HLO identical "
                   "(consistent with runtime-state)")
    elif differing:
        verdict = (f"program-content: {len(differing)} replay module(s) "
                   f"differ — diff the dumps")
    else:
        # all shared modules hash equal but one cell dumped extra replay
        # modules — a lowering-set difference, not a same-module rewrite
        verdict = (f"module-set-mismatch: only-clean={only_clean[:4]} "
                   f"only-poisoned={only_poison[:4]} (shared modules "
                   f"identical)")
    print(json.dumps({
        "metric": "replay_fault_hlo",
        "value": len(shared) or 1,
        "unit": "modules_compared",
        "vs_baseline": None,
        "backend": "tpu",
        "clean_ok": by["clean"]["ok"],
        "poisoned_fault": reproduced,
        "hlo_identical": identical,
        "modules_clean": len(clean),
        "modules_poisoned": len(poison),
        "differing_modules": differing[:8],
        "modules_only_clean": only_clean[:8],
        "modules_only_poisoned": only_poison[:8],
        "verdict": verdict,
        "dump_dirs": [clean_dir, poison_dir],
        "cells": results,
    }), flush=True)


if __name__ == "__main__":
    main()
