"""Diagnose a fused-replay device fault on the attached TPU.

Observed once (2026-07-31, BENCH_HW_r4.jsonl, 1x v5e, before PR 1):
executing ANY `fit_stream` (even a single zero chunk from numpy,
prefetch on or off) followed by the big `_hashed_replay_epochs` scan
program in the SAME process killed the device program with
`jax.errors.JaxRuntimeError: UNAVAILABLE: TPU device error` — while the
identical replay program ran clean standalone, and per-chunk replay of
the same cached epochs was unaffected. PR 22's chip_smoke.py ran the
default one-dispatch replay on a directly attached v5e without a fault
(CHANGES.md); this matrix stays as the tool to run if it ever returns.

It runs a small experiment matrix, each cell in a fresh subprocess (a
faulted cell must not poison the next; this parent never imports jax, so
one cell at a time holds the chip), and prints one JSON line per cell
plus a summary:

  base       fitnp -> replay with emb_update='sorted' (the faulting
             2026-07-31 config; expect FAULT — reproduces the signature)
  embfused   fitnp -> replay with emb_update='fused' (the new 'auto'
             winner): does the sorted custom-vjp inside the scan carry
             the fault?
  cached     replay -> fitnp -> replay2: does a replay EXECUTABLE
             compiled before any step survive re-execution after steps?
             (If yes, bench.py can hoist warm_replay first and keep
             fused replay on hardware.)
  delwarm    fitnp -> free the warm model -> replay: is it live-buffer /
             memory-pressure related?

Usage:
    python tools/replay_fault_diag.py [--chunk-rows 262144]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CELL_SRC = r"""
import sys, time
sys.path.insert(0, __REPO__)
import jax
import numpy as np

chunk_rows = __CHUNK_ROWS__
emb = __EMB__
stages = __STAGES__

from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator,
)

sess = TpuSession.builder_get_or_create()
assert jax.default_backend() == "tpu", jax.default_backend()

def make_est(e, gran="all"):
    return StreamingHashedLinearEstimator(
        n_dims=1 << 22, n_dense=13, n_cat=26, epochs=e,
        chunk_rows=chunk_rows, label_in_chunk=True, prefetch_depth=2,
        emb_update=emb, replay_granularity=gran,
    )

warm = None
for stage in stages:
    t0 = time.perf_counter()
    if stage == "fitnp":
        Xnp = np.zeros((chunk_rows, 40), np.float32)
        def np_source():
            yield Xnp
        warm = make_est(1).fit_stream(
            np_source, session=sess, cache_device=True, holdout_chunks=0)
    elif stage == "delwarm":
        warm = None
        import gc; gc.collect()
    elif stage in ("replay", "replay2"):
        make_est(100).warm_replay(6, session=sess)
    elif stage == "replayepoch":
        # the 'epoch'-granularity lowering: n_epochs=1 scans over the stack,
        # dispatched REPEATEDLY like the real per-epoch replay loop (the
        # fault might need repeated execution / cumulative device state —
        # one dispatch would under-power the verdict). warm_replay with
        # granularity 'epoch' compiles + executes the n_epochs=1 program;
        # repeats hit the jit cache, so 8 rounds ~= 8 executions.
        est = make_est(100, gran="epoch")
        for _ in range(8):
            est.warm_replay(6, session=sess)
    else:
        raise ValueError(stage)
    print(f"STAGE_OK {stage} {time.perf_counter()-t0:.1f}s", flush=True)
print("CELL_OK", flush=True)
"""

CELLS = [
    # (name, emb_update, stages)
    ("base", "sorted", ["fitnp", "replay"]),
    ("embfused", "fused", ["fitnp", "replay"]),
    ("epochwise", "fused", ["fitnp", "replayepoch"]),
    ("cached", "sorted", ["replay", "fitnp", "replay2"]),
    ("delwarm", "sorted", ["fitnp", "delwarm", "replay"]),
]


# --smoke cell: exercises the subprocess/JSON plumbing (spawn, STAGE_OK
# parsing, verdict emission) without importing jax or touching a device —
# the not-slow tier-1 smoke test runs this so a refactor that breaks the
# matrix harness fails in CI instead of on the chip
_SMOKE_SRC = r"""
import time
print("STAGE_OK noop 0.0s", flush=True)
print("CELL_OK", flush=True)
"""


def run_cell(name: str, emb: str, stages: list, chunk_rows: int,
             wall_s: float, src_override: str | None = None) -> dict:
    src = src_override if src_override is not None else (
        _CELL_SRC
        .replace("__REPO__", repr(REPO))
        .replace("__CHUNK_ROWS__", str(chunk_rows))
        .replace("__EMB__", repr(emb))
        .replace("__STAGES__", repr(list(stages))))
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, "-c", src],
                           capture_output=True, text=True, timeout=wall_s,
                           cwd=REPO)
        rc, out, err = r.returncode, r.stdout or "", r.stderr or ""
    except subprocess.TimeoutExpired as e:
        rc = "wall-timeout"

        def _dec(b):
            return (b or b"").decode("utf-8", "replace") \
                if isinstance(b, bytes) else (b or "")
        out, err = _dec(e.stdout), _dec(e.stderr)
    ok_stages = [ln.split()[1] for ln in out.splitlines()
                 if ln.startswith("STAGE_OK ")]
    fault = "UNAVAILABLE" in err or "UNAVAILABLE" in out
    res = {
        "cell": name, "emb_update": emb, "stages": stages,
        "ok": rc == 0 and "CELL_OK" in out,
        "stages_completed": ok_stages, "rc": rc,
        "device_fault": fault, "wall_s": round(time.time() - t0, 1),
    }
    if not res["ok"]:
        tail = err.strip().splitlines()[-1:] if err.strip() else []
        res["error_tail"] = tail[0][-200:] if tail else ""
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk-rows", type=int, default=1 << 18)
    ap.add_argument("--wall-s", type=float, default=420.0)
    ap.add_argument("--smoke", action="store_true",
                    help="plumbing smoke: one trivial no-jax cell (the "
                         "tier-1 not-slow smoke test)")
    args = ap.parse_args()

    if args.smoke:
        res = run_cell("smoke", "none", ["noop"], args.chunk_rows,
                       60.0, src_override=_SMOKE_SRC)
        print(json.dumps(res), flush=True)
        print(json.dumps(_verdict([res], backend="none")), flush=True)
        sys.exit(0 if res["ok"] else 1)

    _run_matrix(args)


def _verdict(results: list, backend: str = "tpu") -> dict:
    by = {r["cell"]: r for r in results}

    def ok(cell):
        r = by.get(cell)
        return None if r is None else r["ok"]

    base = by.get("base")
    return {
        "metric": "replay_fault_diag",
        # value = cells RUN (an all-cells-fault outcome is a valid result)
        "value": len(results),
        "unit": "cells_run",
        "cells_ok": sum(r["ok"] for r in results),
        "vs_baseline": None,
        "backend": backend,
        "reproduced": (None if base is None
                       else (not base["ok"] and base["device_fault"])),
        "fixed_by_fused_emb": ok("embfused"),
        "fixed_by_epoch_granularity": ok("epochwise"),
        "fixed_by_precompile": ok("cached"),
        "fixed_by_freeing_warm": ok("delwarm"),
        # full per-cell records ride inside the summary line
        "cells": results,
    }


def _run_matrix(args) -> None:
    results = []
    for name, emb, stages in CELLS:
        res = run_cell(name, emb, stages, args.chunk_rows, args.wall_s)
        print(json.dumps(res), flush=True)
        results.append(res)
    print(json.dumps(_verdict(results)), flush=True)


if __name__ == "__main__":
    main()
