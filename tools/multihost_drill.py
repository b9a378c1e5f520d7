"""One-shot multihost lost-host drill — the failure ladder's top rung.

Rungs (docs/multihost.md):

  1. GANG UP: ``MultihostLauncher`` spawns N training processes
     (``parallel/mh_worker.py``; ``jax.distributed`` rendezvous when
     N > 1) over one shared CSV, each parsing only its row block.
  2. REFERENCE: the uninterrupted gang fits to completion -> theta_ref,
     plus per-host goodput/ledger attribution (the PR-12 digest).
  3. KILL: a fresh gang runs with ``--die-after-saves 1`` — the last rank
     SIGKILLs itself the instant its first epoch-boundary checkpoint
     lands (the worst moment: some ranks have saved, the victim just
     did).
  4. RECOVER: the launcher detects the lost host TYPED (no hang), aligns
     every rank's checkpoint to the common step, and gang-restarts with
     seeded backoff; each worker fast-forwards its shard through the
     checkpointed prefix.
  5. VERIFY: the resumed fit's theta must equal theta_ref bitwise and
     resume exactly at the snapshot (0 lost work).

Importable: ``run_drill(procs=1, rows=2048, epochs=3, chunk_rows=256,
out_root=None) -> dict`` (the tier-1 smoke and ``bench.py --config
multihost`` both call it). A CPU drill: the gang's environment pins
``JAX_PLATFORMS=cpu`` (gloo collectives across the ranks).

Usage:
    python tools/multihost_drill.py [--procs 1] [--rows 2048]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def say(msg: str) -> None:
    print(f"[mh-drill] {msg}", file=sys.stderr, flush=True)


def _write_csv(path: str, rows: int, d: int = 8, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, d)).astype(np.float32)
    w_true = rng.normal(size=(d,)).astype(np.float32)
    y = (X @ w_true + 0.1 * rng.normal(size=rows).astype(np.float32)
         > 0).astype(np.float32)
    header = ",".join([f"f{j}" for j in range(d)] + ["y"])
    np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.9g",
               header=header, comments="")


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p and p != REPO])
    return env


def _gang(csv: str, n_total: int, d: int, out_dir: str, ckpt_dir: str, *,
          procs: int, epochs: int, chunk_rows: int, die: bool):
    from orange3_spark_tpu.parallel.launcher import MultihostLauncher

    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(ckpt_dir, exist_ok=True)

    def argv(rank: int, n: int, coord: str) -> list:
        a = [sys.executable, "-m", "orange3_spark_tpu.parallel.mh_worker",
             "--rank", str(rank), "--nprocs", str(n), "--coord", coord,
             "--csv", csv, "--class-col", "y",
             "--n-total", str(n_total), "--n-features", str(d),
             "--chunk-rows", str(chunk_rows), "--epochs", str(epochs),
             "--step-size", "0.1", "--out-dir", out_dir,
             "--ckpt-dir", ckpt_dir]
        if die and rank == n - 1:
            a += ["--die-after-saves", "1"]
        return a

    lau = MultihostLauncher(argv, procs, env=_worker_env(),
                            log_dir=os.path.join(out_dir, "logs"),
                            align_ckpt_dir=ckpt_dir)
    res = lau.run()
    theta = dict(np.load(os.path.join(out_dir, "theta.npz")))
    hosts = {}
    for p in sorted(glob.glob(os.path.join(out_dir, "host_*.json"))):
        with open(p) as f:
            hosts[os.path.splitext(os.path.basename(p))[0]] = json.load(f)
    return res, theta, hosts


def run_drill(procs: int = 1, rows: int = 2048, epochs: int = 3,
              chunk_rows: int = 256, out_root: str | None = None) -> dict:
    """Run all five rungs; returns the drill record (see bench keys)."""
    root = out_root or tempfile.mkdtemp(prefix="otpu-mh-drill-")
    made_root = out_root is None
    d = 8
    try:
        csv = os.path.join(root, "drill.csv")
        _write_csv(csv, rows, d)
        say(f"gang A (uninterrupted, {procs} proc): fit {rows} rows "
            f"x {epochs} epochs")
        res_a, theta_a, hosts = _gang(
            csv, rows, d, os.path.join(root, "a"),
            os.path.join(root, "a_ck"), procs=procs, epochs=epochs,
            chunk_rows=chunk_rows, die=False)
        say(f"gang B (+SIGKILL rank {procs - 1} after its first "
            "epoch snapshot)")
        res_b, theta_b, hosts_b = _gang(
            csv, rows, d, os.path.join(root, "b"),
            os.path.join(root, "b_ck"), procs=procs, epochs=epochs,
            chunk_rows=chunk_rows, die=True)
        parity = (np.array_equal(theta_a["coef"], theta_b["coef"])
                  and np.array_equal(theta_a["intercept"],
                                     theta_b["intercept"]))
        local_rows = -(-rows // procs)                # lockstep per-host rows
        spe = -(-local_rows // chunk_rows)            # steps per epoch
        resumed = max(h.get("resumed_from_step", 0)
                      for h in hosts_b.values())
        # 0 lost work: the resumed fit starts exactly at the snapshot the
        # kill followed (one trained epoch = spe steps)
        lost_steps = spe - resumed
        say(f"parity={parity} resumed_from={resumed} "
            f"lost_steps={lost_steps} restarts={res_b.gang_restarts}")
        return {
            "procs": procs,
            "rows": rows,
            "epochs": epochs,
            "hosts_lost": res_b.hosts_lost,
            "gang_restarts": res_b.gang_restarts,
            "gang_starts": res_b.gang_starts,
            "resume_parity_bitwise": bool(parity),
            "resumed_from_step": int(resumed),
            "lost_work_steps": int(lost_steps),
            "ref_steps": int(theta_a["n_steps"]),
            "hosts": hosts,
        }
    finally:
        if made_root:
            shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--chunk-rows", type=int, default=256)
    args = ap.parse_args()
    out = run_drill(procs=args.procs, rows=args.rows, epochs=args.epochs,
                    chunk_rows=args.chunk_rows)
    ok = (out["resume_parity_bitwise"] and out["lost_work_steps"] == 0
          and out["hosts_lost"] >= 1)
    print(json.dumps({"metric": "multihost_drill",
                      "value": 1 if ok else 0, "unit": "ok",
                      "vs_baseline": None, **{k: v for k, v in out.items()
                                              if k != "hosts"}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
