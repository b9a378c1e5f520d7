"""One rank of a MultihostLauncher training gang.

Run as::

    python -m orange3_spark_tpu.parallel.mh_worker \
        --rank R --nprocs N --coord HOST:PORT \
        --csv data.csv --class-col y --n-total ROWS --n-features D \
        --chunk-rows C --epochs E --step-size LR --out-dir OUT \
        [--ckpt-dir CK] [--die-after-saves K] [--model-parallel MP]

Each rank: ``jax.distributed.initialize`` (when N > 1), builds a
``DataParallelPartitioner`` (or ``SPMDPartitioner`` with
``--model-parallel``), streams ONLY its row block of the shared CSV
through ``sharded_csv_chunk_source``, and runs the ordinary
``StreamingLinearEstimator.fit_stream`` — the estimator never knows how
many processes exist. Epoch-boundary checkpoints (``--ckpt-dir``) are the
gang's resume points; rank 0 writes ``theta.npz`` and every rank writes
``host_R.json`` carrying its goodput/ledger attribution (the PR-12 digest
the bench folds per host).

``--die-after-saves K`` arms the lost-host DRILL: the rank SIGKILLs its
own process right after its K-th checkpoint save lands — but only on a
run that started from scratch (a ``rankR.died`` marker disarms the bomb
after the restart, so the drill kills exactly once).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--coord", default="")
    ap.add_argument("--csv", required=True)
    ap.add_argument("--class-col", default="y")
    ap.add_argument("--n-total", type=int, required=True)
    ap.add_argument("--n-features", type=int, required=True)
    ap.add_argument("--chunk-rows", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--step-size", type=float, default=0.1)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--die-after-saves", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    import jax

    if args.nprocs > 1:
        jax.distributed.initialize(args.coord, num_processes=args.nprocs,
                                   process_id=args.rank)
    import numpy as np

    from orange3_spark_tpu.io.streaming import (StreamingLinearEstimator,
                                                sharded_csv_chunk_source)
    from orange3_spark_tpu.parallel.partitioner import (
        DataParallelPartitioner, SPMDPartitioner)
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    part = (SPMDPartitioner(model_parallel=args.model_parallel)
            if args.model_parallel > 1 else DataParallelPartitioner())
    src = part.shard_csv(args.csv, args.class_col, n_total=args.n_total,
                         chunk_rows=args.chunk_rows)

    ck, resumed_from = None, 0
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)
        path = os.path.join(args.ckpt_dir, f"rank{args.rank}.ckpt")
        ck = StreamCheckpointer(path, every_steps=10 ** 9)
        resumed_from = ck.load()[0]
        marker = os.path.join(args.ckpt_dir, f"rank{args.rank}.died")
        if args.die_after_saves > 0 and not os.path.exists(marker):
            # the drill bomb: die right AFTER the Kth epoch snapshot
            # lands on disk (atomic rename done), the worst-case instant
            # for the rest of the gang
            ck = _DieAfterSaves(path, every_steps=10 ** 9,
                                after=args.die_after_saves, marker=marker)

    est = StreamingLinearEstimator(
        loss="logistic", epochs=args.epochs, step_size=args.step_size,
        chunk_rows=args.chunk_rows, replay_granularity="epoch",
        checkpoint_every_epochs=1 if ck is not None else 0)
    t0 = time.perf_counter()
    model = est.fit_stream(src, n_features=args.n_features,
                           session=part.session, cache_device=True,
                           checkpointer=ck)
    jax.block_until_ready(model.coef)
    wall = time.perf_counter() - t0

    os.makedirs(args.out_dir, exist_ok=True)
    report = getattr(model, "run_report_", None)
    rep = report.to_dict() if report is not None else {}
    host = {
        "rank": args.rank,
        "nprocs": args.nprocs,
        "rows_local": args.n_total // max(1, args.nprocs),
        "n_steps": int(model.n_steps_),
        "fit_wall_s": round(wall, 4),
        "resumed_from_step": int(resumed_from),
        "goodput": rep.get("goodput", {}),
        "device_memory": rep.get("device_memory", {}),
    }
    with open(os.path.join(args.out_dir, f"host_{args.rank}.json"),
              "w") as f:
        json.dump(host, f)
    if args.rank == 0:
        np.savez(os.path.join(args.out_dir, "theta.npz"),
                 coef=np.asarray(model.coef),
                 intercept=np.asarray(model.intercept),
                 n_steps=np.asarray(model.n_steps_))
    print(f"OTPU_LIVE mh_worker rank={args.rank} steps={model.n_steps_} "
          f"wall={wall:.3f}s resumed_from={resumed_from}", flush=True)
    return 0


def _die_now(marker: str) -> None:
    with open(marker, "w") as f:
        f.write("killed by --die-after-saves\n")
    os.kill(os.getpid(), signal.SIGKILL)


class _DieAfterSaves:
    """Checkpointer proxy that SIGKILLs the process right after its
    ``after``-th save completes — the drill's fault injector (the marker
    file is written FIRST so the restarted run disarms)."""

    def __init__(self, path: str, *, every_steps: int, after: int,
                 marker: str):
        from orange3_spark_tpu.utils.fault import StreamCheckpointer
        self._inner = StreamCheckpointer(path, every_steps=every_steps)
        self.path = self._inner.path
        self.every_steps = self._inner.every_steps
        self._after = after
        self._saves = 0
        self._marker = marker

    def save(self, step, state, meta=None):
        self._inner.save(step, state, meta)
        self._saves += 1
        if self._saves >= self._after:
            _die_now(self._marker)

    def maybe_save(self, step, state, meta=None):
        if step % self.every_steps != 0:
            return False
        self.save(step, state, meta)
        return True

    def load(self, expect_meta=None):
        return self._inner.load(expect_meta)

    def delete(self):
        self._inner.delete()


if __name__ == "__main__":
    sys.exit(main())
