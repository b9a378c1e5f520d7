"""Readings for the limits of ``correct`` (not part of a benchmark run).

For each seed, at the cell's own size unless ``--rehearse``: the plain
reference in the stated precision once, then each ``--mode`` put through
the SAME comparison a run makes. A job kind names the modes it has
(``Job.modes``):

- ``program``            the program as the configuration states it (the
                         lower readings; also shows a program at fault);
- ``control_program``    the program with its own lower-precision path on
                         (where it has one): the control;
- ``control_reference``  the reference computed in the configuration's
                         ``control_precision``: the control;
- ``fault_skip_step``, ``fault_half_batch``  the reference put in the
                         program's place with that fault planted.

    python3 benchmark/control.py --workload criteo_svc_fit_replay8 \\
        --seeds 11 12 13 --mode program control_program
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--mode", nargs="+", default=["program"])
    ap.add_argument("--repeat", type=int, default=1,
                    help="runs of each program mode per seed")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, args.rehearse)["cell"]
    harness.devices_or_exit(1, args.rehearse)
    from orange3_spark_tpu.core.session import TpuSession

    TpuSession.enable_compilation_cache()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
    for seed in args.seeds:
        config, traffic = harness.load_pair(cell["config"], cell["traffic"],
                                            args.rehearse)
        kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
        job = kind.Job(config, traffic, seed, harness.DATA_DIR)
        job.prepare()
        t0 = time.perf_counter()
        ref = job.reference_for_check()
        ref_s = time.perf_counter() - t0
        for mode in args.mode:
            if mode not in job.modes:
                raise SystemExit(f"{mode!r}: job kind {traffic['job']} has "
                                 f"{job.modes}")
            in_program = mode in ("program", "control_program")
            for rep in range(args.repeat if in_program else 1):
                t0 = time.perf_counter()
                numbers = job.reading(mode, ref)
                line = {"workload": args.workload, "seed": seed, "mode": mode, "run": rep,
                        "numbers": numbers,
                        "logged": getattr(job, "logged", None),
                        "reference_s": ref_s,
                        "seconds": time.perf_counter() - t0}
                print(json.dumps(line), flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(line) + "\n")
                gc.collect()
        del ref, job
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
