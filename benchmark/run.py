"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` — one run of one cell; the last line of standard output is
the result. ``--rehearse`` runs the same code on the CPU at the files' own
rehearsal sizes and prints no device metric. See ``harness.py``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
