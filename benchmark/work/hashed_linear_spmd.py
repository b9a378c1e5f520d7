"""Work functions of the hashed linear fit on a mesh of chips: the SAME
bytes and operations as ``work/hashed_linear.py`` — the algorithm's least:
each touched row's 12 B in and out once, each cached chunk read once,
whatever a layout replicates or sends between chips — and the least
seconds over the AGGREGATE peaks of the chips the cell names. A layout
that replicates the update over its ``data`` axis, or spends its step in
collectives, reads a lower share of this bound; none reads a higher one.
"""

from __future__ import annotations

from benchmark.work import hashed_linear

SCALED = ("flops_per_s", "hbm_bytes_per_s", "hbm_bytes")


def aggregate(peaks: dict, chips: int) -> dict:
    """One chip's peaks -> those of ``chips`` of them together."""
    return {**peaks, **{k: peaks[k] * chips for k in SCALED}, "chips": chips}


def job_work(*, peaks: dict, **shapes) -> dict:
    """``hashed_linear.job_work`` on peaks ``aggregate`` made (one chip's
    as they stand count as one chip)."""
    out = hashed_linear.job_work(peaks=peaks, **shapes)
    out["chips"] = peaks.get("chips", 1)
    return out
