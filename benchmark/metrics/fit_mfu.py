"""The whole job's share of the chip's peak: the least chip seconds of
everything one job has to compute (work function: all steps of all epochs
and the holdout's evaluation) over the job's wall seconds, taken from the
``bench:job`` spans of the trace. It names no program and no kernel, so it
still bounds a claim after a later PR replaces one."""


def read(run: dict):
    trace, work = run["trace"], run["work"]
    if not trace or not work:
        return None
    walls = trace["spans"].get("job", [])[:run["traced_jobs"]]
    if not walls or sum(walls) <= 0:
        return None
    return 100.0 * work["job_least_s"] * len(walls) / sum(walls)
