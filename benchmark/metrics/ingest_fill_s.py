"""The pipeline fill per job: the first ``input_wait`` span of each fit —
chunk 0 parsed, encoded and put before any step can start — mean over the
window's jobs."""

from benchmark.metrics._program_spans import mean_of


def read(run: dict):
    return mean_of(run, lambda job: job["first"].get("input_wait"))
