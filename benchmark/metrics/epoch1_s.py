"""The streaming epoch's wall per job (``stage_times['epoch_s'][0]``: one
``_hashed_step`` per chunk as chunks arrive), mean over the window's jobs."""

from benchmark.metrics._common import mean_span


def read(run: dict):
    return mean_span(run, "epoch1_s")
