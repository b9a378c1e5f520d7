"""Ingest the steps did not hide, per job: the sum of the program's
``input_wait`` spans (the fit thread blocked on the prefetch queue), mean
over the window's jobs."""

from benchmark.metrics._program_spans import mean_of


def read(run: dict):
    return mean_of(run, lambda job: job["sum"].get("input_wait"))
