"""Host ingest per job: the program's own stage seconds parse + encode +
h2d (prefetch thread, host clock), mean over the window's jobs."""

from benchmark.metrics._common import mean_span


def read(run: dict):
    return mean_span(run, "ingest_s")
