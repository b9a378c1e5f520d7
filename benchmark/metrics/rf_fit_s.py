"""``RandomForestClassifier.fit`` per job, ending in ``block_until_ready``
(the benchmark's own span around the call), mean over the window's jobs."""

from benchmark.metrics._common import mean_span


def read(run: dict):
    return mean_span(run, "rf_fit_s")
