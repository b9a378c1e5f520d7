"""Parse seconds per job: the sum of the program's ``parse`` spans (one
pull of a rechunked host chunk each, prefetch thread), mean over the
window's jobs. ``ingest_s`` times the same layer through ``stage_times``."""

from benchmark.metrics._program_spans import mean_of


def read(run: dict):
    return mean_of(run, lambda job: job["sum"].get("parse"))
