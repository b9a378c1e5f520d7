"""Encode seconds per job: the sum of the program's ``encode`` spans (pad,
hash, plan, codec encode of one chunk, prefetch thread), mean over the
window's jobs."""

from benchmark.metrics._program_spans import mean_of


def read(run: dict):
    return mean_of(run, lambda job: job["sum"].get("encode"))
