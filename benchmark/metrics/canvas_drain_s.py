"""Seconds of a refit's ``canvas_drain`` span (the fused program enqueued
until its table and states are ready on the device), mean over the
window's jobs."""

from benchmark.metrics._canvas_spans import mean_span


def read(run: dict):
    return mean_span(run, "canvas_drain")
