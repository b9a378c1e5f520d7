"""The staging layer's host cost: seconds of a refit's ``canvas_dispatch``
span (from ``StagedGraph.run`` to the fused program enqueued), mean over
the window's jobs."""

from benchmark.metrics._canvas_spans import mean_span


def read(run: dict):
    return mean_span(run, "canvas_dispatch")
