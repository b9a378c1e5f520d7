"""The fused HBM replay per job (``stage_times['replay_fused_s']``: epochs
2+ as one dispatch), mean over the window's jobs. A fit that replays
nothing has nothing to read."""

from benchmark.metrics._common import mean_span


def read(run: dict):
    return mean_span(run, "replay_s")
