"""Shared by the readers: the mean of one stage over the window's jobs."""


def mean_span(run: dict, key: str):
    vals = [j["spans"].get(key) for j in run["jobs"]]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
