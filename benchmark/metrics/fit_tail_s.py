"""The tail of a fit, per job: from the end of its last ``chunk`` or
``replay`` span to the end of its ``fit`` span — the finite sweep of theta,
the lazy-decay pass over the table, the model build — mean over the
window's jobs."""

from benchmark.metrics._program_spans import mean_of


def _tail(job: dict):
    end = job["end"]
    last = max(end.get("chunk", 0), end.get("replay", 0))
    return (end["fit"] - last) * 1e-9 if last else None


def read(run: dict):
    return mean_of(run, _tail)
