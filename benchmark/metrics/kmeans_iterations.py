"""Lloyd iterations a refit ran: ``otpu_kmeans_iterations_total{fit=
staged}`` (fed when a staged refit's states come back) over
``otpu_canvas_refits_total``. The work function counts the passes of
exactly these iterations."""

from benchmark.metrics._canvas_spans import counter


def read(run: dict):
    iterations = counter("otpu_kmeans_iterations_total")
    refits = counter("otpu_canvas_refits_total")
    if iterations is None or refits is None or not refits.total():
        return None
    return iterations.value(fit="staged") / refits.total()
