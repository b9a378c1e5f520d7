"""Device dispatches a whole-canvas refit costs: the program's counters
``otpu_canvas_dispatches_total{mode=staged}`` over
``otpu_canvas_refits_total``, over every refit of the process (the warm
job and the window's run the same program). 1.0 is what staging is for."""

from benchmark.metrics._canvas_spans import counter


def read(run: dict):
    dispatches = counter("otpu_canvas_dispatches_total")
    refits = counter("otpu_canvas_refits_total")
    if dispatches is None or refits is None or not refits.total():
        return None
    return dispatches.value(mode="staged") / refits.total()
