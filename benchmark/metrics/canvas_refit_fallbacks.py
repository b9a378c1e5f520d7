"""Estimator nodes that kept their eager state in a refit, summed over the
process's refits (``otpu_canvas_refit_fallbacks_total``): 0, or the refit
did not re-fit the whole canvas."""

from benchmark.metrics._canvas_spans import counter


def read(run: dict):
    fallbacks = counter("otpu_canvas_refit_fallbacks_total")
    refits = counter("otpu_canvas_refits_total")
    if fallbacks is None or refits is None or not refits.total():
        return None
    return fallbacks.total()
