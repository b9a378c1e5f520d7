"""Shared by the readers of the staging layer's spans: the ring of
``orange3_spark_tpu.obs.trace`` read in-process. A staged refit is one
trace whose root span is ``canvas_refit`` (``workflow/staging.py``); the
last ``len(run["jobs"])`` such traces are the window's jobs, the warm
job's before them. Nothing to read (spans switched off, or a program whose
staging layer records no span, as before PR 35) is ``None``, never an
error."""


def mean_span(run: dict, name: str):
    """Mean over the window's jobs of the seconds of their ``name`` spans."""
    try:
        from orange3_spark_tpu.obs import trace
    except ImportError:
        return None
    by_trace: dict = {}         # events() is in order of start
    for ph, ev, t0, dur, _thread, _args, trace_id, _sid, parent in \
            trace.events():
        if ph == "X" and trace_id is not None:
            by_trace.setdefault(trace_id, []).append((ev, t0, dur, parent))
    refits = sorted(
        (evs for evs in by_trace.values()
         if any(ev == "canvas_refit" and parent is None
                for ev, _t0, _dur, parent in evs)),
        key=lambda evs: evs[0][1])
    jobs = refits[-len(run["jobs"]):] if run["jobs"] else []
    vals = [sum(dur for ev, _t0, dur, _p in evs if ev == name) * 1e-9
            for evs in jobs if any(ev == name for ev, *_ in evs)]
    return sum(vals) / len(vals) if vals else None


def counter(name: str):
    """The program's counter ``name``, or ``None`` on a program without it
    (or without ``obs.registry``)."""
    try:
        from orange3_spark_tpu.obs.registry import REGISTRY
    except ImportError:
        return None
    return REGISTRY.get(name)
