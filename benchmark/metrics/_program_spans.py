"""Shared by the readers of the program's own spans: the ring of
``orange3_spark_tpu.obs.trace`` read in-process, one entry per job of the
window. A job's fit is one trace (every span of it, on the fit thread and
on the prefetch worker, carries the fit's ``trace_id``); the last
``len(run["jobs"])`` traces whose root span is ``fit`` are the window's
jobs, the warm job's before them. Nothing to read (spans switched off, or
a program that records no such span) is ``None``, never an error."""


def job_spans(run: dict):
    """-> one dict a job, oldest first: ``sum`` (seconds by span name, all
    threads), ``first`` (seconds of the earliest span of each name) and
    ``end`` (latest end of each name, ns on the ring's clock); ``None``
    where the ring holds no fit trace."""
    try:
        from orange3_spark_tpu.obs import trace
    except ImportError:
        return None
    by_trace: dict = {}         # events() is in order of start
    for ph, name, t0, dur, _thread, _args, trace_id, _sid, parent in \
            trace.events():
        if ph == "X" and trace_id is not None:
            by_trace.setdefault(trace_id, []).append((name, t0, dur, parent))
    fits = sorted(
        (evs for evs in by_trace.values()
         if any(name == "fit" and parent is None
                for name, _t0, _dur, parent in evs)),
        key=lambda evs: evs[0][1])
    jobs = []
    for evs in fits[-len(run["jobs"]):] if run["jobs"] else []:
        job: dict = {"sum": {}, "first": {}, "end": {}}
        for name, t0, dur, _parent in evs:
            job["sum"][name] = job["sum"].get(name, 0.0) + dur * 1e-9
            job["first"].setdefault(name, dur * 1e-9)
            job["end"][name] = max(job["end"].get(name, 0), t0 + dur)
        jobs.append(job)
    return jobs or None


def mean_of(run: dict, value):
    """Mean over the window's jobs of ``value(job)``; a job in which it
    reads ``None`` (no such span) is left out, and none left is ``None``."""
    jobs = job_spans(run)
    vals = [v for v in map(value, jobs or ()) if v is not None]
    return sum(vals) / len(vals) if vals else None
