"""The share of the sparse steps that built their own sort keys (the sort
of a chunk's hashed keys, its segment ids and ``uniq``): key halves built
over optimizer steps, from the program's own counter
(``otpu_sparse_sorts_total`` of ``obs.registry``, fed once per finished
fit from static counts kept on the host), over every fit of the process —
the warm job and the window's jobs run the same schedule. 1.0 is a sort in
every step; a fused replay that builds a cached chunk's keys once per
dispatch reads ``(streamed steps + n_chunks) / steps`` — 2 / epochs where
the first epoch streams; a program without the counter reads ``None``."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs.registry import REGISTRY
    except ImportError:
        return None
    sorts = REGISTRY.get("otpu_sparse_sorts_total")
    if sorts is None:
        return None
    steps = sorts.value(which="steps")
    return sorts.value(which="run") / steps if steps else None
