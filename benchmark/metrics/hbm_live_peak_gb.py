"""Live buffers' high-water mark on the fullest chip, without program temp:
``peak_bytes_in_use`` at the last mark of the program's device-memory ledger
(``obs.prof.LEDGER``; a mark reads the allocator's own ``memory_stats()``
where a fit closes a span). With ``hbm_temp_peak_gb`` it adds up to
``hbm_peak_gb``: the harness reads the same allocator once the window has
closed, and nothing but the last job's small read-back lies between the
last fit's last mark and that. No mark is taken here: the reference's own
device work comes before the readers run and is not the program's. A
program whose ledger takes no marks has nothing to read."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        marks = prof.LEDGER.snapshot()["marks"]
    except (ImportError, AttributeError, KeyError):
        return None
    return marks[-1]["peak_bytes_in_use"] / 1e9 if marks else None
