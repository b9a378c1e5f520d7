"""The device bytes the program itself accounts for: the high-water mark
of its device-memory ledger (``obs.prof.LEDGER``: model state, cached
chunks, the replay stack) since the process started. ``hbm_peak_gb`` less
this is what nobody has named yet."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        peak = prof.LEDGER.peak()
    except (ImportError, AttributeError):
        return None
    return peak / 1e9 if peak else None
