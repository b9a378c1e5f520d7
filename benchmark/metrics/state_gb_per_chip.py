"""The device bytes the program accounts for ON ONE CHIP: the high-water
mark of its device-memory ledger (``obs.prof.LEDGER``: model state, cached
chunks, the replay stack), where a sharded array counts by its shards on
the fullest device — to be read beside ``hbm_peak_gb``, which is that
chip's own high-water mark. A program whose ledger counts global sizes
(no ``peak_global`` beside ``peak``) has no per-chip figure to read."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        prof.LEDGER.peak_global       # the per-chip ledger keeps both
        peak = prof.LEDGER.peak()
    except (ImportError, AttributeError):
        return None
    return peak / 1e9 if peak else None
