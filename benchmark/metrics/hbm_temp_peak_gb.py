"""Program temp's high-water mark on the fullest chip: the reserved
region's ``peak_bytes_reserved`` at the last mark of the program's
device-memory ledger (``obs.prof.LEDGER``), read as ``hbm_live_peak_gb``
is. What a program stages beside its arguments lands here (the replay's
hoisted keys): the number the next hoist moves. A program whose ledger
takes no marks has nothing to read."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        marks = prof.LEDGER.snapshot()["marks"]
    except (ImportError, AttributeError, KeyError):
        return None
    return marks[-1]["peak_bytes_reserved"] / 1e9 if marks else None
