"""Bytes in use that no owner claims where the live peak was set: the
program's device-memory ledger (``obs.prof.LEDGER``) keeps the interval
between two of its marks in which the allocator's ``peak_bytes_in_use``
last rose (``high_water``); this is ``bytes_in_use`` at the fuller of the
two marks less the ledger's total there — what a census of the live arrays
finds under ``unnamed`` plus what the runtime holds beyond the live arrays
(buffers of programs still in flight). Read as ``hbm_live_peak_gb`` is. A
program whose ledger takes no marks has nothing to read."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        high_water = prof.LEDGER.snapshot()["high_water"]
    except (ImportError, AttributeError, KeyError):
        return None
    return high_water["unnamed_bytes"] / 1e9 if high_water else None
