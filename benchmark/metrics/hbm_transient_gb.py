"""Bytes that lived only inside the span that set the live peak: in the
interval between two marks of the program's device-memory ledger
(``obs.prof.LEDGER``) in which the allocator's ``peak_bytes_in_use`` last
rose (``high_water``), the peak less the fuller mark's ``bytes_in_use`` —
an undonated output beside its input shows here at its full size, a stack
built and dropped too. ``hbm_live_peak_gb`` = the ledger's total at the
fuller mark + ``hbm_unnamed_gb`` + this. Read as ``hbm_live_peak_gb`` is. A
program whose ledger takes no marks has nothing to read."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs import prof

        high_water = prof.LEDGER.snapshot()["high_water"]
    except (ImportError, AttributeError, KeyError):
        return None
    return high_water["transient_bytes"] / 1e9 if high_water else None
