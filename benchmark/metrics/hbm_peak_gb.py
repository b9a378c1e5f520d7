"""Peak device memory of the fullest chip after the window: live buffers'
high-water mark plus the reserved program-temp region's."""


def read(run: dict):
    peak = run["memory"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
