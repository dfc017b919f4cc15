"""``hbm_peak_gb`` (device): the run's ``memory_peak_bytes`` in GB: the
allocator's ``peak_bytes_in_use`` plus ``peak_bytes_reserved`` after the
window (``harness/device.py`` says why both), on the fullest of the cell's
chips, before the reference runs."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
