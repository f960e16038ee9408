"""device_peak_gib: ``memory_stats()["peak_bytes_in_use"]`` on the fullest
chip, read after the window and before the reference runs."""


def read(run):
    peak = run.outcome.peak_bytes
    return peak / 2 ** 30 if peak > 0 else None
