"""memory_stats()["peak_bytes_in_use"] after the window, in GB."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
