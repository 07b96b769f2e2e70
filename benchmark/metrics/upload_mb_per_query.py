"""Mean bytes of the device arrays the scans' uploads built per traced
query (uploadBytes), in MB, from the engine's phase account."""
from .phase_account import mean_of


def read(run):
    m = mean_of(run, lambda r: r["counters"]["upload_bytes"], 1e-6)
    return m or None
