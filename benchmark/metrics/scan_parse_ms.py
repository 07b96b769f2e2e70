"""Mean tpuDecodeTime per traced query, summed over the scan's threads:
host page parse and fallback-column decode, from the engine's phase
account. None where the query scans nothing (no such timer)."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["tpuDecodeTime"], 1e-6)
