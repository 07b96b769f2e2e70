"""Mean time per traced query that the host is blocked on the device at
the engine's own download doors: every lazy row count a host decision
forces inside the operators (joins, concatenation, compaction) and every
batch brought to the host (to_arrow: each query.fetch, a host-side sort),
summed over task threads; timers_ns.deviceWaitTime of the engine's phase
account."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["deviceWaitTime"], 1e-6)
