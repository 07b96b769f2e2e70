"""Mean DEVICE time per traced query over the distinct levels of the
query's distinct aggregates (timers_ns.distinctAggDeviceTime: each
level's input program up to the count that sizes its compaction, then the
compaction and the group-by up to the next read-back; stamped at
read-backs that exist), from the engine's phase account. None on a
program without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["distinctAggDeviceTime"],
                   1e-6)
