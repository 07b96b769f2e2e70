"""Mean DEVICE time per traced query over the query's hash aggregates
(timers_ns.aggDeviceTime as HashAggregateExec stamps it: each update a
batch, then the merge and the evaluate; in this cell the count a key below
the join, the sum a customer above it and the count a count; read at a
read-back that exists), from the engine's phase account. None on a program
without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["aggDeviceTime"], 1e-6)
