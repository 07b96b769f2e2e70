"""Mean time per traced query that query.execute spends neither waiting
for the device nor stalled on a scan's pipeline: the host driving the exec
tree (Python between and inside operators, enqueueing programs, building
the result table), from the engine's phase account: phases_ns.execute -
timers_ns.deviceWaitTime - timers_ns.pipelineStallTime. An upper bound: a
sync through np.asarray inside a single exec (a sort's permutation) is not
among the waits the engine times. The two timers are summed over task
threads and execute is one wall time, so a result whose partitions ran in
parallel can take the difference below 0: it reads 0 then."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: max(
        0, r["phases_ns"]["execute"] - r["timers_ns"]["deviceWaitTime"]
        - r["timers_ns"].get("pipelineStallTime", 0)), 1e-6)
