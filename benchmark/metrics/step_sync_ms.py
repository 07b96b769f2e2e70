"""Mean time per traced query that the host is blocked mid-query on a
device value that decides its next step (a size, a count, a width, a key
range, partition offsets...): the nanoseconds of every site of the
record's `syncs` but `fetch`, the result's download, summed over task
threads. None where a record has no `syncs` (a program from before the
doors had sites)."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: sum(
        ns for site, (_, ns) in r["syncs"].items() if site != "fetch"), 1e-6)
