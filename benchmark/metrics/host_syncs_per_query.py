"""Host syncs per traced query: the times the query's threads waited at
one of the engine's doors for a device value (`device_wait(site)`, one
span `sync.<site>` each), counters.host_syncs of the engine's phase
account. A count of a fixed plan: the same in every run of a cell. None
where the account has no such counter (a program from before the doors
had sites)."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["host_syncs"])
