"""Groups the largest aggregate of a traced query emitted
(counters.agg_groups), from the engine's phase account. None on a program
whose account does not count them."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["agg_groups"])
