"""(keys, value) groups the distinct levels of a traced query put out,
summed over its distinct aggregates (counters.distinct_keys), from the
engine's phase account. None on a program whose account does not count
them."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["distinct_keys"])
