"""Live rows ExpandExec handed on per traced query (counters.expand_rows:
a batch's rows once a grouping set, from sizes the host already has), from
the engine's phase account: 0 where the rollup runs as one sort and
expands nothing, the input's rows times the levels where it falls back to
the expansion. None on a program whose account does not count them."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["expand_rows"])
