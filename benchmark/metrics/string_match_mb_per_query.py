"""Mean megabytes per traced query of the columns the query's Filters
matched (counters.string_match_bytes: a flat column's live bytes plus its
offsets, once an evaluation, from sizes the host knows), from the engine's
phase account. None on a program without the counter."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["string_match_bytes"], 1e-6)
