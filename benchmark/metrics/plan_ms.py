"""Mean host time of query.plan (prepare_execution: conf sync, measured
hints, convert_plan) per traced query, from the engine's phase account."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["phases_ns"]["plan"], 1e-6)
