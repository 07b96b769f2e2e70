"""Mean time of query.admit plus query.epilogue per traced query: what
the session's own bookkeeping costs an action (digest, registration,
admission, recorders; attribution, publishing), from the engine's phase
account."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["phases_ns"]["admit"]
                   + r["phases_ns"]["epilogue"], 1e-6)
