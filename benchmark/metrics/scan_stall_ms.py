"""Mean time per traced query that a scan pipeline's consumer, the
query's own thread, waited on an empty queue for its producer: the
`pipeline.stall` spans, timers_ns.pipelineStallTime of the engine's phase
account, summed over threads. None where the query runs no pipeline."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["pipelineStallTime"], 1e-6)
