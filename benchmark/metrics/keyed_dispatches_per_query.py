"""Programs the engine started through its own choke points (fuse.fused()
closures, compiled.run_stage) per traced query, from the engine's phase
account. dispatches_per_query (the trace's "XLA Modules") minus this is
what bypasses the compile cache: module kernels and eager jnp calls."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["keyed_dispatches"])
