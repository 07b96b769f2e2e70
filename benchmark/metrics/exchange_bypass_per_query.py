"""Hash exchanges a traced query did not execute (the exec metric
exchangeBypassed), from the engine's phase account: an exchange between
the halves of one aggregate whose whole input, a few rows of partial state
a shard, was already on the host and went as one batch to one partition.
Over [Q1, Q6] on a mesh 0.5: Q1's one exchange, and Q6, a global
aggregate, has none. None on a program that runs no mesh, or whose
account does not count them."""
from .phase_account import mean_of
from .shard_waves_per_query import _on_mesh


def read(run):
    return mean_of(
        run, lambda r: _on_mesh(r)["counters"]["exchange_bypassed"])
