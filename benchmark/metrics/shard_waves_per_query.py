"""SPMD waves the sharded stages of a traced query dispatched (the exec
metric shardWaves), from the engine's phase account: one a sharded stage
when the table's partitions are as many as the mesh's devices. None on a
program that runs no mesh, or whose account does not count them."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: _on_mesh(r)["counters"]["shard_waves"])


def _on_mesh(record):
    record["mesh"]["devices"]  # KeyError where no sharded stage ran
    return record
