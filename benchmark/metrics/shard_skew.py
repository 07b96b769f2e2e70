"""Largest over mean live rows a shard, over the sharded stages of the
traced queries (mesh.shard_rows of the engine's phase account: the rows
each shard's program took in after its last absorbed member, summed over
the query's waves); 1.0 is even. A stage no row reached is left out. None
on a program that runs no mesh."""
from .phase_account import mean_of


def read(run):
    def skew(record):
        stages = [rows for rows in record["mesh"]["shard_rows"] if sum(rows)]
        return sum(max(rows) * len(rows) / sum(rows)
                   for rows in stages) / len(stages)
    try:
        return mean_of(run, skew)
    except ZeroDivisionError:
        return None
