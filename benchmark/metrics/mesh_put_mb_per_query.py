"""Mean bytes of table planes a traced query moved onto or between chips
to feed a mesh (counters.mesh_put_bytes: a host-packed wave put over the
mesh, or a placed shard handed to an operator on the default chip), in
MB, from the engine's phase account. 0 when the resident shards are
consumed where they live. None on a program that runs no mesh."""
from .phase_account import mean_of
from .shard_waves_per_query import _on_mesh


def read(run):
    return mean_of(run, lambda r: _on_mesh(r)["counters"]["mesh_put_bytes"],
                   1e-6)
