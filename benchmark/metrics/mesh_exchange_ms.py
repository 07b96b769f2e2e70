"""Mean time per traced query inside the in-program all_to_all that merges
the shards' partial states (timers_ns.iciExchangeTime: the shard_map'd
collective's dispatch, issued with no host sync in the span), from the
engine's phase account. Q6 merges through a collect and adds 0; the
payload is a few rows a shard, so this is latency, not bandwidth. None on
a program that runs no mesh."""
from .phase_account import mean_of
from .shard_waves_per_query import _on_mesh


def read(run):
    return mean_of(run, lambda r: _on_mesh(r)["timers_ns"].get(
        "iciExchangeTime", 0), 1e-6)
