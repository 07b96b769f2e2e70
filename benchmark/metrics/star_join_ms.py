"""Mean DEVICE time per traced query over the star's joins
(timers_ns.joinDeviceTime, summed over the query's join nodes: the probe
cut to the build keys' span and compacted, the look-ups, the gathers of
the build's columns; read on the host's clock when the device reaches each
join's output, at a read-back that exists), from the engine's phase
account. None on a program without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["joinDeviceTime"], 1e-6)
