"""Mean DEVICE time per traced query of the Filters that match strings over
a byte plane (timers_ns.stringMatchDeviceTime: the match's own program,
read on the host's clock when the device reaches its output, at a read-back
that exists), from the engine's phase account. None on a program without
such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["stringMatchDeviceTime"],
                   1e-6)
