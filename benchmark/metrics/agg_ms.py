"""Mean DEVICE time per traced query over the rollup aggregate's programs
(timers_ns.aggDeviceTime: pack, argsort and scan up to the read-back of
the levels' counts, then the emit; the program reads the host's clock
when the device reaches each, at a read-back that exists), from the
engine's phase account. None on a program without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["aggDeviceTime"], 1e-6)
