"""Mean DEVICE time per traced query over the ranked window's sort
(timers_ns.windowSortDeviceTime: the key program and the argsort's passes,
apart from the window's scans; read on the host's clock when the device
reaches the permutation, at a read-back that exists), from the engine's
phase account. None on a program without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["windowSortDeviceTime"],
                   1e-6)
