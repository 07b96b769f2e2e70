"""Mean DEVICE time per traced query over the query's hash joins
(timers_ns.joinDeviceTime: in this cell the left outer join's look-up of
750 K customers in the counted orders and the gather of the count; read at
a read-back that exists), from the engine's phase account. None on a
program without such a timer."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["timers_ns"]["joinDeviceTime"], 1e-6)
