"""Mean host time of TpuSession.sql(text), parse and analysis, per query."""
from . import mean


def read(run):
    m = mean(run.parse_s)
    return None if m is None else m * 1e3
