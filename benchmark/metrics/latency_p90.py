"""90th percentile of the wall time of a pass, over every pass, in ms."""
from . import quantile


def read(run):
    return quantile(run.pass_s, 0.9) * 1e3 if run.pass_s else None
