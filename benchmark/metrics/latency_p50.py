"""Median wall time of a pass, over every pass of the window, in ms."""
from . import quantile


def read(run):
    return quantile(run.pass_s, 0.5) * 1e3 if run.pass_s else None
