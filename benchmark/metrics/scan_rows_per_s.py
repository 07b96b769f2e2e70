"""Rows of the tables a pass reads over the median pass time."""
from . import quantile


def read(run):
    if not run.pass_s or not run.pass_rows:
        return None
    return run.pass_rows / quantile(run.pass_s, 0.5)
