"""One reader a metric: metrics/<name>.py exposes read(run) -> number | None.

`run` is run.py's Run. A reader that finds nothing to read returns None and
the metric is left out of the line.
"""


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) of all `values`, by linear interpolation
    between the two nearest ranks of the sorted sample (numpy's default)."""
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values):
    return sum(values) / len(values) if values else None
