"""Parquet bytes that went to the device still encoded, per query."""
from . import mean


def read(run):
    m = mean(run.encoded_bytes)
    return m / 1e6 if m else None
