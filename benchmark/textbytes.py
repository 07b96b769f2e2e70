"""Bytes a literal-run string match has to read, from the counter alone.

The engine's `counters.string_match_bytes` is the matched column's live
bytes plus its offsets (4 a row and one more), counted once an evaluation
from sizes the host knows. A match over a flat string column has to read
each of those bytes once, whatever kernel does it; the boolean a row it
writes is left out (under a fiftieth of what it reads)."""


def match_bytes(counter_bytes: float) -> float:
    """Least bytes over the HBM bus for the evaluations counted."""
    return float(counter_bytes)
