"""Rows the hash joins of a traced query handed on
(counters.join_output_rows, from counts the host has), from the engine's
phase account. None on a program whose account does not count them."""
from .phase_account import mean_of


def read(run):
    return mean_of(run, lambda r: r["counters"]["join_output_rows"])
