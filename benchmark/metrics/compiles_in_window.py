"""XLA compiles between window start and end; should read 0."""


def read(run):
    return run.compiles_in_window
