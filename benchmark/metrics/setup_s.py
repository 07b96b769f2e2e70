"""Process start to window start: generation, placement, compilation or
cache loads, warm passes. The reference's time is not in it."""


def read(run):
    return run.setup_s
